#!/usr/bin/env python3
"""Readings the correctness limits are set from; not part of a run.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] [--control <n> [<n> ...]] \\
        [--fault <name>] [--hold-gib <g>]

In one process (one compile), for each ``--seeds`` seed: serve the
cell's window at its own load and print the numbers ``check.py``
compares, the program's readings. For each ``--control`` seed: the same
window, then the control's readings on the same sampled poses: the
reference itself, computed in bfloat16, in the program's place. The
control has to come out as not correct. With ``--fault``, a fault of
``bench/faults.py`` is planted in the program first, and its readings
are the program's. With ``--hold-gib``, that many GiB stay allocated on
the first chip all through, to show how much of its memory a round
really needs. Each reading goes to standard output as one JSON line.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\\n\\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control", type=int, nargs="*", default=[])
    ap.add_argument("--fault", default=None)
    ap.add_argument("--hold-gib", type=float, default=0.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import check
    import harness
    if args.fault:
        import faults
        faults.install(args.fault)
    held = None
    if args.hold_gib:
        import jax
        import jax.numpy as jnp
        held = jax.device_put(
            jnp.zeros((int(args.hold_gib * 2 ** 28),), jnp.float32),
            jax.devices()[0])
        held.block_until_ready()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    cell = harness.load_cell(args.workload)
    for seed in sorted(set(args.seeds) | set(args.control)):
        m = harness.measure(cell, seed, args.seconds, False,
                            t_start=time.perf_counter(), log=log)
        sides = [("program", "float32")] if seed in args.seeds else []
        if seed in args.control:
            sides.append(("control", "bfloat16"))
        for side, dtype in sides:
            v = check.check(cell, m.scene_arrays, m.sample, log=log,
                            dtype=dtype)
            print(json.dumps({
                "workload": cell.name, "seed": seed, "side": side,
                "fault": args.fault, "hold_gib": args.hold_gib,
                "device": m.result["device"],
                "correct": v.correct, "frames": len(m.frames),
                "checks": v.lines, "metrics": m.result["metrics"]}),
                flush=True)
    del held
    return 0


if __name__ == "__main__":
    sys.exit(main())
