#!/usr/bin/env python3
"""Run one benchmark cell once; print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run it from the root of a checkout, on a machine that holds the chips
the cell asks for (``BENCHMARK.json``). Without a TPU, or with fewer
chips, it exits non-zero and prints no result. With ``--trace 0`` the
result holds the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, read from a JAX profiler trace of a few rounds.
Progress and the numbers the correctness check compared, each beside
its limit, go to standard error; the checks come last there too.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        log(f"bench: the program is not in this checkout ({e})")
        return 2
    # The compile cache: the one the environment names, else the
    # checkout's own .jax_cache, at a fixed path.
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import harness

    cell = harness.load_cell(args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START, log=log)
    except SystemExit as e:
        log(f"bench: {e}")
        return 1
    for name, line in result["checks"].items():
        log(f"{name} {line['value']!r} limit {line['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
