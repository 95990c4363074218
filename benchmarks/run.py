"""Benchmark harness: one module per paper table/figure.

Prints CSV (``key=value`` columns joined by commas) and writes
experiments/artifacts/bench_results.json. ``--only <name>`` selects one;
a selective run MERGES into the artifact (rows of re-run benches are
replaced, every other bench's committed rows survive).
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.compile_cache import enable_compile_cache

BENCHES = ("intersection", "warp_quality", "window_sweep", "ablation",
           "accelerator", "wallclock", "serve_bench", "cull_ablation")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=BENCHES, default=None)
    args = ap.parse_args()
    selected = (args.only,) if args.only else BENCHES

    all_rows = []
    for name in selected:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        rows = mod.run()
        dt = time.time() - t0
        for r in rows:
            print(",".join(f"{k}={v}" for k, v in r.items()), flush=True)
        print(f"# {name} done in {dt:.1f}s", flush=True)
        all_rows.extend(rows)

    out = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "artifacts", "bench_results.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    if os.path.exists(out):
        with open(out) as f:
            prev = json.load(f)
        fresh = {r["bench"] for r in all_rows}
        all_rows = [r for r in prev if r["bench"] not in fresh] + all_rows
    with open(out, "w") as f:
        json.dump(all_rows, f, indent=1)


if __name__ == "__main__":
    main()
