"""Contribution-culling ablation bench (DESIGN.md §12).

``run()`` (the ``benchmarks.run`` entry) sweeps ``cull_threshold`` over
the standard bench scene/trajectory and emits one row per threshold —
sparse-frame PSNR/SSIM against the uncull render, total sort pairs,
re-render demand, culled pairs, and wall clock — next to a threshold-0
reference row. The sweep itself lives in
``benchmarks.wallclock.cull_ablation_rows`` so it shares the wallclock
harness (scenes, timing) while keeping its own ``bench`` key: re-running
``--only cull_ablation`` replaces exactly these rows in
experiments/artifacts/bench_results.json.

``python -m benchmarks.cull_ablation --smoke`` is the CI entry: a
scoped-down single-threshold pass that asserts the culling contract —
every sparse frame >= 30 dB PSNR vs uncull, sort_pairs strictly
decreased, pairs actually culled, and demand not increased.
"""
from __future__ import annotations

import argparse
from typing import List

from benchmarks.common import camera, scenes, trajectory
from benchmarks.wallclock import CULL_THRESHOLDS, cull_ablation_rows
from repro.compile_cache import enable_compile_cache

N_FRAMES = 8
SMOKE_THRESHOLD = 0.05


def run() -> List[dict]:
    cam = camera()
    scene = scenes()["indoor"]
    # The orbit trajectory disoccludes every frame, so sparse frames
    # carry real re-render demand — the slow indoor dolly warps cleanly
    # at bench resolution and would leave the cull nothing to do.
    poses = trajectory("orbit", N_FRAMES)
    return cull_ablation_rows(scene, cam, poses, CULL_THRESHOLDS)


def smoke() -> List[dict]:
    """Small-scene single-threshold pass with hard assertions (CI)."""
    cam = camera(96, 96)
    scene = scenes(1500)["indoor"]
    poses = trajectory("indoor", 6)
    rows = cull_ablation_rows(scene, cam, poses, (SMOKE_THRESHOLD,),
                              window=3, rerender_capacity=18, capacity=128)
    base, row = rows[0], rows[-1]
    assert row["psnr_min_db"] >= 30.0, \
        f"sparse-frame PSNR fell below 30 dB vs uncull: {row}"
    assert row["sort_pairs"] < base["sort_pairs"], \
        f"culling did not reduce sort pairs: {row}"
    assert row["culled_pairs"] > 0, f"nothing was culled: {row}"
    assert row["rerender_demand"] <= base["rerender_demand"], \
        f"culling increased re-render demand: {row}"
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="scoped-down pass with hard assertions (CI)")
    args = ap.parse_args()
    rows = smoke() if args.smoke else run()
    for r in rows:
        print(",".join(f"{k}={v}" for k, v in r.items()), flush=True)
    if args.smoke:
        print("# cull_ablation smoke OK", flush=True)


if __name__ == "__main__":
    main()
