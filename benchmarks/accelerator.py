"""Figs. 14/15a + Table I reproduction: the streaming accelerator.

Drives the discrete-event simulator (core/streaming.py) with REAL per-
frame workload records from the rendered pipeline (not synthetic loads).

Configurations:
  gpu_like    : dynamic scheduler, raw workloads, no streaming — the
                Jetson-GPU stand-in the speedups are measured against.
  gscore_like : dedicated units (streaming across frames), round-robin
                blocks, raw workloads           (Fig. 14 "GSCore")
  +LD1        : + LDU inter-block balancing on DPES predictions
  +LD2 (full) : + light-to-heavy intra-block order (LS-Gaussian)

Every mode schedules on the host (numpy ``load_balance.schedule``) from
the records' workloads and re-render sets; the renderer computes no
schedule of its own.

Table I = raster-core utilization of gscore_like vs full LS-Gaussian.
"""
from __future__ import annotations

from typing import List

from benchmarks.common import camera, scenes, trajectory
from repro.core.pipeline import RenderConfig, render_trajectory
from repro.core.streaming import AcceleratorConfig, frameworks_from_stacked, \
    simulate_sequence, throughput

N_FRAMES = 12

MODES = {
    "gpu_like": dict(policy="dynamic", workload_source="raw",
                     light_to_heavy=False, streaming=False),
    "gscore_like": dict(policy="round_robin", workload_source="raw",
                        light_to_heavy=False, streaming=True),
    "ld1": dict(policy="ls_gaussian", workload_source="dpes",
                light_to_heavy=False, streaming=True),
    "ls_gaussian": dict(policy="ls_gaussian", workload_source="dpes",
                        light_to_heavy=True, streaming=True),
}


def run() -> List[dict]:
    # Tab. I measures RASTER-phase utilization under real per-tile skew:
    # full frames (window=1 — the paper's utilization table predates the
    # sparse-rendering savings), higher resolution, clutter-heavy scenes
    # (Fig. 5's order-of-magnitude tile-load spread).
    cam = camera(256, 256)
    acfg = AcceleratorConfig(num_blocks=32)
    rows = []
    for scene_name in ("indoor", "outdoor", "synthetic"):
        scene = scenes(6000)[scene_name]
        poses = trajectory("indoor" if scene_name != "outdoor" else
                           "outdoor", N_FRAMES)
        res = render_trajectory(scene, cam, poses, RenderConfig(window=1))
        frames = frameworks_from_stacked(res.records, cam.tiles_x,
                                         cam.tiles_y,
                                         cam.width * cam.height)
        base_cycles = None
        for mode, kw in MODES.items():
            t = throughput(simulate_sequence(frames, acfg, **kw),
                           acfg.num_blocks)
            if base_cycles is None:
                base_cycles = t["cycles_per_frame"]
            rows.append({
                "bench": "fig14_15_accelerator", "scene": scene_name,
                "mode": mode,
                "cycles_per_frame": int(t["cycles_per_frame"]),
                "speedup_vs_gpu_like": round(
                    base_cycles / t["cycles_per_frame"], 2),
                "utilization_pct": round(100 * t["utilization"], 1),
                "sort_stall": int(t["sort_stall"]),
            })
    return rows
