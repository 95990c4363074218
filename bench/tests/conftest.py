"""Shared set-up for the benchmark's own tests (run on the CPU).

    python -m pytest bench/tests

Puts ``bench/`` and the program's ``src/`` on the import path and
gives the tests a tiny cell: the benchmark's code path end to end, at a
size the CPU holds.
"""
import copy
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

# The smallest scene that still has the room's statistics, on a camera
# of 6 x 4 tiles; limits from sound CPU runs of this size (see
# test_faults.py).
TINY = {
    "num_gaussians": 512, "image_width": 96, "image_height": 64,
    "tile_capacity": 512, "sh_degree": 3,
    "scene": {"generator": "structured_room", "clutter": 0.5, "room": 4.0},
    "camera": {"fov_deg": 60.0},
    "render": {"chunk": 64, "window": 5, "impl": "jnp_chunked"},
    "serve": {"r_buckets": [16]},
    "limits": {"key_rmse": 1e-4, "key_tile_relerr_p50": 1e-4,
               "sparse_rmse": 1e-4, "sparse_tile_relerr_p90": 1e-4},
}


def tiny_cell(streams=1, scenes=1, window=5):
    import harness
    with open(os.path.join(BENCH, "traffic", "head1.json")) as f:
        mix = json.load(f)
    mix.update(streams=streams, scenes=scenes)
    cfg = copy.deepcopy(TINY)
    cfg["render"]["window"] = window
    if window == 1:     # every frame a key frame: nothing sparse to read
        cfg["limits"] = {k: v for k, v in cfg["limits"].items()
                         if k.startswith("key_")}
    return harness.Cell(f"tiny_w{window}.head{streams}", cfg, mix, 1,
                        [], [])
