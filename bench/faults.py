"""Faults planted in the program's sparse path, each one a way a change
could make sparse frames cheaper and worse. ``bench/tests/test_faults.py``
sees each come out as not correct at a CPU size; ``bench/calibrate.py
--fault <name>`` reads one at a cell's own size.

``install(name, patch)`` patches the program with ``patch(obj, attr,
value)`` (``setattr``, or pytest's ``monkeypatch.setattr``) before its
serve step is traced.
"""
from __future__ import annotations

from typing import Callable


def _r_quarter(patch: Callable) -> None:
    """R cut to a quarter: three in four re-render slots taken away, so
    the Morton tail of the re-render set is interpolated instead."""
    from repro.core import plan
    inner = plan.sparse_plan

    def cut(rerender, tiles_x, tiles_y, capacity):
        r = rerender.shape[0] if capacity is None else int(capacity)
        return inner(rerender, tiles_x, tiles_y, max(1, r // 4))
    patch(plan, "sparse_plan", cut)


def _demand_half(patch: Callable) -> None:
    """The per-tile test loosened: a tile is interpolated once half its
    pixels (not five sixths) were reached by the warp."""
    from repro.core import warp
    inner = warp.viewpoint_transform

    def loose(*args, **kwargs):
        kwargs["n0_ratio"] = 0.5
        return inner(*args, **kwargs)
    patch(warp, "viewpoint_transform", loose)


def _warp_skipped(patch: Callable) -> None:
    """The warp skipped: the frame before is reprojected into its own
    view, so every tile reads as reached and is passed on unmoved."""
    from repro.core import warp
    inner = warp.viewpoint_transform

    def still(rgb, exp_depth, trunc_depth, mask, ref_cam, tgt_cam, **kw):
        return inner(rgb, exp_depth, trunc_depth, mask, ref_cam, ref_cam,
                     **kw)
    patch(warp, "viewpoint_transform", still)


FAULTS = {"r_quarter": _r_quarter, "demand_half": _demand_half,
          "warp_skipped": _warp_skipped}


def install(name: str, patch: Callable = setattr) -> None:
    FAULTS[name](patch)
