"""Whether a run's served frames are correct: the comparison and limits.

The sample is a seeded set of chains from the timed window (see
``harness.Sampler``): a key frame and the sparse frames its stream
served after it. The plain reference (``reference.py``) makes each chain
again from the same scene arrays and poses, in float32: the key frame by
a full render, and each sparse frame by the paper's sparse-frame rule
from the reference's own frame before (warp, the per-tile test, at most
R tiles rendered anew under their depth limit, holes filled, tiles
composed). So a sparse frame is held whole: its re-rendered tiles, its
warped and interpolated ones, the choice between them, and what the
carry brings from the frames before. Each compared number is named
``<kind>_<statistic>`` in the configuration's ``limits``, and is the
largest reading over the sampled frames of that kind:

- kind ``key`` (full renders) or ``sparse`` (frames by the sparse rule);
- statistic ``rmse``, the frame's RMS difference from the reference, or
  ``tile_relerr_p<q>``, the q-th percentile over the frame's lit 16 x 16
  tiles of each tile's RMS difference over the reference tile's RMS. A
  tile is lit when the reference puts any light in it; a tile no
  Gaussian reaches is black in both and says nothing.

A frame has to match the reference up to rounding. Rounding moves a few
things by a whole step: an exp that differs by an ulp can flip a
Gaussian across the 1/255 alpha threshold at a pixel, and a warped pixel
lying on a pixel border can land on the neighbour, which can move a tile
across the per-tile test. Such steps stay in a few tiles of a frame, so
the limits of the percentiles and of the RMS leave room for them; a
precision lower than float32 moves every tile. Every reading is printed
with more percentiles and the frame's PSNR beside it.

A frame that is missing, has the wrong shape or holds a non-finite value
fails outright. The readings the limits were set from are in PERF.md.
"""
from __future__ import annotations

import dataclasses
import re
import time
from typing import Dict, List, Sequence

import numpy as np

import reference

_LIMIT = re.compile(r"^(key|sparse)_(rmse|tile_relerr_p(\d+))$")


@dataclasses.dataclass
class Verdict:
    correct: bool
    failed: int
    lines: Dict[str, dict]


def rmse(a: np.ndarray, b: np.ndarray) -> float:
    d = np.asarray(a, np.float64) - np.asarray(b, np.float64)
    return float(np.sqrt(np.mean(d * d)))


def tile_relerr(a: np.ndarray, ref: np.ndarray, tile: int = 16
                ) -> np.ndarray:
    """Each lit tile x tile block's RMS difference over its RMS in ``ref``
    (blocks where ``ref`` is all black are left out)."""
    def blocks(x):
        x = np.asarray(x, np.float64)
        h, w = x.shape[0] // tile, x.shape[1] // tile
        x = x[:h * tile, :w * tile].reshape(h, tile, w, tile, -1)
        return np.sqrt(np.mean(x * x, axis=(1, 3, 4))).reshape(-1)
    diff, lit = blocks(np.asarray(a, np.float64) - ref), blocks(ref)
    return diff[lit > 0] / lit[lit > 0]


def psnr_db(e: float) -> float:
    return float(20.0 * np.log10(1.0 / max(e, 1e-12)))


def statistic(name: str, frame_rmse: float, tiles: np.ndarray) -> float:
    m = _LIMIT.match(name)
    if m.group(2) == "rmse":
        return frame_rmse
    if not len(tiles):          # an all-black frame: no tile to read
        return float("inf")
    return float(np.percentile(tiles, int(m.group(3))))


def slots(cfg: dict) -> int:
    """R, the tiles a sparse frame may render anew: the configuration's
    one R bucket. With several the program picks R from demand history,
    which a reference cannot follow."""
    if int(cfg["render"]["window"]) == 1:
        return (cfg["image_width"] // reference.TILE) * \
            (cfg["image_height"] // reference.TILE)
    buckets = cfg["serve"]["r_buckets"]
    if len(buckets) != 1:
        raise ValueError(f"sparse frames are checked at one R bucket, "
                         f"not {buckets}")
    return int(buckets[0])


def readings(cell, scene_arrays: dict, chains: Sequence, *,
             dtype="float32", log=print):
    """Each limit's reading per sampled frame of its kind.

    ``chains`` carry ``frames`` (each with ``rgb``, ``k`` and ``key``),
    ``poses`` from their ``key_frame`` on, ``stream`` and ``scene``.
    Returns ({limit name: [reading per frame]}, frames that failed
    outright, pairs the reference dropped past its cap). With ``dtype``
    other than float32 each frame's rgb is ignored and replaced by the
    reference computed in that precision at the same poses: the control.
    """
    cfg = cell.config
    names = list(cfg["limits"])
    for name in names:
        if not _LIMIT.match(name):
            raise ValueError(f"unknown limit {name!r}")
    cam = reference.intrinsics(cfg["image_width"], cfg["image_height"],
                               cfg["camera"]["fov_deg"])
    r = slots(cfg)
    shape = (cfg["image_height"], cfg["image_width"], 3)
    got: Dict[str, List[float]] = {n: [] for n in names}
    bad, dropped = 0, 0
    for chain in chains:
        scene = {k: v[chain.scene] for k, v in scene_arrays.items()}
        served = {f.k: f for f in chain.frames}
        ref = reference.stream(scene, chain.poses, cam, slots=r)
        control = reference.stream(scene, chain.poses, cam, slots=r,
                                   dtype=dtype) \
            if dtype != "float32" else None
        for k, (want, info) in enumerate(ref, start=chain.key_frame):
            t0 = time.perf_counter()
            rgb = next(control)[0] if control is not None else None
            ref_s = time.perf_counter() - t0
            dropped += info["pairs_past_cap"]
            f = served.get(k)
            if f is None:           # before the window: only carried
                continue
            if control is None:
                rgb = f.rgb
            if rgb is None or np.shape(rgb) != shape or \
                    not np.isfinite(rgb).all():
                bad += 1
                continue
            kind = "key" if f.key else "sparse"
            e, tiles = rmse(rgb, want), tile_relerr(rgb, want)
            for name in names:
                if name.startswith(kind + "_"):
                    got[name].append(statistic(name, e, tiles))
            pcts = " ".join(
                f"p{q}={statistic(f'{kind}_tile_relerr_p{q}', e, tiles)!r}"
                for q in (5, 50, 90, 99, 100))
            off = int(np.sum(tiles > 1e-3))
            log(f"frame stream={f.stream} k={k} {kind} rmse={e!r} "
                f"psnr_db={psnr_db(e)!r} lit_tiles={len(tiles)} "
                f"tiles_off_1e-3={off} tile_relerr {pcts} "
                f"rerendered={info['rerender']} "
                f"overflow_tiles={info['overflow_tiles']} "
                f"control_s={ref_s:.3f}")
    return got, bad, dropped


def check(cell, scene_arrays: dict, chains: Sequence, *, log=print,
          dtype="float32") -> Verdict:
    """Compare the sampled chains; print each number beside its limit."""
    limits = cell.config["limits"]
    t0 = time.perf_counter()
    got, bad, dropped = readings(cell, scene_arrays, chains, dtype=dtype,
                                 log=log)
    log(f"reference: {time.perf_counter() - t0:.3f} s for "
        f"{sum(len(c.poses) for c in chains)} frames; pairs past its "
        f"per-tile cap: {dropped}")
    lines: Dict[str, dict] = {}
    ok, failed = bad == 0, bad
    for name, limit in limits.items():
        values = got[name]
        # Every cell serves key frames; a cell with window > 1 serves
        # sparse ones too. A number with nothing to read is a fault.
        if not values:
            ok = False
            lines[name] = {"value": None, "limit": limit}
            continue
        over = sum(v > limit for v in values)
        failed += over
        ok = ok and over == 0
        lines[name] = {"value": max(values), "limit": limit}
    lines["frames_failed_outright"] = {"value": bad, "limit": 0}
    for name, line in lines.items():
        log(f"check {name} {line['value']!r} limit {line['limit']!r}")
    return Verdict(ok, failed, lines)
