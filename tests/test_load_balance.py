"""LDU scheduling invariants (paper Sec. V-B) + hypothesis properties,
and the accelerator simulator's ablation claims on imbalanced frames."""
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core.load_balance import (load_stats, morton_order, morton_rank,
                                     schedule)
from repro.core.streaming import (AcceleratorConfig, FrameWork,
                                  simulate_sequence, throughput)


def test_morton_is_permutation():
    for tx, ty in [(4, 4), (8, 8), (8, 6), (16, 16)]:
        order = morton_order(tx, ty)
        assert sorted(order.tolist()) == list(range(tx * ty))


def test_morton_locality():
    """Z-order neighbors are spatially close: mean manhattan distance of
    consecutive tiles must beat row-major's long row jumps at same size."""
    tx = ty = 16
    order = morton_order(tx, ty)
    xy = np.stack([order % tx, order // tx], 1)
    d_morton = np.abs(np.diff(xy, axis=0)).sum(1).mean()
    assert d_morton < 2.0  # row-major scan has mean ~1.94 w/ 15-jumps; Z ~1.3


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(0, 5000), min_size=16, max_size=256),
       st.integers(2, 32))
def test_cap_property(workloads, b):
    """No block (except possibly the forced last) exceeds (1+1/N)W + one
    tile: the paper's deferral rule."""
    w = np.array(workloads, np.int64)
    t = len(w)
    tx = ty = int(np.ceil(np.sqrt(t)))
    w_full = np.zeros(tx * ty, np.int64)
    w_full[:t] = w
    sched = schedule(w_full, b, policy="ls_gaussian", tiles_x=tx, tiles_y=ty)
    w_ideal = max(w_full.sum() / b, 1.0)
    n_avg = max((tx * ty) / b, 1.0)
    cap = (1 + 1 / n_avg) * w_ideal
    loads = load_stats(sched, w_full)["block_loads"]
    for j in range(b - 1):  # last block takes the remainder by design
        ids = np.where(sched.block_of == j)[0]
        if len(ids) <= 1:
            continue
        assert loads[j] <= cap + w_full[ids].max(), (j, loads[j], cap)


def test_all_tiles_scheduled_once():
    rng = np.random.default_rng(0)
    w = rng.integers(0, 1000, size=64)
    s = schedule(w, 8, policy="ls_gaussian", tiles_x=8, tiles_y=8)
    assert np.all(s.block_of >= 0)
    seen = set()
    for j in range(8):
        for tid in s.tiles_of_block(j):
            assert tid not in seen
            seen.add(tid)
    assert len(seen) == 64


def test_light_to_heavy_order():
    rng = np.random.default_rng(1)
    w = rng.integers(0, 1000, size=64)
    s = schedule(w, 4, policy="ls_gaussian", tiles_x=8, tiles_y=8)
    for j in range(4):
        tiles = s.tiles_of_block(j)
        loads = w[tiles]
        assert np.all(np.diff(loads) >= 0), "intra-block must be ascending"


def test_inactive_tiles_skipped():
    w = np.ones(64, np.int64)
    active = np.zeros(64, bool)
    active[[3, 17, 42]] = True
    s = schedule(w, 4, policy="ls_gaussian", tiles_x=8, tiles_y=8,
                 active=active)
    assert set(np.where(s.block_of >= 0)[0]) == {3, 17, 42}


def test_morton_rank_inverts_morton_order():
    """Device morton_rank is the inverse permutation of numpy morton_order."""
    for tx, ty in [(4, 4), (8, 8), (8, 6), (16, 16)]:
        order = morton_order(tx, ty)
        rank = np.asarray(morton_rank(tx, ty))
        np.testing.assert_array_equal(np.argsort(rank, kind="stable"), order)


def _imbalanced_frame(rng, t=256, heavy_frac=0.08):
    """Order-of-magnitude tile-load spread, like the paper's Fig. 5.
    Raster-dominated (pairs >> gaussians); heavy tiles stay below a whole
    block's ideal budget, as DPES-culled real scenes do."""
    w = rng.integers(20, 80, size=t).astype(np.int64)
    heavy = rng.choice(t, int(t * heavy_frac), replace=False)
    w[heavy] = rng.integers(300, 700, size=len(heavy))
    return FrameWork(
        n_gaussians=2000, candidate_pairs=int(w.sum() * 1.2),
        raw_pairs=w * 2, sort_pairs=w, raster_pairs=w,
        active=np.ones(t, bool), n_warp_pixels=0, tiles_x=16, tiles_y=16)


def test_ls_schedule_beats_baseline_utilization():
    """Core claim of Tab. I: balanced distribution lifts utilization."""
    rng = np.random.default_rng(7)
    frames = [_imbalanced_frame(rng) for _ in range(6)]
    cfg = AcceleratorConfig(num_blocks=32)
    base = throughput(simulate_sequence(
        frames, cfg, policy="round_robin", workload_source="raw",
        light_to_heavy=False, streaming=False), cfg.num_blocks)
    ls = throughput(simulate_sequence(
        frames, cfg, policy="ls_gaussian", workload_source="dpes",
        light_to_heavy=True, streaming=True), cfg.num_blocks)
    assert ls["utilization"] > base["utilization"] + 0.1
    assert ls["cycles_per_frame"] < base["cycles_per_frame"]


def test_light_to_heavy_reduces_sort_stall():
    rng = np.random.default_rng(3)
    frames = [_imbalanced_frame(rng) for _ in range(6)]
    # sorter much slower: stalls become visible
    cfg = AcceleratorConfig(num_blocks=32, gsu_rate=2.0)
    with_ld2 = throughput(simulate_sequence(
        frames, cfg, policy="ls_gaussian", light_to_heavy=True),
        cfg.num_blocks)
    without = throughput(simulate_sequence(
        frames, cfg, policy="ls_gaussian", light_to_heavy=False),
        cfg.num_blocks)
    assert with_ld2["sort_stall"] <= without["sort_stall"] + 1e-6
