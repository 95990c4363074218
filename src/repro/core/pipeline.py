"""LS-Gaussian end-to-end renderer: plan-driven full + TWSR sparse frames.

The streaming loop (paper Fig. 1): one full render every ``window`` frames;
in between, each frame is produced by viewpoint transformation (warp) +
tile-level decisions — interpolated tiles skip preprocess/sort/raster
entirely, re-rendered tiles go through the pipeline with DPES depth culling.

Every frame renders through ONE shared stage pipeline,
``render_planned_frame``: preprocess -> intersect -> (R, K) compacted
binning with DPES limits -> device-LDU schedule -> raster over the plan's
R slots -> scatter back to the full frame. Full frames carry an all-tiles
``TilePlan`` (R = T); TWSR frames carry the warp-predicted re-render set
compacted to ``R = rerender_capacity`` — so sparse-frame bins and raster
scale with R instead of T (DESIGN.md §2). TAIT's pair list covers the
whole grid, and its one sort serves any R (DESIGN.md §3).
``render_full_frame`` / ``render_sparse_frame`` are thin wrappers.

``render_trajectory`` (core/engine.py) is the production driver — the
whole loop as one jitted ``lax.scan``; ``render_trajectory_py`` below is
the host-side reference loop kept for golden comparison. Per-frame work
summaries (``FrameRecord``) — including the device-LDU block assignments
and per-block load summaries — feed both the GPU-style cost model and the
streaming accelerator simulator (core/streaming.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import binning, culling, intersect, warp as warp_mod
from repro.core import plan as plan_mod
from repro.core.camera import TILE, Camera
from repro.core.plan import TilePlan
from repro.core.projection import preprocess
from repro.core.raster import RenderOutput, render_plan_slots, untile
from repro.kernels.ops import default_impl
from repro.obs.trace import annotate


@dataclasses.dataclass(frozen=True)
class RenderConfig:
    intersect_method: str = "tait"      # "aabb" | "obb" | "tait" | "exact"
    capacity: int = 512                 # K: max pairs per tile
    chunk: int = 64                     # rasterizer gaussian-chunk
    # Raster kernel selection (DESIGN.md §9): "pallas_fused" (the fused
    # plan-slot sort+raster kernel — default on TPU) | "pallas" |
    # "jnp_chunked" (default elsewhere) | "ref".
    impl: str = dataclasses.field(default_factory=default_impl)
    window: int = 5                     # full render every n-th frame
    use_mask: bool = True               # no-cumulative-error mask (Fig. 7)
    use_dpes: bool = True
    dpes_margin: float = 1.0
    n0_ratio: float = warp_mod.N0_RATIO
    inpaint_iters: int = 8
    near: float = 0.05
    min_coverage: float = warp_mod.MIN_COVERAGE
    rerender_capacity: Optional[int] = None  # R: static cap on plan slots
    ldu_blocks: int = 32                # B: parallel raster blocks (LDU)
    # Temporal contribution culling (core/culling.py, DESIGN.md §12): on
    # sparse frames, drop intersection pairs whose Gaussian contributed
    # < cull_threshold blend mass at the last key frame, before binning.
    # 0.0 = the pass is structurally skipped (bit-exact baseline).
    cull_threshold: float = 0.0
    # Populate FrameRecord.lane_contrib / FrameState.contrib even with
    # culling off (e.g. to inspect the 0.0 baseline's statistics). The
    # machinery is always on when cull_threshold > 0.
    record_contrib: bool = False


def contrib_enabled(cfg: RenderConfig) -> bool:
    """Static switch: is the contribution/prior machinery threaded?

    When False (the default), ``FrameState.contrib``,
    ``PlanStats.gauss_prior`` and ``FrameRecord.lane_contrib`` stay
    ``None`` — absent from the pytree — so carries, records and compiled
    executables are structurally identical to the pre-culling pipeline.
    """
    return cfg.cull_threshold > 0.0 or cfg.record_contrib


class FrameState(NamedTuple):
    """Reference-frame state carried across the streaming loop."""

    rgb: jax.Array          # (H, W, 3)
    exp_depth: jax.Array    # (H, W)
    trunc_depth: jax.Array  # (H, W)
    source_mask: jax.Array  # (H, W) bool — usable reprojection sources
    frame_idx: jax.Array    # () int32 — true global frame index
    # Key-frame per-Gaussian contribution prior (inf = not considered at
    # the key frame). None unless ``contrib_enabled(cfg)`` — a None leaf
    # vanishes from the pytree, keeping default-path carries unchanged.
    contrib: Optional[jax.Array] = None  # (N,) float32


class FrameRecord(NamedTuple):
    """Per-frame workload summary (device arrays; host converts for sims)."""

    is_full: jax.Array          # () bool
    n_gaussians: jax.Array      # () int32 — valid after frustum cull
    candidate_pairs: jax.Array  # () int32 — pairs entering stage-2 test
    raw_pairs: jax.Array        # (T,) pre-DPES pairs on scheduled tiles
    sort_pairs: jax.Array       # (T,) post-DPES pairs entering sort
    raster_pairs: jax.Array     # (T,) pairs actually traversed
    active: jax.Array           # (T,) bool — re-rendered tiles
    tiles_interpolated: jax.Array  # () int32
    overflow_pairs: jax.Array   # () int32 — bin-capacity overflow
    overflow_tiles: jax.Array   # () int32 — rerender_capacity overflow
    block_of_tile: jax.Array    # (T,) int32 — device-LDU block (-1 = none)
    order_in_block: jax.Array   # (T,) int32 — light-to-heavy position
    block_load: jax.Array       # (B,) int32 — predicted pairs per block
    culled_pairs: jax.Array     # () int32 — pairs removed by culling
    pair_budget_overflow: jax.Array  # () int32 — pairs past the pair budget
    stage1_pairs: jax.Array     # () int32 — stage-1 pairs on the whole grid
    # Per-(tile, lane) blend contribution in bin lane order (DESIGN.md
    # §12); None unless ``contrib_enabled(cfg)``.
    lane_contrib: Optional[jax.Array] = None  # (T, K) float32


class PlanStats(NamedTuple):
    """Per-slot counters from the shared stage pipeline (R-shaped)."""

    candidate_pairs: jax.Array  # () int32 — stage-2 candidates on the plan
    raw_slots: jax.Array        # (R,) pre-DPES pairs per slot
    overflow_pairs: jax.Array   # () int32 — bin-capacity overflow
    culled_pairs: jax.Array     # () int32 — pairs removed by culling
    # Stage-1 pairs past the pair list's budget, dropped (0 on the dense
    # path, which has no budget).
    pair_budget_overflow: jax.Array  # () int32
    # Stage-1 (bbox) pairs over the whole grid, whatever the plan: what
    # the pair list has to hold, before its budget.
    stage1_pairs: jax.Array  # () int32
    # Per-Gaussian contribution with inf where not considered — what key
    # frames store as FrameState.contrib. None unless contrib_enabled.
    gauss_prior: Optional[jax.Array] = None  # (N,) float32


def _tile_flag_to_pixels(flag: jax.Array, tiles_x: int, tiles_y: int):
    """(T,) -> (H, W) by broadcasting each flag over its tile."""
    t = flag.shape[0]
    tiles = jnp.broadcast_to(flag[:, None, None], (t, TILE, TILE))
    return untile(tiles, tiles_x, tiles_y)


# Scenes of more Gaussians than this get a pair budget of at least
# PAIRS_PER_GAUSSIAN slots a Gaussian (DESIGN.md §3).
PAIR_BUDGET_MIN_N = 2 ** 16
PAIRS_PER_GAUSSIAN = 8


def pair_budget(num_tiles: int, capacity: int, num_gaussians: int) -> int:
    """Pair slots of a frame's TAIT pair list: T * K, raised to
    ``PAIRS_PER_GAUSSIAN * N`` for scenes above ``PAIR_BUDGET_MIN_N``.

    N is the scene's row count, its bucket's padded N when served, so
    the budget is fixed per executable. At 1080p (T * K = 8,355,840 at
    K = 1,024) every bucket up to 65,536 keeps T * K; 2**21 rows get
    2**24 slots, for the ~10 M stage-1 pairs of a 2,000,000-Gaussian
    room.
    """
    budget = num_tiles * capacity
    if num_gaussians > PAIR_BUDGET_MIN_N:
        budget = max(budget, PAIRS_PER_GAUSSIAN * num_gaussians)
    return budget


def _stage1_total(proj, grid) -> jax.Array:
    """Stage-1 pairs over the whole grid: the area of each Gaussian's
    tile rectangle, summed (the count ``tait_pairs`` reports as
    ``total``)."""
    _, extent = intersect.tile_rects(proj.mean2d, proj.tight_half_wh,
                                     proj.valid, grid.tiles_x, grid.tiles_y)
    return jnp.sum(extent[:, 0] * extent[:, 1])


def _dense_bins(proj, grid, plan: TilePlan, cfg: RenderConfig, limit,
                cull_prior, cull_gate):
    """Intersect, cull and bin through the dense (N, R) mask and a
    per-slot ``top_k``: the ablation intersect methods' path, and the
    oracle the pair list is pinned to. Returns ``(bins, plan, stats)``."""
    slots = intersect.take_tiles(grid, plan.tile_ids)
    with annotate("repro.frame/intersect"):
        if cfg.intersect_method == "tait":
            stage1 = intersect.tait_stage1_mask(proj, slots)
            mask = intersect.tait_mask(proj, slots)
            cand_src = stage1
        else:
            mask = intersect.intersect(proj, slots, cfg.intersect_method)
            cand_src = mask
        candidate_pairs = jnp.sum(
            (cand_src & plan.slot_active[None, :]).astype(jnp.int32))
        mask = mask & plan.slot_active[None, :]
        stage1_pairs = _stage1_total(proj, grid)
    with annotate("repro.frame/cull"):
        if cull_prior is not None:
            mask, slot_active, culled_pairs = culling.cull_pairs(
                mask, plan.slot_active, plan.tile_ids, cull_prior,
                cull_gate, cfg.cull_threshold)
            plan = plan._replace(slot_active=slot_active)
        else:
            culled_pairs = jnp.int32(0)
        raw_slots = jnp.sum(mask.astype(jnp.int32), axis=0)

    with annotate("repro.frame/bin"):
        bins = binning.build_tile_bins(mask, proj.depth, cfg.capacity,
                                       depth_limit=limit)
    stats = PlanStats(candidate_pairs=candidate_pairs, raw_slots=raw_slots,
                      overflow_pairs=jnp.sum(bins.overflow),
                      culled_pairs=culled_pairs,
                      pair_budget_overflow=jnp.int32(0),
                      stage1_pairs=stage1_pairs)
    return bins, plan, stats


def _pair_list_bins(proj, grid, plan: TilePlan, cfg: RenderConfig, limit,
                    cull_prior, cull_gate, *, budget: Optional[int] = None):
    """TAIT intersect, cull and bin through a pair list sorted once
    (DESIGN.md §3): the same bins, plan and stats as ``_dense_bins``
    while the frame's stage-1 pairs fit the budget, ``pair_budget``'s
    unless ``budget`` says otherwise; pairs past it are counted."""
    t = grid.num_tiles
    if budget is None:
        budget = pair_budget(t, cfg.capacity, proj.depth.shape[0])
    with annotate("repro.frame/intersect"):
        pairs = intersect.tait_pairs(
            proj, grid, budget,
            extra=None if cull_prior is None else cull_prior[None, :])
        # Groups: 0 binned, 1 culled (with culling on), last stage 1 only.
        miss = 1 if cull_prior is None else 2
        group = jnp.where(pairs.hit, 0, miss)
    with annotate("repro.frame/cull"):
        culled_pairs = jnp.int32(0)
        if cull_prior is not None:
            culled = culling.cull_pair_list(
                pairs.hit, pairs.tile, pairs.extra[0], plan.slot_active,
                plan.tile_ids, cull_gate, cfg.cull_threshold)
            culled_pairs = jnp.sum(culled.astype(jnp.int32))
            group = jnp.where(culled, 1, group)
        group = jnp.where(pairs.stage1, group, miss + 1)

    with annotate("repro.frame/bin"):
        rank_limit = None if limit is None else jnp.searchsorted(
            pairs.depth, limit, side="right")
        bins, counts = binning.bin_pair_list(
            pairs.tile, group, pairs.rank, pairs.gauss, num_groups=miss + 1,
            num_tiles=t, num_gaussians=proj.depth.shape[0],
            tile_ids=plan.tile_ids, slot_active=plan.slot_active,
            capacity=cfg.capacity, rank_limit=rank_limit)
    raw_slots = counts[:, 0]
    if cull_prior is not None:
        plan = plan._replace(slot_active=culling.demote_emptied(
            plan.slot_active, raw_slots + counts[:, 1], raw_slots))
    stats = PlanStats(candidate_pairs=jnp.sum(counts), raw_slots=raw_slots,
                      overflow_pairs=jnp.sum(bins.overflow),
                      culled_pairs=culled_pairs,
                      pair_budget_overflow=pairs.dropped,
                      stage1_pairs=pairs.total)
    return bins, plan, stats


def render_planned_frame(scene, cam: Camera, plan: TilePlan,
                         cfg: RenderConfig, *,
                         dpes_depth: Optional[jax.Array] = None,
                         cull_prior: Optional[jax.Array] = None,
                         cull_gate: Optional[jax.Array] = None
                         ) -> Tuple[RenderOutput, TilePlan, "jax.Array",
                                    PlanStats]:
    """The ONE shared stage pipeline every frame renders through.

    preprocess -> intersect against the plan's R slots -> contribution
    cull -> (R, K) compacted binning (with per-slot DPES depth limits) ->
    device-LDU schedule over the slots -> raster the slots -> scatter
    back to the (H, W) frame. TAIT intersects and bins through a pair
    list sorted once (``_pair_list_bins``); the other intersect methods
    through the dense mask and ``top_k`` (``_dense_bins``).

    dpes_depth: optional (T,) per-tile early-stop depth (inf = no prior);
    gathered to the plan's slots before binning.

    cull_prior: optional (N,) key-frame contribution prior (inf = not
    considered); with ``cfg.cull_threshold > 0`` low-contribution pairs
    are removed before binning in slots passed by ``cull_gate`` ((T,)
    bool, default all-True), and fully-culled slots are demoted to
    interpolation (core/culling.py). With the default threshold 0.0 the
    pass is structurally absent and the pipeline is bit-exact with the
    pre-culling code.

    Returns ``(out, plan, n_gaussians, stats)`` where ``out`` is the
    full-frame RenderOutput (unplanned tiles empty), ``plan`` now carries
    the LDU schedule + per-slot workloads, and ``stats`` the remaining
    per-slot counters the wrappers fold into a ``FrameRecord``.
    """
    with annotate("repro.frame/preprocess"):
        proj = preprocess(scene, cam, near=cfg.near)
        grid = intersect.make_tile_grid(cam)
        slots = intersect.take_tiles(grid, plan.tile_ids)

    limit = None
    if dpes_depth is not None:
        limit = dpes_depth[plan.tile_ids] * cfg.dpes_margin
    if cfg.cull_threshold > 0.0 and cull_prior is not None:
        if cull_gate is None:
            cull_gate = jnp.ones((cam.num_tiles,), bool)
    else:
        cull_prior = cull_gate = None
    stage = _pair_list_bins if cfg.intersect_method == "tait" \
        else _dense_bins
    bins, plan, stats = stage(proj, grid, plan, cfg, limit, cull_prior,
                              cull_gate)
    # Device LDU (paper Sec. V-B): post-DPES counts are the workload
    # prediction; the greedy Morton fill + light-to-heavy order runs in
    # jnp, inside whatever jit/scan wraps this frame.
    with annotate("repro.frame/ldu_schedule"):
        plan = plan_mod.schedule_plan(plan, bins.count, cfg.ldu_blocks)

    with annotate("repro.frame/raster"):
        out = render_plan_slots(proj, bins, slots.origins, plan.tile_ids,
                                grid, impl=cfg.impl, chunk=cfg.chunk,
                                slot_active=plan.slot_active)
    if contrib_enabled(cfg):
        # A Gaussian was "considered" if it occupies a valid bin lane
        # anywhere on the plan; everyone else gets inf (= always keep) so
        # Gaussians outside this frame's view are never culled later.
        n = proj.depth.shape[0]
        considered = jnp.zeros((n,), jnp.int32).at[bins.indices].add(
            bins.valid.astype(jnp.int32)) > 0
        stats = stats._replace(gauss_prior=jnp.where(
            considered, out.gauss_contrib, jnp.inf))
    n_gaussians = jnp.sum(proj.valid.astype(jnp.int32))
    return out, plan, n_gaussians, stats


def _plan_record(plan: TilePlan, stats: PlanStats, out: RenderOutput,
                 n_gaussians: jax.Array, num_tiles: int, cfg: RenderConfig,
                 *, is_full: bool, tiles_interpolated: jax.Array
                 ) -> FrameRecord:
    """Fold plan-slot counters into the (T,)-shaped FrameRecord."""
    scat = functools.partial(plan_mod.scatter_slots, plan,
                             num_tiles=num_tiles)
    return FrameRecord(
        is_full=jnp.bool_(is_full),
        n_gaussians=n_gaussians,
        candidate_pairs=stats.candidate_pairs,
        raw_pairs=scat(stats.raw_slots),
        sort_pairs=scat(plan.workload),
        raster_pairs=out.processed_pairs,
        active=scat(plan.slot_active, fill=False),
        tiles_interpolated=tiles_interpolated,
        overflow_pairs=stats.overflow_pairs,
        overflow_tiles=plan.overflow_tiles,
        block_of_tile=scat(plan.block_of, fill=-1),
        order_in_block=scat(plan.order_in_block),
        block_load=plan_mod.block_loads(plan, cfg.ldu_blocks),
        culled_pairs=stats.culled_pairs,
        pair_budget_overflow=stats.pair_budget_overflow,
        stage1_pairs=stats.stage1_pairs,
        # Slot-shaped (R, K) from render_plan_slots -> (T, K) per-tile;
        # gated so the dense view only exists when the record wants it
        # (sparse compiles stay plan-shaped otherwise).
        lane_contrib=scat(out.lane_contrib) if contrib_enabled(cfg)
        else None)


def render_full_frame(scene, cam: Camera, cfg: RenderConfig,
                      frame_idx: Union[int, jax.Array] = 0
                      ) -> Tuple[RenderOutput, FrameState, FrameRecord]:
    """Key frame: ``render_planned_frame`` with an all-tiles plan (R = T).

    ``frame_idx`` is the frame's true global index — mid-trajectory key
    frames must not reset the carried counter (it threads through
    ``FrameState`` for the engine's golden comparison).
    """
    tplan = plan_mod.full_plan(cam.tiles_x, cam.tiles_y)
    out, tplan, n_gaussians, stats = render_planned_frame(
        scene, cam, tplan, cfg)

    coverage = 1.0 - out.transmittance
    state = FrameState(
        rgb=out.rgb, exp_depth=out.exp_depth, trunc_depth=out.trunc_depth,
        source_mask=coverage > cfg.min_coverage,
        frame_idx=jnp.asarray(frame_idx, jnp.int32),
        contrib=stats.gauss_prior)
    rec = _plan_record(tplan, stats, out, n_gaussians, cam.num_tiles, cfg,
                       is_full=True, tiles_interpolated=jnp.int32(0))
    return out, state, rec


def render_sparse_frame(scene, ref_cam: Camera, tgt_cam: Camera,
                        state: FrameState, cfg: RenderConfig
                        ) -> Tuple[jax.Array, FrameState, FrameRecord]:
    """TWSR frame (Algo. 1): warp, plan the re-render set, render the plan.

    The warp's tile decisions become a compacted ``TilePlan`` with
    ``R = rerender_capacity`` slots (or R = T when uncapped); re-render
    tiles beyond R degrade to interpolation and are counted.
    """
    with annotate("repro.frame/warp"):
        w = warp_mod.viewpoint_transform(
            state.rgb, state.exp_depth, state.trunc_depth,
            state.source_mask, ref_cam, tgt_cam, n0_ratio=cfg.n0_ratio,
            near=cfg.near)
        tplan = plan_mod.sparse_plan(w.rerender_tile, tgt_cam.tiles_x,
                                     tgt_cam.tiles_y,
                                     cfg.rerender_capacity)

    limit = jnp.where(jnp.isfinite(w.dpes_depth), w.dpes_depth, jnp.inf) \
        if cfg.use_dpes else None
    gate = culling.warp_gate(w.valid_per_tile) \
        if cfg.cull_threshold > 0.0 else None
    out, tplan, n_gaussians, stats = render_planned_frame(
        scene, tgt_cam, tplan, cfg, dpes_depth=limit,
        cull_prior=state.contrib, cull_gate=gate)
    # Effective re-render set: plan slots that survived compaction.
    rerender = plan_mod.scatter_slots(tplan, tplan.slot_active,
                                      num_tiles=tgt_cam.num_tiles,
                                      fill=False)

    # --- compose the final frame -----------------------------------------
    # Interpolated tiles: warped pixels + diffusion-inpainted holes; the
    # depth maps ride the same inpainting so chaining stays consistent.
    with annotate("repro.frame/compose"):
        stacked = jnp.concatenate(
            [w.rgb, w.exp_depth[..., None], w.trunc_depth[..., None]],
            axis=-1)
        inpainted = warp_mod.inpaint(stacked, w.filled,
                                     iters=cfg.inpaint_iters)
        rgb_warp = inpainted[..., :3]
        depth_warp = inpainted[..., 3]
        trunc_warp = inpainted[..., 4]

        rr_px = _tile_flag_to_pixels(rerender, tgt_cam.tiles_x,
                                     tgt_cam.tiles_y)
        rgb_final = jnp.where(rr_px[..., None], out.rgb, rgb_warp)
        exp_depth = jnp.where(rr_px, out.exp_depth, depth_warp)
        trunc_depth = jnp.where(rr_px, out.trunc_depth, trunc_warp)

    # --- next-frame source mask (the "TW w/ mask" mechanism) -------------
    coverage_ok = (1.0 - out.transmittance) > cfg.min_coverage
    interpolated_px = (~rr_px) & (~w.filled)
    if cfg.use_mask:
        src = jnp.where(rr_px, coverage_ok, w.filled)
    else:
        src = jnp.where(rr_px, coverage_ok,
                        w.filled | interpolated_px)
    # Priors refresh only at key frames; sparse frames carry them through.
    new_state = FrameState(rgb=rgb_final, exp_depth=exp_depth,
                           trunc_depth=trunc_depth, source_mask=src,
                           frame_idx=state.frame_idx + 1,
                           contrib=state.contrib)
    rec = _plan_record(
        tplan, stats, out, n_gaussians, tgt_cam.num_tiles, cfg,
        is_full=False,
        tiles_interpolated=jnp.sum(w.interpolate_tile.astype(jnp.int32)))
    return rgb_final, new_state, rec


class StackedRecords:
    """Scan-stacked per-frame records.

    Every ``FrameRecord`` field carries a leading frame axis ``(F, ...)``
    (or ``(B, F, ...)`` for multi-stream renders) — the natural output
    layout of ``lax.scan``, and one host transfer per trajectory instead
    of one per frame. Attribute access returns the stacked array
    (``records.raster_pairs`` -> ``(F, T)``); indexing recovers a
    per-frame ``FrameRecord`` view (``records[i].raster_pairs`` ->
    ``(T,)``).
    """

    __slots__ = ("stacked",)

    def __init__(self, stacked: FrameRecord):
        self.stacked = stacked

    @classmethod
    def from_list(cls, records: Sequence[FrameRecord]) -> "StackedRecords":
        return cls(jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *records))

    def __len__(self) -> int:
        return int(self.stacked.is_full.shape[0])

    def __getitem__(self, i) -> FrameRecord:
        return jax.tree_util.tree_map(lambda a: a[i], self.stacked)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __getattr__(self, name):
        return getattr(self.stacked, name)


class TrajectoryResult(NamedTuple):
    frames: jax.Array              # (F, H, W, 3)
    records: StackedRecords
    states: Optional[FrameState]   # stacked (F, ...) when keep_states


def render_trajectory(scene, cam: Camera, poses: jax.Array,
                      cfg: RenderConfig, *, keep_states: bool = False,
                      phase: Union[int, jax.Array] = 0
                      ) -> TrajectoryResult:
    """Render a pose sequence with the LS-Gaussian streaming loop.

    Delegates to the scanned engine (core/engine.py): the full/sparse
    loop compiles to ONE executable with no per-frame host dispatch.
    poses: (F, 4, 4) world-to-camera per frame. Frame f is fully rendered
    when (f + phase) % cfg.window == 0, warped otherwise.
    """
    from repro.core import engine  # local import: engine builds on us
    return engine.render_trajectory(scene, cam, poses, cfg,
                                    keep_states=keep_states, phase=phase)


@functools.lru_cache(maxsize=16)
def _legacy_frame_fns(cfg: RenderConfig):
    """Per-config jitted frame functions for the legacy loop. Cached so
    repeated calls (and wall-clock timings) hit warm jit caches instead
    of re-tracing fresh wrappers every trajectory."""
    return (jax.jit(functools.partial(render_full_frame, cfg=cfg)),
            jax.jit(functools.partial(render_sparse_frame, cfg=cfg)))


def render_trajectory_py(scene, cam: Camera, poses: jax.Array,
                         cfg: RenderConfig, *, keep_states: bool = False
                         ) -> TrajectoryResult:
    """Legacy host-side driver: one jitted dispatch per frame.

    Kept as the golden reference for the scanned engine (it is the
    original, straightforwardly-auditable loop). Frame f is fully
    rendered when f % cfg.window == 0, warped otherwise.
    """
    full_fn, sparse_fn = _legacy_frame_fns(cfg)

    frames, records, states = [], [], []
    state = None
    ref_cam = None
    for f in range(poses.shape[0]):
        cam_f = cam.with_pose(poses[f])
        if f % cfg.window == 0 or state is None:
            out, state, rec = full_fn(scene, cam_f,
                                      frame_idx=jnp.int32(f))
            frames.append(out.rgb)
        else:
            rgb, state, rec = sparse_fn(scene, ref_cam, cam_f, state)
            frames.append(rgb)
        ref_cam = cam_f
        records.append(rec)
        if keep_states:
            states.append(state)
    stacked_states = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *states) if keep_states else None
    return TrajectoryResult(frames=jnp.stack(frames),
                            records=StackedRecords.from_list(records),
                            states=stacked_states)
