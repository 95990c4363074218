"""Share of its roofline, in %, that the fused sort+raster kernel
(``raster_plan_fused``) reaches: the raster stage's least time on this
chip (``bench/work.py`` over the traced frames' records, peaks from
``bench/peaks.json``) over the device time of the kernel's operations.
Under vmap the kernel also runs for the branch a frame does not take;
that time counts, the work of the untaken branch does not."""

import work

SCOPE = "repro.raster/pallas_fused"


def is_kernel(kind, scope):
    """The kernel's own ops: its Mosaic custom call, not the copies and
    reshapes around it."""
    return SCOPE in scope and kind == "custom-call"


def read(ctx):
    if ctx.peaks is None or not ctx.records:
        return None
    seconds = ctx.trace.match_s(is_kernel)
    if seconds <= 0:
        return None
    least, bound = work.least_time(ctx.records, ctx.peaks)
    ctx.log(f"raster_plan_fused_roofline: bound by {bound}, least "
            f"{least!r} s of {seconds!r} s")
    return 100.0 * least / seconds
