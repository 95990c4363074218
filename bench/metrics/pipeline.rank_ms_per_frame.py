"""Device time under ``repro.frame/rank``, the depth-rank sort of every
Gaussian and the scatter of its ranks, in ms per frame delivered in the
traced rounds, summed over devices."""

SCOPE = "repro.frame/rank"


def read(ctx):
    seconds = ctx.trace.scope_s(SCOPE)
    if seconds <= 0 or not ctx.frames:
        return None
    return 1e3 * seconds / len(ctx.frames)
