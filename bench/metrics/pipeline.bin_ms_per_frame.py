"""Device time under ``repro.frame/bin``, binning (per-tile selection and depth order), in ms per frame
delivered in the traced rounds, summed over devices."""

SCOPE = "repro.frame/bin"


def read(ctx):
    seconds = ctx.trace.scope_s(SCOPE)
    if seconds <= 0 or not ctx.frames:
        return None
    return 1e3 * seconds / len(ctx.frames)
