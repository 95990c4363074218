"""Device time under ``repro.frame/full``, the key-frame branch, in ms per frame
delivered in the traced rounds, summed over devices."""

SCOPE = "repro.frame/full"


def read(ctx):
    seconds = ctx.trace.scope_s(SCOPE)
    if seconds <= 0 or not ctx.frames:
        return None
    return 1e3 * seconds / len(ctx.frames)
