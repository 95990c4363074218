"""Share of the traced window, in %, in which a device runs no
operation, mean over the cell's devices."""


def read(ctx):
    devs = ctx.trace.devices
    if not devs or ctx.trace.window_s <= 0:
        return None
    busy = sum(ctx.trace.busy_s(d) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / ctx.trace.window_s)
