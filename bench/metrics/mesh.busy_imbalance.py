"""Busiest device's busy time over the mean device's, in the traced
window: 1 when the stream mesh keeps every chip equally busy. Only a
cell on several devices has one."""


def read(ctx):
    busy = [ctx.trace.busy_s(d) for d in ctx.trace.devices]
    if len(busy) < 2 or sum(busy) <= 0:
        return None
    return max(busy) / (sum(busy) / len(busy))
