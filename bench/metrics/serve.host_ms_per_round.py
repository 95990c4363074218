"""Host time of a serve round, in ms: the program's ``round`` span less
its ``barrier`` child (the wait for the device), mean over the traced
rounds. It holds planning, batch building, dispatch and the commit,
which copies the frames to host memory."""


def read(ctx):
    rounds = [e for e in ctx.spans if e["name"] == "round"]
    if not rounds:
        return None
    barriers = [e for e in ctx.spans if e["name"] == "barrier"]
    host_us = 0.0
    for r in rounds:
        lo, hi = r["ts"], r["ts"] + r["dur"]
        waited = sum(b["dur"] for b in barriers
                     if lo <= b["ts"] and b["ts"] + b["dur"] <= hi)
        host_us += r["dur"] - waited
    return host_us / len(rounds) / 1e3
