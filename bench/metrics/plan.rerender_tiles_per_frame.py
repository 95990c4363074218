"""Tiles a sparse frame renders anew, per sparse frame delivered in the
traced rounds: the plan's active slots, from the program's frame
records. Fewer means less work a frame; whether the frame is still right
is for ``correct``, which applies the sparse-frame rule itself."""


def read(ctx):
    sparse = [r["tiles"] for r in ctx.records if not r["is_full"]]
    if not sparse:
        return None
    return float(sum(sparse)) / len(sparse)
