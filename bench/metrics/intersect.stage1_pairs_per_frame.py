"""Stage-1 (Gaussian, tile) pairs per frame delivered in the traced
rounds: the program's ``serve_stage1_pairs_total`` over its
``serve_frames_total``, both counted over the rounds. Each frame's
count is over the whole grid, before the pair list's budget: what the
intersect and the pair sort have to hold, and how near it comes to the
budget."""


def read(ctx):
    pairs = ctx.counters.get("serve_stage1_pairs_total")
    frames = ctx.counters.get("serve_frames_total")
    if pairs is None or not frames:
        return None
    return pairs / frames
