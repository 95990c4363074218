"""Time the serve round spends copying rendered frames to host memory,
in ms per traced round: the program's ``fetch`` spans (one per slot
whose frames are copied, under ``commit``)."""

import program_spans


def read(ctx):
    return program_spans.ms_per_round(ctx.spans, "fetch")
