"""Time the serve round spends stacking the slot carries into one batch,
in ms per traced round: the program's ``stack`` spans under ``build``."""

import program_spans


def read(ctx):
    return program_spans.ms_per_round(ctx.spans, "stack")
