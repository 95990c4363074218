"""Time the serve round spends folding a group's frame records into its
counters and the R policy, in ms per traced round: the program's
``observe`` spans under ``commit``. In a traced run they also hold the
benchmark's own transfer of the frame records (``work.frame_records``),
which the harness runs from a wrapper of the observed method."""

import program_spans


def read(ctx):
    return program_spans.ms_per_round(ctx.spans, "observe")
