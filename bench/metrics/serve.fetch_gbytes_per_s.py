"""Rate of the frame copy to host memory, in GB/s: the bytes the
program's ``serve_fetch_bytes_total`` counted over the traced rounds,
over the time of its ``fetch`` spans in them."""

import program_spans


def read(ctx):
    fetched = ctx.counters.get("serve_fetch_bytes_total")
    seconds = program_spans.seconds(ctx.spans, "fetch")
    if not fetched or seconds <= 0:
        return None
    return fetched / seconds / 1e9
