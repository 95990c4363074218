"""Time a frame waits from its enqueue stamp to its group's dispatch, in
ms per frame: the program's ``serve_wait_seconds_total`` over its
``serve_frames_total``, both counted over the traced rounds. The
benchmark stamps each pose with the time it was due, so this is the
wait from due to dispatch."""


def read(ctx):
    wait = ctx.counters.get("serve_wait_seconds_total")
    frames = ctx.counters.get("serve_frames_total")
    if wait is None or not frames:
        return None
    return 1e3 * wait / frames
