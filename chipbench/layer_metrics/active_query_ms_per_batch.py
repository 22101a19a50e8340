"""Device time of the active-segment query programs (the batched
conjunction over the slice pool, and its finalize) per query batch
dispatched in the traced window."""

PROGRAMS = r"jit_(run|finalize|finalize_scored)"


def read(ctx):
    t, n = ctx.trace, ctx.window.get("query", 0)
    if t is None or not n or not t.calls(PROGRAMS):
        return None
    return 1e3 * t.program_s(PROGRAMS) / n
