"""Device time of the ingest programs (flatten and the allocator's bulk
ingest step with its ``bulk_append`` kernel) per batch applied in the
traced window."""

PROGRAMS = r"jit_(ingest|flatten)"


def read(ctx):
    t, n = ctx.trace, ctx.window.get("ingest", 0)
    if t is None or not n or not t.calls(PROGRAMS):
        return None
    return 1e3 * t.program_s(PROGRAMS) / n
