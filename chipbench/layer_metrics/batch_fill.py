"""Queries served per batch the coalescer dispatched in the window
(``ServeStats``): how much of each dispatch's fixed cost is shared."""


def read(ctx):
    c = ctx.counters
    if not c.get("batches_dispatched"):
        return None
    return c["queries_served"] / c["batches_dispatched"]
