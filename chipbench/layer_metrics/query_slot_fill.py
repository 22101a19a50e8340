"""Share of the (query row, term slot) cells the engine evaluated that
the live queries' terms filled, over the window (``ServeStats``): what
the coalescer's padding costs (rows to a power of two, every query to
the slot bucket of its batch's longest)."""


def read(ctx):
    c = ctx.counters
    if not c.get("query_cells_dispatched"):
        return None
    return 100.0 * c["query_terms_live"] / c["query_cells_dispatched"]
