"""Driver tiles the active early-exit top-k scanned per live query row
over the window (``ServeStats``): how far each query read its shortest
list, 128 postings a tile, before it banked ``k`` hits or ran out."""


def read(ctx):
    c = ctx.counters
    if not c.get("topk_rows_live"):
        return None
    return c["topk_tiles_scanned"] / c["topk_rows_live"]
