"""Share of its roofline that the ``bulk_append`` kernel reached in the
traced window: the least time its streams need at the chip's HBM peak
(``chipbench.roofline``), over the kernel's device time.

A sharded deployment deals each batch over ``shards`` chips, and each
chip runs one call on its ``1 / shards`` of the entries: the bytes are
those of every call, against one chip's peak, over the kernel's time
summed over the chips."""
from chipbench import roofline

PROGRAM = r"jit_ingest"


def read(ctx):
    t, n = ctx.trace, ctx.window.get("ingest", 0)
    if t is None or not n:
        return None
    secs = t.kernel_s(PROGRAM)     # its one Pallas kernel, on each chip
    if secs <= 0:
        return None
    s = ctx.shapes.get("shards", 1)
    need = n * s * roofline.bulk_append_bytes(ctx.shapes["ingest_entries"]
                                              // s)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
