"""Share of its roofline that the ``bulk_append`` kernel reached in the
traced window: the least time its streams need at the chip's HBM peak
(``chipbench.roofline``), over the kernel's device time."""
from chipbench import roofline

PROGRAM = r"jit_ingest"


def read(ctx):
    t, n = ctx.trace, ctx.window.get("ingest", 0)
    if t is None or not n:
        return None
    secs = t.kernel_s(PROGRAM)     # its one Pallas kernel
    if secs <= 0:
        return None
    need = n * roofline.bulk_append_bytes(ctx.shapes["ingest_entries"])
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
