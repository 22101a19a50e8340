"""Bytes and operations of one kernel call, from its shapes.

Each count is the least traffic the call's algorithm needs, so a
roofline share computed from it never exceeds what the chip allows:
the share is ``(bytes / peak bytes per second) / kernel time``.  The
kernel here does integer compares and moves, so bandwidth bounds it.
"""
from __future__ import annotations

WORD = 4                 # bytes per uint32 / int32
BULK_APPEND_CHUNK = 1024  # stream entries the kernel stages per DMA
BULK_APPEND_STREAMS = 7  # post addr/val, ptr addr/val, term idx/tail/freq


def bulk_append_bytes(entries: int) -> int:
    """One ``bulk_append`` call over a batch flattened to ``entries``
    (term, posting) slots: it has to read its seven address and value
    streams whole, each padded to whole staging chunks.  The writes into
    the heap, ``tail`` and ``freq`` depend on the data (skipped lanes
    write nothing) and are not counted."""
    n_pad = max(-(-entries // BULK_APPEND_CHUNK), 1) * BULK_APPEND_CHUNK
    return BULK_APPEND_STREAMS * WORD * n_pad

