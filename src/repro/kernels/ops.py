"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (tests run on the CPU backend and
execute the kernel bodies in Python for correctness); on a TPU backend
the same calls compile to Mosaic.  The jnp oracles live in ref.py and
back both the equivalence tests and the CPU execution path (the kernels
are the TPU target, the jnp path is the semantics).

``checked=True`` on the postings/segment/bulk wrappers runs the call
under ``jax.experimental.checkify`` (index OOB + NaN + div, via
repro.analysis.sanitize) and raises ``sanitize.SanitizerError`` on the
first violation.  The checked route always sanitizes the jnp oracle —
which IS the semantics — regardless of ``use_kernel``/``interpret``:
checkify instruments XLA operations, and a compiled Mosaic kernel is
opaque to it.  CI's checked leg runs the kernel-equivalence suite this
way (REPRO_CHECKED=1).
"""
from __future__ import annotations

import jax

from repro.analysis import sanitize
from repro.kernels import ref
from repro.kernels.bulk_append import bulk_append as _bulk_append
from repro.kernels.embedding_bag import embedding_bag as _embedding_bag
from repro.kernels.paged_attention import PAGE
from repro.kernels.paged_attention import paged_attention as _paged_attention
from repro.kernels.postings_intersect import CHUNK
from repro.kernels.postings_intersect import intersect_mask as _intersect_mask
from repro.kernels.segment_intersect import (
    scored_intersect_batched as _scored_intersect_batched,
    segment_intersect_mask as _segment_intersect_mask,
    segment_intersect_mask_batched as _segment_intersect_mask_batched)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def paged_attention(q, k_heap, v_heap, page_table, lengths, *,
                    page: int = PAGE, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _paged_attention(q, k_heap, v_heap, page_table, lengths,
                            page=page, interpret=interpret)


def embedding_bag(table, indices, offsets, *, mode: str = "sum",
                  interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _embedding_bag(table, indices, offsets, mode=mode,
                          interpret=interpret)


def intersect_mask(a, b, *, ta: int = CHUNK, tb: int = CHUNK,
                   interpret=None, checked: bool = False):
    if checked:
        return sanitize.checked_call(ref.intersect_mask_ref, a, b)
    if interpret is None:
        interpret = _default_interpret()
    return _intersect_mask(a, b, ta=ta, tb=tb, interpret=interpret)


def segment_intersect_mask(a, b, *, interpret=None,
                           checked: bool = False):
    """Gap-decode + blocked intersection of two PackedLists (frozen
    path)."""
    if checked:
        return sanitize.checked_call(ref.segment_intersect_mask_ref, a, b)
    if interpret is None:
        interpret = _default_interpret()
    return _segment_intersect_mask(a, b, interpret=interpret)


def segment_intersect_mask_batched(a, b, *, use_kernel=None,
                                   interpret=None, checked: bool = False):
    """Row-wise masks of a whole (query, segment) batch of StackedLists.

    ``use_kernel=None`` auto-routes like :func:`bulk_append`: the grid
    kernel on a real TPU backend, the vmapped jnp oracle everywhere else
    (the batched query hot path must not pay the interpreter's
    per-element DMA simulation on CPU; the oracle IS the semantics)."""
    if checked:
        return sanitize.checked_call(
            ref.segment_intersect_mask_batched_ref, a, b)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return ref.segment_intersect_mask_batched_ref(a, b)
    if interpret is None:
        interpret = _default_interpret()
    return _segment_intersect_mask_batched(a, b, interpret=interpret)


def scored_intersect_batched(a, b, rest, th, *, use_kernel=None,
                             interpret=None, checked: bool = False):
    """Row-wise scored conjunction over a (query, segment) batch of
    ScoredStacks: impact sums for a-docids present in b, with whole
    a-blocks zeroed when their block-max WAND bound ``a.bmax + rest``
    cannot beat the heap threshold ``th`` (int32[N] each; th = -1
    disables skipping).  ``use_kernel=None`` auto-routes like
    :func:`segment_intersect_mask_batched`."""
    if checked:
        return sanitize.checked_call(
            ref.scored_intersect_batched_ref, a, b, rest, th)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return ref.scored_intersect_batched_ref(a, b, rest, th)
    if interpret is None:
        interpret = _default_interpret()
    return _scored_intersect_batched(a, b, rest, th, interpret=interpret)


def bulk_append(heap, tail, freq, post_addr, post_val, ptr_addr, ptr_val,
                term_idx, term_tail, term_freq, *, use_kernel=None,
                interpret=None, checked: bool = False):
    """Fused scatter-append of one ingest batch into (heap, tail, freq).

    ``use_kernel=None`` auto-routes: the Pallas kernel on a real TPU
    backend, the jnp oracle everywhere else (the ingest hot path must not
    pay the interpreter's per-element DMA simulation on CPU; the oracle
    IS the semantics — see ref.bulk_append_ref).

    ``checked=True`` is STRICTER than the drop contract: checkify's
    index checks flag out-of-bounds scatter addresses even under
    ``mode="drop"``, and the allocator deliberately encodes skip lanes
    as out-of-range addresses — so the checked path asserts that every
    lane of the batch actually landed (no silently skipped writes).
    Expect :class:`~repro.analysis.sanitize.SanitizerError` on any
    operand set with skip lanes; use it to audit batches that are
    supposed to be fully dense."""
    if checked:
        return sanitize.checked_call(
            ref.bulk_append_ref, heap, tail, freq, post_addr, post_val,
            ptr_addr, ptr_val, term_idx, term_tail, term_freq)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return ref.bulk_append_ref(heap, tail, freq, post_addr, post_val,
                                   ptr_addr, ptr_val, term_idx, term_tail,
                                   term_freq)
    if interpret is None:
        interpret = _default_interpret()
    return _bulk_append(heap, tail, freq, post_addr, post_val, ptr_addr,
                        ptr_val, term_idx, term_tail, term_freq,
                        interpret=interpret)


__all__ = ["paged_attention", "embedding_bag", "intersect_mask",
           "segment_intersect_mask", "segment_intersect_mask_batched",
           "scored_intersect_batched", "bulk_append", "ref", "PAGE"]
