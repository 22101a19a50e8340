"""Pallas TPU fused scatter-append for bulk ingest (paper §3.2 hot path).

One kernel applies a whole ingest batch's writes to the live pool state:

  * every posting value at its precomputed heap slot,
  * every fresh slice's previous-pointer (slot 0, pools > 0),
  * every touched term's new ``tail`` pointer and ``freq`` count.

The batch-parallel allocator (``slicepool.make_bulk_ingest_fn``) does all
address arithmetic up front, so the kernel is a pure gather-free scatter.
Skips are encoded as out-of-range addresses (``addr >= len(target)``),
mirroring the jnp oracle's ``mode="drop"`` scatters (kernels/ref.py —
the allclose target and the CPU execution path).

TPU layout.  A DMA cannot address one word of HBM: the compiler only
slices whole 128-lane rows of a ``[rows, 128]`` array.  So the targets
are viewed as 128-word rows and each write is a read-modify-write of its
row: the row is held in VMEM while consecutive writes land in it (a
term's postings in one batch fill consecutive slots, so most writes hit
the row already held), and on a row change the old row is written back
while the new one is read.  The (address, value) streams are staged
through SMEM in chunks, so every address is a scalar read.

heap/tail/freq are input_output_aliased: the state is updated in place,
preserving the zero-copy invariant (postings never move once written).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.kernels.compat import pl, pltpu

ROW = 128      # words per DMA row
CHUNK = 1024   # stream entries staged in SMEM per DMA


def _scatter_rows(addr_hbm, val_hbms, targets, a_sm, v_sms, bufs, sems, *,
                  n_chunks: int, cap: int):
    """Apply ``targets[k][addr[i]] = val_hbms[k][i]`` for every stream
    entry with ``addr[i] < cap``, one held 128-word row per target."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, ROW), 1)
    k = len(targets)

    def copy(src, dst, i):
        return pltpu.make_async_copy(src, dst, sems.at[i])

    def swap(old_row, old_slot, new_row, new_slot, write_back):
        reads = [copy(t.at[pl.ds(new_row, 1)], b.at[pl.ds(new_slot, 1)],
                      3 + j) for j, (t, b) in enumerate(zip(targets, bufs))]
        for cp in reads:
            cp.start()

        @pl.when(write_back)
        def _():
            writes = [copy(b.at[pl.ds(old_slot, 1)], t.at[pl.ds(old_row, 1)],
                           3 + k + j)
                      for j, (t, b) in enumerate(zip(targets, bufs))]
            for cp in writes:
                cp.start()
            for cp in writes:
                cp.wait()

        for cp in reads:
            cp.wait()

    def chunk(c, carry):
        stage = [copy(addr_hbm.at[pl.ds(c * CHUNK, CHUNK)], a_sm, 0)]
        stage += [copy(v.at[pl.ds(c * CHUNK, CHUNK)], s, 1 + j)
                  for j, (v, s) in enumerate(zip(val_hbms, v_sms))]
        for cp in stage:
            cp.start()
        for cp in stage:
            cp.wait()

        def elem(e, carry):
            cur, slot = carry
            a = a_sm[e]
            ok = a < cap
            row = a // ROW
            switch = ok & (row != cur)
            new_slot = 1 - slot

            @pl.when(switch)
            def _():
                swap(cur, slot, row, new_slot, cur >= 0)

            slot = jnp.where(switch, new_slot, slot)
            cur = jnp.where(switch, row, cur)

            @pl.when(ok)
            def _():
                hit = lane == a % ROW
                for b, s in zip(bufs, v_sms):
                    held = b[pl.ds(slot, 1), :]
                    b[pl.ds(slot, 1), :] = jnp.where(hit, s[e], held)

            return cur, slot

        return jax.lax.fori_loop(0, CHUNK, elem, carry)

    cur, slot = jax.lax.fori_loop(0, n_chunks, chunk,
                                  (jnp.int32(-1), jnp.int32(0)))

    @pl.when(cur >= 0)
    def _():
        writes = [copy(b.at[pl.ds(slot, 1)], t.at[pl.ds(cur, 1)], 3 + k + j)
                  for j, (t, b) in enumerate(zip(targets, bufs))]
        for cp in writes:
            cp.start()
        for cp in writes:
            cp.wait()


def _kernel(heap_in, tail_in, freq_in, pa, pv, qa, qv, ti, tt, tf,
            heap, tail, freq, a_sm, u_sm, i_sm, hbuf, tbuf, fbuf, sems,
            *, n_chunks: int, heap_cap: int, vocab: int):
    _scatter_rows(pa, [pv], [heap], a_sm, [u_sm], [hbuf], sems,
                  n_chunks=n_chunks, cap=heap_cap)
    _scatter_rows(qa, [qv], [heap], a_sm, [u_sm], [hbuf], sems,
                  n_chunks=n_chunks, cap=heap_cap)
    _scatter_rows(ti, [tt, tf], [tail, freq], a_sm, [u_sm, i_sm],
                  [tbuf, fbuf], sems, n_chunks=n_chunks, cap=vocab)


def _rows(x):
    """View a 1-D target as ``[rows, 128]`` (zero-padded to whole rows)."""
    return _pad(x, -(-x.shape[0] // ROW) * ROW, 0).reshape(-1, ROW)


def _pad(x, n_pad, fill):
    n = x.shape[0]
    if n == n_pad:
        return x
    return jnp.concatenate(
        [x, jnp.full((n_pad - n,), fill, x.dtype)])


@functools.partial(jax.jit, static_argnames=("interpret",))
def bulk_append(heap, tail, freq, post_addr, post_val, ptr_addr, ptr_val,
                term_idx, term_tail, term_freq, *, interpret: bool = True):
    """Apply one ingest batch's scatters to (heap, tail, freq) in place.

    ``post_addr``/``ptr_addr`` index ``heap`` (``>= len(heap)`` skips);
    ``term_idx`` indexes ``tail``/``freq`` (``>= len(tail)`` skips) and
    carries the term's NEW tail pointer and absolute freq count.  The
    heap must hold whole 128-word rows (the pool layout allocates it so,
    ``slicepool.row_words``): its ``[rows, 128]`` view is a free reshape
    and it is updated in place.  ``tail``/``freq`` are padded for the call
    when the vocabulary is not whole rows (a copy of two vocabulary-sized
    arrays; the production vocabulary, 2^20 terms, is whole rows).
    """
    if heap.shape[0] % ROW:
        raise ValueError(f"bulk_append: the heap must hold whole {ROW}-word "
                         f"rows, got length {heap.shape[0]}")
    n = post_addr.shape[0]
    n_pad = max(-(-n // CHUNK), 1) * CHUNK
    H = heap.shape[0]
    V = tail.shape[0]
    pa = _pad(post_addr.astype(jnp.int32), n_pad, H)
    pv = _pad(post_val.astype(jnp.uint32), n_pad, 0)
    qa = _pad(ptr_addr.astype(jnp.int32), n_pad, H)
    qv = _pad(ptr_val.astype(jnp.uint32), n_pad, 0)
    ti = _pad(term_idx.astype(jnp.int32), n_pad, V)
    tt = _pad(term_tail.astype(jnp.uint32), n_pad, 0)
    tf = _pad(term_freq.astype(jnp.int32), n_pad, 0)
    any_spec = pl.BlockSpec(memory_space=pltpu.ANY)
    heap2, tail2, freq2 = pl.pallas_call(
        functools.partial(_kernel, n_chunks=n_pad // CHUNK, heap_cap=H,
                          vocab=V),
        in_specs=[any_spec] * 10,
        out_specs=[any_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((H // ROW, ROW), heap.dtype),
                   jax.ShapeDtypeStruct((-(-V // ROW), ROW), tail.dtype),
                   jax.ShapeDtypeStruct((-(-V // ROW), ROW), freq.dtype)],
        scratch_shapes=[
            pltpu.SMEM((CHUNK,), jnp.int32),
            pltpu.SMEM((CHUNK,), jnp.uint32),
            pltpu.SMEM((CHUNK,), jnp.int32),
            pltpu.VMEM((2, ROW), heap.dtype),
            pltpu.VMEM((2, ROW), tail.dtype),
            pltpu.VMEM((2, ROW), freq.dtype),
            pltpu.SemaphoreType.DMA((7,)),
        ],
        input_output_aliases={0: 0, 1: 1, 2: 2},
        interpret=interpret,
    )(heap.reshape(-1, ROW), _rows(tail), _rows(freq),
      pa, pv, qa, qv, ti, tt, tf)
    return heap2.reshape(-1), tail2.reshape(-1)[:V], freq2.reshape(-1)[:V]
