"""Pallas TPU paged-attention decode kernel (flash-decoding over a page
table).

The slice-pool KV allocator (repro.paged) flattens each sequence's slice
chain into a table of fixed 64-token PAGES; this kernel walks that table
with online softmax, one async HBM->VMEM DMA per (K page, V page) with
double buffering — the TPU's answer to the paper's pointer-chase cost
``C_p`` (a discontiguous DMA instead of a cache miss; DESIGN.md §2).

Layout:
  q          [B, Hkv, G, D]     (G = query heads per KV head)
  k/v heaps  [Hkv, slots, D]    (slot = token; pages are contiguous)
  page_table int32[B, NP]       (page ids, -1 padding)
  lengths    int32[B]
  out        [B, Hkv, G, D] fp32

Grid: (B, Hkv) — one program per (sequence, kv head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.kernels.compat import pl, pltpu

PAGE = 64
NEG_INF = -1e30  # python float: jnp constants would be captured consts


def _kernel(table_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sem_k, sem_v, *, page: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    G, D = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0].astype(jnp.float32) * (D ** -0.5)      # [G, D]
    n = len_ref[b]
    n_pages = pl.cdiv(n, page)
    nbuf = k_buf.shape[0]  # double-buffer slots

    def start_copy(i, slot):
        pg = table_ref[b, i]
        pltpu.make_async_copy(
            k_hbm.at[h, pl.ds(pg * page, page), :], k_buf.at[slot],
            sem_k.at[slot]).start()
        pltpu.make_async_copy(
            v_hbm.at[h, pl.ds(pg * page, page), :], v_buf.at[slot],
            sem_v.at[slot]).start()

    def wait(slot):
        pltpu.make_async_copy(
            k_hbm.at[h, pl.ds(0, page), :], k_buf.at[slot],
            sem_k.at[slot]).wait()
        pltpu.make_async_copy(
            v_hbm.at[h, pl.ds(0, page), :], v_buf.at[slot],
            sem_v.at[slot]).wait()

    @pl.when(n_pages > 0)
    def _():
        start_copy(0, 0)

    def body(i, carry):
        m, denom, acc = carry
        slot = jax.lax.rem(i, nbuf)

        @pl.when(i + 1 < n_pages)
        def _():
            start_copy(i + 1, jax.lax.rem(i + 1, nbuf))

        wait(slot)
        k = k_buf[slot].astype(jnp.float32)                 # [page, D]
        v = v_buf[slot].astype(jnp.float32)
        s = q @ k.T                                         # [G, page]
        pos = i * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, page), 1)
        s = jnp.where(pos < n, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        denom = denom * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + p @ v
        return m_new, denom, acc

    m0 = jnp.full((G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G, 1), jnp.float32)
    a0 = jnp.zeros((G, D), jnp.float32)
    m, denom, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    o_ref[0, 0] = acc / jnp.maximum(denom, 1e-30)


@functools.partial(jax.jit, static_argnames=("page", "interpret"))
def paged_attention(q, k_heap, v_heap, page_table, lengths, *,
                    page: int = PAGE, interpret: bool = True):
    """Flash-decoding through a page table.  See module docstring."""
    B, Hkv, G, D = q.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, *_: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page, D), k_heap.dtype),   # double-buffered K
            pltpu.VMEM((2, page, D), v_heap.dtype),   # double-buffered V
            pltpu.SemaphoreType.DMA((2,)),            # per-slot semaphores
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
        interpret=interpret,
    )(page_table, lengths, q, k_heap, v_heap)
