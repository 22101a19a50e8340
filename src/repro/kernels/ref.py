"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_attention_ref(q, k_heap, v_heap, page_table, lengths,
                        page: int = 64):
    """Decode attention through a page table.

    q: [B, Hkv, G, D]; k_heap/v_heap: [Hkv, slots, D];
    page_table: int32[B, NP] page ids (-1 pad); lengths: int32[B].
    Returns [B, Hkv, G, D] fp32.
    """
    B, Hkv, G, D = q.shape
    NP = page_table.shape[1]
    slots = (jnp.maximum(page_table, 0)[:, :, None] * page
             + jnp.arange(page)[None, None, :]).reshape(B, NP * page)
    k = jnp.take(k_heap, slots, axis=1)        # [Hkv, B, T, D]
    v = jnp.take(v_heap, slots, axis=1)
    k = jnp.transpose(k, (1, 0, 2, 3)).astype(jnp.float32)  # [B, Hkv, T, D]
    v = jnp.transpose(v, (1, 0, 2, 3)).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhtd->bhgt", q.astype(jnp.float32), k)
    s = s * (D ** -0.5)
    t_pos = jnp.arange(NP * page)[None, None, None, :]
    mask = t_pos < lengths[:, None, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)        # all-masked rows -> 0
    return jnp.einsum("bhgt,bhtd->bhgd", p, v)


def embedding_bag_ref(table, indices, offsets, mode: str = "sum"):
    """CSR embedding bag: bags b = rows indices[offsets[b]:offsets[b+1]].

    table: [R, D]; indices: int32[N]; offsets: int32[B+1] -> [B, D].
    """
    B = offsets.shape[0] - 1
    n = indices.shape[0]
    seg = jnp.searchsorted(offsets[1:], jnp.arange(n), side="right")
    rows = jnp.take(table, indices, axis=0, mode="clip")
    out = jax.ops.segment_sum(rows, seg, num_segments=B)
    if mode == "mean":
        cnt = (offsets[1:] - offsets[:-1]).astype(table.dtype)
        out = out / jnp.maximum(cnt, 1)[:, None]
    return out


def intersect_mask_ref(a, b, invalid: int = 0xFFFFFFFF):
    """Membership mask: 1 where a[i] (valid) appears in b. Both ascending,
    padded with ``invalid`` at the end."""
    pos = jnp.searchsorted(b, a)
    pos = jnp.minimum(pos, b.shape[0] - 1)
    hit = (b[pos] == a) & (a != jnp.uint32(invalid))
    return hit.astype(jnp.int32)


def bulk_append_ref(heap, tail, freq, post_addr, post_val, ptr_addr,
                    ptr_val, term_idx, term_tail, term_freq):
    """Oracle for the fused scatter-append kernel: four plain ``drop``
    scatters.  Skips are DISTINCT out-of-range addresses (``>= len(
    target)``), and live posting/pointer/term slots are disjoint by
    construction (the bulk allocator owns each fresh slice exclusively
    within a batch), so every scatter honestly promises unique indices
    and the write order between the two heap scatters is immaterial."""
    heap = heap.at[post_addr].set(post_val.astype(heap.dtype), mode="drop",
                                  unique_indices=True)
    heap = heap.at[ptr_addr].set(ptr_val.astype(heap.dtype), mode="drop",
                                 unique_indices=True)
    tail = tail.at[term_idx].set(term_tail.astype(tail.dtype), mode="drop",
                                 unique_indices=True)
    freq = freq.at[term_idx].set(term_freq.astype(freq.dtype), mode="drop",
                                 unique_indices=True)
    return heap, tail, freq


def segment_intersect_mask_ref(a_packed, b_packed):
    """Oracle for the segment intersect: decode both PackedLists with
    the all-blocks jnp decoder, then plain membership."""
    from repro.kernels.segment_intersect import decode_packed
    a_ids = decode_packed(a_packed)
    if a_ids.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)
    b_ids = decode_packed(b_packed)
    if b_ids.shape[0] == 0:
        return jnp.zeros(a_ids.shape, jnp.int32)
    return intersect_mask_ref(a_ids, b_ids)


def segment_intersect_mask_batched_ref(a_stacked, b_stacked):
    """Oracle for the batched (query, segment) grid kernel AND its CPU
    execution path: batched all-blocks decode of both stacks, then
    row-wise membership.  Stacks carry one leading ``[N, ...]`` axis."""
    from repro.kernels.segment_intersect import decode_stacked
    a_ids = decode_stacked(a_stacked)           # [N, NBa * SEG_BLOCK]
    if a_ids.shape[-1] == 0 or a_ids.shape[0] == 0:
        return jnp.zeros(a_ids.shape, jnp.int32)
    b_ids = decode_stacked(b_stacked)
    return jax.vmap(intersect_mask_ref)(a_ids, b_ids)


def scored_intersect_batched_ref(a_scored, b_scored, rest, th):
    """Oracle for the scored batched kernel: decode docids + impact
    planes, membership via searchsorted (the first occurrence is the
    real lane — pad lanes only repeat b's last docid with impact 0), sum
    the two impacts, and zero every a-block whose WAND upper bound
    ``a.bmax + rest`` cannot beat ``th``."""
    from repro.kernels.segment_intersect import (SEG_BLOCK, decode_scores,
                                                 decode_stacked)
    a_ids = decode_stacked(a_scored.ids)        # [N, NBa * SEG_BLOCK]
    if a_ids.shape[-1] == 0 or a_ids.shape[0] == 0:
        return jnp.zeros(a_ids.shape, jnp.int32)
    b_ids = decode_stacked(b_scored.ids)
    a_sc = decode_scores(a_scored.swords)
    b_sc = decode_scores(b_scored.swords)

    def one(ar, br, asr, bsr, bmaxr, restr, thr):
        pos = jnp.minimum(jnp.searchsorted(br, ar), br.shape[0] - 1)
        hit = (br[pos] == ar) & (ar != jnp.uint32(0xFFFFFFFF))
        bs = jnp.where(hit, bsr[pos], 0)
        keep = jnp.repeat((bmaxr + restr) > thr, SEG_BLOCK)
        return jnp.where(hit & keep & (bs > 0), asr + bs, 0)

    return jax.vmap(one)(a_ids, b_ids, a_sc, b_sc,
                         jnp.asarray(a_scored.bmax, jnp.int32),
                         jnp.asarray(rest, jnp.int32),
                         jnp.asarray(th, jnp.int32))
