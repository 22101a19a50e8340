"""Functional slice-pool allocator (paper §3.2-3.3), jit friendly.

Two interchangeable, BIT-IDENTICAL ingest implementations share one
state layout: the per-posting ``jax.lax.scan`` (:func:`make_ingest_fn`,
the semantics oracle) and the batch-parallel bulk allocator
(:func:`make_bulk_ingest_fn`, the hot path — sorts a whole arrival
batch by term, walks the slice-size progression analytically, allocates
batch-wide and applies every write in one fused scatter-append).

The allocator state is a pytree of fixed-shape arrays:

  * ``heap``      — one flat uint32 array holding every pool back-to-back
                    (pool p occupies ``[base_p, base_p + slices_p * 2**z_p)``).
  * ``watermark`` — next free slice index per pool (bump allocation: slices
                    are fixed-size per pool, so allocation is O(1) and there
                    is no fragmentation — paper §10).
  * ``tail``      — per-term packed pointer to the most recently written
                    slot (the paper's dictionary "tail" pointer: where the
                    next posting goes and where query evaluation begins).
  * ``freq``      — per-term posting count.
  * ``overflow``  — sticky bit; inserts become no-ops when a pool is
                    exhausted (tests assert it stays False).
  * ``free_list`` / ``free_count`` — per-pool LIFO stacks of reclaimed
                    slice indices (pool p owns region
                    ``[free_base_p, free_base_p + slices_p)``).  Segment
                    rollover returns every slice of the frozen segment
                    here (:func:`release_slices`); allocation pops a
                    recycled slice before bumping the watermark, so the
                    heap high-water mark is bounded under steady churn —
                    the Goldilocks loop of the paper's §3.1 lifecycle.

Zero-copy invariant (paper §3.2): a posting, once written, is never moved
WITHIN a segment's lifetime.  The only mutations are bump-pointer/free-list
allocation and single-slot writes, which XLA performs in place inside the
scan; reclaimed slices are only rewritten after their postings were frozen
into a read-only CSR segment.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pointers as ptr_mod
from repro.core.pointers import NULL, PoolLayout
from repro.kernels.bulk_append import ROW

# Shared lru_cache bound for the jitted-function factories (ingest fns,
# query engines, qexec active-path fns).  A long-lived process cycling
# through distinct layouts/buckets evicts the oldest entry instead of
# growing without bound; each entry only holds compiled functions, so
# eviction costs a recompile, never correctness.
FACTORY_CACHE_SIZE = 64


class PoolState(NamedTuple):
    heap: jax.Array        # uint32[row_words(total_slots)]
    watermark: jax.Array   # int32[P] next never-used slice per pool
    tail: jax.Array        # uint32[V]
    freq: jax.Array        # int32[V]
    overflow: jax.Array    # bool[]
    free_list: jax.Array   # int32[total_slices] reclaimed slices per pool
    free_count: jax.Array  # int32[P] live entries in each pool's region


def row_words(n: int) -> int:
    """``n`` rounded up to whole rows of the ingest kernel (128 words, the
    unit its DMAs move).  The heap is allocated at this length so the
    kernel updates it in place; the words past ``total_slots`` are never
    addressed."""
    return -(-n // ROW) * ROW


def init_state(layout: PoolLayout, vocab_size: int) -> PoolState:
    return PoolState(
        heap=jnp.zeros((row_words(layout.total_slots),), jnp.uint32),
        watermark=jnp.zeros((layout.num_pools,), jnp.int32),
        tail=jnp.full((vocab_size,), NULL, jnp.uint32),
        freq=jnp.zeros((vocab_size,), jnp.int32),
        overflow=jnp.asarray(False),
        free_list=jnp.zeros((layout.total_slices,), jnp.int32),
        free_count=jnp.zeros((layout.num_pools,), jnp.int32),
    )


def init_sharded_state(layout: PoolLayout, vocab_size: int,
                       n_shards: int) -> PoolState:
    """``n_shards`` independent pools stacked on a leading shard axis.

    Every leaf of the single-shard :class:`PoolState` gains a leading
    ``[S, ...]`` dimension (``overflow`` becomes ``bool[S]``); shard s's
    slice of each leaf is exactly a single-device state, so the scan-based
    allocator runs unchanged per shard inside ``shard_map`` (logical axis
    ``"docs"``/``"shard"`` in ``repro.dist.sharding``).
    """
    one = init_state(layout, vocab_size)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_shards,) + x.shape), one)


def memory_slots_used(layout: PoolLayout, state: PoolState) -> int:
    """LIVE allocated slots = paper's empirical memory cost ``C_M*``.

    Slices sitting on the free list are not live — reclaiming a segment
    (freeze + :func:`release_slices`) makes this DROP, while
    :func:`memory_high_water_slots` keeps the historical peak.  Accepts a
    single-shard state (``watermark[P]``) or a sharded one
    (``watermark[S, P]``); sharded states sum over shards.
    """
    live = (np.asarray(state.watermark, np.int64)
            - np.asarray(state.free_count, np.int64))
    return int(np.sum(live * np.asarray(layout.slice_sizes, np.int64)))


def memory_high_water_slots(layout: PoolLayout, state: PoolState) -> int:
    """Heap high-water mark: every slot that was EVER allocated.

    The watermark only moves when the free list is empty, so under steady
    churn with reclamation this is bounded by one segment's demand — the
    lifecycle benchmark asserts exactly that.
    """
    wm = np.asarray(state.watermark, np.int64)
    return int(np.sum(wm * np.asarray(layout.slice_sizes, np.int64)))


def shard_slots_used(layout: PoolLayout, state: PoolState):
    """Per-shard LIVE allocated slots for a sharded state (int64[S])."""
    wm = np.asarray(state.watermark, np.int64)
    assert wm.ndim == 2, "shard_slots_used wants a sharded state [S, P]"
    live = wm - np.asarray(state.free_count, np.int64)
    return np.sum(live * np.asarray(layout.slice_sizes, np.int64)[None, :],
                  axis=1)


def pool_utilization(layout: PoolLayout, state: PoolState) -> float:
    """Worst-case live-slice fill fraction across pools (and shards).

    Per pool: (watermark − free_count) / slices_per_pool — the fraction
    of that pool's slices live RIGHT NOW; the maximum over pools (and
    over shards for a sharded ``[S, P]`` state) is what the
    :class:`~repro.core.lifecycle.AdmissionController` watches.  1.0
    means some pool has zero allocatable slices left: the NEXT
    allocation there trips the sticky ``overflow`` flag and silently
    drops postings.  Host-side numpy (one tiny sync), like the other
    memory gauges.
    """
    live = (np.asarray(state.watermark, np.float64)
            - np.asarray(state.free_count, np.float64))
    caps = np.asarray(layout.slices_per_pool, np.float64)
    return float(np.max(live / caps))


def _insert_one(layout: PoolLayout, tbl, caps, state: PoolState,
                term, posting, start_pool, valid) -> PoolState:
    """Branchless single-posting insert (one scan step)."""
    pb = layout.pool_bits
    P = layout.num_pools
    # writes with mode="drop" go here when disabled (past the row padding)
    oob = jnp.uint32(state.heap.shape[0])

    t = state.tail[term]
    new = ptr_mod.is_null(t)
    pool, sl, off = ptr_mod.decode(tbl, pb, t)
    cap = tbl["slice_size"][pool]
    full = (~new) & (off == cap - jnp.uint32(1))
    need_alloc = (new | full) & valid

    alloc_pool = jnp.where(
        new, start_pool.astype(jnp.uint32),
        jnp.minimum(pool + jnp.uint32(1), jnp.uint32(P - 1)))
    # reclaimed slices first (LIFO pop), then bump the watermark.
    fc = state.free_count[alloc_pool]
    has_free = fc > 0
    free_slot = tbl["free_base"][alloc_pool] + jnp.maximum(fc - 1, 0)
    recycled = state.free_list[free_slot].astype(jnp.uint32)
    fresh = state.watermark[alloc_pool].astype(jnp.uint32)
    slice_new = jnp.where(has_free, recycled, fresh)
    can_alloc = has_free | (fresh < caps[alloc_pool])
    ok = valid & (~need_alloc | can_alloc)
    do_alloc = need_alloc & ok

    watermark = state.watermark.at[
        jnp.where(do_alloc & ~has_free, alloc_pool.astype(jnp.int32), P)
    ].add(1, mode="drop")
    free_count = state.free_count.at[
        jnp.where(do_alloc & has_free, alloc_pool.astype(jnp.int32), P)
    ].add(-1, mode="drop")

    has_ptr_slot = alloc_pool > jnp.uint32(0)
    w_pool = jnp.where(do_alloc, alloc_pool, pool)
    w_slice = jnp.where(do_alloc, slice_new, sl)
    w_off = jnp.where(
        do_alloc,
        jnp.where(has_ptr_slot, jnp.uint32(1), jnp.uint32(0)),
        off + jnp.uint32(1))

    heap = state.heap
    # previous-pointer write at slot 0 of a fresh slice (pools > 0 only).
    prev_addr = ptr_mod.to_addr(tbl, alloc_pool, slice_new, jnp.uint32(0))
    write_prev = do_alloc & has_ptr_slot
    prev_val = jnp.where(new, jnp.uint32(NULL), t)
    heap = heap.at[jnp.where(write_prev, prev_addr, oob)].set(
        prev_val, mode="drop")
    # the posting itself.
    addr = ptr_mod.to_addr(tbl, w_pool, w_slice, w_off)
    heap = heap.at[jnp.where(ok, addr, oob)].set(
        posting.astype(jnp.uint32), mode="drop")

    new_tail = ptr_mod.encode(tbl, pb, w_pool, w_slice, w_off)
    tail = state.tail.at[term].set(jnp.where(ok, new_tail, t))
    freq = state.freq.at[term].add(ok.astype(jnp.int32))
    overflow = state.overflow | (valid & need_alloc & ~can_alloc)
    return PoolState(heap, watermark, tail, freq, overflow,
                     state.free_list, free_count)


@functools.lru_cache(maxsize=FACTORY_CACHE_SIZE)
def make_ingest_fn(layout: PoolLayout, vocab_size: int):
    """Build a jitted ``ingest(state, terms, postings, start_pools, valid)``.

    ``terms``/``postings`` are flat uint32 streams (one entry per term
    occurrence, already positional-encoded via
    :func:`repro.core.postings.pack`).  ``start_pools`` implements the §7
    SP policies (all zeros == ``SP(z_0)``).  ``valid`` masks padding.
    Memoised on (layout, vocab) so segment rollover reuses the jit cache.
    """
    tbl = layout.tables()
    caps = jnp.asarray(
        [layout.slices_per_pool[p] for p in range(layout.num_pools)],
        jnp.uint32)

    def step(state, xs):
        term, posting, start_pool, valid = xs
        return _insert_one(layout, tbl, caps, state, term, posting,
                           start_pool, valid), None

    @jax.jit
    def ingest(state: PoolState, terms, postings,
               start_pools=None, valid=None) -> PoolState:
        n = terms.shape[0]
        if start_pools is None:
            start_pools = jnp.zeros((n,), jnp.uint32)
        if valid is None:
            valid = jnp.ones((n,), bool)
        state, _ = jax.lax.scan(
            step, state,
            (terms.astype(jnp.uint32), postings.astype(jnp.uint32),
             start_pools.astype(jnp.uint32), valid))
        return state

    return ingest


# ---------------------------------------------------------------------------
# Batch-parallel bulk ingest (the hot-path replacement for the scan).
# ---------------------------------------------------------------------------
def _progression_tables(layout: PoolLayout):
    """Static §3.3 slice-size progression tables for the analytic walk.

    ``h[q]``          postings a FRESH slice in pool q holds (slot 0 of
                      pools > 0 is the previous-pointer).
    ``excl[q0, j]``   postings held by the first ``j`` fresh slices of the
                      progression ``q0, q0+1, ..., P-1, P-1, ...`` —
                      exclusive prefix sums, one row per starting pool.
    """
    P = layout.num_pools
    sizes = layout.slice_sizes
    h = np.asarray([sizes[q] - (1 if q > 0 else 0) for q in range(P)],
                   np.int64)
    excl = np.zeros((P, P + 1), np.int64)
    for q0 in range(P):
        acc = 0
        for j in range(P):
            excl[q0, j] = acc
            acc += h[min(q0 + j, P - 1)]
        excl[q0, P] = acc
    return h, excl


@functools.lru_cache(maxsize=FACTORY_CACHE_SIZE)
def make_bulk_ingest_fn(layout: PoolLayout, vocab_size: int, *,
                        use_kernel: Optional[bool] = None,
                        interpret: Optional[bool] = None):
    """Build a jitted batch-parallel ``ingest`` — same signature and
    BIT-IDENTICAL ``PoolState`` as :func:`make_ingest_fn`'s scan, but one
    vectorised dispatch per batch instead of one scan step per posting.

    Pipeline (everything data-parallel over the N occurrences):

      1. stable-sort the (term, posting) stream by term; segment it and
         rank every occurrence within its term (stream order preserved).
      2. walk the §3.3 slice-size progression ANALYTICALLY: from each
         term's current ``tail`` derive, per occurrence, which slice of
         the batch's new allocations it lands in (closed form over the
         static progression prefix sums) — no per-posting chain steps.
      3. allocate batch-wide: per pool, rank allocation events by stream
         position; the first ``free_count`` successes pop the free list
         LIFO, the rest bump the watermark, and events ranked past
         ``free_count + capacity - watermark`` FAIL — the failing term's
         occurrences are truncated from the failing posting onward and
         the sticky ``overflow`` bit is set, reproducing the scan's
         semantics exactly (failure at the same posting index).
      4. write every posting, previous-pointer, new ``tail``/``freq`` in
         one fused scatter-append (the ``bulk_append`` Pallas kernel on
         TPU, its jnp oracle elsewhere — ``use_kernel=None`` auto).

    Constraint (same as every SP policy in the repo): ``start_pools``
    must be constant per term within a batch — a NEW term's start pool is
    read from its first occurrence.  The scan path remains the semantics
    oracle (tests/test_bulk_ingest.py proves leaf-for-leaf equality).
    """
    from repro.kernels import ops as kops

    tbl = layout.tables()
    pb = layout.pool_bits
    P = layout.num_pools
    V = vocab_size
    H = row_words(layout.total_slots)   # skips land past the heap's rows
    caps = jnp.asarray(layout.slices_per_pool, jnp.int32)
    h_np, excl_np = _progression_tables(layout)
    h_tbl = jnp.asarray(h_np, jnp.int32)
    excl_tbl = jnp.asarray(excl_np, jnp.int32)
    hL = int(h_np[P - 1])
    BIG = jnp.int32(np.iinfo(np.int32).max)

    def _plan(state: PoolState, terms, postings, start_pools, valid):
        """Turn one batch into scatter operands + the new small leaves."""
        N = terms.shape[0]
        i_idx = jnp.arange(N, dtype=jnp.int32)
        # -- 1. sort by term (stable: stream order survives per term) ---
        key = jnp.where(valid, terms, jnp.uint32(V))  # invalid sort last
        idx_bits = max((N - 1).bit_length(), 1)
        if V.bit_length() + idx_bits <= 32:
            # pack (term, stream index) into ONE uint32 key: a plain
            # single-array sort is several times faster than the
            # variadic stable argsort and the index IS the tiebreak
            packed = (key << jnp.uint32(idx_bits)) | i_idx.astype(
                jnp.uint32)
            skey = jnp.sort(packed)
            order = (skey
                     & jnp.uint32((1 << idx_bits) - 1)).astype(jnp.int32)
            t_s = skey >> jnp.uint32(idx_bits)
        else:
            order = jnp.argsort(key, stable=True)
            t_s = key[order]
        post_s = postings[order]
        sp_s = start_pools[order]
        valid_s = valid[order]
        stream = order                                # original position
        head = jnp.where(i_idx == 0, True, t_s != jnp.roll(t_s, 1))
        seg_id = jnp.cumsum(head.astype(jnp.int32)) - 1
        seg_start = jax.lax.cummax(jnp.where(head, i_idx, 0))
        r = i_idx - seg_start                         # rank within term

        # -- 2. analytic demand walk from each term's current tail ------
        tail_t = state.tail[jnp.minimum(t_s, jnp.uint32(V - 1))]
        new = ptr_mod.is_null(tail_t)
        cp, sl0, off0 = ptr_mod.decode(tbl, pb, tail_t)
        cap0 = tbl["slice_size"][cp].astype(jnp.int32)
        rem0 = jnp.where(new, 0, cap0 - 1 - off0.astype(jnp.int32))
        sp_first = jnp.minimum(sp_s[seg_start].astype(jnp.int32), P - 1)
        q0 = jnp.where(new, sp_first,
                       jnp.minimum(cp.astype(jnp.int32) + 1, P - 1))
        ra = r - rem0                  # occurrence's rank past the tail
        needs = ra >= 0                # lands in a batch-fresh slice
        exq = excl_tbl[q0]                                   # [N, P+1]
        j_small = jnp.sum(exq[:, 1:] <= ra[:, None], axis=1)
        beyond = ra >= exq[:, P]
        j = jnp.where(beyond, P + (jnp.maximum(ra - exq[:, P], 0)) // hL,
                      j_small).astype(jnp.int32)
        excl_at_j = jnp.where(
            beyond, exq[:, P] + (j - P) * hL,
            jnp.take_along_axis(exq, jnp.clip(j, 0, P)[:, None],
                                axis=1)[:, 0])
        off_in = ra - excl_at_j        # posting's rank inside slice j
        pool_j = jnp.minimum(q0 + jnp.minimum(j, P), P - 1)
        is_event = valid_s & needs & (off_in == 0)   # slice-j allocation

        # -- 3. batch-wide allocation, pool by pool in stream order -----
        # Ranks are computed in ORIGINAL stream order, where "position of
        # this allocation among the pool's allocations" is an exclusive
        # cumsum — no per-pool sort.  One scatter inverts the term sort.
        wm = state.watermark.astype(jnp.int32)
        fc = state.free_count.astype(jnp.int32)
        fb = tbl["free_base"]
        total_slices = state.free_list.shape[0]
        inv = jnp.zeros((N,), jnp.int32).at[stream].set(
            i_idx, mode="promise_in_bounds", unique_indices=True)
        ev_o = is_event[inv]
        pool_o = jnp.where(ev_o, pool_j[inv], P)     # P == no event
        avail = fc + caps - wm                       # int32[P]

        def _assign(k, pool, ok):
            """Slice id for the pool's ``k``-th allocation: free-list
            LIFO pop first, then watermark bump."""
            pop_idx = jnp.clip(fb[pool] + fc[pool] - 1 - k, 0,
                               total_slices - 1)
            return jnp.where(ok & (k < fc[pool]),
                             state.free_list[pop_idx],
                             jnp.where(ok, wm[pool] + k - fc[pool], 0))

        # fast path: assume nothing fails — every pool's ranks come from
        # ONE [P, N] cumsum with no cross-pool dependency.  Sound: if no
        # event exceeds its pool's capacity under the no-truncation
        # demand, no truncation happens and the assignment is exact.
        m_all = pool_o[None, :] == jnp.arange(P, dtype=jnp.int32)[:, None]
        ranks = (jnp.cumsum(m_all.astype(jnp.int32), axis=1)
                 - m_all.astype(jnp.int32))                    # [P, N]
        any_fail = jnp.any(m_all & (ranks >= avail[:, None]))

        def _fast(_):
            k = jnp.take_along_axis(
                ranks, jnp.minimum(pool_o, P - 1)[None, :], axis=0)[0]
            slice_o = _assign(k, jnp.minimum(pool_o, P - 1), ev_o)
            n_succ = jnp.sum(m_all.astype(jnp.int32), axis=1)  # [P]
            return (slice_o, jnp.zeros((N,), bool),
                    wm + jnp.maximum(n_succ - fc, 0),
                    fc - jnp.minimum(n_succ, fc))

        def _slow(_):
            """Exact overflow semantics: pools resolve in increasing
            order; a failed slice truncates its term from that posting
            onward (sticky overflow at the same posting index)."""
            seg_o = seg_id[inv]
            failed_o = jnp.zeros((N,), bool)
            slice_acc = jnp.zeros((N,), jnp.int32)
            new_wm, new_fc = wm, fc
            for p in range(P):         # static, small; lower pools first
                m = (pool_o == p) & ~failed_o
                k = jnp.cumsum(m.astype(jnp.int32)) - m.astype(jnp.int32)
                succ = m & (k < avail[p])
                fail = m & ~succ
                slice_acc = jnp.where(succ, _assign(k, pool_o, succ),
                                      slice_acc)
                n_succ = jnp.sum(succ.astype(jnp.int32))
                new_wm = new_wm.at[p].add(jnp.maximum(n_succ - fc[p], 0))
                new_fc = new_fc.at[p].add(-jnp.minimum(n_succ, fc[p]))
                fp = jax.ops.segment_min(
                    jnp.where(fail, i_idx, BIG), seg_o, num_segments=N)
                failed_o = failed_o | (i_idx >= fp[seg_o])
            return slice_acc, failed_o, new_wm, new_fc

        evt_slice_o, failed_o, new_wm, new_fc = jax.lax.cond(
            any_fail, _slow, _fast, None)

        evt_slice = evt_slice_o[stream]     # back to term-sorted order
        failed_s = failed_o[stream]
        # an event succeeded iff its own posting wasn't truncated
        evt_ok = is_event & ~failed_s
        land = valid_s & ~failed_s

        # -- 4. scatter operands ----------------------------------------
        # every occurrence's slice: its slice-j event sits off_in rows up
        evt_pos = jnp.clip(i_idx - off_in, 0, jnp.maximum(N - 1, 0))
        slice_occ = jnp.where(needs, evt_slice[evt_pos],
                              sl0.astype(jnp.int32))
        pool_occ = jnp.where(needs, pool_j, cp.astype(jnp.int32))
        off_occ = jnp.where(needs, off_in + (pool_j > 0),
                            off0.astype(jnp.int32) + 1 + r)
        addr = ptr_mod.to_addr(tbl, pool_occ.astype(jnp.uint32),
                               slice_occ.astype(jnp.uint32),
                               off_occ.astype(jnp.uint32)).astype(jnp.int32)
        # skip rows get DISTINCT out-of-range addresses (H + row) so the
        # scatters can honestly promise unique indices — XLA applies the
        # surviving writes without the duplicate-resolution slow path
        post_addr = jnp.where(land, addr, H + i_idx)
        post_val = post_s

        # previous-pointer writes: slot 0 of fresh slices in pools > 0
        pool_prev = jnp.minimum(q0 + jnp.maximum(j - 1, 0), P - 1)
        prev_evt = jnp.clip(i_idx - h_tbl[pool_prev], 0,
                            jnp.maximum(N - 1, 0))
        prev_ptr = ptr_mod.encode(
            tbl, pb, pool_prev.astype(jnp.uint32),
            evt_slice[prev_evt].astype(jnp.uint32),
            tbl["slice_size"][pool_prev] - jnp.uint32(1))
        # the first fresh slice links back to the pre-batch chain: by the
        # time that alloc fires, the old tail slice is FULL, so the prev
        # pointer is its last slot (== tail_t when it was already full)
        old_full = ptr_mod.encode(tbl, pb, cp, sl0,
                                  tbl["slice_size"][cp] - jnp.uint32(1))
        ptr_val = jnp.where(j == 0,
                            jnp.where(new, jnp.uint32(NULL), old_full),
                            prev_ptr)
        ptr_write = evt_ok & (pool_j > 0)
        ptr_addr = jnp.where(
            ptr_write,
            ptr_mod.to_addr(tbl, pool_j.astype(jnp.uint32),
                            jnp.maximum(evt_slice, 0).astype(jnp.uint32),
                            jnp.uint32(0)).astype(jnp.int32),
            H + i_idx)

        # per-term tail/freq: landed occurrences are a stream prefix, so
        # the new tail is the (seg_start + n_land - 1)-th occurrence.
        # n_land per term via cumsum over the sorted order (cheaper than
        # a segment reduction): count in [seg_start, seg_end].
        is_last = jnp.where(i_idx == N - 1, True, jnp.roll(head, -1))
        seg_end = jax.lax.cummin(
            jnp.where(is_last, i_idx, BIG), reverse=True)
        c = jnp.cumsum(land.astype(jnp.int32))
        n_land = (c[seg_end] - c[seg_start]
                  + land[seg_start].astype(jnp.int32))
        last = jnp.clip(seg_start + n_land - 1, 0, jnp.maximum(N - 1, 0))
        new_tail = ptr_mod.encode(tbl, pb,
                                  pool_occ[last].astype(jnp.uint32),
                                  slice_occ[last].astype(jnp.uint32),
                                  off_occ[last].astype(jnp.uint32))
        write_term = head & valid_s & (n_land > 0)
        term_idx = jnp.where(write_term, t_s.astype(jnp.int32), V + i_idx)
        term_freq = state.freq[jnp.minimum(t_s, jnp.uint32(V - 1))] + n_land
        overflow = state.overflow | any_fail
        return ((post_addr, post_val, ptr_addr, ptr_val,
                 term_idx, new_tail, term_freq),
                new_wm.astype(jnp.int32), new_fc.astype(jnp.int32),
                overflow)

    # the input state is DONATED: heap/tail/freq update in place (the
    # zero-copy invariant, now end-to-end).  Callers must rebind —
    # ``state = ingest(state, ...)`` — and never touch the old reference
    # afterwards; every engine in the repo already does exactly that.
    # The scan path never donates (it is the comparison oracle).
    @functools.partial(jax.jit, donate_argnums=0)
    def ingest(state: PoolState, terms, postings,
               start_pools=None, valid=None) -> PoolState:
        n = terms.shape[0]
        if start_pools is None:
            start_pools = jnp.zeros((n,), jnp.uint32)
        if valid is None:
            valid = jnp.ones((n,), bool)
        scat, wm, fc, overflow = _plan(
            state, terms.astype(jnp.uint32), postings.astype(jnp.uint32),
            start_pools.astype(jnp.uint32), valid)
        heap, tail, freq = kops.bulk_append(
            state.heap, state.tail, state.freq, *scat,
            use_kernel=use_kernel, interpret=interpret)
        return PoolState(heap, wm, tail, freq, overflow,
                         state.free_list, fc)

    return ingest


# ---------------------------------------------------------------------------
# Slice reclamation (segment rollover -> free list).
# ---------------------------------------------------------------------------
def release_slices(layout: PoolLayout, state: PoolState, freed,
                   *, reset_terms: bool = True) -> PoolState:
    """Return reclaimed slices to the per-pool free lists (host-side).

    ``freed`` is a per-pool sequence of slice-index arrays — exactly what
    :func:`repro.core.segments.freeze_state` reports as
    ``FrozenSegment.freed_slices``; for a sharded state (leaves
    ``[S, ...]``) pass one such sequence per shard.  ``reset_terms``
    clears ``tail``/``freq`` so the pool is an empty active segment again
    (heap bytes are left in place: they were already frozen into the
    read-only CSR segment, and recycled slices overwrite them lazily).

    Rollover is off the ingest hot path (exactly like the freeze walk),
    so this runs in numpy and re-uploads the small non-heap leaves.
    """
    wm = np.asarray(state.watermark)
    sharded = wm.ndim == 2
    fl = np.asarray(state.free_list).copy()
    fc = np.asarray(state.free_count).copy()
    base = np.asarray(layout.free_base, np.int64)
    caps = np.asarray(layout.slices_per_pool, np.int64)

    def _push(fl_row, fc_row, wm_row, per_pool):
        for p, sl in enumerate(per_pool):
            sl = np.asarray(sl, np.int32)
            if sl.size == 0:
                continue
            if np.unique(sl).size != sl.size:
                raise ValueError(
                    f"pool {p}: slice released twice in one call — "
                    f"double release?")
            held = fl_row[base[p]: base[p] + fc_row[p]]
            if np.intersect1d(sl, held).size:
                raise ValueError(
                    f"pool {p}: slice already on the free list — "
                    f"double release?")
            if sl.size and (int(sl.max()) >= int(wm_row[p])
                            or int(sl.min()) < 0):
                raise ValueError(
                    f"pool {p}: slice index outside the allocated range "
                    f"[0, {wm_row[p]}) — not this pool's slice")
            n = int(fc_row[p]) + sl.size
            if n > caps[p]:
                raise ValueError(
                    f"pool {p}: releasing {sl.size} slices overflows the "
                    f"free list ({fc_row[p]} held, capacity {caps[p]})")
            fl_row[base[p] + fc_row[p]: base[p] + n] = sl
            fc_row[p] = n

    if sharded:
        for s, per_pool in enumerate(freed):
            _push(fl[s], fc[s], wm[s], per_pool)
    else:
        _push(fl, fc, wm, freed)

    tail, freq = state.tail, state.freq
    if reset_terms:
        tail = jnp.full_like(state.tail, NULL)
        freq = jnp.zeros_like(state.freq)
    return state._replace(free_list=jnp.asarray(fl),
                          free_count=jnp.asarray(fc),
                          tail=tail, freq=freq)


# ---------------------------------------------------------------------------
# Chain walking / materialisation.
# ---------------------------------------------------------------------------
def make_chain_walker(layout: PoolLayout, max_slices: int):
    """Build ``walk(state, term) -> (base, data_start, last_off, n_slices)``.

    Walks the backwards-linked slice chain newest-first, reading each
    slice's previous-pointer from its slot 0.  ``max_slices`` is a static
    bound (use :func:`repro.core.analytical.slices_needed` for the corpus
    max frequency).
    """
    tbl = layout.tables()
    pb = layout.pool_bits

    def walk(state: PoolState, term):
        def body(i, carry):
            p, bases, starts, lasts, count = carry
            pool, sl, off = ptr_mod.decode(tbl, pb, p)
            live = ~ptr_mod.is_null(p)
            base = ptr_mod.to_addr(tbl, pool, sl, jnp.uint32(0))
            data_start = jnp.where(pool > 0, jnp.uint32(1), jnp.uint32(0))
            bases = bases.at[i].set(jnp.where(live, base, 0))
            starts = starts.at[i].set(jnp.where(live, data_start, 0))
            lasts = lasts.at[i].set(jnp.where(live, off, 0))
            count = count + live.astype(jnp.int32)
            nxt = jnp.where(pool > 0, state.heap[base], jnp.uint32(NULL))
            p = jnp.where(live, nxt, p)
            return p, bases, starts, lasts, count

        init = (
            state.tail[term],
            jnp.zeros((max_slices,), jnp.uint32),
            jnp.zeros((max_slices,), jnp.uint32),
            jnp.zeros((max_slices,), jnp.uint32),
            jnp.int32(0),
        )
        _, bases, starts, lasts, count = jax.lax.fori_loop(
            0, max_slices, body, init)
        return bases, starts, lasts, count

    return walk


def chain_lens_cum(starts, lasts, n_slices, max_slices: int):
    """Cumulative flattened lane counts of a walked chain: ``cum[i]`` is
    the number of postings in the newest ``i + 1`` slices (``cum[-1]`` =
    the chain's total).  Shared by the full materializer and the tiled
    top-k window materializer so both use ONE lane-address source."""
    live = jnp.arange(max_slices) < n_slices
    lens = jnp.where(live, lasts - starts + 1, 0).astype(jnp.int32)
    return jnp.cumsum(lens)


def chain_window_addrs(bases, lasts, cum, lanes, max_slices: int):
    """Heap addresses of reverse-chronological lanes ``lanes`` of a
    walked chain (the materializer's vectorised two-phase gather,
    restricted to an arbitrary lane window).  Lanes >= ``cum[-1]`` yield
    clamped garbage addresses — callers mask by the total."""
    s = jnp.searchsorted(cum, lanes, side="right").astype(jnp.int32)
    s = jnp.minimum(s, max_slices - 1)
    before = jnp.where(s > 0, cum[jnp.maximum(s - 1, 0)], 0)
    within = (lanes - before).astype(jnp.uint32)
    return bases[s] + lasts[s] - within


def make_materializer(layout: PoolLayout, max_slices: int, max_len: int):
    """Build ``materialize(state, term) -> (postings_desc, length)``.

    Returns the term's postings in reverse-chronological order (the paper's
    traversal order), padded to ``max_len``.  Two-phase: O(#slices) chain
    walk, then one fully-vectorised gather — this is the TPU-friendly
    "flatten the chain, then stream" pattern (DESIGN.md §6.2).
    """
    walk = make_chain_walker(layout, max_slices)

    def materialize(state: PoolState, term):
        bases, starts, lasts, n = walk(state, term)
        cum = chain_lens_cum(starts, lasts, n, max_slices)
        total = jnp.minimum(cum[-1], max_len)
        j = jnp.arange(max_len, dtype=jnp.int32)
        addr = chain_window_addrs(bases, lasts, cum, j, max_slices)
        vals = state.heap[addr]
        vals = jnp.where(j < total, vals, jnp.uint32(0))
        return vals, total

    return materialize
