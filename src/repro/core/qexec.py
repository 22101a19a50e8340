"""Batched query execution over the streaming lifecycle (Earlybird §5).

After PR 4 the INGEST side scaled (one fused dispatch per arrival
batch), but queries still ran one at a time: the lifecycle engines
walked frozen segments in a host-side Python loop — one jitted call
plus one device->host ``np.asarray`` sync per segment per query — and
top-k was a full intersection sliced to ``[:k]``.  This module is the
query-side counterpart of bulk ingest, in three layers:

  1. **Segment stacking.**  All G frozen segments' per-term compressed
     docid lists are packed into one padded device-resident stack
     (:class:`FrozenStack` -> ``StackedLists`` with ``[Q, T, G, ...]``
     leaves, pow2-bucketed like ``pack_docids`` shapes so a streaming
     engine sees O(log^2) distinct jit keys).  A query evaluates over
     EVERY frozen segment inside a single jitted vmap — zero host syncs
     in the frozen path.  Per-(term, segment) summaries (valid count,
     first/last docid) ride along for whole-segment skips.  G itself is
     bounded by tiered compaction
     (:class:`~repro.core.segments.CompactionPolicy`): without it the
     stack's gather cost and pow2(G) bucket crossings grow linearly
     with stream age; with it G = O(log N).
  2. **Query batching.**  A ``[Q, max_query_len]`` term matrix is
     evaluated in one dispatch over the active pool (vmap over queries
     on the existing ``*_asc`` engines; the sharded engine already
     composes under ``shard_map`` with ONE ``all_gather`` for the whole
     batch) plus the frozen stack, merged with the vectorised
     :func:`~repro.core.sharded_index.merge_desc` (disjoint per-segment
     docid ranges make the sort a newest-first concatenation).
  3. **Top-k early exit.**  :func:`frozen_topk` banks hits
     newest-segment-first in a ``lax.while_loop`` and stops consuming
     older segments once ``k`` hits are collected;
     :func:`make_active_topk_fn` does the same inside the active
     segment, consuming the query's shortest slice chain in
     newest-first tiles and probing the other terms' chains in place.
     Both are BIT-IDENTICAL to the full
     evaluation's top-k (segments own disjoint descending docid
     ranges; tiles are consumed in docid-descending order), proven in
     tests/test_qexec.py for every k including k > |result|.

The per-query host-loop path survives as the equivalence oracle
(``LifecycleEngine(batched=False)``).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import postings as post
from repro.core import query as q
from repro.core import slicepool
from repro.core.pointers import PoolLayout
from repro.core.sharded_index import merge_desc, merge_desc_scored
from repro.kernels.segment_intersect import (SEG_BLOCK, ScoredStack,
                                             StackedLists, _pow2,
                                             decode_scores, decode_stacked,
                                             pack_docids, pack_scored,
                                             repad_scored, repad_stacked,
                                             stack_packed, stack_scored)

INVALID = q.INVALID


def bucket_pow2(n: int, floor: int = 1) -> int:
    """Next power of two >= max(n, floor) — the shared shape-bucketing
    rule (query batches, top-k buffers, stack paddings), so jit caches
    stay O(log) in every dynamic size."""
    return _pow2(max(int(n), floor))


# ---------------------------------------------------------------------------
# Frozen stack: device-resident [G, ...] view of the packed segments
# ---------------------------------------------------------------------------
class FrozenStack:
    """Stacked device view of an ordered frozen-segment list (oldest ->
    newest).  Wraps the lifecycle's ``PackedSegment`` objects
    (duck-typed: ``.packed(t)`` / ``.postings_asc(t)`` / ``.bounds(t)``
    / ``.doc_base``) and caches, per term, the ``[G, ...]`` stacked
    leaves plus the (count, last-docid) summaries — built once per
    (stack, term), reused by every query batch until the next CHANGE to
    the frozen-segment list invalidates the whole stack.  Rollover
    (appends a segment) and compaction (replaces a window with its
    merge) both count: the lifecycle engines' ``_sync_frozen`` drops the
    stack whenever the list's membership differs, so a compacted set
    rebuilds at its new, smaller G — that shrinking G is exactly how
    compaction bounds the gather cost and the pow2(G) jit-recompile
    cadence under an infinite stream."""

    def __init__(self, psegs: Sequence,
                 floors: Optional[Dict[str, int]] = None):
        self.psegs = list(psegs)
        # shape ratchet (serving-path option): when a dict is supplied,
        # every gather raises its pow2 width bucket to the largest one
        # this dict has recorded and records its own — so the jitted
        # downstream shapes STOP varying with the batch's posting
        # lengths once the heaviest term has been seen.  The dict is
        # owned by the engine and shared across stack rebuilds, keeping
        # the ratchet through rollovers/compactions.  ``None`` (the
        # default) keeps the original per-batch minimal buckets.
        self.floors = floors
        self.doc_bases = np.asarray([p.doc_base for p in self.psegs],
                                    np.uint32)
        self._terms: Dict[int, Tuple[StackedLists, np.ndarray]] = {}
        self._posts: Dict[int, np.ndarray] = {}
        self._empty: Optional[Tuple[StackedLists, np.ndarray]] = None
        # scored stacks: (ScoredStack, lasts, smax) per term — the smax
        # column is the per-(term, segment) max-impact summary the
        # segment-level WAND skip consumes.
        self._sterms: Dict[int, Tuple[ScoredStack, np.ndarray,
                                      np.ndarray]] = {}
        self._sempty: Optional[Tuple[ScoredStack, np.ndarray,
                                     np.ndarray]] = None

    @property
    def n_segments(self) -> int:
        return len(self.psegs)

    # -- per-term caches (host-side, off the jitted query path) ----------
    def _term_stack(self, term: int) -> Tuple[StackedLists, np.ndarray]:
        got = self._terms.get(term)
        if got is None:
            st = stack_packed([p.packed(term) for p in self.psegs])
            lasts = np.zeros(self.n_segments, np.uint32)
            for g, p in enumerate(self.psegs):
                c, _, last = p.bounds(term)
                lasts[g] = last if c else 0
            got = (st, lasts)
            self._terms[term] = got
        return got

    def _empty_stack(self) -> Tuple[StackedLists, np.ndarray]:
        # padding slots of the [Q, T] term matrix gather this instead of
        # term 0's real lists: the fold masks them out anyway, and empty
        # stacks keep the shared NB/PW buckets minimal.
        if self._empty is None:
            st = stack_packed([pack_docids(np.zeros(0, np.uint32))
                               for _ in self.psegs])
            self._empty = (st, np.zeros(self.n_segments, np.uint32))
        return self._empty

    def _scored_term(self, term: int
                     ) -> Tuple[ScoredStack, np.ndarray, np.ndarray]:
        got = self._sterms.get(term)
        if got is None:
            scs = [p.scored(term) for p in self.psegs]
            st = stack_scored(scs)
            lasts = np.zeros(self.n_segments, np.uint32)
            smax = np.zeros(self.n_segments, np.int32)
            for g, p in enumerate(self.psegs):
                c, _, last = p.bounds(term)
                lasts[g] = last if c else 0
                smax[g] = scs[g].smax
            got = (st, lasts, smax)
            self._sterms[term] = got
        return got

    def _empty_scored(self) -> Tuple[ScoredStack, np.ndarray, np.ndarray]:
        if self._sempty is None:
            st = stack_scored([pack_scored(np.zeros(0, np.uint32),
                                           np.zeros(0, np.int32))
                               for _ in self.psegs])
            self._sempty = (st, np.zeros(self.n_segments, np.uint32),
                            np.zeros(self.n_segments, np.int32))
        return self._sempty

    def _post_stack(self, term: int) -> np.ndarray:
        got = self._posts.get(term)
        if got is None:
            arrs = [np.asarray(p.postings_asc(term), np.uint32)
                    for p in self.psegs]
            width = bucket_pow2(max([a.size for a in arrs] + [1]), 8)
            got = np.full((self.n_segments, width), INVALID, np.uint32)
            for g, a in enumerate(arrs):
                got[g, : a.size] = a
            self._posts[term] = got
        return got

    def _ratchet(self, key: str, val: int) -> int:
        """Raise ``val`` to the remembered floor for ``key`` (and the
        floor to ``val``).  Identity when the ratchet is off."""
        if self.floors is None:
            return val
        val = max(val, self.floors.get(key, 1))
        self.floors[key] = val
        return val

    # -- batch gathers ----------------------------------------------------
    def gather(self, terms: np.ndarray, n_terms: np.ndarray
               ) -> Tuple[StackedLists, jax.Array]:
        """Gather a ``[Q, T]`` term matrix into one device stack.

        Returns ``(StackedLists with [Q, T, G, ...] leaves,
        lasts uint32[Q, T, G])`` — every list padded to the batch's
        shared pow2 (NB, PW) bucket.  Host-side numpy; the single
        ``jnp.asarray`` per leaf is the only device transfer.
        """
        with jax.profiler.TraceAnnotation("qexec.frozen_gather") as span:
            cells = [[self._term_stack(int(t)) if j < int(n)
                      else self._empty_stack()
                      for j, t in enumerate(row)]
                     for row, n in zip(terms, n_terms)]
            nb = self._ratchet("nb", bucket_pow2(
                max(c[0].n_blocks for row in cells for c in row)))
            pw = self._ratchet("pw", bucket_pow2(
                max(c[0].n_words for row in cells for c in row)))
            rows = [[repad_stacked(c[0], nb, pw) for c in row]
                    for row in cells]
            leaves = StackedLists(*[
                np.stack([np.stack([getattr(c, f) for c in row])
                          for row in rows])
                for f in StackedLists._fields])
            lasts = np.stack([np.stack([c[1] for c in row])
                              for row in cells])
            span.set_metadata(bytes=_nbytes(leaves, lasts))
            return (jax.tree.map(jnp.asarray, leaves), jnp.asarray(lasts))

    def gather_scored(self, terms: np.ndarray, n_terms: np.ndarray
                      ) -> Tuple[ScoredStack, jax.Array, jax.Array]:
        """Scored counterpart of :meth:`gather`: returns ``(ScoredStack
        with [Q, T, G, ...] leaves, lasts uint32[Q, T, G],
        smax int32[Q, T, G])`` — docid stacks plus impact planes,
        block-max planes and the per-(term, segment) max-impact summary.
        """
        with jax.profiler.TraceAnnotation("qexec.frozen_gather") as span:
            cells = [[self._scored_term(int(t)) if j < int(n)
                      else self._empty_scored()
                      for j, t in enumerate(row)]
                     for row, n in zip(terms, n_terms)]
            nb = self._ratchet("snb", bucket_pow2(
                max(c[0].ids.n_blocks for row in cells for c in row)))
            pw = self._ratchet("spw", bucket_pow2(
                max(c[0].ids.n_words for row in cells for c in row)))
            rows = [[repad_scored(c[0], nb, pw) for c in row]
                    for row in cells]
            ids = StackedLists(*[
                np.stack([np.stack([getattr(c.ids, f) for c in row])
                          for row in rows])
                for f in StackedLists._fields])
            swords = np.stack([np.stack([c.swords for c in row])
                               for row in rows])
            bmax = np.stack([np.stack([c.bmax for c in row])
                             for row in rows])
            leaves = ScoredStack(ids=ids, swords=swords, bmax=bmax)
            lasts = np.stack([np.stack([c[1] for c in row])
                              for row in cells])
            smax = np.stack([np.stack([c[2] for c in row])
                             for row in cells])
            span.set_metadata(bytes=_nbytes(leaves, lasts, smax))
            return (jax.tree.map(jnp.asarray, leaves), jnp.asarray(lasts),
                    jnp.asarray(smax))

    def gather_postings(self, t1s: np.ndarray, t2s: np.ndarray,
                        n_live: Optional[int] = None
                        ) -> Tuple[jax.Array, jax.Array]:
        """Gather positional postings stacks for a phrase batch:
        ``(uint32[Q, G, PL], uint32[Q, G, PL])``, INVALID-padded
        ascending (segment-relative docid, position) postings.  Rows at
        index >= ``n_live`` (batch padding) gather an all-INVALID stack
        instead of term 0's real postings, so padding never inflates the
        shared width bucket or ships discarded data."""
        if n_live is None:
            n_live = len(t1s)
        with jax.profiler.TraceAnnotation("qexec.frozen_gather") as span:
            empty = np.full((self.n_segments, 8), INVALID, np.uint32)
            p1 = [self._post_stack(int(t)) if i < n_live else empty
                  for i, t in enumerate(t1s)]
            p2 = [self._post_stack(int(t)) if i < n_live else empty
                  for i, t in enumerate(t2s)]
            width = self._ratchet("pl", bucket_pow2(
                max(a.shape[1] for a in p1 + p2)))

            def pad(stacks):
                out = np.full((len(stacks), self.n_segments, width),
                              INVALID, np.uint32)
                for i, a in enumerate(stacks):
                    out[i, :, : a.shape[1]] = a
                return out

            h1, h2 = pad(p1), pad(p2)
            span.set_metadata(bytes=h1.nbytes + h2.nbytes)
            return jnp.asarray(h1), jnp.asarray(h2)


def _nbytes(*trees) -> int:
    """Bytes of the host (numpy) leaves a gather ships to the device."""
    return sum(x.nbytes for x in jax.tree.leaves(trees))


# ---------------------------------------------------------------------------
# Jitted batched evaluation
# ---------------------------------------------------------------------------
def _fold_conjunctive(ids_tg, ns_tg, nt, nt_slots, hit01=None):
    """Intersect one (query, segment) cell's term lists: ``[T, W]``
    ascending INVALID-padded decoded docids -> (asc, n).  ``hit01``
    optionally injects the kernel-computed membership mask for the
    (term0, term1) driving pair — bit-identical to the jnp fold."""
    cur, n = ids_tg[0], ns_tg[0]
    for j in range(1, nt_slots):
        use = j < nt
        if j == 1 and hit01 is not None:
            hit = hit01
        else:
            hit = q.member_asc(cur, ids_tg[j])
        nxt, nn = q._compact(cur, hit)
        cur = jnp.where(use, nxt, cur)
        n = jnp.where(use, nn, n)
    return cur, n


@functools.partial(jax.jit,
                   static_argnames=("kind", "nt_slots", "kernel",
                                    "interpret"))
def frozen_merge(active_desc, active_n, lists: StackedLists, n_terms,
                 base, *, kind: str, nt_slots: int, kernel: bool = False,
                 interpret=None):
    """Evaluate + merge a query batch over the frozen stack in ONE
    dispatch.

    ``active_desc``/``active_n``: the active segment's per-query
    descending SEGMENT-RELATIVE docids (single-device or sharded-merged)
    — globalised here by ``base`` and masked for padding rows
    (``n_terms == 0``).  ``lists``: ``[Q, T, G, ...]`` stack.  Returns
    globally-descending ``(uint32[Q, A + G * W_kind], int32[Q])`` —
    bit-identical to the host-loop oracle because segments own disjoint
    docid ranges (the merge sort IS newest-first concatenation).

    ``kernel=True`` routes the driving (term0, term1) intersection of
    every (query, segment) pair through the batched Pallas grid kernel
    (one pallas_call over Q * G rows); the fold for further terms stays
    jnp.  Masks are bit-identical, so results do not depend on the flag.
    """
    from repro.kernels import ops
    Q, T, G, _ = lists.firsts.shape
    W = lists.n_blocks * SEG_BLOCK
    ids = decode_stacked(lists)                       # [Q, T, G, W]
    ns = jnp.asarray(lists.ns)                         # [Q, T, G]

    if kind == "conjunctive":
        hit01 = None
        if kernel and nt_slots >= 2:
            def flat(x):
                return x[:, 0].reshape((Q * G,) + x.shape[3:])

            def flatb(x):
                return x[:, 1].reshape((Q * G,) + x.shape[3:])
            a_st = StackedLists(*[flat(getattr(lists, f))
                                  for f in StackedLists._fields[:-1]],
                                ns=lists.ns[:, 0].reshape(Q * G))
            b_st = StackedLists(*[flatb(getattr(lists, f))
                                  for f in StackedLists._fields[:-1]],
                                ns=lists.ns[:, 1].reshape(Q * G))
            mask = ops.segment_intersect_mask_batched(
                a_st, b_st, use_kernel=True, interpret=interpret)
            hit01 = mask.reshape(Q, G, W).astype(bool)

        def per_seg(ids_tg, ns_tg, nt, hit_g):
            asc, n = _fold_conjunctive(ids_tg, ns_tg, nt, nt_slots, hit_g)
            return q.asc_to_desc(asc, n), n

        if hit01 is None:
            hit01 = jnp.zeros((Q, G, W), bool)  # unused placeholder

            def per_seg_(i, s, nt, h):
                return per_seg(i, s, nt, None)
        else:
            per_seg_ = per_seg
        per_q = jax.vmap(per_seg_, in_axes=(1, 1, None, 0))
        desc_seg, n_seg = jax.vmap(per_q)(ids, ns, n_terms, hit01)
    elif kind == "disjunctive":
        def per_seg(ids_tg, nt):
            slot = jnp.arange(nt_slots)[:, None] < nt
            flat = jnp.where(slot, ids_tg, INVALID).reshape(-1)
            asc, n = q.dedup_asc(jnp.sort(flat))
            return q.asc_to_desc(asc, n), n
        per_q = jax.vmap(per_seg, in_axes=(1, None))
        desc_seg, n_seg = jax.vmap(per_q)(ids, n_terms)
    else:
        raise ValueError(f"unknown kind {kind!r}")

    live = n_terms > 0
    return _merge_parts(active_desc, active_n, desc_seg, n_seg, live, base)


@jax.jit
def frozen_phrase_merge(active_desc, active_n, p1, p2, doc_bases, live,
                        base):
    """Phrase evaluation over the frozen postings stacks
    (``uint32[Q, G, PL]`` ascending packed (docid, pos) postings, the
    positional substrate the compressed docid stacks drop) merged with
    the active part — the batched counterpart of ``phrase_packed``."""
    PL = p1.shape[-1]

    def per_seg(x1, x2, db):
        want = jnp.where(x1 != INVALID, x1 + jnp.uint32(1), INVALID)
        hit = q.member_asc(want, x2)
        ids = jnp.where(hit, post.docid(x1), INVALID)
        asc, n = q.dedup_asc(jnp.sort(ids))
        gids = jnp.where(jnp.arange(PL) < n, asc + db, INVALID)
        return q.asc_to_desc(gids, n), n

    per_q = jax.vmap(per_seg, in_axes=(0, 0, 0))
    desc_seg, n_seg = jax.vmap(per_q, in_axes=(0, 0, None))(p1, p2,
                                                            doc_bases)
    return _merge_parts(active_desc, active_n, desc_seg, n_seg, live > 0,
                        base)


def _merge_parts(active_desc, active_n, desc_seg, n_seg, live, base):
    Q, A = active_desc.shape
    G, W = desc_seg.shape[1], desc_seg.shape[2]
    an = jnp.where(live, active_n, 0)
    a_glob = jnp.where(jnp.arange(A)[None, :] < an[:, None],
                       active_desc + base, INVALID)
    nseg = jnp.where(live[:, None], n_seg, 0)
    dseg = jnp.where(jnp.arange(W)[None, None, :] < nseg[..., None],
                     desc_seg, INVALID)
    flat = jnp.concatenate([a_glob, dseg.reshape(Q, G * W)], axis=1)
    merged = jax.vmap(merge_desc)(flat)
    return merged, an + jnp.sum(nseg, axis=1)


@jax.jit
def finalize(active_desc, active_n, live, base):
    """No-frozen-segments fast path: globalise + mask the active batch."""
    an = jnp.where(live > 0, active_n, 0)
    A = active_desc.shape[1]
    out = jnp.where(jnp.arange(A)[None, :] < an[:, None],
                    active_desc + base, INVALID)
    return out, an


# ---------------------------------------------------------------------------
# Top-k early exit (newest-first while_loop over the stack)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("nt_slots", "k_pad"))
def frozen_topk(active_desc, active_n, lists: StackedLists, n_terms,
                base, lasts_doc, k, *, nt_slots: int, k_pad: int):
    """Bank the newest ``k`` conjunctive hits, consuming segments
    newest-first and STOPPING as soon as k are banked — Earlybird's
    early termination at segment granularity, bit-identical to the full
    evaluation's ``[:k]`` because segments own disjoint descending docid
    ranges.  Per-(term, segment) summaries (count, first/last docid)
    skip whole segments that cannot contribute (an empty term list, or
    term ranges that do not overlap) without decoding a single block.

    ``k`` is dynamic (clamped to the static ``k_pad`` buffer width) so
    one compiled program serves every k in a pow2 bucket.
    """
    Q, T, G, _ = lists.firsts.shape
    W = lists.n_blocks * SEG_BLOCK
    an = jnp.minimum(jnp.where(n_terms > 0, active_n, 0), k)
    A = active_desc.shape[1]
    if A >= k_pad:
        aa = active_desc[:, :k_pad]
    else:
        aa = jnp.concatenate(
            [active_desc,
             jnp.full((Q, k_pad - A), INVALID, active_desc.dtype)], axis=1)
    out0 = jnp.where(jnp.arange(k_pad)[None, :] < an[:, None],
                     aa + base, INVALID)

    def one(out_i, b_i, leaves_q, nt, ld_q):
        fd_q = leaves_q.firsts[..., 0]          # [T, G] first docids

        def cond(c):
            i, b, _ = c
            return (i < G) & (b < k)

        def body(c):
            i, b, out = c
            g = G - 1 - i                       # newest segment first
            seg = jax.tree.map(lambda x: x[:, g], leaves_q)
            ns_g = jnp.asarray(seg.ns)
            slot = jnp.arange(nt_slots) < nt
            nonempty = jnp.all(jnp.where(slot, ns_g > 0, True)) & (nt > 0)
            lo = jnp.max(jnp.where(slot, fd_q[:, g], jnp.uint32(0)))
            hi = jnp.min(jnp.where(slot, ld_q[:, g],
                                   jnp.uint32(INVALID - jnp.uint32(1))))
            live_g = nonempty & (lo <= hi)

            def eval_seg(_):
                ids = decode_stacked(seg)      # [T, W]
                asc, n = _fold_conjunctive(ids, ns_g, nt, nt_slots)
                return q.asc_to_desc(asc, n), n

            desc_g, n_g = jax.lax.cond(
                live_g, eval_seg,
                lambda _: (jnp.full((W,), INVALID, jnp.uint32),
                           jnp.int32(0)),
                None)
            lane = jnp.arange(W)
            idx = jnp.where(lane < n_g, b + lane, k_pad)
            out = out.at[idx].set(desc_g, mode="drop")
            return i + 1, jnp.minimum(k, b + n_g), out

        _, b, out = jax.lax.while_loop(cond, body,
                                       (jnp.int32(0), b_i, out_i))
        return out, b

    return jax.vmap(one, in_axes=(0, 0, 0, 0, 0))(out0, an, lists,
                                                  n_terms, lasts_doc)


# ---------------------------------------------------------------------------
# Scored retrieval: block-max WAND / MaxScore over the frozen stack
# ---------------------------------------------------------------------------
def _rank_scored(ids, scores):
    """Sort lanes by (score desc, docid desc); INVALID lanes last.

    One stable two-key ``lax.sort``: key1 flips the score (impacts are
    tiny — at most max_query_len * SCORE_MAX — so the flip never wraps),
    key2 flips the docid, and INVALID lanes force both keys to the max.
    Score ties therefore resolve newest-doc-first, which is what makes
    banking newest-segment-first exact under early termination."""
    valid = ids != INVALID
    k1 = jnp.where(valid,
                   jnp.uint32(0x7FFFFFFF) - scores.astype(jnp.uint32),
                   jnp.uint32(0xFFFFFFFF))
    k2 = jnp.where(valid, jnp.uint32(0xFFFFFFFF) - ids,
                   jnp.uint32(0xFFFFFFFF))
    _, _, ids_s, sc_s = jax.lax.sort((k1, k2, ids, scores), num_keys=2,
                                     is_stable=True)
    return ids_s, sc_s


def _fold_scored(ids_tg, scs_tg, nt, nt_slots, sc01=None):
    """Scored conjunctive fold over one (query, segment) cell: ``[T, W]``
    decoded docids + impact lanes -> (hit bool[W], score int32[W]) on
    term 0's lanes.  ``sc01`` optionally injects the kernel-computed
    (term0 + term1) impact sums (0 = no hit) for the driving pair."""
    cand = ids_tg[0]
    if sc01 is None:
        hit = cand != INVALID
        score = scs_tg[0]
        start = 1
    else:
        use1 = jnp.int32(1) < nt
        hit = jnp.where(use1, sc01 > 0, cand != INVALID)
        score = jnp.where(use1, sc01, scs_tg[0])
        start = 2
    for j in range(start, nt_slots):
        use = j < nt
        pos = jnp.minimum(jnp.searchsorted(ids_tg[j], cand),
                          cand.shape[0] - 1)
        m = (ids_tg[j][pos] == cand) & (cand != INVALID)
        hit = hit & jnp.where(use, m, True)
        score = score + jnp.where(use & m, scs_tg[j][pos], 0)
    return hit & (cand != INVALID), score


def _merge_parts_scored(active_desc, active_sc, active_n, desc_seg,
                        sc_seg, n_seg, live, base):
    Q, A = active_desc.shape
    G, W = desc_seg.shape[1], desc_seg.shape[2]
    an = jnp.where(live, active_n, 0)
    alane = jnp.arange(A)[None, :] < an[:, None]
    a_glob = jnp.where(alane, active_desc + base, INVALID)
    a_sc = jnp.where(alane, active_sc, 0)
    nseg = jnp.where(live[:, None], n_seg, 0)
    mseg = jnp.arange(W)[None, None, :] < nseg[..., None]
    dseg = jnp.where(mseg, desc_seg, INVALID)
    sseg = jnp.where(mseg, sc_seg, 0)
    flat = jnp.concatenate([a_glob, dseg.reshape(Q, G * W)], axis=1)
    flat_sc = jnp.concatenate([a_sc, sseg.reshape(Q, G * W)], axis=1)
    ids, scs = jax.vmap(merge_desc_scored)(flat, flat_sc)
    return ids, scs, an + jnp.sum(nseg, axis=1)


@functools.partial(jax.jit, static_argnames=("nt_slots", "kernel",
                                             "interpret"))
def frozen_scored_merge(active_desc, active_sc, active_n,
                        sc: ScoredStack, n_terms, base, *, nt_slots: int,
                        kernel: bool = False, interpret=None):
    """FULL scored conjunctive evaluation over the frozen stack in one
    dispatch (no early termination — the exhaustive baseline scored
    top-k is proven bit-identical to).  Returns globally-descending
    ``(ids uint32[Q, A + G * W], scores int32[Q, ...], n int32[Q])``;
    rank by score afterwards with :func:`rank_scored`.

    ``kernel=True`` routes the driving (term0, term1) scored
    intersection of every (query, segment) pair through the batched
    scored Pallas kernel with skipping disabled (th = -1)."""
    from repro.kernels import ops
    lists = sc.ids
    Q, T, G, _ = lists.firsts.shape
    W = lists.n_blocks * SEG_BLOCK
    ids = decode_stacked(lists)                        # [Q, T, G, W]
    scs = decode_scores(sc.swords)                     # [Q, T, G, W]

    sc01 = None
    if kernel and nt_slots >= 2:
        def flat(x, t):
            return x[:, t].reshape((Q * G,) + x.shape[3:])

        def slot_stack(t):
            st = StackedLists(*[flat(getattr(lists, f), t)
                                for f in StackedLists._fields[:-1]],
                              ns=lists.ns[:, t].reshape(Q * G))
            return ScoredStack(ids=st, swords=flat(sc.swords, t),
                               bmax=flat(sc.bmax, t))
        out = ops.scored_intersect_batched(
            slot_stack(0), slot_stack(1),
            jnp.zeros((Q * G,), jnp.int32),
            jnp.full((Q * G,), -1, jnp.int32),
            use_kernel=True, interpret=interpret)
        sc01 = out.reshape(Q, G, W)

    def per_seg(ids_tg, scs_tg, nt, sc01_g):
        hit, score = _fold_scored(ids_tg, scs_tg, nt, nt_slots, sc01_g)
        comp_ids, n = q._compact(ids_tg[0], hit)
        comp_sc, _ = q._compact(score, hit, fill=jnp.int32(0))
        return (q.flip_valid(comp_ids, n, INVALID),
                q.flip_valid(comp_sc, n, jnp.int32(0)), n)

    if sc01 is None:
        sc01 = jnp.zeros((Q, G, W), jnp.int32)  # unused placeholder

        def per_seg_(i, s, nt, h):
            return per_seg(i, s, nt, None)
    else:
        per_seg_ = per_seg
    per_q = jax.vmap(per_seg_, in_axes=(1, 1, None, 0))
    desc_seg, sc_seg, n_seg = jax.vmap(per_q)(ids, scs, n_terms, sc01)
    live = n_terms > 0
    return _merge_parts_scored(active_desc, active_sc, active_n,
                               desc_seg, sc_seg, n_seg, live, base)


@jax.jit
def rank_scored(ids, scores, n):
    """Re-rank docid-descending scored rows by (score desc, docid desc)."""
    W = ids.shape[1]
    m = jnp.arange(W)[None, :] < n[:, None]
    ids = jnp.where(m, ids, INVALID)
    scores = jnp.where(m, scores, 0)
    ids_s, sc_s = jax.vmap(_rank_scored)(ids, scores)
    return ids_s, sc_s, n


@jax.jit
def finalize_scored(active_desc, active_sc, active_n, live, base):
    """No-frozen-segments fast path: globalise, mask and rank the
    active batch by (score desc, docid desc)."""
    an = jnp.where(live > 0, active_n, 0)
    A = active_desc.shape[1]
    m = jnp.arange(A)[None, :] < an[:, None]
    ids = jnp.where(m, active_desc + base, INVALID)
    scs = jnp.where(m, active_sc, 0)
    ids_s, sc_s = jax.vmap(_rank_scored)(ids, scs)
    return ids_s, sc_s, an


@functools.partial(jax.jit, static_argnames=("nt_slots", "k_pad"))
def frozen_scored_topk(active_desc, active_sc, active_n, sc: ScoredStack,
                       n_terms, base, lasts_doc, smax, k, *,
                       nt_slots: int, k_pad: int):
    """Block-max WAND / MaxScore top-k over the frozen stack.

    Walks segments newest-first keeping a ``k_pad``-wide heap of the
    best (score desc, docid desc) candidates.  Three skip levels, each
    justified by an upper bound that cannot beat the heap threshold
    ``th`` (the current k-th best score once ``k`` candidates have been
    seen; -1 before that, which disables skipping):

      * segment-structural — empty term list or disjoint first/last
        docid ranges (the existing recency-top-k summaries);
      * segment-score — sum of the live terms' per-(term, segment) max
        impacts ``smax`` is <= th;
      * block-score — a driving-term block whose block-max plus the
        other terms' segment maxima is <= th contributes nothing.

    Dropped candidates score <= th <= the final k-th score, and on
    equality every heap incumbent is from a NEWER segment (larger
    docid), so they rank past k either way — bit-identical to ranking
    the full evaluation (tests/test_scored.py proves it for every k).
    Unlike recency top-k the walk cannot stop at ``b == k``: an older
    segment may still score higher, so early termination here IS the
    skipping, and the loop visits (but mostly skips) every segment.

    Returns ``(ids uint32[Q, k_pad], scores int32[Q, k_pad],
    n int32[Q], blocks_skipped int32[Q], blocks_live int32[Q])`` — the
    block counters feed the bench's skip-rate metric (driving-term
    blocks of structurally-live segments only).
    """
    lists = sc.ids
    Q, T, G, _ = lists.firsts.shape
    NB = lists.n_blocks
    W = NB * SEG_BLOCK
    an = jnp.where(n_terms > 0, active_n, 0)
    A = active_desc.shape[1]
    m = jnp.arange(A)[None, :] < an[:, None]
    a_ids = jnp.where(m, active_desc + base, INVALID)
    a_sc = jnp.where(m, active_sc, 0)
    if A < k_pad:
        pad = k_pad - A
        a_ids = jnp.concatenate(
            [a_ids, jnp.full((Q, pad), INVALID, a_ids.dtype)], axis=1)
        a_sc = jnp.concatenate(
            [a_sc, jnp.zeros((Q, pad), jnp.int32)], axis=1)
    hi0, hs0 = jax.vmap(_rank_scored)(a_ids, a_sc)
    heap_ids0, heap_sc0 = hi0[:, :k_pad], hs0[:, :k_pad]
    b0 = jnp.minimum(an, k)

    def one(hid_i, hsc_i, b_i, leaves_q, nt, ld_q, sm_q):
        fd_q = leaves_q.ids.firsts[..., 0]      # [T, G] first docids

        def body(i, c):
            hid, hsc, b, bskip, blive = c
            g = G - 1 - i                       # newest segment first
            seg = jax.tree.map(lambda x: x[:, g], leaves_q)
            ns_g = jnp.asarray(seg.ids.ns)
            slot = jnp.arange(nt_slots) < nt
            nonempty = jnp.all(jnp.where(slot, ns_g > 0, True)) & (nt > 0)
            lo = jnp.max(jnp.where(slot, fd_q[:, g], jnp.uint32(0)))
            hi = jnp.min(jnp.where(slot, ld_q[:, g],
                                   jnp.uint32(INVALID - jnp.uint32(1))))
            live_g = nonempty & (lo <= hi)
            ub_g = jnp.sum(jnp.where(slot, sm_q[:, g], 0))
            th = jnp.where(b >= k, hsc[jnp.maximum(k - 1, 0)],
                           jnp.int32(-1))
            eval_g = live_g & (ub_g > th)
            rest = jnp.sum(jnp.where(slot & (jnp.arange(nt_slots) > 0),
                                     sm_q[:, g], 0))
            nblk0 = (ns_g[0] + SEG_BLOCK - 1) // SEG_BLOCK
            blive = blive + jnp.where(live_g, nblk0, 0)
            bskip = bskip + jnp.where(live_g & ~eval_g, nblk0, 0)

            def eval_seg(_):
                ids = decode_stacked(seg.ids)       # [T, W]
                scs = decode_scores(seg.swords)     # [T, W]
                hit, score = _fold_scored(ids, scs, nt, nt_slots)
                blk_ok = (seg.bmax[0] + rest) > th  # [NB]
                keep = hit & jnp.repeat(blk_ok, SEG_BLOCK)
                real_blk = (jnp.arange(NB) * SEG_BLOCK) < ns_g[0]
                nskip = jnp.sum((~blk_ok & real_blk).astype(jnp.int32))
                cid = jnp.where(keep, ids[0], INVALID)
                csc = jnp.where(keep, score, 0)
                return cid, csc, jnp.sum(keep.astype(jnp.int32)), nskip

            cid, csc, nh, nskip = jax.lax.cond(
                eval_g, eval_seg,
                lambda _: (jnp.full((W,), INVALID, jnp.uint32),
                           jnp.zeros((W,), jnp.int32), jnp.int32(0),
                           jnp.int32(0)),
                None)
            bskip = bskip + nskip
            mi_s, ms_s = _rank_scored(jnp.concatenate([hid, cid]),
                                      jnp.concatenate([hsc, csc]))
            return (mi_s[:k_pad], ms_s[:k_pad],
                    jnp.minimum(k, b + nh), bskip, blive)

        hid, hsc, b, bskip, blive = jax.lax.fori_loop(
            0, G, body, (hid_i, hsc_i, b_i, jnp.int32(0), jnp.int32(0)))
        lane = jnp.arange(k_pad)
        return (jnp.where(lane < b, hid, INVALID),
                jnp.where(lane < b, hsc, 0), b, bskip, blive)

    return jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0, 0))(
        heap_ids0, heap_sc0, b0, sc, n_terms, lasts_doc, smax)


@functools.lru_cache(maxsize=slicepool.FACTORY_CACHE_SIZE)
def make_active_scored_fn(layout: PoolLayout, max_slices: int,
                          max_len: int, max_query_len: int = 8):
    """Batched scored-conjunctive evaluation over the ACTIVE pool: vmap
    of the engine's ``conjunctive_scored_asc``, flipped to descending
    with the score lanes kept doc-aligned.  Returns SEGMENT-RELATIVE
    ``(desc uint32[Q, W], scores int32[Q, W], n int32[Q])``."""
    eng = q.make_engine(layout, max_slices, max_len, max_query_len)

    @jax.jit
    def run(state, terms, n_terms):
        def one(trow, nt):
            asc, sc, n = eng.conjunctive_scored_asc(state, trow, nt)
            return (q.asc_to_desc(asc, n),
                    q.flip_valid(sc, n, jnp.int32(0)), n)
        return jax.vmap(one)(terms, n_terms)

    return run


@functools.lru_cache(maxsize=slicepool.FACTORY_CACHE_SIZE)
def make_active_topk_fn(layout: PoolLayout, max_slices: int, max_len: int,
                        k_pad: int = 8, tile: int = 128):
    """Early-exit top-k over the ACTIVE segment, driven by each row's
    shortest list.  Every live term slot's chain is walked and windowed
    (its newest ``max_len`` postings, as the materializer truncates);
    the slot with the smallest window drives (ties: the lowest slot).
    The driver's chain is consumed in newest-first tiles (the
    materializer's reverse-chronological order IS descending docid
    order); each tile's docids are probed for in the other slots' chains
    in place (:func:`slicepool.make_chain_prober`) and count only if
    found inside that slot's window; hits are banked, and the loop stops
    once ``k`` are banked.  Nothing ``max_len`` lanes wide is built.
    Bit-identical to ``QueryEngine.topk_conjunctive`` (the
    full-intersection oracle): hits surface in exactly the full
    evaluation's descending order.

    Returns a jitted ``f(state, terms[Q, T], n_terms[Q], k) ->
    (desc uint32[Q, k_pad], n int32[Q], tiles int32[Q])`` with
    SEGMENT-RELATIVE docids (``frozen_topk`` globalises); ``tiles`` is
    how many driver tiles each row scanned (0 for padding rows and for
    rows with an empty list).  ``k`` is dynamic up to ``k_pad``.
    """
    tile = min(tile, max_len)
    walk = slicepool.make_chain_walker(layout, max_slices)
    probe = slicepool.make_chain_prober(layout, max_slices)

    @jax.jit
    def run(state, terms, n_terms, k):
        heap = state.heap

        def one(trow, nt):
            bases, starts, lasts, nsl = jax.vmap(
                lambda t: walk(state, t))(trow)            # [T, S], [T]
            cum = jax.vmap(lambda s, l, n: slicepool.chain_lens_cum(
                s, l, n, max_slices))(starts, lasts, nsl)
            firsts = jax.vmap(lambda b, s, n: slicepool.chain_first_docids(
                heap, b, s, n, max_slices))(bases, starts, nsl)
            live = jnp.arange(trow.shape[0]) < nt
            win = jnp.minimum(cum[:, -1], max_len)
            drv = jnp.argmin(jnp.where(live, win, max_len + 1))
            total = jnp.where(nt > 0, win[drv], 0)
            out0 = jnp.full((k_pad,), INVALID, jnp.uint32)

            def cond(c):
                ti, b, _, _ = c
                return (b < k) & (ti * tile < total)

            def body(c):
                ti, b, prev, out = c
                # materialize ONE newest-first tile of the driver's chain
                # — the materializer's own address math
                # (slicepool.chain_window_addrs), restricted to lanes
                # [ti * tile, (ti + 1) * tile).
                j = ti * tile + jnp.arange(tile, dtype=jnp.int32)
                addr = slicepool.chain_window_addrs(
                    bases[drv], lasts[drv], cum[drv], j, max_slices)
                d = jnp.where(j < total, post.docid(heap[addr]),
                              jnp.uint32(INVALID))
                prev_lane = jnp.concatenate([prev[None], d[:-1]])
                keep = (d != INVALID) & (d != prev_lane)  # dedup positions
                lane, found = jax.vmap(
                    lambda *ch: probe(heap, *ch, d))(
                        bases, starts, lasts, cum, firsts, nsl)  # [T, tile]
                member = found & (lane < win[:, None])
                hit = keep & jnp.all(member | ~live[:, None], axis=0)
                comp, n_t = q._compact(d, hit)  # descending, hits first
                lanes = jnp.arange(tile)
                idx = jnp.where(lanes < n_t, b + lanes, k_pad)
                out = out.at[idx].set(comp, mode="drop")
                return (ti + 1, jnp.minimum(k, b + n_t), d[tile - 1], out)

            ti, b, _, out = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), jnp.int32(0), jnp.uint32(INVALID), out0))
            return out, b, ti

        return jax.vmap(one)(terms, n_terms)

    return run


# ---------------------------------------------------------------------------
# Batched active evaluation (single-device; the sharded engine is
# already batched — see sharded_index.make_sharded_engine)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=slicepool.FACTORY_CACHE_SIZE)
def make_active_fn(layout: PoolLayout, max_slices: int, max_len: int,
                   max_query_len: int, kind: str):
    """One jitted dispatch for a whole query batch over the active pool:
    vmap over queries of the single-device ``*_asc`` engines (the pure
    jnp engine — its masks are bit-identical to the kernel engine's, and
    jnp composes under vmap).  Returns SEGMENT-RELATIVE descending
    INVALID-padded lists + counts; padding rows are masked downstream.
    """
    eng = q.make_engine(layout, max_slices, max_len, max_query_len)

    if kind == "phrase":
        @jax.jit
        def run(state, t1s, t2s):
            def one(t1, t2):
                asc, n = eng.phrase_asc(state, t1, t2)
                return q.asc_to_desc(asc, n), n
            return jax.vmap(one)(t1s, t2s)
    else:
        fn = getattr(eng, f"{kind}_asc")

        @jax.jit
        def run(state, terms, n_terms):
            def one(trow, nt):
                asc, n = fn(state, trow, nt)
                return q.asc_to_desc(asc, n), n
            return jax.vmap(one)(terms, n_terms)

    return run


# ---------------------------------------------------------------------------
# Deferred host sync (the serving layer's dispatch/wait split)
# ---------------------------------------------------------------------------
class Pending:
    """A dispatched query batch whose device->host sync is DEFERRED.

    Everything up to the final ``np.asarray`` stays asynchronous under
    JAX's dispatch model: the engine's ``*_async`` methods build device
    arrays and return immediately; only :meth:`wait` blocks.  The
    serving loop (:mod:`repro.core.serve`) exploits the gap — dispatch a
    query batch, then dispatch the next ingest batch (whose bulk-append
    donates the active ``PoolState``; same-device dispatch order keeps
    the query's read before the overwrite), and only then sync the query
    results, so ingest compute overlaps the result transfer instead of
    serialising behind it.

    ``arrays`` are the in-flight device arrays; ``finish`` receives
    their host (numpy) values and builds the per-query python result —
    the same structure the synchronous engine method returns.  ``wait``
    is idempotent and drops the device arrays after the first call.
    ``rows`` x ``slots`` is the padded term matrix the engine evaluated
    the batch at (pow2 query rows, pow2 term-slot bucket; a phrase
    batch has 2 slots); both are 0 where no batched evaluation ran (an
    empty batch, or the per-query oracle of ``batched=False``).
    ``tiles`` (device int32[rows], or None) is the driver tiles the
    active early-exit top-k scanned per row, fetched by the same sync:
    after :meth:`wait`, ``topk_tiles`` is their sum and ``topk_rows``
    the ``live_rows`` they were scanned for (both 0 without ``tiles``).
    """

    __slots__ = ("_arrays", "_finish", "_done", "_result", "rows", "slots",
                 "_tiles", "topk_rows", "topk_tiles")

    def __init__(self, arrays, finish, rows: int = 0, slots: int = 0,
                 tiles=None, live_rows: int = 0):
        self._arrays = tuple(arrays)
        self._finish = finish
        self._done = False
        self._result = None
        self.rows = rows
        self.slots = slots
        self._tiles = tiles
        self.topk_rows = live_rows if tiles is not None else 0
        self.topk_tiles = 0

    @property
    def done(self) -> bool:
        return self._done

    def wait(self):
        if not self._done:
            with jax.profiler.TraceAnnotation("qexec.sync") as span:
                host = [np.asarray(a) for a in self._arrays]
                tiles = (None if self._tiles is None
                         else np.asarray(self._tiles))
                span.set_metadata(bytes=sum(h.nbytes for h in host))
            if tiles is not None:
                self.topk_tiles = int(tiles.sum())
            self._arrays, self._tiles = (), None
            finish, self._finish = self._finish, None
            with jax.profiler.TraceAnnotation("qexec.finish"):
                self._result = finish(*host)
            self._done = True
        return self._result


def pad_query_batch(queries: Sequence[Sequence[int]], max_query_len: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Pad a list of term tuples to a pow2-bucketed ``[Qb, T]`` matrix
    plus per-row term counts (0 for padding rows)."""
    Qb = bucket_pow2(len(queries))
    terms = np.zeros((Qb, max_query_len), np.uint32)
    n_terms = np.zeros(Qb, np.int32)
    for i, row in enumerate(queries):
        row = list(row)
        if not 0 < len(row) <= max_query_len:
            raise ValueError(
                f"query {i} has {len(row)} terms; need 1..{max_query_len}")
        terms[i, : len(row)] = row
        n_terms[i] = len(row)
    return terms, n_terms
