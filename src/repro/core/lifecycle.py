"""Streaming lifecycle engine (paper §3.1's full loop, closed).

A live tweet stream never stops: the active segment fills, rolls over
into a frozen read-only CSR segment, its slices return to the pool free
lists (:func:`repro.core.slicepool.release_slices`), and the next active
segment recycles them — so the heap high-water mark is bounded by ONE
segment's demand while queries still see every frozen segment.  This
module drives that loop continuously and gives it a UNIFIED query path:

  * **Active pool** — the jitted slice-pool engines
    (:mod:`repro.core.query` single-device,
    :mod:`repro.core.sharded_index` document-sharded).
  * **Frozen segments** — each frozen segment is wrapped in a
    :class:`PackedSegment`: per-term GLOBAL docid lists gap-compressed
    into 128-docid byte-width blocks
    (:mod:`repro.kernels.segment_intersect`).  Conjunctions decode the
    compressed blocks on the device and intersect them with the blocked
    two-pointer Pallas kernel — never walked host-side.
  * **Merge** — every segment owns a disjoint ascending docid range, so
    per-segment descending lists concatenated newest-segment-first ARE
    the global reverse-chronological result: bit-identical to a
    never-frozen index fed the same stream
    (tests/test_spmd_equivalence.py).

Queries route through :mod:`repro.core.qexec` by default
(``batched=True``): whole query batches evaluate in O(1) jitted
dispatches over the active pool plus a device-resident stack of ALL
frozen segments, with early-exit top-k (``topk_conjunctive`` /
``conjunctive(..., limit=k)``).  The per-query host loop below
(``batched=False``) is kept as the bit-exactness oracle
(tests/test_qexec.py).

The frozen side is bounded too: construct either engine with
``compaction=CompactionPolicy(fanout=r)`` (or call ``compact(k)``
directly) and same-tier frozen segments cascade-merge after every
rollover, keeping the frozen-segment count G = O(log N) — query
results are bit-identical, only the segment tiling changes
(tests/test_compaction.py, docs/lifecycle.md).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import postings as post
from repro.core import qexec
from repro.core import query as q
from repro.core import segments as seg_mod
from repro.core import sharded_index as shx
from repro.core import slicepool
from repro.core.pointers import PoolLayout
from repro.kernels.segment_intersect import (SCORE_MAX, PackedList,
                                             ScoredList, attach_scores,
                                             decode_packed, pack_docids)


# ---------------------------------------------------------------------------
# Frozen segments, device-queryable
# ---------------------------------------------------------------------------
class PackedSegment:
    """Query-side view of one frozen segment (single-device or sharded).

    Wraps a :class:`~repro.core.segments.FrozenSegment` or
    :class:`~repro.core.sharded_index.ShardedFrozenSegment` and exposes,
    per term, the GLOBAL ascending docid list as a block-gap-compressed
    :class:`PackedList` ready for the ``segment_intersect`` kernel.
    Packing is LAZY: the first query touching a (segment, term) pair
    pays a one-time host-side pack, cached for the segment's lifetime.
    Call :meth:`warm` at rollover (e.g. with the query log's hot terms)
    to move that cost off the query path entirely — eagerly packing the
    whole vocabulary would stall ingest instead.
    """

    def __init__(self, seg):
        self.seg = seg
        self.doc_base = int(seg.doc_base)
        self._packed: Dict[int, PackedList] = {}
        self._post: Dict[int, np.ndarray] = {}
        self._tf: Dict[int, tuple] = {}
        self._scored: Dict[int, ScoredList] = {}

    def docids_asc(self, term: int) -> np.ndarray:
        """Ascending GLOBAL docids of ``term`` in this segment."""
        rel = self.seg.docids_desc(int(term))[::-1]
        return rel.astype(np.int64) + self.doc_base

    def packed(self, term: int) -> PackedList:
        term = int(term)
        got = self._packed.get(term)
        if got is None:
            ids = self.docids_asc(term)
            # global docids are uint32 repo-wide (0xFFFFFFFF is the
            # INVALID sentinel); fail loudly instead of wrapping once
            # doc_base outgrows that — resharding territory, not a
            # silent-corruption one.
            if ids.size and ids[-1] >= 0xFFFFFFFF:
                raise OverflowError(
                    f"global docid {int(ids[-1])} exceeds the uint32 "
                    f"docid space; reshard or reset doc_base")
            got = pack_docids(ids.astype(np.uint32))
            self._packed[term] = got
        return got

    def postings_asc(self, term: int) -> np.ndarray:
        """Ascending packed (segment-relative docid, position) postings —
        the positional substrate for phrase queries."""
        term = int(term)
        got = self._post.get(term)
        if got is None:
            if isinstance(self.seg, seg_mod.FrozenSegment):
                got = self.seg.postings(term)   # already (docid, pos) asc
            else:  # sharded: shards are disjoint residue classes
                got = np.sort(np.concatenate(
                    [sh.postings(term) for sh in self.seg.shards]))
            self._post[term] = got
        return got

    def tf_asc(self, term: int) -> tuple:
        """``(docids int64 asc GLOBAL, tf int64)`` — the per-doc term
        frequency of ``term`` in this segment, from the positional
        postings (one posting per occurrence).  Cached like
        :meth:`packed`; compaction rebuilds the CSR and thus recomputes
        tf on the merged segment, so score planes survive merges."""
        term = int(term)
        got = self._tf.get(term)
        if got is None:
            p = self.postings_asc(term)
            rel = (p >> np.uint32(post.POS_BITS)).astype(np.int64)
            ids, tf = np.unique(rel, return_counts=True)
            got = (ids + self.doc_base, tf.astype(np.int64))
            self._tf[term] = got
        return got

    def scored(self, term: int) -> ScoredList:
        """The term's :meth:`packed` list with the quantized-impact
        plane attached: one ``min(tf, SCORE_MAX)`` uint8 per docid lane,
        plus the per-128-docid-block max and the list max — the
        block-max WAND substrate for :func:`qexec.frozen_scored_topk`."""
        term = int(term)
        got = self._scored.get(term)
        if got is None:
            _, tf = self.tf_asc(term)
            imp = np.minimum(tf, SCORE_MAX).astype(np.int32)
            got = attach_scores(self.packed(term), imp)
            self._scored[term] = got
        return got

    def bounds(self, term: int) -> tuple:
        """O(1) (or O(S) sharded) ``(n_postings, first_gid, last_gid)``
        GLOBAL docid summary, WITHOUT forcing a pack — the frozen
        stack's whole-segment-skip substrate (zero postings or disjoint
        term ranges can never intersect)."""
        c, f, last = self.seg.docid_bounds(int(term))
        if not c:
            return 0, 0, 0
        return c, f + self.doc_base, last + self.doc_base

    def warm(self, terms: Sequence[int]) -> None:
        for t in terms:
            self.packed(t)


def conjunctive_packed(pseg: PackedSegment, terms: Sequence[int], *,
                       use_kernel: bool = True,
                       interpret: Optional[bool] = None) -> np.ndarray:
    """Descending GLOBAL docids holding every term, within one frozen
    segment.  The driving intersection decodes the two smallest
    compressed lists and runs the blocked intersect kernel on them;
    further terms fold in with the vectorised membership test on the
    already-compacted list."""
    packs = sorted((pseg.packed(t) for t in terms), key=lambda p: p.n)
    if not packs or packs[0].n == 0:
        return np.zeros(0, np.int64)
    a = packs[0]
    cur = decode_packed(a)                    # ascending, INVALID-padded
    n = jnp.int32(a.n)
    for i, b in enumerate(packs[1:]):
        if b.n == 0:
            return np.zeros(0, np.int64)
        if i == 0 and use_kernel:
            from repro.kernels import ops
            mask = ops.segment_intersect_mask(a, b, interpret=interpret)
            cur, n = q._compact(cur, mask.astype(bool))
        else:
            hit = q.member_asc(cur, decode_packed(b))
            cur, n = q._compact(cur, hit)
    return np.asarray(cur)[: int(n)][::-1].astype(np.int64)


def disjunctive_packed(pseg: PackedSegment,
                       terms: Sequence[int]) -> np.ndarray:
    """Descending GLOBAL docids holding any term, one frozen segment."""
    lists = [pseg.docids_asc(t) for t in terms]
    out = lists[0]
    for more in lists[1:]:
        out = np.union1d(out, more)
    return out[::-1]


def phrase_packed(pseg: PackedSegment, t1: int, t2: int) -> np.ndarray:
    """Descending GLOBAL docids where ``t2`` occurs at position(t1)+1,
    within one frozen segment (packed postings order by (docid, pos), so
    the +1 membership trick from the live engine carries over)."""
    p1 = pseg.postings_asc(t1)
    p2 = pseg.postings_asc(t2)
    if p1.size == 0 or p2.size == 0:
        return np.zeros(0, np.int64)
    want = p1 + np.uint32(1)
    pos = np.minimum(np.searchsorted(p2, want), p2.size - 1)
    hit = p2[pos] == want
    ids = np.unique(p1[hit] >> np.uint32(post.POS_BITS)).astype(np.int64)
    return ids[::-1] + pseg.doc_base


def scored_packed(pseg: PackedSegment, terms: Sequence[int]) -> tuple:
    """Descending ``(docids int64, scores int64)`` of the conjunctive
    scored query within one frozen segment — the pure-numpy oracle the
    block-max path is proven bit-identical to.  Score is the summed
    quantized impact ``min(tf, SCORE_MAX)`` over the query terms."""
    its = [pseg.tf_asc(t) for t in terms]
    ids = its[0][0]
    for more, _ in its[1:]:
        ids = np.intersect1d(ids, more)
    if ids.size == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    sc = np.zeros(ids.size, np.int64)
    for uids, tf in its:
        pos = np.searchsorted(uids, ids)
        sc += np.minimum(tf[pos], SCORE_MAX)
    return ids[::-1].copy(), sc[::-1].copy()


# ---------------------------------------------------------------------------
# Unified engines: active pool + every frozen segment
# ---------------------------------------------------------------------------
# largest conjunctive `limit` routed through the early-exit top-k path;
# beyond it a limit is a generous cap, and full evaluation + slice is
# cheaper than a pow2(limit)-wide banking buffer (results identical).
_TOPK_LIMIT_MAX = 4096


@dataclasses.dataclass
class LifecycleStats:
    docs_ingested: int = 0
    rollovers: int = 0
    compactions: int = 0
    high_water_slots: int = 0
    live_slots: int = 0
    # block-max scored retrieval: frozen 128-docid blocks whose score
    # upper bound could not beat the running top-k threshold (skipped
    # without decoding) vs. blocks in structurally-live segments at all.
    scored_blocks_skipped: int = 0
    scored_blocks_live: int = 0
    # graceful degradation (AdmissionController): rollovers forced by
    # utilization pressure rather than the docs_per_segment boundary,
    # batches that waited for one, and batches refused outright.
    emergency_rollovers: int = 0
    deferred_batches: int = 0
    shed_batches: int = 0


@dataclasses.dataclass(frozen=True)
class AdmissionController:
    """Graceful degradation under memory pressure.

    The slice pool's ``overflow`` flag is STICKY and silent at ingest
    time: once any pool runs out of slices, further postings there are
    dropped and only :meth:`check_health` notices afterwards — by then
    the index is already missing documents.  An engine built with
    ``admission=AdmissionController(...)`` instead watches the
    worst-pool live utilization (:func:`slicepool.pool_utilization`)
    BEFORE each batch:

      * ``utilization >= rollover_at`` — emergency rollover: freeze the
        active segment early (off the ``docs_per_segment`` boundary) so
        its slices return to the free lists before any pool can
        overflow.  ``compact_k`` additionally triggers
        ``segments.compact(compact_k)`` to bound the frozen-segment
        count the early rollovers would otherwise inflate.
      * ``utilization >= shed_at`` still, after any rollover — shed the
        batch: ``ingest`` returns False without indexing, and
        ``stats.shed_batches`` counts the refusal.  A shed batch is a
        LOUD, counted degradation; a truncated posting list is a silent
        one.

    ``min_segment_docs`` withholds the emergency rollover while the
    active segment holds fewer documents: every emergency rollover burns
    a frozen-segment slot (``max_segments`` retires the oldest segment
    once the set fills), so freezing a near-empty segment trades durable
    data for a handful of reclaimed slices.  With the rollover withheld
    and utilization still at/over ``shed_at`` the batch is shed instead
    — the producer backs off, and a later rollover (scheduled, or
    emergency once the segment has grown) frees the slices that let a
    retried batch through (tests/test_serve.py exercises exactly that
    shed-then-retry sequence).

    Both checks are pure functions of engine state, so a journal replay
    (:mod:`repro.core.recovery`) reproduces every admission decision
    bit-for-bit.
    """
    rollover_at: float = 0.85
    shed_at: float = 1.0
    compact_k: Optional[int] = None
    min_segment_docs: int = 0

    def __post_init__(self):
        if not (0.0 <= self.rollover_at <= self.shed_at):
            raise ValueError(
                f"need 0 <= rollover_at <= shed_at, got "
                f"rollover_at={self.rollover_at} shed_at={self.shed_at}")
        if self.min_segment_docs < 0:
            raise ValueError(
                f"need min_segment_docs >= 0, got {self.min_segment_docs}")


class _LifecycleBase:
    """Shared shell: frozen-segment tracking, stats, unified queries.

    Subclasses provide ``self.segments`` (a SegmentSet-like with
    ``ingest``/``frozen``/``active``/``_doc_base``) and
    :meth:`_active_desc` (GLOBAL descending docids from the active
    segment for one query).
    """

    layout: PoolLayout
    max_query_len: int
    use_kernel: bool
    interpret: Optional[bool]
    batched: bool
    validate: bool
    stable_shapes: bool

    def _init_shell(self, batched_kernel: Optional[bool],
                    admission: Optional[AdmissionController]) -> None:
        self._packed: List[PackedSegment] = []
        self._qstack: Optional[qexec.FrozenStack] = None
        # shape-ratchet floors for the frozen-stack gathers (see
        # qexec.FrozenStack): owned here so the ratchet survives stack
        # rebuilds at rollover/compaction.  Results are bit-identical
        # either way — padding is masked — but with the ratchet on, the
        # gather shapes (jit keys) stop varying with per-batch posting
        # lengths, which is what a latency-bounded serving loop needs.
        self._shape_floors = (
            {} if getattr(self, "stable_shapes", False) else None)
        # like ops.bulk_append: the batched grid kernel runs on a real
        # TPU backend; the CPU execution path is the jnp oracle (the
        # interpreter's per-element DMA simulation is not a hot path).
        # The raw arg is kept so snapshots round-trip the CONFIG (None
        # = resolve against the restoring backend), not the resolution.
        self.batched_kernel = batched_kernel
        self._batched_kernel = (
            self.use_kernel and jax.default_backend() == "tpu"
            if batched_kernel is None else bool(batched_kernel))
        self.admission = admission
        self.stats = LifecycleStats()

    # -- ingest ----------------------------------------------------------
    def ingest(self, docs) -> bool:
        """Index one arrival batch; segments roll over (freeze + reclaim
        + re-pack) automatically when they fill.  Returns True when the
        batch was indexed, False when the
        :class:`AdmissionController` shed it (no ``admission`` →
        always True)."""
        if self.admission is not None and not self._admit():
            self.stats.shed_batches += 1
            return False
        self.segments.ingest(jnp.asarray(docs))
        prev = self.stats.rollovers
        self._sync_frozen()
        self.stats.docs_ingested += int(np.asarray(docs).shape[0])
        # refresh memory stats only when a rollover happened: reading
        # the watermark is a host sync that would otherwise stall the
        # async scan dispatch on every batch of the ingest hot path.
        if self.stats.rollovers != prev:
            self._refresh_memory_stats()
            if self.validate:
                self.validate_invariants()
        return True

    def _admit(self) -> bool:
        """Admission check for the next batch: emergency-roll the active
        segment when utilization crosses ``rollover_at`` (reclaiming its
        slices before any pool can overflow), then admit unless the
        worst pool is STILL at/over ``shed_at``."""
        adm = self.admission
        util = slicepool.pool_utilization(self.layout,
                                          self.segments.active.state)
        if (util >= adm.rollover_at
                and self.segments.active.next_docid
                >= max(1, adm.min_segment_docs)):
            self.segments.rollover()
            if adm.compact_k is not None:
                self.segments.compact(adm.compact_k)
            self._sync_frozen()
            self.stats.emergency_rollovers += 1
            self.stats.deferred_batches += 1
            self._refresh_memory_stats()
            if self.validate:
                self.validate_invariants()
            util = slicepool.pool_utilization(self.layout,
                                              self.segments.active.state)
        return util < adm.shed_at

    def _refresh_memory_stats(self) -> None:
        st = self.segments.active.state
        self.stats.high_water_slots = slicepool.memory_high_water_slots(
            self.layout, st)
        self.stats.live_slots = slicepool.memory_slots_used(
            self.layout, st)

    def validate_invariants(self) -> None:
        """Run the repro.analysis.invariants structural validators over
        the allocator state and every frozen segment
        (:func:`~repro.analysis.invariants.check_engine`); raise
        :class:`~repro.analysis.invariants.InvariantViolation` on the
        first broken invariant.  Called automatically at every rollover
        (scheduled or emergency), at engine-driven compaction, and after
        ``recovery.restore`` when the engine was built with
        ``validate=True`` (debug flag — each call is an O(live postings)
        host walk, keep it off the production ingest path)."""
        from repro.analysis import invariants
        invariants.check_engine(self).raise_if_failed()

    def compact(self, k: int):
        """Merge the ``k`` oldest frozen segments
        (:meth:`~repro.core.segments.SegmentSet.compact`) and resync the
        query-side packed views — the qexec ``FrozenStack`` cache is
        invalidated exactly like a rollover.  Returns the merged frozen
        segment, or None when fewer than two segments exist (no-op)."""
        merged = self.segments.compact(k)
        self._sync_frozen()
        if merged is not None and self.validate:
            self.validate_invariants()
        return merged

    def _sync_frozen(self) -> None:
        """Mirror ``segments.frozen`` into packed query-side views.
        Any change to the list — a rollover appending, a compaction
        replacing members, retirement popping — drops the cached
        ``FrozenStack`` so the next batch rebuilds it.  Called after
        every ingest AND at the top of every query entry point, so
        compactions driven directly on the SegmentSet are picked up
        before the stale stack could serve a query."""
        by_id = {id(p.seg): p for p in self._packed}
        fresh = [by_id.get(id(fz)) or PackedSegment(fz)
                 for fz in self.segments.frozen]
        if [id(p) for p in fresh] != [id(p) for p in self._packed]:
            self._qstack = None  # segment set changed: rebuild the stack
        self._packed = fresh
        self.stats.rollovers = self.segments.n_rollovers
        self.stats.compactions = self.segments.n_compactions

    def _frozen_stack(self) -> Optional[qexec.FrozenStack]:
        if self._qstack is None and self._packed:
            self._qstack = qexec.FrozenStack(self._packed,
                                             floors=self._shape_floors)
        return self._qstack

    def check_health(self) -> None:
        self.segments.active.check_health()

    @property
    def doc_base(self) -> int:
        return self.segments._doc_base

    @property
    def frozen_packed(self) -> List[PackedSegment]:
        return list(self._packed)

    def memory_slots_used(self) -> int:
        return slicepool.memory_slots_used(self.layout,
                                           self.segments.active.state)

    def memory_high_water_slots(self) -> int:
        return slicepool.memory_high_water_slots(
            self.layout, self.segments.active.state)

    # -- queries: batched qexec path (default) ---------------------------
    def _base_u32(self) -> jnp.ndarray:
        base = self.doc_base
        if base + self.segments.active.next_docid >= 0xFFFFFFFF:
            raise OverflowError(
                f"doc_base {base} exceeds the uint32 docid space; "
                f"reshard or reset doc_base")
        return jnp.uint32(base)

    def _stub_active(self, rows: int):
        """An empty active part for ``frozen_only`` evaluation: one
        INVALID lane per (padded) query row, zero counts.  The merge
        paths accept any active width, so the 1-wide stub skips the
        active dispatch entirely — including, on the sharded engine, its
        shard_map all_gather — which is the whole point of the
        frozen-only degradation rung."""
        return (jnp.full((rows, 1), qexec.INVALID, jnp.uint32),
                jnp.zeros(rows, jnp.int32))

    def _batch_eval(self, kind: str, queries: Sequence,
                    limit: Optional[int],
                    frozen_only: bool = False) -> List[np.ndarray]:
        """Evaluate a whole query batch in O(1) dispatches: one batched
        active call, one frozen-stack call — NO per-segment host round
        trips (the per-query oracle does one ``np.asarray`` per segment
        per query)."""
        return self._batch_eval_async(kind, queries, limit,
                                      frozen_only=frozen_only).wait()

    def _batch_eval_async(self, kind: str, queries: Sequence,
                          limit: Optional[int], *,
                          frozen_only: bool = False) -> qexec.Pending:
        """Dispatch a whole query batch and return a
        :class:`qexec.Pending`: the ONE host sync for the batch is
        deferred to ``wait()``, so a caller can slip further dispatches
        (the serving loop's ingest batch) into the gap."""
        Q = len(queries)
        if Q == 0:
            return qexec.Pending((), lambda: [])
        self._sync_frozen()   # pick up out-of-band compactions/rollovers
        if (kind == "conjunctive" and limit is not None
                and limit <= _TOPK_LIMIT_MAX):
            # a conjunctive limit IS a top-k: take the early-exit path.
            # Huge limits (a generous cap, not a real top-k) fall through
            # to full evaluation + slice — identical results without
            # compiling a pow2(limit)-wide banking buffer.
            return self._batch_topk_async(queries, limit,
                                          frozen_only=frozen_only)
        base = self._base_u32()
        stack = self._frozen_stack()
        if kind == "phrase":
            Qb = qexec.bucket_pow2(Q)
            t1 = np.zeros(Qb, np.uint32)
            t2 = np.zeros(Qb, np.uint32)
            t1[:Q] = [p[0] for p in queries]
            t2[:Q] = [p[1] for p in queries]
            live = jnp.asarray((np.arange(Qb) < Q).astype(np.int32))
            rows, slots = Qb, 2
            ad, an = (self._stub_active(Qb) if frozen_only
                      else self._active_batch(kind, t1, t2))
            if stack is None:
                desc, n = qexec.finalize(ad, an, live, base)
            else:
                p1, p2 = stack.gather_postings(t1, t2, n_live=Q)
                desc, n = qexec.frozen_phrase_merge(
                    ad, an, p1, p2, jnp.asarray(stack.doc_bases), live,
                    base)
        else:
            terms, n_terms = qexec.pad_query_batch(queries,
                                                   self.max_query_len)
            # trim the term axis to the batch's pow2 bucket: a 2-term
            # batch must not pay for max_query_len slots of decode/fold
            tb = min(qexec.bucket_pow2(int(n_terms.max()), 1),
                     self.max_query_len)
            rows, slots = terms.shape[0], tb
            ad, an = (self._stub_active(terms.shape[0]) if frozen_only
                      else self._active_batch(kind, terms, n_terms, tb))
            if stack is None:
                desc, n = qexec.finalize(ad, an, jnp.asarray(n_terms),
                                         base)
            else:
                lists, _ = stack.gather(terms[:, :tb], n_terms)
                desc, n = qexec.frozen_merge(
                    ad, an, lists, jnp.asarray(n_terms), base, kind=kind,
                    nt_slots=tb,
                    kernel=self._batched_kernel, interpret=self.interpret)

        def finish(D, N):  # ONE sync for the batch (inside wait())
            out = [D[i, : int(N[i])].astype(np.int64) for i in range(Q)]
            return out if limit is None else [o[:limit] for o in out]

        return qexec.Pending((desc, n), finish, rows, slots)

    def _batch_topk(self, queries: Sequence, k: int,
                    frozen_only: bool = False) -> List[np.ndarray]:
        return self._batch_topk_async(queries, k,
                                      frozen_only=frozen_only).wait()

    def _batch_topk_async(self, queries: Sequence, k: int, *,
                          frozen_only: bool = False) -> qexec.Pending:
        Q = len(queries)
        if Q == 0:
            return qexec.Pending((), lambda: [])
        self._sync_frozen()   # pick up out-of-band compactions/rollovers
        k = int(k)
        if k <= 0:
            empty = [np.zeros(0, np.int64) for _ in range(Q)]
            return qexec.Pending((), lambda: empty)
        terms, n_terms = qexec.pad_query_batch(queries, self.max_query_len)
        tb = min(qexec.bucket_pow2(int(n_terms.max()), 1),
                 self.max_query_len)
        base = self._base_u32()
        k_pad = qexec.bucket_pow2(k, floor=8)
        if frozen_only:
            (ad, an), tiles = self._stub_active(terms.shape[0]), None
        else:
            ad, an, tiles = self._active_topk_batch(terms, n_terms, k,
                                                    k_pad, tb)
        stack = self._frozen_stack()
        if stack is None:
            desc, n = qexec.finalize(ad, an, jnp.asarray(n_terms), base)
        else:
            lists, lasts = stack.gather(terms[:, :tb], n_terms)
            desc, n = qexec.frozen_topk(
                ad, an, lists, jnp.asarray(n_terms), base, lasts,
                jnp.int32(k), nt_slots=tb, k_pad=k_pad)

        def finish(D, N):
            return [D[i, : min(int(N[i]), k)].astype(np.int64)
                    for i in range(Q)]

        return qexec.Pending((desc, n), finish, terms.shape[0], tb,
                             tiles=tiles, live_rows=Q)

    def conjunctive_batch(self, queries: Sequence[Sequence[int]],
                          limit: Optional[int] = None,
                          frozen_only: bool = False) -> List[np.ndarray]:
        """Batched :meth:`conjunctive`: one list of GLOBAL descending
        docids per query, all queries in O(1) jitted dispatches."""
        if not self.batched:
            return [self._unified("conjunctive", t, limit, frozen_only)
                    for t in queries]
        return self._batch_eval("conjunctive", queries, limit, frozen_only)

    def disjunctive_batch(self, queries: Sequence[Sequence[int]],
                          limit: Optional[int] = None,
                          frozen_only: bool = False) -> List[np.ndarray]:
        if not self.batched:
            return [self._unified("disjunctive", t, limit, frozen_only)
                    for t in queries]
        return self._batch_eval("disjunctive", queries, limit, frozen_only)

    def phrase_batch(self, pairs: Sequence[Sequence[int]],
                     limit: Optional[int] = None,
                     frozen_only: bool = False) -> List[np.ndarray]:
        if not self.batched:
            return [self._unified("phrase", p, limit, frozen_only)
                    for p in pairs]
        return self._batch_eval("phrase", pairs, limit, frozen_only)

    def topk_conjunctive(self, terms: Sequence[int], k: int,
                         frozen_only: bool = False) -> np.ndarray:
        """The newest ``k`` docs holding every term — early-exit
        evaluation (stops consuming older segments / older slice-chain
        tiles once k hits are banked), bit-identical to
        ``conjunctive(terms)[:k]``."""
        return self.topk_conjunctive_batch([terms], k, frozen_only)[0]

    def topk_conjunctive_batch(self, queries: Sequence[Sequence[int]],
                               k: int,
                               frozen_only: bool = False
                               ) -> List[np.ndarray]:
        if not self.batched:
            return [self._unified("conjunctive", t, int(k), frozen_only)
                    for t in queries]
        return self._batch_topk(queries, k, frozen_only)

    def dispatch(self, kind: str, queries: Sequence, *,
                 k: Optional[int] = None, limit: Optional[int] = None,
                 frozen_only: bool = False) -> qexec.Pending:
        """Dispatch a query batch WITHOUT waiting for its results.

        The async entry point the serving loop
        (:mod:`repro.core.serve`) builds on: device work is enqueued and
        a :class:`qexec.Pending` returned immediately; ``wait()``
        performs the batch's single host sync and yields exactly what
        the corresponding synchronous method returns.  ``kind`` is one
        of ``conjunctive`` / ``disjunctive`` / ``phrase`` (optionally
        ``limit``-capped), ``topk`` (:meth:`topk_conjunctive_batch`,
        needs ``k``), ``scored`` (:meth:`scored_topk_batch`, needs
        ``k``) or ``scored_full`` (:meth:`scored_full_batch`).
        ``frozen_only=True`` evaluates over the frozen segments only
        (docids below :attr:`doc_base`), skipping the active dispatch —
        the serving ladder's cheapest rung.  With ``batched=False`` the
        oracle path runs eagerly and the Pending is already resolved.
        """
        if kind in ("topk", "scored") and k is None:
            raise ValueError(f"kind {kind!r} needs k")
        if not self.batched:
            if kind == "topk":
                res = [self._unified("conjunctive", t, int(k), frozen_only)
                       for t in queries]
            elif kind == "scored":
                res = [self._scored_unified(t, int(k), frozen_only)
                       for t in queries]
            elif kind == "scored_full":
                res = [self._scored_unified(t, k, frozen_only)
                       for t in queries]
            elif kind in ("conjunctive", "disjunctive", "phrase"):
                res = [self._unified(kind, t, limit, frozen_only)
                       for t in queries]
            else:
                raise ValueError(f"unknown query kind {kind!r}")
            return qexec.Pending((), lambda: res)
        if kind == "topk":
            return self._batch_topk_async(queries, int(k),
                                          frozen_only=frozen_only)
        if kind == "scored":
            return self._scored_batch_async(queries, int(k), full=False,
                                            frozen_only=frozen_only)
        if kind == "scored_full":
            return self._scored_batch_async(queries, k, full=True,
                                            frozen_only=frozen_only)
        if kind in ("conjunctive", "disjunctive", "phrase"):
            return self._batch_eval_async(kind, queries, limit,
                                          frozen_only=frozen_only)
        raise ValueError(f"unknown query kind {kind!r}")

    # -- queries: scored retrieval (block-max WAND / MaxScore) -----------
    def scored_topk(self, terms: Sequence[int], k: int) -> tuple:
        """The ``k`` best-scoring docs holding every term, ranked by
        (summed quantized impact desc, docid desc — ties newest first),
        as ``(docids int64[m], scores int64[m])``.  Frozen segments run
        the block-max WAND walk: whole 128-docid blocks and whole
        segments whose score upper bound cannot enter the current top-k
        heap are skipped without decoding, and skip counts accumulate in
        ``stats.scored_blocks_skipped`` / ``scored_blocks_live``.
        Bit-identical to ``scored_full(terms)[:k]``."""
        return self.scored_topk_batch([terms], k)[0]

    def scored_topk_batch(self, queries: Sequence[Sequence[int]],
                          k: int, frozen_only: bool = False
                          ) -> List[tuple]:
        if not self.batched:
            return [self._scored_unified(t, int(k), frozen_only)
                    for t in queries]
        return self._scored_batch(queries, int(k), full=False,
                                  frozen_only=frozen_only)

    def scored_full(self, terms: Sequence[int],
                    k: Optional[int] = None) -> tuple:
        """Exhaustive scored evaluation (no early termination) — the
        batched full-sort baseline ``scored_topk`` is measured against."""
        return self.scored_full_batch([terms], k)[0]

    def scored_full_batch(self, queries: Sequence[Sequence[int]],
                          k: Optional[int] = None,
                          frozen_only: bool = False) -> List[tuple]:
        if not self.batched:
            return [self._scored_unified(t, k, frozen_only)
                    for t in queries]
        return self._scored_batch(queries, k, full=True,
                                  frozen_only=frozen_only)

    def _scored_batch(self, queries: Sequence, k: Optional[int],
                      full: bool,
                      frozen_only: bool = False) -> List[tuple]:
        return self._scored_batch_async(queries, k, full=full,
                                        frozen_only=frozen_only).wait()

    def _scored_batch_async(self, queries: Sequence, k: Optional[int], *,
                            full: bool,
                            frozen_only: bool = False) -> qexec.Pending:
        Q = len(queries)
        if Q == 0:
            return qexec.Pending((), lambda: [])
        self._sync_frozen()   # pick up out-of-band compactions/rollovers
        if not full:
            if k <= 0:
                empty = [(np.zeros(0, np.int64), np.zeros(0, np.int64))
                         for _ in range(Q)]
                return qexec.Pending((), lambda: empty)
            if k > _TOPK_LIMIT_MAX:
                # a generous cap, not a real top-k: full evaluation +
                # slice beats compiling a pow2(k)-wide heap.
                inner = self._scored_batch_async(
                    queries, None, full=True, frozen_only=frozen_only)
                return qexec.Pending(
                    (), lambda: [(i[:k], s[:k]) for i, s in inner.wait()],
                    inner.rows, inner.slots)
        terms, n_terms = qexec.pad_query_batch(queries, self.max_query_len)
        tb = min(qexec.bucket_pow2(int(n_terms.max()), 1),
                 self.max_query_len)
        base = self._base_u32()
        if frozen_only:
            ad, an = self._stub_active(terms.shape[0])
            asc = jnp.zeros((terms.shape[0], 1), jnp.int32)
        else:
            ad, asc, an = self._active_scored_batch(terms, n_terms, tb)
        stack = self._frozen_stack()
        if full:
            if stack is None:
                ids, scs, n = qexec.finalize_scored(
                    ad, asc, an, jnp.asarray(n_terms), base)
            else:
                sc, _, _ = stack.gather_scored(terms[:, :tb], n_terms)
                ids, scs, n = qexec.frozen_scored_merge(
                    ad, asc, an, sc, jnp.asarray(n_terms), base,
                    nt_slots=tb, kernel=self._batched_kernel,
                    interpret=self.interpret)
                ids, scs, n = qexec.rank_scored(ids, scs, n)
            lim = None if k is None else int(k)

            def finish_full(D, S, N):
                return [(D[i, : int(N[i])].astype(np.int64)[:lim],
                         S[i, : int(N[i])].astype(np.int64)[:lim])
                        for i in range(Q)]

            return qexec.Pending((ids, scs, n), finish_full,
                                 terms.shape[0], tb)
        k_pad = qexec.bucket_pow2(k, floor=8)
        if stack is None:
            ids, scs, n = qexec.finalize_scored(
                ad, asc, an, jnp.asarray(n_terms), base)

            def finish_nostack(D, S, N):
                return [(D[i, : min(int(N[i]), k)].astype(np.int64),
                         S[i, : min(int(N[i]), k)].astype(np.int64))
                        for i in range(Q)]

            return qexec.Pending((ids, scs, n), finish_nostack,
                                 terms.shape[0], tb)
        sc, lasts, smax = stack.gather_scored(terms[:, :tb], n_terms)
        ids, scs, n, bskip, blive = qexec.frozen_scored_topk(
            ad, asc, an, sc, jnp.asarray(n_terms), base, lasts, smax,
            jnp.int32(k), nt_slots=tb, k_pad=k_pad)

        def finish(D, S, N, BS, BL):
            # skip-counter bookkeeping rides the deferred sync so the
            # dispatch path stays host-sync-free until wait()
            self.stats.scored_blocks_skipped += int(BS.sum())
            self.stats.scored_blocks_live += int(BL.sum())
            return [(D[i, : min(int(N[i]), k)].astype(np.int64),
                     S[i, : min(int(N[i]), k)].astype(np.int64))
                    for i in range(Q)]

        return qexec.Pending((ids, scs, n, bskip, blive), finish,
                             terms.shape[0], tb)

    def _scored_unified(self, terms: Sequence[int],
                        k: Optional[int],
                        frozen_only: bool = False) -> tuple:
        """Per-query host-loop scored oracle (``batched=False``): active
        scores from the jitted engine, one numpy ``scored_packed`` per
        frozen segment, one stable full sort.  No early termination —
        the exactness reference for ``scored_topk``."""
        self._sync_frozen()
        if frozen_only:
            ids = [np.zeros(0, np.int64)]
            scs = [np.zeros(0, np.int64)]
        else:
            tmat, n_terms = qexec.pad_query_batch([tuple(terms)],
                                                  self.max_query_len)
            tb = min(qexec.bucket_pow2(int(n_terms.max()), 1),
                     self.max_query_len)
            ad, asc, an = self._active_scored_batch(tmat, n_terms, tb)
            n0 = int(an[0])
            ids = [np.asarray(ad[0])[:n0].astype(np.int64)
                   + self.doc_base]
            scs = [np.asarray(asc[0])[:n0].astype(np.int64)]
        for pseg in reversed(self._packed):   # newest frozen first
            i, s = scored_packed(pseg, terms)
            ids.append(i)
            scs.append(s)
        flat_i = np.concatenate(ids)
        flat_s = np.concatenate(scs)
        order = np.lexsort((-flat_i, -flat_s))  # score desc, docid desc
        flat_i, flat_s = flat_i[order], flat_s[order]
        if k is not None:
            flat_i, flat_s = flat_i[:k], flat_s[:k]
        return flat_i, flat_s

    # -- queries: per-query host-loop oracle (batched=False) -------------
    def _unified(self, kind: str, terms: Sequence[int],
                 limit: Optional[int],
                 frozen_only: bool = False) -> np.ndarray:
        self._sync_frozen()   # pick up out-of-band compactions/rollovers
        parts = [np.zeros(0, np.int64) if frozen_only
                 else self._active_desc(kind, terms)]
        total = len(parts[0])
        for pseg in reversed(self._packed):   # newest frozen first
            # segments own disjoint descending docid ranges, so once the
            # newer segments fill the limit, older ones can't contribute
            # — the paper's early-exit, at segment granularity.
            if limit is not None and total >= limit:
                break
            if kind == "conjunctive":
                parts.append(conjunctive_packed(
                    pseg, terms, use_kernel=self.use_kernel,
                    interpret=self.interpret))
            elif kind == "disjunctive":
                parts.append(disjunctive_packed(pseg, terms))
            else:
                parts.append(phrase_packed(pseg, terms[0], terms[1]))
            total += len(parts[-1])
        out = np.concatenate(parts)
        return out[:limit] if limit is not None else out

    def conjunctive(self, terms: Sequence[int],
                    limit: Optional[int] = None,
                    frozen_only: bool = False) -> np.ndarray:
        """GLOBAL docids holding every term, newest first, across the
        active pool and all frozen segments.  ``batched=True`` (default)
        routes through the qexec stack — with a ``limit`` this is the
        early-exit top-k; ``batched=False`` keeps the per-query
        host-loop oracle.  Both are bit-identical.  ``frozen_only=True``
        answers from the frozen segments alone (every docid <
        :attr:`doc_base`) — identical to the full result with
        active-segment docids filtered out."""
        if self.batched:
            return self._batch_eval("conjunctive", [tuple(terms)],
                                    limit, frozen_only)[0]
        return self._unified("conjunctive", terms, limit, frozen_only)

    def disjunctive(self, terms: Sequence[int],
                    limit: Optional[int] = None,
                    frozen_only: bool = False) -> np.ndarray:
        if self.batched:
            return self._batch_eval("disjunctive", [tuple(terms)],
                                    limit, frozen_only)[0]
        return self._unified("disjunctive", terms, limit, frozen_only)

    def phrase(self, t1: int, t2: int,
               limit: Optional[int] = None,
               frozen_only: bool = False) -> np.ndarray:
        if self.batched:
            return self._batch_eval("phrase", [(t1, t2)], limit,
                                    frozen_only)[0]
        return self._unified("phrase", (t1, t2), limit, frozen_only)


class LifecycleEngine(_LifecycleBase):
    """Single-device streaming engine: ingest -> rollover -> reclaim,
    with queries spanning the active pool and all frozen segments."""

    def __init__(self, layout: PoolLayout, vocab_size: int,
                 docs_per_segment: int, *, max_slices: int, max_len: int,
                 max_query_len: int = 8, max_segments: int = 12,
                 use_kernel: bool = True,
                 interpret: Optional[bool] = None,
                 bulk_ingest: bool = True,
                 batched: bool = True,
                 batched_kernel: Optional[bool] = None,
                 validate: bool = False,
                 stable_shapes: bool = False,
                 compaction: Optional[seg_mod.CompactionPolicy] = None,
                 admission: Optional[AdmissionController] = None):
        self.layout = layout
        self.vocab_size = vocab_size
        self.max_slices = max_slices
        self.max_len = max_len
        self.max_query_len = max_query_len
        self.use_kernel = use_kernel
        self.interpret = interpret
        self.batched = batched
        self.validate = validate
        self.stable_shapes = stable_shapes
        self.segments = seg_mod.SegmentSet(
            layout, vocab_size, docs_per_segment, max_segments=max_segments,
            bulk_ingest=bulk_ingest, compaction=compaction)
        self.engine = q.make_engine(layout, max_slices, max_len,
                                    max_query_len, use_kernel=use_kernel,
                                    interpret=interpret)
        self._init_shell(batched_kernel, admission)

    def _active_batch(self, kind: str, *args):
        if kind == "phrase":
            t1, t2 = args
            fn = qexec.make_active_fn(self.layout, self.max_slices,
                                      self.max_len, self.max_query_len,
                                      kind)
            return fn(self.segments.active.state, jnp.asarray(t1),
                      jnp.asarray(t2))
        terms, n_terms, tb = args
        # the engine is rebuilt (lru-cached) at the trimmed term width,
        # so its fold runs tb steps instead of max_query_len
        fn = qexec.make_active_fn(self.layout, self.max_slices,
                                  self.max_len, tb, kind)
        return fn(self.segments.active.state,
                  jnp.asarray(terms[:, :tb]), jnp.asarray(n_terms))

    def _active_topk_batch(self, terms, n_terms, k: int, k_pad: int,
                           tb: int):
        """-> ``(desc, n, tiles)``: the early-exit active top-k, and the
        driver tiles each row scanned."""
        fn = qexec.make_active_topk_fn(self.layout, self.max_slices,
                                       self.max_len, k_pad)
        return fn(self.segments.active.state, jnp.asarray(terms[:, :tb]),
                  jnp.asarray(n_terms), jnp.int32(min(k, k_pad)))

    def _active_scored_batch(self, terms, n_terms, tb: int):
        fn = qexec.make_active_scored_fn(self.layout, self.max_slices,
                                         self.max_len, tb)
        return fn(self.segments.active.state, jnp.asarray(terms[:, :tb]),
                  jnp.asarray(n_terms))

    def _active_desc(self, kind: str, terms: Sequence[int]) -> np.ndarray:
        state = self.segments.active.state
        if kind == "phrase":
            desc, n = self.engine.phrase(state, jnp.uint32(terms[0]),
                                         jnp.uint32(terms[1]))
        else:
            padded = np.zeros(self.max_query_len, np.uint32)
            padded[: len(terms)] = terms
            desc, n = getattr(self.engine, kind)(
                state, jnp.asarray(padded), jnp.int32(len(terms)))
        return (np.asarray(desc)[: int(n)].astype(np.int64)
                + self.doc_base)


class ShardedLifecycleEngine(_LifecycleBase):
    """Document-sharded streaming engine: the same unified query path on
    top of :class:`~repro.core.sharded_index.ShardedSegmentSet` (per-
    shard reclamation, shard_map active queries, global-docid frozen
    segments)."""

    def __init__(self, layout: PoolLayout, vocab_size: int,
                 docs_per_segment: int, mesh, *, max_slices: int,
                 max_len: int, max_query_len: int = 8,
                 max_segments: int = 12, rules=None,
                 use_kernel: bool = True,
                 interpret: Optional[bool] = None,
                 bulk_ingest: bool = True,
                 batched: bool = True,
                 batched_kernel: Optional[bool] = None,
                 validate: bool = False,
                 stable_shapes: bool = False,
                 compaction: Optional[seg_mod.CompactionPolicy] = None,
                 admission: Optional[AdmissionController] = None):
        self.layout = layout
        self.vocab_size = vocab_size
        self.max_slices = max_slices
        self.max_len = max_len
        self.max_query_len = max_query_len
        self.use_kernel = use_kernel
        self.interpret = interpret
        self.batched = batched
        self.validate = validate
        self.stable_shapes = stable_shapes
        self.segments = shx.ShardedSegmentSet(
            layout, vocab_size, docs_per_segment, mesh, rules=rules,
            max_segments=max_segments, bulk_ingest=bulk_ingest,
            compaction=compaction)
        self.engine = shx.make_sharded_engine(
            layout, mesh, max_slices, max_len, max_query_len,
            rules=self.segments.rules, use_kernel=use_kernel,
            interpret=interpret)
        self._init_shell(batched_kernel, admission)
        if batched_kernel is None and mesh.size > 1:
            # the frozen merge is one program replicated over the mesh,
            # and the TPU compiler cannot partition a Mosaic kernel: the
            # frozen intersections take the jnp path (masks identical).
            self._batched_kernel = False

    def _active_batch(self, kind: str, *args):
        """The sharded engine is ALREADY batched: one shard_map with one
        all_gather covers the whole query batch (not one per query);
        its merged output is segment-relative global docids, exactly
        what the qexec merge expects.  The term matrix stays at the
        engine's full ``max_query_len`` width (the shard_map engine is
        compiled for it); only the frozen stack trims."""
        state = self.segments.active.state
        if kind == "phrase":
            t1, t2 = args
            return self.engine.phrase(state, jnp.asarray(t1, jnp.uint32),
                                      jnp.asarray(t2, jnp.uint32))
        terms, n_terms, _tb = args
        return getattr(self.engine, kind)(
            state, jnp.asarray(terms, jnp.uint32),
            jnp.asarray(n_terms, jnp.int32))

    def _active_topk_batch(self, terms, n_terms, k: int, k_pad: int,
                           tb: int):
        # tile-level early exit inside shard_map is not implemented for
        # the sharded active pool; the full batched evaluation feeds the
        # frozen while_loop, which still early-exits across segments.
        # No tile counter: the full evaluation scans no tiles.
        desc, n = self._active_batch("conjunctive", terms, n_terms, tb)
        return desc, jnp.minimum(n, jnp.int32(k)), None

    def _active_scored_batch(self, terms, n_terms, _tb: int):
        # full max_query_len width, like _active_batch: the shard_map
        # engine is compiled for it; only the frozen stack trims.
        state = self.segments.active.state
        return self.engine.conjunctive_scored(
            state, jnp.asarray(terms, jnp.uint32),
            jnp.asarray(n_terms, jnp.int32))

    def _active_desc(self, kind: str, terms: Sequence[int]) -> np.ndarray:
        state = self.segments.active.state
        if kind == "phrase":
            desc, n = self.engine.phrase(
                state, jnp.asarray([terms[0]], jnp.uint32),
                jnp.asarray([terms[1]], jnp.uint32))
        else:
            padded = np.zeros((1, self.max_query_len), np.uint32)
            padded[0, : len(terms)] = terms
            desc, n = getattr(self.engine, kind)(
                state, jnp.asarray(padded),
                jnp.asarray([len(terms)], jnp.int32))
        return (np.asarray(desc[0])[: int(n[0])].astype(np.int64)
                + self.doc_base)


Engine = Union[LifecycleEngine, ShardedLifecycleEngine]
