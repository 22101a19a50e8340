"""Segment lifecycle (paper §3.1): active -> optimized read-only.

Earlybird keeps ~12 segments; at most one is mutable.  When the active
segment fills, it is converted to an optimized read-only structure: the
paper applies "a variant of PForDelta after reversing the order of the
postings".  Here:

  * :func:`freeze` walks every term's slice chain once (host-side numpy —
    this is an offline, off-the-query-path conversion, exactly as in
    production) and produces a contiguous CSR postings store, ascending
    (chronological) within each term.
  * :func:`ForBlocks` implements a Frame-of-Reference/PForDelta-lite
    block codec (128-gap blocks, per-block bit width) for the docid gaps —
    the paper's "variant of PForDelta".
  * :class:`SegmentSet` searches newest-active + frozen segments and merges
    results in reverse-chronological order, using per-segment docid bases.
  * :meth:`SegmentSet.compact` + :class:`CompactionPolicy` bound the
    frozen side: rollover alone appends a frozen segment forever, so the
    segment count G — and with it the qexec stack gather, the merge
    width, and the jit-recompile cadence — grows linearly with stream
    age.  Compaction merges adjacent frozen segments into one larger
    immutable segment (LSM/Earlybird-style tiering; Asadi & Lin, Moffat
    & Mackenzie in PAPERS.md), keeping G = O(log N) under an infinite
    stream.

Usage (compaction)::

    from repro.core.segments import CompactionPolicy, SegmentSet

    # geometric tiering, driven automatically at every rollover:
    ss = SegmentSet(layout, vocab, docs_per_segment,
                    compaction=CompactionPolicy(fanout=2))
    ss.ingest(docs)            # rollovers now cascade same-tier merges
    [fz.tier for fz in ss.frozen]   # non-increasing, no run >= fanout

    # or merge the k oldest frozen segments by hand (a no-op when the
    # window holds fewer than two segments; returns the merged segment):
    merged = ss.compact(k=4)

Compaction is a pure frozen-side rewrite: the frozen slices were
already recycled at rollover, so nothing is handed back to the
allocator; per-term postings are re-merged in global-docid order and
every query sees bit-identical results (tests/test_compaction.py).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import postings as post
from repro.core import slicepool
from repro.core.index import ActiveSegment
from repro.core.pointers import NULL, PoolLayout, decode_host


# ---------------------------------------------------------------------------
# Chain walk in numpy (offline freeze path)
# ---------------------------------------------------------------------------
def _walk_chain_np(layout: PoolLayout, heap: np.ndarray, tail: int,
                   out: List[int],
                   slices_out: Optional[List[List[int]]] = None) -> None:
    base_tbl = layout.pool_base
    sizes = layout.slice_sizes
    ptr = tail
    while ptr != int(NULL):
        pool, sl, off = decode_host(layout, ptr)
        base = base_tbl[pool] + sl * sizes[pool]
        start = 1 if pool > 0 else 0
        out.extend(heap[base + start: base + off + 1][::-1].tolist())
        if slices_out is not None:
            slices_out[pool].append(sl)
        ptr = int(heap[base]) if pool > 0 else int(NULL)


@dataclasses.dataclass
class FrozenSegment:
    """Contiguous CSR postings store (ascending chronological per term)."""
    offsets: np.ndarray       # int64[V+1]
    data: np.ndarray          # uint32[total]
    n_docs: int
    doc_base: int = 0
    # per-pool arrays of slice indices the freeze walked — everything the
    # active segment had allocated, ready for slicepool.release_slices.
    freed_slices: Optional[List[np.ndarray]] = None
    # compaction tier: 0 straight from rollover; merging segments yields
    # max(tier) + 1.  The geometric CompactionPolicy keeps, per tier,
    # fewer than `fanout` segments, so G = O(log N) under a live stream.
    tier: int = 0

    def postings(self, term: int) -> np.ndarray:
        return self.data[self.offsets[term]: self.offsets[term + 1]]

    def docids_desc(self, term: int) -> np.ndarray:
        p = self.postings(term)
        ids = (p >> np.uint32(post.POS_BITS))[::-1]
        return ids[np.concatenate([[True], ids[1:] != ids[:-1]])] \
            if ids.size else ids

    def docid_bounds(self, term: int) -> Tuple[int, int, int]:
        """O(1) per-term summary ``(n_postings, first_docid, last_docid)``
        (docids as stored — segment-relative here, global once a
        ``docid_map`` was baked in).  The qexec frozen stack uses these
        for whole-segment skips without forcing a pack: ``n_postings==0``
        or disjoint ``[first, last]`` ranges can never intersect."""
        a, b = int(self.offsets[term]), int(self.offsets[term + 1])
        if a == b:
            return 0, 0, 0
        shift = np.uint32(post.POS_BITS)
        return b - a, int(self.data[a] >> shift), int(self.data[b - 1] >> shift)

    def term_freqs(self) -> np.ndarray:
        return np.diff(self.offsets).astype(np.int64)

    @property
    def total_postings(self) -> int:
        return int(self.offsets[-1])


def freeze_state(layout: PoolLayout, heap: np.ndarray, tail: np.ndarray,
                 freq: np.ndarray, *, n_docs: int, doc_base: int = 0,
                 docid_map=None) -> FrozenSegment:
    """Freeze raw pool-state arrays into a CSR read-only segment.

    ``docid_map`` (optional) rewrites each posting's docid on the way out
    — the sharded index stores SHARD-LOCAL docids in its postings and
    maps them to global ids (``g = local * S + shard``) here, so frozen
    segments always speak global docids.  Positions are preserved.

    The returned segment's ``freed_slices`` lists every (pool, slice) the
    walk visited — i.e. the active segment's whole allocation — so the
    caller can hand the slices back to the allocator
    (:func:`repro.core.slicepool.release_slices`) and the next segment
    recycles them instead of bumping the watermark.
    """
    V = len(tail)
    offsets = np.zeros(V + 1, np.int64)
    offsets[1:] = np.cumsum(freq)
    data = np.zeros(int(offsets[-1]), np.uint32)
    slices: List[List[int]] = [[] for _ in range(layout.num_pools)]
    for t in np.nonzero(freq)[0]:
        buf: List[int] = []
        _walk_chain_np(layout, heap, int(tail[t]), buf, slices)
        # chain walk yields reverse-chronological; store chronological.
        data[offsets[t]: offsets[t + 1]] = np.asarray(buf, np.uint32)[::-1]
    if docid_map is not None:
        ids = (data >> np.uint32(post.POS_BITS)).astype(np.uint32)
        pos = data & np.uint32(post.MAX_POS)
        data = (docid_map(ids).astype(np.uint32)
                << np.uint32(post.POS_BITS)) | pos
    freed = [np.asarray(s, np.int32) for s in slices]
    return FrozenSegment(offsets=offsets, data=data,
                         n_docs=n_docs, doc_base=doc_base,
                         freed_slices=freed)


def freeze(seg: ActiveSegment, doc_base: int = 0) -> FrozenSegment:
    return freeze_state(seg.layout, np.asarray(seg.state.heap),
                        np.asarray(seg.state.tail),
                        np.asarray(seg.state.freq),
                        n_docs=seg.next_docid, doc_base=doc_base)


# ---------------------------------------------------------------------------
# Tiered compaction: merge adjacent frozen segments (LSM/Earlybird style)
# ---------------------------------------------------------------------------
def _adjacent_window(window) -> Tuple[int, int, List[int]]:
    """Validate that ``window`` (oldest -> newest) tiles a contiguous
    docid range and return ``(doc_base, n_docs, per-segment docid
    offsets)``.  Raises when ranges do not tile (merging would corrupt
    the disjoint-ascending-range invariant every query merge relies on)
    or when the merged docid span overflows the 24-bit docid field."""
    base = int(window[0].doc_base)
    end = base
    offs: List[int] = []
    for fz in window:
        if int(fz.doc_base) != end:
            raise ValueError(
                f"segments are not doc-range adjacent: doc_base "
                f"{int(fz.doc_base)} != previous range end {end}; "
                f"compaction windows must be contiguous oldest-first")
        offs.append(end - base)
        end += int(fz.n_docs)
    n_docs = end - base
    if n_docs - 1 > post.MAX_DOC:
        raise OverflowError(
            f"merged segment would span {n_docs} docs > the 24-bit "
            f"docid field ({post.MAX_DOC + 1}); compact fewer segments")
    return base, n_docs, offs


def _merge_csr(segs: Sequence["FrozenSegment"], docid_offsets: Sequence[int],
               *, n_docs: int, doc_base: int, tier: int) -> FrozenSegment:
    """Merge CSR postings stores: per-term streams are concatenated in
    segment (= ascending docid) order with each posting's docid rebased
    by its segment's offset inside the merged range.  Positions are
    preserved, so phrase queries see identical postings.  Vectorised
    numpy throughout — O(total postings), off the query path like the
    freeze walk.  ``freed_slices`` is None: compaction is a pure
    frozen-side rewrite (slices were recycled at rollover already)."""
    V = len(segs[0].offsets) - 1
    counts = np.zeros(V, np.int64)
    for s in segs:
        if len(s.offsets) - 1 != V:
            raise ValueError(
                f"vocab mismatch: {len(s.offsets) - 1} != {V}")
        counts += np.diff(s.offsets)
    offsets = np.zeros(V + 1, np.int64)
    offsets[1:] = np.cumsum(counts)
    data = np.zeros(int(offsets[-1]), np.uint32)
    placed = np.zeros(V, np.int64)   # postings already placed, per term
    for s, off in zip(segs, docid_offsets):
        cnt = np.diff(s.offsets)
        if s.data.size:
            dest0 = offsets[:-1] + placed
            # each posting lands at its term's destination cursor plus
            # its rank within the source term chunk
            idx = (np.repeat(dest0, cnt) + np.arange(s.data.size)
                   - np.repeat(s.offsets[:-1], cnt))
            data[idx] = s.data + np.uint32(int(off) << post.POS_BITS)
        placed += cnt
    return FrozenSegment(offsets=offsets, data=data, n_docs=n_docs,
                         doc_base=doc_base, freed_slices=None, tier=tier)


def merge_frozen(segs: Sequence[FrozenSegment]) -> FrozenSegment:
    """Merge doc-range-adjacent frozen segments (oldest -> newest) into
    ONE immutable segment covering their union: per-term postings in
    global-docid order, tier = max(member tiers) + 1.  Queries over the
    merged segment are bit-identical to queries over the originals."""
    base, n_docs, offs = _adjacent_window(segs)
    tier = max(int(getattr(s, "tier", 0)) for s in segs) + 1
    return _merge_csr(segs, offs, n_docs=n_docs, doc_base=base, tier=tier)


@dataclasses.dataclass(frozen=True)
class CompactionPolicy:
    """Geometric tiering: compact whenever ``fanout`` same-tier segments
    accumulate (merging them into one tier+1 segment), cascading like a
    base-``fanout`` counter — after N rollovers at most
    ``fanout - 1`` segments survive per tier, so G = O(log_fanout N)
    under an infinite stream."""
    fanout: int = 2

    def __post_init__(self):
        if self.fanout < 2:
            raise ValueError(f"fanout must be >= 2, got {self.fanout}")

    def plan(self, tiers: Sequence[int]) -> Optional[Tuple[int, int]]:
        """First (oldest) run of >= fanout adjacent equal-tier segments,
        as ``(start, k=fanout)`` — the window to compact next — or None
        at the fixpoint.  Merging the oldest ``fanout`` members of a run
        keeps tiers non-increasing oldest-first (the element before the
        run is strictly higher-tier), which ``check_segment_set``
        enforces."""
        tiers = list(tiers)
        i = 0
        while i < len(tiers):
            j = i
            while j < len(tiers) and tiers[j] == tiers[i]:
                j += 1
            if j - i >= self.fanout:
                return i, self.fanout
            i = j
        return None


# ---------------------------------------------------------------------------
# FOR / PForDelta-lite block codec for docid gaps
# ---------------------------------------------------------------------------
BLOCK = 128


@dataclasses.dataclass
class ForBlocks:
    widths: np.ndarray   # uint8[n_blocks] bits per value
    firsts: np.ndarray   # uint32[n_blocks] first raw value per block
    payload: np.ndarray  # uint64 packed little-endian bit stream
    n: int

    @staticmethod
    def encode(values: np.ndarray) -> "ForBlocks":
        values = values.astype(np.uint64)
        n = len(values)
        n_blocks = max(1, -(-n // BLOCK))
        widths = np.zeros(n_blocks, np.uint8)
        firsts = np.zeros(n_blocks, np.uint32)
        bits: List[Tuple[int, int]] = []  # (value, width) stream
        for b in range(n_blocks):
            chunk = values[b * BLOCK:(b + 1) * BLOCK]
            if chunk.size == 0:
                continue
            firsts[b] = chunk[0]
            gaps = np.diff(chunk.astype(np.int64)).astype(np.uint64)
            w = int(gaps.max()).bit_length() if gaps.size else 0
            widths[b] = w
            bits.extend((int(g), w) for g in gaps)
        total_bits = sum(w for _, w in bits)
        payload = np.zeros((total_bits + 63) // 64 + 1, np.uint64)
        pos = 0
        for v, w in bits:
            if w == 0:
                continue
            word, off = pos >> 6, pos & 63
            payload[word] |= np.uint64((v << off) & 0xFFFFFFFFFFFFFFFF)
            if off + w > 64:
                payload[word + 1] |= np.uint64(v >> (64 - off))
            pos += w
        return ForBlocks(widths, firsts, payload, n)

    def decode(self) -> np.ndarray:
        out = np.zeros(self.n, np.uint64)
        pos = 0
        i = 0
        for b in range(len(self.widths)):
            cnt = min(BLOCK, self.n - b * BLOCK)
            if cnt <= 0:
                break
            out[i] = self.firsts[b]
            w = int(self.widths[b])
            acc = int(self.firsts[b])
            for j in range(1, cnt):
                if w == 0:
                    g = 0
                else:
                    word, off = pos >> 6, pos & 63
                    v = int(self.payload[word]) >> off
                    if off + w > 64:
                        v |= int(self.payload[word + 1]) << (64 - off)
                    g = v & ((1 << w) - 1)
                    pos += w
                acc += g
                out[i + j] = acc
            i += cnt
        return out

    @property
    def compressed_bytes(self) -> int:
        return (self.widths.nbytes + self.firsts.nbytes
                + self.payload.nbytes)


def compress_segment(seg: FrozenSegment) -> Tuple[List[Optional[ForBlocks]], int]:
    """Gap-compress each term's docid stream; returns (codecs, bytes)."""
    codecs: List[Optional[ForBlocks]] = []
    total = 0
    for t in range(len(seg.offsets) - 1):
        p = seg.postings(t)
        if p.size == 0:
            codecs.append(None)
            continue
        c = ForBlocks.encode(p.astype(np.uint64))
        codecs.append(c)
        total += c.compressed_bytes
    return codecs, total


# ---------------------------------------------------------------------------
# Multi-segment search
# ---------------------------------------------------------------------------
class SegmentSet:
    """At most one active segment + N frozen ones (paper §3.1)."""

    def __init__(self, layout: PoolLayout, vocab_size: int,
                 docs_per_segment: int, max_segments: int = 12,
                 bulk_ingest: bool = True,
                 compaction: Optional[CompactionPolicy] = None):
        self.layout = layout
        self.vocab_size = vocab_size
        self.docs_per_segment = docs_per_segment
        self.max_segments = max_segments
        self.bulk_ingest = bulk_ingest
        self.compaction = compaction
        self.frozen: List[FrozenSegment] = []
        self.n_rollovers = 0
        self.n_compactions = 0
        self.active = self._new_active()
        self._doc_base = 0
        self._hist_freqs: Optional[np.ndarray] = None

    def _new_active(self, state=None) -> ActiveSegment:
        return ActiveSegment(self.layout, self.vocab_size,
                             max_docs=self.docs_per_segment, state=state,
                             bulk_ingest=self.bulk_ingest)

    def ingest(self, docs, **kw) -> None:
        self.active.ingest(docs, **kw)
        if self.active.is_full:
            self.rollover()

    def rollover(self) -> Optional[FrozenSegment]:
        """Freeze the active segment and RECYCLE its slices: the frozen
        postings live on as read-only CSR, while every slice the segment
        occupied goes back on the pool free lists for the next active
        segment (the Goldilocks loop — watermark bounded under churn).
        With a :class:`CompactionPolicy` attached, same-tier frozen
        segments then cascade-merge so G stays O(log N).

        An EMPTY active segment is a no-op returning None: freezing it
        would append a zero-doc frozen segment (breaking the
        disjoint-ascending-range tiling's usefulness and burning a
        ``max_segments`` slot) without reclaiming anything — the
        emergency-rollover path can fire on an arbitrary batch boundary
        and must be safe to call unconditionally."""
        if self.active.next_docid == 0:
            return None
        with jax.profiler.TraceAnnotation("segments.rollover",
                                          docs=self.active.next_docid):
            fz = freeze(self.active, doc_base=self._doc_base)
            # H(t) snapshot: the freqs of THIS rollover, taken before any
            # compaction can merge the segment into a multi-rollover tier
            # (history_freqs must keep meaning "the last rollover").
            self._hist_freqs = fz.term_freqs()
            self.frozen.append(fz)
            self.n_rollovers += 1
            if len(self.frozen) > self.max_segments - 1:
                # oldest segment retired (paper: bounded set)
                self.frozen.pop(0)
            self._doc_base += self.active.next_docid
            released = slicepool.release_slices(
                self.layout, self.active.state, fz.freed_slices)
            self.active = self._new_active(state=released)
            self._apply_compaction()
        return fz

    def compact(self, k: int, *, start: int = 0
                ) -> Optional[FrozenSegment]:
        """Merge the ``k`` oldest frozen segments (or ``k`` adjacent
        ones from index ``start`` — the policy's window) into one larger
        immutable segment: per-term postings re-merged in global-docid
        order, per-term summaries rebuilt, the disjoint-ascending-range
        tiling preserved.  ``k`` is clamped to the available window; a
        window holding fewer than two segments is a no-op returning
        None.  Recycles nothing — the frozen slices were already freed
        at rollover; this is a pure frozen-side rewrite."""
        k = min(int(k), len(self.frozen) - start)
        if k < 2:
            return None
        with jax.profiler.TraceAnnotation("segments.compact", k=k):
            merged = merge_frozen(self.frozen[start: start + k])
            self.frozen[start: start + k] = [merged]
            self.n_compactions += 1
        return merged

    def _apply_compaction(self) -> None:
        """Run the tiering policy to its fixpoint (no run of >= fanout
        same-tier segments left)."""
        if self.compaction is None:
            return
        while True:
            plan = self.compaction.plan([fz.tier for fz in self.frozen])
            if plan is None:
                return
            self.compact(plan[1], start=plan[0])

    def history_freqs(self) -> np.ndarray:
        """H(t) from the most recent ROLLOVER (paper §7) — a snapshot
        taken at freeze time, so a compaction that merges the newest
        frozen segment into a multi-rollover tier cannot silently widen
        the signal's window."""
        if self._hist_freqs is None:
            return np.zeros(self.vocab_size, np.int64)
        return self._hist_freqs.copy()

    def search_term_desc(self, term: int, engine, limit: int) -> np.ndarray:
        """Global docids (descending, newest segment first).  The frozen
        walk stops as soon as ``limit`` docids are collected — older
        segments are never materialised past the cut."""
        plist, n = engine.docids_asc(self.active.state, term)
        ids = np.asarray(plist)[: int(n)][::-1].astype(np.int64) + self._doc_base
        out = [ids]
        total = ids.size
        for fz in reversed(self.frozen):
            if total >= limit:
                break
            ids = fz.docids_desc(term).astype(np.int64) + fz.doc_base
            out.append(ids)
            total += ids.size
        return np.concatenate(out)[:limit]
