"""Host-side structural validators for the allocator & segment state.

Usage::

    from repro.analysis import invariants

    rep = invariants.check_pool_state(layout, engine.segments.active.state)
    assert rep.ok, rep.render()
    invariants.check_frozen_segment(fz, layout=layout).raise_if_failed()

    # or let the engine self-check at every rollover:
    eng = LifecycleEngine(..., validate=True)

    # or across a whole bench run:
    #   PYTHONPATH=src python benchmarks/run.py --validate

Each ``check_*`` returns a :class:`Report` (never raises by itself):
``ok`` plus a list of :class:`Violation`\\ s naming the field and the
broken invariant, and a small ``stats`` dict so tests can assert the
validator actually inspected something (e.g. walked > 0 chains).
``Report.raise_if_failed()`` converts failures into
:class:`InvariantViolation` for post-condition use.

Validators run in numpy off the hot path (the same policy as the freeze
walk and ``release_slices``); they are O(live postings) and meant for
tests, ``validate=True`` debugging, and bench ``--validate`` sweeps —
not for per-batch production use.

Invariants enforced (the Goldilocks allocator's bookkeeping, paper
§3.1–3.3; see ROADMAP "Architecture reference"):

``check_pool_state``
    Per pool: live-chain slices and free-list entries are DISJOINT and
    together partition ``[0, watermark)``; free entries unique;
    watermark/free_count within capacity; chain pool indices
    non-increasing newest-first along every chain; per-term chain slot
    count equals ``freq``; ``tail`` null iff ``freq`` zero; sticky
    ``overflow`` has the right shape.  Accepts sharded ``[S, ...]``
    states (each shard row is validated independently).  Single-pool
    layouts cannot link continuation slices (pool 0 has no pointer
    slot), so there only the reachable tail slice is checked and the
    partition relaxes to ``live + free <= watermark``.
``check_frozen_segment``
    CSR offsets monotone int64 with ``offsets[0] == 0`` and
    ``offsets[-1] == len(data)``; per-term packed postings strictly
    increasing; docids within ``[0, n_docs)`` when the segment stores
    segment-relative docids; per-term ``docid_bounds`` agrees with the
    data; ``freed_slices`` unique and within pool capacity.  With
    ``scored=[(term, ScoredList), ...]`` it also re-derives each term's
    per-doc tf from the positional CSR and checks the attached impact
    plane quantizes it exactly (``min(tf, SCORE_MAX)`` per unique
    docid, docids aligned) — the substrate the block-max skip bounds
    stand on.
``check_segment_set``
    Frozen segments own disjoint ascending docid ranges tiling
    contiguously oldest-first (compacted segments cover their members'
    union, so the tiling survives any rollover/compaction mix); the
    active base continues exactly where the newest frozen segment ends;
    the set is bounded by ``max_segments``.  With ``fanout=`` (the
    engine's :class:`~repro.core.segments.CompactionPolicy` fanout) it
    also enforces the tier structure: non-increasing oldest-first, no
    run of ``fanout`` adjacent same-tier segments (the geometric
    fixpoint behind G = O(log N)).
``check_stacked_lists``
    Byte widths in {1, 2, 4}; ``woffs`` keep every SLAB_WORDS-word DMA
    in bounds; pad blocks (firsts == INVALID) decode to INVALID; valid
    lanes decode strictly ascending and pad lanes never sort below the
    last valid docid.  Also accepts a ``ScoredStack``: the docid stack
    is validated as above, plus the score planes — valid lanes in
    ``[1, SCORE_MAX]``, every lane past ``ns`` zero (pad lanes and pad
    blocks contribute nothing to any block's bound), and each block-max
    entry EQUAL to the max impact of its 128 lanes (a bmax below a
    member lane breaks the skip-safety proof; above the true max it
    only costs skips, but the builder writes the exact max so drift is
    still a violation).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from repro.core.pointers import NULL, PoolLayout, decode_host
from repro.core.slicepool import row_words

INVALID = 0xFFFFFFFF


class InvariantViolation(AssertionError):
    """A structural invariant of the index state does not hold."""


@dataclasses.dataclass(frozen=True)
class Violation:
    check: str     # which check_* produced it
    field: str     # state leaf / structure member at fault
    message: str

    def render(self) -> str:
        return f"[{self.check}] {self.field}: {self.message}"


@dataclasses.dataclass
class Report:
    check: str
    violations: List[Violation] = dataclasses.field(default_factory=list)
    stats: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.violations

    def add(self, field: str, message: str) -> None:
        self.violations.append(Violation(self.check, field, message))

    def render(self) -> str:
        if self.ok:
            return f"[{self.check}] ok ({self.stats})"
        return "\n".join(v.render() for v in self.violations)

    def raise_if_failed(self) -> "Report":
        if not self.ok:
            raise InvariantViolation(self.render())
        return self


def _merge(into: Report, sub: Report, prefix: str) -> None:
    for v in sub.violations:
        into.violations.append(Violation(
            into.check, f"{prefix}{v.field}", v.message))
    for k, n in sub.stats.items():
        into.stats[k] = into.stats.get(k, 0) + n


# ---------------------------------------------------------------------------
# check_pool_state
# ---------------------------------------------------------------------------
def _check_pool_state_one(layout: PoolLayout, heap, watermark, tail, freq,
                          free_list, free_count, rep: Report) -> None:
    P = layout.num_pools
    V = tail.shape[0]
    caps = np.asarray(layout.slices_per_pool, np.int64)
    fb = np.asarray(layout.free_base, np.int64)
    sizes = np.asarray(layout.slice_sizes, np.int64)
    # Pool 0 has no previous-pointer slot, so a single-pool layout cannot
    # link continuation slices: every alloc-on-full ORPHANS the old slice
    # by design (the paper's §3.3 progression needs P >= 2).  Only the
    # tail slice of each chain is reachable, its fill level is
    # ((freq - 1) mod slice_size) + 1, and orphaned slices legitimately
    # sit inside [0, watermark) outside both live chains and free lists —
    # so the partition equality relaxes to an upper bound.
    single_pool = P == 1
    wm = watermark.astype(np.int64)
    fc = free_count.astype(np.int64)

    if heap.shape != (row_words(layout.total_slots),):
        rep.add("heap", f"shape {heap.shape} != "
                f"({row_words(layout.total_slots)},)")
        return
    if np.any(wm < 0) or np.any(wm > caps):
        rep.add("watermark", f"outside [0, capacity]: {wm} vs {caps}")
        return
    if np.any(fc < 0) or np.any(fc > wm):
        rep.add("free_count",
                f"outside [0, watermark]: {fc} vs watermark {wm}")
        return

    free_sets = []
    for p in range(P):
        entries = free_list[fb[p]: fb[p] + fc[p]].astype(np.int64)
        if entries.size != np.unique(entries).size:
            rep.add("free_list", f"pool {p}: duplicate free entries")
        bad = (entries < 0) | (entries >= wm[p])
        if np.any(bad):
            rep.add("free_list",
                    f"pool {p}: {int(bad.sum())} entries outside the "
                    f"allocated range [0, {wm[p]})")
        free_sets.append(set(int(e) for e in entries))

    # walk every live chain; collect live slices per pool.
    live_sets: List[set] = [set() for _ in range(P)]
    n_chains = 0
    max_steps = int(np.sum(caps)) + 1   # cycle guard: > total slices
    for t in np.nonzero(freq > 0)[0]:
        ptr = int(tail[t])
        if ptr == int(NULL):
            rep.add("tail", f"term {t}: freq {int(freq[t])} > 0 but "
                    "tail is NULL")
            continue
        n_chains += 1
        slots = 0
        prev_pool = P  # sentinel above every real pool
        steps = 0
        while ptr != int(NULL):
            steps += 1
            if steps > max_steps:
                rep.add("tail", f"term {t}: chain exceeds {max_steps} "
                        "slices — cycle or corrupt previous-pointer")
                break
            pool, sl, off = decode_host(layout, ptr)
            if pool >= P or sl >= wm[pool]:
                rep.add("tail", f"term {t}: chain slice (pool {pool}, "
                        f"slice {sl}) outside allocated [0, "
                        f"{wm[pool] if pool < P else '?'})")
                break
            if pool > prev_pool:
                rep.add("tail", f"term {t}: pool {pool} follows pool "
                        f"{prev_pool} newest-first — the §3.3 "
                        "progression never grows backwards")
            prev_pool = pool
            live_sets[pool].add(int(sl))
            start = 1 if pool > 0 else 0
            slots += int(off) - start + 1
            base = layout.pool_base[pool] + int(sl) * int(sizes[pool])
            nxt = int(heap[base]) if pool > 0 else int(NULL)
            if steps > 1:
                # every non-tail slice of the chain was full when its
                # successor was allocated
                if int(off) != int(sizes[pool]) - 1:
                    rep.add("tail", f"term {t}: interior chain slice in "
                            f"pool {pool} is not full (off {int(off)})")
            ptr = nxt
        else:
            want = (((int(freq[t]) - 1) % int(sizes[0])) + 1
                    if single_pool else int(freq[t]))
            if slots != want:
                rep.add("freq", f"term {t}: chain holds {slots} postings "
                        f"but freq {int(freq[t])} implies {want}")

    for t in np.nonzero(freq == 0)[0]:
        if int(tail[t]) != int(NULL):
            rep.add("tail", f"term {t}: freq 0 but tail "
                    f"{int(tail[t]):#x} != NULL")
            break   # one is enough; V can be large

    for p in range(P):
        inter = live_sets[p] & free_sets[p]
        if inter:
            rep.add("free_list",
                    f"pool {p}: {len(inter)} slice(s) BOTH live and on "
                    f"the free list (e.g. slice {min(inter)}) — "
                    "use-after-free territory")
        n_live, n_free = len(live_sets[p]), len(free_sets[p])
        if single_pool:
            if n_live + n_free > int(wm[p]):
                rep.add("watermark",
                        f"pool {p}: live {n_live} + free {n_free} > "
                        f"watermark {int(wm[p])} — slices double-counted")
        elif n_live + n_free != int(wm[p]):
            rep.add("watermark",
                    f"pool {p}: live {n_live} + free {n_free} != "
                    f"watermark {int(wm[p])} — allocated slices leaked "
                    "or double-counted")
    rep.stats["chains_walked"] = rep.stats.get("chains_walked", 0) \
        + n_chains
    rep.stats["live_slices"] = rep.stats.get("live_slices", 0) \
        + sum(len(s) for s in live_sets)
    rep.stats["free_slices"] = rep.stats.get("free_slices", 0) \
        + sum(len(s) for s in free_sets)
    rep.stats["vocab"] = int(V)


def check_pool_state(layout: PoolLayout, state) -> Report:
    """Validate a :class:`~repro.core.slicepool.PoolState` (single-shard
    ``watermark[P]`` or sharded ``watermark[S, P]``)."""
    rep = Report(check="pool-state")
    wm = np.asarray(state.watermark)
    heap = np.asarray(state.heap)
    tail = np.asarray(state.tail)
    freq = np.asarray(state.freq)
    fl = np.asarray(state.free_list)
    fc = np.asarray(state.free_count)
    ov = np.asarray(state.overflow)
    sharded = wm.ndim == 2
    if sharded:
        S = wm.shape[0]
        if ov.shape != (S,):
            rep.add("overflow", f"sharded state wants bool[{S}], got "
                    f"shape {ov.shape}")
        rep.stats["shards"] = S
        for s in range(S):
            sub = Report(check=rep.check)
            _check_pool_state_one(layout, heap[s], wm[s], tail[s],
                                  freq[s], fl[s], fc[s], sub)
            _merge(rep, sub, f"shard {s}: ")
    else:
        if ov.shape != ():
            rep.add("overflow", f"single state wants a bool scalar, got "
                    f"shape {ov.shape}")
        _check_pool_state_one(layout, heap, wm, tail, freq, fl, fc, rep)
    # overflow being SET is defined allocator behaviour (inserts become
    # no-ops), not a structural violation — only its shape is invariant.
    rep.stats["overflowed"] = int(np.any(ov))
    return rep


# ---------------------------------------------------------------------------
# check_frozen_segment
# ---------------------------------------------------------------------------
def check_frozen_segment(seg, *, layout: Optional[PoolLayout] = None,
                         relative_docids: bool = True,
                         scored=None) -> Report:
    """Validate one :class:`~repro.core.segments.FrozenSegment` CSR.

    ``relative_docids=False`` for shard members of a
    ``ShardedFrozenSegment`` (their docids are global-within-segment via
    ``docid_map`` and legitimately exceed the shard-local ``n_docs``).

    ``scored`` takes ``[(term, ScoredList), ...]`` pairs (e.g. from
    ``PackedSegment.scored``) and cross-checks each impact plane
    against the tf derived from this segment's positional CSR:
    decoded docids must equal the term's unique docids (plus the
    segment's ``doc_base``) and decoded impacts must equal
    ``min(tf, SCORE_MAX)`` lane-for-lane.
    """
    from repro.core import postings as post

    rep = Report(check="frozen-segment")
    offsets = np.asarray(seg.offsets)
    data = np.asarray(seg.data)
    V = offsets.shape[0] - 1
    if offsets.dtype != np.int64:
        rep.add("offsets", f"dtype {offsets.dtype} != int64")
    if offsets.size == 0 or offsets[0] != 0:
        rep.add("offsets", "offsets[0] != 0")
        return rep
    d = np.diff(offsets)
    if np.any(d < 0):
        t = int(np.argmax(d < 0))
        rep.add("offsets", f"non-monotone at term {t}: "
                f"{int(offsets[t])} -> {int(offsets[t + 1])}")
        return rep
    if int(offsets[-1]) != data.size:
        rep.add("offsets", f"offsets[-1] {int(offsets[-1])} != "
                f"len(data) {data.size}")
        return rep

    shift = np.uint32(post.POS_BITS)
    docids = (data >> shift).astype(np.int64)
    n_terms = 0
    for t in np.nonzero(d > 0)[0]:
        a, b = int(offsets[t]), int(offsets[t + 1])
        chunk = data[a:b].astype(np.int64)
        n_terms += 1
        if np.any(np.diff(chunk) <= 0):
            rep.add("data", f"term {t}: packed postings not strictly "
                    "increasing (docid/pos order broken)")
        cnt, first, last = seg.docid_bounds(int(t))
        if cnt != b - a or first != int(docids[a]) \
                or last != int(docids[b - 1]):
            rep.add("docid_bounds", f"term {t}: bounds ({cnt}, {first}, "
                    f"{last}) disagree with data "
                    f"({b - a}, {int(docids[a])}, {int(docids[b - 1])})")
    if relative_docids and data.size:
        if int(docids.max()) >= int(seg.n_docs) or int(docids.min()) < 0:
            rep.add("data", f"docid {int(docids.max())} outside "
                    f"[0, n_docs={int(seg.n_docs)})")
    freed = getattr(seg, "freed_slices", None)
    if freed is not None:
        for p, sl in enumerate(freed):
            sl = np.asarray(sl)
            if sl.size != np.unique(sl).size:
                rep.add("freed_slices", f"pool {p}: duplicate slice — "
                        "would double-release")
            if layout is not None and sl.size and (
                    int(sl.min()) < 0
                    or int(sl.max()) >= layout.slices_per_pool[p]):
                rep.add("freed_slices", f"pool {p}: slice index outside "
                        f"[0, {layout.slices_per_pool[p]})")
    if scored:
        from repro.kernels.segment_intersect import (SCORE_MAX,
                                                     decode_packed,
                                                     decode_scores)
        base = int(getattr(seg, "doc_base", 0))
        n_scored = 0
        for term, sl in scored:
            term = int(term)
            a, b = int(offsets[term]), int(offsets[term + 1])
            uniq, cnt = np.unique(docids[a:b], return_counts=True)
            want = np.minimum(cnt, SCORE_MAX).astype(np.int64)
            n = int(sl.ids.n)
            n_scored += 1
            if n != uniq.size:
                rep.add("scored", f"term {term}: impact plane holds {n} "
                        f"docids but the CSR holds {uniq.size} unique "
                        "docids")
                continue
            got_ids = np.asarray(decode_packed(sl.ids))[:n].astype(
                np.int64) - base
            if not np.array_equal(got_ids, uniq):
                rep.add("scored", f"term {term}: packed docids disagree "
                        "with the CSR's unique docids — impacts would "
                        "score the wrong documents")
                continue
            got_sc = np.asarray(decode_scores(sl.swords)).reshape(-1)[
                :n].astype(np.int64)
            if not np.array_equal(got_sc, want):
                i = int(np.argmax(got_sc != want))
                rep.add("scored", f"term {term}: impact {int(got_sc[i])} "
                        f"at lane {i} != min(tf, SCORE_MAX) = "
                        f"{int(want[i])} from the positional CSR")
        rep.stats["scored_terms_checked"] = n_scored
    rep.stats["terms_checked"] = n_terms
    rep.stats["postings"] = int(data.size)
    rep.stats["vocab"] = int(V)
    return rep


# ---------------------------------------------------------------------------
# check_segment_set
# ---------------------------------------------------------------------------
def check_segment_set(segset, *, layout: Optional[PoolLayout] = None,
                      fanout: Optional[int] = None) -> Report:
    """Validate a ``SegmentSet``/``ShardedSegmentSet``-shaped object
    (``frozen`` list + ``_doc_base`` + ``max_segments``): frozen docid
    ranges tile contiguously oldest-first (compacted segments cover the
    union of their members, so the tiling survives any mix of rollovers
    and compactions), the active base continues the newest frozen
    segment, the set stays bounded.  Each member segment is validated
    too (sharded members shard-by-shard).

    ``fanout`` (pass the engine's ``CompactionPolicy.fanout``) adds the
    tier-structure check: tiers are non-increasing oldest-first (the
    geometric cascade merges oldest-first, like carries in a
    base-``fanout`` counter) and no run of ``fanout`` adjacent
    same-tier segments survives — the policy fixpoint that makes
    G = O(log N).  Without ``fanout`` only tier sanity (``tier >= 0``)
    is checked, so hand-driven ``compact(k, start=...)`` windows that
    break the cascade shape are still accepted."""
    rep = Report(check="segment-set")
    frozen = list(segset.frozen)
    if len(frozen) > int(segset.max_segments) - 1:
        rep.add("frozen", f"{len(frozen)} frozen segments exceed "
                f"max_segments - 1 = {int(segset.max_segments) - 1}")
    prev_end = None
    tiers: List[int] = []
    for i, fz in enumerate(frozen):
        base, n = int(fz.doc_base), int(fz.n_docs)
        if n < 0:
            rep.add("frozen", f"segment {i}: negative n_docs {n}")
        if prev_end is not None and base < prev_end:
            rep.add("frozen", f"segment {i}: doc_base {base} overlaps "
                    f"previous segment's range ending at {prev_end}")
        elif prev_end is not None and base > prev_end:
            rep.add("frozen", f"segment {i}: doc_base {base} leaves a "
                    f"gap after previous range end {prev_end} — frozen "
                    "ranges must tile contiguously (rollover appends "
                    "contiguously; compaction merges whole windows)")
        prev_end = base + n
        tier = int(getattr(fz, "tier", 0))
        tiers.append(tier)
        if tier < 0:
            rep.add("tier", f"segment {i}: negative tier {tier}")
        shards = getattr(fz, "shards", None)
        if shards is None:
            _merge(rep, check_frozen_segment(fz, layout=layout),
                   f"segment {i}: ")
        else:
            for s, sh in enumerate(shards):
                _merge(rep, check_frozen_segment(
                    sh, layout=layout, relative_docids=False),
                    f"segment {i} shard {s}: ")
    if frozen and int(segset._doc_base) != prev_end:
        rep.add("_doc_base", f"active doc_base {int(segset._doc_base)} "
                f"!= newest frozen end {prev_end} — ranges must tile")
    if fanout is not None and tiers:
        if int(fanout) < 2:
            rep.add("tier", f"fanout {fanout} < 2 is not a geometric "
                    "policy")
        for i in range(1, len(tiers)):
            if tiers[i] > tiers[i - 1]:
                rep.add("tier", f"segment {i}: tier {tiers[i]} exceeds "
                        f"older segment's tier {tiers[i - 1]} — the "
                        "geometric cascade keeps tiers non-increasing "
                        "oldest-first")
        run, run_tier = 0, None
        for i, t in enumerate(tiers):
            run = run + 1 if t == run_tier else 1
            run_tier = t
            if run >= int(fanout):
                rep.add("tier", f"segments {i - run + 1}..{i}: {run} "
                        f"adjacent tier-{t} segments >= fanout "
                        f"{int(fanout)} — the policy fixpoint was not "
                        "reached (G would grow linearly)")
                break
    rep.stats["segments"] = len(frozen)
    rep.stats["max_tier"] = max(tiers) if tiers else 0
    return rep


# ---------------------------------------------------------------------------
# check_stacked_lists
# ---------------------------------------------------------------------------
def check_stacked_lists(s, *, decode: bool = True) -> Report:
    """Validate a :class:`~repro.kernels.segment_intersect.StackedLists`
    (any leading shape): legal byte widths, in-bounds DMA windows, pad
    blocks decoding to INVALID, ascending valid lanes.  A
    :class:`~repro.kernels.segment_intersect.ScoredStack` is accepted
    too — its docid stack is validated identically, then the score
    planes: valid lanes in ``[1, SCORE_MAX]``, lanes past ``ns`` zero,
    per-block bmax equal to the block's lane max (and hence 0 on pad
    blocks)."""
    from repro.kernels.segment_intersect import (SCORE_MAX, SCORE_WORDS,
                                                 SEG_BLOCK, SLAB_WORDS,
                                                 decode_scores,
                                                 decode_stacked)

    rep = Report(check="stacked-lists")
    swords = bmax = None
    if hasattr(s, "swords"):          # ScoredStack: ids + score planes
        swords = np.asarray(s.swords)
        bmax = np.asarray(s.bmax)
        s = s.ids
    firsts = np.asarray(s.firsts)
    bws = np.asarray(s.bws)
    woffs = np.asarray(s.woffs)
    payload = np.asarray(s.payload)
    ns = np.asarray(s.ns)
    NB = firsts.shape[-1]
    PW = payload.shape[-1]
    rows = int(np.prod(firsts.shape[:-1], dtype=np.int64)) \
        if firsts.ndim > 1 else 1
    f2 = firsts.reshape(rows, NB)
    b2 = bws.reshape(rows, NB)
    w2 = woffs.reshape(rows, NB)
    p2 = payload.reshape(rows, PW)
    n2 = ns.reshape(rows)

    if not np.isin(b2, (1, 2, 4)).all():
        rep.add("bws", f"byte widths outside {{1,2,4}}: "
                f"{sorted(set(np.unique(b2).tolist()) - {1, 2, 4})}")
    if np.any(n2 < 0) or np.any(n2 > NB * SEG_BLOCK):
        rep.add("ns", f"valid counts outside [0, {NB * SEG_BLOCK}]")
    if np.any(w2 < 0) or np.any(w2 > PW - SLAB_WORDS):
        rep.add("woffs", f"word offsets outside [0, {PW - SLAB_WORDS}] "
                f"— a {SLAB_WORDS}-word block DMA would overrun the "
                "payload")
        return rep   # decoding would index OOB; stop here

    n_pad_blocks = 0
    for r in range(rows):
        pad = f2[r] == INVALID
        n_pad_blocks += int(pad.sum())
        for b in np.nonzero(pad)[0]:
            w = int(w2[r, b])
            plane = p2[r, w: w + 32 * int(b2[r, b])]
            if np.any(plane != 0):
                rep.add("payload", f"row {r} block {int(b)}: pad block "
                        "gap plane is non-zero — would decode to "
                        "non-INVALID ghost docids")
    if decode:
        lanes = np.asarray(decode_stacked(s)).reshape(rows, -1)
        lane64 = lanes.astype(np.int64)
        for r in range(rows):
            n = int(n2[r])
            if n > 1 and np.any(np.diff(lane64[r, :n]) <= 0):
                rep.add("payload", f"row {r}: decoded valid lanes not "
                        "strictly ascending")
            if n < lanes.shape[1]:
                floor = lane64[r, n - 1] if n else -1
                if np.any(lane64[r, n:] < floor):
                    rep.add("payload", f"row {r}: pad lane decodes "
                            "below the last valid docid — would corrupt "
                            "the two-pointer walk")
        # full pad blocks must decode to exactly INVALID
        lb = lanes.reshape(rows, NB, SEG_BLOCK)
        bad = (f2 == INVALID) & np.any(lb != np.uint32(INVALID), axis=2)
        if np.any(bad):
            r, b = [int(x[0]) for x in np.nonzero(bad)]
            rep.add("payload", f"row {r} block {b}: pad block decodes "
                    "to non-INVALID lanes")
    if swords is not None:
        if swords.shape[-1] != NB * SCORE_WORDS:
            rep.add("swords", f"score plane width {swords.shape[-1]} != "
                    f"{NB} blocks * {SCORE_WORDS} words")
            return rep
        if bmax.shape[-1] != NB:
            rep.add("bmax", f"block-max width {bmax.shape[-1]} != "
                    f"{NB} blocks")
            return rep
        sc = np.asarray(decode_scores(swords)).reshape(rows, NB,
                                                       SEG_BLOCK)
        bm = bmax.reshape(rows, NB).astype(np.int64)
        lane = np.arange(NB * SEG_BLOCK).reshape(NB, SEG_BLOCK)
        for r in range(rows):
            valid = lane < int(n2[r])
            v = sc[r][valid]
            if v.size and (int(v.min()) < 1 or int(v.max()) > SCORE_MAX):
                rep.add("swords", f"row {r}: valid-lane impact outside "
                        f"[1, {SCORE_MAX}] — 0 is the no-hit sentinel, "
                        "so a 0 impact would drop a real hit")
            if np.any(sc[r][~valid] != 0):
                rep.add("swords", f"row {r}: non-zero impact past "
                        f"ns={int(n2[r])} — a pad lane would leak into "
                        "the intersection scores")
            want = sc[r].max(axis=1).astype(np.int64)
            if not np.array_equal(bm[r], want):
                b = int(np.argmax(bm[r] != want))
                rel = "below" if bm[r][b] < want[b] else "above"
                rep.add("bmax", f"row {r} block {b}: bmax "
                        f"{int(bm[r][b])} {rel} the block's lane max "
                        f"{int(want[b])}" + (
                            " — the skip bound would drop docs that "
                            "belong in the top-k" if rel == "below"
                            else ""))
        rep.stats["scored_rows"] = rows
    rep.stats["rows"] = rows
    rep.stats["pad_blocks"] = n_pad_blocks
    return rep


def check_engine(engine) -> Report:
    """Whole-engine validation: :func:`check_pool_state` on the active
    allocator plus :func:`check_segment_set` (with the engine's layout
    and compaction fanout) over the frozen side, merged into one
    report.  This is what ``validate=True`` engines run at every
    rollover — scheduled or emergency — after engine-driven compaction,
    and immediately after ``recovery.restore``: a snapshot that passes
    its CRCs but encodes a structurally-broken state (tampering, a
    writer bug) must fail HERE, not at the first wrong query result."""
    rep = Report("check_engine")
    _merge(rep, check_pool_state(engine.layout,
                                 engine.segments.active.state), "active/")
    policy = getattr(engine.segments, "compaction", None)
    _merge(rep, check_segment_set(
        engine.segments, layout=engine.layout,
        fanout=policy.fanout if policy is not None else None),
        "segments/")
    return rep


def check_serve(loop) -> Report:
    """Conservation checks over a :class:`repro.core.serve.ServeLoop`'s
    accounting: every submission is exactly one of rejected / served /
    still queued / in flight, per-level service counts sum to the served
    total, every rejection carried a positive retry-after (a zero one
    would be a silent drop with extra steps), and every acked ingest
    batch is exactly one of applied / finally-shed / replay-recovered /
    still queued.  ``bench_serve`` runs this after every load leg, so a
    lost request fails the bench loudly instead of flattering qps."""
    rep = Report("check_serve")
    s = loop.stats
    accounted = (s.queries_rejected + s.queries_served
                 + s.queries_aborted + loop.pending_queries
                 + loop.in_flight_queries)
    if s.queries_submitted != accounted:
        rep.add("queries", f"submitted {s.queries_submitted} != rejected "
                f"{s.queries_rejected} + served {s.queries_served} + "
                f"aborted {s.queries_aborted} + queued "
                f"{loop.pending_queries} + in-flight "
                f"{loop.in_flight_queries} — a request was silently "
                "dropped (or double-counted)")
    if sum(s.served_by_level) != s.queries_served:
        rep.add("levels", f"per-level counts {s.served_by_level} sum to "
                f"{sum(s.served_by_level)} != served {s.queries_served} "
                "— a response left without reporting its ladder rung")
    if s.rejections_without_retry_after != 0:
        rep.add("backpressure", f"{s.rejections_without_retry_after} "
                "rejection(s) carried no positive retry-after — "
                "backpressure must always tell the producer when to "
                "come back")
    ing = (s.ingest_rejected + s.ingest_applied + s.ingest_shed
           + s.ingest_recovered + loop.pending_ingest)
    if s.ingest_submitted != ing:
        rep.add("ingest", f"submitted {s.ingest_submitted} != rejected "
                f"{s.ingest_rejected} + applied {s.ingest_applied} + "
                f"shed {s.ingest_shed} + recovered {s.ingest_recovered} "
                f"+ queued {loop.pending_ingest} — an acked batch "
                "vanished without a verdict")
    rep.stats["queries_served"] = s.queries_served
    rep.stats["ingest_applied"] = s.ingest_applied
    return rep


__all__ = ["InvariantViolation", "Violation", "Report",
           "check_engine", "check_pool_state", "check_frozen_segment",
           "check_segment_set", "check_serve", "check_stacked_lists"]
