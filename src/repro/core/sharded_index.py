"""Document-sharded SPMD index + batched query engine (Earlybird scale-out).

The paper's production deployment document-partitions the tweet stream
across machines; each partition runs an independent slice-pool allocator
and queries fan out to every partition, whose reverse-chronological hit
lists are merged at the front end (paper §3).  This module is that
architecture on one JAX mesh:

  * **Partitioning.**  Global docid ``d`` lives on shard ``d % S`` with
    shard-local docid ``d // S``.  Round-robin interleave keeps every
    shard's local docids dense and ascending, so the single-shard
    allocator, materializer and set ops run UNCHANGED per shard — the
    only new code is the partition/merge shell.
  * **State.**  One :class:`~repro.core.slicepool.PoolState` per shard,
    stacked on a leading ``[S, ...]`` axis and sharded over the logical
    ``"docs"`` axis (``repro.dist.sharding``; data axes of the mesh).
  * **Ingest.**  A ``shard_map`` over the docid-partitioned stream: each
    device flattens its own ``[B/S, L]`` doc block and runs the
    batch-parallel bulk allocator on its private pools
    (``bulk_ingest=False`` for the per-posting scan oracle).  No
    cross-shard traffic at all.
  * **Query.**  Batched (vmap over queries) evaluation inside one
    ``shard_map``: conjunctions run the Pallas ``postings_intersect``
    kernel per shard, shard-local descending lists are translated to
    global docids (``g = local * S + shard``), ``all_gather``-ed over
    the ``docs`` axis and merged with a vectorised top-k merge
    (:func:`merge_desc`).  Shards own disjoint docid residue classes, so
    the merged list is duplicate-free by construction and bit-identical
    to the single-device engine (tests/test_spmd_equivalence.py).
  * **Rollover.**  When the active sharded segment fills, every shard is
    frozen to its own compressed read-only CSR segment (global docids,
    PForDelta-lite blocks) — :class:`ShardedFrozenSegment`.
  * **Compaction.**  :meth:`ShardedSegmentSet.compact` merges adjacent
    frozen segments shard-by-shard (shard ``s`` of the merged segment
    is the CSR merge of each member's shard ``s``); residue-class
    partitioning survives because ``docs_per_segment`` is a multiple of
    ``S``.  With a :class:`~repro.core.segments.CompactionPolicy` the
    cascade runs at every rollover, exactly as in the single-device
    :class:`~repro.core.segments.SegmentSet` — G = O(log N).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import postings as post
from repro.core import query as q
from repro.core import segments as seg_mod
from repro.core import slicepool
from repro.core.index import gather_start_pools, make_flattener
from repro.core.pointers import PoolLayout
from repro.dist import collectives as coll
from repro.dist import sharding as shd

INVALID = q.INVALID
DOCS_AXIS = "docs"  # logical name of the document-partition axis


# ---------------------------------------------------------------------------
# Mesh plumbing
# ---------------------------------------------------------------------------
def make_doc_mesh(n_shards: int):
    """A 1-axis mesh over ``n_shards`` (possibly emulated) devices plus
    the default rules table (``docs -> data axes``)."""
    mesh = coll.host_mesh((n_shards,), ("data",))
    return mesh, shd.default_rules(mesh)


def _doc_axes(rules: shd.Rules):
    axes = rules.axes(DOCS_AXIS)
    if not axes:
        raise ValueError(
            f"rules table maps {DOCS_AXIS!r} to no mesh axis; the sharded "
            f"index needs a docs-partition axis (see dist.sharding)")
    return axes


def _dim(axes):
    return axes[0] if len(axes) == 1 else axes


def _num_shards(mesh: Mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _shard_index(mesh: Mesh, axes):
    """Row-major linear shard id inside a shard_map body — matches the
    block position of this device's slice of a ``P(axes, ...)`` input."""
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _state_specs(d) -> slicepool.PoolState:
    return slicepool.PoolState(
        heap=P(d, None), watermark=P(d, None),
        tail=P(d, None), freq=P(d, None), overflow=P(d),
        free_list=P(d, None), free_count=P(d, None))


def _squeeze0(tree):
    return jax.tree.map(lambda x: x[0], tree)


def _expand0(tree):
    return jax.tree.map(lambda x: x[None], tree)


# ---------------------------------------------------------------------------
# Docid translation + shard-list merge
# ---------------------------------------------------------------------------
def local_to_global(ids, shard, n_shards: int):
    """Map shard-local docids to global (``g = local * S + shard``),
    preserving order and INVALID padding."""
    g = ids * jnp.uint32(n_shards) + jnp.uint32(shard)
    return jnp.where(ids == INVALID, INVALID, g)


def engine_max_len(shard_fmax: int) -> int:
    """Per-shard engine list width for an observed max term frequency:
    next power of two (floor 8, matching the kernel's minimum tile)."""
    return 1 << max(int(shard_fmax - 1).bit_length(), 3)


def merge_desc(flat_desc):
    """Vectorised merge of concatenated descending INVALID-padded lists.

    One sort on a flipped key (``INVALID - 1 - x`` for valid entries,
    INVALID fixed) yields valid docids descending at the front and all
    INVALID padding at the back — no loops, vmap-safe.  Duplicates are
    preserved (shards own disjoint residue classes, so the sharded
    engine never produces any).
    """
    x = flat_desc.astype(jnp.uint32)
    key = jnp.where(x == INVALID, INVALID, INVALID - jnp.uint32(1) - x)
    key = jnp.sort(key)
    return jnp.where(key == INVALID, INVALID, INVALID - jnp.uint32(1) - key)


def merge_desc_scored(flat_desc, flat_scores):
    """:func:`merge_desc` with a parallel int32 score array carried
    through the sort (one stable single-key ``lax.sort`` instead of the
    key-only ``jnp.sort``): returns ``(ids, scores)`` with valid docids
    descending at the front, INVALID / 0 padding at the back."""
    x = flat_desc.astype(jnp.uint32)
    key = jnp.where(x == INVALID, INVALID, INVALID - jnp.uint32(1) - x)
    _, ids, scs = jax.lax.sort((key, x, flat_scores), num_keys=1,
                               is_stable=True)
    return ids, scs


def topk_merge_desc(lists_desc, ns, k: Optional[int] = None):
    """Merge per-shard descending lists ``[S, W]`` (counts ``ns[S]``)
    into one descending list; optionally truncated to the newest ``k``.

    This is the front-end merge of the paper's fan-out: shard hit lists
    arrive newest-first and the union is re-ranked by recency.
    Returns ``(desc, n_total)``.
    """
    merged = merge_desc(lists_desc.reshape(-1))
    n = jnp.sum(jnp.asarray(ns).astype(jnp.int32))
    if k is not None:
        merged = merged[:k]
        n = jnp.minimum(n, k)
    return merged, n


# ---------------------------------------------------------------------------
# Sharded active segment (ingest)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ShardedActiveSegment:
    """Document-sharded :class:`~repro.core.index.ActiveSegment`.

    ``state`` leaves carry a leading shard axis ``[S, ...]``; ingest
    batches must be a multiple of S documents so the round-robin
    partition assigns every shard the same local docid range (global
    docids stay identical to an unsharded ingest of the same stream).
    """
    layout: PoolLayout
    vocab_size: int
    mesh: Mesh
    rules: Optional[shd.Rules] = None
    max_docs: int = post.MAX_DOC
    state: slicepool.PoolState = None
    next_docid: int = 0
    bulk_ingest: bool = True

    def __post_init__(self):
        if self.rules is None:
            self.rules = shd.default_rules(self.mesh)
        self._axes = _doc_axes(self.rules)
        self.num_shards = _num_shards(self.mesh, self._axes)
        if self.state is None:
            self.state = slicepool.init_sharded_state(
                self.layout, self.vocab_size, self.num_shards)
        self._ingest = _make_sharded_ingest(
            self.layout, self.vocab_size, self.mesh, self._axes,
            bulk_ingest=self.bulk_ingest)
        # default SP(z0) table, built once — ingest is the streaming hot
        # path and must not allocate a vocab-sized buffer per batch
        self._zero_table = jnp.zeros((self.vocab_size,), jnp.uint32)
        self._poisoned = False

    @property
    def is_full(self) -> bool:
        return self.next_docid >= self.max_docs

    def _poison_if_donated(self) -> None:
        """Same contract as
        :meth:`repro.core.index.ActiveSegment._poison_if_donated`: after
        a failed (possibly donating) ingest dispatch, mark the segment
        poisoned if any state buffer was consumed, so later uses fail
        loudly at the cause instead of with an opaque deleted-buffer
        error."""
        leaves = jax.tree_util.tree_leaves(self.state)
        if any(getattr(leaf, "is_deleted", lambda: False)()
               for leaf in leaves):
            self._poisoned = True

    def _check_poisoned(self) -> None:
        if self._poisoned:
            raise RuntimeError(
                "ShardedActiveSegment state was donated to an ingest "
                "dispatch that failed: the buffers are gone and the "
                "segment is poisoned. Rebuild the segment (or recover "
                "from a snapshot + journal, see repro.core.recovery).")

    def ingest(self, docs: jax.Array,
               term_start_pools: Optional[jax.Array] = None) -> int:
        """Index ``docs`` (int32[B, L], -1-padded, B % S == 0)."""
        self._check_poisoned()
        S = self.num_shards
        batch, L = docs.shape
        if batch % S:
            raise ValueError(
                f"batch {batch} not a multiple of {S} shards; pad the "
                f"arrival batch (round-robin docid partition needs equal "
                f"shard blocks)")
        assert self.next_docid % S == 0
        # doc j (global docid base+j) -> shard j % S, local row j // S.
        by_shard = jnp.transpose(
            docs.reshape(batch // S, S, L), (1, 0, 2))
        base_local = jnp.uint32(self.next_docid // S)
        table = (self._zero_table if term_start_pools is None
                 else jnp.asarray(term_start_pools, jnp.uint32))
        try:
            self.state = self._ingest(self.state, by_shard, base_local, table)
        except BaseException:
            self._poison_if_donated()
            raise
        self.next_docid += batch
        return batch

    def term_freqs(self) -> np.ndarray:
        """Global per-term frequency (sum over shards)."""
        return np.asarray(self.state.freq).sum(axis=0)

    def memory_slots_used(self) -> int:
        return int(slicepool.memory_slots_used(self.layout, self.state))

    def shard_slots_used(self) -> np.ndarray:
        return slicepool.shard_slots_used(self.layout, self.state)

    def check_health(self) -> None:
        self._check_poisoned()
        if bool(np.asarray(self.state.overflow).any()):
            raise MemoryError(
                "slice pools exhausted on at least one shard; raise "
                "slices_per_pool in the layout")


def _make_sharded_ingest(layout: PoolLayout, vocab_size: int,
                         mesh: Mesh, axes, bulk_ingest: bool = True):
    """shard_map ingest: every device runs the (bulk, by default)
    allocator on its own doc block and pool slice — the batch-parallel
    sort/alloc/scatter pipeline is shard-local throughout, so ingest
    stays zero-communication exactly like the scan path it replaces."""
    inner = (slicepool.make_bulk_ingest_fn(layout, vocab_size)
             if bulk_ingest else
             slicepool.make_ingest_fn(layout, vocab_size))
    flatten = make_flattener()
    d = _dim(axes)
    sspec = _state_specs(d)

    def body(state, docs, base_local, table):
        st = _squeeze0(state)
        terms, plist, valid = flatten(docs[0], base_local)
        start_pools = gather_start_pools(table, terms, vocab_size)
        st = inner(st, terms, plist, start_pools, valid)
        return _expand0(st)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(sspec, P(d, None, None), P(), P(None)),
        out_specs=sspec, check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Batched sharded query engine
# ---------------------------------------------------------------------------
class ShardedQueryEngine(NamedTuple):
    """Batched multi-query evaluation over a sharded PoolState.

    All callables take query BATCHES (leading ``Q`` axis) and return
    ``(desc uint32[Q, S * W], n int32[Q])`` — globally-descending
    docids, INVALID-padded, duplicate-free — where ``W`` is the
    per-shard list width: ``max_len`` for conjunctive/phrase and
    ``max_query_len * max_len`` for disjunctive (unions grow past one
    term's list, so they are never truncated to it).
    """
    conjunctive: Callable       # (state, terms[Q, max_q], n_terms[Q])
    disjunctive: Callable       # (state, terms[Q, max_q], n_terms[Q])
    phrase: Callable            # (state, t1[Q], t2[Q])
    topk_conjunctive: Callable  # (state, terms, n_terms, k) -> ([Q, k], n)
    conjunctive_scored: Callable  # (state, terms, n_terms) ->
                                #   (desc, scores int32, n): quantized
                                #   impact sums, lanes doc-aligned
    num_shards: int
    local: q.QueryEngine        # the per-shard single-device engine


def make_sharded_engine(layout: PoolLayout, mesh: Mesh,
                        max_slices: int, max_len: int,
                        max_query_len: int = 8, *,
                        rules: Optional[shd.Rules] = None,
                        use_kernel: bool = True,
                        interpret: Optional[bool] = None
                        ) -> ShardedQueryEngine:
    """Build the batched sharded engine.

    ``max_len`` bounds the PER-SHARD materialised list; merged outputs
    are ``S * max_len`` wide.  ``use_kernel`` routes shard-local
    conjunctions through the Pallas ``postings_intersect`` kernel.
    """
    rules = rules or shd.default_rules(mesh)
    axes = _doc_axes(rules)
    S = _num_shards(mesh, axes)
    local = q.make_engine(layout, max_slices, max_len, max_query_len,
                          use_kernel=use_kernel, interpret=interpret)
    d = _dim(axes)
    sspec = _state_specs(d)

    def _sharded(local_asc_fn, n_qargs):
        """Wrap a per-shard ascending-list query fn into the fan-out/
        merge shell: vmap over queries, all_gather + top-k merge over
        shards."""
        def body(state, *qargs):
            st = _squeeze0(state)
            sid = _shard_index(mesh, axes)

            def one(*row):
                asc, n = local_asc_fn(st, *row)
                g = local_to_global(asc, sid, S)
                return q.asc_to_desc(g, n), n

            desc, n = jax.vmap(one)(*qargs)         # [Q, max_len], [Q]
            gath = coll.all_gather(desc, DOCS_AXIS, axis=1, rules=rules)
            n_tot = coll.psum(n, DOCS_AXIS, rules=rules)
            merged = jax.vmap(merge_desc)(gath)     # [Q, S * max_len]
            return merged, n_tot

        return jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(sspec,) + (P(),) * n_qargs,
            out_specs=(P(), P()), check_vma=False))

    conjunctive = _sharded(local.conjunctive_asc, 2)
    disjunctive = _sharded(local.disjunctive_asc, 2)
    phrase = _sharded(local.phrase_asc, 2)

    def topk_conjunctive(state, terms, n_terms, k: int):
        desc, n = conjunctive(state, terms, n_terms)
        return desc[:, :k], jnp.minimum(n, k)

    def scored_body(state, terms, n_terms):
        # scored fan-out: the score lanes travel with their docids
        # through the flip, the all_gather and the stable merge sort, so
        # lane i of (ids, scores) always refers to one document.
        st = _squeeze0(state)
        sid = _shard_index(mesh, axes)

        def one(trow, nt):
            asc, sc, n = local.conjunctive_scored_asc(st, trow, nt)
            g = local_to_global(asc, sid, S)
            return (q.asc_to_desc(g, n),
                    q.flip_valid(sc, n, jnp.int32(0)), n)

        desc, dsc, n = jax.vmap(one)(terms, n_terms)
        gath = coll.all_gather(desc, DOCS_AXIS, axis=1, rules=rules)
        gsc = coll.all_gather(dsc, DOCS_AXIS, axis=1, rules=rules)
        n_tot = coll.psum(n, DOCS_AXIS, rules=rules)
        ids, scs = jax.vmap(merge_desc_scored)(gath, gsc)
        return ids, scs, n_tot

    conjunctive_scored = jax.jit(shard_map(
        scored_body, mesh=mesh,
        in_specs=(sspec, P(), P()),
        out_specs=(P(), P(), P()), check_vma=False))

    return ShardedQueryEngine(conjunctive, disjunctive, phrase,
                              topk_conjunctive, conjunctive_scored, S,
                              local)


# ---------------------------------------------------------------------------
# Sharded segment lifecycle
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ShardedFrozenSegment:
    """One rollover's worth of per-shard frozen CSR segments.

    Each shard freezes independently (global docids baked in via
    ``freeze_state(docid_map=...)``); queries merge per-shard descending
    lists exactly like the live engine does.
    """
    shards: List[seg_mod.FrozenSegment]
    n_docs: int
    doc_base: int = 0
    # compaction tier, exactly as on FrozenSegment: 0 from rollover,
    # max(member tiers) + 1 after a merge (see ShardedSegmentSet.compact)
    tier: int = 0

    def docids_desc(self, term: int) -> np.ndarray:
        parts = [fz.docids_desc(term) for fz in self.shards]
        cat = np.concatenate(parts) if parts else np.zeros(0, np.uint32)
        return np.sort(cat)[::-1]  # disjoint residue classes: no dedup

    def docid_bounds(self, term: int):
        """O(S) summary ``(n_postings, first_gid, last_gid)`` over all
        shards (shards store GLOBAL-within-segment docids, so min/max
        across shards bound the merged list)."""
        n, first, last = 0, 0, 0
        for fz in self.shards:
            c, lo, hi = fz.docid_bounds(term)
            if c:
                first = lo if n == 0 else min(first, lo)
                last = hi if n == 0 else max(last, hi)
                n += c
        return n, first, last

    def term_freqs(self) -> np.ndarray:
        return np.sum([fz.term_freqs() for fz in self.shards], axis=0)

    @property
    def total_postings(self) -> int:
        return sum(fz.total_postings for fz in self.shards)

    def compress(self):
        """Per-shard PForDelta-lite compression; returns (codecs_per_
        shard, total_bytes)."""
        codecs, total = [], 0
        for fz in self.shards:
            c, b = seg_mod.compress_segment(fz)
            codecs.append(c)
            total += b
        return codecs, total


class ShardedSegmentSet:
    """Active sharded segment + frozen per-shard history (paper §3.1)."""

    def __init__(self, layout: PoolLayout, vocab_size: int,
                 docs_per_segment: int, mesh: Mesh,
                 rules: Optional[shd.Rules] = None, max_segments: int = 12,
                 bulk_ingest: bool = True,
                 compaction: Optional[seg_mod.CompactionPolicy] = None):
        self.layout = layout
        self.vocab_size = vocab_size
        self.mesh = mesh
        self.rules = rules or shd.default_rules(mesh)
        self.docs_per_segment = docs_per_segment
        self.max_segments = max_segments
        self.bulk_ingest = bulk_ingest
        self.compaction = compaction
        self.frozen: List[ShardedFrozenSegment] = []
        self.n_rollovers = 0
        self.n_compactions = 0
        self._doc_base = 0
        self._hist_freqs: Optional[np.ndarray] = None
        self.active = self._new_active()
        if docs_per_segment % self.active.num_shards:
            raise ValueError("docs_per_segment must be a multiple of the "
                             "shard count")

    def _new_active(self, state=None) -> ShardedActiveSegment:
        return ShardedActiveSegment(
            self.layout, self.vocab_size, self.mesh, rules=self.rules,
            max_docs=self.docs_per_segment, state=state,
            bulk_ingest=self.bulk_ingest)

    @property
    def num_shards(self) -> int:
        return self.active.num_shards

    def ingest(self, docs, **kw) -> None:
        self.active.ingest(docs, **kw)
        if self.active.is_full:
            self.rollover()

    def rollover(self) -> Optional[ShardedFrozenSegment]:
        """Freeze every shard of the active segment into its own
        read-only CSR segment with GLOBAL docids, then recycle: each
        shard's slices go back on that shard's free lists
        (``slicepool.release_slices`` on the stacked state), so the next
        active segment reuses them instead of bumping the watermark.
        An empty active segment is a no-op returning None, matching
        :meth:`~repro.core.segments.SegmentSet.rollover`."""
        if self.active.next_docid == 0:
            return None
        with jax.profiler.TraceAnnotation("segments.rollover",
                                          docs=self.active.next_docid):
            seg = self.active
            S = seg.num_shards
            heap = np.asarray(seg.state.heap)
            tail = np.asarray(seg.state.tail)
            freq = np.asarray(seg.state.freq)
            local_docs = seg.next_docid // S
            shards = [
                seg_mod.freeze_state(
                    self.layout, heap[s], tail[s], freq[s],
                    n_docs=local_docs, doc_base=self._doc_base,
                    docid_map=lambda ids, s=s: (ids * np.uint32(S)
                                                + np.uint32(s)))
                for s in range(S)
            ]
            fz = ShardedFrozenSegment(shards, n_docs=seg.next_docid,
                                      doc_base=self._doc_base)
            # H(t) snapshot: the freqs of THIS rollover, taken before any
            # compaction can merge the segment into a multi-rollover tier
            # (history_freqs must keep meaning "the last rollover").
            self._hist_freqs = fz.term_freqs()
            self.frozen.append(fz)
            self.n_rollovers += 1
            if len(self.frozen) > self.max_segments - 1:
                self.frozen.pop(0)  # oldest segment retired (bounded set)
            self._doc_base += seg.next_docid
            released = slicepool.release_slices(
                self.layout, seg.state, [sh.freed_slices for sh in shards])
            self.active = self._new_active(state=released)
            self._apply_compaction()
        return fz

    def compact(self, k: int, *, start: int = 0
                ) -> Optional[ShardedFrozenSegment]:
        """Merge the ``k`` oldest frozen segments (or ``k`` adjacent
        ones from ``start``) shard-by-shard: shard ``s`` of the merged
        segment is the CSR merge of every window member's shard ``s``.
        Members store global-within-segment docids (``g = local * S +
        shard``), so rebasing by each member's offset inside the merged
        range keeps residue classes intact and the per-shard streams in
        ascending docid order — exactly the single-device merge, S
        times.  Clamped/no-op semantics match
        :meth:`~repro.core.segments.SegmentSet.compact`."""
        k = min(int(k), len(self.frozen) - start)
        if k < 2:
            return None
        with jax.profiler.TraceAnnotation("segments.compact", k=k):
            window = self.frozen[start: start + k]
            base, n_docs, offs = seg_mod._adjacent_window(window)
            tier = max(int(fz.tier) for fz in window) + 1
            S = len(window[0].shards)
            shards = [
                seg_mod._merge_csr([fz.shards[s] for fz in window], offs,
                                   n_docs=n_docs // S, doc_base=base,
                                   tier=tier)
                for s in range(S)
            ]
            merged = ShardedFrozenSegment(shards, n_docs=n_docs,
                                          doc_base=base, tier=tier)
            self.frozen[start: start + k] = [merged]
            self.n_compactions += 1
        return merged

    def _apply_compaction(self) -> None:
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

    def search_term_desc(self, term: int, engine: ShardedQueryEngine,
                         limit: int) -> np.ndarray:
        """Global docids, descending (newest segment first).  The frozen
        walk stops as soon as ``limit`` docids are collected — older
        segments are never materialised past the cut."""
        terms = jnp.zeros((1, 8), jnp.uint32).at[0, 0].set(term)
        desc, n = engine.conjunctive(self.active.state, terms,
                                     jnp.ones((1,), jnp.int32))
        out = [np.asarray(desc[0])[: int(n[0])].astype(np.int64)
               + self._doc_base]
        total = out[0].size
        for fz in reversed(self.frozen):
            if total >= limit:
                break
            ids = fz.docids_desc(term).astype(np.int64) + fz.doc_base
            out.append(ids)
            total += ids.size
        return np.concatenate(out)[:limit]
