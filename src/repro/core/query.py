"""Boolean query evaluation over slice-pool postings (paper §3.1, §8).

Earlybird semantics: postings are traversed newest-first; conjunctions are
postings intersections; disjunctions are unions; phrase queries are
intersections with positional constraints; results are returned in reverse
chronological order (descending docid).  No relevance scoring (paper §3).

TPU adaptation (DESIGN.md §2): the paper's linear merge with early exit
becomes (a) a chain walk that flattens each term's slice chain into a flat
address vector, then (b) fully-vectorised sorted-set operations
(`searchsorted` membership) — data-parallel instead of pointer-at-a-time.

Internal list representation: ASCENDING uint32 arrays, deduped, padded at
the end with INVALID (0xFFFFFFFF, which sorts above every valid docid, so
padded arrays remain sorted and searchsorted-safe).  Public results are
flipped to descending (reverse-chronological) at the API edge.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import postings as post
from repro.core import slicepool
from repro.core.pointers import PoolLayout
from repro.kernels.segment_intersect import SCORE_MAX

INVALID = jnp.uint32(0xFFFFFFFF)
FACTORY_CACHE_SIZE = slicepool.FACTORY_CACHE_SIZE


def _compact(values, keep, fill=INVALID):
    """Stable-compact ``values[keep]`` to the front; pad with ``fill``."""
    n = values.shape[0]
    idx = jnp.cumsum(keep.astype(jnp.int32)) - 1
    out = jnp.full((n,), fill, values.dtype)
    out = out.at[jnp.where(keep, idx, n)].set(values, mode="drop")
    return out, jnp.sum(keep.astype(jnp.int32))


def flip_valid(xs, n, fill):
    """Reverse the valid prefix of ``xs``; pad with ``fill`` past ``n``.
    The alignment-preserving flip: applying it to a docid array and a
    parallel score array keeps lane i of each referring to one doc."""
    m = xs.shape[0]
    idx = n - 1 - jnp.arange(m)
    vals = xs[jnp.clip(idx, 0, m - 1)]
    return jnp.where(jnp.arange(m) < n, vals, fill)


def desc_to_asc(desc, n):
    """Flip the valid prefix of a descending array; INVALID padding at end."""
    return flip_valid(desc, n, INVALID)


def asc_to_desc(asc, n):
    return flip_valid(asc, n, INVALID)  # same index reversal


def dedup_asc(xs):
    """Remove duplicates from an ascending INVALID-padded array."""
    prev = jnp.concatenate([jnp.array([INVALID], xs.dtype), xs[:-1]])
    keep = (xs != INVALID) & (xs != prev)
    return _compact(xs, keep)


def member_asc(xs, ys):
    """For each x in xs, is x present in ascending INVALID-padded ys?"""
    pos = jnp.searchsorted(ys, xs)
    pos = jnp.minimum(pos, ys.shape[0] - 1)
    return (ys[pos] == xs) & (xs != INVALID)


def intersect_asc(a, na, b, nb):
    keep = member_asc(a, b)
    return _compact(a, keep)


def union_asc(a, na, b, nb):
    """Union of two ascending INVALID-padded lists, sized to hold BOTH
    inputs (``|a| + |b|`` wide) — a union can be bigger than either
    operand, so truncating to ``|a|`` silently dropped docids whenever
    ``|A ∪ B| > |a|``.  Callers that need a narrower result slice it
    down explicitly with their own capacity argument."""
    merged = jnp.sort(jnp.concatenate([a, b]))
    return dedup_asc(merged)


class QueryEngine(NamedTuple):
    """Jitted query functions bound to a (layout, max_slices, max_len).

    The ``*_asc`` members return the INTERNAL ascending INVALID-padded
    representation (un-jitted, composable under vmap/shard_map — the
    sharded engine merges shard-local ascending lists before flipping);
    the plain members are the jitted public descending API.
    """
    postings_desc: callable     # (state, term) -> (uint32[max_len], n)
    docids_asc: callable        # (state, term) -> (uint32[max_len], n)
    conjunctive: callable       # (state, terms[max_q], n_terms) -> (desc, n)
    disjunctive: callable       # -> (desc[max_q * max_len], n): unions
                                #    GROW, so the result is sized to hold
                                #    every term's full list (no silent
                                #    truncation of union members)
    phrase: callable            # (state, t1, t2) -> (desc ids, n)
    read_all: callable          # (state, terms[max_q], n_terms) -> checksum
    topk_conjunctive: callable  # (state, terms, n_terms, k) -> (desc[k], n)
    conjunctive_asc: callable   # (state, terms, n_terms) -> (asc, n)
    disjunctive_asc: callable   # (state, terms, n_terms) -> (asc, n)
    phrase_asc: callable        # (state, t1, t2) -> (asc ids, n)
    conjunctive_scored_asc: callable  # (state, terms, n_terms) ->
                                #    (asc, score int32, n): quantized
                                #    impact sum min(tf, SCORE_MAX) per term


@functools.lru_cache(maxsize=FACTORY_CACHE_SIZE)
def make_engine(layout: PoolLayout, max_slices: int, max_len: int,
                max_query_len: int = 8, *, use_kernel: bool = False,
                interpret: bool = None) -> QueryEngine:
    """Build a query engine.

    ``use_kernel=True`` routes conjunctive intersections through the
    Pallas ``postings_intersect`` kernel (blocked two-pointer merge on
    the VPU) instead of the jnp ``searchsorted`` membership test; both
    yield bit-identical masks, so results do not depend on the flag.
    ``interpret`` is forwarded to the kernel (None = auto: interpret
    everywhere but real TPU backends).

    Memoised per (layout, max_slices, max_len, max_query_len, use_kernel,
    interpret) the same way ``make_bulk_ingest_fn`` is, so rollover's
    fresh engines (and the batched qexec path building its own jnp
    engine) reuse jit caches instead of recompiling every query shape.
    """
    materialize = slicepool.make_materializer(layout, max_slices, max_len)

    if use_kernel:
        from repro.kernels import ops

        def _intersect(a, na, b, nb):
            mask = ops.intersect_mask(a, b, interpret=interpret)
            return _compact(a, mask.astype(bool))
    else:
        _intersect = intersect_asc

    @jax.jit
    def postings_desc(state, term):
        return materialize(state, term)

    @jax.jit
    def docids_asc(state, term):
        plist, n = materialize(state, term)  # reverse-chronological
        ids = post.docid(plist)
        ids = jnp.where(jnp.arange(max_len) < n, ids, INVALID)
        asc = desc_to_asc(ids, n)  # ascending docids, may have duplicates
        return dedup_asc(asc)

    def _gather_terms(state, terms):
        return jax.vmap(lambda t: docids_asc(state, t))(terms)

    def _fold_terms(setop, state, terms, n_terms):
        ids, ns = _gather_terms(state, terms)

        def body(i, carry):
            acc, na = carry
            use = i < n_terms
            nxt, nn = setop(acc, na, ids[i], ns[i])
            acc = jnp.where(use, nxt, acc)
            na = jnp.where(use, nn, na)
            return acc, na

        return jax.lax.fori_loop(1, max_query_len, body, (ids[0], ns[0]))

    def conjunctive_asc(state, terms, n_terms):
        return _fold_terms(_intersect, state, terms, n_terms)

    def disjunctive_asc(state, terms, n_terms):
        # a union GROWS: the result is sized to hold max_query_len whole
        # per-term lists.  One flatten + sort + dedup over every active
        # term's list equals the pairwise union fold, with a single sort.
        ids, ns = _gather_terms(state, terms)   # [max_q, max_len]
        live = jnp.arange(max_query_len)[:, None] < n_terms
        flat = jnp.where(live, ids, INVALID).reshape(-1)
        return dedup_asc(jnp.sort(flat))

    @jax.jit
    def conjunctive(state, terms, n_terms):
        acc, na = conjunctive_asc(state, terms, n_terms)
        return asc_to_desc(acc, na), na

    @jax.jit
    def disjunctive(state, terms, n_terms):
        acc, na = disjunctive_asc(state, terms, n_terms)
        return asc_to_desc(acc, na), na

    def phrase_asc(state, t1, t2):
        """Docs where t2 appears at position(t1) + 1 (paper: intersection
        with positional constraints).  Works on raw packed postings: the
        posting uint32 orders by (docid, position)."""
        p1, n1 = materialize(state, t1)
        p2, n2 = materialize(state, t2)
        p1 = jnp.where(jnp.arange(max_len) < n1, p1, INVALID)
        p2 = jnp.where(jnp.arange(max_len) < n2, p2, INVALID)
        a1 = desc_to_asc(p1, n1)
        a2 = desc_to_asc(p2, n2)
        want = jnp.where(a1 != INVALID, a1 + jnp.uint32(1), INVALID)
        hit = member_asc(want, a2)
        ids = jnp.where(hit, post.docid(a1), INVALID)
        ids = jnp.sort(ids)  # ascending, INVALID at end
        return dedup_asc(ids)

    @jax.jit
    def phrase(state, t1, t2):
        asc, n = phrase_asc(state, t1, t2)
        return asc_to_desc(asc, n), n

    @jax.jit
    def read_all(state, terms, n_terms):
        """End-to-end read of all postings for all query terms — the
        paper's C_T* microbenchmark body.  Returns a checksum so XLA
        cannot dead-code the reads."""
        def body(i, acc):
            plist, n = materialize(state, terms[i])
            ok = i < n_terms
            s = jnp.sum(plist.astype(jnp.uint32))
            return acc + jnp.where(ok, s, jnp.uint32(0))
        return jax.lax.fori_loop(0, max_query_len, body, jnp.uint32(0))

    @functools.partial(jax.jit, static_argnums=(3,))
    def topk_conjunctive(state, terms, n_terms, k):
        desc, n = conjunctive(state, terms, n_terms)
        return desc[:k], jnp.minimum(n, k)

    def conjunctive_scored_asc(state, terms, n_terms):
        """Conjunctive docids plus their summed quantized impacts
        (min(tf, SCORE_MAX) per live term).  tf per candidate is the
        occurrence count in the term's raw postings — two searchsorted
        bounds over the sorted docid lanes, no per-doc loop."""
        acc, na = conjunctive_asc(state, terms, n_terms)

        def body(i, score):
            use = i < n_terms
            plist, n = materialize(state, terms[i])
            ids = post.docid(plist)
            ids = jnp.sort(jnp.where(jnp.arange(max_len) < n, ids,
                                     INVALID))
            lo = jnp.searchsorted(ids, acc, side="left")
            hi = jnp.searchsorted(ids, acc, side="right")
            imp = jnp.minimum((hi - lo).astype(jnp.int32), SCORE_MAX)
            return score + jnp.where(use & (acc != INVALID), imp, 0)

        score = jax.lax.fori_loop(0, max_query_len, body,
                                  jnp.zeros(acc.shape, jnp.int32))
        return acc, score, na

    return QueryEngine(postings_desc, docids_asc, conjunctive,
                       disjunctive, phrase, read_all, topk_conjunctive,
                       conjunctive_asc, disjunctive_asc, phrase_asc,
                       conjunctive_scored_asc)
