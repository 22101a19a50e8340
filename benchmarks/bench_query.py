"""Batched query execution vs the per-query host loop (PR 5 tentpole).

Earlybird's latency story is the QUERY side: newest-first traversal,
early termination, and — at scale — batching.  This suite drives one
streaming lifecycle engine (active pool + >= 3 frozen segments) and
measures:

  * queries/s at Q in {1, 16, 128}: the batched qexec path (one stacked
    dispatch for the whole batch) vs the sequential per-query oracle
    (one jitted call + one device->host sync PER SEGMENT PER QUERY);
  * top-k early-exit latency (newest-first while_loop that stops
    consuming older segments once k hits are banked) vs the full
    intersection it is bit-identical to;
  * a structural zero-host-sync check: the batched run must never call
    the per-segment host-loop helpers (counted via monkeypatching).

ASSERTS batched >= 3x sequential at Q = 128 (the CI acceptance bar on 4
forced host devices; observed ~10-30x) and that results are
bit-identical between the two paths.  Returned metrics feed
``benchmarks.run --json`` and the CI regression guard
(``benchmarks.check_regression``).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.core import analytical
from repro.core import lifecycle as lc
from repro.core.lifecycle import LifecycleEngine
from repro.core.pointers import PoolLayout
from repro.data import synth


def _build_engine(fast: bool, validate: bool = False):
    vocab = 4_000 if fast else 16_000
    docs_per_segment = 512 if fast else 2_048
    n_segments = 3          # frozen
    batch = 128
    streams = [
        synth.zipf_corpus(synth.CorpusSpec(
            vocab=vocab, n_docs=docs_per_segment, max_len=14, seed=200 + i))
        for i in range(n_segments + 1)
    ]
    seg_freqs = synth.term_freqs(streams[0], vocab)
    layout = PoolLayout(z=common.ZG,
                        slices_per_pool=analytical.slices_per_pool(
                            common.ZG, seg_freqs, slack=2.5))
    fmax = int(seg_freqs.max())
    max_slices = int(analytical.slices_needed(common.ZG, fmax)) + 2
    max_len = 1 << max(int(2 * fmax - 1).bit_length(), 3)
    # use_kernel=False: masks are bit-identical either way, and the jnp
    # path keeps the SEQUENTIAL baseline honest on CPU (the interpret-
    # mode Pallas walk would slow the oracle by another order of
    # magnitude and inflate the speedup).
    life = LifecycleEngine(layout, vocab, docs_per_segment,
                           max_slices=max_slices, max_len=max_len,
                           use_kernel=False, validate=validate)
    for i, docs in enumerate(streams):
        end = docs_per_segment if i < n_segments else docs_per_segment // 2
        for j in range(0, end, batch):
            life.ingest(docs[j: j + batch])
    assert life.stats.rollovers == n_segments
    all_freqs = sum(synth.term_freqs(d, vocab) for d in streams)
    return life, all_freqs


def _query_pool(freqs, n: int):
    """Two-term conjunctions over the hot vocabulary (the paper's
    intersection-heavy microblog shape)."""
    top = np.argsort(-freqs)
    rng = np.random.default_rng(7)
    pool = []
    for i in range(n):
        a, b = rng.integers(0, 96, size=2)
        pool.append([int(top[a]), int(top[(a + b + 1) % 96])])
    return pool


def run(fast: bool = True, validate: bool = False):
    life, freqs = _build_engine(fast, validate=validate)
    pool = _query_pool(freqs, 128)

    # structural acceptance check: the batched path must never fall back
    # to the per-segment host loop (zero per-segment np round trips).
    calls = {"n": 0}
    orig = lc.conjunctive_packed

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    lc.conjunctive_packed = counting
    try:
        out = {"frozen_segments": life.stats.rollovers}
        rows = []
        for Q in (1, 16, 128):
            qs = pool[:Q]
            # warm both paths (jit compile + stack gather outside timing)
            life.batched = True
            life.conjunctive_batch(qs)
            calls["n"] = 0
            t0 = time.perf_counter()
            batched_res = life.conjunctive_batch(qs)
            t_batched = time.perf_counter() - t0
            assert calls["n"] == 0, \
                "batched path called the per-segment host loop"

            life.batched = False
            life.conjunctive(qs[0])          # warm
            t0 = time.perf_counter()
            seq_res = [life.conjunctive(terms) for terms in qs]
            t_seq = time.perf_counter() - t0
            life.batched = True
            for g, e in zip(batched_res, seq_res):
                assert np.array_equal(g, e), "batched != sequential"
            rows.append({
                "Q": Q,
                "batched_qps": Q / t_batched,
                "sequential_qps": Q / t_seq,
                "batched_ms_per_q": t_batched / Q * 1e3,
                "sequential_ms_per_q": t_seq / Q * 1e3,
                "speedup": t_seq / t_batched,
            })
        out["rows"] = rows
        r128 = rows[-1]
        assert r128["Q"] == 128
        assert r128["speedup"] >= 3.0, (
            f"batched must be >= 3x sequential at Q=128, got "
            f"{r128['speedup']:.2f}x")
        out["batched_qps_q128"] = r128["batched_qps"]
        out["batched_ms_per_q_q128"] = r128["batched_ms_per_q"]
        out["speedup_q128"] = r128["speedup"]

        # top-k early exit vs the full intersection it must equal
        k = 10
        topk_qs = pool[:16]
        life.topk_conjunctive_batch(topk_qs, k)       # warm
        life.conjunctive_batch(topk_qs)
        t0 = time.perf_counter()
        topk_res = life.topk_conjunctive_batch(topk_qs, k)
        t_topk = time.perf_counter() - t0
        t0 = time.perf_counter()
        full_res = life.conjunctive_batch(topk_qs)
        t_full = time.perf_counter() - t0
        for g, e in zip(topk_res, full_res):
            assert np.array_equal(g, e[:k]), "early-exit top-k != full[:k]"
        out["topk_k"] = k
        out["topk_ms_per_q"] = t_topk / len(topk_qs) * 1e3
        out["full_ms_per_q"] = t_full / len(topk_qs) * 1e3
        out["topk_vs_full"] = t_full / t_topk
    finally:
        lc.conjunctive_packed = orig

    print("\n== bench_query: batched qexec vs per-query host loop "
          f"(active + {out['frozen_segments']} frozen segments) ==")
    for r in rows:
        print(f"Q={r['Q']:4d}: batched {r['batched_qps']:9.1f} q/s "
              f"({r['batched_ms_per_q']:7.2f} ms/q)  sequential "
              f"{r['sequential_qps']:9.1f} q/s  -> {r['speedup']:5.1f}x")
    print(f"top-{k} early-exit {out['topk_ms_per_q']:7.2f} ms/q vs full "
          f"{out['full_ms_per_q']:7.2f} ms/q "
          f"({out['topk_vs_full']:.2f}x), bit-identical")
    return out


if __name__ == "__main__":
    run()
