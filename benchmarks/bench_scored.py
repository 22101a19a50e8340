"""Block-max scored top-k vs exhaustive scored evaluation.

Scored retrieval ranks by summed quantized impact (min(tf, SCORE_MAX)
per query term) with ties broken newest-first.  This suite drives one
streaming lifecycle engine (active pool + >= 3 frozen segments) and
measures the block-max WAND path (``scored_topk_batch``: segment- and
128-docid-block-granular skipping against the running top-k threshold)
against the full-sort baseline it is bit-identical to
(``scored_full_batch``):

  * queries/s at Q in {1, 16, 128} for both paths;
  * the BLOCK SKIP RATE: frozen blocks whose score upper bound could
    not beat the heap threshold (never decoded) over all blocks in
    structurally-live segments — the early-termination win the paper's
    recency-only top-k cannot express.

ASSERTS top-k results == full-sort[:k] for every measured batch, a
nonzero skip rate, and top-k latency <= the full scored evaluation at
Q = 128.  Metrics feed ``benchmarks.run --json`` and the CI regression
guard (``benchmarks.check_regression``).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.core import analytical
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
            vocab=vocab, n_docs=docs_per_segment, max_len=14, seed=300 + i))
        for i in range(n_segments + 1)
    ]
    seg_freqs = synth.term_freqs(streams[0], vocab)
    layout = PoolLayout(z=common.ZG,
                        slices_per_pool=analytical.slices_per_pool(
                            common.ZG, seg_freqs, slack=2.5))
    fmax = int(seg_freqs.max())
    max_slices = int(analytical.slices_needed(common.ZG, fmax)) + 2
    max_len = 1 << max(int(2 * fmax - 1).bit_length(), 3)
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
    """Hot-vocabulary mix: half two-term conjunctions (the paper's
    intersection-heavy microblog shape), half single hot terms — long
    single-term lists fill the top-k heap fast, so low-bmax blocks
    actually face a live threshold and the skip machinery gets
    exercised."""
    top = np.argsort(-freqs)
    rng = np.random.default_rng(7)
    pool = []
    for i in range(n):
        a, b = rng.integers(0, 96, size=2)
        if i % 2:
            pool.append([int(top[a])])
        else:
            pool.append([int(top[a]), int(top[(a + b + 1) % 96])])
    return pool


def run(fast: bool = True, validate: bool = False):
    life, freqs = _build_engine(fast, validate=validate)
    pool = _query_pool(freqs, 128)
    k = 10

    out = {"frozen_segments": life.stats.rollovers, "k": k}
    rows = []
    for Q in (1, 16, 128):
        qs = pool[:Q]
        life.scored_topk_batch(qs, k)     # warm (compile + stack gather)
        life.scored_full_batch(qs)
        life.stats.scored_blocks_skipped = 0
        life.stats.scored_blocks_live = 0
        t0 = time.perf_counter()
        topk_res = life.scored_topk_batch(qs, k)
        t_topk = time.perf_counter() - t0
        skipped = life.stats.scored_blocks_skipped
        live = life.stats.scored_blocks_live
        t0 = time.perf_counter()
        full_res = life.scored_full_batch(qs)
        t_full = time.perf_counter() - t0
        for terms, (gi, gs), (ei, es) in zip(qs, topk_res, full_res):
            assert np.array_equal(gi, ei[:k]) and \
                np.array_equal(gs, es[:k]), \
                f"block-max top-k != full-sort[:k] for {terms}"
        rows.append({
            "Q": Q,
            "topk_qps": Q / t_topk,
            "full_qps": Q / t_full,
            "topk_ms_per_q": t_topk / Q * 1e3,
            "full_ms_per_q": t_full / Q * 1e3,
            "speedup": t_full / t_topk,
            "blocks_skipped": skipped,
            "blocks_live": live,
            "block_skip_rate": skipped / max(live, 1),
        })
    out["rows"] = rows
    r128 = rows[-1]
    assert r128["Q"] == 128
    assert r128["blocks_live"] > 0, "no frozen blocks were walked"
    assert r128["block_skip_rate"] > 0, (
        "block-max bounds never skipped a block — the skip plumbing "
        "is dead")
    assert r128["speedup"] >= 1.0, (
        f"scored top-k must not be slower than full scored evaluation "
        f"at Q=128, got {r128['speedup']:.2f}x")
    out["topk_qps_q128"] = r128["topk_qps"]
    out["topk_ms_per_q_q128"] = r128["topk_ms_per_q"]
    out["speedup_q128"] = r128["speedup"]
    out["block_skip_rate"] = r128["block_skip_rate"]

    print("\n== bench_scored: block-max WAND top-k vs full scored "
          f"evaluation (active + {out['frozen_segments']} frozen "
          "segments) ==")
    for r in rows:
        print(f"Q={r['Q']:4d}: top-{k} {r['topk_qps']:9.1f} q/s "
              f"({r['topk_ms_per_q']:7.2f} ms/q)  full "
              f"{r['full_qps']:9.1f} q/s  -> {r['speedup']:5.2f}x  "
              f"skip {r['blocks_skipped']}/{r['blocks_live']} blocks "
              f"({r['block_skip_rate']:.1%})")
    return out


if __name__ == "__main__":
    run()
