"""Streaming lifecycle: sustained ingest across segment rollovers with
slice reclamation, tiered compaction, and unified query latency.

The paper's Goldilocks tension only materialises under a LIVE stream:
segments fill, freeze into read-only CSR, and — with the free-list
allocator — hand their slices back for the next segment.  This suite
drives N rollovers and reports:

  * sustained docs/s INCLUDING freeze/reclaim pauses (the lifecycle
    cost, not just steady-state scan ingest);
  * the heap high-water mark after every rollover — with reclamation it
    must stay bounded near one segment's demand (asserted), where a
    bump-only allocator would grow linearly with segment count;
  * unified query latency over the active pool + all frozen segments
    (conjunctions decode the gap blocks, then run the blocked intersect
    Pallas kernel);
  * the frozen-segment count G under geometric compaction
    (``CompactionPolicy(fanout=2)``) through >= 8 rollovers — asserted
    equal to popcount(#rollovers), i.e. G = O(log N), where the
    uncompacted engine's G grows linearly with stream age; queries over
    the compacted engine are asserted bit-identical to the uncompacted
    one.

Returned metrics feed ``benchmarks.run --json`` (the CI artifact).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.core import analytical
from repro.core.lifecycle import LifecycleEngine
from repro.core.pointers import PoolLayout
from repro.core.segments import CompactionPolicy
from repro.data import synth


def run(fast: bool = True, validate: bool = False):
    vocab = 5_000 if fast else 20_000
    docs_per_segment = 1_024 if fast else 4_096
    n_segments = 4 if fast else 6
    batch = 256

    # per-segment streams: same Zipf shape, fresh draws (realistic churn)
    streams = [
        synth.zipf_corpus(synth.CorpusSpec(
            vocab=vocab, n_docs=docs_per_segment, max_len=14, seed=100 + i))
        for i in range(n_segments)
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
                           validate=validate)
    life.ingest(streams[0][:batch])          # warm the jitted scan
    t0 = time.perf_counter()
    high_water = []
    for i, docs in enumerate(streams):
        start = batch if i == 0 else 0
        for j in range(start, docs_per_segment, batch):
            life.ingest(docs[j: j + batch])
        high_water.append(life.memory_high_water_slots())
    t_ingest = time.perf_counter() - t0
    life.check_health()
    n_docs = n_segments * docs_per_segment
    sustained_dps = (n_docs - batch) / t_ingest

    assert life.stats.rollovers == n_segments, life.stats
    assert life.memory_slots_used() == 0, "rollover must reclaim all slots"
    # bounded memory: after the first rollover seeds the free list, later
    # segments recycle it — growth must stay far below another segment.
    growth = (high_water[-1] - high_water[0]) / high_water[0]
    assert growth < 0.5, (high_water, "reclamation failed: watermark grew")

    # unified queries: active (empty or partial) + every frozen segment
    all_freqs = sum(synth.term_freqs(d, vocab) for d in streams)
    top = np.argsort(-all_freqs)
    queries = [[int(top[a]), int(top[b])]
               for a, b in [(0, 1), (2, 5), (1, 20), (10, 50)]]
    for terms in queries:                    # warm packing + jit shapes:
        life.conjunctive(terms)              # steady-state latency only
    ts = []
    n_hits = 0
    for terms in queries:
        t0 = time.perf_counter()
        hits = life.conjunctive(terms)
        ts.append(time.perf_counter() - t0)
        n_hits += len(hits)

    # --- tiered compaction: G = O(log N) over >= 8 rollovers ----------
    # same stream, half-size segments -> 2x the rollovers; docids are
    # assigned by global arrival order either way, so query results must
    # stay bit-identical to the uncompacted engine above.
    comp_docs_per_seg = docs_per_segment // 2
    n_rollovers = 2 * n_segments            # >= 8
    comp = LifecycleEngine(layout, vocab, comp_docs_per_seg,
                           max_slices=max_slices, max_len=max_len,
                           validate=validate,
                           compaction=CompactionPolicy(fanout=2))
    g_trace = []
    t0 = time.perf_counter()
    for docs in streams:
        for j in range(0, docs_per_segment, batch):
            comp.ingest(docs[j: j + batch])
            g = len(comp.segments.frozen)
            n = comp.stats.rollovers
            # THE bound: a fanout-2 cascade is a base-2 counter, so
            # G == popcount(n) <= floor(log2(n)) + 1 at every rollover.
            assert g == bin(n).count("1"), (n, g)
            if n:
                g_trace.append(g)
                assert g <= int(np.log2(n)) + 1, (n, g)
    t_comp = time.perf_counter() - t0
    assert comp.stats.rollovers == n_rollovers, comp.stats
    assert comp.stats.compactions >= n_rollovers // 2
    g_final = len(comp.segments.frozen)
    for terms in queries:
        assert np.array_equal(comp.conjunctive(terms),
                              life.conjunctive(terms)), terms

    out = {
        "n_docs": n_docs,
        "n_segments": n_segments,
        "docs_per_segment": docs_per_segment,
        "sustained_docs_per_s": sustained_dps,
        "rollovers": life.stats.rollovers,
        "high_water_slots": high_water,
        "high_water_growth": growth,
        "live_slots_after_rollover": life.memory_slots_used(),
        "query_unified_ms": float(np.mean(ts) * 1e3),
        "query_hits": n_hits,
        "compaction_rollovers": n_rollovers,
        "compactions": comp.stats.compactions,
        "g_without_compaction": n_rollovers,
        "g_with_compaction": g_final,
        "g_max_seen": max(g_trace),
        "compaction_docs_per_s": (n_docs / t_comp),
    }
    print("\n== bench_lifecycle: streaming rollover + reclamation "
          "(paper §3.1 closed loop) ==")
    print(f"{n_segments} segments x {docs_per_segment} docs: "
          f"{sustained_dps:9.0f} docs/s sustained (incl. freeze+reclaim)")
    print(f"heap high-water per rollover: {high_water} "
          f"(growth {growth * 100:+.1f}% — bounded by reclamation)")
    print(f"unified active+frozen conjunctive: "
          f"{out['query_unified_ms']:8.2f} ms/query over "
          f"{life.stats.rollovers} frozen segments")
    print(f"tiered compaction (fanout 2): {n_rollovers} rollovers -> "
          f"G = {g_final} frozen segments (max {max(g_trace)} seen; "
          f"uncompacted G would be {n_rollovers}), "
          f"{comp.stats.compactions} merges, queries bit-identical, "
          f"{out['compaction_docs_per_s']:.0f} docs/s incl. compaction")
    return out


if __name__ == "__main__":
    run()
