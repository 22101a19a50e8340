"""Closing the Goldilocks loop: a live stream through segment rollovers.

``examples/realtime_search.py`` shows ONE rollover; this scenario runs
the full lifecycle engine: the stream never stops, segments freeze into
compressed read-only CSR, their slices return to the pool free lists and
the next segment recycles them — so the heap high-water mark plateaus at
roughly one segment's demand while queries keep seeing the entire
history, newest tweets first, through one unified path (active slice
pools + decoded frozen blocks through the blocked intersect kernel).

Ingest runs the PR-4 batch-parallel BULK allocator (sort occurrences by
term, walk the slice-size progression analytically, allocate batch-wide,
one fused scatter-append) — the engine default; pass
``bulk_ingest=False`` to replay the same stream through the per-posting
scan oracle and watch docs/s collapse.

The frozen side is bounded too: the engine runs a geometric
``CompactionPolicy(fanout=2)``, so same-tier frozen segments
cascade-merge at every rollover and the frozen-segment count G stays
O(log N) (= popcount(#rollovers)) instead of growing linearly — queries
stay bit-identical, only the segment tiling changes.

    PYTHONPATH=src python examples/lifecycle_stream.py
"""
import time

import numpy as np

from repro.core import analytical
from repro.core.lifecycle import LifecycleEngine
from repro.core.pointers import PoolLayout
from repro.core.segments import CompactionPolicy
from repro.data import synth

Z = (1, 4, 7, 11)
VOCAB, SEGMENT_DOCS, N_SEGMENTS, BATCH = 1_500, 600, 4, 100

layout = PoolLayout(z=Z, slices_per_pool=(8192, 4096, 1024, 128))
spec = synth.CorpusSpec(vocab=VOCAB, n_docs=SEGMENT_DOCS * N_SEGMENTS + 300,
                        max_len=14, seed=23)
stream = synth.zipf_corpus(spec)
freqs = synth.term_freqs(stream, VOCAB)
fmax = int(freqs.max())

life = LifecycleEngine(
    layout, VOCAB, docs_per_segment=SEGMENT_DOCS,
    max_slices=int(analytical.slices_needed(Z, fmax)) + 1,
    max_len=1 << (fmax - 1).bit_length(),
    compaction=CompactionPolicy(fanout=2))

# --- the stream: batches arrive forever; rollovers happen in-line -----
# the first batch is ingested before the clock starts so the printed
# docs/s measures steady-state bulk ingest, not jit compilation
seen_rollovers = 0
life.ingest(stream[:BATCH])
t0 = time.perf_counter()
for i in range(BATCH, len(stream), BATCH):
    life.ingest(stream[i: i + BATCH])
    if life.stats.rollovers != seen_rollovers:
        seen_rollovers = life.stats.rollovers
        g_now = len(life.segments.frozen)
        tiers = [fz.tier for fz in life.segments.frozen]
        print(f"rollover #{seen_rollovers} at doc {life.doc_base}: "
              f"heap high-water {life.stats.high_water_slots} slots, "
              f"live {life.stats.live_slots} "
              f"(slices recycled); G before compaction "
              f"{seen_rollovers}, after {g_now} (tiers {tiers})")
life.check_health()
wall = time.perf_counter() - t0
timed_docs = life.stats.docs_ingested - BATCH
print(f"stream done: {life.stats.docs_ingested} docs "
      f"({timed_docs / wall:.0f} docs/s after warmup, bulk ingest incl. "
      f"freeze/reclaim/compaction pauses), "
      f"{len(life.segments.frozen)} frozen segments "
      f"(from {seen_rollovers} rollovers via "
      f"{life.stats.compactions} merges) + "
      f"{life.segments.active.next_docid} docs active")

# --- unified queries: one call spans active pool + every frozen CSR ---
top = np.argsort(-freqs)
t1, t2 = int(top[0]), int(top[1])
hits = life.conjunctive([t1, t2], limit=15)
print(f"conjunctive [{t1} AND {t2}]: {len(hits)} newest hits "
      f"(reverse-chronological, segments merged, early-exit at limit): "
      f"{hits.tolist()}")
hits = life.phrase(t1, t2, limit=10)
print(f"phrase [{t1} {t2}]: {hits.tolist()}")

# --- batched queries: a whole front-end batch in O(1) dispatches ------
queries = [[int(top[a]), int(top[b])]
           for a, b in [(0, 1), (2, 5), (1, 20), (3, 7)]] * 8
life.conjunctive_batch(queries)                 # warm the jitted stack
t0 = time.perf_counter()
results = life.conjunctive_batch(queries)
batched_ms = (time.perf_counter() - t0) / len(queries) * 1e3
life.batched = False                            # per-query oracle path
t0 = time.perf_counter()
for terms in queries:
    life.conjunctive(terms)
seq_ms = (time.perf_counter() - t0) / len(queries) * 1e3
life.batched = True
print(f"batched qexec: {len(queries)} queries over "
      f"{len(life.segments.frozen)} frozen segments (compacted from "
      f"{seen_rollovers} rollovers) in one stacked dispatch — "
      f"{batched_ms:.2f} ms/q vs {seq_ms:.2f} ms/q per-query "
      f"({seq_ms / batched_ms:.1f}x), {sum(len(r) for r in results)} hits")

# --- the memory story ------------------------------------------------
bound = life.memory_high_water_slots()
never_frozen = int(np.sum(analytical.memory_slots(Z, freqs[freqs > 0])))
print(f"heap high-water with reclamation: {bound} slots; a never-frozen "
      f"index of the same stream needs {never_frozen} "
      f"({never_frozen / bound:.1f}x) — the rollover/reclaim cycle, not "
      f"steady-state ingest, sets sustained memory use")
