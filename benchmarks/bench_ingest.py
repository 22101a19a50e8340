"""§3.2 performance figures analogue: indexing throughput (docs/s and
postings/s) under the production config, plus the zero-copy property
(slot watermarks only ever grow; no array copies on growth).

The paper reports 7000 tweets/s on a 2009 Xeon; we report the CPU-JAX
ingest rate of the batch-parallel BULK allocator (the hot path since
PR 4), its insensitivity to arrival batch size (the paper's
latency-vs-TPS flatness claim), and the speedup over the per-posting
``lax.scan`` allocator it replaced — the scan stays as the bit-exactness
oracle, so the comparison is apples-to-apples on identical streams and
identical final states.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.core import analytical
from repro.core.index import ActiveSegment
from repro.core.pointers import PoolLayout

# Both sides of the gated comparison take the best of the same number
# of passes, so the asserted ratio is symmetric and noise-resistant
# (at FAST scale each pass times one 1024-doc batch; at --full scale
# the scan side is capped at SCAN_BATCHES batches per pass — it is the
# slow baseline — and docs/s normalises the comparison).
COMPARE_BATCH = 1024
SCAN_BATCHES = 2
COMPARE_PASSES = 5


def _time_ingest(layout, vocab, chunks, bulk: bool, n_batches=None,
                 passes: int = 1):
    """Best-of-``passes`` ingest rate over ``n_batches`` chunks (fresh
    segment per pass; jit warmed by an untimed first chunk)."""
    if n_batches is None:
        n_batches = chunks.shape[0] - 1
    if n_batches < 1:
        raise ValueError(
            f"corpus too small: {chunks.shape[0]} chunk(s) of "
            f"{chunks.shape[1]} docs leaves no timed batch after the "
            f"warmup chunk")
    dev_chunks = [jnp.asarray(chunks[i]) for i in range(1 + n_batches)]
    best = float("inf")
    for _ in range(passes):
        seg = ActiveSegment(layout, vocab, bulk_ingest=bulk)
        seg.ingest(dev_chunks[0])               # warm the jit cache
        jax.block_until_ready(seg.state.heap)
        t0 = time.perf_counter()
        for i in range(1, 1 + n_batches):
            seg.ingest(dev_chunks[i])
        jax.block_until_ready(seg.state.heap)
        best = min(best, time.perf_counter() - t0)
        seg.check_health()
    n_docs = n_batches * chunks.shape[1]
    n_post = int((chunks[1: 1 + n_batches] >= 0).sum())
    return n_docs / best, n_post / best


def run(fast: bool = True):
    scale = common.FAST if fast else common.FULL
    spec, first, second, f1, f2 = common.corpus(scale)
    layout = PoolLayout(z=common.ZG,
                        slices_per_pool=analytical.slices_per_pool(
                            common.ZG, f2, slack=2.0))
    print("\n== bench_ingest: indexing throughput (paper §3.2) ==")
    rows = []
    for batch in (64, 256, 1024):
        docs = second[: (second.shape[0] // batch) * batch]
        chunks = docs.reshape(docs.shape[0] // batch, batch, -1)
        d_s, p_s = _time_ingest(layout, scale.vocab, chunks, bulk=True)
        rows.append((batch, d_s, p_s))
        print(f"batch={batch:5d}: {d_s:9.0f} docs/s  "
              f"{p_s:10.0f} postings/s  (bulk)")
    tput = [r[1] for r in rows]
    spread = (max(tput) - min(tput)) / max(tput)
    print(f"throughput spread across batch sizes: {spread * 100:.0f}% "
          f"(paper: indexing latency insensitive to arrival rate)")

    # -- bulk vs scan on identical streams (identical final states) ----
    batch = COMPARE_BATCH
    docs = second[: (second.shape[0] // batch) * batch]
    chunks = docs.reshape(docs.shape[0] // batch, batch, -1)
    bulk_d, _ = _time_ingest(layout, scale.vocab, chunks, bulk=True,
                             passes=COMPARE_PASSES)
    scan_d, _ = _time_ingest(layout, scale.vocab, chunks, bulk=False,
                             n_batches=min(SCAN_BATCHES,
                                           chunks.shape[0] - 1),
                             passes=COMPARE_PASSES)
    speedup = bulk_d / scan_d
    print(f"bulk vs scan @ batch={batch}: {bulk_d:9.0f} vs "
          f"{scan_d:9.0f} docs/s  ->  {speedup:.1f}x")
    assert speedup >= 5.0, (
        f"bulk ingest regressed: only {speedup:.1f}x over the scan "
        f"oracle (PR 4 requires >= 5x)")
    return {
        "rows": rows,
        "spread": spread,
        "bulk_docs_s": bulk_d,
        "scan_docs_s": scan_d,
        "bulk_vs_scan_speedup": speedup,
    }


if __name__ == "__main__":
    run()
