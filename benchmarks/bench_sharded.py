"""Document-sharded index scaling: ingest throughput and batched query
latency for 1 vs 4 shards (Earlybird document partitioning, paper §3).

Each shard owns a private slice-pool allocator, so ingest parallelises
with zero cross-shard traffic and per-shard postings lists are ~S times
shorter — the query-side win shows up in the per-shard materialise +
intersect widths.  Runs on the CPU host-device emulation in CI
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``); with fewer
devices available it degrades to the shard counts that fit and says so.

Returned metrics feed ``benchmarks.run --json`` (the CI artifact).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import analytical
from repro.core.pointers import PoolLayout
from repro.core.sharded_index import (ShardedActiveSegment, engine_max_len,
                                      make_doc_mesh, make_sharded_engine)


def _bench_one(n_shards: int, scale, docs: np.ndarray, batch: int):
    _, _, _, _, f2 = common.corpus(scale)
    layout = PoolLayout(z=common.ZG,
                        slices_per_pool=analytical.slices_per_pool(
                            common.ZG, f2, slack=2.0))
    mesh, rules = make_doc_mesh(n_shards)
    seg = ShardedActiveSegment(layout, scale.vocab, mesh, rules=rules)

    n_batches = docs.shape[0] // batch
    chunks = docs.reshape(n_batches, batch, -1)
    seg.ingest(jnp.asarray(chunks[0]))  # warm the jitted shard_map scan
    t0 = time.perf_counter()
    for i in range(1, n_batches):
        seg.ingest(jnp.asarray(chunks[i]))
    jax.block_until_ready(seg.state.heap)
    dt = time.perf_counter() - t0
    ingest_dps = (n_batches - 1) * batch / dt
    seg.check_health()

    # per-shard list bound: shards see ~1/S of each term's postings
    shard_fmax = int(np.asarray(seg.state.freq).max())
    max_slices = int(analytical.slices_needed(common.ZG, shard_fmax)) + 1
    max_len = engine_max_len(shard_fmax)
    engine = make_sharded_engine(layout, mesh, max_slices, max_len,
                                 rules=rules)

    freqs = seg.term_freqs()
    top = np.argsort(-freqs)
    n_q = 32
    qs = np.zeros((n_q, 8), np.uint32)
    qs[:, 0] = top[np.arange(n_q) % 16]
    qs[:, 1] = top[(np.arange(n_q) % 16) + 16]
    terms = jnp.asarray(qs)
    n_terms = jnp.full((n_q,), 2, jnp.int32)

    mean_s, std_s = common.time_fn(
        lambda: engine.conjunctive(seg.state, terms, n_terms))
    return {
        "ingest_docs_per_s": ingest_dps,
        "query_batch_ms": mean_s * 1e3,
        "query_batch_ms_std": std_s * 1e3,
        "query_per_q_ms": mean_s * 1e3 / n_q,
        "n_queries": n_q,
        "per_shard_max_len": max_len,
    }


def run(fast: bool = True):
    scale = common.FAST if fast else common.FULL
    _, _, second, _, _ = common.corpus(scale)
    batch = 256
    docs = second[: (second.shape[0] // batch) * batch]

    n_dev = jax.device_count()
    shard_counts = [s for s in (1, 4) if s <= n_dev]
    print("\n== bench_sharded: document-partitioned ingest + batched "
          "query (paper §3 scale-out) ==")
    if 4 not in shard_counts:
        print(f"only {n_dev} device(s); set XLA_FLAGS="
              f"--xla_force_host_platform_device_count=4 for the 4-shard "
              f"column")
    out = {"devices": n_dev, "shards": {}}
    for s in shard_counts:
        m = _bench_one(s, scale, docs, batch)
        out["shards"][s] = m
        print(f"shards={s}: {m['ingest_docs_per_s']:9.0f} docs/s ingest   "
              f"{m['query_batch_ms']:8.2f} ms / {m['n_queries']}-query "
              f"batch ({m['query_per_q_ms']:.3f} ms/q, per-shard "
              f"max_len={m['per_shard_max_len']})")
    if len(shard_counts) == 2:
        a, b = (out["shards"][s] for s in shard_counts)
        print(f"4-shard vs 1-shard: ingest x{b['ingest_docs_per_s'] / a['ingest_docs_per_s']:.2f}, "
              f"query x{a['query_batch_ms'] / b['query_batch_ms']:.2f}")
    return out


if __name__ == "__main__":
    run()
