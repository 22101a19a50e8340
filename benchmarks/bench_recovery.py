"""Durability costs: snapshot/restore throughput, journal overhead on
the ingest path, and replay speed — the recovery-time model.

A real-time index that loses every posting on a crash (the paper keeps
the whole index in RAM) needs the repro.core.recovery stack; this suite
prices it:

  * ``snapshot_s`` / ``snapshot_mb`` — serialize the full engine
    (active PoolState + every frozen CSR) with per-leaf CRC32s;
  * ``restore_s`` — archive back to a queryable engine;
  * ``journal_overhead_pct`` — WAL append-then-apply ingest vs naked
    ingest on the same stream (the price of durability per batch);
  * ``replay_docs_per_s`` — journal batches re-ingested during
    recovery, the slope of the recovery-time model (guarded by
    benchmarks.check_regression);
  * ``recovery_s_model`` — measured recovery time split into its two
    terms: ``restore_s`` (constant in journal length) + journaled docs
    divided by ``replay_docs_per_s`` (linear), so operators can pick a
    snapshot cadence from a target recovery time.

The suite ASSERTS the recovered engine's fingerprint equals the live
engine's — a benchmark that silently measured a wrong recovery would be
worse than no benchmark.
"""
from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from benchmarks import common
from repro.core import analytical
from repro.core import recovery as rec
from repro.core.lifecycle import LifecycleEngine
from repro.core.pointers import PoolLayout
from repro.core.segments import CompactionPolicy
from repro.data import synth


def _engine(layout, vocab, docs_per_segment, max_slices, max_len,
            validate):
    return LifecycleEngine(layout, vocab, docs_per_segment,
                           max_slices=max_slices, max_len=max_len,
                           validate=validate,
                           compaction=CompactionPolicy(fanout=2))


def run(fast: bool = True, validate: bool = False):
    vocab = 5_000 if fast else 20_000
    docs_per_segment = 1_024 if fast else 4_096
    n_segments = 4 if fast else 6
    batch = 256
    n_docs = n_segments * docs_per_segment

    docs = synth.zipf_corpus(synth.CorpusSpec(
        vocab=vocab, n_docs=n_docs, max_len=14, seed=23))
    freqs = synth.term_freqs(docs[:docs_per_segment], vocab)
    layout = PoolLayout(z=common.ZG,
                        slices_per_pool=analytical.slices_per_pool(
                            common.ZG, freqs, slack=2.5))
    fmax = int(freqs.max())
    max_slices = int(analytical.slices_needed(common.ZG, fmax)) + 2
    max_len = 1 << max(int(2 * fmax - 1).bit_length(), 3)
    mk = lambda: _engine(layout, vocab, docs_per_segment, max_slices,
                         max_len, validate)
    batches = [docs[j: j + batch] for j in range(0, n_docs, batch)]

    with tempfile.TemporaryDirectory() as wd:
        snap = os.path.join(wd, "snap.bin")
        jrnl = os.path.join(wd, "journal.bin")

        # -- naked ingest baseline (same stream, no journal) -----------
        naked = mk()
        naked.ingest(batches[0])            # warm the jitted path
        t0 = time.perf_counter()
        for b in batches[1:]:
            naked.ingest(b)
        t_naked = time.perf_counter() - t0

        # -- journaled ingest + snapshot midway -------------------------
        eng = mk()
        eng.ingest(batches[0])
        snap_at = len(batches) // 2
        snapshot_s = snapshot_mb = 0.0
        t_journaled = 0.0
        with rec.IngestJournal(jrnl, base_seq=1) as journal:
            for i, b in enumerate(batches[1:], start=1):
                t0 = time.perf_counter()
                journal.append(b)           # WAL: append THEN apply
                eng.ingest(b)
                t_journaled += time.perf_counter() - t0
                if i + 1 == snap_at:
                    t0 = time.perf_counter()
                    rec.snapshot(eng, snap, seq=i + 1)
                    snapshot_s = time.perf_counter() - t0
                    snapshot_mb = os.path.getsize(snap) / 1e6
        overhead = (t_journaled - t_naked) / t_naked * 100.0
        fp_live = rec.engine_fingerprint(eng)

        # -- recovery: restore + replay the journal tail ----------------
        t0 = time.perf_counter()
        eng2 = rec.restore(snap)
        restore_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        base, records = rec.read_journal(jrnl)
        replayed_docs = 0
        applied = snap_at
        for seq, b in records:
            if seq < applied:
                continue
            eng2.ingest(b)
            applied += 1
            replayed_docs += b.shape[0]
        replay_s = time.perf_counter() - t0
        replay_dps = replayed_docs / replay_s
        assert rec.engine_fingerprint(eng2) == fp_live, (
            "recovered engine is not bit-identical to the live one")

        recovery_s = restore_s + replay_s

    out = {
        "n_docs": n_docs,
        "snapshot_s": snapshot_s,
        "snapshot_mb": snapshot_mb,
        "snapshot_mb_per_s": snapshot_mb / snapshot_s,
        "restore_s": restore_s,
        "journal_overhead_pct": overhead,
        "replayed_docs": replayed_docs,
        "replay_docs_per_s": replay_dps,
        "recovery_s": recovery_s,
        # recovery-time model: T(j docs journaled) ~ restore_s + j/slope
        "recovery_s_model": {"constant_restore_s": restore_s,
                             "linear_docs_per_s": replay_dps},
    }
    print("\n== bench_recovery: snapshot / journal / replay "
          "(docs/durability.md recovery-time model) ==")
    print(f"snapshot: {snapshot_mb:7.1f} MB in {snapshot_s * 1e3:7.1f} ms "
          f"({out['snapshot_mb_per_s']:.0f} MB/s); "
          f"restore {restore_s * 1e3:7.1f} ms")
    print(f"journal overhead on ingest: {overhead:+.1f}% "
          f"(WAL append+flush per {batch}-doc batch)")
    print(f"replay: {replayed_docs} docs in {replay_s * 1e3:7.1f} ms "
          f"({replay_dps:.0f} docs/s) -> recovery "
          f"{recovery_s * 1e3:7.1f} ms total, bit-identical")
    return out


if __name__ == "__main__":
    run()
