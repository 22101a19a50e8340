"""Serving under load: latency/qps for the overload-resilient loop,
plus chaos-under-load (crash mid-serve, recover, resume).

``repro.core.serve`` is where the paper's real-time promise meets
traffic: tweets acked durably while queries coalesce into pow2 batches
with deadlines and a degradation ladder.  This suite drives it with a
closed-loop load generator — Zipfian terms via ``synth.query_log``
(microblog shape), Poisson arrivals with a mid-run burst window, mixed
conjunctive / disjunctive / phrase / top-k / scored traffic — while
ingest runs at a target docs/s through the same loop, and reports:

  * ``p50_ms`` / ``p95_ms`` / ``p99_ms`` — response latency
    (submission to result sync) at the reference load;
  * ``sustained_qps`` — served queries over the timed window, with
    ``ingest_docs_per_s`` indexed concurrently;
  * ``degraded_frac`` / ``burst_degraded_frac`` — how much of the
    traffic the overload gauge pushed down the ladder (the burst leg
    floods the queue to force it);
  * ``chaos_unavailable_s`` — crash (fault-injected mid-rollover,
    PR 9's ``crash_mid_rollover`` site) to resumed serving.

The suite ASSERTS its own contract rather than just reporting numbers:
zero silent drops (``invariants.check_serve`` conservation + every
rejection carries a positive retry-after), p99 under the configured
deadline at the reference load, and the chaos leg recovers with a
bit-identical ``engine_fingerprint`` (vs a fresh engine fed every
journaled batch) and zero acked-ingest loss.  A serving bench that
dropped requests silently would flatter qps — exactly the failure mode
this exists to catch.

CLI: ``python -m benchmarks.bench_serve [--full] [--validate]
[--chaos-only]`` — the last runs just the crash-under-load leg (the CI
chaos job's entry point).
"""
from __future__ import annotations

import heapq
import os
import tempfile
import time

import numpy as np

from benchmarks import common
from repro.analysis import faults
from repro.analysis import invariants as inv
from repro.core import analytical
from repro.core import recovery as rec
from repro.core import serve as sv
from repro.core.lifecycle import LifecycleEngine
from repro.core.pointers import PoolLayout
from repro.data import synth

_KIND_CYCLE = ("conjunctive", "topk", "scored", "disjunctive",
               "conjunctive", "phrase", "topk", "scored")


def _engine(vocab, docs_per_segment, docs, validate):
    freqs = synth.term_freqs(docs[:docs_per_segment], vocab)
    layout = PoolLayout(z=common.ZG,
                        slices_per_pool=analytical.slices_per_pool(
                            common.ZG, freqs, slack=2.5))
    fmax = int(freqs.max())
    max_slices = int(analytical.slices_needed(common.ZG, fmax)) + 2
    max_len = 1 << max(int(2 * fmax - 1).bit_length(), 3)
    return LifecycleEngine(layout, vocab, docs_per_segment,
                           max_slices=max_slices, max_len=max_len,
                           validate=validate, stable_shapes=True)


def _requests(n, docs, vocab, seed=3, k=10):
    """Mixed traffic with Zipfian terms: (kind, terms, k) triples."""
    qlog = synth.query_log("microblog", n, docs, vocab, seed=seed)
    out = []
    for i, row in enumerate(qlog):
        terms = tuple(int(t) for t in row if t >= 0)
        kind = _KIND_CYCLE[i % len(_KIND_CYCLE)]
        if kind == "phrase":
            if len(terms) < 2:
                kind = "conjunctive"
            else:
                terms = terms[:2]
        out.append((kind, terms, k))
    return out


def _warm(loop, requests, heavy, k=10):
    """Compile every jitted shape the load can reach, so the timed leg
    measures serving rather than jit: per query kind, per ladder rung,
    per pow2 batch bucket AND per pow2 term-count bucket (the engine
    trims the term axis to the flush's widest query, so ``tb`` is part
    of the jit key too).

    ``heavy`` is the corpus's most frequent terms: flushing them FIRST
    drives the engine's ``stable_shapes`` gather ratchet straight to
    its maximum posting-width bucket, so every later (kind, rung,
    bucket) combination compiles exactly once at its final shape and
    the timed leg never sees a recompile."""
    vocab_terms = [t for _, terms, _ in requests for t in terms]
    sizes, s = [], 1
    while s <= loop.config.max_batch:
        sizes.append(s)
        s *= 2
    loop.force_level = 0
    for kind in ("conjunctive", "scored", "phrase"):   # one per gather
        loop.submit_query(kind, tuple(heavy[:2]), k=k)
        loop.step(force=True)
    for level in range(4):
        loop.force_level = level
        for kind in ("conjunctive", "disjunctive", "phrase", "topk",
                     "scored"):
            for tb in ((2,) if kind == "phrase" else (1, 2, 4)):
                for size in sizes:
                    for i in range(size):
                        terms = tuple(vocab_terms[(i + j) %
                                                  len(vocab_terms)]
                                      for j in range(tb))
                        loop.submit_query(kind, terms, k=k)
                    loop.step(force=True)
    loop.force_level = None
    loop.take_responses()
    loop.stats = sv.ServeStats()       # warmup must not pollute metrics


def _drive(loop, requests, arrivals, batches, ingest_at):
    """Closed-loop driver: submit what the schedule says is due, retry
    rejected submissions after their retry-after, step the loop.
    Returns (responses, final_query_drops, final_ingest_drops)."""
    t0 = loop.clock()
    responses = []
    ai = bi = 0
    q_retry, b_retry = [], []          # heaps of (due, payload)
    q_dropped = b_dropped = 0

    def submit_q(idx, now, first):
        nonlocal q_dropped
        kind, terms, k = requests[idx % len(requests)]
        r = loop.submit_query(kind, terms, k=k)
        if isinstance(r, sv.Rejected):
            assert r.retry_after_s > 0  # backpressure is never silent
            if first:
                heapq.heappush(q_retry, (now + r.retry_after_s, idx))
            else:
                q_dropped += 1          # one retry per request, then give up

    def submit_b(i, now, attempt):
        nonlocal b_dropped
        r = loop.submit_ingest(batches[i])
        if isinstance(r, sv.Rejected):
            assert r.retry_after_s > 0
            if attempt < 50:
                heapq.heappush(b_retry,
                               (now + r.retry_after_s, (i, attempt + 1)))
            else:
                b_dropped += 1

    while (ai < len(arrivals) or bi < len(batches) or q_retry or b_retry
           or loop.pending_queries or loop.pending_ingest):
        now = loop.clock() - t0
        while ai < len(arrivals) and arrivals[ai] <= now:
            submit_q(ai, now, first=True)
            ai += 1
        while q_retry and q_retry[0][0] <= now:
            _, idx = heapq.heappop(q_retry)
            submit_q(idx, now, first=False)
        while bi < len(batches) and ingest_at[bi] <= now:
            submit_b(bi, now, attempt=0)
            bi += 1
        while b_retry and b_retry[0][0] <= now:
            _, (i, attempt) = heapq.heappop(b_retry)
            submit_b(i, now, attempt)
        done = (ai >= len(arrivals) and bi >= len(batches)
                and not q_retry and not b_retry)
        loop.step(force=done)
        responses.extend(loop.take_responses())
    return responses, q_dropped, b_dropped


def _percentiles_ms(responses):
    lat = np.array([r.latency_s for r in responses]) * 1e3
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 95)),
            float(np.percentile(lat, 99)))


def run_load(fast: bool = True, validate: bool = False):
    vocab = 5_000 if fast else 20_000
    duration_s = 5.0 if fast else 10.0
    # reference load sits at ~40% of the measured CPU service capacity
    # (~14ms/query mixed at this scale): the deadline assert prices the
    # serving layer's overhead, not a saturated box (saturation behaviour
    # is the burst leg's job)
    qps = 25.0
    docs_per_s = 500.0 if fast else 1_000.0
    ingest_batch = 256
    seed_docs = 1_024
    # batch_wait trades latency floor for coalescing width: CPU dispatch
    # overhead is per-flush, so a 50ms window packs ~6 arrivals per
    # bucket at the reference rate instead of paying the overhead per
    # single-query flush.
    cfg = sv.ServeConfig(max_batch=8, batch_wait_s=0.05,
                         deadline_s=0.5, query_queue_cap=256)

    rng = np.random.default_rng(11)
    n_docs = int(docs_per_s * duration_s) + 4 * ingest_batch
    # a multiple of the batch size: a ragged tail batch would be a new
    # jit shape, and its mid-leg compile would masquerade as a latency
    # spike
    n_docs -= n_docs % ingest_batch
    docs = synth.zipf_corpus(synth.CorpusSpec(
        vocab=vocab, n_docs=n_docs + seed_docs + ingest_batch,
        max_len=14, seed=23))
    # docs_per_segment past the whole stream: the reference window has a
    # pinned frozen-stack shape (the explicit rollover below), so the
    # timed leg measures serving, not the per-G jit recompile a rollover
    # would trigger mid-window (rollover-under-load runs in run_chaos).
    eng = _engine(vocab, n_docs + 2 * seed_docs, docs, validate)
    requests = _requests(256, docs, vocab)
    heavy = np.argsort(synth.term_freqs(docs, vocab))[-2:][::-1]
    loop = sv.ServeLoop(eng, cfg)

    eng.ingest(docs[:seed_docs])
    eng.segments.rollover()            # a real frozen side, G fixed at 1
    eng._sync_frozen()
    loop.submit_ingest(docs[seed_docs: seed_docs + ingest_batch])
    loop.step(force=True)              # warm the leg's ingest shape
    stream = docs[seed_docs + ingest_batch:]
    # warm AFTER the active segment has content: query eval against an
    # empty active short-circuits, so warming before the first ingest
    # would leave the active-path compiles to spike the timed leg
    _warm(loop, requests, [int(t) for t in heavy])

    # -- reference leg: steady Poisson arrivals + ingest at docs/s -----
    n_arrivals = int(qps * duration_s)
    arrivals = np.cumsum(rng.exponential(1.0 / qps, n_arrivals)).tolist()
    batches = [stream[j: j + ingest_batch]
               for j in range(0, n_docs, ingest_batch)]
    ingest_at = [(j * ingest_batch) / docs_per_s
                 for j in range(len(batches))]

    t0 = time.perf_counter()
    responses, q_drop, b_drop = _drive(loop, requests, arrivals,
                                       batches, ingest_at)
    elapsed = time.perf_counter() - t0

    inv.check_serve(loop).raise_if_failed()   # zero silent drops
    assert loop.stats.rejections_without_retry_after == 0
    assert b_drop == 0, "sized pools should never shed the ingest stream"
    p50, p95, p99 = _percentiles_ms(responses)
    deadline_ms = cfg.deadline_s * 1e3
    assert p99 < deadline_ms, \
        f"p99 {p99:.1f}ms over the {deadline_ms:.0f}ms deadline"
    served = loop.stats.queries_served
    degraded = sum(loop.stats.served_by_level[1:])
    misses = loop.stats.deadline_misses   # before the burst leg pollutes

    # -- burst leg: flood the queue far past degrade_at[0] in one tick —
    # the gauge MUST push this burst down the ladder (each rung's
    # exactness contract is tested in tests/test_serve.py; here we
    # prove the gauge engages under real pressure; no deadline assert:
    # a burst is exactly when deadlines degrade instead of holding)
    burst_n = int(0.8 * cfg.query_queue_cap)
    for i in range(burst_n):
        kind, terms, k = requests[i % len(requests)]
        r = loop.submit_query(kind, terms, k=k)
        assert not isinstance(r, sv.Rejected) or r.retry_after_s > 0
    burst = loop.drain()
    inv.check_serve(loop).raise_if_failed()
    burst_degraded = sum(1 for r in burst if r.degraded)
    assert burst_degraded > 0, "queue flood never engaged the ladder"

    return {
        "sustained_qps": served / elapsed,
        "p50_ms": p50, "p95_ms": p95, "p99_ms": p99,
        "deadline_ms": deadline_ms,
        "deadline_miss_frac": misses / max(served, 1),
        "degraded_frac": degraded / max(served, 1),
        "burst_degraded_frac": burst_degraded / max(len(burst), 1),
        "queries_served": served,
        "queries_rejected": loop.stats.queries_rejected,
        "query_retry_drops": q_drop,
        "ingest_docs_per_s": loop.stats.docs_indexed / elapsed,
        "flushes_timer": loop.stats.flushes_timer,
        "flushes_full": loop.stats.flushes_full,
    }


def run_chaos(fast: bool = True, validate: bool = False):
    """Crash-under-load: fault-inject ``crash_mid_rollover`` while the
    loop is serving, recover from snapshot + journal, resume serving.
    Asserts zero acked-ingest loss (fingerprint bit-identity against a
    fresh engine fed every journaled batch) and reports unavailability
    (crash to resumed loop)."""
    vocab = 5_000 if fast else 20_000
    docs_per_segment = 512 if fast else 2_048
    ingest_batch = 128
    rng = np.random.default_rng(7)
    docs = synth.zipf_corpus(synth.CorpusSpec(
        vocab=vocab, n_docs=24 * ingest_batch + docs_per_segment,
        max_len=14, seed=29))
    requests = _requests(64, docs, vocab)
    batches = [docs[j: j + ingest_batch]
               for j in range(0, len(docs) - docs_per_segment,
                              ingest_batch)]

    with tempfile.TemporaryDirectory() as wd:
        wal = os.path.join(wd, "wal.bin")
        snap = os.path.join(wd, "snap.bin")
        eng = _engine(vocab, docs_per_segment, docs, validate)
        loop = sv.ServeLoop(eng, sv.ServeConfig(max_batch=8),
                            journal=rec.IngestJournal(wal))
        heavy = np.argsort(synth.term_freqs(docs, vocab))[-2:][::-1]
        _warm(loop, requests[:32], [int(t) for t in heavy])

        def serve_some(i):
            kind, terms, k = requests[i % len(requests)]
            loop.submit_query(kind, terms, k=k)
            loop.step(force=True)

        # healthy serving, snapshot mid-run (docs_per_segment/ingest_batch
        # puts a scheduled rollover every 4 batches)
        for i in range(6):
            assert isinstance(loop.submit_ingest(batches[i]), int)
            serve_some(i)
        loop.snapshot_now(snap)
        snap_seq = loop.applied_seq

        crashed = False
        t_crash = None
        with faults.crash_site("crash_mid_rollover"):
            for i in range(6, len(batches)):
                try:
                    assert isinstance(loop.submit_ingest(batches[i]), int)
                    serve_some(i)
                except faults.InjectedCrash:
                    crashed = True
                    t_crash = time.perf_counter()
                    break
        assert crashed, "the load never reached the armed rollover"
        acked = loop.journal.next_seq
        assert loop.pending_ingest >= 1    # the torn batch stayed queued
        loop.journal.close()               # process death

        replayed = []
        recovered = rec.recover(
            snap, wal, expect_seq=acked,
            on_replay=lambda seq, d, ok: replayed.append(seq))
        loop.resume_with(recovered, journal=rec.IngestJournal(wal))
        unavailable_s = time.perf_counter() - t_crash
        assert replayed == list(range(snap_seq, acked))

        # resumed loop keeps serving AND acking durably
        n_before = loop.stats.queries_served
        for i in range(3):
            assert isinstance(
                loop.submit_ingest(batches[(acked + i) % len(batches)]),
                int)
            serve_some(i)
        loop.drain()
        assert loop.stats.queries_served > n_before
        inv.check_serve(loop).raise_if_failed()

        # zero acked-ingest loss: bit-identical to a fresh engine fed
        # every journaled batch in order
        oracle = _engine(vocab, docs_per_segment, docs, validate)
        for _, d in rec.read_journal(wal)[1]:
            oracle.ingest(d)
        fa = rec.engine_fingerprint(loop.engine)
        fb = rec.engine_fingerprint(oracle)
        fa.pop("stats"), fb.pop("stats")   # query counters legitimately differ
        assert fa == fb, "recovered serving state diverged from the journal"
        loop.journal.close()

    return {
        "chaos_unavailable_s": unavailable_s,
        "chaos_acked_batches": acked,
        "chaos_replayed_batches": len(replayed),
        "chaos_fingerprint_equal": True,
    }


def run(fast: bool = True, validate: bool = False):
    metrics = run_load(fast=fast, validate=validate)
    metrics.update(run_chaos(fast=fast, validate=validate))
    return metrics


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="serving-under-load benchmark (repro.core.serve)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--validate", action="store_true")
    ap.add_argument("--chaos-only", action="store_true",
                    help="run just the crash-under-load leg (CI chaos job)")
    args = ap.parse_args(argv)
    fn = run_chaos if args.chaos_only else run
    metrics = fn(fast=not args.full, validate=args.validate)
    for k, v in metrics.items():
        print(f"  {k:>24}: {v:.3f}" if isinstance(v, float)
              else f"  {k:>24}: {v}")


if __name__ == "__main__":
    main()
