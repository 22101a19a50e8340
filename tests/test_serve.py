"""Serving-layer contract tests (repro.core.serve): bounded admission
queues with explicit retry-after backpressure, coalescer flush triggers
(bucket full vs batch-deadline timer), the degradation ladder's
exactness contract at every rung (including a randomized-overload
property test), deadline accounting, ingest/query overlap bit-identity,
shed-is-final semantics with shed-then-retry after a rollover frees
slices, crash-under-serve recovery via journal replay + ``resume_with``,
and single-device vs 4-shard admission-stats agreement (subprocess)."""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis import invariants as inv
from repro.core import recovery as rec
from repro.core import serve as sv
from repro.core.lifecycle import AdmissionController, LifecycleEngine
from repro.core.pointers import PoolLayout


class Clock:
    """Manual loop clock: tests own time, so flush-timer and deadline
    behaviour is deterministic."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _engine(docs_per_segment=96, **kw):
    layout = PoolLayout(z=(1, 4, 7, 11), slices_per_pool=(256, 96, 24, 6))
    return LifecycleEngine(layout, 300, docs_per_segment, max_slices=64,
                           max_len=64, use_kernel=False, **kw)


def _docs(rng, n, width=6):
    return rng.integers(0, 300, size=(n, width), dtype=np.int64)


@pytest.fixture(scope="module")
def warm_engine():
    """One engine with a frozen side AND a live active segment, shared
    by every query-only test in this module."""
    eng = _engine()
    rng = np.random.default_rng(0)
    for _ in range(6):
        assert eng.ingest(_docs(rng, 24))
    assert eng.doc_base > 0 and eng.segments.active.next_docid > 0
    return eng


def _loop(engine, clock, **cfg):
    return sv.ServeLoop(engine, sv.ServeConfig(**cfg), clock=clock)


def _rung_oracle(eng, kind, terms, k, level, cfg):
    """The exactness contract for one (kind, rung): what the response's
    docids/scores MUST equal (docs/serving.md tabulates this)."""
    kk = k if level <= sv.DEGRADE_EARLY_EXIT \
        else max(1, k // cfg.reduced_k_factor)
    if kind == "scored":
        ids, scs = eng.scored_full_batch([list(terms)], k=256)[0]
        if level == sv.DEGRADE_FROZEN_ONLY:
            m = ids < eng.doc_base
            ids, scs = ids[m], scs[m]
        cut = k if level == sv.DEGRADE_NONE else kk
        return ids[:cut], scs[:cut]
    if kind == "phrase":
        full = eng.phrase(*terms)
    elif kind == "disjunctive":
        full = eng.disjunctive(list(terms))
    else:                              # conjunctive and topk
        full = eng.conjunctive(list(terms))
    if level == sv.DEGRADE_FROZEN_ONLY:
        full = full[full < eng.doc_base]
    if level == sv.DEGRADE_NONE:
        return (full[:k] if kind == "topk" else full), None
    return full[:kk], None


# ---------------------------------------------------------------------------
# Config + submission validation
# ---------------------------------------------------------------------------
def test_config_validation():
    with pytest.raises(ValueError):
        sv.ServeConfig(degrade_at=(0.9, 0.5, 0.95))
    with pytest.raises(ValueError):
        sv.ServeConfig(degrade_at=(0.0, 0.5, 0.9))
    with pytest.raises(ValueError):
        sv.ServeConfig(max_batch=0)
    with pytest.raises(ValueError):
        sv.ServeConfig(reduced_k_factor=1)


def test_unknown_query_kind_raises(warm_engine):
    loop = _loop(warm_engine, Clock())
    with pytest.raises(ValueError, match="unknown query kind"):
        loop.submit_query("regex", (1, 2))


def test_engine_dispatch_validates(warm_engine):
    with pytest.raises(ValueError, match="needs k"):
        warm_engine.dispatch("topk", [(1, 2)])
    with pytest.raises(ValueError, match="unknown query kind"):
        warm_engine.dispatch("regex", [(1, 2)], k=3)


def test_admission_min_segment_docs_validates():
    with pytest.raises(ValueError):
        AdmissionController(min_segment_docs=-1)


# ---------------------------------------------------------------------------
# Coalescer: flush on bucket-full vs batch-deadline timer
# ---------------------------------------------------------------------------
def test_flush_on_full_bucket(warm_engine):
    clock = Clock()
    loop = _loop(warm_engine, clock, max_batch=4, batch_wait_s=10.0)
    for _ in range(4):
        loop.submit_query("conjunctive", (5, 9))
    # timer is nowhere near due — the full bucket alone must flush
    assert loop.step() == 4
    assert loop.stats.flushes_full == 1
    assert loop.stats.flushes_timer == 0
    assert loop.pending_queries == 0


def test_flush_on_timer_not_before(warm_engine):
    clock = Clock()
    loop = _loop(warm_engine, clock, max_batch=32, batch_wait_s=0.010)
    loop.submit_query("conjunctive", (5, 9))
    clock.advance(0.004)
    assert loop.step() == 0            # partial bucket, timer not due
    assert loop.pending_queries == 1
    clock.advance(0.007)               # oldest is now 11ms old
    assert loop.step() == 1
    assert loop.stats.flushes_timer == 1
    assert loop.stats.flushes_full == 0


def test_mixed_kind_flush_coalesces_per_plan(warm_engine):
    """One flush with four execution classes -> four dispatches, one
    response per request, accounting conserved."""
    clock = Clock()
    loop = _loop(warm_engine, clock, max_batch=8)
    loop.force_level = 0
    for q in ((5, 9), (12, 3), (7,)):
        loop.submit_query("conjunctive", q)
    loop.submit_query("topk", (5, 9), k=4)   # its own class: early exit
    loop.submit_query("topk", (7,), k=4)     # coalesces with the topk
    loop.submit_query("scored", (5, 9), k=4)
    loop.submit_query("phrase", (5, 9))
    assert loop.step(force=True) == 7
    assert loop.stats.batches_dispatched == 4
    inv.check_serve(loop).raise_if_failed()


# ---------------------------------------------------------------------------
# Backpressure: bounded queues, explicit retry-after, never silent
# ---------------------------------------------------------------------------
def test_query_queue_backpressure(warm_engine):
    clock = Clock()
    loop = _loop(warm_engine, clock, query_queue_cap=3)
    for _ in range(3):
        assert isinstance(loop.submit_query("conjunctive", (5, 9)), int)
    r = loop.submit_query("conjunctive", (5, 9))
    assert isinstance(r, sv.Rejected)
    assert r.reason == "query_queue_full" and r.retry_after_s > 0
    assert loop.stats.queries_rejected == 1
    assert loop.stats.rejections_without_retry_after == 0
    loop.drain()                       # frees capacity: retry succeeds
    assert isinstance(loop.submit_query("conjunctive", (5, 9)), int)
    loop.drain()
    inv.check_serve(loop).raise_if_failed()


def test_ingest_queue_backpressure():
    eng = _engine()
    rng = np.random.default_rng(1)
    loop = _loop(eng, Clock(), ingest_queue_cap=2)
    assert isinstance(loop.submit_ingest(_docs(rng, 8)), int)
    assert isinstance(loop.submit_ingest(_docs(rng, 8)), int)
    r = loop.submit_ingest(_docs(rng, 8))
    assert isinstance(r, sv.Rejected)
    assert r.reason == "ingest_queue_full" and r.retry_after_s > 0
    loop.drain()
    assert loop.stats.ingest_applied == 2
    inv.check_serve(loop).raise_if_failed()


def test_ingest_pool_pressure_rejects_before_ack(tmp_path):
    """Critical allocator utilization rejects NEW ingest before the
    journal append — nothing is acked, nothing for replay to disagree
    about."""
    eng = _engine()
    rng = np.random.default_rng(2)
    jrnl = rec.IngestJournal(str(tmp_path / "wal.bin"))
    loop = sv.ServeLoop(eng, sv.ServeConfig(ingest_reject_util=0.0),
                        journal=jrnl, clock=Clock())
    r = loop.submit_ingest(_docs(rng, 8))
    assert isinstance(r, sv.Rejected) and r.reason == "pool_pressure"
    assert r.retry_after_s > 0
    jrnl.close()
    assert rec.read_journal(str(tmp_path / "wal.bin"))[1] == []
    inv.check_serve(loop).raise_if_failed()


def test_acked_ingest_applies_with_monotonic_seqs():
    eng = _engine()
    rng = np.random.default_rng(3)
    loop = _loop(eng, Clock())
    seqs = [loop.submit_ingest(_docs(rng, 16)) for _ in range(4)]
    assert seqs == [0, 1, 2, 3]
    loop.drain()
    assert loop.stats.ingest_applied == 4
    assert loop.stats.docs_indexed == 64
    assert loop.applied_seq == 4
    assert eng.doc_base + eng.segments.active.next_docid == 64


# ---------------------------------------------------------------------------
# The degradation ladder: every rung exact against its oracle
# ---------------------------------------------------------------------------
_LADDER_QUERIES = [("conjunctive", (5, 9)), ("conjunctive", (12, 3, 44)),
                   ("topk", (5, 9)), ("topk", (17,)),
                   ("disjunctive", (5, 9, 101)), ("phrase", (5, 9)),
                   ("scored", (5, 9)), ("scored", (12, 3))]


@pytest.mark.parametrize("level", [0, 1, 2, 3])
def test_ladder_rung_exactness(warm_engine, level):
    clock = Clock()
    cfg = sv.ServeConfig(max_batch=16, default_k=8)
    loop = sv.ServeLoop(warm_engine, cfg, clock=clock)
    loop.force_level = level
    for kind, terms in _LADDER_QUERIES:
        loop.submit_query(kind, terms, k=8)
    assert loop.step(force=True) == len(_LADDER_QUERIES)
    responses = sorted(loop.take_responses(), key=lambda r: r.qid)
    for (kind, terms), r in zip(_LADDER_QUERIES, responses):
        ids, scs = _rung_oracle(warm_engine, kind, terms, 8, level, cfg)
        assert np.array_equal(r.docids, ids), (kind, terms, level)
        if scs is None:
            assert r.scores is None
        else:
            assert np.array_equal(r.scores, scs), (kind, terms, level)
        assert r.level == level
        assert r.level_name == sv.LEVEL_NAMES[level]
        assert r.degraded == (level > 0)   # degraded is ALWAYS flagged
    assert loop.stats.served_by_level[level] == len(_LADDER_QUERIES)
    inv.check_serve(loop).raise_if_failed()


def test_gauge_maps_pressure_to_monotone_levels(warm_engine):
    loop = _loop(warm_engine, Clock(), degrade_at=(0.5, 0.75, 0.9))
    got = [loop.degradation_level(p)
           for p in (0.0, 0.49, 0.5, 0.74, 0.75, 0.89, 0.9, 2.0)]
    assert got == [0, 0, 1, 1, 2, 2, 3, 3]
    assert got == sorted(got)
    comp = loop.pressure_components()
    assert set(comp) == {"queue", "pool", "latency"}
    assert loop.overload_pressure() == max(comp.values())


@settings(max_examples=12, deadline=None)
@given(st.lists(st.tuples(st.integers(min_value=0, max_value=3),
                          st.integers(min_value=0, max_value=7),
                          st.integers(min_value=1, max_value=12)),
                min_size=1, max_size=6))
def test_ladder_exactness_random_overload_property(warm_engine, schedule):
    """Randomized overload schedule: each flush serves at an arbitrary
    forced rung; every response must match that rung's oracle exactly
    and carry the degraded flag iff level > 0."""
    cfg = sv.ServeConfig(max_batch=16, default_k=8)
    loop = sv.ServeLoop(warm_engine, cfg, clock=Clock())
    for level, qi, k in schedule:
        kind, terms = _LADDER_QUERIES[qi]
        loop.force_level = level
        qid = loop.submit_query(kind, terms, k=k)
        assert isinstance(qid, int)
        assert loop.step(force=True) == 1
        (r,) = loop.take_responses()
        ids, scs = _rung_oracle(warm_engine, kind, terms, k, level, cfg)
        assert np.array_equal(r.docids, ids), (kind, terms, k, level)
        if scs is not None:
            assert np.array_equal(r.scores, scs), (kind, terms, k, level)
        assert r.level == level and r.degraded == (level > 0)
    inv.check_serve(loop).raise_if_failed()


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------
def test_deadline_met_and_missed(warm_engine):
    clock = Clock()
    loop = _loop(warm_engine, clock, deadline_s=0.25)
    loop.submit_query("conjunctive", (5, 9), deadline_s=0.05)
    loop.submit_query("conjunctive", (5, 9))          # default budget
    clock.advance(0.1)                 # past the first's budget only
    loop.step(force=True)
    by_qid = {r.qid: r for r in loop.take_responses()}
    assert by_qid[0].deadline_met is False
    assert by_qid[1].deadline_met is True
    assert loop.stats.deadline_misses == 1
    assert by_qid[0].latency_s == pytest.approx(0.1)
    assert loop.stats.latency_ewma_s > 0


# ---------------------------------------------------------------------------
# Ingest/query overlap: async dispatch must not change any result
# ---------------------------------------------------------------------------
def test_overlapped_serving_bit_identical_to_reference():
    """Interleaved rounds through the loop (query dispatch -> ingest
    dispatch -> result sync) vs a plain reference engine queried before
    each ingest: every response identical, every round."""
    eng = _engine()
    ref = _engine()
    rng = np.random.default_rng(7)
    loop = _loop(eng, Clock(), max_batch=8)
    loop.force_level = 0
    queries = [(5, 9), (12, 3), (44, 7, 101), (17,)]
    for rnd in range(6):
        for q in queries:
            loop.submit_query("conjunctive", q)
        docs = _docs(rng, 24)
        assert isinstance(loop.submit_ingest(docs), int)
        want = [ref.conjunctive(list(q)) for q in queries]
        ref.ingest(docs)
        assert loop.step(force=True) == len(queries)
        got = sorted(loop.take_responses(), key=lambda r: r.qid)
        for w, g in zip(want, got):
            assert np.array_equal(g.docids, w), rnd
    assert loop.stats.ingest_applied == 6
    assert eng.doc_base == ref.doc_base
    inv.check_serve(loop).raise_if_failed()


# ---------------------------------------------------------------------------
# Shedding: final and loud; retry succeeds once a rollover frees slices
# ---------------------------------------------------------------------------
def _sym_batches(n_batches, vocab=64):
    """Shard-symmetric stream: doc d carries the single term d % vocab,
    so (vocab % n_shards == 0) puts every posting of term i on shard
    i % n_shards and per-shard pool utilization exactly equals the
    single-device trajectory — the basis of the stats-agreement test."""
    out, d = [], 0
    for _ in range(n_batches):
        out.append(np.arange(d, d + vocab, dtype=np.int64)
                   .reshape(vocab, 1) % vocab)
        d += vocab
    return out


def test_shed_is_final_then_retry_succeeds_after_rollover():
    """min_segment_docs withholds the emergency rollover, so utilization
    crosses shed_at and the engine refuses batches — loudly, finally.
    The serve loop counts them (never silently re-ingests: a live retry
    would diverge from single-pass journal replay).  A NEW submission
    after an explicit rollover frees the slices is admitted."""
    eng = _engine(
        docs_per_segment=100_000,
        admission=AdmissionController(rollover_at=0.6, shed_at=0.6,
                                      min_segment_docs=10_000))
    loop = _loop(eng, Clock())
    batches = _sym_batches(5)
    for docs in batches:
        assert isinstance(loop.submit_ingest(docs), int)
        loop.step(force=True)
    assert loop.stats.ingest_applied == 3      # util crosses at batch 4
    assert loop.stats.ingest_shed == 2
    assert eng.stats.shed_batches == 2
    assert eng.stats.emergency_rollovers == 0  # withheld by min_segment_docs
    inv.check_serve(loop).raise_if_failed()

    eng.segments.rollover()                    # operator action frees slices
    eng._sync_frozen()
    assert isinstance(loop.submit_ingest(batches[0]), int)
    loop.step(force=True)
    assert loop.stats.ingest_shed == 2         # retry ADMITTED, not shed
    assert loop.stats.ingest_applied == 4
    inv.check_serve(loop).raise_if_failed()


# ---------------------------------------------------------------------------
# Crash under serve: journal replay + resume_with, zero acked loss
# ---------------------------------------------------------------------------
def test_crash_under_serve_recovers_bit_identical(tmp_path):
    wal = str(tmp_path / "wal.bin")
    snap = str(tmp_path / "snap.bin")
    rng = np.random.default_rng(5)
    jrnl = rec.IngestJournal(wal)
    loop = sv.ServeLoop(_engine(), sv.ServeConfig(), journal=jrnl,
                        clock=Clock())
    for i in range(6):
        assert isinstance(loop.submit_ingest(_docs(rng, 24)), int)
        loop.step(force=True)
        if i == 2:
            loop.snapshot_now(snap)
    # two more batches acked (journaled) but NOT applied before the crash
    for _ in range(2):
        assert isinstance(loop.submit_ingest(_docs(rng, 24)), int)
    assert loop.pending_ingest == 2
    acked = jrnl.next_seq
    jrnl.close()                       # the crash: live engine is gone

    replayed = []
    recovered = rec.recover(
        snap, wal, expect_seq=acked,
        on_replay=lambda seq, docs, ok: replayed.append((seq, ok)))
    loop.resume_with(recovered, journal=rec.IngestJournal(wal))
    assert [s for s, _ in replayed] == [3, 4, 5, 6, 7]
    assert all(ok for _, ok in replayed)
    assert loop.pending_ingest == 0    # queued batches drained as recovered
    assert loop.stats.ingest_recovered == 2
    assert loop.stats.recoveries == 1
    assert loop.applied_seq == acked   # zero acked-ingest loss

    # bit-identity: a fresh engine fed every journaled record
    oracle = _engine()
    for _, docs in rec.read_journal(wal)[1]:
        oracle.ingest(docs)
    fa, fb = rec.engine_fingerprint(loop.engine), \
        rec.engine_fingerprint(oracle)
    fa.pop("stats"), fb.pop("stats")   # serve-side counters may differ
    assert fa == fb
    inv.check_serve(loop).raise_if_failed()

    # the resumed loop keeps serving AND keeps acking durably
    assert isinstance(loop.submit_ingest(_docs(rng, 24)), int)
    loop.submit_query("conjunctive", (5, 9))
    loop.drain()
    assert loop.stats.queries_served == 1
    inv.check_serve(loop).raise_if_failed()


# ---------------------------------------------------------------------------
# check_serve catches broken accounting
# ---------------------------------------------------------------------------
def test_check_serve_detects_lost_request(warm_engine):
    loop = _loop(warm_engine, Clock())
    loop.submit_query("conjunctive", (5, 9))
    loop.drain()
    assert inv.check_serve(loop).ok
    loop.stats.queries_submitted += 1          # a request vanishes
    rep = inv.check_serve(loop)
    assert not rep.ok and "silently dropped" in rep.render()
    loop.stats.queries_submitted -= 1
    loop.stats.rejections_without_retry_after = 1
    with pytest.raises(inv.InvariantViolation):
        inv.check_serve(loop).raise_if_failed()


# ---------------------------------------------------------------------------
# stable_shapes: the frozen-gather bucket ratchet serving relies on
# ---------------------------------------------------------------------------
def test_stable_shapes_bit_identical_and_ratchets():
    """``stable_shapes=True`` pins the frozen-gather pow2 width buckets
    to the widest ever seen — after the heaviest term has been gathered
    there is ONE jit shape per plan, which is what bounds the serving
    loop's tail latency — and changes no result bit (padding is
    masked)."""
    rng = np.random.default_rng(5)
    ref, pin = _engine(), _engine(stable_shapes=True)
    docs = _docs(rng, 24 * 6)
    for j in range(6):
        assert ref.ingest(docs[24 * j: 24 * (j + 1)])
        assert pin.ingest(docs[24 * j: 24 * (j + 1)])
    freqs = np.bincount(docs.ravel(), minlength=300)
    heavy, tail = int(freqs.argmax()), int(freqs.argmin())
    assert ref._shape_floors is None and pin._shape_floors == {}

    # tail-only batch first: the pin engine records small floors ...
    for eng in (ref, pin):
        eng.conjunctive_batch([(tail, tail)])
        eng.scored_topk_batch([(tail,)], 3)
    small = dict(pin._shape_floors)
    assert small.get("nb", 0) >= 1
    # ... the heavy batch ratchets them up ...
    for qs in ([(heavy, tail)], [(heavy,)], [(tail,)], [(heavy, 1, 2)]):
        for fo in (False, True):
            a = ref.conjunctive_batch(qs, frozen_only=fo)
            b = pin.conjunctive_batch(qs, frozen_only=fo)
            np.testing.assert_array_equal(a[0], b[0])
            a = ref.disjunctive_batch(qs, frozen_only=fo)
            b = pin.disjunctive_batch(qs, frozen_only=fo)
            np.testing.assert_array_equal(a[0], b[0])
            a = ref.topk_conjunctive_batch(qs, 5, fo)
            b = pin.topk_conjunctive_batch(qs, 5, fo)
            np.testing.assert_array_equal(a[0], b[0])
            (ai, asc), = ref.scored_topk_batch(qs, 5, frozen_only=fo)
            (bi, bsc), = pin.scored_topk_batch(qs, 5, frozen_only=fo)
            np.testing.assert_array_equal(ai, bi)
            np.testing.assert_array_equal(asc, bsc)
    a = ref.phrase_batch([(heavy, tail)])
    b = pin.phrase_batch([(heavy, tail)])
    np.testing.assert_array_equal(a[0], b[0])
    grown = dict(pin._shape_floors)
    assert grown["nb"] >= small["nb"] and grown["pw"] >= small["pw"]

    # ... and a later tail-only batch REUSES the ratcheted buckets (no
    # shrink => no new jit shape), still bit-identical
    a = ref.conjunctive_batch([(tail,)])
    b = pin.conjunctive_batch([(tail,)])
    np.testing.assert_array_equal(a[0], b[0])
    assert dict(pin._shape_floors) == grown
    # the ratchet survives a rollover's stack rebuild (floors are
    # engine-owned, not stack-owned)
    pin.segments.rollover()
    pin._sync_frozen()
    pin.conjunctive_batch([(tail,)])
    assert pin._shape_floors["nb"] >= grown["nb"]
    # and round-trips through the snapshot config
    from repro.core import recovery as rcv
    with tempfile.TemporaryDirectory() as wd:
        path = os.path.join(wd, "s.bin")
        rcv.snapshot(pin, path)
        back = rcv.restore(path, use_kernel=False)
    assert back.stable_shapes and back._shape_floors == {}


# ---------------------------------------------------------------------------
# 4-shard agreement (subprocess keeps forced host devices isolated)
# ---------------------------------------------------------------------------
SCRIPT_SHARDED = textwrap.dedent("""
    from repro.dist import collectives as C
    C.force_host_device_count(4)
    import json
    import numpy as np

    from repro.analysis import invariants as inv
    from repro.core import serve as sv
    from repro.core.lifecycle import (AdmissionController, LifecycleEngine,
                                      ShardedLifecycleEngine)
    from repro.core.pointers import PoolLayout
    from repro.core.sharded_index import make_doc_mesh

    V = 64
    def sym_batches(n):
        out, d = [], 0
        for _ in range(n):
            out.append(np.arange(d, d + V, dtype=np.int64)
                       .reshape(V, 1) % V)
            d += V
        return out

    mesh, rules = make_doc_mesh(4)
    def mk(adm, sharded):
        # per-shard pools are exactly 1/4 of the single-device pools and
        # the symmetric stream splits term-for-term across shards, so
        # both engines see the SAME utilization trajectory.
        if sharded:
            return ShardedLifecycleEngine(
                PoolLayout(z=(1, 4, 7, 11), slices_per_pool=(64, 24, 6, 2)),
                128, 100_000, mesh, max_slices=64, max_len=64, rules=rules,
                use_kernel=False, admission=adm)
        return LifecycleEngine(
            PoolLayout(z=(1, 4, 7, 11), slices_per_pool=(256, 96, 24, 8)),
            128, 100_000, max_slices=64, max_len=64, use_kernel=False,
            admission=adm)

    batches = sym_batches(30)
    out = {}

    # emergency-rollover stats agree batch for batch
    e1 = mk(AdmissionController(rollover_at=0.6), False)
    e4 = mk(AdmissionController(rollover_at=0.6), True)
    for docs in batches:
        assert e1.ingest(docs) and e4.ingest(docs)
    assert e1.stats.emergency_rollovers == e4.stats.emergency_rollovers > 0
    assert e1.stats.shed_batches == e4.stats.shed_batches == 0
    out["emergency_rollovers"] = e4.stats.emergency_rollovers

    # shed stats agree batch for batch (rollover withheld)
    adm = lambda: AdmissionController(rollover_at=0.6, shed_at=0.6,
                                      min_segment_docs=10_000)
    h1, h4 = mk(adm(), False), mk(adm(), True)
    for docs in batches:
        a, b = h1.ingest(docs), h4.ingest(docs)
        assert a == b
    assert h1.stats.shed_batches == h4.stats.shed_batches > 0
    assert h1.stats.docs_ingested == h4.stats.docs_ingested
    out["shed_batches"] = h4.stats.shed_batches

    # shed-then-retry on the SHARDED engine: rollover frees, retry lands
    assert h4.ingest(batches[0]) is False
    h4.segments.rollover()
    h4._sync_frozen()
    assert h4.ingest(batches[0]) is True
    out["retry_after_rollover"] = True

    # the serving loop runs unmodified over a sharded engine
    loop = sv.ServeLoop(e4, sv.ServeConfig(default_k=8))
    for level in (0, 3):
        loop.force_level = level
        loop.submit_query("conjunctive", (3, 7), k=8)
        loop.step(force=True)
        (r,) = loop.take_responses()
        full = e1.conjunctive([3, 7])
        if level == 3:
            full = full[full < e4.doc_base][:2]
        assert np.array_equal(r.docids, full), level
    inv.check_serve(loop).raise_if_failed()
    out["sharded_serve_ok"] = True
    print(json.dumps(out))
""")


def _run_subprocess(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_admission_stats_agree_with_single_device():
    res = _run_subprocess(SCRIPT_SHARDED)
    assert res["emergency_rollovers"] > 0
    assert res["shed_batches"] > 0
    assert res["retry_after_rollover"] and res["sharded_serve_ok"]
