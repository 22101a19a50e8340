"""The program's spans and counters, and their reduction
(``chipbench/spans.py``): known numbers on hand-made events, a recorded
CPU trace (``data/record_program_spans.py``), a tiny ``ServeLoop``
traced live, and the coalescer's counters and per-query timestamps
under a manual clock."""
from pathlib import Path

import numpy as np
import pytest

from chipbench import peaks, spans, trace
from chipbench.tests import tiny
from chipbench.trace import Op

DATA = Path(__file__).resolve().parent / "data" / \
    "program_window.xplane.pb"
MS = 1_000_000      # ns


def test_split_name_reads_the_metadata_suffix():
    assert spans.split_name("serve.dispatch#qid=3,rows=4,tag=a#") == (
        "serve.dispatch", {"qid": 3, "rows": 4, "tag": "a"})
    assert spans.split_name("journal.append") == ("journal.append", {})


def test_reduce_hand_made_spans():
    ops = [Op(0, 2 * MS, "a", "jit_ingest"), Op(9 * MS, 10 * MS, "b", "p")]
    host = [(0, 10 * MS, "window"), (1 * MS, 6 * MS, "submit_ingest"),
            (6 * MS, 9 * MS, "step")]
    line = ("host", 0)
    raw = [(1 * MS, 2 * MS, "serve.admit", {}, line),
           (2 * MS, 5 * MS, "journal.append", {"seq": 7}, line),
           (6 * MS, 9 * MS, "serve.collect", {"qid": 1}, line),
           (6 * MS, 8.5 * MS, "qexec.sync", {}, line),
           (8.5 * MS, 9 * MS, "qexec.finish", {}, line),
           (11 * MS, 12 * MS, "serve.ingest", {}, line)]   # past it
    s = spans.reduce(ops, host, raw)
    assert s.window_s == pytest.approx(0.010)
    assert s.span_count("serve.ingest") == 0
    assert s.span_s("journal.append") == pytest.approx(0.003)
    assert s.span_s("serve.collect") == pytest.approx(0.0)
    assert s.span_s("serve.collect", self_time=False) == \
        pytest.approx(0.003)
    assert s.span_s("qexec.sync") == pytest.approx(0.0025)
    collect = [x for x in s.spans if x.name == "serve.collect"][0]
    assert [s.spans[x.parent].name for x in s.spans
            if x.name.startswith("qexec.")] == ["serve.collect"] * 2
    assert collect.parent == -1 and collect.args == {"qid": 1}
    # one gap, [2, 9] ms: submit_ingest 4 ms against step 3 ms; the host
    # sat in qexec.sync (2.5 ms) less than in journal.append (3 ms)
    assert s.gaps == [
        pytest.approx((0.007, "submit_ingest/journal.append"))]
    assert s.idle_by_span() == {
        "submit_ingest/journal.append": pytest.approx(0.007)}
    # instant by instant the same gap is four pieces
    assert s.idle == {"submit_ingest/journal.append": pytest.approx(0.003),
                      "step/qexec.sync": pytest.approx(0.0025),
                      "submit_ingest": pytest.approx(0.001),
                      "step/qexec.finish": pytest.approx(0.0005)}


def test_gaps_under_no_program_span_keep_the_harness_name():
    ops = [Op(0, 1 * MS, "a", "p"), Op(4 * MS, 5 * MS, "a", "p")]
    host = [(0, 6 * MS, "window"), (1 * MS, 4 * MS, "wait")]
    s = spans.reduce(ops, host, [])
    assert [n for _, n in s.gaps] == ["wait", "none"]
    assert s.idle_by_span() == {"wait": pytest.approx(0.003),
                                "none": pytest.approx(0.001)}
    assert s.idle == s.idle_by_span()


def test_recorded_program_spans():
    summary, s = spans.load(DATA)
    names = {"serve.admit", "journal.append", "serve.flush",
             "serve.dispatch", "serve.collect", "qexec.sync", "qexec.finish"}
    assert {x.name for x in s.spans} == names
    assert all(s.span_count(n) == 2 for n in names)
    assert [x.args["seq"] for x in s.spans
            if x.name == "journal.append"] == [0, 1]
    assert [(x.args["qid"], x.args["rows"], x.args["slots"])
            for x in s.spans if x.name == "serve.dispatch"] == [
                (0, 2, 4), (2, 2, 4)]
    assert [x.args for x in s.spans if x.name == "serve.flush"] == [
        {"queries": 2, "groups": 1, "level": 0}] * 2
    whole = s.span_s("serve.collect", self_time=False)
    assert s.span_s("serve.collect") == pytest.approx(
        whole - s.span_s("qexec.sync") - s.span_s("qexec.finish"))
    # every gap of the window is named, and the longest as trace.load
    # names them, with the program span the host sat in after the "/"
    assert sum(g for g, _ in s.gaps) == pytest.approx(
        summary.window_s - summary.busy_s)
    longest = sorted(s.gaps, key=lambda g: -g[0])[:trace.TOP]
    assert [n.split("/")[0] for _, n in longest] == [
        n for _, n in summary.gaps]
    idle = s.idle_by_span()
    assert sum(idle.values()) == pytest.approx(sum(g for g, _ in s.gaps))
    assert sum(s.idle.values()) == pytest.approx(sum(idle.values()))
    assert idle["submit_ingest/journal.append"] >= 0.006   # 3 ms, twice
    # qexec.finish's 2 ms runs on into the wait's 4 ms, twice: one gap
    # each time, named by the wait, that the split takes apart
    assert idle["wait"] >= 0.012
    assert s.idle["step/qexec.finish"] >= 0.004
    assert s.idle["wait"] >= 0.008


def _engine(compaction=None):
    from repro.core.lifecycle import LifecycleEngine
    from repro.core.pointers import PoolLayout
    layout = PoolLayout(z=(1, 4, 7, 11), slices_per_pool=(256, 96, 24, 6))
    return LifecycleEngine(layout, 300, 96, max_slices=64, max_len=64,
                           max_query_len=4, use_kernel=False,
                           stable_shapes=True, compaction=compaction)


def test_serve_loop_spans_in_a_live_trace(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core import serve as sv
    from repro.core.recovery import IngestJournal
    from repro.core.segments import CompactionPolicy

    eng = _engine(CompactionPolicy(fanout=2))
    rng = np.random.default_rng(0)
    for _ in range(2):                   # one frozen segment of 96 docs
        assert eng.ingest(rng.integers(0, 300, (48, 6)))
    journal = IngestJournal(str(tmp_path / "wal"))
    loop = sv.ServeLoop(eng, sv.ServeConfig(max_batch=4), journal=journal)
    batches = [rng.integers(0, 300, (48, 6)) for _ in range(4)]
    queries = [(5, 9), (7,), (3, 11, 12), (4,)]

    def one_round(docs):
        with TraceAnnotation("submit_ingest"):
            loop.submit_ingest(docs)
        for t in queries:
            loop.submit_query("topk", t, k=5)
        with TraceAnnotation("step"):
            loop.step(force=True)

    one_round(batches[0])                # compile outside the trace
    loop.take_responses()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    with TraceAnnotation("window"):
        for docs in batches[1:]:
            one_round(docs)
    jax.profiler.stop_trace()
    journal.close()
    assert len(loop.take_responses()) == 3 * len(queries)

    path = spans.newest(tmp_path / "trace")
    host, raw = spans.host_events(path)
    summary = trace.load(path)
    s = spans.reduce(summary.ops, host, raw)
    by = {n: [x for x in s.spans if x.name == n]
          for n in {x.name for x in s.spans}}
    for n in ("serve.admit", "journal.append", "serve.ingest",
              "serve.flush", "serve.dispatch", "serve.collect",
              "qexec.sync", "qexec.finish", "qexec.frozen_gather"):
        assert len(by[n]) == 3, n
    # with the 48 docs before it, the window's 144 fill two segments;
    # fanout 2 merges the first with the one frozen before the loop
    assert len(by["segments.rollover"]) == 2
    assert len(by["segments.compact"]) >= 1
    assert all(x.args == {"docs": 96} for x in by["segments.rollover"])

    def parent(x):
        return s.spans[x.parent].name if x.parent >= 0 else None

    assert {parent(x) for x in by["serve.dispatch"]} == {"serve.flush"}
    assert {parent(x) for x in by["qexec.sync"]} == {"serve.collect"}
    assert {parent(x) for x in by["qexec.finish"]} == {"serve.collect"}
    assert {parent(x) for x in by["qexec.frozen_gather"]} == {
        "serve.dispatch"}
    assert {parent(x) for x in by["segments.rollover"]} == {"serve.ingest"}
    assert {parent(x) for x in by["segments.compact"]} == {
        "segments.rollover"}
    # serve.admit, then journal.append, within each submit_ingest call
    submits = [(a, b) for a, b, n in host if n == "submit_ingest"]
    assert len(submits) == 3
    for (a, b), adm, app in zip(submits, by["serve.admit"],
                                by["journal.append"]):
        assert a <= adm.start < adm.end <= app.start < app.end <= b
    # one request's spans share its id
    first = [4, 8, 12]                   # qids 0-3 came before the trace
    assert [x.args["qid"] for x in by["serve.dispatch"]] == first
    assert [x.args["qid"] for x in by["serve.collect"]] == first
    assert all(x.args["queries"] == 4 for x in by["serve.flush"])
    assert [x.args["seq"] for x in by["journal.append"]] == [1, 2, 3]
    assert [x.args["seq"] for x in by["serve.ingest"]] == [1, 2, 3]
    assert all(x.args["bytes"] == batches[0].nbytes
               for x in by["journal.append"])
    assert all((x.args["rows"], x.args["slots"]) == (4, 4)
               for x in by["serve.dispatch"])
    assert all(x.args["bytes"] > 0 for x in by["qexec.sync"])
    assert all(x.args["bytes"] > 0 for x in by["qexec.frozen_gather"])


def test_counters_and_timestamps_under_a_manual_clock():
    from repro.analysis import invariants
    from repro.core import serve as sv

    eng = _engine()
    rng = np.random.default_rng(1)
    assert eng.ingest(rng.integers(0, 300, (48, 6)))
    pend = eng.dispatch("conjunctive", [(5,), (7, 9, 11)])
    assert (pend.rows, pend.slots) == (2, 4)
    pend.wait()
    oracle = _engine()
    oracle.batched = False               # the per-query oracle path
    pend = oracle.dispatch("topk", [(5,)], k=3)
    assert (pend.rows, pend.slots) == (0, 0)

    # the loop reads its clock at each acceptance, at the step's start
    # (the flush) and once the batch's results are back
    clock = iter([1.0, 1.25, 1.5, 1.75]).__next__
    loop = sv.ServeLoop(eng, sv.ServeConfig(max_batch=8, batch_wait_s=0.5),
                        clock=clock)
    loop.force_level = sv.DEGRADE_NONE
    loop.submit_query("topk", (5,), k=5)
    loop.submit_query("topk", (7, 9, 11), k=5)
    assert loop.step() == 2              # the oldest waited batch_wait_s
    assert loop.stats.query_cells_dispatched == 8      # 2 rows x 4 slots
    assert loop.stats.query_terms_live == 4
    r = {x.qid: x for x in loop.take_responses()}
    assert (r[0].queued_s, r[0].service_s) == (0.5, 0.25)
    assert (r[1].queued_s, r[1].service_s) == (0.25, 0.25)
    for x in r.values():
        assert x.queued_s + x.service_s == x.latency_s
    invariants.check_serve(loop).raise_if_failed()


def test_traced_ingest_run_names_gaps_by_program_span(tmp_path,
                                                      monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.V5E)
    cell = "earlybird.ingest"
    out = tiny.run_tiny(cell, tmp_path, trace=True)
    assert out["correct"]
    # the CPU runs no Pallas kernel, so no kernel roofline
    assert set(out["metrics"]) == {"device_idle_pct.ingest",
                                   "ingest_ms_per_batch"}
    summary, s = spans.load(tmp_path / "trace")
    n = s.span_count("serve.ingest")
    assert n > 0 and abs(s.span_count("journal.append") - n) <= 2
    assert s.span_count("serve.admit") == s.span_count("journal.append")
    assert s.span_s("journal.append") > 0 and s.span_s("serve.ingest") > 0
    idle = s.idle_by_span()
    assert any(k.startswith("submit_ingest/") or k.startswith("step/")
               for k in idle)
    assert sum(idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s)
