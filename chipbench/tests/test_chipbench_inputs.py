"""The benchmark's yardstick on the CPU: generators, reference, byte
counts, lookup by name, and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, peaks, reference, registry, roofline, streams
from chipbench.context import Context
from chipbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def _pool(seed):
    cell = tiny.tiny_cell("tweets2011.active_topk")
    feed = harness.Feed(harness.make_stream(cell.config, seed), 256)
    feed.make(8)
    return feed, harness._query_pool(cell, feed, 2.0, None)


def test_traffic_is_identical_for_one_seed():
    (f1, p1), (f2, p2) = _pool(tiny.SEED), _pool(tiny.SEED)
    for a, b in zip(f1.made, f2.made):
        assert np.array_equal(a.docs, b.docs)
    assert p1.terms == p2.terms
    assert np.array_equal(p1.gaps, p2.gaps)
    assert p1.heavy == p2.heavy


def test_other_seeds_reorder_the_same_sizes():
    # other tweets and terms; the same query lengths and arrivals, in
    # the same order
    (f1, p1), (f2, p2) = _pool(1), _pool(2)
    assert not np.array_equal(f1.made[0].docs, f2.made[0].docs)
    assert p1.terms != p2.terms
    assert np.array_equal(p1.gaps, p2.gaps)
    assert list(map(len, p1.terms)) == list(map(len, p2.terms))


def test_stream_rows_follow_the_configured_shape():
    s = streams.TweetStream(vocab=1000, mean_len=11, alpha=1.0, width=32,
                            seed=tiny.SEED)
    b = s.batch(3, 500)
    lens = (b >= 0).sum(1)
    assert b.shape == (500, 32) and lens.min() >= 1
    assert (b[b >= 0] < 1000).all()
    assert 9 < lens.mean() < 13


def test_roofline_bytes_match_a_hand_count():
    # 8192 tweets x 32 slots = 262144 entries, whole 1024-entry chunks:
    # 7 streams x 4 bytes x 262144
    assert roofline.bulk_append_bytes(8192 * 32) == 7_340_032
    # 1000 entries pad to one chunk of 1024
    assert roofline.bulk_append_bytes(1000) == 7 * 4 * 1024


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_a_new_cell_and_metric_are_found_by_name(tmp_path):
    for d in ("configs", "traffic", "layer_metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "toy.json").write_text(json.dumps({"k": 1}))
    (tmp_path / "traffic" / "burst.json").write_text(
        json.dumps({"feed": "open", "rate": 9}))
    (tmp_path / "layer_metrics" / "toy_share.q.py").write_text(
        "def read(ctx):\n    return ctx.counters['n'] * 2\n")
    bench = {"workloads": [{"name": "toy.burst", "config": "toy",
                            "traffic": "burst", "chips": 1, "why": "x"}],
             "end_to_end": [{"name": "setup_s", "unit": "s"}],
             "per_layer": [{"name": "toy_share.q", "unit": "%",
                            "workloads": ["toy.burst"]},
                           {"name": "other", "unit": "%",
                            "workloads": ["elsewhere"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("toy.burst", tmp_path / "BENCHMARK.json",
                             root=tmp_path)
    assert cell.config == {"k": 1} and cell.mix["rate"] == 9
    assert [m["name"] for m in cell.per_layer] == ["toy_share.q"]
    read = registry.reader("toy_share.q", root=tmp_path / "layer_metrics")
    ctx = Context(trace=None, counters={"n": 21}, window={}, shapes={},
                  peaks={})
    assert read(ctx) == 42
    with pytest.raises(FileNotFoundError):
        registry.reader("missing", root=tmp_path / "layer_metrics")


def test_every_per_layer_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(registry.reader(m["name"]))
    for w in bench["workloads"]:
        assert harness.load_cell(w["name"]).config["name"] == w["config"]


def test_reference_agrees_with_the_engine():
    cell = tiny.tiny_cell("tweets2011.active_topk")
    cfg = cell.config
    engine = harness.build_engine(cfg)
    feed = harness.Feed(harness.make_stream(cfg, 7), cfg["ingest_batch_docs"])
    batches = feed.make(40)                 # a rollover and a part
    for b in batches:
        engine.ingest(b.docs)
    assert engine.stats.rollovers == 1
    counts = np.bincount(np.concatenate([b.docs[b.docs >= 0]
                                         for b in batches]),
                         minlength=cfg["vocab"])
    head = [int(t) for t in np.argsort(-counts)[:6]]
    queries = [(head[0],), (head[1], head[2]), (head[0], head[3], head[5]),
               (head[4], head[0])]
    idx = reference.QueryIndex(head)
    for b in batches:
        idx.add(b.docs)
    idx.finish()
    n = engine.stats.docs_ingested
    for q in queries:
        assert np.array_equal(engine.conjunctive(q), idx.conjunctive(q, n))
        ids, sc = engine.scored_full(q, 30)
        want_ids, want_sc = idx.scored(q, 30, n)
        assert np.array_equal(ids, want_ids) and np.array_equal(sc, want_sc)
    # postings as the pool holds them, for the active segment's batches
    from repro.core.segments import freeze
    act = batches[32:]
    fz = freeze(engine.segments.active)
    first = 0
    want = {t: [] for t in head}
    for b in act:
        for t, p in reference.postings_of(b.docs, first, head).items():
            want[t].append(p)
        first += b.docs.shape[0]
    for t in head:
        assert np.array_equal(fz.postings(t), np.concatenate(want[t]))


def test_journal_reader_reads_what_the_journal_wrote(tmp_path):
    from repro.core.recovery import IngestJournal
    rng = np.random.default_rng(0)
    batches = [rng.integers(-1, 100, (5, 4)).astype(np.int32)
               for _ in range(3)]
    with IngestJournal(str(tmp_path / "j.wal")) as j:
        seqs = [j.append(b) for b in batches]
    base, recs = reference.read_journal(str(tmp_path / "j.wal"))
    assert base == 0 and [s for s, _ in recs] == seqs
    assert all(np.array_equal(a, b) for (_, a), b in zip(recs, batches))
    with open(tmp_path / "j.wal", "ab") as f:       # a torn tail is dropped
        f.write(b"\x01\x02")
    assert len(reference.read_journal(str(tmp_path / "j.wal"))[1]) == 3


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "earlybird.ingest",
         "--seed", str(tiny.SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
