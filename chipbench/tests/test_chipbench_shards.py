"""A configuration's ``shards``: the harness builds and checks a
document-sharded deployment from it.  On four emulated CPU devices a
four-shard configuration runs both mixes correct, its controls and a
fault in one shard's heap come out not correct; a shard count the cell
cannot run is refused at load; one shard builds what it always built;
the trace sums device time over chips and the roofline reader counts
one kernel call a chip."""
import ast
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness, peaks, registry, roofline, trace
from chipbench.context import Context
from chipbench.trace import Op
from chipbench.tests import tiny

MS = 1_000_000      # ns

SHARDED = r"""
import ast, json, sys
import jax
from chipbench import harness
from chipbench.tests import tiny


def heap_corrupted(engine, loop):
    # every ingest leaves position bit 0 set in shard 1's first pool,
    # which holds postings only: its counts stay right, postings do not
    act, lay = engine.segments.active, engine.layout
    n0 = lay.pool_base[0] + lay.slices_per_pool[0] * lay.slice_sizes[0]
    ingest = act._ingest

    def corrupt(state, *a):
        st = ingest(state, *a)
        return st._replace(heap=st.heap.at[1, :n0].set(st.heap[1, :n0] | 1))
    act._ingest = corrupt


CASES = {"ingest": ("earlybird.ingest", {}),
         "topk": ("tweets2011.active_topk", {}),
         "ingest_no_journal": ("earlybird.ingest", {"control": "no_journal"}),
         "topk_degrade": ("tweets2011.active_topk", {"control": "degrade"}),
         "ingest_heap_fault": ("earlybird.ingest", {"fault": heap_corrupted})}
res = {}
for case, (name, kw) in CASES.items():
    cell = tiny.tiny_cell(name)
    cell.chips = 4
    cell.config["shards"] = 4
    lines = []
    out = harness.run_cell(cell, seed=tiny.SEED, seconds=tiny.SECONDS[name],
                           trace=False, devices=jax.devices(),
                           work=sys.argv[1] + "/" + case, log=lines.append,
                           **kw)
    info = [ln for ln in lines if ln.startswith("reference: ")]
    res[case] = {"correct": out["correct"], "device": out["device"],
                 "metrics": sorted(out["metrics"]),
                 "checks": {k: c["value"] for k, c in out["checks"].items()},
                 "info": ast.literal_eval(info[0][len("reference: "):]),
                 "setup": [ln for ln in lines if ln.startswith("setup: ")],
                 "window": [ln for ln in lines if ln.startswith("window: ")]}
print(json.dumps(res))
"""


@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    """Every four-shard run, in one process on four CPU devices."""
    work = tmp_path_factory.mktemp("sharded")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(tiny.ROOT),
                                           str(tiny.ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", SHARDED, str(work)], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1])


@pytest.mark.parametrize("case", ["ingest", "topk"])
def test_four_shards_run_correct(sharded, case):
    r = sharded[case]
    assert r["correct"], r["checks"]
    assert all(v == 0 for v in r["checks"].values()), r["checks"]
    assert r["device"]["count"] == 4
    assert "shards 4" in r["setup"][0]
    assert "compiles inside it: 0 " in r["window"][0]
    if case == "topk":
        assert r["info"]["answers_compared"] > 0
        assert "freshness_mean_ms" in r["metrics"]
    else:
        assert r["info"]["postings_read"] > 0


@pytest.mark.parametrize("case", ["ingest_no_journal", "topk_degrade"])
def test_four_shard_controls_are_not_correct(sharded, case):
    assert not sharded[case]["correct"]


def test_a_fault_in_one_shards_heap_is_not_correct(sharded):
    r = sharded["ingest_heap_fault"]
    assert not r["correct"]
    assert r["checks"]["postings_wrong"] > 0
    assert r["checks"]["freq_wrong"] == 0


def _bench(tmp_path, cfg_changes, chips):
    """A benchmark of one query cell on a changed tweets2011, in
    ``tmp_path``: (BENCHMARK.json, root of configs/ and traffic/)."""
    for d in ("configs", "traffic"):
        (tmp_path / d).mkdir()
    cfg = json.loads((harness.ROOT / "configs" / "tweets2011.json")
                     .read_text())
    cfg.update(cfg_changes)
    (tmp_path / "configs" / "x.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic" / "active_topk.json").write_text(
        (harness.ROOT / "traffic" / "active_topk.json").read_text())
    bench = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "x.active_topk", "config": "x",
                           "traffic": "active_topk", "chips": chips,
                           "why": "test"}]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path, tmp_path


@pytest.mark.parametrize("changes,chips,says", [
    ({"shards": 4, "docs_per_segment": 131074}, 4, "docs_per_segment"),
    ({"shards": 4, "ingest_batch_docs": 510}, 4, "ingest_batch_docs"),
    ({"shards": 4}, 1, "need 4 chips"),
])
def test_a_shard_count_the_cell_cannot_run_is_refused_at_load(
        tmp_path, changes, chips, says):
    bench, root = _bench(tmp_path, changes, chips)
    with pytest.raises(ValueError, match=says):
        harness.load_cell("x.active_topk", bench, root)


def test_a_shard_count_the_cell_can_run_loads(tmp_path):
    bench, root = _bench(tmp_path, {"shards": 4}, 4)
    cell = harness.load_cell("x.active_topk", bench, root)
    assert harness.shards(cell.config) == 4
    assert harness._shapes(cell)["shards"] == 4


# the parent's plans, before configurations had shards: slices a pool,
# max_len, max_slices and heap bytes
PLANS = {"earlybird": ([1362734, 1350767, 488530, 90537], 8388608, 4101,
                       1089157632),
         "tweets2011": ([365710, 64944, 7709, 1138], 131072, 67, 20352000)}


@pytest.mark.parametrize("name", sorted(PLANS))
@pytest.mark.parametrize("one", [{}, {"shards": 1}])
def test_one_shard_plans_what_it_always_planned(name, one):
    from repro.core import slicepool
    cfg = json.loads((harness.ROOT / "configs" / f"{name}.json").read_text())
    assert "shards" not in cfg
    layout, max_len, max_slices = harness.capacity_plan(dict(cfg, **one))
    heap = slicepool.row_words(layout.total_slots) * 4
    assert (list(layout.slices_per_pool), max_len, max_slices,
            heap) == PLANS[name]


@pytest.mark.parametrize("name", ["earlybird.ingest",
                                  "tweets2011.active_topk"])
def test_one_shard_builds_the_one_device_engine(name):
    from repro.core.lifecycle import LifecycleEngine
    cfg = tiny.tiny_cell(name).config
    built = [harness.build_engine(c) for c in (cfg, dict(cfg, shards=1))]
    for e in built:
        assert type(e) is LifecycleEngine
        assert e.segments.active.state.freq.ndim == 1
    a, b = built
    assert (a.layout, a.max_len, a.max_slices) == (b.layout, b.max_len,
                                                   b.max_slices)


def test_device_time_is_summed_over_chips():
    """A kernel's device time is summed over the chips that run it (the
    roofline sets every chip's bytes against it); a program's is its
    wall time, the union across chips, as a time a batch wants."""
    ops = {f"/device:TPU:{i}": [Op(i * MS, (i + 2) * MS, "k", "jit_ingest",
                                   True)]
           for i in range(4)}
    s = trace.reduce(ops, [(0, 8 * MS, "window")], {"jit_ingest": 4})
    assert s.busy_s == pytest.approx(0.002)          # each chip's mean
    assert s.program_s("jit_ingest") == pytest.approx(0.005)
    assert s.kernel_s("jit_ingest") == pytest.approx(0.008)



@pytest.mark.parametrize("shards", [1, 4])
def test_roofline_counts_one_call_a_chip(shards):
    """Each chip's call streams its quarter of the batch, padded to
    whole chunks: the bytes are the calls', over the chips' summed
    kernel time."""
    entries = 512 * 32
    ops = {f"/device:TPU:{i}": [Op(0, 1 * MS, "k", "jit_ingest", True)]
           for i in range(shards)}
    t = trace.reduce(ops, [(0, 2 * MS, "window")], {"jit_ingest": shards})
    ctx = Context(trace=t, counters={}, window={"ingest": 1},
                  shapes={"ingest_entries": entries, "shards": shards},
                  peaks=peaks.V5E)
    got = registry.reader("bulk_append_roofline")(ctx)
    per_call = roofline.bulk_append_bytes(entries // shards)
    want = 100.0 * shards * per_call / peaks.V5E["hbm_bytes_per_s"] / (
        shards * 1e-3)
    assert got == pytest.approx(want)



class _Heap:
    """A pool state's one leaf: its placement, and nothing to wait on."""

    def __init__(self, sharding):
        self.sharding = sharding

    def block_until_ready(self):
        return self


@pytest.mark.parametrize("moves,takes", [(0, 1), (1, 2), (5, None)])
def test_warm_ingest_waits_for_the_placement_to_settle(monkeypatch, moves,
                                                       takes):
    """One warm-up ingest when the state comes back placed as it went in,
    two when the first spreads it; a placement that keeps changing is
    refused with a message, not warmed without end."""
    import collections
    import types
    State = collections.namedtuple("State", "heap")
    active = types.SimpleNamespace(state=State(_Heap(0)))
    loop = types.SimpleNamespace(engine=types.SimpleNamespace(
        segments=types.SimpleNamespace(active=active)))
    ingests = []

    def step(loop):
        if len(ingests) <= moves:
            active.state = State(_Heap(len(ingests)))

    monkeypatch.setattr(harness, "_submit_ingest",
                        lambda loop, b, *a, **k: ingests.append(b))
    monkeypatch.setattr(harness, "_step", step)
    batches = list(range(8))
    if takes is None:
        with pytest.raises(RuntimeError, match="placement"):
            harness._warm_ingest(loop, batches, None, None)
        assert len(ingests) == harness.WARM_INGEST_MAX
    else:
        assert harness._warm_ingest(loop, batches, None, None) == takes
        assert ingests == batches[:takes]
