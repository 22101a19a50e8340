"""Each cell end to end on the CPU at a tiny size: sound runs come out
correct with every metric present and nothing compiled in the window;
each cell's control, a path that breaks one of the configuration's
guarantees, comes out not correct."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import peaks
from chipbench.tests import tiny

BENCH = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
CONTROL = {"earlybird.ingest": "no_journal",
           "tweets2011.active_topk": "degrade"}


def _names(cell, key):
    return sorted(m["name"] for m in getattr(tiny.tiny_cell(cell), key))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tmp_path):
    lines = []
    out = tiny.run_tiny(cell, tmp_path, log=lines.append)
    assert out["correct"], out["checks"]
    assert sorted(out["metrics"]) == _names(cell, "end_to_end")
    assert all(v["value"] > 0 for k, v in out["metrics"].items()
               if k != "peak_hbm_gb")           # the CPU reports no memory
    assert list(out)[-1] == "checks"
    assert all(c["limit"] == 0 for c in out["checks"].values())
    assert any("compiles inside it: 0 " in ln for ln in lines), lines[:2]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, tmp_path):
    out = tiny.run_tiny(cell, tmp_path, control=CONTROL[cell])
    assert not out["correct"]


def test_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.V5E)
    cell = "tweets2011.active_topk"
    out = tiny.run_tiny(cell, tmp_path, trace=True)
    assert out["correct"]
    assert sorted(out["metrics"]) == _names(cell, "per_layer")
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]


FOUR = """
import json, sys
import jax
from chipbench.tests import tiny
from chipbench import harness
cell = tiny.tiny_cell("tweets2011.active_topk")
cell.chips = 4
out = harness.run_cell(cell, seed=tiny.SEED, seconds=0.5, trace=False,
                       devices=jax.devices(), work=sys.argv[1],
                       log=lambda *_: None)
print(json.dumps({"correct": out["correct"], "device": out["device"]}))
"""


def test_a_cell_on_four_chips_reports_them(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(tiny.ROOT),
                                           str(tiny.ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", FOUR, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["correct"] and out["device"]["count"] == 4
