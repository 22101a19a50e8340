"""The benchmark's cells cut to a size a CPU test can run in seconds:
the same configuration and mix files, with the sizes overridden."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness  # noqa: E402

SEED = 2**31 + 12345          # larger than 32 signed bits hold


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.chips = 1      # the program runs on one device whatever it holds
    cell.config.update(vocab=4096, docs_per_segment=8192,
                       ingest_batch_docs=256)
    cell.config["serve"] = dict(cell.config["serve"], max_batch=4)
    if cell.mix["feed"] == "closed":
        cell.config["docs_per_segment"] = 1 << 18
        cell.mix["window_docs_max"] = 1 << 17
    else:
        cell.mix["ingest_docs_per_s"] = 1024
        cell.mix["queries"]["qps"] = 40.0
    return cell


SECONDS = {"earlybird.ingest": 0.2, "tweets2011.active_topk": 1.0}


def run_tiny(name: str, tmp_path, *, seed: int = SEED, trace=False,
             log=None, **kw) -> dict:
    import jax
    return harness.run_cell(tiny_cell(name), seed=seed,
                            seconds=SECONDS[name], trace=trace,
                            devices=jax.devices(), work=tmp_path,
                            log=log or (lambda *_: None), **kw)
