"""Per-layer metric readers, found by name.

The reader of metric ``<name>`` is ``layer_metrics/<name>.py``; it
defines ``read(ctx)``, which returns the metric's value, or None where
the run gave it nothing to read (the metric is then left out of the
result line).  ``ctx`` is a :class:`chipbench.metrics_ctx.Context`.
Adding a metric is adding its reader file and its entry in
``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

LAYER_METRICS = Path(__file__).resolve().parent / "layer_metrics"


def reader(name: str, root: Path = LAYER_METRICS):
    path = Path(root) / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}: "
                                f"expected {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
