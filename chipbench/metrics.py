"""From what a run recorded to the result line's metrics.

End-to-end metrics come from the host clock (``--trace 0``); per-layer
metrics come from the readers in ``layer_metrics/`` over the reduced
device trace and the program's counters (``--trace 1``).  A reader that
finds nothing to read returns None and its metric is left out.
"""
from __future__ import annotations

import numpy as np

from chipbench import registry


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def query_latencies_s(rec) -> list:
    """Every accepted query of the window, from its scheduled arrival to
    its response."""
    return [q["done"] - q["due"] for q in rec.queries.values()
            if "done" in q]


def freshness_s(rec, watch) -> list:
    """Every ingest batch acknowledged in the window, from its scheduled
    arrival until its ingest had run on the device."""
    return [watch.ready[s] - t for s, (t, _, win) in rec.acks.items()
            if win and s in watch.ready]


def end_to_end(cell, rec, watch, peak_bytes: int, setup_s: float) -> dict:
    values = {"setup_s": setup_s, "peak_hbm_gb": peak_bytes / 1e9}
    if rec.window_s and not cell.mix.get("queries"):
        values["ingest_docs_per_s"] = rec.applied_docs / rec.window_s
    lat = query_latencies_s(rec)
    if lat:
        values["query_p95_ms"] = percentile(lat, 95) * 1e3
    if watch is not None:
        fr = freshness_s(rec, watch)
        if fr:
            values["freshness_mean_ms"] = float(np.mean(fr)) * 1e3
    return _pick(cell.end_to_end, values)


def per_layer(cell, ctx) -> dict:
    values = {}
    for m in cell.per_layer:
        v = registry.reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = v
    return _pick(cell.per_layer, values)


def _pick(specs, values) -> dict:
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in specs if m["name"] in values}


def report_lines(cell, rec, watch, counters, info) -> list:
    """The run's context, printed before the result line: compilations
    inside the window, how late the load generator ran, the samples
    behind each percentile, and the queues at the window's end."""
    lines = [f"window: {rec.window_s:.3f}s, compiles inside it: "
             f"{len(rec.compiles)} {[n for n, _ in rec.compiles][:8]}"]
    if rec.late_s:
        late = np.asarray(rec.late_s) * 1e3
        lines.append(f"generator late: median {np.median(late):.3f} ms, "
                     f"p95 {percentile(late, 95):.3f} ms, max "
                     f"{late.max():.3f} ms over {late.size} arrivals")
    lat = query_latencies_s(rec)
    if lat:
        lines.append(f"query latency: {len(lat)} samples, mean "
                     f"{np.mean(lat) * 1e3:.3f} ms, median "
                     f"{percentile(lat, 50) * 1e3:.3f} ms, p95 "
                     f"{percentile(lat, 95) * 1e3:.3f} ms, batches "
                     f"{len(rec.query_batches)}")
    if watch is not None:
        fr = freshness_s(rec, watch)
        if fr:
            lines.append(f"freshness: {len(fr)} samples, mean "
                         f"{np.mean(fr) * 1e3:.3f} ms, median "
                         f"{percentile(fr, 50) * 1e3:.3f} ms, p95 "
                         f"{percentile(fr, 95) * 1e3:.3f} ms")
    if rec.steps:
        top = sorted(rec.steps, reverse=True)[:5]
        lines.append("longest steps (ms, answers, most terms, ingest "
                     "batches): " + ", ".join(
                         f"({t * 1e3:.1f}, {a}, {m}, {i})"
                         for t, a, m, i in top))
    lines.append(f"queues at the window's end: {rec.queue_end}; window "
                 f"batches {rec.window_batches}; rejected queries "
                 f"{rec.rejected_queries}, ingest {rec.rejected_ingest}")
    lines.append("counters: " + " ".join(
        f"{k}={v}" for k, v in counters.items() if k != "lifecycle"))
    lines.append(f"reference: {info}")
    return lines
