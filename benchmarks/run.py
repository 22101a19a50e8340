"""Benchmark harness entry point: one module per paper table/figure plus
the beyond-paper suites (sharded index, paged-KV transfer, roofline).

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAMES] \
        [--json PATH] [--repeat N] [--warmup K] [--validate]

``--validate`` threads ``validate=True`` into every suite whose ``run``
accepts it (the lifecycle-driving suites): the engines then run the
repro.analysis.invariants structural validators at every rollover and
any broken allocator/segment invariant fails the suite.

``--json PATH`` writes per-suite wall times and each suite's returned
metrics to a machine-readable file (CI uploads ``BENCH_ci.json`` as a
build artifact so the perf trajectory accumulates across commits).  Any
suite failure exits 1 so CI can gate on benchmarks.

The report names the device it ran on (``device``: platform, kind,
count).  JAX's persistent compile cache is placed by
:func:`repro.compile_cache.enable`.

``--warmup K`` runs each suite K extra times first (untimed, metrics
discarded) and ``--repeat N`` then times N runs, reporting the MINIMUM
as ``wall_s`` (all runs under ``wall_s_runs``) — so the docs/s and
latency numbers in the JSON artifact measure steady-state execution,
not jit compilation of a cold process.
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time
import traceback


SUITES = ("analytical", "fig2", "fig3", "table1", "table2", "ingest",
          "sharded", "lifecycle", "query", "scored", "recovery",
          "serve", "paged_kv", "roofline")


def _jsonable(x):
    """Best-effort conversion of suite return values to JSON types."""
    import numpy as np
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    if isinstance(x, np.ndarray):
        return x.tolist()
    if isinstance(x, (bool, int, float, str)) or x is None:
        return x
    return repr(x)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="larger corpus/query scale (slower)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(SUITES))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-suite wall times + metrics as JSON")
    ap.add_argument("--repeat", type=int, default=1, metavar="N",
                    help="timed runs per suite; wall_s is the minimum")
    ap.add_argument("--warmup", type=int, default=0, metavar="K",
                    help="untimed warmup runs per suite (jit compile)")
    ap.add_argument("--validate", action="store_true",
                    help="run the structural invariant validators "
                         "(repro.analysis.invariants) inside every "
                         "suite that supports them")
    args = ap.parse_args(argv)
    if args.repeat < 1:
        ap.error("--repeat must be >= 1")
    picked = args.only.split(",") if args.only else SUITES
    unknown = [n for n in picked if n not in SUITES]
    if unknown:
        print(f"unknown suites {unknown}; choose from {SUITES}")
        sys.exit(2)
    fast = not args.full

    import jax
    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}")
    devices = jax.devices()
    t_all = time.perf_counter()
    report = {"fast": fast, "repeat": args.repeat, "warmup": args.warmup,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)},
              "suites": {}, "failures": []}
    for name in picked:
        t_run = time.perf_counter()   # restarted before every run so a
        try:                          # failure reports ITS run, not the
            # import inside the try so a broken suite module is recorded
            # as a failure instead of aborting the whole harness
            mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
            kw = {"fast": fast}
            if args.validate and "validate" in \
                    inspect.signature(mod.run).parameters:
                kw["validate"] = True
            for _ in range(args.warmup):
                t_run = time.perf_counter()
                mod.run(**kw)
            walls, best = [], None
            for _ in range(args.repeat):
                t_run = time.perf_counter()
                metrics = mod.run(**kw)
                walls.append(time.perf_counter() - t_run)
                # keep the metrics of the FASTEST run so wall_s and the
                # reported docs/s describe the same execution
                if best is None or walls[-1] < best[0]:
                    best = (walls[-1], metrics)
            wall = best[0]
            report["suites"][name] = {"wall_s": wall,
                                      "wall_s_runs": walls, "ok": True,
                                      "metrics": _jsonable(best[1])}
            print(f"[{name}: {wall:.1f}s"
                  + (f" (min of {len(walls)})" if len(walls) > 1 else "")
                  + "]")
        except KeyboardInterrupt:
            raise
        except BaseException as exc:
            # BaseException: a suite dying on SystemExit (an argparse
            # sys.exit deep in a dependency) or a failed assert must
            # still leave the OTHER suites' numbers in the JSON.
            wall = time.perf_counter() - t_run
            report["suites"][name] = {
                "wall_s": wall, "ok": False, "metrics": None,
                "error": f"{type(exc).__name__}: {exc}"}
            report["failures"].append(name)
            print(f"[{name}: FAILED]")
            traceback.print_exc()
    report["total_s"] = time.perf_counter() - t_all
    print(f"\n== benchmarks done in {report['total_s']:.1f}s; "
          f"{len(report['failures'])} failures {report['failures'] or ''} ==")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    if report["failures"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
