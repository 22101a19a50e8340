#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip.

    python3 chipbench/run.py --workload earlybird.ingest --seed 7 \\
        --seconds 50 --trace 0

Prints the run's context on earlier lines and one JSON object as the last
line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
the ``breakdown``, and last ``checks``, each compared number beside its
limit; the checks are also the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits 3.

``--control`` and ``--sweep`` are not part of a benchmark run: the first
serves through a path that breaks one of the configuration's guarantees
(its ``correct`` must come out false), the second finds a query cell's
knee by running the window at each of several rates after one set-up.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
CHECKOUT = Path(__file__).resolve().parents[1]
CACHE = CHECKOUT / ".jax_cache"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("none", "degrade", "no_journal"),
                    default="none")
    ap.add_argument("--sweep", default="",
                    help="comma-separated query rates (queries/s)")
    return ap.parse_args(argv)


def require_accelerator(devices, chips: int) -> str:
    """An error message, or '' when ``devices`` are enough TPU chips."""
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        return f"no TPU: JAX found {plat}; the benchmark runs on the chip only"
    if len(devices) < chips:
        return f"the cell needs {chips} TPU chips, JAX found {len(devices)}"
    return ""


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    from chipbench import harness
    cell = harness.load_cell(args.workload, CHECKOUT / "BENCHMARK.json")
    import jax
    devices = jax.devices()
    err = require_accelerator(devices, cell.chips)
    if err:
        print(f"chipbench: {err}", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    def log(msg):
        print(msg, flush=True)

    sweep = [float(r) for r in args.sweep.split(",") if r]
    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), devices=devices,
                           t_start=T_START, control=args.control,
                           log=log, sweep=sweep or None)
    if sweep:
        print(json.dumps(out))
        return 0
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
