"""One run of one cell: set up the deployment, drive the traffic mix
through ``ServeLoop`` for the measured window, check every answer
against the numpy reference, and reduce what was measured to metrics.

The cell is data.  ``BENCHMARK.json`` names a configuration and a
traffic mix; :func:`load_cell` reads ``configs/<config>.json`` and
``traffic/<traffic>.json``, and :func:`run_cell` is the one generator
and load loop that every mix goes through.  A per-layer metric is a reader
in ``layer_metrics/<name>.py`` (see :mod:`chipbench.registry`).

Two feeds exist.  ``closed``: the ingest queue is kept
``queue_depth`` batches deep from batches made during set-up, with no
queries (ingest capacity).  ``open``: tweets arrive at a fixed rate in
fixed batches and queries arrive as a Poisson stream at a fixed rate,
each timed from its scheduled arrival to its response (independent
searchers).  The tweets come from ``--seed``; the query log's ranks and
lengths and the arrival gaps come from the mix's own seed, so every run
seed gives the same sizes and arrivals in the same order, over terms
that its own stream ranks.
"""
from __future__ import annotations

import dataclasses
import json
import math
import queue
import shutil
import threading
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from chipbench import checks as checks_mod
from chipbench import metrics as metrics_mod
from chipbench import peaks as peaks_mod
from chipbench import streams
from chipbench import trace as trace_mod
from chipbench.compile_log import CompileLog
from chipbench.context import Context

ROOT = Path(__file__).resolve().parent
WORK = ROOT.parent / ".chipbench_run"      # journal and traces of a run


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: list
    per_layer: list


def load_cell(name: str, bench_path: Path = ROOT.parent / "BENCHMARK.json",
              root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration
    and mix files read from ``root`` by name."""
    bench = json.loads(Path(bench_path).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path}; "
                       f"have {sorted(cells)}")
    w = cells[name]
    config = json.loads((root / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads((root / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(m):
        return m.get("workloads") is None or name in m["workloads"]
    cell = Cell(name=name, config=config, mix=mix, chips=int(w["chips"]),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])
    check_shards(cell)
    return cell


def shards(cfg: dict) -> int:
    """The configuration's document shards: the stream is dealt over
    ``shards`` chips by docid (``g % S``), one shard's index on each."""
    return int(cfg.get("shards", 1))


def check_shards(cell: Cell) -> None:
    """Refuse a cell its shard count cannot run: every shard holds the
    same number of a segment's and of a batch's documents, one shard a
    chip."""
    cfg, S = cell.config, shards(cell.config)
    if S < 1:
        raise ValueError(f"{cell.name}: {S} shards")
    if S == 1:
        return
    for key in ("docs_per_segment", "ingest_batch_docs"):
        if int(cfg[key]) % S:
            raise ValueError(f"{cell.name}: {key} {cfg[key]} is not a "
                             f"multiple of the {S} shards")
    if cell.chips < S:
        raise ValueError(f"{cell.name}: {S} shards need {S} chips, the "
                         f"cell holds {cell.chips}")


# ---------------------------------------------------------------------------
# The deployment
# ---------------------------------------------------------------------------
def make_stream(cfg: dict, seed: int) -> streams.TweetStream:
    return streams.TweetStream(vocab=cfg["vocab"], mean_len=cfg["mean_terms"],
                               alpha=cfg["alpha"], width=cfg["doc_width"],
                               seed=seed)


def capacity_plan(cfg: dict):
    """``(layout, max_len, max_slices)`` of one shard: pools hold the
    analytical demand of ``docs_per_segment / shards`` documents times
    the slack, for a Poisson draw around the stream's expected term
    counts made from the configuration's ``sizing_seed``, and ``max_len``
    covers that shard's longest list.  The plan is part of the
    deployment, so every run seed gets the same layout and the same
    compiled programs."""
    from repro.core import analytical
    from repro.core.pointers import PoolLayout

    plan = make_stream(cfg, cfg["sizing_seed"])
    freqs = np.random.default_rng([cfg["sizing_seed"], 1]).poisson(
        plan.expected_freqs(int(cfg["docs_per_segment"]) // shards(cfg)))
    z = tuple(cfg["z"])
    layout = PoolLayout(z=z, slices_per_pool=analytical.slices_per_pool(
        z, freqs, slack=cfg["slack"]))
    max_len = 1 << int(freqs.max() * 1.1).bit_length()
    max_slices = int(analytical.slices_needed(z, [max_len])[0])
    return layout, max_len, max_slices


def build_engine(cfg: dict):
    """The configuration's engine, on :func:`capacity_plan`'s layout:
    ``LifecycleEngine`` on one shard, ``ShardedLifecycleEngine`` on a
    mesh of the first ``shards`` devices otherwise."""
    from repro.core.lifecycle import LifecycleEngine, ShardedLifecycleEngine
    from repro.core.segments import CompactionPolicy

    layout, max_len, max_slices = capacity_plan(cfg)
    dps, S = int(cfg["docs_per_segment"]), shards(cfg)
    kw = dict(max_slices=max_slices, max_len=max_len,
              max_query_len=cfg["max_query_terms"],
              stable_shapes=cfg["stable_shapes"],
              compaction=CompactionPolicy(fanout=cfg["compaction_fanout"]))
    if S == 1:
        return LifecycleEngine(layout, cfg["vocab"], dps, **kw)
    from repro.core.sharded_index import make_doc_mesh
    mesh, rules = make_doc_mesh(S)
    return ShardedLifecycleEngine(layout, cfg["vocab"], dps, mesh,
                                  rules=rules, **kw)


@dataclasses.dataclass
class Batch:
    index: int          # stream batch index
    docs: np.ndarray


class Feed:
    """The stream's batches, made in order and kept for the reference."""

    def __init__(self, stream, batch_docs: int):
        self.stream = stream
        self.batch_docs = batch_docs
        self.made = []

    def make(self, n: int) -> list:
        out = []
        for _ in range(n):
            i = len(self.made)
            b = Batch(i, self.stream.batch(i, self.batch_docs))
            self.made.append(b)
            out.append(b)
        return out


# ---------------------------------------------------------------------------
# Freshness: when an applied ingest batch has run on the device
# ---------------------------------------------------------------------------
class FreshnessWatch:
    """Per applied ingest batch, a tiny device value computed from the
    pool state the batch produced; a helper thread waits for each in
    turn and stamps the time it became ready.  Once ready, every query
    dispatched later reads a state that holds the batch."""

    def __init__(self, clock):
        import jax
        import jax.numpy as jnp
        self.clock = clock
        # one shard's freq is [V]; a sharded state's is [S, V], and its
        # mark reads every shard, so it waits for each chip's ingest
        self._mark = {1: jax.jit(lambda freq: freq[0] + 1),
                      2: jax.jit(lambda freq: jnp.sum(freq[:, 0]))}
        self._q = queue.Queue()
        self.ready = {}          # seq -> clock time
        self._t = threading.Thread(target=self._wait, daemon=True)
        self._t.start()

    def _wait(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            seq, val = item
            val.block_until_ready()
            self.ready[seq] = self.clock()

    def applied(self, seq: int, state) -> None:
        self._q.put((seq, self._mark[state.freq.ndim](state.freq)))

    def close(self) -> None:
        self._q.put(None)
        self._t.join(timeout=120)
        if self._t.is_alive():
            raise RuntimeError("freshness watcher did not finish")


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Record:
    """What the window did, for the metrics, the reference and the
    per-layer readers."""
    window_s: float = 0.0
    # seq -> (due time, Batch, acked inside the window)
    acks: dict = dataclasses.field(default_factory=dict)
    applied_docs: int = 0
    queries: dict = dataclasses.field(default_factory=dict)  # qid -> info
    rejected_queries: int = 0
    rejected_ingest: int = 0
    late_s: list = dataclasses.field(default_factory=list)
    query_batches: list = dataclasses.field(default_factory=list)
    # (seconds, responses, most query terms, ingest batches applied)
    steps: list = dataclasses.field(default_factory=list)
    ingest_batches: int = 0
    window_batches: dict = dataclasses.field(default_factory=dict)
    compiles: list = dataclasses.field(default_factory=list)
    queue_end: dict = dataclasses.field(default_factory=dict)


def pow2s(n: int):
    q = 1
    while q <= n:
        yield q
        q *= 2


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices, t_start: Optional[float] = None, control: str = "none",
             fault: Optional[Callable] = None, log=print,
             sweep: Optional[list] = None, work: Path = WORK) -> dict:
    """Run ``cell`` once and return the result line's object.

    ``control`` runs a path that breaks a guarantee of the
    configuration (``degrade``: queries served from the frozen segments
    only, the serving ladder's last rung; ``no_journal``: acks without
    the journal); ``fault`` is called with
    the engine and the loop after set-up so that a test can break the
    timed path.  The benchmark's own runs pass neither.  ``sweep`` (open
    feed only) runs the window once per query rate and returns the
    readings, without a reference check."""
    import jax
    from repro.core import serve
    from repro.core.recovery import IngestJournal

    clock = time.perf_counter
    t_start = clock() if t_start is None else t_start
    cfg, mix = cell.config, cell.mix
    if len(devices) < cell.chips:
        raise RuntimeError(f"{cell.name} needs {cell.chips} devices")
    check_shards(cell)
    comp = CompileLog().__enter__()
    try:
        stream = make_stream(cfg, seed)
        feed = Feed(stream, cfg["ingest_batch_docs"])
        engine = build_engine(cfg)
        work = Path(work)
        work.mkdir(parents=True, exist_ok=True)
        wal = work / "ingest.wal"
        wal.unlink(missing_ok=True)
        journal = (None if control == "no_journal"
                   else IngestJournal(str(wal)))
        scfg = serve.ServeConfig(**cfg["serve"])
        loop = serve.ServeLoop(engine, scfg, journal=journal, clock=clock)
        loop.force_level = (serve.DEGRADE_FROZEN_ONLY if control == "degrade"
                            else serve.DEGRADE_NONE)
        rec = Record()
        rec.pool = None
        with jax.profiler.TraceAnnotation("setup_ingest"):
            _setup_ingest(cell, engine, loop, feed, rec, clock)
        qmix = mix.get("queries")
        pool = None
        if qmix:
            with jax.profiler.TraceAnnotation("make_queries"):
                pool = _query_pool(cell, feed, seconds, sweep)
            rec.pool = pool
        if mix["feed"] == "closed":
            window = feed.make(int(mix["window_docs_max"])
                               // cfg["ingest_batch_docs"])
            window = window[_warm_ingest(loop, window, rec, clock):]
        else:
            n_in = math.ceil(seconds * mix["ingest_docs_per_s"]
                             / cfg["ingest_batch_docs"]) + 1
            window = feed.make(n_in * (len(sweep) if sweep else 1))
        watch = FreshnessWatch(clock) if mix["feed"] == "open" else None
        if qmix:
            with jax.profiler.TraceAnnotation("warm_queries"):
                _warm_queries(cell, engine, loop, pool, watch)
        if fault is not None:
            fault(engine, loop)
        setup_s = clock() - t_start
        heap = engine.segments.active.state.heap
        log(f"setup: {setup_s:.3f}s, {len(comp.programs)} programs "
            f"compiled or loaded, shards {shards(cfg)}, heap "
            f"{heap.nbytes // shards(cfg)} bytes a shard, max_len "
            f"{engine.max_len}, layout {engine.layout.slices_per_pool}")

        if sweep:
            return _sweep(cell, loop, engine, window, pool, sweep, seconds,
                          watch, comp, clock, log)

        trace_dir = work / "trace"
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # device ops and our spans only
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        mark = comp.mark()
        stats0 = dataclasses.asdict(loop.stats)
        if mix["feed"] == "closed":
            _closed_window(loop, engine, window, seconds, rec, clock,
                           mix["queue_depth"])
        else:
            _open_window(cell, loop, engine, window, pool, seconds, rec,
                         watch, clock)
        if trace:
            jax.profiler.stop_trace()
        rec.compiles = comp.since(mark)
        rec.queue_end = {"ingest": loop.pending_ingest,
                         "queries": loop.pending_queries}
        _drain(loop, engine, rec, watch, clock)
        if watch is not None:
            watch.close()
        stats1 = dataclasses.asdict(loop.stats)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:cell.chips])
        counters = {k: stats1[k] - stats0[k] for k in stats1
                    if isinstance(stats1[k], int)}
        counters["lifecycle"] = dataclasses.asdict(engine.stats)
        shapes = _shapes(cell)
        if journal is not None:
            journal.close()
        checks, info = checks_mod.run_checks(cell, engine, loop, rec, wal,
                                             journal is not None, seed)
        summary = trace_mod.load(trace_dir) if trace else None
    finally:
        comp.__exit__()
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": cell.chips, "memory_peak_bytes": int(peak)}
    if trace:
        ctx = Context(trace=summary, counters=counters,
                      window=rec.window_batches, shapes=shapes,
                      peaks=peaks_mod.peaks(d.device_kind))
        metrics = metrics_mod.per_layer(cell, ctx)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        metrics = metrics_mod.end_to_end(cell, rec, watch, peak, setup_s)
    out = {"correct": all(v["value"] <= v["limit"]
                          for v in checks.values()),
           "attempted": _attempted(cell, rec),
           "failed": rec.rejected_queries + rec.rejected_ingest,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = summary.breakdown()
    for line in metrics_mod.report_lines(cell, rec, watch, counters, info):
        log(line)
    out["checks"] = checks
    return out


def _shapes(cell) -> dict:
    """The kernels' call shapes in the window, for the roofline readers:
    the (term, posting) entries of one ingest batch, and the shards it
    is dealt over (each chip's call takes ``1 / shards`` of them)."""
    cfg = cell.config
    return {"ingest_entries": cfg["ingest_batch_docs"] * cfg["doc_width"],
            "shards": shards(cfg)}


def _attempted(cell, rec) -> int:
    if cell.mix.get("queries"):
        return len(rec.queries) + rec.rejected_queries
    return sum(1 for _, _, w in rec.acks.values() if w) + rec.rejected_ingest


def _submit_ingest(loop, batch, rec, clock, window: bool, due=None):
    """Submit one batch; an acked batch is recorded with its due time
    (its scheduled arrival, or the ack itself in a closed feed)."""
    import jax
    from repro.core import serve
    with jax.profiler.TraceAnnotation("submit_ingest"):
        seq = loop.submit_ingest(batch.docs)
    if isinstance(seq, serve.Rejected):
        if window:
            rec.rejected_ingest += 1
        return False
    rec.acks[seq] = (clock() if due is None else due, batch, window)
    return True


def _step(loop):
    import jax
    with jax.profiler.TraceAnnotation("step"):
        return loop.step()


def _setup_ingest(cell, engine, loop, feed, rec, clock) -> None:
    """Ingest ``setup_rollovers`` whole segments and then ``setup_fill``
    of the next, through the loop, before the window."""
    mix, cfg = cell.mix, cell.config
    dps, bd = cfg["docs_per_segment"], cfg["ingest_batch_docs"]
    n = (mix["setup_rollovers"] * dps + int(mix["setup_fill"] * dps)) // bd
    for b in feed.make(n):
        if not _submit_ingest(loop, b, rec, clock, window=False):
            raise RuntimeError(f"set-up ingest batch {b.index} rejected")
        _step(loop)
    if engine.stats.rollovers != mix["setup_rollovers"]:
        raise RuntimeError(f"set-up made {engine.stats.rollovers} "
                           f"rollovers, the mix asks for "
                           f"{mix['setup_rollovers']}")


# warm-up ingests: one, and one more for a sharded state made on one device
WARM_INGEST_MAX = 2


def _warm_ingest(loop, batches, rec, clock) -> int:
    """Ingest the first of ``batches``, and a second if the pool state
    did not come back placed as it went in: the window's ingest program
    is the one for the settled placement.  (A sharded state starts on
    one device and is spread over the mesh by its first ingest.)
    Returns how many batches it took."""
    import jax

    def placed():
        return [x.sharding for x in
                jax.tree.leaves(loop.engine.segments.active.state)]
    for n in range(1, WARM_INGEST_MAX + 1):
        before = placed()
        _submit_ingest(loop, batches[n - 1], rec, clock, window=False)
        _step(loop)
        if placed() == before:
            jax.block_until_ready(loop.engine.segments.active.state.heap)
            return n
    raise RuntimeError(f"the pool state's placement still changed after "
                       f"{WARM_INGEST_MAX} warm-up ingests: each would "
                       f"compile another ingest program")


@dataclasses.dataclass
class QueryPool:
    kind: str
    k: int
    terms: list          # term tuples, in arrival order
    gaps: np.ndarray     # seconds between arrivals
    heavy: tuple         # the most frequent terms of the index
    absent: int          # a term the index does not hold
    qps: float


def _query_pool(cell, feed, seconds, sweep) -> QueryPool:
    """The window's queries: ``synth.query_log`` draws over the
    frequency ranks of the terms ingested in set-up, with the mix's
    ``log_seed``, arriving at exponential gaps (mean ``1 / qps``) drawn
    from the same seed.  Query lengths and arrival times are the same,
    in the same order, for every run seed: a query batch's cost follows
    how many queries share it and their longest, so an order that moved
    with the seed would move the tail with it.  The run seed picks the
    terms, through the stream that ranks them."""
    q, cfg = cell.mix["queries"], cell.config
    counts = np.zeros(cfg["vocab"], np.int64)
    for b in feed.made:
        counts += np.bincount(b.docs[b.docs >= 0], minlength=cfg["vocab"])
    rates = sweep or [q["qps"]]
    n = max(int(round(r * seconds)) for r in rates)
    terms = streams.query_log(q["log"], n, counts, seed=q["log_seed"],
                              max_terms=cfg["max_query_terms"])
    gaps = np.random.default_rng([q["log_seed"], 3]).exponential(1.0, n)
    gaps *= n / gaps.sum()              # mean exactly 1: n arrivals span n
    heavy = tuple(int(t) for t in np.argsort(-counts, kind="stable")
                  [:cfg["max_query_terms"]])
    absent = int(np.nonzero(counts == 0)[0][0]) if (counts == 0).any() \
        else heavy[0]
    return QueryPool(kind=q["kind"], k=q["k"], terms=terms, gaps=gaps,
                     heavy=heavy, absent=absent, qps=q["qps"])


def _warm_queries(cell, engine, loop, pool, watch) -> None:
    """Compile every program the window can run: each pow2 batch bucket
    up to ``max_batch`` at each pow2 term-slot bucket, after one query
    of the index's most frequent terms has raised the frozen stack's
    shape ratchet to its steady-state top (a long-running server
    reaches it with the first query that names a head term)."""
    cfg = cell.config
    nmax = cfg["max_query_terms"]

    def serve_all(qs):
        for t in qs:
            loop.submit_query(pool.kind, t, k=pool.k)
        loop.drain()

    serve_all([pool.heavy])
    serve_all([(pool.absent,)])
    slots = sorted({min(1 << (n - 1).bit_length(), nmax)
                    for n in range(1, nmax + 1)})
    for nt in slots:
        base = pool.heavy[:nt]
        for qb in pow2s(cfg["serve"]["max_batch"]):
            serve_all([base] * qb)
    if watch is not None:
        watch.applied(-1, engine.segments.active.state)
    import jax
    jax.block_until_ready(engine.segments.active.state.heap)


def _closed_window(loop, engine, window, seconds, rec, clock,
                   depth: int) -> None:
    import jax
    docs0 = loop.stats.docs_indexed
    applied0 = loop.stats.ingest_applied
    n = 0
    span = jax.profiler.TraceAnnotation("window")
    span.__enter__()
    t0 = clock()
    while clock() - t0 < seconds:
        while loop.pending_ingest < depth:
            # past the batches made in set-up, their contents come round
            # again (as new tweets: the index numbers them anew)
            _submit_ingest(loop, window[n % len(window)], rec, clock,
                           window=True)
            n += 1
        _step(loop)
    jax.block_until_ready(engine.segments.active.state.heap)
    rec.window_s = clock() - t0
    span.__exit__(None, None, None)
    rec.applied_docs = loop.stats.docs_indexed - docs0
    rec.ingest_batches = loop.stats.ingest_applied - applied0
    rec.window_batches = {"ingest": rec.ingest_batches}


def _open_window(cell, loop, engine, window, pool, seconds, rec, watch,
                 clock, qps=None) -> None:
    """Tweets every ``batch / rate`` seconds and queries at Poisson
    arrivals, both on a fixed schedule whatever the loop is doing."""
    import jax
    qps = pool.qps if qps is None else qps
    period = cell.config["ingest_batch_docs"] / cell.mix["ingest_docs_per_s"]
    q_at = np.cumsum(pool.gaps) / qps
    n_q = int(np.searchsorted(q_at, seconds))
    n_i = min(len(window), int(math.ceil(seconds / period)))
    qi = ii = 0
    docs0 = loop.stats.docs_indexed
    span = jax.profiler.TraceAnnotation("window")
    span.__enter__()
    t0 = clock()
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        while ii < n_i and ii * period <= now:
            rec.late_s.append(clock() - t0 - ii * period)
            _submit_ingest(loop, window[ii], rec, clock, window=True,
                           due=t0 + ii * period)
            ii += 1
        while qi < n_q and q_at[qi] <= now:
            t_sub = clock()
            rec.late_s.append(t_sub - t0 - q_at[qi])
            with jax.profiler.TraceAnnotation("submit_query"):
                qid = loop.submit_query(pool.kind, pool.terms[qi], k=pool.k)
            if isinstance(qid, int):
                rec.queries[qid] = {"terms": pool.terms[qi],
                                    "due": t0 + q_at[qi], "t_sub": t_sub}
            else:
                rec.rejected_queries += 1
            qi += 1
        if loop.pending_queries or loop.pending_ingest:
            _collect_step(loop, engine, rec, watch)
        else:
            nxt = min(q_at[qi] if qi < n_q else seconds,
                      ii * period if ii < n_i else seconds)
            with jax.profiler.TraceAnnotation("wait"):
                time.sleep(max(0.0, min(nxt - (clock() - t0), 0.005)))
    jax.block_until_ready(engine.segments.active.state.heap)
    rec.window_s = clock() - t0
    span.__exit__(None, None, None)
    rec.applied_docs = loop.stats.docs_indexed - docs0
    rec.window_batches = {"ingest": rec.ingest_batches,
                          "query": len(rec.query_batches),
                          "queries": sum(n for n, _ in rec.query_batches)}


def _collect_step(loop, engine, rec, watch, force=False) -> None:
    """One loop step; stamps each response with the documents that were
    visible to its query (those applied before the step dispatched it)."""
    import jax
    visible = loop.stats.docs_indexed
    seq_before = loop.applied_seq
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("step"):
        loop.step(force=force)
    resp = loop.take_responses()
    terms = [len(rec.queries[r.qid]["terms"]) for r in resp
             if r.qid in rec.queries]
    rec.steps.append((time.perf_counter() - t0, len(resp),
                      max(terms, default=0), loop.applied_seq - seq_before))
    if resp:
        rec.query_batches.append((len(resp), max(terms, default=0)))
    for r in resp:
        info = rec.queries.get(r.qid)
        if info is not None:
            info["resp"] = r
            info["visible"] = visible
            info["done"] = info["t_sub"] + r.latency_s
    if watch is not None and loop.applied_seq != seq_before:
        rec.ingest_batches += loop.applied_seq - seq_before
        watch.applied(loop.applied_seq - 1, engine.segments.active.state)


def _drain(loop, engine, rec, watch, clock, limit_s: float = 60.0) -> None:
    """After the window: serve what is queued, for a minute at most, so
    that a late answer is timed and checked and not lost."""
    import jax
    t0 = clock()
    while (loop.pending_queries or loop.pending_ingest
           or loop.in_flight_queries) and clock() - t0 < limit_s:
        _collect_step(loop, engine, rec, watch, force=True)
    jax.block_until_ready(engine.segments.active.state.heap)


def _sweep(cell, loop, engine, window, pool, rates, seconds, watch, comp,
           clock, log) -> dict:
    """The knee sweep: the window once per query rate, back to back after
    one set-up, each with its own slice of the ingest stream.  Answers
    are not checked; the readings say where the backlog starts to grow."""
    n_in = len(window) // len(rates)
    rows = []
    for i, qps in enumerate(rates):
        rec = Record()
        rec.pool = pool
        mark = comp.mark()
        _open_window(cell, loop, engine, window[i * n_in:(i + 1) * n_in],
                     pool, seconds, rec, watch, clock, qps=qps)
        queue_end = {"ingest": loop.pending_ingest,
                     "queries": loop.pending_queries}
        _drain(loop, engine, rec, watch, clock)
        lat = metrics_mod.query_latencies_s(rec)
        fr = metrics_mod.freshness_s(rec, watch)
        row = {"qps": qps, "queries": len(rec.queries),
               "rejected": rec.rejected_queries + rec.rejected_ingest,
               "window_batches": rec.window_batches,
               "batches": len(rec.query_batches),
               "query_p50_ms": metrics_mod.percentile(lat, 50) * 1e3
               if lat else None,
               "query_p95_ms": metrics_mod.percentile(lat, 95) * 1e3
               if lat else None,
               "query_mean_ms": float(np.mean(lat)) * 1e3 if lat else None,
               "freshness_p95_ms": metrics_mod.percentile(fr, 95) * 1e3
               if fr else None,
               "freshness_mean_ms": float(np.mean(fr)) * 1e3
               if fr else None,
               "queue_end": queue_end, "compiles": len(comp.since(mark))}
        log("sweep " + json.dumps(row))
        rows.append(row)
    watch.close()
    return {"sweep": rows}
