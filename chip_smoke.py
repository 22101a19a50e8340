#!/usr/bin/env python3
"""Smoke run of the served real-time index on a TPU.

Drives the main path once, end to end, through the entry points a
deployment uses:

  * a :class:`~repro.core.lifecycle.LifecycleEngine` on the paper's
    production pools ``Z^g = <1,4,7,11>``, its ``slices_per_pool`` sized
    from the analytical per-term demand of one Earlybird segment of 2^23
    tweets, with ``stable_shapes`` and fanout-2 compaction;
  * a :class:`~repro.core.serve.ServeLoop` with an
    :class:`~repro.core.recovery.IngestJournal` in front of it;
  * a seeded Zipf(1.0) tweet stream of ~11 terms per tweet over an assumed
    vocabulary of 2^20 terms, made batch by batch and ingested through
    ``submit_ingest`` until the first segment has rolled over and the next
    one is partly filled;
  * a few queries of every kind (conjunctive, disjunctive, phrase, top-k,
    scored top-k) through ``submit_query`` and ``drain``.

Every answer is checked against a plain numpy inverted index built from
the same stream, without any ``repro.core`` code.  The lowered ingest and
query programs must contain the compiled Pallas kernels
(``tpu_custom_call``): the frozen merge on one chip, the sharded active
conjunction on four (the frozen merge is replicated there and runs in
XLA).  Earlier lines print sizes and per-phase wall times; they are
information, not metrics.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # document-sharded engine, 4 devices

The last line of stdout is one JSON object naming the device, printed only
when every phase passed.  Without a TPU the script prints no result and
exits 2; any failed phase exits non-zero.
"""
from __future__ import annotations

import argparse
import functools
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

Z = (1, 4, 7, 11)               # the paper's production pools Z^g
SEGMENT_DOCS = 1 << 23          # Earlybird segment size (tweets)
VOCAB = 1 << 20                 # assumed distinct terms
MEAN_LEN = 11                   # terms per tweet (synth.CorpusSpec)
MAX_QUERY_LEN = 4               # synth.query_log's longest query
DOC_WIDTH = 32                  # term slots per tweet row (Poisson(11) clip)
BATCH_DOCS = 8192               # tweets per ingest batch
TAIL_BATCHES = 64               # batches after the rollover (at most half
                                # a segment's)
PROGRESS_EVERY = 128            # batches between progress lines
SLACK = 1.3                     # pool headroom over the analytical demand


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# The plain reference: an inverted index over the query terms, numpy only
# ---------------------------------------------------------------------------
class NumpyIndex:
    """Per-term ``(docid, position)`` occurrences of a fixed term set,
    collected from the raw batches as they are generated."""

    def __init__(self, terms):
        self.terms = np.unique(np.asarray(terms, np.int64))
        self._chunks = []
        self.n_docs = 0
        self.n_postings = 0

    def add(self, docs: np.ndarray) -> None:
        self.n_postings += int((docs >= 0).sum())
        rows, cols = np.nonzero(np.isin(docs, self.terms))
        self._chunks.append(np.stack(
            [docs[rows, cols], rows + self.n_docs, cols], 1).astype(np.int64))
        self.n_docs += docs.shape[0]

    def finish(self) -> None:
        occ = np.concatenate(self._chunks) if self._chunks else \
            np.zeros((0, 3), np.int64)
        occ = occ[np.lexsort((occ[:, 2], occ[:, 1], occ[:, 0]))]
        self._occ = {}
        for t in self.terms:
            lo, hi = np.searchsorted(occ[:, 0], [t, t + 1])
            self._occ[int(t)] = occ[lo:hi, 1:]
        self._chunks = []

    def docs(self, t) -> np.ndarray:
        return np.unique(self._occ[int(t)][:, 0])

    def conjunctive(self, terms) -> np.ndarray:
        out = self.docs(terms[0])
        for t in terms[1:]:
            out = np.intersect1d(out, self.docs(t))
        return out[::-1]

    def disjunctive(self, terms) -> np.ndarray:
        out = self.docs(terms[0])
        for t in terms[1:]:
            out = np.union1d(out, self.docs(t))
        return out[::-1]

    def phrase(self, t1, t2) -> np.ndarray:
        key = lambda o: o[:, 0] * 256 + o[:, 1]      # noqa: E731
        a, b = key(self._occ[int(t1)]), key(self._occ[int(t2)])
        return np.unique(a[np.isin(a + 1, b)] // 256)[::-1]

    def scored(self, terms, k):
        ids = self.conjunctive(terms)[::-1]
        score = np.zeros(ids.size, np.int64)
        for t in terms:
            d, tf = np.unique(self._occ[int(t)][:, 0], return_counts=True)
            score += np.minimum(tf[np.searchsorted(d, ids)], 255)
        order = np.lexsort((-ids, -score))[:k]
        return ids[order], score[order]


def query_set(stream):
    """A few queries per kind, as Zipf ranks -> term ids (rank 0 is the
    most frequent term): head, torso and tail terms."""
    ranks = stream.rank_to_term

    def t(r):
        return int(ranks[min(r, ranks.size - 1)])

    return [
        ("conjunctive", (t(1), t(7)), None),
        ("conjunctive", (t(20), t(90), t(400)), None),
        ("disjunctive", (t(300), t(2000)), None),
        ("disjunctive", (t(15000), t(40000), t(90000)), None),
        ("phrase", (t(0), t(1)), None),
        ("phrase", (t(4), t(9)), None),
        ("topk", (t(0), t(2)), 10),
        ("topk", (t(6), t(30)), 100),
        ("scored", (t(0), t(3)), 20),      # one k: one coalesced batch
        ("scored", (t(8), t(25), t(60)), 20),
    ]


def expected(ref: NumpyIndex, kind, terms, k):
    if kind == "conjunctive":
        return ref.conjunctive(terms), None
    if kind == "disjunctive":
        return ref.disjunctive(terms), None
    if kind == "phrase":
        return ref.phrase(*terms), None
    if kind == "topk":
        return ref.conjunctive(terms)[:k], None
    return ref.scored(terms, k)


# ---------------------------------------------------------------------------
# The engine, sized for one segment
# ---------------------------------------------------------------------------
def segment_layout(docs_per_segment: int, *, shards: int, vocab: int,
                   seed: int):
    """``(layout, max_len, max_slices)`` for one segment of the stream on
    each of ``shards`` devices.  Per-term postings of one shard's segment
    are a seeded Poisson draw around the Zipf expectation, so the rare
    terms' slice demand counts the terms that actually occur; the pools
    get the analytical demand of that draw, times ``SLACK``."""
    from repro.core import analytical, pointers
    from repro.data.synth import CorpusSpec, TweetStream

    stream = TweetStream(CorpusSpec(vocab=vocab, mean_len=MEAN_LEN,
                                    seed=seed), width=DOC_WIDTH)
    freqs = np.random.default_rng([seed, 1]).poisson(
        stream.expected_freqs(docs_per_segment // shards))
    spp = analytical.slices_per_pool(Z, freqs, slack=SLACK)
    max_len = 1 << int(freqs.max() * 1.1).bit_length()
    max_slices = int(analytical.slices_needed(Z, [max_len])[0])
    return pointers.production_layout(spp), max_len, max_slices


def build_engine(*, shards: int, docs_per_segment: int, vocab: int,
                 seed: int):
    from repro.core.lifecycle import LifecycleEngine, ShardedLifecycleEngine
    from repro.core.segments import CompactionPolicy

    layout, max_len, max_slices = segment_layout(
        docs_per_segment, shards=shards, vocab=vocab, seed=seed)
    kw = dict(max_slices=max_slices, max_len=max_len,
              max_query_len=MAX_QUERY_LEN, stable_shapes=True,
              compaction=CompactionPolicy(fanout=2))
    if shards == 1:
        return LifecycleEngine(layout, vocab, docs_per_segment, **kw)
    from repro.core.sharded_index import make_doc_mesh
    mesh, rules = make_doc_mesh(shards)
    return ShardedLifecycleEngine(layout, vocab, docs_per_segment, mesh,
                                  rules=rules, **kw)


def kernel_programs(engine, docs, queries):
    """StableHLO of the engine's next ingest step and of the query program
    whose Pallas intersect kernel must be compiled, not interpreted, on a
    TPU, for the conjunctive batch ``queries``: the frozen merge on one
    device; on a mesh, where the frozen merge is replicated and takes the
    jnp path, the sharded active conjunction."""
    import jax.numpy as jnp
    from repro.core import qexec

    act = engine.segments.active
    terms, n_terms = qexec.pad_query_batch(queries, engine.max_query_len)
    if hasattr(act, "num_shards"):
        S, (B, L) = act.num_shards, docs.shape
        by_shard = jnp.transpose(jnp.asarray(docs).reshape(B // S, S, L),
                                 (1, 0, 2))
        ingest = act._ingest.lower(
            act.state, by_shard, jnp.uint32(act.next_docid // S),
            act._zero_table)
        query = engine.engine.conjunctive.lower(
            act.state, jnp.asarray(terms, jnp.uint32),
            jnp.asarray(n_terms, jnp.int32))
        return ingest.as_text(), query.as_text()
    t, plist, valid = act._flatten(jnp.asarray(docs), act.next_docid)
    ingest = act._ingest.lower(act.state, t, plist, None, valid)
    tb = min(qexec.bucket_pow2(int(n_terms.max()), 1), engine.max_query_len)
    ad, an = engine._active_batch("conjunctive", terms, n_terms, tb)
    lists, _ = engine._frozen_stack().gather(terms[:, :tb], n_terms)
    query = qexec.frozen_merge.lower(
        ad, an, lists, jnp.asarray(n_terms), engine._base_u32(),
        kind="conjunctive", nt_slots=tb, kernel=engine._batched_kernel,
        interpret=engine.interpret)
    return ingest.as_text(), query.as_text()


class CompileLog:
    """Seconds JAX spends in the backend compiler, per jitted program, read
    from ``jax.monitoring``'s compile-duration events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.programs = []          # (name, seconds), in compile order
        self._seen = 0

    def __call__(self, event, duration, fun_name="?", **_):
        if event == self.EVENT:
            self.programs.append((fun_name, duration))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)

    def take(self) -> float:
        """Compile seconds since the previous call."""
        new = self.programs[self._seen:]
        self._seen = len(self.programs)
        return sum(s for _, s in new)


def _shard_layout(engine):
    """(device, shard shape) of every addressable shard of the heap."""
    heap = engine.segments.active.state.heap
    return [(str(s.device), tuple(s.data.shape))
            for s in heap.addressable_shards]


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def run_smoke(**kw) -> dict:
    """Ingest one segment and part of the next through a ServeLoop, serve
    every query kind once, and check all answers against
    :class:`NumpyIndex`.  Raises :class:`SmokeFailure` on any mismatch.
    On a TPU it also requires compiled kernels in the ingest and query
    programs (see :func:`kernel_programs`).  Keywords: see :func:`_run`."""
    with CompileLog() as comp:
        return _run(comp, **kw)


def _run(comp: CompileLog, *, devices, chips: int = 1,
         docs_per_segment: int = SEGMENT_DOCS, batch_docs: int = BATCH_DOCS,
         vocab: int = VOCAB, seed: int = 0, workdir=None,
         log=print) -> dict:
    import jax
    from repro.core import serve
    from repro.core.recovery import IngestJournal
    from repro.data.synth import CorpusSpec, TweetStream
    from repro.kernels import ops

    check(len(devices) >= chips, f"need {chips} devices, have {len(devices)}")
    check(docs_per_segment % batch_docs == 0 and batch_docs % chips == 0,
          "batch_docs must divide the segment and be a multiple of chips")
    on_tpu = devices[0].platform == "tpu"
    stream = TweetStream(CorpusSpec(vocab=vocab, mean_len=MEAN_LEN,
                                    seed=seed), width=DOC_WIDTH)
    queries = query_set(stream)
    ref = NumpyIndex([t for _, terms, _ in queries for t in terms])
    times = {}

    t0 = time.perf_counter()
    engine = build_engine(shards=chips, docs_per_segment=docs_per_segment,
                          vocab=vocab, seed=seed)
    workdir = Path(workdir or ROOT / ".chip_smoke")
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    journal = IngestJournal(str(workdir / "ingest.wal"))
    loop = serve.ServeLoop(engine, journal=journal)
    loop.force_level = serve.DEGRADE_NONE   # exact answers at every load
    times["setup_s"] = time.perf_counter() - t0
    layout = engine.layout
    log(f"layout: z={layout.z} slices_per_pool={layout.slices_per_pool} "
        f"heap_bytes={engine.segments.active.state.heap.nbytes} "
        f"max_len={engine.max_len} max_slices={engine.max_slices} "
        f"vocab={vocab} (assumed)")

    seg_batches = docs_per_segment // batch_docs
    n_batches = seg_batches + min(TAIL_BATCHES, seg_batches // 2)
    t_roll = 0.0
    comp.take()
    t0 = time.perf_counter()
    for i in range(n_batches):
        docs = stream.batch(i, batch_docs)
        ref.add(docs)
        seq = loop.submit_ingest(docs)
        check(not isinstance(seq, serve.Rejected), f"batch {i}: {seq}")
        if i == n_batches - 1 and on_tpu:
            # the last batch is lowered (not run) before it is applied
            # the served conjunctive batch's queries, in its order, so
            # the active program run here is the one the batch reuses
            ing_txt, query_txt = kernel_programs(
                engine, docs, [terms for kind, terms, _ in queries
                               if kind == "conjunctive"])
        ts = time.perf_counter()
        rolled = engine.stats.rollovers
        loop.step()
        if engine.stats.rollovers != rolled:
            t_roll += time.perf_counter() - ts
            log(f"rollover at batch {i}: {time.perf_counter() - ts:.3f}s")
        if (i + 1) % PROGRESS_EVERY == 0:
            jax.block_until_ready(engine.segments.active.state.heap)
            log(f"batch {i + 1}/{n_batches}: "
                f"{time.perf_counter() - t0:.3f}s since the first")
    jax.block_until_ready(engine.segments.active.state.heap)
    times["ingest_s"] = time.perf_counter() - t0 - t_roll
    times["rollover_s"] = t_roll
    times["compile_during_ingest_s"] = comp.take()
    journal.close()
    engine.check_health()
    ref.finish()
    st = loop.stats
    check(st.ingest_applied == n_batches and st.ingest_shed == 0,
          f"ingest applied {st.ingest_applied}/{n_batches}, "
          f"shed {st.ingest_shed}")
    check(engine.stats.rollovers == 1 and len(engine.frozen_packed) == 1,
          f"expected one rollover, got {engine.stats.rollovers}")
    check(engine.stats.docs_ingested == ref.n_docs, "doc count mismatch")
    log(f"ingested: docs={ref.n_docs} postings={ref.n_postings} "
        f"batches={n_batches} rollovers={engine.stats.rollovers} "
        f"active_docs={engine.segments.active.next_docid}")

    if on_tpu:
        where = "frozen-merge" if chips == 1 else "sharded active-query"
        check(not ops._default_interpret() and not engine.interpret
              and engine._batched_kernel == (chips == 1),
              "kernels would run in interpret mode or off the kernel route")
        check("tpu_custom_call" in ing_txt,
              "ingest program has no compiled Pallas kernel")
        check("tpu_custom_call" in query_txt,
              f"{where} program has no compiled Pallas kernel")
        log(f"kernels: tpu_custom_call in ingest and {where} programs")
    if chips > 1:
        shards = _shard_layout(engine)
        log(f"heap shards: {shards}")
        check(len({d for d, _ in shards}) == chips
              and all(s[0] == 1 for _, s in shards),
              "heap state is not split one shard per device")

    n_checked = 0
    t0 = time.perf_counter()
    qids = {}
    for kind, terms, k in queries:
        qid = loop.submit_query(kind, terms, k=k)
        check(not isinstance(qid, serve.Rejected), f"{kind}: {qid}")
        qids[qid] = (kind, terms, k)
    responses = loop.drain()
    times["queries_s"] = time.perf_counter() - t0
    times["compile_during_queries_s"] = comp.take()
    check(len(responses) == len(queries), "lost query responses")
    for r in responses:
        kind, terms, k = qids[r.qid]
        want_ids, want_sc = expected(ref, kind, terms, k)
        check(r.level == serve.DEGRADE_NONE, f"{kind} degraded")
        check(np.array_equal(np.asarray(r.docids, np.int64), want_ids),
              f"{kind}{tuple(int(t) for t in terms)}: docids differ "
              f"({len(r.docids)} vs {len(want_ids)} expected)")
        if want_sc is not None:
            check(np.array_equal(np.asarray(r.scores, np.int64), want_sc),
                  f"{kind}: scores differ")
        n_checked += 1
    log(f"answers: {n_checked} checked against the numpy index "
        f"({sum(len(expected(ref, *q)[0]) for q in queries)} docids)")

    stats = [d.memory_stats() or {} for d in devices[:chips]]
    peak = [s.get("peak_bytes_in_use") for s in stats]
    log(f"device: {devices[0].device_kind} x{chips} "
        f"peak_bytes_in_use={peak}")
    log("wall_s: " + " ".join(f"{k}={v:.3f}" for k, v in times.items()))
    slow = sorted(comp.programs, key=lambda p: -p[1])[:8]
    log("slowest compiles: " + " ".join(f"{n}={s:.1f}s" for n, s in slow))
    shutil.rmtree(workdir, ignore_errors=True)
    return {"docs": ref.n_docs, "batches": n_batches,
            "postings": ref.n_postings,
            "answers_checked": n_checked, "times": times,
            "peak_bytes_in_use": peak}


def result_line(devices, chips: int) -> str:
    d = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": chips}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the document-sharded engine")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--docs-per-segment", type=int, default=SEGMENT_DOCS,
                    help="tweets per segment (an Earlybird segment: 2^23)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform}); "
              f"refusing to run on anything else", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    from repro import compile_cache
    log = functools.partial(print, flush=True)
    log(f"compile cache: {compile_cache.enable()}")
    run_smoke(devices=devices, chips=args.chips,
              docs_per_segment=args.docs_per_segment,
              seed=args.seed, log=log)
    print(result_line(devices, args.chips))
    return 0


if __name__ == "__main__":
    sys.exit(main())
