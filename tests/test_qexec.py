"""qexec equivalence: the batched query path (segment stacking + query
batching + early-exit top-k) must be BIT-IDENTICAL to the per-query
host-loop oracle (``batched=False``) — conjunctive / disjunctive /
phrase, random streams through >= 2 rollovers, single-device and
4-shard — and early-exit top-k must equal the full evaluation's
``[:k]`` for every k including k > |result|."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import analytical, qexec
from repro.core import lifecycle as lc
from repro.core.lifecycle import LifecycleEngine
from repro.core.pointers import PoolLayout
from repro.data import synth

Z = (1, 4, 7, 11)
LAYOUT = PoolLayout(z=Z, slices_per_pool=(4096, 2048, 512, 64))


def _build(seed, vocab=500, n_docs=460, docs_per_segment=180, **kw):
    """Drive a fresh lifecycle engine through >= 2 rollovers."""
    spec = synth.CorpusSpec(vocab=vocab, n_docs=n_docs, seed=seed)
    docs = synth.zipf_corpus(spec)
    freqs = synth.term_freqs(docs, vocab)
    fmax = int(freqs.max())
    max_slices = int(analytical.slices_needed(Z, fmax)) + 1
    max_len = 1 << (fmax - 1).bit_length()
    eng = LifecycleEngine(LAYOUT, vocab, docs_per_segment,
                          max_slices=max_slices, max_len=max_len,
                          use_kernel=False, **kw)
    for i in range(0, n_docs, 20):
        eng.ingest(docs[i: i + 20])
    assert eng.stats.rollovers >= (2 if n_docs >= 2 * docs_per_segment
                                   else 0)
    # post-condition: allocator + frozen-segment structural invariants
    # hold on every engine the query-equivalence tests run against.
    eng.validate_invariants()
    return eng, freqs


@pytest.fixture(scope="module", params=[11, 29])
def engine(request):
    return _build(request.param)


def _oracle(eng, kind, terms, limit=None):
    """Per-query host-loop result with the SAME engine object."""
    eng.batched = False
    try:
        if kind == "phrase":
            return eng.phrase(terms[0], terms[1], limit)
        return getattr(eng, kind)(terms, limit)
    finally:
        eng.batched = True


terms_strategy = st.lists(st.integers(0, 499), min_size=1, max_size=4)


@given(st.lists(terms_strategy, min_size=1, max_size=5))
@settings(max_examples=40, deadline=None)
def test_batched_matches_sequential_conjunctive(engine, queries):
    eng, freqs = engine
    # bias half the draws toward hot terms so intersections are nonempty
    top = np.argsort(-freqs)
    queries = [[int(top[t % 64]) if i % 2 else t for i, t in enumerate(q)]
               for q in queries]
    got = eng.conjunctive_batch(queries)
    for terms, g in zip(queries, got):
        exp = _oracle(eng, "conjunctive", terms)
        assert np.array_equal(g, exp), (terms, g[:8], exp[:8])


@given(st.lists(terms_strategy, min_size=1, max_size=4))
@settings(max_examples=25, deadline=None)
def test_batched_matches_sequential_disjunctive(engine, queries):
    eng, _ = engine
    got = eng.disjunctive_batch(queries)
    for terms, g in zip(queries, got):
        exp = _oracle(eng, "disjunctive", terms)
        assert np.array_equal(g, exp), (terms,)


@given(st.lists(st.tuples(st.integers(0, 499), st.integers(0, 499)),
                min_size=1, max_size=4))
@settings(max_examples=25, deadline=None)
def test_batched_matches_sequential_phrase(engine, pairs):
    eng, freqs = engine
    top = np.argsort(-freqs)
    pairs = [(int(top[a % 32]), int(top[b % 32])) for a, b in pairs]
    got = eng.phrase_batch(pairs)
    for (t1, t2), g in zip(pairs, got):
        exp = _oracle(eng, "phrase", (t1, t2))
        assert np.array_equal(g, exp), (t1, t2)


@given(terms_strategy, st.integers(0, 40))
@settings(max_examples=40, deadline=None)
def test_topk_early_exit_matches_full(engine, terms, k):
    """Early-exit top-k == full evaluation's [:k] for EVERY k, including
    k = 0 and k > |result| (the loop must then drain every segment)."""
    eng, freqs = engine
    top = np.argsort(-freqs)
    terms = [int(top[t % 64]) if i % 2 else t
             for i, t in enumerate(terms)]
    full = _oracle(eng, "conjunctive", terms)
    got = eng.topk_conjunctive(terms, k)
    assert np.array_equal(got, full[:k]), (terms, k, got, full[:k])
    # k beyond the result set must return the whole result
    got_all = eng.topk_conjunctive(terms, len(full) + 3)
    assert np.array_equal(got_all, full)
    # and a conjunctive limit routes through the same early-exit path
    assert np.array_equal(eng.conjunctive(terms, limit=k), full[:k])


def test_limit_matches_oracle_all_kinds(engine):
    eng, freqs = engine
    top = np.argsort(-freqs)
    t1, t2 = int(top[0]), int(top[1])
    for kind, args in (("conjunctive", (t1, t2)),
                       ("disjunctive", (t1, t2)),
                       ("phrase", (t1, t2))):
        got = (eng.phrase(t1, t2, 5) if kind == "phrase"
               else getattr(eng, kind)(args, 5))
        exp = _oracle(eng, kind, args, 5)
        assert np.array_equal(got, exp), kind


def test_batched_frozen_path_makes_zero_host_roundtrips(engine, monkeypatch):
    """The acceptance bar: NO per-segment host syncs inside the batched
    frozen path.  The oracle calls ``conjunctive_packed`` (one jit + one
    np.asarray per segment per query); the batched path must never."""
    eng, freqs = engine
    top = np.argsort(-freqs)

    def boom(*a, **k):
        raise AssertionError("batched path fell back to the per-segment "
                             "host loop")

    monkeypatch.setattr(lc, "conjunctive_packed", boom)
    monkeypatch.setattr(lc, "disjunctive_packed", boom)
    monkeypatch.setattr(lc, "phrase_packed", boom)
    qs = [[int(top[0]), int(top[1])], [int(top[2])]]
    assert len(eng.conjunctive_batch(qs)) == 2
    assert len(eng.disjunctive_batch(qs)) == 2
    assert len(eng.phrase_batch([(int(top[0]), int(top[1]))])) == 1
    assert eng.topk_conjunctive([int(top[0])], 3).shape == (3,)
    eng.batched = False
    with pytest.raises(AssertionError):
        eng.conjunctive([int(top[0]), int(top[1])])
    eng.batched = True


def test_batched_kernel_path_matches(engine):
    """The batched Pallas grid kernel (forced, interpret mode on CPU)
    must not change any result — masks are bit-identical to the jnp
    membership fold."""
    eng, freqs = engine
    top = np.argsort(-freqs)
    ek, _ = _build(11, batched_kernel=True)
    for terms in ([int(top[0]), int(top[1])],
                  [int(top[2]), int(top[5]), int(top[9])]):
        exp = _oracle(ek, "conjunctive", terms)
        assert np.array_equal(ek.conjunctive(terms), exp), terms


def test_no_frozen_segments_path():
    """G = 0 (before the first rollover) takes the finalize fast path."""
    eng2, freqs = _build(7, n_docs=100, docs_per_segment=10_000)
    assert eng2.stats.rollovers == 0
    top = np.argsort(-freqs)
    terms = [int(top[0]), int(top[1])]
    exp = _oracle(eng2, "conjunctive", terms)
    assert np.array_equal(eng2.conjunctive(terms), exp)
    assert np.array_equal(eng2.topk_conjunctive(terms, 3), exp[:3])
    assert np.array_equal(eng2.disjunctive(terms),
                          _oracle(eng2, "disjunctive", terms))


def _topk_case(eng, case):
    """``(terms, max_len, tile)`` of one active top-k case, its terms
    picked by their list length in the ACTIVE segment."""
    freq = np.asarray(eng.segments.active.state.freq)
    by_len = [int(t) for t in np.argsort(-freq, kind="stable")]
    absent = int(np.nonzero(freq == 0)[0][0])
    long_, short = by_len[0], by_len[6]
    return {
        "longest_first": ([long_, short], eng.max_len, 128),
        "shortest_first": ([short, long_], eng.max_len, 128),
        "absent_term": ([long_, absent, short], eng.max_len, 128),
        "repeated_term": ([by_len[1], short, by_len[1]], eng.max_len, 128),
        "four_terms": (by_len[:4], eng.max_len, 128),
        # both lists outgrow the 64-posting window, so slot 0 drives
        # (a tie); slot 1 holds more postings a doc, so its window
        # reaches fewer docs back: its probe must honour the window
        "window_truncates": ([by_len[1], long_], 64, 16),
    }[case]


@pytest.mark.parametrize("case", ["longest_first", "shortest_first",
                                  "absent_term", "repeated_term",
                                  "four_terms", "window_truncates"])
def test_active_topk_fn_matches_engine_topk(engine, case):
    """Engine-level: the early-exit active top-k, driven by the row's
    shortest list and probing the others in place, must equal
    ``QueryEngine.topk_conjunctive`` (full intersection then [:k]) at
    the same ``max_len`` window, for every k up to ``k_pad``."""
    from repro.core import query as q
    eng, _ = engine
    state = eng.segments.active.state
    terms, max_len, tile = _topk_case(eng, case)
    k_pad = 32
    engine_q = q.make_engine(eng.layout, eng.max_slices, max_len,
                             eng.max_query_len)
    fn = qexec.make_active_topk_fn(eng.layout, eng.max_slices, max_len,
                                   k_pad=k_pad, tile=tile)
    padded = np.zeros((1, eng.max_query_len), np.uint32)
    padded[0, : len(terms)] = terms
    row, nt = jnp.asarray(padded[0]), jnp.int32(len(terms))
    if case == "window_truncates":
        # the window must bite inside the k range: fewer hits than the
        # untruncated intersection holds, and fewer than k_pad
        n_win = int(engine_q.conjunctive(state, row, nt)[1])
        n_all = int(eng.engine.conjunctive(state, row, nt)[1])
        assert n_win < min(n_all, k_pad), (n_win, n_all)
    for k in range(1, k_pad + 1):
        got_d, got_n, _ = fn(state, jnp.asarray(padded),
                             jnp.asarray([len(terms)], np.int32),
                             jnp.int32(k))
        exp_d, exp_n = engine_q.topk_conjunctive(state, row, nt, k)
        gn, en = int(got_n[0]), int(exp_n)
        assert gn == en, (terms, k, gn, en)
        assert np.array_equal(np.asarray(got_d[0])[:gn],
                              np.asarray(exp_d)[:en]), (terms, k)


def test_active_topk_tiles_counter(engine):
    """The tile counter: a live row scans at most ceil(shortest window /
    tile) driver tiles, a row with an empty list scans none, padding rows
    add nothing, and a ServeLoop's ``ServeStats`` deltas equal the sum of
    the per-row counts of the batches it dispatched."""
    from repro.core import serve as sv
    eng, _ = engine
    state = eng.segments.active.state
    freq = np.asarray(state.freq)
    by_len = [int(t) for t in np.argsort(-freq, kind="stable")]
    absent = int(np.nonzero(freq == 0)[0][0])
    queries = [(by_len[0], by_len[1]), (by_len[0], absent),
               (by_len[2],), (by_len[0], by_len[6], by_len[3])]
    k, tb = 20, eng.max_query_len

    def run(tile, qs):
        terms, n_terms = qexec.pad_query_batch(qs, tb)
        fn = qexec.make_active_topk_fn(eng.layout, eng.max_slices,
                                       eng.max_len, k_pad=32, tile=tile)
        _, n, tiles = fn(state, jnp.asarray(terms), jnp.asarray(n_terms),
                         jnp.int32(k))
        return np.asarray(n), np.asarray(tiles)

    for tile in (8, 128):
        n, tiles = run(tile, queries + [(by_len[4],)] * 3)  # 7 rows -> 8
        assert n.shape == tiles.shape == (8,)
        for i, qt in enumerate(queries):
            shortest = min(min(int(freq[t]), eng.max_len) for t in qt)
            assert 0 <= tiles[i] <= -(-shortest // tile), (tile, qt)
        assert tiles[1] == 0                        # an empty list
        assert tiles[7] == 0                        # the padding row
        assert (tiles[:4] > 0).sum() == 3
    # 8-lane tiles, k = 20: the first row stops early once 20 hits are
    # banked; the single-term row 2 reads up to the tile that holds its
    # 20th distinct docid
    n, tiles = run(8, queries)
    assert n[0] == k and tiles[0] < -(-min(freq[by_len[1]],
                                            eng.max_len) // 8)
    plist, m = eng.engine.postings_desc(state, jnp.uint32(by_len[2]))
    ids = np.asarray(plist)[: int(m)] >> 8
    new_doc = np.r_[True, ids[1:] != ids[:-1]]
    lane_k = int(np.nonzero(new_doc)[0][k - 1])
    assert tiles[2] == lane_k // 8 + 1

    loop = sv.ServeLoop(eng, sv.ServeConfig(max_batch=4, batch_wait_s=0.0))
    loop.force_level = sv.DEGRADE_NONE
    before = dataclasses.asdict(loop.stats)
    for qt in queries:
        loop.submit_query("topk", qt, k=k)
    loop.drain()
    after = dataclasses.asdict(loop.stats)
    terms, n_terms = qexec.pad_query_batch(queries, eng.max_query_len)
    tb_ = min(qexec.bucket_pow2(int(n_terms.max())), eng.max_query_len)
    fn = qexec.make_active_topk_fn(eng.layout, eng.max_slices, eng.max_len,
                                   k_pad=qexec.bucket_pow2(k, 8))
    _, _, tiles = fn(state, jnp.asarray(terms[:, :tb_]),
                     jnp.asarray(n_terms), jnp.int32(k))
    assert (after["topk_tiles_scanned"] - before["topk_tiles_scanned"]
            == int(np.asarray(tiles).sum()) > 0)
    assert after["topk_rows_live"] - before["topk_rows_live"] == 4


def test_topk_ragged_max_len():
    """Regression: a max_len that is NOT a multiple of the 128 top-k
    tile (e.g. 200) must still materialize the ragged last tile —
    ``n_tiles`` floored to ``max_len // tile`` silently dropped every
    hit past lane 128 and broke bit-identity with the full path."""
    spec = synth.CorpusSpec(vocab=50, n_docs=200, seed=1)
    docs = synth.zipf_corpus(spec)
    eng = LifecycleEngine(LAYOUT, 50, 90, max_slices=12, max_len=200,
                          use_kernel=False)
    for i in range(0, 200, 10):
        eng.ingest(docs[i: i + 10])
    freqs = synth.term_freqs(docs, 50)
    top = np.argsort(-freqs)
    widest = 0
    for terms in ([int(top[0]), int(top[1])], [int(top[0])]):
        full = _oracle(eng, "conjunctive", terms)
        widest = max(widest, len(full))
        for k in (128, 170, len(full), len(full) + 1):
            got = eng.topk_conjunctive(terms, k)
            assert np.array_equal(got, full[:k]), (terms, k)
    assert widest > 128  # the bug only bites past the first 128-lane tile


def test_query_batch_padding_rejects_bad_rows():
    with pytest.raises(ValueError):
        qexec.pad_query_batch([[]], 8)
    with pytest.raises(ValueError):
        qexec.pad_query_batch([list(range(9))], 8)


# ---------------------------------------------------------------------------
# 4-shard equivalence (subprocess keeps forced host devices isolated)
# ---------------------------------------------------------------------------
SCRIPT_SHARDED = textwrap.dedent("""
    from repro.dist import collectives as C
    C.force_host_device_count(4)
    import json
    import numpy as np
    import jax.numpy as jnp

    from repro.core import analytical
    from repro.core.lifecycle import (LifecycleEngine,
                                      ShardedLifecycleEngine)
    from repro.core.pointers import PoolLayout
    from repro.core.sharded_index import make_doc_mesh
    from repro.data import synth

    Z = (1, 4, 7, 11)
    layout = PoolLayout(z=Z, slices_per_pool=(4096, 2048, 512, 64))
    spec = synth.CorpusSpec(vocab=400, n_docs=360, seed=17)
    docs = synth.zipf_corpus(spec)
    freqs = synth.term_freqs(docs, spec.vocab)
    fmax = int(freqs.max())
    max_slices = int(analytical.slices_needed(Z, fmax)) + 1
    max_len = 1 << (fmax - 1).bit_length()
    mesh, rules = make_doc_mesh(4)

    # 120-doc segments over 360 docs -> >= 2 rollovers + active data
    single = LifecycleEngine(layout, spec.vocab, 120,
                             max_slices=max_slices, max_len=max_len,
                             use_kernel=False)
    shard = ShardedLifecycleEngine(layout, spec.vocab, 120, mesh,
                                   max_slices=max_slices, max_len=max_len,
                                   rules=rules, use_kernel=False)
    for i in range(0, 360, 40):
        single.ingest(docs[i:i + 40])
        shard.ingest(docs[i:i + 40])
    assert single.stats.rollovers >= 2 and shard.stats.rollovers >= 2

    top = np.argsort(-freqs)
    queries = [[int(top[0]), int(top[1])], [int(top[2]), int(top[5])],
               [int(top[9])], [int(top[1]), int(top[3]), int(top[7])],
               [int(top[0]), 399]]
    n_checked = 0
    for kind in ("conjunctive", "disjunctive"):
        got_b = getattr(shard, kind + "_batch")(queries)
        for terms, g in zip(queries, got_b):
            shard.batched = False
            exp_seq = getattr(shard, kind)(terms)
            shard.batched = True
            exp_single = getattr(single, kind)(terms)
            assert np.array_equal(g, exp_seq), (kind, terms)
            assert np.array_equal(g, exp_single), (kind, terms)
            n_checked += 1
    pairs = [(int(top[0]), int(top[1])), (int(top[2]), int(top[0]))]
    for (t1, t2), g in zip(pairs, shard.phrase_batch(pairs)):
        shard.batched = False
        exp = shard.phrase(t1, t2)
        shard.batched = True
        assert np.array_equal(g, exp), (t1, t2)
        assert np.array_equal(g, single.phrase(t1, t2)), (t1, t2)
        n_checked += 1
    for terms in queries:
        shard.batched = False
        full = shard.conjunctive(terms)
        shard.batched = True
        for k in (1, 4, len(full), len(full) + 2):
            got = shard.topk_conjunctive(terms, k)
            assert np.array_equal(got, full[:k]), (terms, k)
            n_checked += 1
    print(json.dumps({"n_checked": n_checked}))
""")


def _run_subprocess(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_batched_matches_sequential_and_single_device():
    res = _run_subprocess(SCRIPT_SHARDED)
    assert res["n_checked"] == 32
