"""Allocator invariants: exact agreement with the paper's step function,
reference-index equivalence, overflow safety, SP start pools."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import analytical, pointers, slicepool
from repro.core.pointers import NULL, PoolLayout
from repro.data import synth

from conftest import max_slices_for


def _ingest_freqs(z, freqs, start_pools_per_term=None):
    """Insert term t exactly freqs[t] times; return final state."""
    layout = PoolLayout(z=z, slices_per_pool=tuple(4096 for _ in z))
    V = len(freqs)
    terms = np.repeat(np.arange(V, dtype=np.uint32), freqs)
    posts = np.arange(len(terms), dtype=np.uint32)
    ingest = slicepool.make_ingest_fn(layout, V)
    state = slicepool.init_state(layout, V)
    sp = None
    if start_pools_per_term is not None:
        sp = jnp.asarray(np.asarray(start_pools_per_term, np.uint32)[terms])
    state = ingest(state, jnp.asarray(terms), jnp.asarray(posts), sp)
    return layout, state


@st.composite
def z_and_freqs(draw):
    P = draw(st.sampled_from([2, 4, 5, 8]))
    z = tuple(sorted(draw(st.lists(st.integers(0, 10), min_size=P,
                                   max_size=P, unique=True))))
    freqs = draw(st.lists(st.integers(1, 400), min_size=1, max_size=6))
    return z, freqs


@given(z_and_freqs())
@settings(max_examples=25, deadline=None)
def test_slots_match_step_function_exactly(zf):
    """C_M* == sum_t M(f_t): the allocator realises the paper's M exactly."""
    z, freqs = zf
    layout, state = _ingest_freqs(z, freqs)
    assert not bool(state.overflow)
    got = slicepool.memory_slots_used(layout, state)
    want = int(analytical.memory_slots(z, np.asarray(freqs)).sum())
    assert got == want
    assert np.array_equal(np.asarray(state.freq), freqs)


@given(z_and_freqs())
@settings(max_examples=15, deadline=None)
def test_materialized_postings_roundtrip(zf):
    """Everything written comes back, newest-first, per term."""
    z, freqs = zf
    layout, state = _ingest_freqs(z, freqs)
    mat = slicepool.make_materializer(
        layout, max_slices_for(z, freqs), max_len=512)
    V = len(freqs)
    terms = np.repeat(np.arange(V, dtype=np.uint32), freqs)
    posts = np.arange(len(terms), dtype=np.uint32)
    for t in range(V):
        vals, n = mat(state, jnp.uint32(t))
        assert int(n) == freqs[t]
        exp = posts[terms == t][::-1]
        assert np.array_equal(np.asarray(vals)[: int(n)], exp)


@given(z_and_freqs(), st.integers(1, 600))
@settings(max_examples=15, deadline=None)
def test_chain_prober_finds_each_docids_newest_lane(zf, gap):
    """The in-place probe agrees with the materialized chain: every
    docid of a term's list is found at the lane of its newest posting
    (a docid's postings may span slices), and no other docid is found.
    Postings ``i * gap`` put 256 / gap of them in each docid."""
    z, freqs = zf
    layout = PoolLayout(z=z, slices_per_pool=tuple(4096 for _ in z))
    V = len(freqs)
    terms = np.repeat(np.arange(V, dtype=np.uint32), freqs)
    posts = np.arange(len(terms), dtype=np.uint32) * np.uint32(gap)
    state = slicepool.make_ingest_fn(layout, V)(
        slicepool.init_state(layout, V), jnp.asarray(terms),
        jnp.asarray(posts))
    S = max_slices_for(z, freqs)
    walk = slicepool.make_chain_walker(layout, S)
    probe = slicepool.make_chain_prober(layout, S)

    @jax.jit
    def lookup(state, t, xs):
        bases, starts, lasts, n = walk(state, t)
        cum = slicepool.chain_lens_cum(starts, lasts, n, S)
        firsts = slicepool.chain_first_docids(state.heap, bases, starts,
                                              n, S)
        return probe(state.heap, bases, starts, lasts, cum, firsts, n, xs)

    for t in range(V):
        ids = posts[terms == t][::-1] >> 8           # lanes, newest first
        xs = np.arange(int(ids.min()) - 1 if ids.min() else 0,
                       int(ids.max()) + 2, dtype=np.uint32)
        xs = np.pad(xs, (0, 64 - len(xs) % 64), mode="edge")  # few shapes
        lane, found = lookup(state, jnp.uint32(t), jnp.asarray(xs))
        want = np.isin(xs, ids)
        assert np.array_equal(np.asarray(found), want), t
        newest = {int(d): i for i, d in reversed(list(enumerate(ids)))}
        assert [int(v) for v in np.asarray(lane)[want]] == \
            [newest[int(x)] for x in xs[want]], t


def test_overflow_sets_flag_and_preserves_data():
    layout = PoolLayout(z=(1, 4), slices_per_pool=(2, 1))
    ingest = slicepool.make_ingest_fn(layout, 1)
    state = slicepool.init_state(layout, 1)
    # capacity: 2*2 postings in pool0 for 1 term -> slice0 holds 2; then
    # pool1 slice holds 15; then pool1 again but only 1 slice -> overflow.
    n = 2 + 15 + 5
    state = ingest(state, jnp.zeros(n, jnp.uint32),
                   jnp.arange(n, dtype=jnp.uint32))
    assert bool(state.overflow)
    # postings written before exhaustion are intact
    mat = slicepool.make_materializer(layout, 4, 32)
    vals, cnt = mat(state, jnp.uint32(0))
    assert int(cnt) == 17
    assert np.array_equal(np.asarray(vals)[:17],
                          np.arange(17, dtype=np.uint32)[::-1])


def test_overflow_sticky_and_nonallocating_inserts_still_land():
    """The overflow bit is STICKY: once any allocation fails it stays set,
    even across later batches whose inserts succeed.  Inserts needing a
    fresh slice are dropped after exhaustion; inserts into a non-full
    slice still land."""
    layout = PoolLayout(z=(1, 4), slices_per_pool=(2, 1))
    ingest = slicepool.make_ingest_fn(layout, 2)
    state = slicepool.init_state(layout, 2)
    # term 0: 2 (pool0 slice) + 15 (the only pool1 slice) fit; the 18th
    # posting needs a second pool1 slice -> overflow.
    state = ingest(state, jnp.zeros(18, jnp.uint32),
                   jnp.arange(18, dtype=jnp.uint32))
    assert bool(state.overflow)
    assert int(state.freq[0]) == 17

    # term 1 allocates pool0's second slice: the insert SUCCEEDS and the
    # overflow bit must remain set.
    state = ingest(state, jnp.ones(1, jnp.uint32),
                   jnp.asarray([100], jnp.uint32))
    assert bool(state.overflow), "overflow bit must be sticky"
    assert int(state.freq[1]) == 1
    # second posting fills the slice (pool 0 has no pointer slot)...
    state = ingest(state, jnp.ones(1, jnp.uint32),
                   jnp.asarray([101], jnp.uint32))
    assert int(state.freq[1]) == 2
    # ...and the third needs a pool1 slice that no longer exists: no-op.
    state = ingest(state, jnp.ones(1, jnp.uint32),
                   jnp.asarray([102], jnp.uint32))
    assert int(state.freq[1]) == 2
    assert bool(state.overflow)
    mat = slicepool.make_materializer(layout, 4, 32)
    vals, cnt = mat(state, jnp.uint32(1))
    assert int(cnt) == 2
    assert np.asarray(vals)[:2].tolist() == [101, 100]


def test_materializer_truncates_chain_beyond_max_len():
    """A chain longer than max_len yields exactly the NEWEST max_len
    postings (reverse-chronological), with length clamped to max_len."""
    z = (1, 4, 7)
    f = 300
    layout, state = _ingest_freqs(z, [f])
    max_len = 64
    mat = slicepool.make_materializer(layout, max_slices_for(z, [f]),
                                      max_len=max_len)
    vals, n = mat(state, jnp.uint32(0))
    assert int(n) == max_len
    exp = np.arange(f, dtype=np.uint32)[::-1][:max_len]
    assert np.array_equal(np.asarray(vals), exp)


@pytest.mark.parametrize("start_pool", [0, 1, 2, 3])
def test_sp_start_pool_honoured(start_pool):
    z = (1, 4, 7, 11)
    layout, state = _ingest_freqs(z, [1], start_pools_per_term=[start_pool])
    # exactly one slice allocated, in the requested pool
    wm = np.asarray(state.watermark)
    exp = np.zeros(4, np.int32)
    exp[start_pool] = 1
    assert np.array_equal(wm, exp)
    # tail pointer decodes to that pool
    tbl = layout.tables()
    pool, _, off = pointers.decode(tbl, layout.pool_bits, state.tail[0])
    assert int(pool) == start_pool
    assert int(off) == (1 if start_pool > 0 else 0)  # ptr slot skipped


def test_sp_memory_matches_analytical_extension():
    """memory_slots_sp agrees with the allocator for non-zero start pools."""
    z = (1, 4, 7, 11)
    for sp in range(4):
        for f in [1, 2, 3, 15, 16, 40, 200, 3000]:
            layout, state = _ingest_freqs(z, [f], start_pools_per_term=[sp])
            got = slicepool.memory_slots_used(layout, state)
            want = int(analytical.memory_slots_sp(z, [f], [sp])[0])
            assert got == want, (sp, f, got, want)


# ---------------------------------------------------------------------------
# Slice reclamation (freeze -> free list -> reuse): the Goldilocks loop
# ---------------------------------------------------------------------------
def test_memory_drops_after_freeze_release():
    """Freezing a segment and releasing its slices must drop the LIVE
    slot count to zero while the high-water mark stays put."""
    from repro.core import segments
    z = (1, 4, 7)
    layout, state = _ingest_freqs(z, [40, 3, 17])
    used = slicepool.memory_slots_used(layout, state)
    assert used > 0
    assert slicepool.memory_high_water_slots(layout, state) == used
    fz = segments.freeze_state(layout, np.asarray(state.heap),
                               np.asarray(state.tail),
                               np.asarray(state.freq), n_docs=60)
    # the freeze walked exactly the allocated slices, per pool
    n_freed = sum(len(s) for s in fz.freed_slices)
    assert n_freed == int(np.asarray(state.watermark).sum())
    released = slicepool.release_slices(layout, state, fz.freed_slices)
    assert slicepool.memory_slots_used(layout, released) == 0
    assert slicepool.memory_high_water_slots(layout, released) == used
    assert np.all(np.asarray(released.tail) == NULL)
    assert np.all(np.asarray(released.freq) == 0)
    # frozen CSR kept every posting
    assert fz.total_postings == 40 + 3 + 17


def test_freed_slices_reused_watermark_stops_growing():
    """Steady churn: identical segments rolled through the same pools
    must stop bumping the watermark once the free list covers demand."""
    from repro.core import segments
    layout = PoolLayout(z=(1, 4, 7, 11),
                        slices_per_pool=(4096, 2048, 512, 64))
    spec_docs = synth.zipf_corpus(
        synth.CorpusSpec(vocab=300, n_docs=100, seed=8))
    ss = segments.SegmentSet(layout, 300, docs_per_segment=100)
    hw = []
    for _ in range(4):
        ss.ingest(jnp.asarray(spec_docs))        # fills + rolls over
        hw.append(slicepool.memory_high_water_slots(
            layout, ss.active.state))
    assert len(ss.frozen) == 4
    # identical stream per segment -> identical demand -> zero growth
    # after the first rollover seeds the free list.
    assert hw[1] == hw[2] == hw[3], hw
    # live slots are back to zero after each full-segment rollover
    assert slicepool.memory_slots_used(layout, ss.active.state) == 0
    # and queries over the recycled pools still see the latest postings
    freqs = synth.term_freqs(spec_docs, 300)
    assert np.array_equal(ss.frozen[-1].term_freqs(), freqs)


def test_free_list_allocation_preserves_overflow_stickiness():
    """Releasing slices lets later inserts succeed from the free list,
    but a pool-exhaustion overflow observed earlier must stay sticky."""
    from repro.core import segments
    layout = PoolLayout(z=(1, 4), slices_per_pool=(2, 1))
    ingest = slicepool.make_ingest_fn(layout, 2)
    state = slicepool.init_state(layout, 2)
    # term 0: 17 fit (2 + 15), the 18th needs a 2nd pool-1 slice -> overflow
    state = ingest(state, jnp.zeros(18, jnp.uint32),
                   jnp.arange(18, dtype=jnp.uint32))
    assert bool(state.overflow)
    fz = segments.freeze_state(layout, np.asarray(state.heap),
                               np.asarray(state.tail),
                               np.asarray(state.freq), n_docs=18)
    state = slicepool.release_slices(layout, state, fz.freed_slices)
    assert slicepool.memory_slots_used(layout, state) == 0
    # the freed pool-0 and pool-1 slices are reused: 17 postings fit again
    state = ingest(state, jnp.ones(17, jnp.uint32),
                   jnp.arange(100, 117, dtype=jnp.uint32))
    assert int(state.freq[1]) == 17
    # reuse did not bump the watermark...
    assert np.asarray(state.watermark).tolist() == [1, 1]
    # ...returned correct data...
    mat = slicepool.make_materializer(layout, 4, 32)
    vals, cnt = mat(state, jnp.uint32(1))
    assert int(cnt) == 17
    assert np.array_equal(np.asarray(vals)[:17],
                          np.arange(100, 117, dtype=np.uint32)[::-1])
    # ...and the overflow bit stayed sticky across the release.
    assert bool(state.overflow), "overflow must survive reclamation"


def test_release_rejects_double_free():
    """Re-releasing slices that already sit on the free list must fail
    loudly even when the free list has spare capacity — silent aliasing
    would hand one slice to two term chains."""
    from repro.core import segments
    z = (1, 4)
    layout, state = _ingest_freqs(z, [5])
    fz = segments.freeze_state(layout, np.asarray(state.heap),
                               np.asarray(state.tail),
                               np.asarray(state.freq), n_docs=5)
    state = slicepool.release_slices(layout, state, fz.freed_slices)
    with pytest.raises(ValueError, match="double release"):
        slicepool.release_slices(layout, state, fz.freed_slices)
    # never-allocated slice indices are rejected too
    with pytest.raises(ValueError, match="allocated range"):
        slicepool.release_slices(
            layout, state,
            [np.asarray([3], np.int32)] + [np.zeros(0, np.int32)])


def test_zero_copy_invariant():
    """Old postings bytes are never rewritten by later inserts."""
    z = (1, 4, 7, 11)
    layout = PoolLayout(z=z, slices_per_pool=(64, 32, 16, 8))
    ingest = slicepool.make_ingest_fn(layout, 4)
    state = slicepool.init_state(layout, 4)
    rng = np.random.default_rng(0)
    terms = rng.integers(0, 4, 500).astype(np.uint32)
    posts = np.arange(500, dtype=np.uint32)
    snapshots = []
    for chunk in range(5):
        sl = slice(chunk * 100, (chunk + 1) * 100)
        state = ingest(state, jnp.asarray(terms[sl]), jnp.asarray(posts[sl]))
        snapshots.append(np.asarray(state.heap).copy())
    for a, b in zip(snapshots, snapshots[1:]):
        written = a != 0
        assert np.array_equal(a[written], b[written])
