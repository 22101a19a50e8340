"""Pallas kernels vs jnp oracles: shape/dtype sweeps in interpret mode
(spec deliverable c)."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.paged_attention import PAGE

RNG = np.random.default_rng(0)

# REPRO_CHECKED=1 (CI's checked leg) reruns every postings/segment ops.*
# call under the checkify sanitizer (repro.analysis.sanitize: index OOB
# + NaN + div) — same expected outputs, instrumented oracle path.
CHECKED = bool(int(os.environ.get("REPRO_CHECKED", "0")))


# ---------------------------------------------------------------------------
# paged_attention
# ---------------------------------------------------------------------------
def _paged_case(B, Hkv, G, D, lens, dtype, n_free_pages=64):
    n_pages_each = [-(-n // PAGE) if n else 0 for n in lens]
    NP = max(max(n_pages_each), 1)
    perm = RNG.permutation(n_free_pages)
    table = np.full((B, NP), -1, np.int32)
    pi = 0
    for b, npg in enumerate(n_pages_each):
        table[b, :npg] = perm[pi:pi + npg]
        pi += npg
    slots = n_free_pages * PAGE
    q = RNG.normal(size=(B, Hkv, G, D)).astype(dtype)
    kh = RNG.normal(size=(Hkv, slots, D)).astype(dtype)
    vh = RNG.normal(size=(Hkv, slots, D)).astype(dtype)
    return (jnp.asarray(q), jnp.asarray(kh), jnp.asarray(vh),
            jnp.asarray(table), jnp.asarray(np.asarray(lens, np.int32)))


@pytest.mark.parametrize("B,Hkv,G,D", [
    (1, 1, 1, 16), (2, 2, 4, 32), (3, 4, 2, 64), (2, 1, 8, 128),
])
def test_paged_attention_shapes(B, Hkv, G, D):
    lens = [int(x) for x in RNG.integers(1, 5 * PAGE, B)]
    args = _paged_case(B, Hkv, G, D, lens, np.float32)
    out = ops.paged_attention(*args, interpret=True)
    expect = ref.paged_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=3e-5, atol=3e-5)


def test_paged_attention_bf16():
    args = _paged_case(2, 2, 2, 32, [70, 200], jnp.bfloat16)
    out = ops.paged_attention(*args, interpret=True)
    expect = ref.paged_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-2)


def test_paged_attention_page_boundaries():
    """Lengths exactly at page edges (the masking edge cases)."""
    for lens in ([PAGE], [PAGE - 1], [PAGE + 1], [2 * PAGE], [1]):
        args = _paged_case(1, 1, 2, 16, lens, np.float32)
        out = ops.paged_attention(*args, interpret=True)
        expect = ref.paged_attention_ref(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=3e-5, atol=3e-5, err_msg=str(lens))


def test_paged_attention_matches_dense():
    """Through-the-page-table attention == plain dense attention when the
    pages are identity-mapped."""
    B, Hkv, G, D, T = 2, 2, 2, 32, 3 * PAGE
    q = jnp.asarray(RNG.normal(size=(B, Hkv, G, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, T, Hkv, D)), jnp.float32)
    # pack into per-b pages: heap rows [Hkv, B*T, D], page b*3+i
    kh = k.transpose(2, 0, 1, 3).reshape(Hkv, B * T, D)
    vh = v.transpose(2, 0, 1, 3).reshape(Hkv, B * T, D)
    table = jnp.asarray(
        [[b * 3 + i for i in range(3)] for b in range(B)], jnp.int32)
    lens = jnp.asarray([T, T], jnp.int32)
    out = ops.paged_attention(q, kh, vh, table, lens, interpret=True)
    # dense reference
    s = jnp.einsum("bhgd,bthd->bhgt", q, k) * (D ** -0.5)
    dense = jnp.einsum("bhgt,bthd->bhgd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# embedding_bag
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("R,D,B,max_bag", [
    (128, 16, 4, 5), (1000, 32, 8, 12), (64, 128, 3, 3),
])
@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_embedding_bag(R, D, B, max_bag, mode):
    lens = RNG.integers(0, max_bag + 1, B)
    offsets = np.zeros(B + 1, np.int32)
    offsets[1:] = np.cumsum(lens)
    n = int(offsets[-1])
    idx = RNG.integers(0, R, max(n, 1)).astype(np.int32)[:n]
    if n == 0:
        idx = np.zeros(0, np.int32)
    table = RNG.normal(size=(R, D)).astype(np.float32)
    args = (jnp.asarray(table), jnp.asarray(idx), jnp.asarray(offsets))
    out = ops.embedding_bag(*args, mode=mode, interpret=True)
    expect = ref.embedding_bag_ref(*args, mode=mode)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_embedding_bag_matches_model_substrate():
    """Kernel == the models/recsys.py embedding_bag (take+segment_sum)."""
    from repro.models.recsys import embedding_bag as model_bag
    R, D, B = 256, 64, 6
    lens = RNG.integers(1, 6, B)
    offsets = np.zeros(B + 1, np.int32)
    offsets[1:] = np.cumsum(lens)
    idx = RNG.integers(0, R, int(offsets[-1])).astype(np.int32)
    seg = np.repeat(np.arange(B), lens).astype(np.int32)
    table = RNG.normal(size=(R, D)).astype(np.float32)
    out_k = ops.embedding_bag(jnp.asarray(table), jnp.asarray(idx),
                              jnp.asarray(offsets), interpret=True)
    out_m = model_bag(jnp.asarray(table), jnp.asarray(idx),
                      jnp.asarray(seg), B)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_m),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# intersect_mask
# ---------------------------------------------------------------------------
def _pad_asc(vals, width):
    out = np.full(width, 0xFFFFFFFF, np.uint32)
    v = np.unique(np.asarray(vals, np.uint32))
    out[: len(v)] = v
    return out


@pytest.mark.parametrize("na,nb,ta,tb", [
    (256, 256, 256, 256), (512, 256, 128, 128), (1024, 512, 256, 128),
])
def test_intersect_mask(na, nb, ta, tb):
    a = _pad_asc(RNG.choice(4 * na, na // 2, replace=False), na)
    b = _pad_asc(RNG.choice(4 * na, nb // 3, replace=False), nb)
    out = ops.intersect_mask(jnp.asarray(a), jnp.asarray(b),
                             ta=ta, tb=tb, interpret=True,
                             checked=CHECKED)
    expect = ref.intersect_mask_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_intersect_mask_edges():
    # empty a / empty b / disjoint / identical
    empty = _pad_asc([], 256)
    full = _pad_asc(np.arange(100), 256)
    hi = _pad_asc(np.arange(1000, 1100), 256)
    for a, b in [(empty, full), (full, empty), (full, hi), (full, full)]:
        out = ops.intersect_mask(jnp.asarray(a), jnp.asarray(b),
                                 interpret=True, checked=CHECKED)
        expect = ref.intersect_mask_ref(jnp.asarray(a), jnp.asarray(b))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("ta,tb", [(128, 128), (256, 512)])
def test_intersect_mask_under_vmap(ta, tb):
    """Nested vmap (queries x shards, as the engines call it) folds into
    the kernel's row grid; an unmapped operand is broadcast."""
    a = np.stack([np.stack([_pad_asc(RNG.choice(3000, n, replace=False),
                                     640) for n in (0, 300, 640)])
                  for _ in range(2)])
    b = np.stack([np.stack([_pad_asc(RNG.choice(3000, n, replace=False),
                                     384) for n in (200, 0, 384)])
                  for _ in range(2)])

    def kern(x, y):
        return ops.intersect_mask(x, y, ta=ta, tb=tb, interpret=True)

    want = np.stack([
        np.stack([np.isin(a[i, j], b[i, j]) & (a[i, j] != 0xFFFFFFFF)
                  for j in range(3)]) for i in range(2)]).astype(np.int32)
    got = jax.jit(jax.vmap(jax.vmap(kern)))(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got), want)
    got = jax.vmap(kern, in_axes=(0, None))(jnp.asarray(a[0]),
                                            jnp.asarray(b[0, 0]))
    want = np.stack([np.isin(a[0, j], b[0, 0]) & (a[0, j] != 0xFFFFFFFF)
                     for j in range(3)]).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n,order", [(40, "random"), (2500, "random"),
                                     (2500, "sorted")])
def test_bulk_append_matches_oracle(n, order):
    """The row read-modify-write scatter against the jnp oracle across its
    edges: writes into a target's last row, a vocabulary that is not whole
    rows, streams of one and of several SMEM chunks, skip lanes (address >= length), rows held across
    consecutive writes, and rows revisited after a swap."""
    from repro.core.slicepool import row_words
    from repro.kernels.bulk_append import bulk_append
    rng = np.random.default_rng(n)
    H, V = row_words(2 * n + 77), n + 45    # a partial last vocab row
    heap = rng.integers(0, 2**32, H, dtype=np.uint64).astype(np.uint32)
    tail = rng.integers(0, 2**32, V, dtype=np.uint64).astype(np.uint32)
    freq = rng.integers(0, 1000, V).astype(np.int32)
    slots = rng.permutation(H + n // 4)[: 2 * n]    # post + ptr, disjoint
    terms = rng.permutation(V + n // 8)[:n]
    if order == "sorted":
        slots.sort()
        terms.sort()
    args = [jnp.asarray(x) for x in (
        heap, tail, freq,
        slots[:n].astype(np.int32),
        rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32),
        slots[n:].astype(np.int32),
        rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32),
        terms.astype(np.int32),
        rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32),
        rng.integers(0, 1000, n).astype(np.int32))]
    got = bulk_append(*args, interpret=True)
    want = ref.bulk_append_ref(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_bulk_append_refuses_partial_heap_rows():
    """The heap is allocated in whole 128-word rows
    (``slicepool.row_words``); a partial row would need a padded copy of
    the heap, so the kernel refuses it."""
    from repro.kernels.bulk_append import bulk_append
    z = jnp.zeros((1,), jnp.int32)
    u = jnp.zeros((1,), jnp.uint32)
    with pytest.raises(ValueError, match="whole 128-word rows"):
        bulk_append(jnp.zeros((200,), jnp.uint32), jnp.zeros((100,), jnp.uint32),
                    jnp.zeros((100,), jnp.int32), z, u, z, u, z, u, z,
                    interpret=True)


def test_intersect_mask_used_by_query_engine():
    """Kernel mask -> compaction reproduces intersect_asc."""
    from repro.core.query import _compact, intersect_asc
    a = _pad_asc(RNG.choice(500, 80, replace=False), 256)
    b = _pad_asc(RNG.choice(500, 120, replace=False), 256)
    mask = ops.intersect_mask(jnp.asarray(a), jnp.asarray(b),
                              interpret=True, checked=CHECKED)
    got, n_got = _compact(jnp.asarray(a), mask.astype(bool))
    want, n_want = intersect_asc(jnp.asarray(a), 80, jnp.asarray(b), 120)
    assert int(n_got) == int(n_want)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# segment_intersect (fused gap-decode + intersect over frozen segments)
# ---------------------------------------------------------------------------
from repro.kernels.segment_intersect import (SEG_BLOCK, decode_packed,
                                             pack_docids)


def _rand_asc(n, hi):
    if n == 0:
        return np.zeros(0, np.uint32)
    return np.sort(RNG.choice(hi, n, replace=False)).astype(np.uint32)


def test_pack_decode_roundtrip():
    """decode_packed inverts pack_docids; padding lanes are INVALID."""
    for n, hi in [(1, 10), (5, 100), (127, 1 << 12), (128, 1 << 12),
                  (129, 1 << 12), (1000, 1 << 28), (300, 1 << 31)]:
        ids = _rand_asc(n, hi)
        p = pack_docids(ids)
        dec = np.asarray(decode_packed(p))
        assert dec.shape[0] == p.n_blocks * SEG_BLOCK
        np.testing.assert_array_equal(dec[:n], ids)
        assert np.all(dec[n:] == 0xFFFFFFFF)


def test_pack_picks_narrow_byte_planes():
    """Dense lists pack into 1-byte gap planes (the compression claim):
    32 payload words per 128-docid block instead of 128."""
    ids = np.arange(0, 512, dtype=np.uint32)          # gaps == 1
    p = pack_docids(ids)
    assert np.asarray(p.bws).tolist() == [1, 1, 1, 1]
    sparse = pack_docids(_rand_asc(512, 1 << 31))     # huge gaps
    assert int(np.asarray(sparse.bws).max()) >= 2


@pytest.mark.parametrize("na,nb,hi", [
    (100, 100, 1000), (513, 999, 4000), (128, 128, 1 << 20),
    (1000, 50, 1 << 28), (1, 1, 4), (77, 400, 500),
])
def test_segment_intersect_mask(na, nb, hi):
    a = _rand_asc(na, hi)
    b = _rand_asc(nb, hi)
    A, B = pack_docids(a), pack_docids(b)
    got = np.asarray(ops.segment_intersect_mask(A, B, interpret=True,
                                                checked=CHECKED))
    want = np.asarray(ref.segment_intersect_mask_ref(A, B))
    np.testing.assert_array_equal(got, want)
    hits = np.asarray(decode_packed(A))[:na][got[:na].astype(bool)]
    assert set(hits.tolist()) == set(a.tolist()) & set(b.tolist())


def test_segment_intersect_mask_edges():
    full = _rand_asc(300, 2000)
    hi = (_rand_asc(100, 100) + np.uint32(100_000))
    empty = np.zeros(0, np.uint32)
    for a, b in [(empty, full), (full, empty), (full, hi), (full, full),
                 (hi, hi)]:
        A, B = pack_docids(a), pack_docids(b)
        got = np.asarray(ops.segment_intersect_mask(A, B, interpret=True,
                                                    checked=CHECKED))
        want = np.asarray(ref.segment_intersect_mask_ref(A, B))
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# batched segment_intersect: one grid step per (query, segment) row
# ---------------------------------------------------------------------------
from repro.kernels.segment_intersect import (decode_stacked,
                                             repad_stacked, stack_packed,
                                             segment_intersect_mask_batched)


def _to_jnp(s):
    return jax.tree.map(jnp.asarray, s)


def test_stack_decode_roundtrip_and_repad():
    """Stacked decode == each list's own decode, through an extra repad
    (the gather-time bucket growth): values, then INVALID padding."""
    lists = [_rand_asc(n, 1 << 20) for n in (0, 5, 127, 128, 129, 700, 1)]
    st = stack_packed([pack_docids(x) for x in lists])
    st2 = repad_stacked(st, st.n_blocks * 2, st.n_words * 2)
    for s in (st, st2):
        dec = np.asarray(decode_stacked(_to_jnp(s)))
        for g, x in enumerate(lists):
            np.testing.assert_array_equal(dec[g, : x.size], x)
            assert np.all(dec[g, x.size:] == 0xFFFFFFFF)


@pytest.mark.parametrize("rows", [
    [(100, 80), (0, 50), (513, 999), (128, 128), (1, 1)],
    [(300, 300), (50, 1000)],
])
def test_segment_intersect_mask_batched(rows):
    """Grid kernel == vmapped jnp oracle row for row, and each row's
    mask == the UNBATCHED kernel on that row's own (unpadded) lists —
    stacking/padding must not change any membership bit."""
    a_lists = [_rand_asc(na, 1 << 16) for na, _ in rows]
    b_lists = [_rand_asc(nb, 1 << 16) for _, nb in rows]
    A = stack_packed([pack_docids(x) for x in a_lists])
    B = stack_packed([pack_docids(x) for x in b_lists])
    got = np.asarray(segment_intersect_mask_batched(
        _to_jnp(A), _to_jnp(B), interpret=True))
    want = np.asarray(ref.segment_intersect_mask_batched_ref(
        _to_jnp(A), _to_jnp(B)))
    np.testing.assert_array_equal(got, want)
    for g, (a, b) in enumerate(zip(a_lists, b_lists)):
        single = np.asarray(ops.segment_intersect_mask(
            pack_docids(a), pack_docids(b), interpret=True))
        np.testing.assert_array_equal(got[g, : single.shape[0]], single)
        assert np.all(got[g, single.shape[0]:] == 0)
        exp = np.isin(a, b).astype(np.int32)
        np.testing.assert_array_equal(got[g, : a.size], exp)


def test_ops_batched_auto_routes_to_ref_on_cpu():
    a = stack_packed([pack_docids(_rand_asc(100, 1000))])
    b = stack_packed([pack_docids(_rand_asc(60, 1000))])
    got = np.asarray(ops.segment_intersect_mask_batched(
        _to_jnp(a), _to_jnp(b),    # use_kernel=None -> jnp oracle on CPU
        checked=CHECKED))
    want = np.asarray(ref.segment_intersect_mask_batched_ref(
        _to_jnp(a), _to_jnp(b)))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# scored segment intersect: the block-max WAND substrate
# ---------------------------------------------------------------------------
from repro.kernels.segment_intersect import (SCORE_MAX, pack_scored,
                                             repad_scored, stack_scored,
                                             scored_intersect_batched)


def _rand_scored(n, hi):
    ids = _rand_asc(n, hi)
    scores = RNG.integers(1, SCORE_MAX + 1, n).astype(np.int32)
    return ids, scores


@pytest.mark.parametrize("rows", [
    [(100, 80), (0, 50), (513, 999), (128, 128), (1, 1)],
    [(300, 300), (50, 1000)],
])
def test_scored_intersect_batched(rows):
    """Scored grid kernel == jnp oracle row for row, and with skipping
    disabled (th = -1) every valid a-lane carries a_score + b_score iff
    the docid is in b — the numpy ground truth."""
    a_raw = [_rand_scored(na, 1 << 16) for na, _ in rows]
    b_raw = [_rand_scored(nb, 1 << 16) for _, nb in rows]
    A = stack_scored([pack_scored(i, s) for i, s in a_raw])
    B = stack_scored([pack_scored(i, s) for i, s in b_raw])
    N = len(rows)
    rest = jnp.zeros(N, jnp.int32)
    th = jnp.full(N, -1, jnp.int32)
    got = np.asarray(ops.scored_intersect_batched(
        _to_jnp(A), _to_jnp(B), rest, th, use_kernel=True,
        interpret=True, checked=CHECKED))
    want = np.asarray(ref.scored_intersect_batched_ref(
        _to_jnp(A), _to_jnp(B), rest, th))
    np.testing.assert_array_equal(got, want)
    for g, ((ai, asc), (bi, bsc)) in enumerate(zip(a_raw, b_raw)):
        pos = np.minimum(np.searchsorted(bi, ai), max(bi.size - 1, 0))
        hit = bi[pos] == ai if bi.size else np.zeros(ai.size, bool)
        exp = np.where(hit, asc + (bsc[pos] if bi.size else 0), 0)
        np.testing.assert_array_equal(got[g, : ai.size], exp)


def test_scored_intersect_blockmax_skip_matches_oracle():
    """With a live threshold the kernel zeroes exactly the blocks whose
    bmax + rest cannot beat th — same bits as the oracle, and a direct
    numpy check that surviving blocks are exactly the qualifying ones."""
    ids = np.arange(0, 4 * SEG_BLOCK, dtype=np.uint32)
    scores = np.ones(ids.size, np.int32)
    scores[SEG_BLOCK: 2 * SEG_BLOCK] = 50      # one hot block
    A = stack_scored([pack_scored(ids, scores)])
    B = stack_scored([pack_scored(ids, np.ones(ids.size, np.int32))])
    A, B = _to_jnp(A), _to_jnp(B)
    rest = jnp.zeros(1, jnp.int32)
    for th_v in (-1, 1, 5, 50, 300):
        th = jnp.full(1, th_v, jnp.int32)
        got = np.asarray(ops.scored_intersect_batched(
            A, B, rest, th, use_kernel=True, interpret=True,
            checked=CHECKED))
        want = np.asarray(ref.scored_intersect_batched_ref(A, B, rest,
                                                           th))
        np.testing.assert_array_equal(got, want)
        bmax = np.asarray(A.bmax[0])
        for blk in range(4):
            lanes = got[0, blk * SEG_BLOCK: (blk + 1) * SEG_BLOCK]
            if bmax[blk] + 0 > th_v:           # skip bound: bmax + rest
                assert np.all(lanes == scores[blk * SEG_BLOCK] + 1)
            else:
                assert np.all(lanes == 0)


def test_scored_repad_preserves_planes():
    ids, sc = _rand_scored(300, 1 << 20)
    st = stack_scored([pack_scored(ids, sc)])
    st2 = repad_scored(st, st.ids.n_blocks * 2, st.ids.n_words * 2)
    rest = jnp.zeros(1, jnp.int32)
    th = jnp.full(1, -1, jnp.int32)
    got = np.asarray(ref.scored_intersect_batched_ref(
        _to_jnp(st), _to_jnp(st), rest, th))
    got2 = np.asarray(ref.scored_intersect_batched_ref(
        _to_jnp(st2), _to_jnp(st2), rest, th))
    np.testing.assert_array_equal(got2[:, : got.shape[1]], got)
    assert np.all(got2[:, got.shape[1]:] == 0)


def test_ops_scored_auto_routes_to_ref_on_cpu():
    ai, asc = _rand_scored(90, 1000)
    bi, bsc = _rand_scored(70, 1000)
    A = stack_scored([pack_scored(ai, asc)])
    B = stack_scored([pack_scored(bi, bsc)])
    rest = jnp.zeros(1, jnp.int32)
    th = jnp.zeros(1, jnp.int32)
    got = np.asarray(ops.scored_intersect_batched(
        _to_jnp(A), _to_jnp(B), rest, th,   # use_kernel=None -> oracle
        checked=CHECKED))
    want = np.asarray(ref.scored_intersect_batched_ref(
        _to_jnp(A), _to_jnp(B), rest, th))
    np.testing.assert_array_equal(got, want)
