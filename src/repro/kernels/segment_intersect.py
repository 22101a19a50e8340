"""Compressed frozen-segment docid lists and their intersections (the
lifecycle engine's frozen-path conjunctive hot loop).

Frozen read-only segments store each term's docids gap-compressed in
128-docid blocks (a byte-aligned PForDelta-lite: per-block byte width
1/2/4, lane-planar gap words — :func:`pack_docids`).  The paper's
query path decompresses a block and merges; the host-side numpy walk did
that one Python int at a time.  Here a whole ``[N, ...]`` stack decodes
in XLA (:func:`decode_stacked`: a static byte-plane unpack plus a
prefix-sum over the gap lanes) and the decoded rows go through the
blocked two-pointer kernel of :mod:`repro.kernels.postings_intersect`:
one 128 x 128 equality matrix per (a_block, b_block) pair, advance on
block maxima, <= n_a_blocks + n_b_blocks steps.  The frozen merge in
``repro.core.qexec`` decodes the same stacks for its fold anyway, and
the TPU compiler lowers neither the byte-plane unpack's reshapes nor a
prefix sum inside a kernel.

Inputs are :class:`PackedList`s (ascending deduped docids).  Output is an
int32 membership mask over a's decoded docid lanes (1 where lane i < n_a
and a's docid is present in b); compaction happens in the jnp caller.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.postings_intersect import intersect_rows

INVALID = 0xFFFFFFFF
SEG_BLOCK = 128          # docids per compressed block
SLAB_WORDS = SEG_BLOCK   # uint32 words read per block (bw=4 worst case)
SCORE_MAX = 255          # 8-bit quantized impact ceiling (min(tf, 255))
SCORE_WORDS = SEG_BLOCK // 4   # uint32 words per block's packed score plane


class PackedList(NamedTuple):
    """One term's docid list, block-gap-compressed and device-ready.

    ``woffs[b]`` is the start word of block b's gap plane inside
    ``payload``; the plane holds 32 * bw words (bw = bytes per gap),
    lane-planar (byte or half-word ``k`` of word ``m`` is lane
    ``k * 32 * bw + m``), and
    ``payload`` carries SLAB_WORDS trailing pad words so a fixed-size
    block read never overruns.  Lane 0's gap is stored as 0, so a block
    decodes as ``firsts[b] + cumsum(gaps)``.  The last block is padded by
    repeating the final docid (gap 0) — harmless for membership tests,
    masked out of the output by ``n``.
    """
    firsts: jax.Array   # uint32[n_blocks]  docid of lane 0
    bws: jax.Array      # int32[n_blocks]   bytes per gap: 1, 2 or 4
    woffs: jax.Array    # int32[n_blocks]   payload word offset
    payload: jax.Array  # uint32[total_words + SLAB_WORDS]
    n: int              # valid docids (static)

    @property
    def n_blocks(self) -> int:
        return self.firsts.shape[0]


def _pow2(x: int) -> int:
    return 1 << max(int(x - 1).bit_length(), 0)


def pack_docids(ids: np.ndarray) -> PackedList:
    """Gap-compress an ascending deduped uint32 docid array (host-side,
    runs once at segment freeze — off the query path).

    Block count and payload length are padded to the next power of two
    so a streaming engine sees O(log^2) distinct array-shape pairs — the
    jitted kernel call caches per shape, and unbucketed lengths would
    recompile on nearly every new term/segment.  Pad blocks decode to
    the INVALID sentinel (0xFFFFFFFF first, zero gaps), which can never
    equal a real docid and sorts above every block maximum, so the
    two-pointer walk and the membership test ignore them.
    """
    ids = np.asarray(ids, np.uint32)
    n = int(ids.size)
    if n == 0:
        return PackedList(
            firsts=jnp.zeros((0,), jnp.uint32),
            bws=jnp.zeros((0,), jnp.int32),
            woffs=jnp.zeros((0,), jnp.int32),
            payload=jnp.zeros((SLAB_WORDS,), jnp.uint32), n=0)
    nb = -(-n // SEG_BLOCK)
    nb_pad = _pow2(nb)
    firsts = np.full(nb_pad, INVALID, np.uint32)
    bws = np.ones(nb_pad, np.int32)
    woffs = np.zeros(nb_pad, np.int32)
    planes = []
    words_so_far = 0
    for b in range(nb):
        chunk = ids[b * SEG_BLOCK: (b + 1) * SEG_BLOCK].astype(np.int64)
        pad = SEG_BLOCK - chunk.size
        if pad:
            chunk = np.concatenate([chunk, np.full(pad, chunk[-1])])
        gaps = np.diff(chunk, prepend=chunk[0])          # lane 0 -> 0
        firsts[b] = chunk[0]
        g_max = int(gaps.max())
        bw = 1 if g_max < (1 << 8) else 2 if g_max < (1 << 16) else 4
        bws[b] = bw
        plane = _planar_words(gaps.astype({1: "<u1", 2: "<u2", 4: "<u4"}[bw]))
        woffs[b] = words_so_far
        words_so_far += plane.size
        planes.append(plane)
    # pad blocks read the zeroed overrun region: INVALID + cumsum(0)
    woffs[nb:] = words_so_far
    planes.append(np.zeros(
        _pow2(words_so_far + SLAB_WORDS) - words_so_far, np.uint32))
    return PackedList(firsts=jnp.asarray(firsts), bws=jnp.asarray(bws),
                      woffs=jnp.asarray(woffs),
                      payload=jnp.asarray(np.concatenate(planes)), n=n)


class StackedLists(NamedTuple):
    """A batch of :class:`PackedList`s padded to SHARED pow2 shapes and
    stacked on leading axes — the device-resident frozen-segment stack
    (``repro.core.qexec``).

    Leaves carry arbitrary leading dims (``[..., NB]`` block tables,
    ``[..., PW]`` payloads, ``[...]`` counts), so one container covers a
    per-term ``[G, ...]`` segment stack, a gathered ``[Q, T, G, ...]``
    query batch, and the ``[N, ...]`` flattening the batched kernel
    grids over.  Pad blocks decode to the INVALID sentinel (firsts =
    INVALID, gap plane all zero), so set ops ignore them exactly like
    :func:`pack_docids`'s own pad blocks.
    """
    firsts: jax.Array   # uint32[..., NB]
    bws: jax.Array      # int32[..., NB]
    woffs: jax.Array    # int32[..., NB]
    payload: jax.Array  # uint32[..., PW]
    ns: jax.Array       # int32[...] valid docids per list

    @property
    def n_blocks(self) -> int:
        return self.firsts.shape[-1]

    @property
    def n_words(self) -> int:
        return self.payload.shape[-1]


def stack_packed(packs, n_blocks: int = None,
                 n_words: int = None) -> StackedLists:
    """Stack PackedLists into one :class:`StackedLists` (host-side numpy,
    runs at rollover / gather time — off the jitted query path).

    ``n_blocks``/``n_words`` override the shared padded shape (they must
    be >= every input's); by default the next power of two over the
    batch, so a streaming engine sees O(log^2) distinct stack shapes.
    Every pad block's ``woff`` points at the guaranteed-zero tail of its
    own row (``len(payload) - SLAB_WORDS`` — pack_docids always leaves
    >= SLAB_WORDS trailing zeros), so pad blocks decode to INVALID and
    never alias real gap data.
    """
    G = len(packs)
    nb = max([p.n_blocks for p in packs] + [1])
    pw = max([p.payload.shape[0] for p in packs] + [SLAB_WORDS])
    nb = _pow2(nb) if n_blocks is None else n_blocks
    pw = _pow2(pw) if n_words is None else n_words
    firsts = np.full((G, nb), INVALID, np.uint32)
    bws = np.ones((G, nb), np.int32)
    woffs = np.zeros((G, nb), np.int32)
    payload = np.zeros((G, pw), np.uint32)
    ns = np.zeros((G,), np.int32)
    for g, p in enumerate(packs):
        k = p.n_blocks
        pay = np.asarray(p.payload)
        payload[g, : pay.shape[0]] = pay
        woffs[g, :] = pay.shape[0] - SLAB_WORDS
        if k:
            firsts[g, :k] = np.asarray(p.firsts)
            bws[g, :k] = np.asarray(p.bws)
            woffs[g, :k] = np.asarray(p.woffs)
        ns[g] = p.n
    return StackedLists(firsts=firsts, bws=bws, woffs=woffs,
                        payload=payload, ns=ns)


def repad_stacked(s: StackedLists, n_blocks: int,
                  n_words: int) -> StackedLists:
    """Grow a (numpy-leaved) stack to a wider shared bucket.  New pad
    blocks reuse each row's existing zero-tail woff; new payload words
    are zeros, so decode semantics are unchanged."""
    nb0, pw0 = s.n_blocks, s.n_words
    if nb0 == n_blocks and pw0 == n_words:
        return s
    assert nb0 <= n_blocks and pw0 <= n_words, (nb0, n_blocks, pw0, n_words)
    lead = s.firsts.shape[:-1]
    pad_b = [(0, 0)] * len(lead) + [(0, n_blocks - nb0)]
    pad_w = [(0, 0)] * len(lead) + [(0, n_words - pw0)]
    zero_woff = s.payload.shape[-1] - SLAB_WORDS  # per-row zero tail
    woffs = np.concatenate(
        [s.woffs, np.broadcast_to(
            np.asarray(zero_woff, np.int32),
            lead + (n_blocks - nb0,)).copy()]
        , axis=-1) if n_blocks > nb0 else s.woffs
    return StackedLists(
        firsts=np.pad(s.firsts, pad_b, constant_values=INVALID),
        bws=np.pad(s.bws, pad_b, constant_values=1),
        woffs=woffs,
        payload=np.pad(s.payload, pad_w),
        ns=s.ns)


def decode_stacked(s: StackedLists) -> jax.Array:
    """Batched all-blocks decode: uint32[..., NB * SEG_BLOCK] ascending
    docids, INVALID-padded past each list's ``ns``.  Pure jnp over
    arbitrary leading dims — the vmap-able substrate for the batched
    query path (and the batched kernel's oracle)."""
    lead = s.firsts.shape[:-1]
    nb = s.n_blocks
    idx = s.woffs[..., None] + jnp.arange(SLAB_WORDS, dtype=jnp.int32)
    slabs = jnp.take_along_axis(s.payload[..., None, :], idx, axis=-1)
    gaps = _unpack_gaps(slabs, s.bws)
    ids = s.firsts[..., None] + jnp.cumsum(gaps, axis=-1, dtype=jnp.uint32)
    flat = ids.reshape(lead + (nb * SEG_BLOCK,))
    lane = jnp.arange(nb * SEG_BLOCK, dtype=jnp.int32)
    return jnp.where(lane < jnp.asarray(s.ns)[..., None], flat,
                     jnp.uint32(INVALID))


def _planar_words(lanes: np.ndarray) -> np.ndarray:
    """Pack the narrow lanes of 128-lane blocks (``[..., 128]`` uint8 or
    uint16) into lane-planar uint32 words: sub-word ``k`` of word ``m``
    holds lane ``k * n_words + m``.  Host-side, at freeze time."""
    per = 4 // lanes.itemsize
    lead = lanes.shape[:-1]
    n_words = SEG_BLOCK // per
    planes = lanes.reshape(lead + (per, n_words)).swapaxes(-1, -2)
    return np.ascontiguousarray(planes).view("<u4").reshape(lead + (n_words,))


def _unplane(words, bits: int):
    """Inverse of :func:`_planar_words` on the device: ``[..., n]`` words
    -> ``[..., n * 32 // bits]`` lanes.  Each sub-word plane is a shift
    and a mask of the whole word row, and the planes concatenate along
    the lane axis, so no array ever has a narrow minor dimension (an
    interleaved layout needs a ``[..., n, 4]`` relayout, which the TPU
    compiler took minutes over at deployment widths)."""
    mask = jnp.uint32((1 << bits) - 1)
    return jnp.concatenate(
        [(words >> jnp.uint32(bits * k)) & mask for k in range(32 // bits)],
        axis=-1)


def _unpack_gaps(slab, bw):
    """Decode one block's gap lanes from its (up to) 128-word slab.

    Static byte-plane unpack — every width reads fixed planes of the
    slab, selected with ``where`` — so the VPU never gathers.
    ``slab``: uint32[..., SLAB_WORDS]; ``bw``: int32[...] broadcastable.
    """
    b1 = _unplane(slab[..., : SEG_BLOCK // 4], 8)
    b2 = _unplane(slab[..., : SEG_BLOCK // 2], 16)
    bw = jnp.asarray(bw)[..., None]
    return jnp.where(bw == 1, b1, jnp.where(bw == 2, b2, slab))


def decode_packed(packed: PackedList) -> jax.Array:
    """All-blocks jnp decode: ascending uint32[n_blocks * SEG_BLOCK],
    INVALID-padded past ``n`` (the query-engine list representation).
    This is the kernel's oracle and the cross-segment merge's fallback
    when no kernel is wanted (e.g. >2-term folds on already-compacted
    lists)."""
    if packed.n_blocks == 0:
        return jnp.zeros((0,), jnp.uint32)
    idx = packed.woffs[:, None] + jnp.arange(SLAB_WORDS, dtype=jnp.int32)
    slabs = packed.payload[idx]                      # [nb, SLAB_WORDS]
    gaps = _unpack_gaps(slabs, packed.bws)
    ids = packed.firsts[:, None] + jnp.cumsum(gaps, axis=-1,
                                              dtype=jnp.uint32)
    flat = ids.reshape(-1)
    lane = jnp.arange(flat.shape[0], dtype=jnp.int32)
    return jnp.where(lane < packed.n, flat, jnp.uint32(INVALID))


def segment_intersect_mask(a: PackedList, b: PackedList, *,
                           interpret: bool = True) -> jax.Array:
    """Membership mask of a's docids in b, both block-gap-compressed.

    Returns int32[a.n_blocks * SEG_BLOCK] (1 where lane < a.n and a's
    docid occurs in b): both lists are decoded with the jnp decoder and
    walked by the blocked two-pointer kernel
    (:func:`repro.kernels.postings_intersect.intersect_rows`).
    """
    if a.n_blocks == 0:
        return jnp.zeros((0,), jnp.int32)
    if b.n_blocks == 0:
        return jnp.zeros((a.n_blocks * SEG_BLOCK,), jnp.int32)
    return intersect_rows(decode_packed(a)[None], decode_packed(b)[None],
                          interpret=interpret)[0]


def segment_intersect_mask_batched(a: StackedLists, b: StackedLists, *,
                                   interpret: bool = True) -> jax.Array:
    """Row-wise membership masks of a's docids in b over a stacked batch.

    ``a``/``b`` leaves must carry ONE leading axis ``[N, ...]`` (flatten a
    ``[Q, G]`` query x segment batch first); returns
    int32[N, a.n_blocks * SEG_BLOCK].  The stacks decode in XLA (the same
    :func:`decode_stacked` the frozen merge already runs) and one
    pallas_call walks every (query, segment) row — the frozen-path
    conjunction of a whole query batch in a single kernel dispatch.
    """
    assert a.firsts.ndim == 2 and b.firsts.ndim == 2, \
        "stack leaves must be [N, ...]; reshape the (Q, G) batch first"
    if a.n_blocks == 0 or a.firsts.shape[0] == 0:
        return jnp.zeros((a.firsts.shape[0], a.n_blocks * SEG_BLOCK),
                         jnp.int32)
    return intersect_rows(decode_stacked(a), decode_stacked(b),
                          interpret=interpret)


# ---------------------------------------------------------------------------
# Scored lists: per-posting quantized impacts + per-block max-score planes
# ---------------------------------------------------------------------------
class ScoredList(NamedTuple):
    """A :class:`PackedList` plus its quantized impact plane.

    ``swords`` packs one uint8 impact per docid lane, four lanes per
    uint32 word, 32 lane-planar words per 128-docid block (byte ``k`` of
    the block's word ``m`` is lane ``32 k + m``) — decoding to the same
    lane order as the docids, so lane i of ``decode_packed(ids)``
    scores ``decode_scores(swords)[i]``.  Valid lanes carry impacts in
    [1, SCORE_MAX]; pad lanes (including the repeated-last-docid tail of
    the final real block) and pad blocks are zero, so 0 doubles as the
    no-hit sentinel in the scored kernel.  ``bmax[b]`` is the max impact
    in block b (0 for pad blocks) — the block-max WAND skip bound — and
    ``smax`` is the static list-wide max (0 for an empty list), the
    per-(term, segment) summary the segment-level skip uses.
    """
    ids: PackedList
    swords: jax.Array   # uint32[n_blocks * SCORE_WORDS]
    bmax: jax.Array     # int32[n_blocks]
    smax: int           # static list-wide max impact


def attach_scores(ids: PackedList, scores: np.ndarray) -> ScoredList:
    """Attach an impact plane to an already-packed docid list (host-side,
    runs at freeze time).  ``scores[i]`` belongs to the i-th valid docid
    lane and must sit in [1, SCORE_MAX] — 0 is reserved for pad lanes."""
    scores = np.asarray(scores)
    if scores.shape != (ids.n,):
        raise ValueError(f"scores shape {scores.shape} != ({ids.n},)")
    if ids.n and (scores.min() < 1 or scores.max() > SCORE_MAX):
        raise ValueError("impact scores must be in [1, SCORE_MAX]")
    nb = ids.n_blocks
    lanes = np.zeros((nb, SEG_BLOCK), np.uint8)
    lanes.reshape(-1)[: ids.n] = scores
    swords = _planar_words(lanes).reshape(-1)
    bmax = (lanes.max(axis=1).astype(np.int32)
            if nb else np.zeros(0, np.int32))
    smax = int(scores.max()) if ids.n else 0
    return ScoredList(ids=ids, swords=jnp.asarray(swords),
                      bmax=jnp.asarray(bmax), smax=smax)


def pack_scored(ids: np.ndarray, scores: np.ndarray) -> ScoredList:
    """Gap-compress ascending deduped docids and attach their impacts."""
    return attach_scores(pack_docids(ids), scores)


class ScoredStack(NamedTuple):
    """A batch of :class:`ScoredList`s on shared pow2 shapes — the scored
    counterpart of :class:`StackedLists` (a nested NamedTuple is a plain
    pytree, so it vmaps/gathers exactly like the unscored stack).  Pad
    rows/blocks carry all-zero score planes and zero ``bmax``."""
    ids: StackedLists
    swords: jax.Array   # uint32[..., NB * SCORE_WORDS]
    bmax: jax.Array     # int32[..., NB]


def stack_scored(scoreds, n_blocks: int = None,
                 n_words: int = None) -> ScoredStack:
    """Stack ScoredLists into one :class:`ScoredStack` (host-side numpy,
    off the jitted query path) — see :func:`stack_packed`."""
    ids = stack_packed([s.ids for s in scoreds], n_blocks, n_words)
    G, nb = len(scoreds), ids.n_blocks
    swords = np.zeros((G, nb * SCORE_WORDS), np.uint32)
    bmax = np.zeros((G, nb), np.int32)
    for g, s in enumerate(scoreds):
        k = s.ids.n_blocks
        if k:
            swords[g, : k * SCORE_WORDS] = np.asarray(s.swords)
            bmax[g, :k] = np.asarray(s.bmax)
    return ScoredStack(ids=ids, swords=swords, bmax=bmax)


def repad_scored(s: ScoredStack, n_blocks: int,
                 n_words: int) -> ScoredStack:
    """Grow a (numpy-leaved) scored stack to a wider shared bucket; new
    pad blocks get zero score planes, preserving decode semantics."""
    ids = repad_stacked(s.ids, n_blocks, n_words)
    nb0 = s.ids.n_blocks
    if nb0 == n_blocks:
        return ScoredStack(ids=ids, swords=s.swords, bmax=s.bmax)
    lead = s.bmax.shape[:-1]
    pad_w = [(0, 0)] * len(lead) + [(0, (n_blocks - nb0) * SCORE_WORDS)]
    pad_b = [(0, 0)] * len(lead) + [(0, n_blocks - nb0)]
    return ScoredStack(ids=ids, swords=np.pad(s.swords, pad_w),
                       bmax=np.pad(s.bmax, pad_b))


def decode_scores(swords: jax.Array) -> jax.Array:
    """Unpack uint8 impact lanes from uint32 score words: int32[..., 4*W]
    over arbitrary leading dims.  Same static byte-plane unpack as the
    gap decoder, fixed at one byte per lane."""
    lead = swords.shape[:-1]
    w = swords.shape[-1]
    blocks = swords.reshape(lead + (w // SCORE_WORDS, SCORE_WORDS))
    return _unplane(blocks, 8).reshape(lead + (w * 4,)).astype(jnp.int32)


def scored_intersect_batched(a: ScoredStack, b: ScoredStack, rest, th, *,
                             interpret: bool = True) -> jax.Array:
    """Row-wise scored conjunction of a's docids with b over a stacked
    batch: int32[N, a.n_blocks * SEG_BLOCK] where lane i holds
    ``a_impact + b_impact`` if a's docid i also occurs in b AND its
    block's WAND bound ``a.bmax + rest`` beats ``th``, else 0.

    ``rest``/``th`` are int32[N]: the summed max impacts of the other
    live query terms in this segment, and the current top-k heap
    threshold (-1 disables skipping — every bound is > -1).  The kernel
    returns b's impact on each matched a-lane; the impact sum and the
    block-max mask are elementwise jnp on its output.
    """
    assert a.ids.firsts.ndim == 2 and b.ids.firsts.ndim == 2, \
        "stack leaves must be [N, ...]; reshape the (Q, G) batch first"
    if a.ids.n_blocks == 0 or a.ids.firsts.shape[0] == 0:
        return jnp.zeros((a.ids.firsts.shape[0],
                          a.ids.n_blocks * SEG_BLOCK), jnp.int32)
    matched = intersect_rows(decode_stacked(a.ids), decode_stacked(b.ids),
                             decode_scores(b.swords), interpret=interpret)
    keep = (jnp.asarray(a.bmax, jnp.int32)
            + jnp.asarray(rest, jnp.int32)[:, None]
            > jnp.asarray(th, jnp.int32)[:, None])
    keep = jnp.repeat(keep, SEG_BLOCK, axis=1)
    return jnp.where(keep & (matched > 0),
                     decode_scores(a.swords) + matched, 0)
