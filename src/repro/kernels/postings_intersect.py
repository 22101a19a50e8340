"""Pallas TPU blocked sorted-set intersection (the paper's conjunctive-
query hot path, §3.1/§8).

TPU adaptation of the paper's linear merge: instead of pointer-at-a-time
compares, both lists stream through VMEM in 128-docid blocks and each
(a_block, b_block) pair is tested with ONE vectorised 128 x 128 equality
matrix on the VPU; block advance follows the classic two-pointer rule on
block maxima.  Total steps <= n_a_blocks + n_b_blocks.

Layout.  Lists live in HBM as ``[N, n_blocks, 128]`` rows (a batch of N
independent (a, b) pairs, one grid step per pair).  A DMA moves whole
128-lane rows: the TPU compiler refuses HBM slices of a 1-D array that
are not multiples of its 1024-element tiling, but a row slice of a
``[..., 128]`` array is always legal.  Rows travel ``ta // 128`` (or
``tb // 128``) at a time into VMEM chunks; the walk indexes one row of a
chunk per step.

Inputs are ASCENDING uint32 rows padded with INVALID (0xFFFFFFFF) — the
query-engine representation, and what ``decode_stacked`` yields for a
frozen stack.  The kernel's output over a's lanes is either a membership
mask (int32 1/0) or, given b's per-lane impacts, the impact of the
matching b lane (0 for no match) — the scored conjunction's substrate.
Compaction and score sums happen in the jnp callers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.kernels.compat import pl, pltpu

INVALID = 0xFFFFFFFF
LANES = 128            # docids per block: one vector row
CHUNK = 1024           # default elements per DMA (8 rows of 128)


def _kernel(a_hbm, b_hbm, *refs, ra: int, rb: int, na_blocks: int,
            nb_blocks: int, scored: bool):
    if scored:
        (bs_hbm, o_hbm, a_buf, b_buf, bs_buf, m_buf,
         sem_a, sem_b, sem_s, sem_o) = refs
    else:
        o_hbm, a_buf, b_buf, m_buf, sem_a, sem_b, sem_o = refs
    r = pl.program_id(0)

    def load_a(c):
        cp = pltpu.make_async_copy(a_hbm.at[r, pl.ds(c * ra, ra)], a_buf,
                                   sem_a)
        cp.start()
        cp.wait()

    def load_b(c):
        cps = [pltpu.make_async_copy(b_hbm.at[r, pl.ds(c * rb, rb)], b_buf,
                                     sem_b)]
        if scored:
            cps.append(pltpu.make_async_copy(
                bs_hbm.at[r, pl.ds(c * rb, rb)], bs_buf, sem_s))
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    def flush(c):
        cp = pltpu.make_async_copy(m_buf, o_hbm.at[r, pl.ds(c * ra, ra)],
                                   sem_o)
        cp.start()
        cp.wait()

    load_a(0)
    load_b(0)
    m_buf[...] = jnp.zeros(m_buf.shape, jnp.int32)

    def step(_, carry):
        ia, ib = carry
        live = ia < na_blocks
        row_a = ia % ra
        row_b = ib % rb
        a = a_buf[pl.ds(row_a, 1), :]                     # [1, 128]
        b = b_buf[pl.ds(row_b, 1), :]
        eq = jnp.transpose(b) == a          # [128, 128]: b[j] == a[i]
        if scored:
            bs = jnp.transpose(bs_buf[pl.ds(row_b, 1), :])   # [128, 1]
            val = jnp.max(jnp.where(eq, bs, 0), axis=0, keepdims=True)
        else:
            val = jnp.max(eq.astype(jnp.int32), axis=0, keepdims=True)
        val = jnp.where(a != jnp.uint32(INVALID), val, 0)

        @pl.when(live)
        def _():
            cur = m_buf[pl.ds(row_a, 1), :]
            m_buf[pl.ds(row_a, 1), :] = jnp.maximum(cur, val)

        a_max = a[0, LANES - 1]
        b_max = b[0, LANES - 1]
        b_done = ib >= nb_blocks - 1
        adv_a = live & ((a_max <= b_max) | b_done)
        adv_b = live & ((b_max <= a_max) & ~b_done)
        ia2 = ia + adv_a.astype(jnp.int32)
        ib2 = ib + adv_b.astype(jnp.int32)
        a_wrap = adv_a & (ia2 % ra == 0)

        @pl.when(a_wrap)
        def _():
            flush(ia // ra)
            m_buf[...] = jnp.zeros(m_buf.shape, jnp.int32)

        @pl.when(a_wrap & (ia2 < na_blocks))
        def _():
            load_a(ia2 // ra)

        @pl.when(adv_b & (ib2 % rb == 0))
        def _():
            load_b(ib2 // rb)

        return ia2, ib2

    jax.lax.fori_loop(0, na_blocks + nb_blocks, step,
                      (jnp.int32(0), jnp.int32(0)))


def _pad_rows(x, width, fill):
    """Pad ``[N, W]`` to ``[N, width]`` and view it as 128-lane rows."""
    n, w = x.shape
    if w < width:
        x = jnp.concatenate(
            [x, jnp.full((n, width - w), fill, x.dtype)], axis=1)
    return x.reshape(n, width // LANES, LANES)


def _chunk_rows(n_elems: int, chunk: int) -> tuple:
    """(rows per DMA chunk, padded row count) for an ``n_elems`` list."""
    assert chunk % LANES == 0 and chunk > 0, chunk
    blocks = max(-(-n_elems // LANES), 1)
    rows = min(chunk // LANES, blocks)
    return rows, -(-blocks // rows) * rows


def _call(a, b, b_scores=None, *, ta: int, tb: int, interpret: bool):
    """One pallas_call over the ``N`` rows of 2-D ``a``/``b``."""
    N, wa = a.shape
    wb = b.shape[1]
    if N == 0 or wa == 0:
        return jnp.zeros((N, wa), jnp.int32)
    ra, nra = _chunk_rows(wa, ta)
    rb, nrb = _chunk_rows(wb, tb)
    a3 = _pad_rows(a.astype(jnp.uint32), nra * LANES, INVALID)
    b3 = _pad_rows(b.astype(jnp.uint32), nrb * LANES, INVALID)
    scored = b_scores is not None
    args = [a3, b3]
    scratch = [pltpu.VMEM((ra, LANES), jnp.uint32),
               pltpu.VMEM((rb, LANES), jnp.uint32)]
    if scored:
        args.append(_pad_rows(b_scores.astype(jnp.int32), nrb * LANES, 0))
        scratch.append(pltpu.VMEM((rb, LANES), jnp.int32))
    scratch += [pltpu.VMEM((ra, LANES), jnp.int32)]
    scratch += [pltpu.SemaphoreType.DMA] * (4 if scored else 3)
    out = pl.pallas_call(
        functools.partial(_kernel, ra=ra, rb=rb, na_blocks=nra,
                          nb_blocks=nrb, scored=scored),
        grid=(N,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)] * len(args),
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        out_shape=jax.ShapeDtypeStruct((N, nra, LANES), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*args)
    return out.reshape(N, nra * LANES)[:, :wa]


@functools.lru_cache(maxsize=None)
def _batchable(ta: int, tb: int, interpret: bool):
    """:func:`_call` with a vmap rule that folds the mapped axis into the
    row axis.  The TPU lowering takes no extra batch dimension on a
    ``pl.ANY`` operand, and the query engines call the kernel under
    ``vmap`` (over queries, and per shard)."""
    @jax.custom_batching.custom_vmap
    def rows(*args):
        return _call(*args, ta=ta, tb=tb, interpret=interpret)

    @rows.def_vmap
    def _(axis_size, in_batched, *args):
        args = [x if bat else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, bat in zip(args, in_batched)]
        out = rows(*[x.reshape((-1,) + x.shape[2:]) for x in args])
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return rows


@functools.partial(jax.jit, static_argnames=("ta", "tb", "interpret"))
def intersect_rows(a, b, b_scores=None, *, ta: int = CHUNK,
                   tb: int = CHUNK, interpret: bool = True):
    """Row-wise two-pointer intersection of ascending INVALID-padded
    ``a[N, Wa]`` against ``b[N, Wb]`` (uint32).

    Returns int32[N, Wa]: without ``b_scores`` a membership mask (1 where
    a's valid docid occurs in b's row); with ``b_scores`` (int32[N, Wb],
    positive on b's valid lanes) the matched lane's score, 0 for no
    match.  ``ta``/``tb`` are the elements per DMA chunk (multiples of
    128); rows are padded with INVALID to whole chunks.  Under ``vmap``
    the mapped axis joins the rows of the same single kernel call.
    """
    args = (a, b) if b_scores is None else (a, b, b_scores)
    return _batchable(ta, tb, interpret)(*args)


def intersect_mask(a, b, *, ta: int = CHUNK, tb: int = CHUNK,
                   interpret: bool = True):
    """Membership mask of ascending INVALID-padded ``a`` in ``b`` (1-D)."""
    return intersect_rows(a[None], b[None], ta=ta, tb=tb,
                          interpret=interpret)[0]
