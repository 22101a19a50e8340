"""Pallas TPU fused embedding-bag kernel (gather + segment-sum).

The recsys hot path: bag b sums table rows ``indices[offsets[b]:
offsets[b+1]]``.  One program per bag block; rows stream HBM->VMEM with
async copies (the huge-table case — the table never fits VMEM).  Row DMAs
for a bag are issued back-to-back and accumulated in fp32 VMEM scratch.

  table    [R, D]        (ANY / HBM-resident)
  indices  int32[N]      (scalar-prefetch)
  offsets  int32[B+1]    (scalar-prefetch, CSR bag boundaries)
  out      [B, D] fp32
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.kernels.compat import pl, pltpu


def _kernel(offsets_ref, idx_ref, table_hbm, o_ref, row_buf, sem,
            *, mode: str):
    b = pl.program_id(0)
    lo = offsets_ref[b]
    hi = offsets_ref[b + 1]
    D = o_ref.shape[1]
    nbuf = row_buf.shape[0]

    def start(j, slot):
        pltpu.make_async_copy(
            table_hbm.at[pl.ds(idx_ref[j], 1), :], row_buf.at[slot],
            sem.at[slot]).start()

    def wait(slot):
        pltpu.make_async_copy(
            table_hbm.at[pl.ds(0, 1), :], row_buf.at[slot],
            sem.at[slot]).wait()

    @pl.when(hi > lo)
    def _():
        start(lo, 0)

    def body(j, acc):
        slot = jax.lax.rem(j - lo, nbuf)

        @pl.when(j + 1 < hi)
        def _():
            start(j + 1, jax.lax.rem(j + 1 - lo, nbuf))

        wait(slot)
        return acc + row_buf[slot].astype(jnp.float32)

    acc = jax.lax.fori_loop(lo, hi, body, jnp.zeros((1, D), jnp.float32))
    if mode == "mean":
        acc = acc / jnp.maximum(hi - lo, 1).astype(jnp.float32)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def embedding_bag(table, indices, offsets, *, mode: str = "sum",
                  interpret: bool = True):
    B = offsets.shape[0] - 1
    D = table.shape[1]
    if indices.shape[0] == 0:  # all-empty bags: keep prefetch non-empty
        indices = jnp.zeros((1,), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec((1, D), lambda b, *_: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 1, D), table.dtype),   # double-buffered rows
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, mode=mode),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )(offsets, indices, table)
