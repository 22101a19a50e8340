"""Pallas TPU API surface, pinned in one place.

Policy (see ROADMAP.md): kernels NEVER import ``jax.experimental.pallas.
tpu`` directly — they import the ``pltpu`` proxy below, which pins the
spelling of every name they use to the installed JAX (0.9).  When a JAX
upgrade renames Pallas API, fix this file only.

Pinned symbols:
  MemorySpace                    the TPU memory-space enum
  ANY                            ``pl.ANY``: the array stays where XLA put
                                 it (HBM); kernels DMA from it by hand
  VMEM / SMEM                    scratch-shape constructors
  PrefetchScalarGridSpec         scalar-prefetch grid spec
  SemaphoreType                  DMA semaphores
  make_async_copy                local DMA
"""
from __future__ import annotations

from jax.experimental import pallas as pl  # noqa: F401  (re-export)
from jax.experimental.pallas import tpu as _tpu


class _PltpuCompat:
    """``pltpu`` stand-in: exactly the names the kernels use."""

    MemorySpace = _tpu.MemorySpace
    ANY = pl.ANY
    VMEM = _tpu.VMEM
    SMEM = _tpu.SMEM
    PrefetchScalarGridSpec = _tpu.PrefetchScalarGridSpec
    SemaphoreType = _tpu.SemaphoreType
    make_async_copy = staticmethod(_tpu.make_async_copy)


pltpu = _PltpuCompat()
