"""Compile-only checks for a TPU v5e: the Pallas kernels of the served
index path, and the jitted bulk-ingest step, at the sizes ``chip_smoke.py``
runs.  Nothing executes: XLA's TPU compiler builds each program for a
described ``v5e:2x2`` topology, which refuses what interpret mode accepts
(unaligned slices, unsupported gathers, programs larger than the chip).

The topology is described inside a fixture, never while the module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""
import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import qexec, slicepool
from repro.kernels import bulk_append as ba
from repro.kernels import postings_intersect as pi
from repro.kernels import segment_intersect as si

HBM_BYTES = 16 * 10**9          # one TPU v5e chip
N_ROWS = 8                      # (query, segment) rows per batched call
NB = 1 << 16                    # blocks per frozen list: 8M docids


def _load_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure: no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The described chip, with the persistent compile cache off: a
    compile for a described device is written but can never be read."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def smoke_layout():
    smoke = _load_smoke()
    layout, max_len, _ = smoke.segment_layout(
        smoke.SEGMENT_DOCS, shards=1, vocab=smoke.VOCAB, seed=0)
    return smoke, layout, max_len


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _stack(sds, n, nb):
    return si.StackedLists(sds((n, nb), jnp.uint32), sds((n, nb), jnp.int32),
                           sds((n, nb), jnp.int32),
                           sds((n, nb * si.SLAB_WORDS), jnp.uint32),
                           sds((n,), jnp.int32))


def _sds(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def test_bulk_append_compiles(one_chip, smoke_layout):
    smoke, layout, _ = smoke_layout
    sds = _sds(one_chip)
    n = smoke.BATCH_DOCS * smoke.DOC_WIDTH
    H = slicepool.row_words(layout.total_slots)
    V = smoke.VOCAB
    u, i = jnp.uint32, jnp.int32
    _compile(lambda *a: ba.bulk_append(*a, interpret=False),
             sds((H,), u), sds((V,), u), sds((V,), i),
             sds((n,), i), sds((n,), u), sds((n,), i), sds((n,), u),
             sds((n,), i), sds((n,), u), sds((n,), i))


@pytest.mark.parametrize("width", [1 << 10, 1 << 23])
def test_intersect_mask_compiles(one_chip, width):
    sds = _sds(one_chip)
    _compile(lambda a, b: pi.intersect_mask(a, b, interpret=False),
             sds((width,), jnp.uint32), sds((width,), jnp.uint32))


def test_intersect_mask_compiles_under_vmap(one_chip):
    """The sharded and sequential engines call the kernel per query
    under vmap: the batch must fold into the kernel's row grid."""
    sds = _sds(one_chip)
    f = jax.vmap(jax.vmap(lambda a, b: pi.intersect_mask(
        a, b, interpret=False)))
    _compile(f, sds((4, 2, 1 << 16), jnp.uint32),
             sds((4, 2, 1 << 16), jnp.uint32))


def test_segment_intersect_batched_compiles(one_chip):
    sds = _sds(one_chip)
    _compile(lambda a, b: si.segment_intersect_mask_batched(
        a, b, interpret=False), _stack(sds, N_ROWS, NB),
        _stack(sds, N_ROWS, NB))


def test_scored_intersect_batched_compiles(one_chip):
    sds = _sds(one_chip)

    def scored(n):
        return si.ScoredStack(_stack(sds, n, NB),
                              sds((n, NB * si.SCORE_WORDS), jnp.uint32),
                              sds((n, NB), jnp.int32))

    _compile(lambda a, b, r, t: si.scored_intersect_batched(
        a, b, r, t, interpret=False), scored(N_ROWS), scored(N_ROWS),
        sds((N_ROWS,), jnp.int32), sds((N_ROWS,), jnp.int32))


def test_replicated_frozen_merge_compiles_on_four_chips(one_chip, topo):
    """On a mesh the frozen merge is one program replicated over every
    chip, and the TPU compiler cannot partition a Mosaic kernel, so the
    sharded engine runs it without the batched kernel: that program
    must compile for four chips."""
    mesh = Mesh(np.array(topo.devices[:4]), ("docs",))
    sds = _sds(NamedSharding(mesh, PartitionSpec()))
    Q, T, nb = 4, 4, 64
    lists = si.StackedLists(*[sds((Q, T, 1) + x.shape[1:], x.dtype)
                              for x in _stack(sds, 1, nb)[:-1]],
                            sds((Q, T, 1), jnp.int32))
    compiled = qexec.frozen_merge.lower(
        sds((Q, 1024), jnp.uint32), sds((Q,), jnp.int32), lists,
        sds((Q,), jnp.int32), sds((), jnp.uint32), kind="conjunctive",
        nt_slots=T, kernel=False, interpret=False).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def _compile_ingest(sds, layout, vocab, n):
    state = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: slicepool.init_state(layout, vocab)))
    fn = slicepool.make_bulk_ingest_fn(layout, vocab, use_kernel=True,
                                       interpret=False)
    compiled = fn.lower(state, sds((n,), jnp.uint32), sds((n,), jnp.uint32),
                        None, sds((n,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return state, compiled


def _assert_in_place(state, compiled):
    """heap/tail/freq are donated and aliased to the outputs, and the
    kernel writes the heap where it lies: no op of the program pads the
    heap to whole rows or slices it back (a pass over all of it)."""
    m = compiled.memory_analysis()
    targets = sum(x.size * x.dtype.itemsize
                  for x in (state.heap, state.tail, state.freq))
    assert m.alias_size_in_bytes >= targets, m
    H = state.heap.shape[0]
    for n in {H, -(-H // ba.ROW) * ba.ROW}:
        assert not re.search(rf"= u32\[{n}\]\S* (pad|slice)\(",
                             compiled.as_text()), n


def test_bulk_ingest_step_fits_one_chip(one_chip, smoke_layout):
    """The whole jitted ingest step at the smoke's batch and production
    layout: the Pallas scatter is in it, and the program fits in HBM."""
    smoke, layout, _ = smoke_layout
    state, compiled = _compile_ingest(_sds(one_chip), layout, smoke.VOCAB,
                                      smoke.BATCH_DOCS * smoke.DOC_WIDTH)
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, m
    _assert_in_place(state, compiled)


def test_bulk_ingest_step_in_place_for_any_layout(one_chip):
    """Pool and vocabulary sizes that are not whole 128-word rows: the
    heap is allocated in whole rows, so the step still updates it in
    place."""
    from repro.core import pointers
    layout = pointers.production_layout((100_001, 10_001, 1_001, 1_001))
    vocab = 50_001
    assert layout.total_slots % ba.ROW and vocab % ba.ROW
    _assert_in_place(*_compile_ingest(_sds(one_chip), layout, vocab, 4096))
