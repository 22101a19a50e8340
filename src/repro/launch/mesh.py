"""Production mesh factories.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run entry point
(``repro.launch.dryrun``) sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import; everything else (tests, benches) sees 1 device.

Axis roles (DESIGN.md §5):
  pod    outer data-parallel dim, gradient all-reduce crosses DCN
  data   inner data-parallel / FSDP dim (ICI)
  model  tensor/expert/kv-seq parallel dim (ICI)
"""
from __future__ import annotations

from repro.dist.collectives import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/elastic restore (divisor meshes)."""
    return auto_mesh(shape, axes)


def dp_extent(mesh) -> int:
    """Total data-parallel ways (pod x data when pod exists)."""
    e = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        e *= mesh.shape["pod"]
    return e


def batch_axes_for(mesh, global_batch: int):
    """Largest data-parallel axis tuple that evenly divides the batch.

    Keeps cells like ``long_500k`` (batch=1) lowerable: a size-1 batch dim
    cannot be sharded 32 ways, so it degrades to replication and the work
    lives on the 'model' axis instead (kv_seq sharding).
    """
    has_pod = "pod" in mesh.axis_names
    if has_pod and global_batch % (mesh.shape["pod"] * mesh.shape["data"]) == 0:
        return ("pod", "data")
    if global_batch % mesh.shape["data"] == 0:
        return ("data",)
    if has_pod and global_batch % mesh.shape["pod"] == 0:
        return ("pod",)
    return None
