"""Mesh-aware collectives over LOGICAL axis names + the CPU CI fallback.

These helpers are the manual-collective counterpart of
``sharding.constrain``: inside a ``shard_map``/``pmap`` region they issue
``lax`` collectives over whatever mesh axes the active :class:`Rules`
table assigns to a logical name, and degrade to exact no-ops when the
name is unmapped — the same "one model source, many schemes" contract.

The CPU fallback: XLA's host platform can emulate an N-device mesh
(``--xla_force_host_platform_device_count=N``), which is how every SPMD
path in this repo is exercised in CI without a TPU.  The flag must be set
before the first backend initialisation; :func:`force_host_device_count`
wraps that dance and :func:`require_devices` asserts it worked.
"""
from __future__ import annotations

import os
import re
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh

from repro.dist import sharding as _sh

_FLAG = "--xla_force_host_platform_device_count"


# ---------------------------------------------------------------------------
# CPU multi-device fallback
# ---------------------------------------------------------------------------
def force_host_device_count(n: int) -> None:
    """Request ``n`` emulated host devices (call before first jax use).

    Sets ``--xla_force_host_platform_device_count=n`` in ``XLA_FLAGS``,
    REPLACING any count already forced (an inherited CI default must not
    shadow an explicit request), and keeping unrelated flags.  Safe to
    call when jax is imported but no backend is initialised yet; too late
    after that (XLA reads the flag once, at backend init) — pair with
    :func:`require_devices`.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(rf"{_FLAG}=\d+\s*", "", flags).strip()
    os.environ["XLA_FLAGS"] = f"{flags} {_FLAG}={n}".strip()


def require_devices(n: int) -> None:
    """Fail fast (with the fix spelled out) when fewer devices exist."""
    have = jax.device_count()
    if have < n:
        raise RuntimeError(
            f"need {n} devices, have {have}; set XLA_FLAGS={_FLAG}={n} "
            f"before the first jax backend init (see repro.dist."
            f"collectives.force_host_device_count)")


def auto_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axis types.  The rules layer places
    arrays with ``with_sharding_constraint`` (:func:`sharding.constrain`),
    which the default ``Explicit`` axes of ``jax.make_mesh`` refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def host_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """Mesh over the (possibly emulated) host devices."""
    n = 1
    for s in shape:
        n *= s
    require_devices(n)
    return auto_mesh(shape, axes)


# ---------------------------------------------------------------------------
# Logical-axis collectives (valid inside shard_map/pmap regions)
# ---------------------------------------------------------------------------
def _resolve(logical: str, rules: Optional[_sh.Rules]) -> Tuple[str, ...]:
    rules = rules or _sh.current_rules()
    if rules is None:
        return ()
    return rules.axes(logical)


def axis_size(logical: str, rules: Optional[_sh.Rules] = None) -> int:
    """Total ways the logical axis is split (1 when unmapped)."""
    rules = rules or _sh.current_rules()
    n = 1
    for ax in _resolve(logical, rules):
        n *= rules.mesh.shape[ax]
    return n


def psum(x, logical: str, rules: Optional[_sh.Rules] = None):
    axes = _resolve(logical, rules)
    return jax.lax.psum(x, axes) if axes else x


def pmean(x, logical: str, rules: Optional[_sh.Rules] = None):
    axes = _resolve(logical, rules)
    return jax.lax.pmean(x, axes) if axes else x


def pmax(x, logical: str, rules: Optional[_sh.Rules] = None):
    axes = _resolve(logical, rules)
    return jax.lax.pmax(x, axes) if axes else x


def all_gather(x, logical: str, *, axis: int = 0, tiled: bool = True,
               rules: Optional[_sh.Rules] = None):
    """Concatenate shards along ``axis`` (identity when unmapped)."""
    axes = _resolve(logical, rules)
    if not axes:
        return x
    return jax.lax.all_gather(x, axes, axis=axis, tiled=tiled)


def all_to_all(x, logical: str, *, split_axis: int, concat_axis: int,
               rules: Optional[_sh.Rules] = None):
    """Expert-parallel dispatch primitive (identity when unmapped)."""
    axes = _resolve(logical, rules)
    if not axes:
        return x
    return jax.lax.all_to_all(x, axes, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
