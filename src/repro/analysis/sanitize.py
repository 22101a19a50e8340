"""``jax.experimental.checkify`` wiring for the oracle/interpret paths.

Usage::

    from repro.analysis import sanitize

    # one-shot: run fn under index-OOB + NaN + div checks, raising
    # checkify.JaxRuntimeError on the first violation
    out = sanitize.checked_call(ref.bulk_append_ref, heap, tail, ...)

    # reusable: wrap once, call many times
    safe = sanitize.sanitized(ref.segment_intersect_mask_batched_ref)
    masks = safe(stacked_a, stacked_b)

Every wrapper in :mod:`repro.kernels.ops` takes ``checked=True`` and
routes through here, so tests and benchmarks flip one flag to run the
whole oracle surface under the sanitizer (CI runs the kernel-equivalence
suite that way; see .github/workflows/ci.yml).

``checked`` always sanitizes the **jnp oracle**, which repo policy
declares to be the semantics (ops.py docstring): checkify instruments
XLA operations, and a kernel compiled by Mosaic on a TPU is opaque to
it.  The Pallas bodies are covered by the oracle-equivalence tests in
interpret mode and by the TPU compile tests (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

from jax.experimental import checkify

# Index OOB + NaN + div-by-zero: the three classes an allocator bug
# (dangling pointer, bad watermark, zero-width slice) manifests as.
DEFAULT_CHECKS = (checkify.index_checks | checkify.nan_checks
                  | checkify.div_checks)

# Re-export so callers can `except sanitize.SanitizerError` without
# importing checkify themselves.
SanitizerError = checkify.JaxRuntimeError


def sanitized(fn, *, errors=None):
    """Wrap ``fn`` so calls run under checkify and throw on violation.

    Returns a callable with ``fn``'s signature; the checkify error is
    consumed via ``err.throw()`` so a clean run returns ``fn``'s output
    unchanged and a violation raises :class:`SanitizerError`.
    """
    checked_fn = checkify.checkify(
        fn, errors=DEFAULT_CHECKS if errors is None else errors)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        err, out = checked_fn(*args, **kwargs)
        err.throw()
        return out

    return wrapper


def checked_call(fn, *args, errors=None, **kwargs):
    """One-shot :func:`sanitized` — build, call, throw-or-return."""
    return sanitized(fn, errors=errors)(*args, **kwargs)


__all__ = ["DEFAULT_CHECKS", "SanitizerError", "sanitized",
           "checked_call"]
