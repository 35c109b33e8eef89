"""Execution-backend abstraction for the compile plans under ``tpu/``.

The lowering passes (``expr_compile`` programs, ``query_compile`` window
steps, the blocked NFA plan in ``nfa.py``/``nfa_block.py``) emit closures
over an array namespace. Historically that namespace was hard-wired to
``jax.numpy``; this module makes it a parameter so the SAME compiled plan
can execute two ways:

- **jax** (device path): jitted, static shapes, f32 policy (``dtypes.JNP``)
  — unchanged behavior, still the default;
- **numpy** (columnar host path): eager, dynamic shapes, f64/i64 policy
  (``NP_HOST`` below) so results match the scalar host interpreter's Python
  float/int semantics instead of the device's f32 tolerance band.

``jnp`` here is a lazy module proxy: importing this module (or compiling a
plan on the numpy backend) never imports jax — only touching a ``jnp``
attribute does. That keeps the columnar host engine importable in processes
that must not claim the chip: a chip belongs to one process, so the parent
of a process that needs it (a procmesh supervisor) and
workers that only run the NumPy tiers stay clear of PJRT backend init.
"""

from __future__ import annotations

import numpy as np

from ..query_api.definition import DataType


class _LazyJnp:
    """Attribute-level lazy ``jax.numpy`` import."""

    _mod = None

    def _load(self):
        if _LazyJnp._mod is None:
            import jax.numpy as _jnp
            _LazyJnp._mod = _jnp
        return _LazyJnp._mod

    def __getattr__(self, name):
        return getattr(self._load(), name)


jnp = _LazyJnp()

# host-backend (numpy) representation per declared attribute type: full-width
# like the scalar interpreter (Java long/double), NOT the device's f32 policy
# — the columnar host engine is parity-exact against the interpreter, no
# tolerance band needed
NP_HOST = {
    DataType.STRING: np.int32,    # dictionary codes
    DataType.INT: np.int64,
    DataType.LONG: np.int64,
    DataType.FLOAT: np.float64,
    DataType.DOUBLE: np.float64,
    DataType.BOOL: np.bool_,
}


def is_numpy_backend(xp) -> bool:
    return xp is np


def policy_dtype(t: DataType, xp):
    """Backend dtype policy for a declared attribute type."""
    if xp is np:
        return NP_HOST[t]
    from .dtypes import JNP
    return JNP[t]


def resolver_xp(resolver):
    """The array namespace a compile pass should emit against — resolvers
    carry ``xp`` (numpy on the host columnar backend); default is the lazy
    jax.numpy proxy."""
    return getattr(resolver, "xp", None) or jnp


# ---------------------------------------------------------------------------
# shared kernel helpers (previously duplicated per compile module)
# ---------------------------------------------------------------------------

def avalanche(x, xp=jnp):
    """splitmix64 finalizer: spreads packed multi-key ids over buckets.

    One definition for every consumer (``query_compile`` group-by bucketing,
    the host columnar engine's lane spreading) — backend-parametric so the
    numpy path runs it eagerly.
    """
    x = xp.asarray(x).astype(xp.uint64)
    x = (x ^ (x >> xp.uint64(30))) * xp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> xp.uint64(27))) * xp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> xp.uint64(31))
    return (x & xp.uint64(0x7FFFFFFFFFFFFFFF)).astype(xp.int64)


def reduce_identity(dtype, is_min: bool, xp=jnp):
    """Reduction identity for min/max lanes (shared by ``query_compile`` and
    ``aggregation_compile``, which carried byte-identical copies)."""
    if xp.issubdtype(dtype, xp.floating):
        return xp.asarray(xp.inf if is_min else -xp.inf, dtype)
    info = xp.iinfo(dtype)
    return xp.asarray(info.max if is_min else info.min, dtype)
