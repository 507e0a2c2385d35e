"""Tensor-parallel collective primitives (reference:
python/paddle/distributed/fleet/layers/mpu/mp_ops.py — _c_identity,
_c_concat, _c_split, _mp_allreduce; CUDA ops
paddle/fluid/operators/collective/c_*).

These are the explicit-mode building blocks used *inside shard_map* where
the 'mp' mesh axis is in scope. Each op pairs a forward collective with the
matching backward collective via jax.custom_vjp — the same fwd/bwd pairing
the reference encodes in its c_* op grad registrations:

  identity fwd / all_reduce bwd   (input to column-parallel)
  all_reduce fwd / identity bwd   (output of row-parallel)
  split fwd / all_gather bwd
  all_gather fwd / split bwd

The sequence-parallel entry points (``ag_matmul``/``matmul_rs`` — the
AG->GEMM / GEMM->RS block boundaries, optionally ring-decomposed into a
collective matmul) are implemented in
``distributed.comm_overlap.collective_matmul`` and re-exported here so
model code has ONE import surface for explicit-mode TP collectives.

Every op validates that the named mesh axis is actually in scope and
raises a typed ``InvalidArgumentError`` (instead of jax's opaque
unbound-axis trace error) when it is not.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .....observability.trace import SCOPES

__all__ = ["c_identity", "mp_allreduce", "c_split", "c_concat",
           "ag_matmul", "matmul_rs",
           "explicit_mode", "in_explicit_mode", "explicit_axis"]


def _require_axis(axis, op: str) -> int:
    # lazy import: comm_overlap must stay importable without fleet
    from ....comm_overlap.collective_matmul import require_axis
    return require_axis(axis, op)

import contextlib
import threading


class _Mode(threading.local):
    def __init__(self):
        self.axis = None


_mode = _Mode()


@contextlib.contextmanager
def explicit_mode(axis: str = "mp"):
    """Inside this scope, TP layers use explicit collectives over `axis`
    (for shard_map-traced programs) instead of GSPMD annotations."""
    prev = _mode.axis
    _mode.axis = axis
    try:
        yield
    finally:
        _mode.axis = prev


def in_explicit_mode() -> bool:
    return _mode.axis is not None


def explicit_axis() -> Optional[str]:
    return _mode.axis


@jax.named_scope(SCOPES.coll_mp)
def _psum(x, axis):
    return lax.psum(x, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _c_identity(x, axis: str):
    return x


def _c_identity_fwd(x, axis):
    return x, None


def _c_identity_bwd(axis, res, g):
    return (_psum(g, axis),)


_c_identity.defvjp(_c_identity_fwd, _c_identity_bwd)


def c_identity(x, axis: str):
    """Identity forward; all-reduce backward (column-parallel input)."""
    _require_axis(axis, "c_identity")
    return _c_identity(x, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _mp_allreduce(x, axis: str):
    return _psum(x, axis)


def _mp_allreduce_fwd(x, axis):
    return _psum(x, axis), None


def _mp_allreduce_bwd(axis, res, g):
    return (g,)


_mp_allreduce.defvjp(_mp_allreduce_fwd, _mp_allreduce_bwd)


def mp_allreduce(x, axis: str):
    """All-reduce forward; identity backward (row-parallel output)."""
    _require_axis(axis, "mp_allreduce")
    return _mp_allreduce(x, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def c_split(x, axis: str, dim: int = -1):
    """Take this rank's slice along `dim`; backward all-gathers."""
    n = _require_axis(axis, "c_split")
    idx = lax.axis_index(axis)
    d = dim if dim >= 0 else x.ndim + dim
    from .....enforce import enforce
    enforce(x.shape[d] % n == 0,
            f"c_split dim {dim} (extent {x.shape[d]}) is not divisible by "
            f"the '{axis}' degree {n}", op="c_split", shape=tuple(x.shape))
    size = x.shape[d] // n
    return lax.dynamic_slice_in_dim(x, idx * size, size, axis=d)


def _c_split_fwd(x, axis, dim):
    return c_split(x, axis, dim), None


def _c_split_bwd(axis, dim, res, g):
    return (_all_gather_concat(g, axis, dim),)


c_split.defvjp(_c_split_fwd, _c_split_bwd)


@jax.named_scope(SCOPES.coll_mp)
def _all_gather_concat(x, axis: str, dim: int):
    d = dim if dim >= 0 else x.ndim + dim
    return lax.all_gather(x, axis, axis=d, tiled=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def c_concat(x, axis: str, dim: int = -1):
    """All-gather-concat along `dim`; backward takes this rank's slice."""
    _require_axis(axis, "c_concat")
    return _all_gather_concat(x, axis, dim)


def _c_concat_fwd(x, axis, dim):
    # route through the validated primal (like _c_split_fwd) — the fwd
    # rule REPLACES the primal under vjp, so calling _all_gather_concat
    # directly would skip the axis check on differentiated paths
    return c_concat(x, axis, dim), None


def _c_concat_bwd(axis, dim, res, g):
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    d = dim if dim >= 0 else g.ndim + dim
    size = g.shape[d] // n
    return (lax.dynamic_slice_in_dim(g, idx * size, size, axis=d),)


c_concat.defvjp(_c_concat_fwd, _c_concat_bwd)


def ag_matmul(x, w, axis: str = "mp", *, seq_dim: int = 1,
              ring: bool = False, mm=None):
    """Sequence-parallel column entry: ``all_gather(x over seq_dim) @ w``
    (bwd reduce-scatters). ring=True = collective-matmul ppermute ring;
    mm = fp8 site_mm routing (fused path only). Implementation:
    distributed.comm_overlap.collective_matmul."""
    from ....comm_overlap.collective_matmul import ag_matmul as _impl
    return _impl(x, w, axis, seq_dim=seq_dim, ring=ring, mm=mm)


def matmul_rs(x, w, axis: str = "mp", *, seq_dim: int = 1,
              ring: bool = False, mm=None):
    """Sequence-parallel row exit: ``reduce_scatter(x @ w over seq_dim)``
    (bwd all-gathers). ring/mm as in :func:`ag_matmul`."""
    from ....comm_overlap.collective_matmul import matmul_rs as _impl
    return _impl(x, w, axis, seq_dim=seq_dim, ring=ring, mm=mm)
