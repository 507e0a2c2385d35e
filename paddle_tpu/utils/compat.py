"""Thin wrappers over jax APIs the framework calls with its own defaults."""

from __future__ import annotations

import functools

import jax
from jax._src.core import trace_state_clean  # noqa: F401  (no public home)

__all__ = ["shard_map", "trace_state_clean"]


def shard_map(f=None, *, mesh, in_specs, out_specs, check=False, **kwargs):
    """jax.shard_map with the varying-axes check defaulting OFF: the
    engine's explicit-mode collectives legitimately mix replicated and
    varying values."""
    if f is None:
        return functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check=check, **kwargs)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check, **kwargs)
