"""Shared constants/helpers for the Pallas kernel tier."""

import jax

LANES = 128  # TPU lane width; row-scalar scratch is lane-replicated


def interpret() -> bool:
    """Run kernels in interpret mode on the CPU backend (tier-1 tests);
    compiled on the 'tpu' backend."""
    return jax.default_backend() == "cpu"
