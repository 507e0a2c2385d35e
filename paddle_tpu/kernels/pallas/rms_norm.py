"""Fused RMSNorm (Pallas).

TPU-native equivalent of the reference's fused CUDA RMSNorm
(reference: paddle/phi/kernels/gpu/rms_norm_kernel.cu; Python surface
paddle.incubate.nn.functional.fused_rms_norm).

One pass over rows in VMEM: mean-square, rsqrt, scale — the normalized
activation never round-trips to HBM. Backward fuses the dx recurrence in a
second row-blocked kernel; dw is a cross-row reduction left to XLA (it
fuses into a single segment-sum over the saved rstd).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import LANES as _LANES
from ._common import interpret as _interpret
from ...observability.trace import KERNELS

__all__ = ["rms_norm", "supported"]


def _pick_rows(n: int, hidden: int) -> int:
    # target ~2MB of fp32 rows in VMEM
    r = max(1, min(n, (1 << 19) // max(hidden, 1)))
    while n % r:
        r -= 1
    return r


def supported(x, weight, epsilon=1e-6, **kwargs) -> bool:
    return x.ndim >= 2 and x.shape[-1] == weight.shape[-1]


def _fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)  # [rows, 1]
    y_ref[:] = (x * rstd * w_ref[:].astype(jnp.float32)).astype(y_ref.dtype)
    rstd_ref[:] = jnp.broadcast_to(rstd, rstd_ref.shape)


def _bwd_kernel(x_ref, w_ref, rstd_ref, dy_ref, dx_ref):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:][:, 0][:, None]  # [rows, 1]
    h = x.shape[-1]
    dyw = dy * w
    dot = jnp.sum(dyw * x, axis=-1, keepdims=True)
    dx = rstd * dyw - (rstd ** 3) * x * dot / h
    dx_ref[:] = dx.astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, weight, epsilon=1e-6):
    """y = x / sqrt(mean(x^2) + eps) * weight over the last axis."""
    y, _ = _rms_fwd(x, weight, epsilon)
    return y


def _rms_fwd(x, weight, epsilon):
    shape = x.shape
    h = shape[-1]
    x2 = x.reshape(-1, h)
    n = x2.shape[0]
    rows = _pick_rows(n, h)
    y, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=epsilon),
        grid=(n // rows,),
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x.dtype),
            jax.ShapeDtypeStruct((n, _LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name=KERNELS.rms_norm_fwd,
    )(x2, weight.reshape(1, h))
    return y.reshape(shape), (x2, weight, rstd, shape)


def _rms_bwd(epsilon, res, g):
    x2, weight, rstd, shape = res
    h = shape[-1]
    dy = g.reshape(-1, h)
    n = x2.shape[0]
    rows = _pick_rows(n, h)
    dx = pl.pallas_call(
        _bwd_kernel,
        grid=(n // rows,),
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), x2.dtype),
        interpret=_interpret(),
        name=KERNELS.rms_norm_bwd,
    )(x2, weight.reshape(1, h), rstd, dy)
    # dw: cross-row reduction — a single fused XLA reduce over saved rstd
    xf = x2.astype(jnp.float32)
    dw = jnp.sum(dy.astype(jnp.float32) * xf * rstd[:, :1], axis=0)
    return dx.reshape(shape), dw.astype(weight.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)
