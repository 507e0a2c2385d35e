"""Fused LayerNorm (Pallas).

TPU-native equivalent of the reference's fused LayerNorm kernel
(reference: paddle/phi/kernels/gpu/layer_norm_kernel.cu; the BERT-era
fused_attention/fused_feedforward kernels fold the same residual+LN
pattern, fusion/gpu/fused_attention_kernel.cu).

One row-blocked pass: mean, variance, normalize, affine — x is read once
and the [rows] statistics live in VMEM. The XLA-composed fallback
(nn/functional/norm.py layer_norm) emits separate convert_reduce fusions
for the stats, each a pass of its own over the bf16 rows; this kernel
removes that round trip. Backward fuses the dx
recurrence in a second row-blocked kernel; dw/db are cross-row reductions
left to one fused XLA reduce (same split as rms_norm.py).

Relation to kernels/pallas/primitives.py layer_norm: that one is the
KPS-primitives teaching tier (in-kernel dg/db accumulation over a
sequential grid, bias required); this module is the dispatch tier wired
into the op registry (optional bias, cross-row reductions delegated to
XLA so the grid stays embarrassingly parallel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import LANES as _LANES
from ._common import interpret as _interpret
from ...observability.trace import KERNELS

__all__ = ["layer_norm", "supported"]


def _pick_rows(n: int, hidden: int) -> int:
    # ~2MB of fp32 rows per block; the grid uses pl.cdiv with a masked
    # edge block, so no exact-divisor hunt (a prime row count would
    # otherwise degrade to 1-row tiles at 1/8 sublane utilization).
    # Mosaic wants the sublane block divisible by 8 (or == the array dim).
    r = min(n, (1 << 19) // max(hidden, 1))
    if r < n:
        r = max(8, (r // 8) * 8)
    return r


def supported(x, weight, epsilon=1e-5, **kwargs) -> bool:
    return x.ndim >= 2 and x.shape[-1] == weight.shape[-1]


def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, stat_ref, *, eps, has_bias):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)        # [rows, 1]
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd * w_ref[:].astype(jnp.float32)
    if has_bias:
        y = y + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    # stats lane-replicated: [rows, 128] x (mean, rstd) interleaved as two
    # outputs would double the launches; pack mean in [:, :64]? No —
    # keep it simple: stat_ref is [rows, 2*LANES] = [mean | rstd] halves
    stat_ref[:, :_LANES] = jnp.broadcast_to(mean, (x.shape[0], _LANES))
    stat_ref[:, _LANES:] = jnp.broadcast_to(rstd, (x.shape[0], _LANES))


def _bwd_kernel(x_ref, w_ref, stat_ref, dy_ref, dx_ref):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    mean = stat_ref[:, :1]               # [rows, 1]
    rstd = stat_ref[:, _LANES:_LANES + 1]
    h = x.shape[-1]
    xhat = (x - mean) * rstd
    dxhat = dy * w
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = rstd * (dxhat - m1 - xhat * m2)
    dx_ref[:] = dx.astype(dx_ref.dtype)


def layer_norm(x, weight, bias=None, epsilon=1e-5):
    """y = (x - mean) / sqrt(var + eps) * weight (+ bias) over the last
    axis, output in x.dtype (the mixed-precision contract of the composed
    path)."""
    x = jnp.asarray(x)
    weight = jnp.asarray(weight)
    bias = None if bias is None else jnp.asarray(bias)
    return _ln(x, weight, bias, epsilon)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln(x, weight, bias, epsilon):
    y, _ = _ln_fwd(x, weight, bias, epsilon)
    return y


def _ln_fwd(x, weight, bias, epsilon):
    shape = x.shape
    h = shape[-1]
    x2 = x.reshape(-1, h)
    n = x2.shape[0]
    rows = _pick_rows(n, h)
    has_bias = bias is not None
    b_in = (bias.reshape(1, h) if has_bias
            else jnp.zeros((1, h), weight.dtype))
    y, stat = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=epsilon, has_bias=has_bias),
        grid=(pl.cdiv(n, rows),),
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((rows, 2 * _LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x.dtype),
            jax.ShapeDtypeStruct((n, 2 * _LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name=KERNELS.layer_norm_fwd,
    )(x2, weight.reshape(1, h), b_in)
    return y.reshape(shape), (x2, weight, has_bias, stat, shape)


def _ln_bwd(epsilon, res, g):
    x2, weight, has_bias, stat, shape = res
    h = shape[-1]
    dy = g.reshape(-1, h)
    n = x2.shape[0]
    rows = _pick_rows(n, h)
    dx = pl.pallas_call(
        _bwd_kernel,
        grid=(pl.cdiv(n, rows),),
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((rows, 2 * _LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), x2.dtype),
        interpret=_interpret(),
        name=KERNELS.layer_norm_bwd,
    )(x2, weight.reshape(1, h), stat, dy)
    # dw/db: cross-row reductions — one fused XLA reduce over the saved
    # stats (xhat recomputed elementwise, fuses into the reduction)
    xf = x2.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    xhat = (xf - stat[:, :1]) * stat[:, _LANES:_LANES + 1]
    dw = jnp.sum(dyf * xhat, axis=0).astype(weight.dtype)
    db = jnp.sum(dyf, axis=0).astype(weight.dtype) if has_bias else None
    return dx.reshape(shape), dw, db


_ln.defvjp(_ln_fwd, _ln_bwd)
