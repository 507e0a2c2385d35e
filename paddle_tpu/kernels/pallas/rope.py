"""Fused rotary position embedding (Pallas).

TPU-native equivalent of the reference's fused_rope CUDA kernel
(reference: paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu; Python
surface paddle.incubate.nn.functional.fused_rotary_position_embedding).

The rotation is elementwise over [S, D/2] cos/sin tables; fusing it keeps
q/k in VMEM between the load and the two multiplies (XLA usually fuses this
too — the kernel exists so the decode path can call one op per layer and to
pin the half-split convention). Backward is the inverse rotation (cos, -sin),
expressed via custom_vjp so autodiff never differentiates through the tables.

Convention: NeoX/Llama half-split — x = [x1, x2] halves of the head dim,
rot(x) = [x1*cos - x2*sin, x2*cos + x1*sin].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import interpret as _interpret
from ...observability.trace import KERNELS

__all__ = ["apply_rope", "supported"]


def supported(x, cos, sin, **kwargs) -> bool:
    return x.ndim == 4 and x.shape[-1] % 2 == 0


def _rope_kernel(x_ref, cos_ref, sin_ref, y_ref, *, neg_sin):
    x = x_ref[0].astype(jnp.float32)   # [s, h*d]
    cos = cos_ref[0].astype(jnp.float32)  # [s, d/2]
    sin = sin_ref[0].astype(jnp.float32)
    if neg_sin:
        sin = -sin
    s, hd = x.shape
    half = cos.shape[-1]
    d = half * 2
    h = hd // d
    x = x.reshape(s, h, d)
    x1 = x[..., :half]
    x2 = x[..., half:]
    c = cos[:, None, :]
    sn = sin[:, None, :]
    y1 = x1 * c - x2 * sn
    y2 = x2 * c + x1 * sn
    y = jnp.concatenate([y1, y2], axis=-1).reshape(s, hd)
    y_ref[0] = y.astype(y_ref.dtype)


def _pick_seq_block(s: int, row_bytes: int) -> int:
    # keep an x block ≲1MB in VMEM (plus f32 temporaries)
    bs = max(1, min(s, (1 << 20) // max(row_bytes, 1)))
    while s % bs:
        bs -= 1
    return bs


def _rope_call(x, cos, sin, neg_sin):
    b, s, h, d = x.shape
    x2 = x.reshape(b, s, h * d)
    bs = _pick_seq_block(s, h * d * x.dtype.itemsize)
    y = pl.pallas_call(
        functools.partial(_rope_kernel, neg_sin=neg_sin),
        grid=(b, s // bs),
        in_specs=[
            pl.BlockSpec((1, bs, h * d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bs, d // 2), lambda i, j: (0, j, 0)),
            pl.BlockSpec((1, bs, d // 2), lambda i, j: (0, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, h * d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), x.dtype),
        interpret=_interpret(),
        name=KERNELS.rope,
    )(x2, cos.reshape(1, s, d // 2), sin.reshape(1, s, d // 2))
    return y.reshape(b, s, h, d)


@jax.custom_vjp
def apply_rope(x, cos, sin):
    """x: [B, S, H, D]; cos/sin: [S, D/2] (or broadcastable). Rotates the
    half-split head dim by position-dependent angles."""
    return _rope_call(x, cos, sin, neg_sin=False)


def _rope_fwd(x, cos, sin):
    return _rope_call(x, cos, sin, neg_sin=False), (cos, sin)


def _rope_bwd(res, g):
    cos, sin = res
    return _rope_call(g, cos, sin, neg_sin=True), None, None


apply_rope.defvjp(_rope_fwd, _rope_bwd)
