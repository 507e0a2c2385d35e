"""Portable tile primitives (reference: paddle/phi/kernels/primitive/ —
the "Kernel Primitive API": ReadData/WriteData/ElementwiseUnary/
ElementwiseBinary/Reduce building blocks the reference composes CUDA/XPU
kernels from, SURVEY §2.2 KPS).

TPU translation: the primitives are Pallas TILE builders. Each returns a
ready pallas_call over a [rows, cols] tiling discipline (rows on
sublanes, cols on lanes; tiles sized to VMEM), so a kernel author writes
only the per-tile math — exactly the KPS division of labor. The
framework's composed ops don't NEED these for fusion (XLA fuses
elementwise chains); they exist for custom-kernel authors (the same
audience as the reference's primitive/) and back the fused LN kernel
below.

Primitives:
  elementwise(fn, *arrays)            y = fn(*xs), tiled
  row_reduce(fn, identity, x)         [R, C] -> [R] with a VMEM carry
                                      across column tiles
  online_softmax_update(s, m, l, acc) the flash-attention streaming-
                                      softmax update rule, shared math
  layer_norm(x, g, b)                 fused row LN (fwd+bwd custom_vjp)
                                      built on the tiling discipline
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from ...enforce import InvalidArgumentError
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import LANES
from ._common import interpret as _interpret
from ...observability.trace import KERNELS

__all__ = ["elementwise", "row_reduce", "online_softmax_update",
           "layer_norm"]


def _tile(n, target):
    t = min(target, n)
    while n % t:
        t -= 1
    return max(t, 1)


def _as2d(x):
    """[*, C] view -> [R, C] (the tiling discipline is 2-D)."""
    x = jnp.asarray(x)
    if x.ndim == 0:
        return x.reshape(1, 1), ()
    return x.reshape(-1, x.shape[-1]), x.shape


def elementwise(fn: Callable, *arrays, block_rows: int = 256,
                out_dtype=None):
    """KPS ElementwiseUnary/Binary/Ternary: apply `fn` tile-by-tile.
    Arrays must share a shape (broadcast upstream); the last dim rides
    lanes. Equivalent XLA fusion exists — this is the explicit-kernel
    form for custom-kernel composition."""
    xs2, shape = zip(*[_as2d(a) for a in arrays])
    r, c = xs2[0].shape
    for a in xs2[1:]:
        if a.shape != (r, c):
            raise InvalidArgumentError(
                f"elementwise primitive needs equal shapes, got "
                f"{[tuple(a.shape) for a in xs2]}")
    br = _tile(r, block_rows)
    out_dtype = out_dtype or xs2[0].dtype

    def kernel(*refs):
        o_ref = refs[-1]
        o_ref[...] = fn(*(ref[...] for ref in refs[:-1])).astype(
            o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(r // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0))] * len(xs2),
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype),
        interpret=_interpret(),
        name=KERNELS.rowwise,
    )(*xs2)
    return out.reshape(shape[0] or (1,)) if shape[0] != () else out[0, 0]


def row_reduce(fn: Callable, identity, x, block_rows: int = 256,
               block_cols: int = 2048):
    """KPS Reduce (kps::details::Reduce row mode): [R, C] -> [R] for an
    associative elementwise `fn` (jnp.add/maximum/minimum/...).
    Column tiles stream through a VMEM accumulator carried across the
    innermost grid axis — the scores-row pattern every flash kernel uses,
    exposed as a primitive. In-kernel folds stay LANE-ALIGNED (the
    accumulator is [rows, 128]); the final 128-way cross-lane fold
    happens outside, where it costs one tiny fused op instead of a
    per-tile relayout. C must be a multiple of the 128-lane width."""
    from ...enforce import enforce
    x2, shape = _as2d(x)
    r, c = x2.shape
    enforce(c % LANES == 0,
            f"row_reduce needs the reduced dim ({c}) to be a multiple of "
            f"the {LANES}-lane width (pad upstream)", op="row_reduce", x=x)
    br = _tile(r, block_rows)
    bc = c
    while bc > block_cols and bc % 2 == 0 and (bc // 2) % LANES == 0:
        bc //= 2
    nc = c // bc

    def kernel(x_ref, o_ref, acc):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            acc[...] = jnp.full_like(acc, identity)

        tile = x_ref[...].astype(jnp.float32)
        parts = [tile[:, k * LANES:(k + 1) * LANES]
                 for k in range(bc // LANES)]
        acc[...] = fn(acc[...], functools.reduce(fn, parts))

        @pl.when(j == nc - 1)
        def _out():
            o_ref[...] = acc[...]

    out = pl.pallas_call(
        kernel,
        grid=(r // br, nc),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, LANES), jnp.float32)],
        interpret=_interpret(),
        name=KERNELS.row_reduce,
    )(x2)
    res = functools.reduce(fn, [out[:, k] for k in range(LANES)])
    return res.reshape(shape[:-1])


def online_softmax_update(s, m_prev, l_prev, acc_prev, v=None):
    """The streaming-softmax update rule (KPS-style shared math used by
    every flash/ring kernel): returns (m, l, acc, p). s: [bq, bk] scores
    tile; acc accumulates p @ v when v is given, else p itself."""
    m_cur = jnp.max(s, axis=-1)
    m = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m)
    p = jnp.exp(s - m[:, None])
    l = l_prev * alpha + jnp.sum(p, axis=-1)
    if v is not None:
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc = acc_prev * alpha[:, None] + pv
    else:
        acc = acc_prev * alpha[:, None] + p
    return m, l, acc, p


# ---------------------------------------------------------------------------
# fused layer norm on the primitives' tiling discipline
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mu_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mu) * rstd
    y_ref[...] = (y * g_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(y_ref.dtype)
    mu_ref[...] = jnp.broadcast_to(mu, mu_ref.shape)
    rstd_ref[...] = jnp.broadcast_to(rstd, rstd_ref.shape)


def _ln_bwd_kernel(x_ref, g_ref, mu_ref, rstd_ref, dy_ref, dx_ref,
                   dg_ref, db_ref, dg_acc, db_acc, *, nrows):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mu = mu_ref[...][:, :1]
    rstd = rstd_ref[...][:, :1]
    xhat = (x - mu) * rstd
    dyg = dy * g
    c1 = jnp.mean(dyg, axis=1, keepdims=True)
    c2 = jnp.mean(dyg * xhat, axis=1, keepdims=True)
    dx_ref[...] = ((dyg - c1 - xhat * c2) * rstd).astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _init():
        dg_acc[...] = jnp.zeros_like(dg_acc)
        db_acc[...] = jnp.zeros_like(db_acc)

    dg_acc[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_acc[...] += jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == nrows - 1)
    def _out():
        dg_ref[...] = dg_acc[...]
        db_ref[...] = db_acc[...]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm(x, weight, bias, eps: float = 1e-5):
    """Fused row LayerNorm over the last dim — the KPS-primitives demo
    kernel (reference analogue: phi/kernels/gpu/layer_norm_kernel.cu's
    welford+affine fusion). Matches the composed fp32 LN numerics."""
    return _ln_fwd(x, weight, bias, eps)[0]


def _ln_fwd(x, weight, bias, eps):
    x2, shape = _as2d(x)
    r, c = x2.shape
    br = _tile(r, 256)
    y, mu, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(r // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                   pl.BlockSpec((br, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((br, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, c), x2.dtype),
                   jax.ShapeDtypeStruct((r, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((r, LANES), jnp.float32)],
        interpret=_interpret(),
        name=KERNELS.prim_layer_norm_fwd,
    )(x2, jnp.asarray(weight)[None, :], jnp.asarray(bias)[None, :])
    return y.reshape(shape), (x2, shape, mu, rstd)


def _ln_fwd_rule(x, weight, bias, eps):
    y, res = _ln_fwd(x, weight, bias, eps)
    return y, res + (jnp.asarray(weight),)


def _ln_bwd_rule(eps, res, dy):
    x2, shape, mu, rstd, weight = res
    r, c = x2.shape
    br = _tile(r, 256)
    n = r // br
    dy2 = jnp.asarray(dy).reshape(r, c)
    dx, dg, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, nrows=n),
        grid=(n,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((br, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((br, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                   pl.BlockSpec((1, c), lambda i: (0, 0)),
                   pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, c), x2.dtype),
                   jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, c), jnp.float32),
                        pltpu.VMEM((1, c), jnp.float32)],
        interpret=_interpret(),
        name=KERNELS.prim_layer_norm_bwd,
    )(x2, weight[None, :], mu, rstd, dy2)
    return (dx.reshape(shape), dg[0].astype(weight.dtype),
            db[0].astype(weight.dtype))


layer_norm.defvjp(_ln_fwd_rule, _ln_bwd_rule)
