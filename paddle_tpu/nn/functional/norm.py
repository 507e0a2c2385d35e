"""Normalization ops (reference: python/paddle/nn/functional/norm.py;
fused kernels paddle/phi/kernels/gpu/{layer_norm,rms_norm}_kernel.cu).

TPU: expressed as jnp reductions; XLA fuses mean/var/normalize/affine into a
single VPU pass. rms_norm additionally has a Pallas fast path registered in
paddle_tpu.kernels.pallas.rms_norm.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...ops import register_op

__all__ = ["normalize", "batch_norm", "layer_norm", "instance_norm",
           "group_norm", "local_response_norm", "rms_norm"]


def normalize(x, p=2, axis=1, epsilon=1e-12):
    if p == 2:
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    else:
        n = jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis, keepdims=True), 1.0 / p)
    return x / jnp.maximum(n, epsilon)


@register_op("layer_norm", tags=["norm", "fusion"], dispatch=True)
def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    del name
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
    xf = x.astype(jnp.float32)  # accumulate stats in fp32 (bf16-safe)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + epsilon)
    out = out.astype(x.dtype)
    # mixed-precision contract: output dtype == input dtype. The affine
    # params commonly stay fp32 next to bf16 activations; multiplying in
    # their dtype would silently re-promote every downstream activation
    # (and the attention kernels) to fp32, twice the bytes of every later
    # pass.
    if weight is not None:
        out = out * jnp.asarray(weight).astype(x.dtype)
    if bias is not None:
        out = out + jnp.asarray(bias).astype(x.dtype)
    return out


@register_op("rms_norm", tags=["norm", "fusion"], dispatch=True)
def rms_norm(x, weight=None, bias=None, epsilon=1e-6, begin_norm_axis=-1):
    """RMSNorm (reference: paddle/phi/kernels/gpu/rms_norm_kernel.cu;
    python/paddle/incubate/nn/functional/fused_rms_norm.py)."""
    axes = begin_norm_axis % x.ndim
    red = tuple(range(axes, x.ndim))
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=red, keepdims=True)
    out = (xf * jax.lax.rsqrt(ms + epsilon)).astype(x.dtype)
    # same output-dtype contract as layer_norm (fp32 affine params must
    # not promote bf16 activations)
    if weight is not None:
        out = out * jnp.asarray(weight).astype(x.dtype)
    if bias is not None:
        out = out + jnp.asarray(bias).astype(x.dtype)
    return out


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW",
               use_global_stats=None, name=None):
    """Returns (out, new_mean, new_var) when training else out.

    NOTE (design departure): the reference mutates running stats in-place
    inside the kernel (paddle/phi/kernels/gpu/batch_norm_kernel.cu); here the
    updated stats are *returned* and the Layer threads them through the
    functional state (see nn/layer/norm.py BatchNorm.forward).
    """
    del name
    channels_last = data_format.endswith("C") and data_format != "NC"
    c_axis = x.ndim - 1 if channels_last else (1 if x.ndim > 1 else 0)
    red_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]

    if use_global_stats is None:
        use_global_stats = not training

    xf = x.astype(jnp.float32)
    if not use_global_stats:
        mean = jnp.mean(xf, axis=red_axes)
        var = jnp.var(xf, axis=red_axes)
        new_rm = momentum * jnp.asarray(running_mean) + (1 - momentum) * mean
        new_rv = momentum * jnp.asarray(running_var) + (1 - momentum) * var
    else:
        mean = jnp.asarray(running_mean)
        var = jnp.asarray(running_var)
        new_rm, new_rv = running_mean, running_var

    out = (xf - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + epsilon)
    out = out.astype(x.dtype)
    if weight is not None:
        out = out * jnp.asarray(weight).reshape(shape)
    if bias is not None:
        out = out + jnp.asarray(bias).reshape(shape)
    if training and not use_global_stats:
        return out, new_rm, new_rv
    return out


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW"):
    del running_mean, running_var, use_input_stats, momentum
    channels_last = data_format.endswith("C") and x.ndim > 2
    if channels_last:
        red_axes = tuple(range(1, x.ndim - 1))
        shape = (1,) * (x.ndim - 1) + (x.shape[-1],)
    else:
        red_axes = tuple(range(2, x.ndim))
        shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=red_axes, keepdims=True)
    var = jnp.var(xf, axis=red_axes, keepdims=True)
    out = ((xf - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    if weight is not None:
        out = out * jnp.asarray(weight).reshape(shape)
    if bias is not None:
        out = out + jnp.asarray(bias).reshape(shape)
    return out


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    del name
    channels_last = data_format.endswith("C") and data_format not in ("NC",)
    if channels_last:
        x_t = jnp.moveaxis(x, -1, 1)
        out = group_norm(x_t, num_groups, epsilon, weight, bias, "NCHW")
        return jnp.moveaxis(out, 1, -1)
    N, C = x.shape[0], x.shape[1]
    g_shape = (N, num_groups, C // num_groups) + x.shape[2:]
    xf = x.astype(jnp.float32).reshape(g_shape)
    red = tuple(range(2, xf.ndim))
    mean = jnp.mean(xf, axis=red, keepdims=True)
    var = jnp.var(xf, axis=red, keepdims=True)
    out = ((xf - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape).astype(x.dtype)
    shape = (1, C) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * jnp.asarray(weight).reshape(shape)
    if bias is not None:
        out = out + jnp.asarray(bias).reshape(shape)
    return out


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW"):
    channels_last = data_format.endswith("C") and x.ndim > 2
    c_axis = x.ndim - 1 if channels_last else 1
    sq = jnp.square(x)
    pad_lo = (size - 1) // 2
    pad_hi = size - 1 - pad_lo
    pads = [(0, 0)] * x.ndim
    pads[c_axis] = (pad_lo, pad_hi)
    sq_p = jnp.pad(sq, pads)
    window = [1] * x.ndim
    window[c_axis] = size
    summed = jax.lax.reduce_window(sq_p, 0.0, jax.lax.add, tuple(window),
                                   (1,) * x.ndim, "VALID")
    div = jnp.power(k + alpha * summed / size, beta)
    return x / div
