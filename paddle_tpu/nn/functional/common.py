"""Common functional ops: linear, dropout, embedding, pad, interpolate, unfold.
(reference: python/paddle/nn/functional/common.py, input.py)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ...random import next_key, next_mask_key

__all__ = [
    "linear", "dropout", "dropout2d", "dropout3d", "alpha_dropout", "embedding",
    "one_hot", "pad", "interpolate", "upsample", "unfold", "fold",
    "pixel_shuffle", "pixel_unshuffle", "channel_shuffle", "label_smooth",
    "cosine_similarity", "bilinear", "class_center_sample",
]


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b. W is [in, out] (paddle layout). Rides the MXU; keep the
    contraction dims multiples of 128 for best tiling."""
    del name
    from ...amp.auto_cast import white_cast
    from ...enforce import enforce
    x, weight, bias = white_cast("linear", x, weight, bias)
    w = jnp.asarray(weight)
    enforce(w.ndim == 2 and getattr(x, "ndim", 0) >= 1
            and x.shape[-1] == w.shape[0],
            f"linear: x{tuple(getattr(x, 'shape', ()))} @ "
            f"W{tuple(w.shape)} — last dim of x must equal W's in dim",
            op="linear", x=x, weight=w)
    out = jnp.matmul(x, w)
    if bias is not None:
        # bias in the matmul's dtype: an fp32 bias next to bf16 x/W would
        # promote the output (and everything downstream) to fp32
        out = out + jnp.asarray(bias).astype(out.dtype)
    return out


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    del name
    if not training or p == 0.0:
        return x if mode == "upscale_in_train" else x * (1.0 - p)
    if p == 1.0:
        return jnp.zeros_like(x)
    shape = list(x.shape)
    if axis is not None:
        axes = [axis] if isinstance(axis, int) else list(axis)
        shape = [s if i in axes else 1 for i, s in enumerate(shape)]
    keep = 1.0 - p
    # rbg mask bits: threefry expansion costs ~10 ALU ops an element
    # (see random.next_mask_key)
    mask = jax.random.bernoulli(next_mask_key(), keep, tuple(shape))
    if mode == "upscale_in_train":
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)
    return jnp.where(mask, x, 0.0).astype(x.dtype)


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW"):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True):
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = 1.0 - p
    a = (keep + alpha_p ** 2 * keep * (1 - keep)) ** -0.5
    b = -a * alpha_p * (1 - keep)
    mask = jax.random.bernoulli(next_mask_key(), keep, x.shape)
    return (a * jnp.where(mask, x, alpha_p) + b).astype(x.dtype)


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Gather rows of `weight` by integer ids. On TPU this lowers to a
    dynamic-gather XLA HLO; the backward is a scatter-add (the reference's
    sparse=True SelectedRows path is unnecessary — XLA handles it)."""
    del sparse, name
    w = jnp.asarray(weight)
    out = jnp.take(w, x, axis=0)
    if padding_idx is not None:
        mask = (x == padding_idx)[..., None]
        out = jnp.where(mask, 0.0, out)
    return out


def one_hot(x, num_classes):
    return jax.nn.one_hot(x, num_classes)


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    del name
    from ... import tensor as T
    if isinstance(pad, int):
        pad = [pad] * (2 * x.ndim)
    pad = list(pad)
    if len(pad) == 2 * x.ndim:
        return T.pad(x, pad, mode=mode, value=value)
    # paddle semantics: partial pad list applies LAST-SPATIAL-DIM FIRST
    # ((pad_left, pad_right) pad W, then (pad_top, pad_bottom) pad H, ...)
    n = len(pad) // 2
    pairs = [(0, 0)] * x.ndim
    if data_format.startswith("NC"):  # NCL/NCHW/NCDHW: spatial dims are 2..
        spatial = list(range(2, x.ndim))
    else:  # NLC/NHWC/NDHWC: spatial dims are 1..ndim-1
        spatial = list(range(1, x.ndim - 1))
    for i in range(n):
        ax = spatial[len(spatial) - 1 - i]
        pairs[ax] = (pad[2 * i], pad[2 * i + 1])
    flat = [v for p in pairs for v in p]
    return T.pad(x, flat, mode=mode, value=value)


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW", name=None):
    del name
    nchw = data_format in ("NCHW", "NCL", "NCDHW")
    spatial_axes = list(range(2, x.ndim)) if nchw else list(range(1, x.ndim - 1))
    in_sizes = [x.shape[a] for a in spatial_axes]
    if size is None:
        if isinstance(scale_factor, (int, float)):
            scale_factor = [scale_factor] * len(spatial_axes)
        size = [int(s * f) for s, f in zip(in_sizes, scale_factor)]
    elif isinstance(size, int):
        size = [size] * len(spatial_axes)
    size = [int(s) for s in size]

    method = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
              "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]
    if mode == "nearest" or not align_corners:
        new_shape = list(x.shape)
        for a, s in zip(spatial_axes, size):
            new_shape[a] = s
        return jax.image.resize(x, new_shape, method=method).astype(x.dtype)
    # align_corners=True: gather with explicit index mapping per axis
    out = x
    for a, s_out in zip(spatial_axes, size):
        s_in = out.shape[a]
        if s_out == s_in:
            continue
        if s_out == 1 or s_in == 1:
            idx = jnp.zeros((s_out,), jnp.float32)
        else:
            idx = jnp.linspace(0.0, s_in - 1, s_out)
        if method == "nearest":
            gathered = jnp.take(out, jnp.round(idx).astype(jnp.int32), axis=a)
        else:
            lo = jnp.clip(jnp.floor(idx).astype(jnp.int32), 0, s_in - 1)
            hi = jnp.clip(lo + 1, 0, s_in - 1)
            w = (idx - lo).astype(out.dtype)
            shape = [1] * out.ndim
            shape[a] = s_out
            w = w.reshape(shape)
            gathered = jnp.take(out, lo, axis=a) * (1 - w) + jnp.take(out, hi, axis=a) * w
        out = gathered
    return out.astype(x.dtype)


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, data_format="NCHW"):
    return interpolate(x, size, scale_factor, mode, align_corners, data_format)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (reference: paddle/phi/kernels/cpu/unfold_kernel.cc).
    x: [N, C, H, W] -> [N, C*kh*kw, L]."""
    del name
    def _pair(v):
        return (v, v) if isinstance(v, int) else tuple(v)
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    dh, dw = _pair(dilations)
    p = _pair(paddings) if not (isinstance(paddings, (list, tuple)) and len(paddings) == 4) else paddings
    if len(p) == 2:
        ph0 = ph1 = p[0]
        pw0 = pw1 = p[1]
    else:
        ph0, pw0, ph1, pw1 = p
    N, C, H, W = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (ph0, ph1), (pw0, pw1)))
    Ho = (H + ph0 + ph1 - dh * (kh - 1) - 1) // sh + 1
    Wo = (W + pw0 + pw1 - dw * (kw - 1) - 1) // sw + 1
    patches = lax.conv_general_dilated_patches(
        xp, (kh, kw), (sh, sw), "VALID", rhs_dilation=(dh, dw),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    # patches: [N, C*kh*kw, Ho, Wo]
    return patches.reshape(N, C * kh * kw, Ho * Wo)


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """col2im: inverse of unfold via scatter-add."""
    del name
    def _pair(v):
        return (v, v) if isinstance(v, int) else tuple(v)
    oh, ow = _pair(output_sizes)
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    dh, dw = _pair(dilations)
    ph, pw = _pair(paddings)
    N, CKK, L = x.shape
    C = CKK // (kh * kw)
    Ho = (oh + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    Wo = (ow + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    cols = x.reshape(N, C, kh, kw, Ho, Wo)
    out = jnp.zeros((N, C, oh + 2 * ph, ow + 2 * pw), x.dtype)
    for i in range(kh):
        for j in range(kw):
            hi = i * dh
            wj = j * dw
            out = out.at[:, :, hi:hi + sh * Ho:sh, wj:wj + sw * Wo:sw].add(cols[:, :, i, j])
    return out[:, :, ph:ph + oh, pw:pw + ow]


def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    if data_format == "NCHW":
        N, C, H, W = x.shape
        x = x.reshape(N, C // (r * r), r, r, H, W)
        x = x.transpose(0, 1, 4, 2, 5, 3)
        return x.reshape(N, C // (r * r), H * r, W * r)
    N, H, W, C = x.shape
    x = x.reshape(N, H, W, r, r, C // (r * r))
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(N, H * r, W * r, C // (r * r))


def pixel_unshuffle(x, downscale_factor, data_format="NCHW"):
    r = downscale_factor
    if data_format == "NCHW":
        N, C, H, W = x.shape
        x = x.reshape(N, C, H // r, r, W // r, r)
        x = x.transpose(0, 1, 3, 5, 2, 4)
        return x.reshape(N, C * r * r, H // r, W // r)
    N, H, W, C = x.shape
    x = x.reshape(N, H // r, r, W // r, r, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(N, H // r, W // r, C * r * r)


def channel_shuffle(x, groups, data_format="NCHW"):
    if data_format == "NCHW":
        N, C, H, W = x.shape
        x = x.reshape(N, groups, C // groups, H, W)
        x = x.transpose(0, 2, 1, 3, 4)
        return x.reshape(N, C, H, W)
    N, H, W, C = x.shape
    x = x.reshape(N, H, W, groups, C // groups)
    x = x.transpose(0, 1, 2, 4, 3)
    return x.reshape(N, H, W, C)


def label_smooth(label, prior_dist=None, epsilon=0.1):
    k = label.shape[-1]
    if prior_dist is None:
        return (1 - epsilon) * label + epsilon / k
    return (1 - epsilon) * label + epsilon * jnp.asarray(prior_dist)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.sqrt(jnp.sum(x1 * x1, axis=axis))
    n2 = jnp.sqrt(jnp.sum(x2 * x2, axis=axis))
    return dot / jnp.maximum(n1 * n2, eps)


def bilinear(x1, x2, weight, bias=None):
    # weight: [out, in1, in2]
    out = jnp.einsum("bi,oij,bj->bo", x1, jnp.asarray(weight), x2)
    if bias is not None:
        out = out + jnp.asarray(bias)
    return out


def class_center_sample(label, num_classes, num_samples, group=None):
    del group
    # Simplified deterministic variant: keep positives, fill with smallest ids.
    pos = jnp.unique(label, size=min(num_samples, num_classes), fill_value=num_classes)
    remap = jnp.searchsorted(pos, label)
    return remap, pos
