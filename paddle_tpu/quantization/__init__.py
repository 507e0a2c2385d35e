"""Quantization (reference: python/paddle/quantization/ — QAT fake-quant
framework, PTQ observers; kernels paddle/phi/kernels/.../quantize_*).

TPU design: fake-quant as straight-through-estimator ops (custom_vjp),
QuantConfig + QAT wrapper inserting FakeQuant layers around Linear/Conv;
PTQ observers collect absmax ranges. Round 2 adds REAL int8 execution
(quantize_to_int8 / int8_matmul / qlinear / QuantizedLinear): int8×int8
→int32 on the v5e MXU via preferred_element_type (its rate against
bf16 is not measured on the current installation).

Scale convention (ONE convention module-wide): scale = absmax, integer
value q ≈ x·qmax/scale, dequant = q·scale/qmax — what absmax_scale /
quantize_weights / dequantize and the int8 execution path all share.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..nn.layer.layers import Layer

__all__ = ["fake_quant", "dequantize", "quantize_weights", "AbsmaxObserver",
           "HistObserver", "FakeQuant", "QuantConfig", "QAT", "PTQ"]


def absmax_scale(x):
    """Symmetric per-tensor scale — THE quantization range used by fake-
    quant, weight quantization and observers alike (one floor constant)."""
    return jnp.maximum(jnp.max(jnp.abs(x)), 1e-8)


@jax.custom_vjp
def fake_quant(x, scale, bits=8):
    qmax = 2.0 ** (bits - 1) - 1
    q = jnp.clip(jnp.round(x / scale * qmax), -qmax, qmax)
    return q * scale / qmax


def _fq_fwd(x, scale, bits=8):
    return fake_quant(x, scale, bits), (x, scale)


def _fq_bwd(res, g):
    x, scale = res
    # straight-through: pass gradient inside the clip range, zero outside
    inside = (jnp.abs(x) <= scale).astype(g.dtype)
    return g * inside, jnp.zeros_like(scale), None


fake_quant.defvjp(_fq_fwd, _fq_bwd)


def quantize_weights(w, bits: int = 8):
    """Symmetric per-tensor int quantization. Returns (int_values, scale)."""
    qmax = 2.0 ** (bits - 1) - 1
    scale = absmax_scale(w)
    q = jnp.clip(jnp.round(w / scale * qmax), -qmax, qmax).astype(jnp.int8)
    return q, scale


def dequantize(q, scale, bits: int = 8):
    qmax = 2.0 ** (bits - 1) - 1
    return q.astype(jnp.float32) * scale / qmax


class AbsmaxObserver:
    """PTQ range observer (reference: quantization/observers/abs_max.py)."""

    def __init__(self, quant_bits: int = 8):
        self.bits = quant_bits
        self._absmax = 0.0

    def observe(self, x):
        self._absmax = max(self._absmax, float(jnp.max(jnp.abs(x))))
        return x

    @property
    def scale(self) -> float:
        return max(self._absmax, 1e-8)


class HistObserver:
    """Percentile histogram observer (reference:
    quantization/observers/hist.py): accumulates a fixed-bin histogram of
    |x| with range-doubling rebinning and picks the scale at the `percent`
    quantile of observed mass — robust to the activation outliers an
    absmax observer chases (one spike would otherwise blow up the scale
    and crush resolution for the bulk)."""

    def __init__(self, quant_bits: int = 8, bins: int = 2048,
                 percent: float = 0.999):
        import numpy as _np
        self.bits = quant_bits
        self.bins = bins
        self.percent = percent
        self._hist = _np.zeros(bins, _np.float64)
        self._range = 0.0

    def observe(self, x):
        import numpy as _np
        ax = _np.abs(_np.asarray(jax.device_get(x), _np.float32)).ravel()
        top = float(ax.max()) if ax.size else 0.0
        if top > self._range:
            # double the range until it covers, merging bin pairs so the
            # accumulated mass survives the re-binning
            new_range = max(self._range, 1e-8)
            while new_range < top:
                new_range *= 2.0
                half = self._hist.reshape(-1, 2).sum(axis=1)
                self._hist = _np.concatenate(
                    [half, _np.zeros(self.bins // 2)])
            self._range = new_range
        if self._range > 0 and ax.size:
            h, _ = _np.histogram(ax, bins=self.bins,
                                 range=(0.0, self._range))
            self._hist += h
        return x

    @property
    def scale(self) -> float:
        import numpy as _np
        total = self._hist.sum()
        if total <= 0:
            return 1e-8
        c = _np.cumsum(self._hist) / total
        idx = int(_np.searchsorted(c, self.percent))
        return max((idx + 1) / self.bins * self._range, 1e-8)


_OBSERVER_TYPES = {"abs_max": AbsmaxObserver, "hist": HistObserver}


class FakeQuant(Layer):
    """QAT fake-quant node with a learned-from-data running scale."""

    def __init__(self, bits: int = 8, momentum: float = 0.9):
        super().__init__()
        self.bits = bits
        self.momentum = momentum
        self.register_buffer("scale", jnp.asarray(1.0))

    def forward(self, x):
        if self.training:
            cur = absmax_scale(x)
            new = self.momentum * self.scale + (1 - self.momentum) * cur
            self.scale = new
        return fake_quant(x, jnp.asarray(self.scale), self.bits)


class QuantConfig:
    """(reference: quantization/config.py) — which layer types to quantize
    and with how many bits."""

    def __init__(self, activation_bits: int = 8, weight_bits: int = 8,
                 quantizable_layer_type=("Linear", "Conv2D")):
        self.activation_bits = activation_bits
        self.weight_bits = weight_bits
        self.types = tuple(quantizable_layer_type)


class _QuantWrapper(Layer):
    def __init__(self, inner: Layer, cfg: QuantConfig):
        super().__init__()
        self.inner = inner
        self.act_q = FakeQuant(cfg.activation_bits)
        self.w_bits = cfg.weight_bits

    def forward(self, x):
        x = self.act_q(x)
        w = self.inner.weight.value
        scale = absmax_scale(w)
        orig = w
        self.inner.weight.value = fake_quant(w, scale, self.w_bits)
        try:
            out = self.inner(x)
        finally:
            self.inner.weight.value = orig
        return out


class QAT:
    """Quantization-aware training driver (reference: quantization/qat.py
    QAT.quantize wraps eligible layers with fake-quant)."""

    def __init__(self, config: Optional[QuantConfig] = None):
        self.config = config or QuantConfig()

    def quantize(self, model: Layer) -> Layer:
        def convert(layer: Layer) -> Layer:
            for name, sub in list(layer._sub_layers.items()):
                if type(sub).__name__ in self.config.types and hasattr(
                        sub, "weight"):
                    layer._sub_layers[name] = _QuantWrapper(sub, self.config)
                else:
                    convert(sub)
            return layer
        return convert(model)

    def convert(self, model: Layer) -> Dict[str, tuple]:
        """Produce deploy weights: {param_name: (int8_values, scale)}."""
        out = {}
        for name, p in model.named_parameters():
            if p.value.ndim >= 2:
                out[name] = quantize_weights(p.value,
                                             self.config.weight_bits)
        return out


class _Observed(Layer):
    def __init__(self, inner, name, observer):
        super().__init__()
        self.inner = inner
        self._obs_name = name
        self._observer = observer

    def forward(self, x):
        self._observer.observe(x)
        return self.inner(x)


class PTQ:
    """Post-training quantization (reference: quantization/ptq.py):
    `quantize(model)` wraps eligible layers with activation observers,
    calibration forwards populate them, and `convert(model)` replaces each
    observed Linear with a W8A8 QuantizedLinear whose ACTIVATION scale is
    the calibrated one (static quantization — no per-call absmax at
    deploy time). observer: "abs_max" or "hist" (percentile)."""

    def __init__(self, config: Optional[QuantConfig] = None,
                 observer: str = "abs_max", **observer_kw):
        from ..enforce import enforce_in
        self.config = config or QuantConfig()
        enforce_in(observer, set(_OBSERVER_TYPES), op="PTQ",
                   observer=observer)
        self._obs_cls = _OBSERVER_TYPES[observer]
        self._obs_kw = observer_kw
        self.observers: Dict[str, object] = {}

    def quantize(self, model: Layer) -> Layer:
        def convert(layer: Layer, prefix=""):
            for name, sub in list(layer._sub_layers.items()):
                path = f"{prefix}.{name}" if prefix else name
                if type(sub).__name__ in self.config.types:
                    obs = self._obs_cls(self.config.activation_bits,
                                        **self._obs_kw)
                    self.observers[path] = obs
                    layer._sub_layers[name] = _Observed(sub, path, obs)
                else:
                    convert(sub, path)
            return layer
        return convert(model)

    def scales(self) -> Dict[str, float]:
        return {k: o.scale for k, o in self.observers.items()}

    def convert(self, model: Layer) -> Layer:
        """Calibrated deploy conversion: every observed Linear becomes a
        QuantizedLinear with static activation scale from its observer
        (per-output-channel weight scales). Non-Linear observed layers are
        unwrapped (their scales remain available via scales())."""
        from ..nn.layer.common import Linear

        def walk(layer: Layer):
            for name, sub in list(layer._sub_layers.items()):
                if isinstance(sub, _Observed):
                    inner = sub.inner
                    if isinstance(inner, Linear):
                        layer._sub_layers[name] = (
                            QuantizedLinear.from_linear(
                                inner, act_scale=sub._observer.scale))
                    else:
                        layer._sub_layers[name] = inner
                else:
                    walk(sub)
            return layer
        return walk(model)


# ---------------------------------------------------------------------------
# real int8 execution (round 2) — reference: paddle/phi/kernels/fusion/gpu
# quant_dequant + int8 matmul kernels (fused_multi_transformer_int8 etc.).
# On v5e the MXU runs int8 x int8 -> int32 at 2x the bf16 rate; this is the
# TPU-native int8 path, not a fake-quant simulation.
# ---------------------------------------------------------------------------

def quantize_to_int8(x, scale=None, axis=None):
    """Symmetric int8 quantization in the module's absmax convention
    (scale = absmax; dequant = q*scale/127 — interchangeable with
    quantize_weights/dequantize/observer scales). Pass axis= for
    per-channel scales computed here."""
    if scale is None:
        if axis is None:
            scale = absmax_scale(x)
        else:
            red = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
            scale = jnp.maximum(
                jnp.max(jnp.abs(x), axis=red, keepdims=True), 1e-8)
    q = jnp.clip(jnp.round(x / scale * 127.0), -127, 127).astype(jnp.int8)
    return q, scale


def int8_matmul(x_q, w_q, x_scale, w_scale, out_dtype=jnp.float32,
                psum_axis=None):
    """int8 @ int8 with int32 accumulation on the MXU, dequantized by the
    product of scales. x_scale: scalar (per-tensor); w_scale: scalar or
    per-output-channel (broadcasts on the last dim). psum_axis: for
    row-parallel (contraction-sharded) TP matmuls — psum the INT32
    accumulator across the axis before dequantizing, so the sharded
    product is bit-identical to the dense one (int32 partial sums are
    exact; a float psum of dequantized partials would reassociate the
    rounding)."""
    acc = jax.lax.dot_general(
        x_q, w_q, (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    if psum_axis is not None:
        acc = jax.lax.psum(acc, psum_axis)
    ws = jnp.reshape(jnp.asarray(w_scale), (-1,))  # [out] or [1]
    return (acc.astype(jnp.float32)
            * (jnp.asarray(x_scale) / 127.0) * (ws / 127.0)
            ).astype(out_dtype)


def qlinear(x, w_q, w_scale, bias=None, out_dtype=None, per_row=False,
            psum_axis=None):
    """Dynamic-activation-quant linear: quantize x per call, run the int8
    MXU matmul, dequantize (W8A8 dynamic — the llm.int8-style serving
    path). per_row=True scales each row (reduce only the contraction
    dim) instead of the whole tensor — REQUIRED when x batches
    independent requests (continuous batching): a per-tensor absmax would
    make one request's quantization grid depend on its co-scheduled
    batchmates' outliers. psum_axis: row-parallel TP — the per-row
    activation absmax is SHARED across the axis (pmax), each shard
    quantizes its slice on the common grid, and the int32 accumulator is
    psum'd before dequantization (see int8_matmul) — the sharded linear
    reproduces the dense int8 linear exactly."""
    out_dtype = out_dtype or x.dtype
    if per_row:
        x_scale = jnp.maximum(
            jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8)
        if psum_axis is not None:
            x_scale = jax.lax.pmax(x_scale, psum_axis)
        x_q, _ = quantize_to_int8(x, scale=x_scale)
    elif psum_axis is not None:
        x_scale = jax.lax.pmax(absmax_scale(x), psum_axis)
        x_q, _ = quantize_to_int8(x, scale=x_scale)
    else:
        x_q, x_scale = quantize_to_int8(x)
    out = int8_matmul(x_q, w_q, x_scale, w_scale, out_dtype=jnp.float32,
                      psum_axis=psum_axis)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(out_dtype)


class QuantizedLinear(Layer):
    """Weight-only-storage / W8A8-compute linear (reference:
    fused int8 matmul kernels). Construct from a trained Linear via
    from_linear(); weights live as int8 + per-output-channel scales.
    act_scale: optional STATIC activation scale (PTQ-calibrated) — when
    absent, activations quantize dynamically per call (absmax)."""

    def __init__(self, w_q, w_scale, bias=None, act_scale=None):
        super().__init__()
        self.register_buffer("w_q", w_q)
        self.register_buffer("w_scale", jnp.reshape(w_scale, (-1,)))
        if act_scale is not None:
            self.register_buffer("act_scale", jnp.asarray(act_scale))
        else:
            self.act_scale = None
        if bias is not None:
            self.register_buffer("bias", bias)
        else:
            self.bias = None

    @classmethod
    def from_linear(cls, linear, act_scale=None):
        w = jnp.asarray(linear.weight.value)  # [in, out]
        w_q, w_scale = quantize_to_int8(w, axis=1)
        b = (jnp.asarray(linear.bias.value)
             if getattr(linear, "bias", None) is not None else None)
        return cls(w_q, w_scale, b, act_scale=act_scale)

    def forward(self, x):
        if self.act_scale is not None:
            x_q, _ = quantize_to_int8(x, scale=self.act_scale)
            out = int8_matmul(x_q, self.w_q, self.act_scale, self.w_scale,
                              out_dtype=jnp.float32)
            if self.bias is not None:
                out = out + self.bias.astype(jnp.float32)
            return out.astype(x.dtype)
        return qlinear(x, self.w_q, self.w_scale, self.bias)


__all__ += ["quantize_to_int8", "int8_matmul", "qlinear", "QuantizedLinear"]


def convert_to_int8(model, skip=()):
    """Replace every nn.Linear in `model` (in place, recursively) with a
    W8A8 QuantizedLinear built from its trained weights — the deploy-time
    int8 path the reference reaches through fused int8 kernels +
    config.enable_tensorrt_engine(precision_mode=Int8). `skip`: substring
    names to leave in fp (e.g. ("head",) for a sensitive output layer).
    Returns the model."""
    from ..nn.layer.common import Linear

    def walk(layer, prefix=""):
        for name, sub in list(layer._sub_layers.items()):
            full = f"{prefix}.{name}" if prefix else name
            if any(s in full for s in skip):
                continue
            if isinstance(sub, Linear):
                layer._sub_layers[name] = QuantizedLinear.from_linear(sub)
            else:
                walk(sub, full)

    walk(model)
    return model


__all__ += ["convert_to_int8"]

# delayed-scaling e4m3/e5m2 TRAINING tier (fp8_dot / Fp8Linear / amax-meta
# state) — submodule import only: fp8.py is jax-pure and must stay
# importable from the functional model paths without the Layer surface
from . import fp8  # noqa: E402,F401

__all__ += ["fp8"]
