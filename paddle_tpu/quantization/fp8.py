"""FP8 mixed-precision training with delayed scaling (ISSUE 3 tentpole).

Modern TPU/XLA lowers scaled fp8 dots at roughly 2x the bf16 MXU rate; the
remaining step-time lever after the comm-overlap work is precision. This
module provides the training-side fp8 path the reference reaches through
its low-precision tier (the int8 QAT surface lives in
``quantization/__init__``; this is the e4m3/e5m2 TRAINING analogue):

* ``fp8_dot(x, w, site)`` — a custom_vjp GEMM: forward operands quantize to
  **e4m3**, the backward cotangent quantizes to **e5m2** (wider range for
  gradients), every dot accumulates **fp32** via preferred_element_type,
  and outputs dequantize by the product of per-tensor scales. BOTH backward
  GEMMs (dx and dw) run on fp8 operands.

* **Delayed scaling** — quantization scales are not computed from the
  current tensor (that would serialize an extra absmax reduction before
  every GEMM); they come from a rolling **amax history** of previous steps
  (Transformer-Engine-style). The observed amaxes ride OUT of the backward
  as the cotangents of the scale arguments: ``fp8_dot``'s vjp returns
  max|x|, max|w|, max|dy| in the grad slots of the three scales, so one
  ``jax.value_and_grad(loss, argnums=(0, 1))`` over (params, scales)
  yields param grads AND this step's amax observations with zero extra
  passes. ``update_fp8_meta`` then rotates the history and derives the
  next step's scales.

* **State threading** — the (scale, amax_history) pytree is functional
  state. The hybrid engine carries it as ``opt_state["fp8_meta"]`` exactly
  the way the int8 error-feedback residuals ride ``opt_state["comm_ef"]``
  (models/hybrid_engine.py), so the step signature and checkpoint surface
  stay (params, state, batch..., lr).

* **Remat composition** — the fwd tags the quantized operands with
  ``checkpoint_name`` so a selective-remat policy can keep them and the
  backward reuses the quantized bytes instead of re-quantizing. jax
  0.4.37's save_only_these_names mis-saves raw float8 buffers (NaNs on
  replay), so the tagged value is the **uint8 bitcast** of the fp8 payload
  (``FP8_REMAT_NAMES``), bitcast back at the consumer — same trick
  production Neuron/JAX stacks use for fp8 storage dtypes.

* **Sharding** — per-tensor scales are replicated over dp/mp; under TP each
  rank observes its local shard's amax and the engine reduces with
  lax.pmax over the replicated axes before the meta update, so every rank
  derives identical next-step scales. Stacked-layer models carry scales
  with a leading [L] axis that rides the same lax.scan (and 'pp'
  sharding) as the stacked block params — per-layer scales, and the scan
  keeps each layer's amax cotangent separate instead of summing them.

CPU note: jnp float8 dtypes are emulated (the dot upcasts internally), so
the bookkeeping — scale updates, history rotation, quantization grids —
is exactly the TPU math and fully testable without hardware; only the
speed win needs the MXU.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

__all__ = ["E4M3", "E5M2", "E4M3_MAX", "E5M2_MAX", "FP8_REMAT_NAMES",
           "role_fmax",
           "fp8_enabled", "quantize_fp8", "dequantize_fp8", "fp8_dot",
           "site_mm", "Fp8Linear", "init_fp8_meta", "scales_of",
           "update_fp8_meta", "fp8_meta_specs", "fp8_plan",
           "resolve_fp8_plan", "make_fp8_train_step"]

E4M3 = jnp.float8_e4m3fn
E5M2 = jnp.float8_e5m2
E4M3_MAX = float(jnp.finfo(E4M3).max)   # 448
E5M2_MAX = float(jnp.finfo(E5M2).max)   # 57344

# checkpoint_name tags on the (uint8-bitcast) quantized operands — add to
# a save_only_these_names remat policy so backward reuses the quantized
# bytes instead of re-running the quantize (models/gpt.py dense_forward
# appends these to its remat_save when fp8 is on)
FP8_REMAT_NAMES = ("fp8_qx", "fp8_qw")

_ROLES = ("x", "w", "g")        # fwd activation, fwd weight, bwd gradient
_TINY = 1e-12                   # amax floor — a scale must never be 0


def _fmax(role: str) -> float:
    return E5M2_MAX if role == "g" else E4M3_MAX


def role_fmax(role: str) -> float:
    """Public form of the per-role dtype max (fwd operands are e4m3, the
    bwd cotangent e5m2) — the numerics telemetry derives each site's
    scale-saturation ratio amax / (scale x fmax) from it
    (observability.numerics.fp8_site_health)."""
    return _fmax(role)


def fp8_enabled() -> bool:
    """The fp8 flag surface: FLAGS_fp8, or an active amp.auto_cast
    (level="O3") context — O3 is 'O2 plus fp8 GEMMs'."""
    from ..flags import flag
    if flag("fp8"):
        return True
    from ..amp.auto_cast import amp_state
    st = amp_state()
    return bool(st.enabled and st.level == "O3")


def quantize_fp8(x, scale, dtype=E4M3):
    """Saturating cast to fp8 in the dequant-scale convention:
    q = cast(clip(x / scale)), dequant = q * scale. With delayed scaling
    `scale` ≈ amax/fmax from the history, so a fresh outlier saturates (one
    step) instead of overflowing to inf."""
    m = float(jnp.finfo(dtype).max)
    y = x.astype(jnp.float32) / scale.astype(jnp.float32)
    return jnp.clip(y, -m, m).astype(dtype)


def dequantize_fp8(q, scale):
    return q.astype(jnp.float32) * scale.astype(jnp.float32)


def _tag8(q, name):
    """checkpoint_name the fp8 payload as uint8 (see module docstring) and
    hand back the fp8 view."""
    b = checkpoint_name(lax.bitcast_convert_type(q, jnp.uint8), name)
    return lax.bitcast_convert_type(b, q.dtype)


@jax.custom_vjp
def _fp8_dot(x, w, sx, sw, sg):
    qx = quantize_fp8(x, sx, E4M3)
    qw = quantize_fp8(w, sw, E4M3)
    acc = lax.dot_general(qx, qw, (((x.ndim - 1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    return (acc * (sx * sw)).astype(x.dtype)


def _fp8_dot_fwd(x, w, sx, sw, sg):
    qx = _tag8(quantize_fp8(x, sx, E4M3), "fp8_qx")
    qw = _tag8(quantize_fp8(w, sw, E4M3), "fp8_qw")
    acc = lax.dot_general(qx, qw, (((x.ndim - 1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    amax_x = jnp.max(jnp.abs(x)).astype(jnp.float32)
    amax_w = jnp.max(jnp.abs(w)).astype(jnp.float32)
    out = (acc * (sx * sw)).astype(x.dtype)
    # zero-size dtype witnesses: residuals must be jax types, and the
    # cotangents must come back in x/w's dtypes
    wit_x = jnp.zeros((0,), x.dtype)
    wit_w = jnp.zeros((0,), w.dtype)
    return out, (qx, qw, sx, sw, sg, amax_x, amax_w, wit_x, wit_w)


def _fp8_dot_bwd(res, dy):
    qx, qw, sx, sw, sg, amax_x, amax_w, wit_x, wit_w = res
    x_dtype, w_dtype, xnd = wit_x.dtype, wit_w.dtype, qx.ndim
    # observe BEFORE quantizing: amax of the real cotangent feeds the next
    # step's e5m2 scale
    amax_g = jnp.max(jnp.abs(dy)).astype(jnp.float32)
    qdy = quantize_fp8(dy, sg, E5M2)
    # dx = dy @ w^T — e5m2 x e4m3, fp32 accumulation
    dx = lax.dot_general(qdy, qw, (((qdy.ndim - 1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32) * (sg * sw)
    # dw = x^T @ dy — contract every batch dim
    bd = tuple(range(xnd - 1))
    dw = lax.dot_general(qx, qdy, ((bd, bd), ((), ())),
                         preferred_element_type=jnp.float32) * (sx * sg)
    # the scale slots carry the amax OBSERVATIONS, not real gradients —
    # value_and_grad over (params, scales) returns them for free; scales
    # must therefore never be updated by gradient descent, only by
    # update_fp8_meta
    return (dx.astype(x_dtype), dw.astype(w_dtype), amax_x, amax_w, amax_g)


_fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def fp8_dot(x, w, site: Dict[str, Any]):
    """fp8 GEMM for one site: x [..., K] @ w [K, N] with the site's
    {"x", "w", "g"} scalar scales (from ``scales_of(meta)``). Grad w.r.t.
    `site` is the {"x", "w", "g"} amax observation dict."""
    return _fp8_dot(x, w, site["x"], site["w"], site["g"])


def site_mm(fp8, site: str):
    """(a, b) -> a @ b for one named GEMM site: plain dot when `fp8` (the
    layer's {site: {x, w, g}} scale dict) is None — bitwise-unchanged
    baseline — fp8_dot with that site's delayed scales otherwise. The one
    routing helper every model block body shares (gpt/llama)."""
    if fp8 is None:
        return lambda a, b: a @ b
    return lambda a, b: fp8_dot(a, b, fp8[site])


# ---------------------------------------------------------------------------
# Delayed-scaling meta state
# ---------------------------------------------------------------------------
def init_fp8_meta(sites: Sequence[str], num_layers: int = None,
                  history_len: int = None) -> Dict[str, Any]:
    """Fresh (scale, amax_history) pytree for `sites`. num_layers: stack a
    leading [L] axis so the scales ride a lax.scan over stacked block
    params (None = unstacked scalars). Scales start at 1/fmax (assume
    amax 1.0); the first real amax lands after step 1 and every scale is
    data-derived from step 2 on."""
    if history_len is None:
        from ..flags import flag
        history_len = int(flag("fp8_amax_history"))
    lead = () if num_layers is None else (int(num_layers),)
    scale = {s: {r: jnp.full(lead, 1.0 / _fmax(r), jnp.float32)
                 for r in _ROLES} for s in sites}
    hist = {s: {r: jnp.zeros(lead + (history_len,), jnp.float32)
                for r in _ROLES} for s in sites}
    return {"scale": scale, "amax_history": hist}


def scales_of(meta):
    """The differentiable scale tree to pass into the loss (site → role →
    scale); its 'gradient' is the amax-observation tree."""
    return meta["scale"]


def update_fp8_meta(meta, amax_obs, margin: int = None):
    """Rotate each site/role's amax history with this step's observation
    and derive the next step's scale from the window max:
    scale = 2^margin * max(history) / fmax (delayed scaling — the scale a
    step USES always predates the tensors it quantizes). All-zero history
    (nothing observed yet) keeps the current scale.

    Observation semantics: when one scale leaf feeds SEVERAL GEMM
    applications in a step (the pipelined hybrid path applies each block
    once per microbatch time step), the cotangents SUM — the observation
    is then an additive upper bound (<= T x true amax for T
    applications), not the exact amax. That is deliberate: fp grids are
    scale-invariant inside the normal range, so a small constant
    overestimate costs ZERO mantissa precision — only log2(T) bits of
    e4m3's ~2^18 dynamic-range headroom (tests assert loss parity holds
    through the pipelined path)."""
    if margin is None:
        from ..flags import flag
        margin = int(flag("fp8_margin"))
    new_scale, new_hist = {}, {}
    for site, roles in meta["amax_history"].items():
        new_scale[site], new_hist[site] = {}, {}
        for role, hist in roles.items():
            a = jnp.maximum(amax_obs[site][role].astype(jnp.float32), 0.0)
            h = jnp.concatenate([a[..., None], hist[..., :-1]], axis=-1)
            amax = jnp.max(h, axis=-1)
            scale = (2.0 ** margin) * jnp.maximum(amax, _TINY) / _fmax(role)
            new_scale[site][role] = jnp.where(
                amax > 0.0, scale, meta["scale"][site][role])
            new_hist[site][role] = h
    return {"scale": new_scale, "amax_history": new_hist}


def fp8_meta_specs(sites: Sequence[str], stacked_axis=None):
    """PartitionSpec tree matching init_fp8_meta's structure: stacked [L]
    scales shard their layer axis over `stacked_axis` (the pipeline axis,
    like the stacked block params); history leaves add a replicated
    window dim. Unstacked meta replicates."""
    from jax.sharding import PartitionSpec as P
    sspec = P() if stacked_axis is None else P(stacked_axis)
    hspec = P() if stacked_axis is None else P(stacked_axis, None)
    return {"scale": {s: {r: sspec for r in _ROLES} for s in sites},
            "amax_history": {s: {r: hspec for r in _ROLES} for s in sites}}


def fp8_plan(sites: Sequence[str], num_layers: int = None,
             stacked_axis=None, amax_axes=()) -> Dict[str, Any]:
    """The fp8 contract models hand to hybrid_engine.build_train_step(fp8=):
    `init` builds the meta, `specs` shards it (meta rides
    opt_state["fp8_meta"]), `axes` are the mesh axes the per-rank amax
    observations pmax over before the meta update (the axes scales are
    REPLICATED on — dp/mp, never the pipeline axis: pp shards the layer
    dim, and a pmax over it would mix different layers' amaxes)."""
    return {
        "init": functools.partial(init_fp8_meta, tuple(sites), num_layers),
        "specs": fp8_meta_specs(tuple(sites), stacked_axis),
        "axes": tuple(amax_axes),
    }


def resolve_fp8_plan(fp8_arg, sites: Sequence[str], num_layers: int,
                     stacked_axis=None, amax_axes=()):
    """ONE resolution of a model builder's fp8= argument ("auto" reads
    FLAGS_fp8 / amp O3; bool forces) to an fp8_plan or None — gpt and
    llama build_hybrid_train_step both route through here so the flag
    semantics can never drift between model families."""
    on = fp8_enabled() if fp8_arg == "auto" else bool(fp8_arg)
    if not on:
        return None
    return fp8_plan(sites, num_layers, stacked_axis=stacked_axis,
                    amax_axes=amax_axes)


# ---------------------------------------------------------------------------
# Dense-path train step (the benchmark's `fp8` control + tests; the hybrid
# engine has its own fp8_meta threading)
# ---------------------------------------------------------------------------
def make_fp8_train_step(loss_fn, optimizer, donate: bool = True):
    """jitted step over a dense (single-program) fp8 loss.

    loss_fn(params, scales, tokens, labels) -> scalar. Returns
    step(params, opt_state, fp8_meta, tokens, labels, lr) ->
    (params, opt_state, fp8_meta, loss). params, opt_state AND fp8_meta
    are donated — the meta carry must not cost a second buffer copy any
    more than the moments do (tests/test_donation_guard.py asserts)."""
    def step(params, opt_state, fp8_meta, tokens, labels, lr):
        loss, (gp, amax) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            params, scales_of(fp8_meta), tokens, labels)
        new_params, new_state = optimizer.apply(params, gp, opt_state, lr)
        new_meta = update_fp8_meta(fp8_meta, amax)
        return new_params, new_state, new_meta, loss

    if donate:
        return jax.jit(step, donate_argnums=(0, 1, 2))
    return jax.jit(step)


# ---------------------------------------------------------------------------
# Eager layer surface
# ---------------------------------------------------------------------------
class Fp8Linear:
    """Eager Fp8Linear built on the same fp8_dot/meta machinery (the
    nn-surface analogue of QuantizedLinear for training). Forward observes
    x/w amax eagerly and rotates its own buffers; the gradient amax ('g'
    role) updates only when the layer runs inside the functional path —
    eager autograd is out of scope here, so `g` keeps its init scale.
    Construct from an existing nn.Linear via from_linear()."""

    def __init__(self, weight, bias=None, history_len: int = None):
        self.weight = weight              # [in, out] jax array
        self.bias = bias
        self.meta = init_fp8_meta(("gemm",), history_len=history_len)

    @classmethod
    def from_linear(cls, linear, history_len: int = None):
        w = jnp.asarray(linear.weight.value)
        b = (jnp.asarray(linear.bias.value)
             if getattr(linear, "bias", None) is not None else None)
        return cls(w, b, history_len=history_len)

    def __call__(self, x):
        site = scales_of(self.meta)["gemm"]
        out = fp8_dot(x, self.weight.astype(x.dtype), site)
        if self.bias is not None:
            out = out + self.bias.astype(out.dtype)
        amax = {"gemm": {
            "x": jnp.max(jnp.abs(x)).astype(jnp.float32),
            "w": jnp.max(jnp.abs(self.weight)).astype(jnp.float32),
            # no eager backward to observe dy: re-circulate the window max
            # so the g scale at least never decays to the init value
            "g": jnp.max(self.meta["amax_history"]["gemm"]["g"], axis=-1),
        }}
        self.meta = update_fp8_meta(self.meta, amax)
        return out
