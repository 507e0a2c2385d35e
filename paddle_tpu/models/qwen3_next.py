"""Qwen3-Next (Qwen, `model_type: qwen3_next`) on the serving path: a layer
PATTERN — of every `full_attention_interval` layers the last is gated
softmax attention, the others Gated DeltaNet (linear attention with a
per-slot recurrent state) — and routed experts with a shared expert in
EVERY layer.

    RMS(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)          (zero-centred)
    x = x + mixer_i(RMS(x; ln1));   x = x + moe(RMS(x; ln2))

  attention layer:  [q | gate] = u Wq per head; q, k RMS-normed per head;
      rotate-half RoPE on the first `rotary_dim` of each head;
      out = (softmax-attention * sigmoid(gate)) Wo
  Gated DeltaNet:   [q | k | v | z] per key head = u W_qkvz, [b | a] = u W_ba;
      silu(causal conv) over [q | k | v]; beta = sigmoid(b),
      g = -exp(A_log) softplus(a + dt_bias); the gated delta rule
      (`kernels/pallas/gdn.py`); out = (RMS(o; w_norm) * silu(z)) W_out
      (w_norm a PLAIN gain)
  experts: p = softmax(f Wr) over ALL `num_experts` in float32; top-k by p,
      weights p / sum(top-k p); each pick a gated feed-forward; plus
      sigmoid(f w_sg) * shared(f)

The chip may hold a SHARE of the experts, ``experts_held = (lo, hi)``: the
router keeps its width, its k picks and the renormalisation over all k;
the layer adds the weighted outputs of the picks it holds and the whole
shared expert, and what the others would have added is left out (the
partial sum goes on to the next layer; nothing stands in for the other
chips). No token is dropped: there is no capacity.

This module is the model's side of the serving seam
(`inference.serving.serving_model`). Parameter tree (every leaf in
``cfg.param_dtype``): ``embed [V, H]``, ``head_w [H, V]``, ``lnf_g [H]``;
``blocks`` one dict a RUN of the pattern, leaves ``[periods, run, ...]``:

    linear:    ln1_g  in_qkvz_w [H, 2 kd + 2 vd]  in_ba_w [H, 2 Hv]
               conv_w [K, 2 kd + vd]  A_log, dt_bias [Hv]  norm_w [dv]
               out_w [vd, H]
    attention: ln1_g  q_w [H, hq 2 D]  k_w, v_w [H, hkv D]
               q_norm, k_norm [D]  o_w [hq D, H]
    both:      ln2_g  router_w [H, E]  shared_gate_w, shared_up_w [H, Fs]
               shared_down_w [Fs, H]  shared_sg_w [H]

and ``experts``, one dict a run, taken WHOLE by the expert kernel (layer
and expert ride scalar prefetch; a layer scan that sliced them would copy
1.6 GB a layer): ``gate_w, up_w [periods * run, E_held, H, F]``,
``down_w [periods * run, E_held, F, H]``.

Left out: the multi-token-prediction module (no key of the published
config gives its shape). Training is not here.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ..enforce import enforce
from ..kernels.pallas import moe as M
from ..kernels.pallas.gdn import gdn_scan
from ..kernels.pallas.ssm import ssm_conv
from ..observability.trace import SCOPES

__all__ = ["Qwen3NextConfig", "init_params", "Serving", "state_shapes",
           "moe_layer"]


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936            # the rows held here
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    linear_conv: int = 4
    num_experts: int = 512              # the router's width
    experts_per_tok: int = 10
    moe_ffn: int = 512
    shared_ffn: int = 512
    experts_held: Tuple[int, int] = (0, 512)
    ssm_chunk: int = 128                # the chunk scan's longest chunk
    router_dtype: Any = jnp.float32     # the router's product and softmax
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        self.experts_held = tuple(int(e) for e in self.experts_held)
        lo, hi = self.experts_held
        enforce(self.num_layers % self.full_attention_interval == 0,
                "the depth must be whole periods of the layer pattern",
                op="Qwen3NextConfig", num_layers=self.num_layers,
                full_attention_interval=self.full_attention_interval)
        enforce(self.num_heads % self.num_kv_heads == 0
                and self.linear_value_heads % self.linear_key_heads == 0,
                "query (value) heads must divide into the KV (key) heads",
                op="Qwen3NextConfig")
        enforce(0 <= lo < hi <= self.num_experts,
                "experts_held must be a range of the router's experts",
                op="Qwen3NextConfig", experts_held=self.experts_held)

    @property
    def periods(self):
        return self.num_layers // self.full_attention_interval

    @property
    def key_dim(self):
        return self.linear_key_heads * self.linear_key_dim

    @property
    def value_dim(self):
        return self.linear_value_heads * self.linear_value_dim

    @property
    def conv_dim(self):
        return 2 * self.key_dim + self.value_dim

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def serving_model(self):
        return Serving


def _runs(cfg):
    return (("linear", cfg.full_attention_interval - 1), ("attention", 1))


def state_shapes(cfg, slots):
    """Shapes of the two per-slot buffers, one entry a LINEAR layer: the
    state [L_lin, slots, Hv, dk, dv] and the conv tail
    [L_lin, K-1, slots, channels]."""
    n = cfg.periods * (cfg.full_attention_interval - 1)
    return ((n, slots, cfg.linear_value_heads, cfg.linear_key_dim,
             cfg.linear_value_dim),
            (n, cfg.linear_conv - 1, slots, cfg.conv_dim))


def init_params(cfg, key, std=0.02):
    """The program's own initialiser (tests and examples; the benchmark
    makes its seeded tree itself): N(0, std) matrices and zero-centred
    gains, w_norm 1 + N(0, std), conv taps U(-1/2, 1/2),
    A_log = log U[1, 16], dt_bias the inverse softplus of a log-uniform
    step in [1e-3, 0.1]."""
    P, H, V = cfg.periods, cfg.hidden_size, cfg.vocab_size
    hq, hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hv, K = cfg.linear_value_heads, cfg.linear_conv
    E, F, Fs = cfg.num_experts, cfg.moe_ffn, cfg.shared_ffn
    held = cfg.experts_held[1] - cfg.experts_held[0]
    keys = iter(jax.random.split(key, 64))
    dt = cfg.param_dtype

    def normal(*shape, mean=0.0):
        return (mean + std * jax.random.normal(next(keys), shape,
                                               jnp.float32)).astype(dt)

    def moe(n):
        return {"ln2_g": normal(P, n, H), "router_w": normal(P, n, H, E),
                "shared_gate_w": normal(P, n, H, Fs),
                "shared_up_w": normal(P, n, H, Fs),
                "shared_down_w": normal(P, n, Fs, H),
                "shared_sg_w": normal(P, n, H)}

    def experts(n):
        return {"gate_w": normal(P * n, held, H, F),
                "up_w": normal(P * n, held, H, F),
                "down_w": normal(P * n, held, F, H)}

    n = cfg.full_attention_interval - 1
    step = jnp.exp(jax.random.uniform(next(keys), (P, n, Hv), jnp.float32,
                                      math.log(1e-3), math.log(0.1)))
    linear = {
        "ln1_g": normal(P, n, H),
        "in_qkvz_w": normal(P, n, H, 2 * cfg.key_dim + 2 * cfg.value_dim),
        "in_ba_w": normal(P, n, H, 2 * Hv),
        "conv_w": jax.random.uniform(next(keys), (P, n, K, cfg.conv_dim),
                                     jnp.float32, -0.5, 0.5).astype(dt),
        "A_log": jnp.log(jax.random.uniform(next(keys), (P, n, Hv),
                                            jnp.float32, 1.0, 16.0)
                         ).astype(dt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "norm_w": normal(P, n, cfg.linear_value_dim, mean=1.0),
        "out_w": normal(P, n, cfg.value_dim, H), **moe(n)}
    attention = {
        "ln1_g": normal(P, 1, H), "q_w": normal(P, 1, H, hq * 2 * D),
        "k_w": normal(P, 1, H, hkv * D), "v_w": normal(P, 1, H, hkv * D),
        "q_norm": normal(P, 1, D), "k_norm": normal(P, 1, D),
        "o_w": normal(P, 1, hq * D, H), **moe(1)}
    return {"embed": normal(V, H), "blocks": (linear, attention),
            "experts": (experts(n), experts(1)),
            "lnf_g": normal(H), "head_w": normal(H, V)}


# -- the pieces ---------------------------------------------------------------
_F32 = jnp.float32


def _rms(x, g, eps):
    """The zero-centred RMS norm: the stored gain starts at 0."""
    xf = x.astype(_F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * (1.0 + g.astype(_F32))).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rope(x, pos, theta, rot):
    """Rotate-half RoPE on dims [0, rot) of each head, the rest passing
    through; x: [B, T, heads, D], pos: [B, T]."""
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=_F32) / rot)
    ang = pos.astype(_F32)[..., None] * inv                  # [B, T, rot/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    xr = x[..., :rot].astype(_F32)
    turned = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([(xr * cos + turned * sin).astype(x.dtype),
                            x[..., rot:]], -1)


@jax.named_scope(SCOPES.moe_shared)
def shared_expert(p, f, cfg):
    """sigmoid(f w_sg) * shared(f), float32; every chip computes it whole."""
    dt = cfg.dtype
    act = (_silu((f @ p["shared_gate_w"].astype(dt)).astype(_F32))
           * (f @ p["shared_up_w"].astype(dt)).astype(_F32)).astype(dt)
    shared = (act @ p["shared_down_w"].astype(dt)).astype(_F32)
    gate = jnp.dot(f, p["shared_sg_w"].astype(dt)[:, None],
                   preferred_element_type=_F32)
    return jax.nn.sigmoid(gate) * shared


def moe_layer(p, f, experts, layer, cfg):
    """The expert layer on the normed tokens f: [T, H]. Returns (y [T, H]
    in cfg.dtype, ids [T, k] int16 — the router's picks over ALL experts
    —, stats int32: `Serving.route_stats` names its columns)."""
    lo, hi = cfg.experts_held
    with jax.named_scope(SCOPES.moe_route):
        logits = jnp.dot(f, p["router_w"].astype(cfg.dtype),
                         preferred_element_type=jnp.dtype(cfg.router_dtype))
        probs = jax.nn.softmax(logits, axis=-1).astype(_F32)
        top, ids = jax.lax.top_k(probs, cfg.experts_per_tok)
        weights = top / jnp.sum(top, -1, keepdims=True)
        plan = M.tiles(M.plan(ids, lo, hi),
                       M.tile_rows(*ids.shape, cfg.num_experts))
        stats = M.pass_stats(plan)
    with jax.named_scope(SCOPES.moe_experts):
        y_pad = M.grouped_ffn(f, experts["gate_w"], experts["up_w"],
                              experts["down_w"], layer, plan)
    with jax.named_scope(SCOPES.moe_route):
        y = M.combine(y_pad, weights, plan)
    y = y + shared_expert(p, f, cfg)
    return y.astype(cfg.dtype), ids.astype(jnp.int16), stats


class Serving:
    """What the serving step asks of a model (`inference.serving`'s
    `GPTServing` states the seam). `pattern` gives one period's runs of
    layers; `recurrent` the per-slot state of the linear layers; `routed`
    that `block_math` takes the run's experts whole and hands back what
    its router chose."""

    recurrent = True
    routed = True
    route_stats = M.PASS_STATS   # `moe_layer`'s stats, by column
    latent = False
    state_shapes = staticmethod(state_shapes)
    pattern = staticmethod(_runs)

    @staticmethod
    def prologue(cfg):
        return ("attention", 0)     # no leading layers of another kind

    @staticmethod
    def routed_layers(cfg):
        return cfg.num_layers           # every layer has a router

    @staticmethod
    def kv_layers(cfg):
        return cfg.periods              # one attention layer a period

    @staticmethod
    def positions(pos, cfg):
        return pos                      # RoPE: no table to stay inside

    @staticmethod
    @jax.named_scope(SCOPES.embed)
    def embed(params, tokens, pos, cfg):
        return jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)

    @staticmethod
    def qkv(p, x, pos, cfg, mp_axis=None):
        """An attention layer's q [B, T, hq, D], k, v [B, T, hkv, D] (q, k
        normed per head, RoPE at `pos` on the rotary dims) and the
        output gate [B, T, hq * D], which `block_math` applies."""
        B, T, _ = x.shape
        hq, hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.dtype
        with jax.named_scope(SCOPES.qkv):
            u = _rms(x, p["ln1_g"], cfg.rms_norm_eps)
            qg = (u @ p["q_w"].astype(dt)).reshape(B, T, hq, 2 * D)
            q, gate = qg[..., :D], qg[..., D:].reshape(B, T, hq * D)
            k = (u @ p["k_w"].astype(dt)).reshape(B, T, hkv, D)
            v = (u @ p["v_w"].astype(dt)).reshape(B, T, hkv, D)
            q = _rms(q, p["q_norm"], cfg.rms_norm_eps)
            k = _rms(k, p["k_norm"], cfg.rms_norm_eps)
        with jax.named_scope(SCOPES.rope):
            q = _rope(q, pos, cfg.rope_theta, cfg.rotary_dim)
            k = _rope(k, pos, cfg.rope_theta, cfg.rotary_dim)
        return q, k, v, gate

    @staticmethod
    def mixer(p, x, ssm, layer, plan, cfg):
        """A Gated DeltaNet layer's mixer over the packed rows x:
        [1, T, H] (the residual stream: the layer norms it itself), from
        and into the slots' state. ssm: (state, tail) as `state_shapes`;
        plan: `ragged_step.ragged_pass`'s. Returns (m [1, T, H] before the
        residual sum, (state, tail))."""
        state, tail = ssm
        Hk, Hv = cfg.linear_key_heads, cfg.linear_value_heads
        dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
        kd, vd, rep = cfg.key_dim, cfg.value_dim, Hv // Hk
        row_of, off_of, q_lens = plan["row_of"], plan["off_of"], plan["q_lens"]
        tile_idx = plan["tile_idx"]
        R, c_att = tile_idx.shape
        T = x.shape[1]
        dt = cfg.dtype
        with jax.named_scope(SCOPES.gdn_in):
            u = _rms(x[0], p["ln1_g"], cfg.rms_norm_eps)
            # the projection's outputs are laid out per KEY head
            qkvz = (u @ p["in_qkvz_w"].astype(dt)).reshape(
                T, Hk, 2 * dk + 2 * rep * dv)
            ba = (u @ p["in_ba_w"].astype(dt)).reshape(T, Hk, 2 * rep)
            z = qkvz[..., 2 * dk + rep * dv:].reshape(T, Hv, dv)
            b, a = ba[..., :rep].reshape(T, Hv), ba[..., rep:].reshape(T, Hv)
            mixed = jnp.concatenate(
                [qkvz[..., :dk].reshape(T, kd),
                 qkvz[..., dk:2 * dk].reshape(T, kd),
                 qkvz[..., 2 * dk:2 * dk + rep * dv].reshape(T, vd)], -1)
        with jax.named_scope(SCOPES.gdn_conv):
            mixed, tail = ssm_conv(mixed, p["conv_w"], None, tail, layer,
                                   row_of, off_of, plan["starts"], q_lens,
                                   plan["reset"])
        with jax.named_scope(SCOPES.gdn_scan):
            live = (jnp.arange(c_att)[None, :] < q_lens[:, None])[..., None]
            beta = jax.nn.sigmoid(b.astype(_F32))
            g = -jnp.exp(p["A_log"].astype(_F32)) * jax.nn.softplus(
                a.astype(_F32) + p["dt_bias"].astype(_F32))      # [T, Hv]
            tiles = mixed[tile_idx]                          # [R, c, conv]
            o_t, state = gdn_scan(
                tiles[..., :kd].reshape(R, c_att, Hk, dk),
                tiles[..., kd:2 * kd].reshape(R, c_att, Hk, dk),
                tiles[..., 2 * kd:].reshape(R, c_att, Hv, dv),
                jnp.where(live, g[tile_idx], 0.0),
                jnp.where(live, beta[tile_idx], 0.0),
                state, layer, q_lens, plan["reset"])
            real = (off_of < q_lens[row_of])[:, None, None]
            o = jnp.where(real, o_t[row_of, jnp.minimum(off_of, c_att - 1)],
                          0.0)                               # [T, Hv, dv]
        with jax.named_scope(SCOPES.gdn_out):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                  + cfg.rms_norm_eps)
            y = (o * p["norm_w"].astype(_F32) * _silu(z.astype(_F32))
                 ).astype(dt).reshape(T, vd)
            m = y @ p["out_w"].astype(dt)
        return m[None], (state, tail)

    @staticmethod
    def block_math(p, x, attn, mixed, cfg, mp_axis=None, *, experts, layer):
        """The layer after its mixer: a linear layer's `mixed` is the
        mixer's output; an attention layer's is the output gate `qkv`
        returned, applied to `attn` before the output projection. Then
        the expert layer. Returns (x, (ids, stats)) as `moe_layer`."""
        B, T, H = x.shape
        if attn is not None:
            with jax.named_scope(SCOPES.proj_mlp):
                gated = (attn.reshape(B, T, -1).astype(_F32)
                         * jax.nn.sigmoid(mixed.astype(_F32))
                         ).astype(cfg.dtype)
                mixed = gated @ p["o_w"].astype(cfg.dtype)
        with jax.named_scope(SCOPES.proj_mlp):
            x = x + mixed
            f = _rms(x, p["ln2_g"], cfg.rms_norm_eps)
        y, ids, stats = moe_layer(p, f.reshape(B * T, H), experts, layer,
                                  cfg)
        return x + y.reshape(B, T, H), (ids, stats)

    @staticmethod
    def final_norm(params, x, cfg):
        return _rms(x, params["lnf_g"], cfg.rms_norm_eps)

    @staticmethod
    @jax.named_scope(SCOPES.head)
    def head_logits(params, x_last, cfg, mp_axis=None):
        """Float32 logits from the stored head: its own operands, float32
        accumulation, no widened copy of the [H, V] matrix."""
        return jnp.dot(x_last.astype(params["head_w"].dtype),
                       params["head_w"], preferred_element_type=_F32)
