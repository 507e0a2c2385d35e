"""Falcon-H1 (tiiuae, `model_type: falcon_h1`) on the serving path: every
block runs GQA attention and a Mamba-2 mixer side by side on the same
normed input and adds both to the residual stream, then a gated
feed-forward; muP multipliers scale each projection.

    u  = rmsnorm(x)
    a  = Wo(attention(rope(Wq u), rope(Wk u * key_mult), Wv u)) * attn_out_mult
    m  = out_proj(gated_group_rmsnorm(ssm(conv(in_proj(u * ssm_in_mult) * mup)))) * ssm_out_mult
    x  = x + a + m
    x  = x + down(up(f) * silu(gate(f) * mlp_mult[0])) * mlp_mult[1],  f = rmsnorm(x)

This module is the model's side of the serving engine's seam
(`inference.serving.serving_model`): its embedding, its per-layer mixing
(queries and K/V for the paged pool, and the recurrent mixer on the packed
ragged batch with its per-slot state), its post-mix half and its head. The
engine, the allocator, the attention kernel and the step program are the
ones GPT runs through. Training is not here.

Parameter tree (stacked on a leading [L] axis under "blocks"; every leaf
in ``cfg.param_dtype``, widened where the mathematics is float32):

    embed [V, H]  head_w [H, V]  lnf_g [H]
    ln1_g [H]  q_w [H, hq*D]  k_w, v_w [H, hkv*D]  o_w [hq*D, H]
    ssm_in_w [H, 2*d_ssm + 2*G*N + heads]     (z | x | B | C | dt)
    conv_w [K, d_ssm + 2*G*N]  conv_b  (conv_w[K-1] is the token's own tap)
    dt_bias, A_log, D [heads]  ssm_norm_g [d_ssm]  ssm_out_w [d_ssm, H]
    ln2_g [H]  gate_w, up_w [H, FF]  down_w [FF, H]
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ..enforce import enforce
from ..kernels.pallas.ssm import ssm_conv, ssm_scan
from ..observability.trace import SCOPES

__all__ = ["FalconH1Config", "init_params", "Serving", "state_shapes"]


@dataclasses.dataclass
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_layers: int = 72
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    ffn_hidden: int = 21504
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    ssm_groups: int = 2
    ssm_state: int = 256
    ssm_conv: int = 4
    ssm_chunk: int = 128
    embedding_multiplier: float = 5.656854249492381
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 0.011048543456039804
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)                     # z, x, B, C, dt
    ssm_out_multiplier: float = 0.08838834764831845
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    lm_head_multiplier: float = 0.0078125
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        enforce(self.num_heads % self.num_kv_heads == 0,
                "query heads must divide into the KV heads",
                op="FalconH1Config", num_heads=self.num_heads,
                num_kv_heads=self.num_kv_heads)
        enforce(self.ssm_heads % self.ssm_groups == 0,
                "mixer heads must divide into the state groups",
                op="FalconH1Config", ssm_heads=self.ssm_heads,
                ssm_groups=self.ssm_groups)

    @property
    def d_ssm(self):
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self):
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def serving_model(self):
        return Serving


def state_shapes(cfg, slots):
    """Shapes of the two per-slot buffers of the recurrent path: the state
    [L, slots, heads, P, N] and the conv tail [L, K-1, slots, channels]
    (slots before channels: 64 rows fill sublane tiles that K-1 = 3 rows
    would pad to 16)."""
    return ((cfg.num_layers, slots, cfg.ssm_heads, cfg.ssm_head_dim,
             cfg.ssm_state),
            (cfg.num_layers, cfg.ssm_conv - 1, slots, cfg.conv_dim))


def init_params(cfg, key, std=0.02):
    """The program's own initialiser (tests and examples; the benchmark
    makes its seeded tree itself): N(0, std) weights, gains 1, and the
    Mamba-2 reference initialiser's A_log = log U[1, 16], dt_bias the
    inverse softplus of a log-uniform dt in [1e-3, 0.1], D = 1, conv taps
    and bias U(-1/sqrt(K), 1/sqrt(K))."""
    L, H, FF, V = (cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden,
                   cfg.vocab_size)
    hq, hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hm, K, Cc = cfg.ssm_heads, cfg.ssm_conv, cfg.conv_dim
    keys = iter(jax.random.split(key, 32))
    dt = cfg.param_dtype

    def normal(*shape):
        return (std * jax.random.normal(next(keys), shape,
                                        jnp.float32)).astype(dt)

    def ones(*shape):
        return jnp.ones(shape, dt)

    def conv(*shape):
        return jax.random.uniform(next(keys), shape, jnp.float32,
                                  -K ** -0.5, K ** -0.5).astype(dt)

    step = jnp.exp(jax.random.uniform(next(keys), (L, Hm), jnp.float32,
                                      math.log(1e-3), math.log(0.1)))
    blocks = {
        "ln1_g": ones(L, H), "q_w": normal(L, H, hq * D),
        "k_w": normal(L, H, hkv * D), "v_w": normal(L, H, hkv * D),
        "o_w": normal(L, hq * D, H),
        "ssm_in_w": normal(L, H, 2 * cfg.d_ssm
                           + 2 * cfg.ssm_groups * cfg.ssm_state + Hm),
        "conv_w": conv(L, K, Cc), "conv_b": conv(L, Cc),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
        "A_log": jnp.log(jax.random.uniform(next(keys), (L, Hm),
                                            jnp.float32, 1.0, 16.0)
                         ).astype(dt),
        "D": ones(L, Hm), "ssm_norm_g": ones(L, cfg.d_ssm),
        "ssm_out_w": normal(L, cfg.d_ssm, H),
        "ln2_g": ones(L, H), "gate_w": normal(L, H, FF),
        "up_w": normal(L, H, FF), "down_w": normal(L, FF, H)}
    return {"embed": normal(V, H), "blocks": blocks, "lnf_g": ones(H),
            "head_w": normal(H, V)}


# -- the pieces ---------------------------------------------------------------
_F32 = jnp.float32


def _rms(x, g, eps):
    xf = x.astype(_F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * g.astype(_F32)).astype(x.dtype)


def _rope(x, pos, theta):
    """Rotate-half RoPE; x: [B, T, heads, D], pos: [B, T]."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=_F32) / D)
    ang = pos.astype(_F32)[..., None] * inv                  # [B, T, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    xf = x.astype(_F32)
    rot = jnp.concatenate([-xf[..., D // 2:], xf[..., :D // 2]], -1)
    return (xf * cos + rot * sin).astype(x.dtype)


def _mup_vector(cfg):
    z, x, b, c, dt = cfg.ssm_multipliers
    gn = cfg.ssm_groups * cfg.ssm_state
    return jnp.concatenate([jnp.full((n,), m, _F32) for n, m in (
        (cfg.d_ssm, z), (cfg.d_ssm, x), (gn, b), (gn, c),
        (cfg.ssm_heads, dt))])


class Serving:
    """What the serving step asks of a model (the GPT block's answers are
    `inference.serving.GPTServing`). `recurrent` tells the engine to keep
    the per-slot state this model's `mixer` reads and writes."""

    recurrent = True
    routed = False
    latent = False
    state_shapes = staticmethod(state_shapes)

    @staticmethod
    def prologue(cfg):
        return ("parallel", 0)      # no leading layers of another kind

    @staticmethod
    def pattern(cfg):
        return (("parallel", 1),)   # attention and the mixer side by side

    @staticmethod
    def kv_layers(cfg):
        return cfg.num_layers

    @staticmethod
    def positions(pos, cfg):
        return pos                      # RoPE: no table to stay inside

    @staticmethod
    @jax.named_scope(SCOPES.embed)
    def embed(params, tokens, pos, cfg):
        x = jnp.take(params["embed"], tokens, axis=0).astype(_F32)
        return (x * cfg.embedding_multiplier).astype(cfg.dtype)

    @staticmethod
    def qkv(p, x, pos, cfg, mp_axis=None):
        """q [B, T, hq, D], k, v [B, T, hkv, D] with RoPE at `pos`, and
        the normed input the mixer shares."""
        B, T, _ = x.shape
        with jax.named_scope(SCOPES.qkv):
            u = _rms(x, p["ln1_g"], cfg.rms_norm_eps)
            ua = u if cfg.attention_in_multiplier == 1.0 else (
                u * cfg.attention_in_multiplier).astype(cfg.dtype)
            q = (ua @ p["q_w"].astype(cfg.dtype)).reshape(
                B, T, cfg.num_heads, cfg.head_dim)
            k = ((ua @ p["k_w"].astype(cfg.dtype)) * cfg.key_multiplier
                 ).astype(cfg.dtype).reshape(B, T, cfg.num_kv_heads,
                                             cfg.head_dim)
            v = (ua @ p["v_w"].astype(cfg.dtype)).reshape(
                B, T, cfg.num_kv_heads, cfg.head_dim)
        with jax.named_scope(SCOPES.rope):
            q = _rope(q, pos, cfg.rope_theta)
            k = _rope(k, pos, cfg.rope_theta)
        return q, k, v, u

    @staticmethod
    def mixer(p, u, ssm, layer, plan, cfg):
        """The Mamba-2 mixer over the packed rows u: [1, T, H], from and
        into the slots' state. ssm: (state, tail) as `state_shapes`;
        plan: the pass's row_of/off_of [T], starts/q_lens/reset [R] and
        tile_idx [R, c_att] (`ragged_step.ragged_pass`). Returns
        (m [1, T, H] before the residual sum, (state, tail))."""
        state, tail = ssm
        Hm, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                       cfg.ssm_state)
        d, gn = cfg.d_ssm, cfg.ssm_groups * cfg.ssm_state
        row_of, off_of, q_lens = plan["row_of"], plan["off_of"], plan["q_lens"]
        tile_idx = plan["tile_idx"]
        c_att = tile_idx.shape[1]
        with jax.named_scope(SCOPES.ssm_in):
            zxbcdt = ((u[0] * cfg.ssm_in_multiplier).astype(cfg.dtype)
                      @ p["ssm_in_w"].astype(cfg.dtype))
            zxbcdt = (zxbcdt * _mup_vector(cfg)).astype(cfg.dtype)
            z, xbc, dt = (zxbcdt[:, :d], zxbcdt[:, d:2 * d + 2 * gn],
                          zxbcdt[:, 2 * d + 2 * gn:])
        with jax.named_scope(SCOPES.ssm_conv):
            xbc, tail = ssm_conv(xbc, p["conv_w"], p["conv_b"], tail, layer,
                                 row_of, off_of, plan["starts"], q_lens,
                                 plan["reset"])
        with jax.named_scope(SCOPES.ssm_scan):
            xs, Bm, Cm = xbc[:, :d], xbc[:, d:d + gn], xbc[:, d + gn:]
            step = jax.nn.softplus(dt.astype(_F32)
                                   + p["dt_bias"].astype(_F32))  # [T, Hm]
            live = (jnp.arange(c_att)[None, :] < q_lens[:, None])[..., None]
            step_t = jnp.where(live, step[tile_idx], 0.0)    # [R, c, Hm]
            cum_t = jnp.cumsum(
                step_t * -jnp.exp(p["A_log"].astype(_F32)), axis=1)
            y_t, state = ssm_scan(xs[tile_idx], Bm[tile_idx], Cm[tile_idx],
                                  step_t, cum_t, state, layer, q_lens,
                                  plan["reset"], groups=G)
            real = (off_of < q_lens[row_of])[:, None]
            y = jnp.where(real, y_t[row_of, jnp.minimum(off_of, c_att - 1)],
                          0.0)                               # [T, d] f32
            T = y.shape[0]
            y = y + (xs.astype(_F32).reshape(T, Hm, P)
                     * p["D"].astype(_F32)[None, :, None]).reshape(T, d)
        with jax.named_scope(SCOPES.ssm_out):
            zf = z.astype(_F32)
            y = (y * (zf * jax.nn.sigmoid(zf))).reshape(T, G, d // G)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                                  + cfg.rms_norm_eps)
            y = (y.reshape(T, d) * p["ssm_norm_g"].astype(_F32)
                 ).astype(cfg.dtype)
            m = ((y @ p["ssm_out_w"].astype(cfg.dtype))
                 * cfg.ssm_out_multiplier).astype(cfg.dtype)
        return m[None], (state, tail)

    @staticmethod
    @jax.named_scope(SCOPES.proj_mlp)
    def block_math(p, x, attn, mixed, cfg, mp_axis=None):
        """The block after its two mixers: both onto the residual stream,
        then the gated feed-forward."""
        B, T, _ = x.shape
        a = ((attn.reshape(B, T, -1) @ p["o_w"].astype(cfg.dtype))
             * cfg.attention_out_multiplier).astype(cfg.dtype)
        x = x + a + mixed
        f = _rms(x, p["ln2_g"], cfg.rms_norm_eps)
        gate = (f @ p["gate_w"].astype(cfg.dtype)).astype(_F32) \
            * cfg.mlp_multipliers[0]
        act = (f @ p["up_w"].astype(cfg.dtype)).astype(_F32) \
            * (gate * jax.nn.sigmoid(gate))
        down = act.astype(cfg.dtype) @ p["down_w"].astype(cfg.dtype)
        return x + (down * cfg.mlp_multipliers[1]).astype(cfg.dtype)

    @staticmethod
    def final_norm(params, x, cfg):
        return _rms(x, params["lnf_g"], cfg.rms_norm_eps)

    @staticmethod
    @jax.named_scope(SCOPES.head)
    def head_logits(params, x_last, cfg, mp_axis=None):
        """Float32 logits from the stored head: its own operands, float32
        accumulation, no widened copy of the [H, V] matrix."""
        logits = jnp.dot(x_last.astype(params["head_w"].dtype),
                         params["head_w"], preferred_element_type=_F32)
        return logits * cfg.lm_head_multiplier
