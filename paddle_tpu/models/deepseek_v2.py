"""DeepSeek-V2 (deepseek-ai, `model_type: deepseek_v2`) on the serving path:
multi-head LATENT attention (a low-rank query, and one compressed key-value
vector plus one shared rotary key a token, which is all the cache keeps), a
leading dense layer, then expert layers whose router keeps a token to a few
GROUPS of experts.

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g
    x = x + Attn(RMS(x; ln1));   x = x + FFN(RMS(x; ln2))

  attention (naive form):  c_q = RMS(h W_DQ);  [q_nope_i | q_rope_i] = c_q W_UQ
      a head i ([nope | rope]);  [c | k_r] = h W_DKV, c = RMS(c);  q_rope_i and
      k_r rotated at the token's position by the YaRN frequencies (k_r is ONE
      key for all heads);  k_nope_i = c W_UK_i, v_i = c W_UV_i;
      s_i = (q_nope_i . k_nope_i + q_rope_i . k_r) * scale, causal soft-max in
      float32;  out = concat_i(p_i v_i) W_O
  what this module computes (ABSORBED form): the cache's entry is [c | k_r];
      qa_i = q_nope_i W_UK_i^T;  s_i = (qa_i . c + q_rope_i . k_r) * scale;
      u_i = sum_t p_i,t c_t;  o_i = u_i W_UV_i    (`kernels.pallas.mla_attention`)
  scale = (nope + rope)^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
  rotary: rotate-half pairing (dim j turns with dim j + rope/2, frequency j);
      per pair the YaRN blend of theta^(-2j/rope) and that divided by `factor`
      (`yarn_inv_freq`); the cos/sin factor mscale / mscale_all_dim is 1 here
  FFN: layers [0, first_k_dense) the dense gated (silu(h Wg) * (h Wu)) Wd; the
      others  s = softmax(h W_r) over ALL experts in float32; a group's score
      is its best expert's; the best `topk_group` groups are kept; the top
      `experts_per_tok` of what is left are the picks, weights
      `routed_scaling_factor` * s_e (not renormalised);
      y = sum_picks w_e FFN_e(h) + FFN_shared(h)

The chip may hold a SHARE of the experts, ``experts_held = (lo, hi)`` (one
group, where the groups lie one a chip): the router keeps its width, its
groups and its picks; the layer adds the picks it holds and the whole shared
expert, and what the others would have added is left out (as
`models.qwen3_next`). No token is dropped: there is no capacity.

This module is the model's side of the serving seam
(`inference.serving.serving_model`): ``latent = True`` (the pool is
`pool_shapes`' two head-less pools, a layer of kind "latent" in
`ragged_step.ragged_pass`), a ``prologue`` of dense layers before the
periods, ``routed = True``. Parameter tree (every leaf ``cfg.param_dtype``):
``embed [V, H]``, ``head_w [H, V]``, ``lnf_g [H]``; ``prologue`` (leaves
``[first_k_dense, ...]``) and ``blocks`` = one dict, leaves ``[layers, 1,
...]``:

    ln1_g [H]  dq_w [H, rq]  q_norm_g [rq]  uq_w [rq, heads (nope + rope)]
    dkv_w [H, C + rope]  kv_norm_g [C]  uk_w [heads, nope, C]
    uv_w [heads, C, v]  o_w [heads v, H]  ln2_g [H]
    prologue: gate_w, up_w [H, FF]  down_w [FF, H]
    blocks:   router_w [H, E]  shared_gate_w, shared_up_w [H, Fs]
              shared_down_w [Fs, H]

and ``experts`` = one dict, taken WHOLE by the expert kernel: ``gate_w, up_w
[layers, E_held, H, F]``, ``down_w [layers, E_held, F, H]``. Training is not
here.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..enforce import enforce
from ..kernels.pallas import moe as M
from ..observability.trace import SCOPES

__all__ = ["DeepseekV2Config", "init_params", "Serving", "yarn_inv_freq",
           "attn_scale", "route", "moe_layer"]


@dataclasses.dataclass
class DeepseekV2Config:
    vocab_size: int = 102400            # the rows held here
    hidden_size: int = 5120
    num_layers: int = 60
    first_k_dense: int = 1
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 12288      # the dense layers' FFN
    moe_ffn: int = 1536
    num_experts: int = 160              # the router's width
    experts_per_tok: int = 6
    shared_ffn: int = 3072              # n_shared_experts x moe_ffn
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.707
    experts_held: Tuple[int, int] = (0, 160)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        self.experts_held = tuple(int(e) for e in self.experts_held)
        lo, hi = self.experts_held
        enforce(0 < self.first_k_dense < self.num_layers,
                "a leading run of dense layers, then expert layers",
                op="DeepseekV2Config", first_k_dense=self.first_k_dense)
        enforce(self.num_experts % self.n_group == 0
                and 0 < self.topk_group <= self.n_group,
                "the experts must divide into the router's groups",
                op="DeepseekV2Config")
        enforce(0 <= lo < hi <= self.num_experts,
                "experts_held must be a range of the router's experts",
                op="DeepseekV2Config", experts_held=self.experts_held)
        enforce(self.moe_ffn % min(M.BLOCK_F, self.moe_ffn) == 0,
                "an expert's width must be whole blocks of the kernel's",
                op="DeepseekV2Config", moe_ffn=self.moe_ffn)

    @property
    def head_dim(self):                 # a head's query and key width
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def serving_model(self):
        return Serving


# -- rotary positions (YaRN) ---------------------------------------------------
def yarn_inv_freq(cfg):
    """The rope/2 rotary frequencies, float64 numpy: per pair j the blend
    of theta^(-2j/rope) (kept where the pair turns often within the
    original context) and that divided by `rope_factor` (where it turns
    seldom), by the linear ramp between the two correction dims
    (`yarn_find_correction_range` / `yarn_linear_ramp_mask` as
    published)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / cfg.rope_factor

    def correction_dim(turns):
        return (dim * math.log(cfg.rope_original_max / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def attn_scale(cfg):
    """(nope + rope)^-0.5 * m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1."""
    m = 1.0
    if cfg.rope_factor > 1.0 and cfg.rope_mscale_all_dim:
        m = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
    return cfg.head_dim ** -0.5 * m * m


_F32 = jnp.float32


def _lanes(width):
    return -(-width // 128) * 128


def _rope(x, pos, inv_freq):
    """Rotate-half RoPE over the whole last dim; x: [..., T, (heads,) D],
    pos: [T] (broadcast from the left of the heads axis)."""
    ang = pos.astype(_F32)[:, None] * jnp.asarray(inv_freq, _F32)  # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    if x.ndim == 3:                     # [T, heads, D]
        cos, sin = cos[:, None, :], sin[:, None, :]
    xf = x.astype(_F32)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-xf[..., half:], xf[..., :half]], -1)
    return (xf * cos + turned * sin).astype(x.dtype)


def _rms(x, g, eps):
    xf = x.astype(_F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * g.astype(_F32)).astype(x.dtype)


def _gated_ffn(f, gate_w, up_w, down_w, dt):
    act = (jax.nn.silu((f @ gate_w.astype(dt)).astype(_F32))
           * (f @ up_w.astype(dt)).astype(_F32)).astype(dt)
    return act @ down_w.astype(dt)


def init_params(cfg, key, std=0.02):
    """The program's own initialiser (tests and examples; the benchmark
    makes its seeded tree itself): N(0, std) matrices, gains 1 + N(0, std)."""
    H, V, P = cfg.hidden_size, cfg.vocab_size, cfg.first_k_dense
    L = cfg.num_layers - P
    hd, C, rope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, v, rq = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.q_lora_rank
    held = cfg.experts_held[1] - cfg.experts_held[0]
    keys = iter(jax.random.split(key, 64))
    dt = cfg.param_dtype

    def normal(*shape, mean=0.0):
        return (mean + std * jax.random.normal(next(keys), shape,
                                               jnp.float32)).astype(dt)

    def attention(*lead):
        return {"ln1_g": normal(*lead, H, mean=1.0),
                "dq_w": normal(*lead, H, rq),
                "q_norm_g": normal(*lead, rq, mean=1.0),
                "uq_w": normal(*lead, rq, hd * (nope + rope)),
                "dkv_w": normal(*lead, H, C + rope),
                "kv_norm_g": normal(*lead, C, mean=1.0),
                "uk_w": normal(*lead, hd, nope, C),
                "uv_w": normal(*lead, hd, C, v),
                "o_w": normal(*lead, hd * v, H),
                "ln2_g": normal(*lead, H, mean=1.0)}

    FF, Fs, F, E = (cfg.intermediate_size, cfg.shared_ffn, cfg.moe_ffn,
                    cfg.num_experts)
    prologue = {**attention(P), "gate_w": normal(P, H, FF),
                "up_w": normal(P, H, FF), "down_w": normal(P, FF, H)}
    blocks = {**attention(L, 1), "router_w": normal(L, 1, H, E),
              "shared_gate_w": normal(L, 1, H, Fs),
              "shared_up_w": normal(L, 1, H, Fs),
              "shared_down_w": normal(L, 1, Fs, H)}
    experts = {"gate_w": normal(L, held, H, F), "up_w": normal(L, held, H, F),
               "down_w": normal(L, held, F, H)}
    return {"embed": normal(V, H), "prologue": prologue, "blocks": (blocks,),
            "experts": (experts,), "lnf_g": normal(H, mean=1.0),
            "head_w": normal(H, V)}


# -- the expert layer -----------------------------------------------------------
def route(probs, cfg):
    """Group-limited greedy picks of probs: [T, E] float32 (the soft-max
    over all experts). Returns (weights [T, k] float32 =
    routed_scaling_factor * probs at the picks, ids [T, k] int32)."""
    T, E = probs.shape
    G = cfg.n_group
    best = jnp.max(probs.reshape(T, G, E // G), axis=-1)            # [T, G]
    _, groups = jax.lax.top_k(best, cfg.topk_group)
    kept = jnp.sum(jax.nn.one_hot(groups, G, dtype=jnp.int32), axis=1) > 0
    masked = jnp.where(jnp.repeat(kept, E // G, axis=1), probs, 0.0)
    top, ids = jax.lax.top_k(masked, cfg.experts_per_tok)
    return top * cfg.routed_scaling_factor, ids


def moe_layer(p, f, experts, layer, cfg, real=None):
    """The expert layer on the normed tokens f: [T, H]; real: [T] bool,
    the positions that carry a token (padding is not routed: its picks
    are moved past the router's width, so it reads no expert and counts
    nowhere). Returns (y [T, H] in cfg.dtype, ids [T, k] int16 — the
    router's picks over ALL experts —, stats int32: `Serving.route_stats`
    names its columns; the group-limited router's two are the tokens with
    a pick among the held experts and the tokens routed)."""
    lo, hi = cfg.experts_held
    dt = cfg.dtype
    with jax.named_scope(SCOPES.moe_route):
        logits = jnp.dot(f, p["router_w"].astype(dt),
                         preferred_element_type=_F32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = route(probs, cfg)
        if real is not None:
            ids = jnp.where(real[:, None], ids, cfg.num_experts)
        plan = M.tiles(M.plan(ids, lo, hi),
                       M.tile_rows(*ids.shape, cfg.num_experts))
        stats = jnp.concatenate([M.pass_stats(plan), jnp.stack([
            jnp.sum(jnp.any(plan["held"], axis=1).astype(jnp.int32)),
            (f.shape[0] if real is None
             else jnp.sum(real.astype(jnp.int32)))]).astype(jnp.int32)])
    with jax.named_scope(SCOPES.moe_experts):
        y_pad = M.grouped_ffn(f, experts["gate_w"], experts["up_w"],
                              experts["down_w"], layer, plan)
    with jax.named_scope(SCOPES.moe_route):
        y = M.combine(y_pad, weights, plan)
    with jax.named_scope(SCOPES.moe_shared):
        y = y + _gated_ffn(f, p["shared_gate_w"], p["shared_up_w"],
                           p["shared_down_w"], dt).astype(_F32)
    return y.astype(dt), ids.astype(jnp.int16), stats


class Serving:
    """What the serving step asks of a model (`inference.serving`'s
    `GPTServing` states the seam). `latent`: the cache is one compressed
    vector and one rotary key a token (`pool_shapes`), its layers are of
    kind "latent" (`latent_qkv` instead of `qkv`); `prologue`: that many
    leading layers of ``params["prologue"]`` run before the periods, with
    no experts; `routed`: `block_math` takes the run's experts whole and
    hands back what its router chose."""

    recurrent = False
    routed = True
    # `moe_layer`'s stats, by column
    route_stats = M.PASS_STATS + ("local_tokens", "tokens")
    latent = True

    @staticmethod
    def pattern(cfg):
        return (("latent", 1),)

    @staticmethod
    def prologue(cfg):
        return ("latent", cfg.first_k_dense)

    @staticmethod
    def kv_layers(cfg):
        return cfg.num_layers

    @staticmethod
    def routed_layers(cfg):
        return cfg.num_layers - cfg.first_k_dense

    @staticmethod
    def pool_shapes(cfg):
        """(heads, width) of the two pools' pages: the compressed vector
        and the shared rotary key, no heads. The rotary key's page is
        whole lane tiles (128 lanes, the key in the first `rope` of them,
        zeros behind): the tiled layout pads a narrower minor dimension to
        128 lanes in HBM whatever the program says, and refuses a copy of
        a slice of it ("must be aligned to tiling")."""
        return (1, cfg.kv_lora_rank), (1, _lanes(cfg.qk_rope_head_dim))

    attn_scale = staticmethod(attn_scale)

    @staticmethod
    def positions(pos, cfg):
        return pos                      # RoPE: no table to stay inside

    @staticmethod
    @jax.named_scope(SCOPES.embed)
    def embed(params, tokens, pos, cfg):
        return jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)

    @staticmethod
    @jax.named_scope(SCOPES.mla_proj)
    def latent_qkv(p, x, pos, cfg):
        """x: [1, T, H], pos: [1, T]. Returns the absorbed queries qa
        [T, heads, C] and their rotated rotary parts qr [T, heads, lanes],
        and the token's cache entry: c [T, C] (normed) and k_r [T, lanes]
        (rotated); `lanes`: `rope` widened to whole lane tiles with zeros
        (`pool_shapes`)."""
        T = x.shape[1]
        hd, C = cfg.num_heads, cfg.kv_lora_rank
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        dt, eps = cfg.dtype, cfg.rms_norm_eps
        inv_freq = yarn_inv_freq(cfg)
        h = _rms(x[0], p["ln1_g"], eps)
        c_q = _rms(h @ p["dq_w"].astype(dt), p["q_norm_g"], eps)
        # the barrier keeps the product a 2-D GEMM on the stored matrix
        # (`serving._qkv`: folded into the reshape it is taken transposed,
        # a copy of the layer's matrix before every product)
        q = jax.lax.optimization_barrier(c_q @ p["uq_w"].astype(dt)).reshape(
            T, hd, nope + rope)
        ckr = jax.lax.optimization_barrier(h @ p["dkv_w"].astype(dt))
        c = _rms(ckr[:, :C], p["kv_norm_g"], eps)
        wide = _lanes(rope) - rope
        k_r = jnp.pad(_rope(ckr[:, C:], pos[0], inv_freq),
                      ((0, 0), (0, wide)))
        qr = jnp.pad(_rope(q[..., nope:], pos[0], inv_freq),
                     ((0, 0), (0, 0), (0, wide)))
        # absorb W_UK into the query: heads are the batch of the product
        qa = jnp.einsum("thd,hdc->thc", q[..., :nope], p["uk_w"].astype(dt))
        return qa, qr, c, k_r

    @staticmethod
    def block_math(p, x, attn, mixed, cfg, mp_axis=None, *, experts, layer,
                   real=None):
        """The layer after its attention: attn [1, T, heads, C] is the
        kernel's soft-max-weighted sum of latents a head; W_UV and W_O
        follow, then the dense FFN (``experts is None``: a prologue
        layer) or the expert layer. Returns (x, (ids, stats)) as
        `moe_layer`, (x, None) for a dense layer."""
        B, T, H = x.shape
        dt = cfg.dtype
        with jax.named_scope(SCOPES.mla_proj):
            o = jnp.einsum("thc,hcv->thv", attn[0], p["uv_w"].astype(dt))
            x = x + (o.reshape(T, -1) @ p["o_w"].astype(dt))[None]
        with jax.named_scope(SCOPES.proj_mlp):
            f = _rms(x, p["ln2_g"], cfg.rms_norm_eps)
            if experts is None:
                return x + _gated_ffn(f, p["gate_w"], p["up_w"], p["down_w"],
                                      dt), None
        y, ids, stats = moe_layer(p, f.reshape(B * T, H), experts, layer,
                                  cfg, real)
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
