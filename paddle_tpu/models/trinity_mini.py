"""Trinity-Mini (Arcee, `model_type: afmoe`) on the serving path: WINDOWED
and full attention layers side by side — of every
`global_attn_every` layers the last attends everything, the others a
sliding window — gated attention with q/k norms, sandwich norms, and a
sigmoid router with a choice-only bias over routed experts plus a shared
one. The equations are ISSUE 54's (the published `config.json`'s keys and,
where the keys do not spell a thing out, the family's modelling code; the
benchmark's configuration file lists those under `assumed`).

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g
    h0 = E[tokens] * sqrt(hidden)                        (mup_enabled)
    a  = h + RMS(Attn(RMS(h; ln1)); post_attn)           (sandwich norms)
    h' = a + RMS(FFN(RMS(a; ln2)); post_mlp)

  Attn(u): q = u Wq, k = u Wk, v = u Wv, gate = u Wg; q, k RMS-normed per
      head (learned gains); a WINDOW layer rotates q and k (rotate-half
      RoPE over the whole head) and query i attends keys j with
      i - window < j <= i; a FULL layer has NO position encoding and the
      causal mask; out = (softmax-attention * sigmoid(gate)) Wo
  FFN of a leading dense layer: (silu(f Wgate) * (f Wup)) Wdown
  FFN of an expert layer: s = sigmoid(f Wr) in float32; picks = top-k of
      s + b (b: the layer's `expert_bias`, felt by the CHOICE only);
      w = s[picks] / (sum s[picks] + 1e-20) * route_scale;
      y = shared(f) + sum_i w_i expert_{p_i}(f), the shared expert with no
      gate of its own

This module is the model's side of the serving seam
(`inference.serving.serving_model`). Its window layers are runs of kind
"window": `ragged_step.ragged_pass` keeps their K and V in a second pair
of pools whose pages the engine gives back as the window slides
(`Serving.windowed`, `window`, `window_layers`). The leading dense layers
are the `prologue` (kind "window", no experts); after them the depth must
be whole periods of (`global_attn_every` - 1 window layers, one full
layer). The published depth (2 dense layers + 30 expert layers with a full
layer at every fourth index) has two expert layers before its first whole
period and is not expressible in that form yet; the benchmark's cut (1
dense layer + one period) is.

Parameter tree (matrices and gains in ``cfg.param_dtype``, `expert_bias`
float32): ``embed [V, H]``, ``head_w [H, V]``, ``lnf_g [H]``;
``prologue``, leaves ``[dense layers, ...]``: the attention leaves below
and ``gate_w, up_w [H, FF]``, ``down_w [FF, H]``; ``blocks`` one dict a
run of the pattern (window, full), leaves ``[periods, run, ...]``:

    attention: ln1_g  post_attn_g  q_w, g_w [H, hq D]  k_w, v_w [H, hkv D]
               q_norm, k_norm [D]  o_w [hq D, H]
    experts':  ln2_g  post_mlp_g  router_w [H, E]  expert_bias [E]
               shared_gate_w, shared_up_w [H, Fs]  shared_down_w [Fs, H]

and ``experts``, one dict a run, taken WHOLE by the expert kernel:
``gate_w, up_w [periods * run, E_held, H, F]``, ``down_w [.., F, H]``.
Training is not here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ..enforce import enforce
from ..kernels.pallas import moe as M
from ..observability.trace import SCOPES

__all__ = ["TrinityMiniConfig", "init_params", "Serving", "route",
           "moe_layer"]


@dataclasses.dataclass
class TrinityMiniConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    num_layers: int = 32
    num_dense_layers: int = 2
    global_attn_every: int = 4
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048          # counts the query's own position
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-5
    intermediate_size: int = 6144       # a dense layer's FFN
    num_experts: int = 128              # the router's width
    experts_per_tok: int = 8
    moe_ffn: int = 1024
    shared_ffn: int = 1024              # moe_ffn x num_shared_experts
    route_scale: float = 2.826
    route_norm: bool = True
    mup_enabled: bool = True            # the embedding's sqrt(hidden)
    experts_held: Tuple[int, int] = (0, 128)
    router_dtype: Any = jnp.float32     # the router's product and scores
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        self.experts_held = tuple(int(e) for e in self.experts_held)
        lo, hi = self.experts_held
        g = self.global_attn_every
        enforce(g >= 2 and self.num_dense_layers < g,
                "the leading dense layers must all be window layers",
                op="TrinityMiniConfig",
                num_dense_layers=self.num_dense_layers)
        enforce((self.num_layers - self.num_dense_layers) % g == 0
                and self.num_layers > self.num_dense_layers,
                "after the dense layers the depth must be whole periods "
                "of the layer pattern", op="TrinityMiniConfig",
                num_layers=self.num_layers, global_attn_every=g)
        enforce(self.num_heads % self.num_kv_heads == 0,
                "query heads must divide into the KV heads",
                op="TrinityMiniConfig")
        enforce(0 <= lo < hi <= self.num_experts,
                "experts_held must be a range of the router's experts",
                op="TrinityMiniConfig", experts_held=self.experts_held)

    @property
    def periods(self):
        return ((self.num_layers - self.num_dense_layers)
                // self.global_attn_every)

    @property
    def serving_model(self):
        return Serving


def _runs(cfg):
    return (("window", cfg.global_attn_every - 1), ("attention", 1))


def init_params(cfg, key, std=0.02, bias_std=0.01):
    """The program's own initialiser (tests and examples; the benchmark
    makes its seeded tree itself): N(0, std) matrices, gains 1 + N(0, std),
    `expert_bias` N(0, bias_std) in float32 so that the choice feels it."""
    P, H, V = cfg.periods, cfg.hidden_size, cfg.vocab_size
    hq, hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    E, F, Fs, FF = (cfg.num_experts, cfg.moe_ffn, cfg.shared_ffn,
                    cfg.intermediate_size)
    held = cfg.experts_held[1] - cfg.experts_held[0]
    keys = iter(jax.random.split(key, 96))
    dt = cfg.param_dtype

    def normal(*shape, mean=0.0, std=std, dtype=dt):
        return (mean + std * jax.random.normal(next(keys), shape,
                                               jnp.float32)).astype(dtype)

    def attention(*lead):
        return {"ln1_g": normal(*lead, H, mean=1.0),
                "post_attn_g": normal(*lead, H, mean=1.0),
                "q_w": normal(*lead, H, hq * D),
                "g_w": normal(*lead, H, hq * D),
                "k_w": normal(*lead, H, hkv * D),
                "v_w": normal(*lead, H, hkv * D),
                "q_norm": normal(*lead, D, mean=1.0),
                "k_norm": normal(*lead, D, mean=1.0),
                "o_w": normal(*lead, hq * D, H),
                "ln2_g": normal(*lead, H, mean=1.0),
                "post_mlp_g": normal(*lead, H, mean=1.0)}

    def moe(*lead):
        return {"router_w": normal(*lead, H, E),
                "expert_bias": normal(*lead, E, std=bias_std,
                                      dtype=jnp.float32),
                "shared_gate_w": normal(*lead, H, Fs),
                "shared_up_w": normal(*lead, H, Fs),
                "shared_down_w": normal(*lead, Fs, H)}

    def experts(n):
        return {"gate_w": normal(P * n, held, H, F),
                "up_w": normal(P * n, held, H, F),
                "down_w": normal(P * n, held, F, H)}

    nd, n = cfg.num_dense_layers, cfg.global_attn_every - 1
    prologue = {**attention(nd), "gate_w": normal(nd, H, FF),
                "up_w": normal(nd, H, FF), "down_w": normal(nd, FF, H)}
    return {"embed": normal(V, H), "prologue": prologue,
            "blocks": ({**attention(P, n), **moe(P, n)},
                       {**attention(P, 1), **moe(P, 1)}),
            "experts": (experts(n), experts(1)),
            "lnf_g": normal(H, mean=1.0), "head_w": normal(H, V)}


# -- the pieces ---------------------------------------------------------------
_F32 = jnp.float32


def _rms(x, g, eps):
    xf = x.astype(_F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * g.astype(_F32)).astype(x.dtype)


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head; x: [B, T, heads, D],
    pos: [B, T]."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=_F32) / D)
    ang = pos.astype(_F32)[..., None] * inv                  # [B, T, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    xf = x.astype(_F32)
    turned = jnp.concatenate([-xf[..., D // 2:], xf[..., :D // 2]], -1)
    return (xf * cos + turned * sin).astype(x.dtype)


def _gated_ffn(f, gate_w, up_w, down_w, dt):
    act = (jax.nn.silu((f @ gate_w.astype(dt)).astype(_F32))
           * (f @ up_w.astype(dt)).astype(_F32)).astype(dt)
    return act @ down_w.astype(dt)


def route(logits, bias, cfg):
    """The sigmoid router: scores s = sigmoid(logits) [T, E] in float32;
    the picks are the top-k of s + bias (the bias moves the CHOICE only);
    weights s at the picks, renormalised over them (`route_norm`) and
    scaled by `route_scale`. Returns (weights [T, k] float32, ids [T, k]
    int32). A second scoring function beside `models.deepseek_v2.route`
    (soft-max scores, group-limited)."""
    s = jax.nn.sigmoid(logits.astype(_F32))
    _, ids = jax.lax.top_k(s + bias.astype(_F32), cfg.experts_per_tok)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if cfg.route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg.route_scale, ids


def moe_layer(p, f, experts, layer, cfg, real=None):
    """The expert layer on the normed tokens f: [T, H]; real: [T] bool,
    the positions that carry a token (padding is not routed: its picks are
    moved past the router's width, so it reads no expert and counts
    nowhere). Returns (y [T, H] float32, ids [T, k] int16 — the router's
    picks over ALL experts —, stats int32: `Serving.route_stats` names
    its columns)."""
    lo, hi = cfg.experts_held
    dt = cfg.dtype
    with jax.named_scope(SCOPES.moe_route):
        logits = jnp.dot(f, p["router_w"].astype(dt),
                         preferred_element_type=jnp.dtype(cfg.router_dtype))
        weights, ids = route(logits, p["expert_bias"], cfg)
        if real is not None:
            ids = jnp.where(real[:, None], ids, cfg.num_experts)
        plan = M.tiles(M.plan(ids, lo, hi),
                       M.tile_rows(*ids.shape, cfg.num_experts))
        stats = M.pass_stats(plan)
    with jax.named_scope(SCOPES.moe_experts):
        y_pad = M.grouped_ffn(f, experts["gate_w"], experts["up_w"],
                              experts["down_w"], layer, plan)
    with jax.named_scope(SCOPES.moe_route):
        y = M.combine(y_pad, weights, plan)
    with jax.named_scope(SCOPES.moe_shared):
        y = y + _gated_ffn(f, p["shared_gate_w"], p["shared_up_w"],
                           p["shared_down_w"], dt).astype(_F32)
    return y, ids.astype(jnp.int16), stats


class Serving:
    """What the serving step asks of a model (`inference.serving`'s
    `GPTServing` states the seam). `windowed`: some layers are of kind
    "window" (`window` positions, `window_layers` of them), whose pages
    have a lifetime of their own; `prologue`: the leading dense layers,
    window layers without experts; `routed`: `block_math` takes the run's
    experts whole and hands back what its router chose; `mask_padding`:
    padding positions are not routed."""

    recurrent = False
    routed = True
    route_stats = M.PASS_STATS   # `moe_layer`'s stats, by column
    latent = False
    windowed = True
    mask_padding = True
    pattern = staticmethod(_runs)

    @staticmethod
    def prologue(cfg):
        return ("window", cfg.num_dense_layers)

    @staticmethod
    def routed_layers(cfg):
        return cfg.num_layers - cfg.num_dense_layers

    @staticmethod
    def kv_layers(cfg):
        return cfg.periods              # the layers that attend everything

    @staticmethod
    def window_layers(cfg):
        return cfg.num_layers - cfg.periods

    @staticmethod
    def window(cfg):
        return cfg.sliding_window

    @staticmethod
    def positions(pos, cfg):
        return pos                      # RoPE: no table to stay inside

    @staticmethod
    @jax.named_scope(SCOPES.embed)
    def embed(params, tokens, pos, cfg):
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.mup_enabled:
            x = x.astype(_F32) * (cfg.hidden_size ** 0.5)
        return x.astype(cfg.dtype)

    @staticmethod
    def qkv(p, x, pos, cfg, mp_axis=None, kind="attention"):
        """A layer's q [B, T, hq, D], k, v [B, T, hkv, D] (q, k normed per
        head; rotated at `pos` in a "window" layer, NOT in a full one) and
        the output gate [B, T, hq * D], which `block_math` applies."""
        B, T, _ = x.shape
        hq, hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.dtype
        with jax.named_scope(SCOPES.qkv):
            u = _rms(x, p["ln1_g"], cfg.rms_norm_eps)
            q = (u @ p["q_w"].astype(dt)).reshape(B, T, hq, D)
            k = (u @ p["k_w"].astype(dt)).reshape(B, T, hkv, D)
            v = (u @ p["v_w"].astype(dt)).reshape(B, T, hkv, D)
            gate = u @ p["g_w"].astype(dt)
            q = _rms(q, p["q_norm"], cfg.rms_norm_eps)
            k = _rms(k, p["k_norm"], cfg.rms_norm_eps)
        if kind == "window":
            with jax.named_scope(SCOPES.rope):
                q = _rope(q, pos, cfg.rope_theta)
                k = _rope(k, pos, cfg.rope_theta)
        return q, k, v, gate

    @staticmethod
    def block_math(p, x, attn, mixed, cfg, mp_axis=None, *, experts, layer,
                   real=None):
        """The layer after its attention: `mixed` is the output gate `qkv`
        returned, applied to `attn` before the output projection; the
        sandwich norms; then the dense FFN (``experts is None``: a prologue
        layer) or the expert layer. Returns (x, (ids, stats)) as
        `moe_layer`, (x, None) for a dense layer."""
        B, T, H = x.shape
        dt, eps = cfg.dtype, cfg.rms_norm_eps
        with jax.named_scope(SCOPES.proj_mlp):
            gated = (attn.reshape(B, T, -1).astype(_F32)
                     * jax.nn.sigmoid(mixed.astype(_F32))).astype(dt)
            x = x + _rms(gated @ p["o_w"].astype(dt), p["post_attn_g"], eps)
            f = _rms(x, p["ln2_g"], eps)
            if experts is None:
                y = _gated_ffn(f, p["gate_w"], p["up_w"], p["down_w"], dt)
                return x + _rms(y, p["post_mlp_g"], eps), None
        y, ids, stats = moe_layer(p, f.reshape(B * T, H), experts, layer,
                                  cfg, real)
        with jax.named_scope(SCOPES.proj_mlp):
            x = x + _rms(y.astype(dt).reshape(B, T, H), p["post_mlp_g"],
                         eps)
        return x, (ids, stats)

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
