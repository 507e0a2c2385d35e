"""Llama model family (reference: the Llama model exercised by semi-auto
parallel tests — test/auto_parallel/hybrid_strategy/
semi_auto_parallel_llama_model.py:93 LlamaAttentionAuto/LlamaMLPAuto/
LlamaRMSNormAuto; the reference's Llama-2 7B, flash_attn + fused RoPE).

Same two-execution design as gpt.py:

* ``Llama`` — eager nn.Layer (RMSNorm pre-norm, RoPE, GQA attention, SwiGLU
  MLP, untied vocab head) for single-device / GSPMD-auto use.

* hybrid engine — stacked-parameter functional form for explicit SPMD:
  vocab-parallel embedding + Megatron TP in every block over 'mp', scan +
  ppermute pipeline over 'pp' (spmd_pipeline), built into one program by
  models.hybrid_engine.build_train_step.

GQA under TP: q heads and kv heads are both sharded contiguously over 'mp';
rank r holds q heads [r·nh/mp, …) and kv heads [r·nkv/mp, …), and q head i
attends kv head i // (nh/nkv), so the grouping never crosses ranks as long
as num_kv_heads % mp == 0.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from ..enforce import enforce
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .. import nn
from ..nn import functional as F
from ..distributed.fleet.meta_parallel.pp_utils.spmd_pipeline import (
    spmd_pipeline, spmd_pipeline_interleaved, vpp_chunk_blocks,
    vpp_wrap_shard_params)
from ..quantization.fp8 import site_mm as _fp8_mm
from .gpt import _vocab_parallel_ce, _vocab_parallel_embed

__all__ = ["LlamaConfig", "Llama", "llama_tiny", "llama2_7b", "llama2_13b",
           "llama3_8b", "init_hybrid_params", "hybrid_param_specs",
           "hybrid_loss_fn", "build_hybrid_train_step", "dense_forward",
           "dense_loss", "split_streamed_params", "init_streamed_params",
           "streamed_fns", "LLAMA_FP8_SITES"]

# the decoder GEMM sites that run fp8 under FLAGS_fp8 / amp O3 (attention,
# RoPE, the LM head and embedding stay bf16 — quantization.fp8)
LLAMA_FP8_SITES = ("q", "k", "v", "o", "gate", "up", "down")


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # None → MHA
    intermediate_size: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.intermediate_size is None:
            # Llama sizing: 2/3 · 4H rounded up to a multiple of 256
            self.intermediate_size = 256 * math.ceil(8 * self.hidden_size
                                                     / 3 / 256)
        enforce(self.hidden_size % self.num_heads == 0,
                "hidden_size must be divisible by num_heads", op="LlamaConfig",
                hidden_size=self.hidden_size, num_heads=self.num_heads)
        enforce(self.num_heads % self.num_kv_heads == 0,
                "num_heads must be divisible by num_kv_heads (GQA groups)",
                op="LlamaConfig", num_heads=self.num_heads,
                num_kv_heads=self.num_kv_heads)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


def llama_tiny(**kw):
    return LlamaConfig(vocab_size=1024, hidden_size=128, num_layers=4,
                       num_heads=4, num_kv_heads=2, intermediate_size=256,
                       max_seq_len=256, **kw)


def llama2_7b(**kw):
    return LlamaConfig(hidden_size=4096, num_layers=32, num_heads=32,
                       intermediate_size=11008, **kw)


def llama2_13b(**kw):
    return LlamaConfig(hidden_size=5120, num_layers=40, num_heads=40,
                       intermediate_size=13824, **kw)


def llama3_8b(**kw):
    return LlamaConfig(vocab_size=128256, hidden_size=4096, num_layers=32,
                       num_heads=32, num_kv_heads=8, intermediate_size=14336,
                       max_seq_len=8192, rope_theta=500000.0, **kw)


# ---------------------------------------------------------------------------
# RoPE helpers (NeoX half-split convention, matching incubate fused_rope)
# ---------------------------------------------------------------------------
def rope_tables(cfg: LlamaConfig, seq_len: int):
    inv = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, cfg.head_dim, 2, dtype=jnp.float32) / cfg.head_dim))
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)  # [S, D/2]


def _rope(x, cos, sin):
    """x: [B, S, h, D] — rotate the half-split pairs."""
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _flash_gqa(q, k, v):
    """Ride the registry attention with native GQA — the Pallas kernel
    indexes KV heads per query-head group (no HBM head repeat); the
    XLA-composed fallback repeats on the fly. Grouping is inferred from
    the q/k head dims."""
    return F.scaled_dot_product_attention(q, k, v, is_causal=True)


def _gqa_attention(q, k, v):
    """Causal GQA attention. q: [B, S, hq, D], k/v: [B, S, hkv, D]."""
    B, S, hq, D = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(B, S, hkv, g, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) / math.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, S, hq, D)


# ---------------------------------------------------------------------------
# Eager nn.Layer form
# ---------------------------------------------------------------------------
class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        H, D = cfg.hidden_size, cfg.head_dim
        self.q_proj = nn.Linear(H, cfg.num_heads * D, bias_attr=False)
        self.k_proj = nn.Linear(H, cfg.num_kv_heads * D, bias_attr=False)
        self.v_proj = nn.Linear(H, cfg.num_kv_heads * D, bias_attr=False)
        self.o_proj = nn.Linear(cfg.num_heads * D, H, bias_attr=False)

    def forward(self, x, cos, sin):
        cfg = self.cfg
        B, S, H = x.shape
        q = self.q_proj(x).reshape(B, S, cfg.num_heads, cfg.head_dim)
        k = self.k_proj(x).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
        v = self.v_proj(x).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        out = _flash_gqa(q, k, v)
        return self.o_proj(out.reshape(B, S, -1))


class LlamaMLP(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        H, I = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = nn.Linear(H, I, bias_attr=False)
        self.up_proj = nn.Linear(H, I, bias_attr=False)
        self.down_proj = nn.Linear(I, H, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, cos, sin):
        x = x + self.self_attn(self.input_layernorm(x), cos, sin)
        return x + self.mlp(self.post_attention_layernorm(x))


class Llama(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(cfg) for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False)

    def forward(self, tokens):
        cfg = self.cfg
        cos, sin = rope_tables(cfg, tokens.shape[1])
        x = self.embed_tokens(tokens).astype(cfg.dtype)
        for layer in self.layers:
            x = layer(x, cos, sin)
        x = self.norm(x)
        return self.lm_head(x.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Hybrid (explicit SPMD) form: stacked params + shard_map engine
# ---------------------------------------------------------------------------
def init_hybrid_params(cfg: LlamaConfig, key) -> Dict[str, Any]:
    H, L, I, V = (cfg.hidden_size, cfg.num_layers, cfg.intermediate_size,
                  cfg.vocab_size)
    D, nkv = cfg.head_dim, cfg.num_kv_heads
    k = jax.random.split(key, 9)
    std = 0.02
    pd = cfg.param_dtype

    def nrm(key, shape, scale=std):
        return (scale * jax.random.normal(key, shape)).astype(pd)

    return {
        "wte": nrm(k[0], (V, H)),
        "blocks": {
            "ln1_g": jnp.ones((L, H), pd),
            "q_w": nrm(k[1], (L, H, H)),
            "k_w": nrm(k[2], (L, H, nkv * D)),
            "v_w": nrm(k[3], (L, H, nkv * D)),
            "o_w": nrm(k[4], (L, H, H), std / math.sqrt(2 * L)),
            "ln2_g": jnp.ones((L, H), pd),
            "gate_w": nrm(k[5], (L, H, I)),
            "up_w": nrm(k[6], (L, H, I)),
            "down_w": nrm(k[7], (L, I, H), std / math.sqrt(2 * L)),
        },
        "lnf_g": jnp.ones((H,), pd),
        "head_w": nrm(k[8], (H, V)),  # own key: head is untied from wte
    }


def hybrid_param_specs(cfg: LlamaConfig) -> Dict[str, Any]:
    """Blocks stacked-L over 'pp'; Megatron column/row shardings over 'mp';
    vocab-parallel embedding + head."""
    return {
        "wte": P("mp", None),
        "blocks": {
            "ln1_g": P("pp"),
            "q_w": P("pp", None, "mp"),
            "k_w": P("pp", None, "mp"),
            "v_w": P("pp", None, "mp"),
            "o_w": P("pp", "mp", None),
            "ln2_g": P("pp"),
            "gate_w": P("pp", None, "mp"),
            "up_w": P("pp", None, "mp"),
            "down_w": P("pp", "mp", None),
        },
        "lnf_g": P(),
        "head_w": P(None, "mp"),
    }


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                           + eps)).astype(x.dtype) * g


def _block_fn(p, x, cos, sin, cfg: LlamaConfig, mp_axis: str = "mp",
              fp8=None, sp=None, flash=None, sep_axis=None):
    """One decoder layer with explicit Megatron TP (inside shard_map).
    Column shards hold complete heads: q_w's out dim is head-major [hq·D],
    k_w/v_w's is [hkv·D] — contiguous mp shards keep q-head↔kv-head groups
    rank-local (see module docstring). fp8: this layer's {site: {x, w, g}}
    delayed scales routing the seven GEMMs (LLAMA_FP8_SITES) through
    quantization.fp8.fp8_dot.

    sp: None (plain TP, bitwise-unchanged) or comm_overlap.MpOverlapConfig
    — x arrives sequence-sharded [B, S/mp, H] (see gpt._block_fn). The
    three attention column GEMMs (and gate/up) share ONE sequence
    all-gather: fused mode gathers h once and feeds the site GEMMs; ring
    mode concatenates the local weight shards so one collective matmul
    produces q|k|v (resp. gate|up) — otherwise each ring would move the
    same chunks again, tripling the wire.

    flash: None (registry attention, bitwise-unchanged) or a
    kernels.pallas.flash_training.FlashAttentionConfig — the fused flash
    kernel (GQA native: KV heads indexed per query group), optionally
    with sep ring/Ulysses context parallelism over `sep_axis` (x and
    cos/sin then carry this rank's sequence shard)."""
    mp = lax.axis_size(mp_axis)
    hq, hkv = cfg.num_heads // mp, cfg.num_kv_heads // mp
    B = x.shape[0]
    H = cfg.hidden_size
    cd = cfg.dtype
    from ..distributed.fleet.layers.mpu import mp_ops
    if sp is not None:
        from ..distributed.comm_overlap import collective_matmul as _cm
        S = x.shape[1] * mp
        # replicated-but-sequence-parallel params: RMSNorm gains see only
        # this rank's seq shard — identity-fwd/psum-bwd restores the
        # full-sequence gradient (see gpt._block_fn)
        p = dict(p)
        for k in ("ln1_g", "ln2_g"):
            p[k] = mp_ops.c_identity(p[k], mp_axis)
    else:
        S = x.shape[1]

    h = _rms(x, p["ln1_g"], cfg.rms_eps)
    if sp is None:
        hi = mp_ops.c_identity(h, mp_axis).astype(cd)
    elif sp.ring:
        wqkv = jnp.concatenate(
            [p["q_w"], p["k_w"], p["v_w"]], axis=-1).astype(cd)
        qkv = mp_ops.ag_matmul(h.astype(cd), wqkv, mp_axis, ring=True)
        q, kk, vv = jnp.split(
            qkv, [hq * cfg.head_dim, (hq + hkv) * cfg.head_dim], axis=-1)
    else:
        # cast BEFORE the gather: _rms promotes to param dtype, and an
        # fp32 wire would double the AG/RS bytes vs the compute dtype
        hi = _cm.ag_seq(h.astype(cd), mp_axis, dim=1)  # one AG, 3 GEMMs
    if sp is None or not sp.ring:
        q = _fp8_mm(fp8, "q")(hi, p["q_w"].astype(cd))
        kk = _fp8_mm(fp8, "k")(hi, p["k_w"].astype(cd))
        vv = _fp8_mm(fp8, "v")(hi, p["v_w"].astype(cd))
    q = q.reshape(B, S, hq, cfg.head_dim)
    kk = kk.reshape(B, S, hkv, cfg.head_dim)
    vv = vv.reshape(B, S, hkv, cfg.head_dim)
    q, kk = _rope(q, cos, sin), _rope(kk, cos, sin)
    # heads are rank-local under TP; under sp they see the FULL sequence
    # (only the residual stream is sharded), under a sep-mode flash plan
    # this rank's sequence shard (RoPE already used global positions)
    if flash is not None:
        # training-grade fused path (no registry hop); GQA native
        from ..kernels.pallas import flash_training as _ft
        attn = _ft.attention(q, kk, vv, flash,
                             sep_axis=sep_axis).reshape(B, S, H // mp)
    else:
        # registry attention (Pallas flash with native GQA on TPU — the
        # engine's shard_map runs check_vma=False so the kernel traces
        # inside it; composed fallback elsewhere)
        attn = _flash_gqa(q, kk, vv).reshape(B, S, H // mp)
    if sp is None:
        out = _fp8_mm(fp8, "o")(attn, p["o_w"].astype(cd))  # row-parallel
        x = x + mp_ops.mp_allreduce(out, mp_axis)
    else:
        x = x + mp_ops.matmul_rs(
            attn, p["o_w"].astype(cd), mp_axis, ring=sp.ring,
            mm=None if fp8 is None else _fp8_mm(fp8, "o"))

    h = _rms(x, p["ln2_g"], cfg.rms_eps)
    if sp is None:
        hi = mp_ops.c_identity(h, mp_axis).astype(cd)
    elif sp.ring:
        wgu = jnp.concatenate([p["gate_w"], p["up_w"]], axis=-1).astype(cd)
        gu = mp_ops.ag_matmul(h.astype(cd), wgu, mp_axis, ring=True)
        g_, u_ = jnp.split(gu, 2, axis=-1)
    else:
        hi = _cm.ag_seq(h.astype(cd), mp_axis, dim=1)  # cast pre-gather
    if sp is None or not sp.ring:
        g_ = _fp8_mm(fp8, "gate")(hi, p["gate_w"].astype(cd))
        u_ = _fp8_mm(fp8, "up")(hi, p["up_w"].astype(cd))
    m = jax.nn.silu(g_.astype(jnp.float32)).astype(cd) * u_
    if sp is None:
        m = _fp8_mm(fp8, "down")(m, p["down_w"].astype(cd))  # row-parallel
        return x + mp_ops.mp_allreduce(m, mp_axis)
    return x + mp_ops.matmul_rs(
        m, p["down_w"].astype(cd), mp_axis, ring=sp.ring,
        mm=None if fp8 is None else _fp8_mm(fp8, "down"))


def dense_embed(params, tokens, cfg: LlamaConfig):
    return jnp.take(params["wte"], tokens, axis=0).astype(cfg.dtype)


def dense_block(p, x, cfg: LlamaConfig, fp8=None):
    """One decoder layer on an UNstacked per-layer tree — shared by the
    scan in dense_forward and the param-streaming trainer (RoPE tables
    are a deterministic function of static cfg + S; XLA folds them).
    fp8: this layer's {site: {x, w, g}} delayed scales (None = plain
    path, bitwise-unchanged)."""
    cd = cfg.dtype
    B, S, H = x.shape
    cos, sin = rope_tables(cfg, S)
    h = _rms(x, p["ln1_g"], cfg.rms_eps).astype(cd)
    q = _fp8_mm(fp8, "q")(h, p["q_w"].astype(cd)).reshape(
        B, S, cfg.num_heads, cfg.head_dim)
    k = _fp8_mm(fp8, "k")(h, p["k_w"].astype(cd)).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim)
    v = _fp8_mm(fp8, "v")(h, p["v_w"].astype(cd)).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    attn = _flash_gqa(q, k, v)
    x = x + _fp8_mm(fp8, "o")(attn.reshape(B, S, H), p["o_w"].astype(cd))
    h = _rms(x, p["ln2_g"], cfg.rms_eps).astype(cd)
    m = jax.nn.silu(_fp8_mm(fp8, "gate")(h, p["gate_w"].astype(cd))
                    .astype(jnp.float32)).astype(cd) \
        * _fp8_mm(fp8, "up")(h, p["up_w"].astype(cd))
    return x + _fp8_mm(fp8, "down")(m, p["down_w"].astype(cd))


def dense_head_loss(params, x, labels, cfg: LlamaConfig):
    """Final RMSNorm + LM head + logsumexp CE over the head sub-tree —
    identical math to dense_loss's tail."""
    x = _rms(x, params["lnf_g"], cfg.rms_eps)
    logits = (x.astype(cfg.dtype)
              @ params["head_w"].astype(cfg.dtype)).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def dense_forward(params, tokens, cfg: LlamaConfig, remat: bool = True,
                  fp8=None):
    """Single-device forward over the stacked pytree (no collectives); same
    math/layout as the hybrid engine. fp8: per-layer delayed scales,
    stacked [L] like the block params (see gpt.dense_forward)."""
    x = dense_embed(params, tokens, cfg)

    def block(p, x, f=None):
        return dense_block(p, x, cfg, fp8=f)

    blk = jax.checkpoint(block) if remat else block

    if fp8 is not None:
        def body(carry, pf):
            p, f = pf
            return blk(p, carry, f), None
        x, _ = lax.scan(body, x, (params["blocks"], fp8))
    else:
        def body(carry, p):
            return blk(p, carry), None
        x, _ = lax.scan(body, x, params["blocks"])
    x = _rms(x, params["lnf_g"], cfg.rms_eps)
    return x.astype(cfg.dtype) @ params["head_w"].astype(cfg.dtype)


# ---------------------------------------------------------------------------
# Param-streaming (bigger-than-HBM) form — Llama-2 7B on one v5e
# ---------------------------------------------------------------------------
def split_streamed_params(params, cfg: LlamaConfig):
    """Stacked hybrid tree → segmented {embed, blocks: [per-layer], head}
    layout for build_param_streamed_train_step (tests / small models)."""
    blocks = [jax.tree.map(lambda a: a[i], params["blocks"])
              for i in range(cfg.num_layers)]
    return {"embed": {"wte": params["wte"]},
            "blocks": blocks,
            "head": {"lnf_g": params["lnf_g"], "head_w": params["head_w"]}}


def init_streamed_params(cfg: LlamaConfig, key, park=lambda t: t):
    """Segmented init, ONE segment on device at a time (cf. gpt.py —
    a 7B whole-tree init would OOM HBM before the first step)."""
    H, L, I, V = (cfg.hidden_size, cfg.num_layers, cfg.intermediate_size,
                  cfg.vocab_size)
    D, nkv = cfg.head_dim, cfg.num_kv_heads
    std, pd = 0.02, cfg.param_dtype
    k_embed, k_head, *k_blocks = jax.random.split(key, 2 + L)

    def nrm(key, shape, scale=std):
        return (scale * jax.random.normal(key, shape)).astype(pd)

    @jax.jit
    def one_block(key):
        ks = jax.random.split(key, 7)
        return {
            "ln1_g": jnp.ones((H,), pd),
            "q_w": nrm(ks[0], (H, H)),
            "k_w": nrm(ks[1], (H, nkv * D)),
            "v_w": nrm(ks[2], (H, nkv * D)),
            "o_w": nrm(ks[3], (H, H), std / math.sqrt(2 * L)),
            "ln2_g": jnp.ones((H,), pd),
            "gate_w": nrm(ks[4], (H, I)),
            "up_w": nrm(ks[5], (H, I)),
            "down_w": nrm(ks[6], (I, H), std / math.sqrt(2 * L)),
        }

    return {
        "embed": park(jax.jit(lambda k: {"wte": nrm(k, (V, H))})(k_embed)),
        "blocks": [park(one_block(k)) for k in k_blocks],
        "head": park(jax.jit(lambda k: {
            "lnf_g": jnp.ones((H,), pd),
            "head_w": nrm(k, (H, V))})(k_head)),
    }


def streamed_fns(cfg: LlamaConfig):
    """(embed_fn, block_fn, head_loss_fn) for
    build_param_streamed_train_step — same math as dense_loss."""
    return (lambda p, tokens: dense_embed(p, tokens, cfg),
            lambda p, x: dense_block(p, x, cfg),
            lambda p, x, labels: dense_head_loss(p, x, labels, cfg))


def dense_loss(params, tokens, labels, cfg: LlamaConfig, remat: bool = True,
               fp8=None):
    logits = dense_forward(params, tokens, cfg, remat=remat, fp8=fp8)
    # bf16-logit logsumexp CE (one shared implementation — gpt.py)
    from .gpt import lm_logsumexp_ce
    return lm_logsumexp_ce(logits, labels)


def _hybrid_rope(cfg: LlamaConfig, S: int, sep_on: bool, sep_axis):
    """(cos, sin) for this rank's S positions of the hybrid loss."""
    if sep_on:
        # this rank's slice of the GLOBAL rotation tables — K blocks
        # carry their rotated values around the ring
        n_sep = lax.axis_size(sep_axis)
        cos_g, sin_g = rope_tables(cfg, S * n_sep)
        off = lax.axis_index(sep_axis) * S
        cos = lax.dynamic_slice_in_dim(cos_g, off, S, axis=0)
        sin = lax.dynamic_slice_in_dim(sin_g, off, S, axis=0)
    else:
        cos, sin = rope_tables(cfg, S)
    return cos, sin


def _hybrid_embed(params, tokens, cfg: LlamaConfig, mp_axis, sp):
    """Vocabulary-parallel token embedding of the hybrid loss: [b, S, H],
    or this rank's [b, S/mp, H] sequence shard under sp."""
    from ..distributed.comm_overlap import collective_matmul as _cm
    S = tokens.shape[1]
    x = _vocab_parallel_embed(params["wte"], tokens, mp_axis)
    x = x.astype(cfg.dtype)
    if sp is not None:
        enforce(S % lax.axis_size(mp_axis) == 0,
                "sequence parallelism needs S divisible by the mp degree",
                op="llama.hybrid_loss_fn", seq=S,
                mp=lax.axis_size(mp_axis))
        x = _cm.scatter_seq(x, mp_axis, dim=1)  # [b_local, S/mp, H]
    return x


def _head_logits(params, out, cfg: LlamaConfig, mp_axis, sp):
    """Final RMSNorm and the column-parallel head of the hybrid loss: this
    mp rank's [b, S, V/mp] logits."""
    from ..distributed.fleet.layers.mpu import mp_ops
    lnf_g = params["lnf_g"]
    if sp is not None:
        # final RMSNorm runs on the seq shard — its gain grad is partial
        # over mp (see gpt.hybrid_loss_fn)
        lnf_g = mp_ops.c_identity(lnf_g, mp_axis)
    out = _rms(out, lnf_g, cfg.rms_eps)
    if sp is None:
        out = mp_ops.c_identity(out, mp_axis)  # column-parallel head
        logits_local = (out.astype(cfg.dtype)
                        @ params["head_w"].astype(cfg.dtype))
    else:
        logits_local = mp_ops.ag_matmul(
            out.astype(cfg.dtype), params["head_w"].astype(cfg.dtype),
            mp_axis, ring=sp.ring)
    return logits_local


def hybrid_microbatch_share(params, tokens, labels, denom, layers,
                            cfg: LlamaConfig, pp_axis="pp", mp_axis="mp",
                            sp=None, flash=None, sep_axis="sep"):
    """One microbatch's share of the per-device loss on a mesh with ONE
    pipeline stage: these rows' token losses summed and divided by
    `denom`, the valid labels of the whole local batch; no pipeline, no
    stage checkpoint (see gpt.hybrid_microbatch_share)."""
    from .gpt import _note_mp_wire, share_of_loss
    sep_on = flash is not None and flash.sep is not None
    cos, sin = _hybrid_rope(cfg, tokens.shape[1], sep_on, sep_axis)
    x = _hybrid_embed(params, tokens, cfg, mp_axis, sp)

    out = layers(
        lambda p, x: _block_fn(p, x, cos, sin, cfg, mp_axis, sp=sp,
                               flash=flash, sep_axis=sep_axis),
        x, params["blocks"])
    logits_local = _head_logits(params, out, cfg, mp_axis, sp)
    _note_mp_wire(cfg, tokens, sp, mp_axis, pp_axis, 1,
                  jax.tree.leaves(params["blocks"])[0].shape[0])
    return share_of_loss(logits_local, labels, denom, mp_axis)


def hybrid_loss_fn(params, tokens, labels, cfg: LlamaConfig,
                   num_microbatches: int, dp_axis="dp", pp_axis="pp",
                   mp_axis="mp", virtual_pp: int = 1, fp8=None, sp=None,
                   flash=None, sep_axis="sep", z3=None, num=None):
    """Per-device loss of the full hybrid Llama (inside shard_map).
    num_microbatches fill spmd_pipeline's ticks, at pp = 1 too: the builder
    does not call this on a one-stage mesh unless a side channel needs it
    (see gpt.hybrid_loss_fn, hybrid_microbatch_share). fp8:
    this pp rank's stacked [L/pp] delayed scales (1F1B only — see
    gpt.hybrid_loss_fn). sp: None or comm_overlap.MpOverlapConfig —
    sequence-parallel TP over mp (see gpt.hybrid_loss_fn); RoPE tables
    stay full-sequence (attention always runs on the gathered sequence),
    requires S % mp == 0. flash: None or a FlashAttentionConfig (see
    gpt.hybrid_loss_fn) — with flash.sep, tokens arrive sequence-sharded
    over `sep_axis` and the RoPE tables become this rank's GLOBAL
    position slice (ring rotation / the Ulysses gather both preserve the
    already-rotated K blocks). z3: None or the ZeRO-3 gather-on-use plan
    (see gpt.hybrid_loss_fn — dp-sharded params, per-layer all-gathers
    inside the stage scan; the llama builder's stage 3 is always the
    unquantized gather). num: None or a numerics plan — with num.act
    the block scan emits per-layer activation rms/absmax through the
    pipeline aux channel (plain-1F1B path; see gpt.hybrid_loss_fn)."""
    b_local, S = tokens.shape
    M = num_microbatches
    enforce(b_local % M == 0,
            "per-dp-rank batch must be divisible by num_microbatches",
            op="llama.hybrid_loss_fn", batch_local=b_local, microbatches=M)
    enforce(fp8 is None or virtual_pp == 1,
            "fp8 delayed scaling supports the 1F1B schedule only",
            op="llama.hybrid_loss_fn", virtual_pp=virtual_pp)
    sep_on = flash is not None and flash.sep is not None
    if sep_on:
        enforce(sp is None,
                "sep context parallelism and mp sequence parallelism "
                "both shard the sequence dim", op="llama.hybrid_loss_fn")
    cos, sin = _hybrid_rope(cfg, S, sep_on, sep_axis)
    if z3 is not None:
        from ..distributed.comm_overlap import zero3 as _z3g
        from .gpt import _note_zero3_wire
        _note_zero3_wire(z3, params, pp_axis, M, virtual_pp=virtual_pp)
        params = dict(params)
        for name in z3["other_leaves"]:
            zd_ = z3["zdims"][name]
            if zd_ >= 0:
                params[name] = _z3g.all_gather_param(params[name], zd_,
                                                     z3["axis"])
    x = _hybrid_embed(params, tokens, cfg, mp_axis, sp)
    x_mb = x.reshape(M, b_local // M, x.shape[1], cfg.hidden_size)

    num_act = num is not None and num.act
    if num_act:
        enforce(virtual_pp == 1,
                "per-layer activation telemetry rides the plain 1F1B "
                "pipeline's aux channel (the builder disables num.act "
                "for VPP — per-layer grad norms stay on)",
                op="llama.hybrid_loss_fn")
    from .gpt import _act_stats, _deposit_act_stats, _pack_num_aux

    def _y(out):
        return _act_stats(out) if num_act else None

    def stage_fn(block_params, h):
        if fp8 is not None:
            blocks, scales = block_params
            if z3 is not None:
                def blk_fn(p, c, f):
                    o = _block_fn(p, c, cos, sin, cfg, mp_axis,
                                  fp8=f, sp=sp, flash=flash,
                                  sep_axis=sep_axis)
                    return o, _y(o)
                out, ys, _ = _z3g.scan_gather(
                    blk_fn, h, blocks, z3["zdims"]["blocks"],
                    z3["axis"], extras=(scales,), cfg=z3["cfg"])
            else:
                def body(carry, pf):
                    p, f = pf
                    o = _block_fn(p, carry, cos, sin, cfg, mp_axis,
                                  fp8=f, sp=sp, flash=flash,
                                  sep_axis=sep_axis)
                    return o, _y(o)
                out, ys = lax.scan(body, h, (blocks, scales))
            return _pack_num_aux(out, ys, num_act, pp_axis)

        if z3 is not None:
            def blk_fn(p, c):
                o = _block_fn(p, c, cos, sin, cfg, mp_axis, sp=sp,
                              flash=flash, sep_axis=sep_axis)
                return o, _y(o)
            out, ys, _ = _z3g.scan_gather(
                blk_fn, h, block_params, z3["zdims"]["blocks"],
                z3["axis"], cfg=z3["cfg"])
            return _pack_num_aux(out, ys, num_act, pp_axis)

        def body(carry, p):
            o = _block_fn(p, carry, cos, sin, cfg, mp_axis, sp=sp,
                          flash=flash, sep_axis=sep_axis)
            return o, _y(o)
        out, ys = lax.scan(body, h, block_params)
        return _pack_num_aux(out, ys, num_act, pp_axis)

    stage_params = (params["blocks"] if fp8 is None
                    else (params["blocks"], fp8))
    num_aux = None
    if virtual_pp > 1:
        out = spmd_pipeline_interleaved(
            stage_fn, vpp_chunk_blocks(params["blocks"], virtual_pp), x_mb,
            axis=pp_axis)
    elif num_act:
        out, aux = spmd_pipeline(stage_fn, stage_params, x_mb,
                                 axis=pp_axis, with_aux=True)
        num_aux = aux["num"]
    else:
        out = spmd_pipeline(stage_fn, stage_params, x_mb, axis=pp_axis)
    out = out.reshape(b_local, x.shape[1], cfg.hidden_size)
    logits_local = _head_logits(params, out, cfg, mp_axis, sp)
    from .gpt import _note_mp_wire
    _note_mp_wire(cfg, tokens, sp, mp_axis, pp_axis, M,
                  jax.tree.leaves(params["blocks"])[0].shape[0],
                  virtual_pp=virtual_pp)
    if num_aux is not None:
        _deposit_act_stats(num_aux, M,
                           (dp_axis,)
                           + ((mp_axis,) if sp is not None else ())
                           + ((sep_axis,) if sep_on else ()))
    loss, valid = _vocab_parallel_ce(logits_local, labels, mp_axis)
    total = jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1)
    if sep_on:
        # equal-size sequence shards: mean of per-shard means IS the
        # global mean (see gpt.hybrid_loss_fn)
        return lax.pmean(total, (dp_axis, sep_axis))
    return lax.pmean(total, dp_axis)


def build_hybrid_train_step(cfg: LlamaConfig, mesh: Mesh, optimizer,
                            num_microbatches: int = 1, dp_axis="dp",
                            pp_axis="pp", mp_axis="mp", extra_grad_axes=(),
                            virtual_pp: int = 1, grad_reduce_dtype="auto",
                            zero1_dp: bool = False, zero_stage="auto",
                            zero3="auto", fp8="auto",
                            telemetry="auto", mp_overlap="auto",
                            flash_attention="auto", sep_axis="sep",
                            numerics="auto", donate: bool = True):
    """The step owns (params, opt_state): it donates them, so rebind all
    of its outputs; donate=False keeps a caller's inputs alive (see
    hybrid_engine.build_train_step).

    num_microbatches: at pp > 1 the slices that fill the pipeline; on
    a mesh whose pp axis has ONE rank, gradient accumulation (each
    microbatch's forward and backward one after another, one dp
    reduction, clip and update); see gpt.build_hybrid_train_step.

    mp_overlap: "auto" (FLAGS_mp_seq_parallel / FLAGS_mp_collective_
    matmul) / None / mode string / MpOverlapConfig — sequence-parallel TP
    with optional ring collective matmul; see gpt.build_hybrid_train_step
    (off: the allreduce path is bitwise unchanged; collective_matmul
    refuses fp8).

    flash_attention: "auto" (flags, default off) / None / bool / sep-mode
    string / FlashAttentionConfig — the fused flash kernel (GQA native)
    in every decoder layer; see gpt.build_hybrid_train_step. A sep mode
    mounts `sep_axis` as a context-parallel axis ("ulysses" needs BOTH
    heads/mp and kv_heads/mp divisible by the sep degree — the
    all-to-all trades seq for heads on q and kv alike).

    zero_stage: "auto" (FLAGS_zero_stage) / None / 0/1/2/3 — ZeRO over
    dp; see gpt.build_hybrid_train_step. zero3: "auto" (flags) / None /
    Zero3Config — the stage-3 gather knobs (the planner pins an
    explicit config so plans stay flag-independent); the llama
    builder's stage 3 is always the UNQUANTIZED gather (the
    narrower-surface convention — a quantizing config is refused here;
    the gpt builder carries the int8-EF path).

    numerics: "auto" (FLAGS_numerics) / None / bool / NumericsConfig —
    in-program tensor-health telemetry (per-layer grad norms every
    schedule, activation rms/absmax on the plain-1F1B path, EF/fp8
    health); see gpt.build_hybrid_train_step. Off compiles
    BITWISE-identically."""
    from .hybrid_engine import build_train_step
    from ..quantization import fp8 as _f8
    from ..distributed.comm_overlap.collective_matmul import \
        resolve_mp_overlap
    from ..kernels.pallas.flash_training import resolve_flash_attention

    sp = resolve_mp_overlap(mp_overlap)
    flash = resolve_flash_attention(flash_attention)
    sep_on = flash is not None and flash.sep is not None
    if sep_on:
        enforce(sep_axis in mesh.axis_names,
                "a sep-mode flash plan mounts context parallelism on a "
                f"mesh axis: add '{sep_axis}' (degree >= 1) to the mesh",
                op="llama.build_hybrid_train_step",
                axes=tuple(mesh.axis_names))
        enforce(sp is None,
                "sep context parallelism and mp sequence parallelism "
                "both shard the sequence dim",
                op="llama.build_hybrid_train_step")
        sep_n = int(mesh.shape[sep_axis])
        if flash.sep == "ulysses" and sep_n > 1:
            mp_n = int(mesh.shape[mp_axis])
            enforce((cfg.num_heads // mp_n) % sep_n == 0
                    and (cfg.num_kv_heads // max(mp_n, 1)) % sep_n == 0,
                    "ulysses trades the sequence shard for a head shard "
                    "on q AND kv: both heads/mp and kv_heads/mp must "
                    "divide by the sep degree — use ring attention "
                    "otherwise", op="llama.build_hybrid_train_step",
                    heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                    mp=mp_n, sep=sep_n)
        extra_grad_axes = tuple(extra_grad_axes) + (sep_axis,)
    fp8_plan = _f8.resolve_fp8_plan(
        fp8, LLAMA_FP8_SITES, cfg.num_layers, stacked_axis=pp_axis,
        amax_axes=(dp_axis, mp_axis) + tuple(extra_grad_axes))
    # fp8 x ring-collective-matmul is refused by the engine (the ONE copy
    # of that compose rule — hybrid_engine.build_train_step)
    if fp8_plan is not None:
        enforce(virtual_pp == 1,
                "fp8 delayed scaling supports the 1F1B schedule only",
                op="llama.build_hybrid_train_step", virtual_pp=virtual_pp)

    # -- ZeRO stage resolution (see gpt.build_hybrid_train_step) ----------
    from .hybrid_engine import zero_dims
    from ..distributed.comm_overlap.zero3 import (resolve_zero3,
                                                  resolve_zero_stage)
    specs = hybrid_param_specs(cfg)
    example = jax.eval_shape(
        lambda: init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    stage = resolve_zero_stage(zero_stage, zero1_dp,
                               op="llama.build_hybrid_train_step")
    z3plan = None
    z3_engine = None
    if stage >= 3:
        z3cfg = resolve_zero3(zero3)
        enforce(not z3cfg.quantize,
                "the llama builder's stage 3 is the unquantized gather "
                "(narrower surface) — disable FLAGS_zero3_quantize_ag or "
                "use the gpt builder",
                op="llama.build_hybrid_train_step")
        zdims = zero_dims(specs, example, mesh, dp_axis)
        z3plan = {"zdims": zdims, "axis": dp_axis, "cfg": z3cfg,
                  "other_leaves": ("wte", "lnf_g", "head_w")}
        z3_engine = {"ef": None, "meta": z3cfg.meta()}

    # -- numerics plan (tensor-health telemetry; ISSUE 15) ----------------
    from ..observability.numerics import resolve_numerics
    ncfg = resolve_numerics(numerics, num_layers=cfg.num_layers,
                            act=(virtual_pp == 1), pp_axis=pp_axis)

    if fp8_plan is not None:
        def loss_fn(p, tokens, labels, scales):
            return hybrid_loss_fn(p, tokens, labels, cfg, num_microbatches,
                                  dp_axis, pp_axis, mp_axis,
                                  virtual_pp=virtual_pp, fp8=scales, sp=sp,
                                  flash=flash, sep_axis=sep_axis,
                                  z3=z3plan, num=ncfg)
    else:
        def loss_fn(p, tokens, labels):
            return hybrid_loss_fn(p, tokens, labels, cfg, num_microbatches,
                                  dp_axis, pp_axis, mp_axis,
                                  virtual_pp=virtual_pp, sp=sp,
                                  flash=flash, sep_axis=sep_axis,
                                  z3=z3plan, num=ncfg)

    # ONE pipeline stage is no pipeline (see gpt.build_hybrid_train_step)
    if (int(mesh.shape[pp_axis]) == 1 and fp8_plan is None
            and z3plan is None and not (ncfg is not None and ncfg.act)):
        from .gpt import one_stage_loss
        loss_fn = one_stage_loss(
            loss_fn, num_microbatches,
            lambda p, tokens, labels, denom, layers: hybrid_microbatch_share(
                p, tokens, labels, denom, layers, cfg, pp_axis, mp_axis,
                sp=sp, flash=flash, sep_axis=sep_axis),
            (dp_axis, sep_axis) if sep_on else (dp_axis,))

    step, shard_params, init_state = build_train_step(
        loss_fn, specs, mesh, optimizer, dp_axis=dp_axis,
        data_spec=(P(dp_axis, sep_axis) if sep_on else None),
        extra_grad_axes=extra_grad_axes, example_params=example,
        grad_reduce_dtype=grad_reduce_dtype, zero_stage=stage,
        zero3=z3_engine,
        fp8=fp8_plan, telemetry=telemetry, mp_overlap=sp, flash=flash,
        numerics=ncfg, donate=donate)
    # elastic-checkpoint hint: see gpt.build_hybrid_train_step
    init_state.layout_extra["pp"] = {
        "num_layers": int(cfg.num_layers), "pp": int(mesh.shape[pp_axis]),
        "vpp": int(virtual_pp),
        "stacked_components": ["blocks", "fp8_meta"],
    }
    if fp8_plan is not None:
        init_state.layout_extra["fp8_amax_ticks"] = (
            num_microbatches + int(mesh.shape[pp_axis]) - 1)

    if virtual_pp > 1:
        shard_params = vpp_wrap_shard_params(
            shard_params, cfg.num_layers, mesh.shape[pp_axis], virtual_pp)
    return step, shard_params, init_state
