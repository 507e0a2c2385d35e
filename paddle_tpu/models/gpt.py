"""GPT model family (reference: the GPT/GPT-3 configs exercised by Fleet
hybrid-parallel — model definition test/legacy_test/auto_parallel_gpt_model.py,
used via test/auto_parallel/get_gpt_model.py:18; Fleet GPT-3 1.3B/6.7B).

Two executions of the same architecture:

* ``GPT`` — eager nn.Layer for single-device / GSPMD-auto use (Model.fit,
  generation). Attention rides the op-registry scaled_dot_product_attention
  (Pallas flash kernel on TPU).

* ``hybrid`` engine — functional stacked-parameter form for the explicit
  SPMD path: vocab-parallel embedding + Megatron TP inside each block (over
  'mp'; optionally sequence-parallel with ring collective-matmul overlap —
  FLAGS_mp_seq_parallel / FLAGS_mp_collective_matmul via
  distributed.comm_overlap.collective_matmul), scan+ppermute pipeline over
  'pp' (spmd_pipeline), dp gradient sync (monolithic pmean, or
  bucketed/overlapped/int8-quantized via distributed.comm_overlap —
  FLAGS_comm_bucket_mb et al.), all inside ONE shard_map/jit program. This is the TPU-native
  equivalent of the reference's PipelineParallel+TensorParallel meta_parallel
  stack (fleet/meta_parallel/pipeline_parallel.py:547,
  fleet/layers/mpu/mp_layers.py).
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
from ..observability.trace import SCOPES
from ..kernels.pallas.flash_attention import FLASH_REMAT_NAMES
from ..quantization.fp8 import site_mm as _fp8_mm
from ..distributed.fleet.meta_parallel.pp_utils.spmd_pipeline import (
    spmd_pipeline, spmd_pipeline_interleaved, spmd_pipeline_zero_bubble,
    vpp_block_permutation, vpp_chunk_blocks, vpp_wrap_shard_params)

__all__ = ["GPTConfig", "GPT", "gpt_tiny", "gpt_small", "gpt_moe_tiny",
           "gpt_1p3b", "gpt_6p7b",
           "init_hybrid_params", "hybrid_param_specs", "hybrid_loss_fn",
           "build_hybrid_train_step", "split_streamed_params",
           "init_streamed_params", "streamed_fns", "GPT_FP8_SITES",
           "moe_telemetry_series"]

# the dense-stack GEMM sites that run fp8 under FLAGS_fp8 / amp O3 (the
# attention einsums, LM head and embedding stay bf16 — quantization.fp8)
GPT_FP8_SITES = ("qkv", "proj", "fc1", "fc2")


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden: Optional[int] = None
    max_seq_len: int = 1024
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16  # MXU-native compute dtype
    param_dtype: Any = jnp.float32
    use_bias: bool = True
    # GPT-MoE (hybrid engine): > 0 replaces every SECOND layer's FFN with
    # a switch-routed (top-1, capacity-bounded) bank of this many experts,
    # dispatched over the 'ep' mesh axis — the alternating dense/MoE
    # layout of Switch-Transformer-style GPT variants. 0 = dense GPT,
    # bitwise-unchanged.
    moe_num_experts: int = 0
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 1e-2

    def __post_init__(self):
        if self.ffn_hidden is None:
            self.ffn_hidden = 4 * self.hidden_size
        enforce(self.hidden_size % self.num_heads == 0,
                "hidden_size must be divisible by num_heads", op="GPTConfig",
                hidden_size=self.hidden_size, num_heads=self.num_heads)
        enforce(self.moe_num_experts == 0 or self.num_layers % 2 == 0,
                "GPT-MoE stacks (dense, MoE) layer PAIRS so the pipeline "
                "scan stays homogeneous — num_layers must be even",
                op="GPTConfig", num_layers=self.num_layers)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def moe_on(self) -> bool:
        return self.moe_num_experts > 0


def gpt_tiny(**kw):
    return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=4,
                     num_heads=4, max_seq_len=256, **kw)


def gpt_small(**kw):
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt_moe_tiny(**kw):
    kw.setdefault("moe_num_experts", 8)
    return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=4,
                     num_heads=4, max_seq_len=256, **kw)


def gpt_1p3b(**kw):
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                     max_seq_len=2048, **kw)


def gpt_6p7b(**kw):
    return GPTConfig(hidden_size=4096, num_layers=32, num_heads=32,
                     max_seq_len=2048, **kw)


# ---------------------------------------------------------------------------
# Eager nn.Layer form
# ---------------------------------------------------------------------------
class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        H = cfg.hidden_size
        self.cfg = cfg
        self.ln1 = nn.LayerNorm(H)
        self.qkv = nn.Linear(H, 3 * H, bias_attr=cfg.use_bias)
        self.proj = nn.Linear(H, H, bias_attr=cfg.use_bias)
        self.ln2 = nn.LayerNorm(H)
        self.fc1 = nn.Linear(H, cfg.ffn_hidden, bias_attr=cfg.use_bias)
        self.fc2 = nn.Linear(cfg.ffn_hidden, H, bias_attr=cfg.use_bias)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x):
        cfg = self.cfg
        B, S, H = x.shape
        h = self.ln1(x)
        qkv = self.qkv(h).reshape(B, S, 3, cfg.num_heads, cfg.head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        attn = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                              dropout_p=cfg.dropout,
                                              training=self.training)
        attn = self.proj(attn.reshape(B, S, H))
        x = x + self.drop(attn)
        h = self.ln2(x)
        x = x + self.drop(self.fc2(F.gelu(self.fc1(h), approximate=True)))
        return x


class GPT(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        from ..nn.initializer import Normal
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False)

    def forward(self, tokens):
        B, S = tokens.shape
        pos = jnp.arange(S)[None, :]
        x = self.wte(tokens) + self.wpe(pos)
        x = self.drop(x).astype(self.cfg.dtype)
        for blk in self.blocks:
            x = blk(x)
        x = self.ln_f(x)
        return self.lm_head(x.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Hybrid (explicit SPMD) form: stacked params + shard_map engine
# ---------------------------------------------------------------------------
def init_hybrid_params(cfg: GPTConfig, key) -> Dict[str, Any]:
    """Stacked-parameter pytree. Blocks are stacked on a leading [L] axis so
    the pipeline can shard them over 'pp' and scan within a stage.

    GPT-MoE (cfg.moe_num_experts > 0): blocks become (dense, MoE) layer
    PAIRS stacked [L/2] — ``blocks = {"dense": {...}, "moe": {...}}`` —
    so the pipeline scan stays homogeneous while every second layer runs
    the switch-routed expert FFN. The MoE half carries its own attention
    sublayer (same TP layout) plus ``gate_w [L/2, H, E]`` and the stacked
    expert bank ``w1 [L/2, E, H, FF] / w2 [L/2, E, FF, H]`` that shards
    over 'ep' (and 'mp' on the expert hidden dim)."""
    H, L, FF, V = cfg.hidden_size, cfg.num_layers, cfg.ffn_hidden, cfg.vocab_size
    k = jax.random.split(key, 12)
    std = 0.02
    pd = cfg.param_dtype

    def nrm(key, shape, scale=std):
        return (scale * jax.random.normal(key, shape)).astype(pd)

    def dense_blocks(nl, kq, kp, k1, k2):
        return {
            "ln1_g": jnp.ones((nl, H), pd),
            "ln1_b": jnp.zeros((nl, H), pd),
            "qkv_w": nrm(kq, (nl, H, 3 * H)),
            "qkv_b": jnp.zeros((nl, 3 * H), pd),
            "proj_w": nrm(kp, (nl, H, H), std / math.sqrt(2 * L)),
            "proj_b": jnp.zeros((nl, H), pd),
            "ln2_g": jnp.ones((nl, H), pd),
            "ln2_b": jnp.zeros((nl, H), pd),
            "fc1_w": nrm(k1, (nl, H, FF)),
            "fc1_b": jnp.zeros((nl, FF), pd),
            "fc2_w": nrm(k2, (nl, FF, H), std / math.sqrt(2 * L)),
            "fc2_b": jnp.zeros((nl, H), pd),
        }

    if cfg.moe_on:
        L2, E = L // 2, cfg.moe_num_experts
        blocks = {
            "dense": dense_blocks(L2, k[2], k[3], k[4], k[5]),
            "moe": {
                "ln1_g": jnp.ones((L2, H), pd),
                "ln1_b": jnp.zeros((L2, H), pd),
                "qkv_w": nrm(k[7], (L2, H, 3 * H)),
                "qkv_b": jnp.zeros((L2, 3 * H), pd),
                "proj_w": nrm(k[8], (L2, H, H), std / math.sqrt(2 * L)),
                "proj_b": jnp.zeros((L2, H), pd),
                "ln2_g": jnp.ones((L2, H), pd),
                "ln2_b": jnp.zeros((L2, H), pd),
                "gate_w": nrm(k[9], (L2, H, E)),
                "w1": nrm(k[10], (L2, E, H, FF)),
                "b1": jnp.zeros((L2, E, FF), pd),
                "w2": nrm(k[11], (L2, E, FF, H), std / math.sqrt(2 * L)),
                "b2": jnp.zeros((L2, E, H), pd),
            },
        }
    else:
        blocks = dense_blocks(L, k[2], k[3], k[4], k[5])

    params = {
        "wte": nrm(k[0], (V, H)),
        "wpe": nrm(k[1], (cfg.max_seq_len, H)),
        "blocks": blocks,
        "lnf_g": jnp.ones((H,), pd),
        "lnf_b": jnp.zeros((H,), pd),
        "head_w": nrm(k[6], (H, V)),
    }
    return params


def hybrid_param_specs(cfg: GPTConfig) -> Dict[str, Any]:
    """PartitionSpecs: blocks stacked-L over 'pp'; Megatron shardings over
    'mp'; vocab-parallel embedding + head over 'mp'. GPT-MoE additionally
    shards the stacked expert bank's E dim over 'ep' and the expert
    hidden dim over 'mp' (w1 column-parallel, w2 row-parallel — one mp
    all-reduce per expert FFN); the gate stays replicated over ep/mp so
    routing is identical on every rank."""
    dense = {
        "ln1_g": P("pp"), "ln1_b": P("pp"),
        "qkv_w": P("pp", None, "mp"), "qkv_b": P("pp", "mp"),
        "proj_w": P("pp", "mp", None), "proj_b": P("pp"),
        "ln2_g": P("pp"), "ln2_b": P("pp"),
        "fc1_w": P("pp", None, "mp"), "fc1_b": P("pp", "mp"),
        "fc2_w": P("pp", "mp", None), "fc2_b": P("pp"),
    }
    if cfg.moe_on:
        blocks = {
            "dense": dense,
            "moe": {
                "ln1_g": P("pp"), "ln1_b": P("pp"),
                "qkv_w": P("pp", None, "mp"), "qkv_b": P("pp", "mp"),
                "proj_w": P("pp", "mp", None), "proj_b": P("pp"),
                "ln2_g": P("pp"), "ln2_b": P("pp"),
                "gate_w": P("pp"),
                "w1": P("pp", "ep", None, "mp"),
                "b1": P("pp", "ep", "mp"),
                "w2": P("pp", "ep", "mp", None),
                "b2": P("pp", "ep"),
            },
        }
    else:
        blocks = dense
    return {
        "wte": P("mp", None),
        "wpe": P(),
        "blocks": blocks,
        "lnf_g": P(), "lnf_b": P(),
        "head_w": P(None, "mp"),
    }


def _ln(x, g, b, eps=1e-5):
    # deliberately the COMPOSED form, not the Pallas fused LayerNorm the
    # registry dispatches for nn-level users: inside this model's
    # scan-over-layers + remat structure the kernel's call and its
    # saved-stats traffic are extra work beside what XLA already fuses
    # here (which of the two is faster on the 1.3B step is not measured
    # on the current installation)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def _attention(q, k, v):
    """Causal attention on local heads. [B, S, h_local, D]."""
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    S = logits.shape[-1]
    mask = jnp.tril(jnp.ones((S, S), bool))
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@jax.named_scope(SCOPES.attn)
def _attn_sublayer(p, x, cfg: GPTConfig, mp_axis: str = "mp", fp8=None,
                   sp=None, flash=None, sep_axis=None):
    """ln1 + Megatron-TP causal attention + residual — the shared first
    half of the dense and MoE hybrid blocks (reads the ln1_*/qkv_*/proj_*
    keys; sp callers must have pre-wrapped the replicated-but-SP params,
    see _block_fn).

    flash: None (the registry scaled_dot_product_attention — composed
    einsum off-TPU, bitwise-unchanged legacy path) or a
    kernels.pallas.flash_training.FlashAttentionConfig: the fused flash
    fwd + custom_vjp bwd kernel wired DIRECTLY into the block (no
    registry hop), optionally with sep ring/Ulysses context parallelism
    over `sep_axis` (x then carries this rank's sequence shard)."""
    mp = lax.axis_size(mp_axis)
    heads_local = cfg.num_heads // mp
    B = x.shape[0]
    H = cfg.hidden_size
    from jax.ad_checkpoint import checkpoint_name
    from ..distributed.fleet.layers.mpu import mp_ops

    with jax.named_scope(SCOPES.qkv):
        h = _ln(x, p["ln1_g"], p["ln1_b"])
        if sp is None:
            S = x.shape[1]
            hi = mp_ops.c_identity(h, mp_axis)
            qkv = (_fp8_mm(fp8, "qkv")(hi.astype(cfg.dtype),
                                       p["qkv_w"].astype(cfg.dtype))
                   + p["qkv_b"].astype(cfg.dtype))  # [B, S, 3H/mp]
        else:
            S = x.shape[1] * mp  # x is this rank's sequence shard
            qkv = (mp_ops.ag_matmul(
                h.astype(cfg.dtype), p["qkv_w"].astype(cfg.dtype), mp_axis,
                ring=sp.ring,
                mm=None if fp8 is None else _fp8_mm(fp8, "qkv"))
                + p["qkv_b"].astype(cfg.dtype))  # [B, S, 3H/mp]
        # checkpoint_name tags are inert under plain jax.checkpoint (the
        # pipeline's stage checkpoint); hybrid_microbatch_share's policy
        # keys on them (ONE_STAGE_SAVE)
        qkv = checkpoint_name(qkv, "qkv")
        qkv = qkv.reshape(B, S, heads_local, 3, cfg.head_dim)
    # heads are fully local under TP, so per-shard attention is the whole
    # computation (over the FULL sequence under sp — only the
    # between-block residual stream is seq-sharded there; over this
    # rank's sequence SHARD under a sep-mode flash plan)
    with jax.named_scope(SCOPES.flash):
        if flash is not None:
            # training-grade path: the fused kernel (interpreter mode on
            # CPU tier-1) wired directly, bypassing the registry hop —
            # with flash.sep, ring/Ulysses context parallelism over
            # sep_axis
            from ..kernels.pallas import flash_training as _ft
            attn = _ft.attention(qkv[:, :, :, 0], qkv[:, :, :, 1],
                                 qkv[:, :, :, 2], flash, sep_axis=sep_axis)
        else:
            # registry op: Pallas flash on TPU (the engine's shard_map
            # runs with check_vma=False, so the kernel traces inside it);
            # composed O(S^2) fallback elsewhere
            attn = F.scaled_dot_product_attention(
                qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2],
                is_causal=True)
    with jax.named_scope(SCOPES.attn_out):
        attn = attn.reshape(B, S, H // mp)
        if sp is None:
            out = _fp8_mm(fp8, "proj")(attn, p["proj_w"].astype(cfg.dtype))
            out = mp_ops.mp_allreduce(out, mp_axis)
        else:
            out = mp_ops.matmul_rs(
                attn, p["proj_w"].astype(cfg.dtype), mp_axis, ring=sp.ring,
                mm=None if fp8 is None else _fp8_mm(fp8, "proj"))
        # tagged AFTER the collective: a policy that keeps it replays
        # neither the GEMM nor the all-reduce
        out = checkpoint_name(out, "proj")
        return x + (out + p["proj_b"].astype(cfg.dtype))


def _block_fn(p, x, cfg: GPTConfig, mp_axis: str = "mp", fp8=None, sp=None,
              flash=None, sep_axis=None):
    """One transformer block, explicit Megatron TP (runs inside shard_map;
    degenerates correctly at mp degree 1).

    QKV channel layout is HEAD-MAJOR: [H, heads * 3 * head_dim], so a
    contiguous column shard over 'mp' holds COMPLETE heads (each with its
    q, k and v) — a [H, 3H] q|k|v-major packing would split heads across
    ranks and silently corrupt attention under TP.

    fp8: this layer's {site: {x, w, g}} delayed scales (replicated over
    dp/mp) routing the four GEMMs through quantization.fp8.fp8_dot; each
    rank quantizes its LOCAL weight shard with the shared per-tensor
    scale, and the engine pmaxes the observed amaxes over dp/mp before
    the meta update.

    sp: None (plain TP: replicated activations, c_identity/mp_allreduce
    pairs — bitwise-unchanged legacy path) or a
    comm_overlap.MpOverlapConfig. With sp on, x arrives SEQUENCE-SHARDED
    [B, S/mp, H]: each pair becomes ag_matmul / matmul_rs (all_gather on
    the way into the column GEMM, reduce-scatter on the way out of the
    row GEMM — same wire bytes, 1/mp the LayerNorm/residual math and
    saved between-block activations), and sp.ring additionally decomposes
    those collectives into ppermute rings interleaved with the GEMM
    partial products (collective matmul; fp8 must be off — per-chunk
    fp8_dot calls would sum partial amax observations).

    flash/sep_axis: see _attn_sublayer — the attention implementation is
    the ONLY thing they change; every TP/sp collective stays as-is."""
    mp = lax.axis_size(mp_axis)
    from ..distributed.fleet.layers.mpu import mp_ops

    if sp is not None:
        # replicated-but-sequence-parallel params (the reference's
        # mark_as_sequence_parallel_parameter allreduce hook,
        # sequence_parallel_utils.py:192): LayerNorm weights and the
        # row-GEMM biases see only this rank's seq shard, so their local
        # grads are PARTIAL — identity-fwd/psum-bwd (c_identity) restores
        # the full-sequence gradient. mp-sharded leaves (qkv/fc1 weights
        # and biases, proj/fc2 weights) never need this: their grads come
        # from the gathered full-sequence activations.
        p = dict(p)
        for k in ("ln1_g", "ln1_b", "ln2_g", "ln2_b", "proj_b", "fc2_b"):
            p[k] = mp_ops.c_identity(p[k], mp_axis)
    x = _attn_sublayer(p, x, cfg, mp_axis, fp8=fp8, sp=sp, flash=flash,
                       sep_axis=sep_axis)
    return _mlp_sublayer(p, x, cfg, mp_axis, fp8=fp8, sp=sp)


@jax.named_scope(SCOPES.mlp)
def _mlp_sublayer(p, x, cfg: GPTConfig, mp_axis: str = "mp", fp8=None,
                  sp=None):
    """ln2 + Megatron-TP MLP + residual — the second half of the dense
    hybrid block (column-parallel fc1, row-parallel fc2; see _block_fn
    for the sp forms)."""
    from jax.ad_checkpoint import checkpoint_name
    from ..distributed.fleet.layers.mpu import mp_ops
    h = _ln(x, p["ln2_g"], p["ln2_b"])
    if sp is None:
        hi = mp_ops.c_identity(h, mp_axis)
        m = (_fp8_mm(fp8, "fc1")(hi.astype(cfg.dtype),
                                 p["fc1_w"].astype(cfg.dtype))
             + p["fc1_b"].astype(cfg.dtype))
    else:
        m = (mp_ops.ag_matmul(
            h.astype(cfg.dtype), p["fc1_w"].astype(cfg.dtype), mp_axis,
            ring=sp.ring,
            mm=None if fp8 is None else _fp8_mm(fp8, "fc1"))
            + p["fc1_b"].astype(cfg.dtype))
    m = checkpoint_name(m, "fc1")
    m = jax.nn.gelu(m.astype(jnp.float32), approximate=True).astype(cfg.dtype)
    if sp is None:
        m = _fp8_mm(fp8, "fc2")(m, p["fc2_w"].astype(cfg.dtype))
        m = mp_ops.mp_allreduce(m, mp_axis) + p["fc2_b"].astype(cfg.dtype)
    else:
        m = (mp_ops.matmul_rs(
            m, p["fc2_w"].astype(cfg.dtype), mp_axis, ring=sp.ring,
            mm=None if fp8 is None else _fp8_mm(fp8, "fc2"))
            + p["fc2_b"].astype(cfg.dtype))
    return x + m


def _moe_block_fn(p, x, cfg: GPTConfig, mp_axis: str = "mp",
                  ep_axis: str = "ep", mcfg=None, ef=None, flash=None):
    """One MoE transformer block of the hybrid path: the shared TP
    attention sublayer, then a switch-routed (top-1, capacity-bounded)
    expert FFN dispatched over the 'ep' mesh axis.

    Routing runs in fp32 on the LOCAL token shard (the gate is replicated
    over ep/mp, so every rank derives identical slot math for its own
    tokens); the routed [E, C, D] buffer crosses the ep axis through
    comm_overlap.a2a.expert_exchange — plain all-to-alls by default,
    index dispatch / int8-EF wire / chunked overlap per `mcfg`
    (MoeDispatchConfig). `ef` is this layer's {"disp", "comb"} residual
    slice when the exchange is quantized.

    Returns (x_out, stats, new_ef): stats = {"aux": switch load-balance
    loss E*sum(me*ce), "tokens": routed tokens per expert [E] (pre-drop),
    "kept": tokens that won a capacity slot} — per (layer, microbatch)
    execution, summed by the callers."""
    from ..incubate.distributed.models.moe.gate import (
        _capacity_dispatch, _capacity_dispatch_idx, _one_hot,
        compute_capacity)
    from ..incubate.distributed.models.moe.moe_layer import (
        _index_combine, _index_scatter)
    from ..distributed.comm_overlap import a2a as _a2a

    x = _attn_sublayer(p, x, cfg, mp_axis, flash=flash)
    h = _ln(x, p["ln2_g"], p["ln2_b"])
    B, S, H = h.shape
    T = B * S
    E = cfg.moe_num_experts
    xt = h.reshape(T, H).astype(cfg.dtype)
    # route in fp32 (the BaseGate.logits discipline: softmax/argmax
    # numerics matter more than MXU speed on a [T, E] matmul)
    logits = xt.astype(jnp.float32) @ p["gate_w"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_val = probs.max(axis=-1)
    expert = probs.argmax(axis=-1)
    me = probs.mean(axis=0)
    ce = _one_hot(expert, E).mean(axis=0)
    aux = jnp.sum(me * ce) * E  # Switch-Transformer load-balance loss
    C = compute_capacity(T, E, 1, cfg.moe_capacity_factor)
    index = mcfg is not None and mcfg.index
    if index:
        # zero-flop slot-id dispatch (the reference's CUDA global_scatter
        # analogue) — saves the 2*T*E*C*D one-hot einsum each way
        slot, gates, counts = _capacity_dispatch_idx(expert, gate_val, C, E)
        kept = jnp.sum((slot >= 0).astype(jnp.float32))
        dispatched, slot_safe = _index_scatter(xt, slot[:, None], E, C)
    else:
        combine, keep_tok, counts = _capacity_dispatch(expert, gate_val,
                                                       C, E)
        kept = jnp.sum(keep_tok.astype(jnp.float32))
        dispatched = jnp.einsum("tec,td->ecd",
                                (combine > 0).astype(xt.dtype), xt)

    def act(m):
        return jax.nn.gelu(m.astype(jnp.float32),
                           approximate=True).astype(cfg.dtype)

    returned, new_ef = _a2a.expert_exchange(
        dispatched, p["w1"].astype(cfg.dtype), p["b1"].astype(cfg.dtype),
        p["w2"].astype(cfg.dtype), p["b2"].astype(cfg.dtype),
        ep_axis=ep_axis, mp_axis=mp_axis, activation=act, cfg=mcfg,
        residuals=ef)
    if index:
        y = _index_combine(returned, gates[:, None], slot_safe)
    else:
        y = jnp.einsum("tec,ecd->td", combine.astype(returned.dtype),
                       returned)
    stats = {"aux": aux, "tokens": counts.astype(jnp.float32),
             "kept": kept}
    return x + y.reshape(B, S, H).astype(x.dtype), stats, new_ef


def _vocab_parallel_embed(wte_local, tokens, mp_axis: str = "mp"):
    from ..distributed.fleet.layers.mpu import mp_ops
    idx = lax.axis_index(mp_axis)
    per = wte_local.shape[0]
    local = tokens - idx * per
    ok = (local >= 0) & (local < per)
    safe = jnp.where(ok, local, 0)
    out = jnp.take(wte_local, safe, axis=0)
    out = jnp.where(ok[..., None], out, 0.0)
    # psum fwd / identity bwd: downstream is replicated across mp, so a raw
    # psum would deliver mp-times the cotangent to the local shard
    return mp_ops.mp_allreduce(out, mp_axis)


def _vocab_parallel_ce(logits_local, labels, mp_axis: str = "mp",
                       ignore_index: int = -100):
    """Stable vocab-sharded softmax CE; returns per-token loss."""
    mp_idx = lax.axis_index(mp_axis)
    per = logits_local.shape[-1]
    lf = logits_local.astype(jnp.float32)
    # max-shift is for stability only; its gradient cancels, and pmax has no
    # differentiation rule — stop_gradient is exact here
    from ..distributed.fleet.layers.mpu import mp_ops
    with jax.named_scope(SCOPES.coll_mp):
        lmax = lax.pmax(lax.stop_gradient(jnp.max(lf, -1, keepdims=True)),
                        mp_axis)
    shifted = lf - lmax
    # mp_allreduce (identity bwd) — see _vocab_parallel_embed
    lse = jnp.log(mp_ops.mp_allreduce(
        jnp.sum(jnp.exp(shifted), -1, keepdims=True), mp_axis)) + lmax
    local_label = labels - mp_idx * per
    ok = (local_label >= 0) & (local_label < per)
    safe = jnp.where(ok, local_label, 0)
    picked = jnp.take_along_axis(lf, safe[..., None], axis=-1)
    picked = mp_ops.mp_allreduce(jnp.where(ok[..., None], picked, 0.0), mp_axis)
    loss = (lse - picked)[..., 0]
    valid = labels != ignore_index
    return jnp.where(valid, loss, 0.0), valid


@jax.named_scope(SCOPES.embed)
def dense_embed(params, tokens, cfg: GPTConfig):
    """Token+position embedding over the embed sub-tree {wte, wpe}."""
    x = jnp.take(params["wte"], tokens, axis=0) + params["wpe"][None, :tokens.shape[1]]
    return x.astype(cfg.dtype)


def dense_block(p, x, cfg: GPTConfig, fp8=None, flash=None):
    """One transformer block on an UNstacked per-layer param tree — shared
    by the scan in dense_forward and the param-streaming trainer. fp8:
    this layer's {site: {x, w, g}} delayed scales — the qkv/proj/fc1/fc2
    GEMMs route through quantization.fp8.fp8_dot (None = plain bf16/f32
    path, bitwise-unchanged). flash: None or a FlashAttentionConfig —
    the fused kernel instead of the registry attention (sep does not
    apply to the single-device dense path)."""
    x = _dense_attn(p, x, cfg, fp8, flash)
    return _dense_mlp(p, x, cfg, fp8)


@jax.named_scope(SCOPES.attn)
def _dense_attn(p, x, cfg: GPTConfig, fp8, flash):
    from jax.ad_checkpoint import checkpoint_name
    B, S, H = x.shape
    with jax.named_scope(SCOPES.qkv):
        h = _ln(x, p["ln1_g"], p["ln1_b"])
        qkv = (_fp8_mm(fp8, "qkv")(h.astype(cfg.dtype),
                                   p["qkv_w"].astype(cfg.dtype))
               + p["qkv_b"].astype(cfg.dtype))
        # checkpoint_name tags are inert under plain jax.checkpoint; the
        # selective remat policy (dense_forward remat_save=) keys on them
        qkv = checkpoint_name(qkv, "qkv")
        qkv = qkv.reshape(B, S, cfg.num_heads, 3, cfg.head_dim)
    with jax.named_scope(SCOPES.flash):
        # whichever arm runs the kernel, its (out, lse) residuals carry
        # the FLASH_REMAT_NAMES tags, which dense_forward's policy keeps
        # wherever the kernel runs (_dense_attn_is_flash)
        if flash is not None:
            # direct fused path
            from ..kernels.pallas import flash_training as _ft
            attn = _ft.attention(qkv[:, :, :, 0], qkv[:, :, :, 1],
                                 qkv[:, :, :, 2], flash)
        else:
            # registry op: Pallas flash kernel on TPU (O(S) VMEM), XLA
            # composition elsewhere — same math as the hybrid engine's
            attn = F.scaled_dot_product_attention(
                qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2],
                is_causal=True)
        attn = checkpoint_name(attn, "attn_out")
    with jax.named_scope(SCOPES.attn_out):
        out = _fp8_mm(fp8, "proj")(attn.reshape(B, S, H),
                                   p["proj_w"].astype(cfg.dtype))
        return x + out + p["proj_b"].astype(cfg.dtype)


@jax.named_scope(SCOPES.mlp)
def _dense_mlp(p, x, cfg: GPTConfig, fp8):
    from jax.ad_checkpoint import checkpoint_name
    h = _ln(x, p["ln2_g"], p["ln2_b"])
    m = (_fp8_mm(fp8, "fc1")(h.astype(cfg.dtype),
                             p["fc1_w"].astype(cfg.dtype))
         + p["fc1_b"].astype(cfg.dtype))
    m = checkpoint_name(m, "fc1")
    m = jax.nn.gelu(m.astype(jnp.float32), approximate=True).astype(cfg.dtype)
    return (x + _fp8_mm(fp8, "fc2")(m, p["fc2_w"].astype(cfg.dtype))
            + p["fc2_b"].astype(cfg.dtype))


@jax.named_scope(SCOPES.head_loss)
def lm_logsumexp_ce(logits, labels):
    """Mean next-token CE in logsumexp+gather form, shared by the GPT and
    Llama dense losses. Logits stay in their compute dtype (bf16 on TPU)
    in HBM — the f32 convert fuses into the reduction, so the V-length
    accumulation is fp32 without a whole-tensor [B, S, V] fp32 copy (the
    largest write of the step) ever materializing; no [B, S, V]
    log_softmax either."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0].astype(jnp.float32)
    return jnp.mean(lse - picked)


@jax.named_scope(SCOPES.head_loss)
def dense_head_loss(params, x, labels, cfg: GPTConfig):
    """Final LN + LM head + logsumexp CE over the head sub-tree
    {lnf_g, lnf_b, head_w}. Identical math to dense_loss's tail."""
    x = _ln(x, params["lnf_g"], params["lnf_b"])
    logits = x.astype(cfg.dtype) @ params["head_w"].astype(cfg.dtype)
    return lm_logsumexp_ce(logits, labels)


def _dense_attn_is_flash(cfg: GPTConfig, batch: int, seq: int, flash) -> bool:
    """Whether _dense_attn's attention lowers to the flash kernel for a
    [batch, seq] step: always under a flash= plan (the kernel wired
    directly), else exactly when the registry's dispatch takes its Pallas
    arm for the block's q, k, v (asked of the op's own gate, on shapes)."""
    if flash is not None:
        return True
    qkv = jax.ShapeDtypeStruct((batch, seq, cfg.num_heads, cfg.head_dim),
                               cfg.dtype)
    return F.scaled_dot_product_attention.__op_schema__.takes_pallas(
        qkv, qkv, qkv, is_causal=True)


def dense_forward(params, tokens, cfg: GPTConfig, remat: bool = True,
                  remat_save=("attn_out", "qkv"), fp8=None, flash=None):
    """Single-device forward over the stacked-parameter pytree (no
    collectives). Same math/layout as the hybrid engine — head-major QKV.
    remat=True checkpoints each block (recompute in backward) — the memory/
    FLOPs trade that keeps long-sequence training inside HBM.
    remat_save: checkpoint_name'd intermediates kept instead of recomputed
    (see dense_block tags); pass remat_save=() for the minimum-memory
    full-remat form (bigger-than-HBM configs), which replays the whole
    block, the attention KERNEL included.

    What a block keeps follows which attention runs in it, decided at
    trace time (_dense_attn_is_flash). Where the flash kernel runs (a
    flash= plan, or the registry op on the chip at a supported shape) the
    kernel's (out, lse) residuals (FLASH_REMAT_NAMES) take "attn_out"'s
    place: "attn_out" is a pure reshape of "flash_out", and without lse
    (0.5 MB a layer) the backward kernels cannot start and the forward
    kernel runs a second time. Where the composed XLA attention runs
    (CPU, an unsupported shape) those tags do not exist and "attn_out" is
    the only copy. An explicit remat_save= gets the same substitution.
    So the default keeps qkv + (out, lse) on the chip and replays a
    block's norms, GELU and its proj and fc1 GEMMs (GPT-3 1.3B, 4 x 2048,
    one v5e: 608.8 ms a step against 629.5 with the kernel run twice,
    PERF.md §6, PR 39); keeping "fc1" as well does not fit beside four rows
    (the [24,4,2048,8192] stack is 3 GiB) and "proj" makes the compiler
    clone the fc1 replay.

    fp8: per-layer delayed scales, stacked [L] like the block params (see
    quantization.fp8.init_fp8_meta) — they ride the same scan, so each
    layer's amax observation comes back separately instead of summed. The
    selective-remat policy additionally saves the quantized operands
    (FP8_REMAT_NAMES) so backward reuses them instead of re-quantizing.

    flash: None or a FlashAttentionConfig — the fused attention kernel
    wired directly into every block instead of the registry op."""
    x = dense_embed(params, tokens, cfg)

    def block(p, x, f=None):
        return dense_block(p, x, cfg, fp8=f, flash=flash)

    if remat and remat_save:
        if fp8 is not None:
            from ..quantization.fp8 import FP8_REMAT_NAMES
            remat_save = tuple(remat_save) + tuple(FP8_REMAT_NAMES)
        if _dense_attn_is_flash(cfg, *tokens.shape, flash):
            remat_save = tuple(n for n in remat_save
                               if n != "attn_out") + FLASH_REMAT_NAMES
        blk = jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.save_only_these_names(
                *remat_save))
    elif remat:
        blk = jax.checkpoint(block)
    else:
        blk = block

    if fp8 is not None:
        def body(carry, pf):
            p, f = pf
            return blk(p, carry, f), None
        x, _ = lax.scan(body, x, (params["blocks"], fp8))
    else:
        def body(carry, p):
            return blk(p, carry), None
        x, _ = lax.scan(body, x, params["blocks"])
    with jax.named_scope(SCOPES.head_loss):
        x = _ln(x, params["lnf_g"], params["lnf_b"])
        return x.astype(cfg.dtype) @ params["head_w"].astype(cfg.dtype)


def dense_loss(params, tokens, labels, cfg: GPTConfig, remat: bool = True,
               remat_save=("attn_out", "qkv"), fp8=None, flash=None):
    """remat_save threads through to dense_forward — bigger-than-HBM
    callers (host-offloaded moments) pass () for the minimum-memory
    full-remat form. fp8: per-layer delayed scales; flash:
    fused-attention plan (see dense_forward)."""
    logits = dense_forward(params, tokens, cfg, remat=remat,
                           remat_save=remat_save, fp8=fp8, flash=flash)
    return lm_logsumexp_ce(logits, labels)


# ---------------------------------------------------------------------------
# Param-streaming (bigger-than-HBM) form: segmented params
# ---------------------------------------------------------------------------
def split_streamed_params(params, cfg: GPTConfig):
    """Stacked hybrid tree → segmented {embed, blocks: [per-layer], head}
    layout for the param-streaming trainer (small models / tests — a
    bigger-than-HBM model must use init_streamed_params instead, which
    never materializes the whole tree on device)."""
    blocks = [jax.tree.map(lambda a: a[i], params["blocks"])
              for i in range(cfg.num_layers)]
    return {
        "embed": {"wte": params["wte"], "wpe": params["wpe"]},
        "blocks": blocks,
        "head": {"lnf_g": params["lnf_g"], "lnf_b": params["lnf_b"],
                 "head_w": params["head_w"]},
    }


def init_streamed_params(cfg: GPTConfig, key, park=lambda t: t):
    """Segmented init that materializes ONE segment on device at a time,
    parking each through `park` (pinned_host placement) before the next is
    generated — a whole-tree init of a 6.7B model would OOM HBM before the
    first step ran. Same distributions as init_hybrid_params."""
    H, L, FF, V = (cfg.hidden_size, cfg.num_layers, cfg.ffn_hidden,
                   cfg.vocab_size)
    std, pd = 0.02, cfg.param_dtype
    k_embed, k_head, *k_blocks = jax.random.split(key, 2 + L)

    def nrm(key, shape, scale=std):
        return (scale * jax.random.normal(key, shape)).astype(pd)

    @jax.jit
    def one_block(key):
        ks = jax.random.split(key, 4)
        return {
            "ln1_g": jnp.ones((H,), pd), "ln1_b": jnp.zeros((H,), pd),
            "qkv_w": nrm(ks[0], (H, 3 * H)), "qkv_b": jnp.zeros((3 * H,), pd),
            "proj_w": nrm(ks[1], (H, H), std / math.sqrt(2 * L)),
            "proj_b": jnp.zeros((H,), pd),
            "ln2_g": jnp.ones((H,), pd), "ln2_b": jnp.zeros((H,), pd),
            "fc1_w": nrm(ks[2], (H, FF)), "fc1_b": jnp.zeros((FF,), pd),
            "fc2_w": nrm(ks[3], (FF, H), std / math.sqrt(2 * L)),
            "fc2_b": jnp.zeros((H,), pd),
        }

    @jax.jit
    def embed_init(key):
        k1, k2 = jax.random.split(key)
        return {"wte": nrm(k1, (V, H)), "wpe": nrm(k2, (cfg.max_seq_len, H))}

    @jax.jit
    def head_init(key):
        return {"lnf_g": jnp.ones((H,), pd), "lnf_b": jnp.zeros((H,), pd),
                "head_w": nrm(key, (H, V))}

    return {
        "embed": park(embed_init(k_embed)),
        "blocks": [park(one_block(k)) for k in k_blocks],
        "head": park(head_init(k_head)),
    }


def streamed_fns(cfg: GPTConfig):
    """(embed_fn, block_fn, head_loss_fn) for
    build_param_streamed_train_step — the same math as dense_loss."""
    return (lambda p, tokens: dense_embed(p, tokens, cfg),
            lambda p, x: dense_block(p, x, cfg),
            lambda p, x, labels: dense_head_loss(p, x, labels, cfg))


def _note_mp_wire(cfg, tokens, sp, mp_axis, pp_axis, num_microbatches,
                  n_block_layers, virtual_pp=1):
    """Deposit the analytic per-step mp wire bytes (trace-time constant)
    for the telemetry comms_bytes series — one shared accounting for the
    gpt and llama hybrid losses (both have 2 column/row GEMM pairs per
    block: attention + MLP). See observability.metrics.mp_wire_bytes for
    the per-term cost model.

    Executed-block count per schedule (every pipeline tick executes the
    stage body on every rank, bubbles included — those collectives move
    real bytes): 1F1B runs M+P-1 ticks of all L/P local layers; the
    interleaved schedule runs V*M+P-1 ticks of ONE L/(P*V)-layer chunk.
    ZBH1's forward matches 1F1B and its split backward is approximated
    by the same fwd+bwd pair model."""
    from ..observability import metrics as _metrics
    mp = lax.axis_size(mp_axis)
    P_ = lax.axis_size(pp_axis)
    b_local, S = tokens.shape
    dt = jnp.dtype(cfg.dtype).itemsize
    a_blk = (b_local // num_microbatches) * S * cfg.hidden_size * dt
    a_full = b_local * S * cfg.hidden_size * dt
    V = max(int(virtual_pp), 1)
    executed = (V * num_microbatches + P_ - 1) * (n_block_layers / V)
    mode = "allreduce" if sp is None else sp.mode
    _metrics.note_mp_comm(mode, _metrics.mp_wire_bytes(
        mode, mp,
        gemm_pair_bytes=2.0 * executed * a_blk,
        # embed psum + head boundary + the 4 CE reductions ([b, S, 1] f32)
        allreduce_bytes=2.0 * a_full + 4.0 * b_local * S * 4,
        scatter_bytes=a_full))


def _note_zero3_wire(z3, params, pp_axis, num_microbatches: int,
                     virtual_pp: int = 1):
    """Deposit the analytic per-step ZeRO-3 param-gather wire bytes
    (trace-time constant) for the telemetry comms_bytes series — one
    shared accounting for the gpt and llama hybrid losses. Must run on
    the ORIGINAL (dp-sharded) param leaves: local size x dp is each
    leaf's full-over-dp byte count. See
    observability.metrics.zero3_ag_wire_bytes for the cost model."""
    from ..observability import metrics as _metrics
    zax = z3["axis"]
    dp = lax.axis_size(zax)
    P_ = lax.axis_size(pp_axis)
    V = max(int(virtual_pp), 1)
    zd_blk = jax.tree.leaves(z3["zdims"]["blocks"])
    blk = sum(float(p.size) * dp * jnp.dtype(p.dtype).itemsize
              for p, zd in zip(jax.tree.leaves(params["blocks"]), zd_blk)
              if zd >= 0) / V  # one V-chunk's layers gather per tick
    other = sum(float(params[k].size) * dp
                * jnp.dtype(params[k].dtype).itemsize
                for k in z3["other_leaves"] if z3["zdims"][k] >= 0)
    p0 = jax.tree.leaves(params["blocks"])[0]
    _metrics.note_zero3_comm(_metrics.zero3_ag_wire_bytes(
        dp, block_param_bytes=blk,
        n_stage_executions=float(V * num_microbatches + P_ - 1),
        other_param_bytes=other, quantize=z3["cfg"].quantize,
        param_itemsize=jnp.dtype(p0.dtype).itemsize))


def _act_stats(x):
    """Per-layer activation health of one block output (trace-time, fp32):
    mean-square (rms after the host sqrt) and absmax — the numerics
    deposit each scan body makes when the plan's `act` is on.
    stop_gradient at the source: the stats are diagnostics riding the
    aux channel, and the downstream pmax has no differentiation rule."""
    xf = lax.stop_gradient(x).astype(jnp.float32)
    return {"sq": jnp.mean(xf * xf), "am": jnp.max(jnp.abs(xf))}


def _scatter_layer_stats(ys, pp_axis):
    """This pp rank's stacked per-layer stats [L_local] scattered into
    the GLOBAL layer vector [L_local x pp] at the rank's slice (zeros
    elsewhere) — the pipeline aux channel's psum over pp then assembles
    the full vector with no overlap. Shared by the gpt and llama hybrid
    losses."""
    L_loc = int(ys["sq"].shape[0])
    Lg = L_loc * lax.axis_size(pp_axis)
    pos = lax.axis_index(pp_axis) * L_loc
    return jax.tree.map(
        lambda v: lax.dynamic_update_slice(
            jnp.zeros((Lg,), jnp.float32), v.astype(jnp.float32), (pos,)),
        ys)


def _pack_num_aux(out, ys, num_act, pp_axis, extra=None):
    """ONE copy of the stage-aux packaging every scan branch shares
    (gpt + llama): the per-layer activation stats when the numerics
    plan asks, merged next to any existing side-channel entries (the
    z3ef residuals). Plain `out` when there is no aux — the pipeline
    is then called without with_aux and the program is
    bitwise-unchanged."""
    if extra is None and not num_act:
        return out
    aux = dict(extra or {})
    if num_act:
        aux["num"] = _scatter_layer_stats(ys, pp_axis)
    return out, aux


def _deposit_act_stats(aux, M: int, axes):
    """Observe the per-layer activation series from the pipeline aux
    (summed over the M valid ticks — /M is the mean over microbatches;
    rms additionally pmeans and absmax pmaxes over the data axes so the
    replicated telemetry row is rank-identical). Shared gpt/llama."""
    from ..observability import metrics as _metrics
    sq = aux["sq"] / float(M)
    am = aux["am"] / float(M)
    if axes:
        with jax.named_scope(SCOPES.coll_dp):
            sq = lax.pmean(sq, axes)
            am = lax.pmax(am, axes)
    for i in range(int(sq.shape[0])):
        _metrics.observe(f"num_act_rms_l{i}", jnp.sqrt(sq[i]))
        _metrics.observe(f"num_act_absmax_l{i}", am[i])


def _moe_pipeline(params, x_mb, cfg: GPTConfig, M: int, pp_axis, mp_axis,
                  ep_axis, mcfg, moe_ef, flash=None, z3=None):
    """1F1B pipeline over (dense, MoE) layer pairs with the aux side
    channel (spmd_pipeline with_aux): returns (out [M, mb, s, H], stats
    summed over every (layer, microbatch) execution and psum'd over pp,
    new flat moe_ef residuals or None). z3: ZeRO-3 plan — the pair scan
    gathers each (dense, MoE) layer pair's dp-sharded leaves on use
    (comm_overlap.zero3.scan_gather; the expert bank included — its ep/mp
    shardings keep their axes, dp is gathered away just like any other
    leaf)."""
    dense_p = params["blocks"]["dense"]
    moe_p = params["blocks"]["moe"]
    l2_local = jax.tree.leaves(dense_p)[0].shape[0]
    ef_p = None
    if moe_ef is not None:
        from ..distributed.comm_overlap import a2a as _a2a
        from ..incubate.distributed.models.moe.gate import compute_capacity
        T = x_mb.shape[1] * x_mb.shape[2]
        ep = lax.axis_size(ep_axis)
        C = compute_capacity(T, cfg.moe_num_experts, 1,
                             cfg.moe_capacity_factor)
        chunks = mcfg.chunks if (mcfg is not None and mcfg.overlap) else 1
        shapes = _a2a.moe_ef_local_shapes(cfg.moe_num_experts, C,
                                          cfg.hidden_size, ep, chunks)
        ef_p = {}
        for key, shp in shapes.items():
            want = l2_local * math.prod(shp)
            enforce(moe_ef[key].size == want,
                    "moe_ef residual size mismatch: the quantized-a2a "
                    "residuals were sized at build time from "
                    "moe_ef_tokens — pass the ACTUAL per-rank "
                    "(batch, seq) of the training data",
                    op="gpt.hybrid_loss_fn", leaf=key,
                    have=int(moe_ef[key].size), want=int(want))
            ef_p[key] = moe_ef[key].reshape((l2_local,) + shp)

    def stage_fn(bp, h):
        if moe_ef is not None:
            pd, pm, efl = bp

            if z3 is not None:
                from ..distributed.comm_overlap import zero3 as _z3g

                def pair_fn(p_full, carry, efll):
                    pdl, pml = p_full
                    hh = _block_fn(pdl, carry, cfg, mp_axis, flash=flash)
                    hh, st, nef = _moe_block_fn(pml, hh, cfg, mp_axis,
                                                ep_axis, mcfg, efll,
                                                flash=flash)
                    return hh, (st, nef)
                out, (st, nef), _ = _z3g.scan_gather(
                    pair_fn, h, (pd, pm),
                    (z3["zdims"]["blocks"]["dense"],
                     z3["zdims"]["blocks"]["moe"]),
                    z3["axis"], extras=(efl,), cfg=z3["cfg"])
            else:
                def body(carry, xs):
                    pdl, pml, efll = xs
                    hh = _block_fn(pdl, carry, cfg, mp_axis, flash=flash)
                    hh, st, nef = _moe_block_fn(pml, hh, cfg, mp_axis,
                                                ep_axis, mcfg, efll,
                                                flash=flash)
                    return hh, (st, nef)
                out, (st, nef) = lax.scan(body, h, (pd, pm, efl))
        else:
            pd, pm = bp

            if z3 is not None:
                from ..distributed.comm_overlap import zero3 as _z3g

                def pair_fn(p_full, carry):
                    pdl, pml = p_full
                    hh = _block_fn(pdl, carry, cfg, mp_axis, flash=flash)
                    hh, st, _ = _moe_block_fn(pml, hh, cfg, mp_axis,
                                              ep_axis, mcfg, None,
                                              flash=flash)
                    return hh, st
                out, st, _ = _z3g.scan_gather(
                    pair_fn, h, (pd, pm),
                    (z3["zdims"]["blocks"]["dense"],
                     z3["zdims"]["blocks"]["moe"]),
                    z3["axis"], cfg=z3["cfg"])
            else:
                def body(carry, xs):
                    pdl, pml = xs
                    hh = _block_fn(pdl, carry, cfg, mp_axis, flash=flash)
                    hh, st, _ = _moe_block_fn(pml, hh, cfg, mp_axis,
                                              ep_axis, mcfg, None,
                                              flash=flash)
                    return hh, st
                out, st = lax.scan(body, h, (pd, pm))
            nef = ()
        return out, {"stats": jax.tree.map(lambda a: a.sum(axis=0), st),
                     "ef": nef}

    stage_args = ((dense_p, moe_p) if moe_ef is None
                  else (dense_p, moe_p, ef_p))
    out, aux = spmd_pipeline(stage_fn, stage_args, x_mb, axis=pp_axis,
                             with_aux=True)
    new_ef = None
    if moe_ef is not None:
        new_ef = {k: v.reshape(-1) for k, v in aux["ef"].items()}
    return out, aux["stats"], new_ef


def _note_moe_wire(cfg: GPTConfig, tokens, mp_axis, pp_axis, ep_axis,
                   num_microbatches: int, n_pairs_local: int, mcfg):
    """Analytic per-step wire deposits for the GPT-MoE hybrid loss
    (trace-time constants): the mp term — a (dense, MoE) pair costs the
    dense layer's 2 column/row GEMM pairs plus the MoE attention's 1,
    and the expert FFN's forward-only mp all-reduce of the arrived
    [E, C, D] buffer — via note_mp_comm, and the ep dispatch/combine
    all-to-all term via note_ep_comm. The telemetry tests re-derive both
    independently (the PR 5 pattern)."""
    from ..incubate.distributed.models.moe.gate import compute_capacity
    from ..observability import metrics as _metrics
    mp = lax.axis_size(mp_axis)
    P_ = lax.axis_size(pp_axis)
    ep = lax.axis_size(ep_axis)
    b_local, S = tokens.shape
    M = num_microbatches
    dt = jnp.dtype(cfg.dtype).itemsize
    H, E = cfg.hidden_size, cfg.moe_num_experts
    C = compute_capacity((b_local // M) * S, E, 1, cfg.moe_capacity_factor)
    a_blk = (b_local // M) * S * H * dt
    a_full = b_local * S * H * dt
    executed = (M + P_ - 1) * n_pairs_local
    _metrics.note_mp_comm("allreduce", _metrics.mp_wire_bytes(
        "allreduce", mp,
        gemm_pair_bytes=3.0 * executed * a_blk,
        allreduce_bytes=(2.0 * a_full + 4.0 * b_local * S * 4
                         + executed * float(E * C * H * dt))))
    _metrics.note_ep_comm(_metrics.ep_a2a_wire_bytes(
        ep, payload_elems=float(E * C * H),
        n_layer_executions=float(executed), itemsize=dt,
        quantize=bool(mcfg is not None and mcfg.quantize)))


def _hybrid_embed(params, tokens, cfg: GPTConfig, mp_axis, sp, sep_on,
                  sep_axis):
    """Vocabulary-parallel token embedding + positions of the hybrid loss:
    [b, S, H], or this rank's [b, S/mp, H] sequence shard under sp."""
    S = tokens.shape[1]
    with jax.named_scope(SCOPES.embed):
        x = _vocab_parallel_embed(params["wte"], tokens, mp_axis)
        if sep_on:
            # tokens are this rank's sequence shard: position embedding reads
            # the rank's GLOBAL slice (causal masking inside ring/Ulysses
            # likewise uses global positions). The GLOBAL length must fit the
            # table — dynamic_slice CLAMPS an out-of-range start, so an
            # oversized sequence would silently hand later ranks the first
            # ranks' position rows instead of erroring
            n_sep = lax.axis_size(sep_axis)
            enforce(S * n_sep <= cfg.max_seq_len,
                    "sep context parallelism: the global sequence "
                    "(per-rank S x sep degree) must fit max_seq_len — the "
                    "position table is sliced per rank",
                    op="gpt.hybrid_loss_fn", seq_local=S, sep=n_sep,
                    max_seq_len=cfg.max_seq_len)
            off = lax.axis_index(sep_axis) * S
            x = x + lax.dynamic_slice_in_dim(params["wpe"], off, S,
                                             axis=0)[None]
        else:
            x = x + params["wpe"][None, :S]
        x = x.astype(cfg.dtype)
    if sp is not None:
        from ..distributed.comm_overlap import collective_matmul as _cm
        enforce(S % lax.axis_size(mp_axis) == 0,
                "sequence parallelism needs S divisible by the mp degree",
                op="gpt.hybrid_loss_fn", seq=S,
                mp=lax.axis_size(mp_axis))
        x = _cm.scatter_seq(x, mp_axis, dim=1)  # [b_local, S/mp, H]
    return x


def _head_logits(params, out, cfg: GPTConfig, mp_axis, sp):
    """Final LayerNorm and the column-parallel head of the hybrid loss:
    this mp rank's [b, S, V/mp] logits."""
    from ..distributed.fleet.layers.mpu import mp_ops
    lnf_g, lnf_b = params["lnf_g"], params["lnf_b"]
    if sp is not None:
        # final LN runs on the seq shard — its param grads are partial
        # (see the _block_fn sp note)
        lnf_g = mp_ops.c_identity(lnf_g, mp_axis)
        lnf_b = mp_ops.c_identity(lnf_b, mp_axis)
    out = _ln(out, lnf_g, lnf_b)
    if sp is None:
        # column-parallel head: identity fwd / allreduce bwd on its input
        out = mp_ops.c_identity(out, mp_axis)
        logits_local = (out.astype(cfg.dtype)
                        @ params["head_w"].astype(cfg.dtype))
    else:
        # seq-sharded final LN, then AG -> column GEMM (bwd RS) — same
        # wire as the allreduce-mode head boundary
        logits_local = mp_ops.ag_matmul(
            out.astype(cfg.dtype), params["head_w"].astype(cfg.dtype),
            mp_axis, ring=sp.ring)
    return logits_local


# What a block of hybrid_microbatch_share keeps for its backward besides
# its input: every GEMM's output (qkv and fc1 with their biases, the
# attention projection AFTER its mp all-reduce) and the flash kernel's
# (out, lse): ~107 MB a layer and microbatch at the 6.7B widths on mp 2.
# No GEMM, kernel or collective runs again; the LayerNorms, the GELU and
# the residual adds do, from those. Kept whole, AD's float32 residuals of
# the GELU and the norms are 0.55 GB a layer (five [S, F/mp] and four
# [S, H] float32 stacks): the compiler then re-materialises GEMMs of its
# own choosing to fit them (PERF.md, PR 37).
ONE_STAGE_SAVE = ("qkv", "proj", "fc1") + FLASH_REMAT_NAMES


def hybrid_microbatch_share(params, tokens, labels, denom, layers,
                            cfg: GPTConfig, pp_axis="pp", mp_axis="mp",
                            sp=None, flash=None, sep_axis="sep"):
    """One microbatch's share of the per-device loss on a mesh with ONE
    pipeline stage (runs inside shard_map): embedding, the block scan,
    final norm, head and vocabulary-parallel loss of these rows, the token
    losses summed and divided by `denom`, the valid labels of the WHOLE
    local batch. No pipeline is built (no tick scan, no ppermute) and the
    blocks carry no stage checkpoint: the engine differentiates one share
    at a time (hybrid_engine.AccumulatedLoss), so one microbatch's
    residuals are alive; each block keeps ONE_STAGE_SAVE and the head its
    logits, so no GEMM, kernel or collective is run again. `layers` runs
    the blocks (the engine's: hybrid_engine.scan_layers, or the scan whose
    backward adds a layer's gradient to the microbatches' sum where it is
    made). sp / flash / sep_axis: as hybrid_loss_fn."""
    sep_on = flash is not None and flash.sep is not None
    x = _hybrid_embed(params, tokens, cfg, mp_axis, sp, sep_on, sep_axis)

    # prevent_cse=False: the block runs in a scan's body, where the
    # forward and its replay cannot be merged anyway, and the barriers it
    # saves cost 3% of the cell's step (PERF.md, PR 37)
    block = jax.checkpoint(
        lambda p, x: _block_fn(p, x, cfg, mp_axis, sp=sp, flash=flash,
                               sep_axis=sep_axis),
        prevent_cse=False,
        policy=jax.checkpoint_policies.save_only_these_names(
            *ONE_STAGE_SAVE))

    out = layers(block, x, params["blocks"])
    with jax.named_scope(SCOPES.head_loss):
        logits_local = _head_logits(params, out, cfg, mp_axis, sp)
        _note_mp_wire(cfg, tokens, sp, mp_axis, pp_axis, 1,
                      jax.tree.leaves(params["blocks"])[0].shape[0])
        return share_of_loss(logits_local, labels, denom, mp_axis)


def share_of_loss(logits_local, labels, denom, mp_axis):
    """A microbatch's token losses (vocabulary-parallel) summed over
    `denom`. The loss reads the logits four times (their max, the sum of
    exponentials, the label's, the backward's softmax) with an mp
    all-reduce between the readers; in a loop's body the compiler runs the
    head GEMM again for each reader rather than keep them (PERF.md, PR 37),
    so they are made a value it cannot re-derive."""
    loss, _ = _vocab_parallel_ce(lax.optimization_barrier(logits_local),
                                 labels, mp_axis)
    return jnp.sum(loss) / denom


def one_stage_loss(loss_fn, num_microbatches: int, share, loss_axes):
    """The hybrid_engine.AccumulatedLoss of a language-model loss, for the
    gpt and llama builders: `loss_fn` is the builder's own (the pipeline
    path, for an engine that does not accumulate);
    `share(params, tokens, labels, denom, layers)` is one microbatch's
    token losses summed over `denom`, the local batch's valid labels
    (_vocab_parallel_ce's ignore_index); the step reports the mean over
    `loss_axes`, the data axes, as hybrid_loss_fn does."""
    from .hybrid_engine import AccumulatedLoss

    def reported(total):
        with jax.named_scope(SCOPES.coll_dp):
            return lax.pmean(total, loss_axes)
    return AccumulatedLoss(
        loss_fn, num_microbatches,
        lambda labels: jnp.maximum(jnp.sum(labels != -100), 1),
        share, reported)


def hybrid_loss_fn(params, tokens, labels, cfg: GPTConfig,
                   num_microbatches: int, dp_axis="dp", pp_axis="pp",
                   mp_axis="mp", virtual_pp: int = 1,
                   schedule: str = "1F1B", fp8=None, sp=None,
                   ep_axis="ep", moe=None, moe_ef=None, flash=None,
                   sep_axis="sep", z3=None, z3_ef=None, num=None):
    """Per-device loss of the full hybrid GPT (runs inside shard_map).

    num_microbatches: the slices that fill spmd_pipeline's M + P - 1 ticks.
    This function always builds the pipeline, at pp = 1 too (one stage,
    M ticks); build_hybrid_train_step does not call it on a mesh with one
    pipeline stage unless a side channel needs it, and differentiates
    hybrid_microbatch_share one microbatch at a time instead.

    tokens/labels: this dp shard's batch [b_local, S]. virtual_pp > 1 runs
    the interleaved schedule (blocks must be stacked in
    vpp_block_permutation order — build_hybrid_train_step does this).
    schedule="ZBH1" selects the zero-bubble pipeline
    (PipelineZeroBubblePass / spmd_pipeline_zero_bubble).
    fp8: this pp rank's stacked [L/pp] delayed scales (sharded over pp
    like the block params); 1F1B schedule only — the interleaved/ZB
    permutations would need the same block reorder applied to the scales.
    sp: None (plain TP, bitwise-unchanged) or comm_overlap.MpOverlapConfig
    — sequence-parallel TP: activations between blocks (and through the
    pp ppermutes, whose transfers shrink mp-fold too) are seq-sharded
    over mp; the LM head becomes an ag_matmul and the embedding output is
    seq-scattered. Requires S % mp == 0.

    GPT-MoE (cfg.moe_num_experts > 0): the batch is sharded over dp AND
    ep (b_local is the per-(dp, ep)-rank shard), every second layer runs
    the expert FFN over the ep axis, the switch load-balance loss rides
    the pipeline's aux channel (spmd_pipeline with_aux) weighted by
    cfg.moe_aux_weight, and the final loss pmean spans (dp, ep). moe: a
    comm_overlap.MoeDispatchConfig (or None for the dense-dispatch
    baseline); moe_ef: this rank's flat {"disp", "comb"} int8
    error-feedback residuals when the exchange is quantized — the return
    value then becomes (loss, new_moe_ef). 1F1B only; not composed with
    fp8 or sequence parallelism (the MoE block runs the
    replicated-activation TP path).

    flash: None (composed-einsum attention, bitwise-unchanged) or a
    kernels.pallas.flash_training.FlashAttentionConfig — the fused flash
    kernel in every block. With flash.sep set, tokens/labels arrive
    SEQUENCE-SHARDED over `sep_axis` ([b_local, S/sep] per rank): the
    position embedding reads this rank's global slice, attention runs
    ring/Ulysses context parallelism per shard, and the loss mean spans
    (dp, sep). Not composed with sp (both shard the sequence dim) or
    MoE (enforced at build).

    z3: None (params arrive full per mp/pp shard — bitwise-unchanged) or
    the ZeRO-3 plan from build_hybrid_train_step ({"zdims": per-leaf dp
    shard dims, "axis": dp axis, "cfg": comm_overlap.zero3.Zero3Config,
    "other_leaves": the once-per-step leaf names}): every dp-shardable
    param leaf then arrives as this rank's 1/dp SHARD and is
    all-gathered ON USE — embeddings/head/final-LN once at their sites,
    the stacked block leaves per layer inside the stage scan
    (scan_gather: block i+1's gather issues beside block i's compute;
    the checkpointed stage bodies re-gather in the backward). z3_ef:
    this rank's stacked int8-EF residual tree when the block gathers are
    quantized — the return value then becomes (loss, new_z3_ef)
    (pp degree 1, one pipeline microbatch, enforced at build).

    num: None or an observability.numerics.NumericsConfig — with
    num.act the dense block scan additionally emits each layer's
    activation mean-square/absmax as scan ys, the pipeline aux channel
    assembles the global per-layer vectors (each pp rank scatters its
    slice; valid-tick masked, psum'd over pp), and the loss observes
    the ``num_act_rms_l<i>`` / ``num_act_absmax_l<i>`` telemetry series
    (mean over microbatches, pmean/pmax over the data axes so the
    replicated row is rank-identical). Plain-1F1B dense path only (the
    aux channel); per-layer GRAD norms are engine-side and cover every
    schedule. None is bitwise-unchanged.
    """
    b_local, S = tokens.shape
    M = num_microbatches
    enforce(b_local % M == 0,
            "per-dp-rank batch must be divisible by num_microbatches",
            op="gpt.hybrid_loss_fn", batch_local=b_local, microbatches=M)
    enforce(fp8 is None or (virtual_pp == 1 and schedule == "1F1B"),
            "fp8 delayed scaling supports the 1F1B schedule only",
            op="gpt.hybrid_loss_fn", virtual_pp=virtual_pp,
            schedule=schedule)
    moe_on = cfg.moe_on
    if moe_on:
        enforce(fp8 is None and sp is None,
                "the GPT-MoE hybrid path is not composed with fp8 delayed "
                "scaling or sequence parallelism (the MoE block runs the "
                "replicated-activation TP path)", op="gpt.hybrid_loss_fn")
        enforce(virtual_pp == 1 and schedule == "1F1B",
                "GPT-MoE supports the 1F1B schedule only (the aux channel "
                "and expert stacking follow the plain pipeline layout)",
                op="gpt.hybrid_loss_fn", virtual_pp=virtual_pp,
                schedule=schedule)
    sep_on = flash is not None and flash.sep is not None
    if sep_on:
        enforce(sp is None and not moe_on,
                "sep context parallelism shards the sequence dim — not "
                "composed with mp sequence parallelism (which also "
                "shards it) or the MoE batch layout",
                op="gpt.hybrid_loss_fn")
    if z3 is not None:
        from ..distributed.comm_overlap import zero3 as _z3g
        # analytic AG/RS wire deposit from the ORIGINAL (sharded) leaves
        _note_zero3_wire(z3, params, pp_axis, M, virtual_pp=virtual_pp)
        # once-per-step leaves gather at their (single) use sites: a
        # shallow copy swaps the shards for the gathered leaves so the
        # downstream code is byte-identical to the replicated path
        params = dict(params)
        for name in z3["other_leaves"]:
            zd_ = z3["zdims"][name]
            if zd_ >= 0:
                params[name] = _z3g.all_gather_param(params[name], zd_,
                                                     z3["axis"])
    x = _hybrid_embed(params, tokens, cfg, mp_axis, sp, sep_on, sep_axis)
    x_mb = x.reshape(M, b_local // M, x.shape[1], cfg.hidden_size)

    moe_stats = None
    new_z3_ef = None
    num_aux = None
    num_act = num is not None and num.act
    if num_act:
        enforce(not moe_on and virtual_pp == 1 and schedule == "1F1B",
                "per-layer activation telemetry rides the plain 1F1B "
                "pipeline's aux channel (the builders disable num.act "
                "for MoE/ZBH1/VPP — per-layer grad norms stay on)",
                op="gpt.hybrid_loss_fn")
    if moe_on:
        out, moe_stats, new_moe_ef = _moe_pipeline(
            params, x_mb, cfg, M, pp_axis, mp_axis, ep_axis, moe, moe_ef,
            flash=flash, z3=z3)
    else:
        def _y(out):
            # per-layer scan output: activation health when the numerics
            # plan asks for it (None keeps the scan ys empty — bitwise)
            return _act_stats(out) if num_act else None

        def stage_fn(block_params, h):
            if fp8 is not None:
                blocks, scales = block_params
                if z3 is not None:
                    def blk_fn(p, c, f):
                        o = _block_fn(p, c, cfg, mp_axis, fp8=f,
                                      sp=sp, flash=flash,
                                      sep_axis=sep_axis)
                        return o, _y(o)
                    out, ys, _ = _z3g.scan_gather(
                        blk_fn, h, blocks, z3["zdims"]["blocks"],
                        z3["axis"], extras=(scales,), cfg=z3["cfg"])
                else:
                    def body(carry, pf):
                        p, f = pf
                        o = _block_fn(p, carry, cfg, mp_axis, fp8=f,
                                      sp=sp, flash=flash,
                                      sep_axis=sep_axis)
                        return o, _y(o)
                    out, ys = lax.scan(body, h, (blocks, scales))
                return _pack_num_aux(out, ys, num_act, pp_axis)

            if z3 is not None and z3_ef is not None:
                blocks, resid = block_params

                def blk_fn(p, c):
                    o = _block_fn(p, c, cfg, mp_axis, sp=sp,
                                  flash=flash, sep_axis=sep_axis)
                    return o, _y(o)
                out, ys, nres = _z3g.scan_gather(
                    blk_fn, h, blocks, z3["zdims"]["blocks"], z3["axis"],
                    cfg=z3["cfg"], residuals=resid)
                return _pack_num_aux(out, ys, num_act, pp_axis,
                                     extra={"z3ef": nres})

            if z3 is not None:
                def blk_fn(p, c):
                    o = _block_fn(p, c, cfg, mp_axis, sp=sp,
                                  flash=flash, sep_axis=sep_axis)
                    return o, _y(o)
                out, ys, _ = _z3g.scan_gather(
                    blk_fn, h, block_params, z3["zdims"]["blocks"],
                    z3["axis"], cfg=z3["cfg"])
                return _pack_num_aux(out, ys, num_act, pp_axis)

            def body(carry, p):
                o = _block_fn(p, carry, cfg, mp_axis, sp=sp,
                              flash=flash, sep_axis=sep_axis)
                return o, _y(o)
            out, ys = lax.scan(body, h, block_params)
            return _pack_num_aux(out, ys, num_act, pp_axis)

        stage_params = (params["blocks"] if fp8 is None
                        else (params["blocks"], fp8))
        if z3 is not None and z3_ef is not None:
            # quantized gathers: the refreshed EF residuals ride the
            # pipeline's aux side channel (pp degree 1 / one microbatch,
            # enforced at build — the single valid tick IS the step)
            out, aux = spmd_pipeline(stage_fn, (params["blocks"], z3_ef),
                                     x_mb, axis=pp_axis, with_aux=True)
            new_z3_ef = aux["z3ef"]
            num_aux = aux.get("num")
        elif virtual_pp > 1:
            out = spmd_pipeline_interleaved(
                stage_fn, vpp_chunk_blocks(params["blocks"], virtual_pp),
                x_mb, axis=pp_axis)
        elif schedule == "ZBH1":
            out = spmd_pipeline_zero_bubble(stage_fn, params["blocks"],
                                            x_mb, axis=pp_axis)
        elif num_act:
            # the activation stats ride the same valid-tick-masked aux
            # side channel the MoE routing stats use
            out, aux = spmd_pipeline(stage_fn, stage_params, x_mb,
                                     axis=pp_axis, with_aux=True)
            num_aux = aux["num"]
        else:
            out = spmd_pipeline(stage_fn, stage_params, x_mb, axis=pp_axis)
    with jax.named_scope(SCOPES.head_loss):
        out = out.reshape(b_local, x.shape[1], cfg.hidden_size)
        logits_local = _head_logits(params, out, cfg, mp_axis, sp)
        if moe_on:
            _note_moe_wire(cfg, tokens, mp_axis, pp_axis, ep_axis, M,
                           jax.tree.leaves(params["blocks"]["dense"])[0]
                           .shape[0], moe)
        else:
            _note_mp_wire(cfg, tokens, sp, mp_axis, pp_axis, M,
                          jax.tree.leaves(params["blocks"])[0].shape[0],
                          virtual_pp=virtual_pp)
        if num_aux is not None:
            # sp shards the sequence over mp (per-rank shards differ); plain
            # TP replicates the activations, so mp needs no reduction there
            _deposit_act_stats(num_aux, M,
                               (dp_axis,)
                               + ((mp_axis,) if sp is not None else ())
                               + ((sep_axis,) if sep_on else ()))
        loss, valid = _vocab_parallel_ce(logits_local, labels, mp_axis)
        total = jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1)
    if moe_on:
        from ..observability import metrics as _metrics
        L2 = cfg.num_layers // 2
        # aux summed over every (layer, microbatch) execution -> mean
        aux_mean = moe_stats["aux"] / float(L2 * M)
        total = total + jnp.float32(cfg.moe_aux_weight) * aux_mean
        routed = float(L2 * M) * float((b_local // M) * S)
        _metrics.observe("moe_drop_frac",
                         1.0 - moe_stats["kept"] / routed)
        if cfg.moe_num_experts <= 32:
            for i in range(cfg.moe_num_experts):
                _metrics.observe(f"moe_tokens_e{i}",
                                 moe_stats["tokens"][i])
        # the batch is sharded over dp AND ep — the loss mean spans both
        with jax.named_scope(SCOPES.coll_dp):
            total = lax.pmean(total, (dp_axis, ep_axis))
        if moe_ef is not None:
            return total, new_moe_ef
        return total
    if sep_on:
        # sequence shards are equal-size (and every position valid), so
        # the mean of per-shard means IS the global mean; sep grads are
        # genuinely partial and combine through the engine's
        # extra_grad_axes pmean — the same convention as dp
        with jax.named_scope(SCOPES.coll_dp):
            total = lax.pmean(total, (dp_axis, sep_axis))
    else:
        with jax.named_scope(SCOPES.coll_dp):
            total = lax.pmean(total, dp_axis)
    if z3_ef is not None:
        return total, new_z3_ef
    return total


def moe_telemetry_series(cfg: GPTConfig):
    """Telemetry series the GPT-MoE hybrid loss observes: the
    capacity-drop fraction plus (for expert counts small enough to chart)
    one routed-tokens series per expert. build_hybrid_train_step
    registers these onto an explicitly-passed TelemetryConfig; flag-driven
    telemetry registers them via FLAGS_telemetry_extra."""
    if not cfg.moe_on:
        return ()
    series = ("moe_drop_frac",)
    if cfg.moe_num_experts <= 32:
        series += tuple(f"moe_tokens_e{i}"
                        for i in range(cfg.moe_num_experts))
    return series


def build_hybrid_train_step(cfg: GPTConfig, mesh: Mesh, optimizer,
                            num_microbatches: int = 1, dp_axis="dp",
                            pp_axis="pp", mp_axis="mp", extra_grad_axes=(),
                            virtual_pp: int = 1, schedule: str = "1F1B",
                            grad_reduce_dtype="auto",
                            zero1_dp: bool = False, zero_stage="auto",
                            zero3="auto", comm_overlap="auto",
                            fp8="auto", telemetry="auto",
                            mp_overlap="auto", ep_axis="ep",
                            moe_dispatch="auto", moe_ef_tokens=None,
                            flash_attention="auto", sep_axis="sep",
                            numerics="auto", donate: bool = True):
    """Compile the full hybrid train step: one program containing embedding,
    the blocks, vocab-parallel loss, backward, dp grad sync and the
    optimizer update. Returns (step_fn, shard_params_fn, init_state_fn).
    The step owns (params, opt_state): it donates them, so rebind all of
    its outputs; donate=False keeps a caller's inputs alive (see
    hybrid_engine.build_train_step).

    num_microbatches: how many slices the per-dp-rank batch is cut into.
    On a mesh with pp > 1 they fill the pipeline (spmd_pipeline: M + P - 1
    ticks, every stage checkpointed and replayed in its backward). On a
    mesh whose pp axis has ONE rank there is no pipeline to fill and they
    are gradient accumulation: the step scans over the microbatches, each
    iteration the forward AND the backward of one (embedding, the block
    scan with no stage checkpoint, head, loss), whose backward adds each
    gradient to the carry's sum where it is made (a layer's inside the
    block scan: hybrid_engine.build_train_step), and reduces over dp,
    clips and updates ONCE; the loss is still sum(token losses) / valid
    labels of the whole batch. One microbatch's residuals are alive at a
    time, as under the replay, and no pass runs twice. The builder reads
    this off the mesh it is given
    (mesh.shape[pp_axis] == 1); virtual_pp and schedule say nothing at
    pp = 1. Builds whose loss rides the pipeline's side channels (fp8,
    GPT-MoE, per-layer activation numerics, zero_stage 3) and an engine
    with its own accumulation scan (comm_overlap) keep the pipeline path at
    pp = 1 too.

    virtual_pp > 1 selects the interleaved schedule; shard_params then
    reorders the stacked blocks into the chunk-major layout (checkpoints
    saved from these sharded params are in that layout — reload through
    the same shard_params). schedule="ZBH1" selects the zero-bubble
    pipeline (what PipelineZeroBubblePass sets on a TrainSpec).

    comm_overlap: "auto" (flag-driven, default off) / None /
    CommOverlapConfig — replaces the monolithic end-of-backward dp pmean
    with bucketed, schedule-overlapped (optionally int8 error-feedback)
    collectives; see hybrid_engine.build_train_step. When the overlap
    scan accumulates over its own microbatches, the per-dp-rank batch
    must divide comm microbatches x pipeline num_microbatches.

    fp8: "auto" (FLAGS_fp8 / amp O3, default off) / bool — route the
    block GEMMs (GPT_FP8_SITES) through delayed-scaling fp8_dot; the
    (scale, amax_history) state rides opt_state["fp8_meta"], sharded
    over pp with the stacked blocks, and amaxes pmax over dp/mp (+extra
    axes) so scales stay replicated. 1F1B schedule only.

    mp_overlap: "auto" (FLAGS_mp_seq_parallel / FLAGS_mp_collective_
    matmul, default off) / None / mode string / MpOverlapConfig —
    sequence-parallel TP over the mp axis, optionally with the AG/RS
    boundaries decomposed into ppermute ring collective matmuls
    (distributed.comm_overlap.collective_matmul). Off: the allreduce
    path compiles BITWISE-identically to a build without the argument.
    collective_matmul composes with everything but fp8 (the ring's
    per-chunk GEMMs would sum partial amax observations — seq_parallel
    itself composes with fp8 fine: the site GEMMs see the gathered
    full-sequence input exactly as the allreduce path does).

    GPT-MoE (cfg.moe_num_experts > 0): the mesh must carry an `ep_axis`
    (degree >= 1, dividing the expert count); the batch shards over
    dp x ep and every second layer's FFN becomes the switch-routed
    expert bank exchanged over ep. moe_dispatch: "auto" reads
    FLAGS_moe_index_dispatch / FLAGS_moe_quantize_a2a / FLAGS_moe_overlap
    (all off = the dense-dispatch plain-exchange baseline, which
    compiles BITWISE-identically to an explicit moe_dispatch=None
    build); a comm_overlap.MoeDispatchConfig forces. The quantized
    exchange threads int8 error-feedback residuals through
    opt_state["moe_ef"] and needs moe_ef_tokens=(per-rank batch, seq)
    to size them at build time (pp degree 1, one pipeline microbatch).
    Not composed with fp8, sequence parallelism, VPP or ZBH1.

    zero_stage: "auto" (FLAGS_zero_stage, default 0) / None / 0/1/2/3 —
    ZeRO sharding over the dp axis (hybrid_engine.build_train_step).
    Stage 1 == the legacy zero1_dp=True (dp-sharded optimizer state);
    stage 2 additionally accounts the grad buffer dp-sharded (same
    compiled collectives — the reduce-scatter already owns the dp sync);
    stage 3 shards the PARAMS over dp at rest and gathers each block's
    leaves on use inside the layer scan (prefetched per
    FLAGS_zero3_overlap_ag; the checkpointed stage bodies re-gather in
    the backward, so live full params stay O(1 block)). Stage 3
    composes with mp/pp (all schedules), sp/ring, fp8, flash, sep and
    MoE exactly as stage 1 does. zero3: "auto" (flags) / None /
    comm_overlap.zero3.Zero3Config — the stage-3 gather knobs; with
    .quantize the BLOCK all-gathers travel as int8 + error-feedback
    residuals riding opt_state["zero3_ef"] (pp degree 1, one pipeline
    microbatch, not composed with fp8 / comm_overlap /
    moe_quantize_a2a). Unset (stage 0) compiles BITWISE-identically to
    a build without the argument.

    flash_attention: "auto" (FLAGS_flash_attention / FLAGS_flash_sep,
    default off) / None / bool / "ring" / "ulysses" /
    FlashAttentionConfig — the fused Pallas flash fwd + custom_vjp bwd
    kernel wired directly into every block (no registry hop). Off: the
    composed einsum path compiles BITWISE-identically. Composes with
    mp_overlap (attention consumes the gathered full sequence; heads
    stay local under TP), fp8 (the surrounding qkv/proj GEMMs keep their
    site_mm routing — attention itself stays bf16/f32), zero1,
    comm_overlap and every pipeline schedule. A sep mode additionally
    mounts `sep_axis` as a context-parallel mesh axis: the batch shards
    over dp AND the sequence over sep (data_spec P(dp, sep)), sep joins
    extra_grad_axes, and attention runs ring/Ulysses per shard with
    flash as the inner kernel — requires the axis on the mesh, S
    divisible by its degree (trace-time), no mp sequence parallelism
    and no MoE; "ulysses" further needs heads/mp divisible by sep.

    numerics: "auto" (FLAGS_numerics, default off) / None / bool /
    observability.numerics.NumericsConfig — in-program tensor-health
    telemetry riding the telemetry ring (ISSUE 15): per-stacked-layer
    grad norms (every schedule; MoE sums the dense+moe pair per index),
    per-layer activation rms/absmax deposited from the block scan
    (plain-1F1B dense path — the pipeline aux channel), EF-residual
    norms for whichever of comm_ef/moe_ef/zero3_ef the build threads,
    and fp8 per-site scale saturation/headroom. Implies a (non-strict)
    telemetry config when FLAGS_telemetry is off. Off compiles
    BITWISE-identically (tier-1 asserted).
    """
    from .hybrid_engine import build_train_step
    from ..quantization import fp8 as _f8
    from ..distributed.comm_overlap.collective_matmul import \
        resolve_mp_overlap
    from ..distributed.comm_overlap.a2a import resolve_moe_dispatch
    from ..kernels.pallas.flash_training import resolve_flash_attention

    sp = resolve_mp_overlap(mp_overlap)
    flash = resolve_flash_attention(flash_attention)
    sep_on = flash is not None and flash.sep is not None
    if sep_on:
        enforce(sep_axis in mesh.axis_names,
                "a sep-mode flash plan mounts context parallelism on a "
                f"mesh axis: add '{sep_axis}' (degree >= 1) to the mesh",
                op="gpt.build_hybrid_train_step",
                axes=tuple(mesh.axis_names))
        enforce(sp is None,
                "sep context parallelism and mp sequence parallelism "
                "both shard the sequence dim — disable "
                "FLAGS_mp_seq_parallel / mp_overlap or the flash sep "
                "mode", op="gpt.build_hybrid_train_step")
        enforce(not cfg.moe_on,
                "sep context parallelism is not composed with the "
                "GPT-MoE batch layout (batch shards over dp x ep)",
                op="gpt.build_hybrid_train_step")
        sep_n = int(mesh.shape[sep_axis])
        if flash.sep == "ulysses" and sep_n > 1:
            heads_local = cfg.num_heads // int(mesh.shape[mp_axis])
            enforce(heads_local % sep_n == 0,
                    "ulysses trades the sequence shard for a head shard: "
                    "local heads (num_heads / mp) must divide by the sep "
                    "degree — use ring attention otherwise",
                    op="gpt.build_hybrid_train_step",
                    heads_local=heads_local, sep=sep_n)
        # sep grads are genuinely partial (each rank saw a sequence
        # shard) — combine them exactly as the engine combines any
        # context-parallel axis
        extra_grad_axes = tuple(extra_grad_axes) + (sep_axis,)
    fp8_plan = _f8.resolve_fp8_plan(
        fp8, GPT_FP8_SITES, cfg.num_layers, stacked_axis=pp_axis,
        amax_axes=(dp_axis, mp_axis) + tuple(extra_grad_axes))
    # fp8 x ring-collective-matmul is refused by the engine (the ONE copy
    # of that compose rule — hybrid_engine.build_train_step); S % mp
    # divisibility is checked at trace time in hybrid_loss_fn (the
    # runtime sequence length may be shorter than max_seq_len)
    if fp8_plan is not None:
        enforce(virtual_pp == 1 and schedule == "1F1B",
                "fp8 delayed scaling supports the 1F1B schedule only "
                "(scales are stacked per layer and must follow any block "
                "permutation)", op="gpt.build_hybrid_train_step",
                virtual_pp=virtual_pp, schedule=schedule)

    moe_on = cfg.moe_on
    mcfg = resolve_moe_dispatch(moe_dispatch) if moe_on else None
    moe_plan = None
    if moe_on:
        from ..distributed.comm_overlap import a2a as _a2a
        from ..incubate.distributed.models.moe.gate import compute_capacity
        from .. import observability as _obs
        enforce(ep_axis in mesh.axis_names,
                "GPT-MoE shards the expert bank over an expert-parallel "
                f"mesh axis: add '{ep_axis}' (degree >= 1) to the mesh",
                op="gpt.build_hybrid_train_step",
                axes=tuple(mesh.axis_names))
        ep = int(mesh.shape[ep_axis])
        E = cfg.moe_num_experts
        enforce(E % ep == 0, "the ep degree must divide the expert count",
                op="gpt.build_hybrid_train_step", experts=E, ep=ep)
        enforce(cfg.ffn_hidden % int(mesh.shape[mp_axis]) == 0,
                "the expert hidden dim shards over mp",
                op="gpt.build_hybrid_train_step",
                ffn_hidden=cfg.ffn_hidden, mp=int(mesh.shape[mp_axis]))
        enforce(fp8_plan is None and sp is None,
                "the GPT-MoE hybrid path is not composed with fp8 "
                "delayed scaling or sequence parallelism — disable "
                "FLAGS_fp8 / FLAGS_mp_seq_parallel",
                op="gpt.build_hybrid_train_step")
        enforce(virtual_pp == 1 and schedule == "1F1B",
                "GPT-MoE supports the 1F1B schedule only",
                op="gpt.build_hybrid_train_step", virtual_pp=virtual_pp,
                schedule=schedule)
        ef = None
        if mcfg is not None and mcfg.quantize:
            enforce(int(mesh.shape[pp_axis]) == 1
                    and num_microbatches == 1,
                    "moe_quantize_a2a threads ONE error-feedback "
                    "residual slot per MoE layer per step; pipeline "
                    "microbatching would sum residuals across "
                    "microbatches — use pp degree 1 and "
                    "num_microbatches 1",
                    op="gpt.build_hybrid_train_step",
                    pp=int(mesh.shape[pp_axis]),
                    num_microbatches=num_microbatches)
            enforce(moe_ef_tokens is not None,
                    "moe_quantize_a2a sizes the residual state at build "
                    "time: pass moe_ef_tokens=(per-rank batch, seq)",
                    op="gpt.build_hybrid_train_step")
            b_loc, s_ef = moe_ef_tokens
            C = compute_capacity(int(b_loc) * int(s_ef), E, 1,
                                 cfg.moe_capacity_factor)
            chunks = mcfg.chunks if mcfg.overlap else 1
            shapes = _a2a.moe_ef_local_shapes(E, C, cfg.hidden_size, ep,
                                              chunks)
            L2 = cfg.num_layers // 2
            n_dev = int(mesh.devices.size)
            sizes = {k: L2 * math.prod(s) for k, s in shapes.items()}
            ef = {
                "init": (lambda: {k: jnp.zeros((n_dev * sz,), jnp.float32)
                                  for k, sz in sizes.items()}),
                "specs": {k: P(tuple(mesh.axis_names)) for k in sizes},
            }
        moe_plan = {
            "ep_axis": ep_axis, "ef": ef,
            "meta": {"ep": ep, "experts": E,
                     "dispatch": ("index" if (mcfg is not None
                                              and mcfg.index)
                                  else "dense"),
                     "quantize": bool(mcfg is not None and mcfg.quantize),
                     "overlap": bool(mcfg is not None and mcfg.overlap)},
        }
        if isinstance(telemetry, _obs.TelemetryConfig):
            # register the MoE series on the caller's config (in place —
            # their TelemetryHost decodes from the same object; build the
            # engine before constructing the host)
            telemetry.extra = telemetry.extra + tuple(
                s for s in moe_telemetry_series(cfg)
                if s not in telemetry.extra)

    # -- ZeRO stage resolution (stage 3 builds the gather-on-use plan) ----
    from .hybrid_engine import zero_dims, zero_extend_spec
    from ..distributed.comm_overlap.zero3 import (resolve_zero3,
                                                  resolve_zero_stage)
    specs = hybrid_param_specs(cfg)
    example = jax.eval_shape(
        lambda: init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    stage = resolve_zero_stage(zero_stage, zero1_dp,
                               op="gpt.build_hybrid_train_step")
    z3plan = None
    z3_engine = None
    if stage >= 3:
        z3cfg = resolve_zero3(zero3)
        zdims = zero_dims(specs, example, mesh, dp_axis)
        z3plan = {"zdims": zdims, "axis": dp_axis, "cfg": z3cfg,
                  "other_leaves": ("wte", "wpe", "lnf_g", "lnf_b",
                                   "head_w")}
        z3_engine = {"ef": None, "meta": z3cfg.meta()}
        if z3cfg.quantize:
            enforce(int(mesh.shape[pp_axis]) == 1
                    and num_microbatches == 1 and virtual_pp == 1,
                    "zero3_quantize_ag threads ONE error-feedback "
                    "residual slot per layer per step; pipeline "
                    "microbatching would sum residuals across ticks — "
                    "use pp degree 1, num_microbatches 1",
                    op="gpt.build_hybrid_train_step",
                    pp=int(mesh.shape[pp_axis]),
                    num_microbatches=num_microbatches)
            enforce(fp8_plan is None,
                    "zero3_quantize_ag and fp8 delayed scaling both own "
                    "the loss's 4th argument — disable one of the two",
                    op="gpt.build_hybrid_train_step")
            enforce(not moe_on,
                    "zero3_quantize_ag is not composed with the GPT-MoE "
                    "hybrid path (the pair scan does not thread the AG "
                    "residuals) — disable FLAGS_zero3_quantize_ag or "
                    "FLAGS_moe_*", op="gpt.build_hybrid_train_step")
            enforce(not (mcfg is not None and mcfg.quantize),
                    "zero3_quantize_ag and moe_quantize_a2a both thread "
                    "their residuals as the loss's 4th argument — "
                    "disable one of the two",
                    op="gpt.build_hybrid_train_step")
            blocks_ex, blocks_sp, zd_blk = (example["blocks"],
                                            specs["blocks"],
                                            zdims["blocks"])
            # residuals mirror the SHARDED block leaves: stacked global
            # shapes with the dp-extended specs, fp32; not-quantized
            # (replicated) leaves get a 0-column placeholder so the scan
            # structure stays homogeneous
            ef_specs = jax.tree.map(
                lambda sp_, zd, ex: (zero_extend_spec(sp_, zd, dp_axis,
                                                      ex.ndim)
                                     if zd >= 0 else P(sp_[0])),
                blocks_sp, zd_blk, blocks_ex,
                is_leaf=lambda x: isinstance(x, P))

            def ef_init(_ex=blocks_ex, _zd=zd_blk):
                return jax.tree.map(
                    lambda ex, zd: jnp.zeros(
                        tuple(ex.shape) if zd >= 0 else (ex.shape[0], 0),
                        jnp.float32),
                    _ex, _zd)
            z3_engine = {"ef": {"init": ef_init, "specs": ef_specs},
                         "meta": z3cfg.meta()}

    # -- numerics plan (tensor-health telemetry; ISSUE 15) ----------------
    from ..observability.numerics import resolve_numerics
    ncfg = resolve_numerics(
        numerics,
        # the stacked block subtree's layer-index count: GPT-MoE stacks
        # (dense, moe) PAIRS, so its per-layer series span L/2 indices
        num_layers=(cfg.num_layers // 2 if moe_on else cfg.num_layers),
        # activation stats need the plain-1F1B aux channel; per-layer
        # grad norms (engine-side) stay on for every schedule
        act=(not moe_on and virtual_pp == 1 and schedule == "1F1B"),
        pp_axis=pp_axis)

    if moe_plan is not None and moe_plan["ef"] is not None:
        def loss_fn(p, tokens, labels, moe_ef):
            return hybrid_loss_fn(p, tokens, labels, cfg, num_microbatches,
                                  dp_axis, pp_axis, mp_axis,
                                  virtual_pp=virtual_pp, schedule=schedule,
                                  sp=sp, ep_axis=ep_axis, moe=mcfg,
                                  moe_ef=moe_ef, flash=flash,
                                  sep_axis=sep_axis, z3=z3plan, num=ncfg)
    elif fp8_plan is not None:
        def loss_fn(p, tokens, labels, scales):
            return hybrid_loss_fn(p, tokens, labels, cfg, num_microbatches,
                                  dp_axis, pp_axis, mp_axis,
                                  virtual_pp=virtual_pp, schedule=schedule,
                                  fp8=scales, sp=sp, flash=flash,
                                  sep_axis=sep_axis, z3=z3plan, num=ncfg)
    elif z3_engine is not None and z3_engine["ef"] is not None:
        def loss_fn(p, tokens, labels, z3_ef):
            return hybrid_loss_fn(p, tokens, labels, cfg, num_microbatches,
                                  dp_axis, pp_axis, mp_axis,
                                  virtual_pp=virtual_pp, schedule=schedule,
                                  sp=sp, ep_axis=ep_axis, moe=mcfg,
                                  flash=flash, sep_axis=sep_axis,
                                  z3=z3plan, z3_ef=z3_ef, num=ncfg)
    else:
        def loss_fn(p, tokens, labels):
            return hybrid_loss_fn(p, tokens, labels, cfg, num_microbatches,
                                  dp_axis, pp_axis, mp_axis,
                                  virtual_pp=virtual_pp, schedule=schedule,
                                  sp=sp, ep_axis=ep_axis, moe=mcfg,
                                  flash=flash, sep_axis=sep_axis,
                                  z3=z3plan, num=ncfg)

    # ONE pipeline stage is no pipeline: the engine runs the microbatches
    # one after another, each with its own backward. Builds whose loss
    # rides the pipeline's side channels (fp8 amax sums, the MoE aux loss
    # and routing stats, per-layer activation stats, the ZeRO-3 gathers
    # that count on the stage replay) keep the pipeline path they have.
    if (int(mesh.shape[pp_axis]) == 1 and fp8_plan is None and not moe_on
            and z3plan is None and not (ncfg is not None and ncfg.act)):
        loss_fn = one_stage_loss(
            loss_fn, num_microbatches,
            lambda p, tokens, labels, denom, layers: hybrid_microbatch_share(
                p, tokens, labels, denom, layers, cfg, pp_axis, mp_axis,
                sp=sp, flash=flash, sep_axis=sep_axis),
            (dp_axis, sep_axis) if sep_on else (dp_axis,))

    if moe_on:
        data_spec = P((dp_axis, ep_axis))
    elif sep_on:
        # batch over dp, sequence over the context-parallel axis
        data_spec = P(dp_axis, sep_axis)
    else:
        data_spec = None
    step, shard_params, init_state = build_train_step(
        loss_fn, specs, mesh, optimizer, dp_axis=dp_axis,
        data_spec=data_spec,
        extra_grad_axes=extra_grad_axes, example_params=example,
        grad_reduce_dtype=grad_reduce_dtype, zero_stage=stage,
        zero3=z3_engine,
        comm_overlap=comm_overlap, fp8=fp8_plan, telemetry=telemetry,
        mp_overlap=sp, moe=moe_plan, flash=flash, numerics=ncfg,
        donate=donate)
    # elastic-checkpoint hint (checkpoint.reshard): the stacked-[L] block
    # leaves' STORAGE order is (pp, vpp)-dependent under the interleaved
    # schedule; resume onto a different layout permutes them (fp8_meta's
    # per-layer scale stacks follow the same assignment)
    init_state.layout_extra["pp"] = {
        "num_layers": int(cfg.num_layers), "pp": int(mesh.shape[pp_axis]),
        "vpp": int(virtual_pp),
        "stacked_components": ["blocks", "fp8_meta"],
    }
    if fp8_plan is not None:
        # pipelined amax observations sum over T = M + P - 1 time steps
        # (test_fp8 asserts exact T x dense); a resume onto a different pp
        # degree rescales the carried histories by T_new/T_old so the
        # delayed scales keep their magnitude (checkpoint.reshard)
        init_state.layout_extra["fp8_amax_ticks"] = (
            num_microbatches + int(mesh.shape[pp_axis]) - 1)

    if virtual_pp > 1:
        shard_params = vpp_wrap_shard_params(
            shard_params, cfg.num_layers, mesh.shape[pp_axis], virtual_pp)
    return step, shard_params, init_state
