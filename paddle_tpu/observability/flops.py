"""Analytic FLOPs models for step accounting (MFU).

One place for the math every report needs: per-token training FLOPs
for the GPT and Llama families,
fwd/bwd/remat-aware, plus the comms-time estimate that turns a
comm_overlap bucket plan into an expected comms fraction.

Conventions (the PaLM/Chinchilla accounting):

* matmul params N (embeddings excluded) cost ``2N`` FLOPs/token forward
  and ``4N`` backward — ``6N`` per trained token;
* attention adds ``12 * L * H * S`` per token (QK^T + AV, fwd+bwd) for
  seq len S — the causal-mask halving is deliberately NOT applied,
  matching the frozen bench series;
* ``model_flops`` counts the model's useful work (the MFU numerator);
  ``hardware_flops`` additionally counts recomputation (full per-block
  remat re-runs the forward: +2N +4LHS per token), which is what the
  chip actually executes.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

__all__ = ["transformer_flops_per_token", "attention_flops_per_token",
           "gpt_flops_per_token",
           "llama_flops_per_token", "gpt_moe_flops_per_token",
           "param_count", "mfu", "peak_flops", "chip_profile_name",
           "CHIP_PEAKS", "PROFILE_PEAKS", "CPU_NOMINAL_PEAK",
           "collective_seconds", "plan_wire_bytes"]

_REMAT_MODES = ("none", "full", "selective")


def param_count(params, exclude=("wte", "wpe", "emb", "embedding")) -> int:
    """Matmul-relevant parameter count of a concrete/abstract param tree:
    total leaves minus top-level embedding tables (6N-rule accounting)."""
    import jax
    import numpy as np
    total = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params))
    emb = 0
    if isinstance(params, dict):
        for k in exclude:
            if k in params:
                emb += sum(int(np.prod(v.shape))
                           for v in jax.tree.leaves(params[k]))
    return total - emb


def transformer_flops_per_token(*, n_params: int, num_layers: int,
                                hidden_size: int, seq_len: int,
                                remat: str = "none") -> Dict[str, float]:
    """{"model": model FLOPs/token, "hardware": executed FLOPs/token}."""
    if remat not in _REMAT_MODES:
        raise ValueError(f"remat must be one of {_REMAT_MODES}, got {remat}")
    attn = 12.0 * num_layers * hidden_size * seq_len
    model = 6.0 * n_params + attn
    fwd = 2.0 * n_params + attn / 3.0
    hardware = model
    if remat == "full":
        hardware = model + fwd          # backward re-runs the forward
    elif remat == "selective":
        hardware = model + 0.5 * fwd    # half the forward recomputed
    return {"model": model, "hardware": hardware}


def attention_flops_per_token(*, num_layers: int, hidden_size: int,
                              seq_len: int, impl: str = "einsum",
                              remat: str = "full") -> Dict[str, float]:
    """Attention-only executed-FLOPs model, in matmul PASSES of
    ``2 * L * H * S`` flops/token each (QK^T and PV/AV are one pass
    apiece — the 12·L·H·S model term is 6 passes: 2 fwd + 4 bwd).

    impl="einsum" (the composed path): fwd 2 passes, bwd 4; full remat
    re-runs the fwd (+2), selective (attn_out/qkv saved) skips the PV
    re-run (+1).

    impl="flash" (the fused kernel): fwd 2; the two-kernel
    FlashAttention-2 backward re-derives the scores tile inside each
    kernel — dkv = {s, dp, dv, dk} (4 passes), dq = {s, dp, dq} (3) — so
    bwd is 7; full remat replays the fwd KERNEL (+2, still O(S) HBM),
    selective (FLASH_REMAT_NAMES: out+lse saved) skips the replay —
    dense_forward's default wherever the kernel runs, through the
    registry op or a flash= plan alike.
    Flash thus EXECUTES more attention flops than the composed path
    (11 vs 8 passes under full remat) — the win is the O(S²)→O(S) HBM
    traffic and residency, which is why the planner scores it honestly
    as a compute cost and a memory saving."""
    if remat not in _REMAT_MODES:
        raise ValueError(f"remat must be one of {_REMAT_MODES}, got {remat}")
    passes = {
        "einsum": {"none": 6.0, "selective": 7.0, "full": 8.0},
        "flash": {"none": 9.0, "selective": 9.0, "full": 11.0},
    }.get(impl)
    if passes is None:
        raise ValueError(f"impl must be 'einsum' or 'flash', got {impl!r}")
    unit = 2.0 * num_layers * hidden_size * seq_len
    return {"model": 6.0 * unit, "hardware": passes[remat] * unit}


def _gpt_matmul_params(cfg) -> int:
    h, L = cfg.hidden_size, cfg.num_layers
    per_layer = 3 * h * h + h * h + h * cfg.ffn_hidden + cfg.ffn_hidden * h
    return L * per_layer + h * cfg.vocab_size  # blocks + untied LM head


def _llama_matmul_params(cfg) -> int:
    h, L, d = cfg.hidden_size, cfg.num_layers, cfg.head_dim
    kv = cfg.num_kv_heads * d
    attn = h * h + 2 * h * kv + h * h              # q, k, v, o
    ffn = 3 * h * cfg.intermediate_size            # gate, up, down
    return L * (attn + ffn) + h * cfg.vocab_size


def gpt_flops_per_token(cfg, seq_len: int, *, params=None,
                        remat: str = "none") -> Dict[str, float]:
    """FLOPs/token for a GPTConfig. Pass the concrete param tree to count
    N exactly (embeddings excluded, as the accounting above says);
    otherwise N comes from the config analytically."""
    n = (param_count(params) if params is not None
         else _gpt_matmul_params(cfg))
    return transformer_flops_per_token(
        n_params=n, num_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        seq_len=seq_len, remat=remat)


def llama_flops_per_token(cfg, seq_len: int, *, params=None,
                          remat: str = "none") -> Dict[str, float]:
    n = (param_count(params) if params is not None
         else _llama_matmul_params(cfg))
    return transformer_flops_per_token(
        n_params=n, num_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        seq_len=seq_len, remat=remat)


def gpt_moe_flops_per_token(cfg, *, tokens_per_rank: int,
                            mp: int = 1) -> Dict[str, float]:
    """MoE flop accounting for a GPT-MoE config (cfg.moe_num_experts > 0),
    the ONE copy of the math the auto-parallel planner consumes
    (tests/test_auto_tuner.py holds it to the frozen formulas bit-for-bit).

    tokens_per_rank: tokens one (dp, ep) rank routes per step (per-rank
    batch x seq — per MICROBATCH when pipelined, matching the capacity the
    gate actually computes).

    Returns:

    * ``capacity`` — slots per expert C (the gate's compute_capacity).
    * ``expert_gemm_flops_per_rank_step`` — MXU flops of one rank's local
      expert shard per step: after the all-to-all each rank processes all
      E*C capacity slots of its ep group (padding slots do real MXU work),
      2 GEMMs of H x FF/mp each, fwd + 2x bwd, over the L/2 MoE layers.
    * ``dense_dispatch_flops_per_moe_layer`` — the 2*T*E*C*D one-hot
      einsum cost the index dispatch deletes, PER dispatch AND combine,
      forward (the backward re-runs both; FLAGS_moe_index_dispatch).
    * ``model_flops_per_token`` — useful (MFU-numerator) expert work per
      routed token: top-1 routing runs ONE H x FF FFN per token per MoE
      layer, 6 flops/param-touch fwd+bwd.
    * ``hardware_flops_per_token`` — executed expert work per token at
      capacity (padded slots included), summed over the mp group.
    """
    from ..incubate.distributed.models.moe.gate import compute_capacity
    E = cfg.moe_num_experts
    if E <= 0:
        raise ValueError("gpt_moe_flops_per_token needs a MoE config "
                         "(cfg.moe_num_experts > 0)")
    H, FF, L2 = cfg.hidden_size, cfg.ffn_hidden, cfg.num_layers // 2
    T = int(tokens_per_rank)
    C = compute_capacity(T, E, 1, cfg.moe_capacity_factor)
    expert_rank_step = 12.0 * E * C * H * (FF // mp) * L2
    return {
        "capacity": float(C),
        "expert_gemm_flops_per_rank_step": expert_rank_step,
        "dense_dispatch_flops_per_moe_layer": 2.0 * 2 * T * E * C * H,
        "model_flops_per_token": 6.0 * 2 * H * FF * L2,
        "hardware_flops_per_token": 12.0 * E * C * H * FF * L2 / T,
    }


# THE peak table: per-chip bf16 matmul FLOP/s keyed by the device_kind
# jax reports, with the auto-tuner profile name each kind maps to. Every
# MFU and every planner profile reads it; no peak literal lives anywhere
# else. Source: Google Cloud TPU documentation, the per-generation system
# architecture pages ("TPU v5e": 197 TFLOP/s bf16 per chip; v2 45, v3 123,
# v4 275, v5p 459, v6e 918). An unknown TPU kind is an error, never a
# default — add the row with its source.
CHIP_PEAKS: Dict[str, Tuple[str, float]] = {
    "TPU v2": ("tpu-v2", 45e12),
    "TPU v3": ("tpu-v3", 123e12),
    "TPU v4": ("tpu-v4", 275e12),
    "TPU v5 lite": ("tpu-v5e", 197e12),
    "TPU v5": ("tpu-v5p", 459e12),
    "TPU v5p": ("tpu-v5p", 459e12),
    "TPU v6 lite": ("tpu-v6e", 918e12),
}
PROFILE_PEAKS: Dict[str, float] = dict(CHIP_PEAKS.values())
# The CPU has no published matmul peak. Tier-1 tests and CPU dry runs get
# this nominal value so MFU-SHAPED numbers stay finite; it is not a device
# metric, and every chip entry refuses a non-TPU platform before it could
# reach it.
CPU_NOMINAL_PEAK = 1e12


def chip_profile_name(devices=None) -> str:
    """Auto-tuner profile name of the current backend: the CHIP_PEAKS row
    of a TPU's device_kind (unknown kind = error), ``"cpu"`` on the CPU."""
    import jax
    dev = (devices if devices is not None else jax.devices())[0]
    if dev.platform == "cpu":
        return "cpu"
    kind = getattr(dev, "device_kind", "")
    if dev.platform != "tpu" or kind not in CHIP_PEAKS:
        raise ValueError(
            f"no published peak for platform {dev.platform!r} device_kind "
            f"{kind!r}: add it, with its source, to "
            "observability.flops.CHIP_PEAKS")
    return CHIP_PEAKS[kind][0]


def peak_flops(devices=None) -> float:
    """Per-chip bf16 matmul peak (FLOP/s) of the current backend, from
    CHIP_PEAKS. An unknown TPU device_kind raises; the CPU gets
    CPU_NOMINAL_PEAK (tier-1 only, never a device metric)."""
    name = chip_profile_name(devices)
    return CPU_NOMINAL_PEAK if name == "cpu" else PROFILE_PEAKS[name]


def mfu(tokens_per_sec: float, flops_per_token: float,
        peak: Optional[float] = None) -> float:
    peak = peak_flops() if peak is None else peak
    return tokens_per_sec * flops_per_token / peak


# ---------------------------------------------------------------------------
# Comms accounting from bucket plans.
# ---------------------------------------------------------------------------
def plan_wire_bytes(plan, *, wire_itemsize: Optional[int] = None) -> list:
    """Per-bucket wire bytes of a comm_overlap BucketPlan (int8 quantized
    plans pass wire_itemsize=1)."""
    out = []
    for b in plan.buckets:
        if wire_itemsize is None:
            out.append(int(b.nbytes))
        else:
            out.append(int(b.size * wire_itemsize))
    return out


def collective_seconds(wire_bytes: float, axis_size: int,
                       bandwidth_gbs: float, op: str = "allreduce") -> float:
    """Ring-algorithm time for one collective of `wire_bytes` payload over
    `axis_size` ranks at `bandwidth_gbs` per-link GB/s (the accounting
    collective_perf reports)."""
    n = max(int(axis_size), 1)
    if n == 1:
        return 0.0
    factor = {"allreduce": 2.0 * (n - 1) / n,
              "reduce_scatter": (n - 1) / n,
              "allgather": (n - 1) / n}.get(op)
    if factor is None:
        raise ValueError(f"unknown collective op {op!r}")
    return wire_bytes * factor / (bandwidth_gbs * 1e9)
