"""Auto-parallel planner: analytic config search over the hybrid engine's
real flag surface.

The repo's asset is that observability carries *measurement-validated*
analytic models — per-token FLOPs (``observability.flops``), mp-axis wire
bytes (``mp_wire_bytes``), dp bucket-plan accounting, the ep all-to-all
wire model (``ep_a2a_wire_bytes``) and the (M, P, V, schedule) pipeline
tick formulas the telemetry tests re-derive. This module turns them into
a search: given a model config and a mesh size, enumerate
:class:`PlanCandidate` configurations of ``build_hybrid_train_step``
under divisibility/shape constraints, score each with a three-part cost
model (compute seconds incl. the schedule bubble, exposed-communication
seconds with per-mode overlap discounts, per-collective dispatch
overhead), prune candidates whose analytic per-chip HBM exceeds the
budget, and emit the top-k as ready-to-run engine kwargs.

The MLPerf TPU-pod scaling study (arXiv:1909.09756) is this search run by
hand across pod slices; the reference's ``InferSpmd``/spmd_rules layer is
Paddle's version of the capability. The T3 framing (arXiv:2401.16677)
supplies the overlap model: a collective adjacent to a GEMM hides under
it up to a mode-dependent *hidable fraction*.

fp8 candidates are enumerable (``fp8_options=(False, True)``) but scored
compute-NEUTRAL: no bench round has recorded the fp8 MXU speedup on
hardware yet (the CPU emulation is ~neutral too), and inventing a rate
multiplier would break the model's measurement-validated contract — the
constraint checker still guarantees emitted fp8 configs compose legally.

Model constants (the ``_HIDE_*`` tables, ``gemm_efficiency``) are
table constants from earlier rounds (mp_overlap temp-bytes, the
CPU-mesh op-count ordering, the PR 2 bucketed-overlap deltas) that no run
on the current installation has checked — and are *re-calibratable from
measurement*:
:meth:`CostModel.calibrate` fits the compute rate and per-collective
launch overhead to a measured anchor sweep (``auto_tuner.sweep``), which
is how the CPU-smoke validation closes the loop between predicted and
measured step times.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ...observability.flops import (CPU_NOMINAL_PEAK, PROFILE_PEAKS as _PEAK,
                                     chip_profile_name)

__all__ = ["PlanCandidate", "ModelSpec", "HardwareProfile", "profile_for",
           "KNOWN_PROFILES", "CostModel", "Prediction",
           "generate_plan_candidates", "plan", "PlanReport", "ScoredPlan",
           "model_config_by_name", "PLAN_MODELS", "HIDE_KEYS",
           "profile_to_json", "profile_from_json", "resolve_profile"]

SCHEDULES = ("1f1b", "zbh1", "interleaved")
MP_OVERLAP_MODES = (None, "seq_parallel", "collective_matmul")

# T3-style hidable fractions: the share of a mode's wire time the
# adjacent compute can hide (exposed = wire * (1 - hide)). Table
# constants, never measured on the current installation: plain allreduce
# TP leaves most of the wire exposed, seq-parallel's
# AG/RS pairs schedule async against the GEMMs, the ring collective
# matmul interleaves chunk transfers with partial products (PR 5).
# These are the TABLE defaults; a measured HardwareProfile (the
# observability.profile_reader capture pipeline) carries per-mode
# overrides in its ``hide`` dict under the HIDE_KEYS vocabulary, and
# measured entries WIN over both the table and the overlap_capable
# zeroing — attribution from the observed timeline beats the heuristic.
_HIDE_MP = {None: 0.2, "allreduce": 0.2,
            "seq_parallel": 0.55, "collective_matmul": 0.85}
# dp gradient sync: the monolithic end-of-backward pmean serializes
# against the optimizer; size-targeted buckets issued in backward order
# hide under later backward compute (PR 2's measured win).
_HIDE_DP_MONOLITHIC = 0.0
_HIDE_DP_BUCKETED = 0.7
# zero3 per-block param all-gather: the scan_gather prefetch issues block
# i+1's transfer beside block i's GEMMs (the engine's default,
# FLAGS_zero3_overlap_ag) — most of the wire hides; without the prefetch
# the gather sits at the top of each block's critical path.
_HIDE_DP_ZERO3_AG = {True: 0.8, False: 0.3}
# ep all-to-alls: chunk-overlapped exchange (FLAGS_moe_overlap) hides
# chunk j+1's transfer behind chunk j's expert GEMM.
_HIDE_EP = {False: 0.1, True: 0.6}
_HIDE_PP = 0.0  # pipeline ppermutes sit on the critical path

# the hide-override vocabulary a measured profile may carry (profile
# capture labels its windows with these; CostModel consults them)
HIDE_KEYS = ("mp:allreduce", "mp:seq_parallel", "mp:collective_matmul",
             "dp:monolithic", "dp:bucketed", "dp:zero3_ag", "ep:plain",
             "ep:overlap", "pp")


# ---------------------------------------------------------------------------
# Hardware profiles.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    """Per-chip rates the cost model converts bytes/flops into seconds
    with. ``gemm_efficiency`` is the achievable fraction of peak on the
    dense stack (a table constant until a chip run calibrates it);
    ``collective_launch_s`` is the per-collective dispatch
    overhead — microseconds on TPU, ~fractions of a millisecond on the
    CPU smoke mesh where collectives are scheduler ops, which is exactly
    why the CPU proxy ranks mp modes by op count
    while a real pod ranks them by exposed wire."""
    name: str = "tpu-v5e"
    peak_flops: float = _PEAK["tpu-v5e"]
    hbm_gb: float = 16.0
    ici_gbs: float = 45.0
    collective_launch_s: float = 2e-6
    gemm_efficiency: float = 0.6
    # whether the backend's scheduler can actually hide collectives under
    # adjacent compute (the latency-hiding/async-collective machinery).
    # False on the CPU smoke mesh: every mode's wire is equally exposed
    # there, so configs rank by collective COUNT — the measured round-6
    # CPU proxy ordering (allreduce < sp < ring) — while TPU profiles
    # rank by exposed wire after the T3 hidable-fraction discount.
    overlap_capable: bool = True
    # measured per-mode hidable-fraction overrides (HIDE_KEYS vocabulary),
    # filled by observability.profile_reader.derive_hardware_profile; a
    # key present here WINS over the table constant AND the
    # overlap_capable zeroing (it IS the measurement). compare=False so
    # frozen-dataclass hashing never touches the dict.
    hide: Optional[Dict[str, float]] = dataclasses.field(
        default=None, compare=False)
    source: str = dataclasses.field(default="table", compare=False)


# peaks come from the one table (observability.flops.CHIP_PEAKS); the
# rows here add what the cost model needs beside them
KNOWN_PROFILES: Dict[str, HardwareProfile] = {
    "tpu-v5e": HardwareProfile("tpu-v5e", _PEAK["tpu-v5e"], 16.0, 45.0,
                               2e-6, 0.6),
    "tpu-v5p": HardwareProfile("tpu-v5p", _PEAK["tpu-v5p"], 95.0, 90.0,
                               2e-6, 0.6),
    "tpu-v4": HardwareProfile("tpu-v4", _PEAK["tpu-v4"], 32.0, 45.0,
                              2e-6, 0.6),
    "tpu-v6e": HardwareProfile("tpu-v6e", _PEAK["tpu-v6e"], 32.0, 90.0,
                               2e-6, 0.6),
    "tpu-v3": HardwareProfile("tpu-v3", _PEAK["tpu-v3"], 16.0, 35.0,
                              2e-6, 0.6),
    # CPU dry-run mesh: the nominal flops.CPU_NOMINAL_PEAK (not a device
    # metric), collectives are cheap memcpys but each costs real
    # scheduling time, and nothing hides under anything
    # (overlap_capable=False).
    "cpu": HardwareProfile("cpu", CPU_NOMINAL_PEAK, 4.0, 8.0, 5e-4, 0.5,
                           overlap_capable=False),
}


def profile_for(devices=None, *, hbm_gb: Optional[float] = None
                ) -> HardwareProfile:
    """Profile of the current backend (flag/CLI ``--hbm-gb`` overrides the
    budget — FLAGS_auto_parallel_hbm_gb is read by the CLI/launcher)."""
    prof = KNOWN_PROFILES[chip_profile_name(devices)]
    if hbm_gb is not None and hbm_gb > 0:
        prof = dataclasses.replace(prof, hbm_gb=float(hbm_gb))
    return prof


def profile_to_json(profile: HardwareProfile) -> Dict[str, Any]:
    return dataclasses.asdict(profile)


def profile_from_json(d: Dict[str, Any]) -> HardwareProfile:
    """HardwareProfile from a dict (the ``hardware_profile`` payload the
    profile-capture pipeline serializes); unknown keys are ignored so the
    format can grow."""
    fields = {f.name for f in dataclasses.fields(HardwareProfile)}
    kw = {k: v for k, v in d.items() if k in fields}
    if kw.get("hide") is not None:
        kw["hide"] = {str(k): float(v) for k, v in kw["hide"].items()}
    return HardwareProfile(**kw)


def resolve_profile(spec: Optional[str], *,
                    hbm_gb: Optional[float] = None) -> HardwareProfile:
    """CLI/launcher profile resolution: a KNOWN_PROFILES name, a path to
    a measured-profile JSON (the observability.profile_reader artifact —
    anything ending in .json or naming an existing file), or None to
    detect from the current backend."""
    import os
    if spec:
        if spec in KNOWN_PROFILES:
            prof = KNOWN_PROFILES[spec]
        elif spec.endswith(".json") or os.path.exists(spec):
            from ...observability.profile_reader import load_profile_json
            prof = load_profile_json(spec)
        else:
            raise ValueError(
                f"unknown profile {spec!r}: not one of "
                f"{sorted(KNOWN_PROFILES)} and not a profile JSON path")
        if hbm_gb is not None and hbm_gb > 0:
            prof = dataclasses.replace(prof, hbm_gb=float(hbm_gb))
        return prof
    return profile_for(hbm_gb=hbm_gb)


# ---------------------------------------------------------------------------
# The candidate: one point on the hybrid flag surface.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PlanCandidate:
    """One ``build_hybrid_train_step`` configuration over the REAL flag
    surface (the axes the hybrid engine actually mounts: dp/ep/pp/mp — the
    old tuner's "sharding"/"sep" vocabulary is gone).

    ``schedule`` uses the planner vocabulary {"1f1b", "zbh1",
    "interleaved"}; "interleaved" requires ``vpp > 1`` and maps to
    ``virtual_pp=vpp`` on the engine. ``remat`` records the activation
    policy the cost/memory model assumes — the hybrid pipeline always
    checkpoints each stage (``jax.checkpoint`` around the stage body), so
    generated candidates carry "full"; it is NOT an engine kwarg.
    ``moe_*`` fields only apply to MoE configs (cfg.moe_num_experts > 0).
    """
    dp: int = 1
    mp: int = 1
    pp: int = 1
    ep: int = 1
    vpp: int = 1
    schedule: str = "1f1b"
    micro_batches: int = 1
    # ZeRO stage over dp (replaces the old boolean `zero1`): 0 off;
    # 1 dp-sharded optimizer state; 2 additionally accounts the grad
    # buffer dp-sharded (same collectives as 1 in the fused engine — an
    # HBM-rule axis); 3 params dp-sharded at rest, per-block all-gather
    # on use (the exposed-comm term below prices that AG, discounted by
    # the scan_gather prefetch's hidable fraction)
    zero_stage: int = 0
    remat: str = "full"
    fp8: bool = False
    comm_bucket_mb: float = 0.0
    mp_overlap: Optional[str] = None
    # fused Pallas flash attention in the block bodies
    # (build_hybrid_train_step(flash_attention=...)): trades MORE executed
    # attention flops (the two-kernel backward re-derives scores tiles)
    # for O(S) instead of O(S²) rematted-activation HBM — the cost model
    # scores both sides, so flash candidates win exactly where the scores
    # memory is the binding constraint (long S under a tight HBM budget)
    flash_attention: bool = False
    moe_index: bool = True
    moe_quantize: bool = False
    moe_overlap: bool = False

    @property
    def world(self) -> int:
        return self.dp * self.mp * self.pp * self.ep

    def mesh_dims(self) -> Dict[str, int]:
        """Axis -> degree in the engine's mount order (outer -> inner;
        the axes ``build_hybrid_train_step`` names: dp, ep, pp, mp —
        degree-1 axes are kept so shardings can name them)."""
        return {"dp": self.dp, "ep": self.ep, "pp": self.pp, "mp": self.mp}

    def build_mesh(self, devices=None):
        """jax Mesh for this candidate on the first ``world`` devices."""
        import jax
        from ..topology import build_mesh
        devices = list(devices if devices is not None else jax.devices())
        return build_mesh(self.mesh_dims(), devices[:self.world])

    def engine_kwargs(self, *, family: str = "gpt",
                      global_batch: Optional[int] = None,
                      seq: Optional[int] = None) -> Dict[str, Any]:
        """Ready-to-run ``build_hybrid_train_step(cfg, mesh, opt, **kw)``
        kwargs. Everything is EXPLICIT (never "auto") so a plan is
        reproducible regardless of ambient FLAGS_*. The llama builder
        exposes a subset of the surface (no schedule/comm_overlap/moe
        kwargs); candidates outside it are never generated for llama."""
        kw: Dict[str, Any] = {
            "num_microbatches": self.micro_batches,
            "virtual_pp": self.vpp,
            "zero_stage": int(self.zero_stage),
            "fp8": bool(self.fp8),
            "telemetry": None,
            "mp_overlap": self.mp_overlap,
            "flash_attention": bool(self.flash_attention),
        }
        if self.zero_stage >= 3:
            # pin the gather knobs the cost model scored (prefetch on,
            # unquantized) so a plan is reproducible regardless of
            # ambient FLAGS_zero3_* — the same explicitness rule as
            # every other kwarg here (both builders accept zero3=)
            from ..comm_overlap import Zero3Config
            kw["zero3"] = Zero3Config()
        if family == "gpt":
            from ..comm_overlap import CommOverlapConfig, MoeDispatchConfig
            kw["schedule"] = "ZBH1" if self.schedule == "zbh1" else "1F1B"
            kw["comm_overlap"] = (
                CommOverlapConfig(bucket_mb=self.comm_bucket_mb)
                if self.comm_bucket_mb > 0 else None)
            # always explicit: the engine only consumes this when the
            # config is MoE, and an "auto" default would re-open the
            # flag-surface dependence plans exist to pin down
            kw["moe_dispatch"] = MoeDispatchConfig(
                index=self.moe_index, quantize=self.moe_quantize,
                overlap=self.moe_overlap)
            if self.moe_quantize:
                if global_batch is None or seq is None:
                    raise ValueError(
                        "a quantized-a2a candidate sizes its error-feedback "
                        "residuals at build time: pass global_batch and seq "
                        "to engine_kwargs()")
                kw["moe_ef_tokens"] = (global_batch // (self.dp * self.ep),
                                       seq)
        return kw

    def __str__(self):
        parts = [f"dp{self.dp}"]
        if self.ep > 1:
            parts.append(f"ep{self.ep}")
        if self.pp > 1:
            parts.append(f"pp{self.pp}")
        if self.mp > 1:
            parts.append(f"mp{self.mp}")
        s = "x".join(parts) + f" {self.schedule}"
        if self.vpp > 1:
            s += f"v{self.vpp}"
        s += f" M{self.micro_batches}"
        if self.zero_stage:
            s += f" zero{self.zero_stage}"
        if self.fp8:
            s += " fp8"
        if self.comm_bucket_mb > 0:
            s += f" bkt{self.comm_bucket_mb:g}"
        if self.mp_overlap:
            s += " " + {"seq_parallel": "sp",
                        "collective_matmul": "ring"}.get(
                str(self.mp_overlap), str(self.mp_overlap))
        if self.flash_attention:
            s += " flash"
        if self.ep > 1 or self.moe_quantize or self.moe_overlap:
            s += " moe:" + ("i" if self.moe_index else "d") \
                + ("q" if self.moe_quantize else "") \
                + ("o" if self.moe_overlap else "")
        return s


# ---------------------------------------------------------------------------
# The model's shape, parameter layout and flop structure.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ModelSpec:
    """Everything the cost/memory model needs about a model config,
    extracted ONCE (the per-leaf (shape, dtype, spec) table comes from the
    model's own ``init_hybrid_params``/``hybrid_param_specs`` via
    eval_shape — no buffers)."""
    family: str
    cfg: Any
    hidden: int
    layers: int
    ffn: int
    vocab: int
    heads: int
    act_itemsize: int
    param_itemsize: int
    n_block_params: int       # matmul params inside the pipelined blocks
    n_head_params: int        # LM head (outside the pipeline/remat)
    moe_experts: int = 0
    leaves: List[Tuple[int, int, Tuple, Tuple]] = dataclasses.field(
        default_factory=list)  # (n_elems, itemsize, spec_axes, shape)

    @classmethod
    def from_config(cls, cfg, family: str = "gpt") -> "ModelSpec":
        import jax
        import jax.numpy as jnp
        import numpy as np
        if family == "gpt":
            from ...models import gpt as M
        elif family == "llama":
            from ...models import llama as M
        else:
            raise ValueError(f"unknown model family {family!r}")
        pshape = jax.eval_shape(
            lambda: M.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
        specs = M.hybrid_param_specs(cfg)
        from jax.sharding import PartitionSpec as P

        leaves: List[Tuple[int, int, Tuple, Tuple]] = []

        def one(sp, s):
            axes = []
            for d, e in enumerate(tuple(sp or ())):
                if e is None:
                    continue
                axes.append((d, tuple(e) if isinstance(e, tuple) else (e,)))
            leaves.append((int(np.prod(s.shape)),
                           jnp.dtype(s.dtype).itemsize, tuple(axes),
                           tuple(s.shape)))
            return 0

        jax.tree.map(one, specs, pshape,
                     is_leaf=lambda x: x is None or isinstance(x, P))

        H, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
        if family == "gpt":
            FF = cfg.ffn_hidden
            heads = cfg.num_heads
            attn_p = 4 * H * H
            ffn_p = 2 * H * FF
            moe_e = getattr(cfg, "moe_num_experts", 0)
            n_ffn_layers = (L // 2) if moe_e > 0 else L
            n_block = L * attn_p + n_ffn_layers * ffn_p
        else:
            FF = cfg.intermediate_size
            heads = cfg.num_heads
            d = cfg.head_dim
            kv = cfg.num_kv_heads * d
            n_block = L * (2 * H * H + 2 * H * kv + 3 * H * FF)
            moe_e = 0
        return cls(family=family, cfg=cfg, hidden=H, layers=L, ffn=FF,
                   vocab=V, heads=heads,
                   act_itemsize=jnp.dtype(cfg.dtype).itemsize,
                   param_itemsize=jnp.dtype(cfg.param_dtype).itemsize,
                   n_block_params=n_block, n_head_params=H * V,
                   moe_experts=moe_e, leaves=leaves)

    @property
    def moe_on(self) -> bool:
        return self.moe_experts > 0


def _shard_product(spec_axes, sizes: Dict[str, int]) -> int:
    prod = 1
    for _, axes in spec_axes:
        for a in axes:
            prod *= sizes.get(a, 1)
    return prod


def _leaf_dp_shardable(shape, spec_axes, dp: int) -> bool:
    """Mirror of hybrid_engine.zero_dims (the ONE per-leaf
    dp-shardability rule): the first dim with no mesh axis whose extent
    divides dp (and is >= dp) shards the optimizer state (stage >= 1),
    the grad buffer (stage >= 2) and the params themselves (stage 3)
    over dp."""
    sharded_dims = {d for d, _ in spec_axes}
    for d, extent in enumerate(shape):
        if d in sharded_dims:
            continue
        if extent % dp == 0 and extent >= dp:
            return True
    return False


# ---------------------------------------------------------------------------
# Candidate generation under the engine's real constraints.
# ---------------------------------------------------------------------------
def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def check_candidate(c: PlanCandidate, spec: ModelSpec, *, world: int,
                    global_batch: int, seq: int) -> Optional[str]:
    """The ONE copy of the engine's composition/divisibility rules the
    generator and the CLI both consult. Returns a prune reason, or None
    when ``build_hybrid_train_step(**engine_kwargs)`` will accept the
    candidate."""
    cfg = spec.cfg
    if c.world != world:
        return f"needs {c.world} devices, mesh has {world}"
    if c.schedule not in SCHEDULES:
        return f"unknown schedule {c.schedule!r}"
    if c.zero_stage not in (0, 1, 2, 3):
        return f"zero_stage must be 0/1/2/3, got {c.zero_stage}"
    if c.zero_stage and c.dp <= 1:
        # the engine degenerates fine at dp=1, but a size-1 shard axis
        # buys nothing — pruning it keeps the ranked list free of
        # score-tied duplicates (the launcher trial-runs only top-k)
        return "zero_stage shards over dp: needs dp > 1"
    if c.mp_overlap not in MP_OVERLAP_MODES:
        return f"unknown mp_overlap mode {c.mp_overlap!r} " \
               f"(one of {MP_OVERLAP_MODES})"
    if (c.schedule == "interleaved") != (c.vpp > 1):
        return "interleaved iff vpp > 1"
    if c.schedule != "1f1b" and c.pp <= 1:
        return f"{c.schedule} needs a pipeline (pp > 1): zbh1's split " \
               "backward and the interleaved chunk wrap only buy bubble"
    if spec.layers % (c.pp * c.vpp) != 0:
        return f"layers {spec.layers} not divisible by pp*vpp " \
               f"{c.pp * c.vpp}"
    if c.vpp > 1 and c.micro_batches < c.pp:
        return "interleaved schedule needs micro_batches >= pp"
    if spec.heads % c.mp != 0:
        return f"heads {spec.heads} not divisible by mp {c.mp}"
    if spec.vocab % c.mp != 0:
        return f"vocab {spec.vocab} not divisible by mp {c.mp}"
    if spec.family == "llama":
        if cfg.num_kv_heads % c.mp != 0:
            return f"kv heads {cfg.num_kv_heads} not divisible by mp {c.mp}"
        if c.schedule == "zbh1":
            return "llama builder exposes 1f1b/interleaved only"
        if c.comm_bucket_mb > 0:
            return "llama builder has no comm_overlap kwarg (flag-driven)"
        if c.ep > 1 or c.moe_quantize or c.moe_overlap:
            return "llama has no MoE path"
    replicas = c.dp * c.ep
    if global_batch % replicas != 0:
        return f"global batch {global_batch} not divisible by dp*ep " \
               f"{replicas}"
    b_rank = global_batch // replicas
    if b_rank % c.micro_batches != 0:
        return f"per-rank batch {b_rank} not divisible by " \
               f"micro_batches {c.micro_batches}"
    if c.mp_overlap is not None:
        if c.mp <= 1:
            return "mp_overlap needs mp > 1"
        if seq % c.mp != 0:
            return f"sequence parallelism needs seq {seq} divisible by " \
                   f"mp {c.mp}"
    if c.fp8:
        if c.schedule != "1f1b" or c.vpp > 1:
            return "fp8 delayed scaling supports the 1F1B schedule only"
        if c.mp_overlap == "collective_matmul":
            return "fp8 x ring collective-matmul sums partial amax " \
                   "observations"
        if c.comm_bucket_mb > 0:
            return "fp8 is not composed with comm_overlap"
    if c.flash_attention:
        head_dim = spec.hidden // spec.heads
        if head_dim > 256:
            return f"flash kernel caps head_dim at 256 (got {head_dim})"
        if seq % 128 != 0:
            return f"flash attention tiles 128-lane sequence blocks: " \
                   f"seq {seq} % 128 != 0 (pad upstream)"
    if spec.moe_on:
        if spec.moe_experts % c.ep != 0:
            return f"ep {c.ep} must divide expert count {spec.moe_experts}"
        if spec.ffn % c.mp != 0:
            return f"expert hidden {spec.ffn} not divisible by mp {c.mp}"
        if c.schedule != "1f1b" or c.vpp > 1:
            return "GPT-MoE supports the 1F1B schedule only"
        if c.fp8 or c.mp_overlap is not None:
            return "GPT-MoE is not composed with fp8 or sequence " \
                   "parallelism"
        if c.moe_quantize:
            if c.pp != 1 or c.micro_batches != 1:
                return "moe_quantize_a2a needs pp=1 and micro_batches=1"
            if c.comm_bucket_mb > 0:
                return "moe_quantize_a2a is not composed with comm_overlap"
    else:
        if c.ep != 1:
            return "dense model: ep must be 1"
        if c.moe_quantize or c.moe_overlap:
            return "dense model: no MoE exchange to configure"
    return None


def generate_plan_candidates(
        spec: ModelSpec, world: int, *, global_batch: int, seq: int,
        micro_batch_options: Sequence[int] = (1, 2, 4, 8),
        schedules: Sequence[str] = SCHEDULES,
        vpp_options: Sequence[int] = (1, 2),
        # stage 1 is deliberately absent from the default enumeration:
        # stages 1 and 2 compile the SAME program (tier-1 asserted) and
        # score identically, but stage 2's HBM accounting dominates —
        # enumerating both just fills the ranked list with score-tied
        # twins that burn launcher trial slots. Stage 1 remains fully
        # constructible/checkable for explicit candidates.
        zero_stage_options: Sequence[int] = (0, 2, 3),
        fp8_options: Sequence[bool] = (False,),
        comm_bucket_options: Sequence[float] = (0.0, 4.0),
        mp_overlap_options: Sequence[Optional[str]] = MP_OVERLAP_MODES,
        flash_options: Sequence[bool] = (False, True),
        moe_variants: Optional[Sequence[Dict[str, bool]]] = None,
) -> Tuple[List[PlanCandidate], List[Tuple[PlanCandidate, str]]]:
    """Enumerate the surface and split it into (valid, pruned-with-reason).

    fp8 defaults OFF in the enumeration (it changes numerics, not just
    schedule — opt in with fp8_options=(False, True) when an fp8 run is
    acceptable). flash_attention defaults to BOTH (numerics-preserving:
    the kernel computes the same softmax-attention; the search trades its
    higher executed flops against the O(S²)→O(S) activation HBM). MoE
    variants default to index dispatch with and without the
    overlapped/quantized exchange where legal.
    """
    if moe_variants is None:
        if spec.moe_on:
            moe_variants = ({"moe_index": True},
                            {"moe_index": True, "moe_overlap": True},
                            {"moe_index": True, "moe_quantize": True,
                             "moe_overlap": True})
        else:
            moe_variants = ({},)
    ep_options = ([e for e in _divisors(world)
                   if spec.moe_experts % e == 0] if spec.moe_on else [1])
    valid: List[PlanCandidate] = []
    pruned: List[Tuple[PlanCandidate, str]] = []
    seen = set()
    for ep in ep_options:
        for dp in _divisors(world // ep):
            rem = world // (ep * dp)
            for mp in _divisors(rem):
                pp = rem // mp
                for (M, sched, vpp, zs, f8, bkt, mpo, fl, moe) in \
                        itertools.product(micro_batch_options, schedules,
                                          vpp_options, zero_stage_options,
                                          fp8_options, comm_bucket_options,
                                          mp_overlap_options, flash_options,
                                          moe_variants):
                    if (sched == "interleaved") != (vpp > 1):
                        continue  # structural, not worth a prune record
                    c = PlanCandidate(
                        dp=dp, mp=mp, pp=pp, ep=ep, vpp=vpp,
                        schedule=sched, micro_batches=M, zero_stage=zs,
                        fp8=f8, comm_bucket_mb=bkt, mp_overlap=mpo,
                        flash_attention=fl, **moe)
                    if c in seen:
                        continue
                    seen.add(c)
                    reason = check_candidate(c, spec, world=world,
                                             global_batch=global_batch,
                                             seq=seq)
                    if reason is None:
                        valid.append(c)
                    else:
                        pruned.append((c, reason))
    return valid, pruned


# ---------------------------------------------------------------------------
# The three-part cost model.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Prediction:
    """One candidate's scored estimate. ``step_s = compute_s (bubble
    included) + exposed_comm_s + launch_s``."""
    step_s: float
    compute_s: float
    exposed_comm_s: float
    launch_s: float
    bubble_frac: float
    comm_frac: float
    mfu: float
    hbm_bytes: float
    n_collectives: int
    compute_units: float        # executed FLOPs per chip (calibration x)
    wire: Dict[str, float] = dataclasses.field(default_factory=dict)
    hbm: Dict[str, float] = dataclasses.field(default_factory=dict)


class CostModel:
    """Analytic step-time/HBM model over :class:`PlanCandidate`s.

    predict() returns seconds from three parts:

    (a) compute — executed FLOPs per chip from ``observability.flops``
        (remat-aware: the hybrid pipeline fully remats each stage;
        MoE adds the capacity expert GEMMs and, for dense dispatch, the
        2*T*E*C*D einsum delta — ``gpt_moe_flops_per_token``), scaled by
        the schedule's executed-tick ratio ((M+P-1)/M for 1F1B,
        (V*M+P-1)/(V*M) interleaved, the ``zbh1_speedup`` model for
        ZBH1), divided by the measured-or-peak rate;

    (b) exposed communication — the validated wire models
        (``mp_wire_bytes`` with schedule-aware executed-block counts, dp
        bucket accounting, ``ep_a2a_wire_bytes``, pp boundary ppermutes)
        over the profile's link bandwidth, discounted by the T3 hidable
        fraction of each mode;

    (c) per-collective launch overhead — n_collectives x
        ``collective_launch_s``; negligible on TPU, DOMINANT on the CPU
        smoke mesh (which is why the CPU proxy ranks ring > sp >
        allreduce by op count, while the same
        model with TPU rates ranks them the other way around).
    """

    def __init__(self, spec: ModelSpec, profile: HardwareProfile, *,
                 global_batch: int, seq: int,
                 rate_flops: Optional[float] = None,
                 collective_launch_s: Optional[float] = None,
                 step_overhead_s: float = 0.0):
        self.spec = spec
        self.profile = profile
        self.B = int(global_batch)
        self.S = int(seq)
        self.rate = (rate_flops if rate_flops is not None
                     else profile.peak_flops * profile.gemm_efficiency)
        self.t_launch = (collective_launch_s
                         if collective_launch_s is not None
                         else profile.collective_launch_s)
        # fixed per-step dispatch/host overhead (seconds): ~0 on TPU at
        # real shapes, tens of ms on the CPU smoke mesh at toy shapes —
        # calibrate() fits it from the measured anchors
        self.step_overhead_s = float(step_overhead_s)

    # -- schedule structure --------------------------------------------------
    @staticmethod
    def _ticks(c: PlanCandidate) -> float:
        M, P, V = c.micro_batches, c.pp, c.vpp
        if c.schedule == "interleaved":
            return V * M + P - 1
        return M + P - 1

    @staticmethod
    def _tick_ratio(c: PlanCandidate) -> float:
        """Executed stage work / useful stage work (>= 1): every pipeline
        tick runs the stage body, bubbles included (they compute on
        zeros and move real bytes — the telemetry tests' accounting)."""
        M, P, V = c.micro_batches, c.pp, c.vpp
        if c.schedule == "interleaved":
            return (V * M + P - 1) / (V * M)
        if c.schedule == "zbh1":
            from ...distributed.fleet.meta_parallel.pp_utils.spmd_pipeline \
                import zbh1_speedup
            return ((M + P - 1) / M) / zbh1_speedup(P, M)
        return (M + P - 1) / M

    def bubble_frac(self, c: PlanCandidate) -> float:
        r = self._tick_ratio(c)
        return max(0.0, 1.0 - 1.0 / r)

    # -- (a) compute ---------------------------------------------------------
    def compute_units(self, c: PlanCandidate) -> float:
        """Executed FLOPs per chip per step."""
        from ...observability import flops as F
        sp = self.spec
        b_rank = self.B // (c.dp * c.ep)
        mb = b_rank // c.micro_batches
        # pipelined blocks: remat full (the stage bodies are checkpointed)
        blk = F.transformer_flops_per_token(
            n_params=sp.n_block_params, num_layers=sp.layers,
            hidden_size=sp.hidden, seq_len=self.S, remat=c.remat)
        units = (b_rank * self.S) * blk["hardware"] / (c.mp * c.pp) \
            * self._tick_ratio(c)
        if c.flash_attention:
            # swap the einsum attention term for the flash one: the fused
            # kernel EXECUTES more flops (its two-kernel backward
            # re-derives the scores tiles) — the honest compute cost the
            # O(S²)→O(S) HBM saving below is traded against
            a_e = F.attention_flops_per_token(
                num_layers=sp.layers, hidden_size=sp.hidden,
                seq_len=self.S, impl="einsum", remat=c.remat)
            a_f = F.attention_flops_per_token(
                num_layers=sp.layers, hidden_size=sp.hidden,
                seq_len=self.S, impl="flash", remat=c.remat)
            units += (b_rank * self.S) \
                * (a_f["hardware"] - a_e["hardware"]) / (c.mp * c.pp) \
                * self._tick_ratio(c)
        # LM head + embedding run on every pp rank (outside the remat'd
        # pipeline): 6 flops/param fwd+bwd, sharded over mp only
        units += (b_rank * self.S) * 6.0 * sp.n_head_params / c.mp
        if sp.moe_on:
            m = F.gpt_moe_flops_per_token(sp.cfg, tokens_per_rank=mb * self.S,
                                          mp=c.mp)
            L2 = sp.layers // 2
            per_layer_exec = m["expert_gemm_flops_per_rank_step"] / L2
            n_exec = (c.micro_batches + c.pp - 1) * (L2 / c.pp)
            units += per_layer_exec * n_exec
            if not c.moe_index:
                # dense one-hot dispatch einsums: fwd pays the delta,
                # backward re-runs both under remat (~3x forward)
                units += 3.0 * m["dense_dispatch_flops_per_moe_layer"] \
                    * n_exec
        return units

    def model_flops_per_token(self) -> float:
        """The MFU numerator (useful work per trained token)."""
        from ...observability import flops as F
        sp = self.spec
        f = F.transformer_flops_per_token(
            n_params=sp.n_block_params + sp.n_head_params,
            num_layers=sp.layers, hidden_size=sp.hidden,
            seq_len=self.S)["model"]
        if sp.moe_on:
            f += F.gpt_moe_flops_per_token(
                sp.cfg, tokens_per_rank=self.B * self.S
            )["model_flops_per_token"]
        return f

    # -- (b) wire ------------------------------------------------------------
    def wire_bytes(self, c: PlanCandidate) -> Dict[str, float]:
        """Per-rank per-step wire bytes by mesh axis, from the validated
        observability models (the SAME formulas the models deposit via
        note_mp_comm/note_ep_comm and the telemetry tests re-derive)."""
        from ...observability.metrics import ep_a2a_wire_bytes, \
            mp_wire_bytes
        sp = self.spec
        dt = sp.act_itemsize
        b_rank = self.B // (c.dp * c.ep)
        mb = b_rank // c.micro_batches
        a_blk = mb * self.S * sp.hidden * dt
        a_full = b_rank * self.S * sp.hidden * dt
        M, P, V = c.micro_batches, c.pp, c.vpp
        out: Dict[str, float] = {"mp": 0.0, "dp": 0.0, "ep": 0.0,
                                 "pp": 0.0, "z3ag": 0.0}
        if c.mp > 1:
            if sp.moe_on:
                n_pairs_local = (sp.layers // 2) / c.pp
                executed = (M + P - 1) * n_pairs_local
                from ...incubate.distributed.models.moe.gate import \
                    compute_capacity
                E = sp.moe_experts
                C = compute_capacity(mb * self.S, E, 1,
                                     sp.cfg.moe_capacity_factor)
                out["mp"] = mp_wire_bytes(
                    "allreduce", c.mp,
                    gemm_pair_bytes=3.0 * executed * a_blk,
                    allreduce_bytes=(2.0 * a_full
                                     + 4.0 * b_rank * self.S * 4
                                     + executed * float(E * C * sp.hidden
                                                        * dt)))
            else:
                executed = (V * M + P - 1) * (sp.layers / c.pp) / V
                mode = c.mp_overlap or "allreduce"
                out["mp"] = mp_wire_bytes(
                    mode, c.mp,
                    gemm_pair_bytes=2.0 * executed * a_blk,
                    allreduce_bytes=(2.0 * a_full
                                     + 4.0 * b_rank * self.S * 4),
                    scatter_bytes=a_full)
        if c.dp > 1:
            if c.zero_stage >= 3:
                # sharded leaves reduce inside the loss's AD transpose —
                # their 2·AG + 1·RS are the z3ag term (the validated
                # observability model); only the replicated-leaf grads
                # still all-reduce on the classic dp path
                from ...observability.metrics import zero3_ag_wire_bytes
                blk, oth, repl, _ = self._z3_leaf_split(c)
                out["dp"] = 2.0 * (c.dp - 1) / c.dp * repl
                out["z3ag"] = zero3_ag_wire_bytes(
                    c.dp, block_param_bytes=blk / V,
                    n_stage_executions=self._ticks(c),
                    other_param_bytes=oth, quantize=False)
            else:
                # stages 0/1/2 move the same dp bytes: one all-reduce, or
                # the RS + closing param AG pair (each f·G)
                grad_local = self._grad_local_bytes(c)
                out["dp"] = 2.0 * (c.dp - 1) / c.dp * grad_local
        if c.ep > 1:
            from ...incubate.distributed.models.moe.gate import \
                compute_capacity
            E = sp.moe_experts
            C = compute_capacity(mb * self.S, E, 1,
                                 sp.cfg.moe_capacity_factor)
            n_exec = (M + P - 1) * (sp.layers // 2) / c.pp
            out["ep"] = ep_a2a_wire_bytes(
                c.ep, payload_elems=float(E * C * sp.hidden),
                n_layer_executions=float(n_exec), itemsize=dt,
                quantize=c.moe_quantize)
        if c.pp > 1:
            a_pp = a_blk / (c.mp if c.mp_overlap else 1)
            out["pp"] = 2.0 * self._ticks(c) * a_pp
        return out

    def _grad_local_bytes(self, c: PlanCandidate) -> float:
        sizes = c.mesh_dims()
        total = 0.0
        for n, item, spec_axes, _shape in self.spec.leaves:
            total += n * item / _shard_product(spec_axes, sizes)
        return total

    def _z3_leaf_split(self, c: PlanCandidate):
        """(block_bytes, other_bytes, repl_bytes, per_layer_bytes) for the
        zero3 wire/HBM model — param bytes LOCAL to the mp/pp/ep shards,
        full over dp. `block` = dp-shardable stacked pipeline leaves
        (spec carries 'pp'): gathered per layer per tick. `other` =
        dp-shardable once-per-step leaves (embeddings/head/final LN).
        `repl` = leaves with no dp-shardable dim (stay replicated; their
        grads still pmean). per_layer_bytes = one LOCAL layer's gathered
        block params (the stage-3 live-working-set unit: the scan carry
        holds ~2 of these under the prefetch)."""
        sizes = c.mesh_dims()
        blk = oth = repl = per_layer = 0.0
        for n, item, spec_axes, shape in self.spec.leaves:
            local = n * item / _shard_product(spec_axes, sizes)
            if not _leaf_dp_shardable(shape, spec_axes, c.dp):
                repl += local
                continue
            if any("pp" in axes for _, axes in spec_axes):
                blk += local
                per_layer += local * c.pp / max(shape[0], 1)
            else:
                oth += local
        return blk, oth, repl, per_layer

    def _hide(self, key: str, table: float) -> float:
        """Hidable fraction for one wire term: a measured override in the
        profile's ``hide`` dict wins outright (it came from attributing a
        real window); otherwise the table constant, zeroed when the
        backend cannot overlap at all."""
        h = self.profile.hide or {}
        if key in h:
            return min(max(float(h[key]), 0.0), 1.0)
        return table if self.profile.overlap_capable else 0.0

    def hide_fractions(self, c: PlanCandidate) -> Dict[str, float]:
        """The per-axis hidable fractions this candidate is scored with
        (override-aware) — also what the bench's profile_attribution
        section prints next to the measured ones."""
        mp_mode = ("allreduce" if self.spec.moe_on
                   else (c.mp_overlap or "allreduce"))
        return {
            "mp": self._hide(f"mp:{mp_mode}", _HIDE_MP[
                c.mp_overlap if not self.spec.moe_on else "allreduce"]),
            "dp": (self._hide("dp:bucketed", _HIDE_DP_BUCKETED)
                   if c.comm_bucket_mb > 0
                   else self._hide("dp:monolithic", _HIDE_DP_MONOLITHIC)),
            # engine_kwargs PINS the prefetched unquantized gather
            # (Zero3Config()), so candidates are scored with the
            # overlapped fraction they will actually run; the [False]
            # table entry prices a hand-built no-prefetch engine, and a
            # measured dp:zero3_ag profile override wins over both
            "z3ag": self._hide("dp:zero3_ag", _HIDE_DP_ZERO3_AG[True]),
            "ep": self._hide("ep:overlap" if c.moe_overlap else "ep:plain",
                             _HIDE_EP[bool(c.moe_overlap)]),
            "pp": self._hide("pp", _HIDE_PP),
        }

    def exposed_comm_s(self, c: PlanCandidate) -> Tuple[float,
                                                        Dict[str, float]]:
        wire = self.wire_bytes(c)
        bw = self.profile.ici_gbs * 1e9
        hide = self.hide_fractions(c)
        exp = {ax: wire[ax] / bw * (1 - hide[ax])
               for ax in ("mp", "dp", "ep", "pp", "z3ag")}
        return sum(exp.values()), wire

    # -- (c) collective dispatch count --------------------------------------
    def n_collectives(self, c: PlanCandidate) -> int:
        sp = self.spec
        n = 0.0
        M, P, V = c.micro_batches, c.pp, c.vpp
        if c.mp > 1:
            if sp.moe_on:
                pairs = 3.0 * (M + P - 1) * (sp.layers // 2) / c.pp
                per_pair = 2
            else:
                pairs = 2.0 * (V * M + P - 1) * (sp.layers / c.pp) / V
                per_pair = {None: 2, "seq_parallel": 4,
                            "collective_matmul": 4 * (c.mp - 1)}[
                    c.mp_overlap]
            n += pairs * per_pair + 4  # + embed/head/CE boundary
        if c.dp > 1:
            if c.comm_bucket_mb > 0:
                n_buckets = max(1.0, math.ceil(
                    self._grad_local_bytes(c)
                    / (c.comm_bucket_mb * (1 << 20))))
            else:
                n_buckets = 1.0  # XLA fuses the monolithic pmean
            if c.zero_stage >= 3:
                # replicated-leaf pmean only; the sharded leaves' AG/RS
                # execute per (tick, layer, leaf kind): 2 gathers (fwd +
                # remat replay) + 1 cotangent reduce-scatter each, plus
                # the once-per-step pairs for embeddings/head
                n += n_buckets
                sizes = c.mesh_dims()
                for _, _, spec_axes, shape in sp.leaves:
                    if not _leaf_dp_shardable(shape, spec_axes, c.dp):
                        continue
                    if any("pp" in axes for _, axes in spec_axes):
                        layers_exec = self._ticks(c) * shape[0] \
                            / (c.pp * c.vpp)
                        n += 3.0 * layers_exec
                    else:
                        n += 2.0
            else:
                n += n_buckets * (2 if c.zero_stage else 1)
        if c.pp > 1:
            n += 2.0 * self._ticks(c)
        if c.ep > 1:
            chunks = 2 if c.moe_overlap else 1
            n += 4.0 * (M + P - 1) * (sp.layers // 2) / c.pp * chunks
        return int(round(n))

    # -- memory --------------------------------------------------------------
    def hbm_bytes(self, c: PlanCandidate, *, moment_itemsize: int = 4,
                  optimizer_slots: int = 2) -> Tuple[float,
                                                     Dict[str, float]]:
        """Per-chip analytic HBM: per-leaf params/grads from the model's
        own spec tree (the hbm_audit accounting without a Mesh),
        optimizer slots with the zero1 per-leaf dp sharding rule, and an
        activation estimate for the fully-rematted pipeline (saved stage
        inputs per tick + one block's working set + attention scores +
        the vocab-parallel logits). Cross-check against compiled
        ``memory_analysis`` with hbm_audit.audit_plan_compile."""
        sp = self.spec
        sizes = c.mesh_dims()
        params = grads = opt = 0.0
        for n, item, spec_axes, shape in sp.leaves:
            local = n / _shard_product(spec_axes, sizes)
            shardable = _leaf_dp_shardable(shape, spec_axes, c.dp)
            pb = local * item
            # the zero stage axis: stage >= 1 shards the slots, stage
            # >= 2 the grad buffer, stage 3 the resident params (each by
            # the SAME per-leaf rule the engine's zero_dims applies)
            params += pb / c.dp if (c.zero_stage >= 3 and shardable) \
                else pb
            grads += pb / c.dp if (c.zero_stage >= 2 and shardable) \
                else pb
            slot = local * moment_itemsize * optimizer_slots
            if c.zero_stage >= 1 and shardable:
                slot /= c.dp
            opt += slot
        dt = sp.act_itemsize
        b_rank = self.B // (c.dp * c.ep)
        mb = b_rank // c.micro_batches
        s_sp = self.S // (c.mp if c.mp_overlap else 1)
        H, FF = sp.hidden, sp.ffn
        act = self._ticks(c) * mb * s_sp * H * dt          # saved inputs
        act += mb * self.S * dt * (2 * H + (4 * H + 2 * FF) / c.mp)
        if c.flash_attention:
            # the fused kernel never materializes scores in HBM — its
            # rematted working set is the (out, lse) residual pair, O(S)
            act += mb * self.S * ((H / c.mp) * dt
                                  + (sp.heads / c.mp) * 4.0)
        else:
            act += mb * (sp.heads / c.mp) * self.S ** 2 * dt  # attn scores
        act += b_rank * self.S * (sp.vocab / c.mp) * (dt + 8)  # logits+CE
        act += 2.0 * b_rank * self.S * H * dt              # embed in/out
        if sp.moe_on:
            from ...incubate.distributed.models.moe.gate import \
                compute_capacity
            E = sp.moe_experts
            C = compute_capacity(mb * self.S, E, 1,
                                 sp.cfg.moe_capacity_factor)
            act += 4.0 * E * C * H * dt                    # a2a buffers
        if c.zero_stage >= 3:
            # stage-3 live working set: the scan carry holds the current
            # block's gathered params plus the prefetched next block's
            _, _, _, per_layer = self._z3_leaf_split(c)
            act += 2.0 * per_layer
        parts = {"params": params, "grads": grads, "opt": opt, "act": act}
        return 1.10 * sum(parts.values()), parts

    # -- the verdict ---------------------------------------------------------
    def predict(self, c: PlanCandidate) -> Prediction:
        units = self.compute_units(c)
        t_comp = units / self.rate
        t_comm, wire = self.exposed_comm_s(c)
        ncoll = self.n_collectives(c)
        t_launch = ncoll * self.t_launch
        step = t_comp + t_comm + t_launch + self.step_overhead_s
        hbm, hbm_parts = self.hbm_bytes(c)
        toks = self.B * self.S
        mfu = (toks * self.model_flops_per_token()
               / (c.world * self.profile.peak_flops * step))
        return Prediction(
            step_s=step, compute_s=t_comp, exposed_comm_s=t_comm,
            launch_s=t_launch, bubble_frac=self.bubble_frac(c),
            comm_frac=(t_comm + t_launch) / step, mfu=mfu,
            hbm_bytes=hbm, n_collectives=ncoll, compute_units=units,
            wire=wire, hbm=hbm_parts)

    def calibrate(self, anchors: Sequence[Tuple[PlanCandidate, float]]
                  ) -> "CostModel":
        """Fit (compute rate, per-collective launch overhead, fixed
        per-step overhead) to measured anchor step times — the
        measured-or-peak leg of the model. Wire terms stay at the
        profile's bandwidth (known offset). One anchor fits the rate
        only; two fit rate + per-step overhead; three or more
        least-squares all three over
        ``measured ~= units/rate + n_coll*t_launch + overhead + wire``.
        Returns a NEW CostModel; self is untouched."""
        import numpy as np
        units = []
        ncoll = []
        rhs = []
        for cand, measured in anchors:
            wire_s, _ = self.exposed_comm_s(cand)
            units.append(self.compute_units(cand))
            ncoll.append(float(self.n_collectives(cand)))
            rhs.append(max(measured - wire_s, 1e-9))
        b = np.asarray(rhs)
        t_launch, overhead = self.t_launch, self.step_overhead_s
        if len(anchors) >= 3:
            # a joint 3-parameter lstsq is ill-conditioned (units and
            # collective counts correlate across realistic anchors and
            # timing noise then tips the fit into degenerate corners), so
            # fit SEQUENTIALLY: t_launch from the anchor pair with the
            # closest compute units but different collective counts (their
            # time difference is almost purely dispatch count)...
            best = None
            for i in range(len(anchors)):
                for j in range(i + 1, len(anchors)):
                    dn = abs(ncoll[i] - ncoll[j])
                    if dn < 1:
                        continue
                    du = abs(units[i] - units[j]) / max(units[i], units[j])
                    if best is None or du < best[0]:
                        best = (du, i, j)
            if best is not None and best[0] < 0.25:
                _, i, j = best
                t_launch = max((b[i] - b[j]) / (ncoll[i] - ncoll[j]), 0.0)
        if len(anchors) == 1:
            inv_rate = (b[0] - ncoll[0] * t_launch - overhead) / units[0]
        else:
            # ...then (rate, fixed overhead) over all anchors with the
            # launch term subtracted
            A = np.asarray([[u, 1.0] for u in units])
            sol, *_ = np.linalg.lstsq(A, b - np.asarray(ncoll) * t_launch,
                                      rcond=None)
            inv_rate, overhead = sol[0], max(sol[1], 0.0)
        inv_rate = max(inv_rate, 1e-18)
        return CostModel(self.spec, self.profile, global_batch=self.B,
                         seq=self.S, rate_flops=1.0 / inv_rate,
                         collective_launch_s=t_launch,
                         step_overhead_s=overhead)


# ---------------------------------------------------------------------------
# Top-level plan(): generate -> prune (constraints + HBM) -> score -> rank.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ScoredPlan:
    candidate: PlanCandidate
    prediction: Prediction

    def row(self) -> Dict[str, Any]:
        p = self.prediction
        return {"candidate": str(self.candidate),
                "mesh": self.candidate.mesh_dims(),
                "step_ms": round(p.step_s * 1e3, 3),
                "mfu_pct": round(p.mfu * 100, 2),
                "comm_frac": round(p.comm_frac, 4),
                "bubble_frac": round(p.bubble_frac, 4),
                "hbm_gb": round(p.hbm_bytes / 1e9, 3),
                "n_collectives": p.n_collectives}


@dataclasses.dataclass
class PlanReport:
    spec: ModelSpec
    profile: HardwareProfile
    global_batch: int
    seq: int
    ranked: List[ScoredPlan]
    pruned: List[Tuple[PlanCandidate, str]]
    n_generated: int = 0

    def top(self, k: int) -> List[ScoredPlan]:
        return self.ranked[:k]

    def to_json(self, top_k: Optional[int] = None) -> Dict[str, Any]:
        rows = self.ranked if top_k is None else self.ranked[:top_k]
        return {
            "model": type(self.spec.cfg).__name__,
            "family": self.spec.family,
            "profile": dataclasses.asdict(self.profile),
            "global_batch": self.global_batch, "seq": self.seq,
            "n_generated": self.n_generated,
            "n_valid": len(self.ranked),
            "n_pruned": len(self.pruned),
            "ranked": [s.row() for s in rows],
            "pruned": [{"candidate": str(c), "reason": r}
                       for c, r in self.pruned],
        }


def plan(cfg, *, world: int, global_batch: int, seq: int,
         family: str = "gpt", profile: Optional[HardwareProfile] = None,
         hbm_gb: Optional[float] = None, cost_model: Optional[CostModel]
         = None, **gen_options) -> PlanReport:
    """The planner entry point: enumerate, constraint-prune, HBM-prune,
    score and rank every PlanCandidate for (cfg, world devices).

    hbm_gb overrides the profile's per-chip budget (the CLI's --hbm-gb /
    FLAGS_auto_parallel_hbm_gb). Extra kwargs go to
    generate_plan_candidates (micro_batch_options etc.)."""
    spec = ModelSpec.from_config(cfg, family)
    if profile is None:
        profile = profile_for(hbm_gb=hbm_gb)
    elif hbm_gb is not None and hbm_gb > 0:
        profile = dataclasses.replace(profile, hbm_gb=float(hbm_gb))
    cm = cost_model if cost_model is not None else CostModel(
        spec, profile, global_batch=global_batch, seq=seq)
    cands, pruned = generate_plan_candidates(
        spec, world, global_batch=global_batch, seq=seq, **gen_options)
    n_generated = len(cands) + len(pruned)
    budget = profile.hbm_gb * 1e9
    scored: List[ScoredPlan] = []
    for c in cands:
        pred = cm.predict(c)
        if pred.hbm_bytes > budget:
            pruned.append((c, f"analytic HBM {pred.hbm_bytes / 1e9:.2f} GB "
                              f"> budget {profile.hbm_gb:g} GB"))
            continue
        scored.append(ScoredPlan(c, pred))
    scored.sort(key=lambda s: s.prediction.step_s)
    return PlanReport(spec=spec, profile=profile, global_batch=global_batch,
                      seq=seq, ranked=scored, pruned=pruned,
                      n_generated=n_generated)


# ---------------------------------------------------------------------------
# Named model configs for the CLI / launcher.
# ---------------------------------------------------------------------------
PLAN_MODELS = ("gpt_tiny", "gpt1p3b", "gpt_moe_tiny", "llama_tiny")


def model_config_by_name(name: str, dtype=None):
    """(cfg, family) for the CLI's --model vocabulary."""
    import jax.numpy as jnp
    kw = {}
    if dtype is not None:
        kw = {"dtype": dtype,
              "param_dtype": jnp.float32 if dtype == jnp.float32 else dtype}
    if name == "gpt_tiny":
        from ...models.gpt import gpt_tiny
        return gpt_tiny(**kw), "gpt"
    if name in ("gpt1p3b", "gpt_1p3b"):
        from ...models.gpt import gpt_1p3b
        return gpt_1p3b(**kw), "gpt"
    if name == "gpt_moe_tiny":
        from ...models.gpt import gpt_moe_tiny
        return gpt_moe_tiny(**kw), "gpt"
    if name == "llama_tiny":
        from ...models.llama import llama_tiny
        return llama_tiny(**kw), "llama"
    raise ValueError(f"unknown model {name!r}; choose from {PLAN_MODELS}")
