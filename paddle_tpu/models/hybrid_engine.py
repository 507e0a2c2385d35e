"""Generic hybrid-parallel train-step builder shared by the model families.

Compiles ONE program containing: forward (vocab-parallel embed, pipelined
blocks, TP collectives), backward, dp gradient sync (monolithic pmean, or
the distributed.comm_overlap bucketed/overlapped/int8 schedule), and the
optimizer update — the TPU-native equivalent of the reference's
per-strategy wrapper stack (fleet/meta_parallel/*). Model files supply a
per-device loss_fn and a PartitionSpec tree; XLA schedules every
collective over ICI.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability.trace import SCOPES
from ..utils import shard_map as _shard_map

__all__ = ["build_train_step", "AccumulatedLoss", "scan_layers",
           "state_specs_for",
           "zero_dims", "zero_extend_spec", "zero_state_specs",
           "zero_param_specs", "zero1_state_specs"]


class AccumulatedLoss(NamedTuple):
    """The per-device loss of a mesh with ONE pipeline stage: callable like
    any loss_fn (`whole`, the loss of the whole local batch), and also the
    pieces build_train_step needs to run each microbatch's forward and
    backward one after another (there is no pipeline to fill:
    `microbatches` > 1 is gradient accumulation). The local loss is
    sum(share over the microbatches) for ANY label mask, because every
    share is divided by the whole local batch's `denom`, not by its own
    count."""
    whole: Callable     # loss_fn(params, tokens, labels, ...) of the batch
    microbatches: int
    denom: Callable     # labels [b_local, ...] -> scalar the shares divide by
    # (params, tokens_mb, labels_mb, denom, layers) -> local scalar.
    # `params` is a dict with the stacked layers under STACKED, and the
    # share says where a layer's parameters enter its body by running them
    # through `layers(body, x, params[STACKED])` (body(p, x) -> x;
    # `scan_layers` is the plain form): the engine's `layers` adds a
    # microbatch's gradient to the sum there (build_train_step)
    share: Callable
    reported: Callable  # local loss -> the loss the step returns

    def __call__(self, *args):
        return self.whole(*args)


STACKED = "blocks"   # an AccumulatedLoss's stacked layers in its `params`


def scan_layers(body, x, stack):
    """x through body(p, x) -> x for each layer p of `stack`, in order: the
    `layers` of an AccumulatedLoss's share where nothing rides the scan."""
    return lax.scan(lambda c, p: (body(p, c), None), x, stack)[0]


def state_specs_for(optimizer, specs, example_params=None):
    """Sharding specs for the optimizer state pytree: every array that
    mirrors a parameter (slots, accumulators — found by matching the
    parameter's key path inside the state leaf's path) inherits that
    parameter's spec; everything else (step counters, scalars) replicates.
    This is what makes ZeRO composition free — sharding the state tree IS
    sharding the optimizer — and it works for ANY wrapper structure
    (gradient merge, multi_precision master slots, nested inners).

    Without example_params a synthetic fp32 example is derived from the
    spec tree — exact for any wrapper STRUCTURE, but dtype-conditional
    slots (multi_precision master weights) need the real example."""
    is_spec = lambda x: isinstance(x, P)
    if example_params is None:
        example_params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((1,) * max(len(s), 1),
                                           jnp.float32),
            specs, is_leaf=is_spec)

    def path_keys(path):
        return tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path)

    spec_paths = {}
    for path, spec in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=is_spec)[0]:
        spec_paths[path_keys(path)] = spec
    lens = sorted({len(k) for k in spec_paths}, reverse=True)

    state_shape = jax.eval_shape(optimizer.init_state, example_params)

    def spec_for(path, leaf):
        keys = path_keys(path)
        for plen in lens:  # longest param-path embedded in the state path
            for i in range(len(keys) - plen + 1):
                cand = spec_paths.get(keys[i:i + plen])
                if cand is not None and len(cand) <= leaf.ndim:
                    return cand
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, state_shape)


def zero_dims(specs, example_params, mesh: Mesh, dp_axis: str):
    """Per-param-leaf dim index to shard over the dp axis — the ONE copy
    of the per-leaf dp-shardability rule shared by every ZeRO stage
    (stage 1/2: optimizer state + the update; stage 3: the params
    themselves) and mirrored by the planner's HBM math (reference:
    DygraphShardingOptimizer stage-1 partitioning,
    fleet/meta_parallel/dygraph_optimizer/dygraph_sharding_optimizer.py:44
    `_partition_parameters`, running under HybridParallelOptimizer).
    Picks the first dim with no existing mesh axis whose LOCAL extent
    (global / pp·mp shards) divides the dp degree; -1 = leaf stays
    replicated (tiny vectors; -1 not None — a None pytree leaf would
    vanish from tree_map/flatten_up_to)."""
    dp = mesh.shape[dp_axis]

    def dim_for(spec, leaf):
        shape = getattr(leaf, "shape", ())
        for d in range(len(shape)):
            ax = spec[d] if d < len(spec) else None
            if ax is not None:
                continue
            local = shape[d]
            if local % dp == 0 and local >= dp:
                return d
        return -1

    return jax.tree.map(dim_for, specs, example_params,
                        is_leaf=lambda x: isinstance(x, P))


def zero_extend_spec(spec: P, zd, dp_axis: str, ndim: int) -> P:
    if zd < 0:
        return spec
    entries = list(spec) + [None] * (ndim - len(spec))
    entries[zd] = dp_axis
    return P(*entries)


def zero_param_specs(specs, zdims, example_params, dp_axis: str = "dp"):
    """Stage-3 PARAM specs: every dp-shardable leaf's PartitionSpec grows
    the dp axis on its zero_dims dim (params dp-sharded AT REST); -1
    leaves keep their spec (replicated over dp)."""
    return jax.tree.map(
        lambda s, zd, p: zero_extend_spec(s, zd, dp_axis, p.ndim),
        specs, zdims, example_params,
        is_leaf=lambda x: isinstance(x, P))


def zero_state_specs(optimizer, specs, example_params, mesh: Mesh,
                     dp_axis: str = "dp"):
    """(zdims, state_specs) for ZeRO-over-dp: the ONE derivation of the
    dp-sharded optimizer-state layout, shared by build_train_step (every
    stage — the slots shard identically under stages 1/2/3), the
    hbm_audit 6.7B compile and the byte-shrink test (the call sites must
    agree or audited bytes stop matching the real program)."""
    zdims = zero_dims(specs, example_params, mesh, dp_axis)
    ext = zero_param_specs(specs, zdims, example_params, dp_axis)
    return zdims, state_specs_for(optimizer, ext, example_params)


# thin compat wrappers: PR 7 layout_extra fingerprints and the pre-stage
# call sites (hbm_audit, tests) keep working unchanged
_zero1_dims = zero_dims
_zero1_extend_spec = zero_extend_spec


def zero1_state_specs(optimizer, specs, example_params, mesh: Mesh,
                      dp_axis: str = "dp"):
    return zero_state_specs(optimizer, specs, example_params, mesh,
                            dp_axis)


def _effective_clip(opt):
    """(clip, owner): walk wrapper optimizers' `_inner` chain so a clip
    configured on the wrapped optimizer (LocalSGD(AdamW(grad_clip=...)))
    is seen — wrappers forward apply() to the inner, whose clip would
    otherwise silently compute rank-local norms under shard_map."""
    seen = set()
    o = opt
    while o is not None and id(o) not in seen:
        seen.add(id(o))
        c = getattr(o, "_grad_clip", None)
        if c is not None:
            return c, o
        o = getattr(o, "_inner", None)
    return None, None


# ONE copy of the spec-sharding/replication accounting, shared with the
# EF-residual norms (comm_overlap.quantize.residual_sq_norm) so the
# numerics telemetry can never drift from the grad-norm/clip rule
from ..distributed.comm_overlap.quantize import (  # noqa: E402
    replication_factor as _replication_factor, spec_axes as _spec_axes)


def _repl_factor(spec, zd, mesh: Mesh, dp_axis) -> int:
    """How many ranks hold a copy of this leaf: product of mesh axes it is
    NOT sharded over (zd >= 0 adds the ZeRO dp sharding)."""
    extra = (dp_axis,) if (zd is not None and zd >= 0) else ()
    return _replication_factor(spec, mesh, extra_sharded=extra)


def _global_leaf_reduce(per_leaf, red, leaves_spec, leaves_z, mesh: Mesh,
                        dp_axis):
    """Replication-aware global reduction over a sharded grad list: each
    leaf's local `per_leaf(g)` (an fp32 scalar) is divided by its
    replication factor, then ONE psum over every mesh axis counts each
    distinct element exactly once. The shared accounting under the
    global-norm clip and the telemetry grad-norm/nonfinite series."""
    acc = jnp.zeros((), jnp.float32)
    for g, sp, zd in zip(red, leaves_spec, leaves_z):
        if g is None:
            continue
        acc = acc + per_leaf(g) / _repl_factor(sp, zd, mesh, dp_axis)
    with jax.named_scope(SCOPES.coll_dp):   # scalars, every mesh axis
        return lax.psum(acc, tuple(mesh.axis_names))


def _global_sq_norm(red, leaves_spec, leaves_z, mesh: Mesh, dp_axis):
    from ..nn.clip import sum_squares
    return _global_leaf_reduce(lambda g: sum_squares([g]), red,
                               leaves_spec, leaves_z, mesh, dp_axis)


def _global_nonfinite_count(red, leaves_spec, leaves_z, mesh: Mesh,
                            dp_axis):
    return _global_leaf_reduce(
        lambda g: jnp.sum((~jnp.isfinite(g)).astype(jnp.float32)),
        red, leaves_spec, leaves_z, mesh, dp_axis)


def _global_clip_scale(red, leaves_spec, leaves_z, mesh: Mesh, dp_axis,
                       clip):
    """TRUE global-norm clip coefficient inside shard_map (reference:
    HybridParallelClipGrad, hybrid_parallel_optimizer.py:41 — partial
    norms combined across mp/pp/sharding before one shared coefficient);
    a naive ClipGradByGlobalNorm under shard_map would clip each
    model-parallel rank with a DIFFERENT partial norm."""
    n2 = _global_sq_norm(red, leaves_spec, leaves_z, mesh, dp_axis)
    return clip.scale_from_norm(jnp.sqrt(n2))


def _shares_buffer(a, b) -> bool:
    """Whether two arrays hold a device buffer in common (donating one
    then deletes the other)."""
    if not (isinstance(a, jax.Array) and isinstance(b, jax.Array)):
        return False
    held = {s.data.unsafe_buffer_pointer() for s in b.addressable_shards}
    return any(s.data.unsafe_buffer_pointer() in held
               for s in a.addressable_shards)


def build_train_step(loss_fn: Callable, specs: Dict[str, Any], mesh: Mesh,
                     optimizer, data_spec: P = None, dp_axis: str = "dp",
                     extra_grad_axes=(), example_params=None,
                     grad_reduce_dtype="auto", zero1_dp: bool = False,
                     zero_stage=None, zero3=None,
                     comm_overlap="auto", fp8=None, telemetry="auto",
                     mp_overlap=None, moe=None, flash=None, numerics=None,
                     donate: bool = True):
    """loss_fn(params, tokens, labels) -> scalar, running per-device inside
    shard_map. Returns (jitted_step, shard_params, init_state).

    loss_fn may be an AccumulatedLoss: what a model builder hands over
    when its mesh has ONE pipeline stage and nothing rides its pipeline's
    side channels. The step then scans over the microbatches, each
    iteration the forward AND the backward of one share (so one
    microbatch's residuals are alive at a time and nothing is replayed),
    summing the gradients in their own dtype on the carry: a gradient
    joins the sum where the backward makes it, a layer's inside the
    share's layer scan (the sum is its weight-gradient GEMM's epilogue,
    not a pass over the stack); the dp reduction, the clip and the
    optimizer follow ONCE, as after a plain loss_fn. Where that reduction
    is the plain pmean over ranks that are a power of two, the mean's
    division is made as the gradient joins the sum, and the all-reduce
    that follows is a psum (`folds`, below: the same bits, one pass over
    the gradients less). Builds whose gradient path is not the plain one
    (comm_overlap's own scan, fp8, the error-feedback carries) call it
    whole, as any loss_fn.

    grad_reduce_dtype: cast gradients to this dtype for the dp reduction
    and back (the reference's fp16_allreduce meta-optimizer,
    fleet/meta_optimizers/fp16_allreduce_optimizer.py — halves the
    ICI/DCN bytes of the gradient all-reduce; bf16 recommended on TPU).
    The default "auto" reads the active fleet strategy, so the reference
    flow `strategy.fp16_allreduce = True; fleet.init(strategy=s)` engages
    with no extra plumbing; pass None to force fp32 reduction. Optimizers
    that manage their own synchronization (LocalSGD/DGC — attribute
    `_skips_grad_sync`) receive dp-UNreduced local gradients.

    zero_stage: ZeRO sharding stage over the dp axis composed with the
    hybrid mesh (None/0 = off, compiles bitwise-identically to a build
    without the argument). Requires the per-leaf optimizer protocol
    (AdamW-family; name filters ride the ctx protocol) and supports
    ClipGradByGlobalNorm/ByValue. The per-leaf dp shard dim is the ONE
    `zero_dims` rule for every stage.

    * stage 1 (== the legacy ``zero1_dp=True``): optimizer state shards
      over dp (on top of its pp/mp shardings), grads reduce-scatter
      instead of all-reduce, each dp rank updates only its param shard
      and the new params all-gather back. Same bytes on the wire as
      allreduce (RS + AG), 1/dp the optimizer-state HBM and update
      flops. Reference: DygraphShardingOptimizer (stage 1) under
      HybridParallelOptimizer.
    * stage 2: stage 1 with the gradient reduce-scatter OWNING the dp
      grad buffer — the scattered shards are the only dp-synchronized
      gradients that exist next to the dp-sharded slots. In this
      one-compiled-program engine stage 1 already reduce-scatters
      before the update, so stages 1 and 2 issue the SAME collectives
      (trajectories are asserted identical in tests); the stage exists
      as an explicit axis because the planner's HBM rule and the
      checkpoint layout metadata account the grad buffer dp-sharded.
    * stage 3: params dp-sharded AT REST — every dp-shardable leaf's
      spec grows the dp axis (`zero_param_specs`), and the LOSS gathers
      each leaf on use (the model builders thread a zero3 plan:
      per-block all-gathers inside the layer scan, prefetched so block
      i+1's transfer hides under block i's compute, re-gathered by the
      backward's remat replay — comm_overlap.zero3.scan_gather). The
      all-gather's AD transpose delivers each leaf's gradient SHARD
      already dp-summed (psum_scatter), so the engine's update divides
      by dp and updates the resident shard in place: no full grad, no
      end-of-step param all-gather, params/grads/opt state all ~1/dp.
      Reference anchors: group_sharded_stage3.py:85,
      dygraph_sharding_optimizer.py:571 (allgather-overlap comm
      buffers).

    zero3: the stage-3 extras plan a model builder threads when the
    quantized gather is on — {"ef": {"init", "specs"} or None, "meta":
    build metadata}. The int8 error-feedback AG residuals then ride
    ``opt_state["zero3_ef"]`` (the moe_ef carry discipline: the loss
    takes the flat residual tree as 4th arg and returns
    (loss, new_residuals)); pp degree 1 / one pipeline microbatch only,
    not composed with fp8 / comm_overlap / the quantized-a2a MoE plan
    (each already owns the loss arity or the accumulation schedule).

    comm_overlap: bucketed, schedule-overlapped dp gradient collectives
    (distributed.comm_overlap) replacing the monolithic end-of-backward
    reduction — per-bucket psum (replicated) / per-leaf psum_scatter
    (zero1_dp), optionally issued per accumulation microbatch inside a
    lax.scan so they hide under later microbatches' compute, and
    optionally int8-quantized with error-feedback residuals (threaded as
    opt_state["comm_ef"]; needs example_params; replicated path only).
    "auto" reads FLAGS_comm_bucket_mb / FLAGS_comm_quantize /
    FLAGS_comm_overlap_microbatches (all default off); pass a
    CommOverlapConfig to force, or None to disable. Self-synchronizing
    optimizers (_skips_grad_sync) own the dp axis, so overlap is inert
    for them — pair them with comm_overlap.make_merge_comm_fn instead.

    telemetry: "auto" (FLAGS_telemetry, default off) / None /
    observability.TelemetryConfig — in-program device metrics: a fixed
    ring buffer {"data": f32[interval, n_series], "count": i32[]} rides
    opt_state["telemetry"] exactly as fp8_meta/comm_ef do (composes with
    both, and with zero1/donation), recording per step the loss, the
    replication-aware global grad norm, the global nonfinite-element
    count, the dp-collective wire bytes of THIS program's sync path
    (monolithic / bucketed / int8 / reduce-scatter+all-gather, from the
    same trace that issues them), fp8 amax/scale drift, and any
    observability.observe() series made under the loss (threaded out of
    value_and_grad — and out of the overlap scan — as aux outputs).
    Fetch on the host with observability.TelemetryHost.poll: one device
    fetch per interval, zero extra dispatches. When resolved off this is
    a STRICT no-op — the compiled program is bitwise identical.

    donate: the compiled step OWNS the state it updates (the default,
    as every other train-step builder of the library): params and
    opt_state are donated to it (jit's donate_argnums=(0, 1)), so
    fused_adam overwrites a leaf where it lies, with no copy of it in
    front of the kernel, and the state is resident once. Everything that
    rides opt_state goes with it: the moments, ZeRO's dp-sharded slots,
    the `step` counter, and the telemetry ring / fp8 meta / error-feedback
    carries, so none of the bookkeeping costs a second resident copy. The
    caller REBINDS: `params, state, loss = step(params, state, ...)`; the
    trees it passed in are deleted (reading one raises "Array has been
    deleted"), and so is whatever shares their buffers (device_put onto a
    sharding an array already has returns the same buffers: shard_params
    copies such a leaf, so the tree it was given stays the caller's). A
    caller that keeps its inputs (the same trees fed to two builds, a
    sweep that times one state again and again, a host that rolls back to
    the state before a bad step) passes donate=False: the same program
    text but for the aliasing, and the copies in front of the update.

    fp8: a quantization.fp8.fp8_plan dict (models build it) enabling
    delayed-scaling fp8 GEMMs in the loss: loss_fn then takes a fourth
    arg (the scale tree), value_and_grad runs over (params, scales) so
    the scale 'gradients' deliver this step's amax observations, those
    pmax over plan["axes"] (the axes scales are replicated on), and
    update_fp8_meta rotates the history. The (scale, amax_history) state
    rides opt_state["fp8_meta"] exactly as the int8 error-feedback
    residuals ride opt_state["comm_ef"] — same step signature, same
    checkpoint surface, donation preserved. Not composed with
    comm_overlap (the overlap scan's weighted accumulation would corrupt
    the amax semantics — disable one of the two).

    mp_overlap: metadata describing the mp-axis (tensor-parallel) comm
    structure the LOSS FUNCTION implements — None (plain allreduce TP),
    a comm_overlap.MpOverlapConfig, or a mode string ("seq_parallel" /
    "collective_matmul"). The engine cannot inject the mp path (it lives
    in the model's block bodies; gpt/llama build_hybrid_train_step
    thread it via their own mp_overlap="auto"); here it (a) lands in the
    telemetry JSONL header as static["mp_mode"], and (b) guards the
    fp8 x ring-collective-matmul combination, which is invalid for the
    same reason as fp8 x comm_overlap: the ring's per-chunk GEMMs would
    sum partial amax observations. The mp-axis WIRE BYTES are not a
    build-time constant (activation shapes appear at trace time), so the
    models deposit them through observability.note_mp_comm inside the
    loss trace; the engine opens the collecting scope around the step
    body and folds the value into the comms_bytes telemetry series.

    moe: expert-parallelism plan from a MoE model builder —
    {"ep_axis": mesh axis the expert bank shards over, "ef": None or
    {"init", "specs"} for the quantized-a2a error-feedback residuals,
    "meta": build metadata for the telemetry header}. The engine then
    (a) ep-synchronizes gradients with SPEC-AWARE semantics: leaves
    whose PartitionSpec carries the ep axis (the expert bank) already
    hold the COMPLETE sum of the ep group's token contributions via the
    transposed all-to-all and only rescale by 1/ep, while every other
    leaf is replicated over ep with PARTIAL local-shard grads and
    pmeans; (b) threads the residuals as opt_state["moe_ef"] — the loss
    then takes a fourth arg (the flat residual tree) and returns
    (loss, new_residuals), exactly the comm_ef/fp8_meta carry
    discipline; (c) counts the ep sync and the model-deposited a2a wire
    bytes (observability.note_ep_comm) into the comms_bytes telemetry
    series. The replication-aware global-norm clip and the telemetry
    grad-norm need NO MoE special-casing: _repl_factor reads the specs,
    so expert leaves count once per distinct element automatically.
    Not composed with fp8; the "ef" form is not composed with
    comm_overlap (the overlap scan calls the loss once per comm
    microbatch — residual slots are per step).

    flash: metadata describing the fused-attention plan the LOSS
    FUNCTION implements (a kernels.pallas.flash_training
    FlashAttentionConfig or None) — like mp_overlap, the engine cannot
    inject the path (it lives in the model's block bodies; gpt/llama
    thread it via their own flash_attention="auto"); here it lands in
    the telemetry JSONL header as static["flash"]. A sep-mode plan's
    context-parallel gradients arrive through extra_grad_axes like any
    other partial-grad axis — no engine special-casing.

    numerics: None, or an observability.numerics.NumericsConfig (the
    model builders resolve their numerics="auto" off FLAGS_numerics) —
    in-program tensor-health telemetry riding the SAME ring buffer. The
    engine then (a) auto-creates a non-strict TelemetryConfig when
    telemetry resolved off (numerics implies the carry), (b) registers
    the numerics series (observability.numerics.numerics_series) onto
    the config from its own live plans — per-stacked-layer grad norms,
    EF-residual norms for whichever of comm_ef/moe_ef/zero3_ef this
    build threads, fp8 per-site saturation/headroom — and (c) computes
    the engine-side values at trace time with the same replication
    accounting the global-norm clip uses. Models deposit the per-layer
    activation rms/absmax through observe() (ncfg.act). None compiles
    bitwise-identically to a build without the argument."""
    if grad_reduce_dtype == "auto":
        from ..distributed.fleet.fleet import fleet as _fleet
        grad_reduce_dtype = _fleet.grad_reduce_dtype()
    data_spec = P(dp_axis) if data_spec is None else data_spec
    # -- ZeRO stage resolution (zero1_dp is the legacy stage-1 spelling) ----
    zero_stage = 0 if zero_stage is None else int(zero_stage)
    if zero1_dp:
        from ..enforce import enforce
        enforce(zero_stage in (0, 1),
                "zero1_dp is the legacy spelling of zero_stage=1 — do not "
                "combine it with a different explicit stage",
                op="build_train_step", zero_stage=zero_stage)
        zero_stage = 1
    zdims = None
    pspecs = specs  # the PARAM specs the program shards with
    if zero_stage:
        from ..distributed.sharding.group_sharded import _leaf_streamable
        from ..enforce import enforce
        enforce(zero_stage in (1, 2, 3),
                "zero_stage must be one of 0/1/2/3",
                op="build_train_step", zero_stage=zero_stage)
        enforce(example_params is not None,
                "zero_stage needs example_params (leaf shapes pick the dp "
                "shard dims)", op="build_train_step")
        enforce(_leaf_streamable(optimizer),
                "zero_stage re-runs the update per leaf shard; the "
                "optimizer must follow the per-leaf _init_slot/_update "
                f"protocol (AdamW-family). Got {type(optimizer).__name__}",
                op="build_train_step")
        enforce(not getattr(optimizer, "_skips_grad_sync", False),
                "LocalSGD/DGC own the dp axis — incompatible with "
                "zero_stage", op="build_train_step")
        zdims, sspec = zero_state_specs(optimizer, specs, example_params,
                                        mesh, dp_axis)
        if zero_stage >= 3:
            # params dp-sharded at rest: the loss gathers on use (model
            # builders thread the zero3 plan into their loss closures)
            pspecs = zero_param_specs(specs, zdims, example_params,
                                      dp_axis)
    else:
        sspec = state_specs_for(optimizer, specs, example_params)
    z3_ef = (zero3 or {}).get("ef") if zero3 is not None else None
    if z3_ef is not None:
        from ..enforce import enforce
        enforce(zero_stage == 3,
                "a zero3 EF plan (quantized param all-gather) requires "
                "zero_stage=3", op="build_train_step",
                zero_stage=zero_stage)

    # -- bucketed/overlapped dp gradient collectives -------------------------
    from ..distributed import comm_overlap as _co
    skips_dp = getattr(optimizer, "_skips_grad_sync", False)
    ocfg = _co.config_from_flags() if comm_overlap == "auto" else comm_overlap
    if ocfg is not None and skips_dp:
        # LocalSGD/DGC/GradientMerge(comm_fn=...) own the dp axis — there
        # is no per-step dp reduction here to bucket or quantize
        ocfg = None
    ef_plan = None
    if ocfg is not None and ocfg.quantize:
        from ..enforce import enforce
        enforce(not zero_stage,
                "comm_quantize=int8 is the replicated all-reduce path; "
                "the ZeRO stages reduce-scatter shards whose codes cannot "
                "share a bucket scale — disable one of the two",
                op="build_train_step")
        enforce(example_params is not None,
                "comm_quantize=int8 needs example_params (the "
                "error-feedback residual state is sized from the local "
                "gradient shapes at build time)", op="build_train_step")
        ef_plan = _co.ef_plan_for(example_params, specs, mesh,
                                  ocfg.bucket_bytes)
    if z3_ef is not None:
        from ..enforce import enforce
        enforce(ocfg is None,
                "zero3_quantize_ag threads ONE error-feedback residual "
                "slot per step; the comm_overlap scan calls the loss once "
                "per comm microbatch and would sum residuals — disable "
                "FLAGS_comm_* or FLAGS_zero3_quantize_ag",
                op="build_train_step")
        enforce(fp8 is None,
                "zero3_quantize_ag and fp8 delayed scaling both own the "
                "loss's 4th argument (residuals vs scales) — disable one "
                "of the two", op="build_train_step")
    fp8_plan = fp8
    if fp8_plan is not None:
        from ..enforce import enforce
        enforce(ocfg is None,
                "fp8 delayed scaling is not composed with comm_overlap: "
                "the overlap scan's weighted gradient accumulation would "
                "sum/scale the amax observations riding the scale "
                "cotangents — disable FLAGS_comm_* or fp8",
                op="build_train_step")
        from ..quantization import fp8 as _f8
        fp8_axes = tuple(a for a in fp8_plan.get("axes", ())
                         if a in mesh.axis_names)
    # -- mp-axis overlap metadata (the loss implements the path) -------------
    mp_mode = None
    if mp_overlap is not None:
        mp_mode = getattr(mp_overlap, "mode", str(mp_overlap))
        if fp8_plan is not None:
            from ..enforce import enforce
            enforce(mp_mode != "collective_matmul",
                    "ring collective-matmul is not composed with fp8 "
                    "delayed scaling: the per-chunk GEMMs would sum "
                    "partial amax observations — use seq_parallel with "
                    "fp8, or disable one of the two",
                    op="build_train_step")
    # -- expert parallelism (MoE plan from the model builder) ----------------
    moe_plan = moe
    ep_axis = None
    ep_n = 1
    if moe_plan is not None:
        from ..enforce import enforce
        ep_axis = moe_plan["ep_axis"]
        enforce(ep_axis in mesh.axis_names,
                f"the MoE plan names ep axis '{ep_axis}' which the mesh "
                "does not define", op="build_train_step",
                axes=tuple(mesh.axis_names))
        ep_n = int(mesh.shape[ep_axis])
        enforce(fp8_plan is None,
                "fp8 delayed scaling is not composed with the MoE plan "
                "(the expert scan's stacking differs from the fp8 scale "
                "threading) — disable one of the two",
                op="build_train_step")
        if moe_plan.get("ef") is not None:
            enforce(ocfg is None,
                    "moe_quantize_a2a threads ONE error-feedback "
                    "residual slot per step; the comm_overlap scan calls "
                    "the loss once per comm microbatch and would sum "
                    "residuals — disable FLAGS_comm_* or "
                    "FLAGS_moe_quantize_a2a", op="build_train_step")
            enforce(z3_ef is None,
                    "moe_quantize_a2a and zero3_quantize_ag both thread "
                    "their residuals as the loss's 4th argument — "
                    "disable one of the two", op="build_train_step")
    accumulate = loss_fn if isinstance(loss_fn, AccumulatedLoss) else None
    if (ocfg is not None or fp8_plan is not None or z3_ef is not None
            or (moe_plan is not None and moe_plan.get("ef") is not None)):
        accumulate = None   # these gradient paths call it whole (docstring)
    # the plain dp reduction (the EagerReducer equivalent: one pmean a
    # leaf, after the last backward). Self-synchronizing optimizers
    # (LocalSGD/DGC: _skips_grad_sync) own the dp axis but NOT the extra
    # axes (sep/context-parallel partial grads must always be combined:
    # skipping them would train on wrong gradients).
    dp_axes = () if skips_dp else (dp_axis,)
    extra_axes = tuple(extra_grad_axes)
    ranks = math.prod(int(mesh.shape[a]) for a in dp_axes + extra_axes)
    # an accumulating build divides by `ranks` as a microbatch's gradient
    # joins the sum (_accumulated_grads) and reduces with psum, where that
    # is pmean's result bit for bit: a scaling by 2**-k commutes with every
    # rounding as long as nothing underflows, so for ranks that are a power
    # of two and (mean_folds) dtypes of float32's exponent range. ZeRO-1/2
    # and an ep rescale divide on their own ways.
    folds = (accumulate is not None and int(accumulate.microbatches) > 1
             and not zero_stage and not (moe_plan is not None and ep_n > 1)
             and ranks > 1 and ranks & (ranks - 1) == 0)

    def mean_folds(dtype):
        """Whether a gradient of `dtype` was divided as it was summed: the
        gradient's own dtype and the wire's (float16's would lose up to
        log2(ranks) bits of a small g / ranks before the cast)."""
        on_the_way = [dtype] + ([grad_reduce_dtype] if dp_axes and
                                grad_reduce_dtype is not None else [])
        return folds and all(
            jnp.finfo(d).minexp <= jnp.finfo(jnp.float32).minexp
            for d in on_the_way)
    # -- in-program telemetry (observability) --------------------------------
    from .. import observability as _obs
    tcfg = _obs.telemetry_from_flags() if telemetry == "auto" else telemetry
    ncfg = numerics
    if ncfg is not None and tcfg is None:
        # numerics rides the telemetry carry: a numerics build with
        # telemetry resolved off gets a non-strict flag-interval config
        # (the whole point of FLAGS_numerics is one switch)
        from ..flags import flag as _flag
        tcfg = _obs.TelemetryConfig(
            interval=int(_flag("telemetry_interval")), strict=False)
    if tcfg is not None:
        # rewrite (never merge) the build metadata: a config reused for a
        # second build must not carry the previous engine's mesh/bucket
        # accounting into this run's JSONL header
        tcfg.static["mesh"] = {a: int(mesh.shape[a])
                               for a in mesh.axis_names}
        # attribution metadata for MERGED streams (fleet aggregation /
        # merge_event_streams): which process and which half of the
        # system this buffer's telemetry events came from
        from ..observability.events import default_host
        tcfg.static["host"] = default_host()
        tcfg.static["role"] = "trainer"
        for k in ("comm_buckets_bytes", "comm_quantize",
                  "comm_microbatches", "mp_mode", "moe", "flash",
                  "zero_stage", "zero3"):
            tcfg.static.pop(k, None)
        if zero_stage:
            tcfg.static["zero_stage"] = zero_stage
            if zero3 is not None:
                tcfg.static["zero3"] = dict(zero3.get("meta", {}))
        if mp_mode is not None:
            tcfg.static["mp_mode"] = mp_mode
        if moe_plan is not None:
            tcfg.static["moe"] = dict(moe_plan.get("meta", {}))
        if flash is not None:
            tcfg.static["flash"] = dict(flash.meta())
        if ocfg is not None and example_params is not None:
            # per-bucket wire bytes from the bucket plan over the LOCAL
            # grad shapes (the int8 path's residual plan IS this plan)
            plan = ef_plan if ef_plan is not None else _co.ef_plan_for(
                example_params, specs, mesh, ocfg.bucket_bytes)
            tcfg.static["comm_buckets_bytes"] = _obs.plan_wire_bytes(
                plan, wire_itemsize=1 if ocfg.quantize else None)
            tcfg.static["comm_quantize"] = ocfg.quantize or "none"
            tcfg.static["comm_microbatches"] = ocfg.microbatches
        tcfg.static.pop("numerics", None)

    # -- numerics: tensor-health series registered from the live plans -------
    layer_gather_ax = None   # mesh axis sharding the stacked layer dim
    z_noop_blocks = None     # all-replicated zdims stand-in (zero off)
    if ncfg is not None:
        from ..enforce import enforce
        from ..observability import numerics as _onum
        if ncfg.num_layers:
            enforce(example_params is not None
                    and isinstance(example_params, dict)
                    and ncfg.block_key in example_params,
                    "numerics per-layer series need example_params with "
                    f"the stacked '{ncfg.block_key}' subtree",
                    op="build_train_step")
            blocks_ex = example_params[ncfg.block_key]
            dims0 = {int(l.shape[0]) for l in jax.tree.leaves(blocks_ex)}
            enforce(dims0 == {int(ncfg.num_layers)},
                    "numerics num_layers must equal the stacked block "
                    "leaves' global dim 0", op="build_train_step",
                    num_layers=int(ncfg.num_layers), dims0=sorted(dims0))
            d0 = set()
            for sp_ in jax.tree.leaves(
                    specs[ncfg.block_key],
                    is_leaf=lambda x: isinstance(x, P)):
                d0.add(sp_[0] if len(sp_) else None)
            enforce(len(d0) == 1,
                    "per-layer grad norms need every stacked block leaf "
                    "to shard its layer dim the same way",
                    op="build_train_step", dim0_entries=sorted(map(str, d0)))
            layer_gather_ax = d0.pop()
            if layer_gather_ax is not None:
                enforce(isinstance(layer_gather_ax, str)
                        and layer_gather_ax in mesh.axis_names,
                        "the stacked layer dim's spec entry must be one "
                        "mesh axis", op="build_train_step",
                        entry=str(layer_gather_ax))
            z_noop_blocks = jax.tree.map(lambda _l: -1, blocks_ex)
        ef_ns = [ns for ns, on in (
            ("comm_ef", ef_plan is not None),
            ("moe_ef", moe_plan is not None
             and moe_plan.get("ef") is not None),
            ("zero3_ef", z3_ef is not None)) if on]
        fp8_sites = (tuple(fp8_plan["specs"]["scale"])
                     if fp8_plan is not None else ())
        nser = _onum.numerics_series(ncfg, ef_namespaces=ef_ns,
                                     fp8_sites=fp8_sites)
        # register in place (the moe-series discipline: a caller-owned
        # config decodes from the same object — build before the host)
        tcfg.extra = tcfg.extra + tuple(s for s in nser
                                        if s not in tcfg.extra)
        tcfg.static["numerics"] = ncfg.meta()

    # extra state riding the optimizer carry: the step signature and the
    # checkpoint surface stay (params, state, batch..., lr) no matter
    # which subset (EF residuals, fp8 meta, telemetry buffer) is on
    opt_sspec = sspec
    wrap_specs = {}
    if ef_plan is not None:
        wrap_specs["comm_ef"] = _co.ef_residual_specs(ef_plan, mesh)
    if fp8_plan is not None:
        wrap_specs["fp8_meta"] = fp8_plan["specs"]
    if moe_plan is not None and moe_plan.get("ef") is not None:
        wrap_specs["moe_ef"] = moe_plan["ef"]["specs"]
    if z3_ef is not None:
        wrap_specs["zero3_ef"] = z3_ef["specs"]
    if tcfg is not None:
        wrap_specs["telemetry"] = _obs.buffer_specs(tcfg)
    if wrap_specs:
        sspec = {"opt": opt_sspec, **wrap_specs}

    def shard_params(params):
        def put(v, s):
            out = jax.device_put(v, NamedSharding(mesh, s))
            # the step donates what this returns, and device_put hands
            # back the caller's own buffer where it can (an array that has
            # the sharding already; a replicated leaf's shard on the
            # device the array came from): such a leaf gets its own, so
            # the tree the caller passed stays the caller's
            if donate and _shares_buffer(out, v):
                out = jnp.copy(out)
            return out
        return jax.tree.map(put, params, pspecs)

    # Elastic-checkpoint hints (checkpoint.reshard): everything about this
    # build's topology that the saved arrays' shardings cannot express —
    # which carries ride the state and how to remap them on a mesh change,
    # the comm_ef bucket-plan fingerprint (residuals are LOCAL rounding
    # errors; a changed plan resets them with a JSONL event), zero1
    # on/off. Models add the "pp" stacked-block layout on top. Thread it
    # to run_resilient(layout_extra=init_state.layout_extra) /
    # commit_checkpoint so both the save and the resumed template agree.
    layout_extra: Dict[str, Any] = {"zero1": zero_stage >= 1,
                                    "zero_stage": int(zero_stage),
                                    "carries": {}}
    if ef_plan is not None:
        layout_extra["carries"]["comm_ef"] = "reset_on_mismatch"
        layout_extra["comm_plan"] = {
            "n_dev": int(mesh.devices.size),
            "buckets": [int(b.size) for b in ef_plan.buckets],
        }
    if fp8_plan is not None:
        layout_extra["carries"]["fp8_meta"] = "follow"
    if moe_plan is not None and moe_plan.get("ef") is not None:
        # a2a residuals are per-rank rounding errors of a mesh-shaped
        # exchange — any topology change invalidates them
        layout_extra["carries"]["moe_ef"] = "reset_on_mismatch"
    if z3_ef is not None:
        # AG-EF residuals are each dp rank's rounding error for ITS param
        # shard — any topology/stage change invalidates them
        layout_extra["carries"]["zero3_ef"] = "reset_on_mismatch"
    if tcfg is not None:
        layout_extra["carries"]["telemetry"] = "reinit"

    def init_state(params):
        # zeros_like under jit preserves input shardings; zero1 pins the
        # state to its dp-sharded specs instead (1/dp per-chip moments)
        inner = jax.jit(
            optimizer.init_state,
            out_shardings=jax.tree.map(
                lambda s: NamedSharding(mesh, s), opt_sspec))(params)
        extras = {}
        if ef_plan is not None:
            extras["comm_ef"] = _co.init_ef_residuals(ef_plan, mesh)
        if fp8_plan is not None:
            extras["fp8_meta"] = jax.tree.map(
                lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
                fp8_plan["init"](), fp8_plan["specs"])
        if moe_plan is not None and moe_plan.get("ef") is not None:
            extras["moe_ef"] = jax.tree.map(
                lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
                moe_plan["ef"]["init"](), moe_plan["ef"]["specs"])
        if z3_ef is not None:
            extras["zero3_ef"] = jax.tree.map(
                lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
                z3_ef["init"](), z3_ef["specs"])
        if tcfg is not None:
            extras["telemetry"] = jax.tree.map(
                lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
                _obs.init_buffer(tcfg), _obs.buffer_specs(tcfg))
        if extras:
            return {"opt": inner, **extras}
        return inner
    init_state.layout_extra = layout_extra

    def abstract_state(params_shape):
        """ShapeDtypeStruct tree of the full step-state carry (opt state +
        whatever extras this build threads) WITHOUT materializing any
        buffer — the AOT hook the auto-parallel planner's
        `jit(step).lower(...).compile().memory_analysis()` cross-check
        compiles against (hbm_audit.audit_plan_compile)."""
        inner = jax.eval_shape(optimizer.init_state, params_shape)
        extras = {}
        if ef_plan is not None:
            extras["comm_ef"] = jax.eval_shape(
                lambda: _co.init_ef_residuals(ef_plan, mesh))
        if fp8_plan is not None:
            extras["fp8_meta"] = jax.eval_shape(fp8_plan["init"])
        if moe_plan is not None and moe_plan.get("ef") is not None:
            extras["moe_ef"] = jax.eval_shape(moe_plan["ef"]["init"])
        if z3_ef is not None:
            extras["zero3_ef"] = jax.eval_shape(z3_ef["init"])
        if tcfg is not None:
            extras["telemetry"] = jax.eval_shape(
                lambda: _obs.init_buffer(tcfg))
        if extras:
            return {"opt": inner, **extras}
        return inner
    init_state.abstract = abstract_state
    init_state.state_specs = sspec
    init_state.param_specs = pspecs
    # the RESOLVED telemetry config (numerics may have auto-created or
    # extended it): flag-driven callers build their TelemetryHost /
    # NumericsGuard from this so host decode always matches the buffer
    init_state.telemetry_config = tcfg

    def _layer_gsq(red_blocks, spec_blocks, z_blocks):
        """Per-stacked-layer-index GLOBAL grad sq norms [L_global],
        replicated on every rank: each block leaf's per-layer local sum
        of squares divided by its replication factor (the global-norm
        clip's accounting), ONE psum over every non-layer mesh axis,
        then an all-gather over the layer-sharding axis so the telemetry
        row is rank-identical. Storage order (vpp chunk-major under the
        interleaved schedule; MoE sums the dense+moe pair per index)."""
        per = []

        def one(g, sp, zd):
            if g is not None:
                gf = g.astype(jnp.float32)
                per.append(jnp.sum(gf * gf,
                                   axis=tuple(range(1, gf.ndim)))
                           / _repl_factor(sp, zd, mesh, dp_axis))
            return g
        jax.tree.map(one, red_blocks, spec_blocks, z_blocks,
                     is_leaf=lambda x: x is None)
        if not per:
            return None
        acc = sum(per)
        other = tuple(a for a in mesh.axis_names if a != layer_gather_ax)
        if other:
            with jax.named_scope(SCOPES.coll_dp):
                acc = lax.psum(acc, other)
        if layer_gather_ax is not None:
            with jax.named_scope(SCOPES.coll_pp):
                acc = lax.all_gather(acc, layer_gather_ax, axis=0,
                                     tiled=True)
        return acc

    def _numerics_layer_tele(tele, red_tree, z_blocks):
        """Fold the per-layer grad series into a tele dict (no-op unless
        the numerics plan registered them)."""
        if (ncfg is not None and ncfg.num_layers
                and z_noop_blocks is not None
                and isinstance(red_tree, dict)
                and ncfg.block_key in red_tree):
            tele["layer_gsq"] = _layer_gsq(red_tree[ncfg.block_key],
                                           specs[ncfg.block_key],
                                           z_blocks)
        return tele

    @jax.named_scope(SCOPES.optimizer)
    def _zero_apply(params, grads, opt_state, lr, pre_reduced=False):
        """Per-leaf ZeRO update inside shard_map, all stages.

        Stages 1/2: reduce-scatter the leaf's grad over dp, update only
        this rank's param/state shard (dynamic-sliced from the
        replicated leaf), all-gather the new params.

        Stage 3: the resident leaf IS this rank's shard, and its grad
        arrived already dp-SUMMED and scattered (the loss's per-block
        all-gather transposes to psum_scatter in the backward) — pass 1
        only folds the 1/dp of the loss mean (+ any extra-axis pmean),
        and pass 2 updates the shard in place with NO dynamic slice and
        NO closing all-gather. Replicated leaves (no dp-shardable dim)
        keep pmean + full update under every stage.

        The per-leaf name/ctx/rng protocol comes from
        Optimizer._leaf_items (one implementation across every per-leaf
        loop). pre_reduced=True: grads arrived already scattered/averaged
        (the comm_overlap scan reduced them under backward) — skip
        pass 1's collectives.

        Returns (new_params, new_state, tele): tele is None unless
        telemetry is on, else the grad-norm/nonfinite series computed
        from the REDUCED (scattered) grads with the same replication
        accounting the global-norm clip uses."""
        from ..nn.clip import ClipGradByGlobalNorm, ClipGradByValue

        dp = mesh.shape[dp_axis]
        idx = lax.axis_index(dp_axis)
        step_no = opt_state["step"] + 1
        treedef, items = optimizer._leaf_items(
            params, grads, opt_state["slots"], step_no)
        leaves_z = treedef.flatten_up_to(zdims)
        leaves_spec = treedef.flatten_up_to(specs)

        # pass 1: reduce grads (scatter where dp-sharded)
        clip = optimizer._grad_clip
        if pre_reduced:
            red = [g for (_, g, _, _, _) in items]
        else:
            red = []
            for (p, g, s, ctx, rng), zd in zip(items, leaves_z):
                if g is None:
                    red.append(None)
                    continue
                if extra_grad_axes:
                    with jax.named_scope(SCOPES.coll_dp):
                        g = lax.pmean(g, tuple(extra_grad_axes))
                if zero_stage >= 3 and zd >= 0:
                    # the gather's AD transpose already psum_scattered
                    # this leaf (dp SUM at the shard) — only the loss
                    # mean's divisor remains
                    red.append((g / dp).astype(g.dtype))
                    continue
                gr = g.astype(grad_reduce_dtype) \
                    if grad_reduce_dtype is not None else g
                with jax.named_scope(SCOPES.coll_dp):
                    if zd < 0:
                        gm = lax.pmean(gr, dp_axis).astype(g.dtype)
                    else:
                        gm = (lax.psum_scatter(
                            gr, dp_axis, scatter_dimension=zd,
                            tiled=True) / dp).astype(g.dtype)
                red.append(gm)

        tele = None
        if tcfg is not None:
            tele = {
                "grad_sq": _global_sq_norm(red, leaves_spec, leaves_z,
                                           mesh, dp_axis),
                "nonfinite": _global_nonfinite_count(
                    red, leaves_spec, leaves_z, mesh, dp_axis),
            }
            if ncfg is not None and ncfg.num_layers:
                _numerics_layer_tele(
                    tele, jax.tree.unflatten(treedef, red),
                    zdims[ncfg.block_key])
            # wire accounting (trace-time constants): RS/pmean of the
            # grads (unless the overlap scan already counted them) + the
            # param all-gather that closes every stage-1/2 step. Stage-3
            # sharded leaves move their bytes inside the loss (the
            # per-block AG and its RS transpose) — the model deposits
            # those through observability.note_zero3_comm, so only the
            # replicated-leaf pmean is counted here.
            dpn = dp
            f = (dpn - 1) / dpn
            wire = (jnp.dtype(grad_reduce_dtype).itemsize
                    if grad_reduce_dtype is not None else None)
            rs_b = ag_b = 0.0
            for (p, g, s, ctx, rng), zd in zip(items, leaves_z):
                if g is None:
                    continue
                pb = float(p.size * jnp.dtype(p.dtype).itemsize)
                gb = float(p.size * (wire if wire is not None
                                     else jnp.dtype(p.dtype).itemsize))
                if zd < 0:
                    rs_b += 2 * f * gb   # pmean all-reduce
                elif zero_stage >= 3:
                    pass                 # counted by the model's deposit
                else:
                    rs_b += f * gb       # psum_scatter
                    ag_b += f * pb       # new-param all-gather
            if not pre_reduced and tele_comms["reduce"] is None:
                tele_comms["reduce"] = rs_b
            if tele_comms["zero1"] is None:
                tele_comms["zero1"] = ag_b

        scale = None
        if isinstance(clip, ClipGradByGlobalNorm):
            scale = _global_clip_scale(red, leaves_spec, leaves_z, mesh,
                                       dp_axis, clip)
        elif clip is not None and not isinstance(clip, ClipGradByValue):
            raise NotImplementedError(
                f"zero_stage supports global-norm/by-value clip, got "
                f"{type(clip).__name__}")

        # pass 2: per-leaf update on this rank's shard; stages 1/2 gather
        # the new params back, stage 3 keeps the resident shard
        new_p, new_s = [], []
        for (p, g_unused, s, ctx, rng), g, zd in zip(items, red, leaves_z):
            if g is None:
                new_p.append(p)
                new_s.append(s)
                continue
            if isinstance(clip, ClipGradByValue):
                g = jnp.clip(g, clip.min, clip.max).astype(g.dtype)
            if scale is not None:
                g = (g * scale).astype(g.dtype)
            if zd < 0:
                # replicated leaf: every dp rank MUST run the identical
                # update (same SR key included) or replicas drift
                np_, ns_ = optimizer._update_ctx(ctx, p, g, s, lr,
                                                 step_no, rng=rng)
            else:
                if rng is not None:
                    # dp-sharded leaf: each rank updates a DISTINCT param
                    # shard — fold the dp rank into the per-leaf SR key,
                    # else every shard gets the identical stochastic-
                    # rounding noise pattern (ADVICE r5; mp/pp shards of
                    # the per-leaf key remain correlated — accepted, the
                    # per-leaf protocol has no mesh knowledge there)
                    rng = jax.random.fold_in(rng, idx)
                if zero_stage >= 3:
                    # p IS the resident shard; the next step's loss
                    # re-gathers it on use
                    np_, ns_ = optimizer._update_ctx(ctx, p, g, s, lr,
                                                     step_no, rng=rng)
                else:
                    shard = p.shape[zd] // dp
                    p_sh = lax.dynamic_slice_in_dim(p, idx * shard, shard,
                                                    zd)
                    np_sh, ns_ = optimizer._update_ctx(ctx, p_sh, g, s, lr,
                                                       step_no, rng=rng)
                    with jax.named_scope(SCOPES.coll_dp):
                        np_ = lax.all_gather(np_sh, dp_axis, axis=zd,
                                             tiled=True)
            new_p.append(np_)
            new_s.append(ns_)
        return (jax.tree.unflatten(treedef, new_p),
                {"step": step_no,
                 "slots": jax.tree.unflatten(treedef, new_s)},
                tele)

    def _ep_sync(grads):
        """MoE ep-axis gradient combine (spec-aware): expert leaves
        (PartitionSpec carries the ep axis) already hold the COMPLETE
        sum of the ep group's token contributions — the transposed
        all-to-all delivered every visiting token's cotangent — so they
        only rescale by 1/ep (the pmean's divisor without its psum);
        every other leaf is replicated over ep and its local-shard grad
        is PARTIAL -> pmean. Runs BEFORE the dp sync in every grad path
        (monolithic / overlap scan / zero1)."""
        if moe_plan is None or ep_n <= 1:
            return grads

        def one(g, sp):
            if ep_axis in _spec_axes(sp):
                return (g / ep_n).astype(g.dtype)
            return lax.pmean(g, ep_axis)

        if tcfg is not None and tele_comms["ep"] is None:
            td = jax.tree.structure(grads)
            f = 2.0 * (ep_n - 1) / ep_n
            mult = ocfg.microbatches if ocfg is not None else 1
            tele_comms["ep"] = mult * sum(
                f * g.size * jnp.dtype(g.dtype).itemsize
                for g, sp in zip(td.flatten_up_to(grads),
                                 td.flatten_up_to(specs))
                if ep_axis not in _spec_axes(sp))
        return jax.tree.map(one, grads, specs)

    def _accumulated_grads(params, tokens, labels):
        """(loss, grads, obs) of the AccumulatedLoss: value-and-grad one
        microbatch inside lax.scan, sums on the carry (observe() series
        are averaged over the microbatches, as the overlap scan does).
        The gradients are summed by the backward itself, each where it is
        made (a layer's inside the share's layer scan: below), so what
        value_and_grad returns is the new carry and no pass over the
        gradients follows a microbatch."""
        from ..enforce import enforce
        M = int(accumulate.microbatches)
        b = tokens.shape[0]
        enforce(M >= 1 and b % M == 0,
                "per-dp-rank batch must be divisible by num_microbatches",
                op="build_train_step", batch_local=b, microbatches=M)
        denom = accumulate.denom(labels)

        def share(p, t, l, layers=scan_layers):
            if tcfg is None:
                return accumulate.share(p, t, l, denom, layers), {}
            with _obs.collecting() as sink:
                s = accumulate.share(p, t, l, denom, layers)
            return s, _obs.metrics.obs_dict(sink)
        if M == 1:
            (loss, obs), grads = jax.value_and_grad(share, has_aux=True)(
                params, tokens, labels)
            return accumulate.reported(loss), grads, obs
        mbs = tuple(a.reshape((M, b // M) + a.shape[1:])
                    for a in (tokens, labels))
        # the carry: the share's (loss, obs) from an ABSTRACT forward (no
        # first microbatch is peeled: the fwd/bwd body compiles once), the
        # gradients in the parameters' own structure and dtype
        zeros = jax.tree.map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype),
            (jax.eval_shape(share, params, *(a[0] for a in mbs)), params))

        def join(acc, ct):
            if mean_folds(ct.dtype):   # the mean's division (`folds`)
                ct = ct * jnp.asarray(1 / ranks, ct.dtype)
            return acc + ct
        # an unstacked leaf: identity, whose backward adds the carry's
        joined = jax.custom_vjp(lambda p, acc: p)
        joined.defvjp(lambda p, acc: (p, acc), lambda acc, ct: (
            jax.tree.map(join, acc, ct), None))
        # the stack: its sum rides the BACKWARD layer scan's carry, so a
        # layer's slice is updated where it lies (scanned in as xs and out
        # as ys the compiler copies the stack, a pass a microbatch). The
        # forward scan carries the sum `acc` through `layer_of`, identity;
        # `seeded` makes acc itself the cotangent the backward starts
        # from, layer_of's backward adds layer i's gradient to slice i, and
        # what arrives as "d loss / d acc" is the new sum
        def add_layer(i, cts):
            ct_p, ct_acc = cts
            return None, jax.tree.map(
                lambda a, ct: lax.dynamic_update_index_in_dim(
                    a, join(lax.dynamic_index_in_dim(a, i, keepdims=False),
                            ct), i, 0), ct_acc, ct_p), None
        layer_of = jax.custom_vjp(lambda p, acc, i: (p, acc))
        layer_of.defvjp(lambda p, acc, i: ((p, acc), i), add_layer)
        seeded = jax.custom_vjp(lambda x, acc: x)
        seeded.defvjp(lambda x, acc: (x, acc), lambda acc, ct: (ct, acc))
        rest = lambda tree: {k: v for k, v in tree.items() if k != STACKED}
        seen = []   # the share did run its stack through `layers`

        def body(carry, mb):
            sums, acc = carry

            def summed_share(p, stack_acc):
                def layers(block, x, stack):
                    seen.append(True)

                    def layer(c, xs):
                        p_i, a = layer_of(xs[1], c[1], xs[0])
                        return (block(p_i, c[0]), a), None
                    n = jax.tree.leaves(stack)[0].shape[0]
                    return seeded(*lax.scan(
                        layer, (x, stack_acc), (jnp.arange(n), stack))[0])
                return share({**joined(p, rest(acc)),
                              STACKED: params[STACKED]}, *mb, layers)
            out, (g, g_stack) = jax.value_and_grad(
                summed_share, argnums=(0, 1), has_aux=True)(
                    rest(params), acc[STACKED])
            return (jax.tree.map(jnp.add, sums, out),
                    {**g, STACKED: g_stack}), None
        ((loss, obs), grads), _ = lax.scan(body, zeros, mbs)
        enforce(seen, "an AccumulatedLoss's share runs its stacked layers "
                "through the `layers` it is given: that is where a "
                "microbatch's gradient joins the sum",
                op="build_train_step", stacked=STACKED)
        return (accumulate.reported(loss), grads,
                jax.tree.map(lambda o: o / M, obs))

    def _overlap_bytes(g_leaves, z_leaves, wire_dtype):
        """Trace-time dp wire bytes of ONE microbatch's overlap reduction
        (ring accounting, same tables as fleet.collective_perf)."""
        dpn = mesh.shape[dp_axis]
        f = (dpn - 1) / dpn
        total = 0.0
        for g, zd in zip(g_leaves, z_leaves):
            if g is None:
                continue
            if zero_stage >= 3 and zd >= 0:
                # stage-3 sharded leaves reduce inside the loss's AD
                # (counted by the model's note_zero3_comm deposit)
                continue
            if ocfg.quantize:
                b = float(g.size)  # int8 codes on the wire
            else:
                wd = wire_dtype if wire_dtype is not None else g.dtype
                b = float(g.size * jnp.dtype(wd).itemsize)
            total += (f if (zero_stage >= 1 and zd >= 0) else 2 * f) * b
        return total

    def _overlap_grads(params, tokens, labels, residuals):
        """Bucketed/overlapped dp gradient path: grads come back already
        dp-REDUCED (and scattered under zero1), with each microbatch's
        per-bucket collectives issued inside the accumulation scan; with
        telemetry on, observe() series collected under the loss ride out
        as a 4th element."""
        dp = mesh.shape[dp_axis]
        extra_axes = tuple(extra_grad_axes)
        weight = 1.0 / ocfg.microbatches
        # config's own wire dtype wins; fall back to the engine-level
        # grad_reduce_dtype (fleet fp16_allreduce) when unset
        wire_dtype = (ocfg.reduce_dtype if ocfg.reduce_dtype is not None
                      else grad_reduce_dtype)

        def reduce_fn(g, res):
            g = _ep_sync(g)
            if extra_axes:
                # sep/context-parallel partial grads combine in their own
                # dtype, exactly as the monolithic path does
                with jax.named_scope(SCOPES.coll_dp):
                    g = jax.tree.map(lambda x: lax.pmean(x, extra_axes), g)
            if tcfg is not None and tele_comms["reduce"] is None:
                # idempotent: the scan body may trace twice (eval_shape)
                z_leaves = (jax.tree.structure(g).flatten_up_to(zdims)
                            if zero_stage else
                            [-1] * len(jax.tree.leaves(g)))
                tele_comms["reduce"] = ocfg.microbatches * _overlap_bytes(
                    jax.tree.leaves(g), z_leaves, wire_dtype)
            if zero_stage >= 3:
                # sharded leaves arrived dp-SUMMED at the shard (gather
                # transpose) — scale by the microbatch weight / dp; only
                # the replicated leaves still need a collective
                def z3_one(g_, zd):
                    if g_ is None:
                        return None
                    if zd >= 0:
                        return (g_ * jnp.asarray(weight / dp, g_.dtype)
                                ).astype(g_.dtype)
                    gr = (g_.astype(wire_dtype) if wire_dtype is not None
                          else g_)
                    gr = gr * jnp.asarray(weight, gr.dtype)
                    with jax.named_scope(SCOPES.coll_dp):
                        return lax.pmean(gr, dp_axis).astype(g_.dtype)
                return jax.tree.map(z3_one, g, zdims,
                                    is_leaf=lambda x: x is None), res
            if zero_stage:
                red = _co.reduce_scatter_tree(
                    g, zdims, dp_axis, axis_size=dp,
                    reduce_dtype=wire_dtype, weight=weight)
                return red, res
            return _co.reduce_bucketed(
                g, dp_axis, axis_size=dp, plan=ef_plan,
                bucket_bytes=ocfg.bucket_bytes, quantize=ocfg.quantize,
                residuals=res,
                reduce_dtype=(None if ocfg.quantize else wire_dtype),
                weight=weight)

        out = _co.microbatched_reduced_grads(
            lambda p, t, l: loss_fn(p, t, l), params, (tokens, labels),
            ocfg.microbatches, reduce_fn, residuals=residuals,
            with_obs=tcfg is not None)
        return out if tcfg is not None else out + ({},)

    def local_step(params, opt_state, tokens, labels, lr):
        # trace-time mp wire-byte collection: the model's loss deposits
        # its analytic per-step bytes via observability.note_mp_comm
        # while it traces; pure Python — zero HLO impact
        with _obs.mp_comm_scope() as mp_cell:
            return _local_step(mp_cell, params, opt_state, tokens, labels,
                               lr)

    def _local_step(mp_cell, params, opt_state, tokens, labels, lr):
        ef = fmeta = tbuf = mef = zef = None
        if wrap_specs:
            ef = opt_state.get("comm_ef")
            fmeta = opt_state.get("fp8_meta")
            mef = opt_state.get("moe_ef")
            zef = opt_state.get("zero3_ef")
            tbuf = opt_state.get("telemetry")
            opt_state = opt_state["opt"]

        def tele_of(grads):
            """grad-norm/nonfinite for the non-zero1 paths: grads are the
            dp-SYNCHRONIZED tree here (after pmean / the overlap scan),
            PRE-clip — the replication accounting matches the global-norm
            clip's. (Self-synchronizing optimizers' unreduced grads yield
            the dp-average of the local norms — a diagnostic, not the
            norm of a synced gradient.)"""
            treedef = jax.tree.structure(params)
            lg = treedef.flatten_up_to(grads)
            lsp = treedef.flatten_up_to(specs)
            lz = [-1] * len(lg)
            tele = {"grad_sq": _global_sq_norm(lg, lsp, lz, mesh, dp_axis),
                    "nonfinite": _global_nonfinite_count(lg, lsp, lz, mesh,
                                                         dp_axis)}
            return _numerics_layer_tele(tele, grads, z_noop_blocks)

        def rewrap(new_params, new_state, new_ef, new_fmeta, loss, *,
                   tele=None, amax=None, obs=None):
            """Common exit: fold this step's telemetry row into the ring
            buffer, then re-attach the extra carries."""
            new_tbuf = tbuf
            if tcfg is not None:
                vals = dict(obs or {})
                vals["loss"] = loss
                vals["grad_norm"] = jnp.sqrt(tele["grad_sq"])
                vals["nonfinite_count"] = tele["nonfinite"]
                if ncfg is not None:
                    lg = tele.get("layer_gsq")
                    if lg is not None:
                        for i in range(int(ncfg.num_layers)):
                            vals[f"num_gnorm_l{i}"] = jnp.sqrt(lg[i])
                    # EF residual norms: forward-side carry health, the
                    # same replication accounting as the grad norm
                    from ..distributed.comm_overlap.quantize import \
                        residual_sq_norm
                    for ns, tree in (("comm_ef", new_ef), ("moe_ef", mef),
                                     ("zero3_ef", zef)):
                        if ns in wrap_specs and tree is not None:
                            vals[_obs.numerics.EF_SERIES[ns]] = jnp.sqrt(
                                residual_sq_norm(tree, wrap_specs[ns],
                                                 mesh))
                # mp/ep a2a bytes are per loss CALL — the overlap scan
                # calls the loss once per comm microbatch on the split
                # batch
                mp_calls = (ocfg.microbatches if ocfg is not None
                            else accumulate.microbatches
                            if accumulate is not None else 1)
                vals["comms_bytes"] = ((tele_comms["reduce"] or 0.0)
                                       + (tele_comms["zero1"] or 0.0)
                                       + (tele_comms["ep"] or 0.0)
                                       + mp_calls
                                       * (mp_cell.get("wire_bytes", 0.0)
                                          + mp_cell.get("ep_bytes", 0.0)
                                          + mp_cell.get("zero3_bytes",
                                                        0.0)))
                if fp8_plan is not None and amax is not None:
                    vals["fp8_amax_max"] = jnp.stack(
                        [jnp.max(a) for a in jax.tree.leaves(amax)]).max()
                    vals["fp8_scale_max"] = jnp.stack(
                        [jnp.max(s) for s in
                         jax.tree.leaves(_f8.scales_of(new_fmeta))]).max()
                new_tbuf = _obs.update_buffer(tbuf, tcfg, vals)
            if wrap_specs:
                w = {"opt": new_state}
                if ef_plan is not None:
                    w["comm_ef"] = new_ef
                if fp8_plan is not None:
                    w["fp8_meta"] = new_fmeta
                if moe_plan is not None and moe_plan.get("ef") is not None:
                    # reads the enclosing `mef`, which the moe-ef branch
                    # rebinds to the loss's new residuals before exiting
                    w["moe_ef"] = mef
                if z3_ef is not None:
                    # same discipline: the zero3-ef branch rebinds `zef`
                    # to the loss's refreshed AG residuals
                    w["zero3_ef"] = zef
                if tcfg is not None:
                    w["telemetry"] = new_tbuf
                new_state = w
            return new_params, new_state, loss

        obs = {}
        amax = None
        if ocfg is not None:
            loss, grads, ef, obs = _overlap_grads(params, tokens, labels,
                                                  ef)
            if zero_stage:
                new_params, new_state, z1t = _zero_apply(
                    params, grads, opt_state, lr, pre_reduced=True)
                return rewrap(new_params, new_state, ef, fmeta, loss,
                              tele=z1t, obs=obs)
        elif fp8_plan is not None:
            # grads over (params, scales): the scale cotangents ARE the
            # amax observations (quantization.fp8), pmax'd over the axes
            # scales are replicated on so every rank derives identical
            # next-step scales from the global amax
            fp8_loss = lambda p, s: loss_fn(p, tokens, labels, s)
            if tcfg is not None:
                def fp8_loss_obs(p, s):
                    with _obs.collecting() as sink:
                        l = fp8_loss(p, s)
                    return l, _obs.metrics.obs_dict(sink)
                (loss, obs), (grads, amax) = jax.value_and_grad(
                    fp8_loss_obs, argnums=(0, 1), has_aux=True)(
                        params, _f8.scales_of(fmeta))
            else:
                loss, (grads, amax) = jax.value_and_grad(
                    fp8_loss, argnums=(0, 1))(params, _f8.scales_of(fmeta))
            if fp8_axes:
                amax = jax.tree.map(lambda a: lax.pmax(a, fp8_axes), amax)
            if tcfg is not None and ncfg is not None:
                # scale health vs the delayed scales this step USED
                # (pre-rotation) — saturation > 1 means the cast
                # clipped; pmax over EVERY mesh axis (the stacked pp
                # axis included — amax itself never reduces over it, so
                # each rank's local max only covers its own layers and
                # the replicated row must still be rank-identical)
                obs = dict(obs)
                obs.update(_obs.numerics.fp8_site_health(
                    amax, _f8.scales_of(fmeta),
                    axes=tuple(mesh.axis_names)))
            fmeta = _f8.update_fp8_meta(fmeta, amax)
            if zero_stage:
                new_params, new_state, z1t = _zero_apply(params, grads,
                                                         opt_state, lr)
                return rewrap(new_params, new_state, ef, fmeta, loss,
                              tele=z1t, amax=amax, obs=obs)
        elif moe_plan is not None and moe_plan.get("ef") is not None:
            # quantized-a2a MoE: the residuals ride in as a loss arg and
            # the refreshed residuals ride out as an aux output — the
            # fp8_meta discipline with aux instead of cotangents (the
            # residual is a forward-side value, not a gradient)
            mef_loss = lambda p: loss_fn(p, tokens, labels, mef)
            if tcfg is not None:
                def mef_loss_obs(p):
                    with _obs.collecting() as sink:
                        l, nef = mef_loss(p)
                    return l, (nef, _obs.metrics.obs_dict(sink))
                (loss, (new_mef, obs)), grads = jax.value_and_grad(
                    mef_loss_obs, has_aux=True)(params)
            else:
                (loss, new_mef), grads = jax.value_and_grad(
                    mef_loss, has_aux=True)(params)
            mef = new_mef
            grads = _ep_sync(grads)
            if zero_stage:
                new_params, new_state, z1t = _zero_apply(params, grads,
                                                         opt_state, lr)
                return rewrap(new_params, new_state, ef, fmeta, loss,
                              tele=z1t, obs=obs)
        elif z3_ef is not None:
            # int8-EF quantized zero3 param all-gather: the residuals
            # ride in as a loss arg and the refreshed residuals ride out
            # as an aux output — the moe_ef discipline (the residual is
            # a forward-side value, not a gradient)
            zef_loss = lambda p: loss_fn(p, tokens, labels, zef)
            if tcfg is not None:
                def zef_loss_obs(p):
                    with _obs.collecting() as sink:
                        l, nzef = zef_loss(p)
                    return l, (nzef, _obs.metrics.obs_dict(sink))
                (loss, (new_zef, obs)), grads = jax.value_and_grad(
                    zef_loss_obs, has_aux=True)(params)
            else:
                (loss, new_zef), grads = jax.value_and_grad(
                    zef_loss, has_aux=True)(params)
            zef = new_zef
            grads = _ep_sync(grads)
            # z3_ef implies zero_stage == 3 (enforced at build)
            new_params, new_state, z1t = _zero_apply(params, grads,
                                                     opt_state, lr)
            return rewrap(new_params, new_state, ef, fmeta, loss,
                          tele=z1t, obs=obs)
        else:
            plain_loss = lambda p: loss_fn(p, tokens, labels)
            if accumulate is not None:   # one pipeline stage: no loss_fn
                loss, grads, obs = _accumulated_grads(params, tokens,
                                                      labels)
            elif tcfg is not None:
                def plain_loss_obs(p):
                    with _obs.collecting() as sink:
                        l = plain_loss(p)
                    return l, _obs.metrics.obs_dict(sink)
                (loss, obs), grads = jax.value_and_grad(
                    plain_loss_obs, has_aux=True)(params)
            else:
                loss, grads = jax.value_and_grad(plain_loss)(params)
            grads = _ep_sync(grads)
            if zero_stage:
                new_params, new_state, z1t = _zero_apply(params, grads,
                                                         opt_state, lr)
                return rewrap(new_params, new_state, ef, fmeta, loss,
                              tele=z1t, obs=obs)
        # dp gradient reduction: one pmean a leaf (dp_axes, extra_axes and
        # `folds`, above: a psum of what was divided as it was summed)
        if ocfg is None and (dp_axes or extra_axes):
            def reduce_one(g):
                # extra axes (sep/context-parallel) combine genuinely
                # PARTIAL gradients — always in the grad's own dtype; the
                # reduced-dtype compression applies only to the dp
                # all-reduce of identical replicas, matching the reference
                # fp16_allreduce scope (dp grad allreduce only).
                mean = lax.psum if mean_folds(g.dtype) else lax.pmean
                with jax.named_scope(SCOPES.coll_dp):
                    if extra_axes:
                        g = mean(g, extra_axes)
                    if dp_axes:
                        if grad_reduce_dtype is not None:
                            return mean(g.astype(grad_reduce_dtype),
                                        dp_axes).astype(g.dtype)
                        return mean(g, dp_axes)
                return g

            grads = jax.tree.map(reduce_one, grads)
            if tcfg is not None and dp_axes and tele_comms["reduce"] is None:
                # monolithic dp all-reduce wire bytes (trace-time const)
                dpn = mesh.shape[dp_axis]
                f = 2 * (dpn - 1) / dpn
                wire = (jnp.dtype(grad_reduce_dtype).itemsize
                        if grad_reduce_dtype is not None else None)
                tele_comms["reduce"] = sum(
                    f * g.size * (wire if wire is not None
                                  else jnp.dtype(g.dtype).itemsize)
                    for g in jax.tree.leaves(grads))
        tele = tele_of(grads) if tcfg is not None else None
        # Norm-based clips under shard_map must see norms of WHOLE
        # tensors: the optimizer's own _grad_clip would compute each
        # mp/pp rank's norm from its local shard and scale shards of the
        # same tensor by DIFFERENT factors. Global-norm clip gets the
        # axes-aware coefficient here; per-tensor ClipGradByNorm has no
        # cheap sharded form and is refused when model axes exist.
        from ..nn.clip import ClipGradByGlobalNorm, ClipGradByNorm
        clip, _ = _effective_clip(optimizer)
        model_axes = any(mesh.shape[a] > 1 for a in mesh.axis_names
                         if a != dp_axis and a not in extra_axes)
        if isinstance(clip, ClipGradByNorm) and model_axes:
            raise NotImplementedError(
                "ClipGradByNorm computes PER-TENSOR norms; under mp/pp "
                "sharding each rank would clip its shard with a different "
                "coefficient. Use ClipGradByGlobalNorm (axes-aware here) "
                "or clip-by-value.")
        if isinstance(clip, ClipGradByGlobalNorm) and model_axes:
            # (on a dp-only mesh the local grads ARE the full tensors, so
            # the optimizer's own clip is already globally correct — no
            # interception, exact legacy semantics incl. GradientMerge's
            # clip-on-the-MERGED-grad timing)
            if skips_dp:
                raise NotImplementedError(
                    "LocalSGD/DGC run on local (unreduced) gradients; a "
                    "global-norm clip across their dp-desynced grads is "
                    "ill-defined. Clip inside the inner optimizer on a "
                    "1-model-axis mesh, or drop the clip.")
            from ..distributed.sharding.group_sharded import \
                _leaf_streamable
            if not _leaf_streamable(optimizer):
                # GradientMerge-style wrappers clip the MERGED gradient
                # inside their own apply — pre-scaling per micro-step here
                # would change that semantic, and their internal clip
                # would compute rank-local norms. Refuse rather than
                # silently do either wrong thing.
                raise NotImplementedError(
                    f"{type(optimizer).__name__} applies global-norm clip "
                    "inside its own accumulation schedule; on a "
                    "model-parallel mesh that clip would be rank-local. "
                    "Use zero1_dp/plain AdamW-family clip, or merge on a "
                    "dp-only mesh.")
            treedef = jax.tree.structure(params)
            leaves_g = treedef.flatten_up_to(grads)
            leaves_spec = treedef.flatten_up_to(specs)
            scale = _global_clip_scale(leaves_g, leaves_spec,
                                       [-1] * len(leaves_g), mesh,
                                       dp_axis, clip)
            grads = jax.tree.map(
                lambda g: (g * scale).astype(g.dtype), grads)
            # per-leaf protocol never applies _grad_clip (clip lives in
            # apply()), so run it directly. NOTE: this also routes
            # use_multi_tensor=True through the per-leaf loop — fused
            # multi-tensor Adam ships default-off, so clip+mp/pp configs
            # simply get the default path.
            step_no = opt_state["step"] + 1
            with jax.named_scope(SCOPES.optimizer):
                new_p, new_slots = optimizer._apply_leaves(
                    params, grads, opt_state["slots"], lr, step_no)
            return rewrap(new_p, {"step": step_no, "slots": new_slots},
                          ef, fmeta, loss, tele=tele, amax=amax, obs=obs)
        new_params, new_state = optimizer.apply(params, grads, opt_state, lr)
        return rewrap(new_params, new_state, ef, fmeta, loss, tele=tele,
                      amax=amax, obs=obs)

    # trace-time dp wire-byte accounting cells (telemetry comms_bytes):
    # "reduce" is set once by whichever grad-sync path traces (monolithic
    # pmean / overlap scan / zero1 pass 1), "zero1" by the param
    # all-gather; a retrace re-derives identical values (grad shapes do
    # not depend on the batch), so the idempotent set is safe
    tele_comms = {"reduce": None, "zero1": None, "ep": None}
    step = _shard_map(
        local_step, mesh=mesh,
        in_specs=(pspecs, sspec, data_spec, data_spec, P()),
        out_specs=(pspecs, sspec, P()))
    return (jax.jit(step, donate_argnums=(0, 1) if donate else ()),
            shard_params, init_state)
