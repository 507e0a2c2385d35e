"""Distributed program passes (reference:
python/paddle/distributed/passes/ — pass_base.py new_pass/PassContext and
the auto_parallel_* pass family: amp, recompute, sharding, gradient_merge,
pipeline_scheduler_pass/{pipeline_1f1b,pipeline_fthenb,pipeline_vpp}).

TPU design: the reference's passes rewrite a static ProgramDesc op-by-op.
Here the "program" is a TrainSpec — the declarative inputs to
models.hybrid_engine.build_train_step — and each pass is a REAL transform
on it (wrap the loss in autocast/remat, wrap the optimizer in gradient
merge, select the pipeline schedule); XLA then owns the op-level rewrites
the reference does by hand.
"""

from __future__ import annotations
from ...enforce import (InvalidArgumentError,
                        PreconditionNotMetError, enforce,
                        enforce_in)

import dataclasses
from typing import Any, Callable, Dict, List, Optional

__all__ = ["TrainSpec", "PassBase", "PassContext", "new_pass",
           "apply_passes", "list_passes", "build_train_step"]


@dataclasses.dataclass
class TrainSpec:
    """Declarative training program (the pass IR).

    Either give a static `loss_fn` (already embedding its microbatching /
    pipeline schedule), or a `loss_fn_factory(spec) -> loss_fn` so the
    pipeline passes (schedule/virtual_pp/num_microbatches) take effect at
    build time — the model families' hybrid_loss_fn maps onto a factory
    directly."""

    loss_fn: Optional[Callable] = None   # (params, tokens, labels) -> scalar
    optimizer: Any = None
    param_specs: Any = None              # PartitionSpec tree
    mesh: Any = None
    num_microbatches: int = 1
    schedule: str = "1F1B"               # 1F1B | FThenB | VPP | ZBH1
    virtual_pp: int = 1
    loss_fn_factory: Optional[Callable] = None
    applied: tuple = ()

    def resolved_loss_fn(self) -> Callable:
        if self.loss_fn_factory is not None:
            return self.loss_fn_factory(self)
        # FThenB compiles identically to 1F1B (the scan IS fill-then-
        # drain), so a static loss_fn stays valid for it
        if (self.schedule not in ("1F1B", "FThenB") or self.virtual_pp != 1
                or self.num_microbatches != 1):
            raise InvalidArgumentError(
                "schedule/virtual_pp/num_microbatches are set but loss_fn "
                "is static — pass loss_fn_factory so pipeline passes can "
                "take effect (a bare loss_fn cannot be re-scheduled)")
        enforce(self.loss_fn is not None, "TrainSpec needs a loss_fn",
                op="TrainSpec", error=PreconditionNotMetError)
        return self.loss_fn

    def build(self, **kw):
        """Compile via the hybrid engine (passes must run first)."""
        from ...models.hybrid_engine import build_train_step
        return build_train_step(self.resolved_loss_fn(), self.param_specs,
                                self.mesh, self.optimizer, **kw)


class PassContext:
    def __init__(self):
        self._applied: List[str] = []

    def record(self, name: str):
        self._applied.append(name)

    @property
    def passes(self):
        return list(self._applied)


class PassBase:
    name = "base"

    def __init__(self, attrs: Optional[Dict] = None):
        self.attrs = dict(attrs or {})

    def check(self, spec: TrainSpec) -> bool:
        return True

    def apply(self, spec: TrainSpec, context: Optional[PassContext] = None
              ) -> TrainSpec:
        enforce(self.check(spec),
                f"pass {self.name}: precondition failed", op=self.name,
                error=PreconditionNotMetError)
        out = self._apply_impl(spec)
        # replace, never mutate: an impl may legitimately return its input
        out = dataclasses.replace(out, applied=spec.applied + (self.name,))
        if context is not None:
            context.record(self.name)
        return out

    def _apply_impl(self, spec: TrainSpec) -> TrainSpec:
        raise NotImplementedError


def _wrap_loss(spec: TrainSpec, wrapper: Callable) -> TrainSpec:
    """Apply a loss-transform through whichever form the spec carries."""
    enforce(spec.loss_fn is not None or spec.loss_fn_factory is not None,
            "TrainSpec needs a loss_fn or loss_fn_factory before loss "
            "passes", error=PreconditionNotMetError, op="apply_passes")
    if spec.loss_fn_factory is not None:
        inner_factory = spec.loss_fn_factory
        return dataclasses.replace(
            spec, loss_fn_factory=lambda s: wrapper(inner_factory(s)))
    return dataclasses.replace(spec, loss_fn=wrapper(spec.loss_fn))


class AMPPass(PassBase):
    """reference: auto_parallel_amp.py / auto_parallel_fp16.py — cast the
    compute into bf16/fp16 around the loss."""

    name = "auto_parallel_amp"

    def _apply_impl(self, spec):
        if self.name in spec.applied:  # idempotent: one autocast wrap
            return spec
        from ...amp import auto_cast
        level = self.attrs.get("level", "O1")
        dtype = self.attrs.get("dtype", "bfloat16")

        def wrap(inner):
            def amp_loss(params, tokens, labels):
                with auto_cast(True, level=level, dtype=dtype):
                    return inner(params, tokens, labels)
            return amp_loss

        return _wrap_loss(spec, wrap)


class RecomputePass(PassBase):
    """reference: auto_parallel_recompute.py — rematerialize the forward in
    backward. Whole-loss jax.checkpoint here; per-block remat already lives
    inside the model families' stage functions."""

    name = "auto_parallel_recompute"

    def _apply_impl(self, spec):
        if self.name in spec.applied:  # nesting checkpoint only re-runs
            return spec                # the forward redundantly
        import jax
        policy = self.attrs.get("policy")
        kw = {"policy": policy} if policy is not None else {}
        return _wrap_loss(spec, lambda inner: jax.checkpoint(inner, **kw))


class GradientMergePass(PassBase):
    """reference: auto_parallel_gradient_merge.py."""

    name = "auto_parallel_gradient_merge"

    def check(self, spec):
        return self.attrs.get("k_steps", 1) >= 1

    def _apply_impl(self, spec):
        from ...optimizer import GradientMergeOptimizer
        k = self.attrs.get("k_steps", 1)
        avg = self.attrs.get("avg", True)
        if isinstance(spec.optimizer, GradientMergeOptimizer):
            # re-application RECONFIGURES (never nests — k would compound)
            inner = spec.optimizer._inner
            if k <= 1:
                return dataclasses.replace(spec, optimizer=inner)
            return dataclasses.replace(
                spec, optimizer=GradientMergeOptimizer(inner, k_steps=k,
                                                       avg=avg))
        if k <= 1:
            return spec
        return dataclasses.replace(
            spec, optimizer=GradientMergeOptimizer(spec.optimizer, k_steps=k,
                                                   avg=avg))


class ShardingPass(PassBase):
    """reference: auto_parallel_sharding.py — ZeRO stages. Under GSPMD the
    optimizer-state sharding IS the param-spec tree; this pass re-annotates
    the specs so state (and for stage>=3, params) shard over the axis."""

    name = "auto_parallel_sharding"

    def _apply_impl(self, spec):
        import jax
        from jax.sharding import PartitionSpec as P
        axis = self.attrs.get("axis", "sharding")
        stage = self.attrs.get("stage", 1)
        if stage < 3 or spec.param_specs is None:
            # stages 1/2: state sharding follows the (unchanged) specs via
            # state_specs_for; nothing to rewrite in the spec tree
            return dataclasses.replace(spec)

        import warnings

        # shape-aware when example params are provided (the safe path:
        # group_sharded.shard_spec_for picks a divisible dim); spec-only
        # otherwise, touching ONLY explicit None dims
        example = self.attrs.get("example_params")
        axis_size = (spec.mesh.shape[axis]
                     if spec.mesh is not None and axis in getattr(
                         spec.mesh, "shape", {}) else None)

        def shard_first_free(s, leaf=None):
            if not isinstance(s, P):
                return s
            if axis in tuple(s):  # idempotent: never duplicate a mesh axis
                return s
            dims = list(s)
            for i, d in enumerate(dims):
                if d is not None:
                    continue
                if leaf is not None and axis_size is not None and \
                        leaf.shape[i] % axis_size != 0:
                    continue  # dim not divisible by the axis: skip it
                dims[i] = axis
                return P(*dims)
            # a spec like P('mp') may still have implicit free trailing
            # dims, but the spec alone doesn't carry the array rank — be
            # loud instead of silently leaving the param replicated
            warnings.warn(
                f"auto_parallel_sharding: spec {s} has no explicit free "
                f"dim; param stays unsharded over '{axis}' (write specs "
                f"with explicit None dims for stage-3)")
            return s

        is_spec = lambda x: isinstance(x, P)
        if example is not None:
            new_specs = jax.tree.map(shard_first_free, spec.param_specs,
                                     example, is_leaf=is_spec)
        else:
            new_specs = jax.tree.map(shard_first_free, spec.param_specs,
                                     is_leaf=is_spec)
        return dataclasses.replace(spec, param_specs=new_specs)


class Pipeline1F1BPass(PassBase):
    """reference: pipeline_scheduler_pass/pipeline_1f1b.py."""

    name = "pipeline_scheduler_1F1B"

    def _apply_impl(self, spec):
        return dataclasses.replace(spec, schedule="1F1B", virtual_pp=1)


class PipelineFThenBPass(PassBase):
    """reference: pipeline_scheduler_pass/pipeline_fthenb.py — on TPU the
    compiled scan IS fill-then-drain; same engine as 1F1B."""

    name = "pipeline_scheduler_FThenB"

    def _apply_impl(self, spec):
        return dataclasses.replace(spec, schedule="FThenB", virtual_pp=1)


class PipelineVPPPass(PassBase):
    """reference: pipeline_scheduler_pass/pipeline_vpp.py — interleaved
    virtual stages (spmd_pipeline_interleaved)."""

    name = "pipeline_scheduler_VPP"

    def check(self, spec):
        return self.attrs.get("vpp_degree", 2) >= 1

    def _apply_impl(self, spec):
        return dataclasses.replace(spec, schedule="VPP",
                                   virtual_pp=self.attrs.get("vpp_degree", 2))


class PipelineZeroBubblePass(PassBase):
    """reference: pipeline_scheduler_pass/pipeline_zero_bubble.py — ZB-H1:
    the backward splits into activation-grad and weight-grad half-units
    and weight-grads fill the bubble (spmd_pipeline_zero_bubble's
    hand-scheduled custom_vjp)."""

    name = "pipeline_scheduler_ZBH1"

    def _apply_impl(self, spec):
        return dataclasses.replace(spec, schedule="ZBH1", virtual_pp=1)


_PASSES = {p.name: p for p in
           (AMPPass, RecomputePass, GradientMergePass, ShardingPass,
            Pipeline1F1BPass, PipelineFThenBPass, PipelineVPPPass,
            PipelineZeroBubblePass)}


def new_pass(name: str, attrs: Optional[Dict] = None) -> PassBase:
    """(reference: pass_base.py new_pass)."""
    enforce_in(name, _PASSES,
               f"unknown pass {name!r}; have {sorted(_PASSES)}",
               op="new_pass")
    return _PASSES[name](attrs)


def list_passes():
    return sorted(_PASSES)


def apply_passes(spec: TrainSpec, passes, context: Optional[PassContext] = None
                 ) -> TrainSpec:
    context = context or PassContext()
    for p in passes:
        if isinstance(p, str):
            p = new_pass(p)
        elif isinstance(p, tuple):  # ("name", {attrs}) shorthand
            p = new_pass(p[0], p[1] if len(p) > 1 else None)
        spec = p.apply(spec, context)
    return spec


def build_train_step(spec: TrainSpec, vpp_layers: Optional[int] = None):
    """Compile a TrainSpec into an executable hybrid train step — the piece
    that makes with/without-pass parity testable the reference way
    (test/distributed_passes/dist_pass_test_base.py runs the program both
    ways and compares outputs).

    Returns (step, shard_params, init_state) from
    models.hybrid_engine.build_train_step. `vpp_layers` (total block count)
    re-layouts stacked block params chunk-major when the spec's schedule is
    VPP with virtual_pp > 1. The step donates (params, opt_state), as the
    engine's does by default: rebind its outputs (a parity run that feeds
    the same trees to both sides builds through TrainSpec.build(
    donate=False)).
    """
    import jax

    from ...models.hybrid_engine import build_train_step as _build
    from ..fleet.meta_parallel.pp_utils.spmd_pipeline import (
        vpp_wrap_shard_params)

    enforce(spec.mesh is not None and spec.optimizer is not None,
            "TrainSpec needs mesh and optimizer to build a train step",
            error=PreconditionNotMetError, op="build_from_spec")
    loss_fn = spec.resolved_loss_fn()
    step, shard_params, init_state = _build(
        loss_fn, spec.param_specs, spec.mesh, spec.optimizer)
    if spec.virtual_pp > 1 and vpp_layers is not None:
        pp = spec.mesh.shape.get("pp", 1)
        shard_params = vpp_wrap_shard_params(shard_params, vpp_layers, pp,
                                             spec.virtual_pp)
    return step, shard_params, init_state
