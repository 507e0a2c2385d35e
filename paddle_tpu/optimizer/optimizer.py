"""Optimizers (reference: python/paddle/optimizer/ — SGD/Momentum/Adam/AdamW/
Lamb/... backed by per-op CUDA kernels e.g. paddle/phi/kernels/gpu/adam_kernel.cu).

TPU design: each optimizer defines a pure functional core
  init_state(params) -> state pytree
  apply(params, grads, state, lr) -> (new_params, new_state)
usable directly under jit/pjit — XLA fuses the whole update into a few
elementwise kernels, and sharded params get sharded updates for free (this is
how ZeRO sharding composes: shard the state pytree, not the optimizer code).
The eager surface (`opt.step()` reading `param.grad`) matches the reference
for porting convenience.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from ..enforce import (InvalidArgumentError, InvalidTypeError,
                       PreconditionNotMetError, enforce)
import numpy as np

from ..nn.layer.layers import Layer, Parameter
from ..observability.trace import SCOPES
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adagrad", "Adadelta", "RMSProp",
           "Adam", "AdamW", "Adamax", "Lamb", "Lars", "NAdam", "RAdam"]


def _tree_map(f, *trees):
    return jax.tree.map(f, *trees)


def _path_name(path) -> str:
    """Dot-joined pytree path → parameter name (for a flat dict the name
    IS the key, matching what apply_decay_param_fun-style predicates see
    on the reference's named-parameter surface)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):          # DictKey
            parts.append(str(k.key))
        elif hasattr(k, "idx"):        # SequenceKey
            parts.append(str(k.idx))
        elif hasattr(k, "name"):       # GetAttrKey (str() would add a dot)
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return ".".join(parts)


def _sr_to_bf16(x, key):
    """Unbiased stochastic rounding f32 → bf16: add uniform noise below the
    bf16 mantissa cutoff in integer space, then truncate. Needed for
    low-precision EMA stores — with beta2=0.999 the per-step relative
    update (~1e-3) is below bf16's ~4e-3 ulp, so nearest-rounding would
    freeze moment2 at a stale value; stochastic rounding keeps the EMA
    unbiased in expectation."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    noise = jax.random.bits(key, x.shape, jnp.uint32) & jnp.uint32(0xFFFF)
    rounded = (bits + noise) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(rounded, jnp.float32).astype(
        jnp.bfloat16)


def _store_moment(x, dtype, key):
    from ..flags import flag
    if dtype == jnp.bfloat16 and key is not None \
            and flag("bf16_stochastic_rounding_moments"):
        return _sr_to_bf16(x, key)
    return x.astype(dtype)


class Optimizer:
    """Base optimizer. Subclasses implement `_init_slot` and `_update`."""

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        del name
        self._lr = learning_rate
        self._parameter_list: Optional[List[Parameter]] = None
        if parameters is not None:
            self._parameter_list = [p for p in parameters
                                    if isinstance(p, Parameter)]
        self._weight_decay = 0.0 if weight_decay is None else weight_decay
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._step_count = 0
        self._eager_state = None

    # -- lr ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return float(self._lr())
        return float(self._lr)

    def set_lr(self, lr: float):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = lr

    def _lr_step(self):
        if isinstance(self._lr, LRScheduler):
            self._lr.step()

    # -- functional core -----------------------------------------------------
    def _init_slot(self, p: jax.Array) -> Dict[str, jax.Array]:
        return {}

    def _update(self, p, g, slot, lr, step):
        raise NotImplementedError

    # -- per-leaf name/context protocol --------------------------------------
    # Optimizers whose update depends on the PARAMETER NAME (AdamW
    # apply_decay_param_fun, Lars exclude_from_weight_decay — reference:
    # adamw.py / fleet LarsOptimizer) expose that dependence as a small
    # hashable context so the per-leaf streaming loops (group_sharded
    # offload, _apply_leaves) can thread it: `_leaf_ctx(name)` maps a
    # pytree-path name to the context (None = name-independent, the
    # default), and `_update_ctx(ctx, ...)` runs one leaf's update under
    # it. Contexts are jit-static: distinct values trace distinct
    # programs, so keep the codomain tiny (bools, not raw names).
    _needs_leaf_names = False  # subclasses set True when ctx is active

    def _leaf_ctx(self, name):
        del name
        return None

    def _update_ctx(self, ctx, p, g, slot, lr, step, rng=None):
        del ctx  # default: name-independent update
        if rng is not None:
            return self._update(p, g, slot, lr, step, rng=rng)
        return self._update(p, g, slot, lr, step)

    def init_state(self, params) -> Dict[str, Any]:
        slots = _tree_map(lambda p: self._init_slot(p), params)
        return {"step": jnp.zeros((), jnp.int32), "slots": slots}

    def _leaf_items(self, params, grads, slots, step, offset=None):
        """ONE implementation of the per-leaf iteration protocol shared by
        every per-leaf update loop (_apply_leaves, the group_sharded
        offload loop, the hybrid engine's ZeRO-1 loop): flatten with
        paths, derive names → ctx, build the per-leaf stochastic-rounding
        keys. Returns (treedef, items) with items =
        [(p, g_or_None, slot, ctx, rng_or_None), ...]; `offset` rebases
        the rng stream when the loop is split across programs."""
        paths_p, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves_p = [leaf for _, leaf in paths_p]
        names = [_path_name(path) for path, _ in paths_p]
        leaves_g = treedef.flatten_up_to(grads)
        leaves_s = treedef.flatten_up_to(slots)
        rng_base = None
        if getattr(self, "_needs_update_rng", False):
            # per-step, per-leaf keys for stochastic rounding of
            # low-precision state stores (deterministic given `step`).
            # rbg = XLA's hardware RngBitGenerator — ~free on TPU, where
            # threefry on billions of moment elements costs ~5% step time
            rng_base = jax.random.key(step.astype(jnp.uint32), impl="rbg")
        items = []
        for i, (p, g, s) in enumerate(zip(leaves_p, leaves_g, leaves_s)):
            rng = None
            if rng_base is not None and g is not None:
                idx = i if offset is None else offset + i
                rng = jax.random.fold_in(rng_base, idx)
            ctx = self._leaf_ctx(names[i]) if g is not None else None
            items.append((p, g, s, ctx, rng))
        return treedef, items

    def _apply_leaves(self, params, grads, slots, lr, step, offset=None):
        """Per-leaf update loop shared by apply() and the param-streaming
        tier (distributed/sharding/param_stream.py)."""
        treedef, items = self._leaf_items(params, grads, slots, step,
                                          offset=offset)
        new_p, new_s = [], []
        for p, g, s, ctx, rng in items:
            if g is None:
                new_p.append(p)
                new_s.append(s)
                continue
            np_, ns_ = self._update_ctx(ctx, p, g, s, lr, step, rng=rng)
            new_p.append(np_)
            new_s.append(ns_)
        return (jax.tree.unflatten(treedef, new_p),
                jax.tree.unflatten(treedef, new_s))

    @jax.named_scope(SCOPES.optimizer)
    def apply(self, params, grads, state, lr=None):
        """Pure update: returns (new_params, new_state). jit/pjit-safe."""
        lr = self.get_lr() if lr is None else lr
        step = state["step"] + 1
        if self._grad_clip is not None:
            grads = self._grad_clip(grads)
        new_p, new_slots = self._apply_leaves(params, grads, state["slots"],
                                              lr, step)
        return new_p, {"step": step, "slots": new_slots}

    # -- weight decay helpers ------------------------------------------------
    def _decay_coeff(self) -> float:
        wd = self._weight_decay
        if wd is None:
            return 0.0
        if hasattr(wd, "__float__"):
            return float(wd)
        return float(wd)

    def _apply_l2(self, g, p):
        """L2 regularization folded into the gradient (reference semantics for
        `weight_decay` on non-AdamW optimizers)."""
        wd = self._decay_coeff()
        if wd:
            return g + wd * p
        return g

    # -- eager surface -------------------------------------------------------
    def _ensure_params(self):
        enforce(self._parameter_list is not None,
                "optimizer constructed without `parameters`",
                op="Optimizer.step", error=PreconditionNotMetError)

    def _param_key(self, idx: int, p: Parameter) -> str:
        return p.name if p.name else f"param_{idx}"

    def step(self):
        """Eager step using param.grad slots (numpy/jax arrays)."""
        self._ensure_params()
        items = [(self._param_key(i, p), p)
                 for i, p in enumerate(self._parameter_list)
                 if p.trainable and p.grad is not None]
        if not items:
            self._step_count += 1
            return
        params = {k: p.value for k, p in items}
        grads = {k: jnp.asarray(p.grad) for k, p in items}
        if self._eager_state is None:
            self._eager_state = self.init_state(params)
        else:
            # slots follow parameter names; init only newly-seen params so a
            # frozen/unfrozen subset never resets or mis-assigns moments
            slots = self._eager_state["slots"]
            for k, p in items:
                if k not in slots:
                    slots[k] = self._init_slot(p.value)
            state = {"step": self._eager_state["step"],
                     "slots": {k: slots[k] for k, _ in items}}
            new_params, new_state = self.apply(params, grads, state)
            slots.update(new_state["slots"])
            self._eager_state = {"step": new_state["step"], "slots": slots}
            for k, p in items:
                p.value = new_params[k]
            self._step_count += 1
            return
        new_params, self._eager_state = self.apply(params, grads, self._eager_state)
        for k, p in items:
            p.value = new_params[k]
        self._step_count += 1

    def clear_grad(self):
        self._ensure_params()
        for p in self._parameter_list:
            p.grad = None

    clear_gradients = clear_grad

    def state_dict(self):
        out = {"step_count": self._step_count}
        if self._eager_state is not None:
            out["state"] = self._eager_state
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        return out

    def set_state_dict(self, state):
        self._step_count = state.get("step_count", 0)
        if "state" in state:
            self._eager_state = state["state"]
        if "LR_Scheduler" in state and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state["LR_Scheduler"])

    # minimize-style API (reference: Optimizer.minimize)
    def minimize(self, loss_fn: Callable, *args, **kwargs):
        raise NotImplementedError(
            "minimize over a traced loss is not supported; use a jitted "
            "train step with jax.value_and_grad + opt.apply")


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _update(self, p, g, slot, lr, step):
        g = self._apply_l2(g.astype(jnp.float32), p)
        return (p - lr * g).astype(p.dtype), slot


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_slot(self, p):
        return {"velocity": jnp.zeros_like(p, dtype=jnp.float32)}

    def _update(self, p, g, slot, lr, step):
        g = self._apply_l2(g.astype(jnp.float32), p)
        v = self._momentum * slot["velocity"] + g
        if self._nesterov:
            upd = g + self._momentum * v
        else:
            upd = v
        return (p - lr * upd).astype(p.dtype), {"velocity": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _init_slot(self, p):
        return {"moment": jnp.full_like(p, self._init_acc, dtype=jnp.float32)}

    def _update(self, p, g, slot, lr, step):
        g = self._apply_l2(g.astype(jnp.float32), p)
        m = slot["moment"] + jnp.square(g)
        return (p - lr * g / (jnp.sqrt(m) + self._epsilon)).astype(p.dtype), {"moment": m}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon, self._rho = epsilon, rho

    def _init_slot(self, p):
        z = jnp.zeros_like(p, dtype=jnp.float32)
        return {"avg_sq_grad": z, "avg_sq_update": z}

    def _update(self, p, g, slot, lr, step):
        g = self._apply_l2(g.astype(jnp.float32), p)
        asg = self._rho * slot["avg_sq_grad"] + (1 - self._rho) * jnp.square(g)
        upd = g * jnp.sqrt(slot["avg_sq_update"] + self._epsilon) / jnp.sqrt(asg + self._epsilon)
        asu = self._rho * slot["avg_sq_update"] + (1 - self._rho) * jnp.square(upd)
        return (p - lr * upd).astype(p.dtype), {"avg_sq_grad": asg, "avg_sq_update": asu}


class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, rho=0.95, epsilon=1e-6,
                 momentum=0.0, centered=False, parameters=None,
                 weight_decay=None, grad_clip=None, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _init_slot(self, p):
        z = jnp.zeros_like(p, dtype=jnp.float32)
        s = {"mean_square": z, "momentum": z}
        if self._centered:
            s["mean_grad"] = z
        return s

    def _update(self, p, g, slot, lr, step):
        g = self._apply_l2(g.astype(jnp.float32), p)
        ms = self._rho * slot["mean_square"] + (1 - self._rho) * jnp.square(g)
        out = {"mean_square": ms}
        if self._centered:
            mg = self._rho * slot["mean_grad"] + (1 - self._rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self._epsilon)
            out["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * slot["momentum"] + lr * g / denom
        out["momentum"] = mom
        return (p - mom).astype(p.dtype), out


class Adam(Optimizer):
    """moment_dtype: storage dtype for moment1/moment2 (default fp32).
    TPU extension: bf16 moments halve optimizer-state HBM — the update
    itself always runs in fp32 and rounds the moments on store. This is
    the single-chip analogue of the reference's sharded/offloaded state
    layouts (GroupSharded); it is what lets a 1.3B GPT train on one v5e."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 moment_dtype=None, use_multi_tensor=None, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._moment_dtype = moment_dtype
        self._lazy_mode = lazy_mode
        # reference API (python/paddle/optimizer/adam.py:210
        # use_multi_tensor): update all parameters in one fused pass.
        # Default OFF like the reference: the concat/split copies are two
        # more passes over every leaf than the per-leaf update makes
        # (what they cost against the launches they save is not measured
        # on the current installation), and on sharded params the concat
        # would also discard per-leaf shardings. Kept for API parity + the rare
        # many-tiny-leaves tree where launches dominate.
        self._use_multi_tensor = bool(use_multi_tensor)
        # low-precision EMA stores need stochastic rounding (see _sr_to_bf16)
        self._needs_update_rng = (moment_dtype is not None
                                  and jnp.dtype(moment_dtype) != jnp.float32)

    def _init_slot(self, p):
        z = jnp.zeros_like(p, dtype=self._moment_dtype or jnp.float32)
        slot = {"moment1": z, "moment2": z}
        if self._multi_precision and p.dtype != jnp.float32:
            slot["master"] = p.astype(jnp.float32)
        return slot

    def _decoupled_decay(self, p, lr):
        return 0.0

    def _adam_core(self, pf, gf, m1_prev, m2_prev, lr, step):
        """Shared EMA + bias-corrected update (dense and per-row sparse
        paths both use this — one place for the Adam math)."""
        m1 = self._beta1 * m1_prev + (1 - self._beta1) * gf
        m2 = self._beta2 * m2_prev + (1 - self._beta2) * jnp.square(gf)
        stepf = step.astype(jnp.float32)
        m1_hat = m1 / (1 - self._beta1 ** stepf)
        m2_hat = m2 / (1 - self._beta2 ** stepf)
        upd = m1_hat / (jnp.sqrt(m2_hat) + self._epsilon)
        new_pf = pf - lr * upd - self._decoupled_decay(pf, lr)
        return new_pf, m1, m2

    def _fused_coeffs(self):
        """(l2_into_grad, decoupled) decay coefficients for the Pallas
        fused kernel — must mirror _apply_l2/_decoupled_decay exactly."""
        return self._decay_coeff(), 0.0

    def _update(self, p, g, slot, lr, step, rng=None):
        from ..framework.selected_rows import SelectedRows
        if isinstance(g, SelectedRows):
            return self._update_sparse(p, g, slot, lr, step, rng)
        # Pallas fused single-pass update (reference:
        # fusion/gpu/fused_adam_kernel.cu). Only for exact Adam/AdamW math
        # (subclasses override pieces of _adam_core); XLA path otherwise.
        from ..flags import flag
        from ..ops.registry import _on_tpu
        if type(self) in _FUSED_TYPES and _on_tpu() \
                and flag("enable_pallas_kernels"):
            from ..kernels.pallas import fused_adam as _fa
            if _fa.supported(p, g, slot):
                sr_rng = rng if (rng is not None and flag(
                    "bf16_stochastic_rounding_moments")) else None
                l2c, decc = self._fused_coeffs()
                return _fa.adam_update(
                    p, g, slot, lr, step, sr_rng, beta1=self._beta1,
                    beta2=self._beta2, epsilon=self._epsilon, l2=l2c,
                    decoupled=decc)
        gf = g.astype(jnp.float32)
        master = slot.get("master", None)
        pf = master if master is not None else p.astype(jnp.float32)
        gf = self._apply_l2(gf, pf) if type(self) is Adam else gf
        new_pf, m1, m2 = self._adam_core(
            pf, gf, slot["moment1"].astype(jnp.float32),
            slot["moment2"].astype(jnp.float32), lr, step)
        # only moment2 needs stochastic rounding: its per-step relative
        # update (1-beta2 ~ 1e-3) is below bf16 ulp, while moment1's
        # (1-beta1 ~ 0.1) is far above it — nearest rounding tracks fine
        out = {"moment1": m1.astype(slot["moment1"].dtype),
               "moment2": _store_moment(m2, slot["moment2"].dtype, rng)}
        if master is not None:
            out["master"] = new_pf
        return new_pf.astype(p.dtype), out


    # -- fused (multi-tensor) path ------------------------------------------
    def _fusable(self, grads) -> bool:
        """One fused elementwise pass is exact for plain Adam/AdamW (the
        update reads only (p, g, m1, m2[, master]) per element). Anything
        that threads per-parameter context — decay filters, lr_ratio,
        lazy/sparse rows, subclass math (NAdam/RAdam/...) — keeps the
        per-leaf loop."""
        if type(self) not in _FUSED_TYPES:
            return False
        if self._lazy_mode:
            return False
        if getattr(self, "_apply_decay_param_fun", None) is not None \
                or getattr(self, "_lr_ratio", None) is not None:
            return False
        from ..framework.selected_rows import SelectedRows
        leaves = jax.tree.leaves(
            grads, is_leaf=lambda x: isinstance(x, SelectedRows))
        return not any(isinstance(g, SelectedRows) for g in leaves)

    def apply(self, params, grads, state, lr=None):
        use_mt = self._use_multi_tensor
        if use_mt and not self._fusable(grads):
            raise InvalidArgumentError(
                "use_multi_tensor=True needs a plain Adam/AdamW update "
                "(no lazy_mode/apply_decay_param_fun/lr_ratio/SelectedRows "
                "grads)")
        if not use_mt:
            return super().apply(params, grads, state, lr)
        with jax.named_scope(SCOPES.optimizer):
            lr = self.get_lr() if lr is None else lr
            step = state["step"] + 1
            if self._grad_clip is not None:
                grads = self._grad_clip(grads)
            new_p, new_slots = self._fused_update(
                params, grads, state["slots"], lr, step)
            return new_p, {"step": step, "slots": new_slots}

    def _fused_update(self, params, grads, slots, lr, step):
        """Multi-tensor update (reference: use_multi_tensor /
        fused_adam_kernel.cu): leaves grouped by (dtype, moment dtype,
        master?) are raveled into ONE flat buffer per group and updated in
        a single fused elementwise pass — on TPU this collapses hundreds
        of per-leaf convert fusions into a handful of HBM-bound sweeps.
        Elementwise math is identical to _update; only the SR rng stream
        differs (one key per group instead of per leaf)."""
        leaves_p, treedef = jax.tree.flatten(params)
        leaves_g = treedef.flatten_up_to(grads)
        leaves_s = treedef.flatten_up_to(slots)
        groups = {}
        for i, (p, g, s) in enumerate(zip(leaves_p, leaves_g, leaves_s)):
            if g is None:
                continue
            key = (jnp.dtype(p.dtype), jnp.dtype(s["moment1"].dtype),
                   jnp.dtype(s["moment2"].dtype), "master" in s)
            groups.setdefault(key, []).append(i)
        rng_base = (jax.random.key(step.astype(jnp.uint32), impl="rbg")
                    if self._needs_update_rng else None)
        new_p = list(leaves_p)
        new_s = list(leaves_s)
        wd = self._decay_coeff()
        for gi, (key, idxs) in enumerate(sorted(groups.items(),
                                                key=lambda kv: str(kv[0]))):
            has_master = key[3]
            shapes = [leaves_p[i].shape for i in idxs]
            sizes = [int(np.prod(s)) for s in shapes]

            def flat(arrs):
                return jnp.concatenate([jnp.ravel(a) for a in arrs])

            p_flat = flat([leaves_p[i] for i in idxs])
            gf = flat([leaves_g[i] for i in idxs]).astype(jnp.float32)
            m1f = flat([leaves_s[i]["moment1"] for i in idxs]).astype(
                jnp.float32)
            m2f = flat([leaves_s[i]["moment2"] for i in idxs]).astype(
                jnp.float32)
            pf = (flat([leaves_s[i]["master"] for i in idxs]) if has_master
                  else p_flat.astype(jnp.float32))
            if type(self) is Adam and wd:
                gf = gf + wd * pf  # _apply_l2, as in the per-leaf path
            new_pf, m1, m2 = self._adam_core(pf, gf, m1f, m2f, lr, step)
            m1 = m1.astype(key[1])
            m2 = _store_moment(
                m2, key[2],
                jax.random.fold_in(rng_base, gi) if rng_base is not None
                else None)
            out_p = new_pf.astype(key[0])
            splits = list(np.cumsum(sizes)[:-1])
            for arr, dst in ((out_p, "p"), (m1, "moment1"), (m2, "moment2"),
                             (new_pf if has_master else None, "master")):
                if arr is None:
                    continue
                for i, piece in zip(idxs, jnp.split(arr, splits)):
                    piece = piece.reshape(leaves_p[i].shape)
                    if dst == "p":
                        new_p[i] = piece
                    else:
                        if new_s[i] is leaves_s[i]:
                            new_s[i] = dict(leaves_s[i])
                        new_s[i][dst] = piece
        return (jax.tree.unflatten(treedef, new_p),
                jax.tree.unflatten(treedef, new_s))

    def _update_sparse(self, p, g, slot, lr, step, rng=None):
        """LazyAdam row update (reference: lazy_mode in adam_op /
        LazyAdam): only the touched rows' moments and parameters move —
        the contract for huge embedding tables. Rows MUST be unique (call
        SelectedRows.coalesced() outside jit — duplicate rows would
        collide in the row scatter); bias correction uses the global
        step, matching the reference."""
        if not self._lazy_mode:
            return self._update(p, g.to_dense(), slot, lr, step, rng)
        if slot.get("master") is not None:
            raise NotImplementedError(
                "multi_precision with SelectedRows grads is not supported")
        rows, gf = g.rows, g.value.astype(jnp.float32)
        new_rows, m1, m2 = self._adam_core(
            p[rows].astype(jnp.float32), gf,
            slot["moment1"][rows].astype(jnp.float32),
            slot["moment2"][rows].astype(jnp.float32), lr, step)
        out = {
            "moment1": slot["moment1"].at[rows].set(
                m1.astype(slot["moment1"].dtype)),
            "moment2": slot["moment2"].at[rows].set(
                _store_moment(m2, slot["moment2"].dtype, rng)),
        }
        return p.at[rows].set(new_rows.astype(p.dtype)), out


class AdamW(Adam):
    """Adam with decoupled weight decay (reference:
    python/paddle/optimizer/adamw.py; kernel adamw_kernel.cu)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, moment_dtype=None,
                 use_multi_tensor=None, name=None, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         moment_dtype, use_multi_tensor, name)
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio
        if use_multi_tensor and (apply_decay_param_fun is not None
                                 or lr_ratio is not None):
            raise InvalidArgumentError(
                "use_multi_tensor=True needs a plain AdamW update — "
                "apply_decay_param_fun/lr_ratio thread per-parameter "
                "context the fused pass cannot")

    def _decoupled_decay(self, p, lr):
        # the apply_decay_param_fun filter reaches here ONLY via the ctx
        # protocol (_leaf_ctx/_update_ctx — every per-leaf loop threads it)
        if getattr(self, "_ctx_decay", None) is False:
            return 0.0
        return lr * self._decay_coeff() * p

    def _fused_coeffs(self):
        # _decoupled_decay(p=1, lr=1) IS the scalar coefficient — one
        # implementation of the decay-filter predicate, not two
        return 0.0, float(self._decoupled_decay(1.0, 1.0))

    # -- per-leaf name protocol (base class hook): the decay filter is the
    # only name dependence, so the context is a single bool. The base
    # _apply_leaves threads it through every per-leaf path (dense apply,
    # offload streaming) — the reference's adamw.py consults the predicate
    # per parameter inside its C++ loop.
    @property
    def _needs_leaf_names(self):
        return self._apply_decay_param_fun is not None

    def _leaf_ctx(self, name):
        fn = self._apply_decay_param_fun
        if fn is None:
            return None
        return bool(fn(name)) if name is not None else True

    def _update_ctx(self, ctx, p, g, slot, lr, step, rng=None):
        prev = getattr(self, "_ctx_decay", None)
        self._ctx_decay = ctx
        try:
            return super()._update_ctx(ctx, p, g, slot, lr, step, rng=rng)
        finally:
            self._ctx_decay = prev


# exact-fusable types for the multi-tensor path (subclasses override the
# update math — NAdam/RAdam must keep the per-leaf loop)
_FUSED_TYPES = (Adam, AdamW)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_slot(self, p):
        z = jnp.zeros_like(p, dtype=jnp.float32)
        return {"moment": z, "inf_norm": z}

    def _update(self, p, g, slot, lr, step):
        g = self._apply_l2(g.astype(jnp.float32), p)
        m = self._beta1 * slot["moment"] + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * slot["inf_norm"], jnp.abs(g))
        stepf = step.astype(jnp.float32)
        lr_t = lr / (1 - self._beta1 ** stepf)
        return (p - lr_t * m / (u + self._epsilon)).astype(p.dtype), \
               {"moment": m, "inf_norm": u}


class Lars(Optimizer):
    """LARS — layer-wise adaptive rate scaling for large-batch SGD
    (reference: fleet/meta_optimizers lars_optimizer + the
    lars_momentum kernel). local_lr = lr * coeff * ||w|| /
    (||g|| + lambda*||w||); momentum on the rescaled gradient."""

    def __init__(self, learning_rate=0.001, momentum=0.9,
                 lars_coeff=0.001, lars_weight_decay=0.0005,
                 parameters=None, grad_clip=None,
                 exclude_from_weight_decay=None, epsilon=1e-9, name=None,
                 **kw):
        if "weight_decay" in kw:
            raise InvalidTypeError(
                "Lars takes lars_weight_decay=, not weight_decay= — "
                "refusing to silently ignore it")
        super().__init__(learning_rate, parameters, lars_weight_decay,
                         grad_clip, name)
        self._momentum = momentum
        self._coeff = lars_coeff
        self._epsilon = epsilon
        # parameter-NAME substrings excluded from decay AND trust scaling
        # (reference: fleet LarsOptimizer exclude_from_weight_decay —
        # typically ["batch_norm", ".b_0"]); honored on the eager path
        # where names exist, and via apply()'s dict keys functionally
        self._exclude = tuple(exclude_from_weight_decay or ())

    def _init_slot(self, p):
        return {"velocity": jnp.zeros_like(p, dtype=jnp.float32)}

    def _is_excluded(self, name) -> bool:
        return any(tok in name for tok in self._exclude) if name else False

    # -- per-leaf name protocol: the exclude list is the only name
    # dependence; ctx is "is this leaf excluded". The base _apply_leaves
    # derives dotted pytree-path names (flat-dict keys unchanged, nested
    # trees now get real paths instead of silently losing the filter).
    @property
    def _needs_leaf_names(self):
        return bool(self._exclude)

    def _leaf_ctx(self, name):
        if not self._exclude:
            return None
        return self._is_excluded(name)

    def _update_ctx(self, ctx, p, g, slot, lr, step, rng=None):
        prev = getattr(self, "_ctx_excluded", None)
        self._ctx_excluded = ctx
        try:
            return super()._update_ctx(ctx, p, g, slot, lr, step, rng=rng)
        finally:
            self._ctx_excluded = prev

    def _update(self, p, g, slot, lr, step):
        gf = g.astype(jnp.float32)
        pf = p.astype(jnp.float32)
        if getattr(self, "_ctx_excluded", None):
            # excluded params: plain momentum SGD, no decay, no trust ratio
            v = self._momentum * slot["velocity"] + lr * gf
            return (pf - v).astype(p.dtype), {"velocity": v}
        wd = self._decay_coeff()
        w_norm = jnp.linalg.norm(pf)
        g_norm = jnp.linalg.norm(gf)
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            self._coeff * w_norm / (g_norm + wd * w_norm + self._epsilon),
            1.0)
        v = (self._momentum * slot["velocity"]
             + lr * local_lr * (gf + wd * pf))
        return (pf - v).astype(p.dtype), {"velocity": v}


class Lamb(Optimizer):
    """LAMB (reference: python/paddle/optimizer/lamb.py)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None, **kw):
        super().__init__(learning_rate, parameters, lamb_weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_slot(self, p):
        z = jnp.zeros_like(p, dtype=jnp.float32)
        return {"moment1": z, "moment2": z}

    def _update(self, p, g, slot, lr, step):
        gf = g.astype(jnp.float32)
        pf = p.astype(jnp.float32)
        m1 = self._beta1 * slot["moment1"] + (1 - self._beta1) * gf
        m2 = self._beta2 * slot["moment2"] + (1 - self._beta2) * jnp.square(gf)
        stepf = step.astype(jnp.float32)
        m1_hat = m1 / (1 - self._beta1 ** stepf)
        m2_hat = m2 / (1 - self._beta2 ** stepf)
        r = m1_hat / (jnp.sqrt(m2_hat) + self._epsilon)
        wd = self._decay_coeff()
        if self._exclude_fn is None or not self._exclude_fn(p):
            r = r + wd * pf
        w_norm = jnp.linalg.norm(pf)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return (pf - lr * trust * r).astype(p.dtype), {"moment1": m1, "moment2": m2}


class NAdam(Adam):
    def _update(self, p, g, slot, lr, step):
        gf = self._apply_l2(g.astype(jnp.float32), p)
        m1 = self._beta1 * slot["moment1"] + (1 - self._beta1) * gf
        m2 = self._beta2 * slot["moment2"] + (1 - self._beta2) * jnp.square(gf)
        stepf = step.astype(jnp.float32)
        bc1 = 1 - self._beta1 ** stepf
        bc2 = 1 - self._beta2 ** stepf
        m1_bar = (self._beta1 * m1 + (1 - self._beta1) * gf) / bc1
        upd = m1_bar / (jnp.sqrt(m2 / bc2) + self._epsilon)
        return (p - lr * upd).astype(p.dtype), {"moment1": m1, "moment2": m2}


class RAdam(Adam):
    def _update(self, p, g, slot, lr, step):
        gf = self._apply_l2(g.astype(jnp.float32), p)
        m1 = self._beta1 * slot["moment1"] + (1 - self._beta1) * gf
        m2 = self._beta2 * slot["moment2"] + (1 - self._beta2) * jnp.square(gf)
        stepf = step.astype(jnp.float32)
        bc1 = 1 - self._beta1 ** stepf
        rho_inf = 2.0 / (1 - self._beta2) - 1
        rho_t = rho_inf - 2 * stepf * self._beta2 ** stepf / (1 - self._beta2 ** stepf)
        m1_hat = m1 / bc1
        r = jnp.sqrt(((rho_t - 4) * (rho_t - 2) * rho_inf)
                     / jnp.maximum((rho_inf - 4) * (rho_inf - 2) * rho_t, 1e-8))
        v_hat = jnp.sqrt(m2 / (1 - self._beta2 ** stepf)) + self._epsilon
        upd = jnp.where(rho_t > 5.0, r * m1_hat / v_hat, m1_hat)
        return (p - lr * upd).astype(p.dtype), {"moment1": m1, "moment2": m2}
