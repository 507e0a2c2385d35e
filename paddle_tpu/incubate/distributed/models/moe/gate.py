"""MoE gates (reference: python/paddle/incubate/distributed/models/moe/gate/
— naive_gate.py, gshard_gate.py, switch_gate.py, base_gate.py).

Each gate maps token activations to a capacity-bounded routing plan:

  combine_weights [T, E, C] — weight each token contributes to each
                              (expert, capacity-slot); zero where dropped
  dispatch_mask   [T, E, C] — boolean one-hot of slot assignment
  aux_loss        scalar    — load-balancing loss (0 for NaiveGate)

The [T, E, C] formulation is the GShard einsum dispatch: on TPU the
dispatch/combine einsums compile to MXU matmuls and the E dimension carries
the expert-parallel sharding, so XLA lowers the token exchange to a single
all-to-all over the 'ep' mesh axis. The reference instead materializes
variable-length per-expert token lists and NCCL-alltoalls them
(global_scatter) — dynamic shapes XLA cannot tile.

All routing math is fully vectorized (cumsum-based position assignment,
no data-dependent control flow) so it jits to one fused region.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .....nn.initializer import XavierUniform
from .....nn.layer.layers import Layer, Parameter

__all__ = ["BaseGate", "NaiveGate", "SwitchGate", "GShardGate", "TopKGate",
           "compute_capacity"]


def compute_capacity(num_tokens: int, num_experts: int, top_k: int,
                     capacity_factor: float) -> int:
    """Slots per expert. Reference gates bound tokens-per-expert the same
    way (gshard_gate.py capacity arg)."""
    cap = int(math.ceil(num_tokens * top_k / num_experts * capacity_factor))
    return max(cap, top_k)


def _one_hot(idx, num):
    return jax.nn.one_hot(idx, num, dtype=jnp.float32)


def _positions_in_expert(mask: jnp.ndarray) -> jnp.ndarray:
    """mask [T, E] 0/1 → slot index each token takes in its expert's queue
    (cumsum order = token order, the reference's prune_gate_by_capacity
    semantics)."""
    return (jnp.cumsum(mask, axis=0) - 1.0) * mask


def _slot_assign(expert_idx, capacity, num_experts, prev_counts=None):
    """Shared capacity-slot assignment for one routing choice (the ONE
    copy of the queueing math — both dispatch encodings derive from it).

    expert_idx [T] int. prev_counts [E] — slots already
    taken by earlier choices (top-2's second expert queues behind the
    first, matching GShard). Returns (mask [T,E], pos_idx [T] int32,
    keep_tok [T] bool, counts [E])."""
    mask = _one_hot(expert_idx, num_experts)  # [T, E]
    pos = _positions_in_expert(mask)
    if prev_counts is not None:
        pos = pos + prev_counts[None, :] * mask
    keep = (pos < capacity) & (mask > 0)
    pos_idx = pos.sum(axis=1).astype(jnp.int32)  # [T]
    keep_tok = keep.any(axis=1)
    counts = mask.sum(axis=0)
    return mask, pos_idx, keep_tok, counts


def _capacity_dispatch(expert_idx, gate_w, capacity, num_experts,
                       prev_counts=None):
    """Dense [T, E, C] one-hot encoding of _slot_assign (the GSPMD/einsum
    dispatch form). Returns (combine, kept_mask, counts)."""
    mask, pos_idx, keep_tok, counts = _slot_assign(
        expert_idx, capacity, num_experts, prev_counts)
    combine = (gate_w * keep_tok)[:, None, None] * (
        mask[:, :, None] * _one_hot(pos_idx, capacity)[:, None, :])
    return combine, keep_tok, counts


def _capacity_dispatch_idx(expert_idx, gate_w, capacity, num_experts,
                           prev_counts=None):
    """INDEX encoding of _slot_assign — flat slot ids instead of the dense
    [T, E, C] one-hot.

    Returns (slot [T] int32 = e*C + pos, or -1 when dropped;
    gate [T] f32 zeroed for dropped tokens; counts [E]). The MoE layer's
    gather/scatter dispatch consumes this: the dense [T,E,C] einsum costs
    2·T·E·C·D MXU flops per dispatch/combine, where the reference's CUDA
    scatter
    (fluid/operators/collective/global_scatter_op.cu.cc) is ~free —
    index routing is the TPU analogue of that zero-flop scatter.
    """
    mask, pos_idx, keep_tok, counts = _slot_assign(
        expert_idx, capacity, num_experts, prev_counts)
    slot = jnp.where(keep_tok,
                     expert_idx.astype(jnp.int32) * capacity + pos_idx,
                     -1).astype(jnp.int32)
    return slot, gate_w * keep_tok, counts


class BaseGate(Layer):
    """Reference: moe/gate/base_gate.py — holds expert counts and the
    learned routing projection."""

    def __init__(self, d_model: int, num_experts: int, top_k: int = 2,
                 capacity_factor: float = 1.25, name: Optional[str] = None):
        super().__init__(name_scope=name)
        self.d_model = d_model
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.weight = self.create_parameter(
            [d_model, num_experts], default_initializer=XavierUniform())

    def logits(self, x):
        # route in fp32: softmax/cumsum numerics matter more than MXU speed
        return jnp.asarray(x, jnp.float32) @ jnp.asarray(
            self.weight.value, jnp.float32)

    def capacity(self, num_tokens: int) -> int:
        return compute_capacity(num_tokens, self.num_experts, self.top_k,
                                self.capacity_factor)

    def _route(self, x):
        """(choices, aux): choices = [(expert_idx [T], gate_w [T]), ...]
        in priority order (later choices queue behind earlier ones for
        capacity slots). Subclasses implement routing here ONCE; dense
        (forward) and index (forward_index) dispatch both derive from it."""
        raise NotImplementedError

    def forward(self, x) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        choices, aux = self._route(x)
        cap = self.capacity(x.shape[0])
        combine = jnp.zeros((x.shape[0], self.num_experts, cap),
                            jnp.float32)
        counts = None
        for ei, wi in choices:
            c, _, n = _capacity_dispatch(ei, wi, cap, self.num_experts,
                                         counts)
            combine = combine + c
            counts = n if counts is None else counts + n
        return combine, combine > 0, aux

    def forward_index(self, x):
        """(slots [T, K] int32 (-1 = dropped), gates [T, K] f32, aux) —
        the gather/scatter dispatch form (see _capacity_dispatch_idx)."""
        choices, aux = self._route(x)
        cap = self.capacity(x.shape[0])
        slots, gates = [], []
        counts = None
        for ei, wi in choices:
            s, g, n = _capacity_dispatch_idx(ei, wi, cap, self.num_experts,
                                             counts)
            slots.append(s)
            gates.append(g)
            counts = n if counts is None else counts + n
        return jnp.stack(slots, axis=1), jnp.stack(gates, axis=1), aux


class NaiveGate(BaseGate):
    """Reference: moe/gate/naive_gate.py — plain top-k, no aux loss. Kept
    capacity-bounded here (capacity_factor defaults high enough that drops
    are rare at test scale)."""

    def __init__(self, d_model, num_experts, top_k=2, capacity_factor=2.0):
        super().__init__(d_model, num_experts, top_k, capacity_factor)

    def _route(self, x):
        logits = self.logits(x)
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, self.top_k)
        topw = topw / jnp.clip(topw.sum(-1, keepdims=True), 1e-9)
        choices = [(topi[:, k], topw[:, k]) for k in range(self.top_k)]
        return choices, jnp.zeros((), jnp.float32)


class SwitchGate(BaseGate):
    """Reference: moe/gate/switch_gate.py — top-1 routing with the Switch
    Transformer load-balance loss E·Σ_e f_e·P_e."""

    def __init__(self, d_model, num_experts, capacity_factor=1.25,
                 jitter_eps: float = 0.0):
        super().__init__(d_model, num_experts, top_k=1,
                         capacity_factor=capacity_factor)
        self.jitter_eps = jitter_eps

    def _route(self, x):
        logits = self.logits(x)
        if self.jitter_eps > 0.0:
            # Switch-Transformer multiplicative routing jitter; key drawn
            # from the framework RNG so seeding stays reproducible.
            from .....random import next_key
            noise = jax.random.uniform(
                next_key(), logits.shape, jnp.float32,
                1.0 - self.jitter_eps, 1.0 + self.jitter_eps)
            logits = logits * noise
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w = probs.max(axis=-1)
        expert = probs.argmax(axis=-1)
        me = probs.mean(axis=0)
        ce = _one_hot(expert, self.num_experts).mean(axis=0)
        aux = jnp.sum(me * ce) * self.num_experts
        return [(expert, gate_w)], aux


class GShardGate(BaseGate):
    """Reference: moe/gate/gshard_gate.py — top-2 with aux loss on the
    first choice and the second expert queued behind the first's slots."""

    def __init__(self, d_model, num_experts, capacity_factor=2.0):
        super().__init__(d_model, num_experts, top_k=2,
                         capacity_factor=capacity_factor)

    def _route(self, x):
        logits = self.logits(x)
        probs = jax.nn.softmax(logits, axis=-1)
        e1 = probs.argmax(axis=-1)
        w1 = probs.max(axis=-1)
        masked = probs - _one_hot(e1, self.num_experts) * probs
        e2 = masked.argmax(axis=-1)
        w2 = masked.max(axis=-1)
        denom = jnp.clip(w1 + w2, 1e-9)
        me = probs.mean(axis=0)
        ce = _one_hot(e1, self.num_experts).mean(axis=0)
        aux = jnp.sum(me * ce) * self.num_experts
        return [(e1, w1 / denom), (e2, w2 / denom)], aux


class TopKGate(NaiveGate):
    """General top-k alias (the reference exposes NaiveGate(topk=k))."""
    pass
