"""Bigger-than-HBM training via per-block PARAMETER streaming.

Extends the offload tier past optimizer state (group_sharded.py
offload=True streams moments only): here the parameters themselves live in
``pinned_host`` and stream through HBM one transformer block at a time —
forward and backward — the TPU-native analogue of the reference's
GroupShardedStage3 param slicing with gather-on-use and release
(python/paddle/distributed/fleet/meta_parallel/sharding/
group_sharded_stage3.py:85 — `_sync_params_and_buffers`, forward allgather
+ `_release_param`, offload=True).

Memory profile of one train step on one chip:

  HBM  = boundary-activation cache (L x [B,S,H] bf16, ~32 MB each at 6.7B
         shapes) + ONE block's params + that block's grads + its Adam
         moments + one block's vjp residuals
  host = ALL params + ALL moments (pinned_host)

The backward is fused with the optimizer update per block: a block's grads
exist only inside one jitted program and are never materialized for the
whole model, so grad HBM is one block's, not L blocks'. PCIe traffic per
step = params down twice (fwd + bwd recompute), new params up once,
moments down+up once — the step is host-link-bound by design. The point is
capability: a model bigger than one chip's HBM (the GPT-3 6.7B shape on a
16 GB v5e) can still take a step there; not measured on the chip.

Five compiled programs in the unclipped step, each reused across all L
blocks (identical shapes): embed fwd, block fwd, head vjp+update, block
vjp+update, embed vjp+update. Global-norm clip adds four more (head/
block/embed norm passes + the clip coefficient). All params/state are
passed as jit ARGUMENTS (closure constants would be baked into the
serialized HLO).

Global-norm grad clip (the GPT-3 recipe's clip-at-1.0) works via a
TWO-PASS backward: pass 1 re-streams the params through an update-free
backward that only accumulates the fp32 global grad-norm² (the forward's
cached boundary activations serve both passes — no second forward), then
pass 2 is the normal fused update backward with every grad scaled by the
shared clip coefficient. Cost: one extra param down-stream + backward
flops (its step-time cost on the host-link-bound tiers is not measured
on the current installation). By-value clip
is free — it fuses into the per-block update. Reference equivalents:
GroupShardedStage3 param slicing with clip (group_sharded_stage3.py:85
region) and HybridParallelClipGrad (hybrid_parallel_optimizer.py:41).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from .group_sharded import _leaf_streamable

__all__ = ["build_param_streamed_train_step", "host_sharding",
           "device_sharding", "park", "fetch", "supports_pinned_host"]


def _dev(device=None):
    return device if device is not None else jax.devices()[0]


@functools.lru_cache(maxsize=None)
def _pinned_host_supported(device) -> bool:
    try:
        sh = jax.sharding.SingleDeviceSharding(device,
                                               memory_kind="pinned_host")
        jax.device_put(jnp.zeros((1,), jnp.float32), sh)
        return True
    except Exception:
        return False


def supports_pinned_host(device=None) -> bool:
    """Whether the backend can address a ``pinned_host`` memory kind (TPU
    runtimes can; the CPU backend exposes only ``unpinned_host``). The
    offload/streaming tiers need it; tests skip cleanly without it."""
    return _pinned_host_supported(_dev(device))


def host_sharding(device=None):
    return jax.sharding.SingleDeviceSharding(_dev(device),
                                             memory_kind="pinned_host")


def device_sharding(device=None):
    return jax.sharding.SingleDeviceSharding(_dev(device),
                                             memory_kind="device")


def park(tree, device=None):
    """Move every array leaf of `tree` to pinned_host (eager per-buffer
    DMA — in-jit host annotations are avoided throughout, see
    group_sharded.py)."""
    sh = host_sharding(device)
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


def fetch(tree, device=None):
    """Move every array leaf of `tree` from pinned_host to device HBM.
    device_put dispatches are async — issuing the NEXT block's fetch before
    computing the current one overlaps PCIe with compute."""
    sh = device_sharding(device)
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


def build_param_streamed_train_step(
    embed_fn: Callable, block_fn: Callable, head_loss_fn: Callable,
    optimizer, device=None, donate: bool = True,
):
    """Param-streaming trainer over a segmented model:

      embed_fn(embed_params, inputs) -> x          [B, S, H] activations
      block_fn(block_params, x) -> x               one transformer block
      head_loss_fn(head_params, x, targets) -> scalar loss

    Params layout: {"embed": tree, "blocks": [tree x L], "head": tree}
    (models.gpt.streamed_fns / init_streamed_params produce these).

    Returns (place, init_state, step):
      place(params)        -> host params (every leaf parked in pinned_host)
      init_state(hparams)  -> host optimizer state, built ONE segment at a
                              time (no whole-tree HBM spike)
      step(hparams, hstate, inputs, targets, lr) -> (hparams, hstate, loss)

    The optimizer must follow the per-leaf `_init_slot`/`_update` protocol
    (AdamW-family — same gate as the group_sharded offload tier).
    grad_clip: ClipGradByGlobalNorm engages the two-pass backward (module
    docstring); ClipGradByValue fuses into the per-block update; other
    clip types raise.
    """
    if not _leaf_streamable(optimizer):
        raise NotImplementedError(
            "param streaming updates each block the moment its grads exist; "
            "the optimizer must follow the per-leaf _init_slot/_update "
            f"protocol (AdamW-family). Got {type(optimizer).__name__} with "
            "a custom apply(); use build_sharded_train_step(offload=True).")
    if getattr(optimizer, "_needs_leaf_names", False):
        raise NotImplementedError(
            "name-dependent updates (apply_decay_param_fun / "
            "exclude_from_weight_decay) would see SEGMENT-relative names "
            "here (the per-block programs update subtrees, e.g. 'qkv_w' "
            "instead of 'blocks.3.qkv_w'), silently changing which "
            "parameters the filter matches. Use the moments-offload tier "
            "(build_sharded_train_step(offload=True) — threads full-tree "
            "names), or drop the name filter.")
    from ...nn.clip import ClipGradByGlobalNorm, ClipGradByValue
    clip = optimizer._grad_clip
    global_clip = isinstance(clip, ClipGradByGlobalNorm)
    value_clip = isinstance(clip, ClipGradByValue)
    if clip is not None and not (global_clip or value_clip):
        raise NotImplementedError(
            "the streamed tier supports ClipGradByGlobalNorm (two-pass "
            "backward: norm pass then update pass) and ClipGradByValue "
            f"(fused per block). Got {type(clip).__name__}; drop grad_clip= "
            "or use the moments-only offload tier "
            "(build_sharded_train_step).")

    def _seg_update(p, g, slot, lr, step, offset, scale):
        """Per-leaf optimizer update of one segment inside jit — the shared
        Optimizer._apply_leaves loop with a traced `offset` decorrelating
        the stochastic-rounding streams across segments (the five programs
        are reused by every block). `scale` is the global-norm clip
        coefficient, applied only when that clip mode is compiled in
        (otherwise the argument is unused and traces to nothing);
        by-value clip clamps here, inside the same fused program.

        Global-norm clip matches the reference's sharded-mode discipline
        (HybridParallelClipGrad, fleet/dygraph_optimizer/
        hybrid_parallel_optimizer.py:41: partial norms combined across the
        sharded axis before one shared coefficient) — here the "axis" is
        the stream of per-block backward programs instead of ranks."""
        if value_clip:
            g = jax.tree.map(
                lambda t: jnp.clip(t, clip.min, clip.max).astype(t.dtype), g)
        if global_clip:
            g = jax.tree.map(lambda t: (t * scale).astype(t.dtype), g)
        return optimizer._apply_leaves(p, g, slot, lr, step, offset=offset)

    from ...nn.clip import sum_squares as _norm2  # per-segment norm² term

    dn = (lambda *idx: {"donate_argnums": idx}) if donate else (
        lambda *idx: {})

    # -- the five programs --------------------------------------------------
    @functools.partial(jax.jit, **dn(0))
    def jembed_fwd(ep, inputs):
        return embed_fn(ep, inputs)

    @functools.partial(jax.jit, **dn(0))
    def jblock_fwd(p, x):
        # x is NOT donated: it is the boundary activation the backward
        # recomputes from
        return block_fn(p, x)

    @functools.partial(jax.jit, **dn(0, 1, 3))
    def jhead_step(hp, x, targets, slot, lr, step, offset, scale):
        loss, vjp_fn = jax.vjp(lambda hp_, x_: head_loss_fn(hp_, x_, targets),
                               hp, x)
        dhp, dx = vjp_fn(jnp.ones_like(loss))
        new_hp, new_slot = _seg_update(hp, dhp, slot, lr, step, offset, scale)
        return loss, dx, new_hp, new_slot

    @functools.partial(jax.jit, **dn(0, 1, 2, 3))
    def jblock_step(p, x_in, dx_out, slot, lr, step, offset, scale):
        _, vjp_fn = jax.vjp(block_fn, p, x_in)
        dp, dx_in = vjp_fn(dx_out)
        new_p, new_slot = _seg_update(p, dp, slot, lr, step, offset, scale)
        return dx_in, new_p, new_slot

    @functools.partial(jax.jit, **dn(0, 2, 3))
    def jembed_step(ep, inputs, dx, slot, lr, step, offset, scale):
        _, vjp_fn = jax.vjp(lambda ep_: embed_fn(ep_, inputs), ep)
        (dep,) = vjp_fn(dx)
        new_ep, new_slot = _seg_update(ep, dep, slot, lr, step, offset, scale)
        return new_ep, new_slot

    # -- norm-pass programs (global-norm clip only) -------------------------
    # A second, update-free backward that streams the params down once more
    # and accumulates the fp32 global grad-norm² — the boundary activations
    # cached by the forward serve BOTH backward passes, so the extra cost
    # is one param down-stream plus the vjp flops, never a second forward.
    # Params ARE donated (they're throwaway fetched copies); x / x_in are
    # NOT (the update pass consumes them afterwards).
    @functools.partial(jax.jit, **dn(0))
    def jhead_norm(hp, x, targets):
        loss, vjp_fn = jax.vjp(lambda hp_, x_: head_loss_fn(hp_, x_, targets),
                               hp, x)
        dhp, dx = vjp_fn(jnp.ones_like(loss))
        return loss, dx, _norm2(dhp)

    @functools.partial(jax.jit, **dn(0, 2))
    def jblock_norm(p, x_in, dx_out, n2_acc):
        _, vjp_fn = jax.vjp(block_fn, p, x_in)
        dp, dx_in = vjp_fn(dx_out)
        return dx_in, n2_acc + _norm2(dp)

    @functools.partial(jax.jit, **dn(0, 2))
    def jembed_norm(ep, inputs, dx, n2_acc):
        _, vjp_fn = jax.vjp(lambda ep_: embed_fn(ep_, inputs), ep)
        (dep,) = vjp_fn(dx)
        return n2_acc + _norm2(dep)

    @jax.jit
    def jclip_scale(n2):
        return clip.scale_from_norm(jnp.sqrt(n2))

    # -----------------------------------------------------------------------
    def place(params):
        return {"embed": park(params["embed"], device),
                "blocks": [park(b, device) for b in params["blocks"]],
                "head": park(params["head"], device)}

    slot_init = jax.jit(lambda p_: jax.tree.map(optimizer._init_slot, p_))

    def init_state(hparams):
        """Slots one segment at a time: fetch the segment's params, init
        its slots on device, park, release — never the whole state. One
        jitted init shared by all segments (blocks share shapes → one
        compile, not L)."""
        def seg_slots(seg):
            return park(slot_init(fetch(seg, device)), device)

        return {
            "step": jnp.zeros((), jnp.int32),
            "slots": {
                "embed": seg_slots(hparams["embed"]),
                "blocks": [seg_slots(b) for b in hparams["blocks"]],
                "head": seg_slots(hparams["head"]),
            },
        }

    def step(hparams, hstate, inputs, targets, lr):
        L = len(hparams["blocks"])
        # leaf-count SR-stream offsets, derived per call (a cached count
        # would silently mis-offset if one built step were reused across
        # models with different embed leaf layouts)
        n_embed = len(jax.tree.leaves(hparams["embed"]))
        n_block = len(jax.tree.leaves(hparams["blocks"][0]))
        off_head = jnp.int32(n_embed + L * n_block)
        step_no = hstate["step"] + 1
        lr = jnp.float32(lr)

        # ---- forward: stream blocks down, cache boundary activations ----
        x = jembed_fwd(fetch(hparams["embed"], device), inputs)
        x_ins = []
        nxt = fetch(hparams["blocks"][0], device)
        for i in range(L):
            p_i, nxt = nxt, (fetch(hparams["blocks"][i + 1], device)
                             if i + 1 < L else None)
            x_ins.append(x)
            x = jblock_fwd(p_i, x)

        # ---- pass 1 (global-norm clip only): update-free backward over
        # the SAME cached boundary activations, accumulating grad-norm² ----
        if global_clip:
            _, dxn, n2 = jhead_norm(fetch(hparams["head"], device),
                                    x, targets)
            nxt = fetch(hparams["blocks"][L - 1], device)
            for i in range(L - 1, -1, -1):
                p_i = nxt
                nxt = (fetch(hparams["blocks"][i - 1], device)
                       if i > 0 else None)
                dxn, n2 = jblock_norm(p_i, x_ins[i], dxn, n2)
            n2 = jembed_norm(fetch(hparams["embed"], device), inputs,
                             dxn, n2)
            scale = jclip_scale(n2)
        else:
            scale = jnp.float32(1.0)

        # ---- head: loss + grads + update in one program ----
        loss, dx, new_hp, new_hs = jhead_step(
            fetch(hparams["head"], device), x, targets,
            fetch(hstate["slots"]["head"], device), lr, step_no, off_head,
            scale)
        new_head = park(new_hp, device)
        new_head_s = park(new_hs, device)

        # ---- backward: stream blocks up, update each the moment its
        # grads exist (grads never accumulate model-wide) ----
        new_blocks = [None] * L
        new_block_s = [None] * L
        nxt = (fetch(hparams["blocks"][L - 1], device),
               fetch(hstate["slots"]["blocks"][L - 1], device))
        for i in range(L - 1, -1, -1):
            p_i, s_i = nxt
            nxt = ((fetch(hparams["blocks"][i - 1], device),
                    fetch(hstate["slots"]["blocks"][i - 1], device))
                   if i > 0 else None)
            dx, new_p, new_s = jblock_step(
                p_i, x_ins.pop(), dx, s_i, lr, step_no,
                jnp.int32(n_embed + i * n_block), scale)
            new_blocks[i] = park(new_p, device)
            new_block_s[i] = park(new_s, device)

        new_ep, new_es = jembed_step(
            fetch(hparams["embed"], device), inputs, dx,
            fetch(hstate["slots"]["embed"], device), lr, step_no,
            jnp.int32(0), scale)

        return (
            {"embed": park(new_ep, device), "blocks": new_blocks,
             "head": new_head},
            {"step": step_no,
             "slots": {"embed": park(new_es, device), "blocks": new_block_s,
                       "head": new_head_s}},
            loss,
        )

    return place, init_state, step
