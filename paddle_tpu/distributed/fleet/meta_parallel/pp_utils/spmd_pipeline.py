"""SPMD pipeline parallelism (reference:
python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py —
forward_backward_pipeline :547 1F1B schedule; p2p layer
pp_utils/p2p_communication.py :570 _p2p_helper).

TPU redesign: the reference runs a host-driven 1F1B loop with explicit NCCL
send/recv per microbatch. On TPU the whole pipeline is ONE compiled program:
a lax.scan over time steps where every pp rank computes its stage and
activations rotate with lax.ppermute over the ICI ring. Differentiating the
scanned forward yields the reverse pipeline automatically — the backward
ppermutes are the transposes of the forward ones, so the compiler sees the
complete 1F1B dataflow and overlaps compute with neighbor transfers.

Layout: every pp rank holds L/P consecutive blocks, parameters stacked on a
leading layer axis sharded over 'pp'. Microbatch m enters stage 0 at t=m,
reaches stage d at t=m+d; total T = M + P - 1 steps (the pipeline bubble is
the same (P-1)/(M+P-1) fraction as the reference's 1F1B fill/drain).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from .....enforce import enforce
from jax import lax

from .....observability.trace import SCOPES

__all__ = ["spmd_pipeline", "spmd_pipeline_interleaved",
           "spmd_pipeline_zero_bubble", "pipeline_last_stage_value",
           "vpp_block_permutation", "vpp_chunk_blocks",
           "vpp_wrap_shard_params"]


@jax.named_scope(SCOPES.coll_pp)
def _ppermute(x, axis, perm):
    return lax.ppermute(x, axis, perm)


def vpp_block_permutation(num_layers: int, pp: int, vpp: int):
    """Stacked-block reorder for the interleaved schedule: position
    r·(V·cl) + v·cl + j holds global layer (v·pp + r)·cl + j, so each pp
    shard is [V, cl] chunk-major (reference: interleave chunk assignment,
    pp_layers.py PipelineLayerChunk). Model-agnostic — any family with a
    [L, ...]-stacked block pytree uses this."""
    enforce(num_layers % (pp * vpp) == 0,
            "num_layers must be divisible by pp*virtual_pp",
            op="spmd_pipeline", num_layers=num_layers, pp=pp, vpp=vpp)
    cl = num_layers // (pp * vpp)
    order = []
    for r in range(pp):
        for v in range(vpp):
            for j in range(cl):
                order.append((v * pp + r) * cl + j)
    return order


def vpp_chunk_blocks(blocks, vpp: int):
    """Reshape each local [V·cl, ...] block leaf to [V, cl, ...] for
    spmd_pipeline_interleaved."""
    return jax.tree.map(
        lambda b: b.reshape(vpp, b.shape[0] // vpp, *b.shape[1:]), blocks)


def vpp_wrap_shard_params(shard_params, num_layers: int, pp: int, vpp: int,
                          blocks_key: str = "blocks"):
    """Wrap a shard_params fn so the stacked blocks are permuted into the
    interleaved chunk-major layout before placement."""
    order = jnp.asarray(vpp_block_permutation(num_layers, pp, vpp))

    def wrapped(params):
        params = dict(params)
        params[blocks_key] = jax.tree.map(lambda b: b[order],
                                          params[blocks_key])
        return shard_params(params)

    return wrapped


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _replicate_from_last(x, axis: str):
    """Broadcast the last pp stage's value to all stages.

    Needs a custom vjp: a plain masked psum would deliver the SUM of the
    (identical, replicated) downstream cotangents to the last stage —
    scaling gradients by pp_degree. The correct transpose consumes the
    cotangent on the last stage only."""
    P = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    return lax.psum(jnp.where(idx == P - 1, x, jnp.zeros_like(x)), axis)


def _replicate_from_last_fwd(x, axis):
    return _replicate_from_last(x, axis), None


def _replicate_from_last_bwd(axis, res, g):
    P = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    return (jnp.where(idx == P - 1, g, jnp.zeros_like(g)),)


_replicate_from_last.defvjp(_replicate_from_last_fwd, _replicate_from_last_bwd)


def spmd_pipeline(stage_fn: Callable, stage_params, x_microbatches,
                  axis: str = "pp", checkpoint_stages: bool = True,
                  with_aux: bool = False):
    """Run a homogeneous-stage pipeline inside shard_map.

    stage_fn(stage_params_local, x) -> y with y.shape == x.shape
        (the per-rank segment: typically a lax.scan over L/P stacked blocks).
    stage_params: this rank's local (already sharded-in) parameter pytree.
    x_microbatches: [M, mb, ...] — microbatch inputs, replicated over `axis`
        (only stage 0 consumes them). M microbatches take T = M + P - 1
        ticks; with checkpoint_stages every tick's stage is run again in
        its backward, so that M ticks' residuals are never alive at once.
        At P = 1 that is M forwards, then M replays each with its backward:
        nothing is pipelined, and the hybrid builders (models/gpt.py,
        models/llama.py) accumulate their microbatches without this
        function on such a mesh; what rides with_aux still comes here.

    Returns [M, mb, ...] — outputs of the LAST stage, valid on every rank
    (zeros elsewhere are summed into place with one psum at the end).

    with_aux=True: stage_fn returns (y, aux_tree) instead — a side channel
    for per-stage scalars/stats that cannot ride the activation (the MoE
    load-balance loss and routing stats, whose producing layers live
    INSIDE the pipeline). Aux contributions are summed over the M VALID
    ticks of each rank (bubble iterations run the stage body on zeros and
    are masked out — their activations were always discarded; the mask
    extends that to the side channel) and psum'd over the pipe axis, so
    the returned aux tree is the sum over every (stage, microbatch)
    execution, replicated on all ranks. Returns (outputs, aux)."""
    P = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    M = x_microbatches.shape[0]
    T = M + P - 1

    fn = jax.checkpoint(stage_fn) if checkpoint_stages else stage_fn

    if with_aux:
        aux_shape = jax.eval_shape(stage_fn, stage_params,
                                   x_microbatches[0])[1]
        aux0 = _zb_pvary(jax.tree.map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype), aux_shape), axis)
    else:
        aux0 = ()

    def step(carry, t):
        state, outputs, aux_acc = carry
        # rotate activations one stage down the ring (stage d-1 -> d)
        prev = _ppermute(state, axis, [(i, i + 1) for i in range(P - 1)])
        inj = jnp.take(x_microbatches, jnp.clip(t, 0, M - 1), axis=0)
        inj = jnp.where(t < M, inj, jnp.zeros_like(inj))
        inp = jnp.where(idx == 0, inj, prev)
        if with_aux:
            out, aux = fn(stage_params, inp)
            # rank idx runs microbatch m = t - idx; everything else is
            # bubble compute on garbage
            valid = (t >= idx) & (t - idx < M)
            aux_acc = jax.tree.map(
                lambda a, v: a + jnp.where(valid, v, jnp.zeros_like(v)),
                aux_acc, aux)
        else:
            out = fn(stage_params, inp)
        # last stage emits microbatch m = t - (P-1)
        m = t - (P - 1)
        mc = jnp.clip(m, 0, M - 1)
        write = (m >= 0) & (idx == P - 1)
        cur = lax.dynamic_index_in_dim(outputs, mc, axis=0, keepdims=False)
        val = jnp.where(write, out, cur)
        outputs = lax.dynamic_update_index_in_dim(outputs, val, mc, axis=0)
        return (out, outputs, aux_acc), None

    out0 = _zb_pvary(jnp.zeros_like(x_microbatches), axis)
    state0 = _zb_pvary(jnp.zeros_like(x_microbatches[0]), axis)
    (_, outputs, aux_acc), _ = lax.scan(step, (state0, out0, aux0),
                                        jnp.arange(T))
    # replicate last-stage outputs to every rank (loss is computed SPMD)
    outputs = _replicate_from_last(outputs, axis)
    if with_aux:
        # psum-fwd / identity-bwd: the downstream cotangent is replicated
        # across the pipe ranks, so a raw psum's transpose would deliver
        # P times the aux-loss gradient (the _replicate_from_last lesson)
        from ...layers.mpu import mp_ops
        return outputs, jax.tree.map(
            lambda a: mp_ops.mp_allreduce(a, axis), aux_acc)
    return outputs


def spmd_pipeline_interleaved(stage_fn: Callable, stage_params_chunks,
                              x_microbatches, axis: str = "pp",
                              checkpoint_stages: bool = True):
    """Interleaved (virtual-stage / VPP) pipeline (reference:
    PipelineParallelWithInterleave, pipeline_parallel.py:1138; static pass
    pipeline_scheduler_pass/pipeline_vpp.py).

    Circular schedule: every rank holds V chunks of L/(P·V) layers
    (stage_params_chunks stacked [V, ...] per rank); a microbatch traverses
    ranks 0..P-1 for chunk 0, wraps back to rank 0 for chunk 1, etc.
    Token (v, m) runs on rank r at tick t = v·M + m + r; the rank-(P-1)
    output wraps to a rank-0 slot buffer until its chunk-(v+1) tick. The
    pipeline bubble shrinks from (P-1) full-stage steps to (P-1) CHUNK
    steps — the factor-V reduction that motivates VPP.

    Requires M >= P (same constraint as the reference's interleave mode).
    Returns the last chunk's outputs [M, mb, ...], valid on every rank.
    """
    P = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    M = x_microbatches.shape[0]
    V = jax.tree.leaves(stage_params_chunks)[0].shape[0]
    enforce(M >= P, f"interleaved schedule needs microbatches >= pp degree "
            f"({M} < {P})", op="spmd_pipeline_interleaved")
    T = V * M + P - 1

    fn = jax.checkpoint(stage_fn) if checkpoint_stages else stage_fn

    def step(carry, t):
        state, wrap_buf, outputs = carry
        # ONE circular permute: ranks > 0 read their predecessor ("prev"),
        # rank 0 reads rank P-1's value (the wrap) — halves the collective
        # count vs separate shift + wrap permutes on this hot loop
        rotated = _ppermute(state, axis,
                               [(i, (i + 1) % P) for i in range(P)])
        prev = rotated
        wrapped = rotated  # meaningful on rank 0 only

        # rank 0 consumes token (v0, m0) with v0*M + m0 == t
        m0 = t % M
        v0 = t // M
        stored = lax.dynamic_index_in_dim(wrap_buf, m0, axis=0,
                                          keepdims=False)
        # M == P edge: the wrap arrives in the very tick it is consumed
        m_w = (t - P) % M
        use_direct = (m_w == m0) & (v0 > 0)
        from_wrap = jnp.where(use_direct, wrapped, stored)
        inj = jnp.take(x_microbatches, m0, axis=0)
        rank0_in = jnp.where(v0 == 0, inj, from_wrap)
        inp = jnp.where(idx == 0, rank0_in, prev)

        # this rank's active chunk at tick t
        v_r = jnp.clip((t - idx) // M, 0, V - 1)
        params_v = jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(p, v_r, axis=0,
                                               keepdims=False),
            stage_params_chunks)
        out = fn(params_v, inp)

        # store the wrapped activation for its later chunk tick (rank 0)
        cur_w = lax.dynamic_index_in_dim(wrap_buf, m_w, axis=0,
                                         keepdims=False)
        new_w = jnp.where(idx == 0, wrapped, cur_w)
        wrap_buf = lax.dynamic_update_index_in_dim(wrap_buf, new_w, m_w,
                                                   axis=0)

        # last rank finishing chunk V-1 emits microbatch m_out
        m_out = t - (P - 1) - (V - 1) * M
        moc = jnp.clip(m_out, 0, M - 1)
        write = (m_out >= 0) & (m_out < M) & (idx == P - 1)
        cur_o = lax.dynamic_index_in_dim(outputs, moc, axis=0,
                                         keepdims=False)
        val = jnp.where(write, out, cur_o)
        outputs = lax.dynamic_update_index_in_dim(outputs, val, moc, axis=0)
        return (out, wrap_buf, outputs), None

    state0 = _zb_pvary(jnp.zeros_like(x_microbatches[0]), axis)
    wrap0 = _zb_pvary(jnp.zeros_like(x_microbatches), axis)
    out0 = _zb_pvary(jnp.zeros_like(x_microbatches), axis)
    (_, _, outputs), _ = lax.scan(step, (state0, wrap0, out0),
                                  jnp.arange(T))
    return _replicate_from_last(outputs, axis)


def pipeline_last_stage_value(value, axis: str = "pp"):
    """Broadcast a value computed on the last pp stage to all stages
    (reference: pipeline_parallel.py:1024 _broadcast_final_loss)."""
    return _replicate_from_last(value, axis)


# ---------------------------------------------------------------------------
# zero-bubble schedule (reference:
# python/paddle/distributed/passes/pipeline_scheduler_pass/
# pipeline_zero_bubble.py — ZB-H1: split the backward into activation-grad
# and weight-grad, schedule weight-grads into the pipeline bubble)
# ---------------------------------------------------------------------------

def _zb_pvary(x, axis):
    """Mark fresh constants device-varying over `axis` (shard_map vma).
    Leaves that are already varying (e.g. zeros_like of a varying input)
    pass through — pcast rejects varying→varying."""

    def mark(a):
        try:
            if hasattr(lax, "pcast"):
                return lax.pcast(a, (axis,), to="varying")
            if hasattr(lax, "pvary"):
                return lax.pvary(a, (axis,))
        except ValueError as e:
            # only the known benign case: the leaf is already varying
            if "varying" not in str(e):
                raise
        return a

    return jax.tree.map(mark, x)


def spmd_pipeline_zero_bubble(stage_fn: Callable, stage_params,
                              x_microbatches, axis: str = "pp"):
    """1F1B-parity pipeline with a hand-scheduled zero-bubble backward.

    The standard spmd_pipeline differentiates through the forward scan, so
    every backward tick pays dgrad+wgrad together and the cooldown ticks of
    early ranks idle. Here the backward is its own lockstep scan of
    T_b = 2M + P - 1 ticks in which each rank runs at most ONE half-unit
    per tick (lax.cond — devices genuinely branch under SPMD):

      rank r: dgrad for microbatch m at tick  (P-1-r) + m
              wgrad for microbatch m at tick  (P-1-r) + M + m

    so activation cotangents stream upstream at full rate while weight
    grads fill the ticks that were bubble in the fused schedule:
    2M + P - 1 half-unit ticks vs (M + P - 1) full-unit ticks
    (= 2M + 2P - 2 half-units) — the (P-1) backward bubble is gone.

    Cost note: dgrad and wgrad each recompute the stage forward (the
    forward saves only each microbatch's input), so the split trades one
    extra forward per microbatch for the bubble — the same trade the
    reference's ZB-H1 makes under recompute. Use `zbh1_speedup(pp, M)` for
    the break-even estimate before choosing the schedule.
    """
    return _zb(stage_fn, axis, stage_params, x_microbatches)


def zbh1_speedup(pp: int, num_microbatches: int,
                 fwd_fraction: float = 1 / 3) -> float:
    """Model-based ZB-H1 vs 1F1B step-time ratio (>1 = ZB-H1 wins).

    Under full remat a 1F1B tick costs 1 fwd + 1 (fwd+bwd) unit and idles
    (pp-1) ticks of bubble; ZB-H1 removes the backward bubble but re-runs
    the stage forward once more per microbatch (dgrad and wgrad each replay
    it). With f = fwd_fraction of a fused fwd+bwd unit (1/3 for the classic
    1:2 fwd:bwd split):

      t_1f1b  ~ (M + pp - 1) * (1 + f)           # fused units incl. bubble
      t_zbh1  ~ (M + pp - 1) * f                 # forward scan unchanged
               + (2M + pp - 1) * (1 + f) / 2     # half-unit backward ticks
                                                 #  (each replays a fwd)

    The crossover cannot be measured on this box (one chip; the CPU mesh
    timing does not model ICI), so the dryrun asserts parity and THIS
    estimate guides schedule choice: ZB-H1 pays off for small M/pp ratios
    (deep pipelines, few microbatches) and loses once M >> pp.
    """
    M, P = num_microbatches, pp
    f = fwd_fraction
    t_1f1b = (M + P - 1) * (1 + f)
    t_zb = (M + P - 1) * f + (2 * M + P - 1) * (1 + f) / 2
    return t_1f1b / t_zb


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _zb(stage_fn, axis, stage_params, x_microbatches):
    out, _ = _zb_fwd(stage_fn, axis, stage_params, x_microbatches)
    return out


def _zb_fwd(stage_fn, axis, stage_params, x_microbatches):
    P = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    M = x_microbatches.shape[0]
    T = M + P - 1

    def step(carry, t):
        state, outputs, saved = carry
        prev = _ppermute(state, axis, [(i, i + 1) for i in range(P - 1)])
        inj = jnp.take(x_microbatches, jnp.clip(t, 0, M - 1), axis=0)
        inj = jnp.where(t < M, inj, jnp.zeros_like(inj))
        inp = jnp.where(idx == 0, inj, prev)
        out = stage_fn(stage_params, inp)
        # this rank runs microbatch m at tick t = m + idx: save its input
        # (the only residual — dgrad/wgrad recompute the stage from it)
        m_in = t - idx
        mic = jnp.clip(m_in, 0, M - 1)
        live_in = (m_in >= 0) & (m_in < M)
        cur_s = lax.dynamic_index_in_dim(saved, mic, axis=0, keepdims=False)
        saved = lax.dynamic_update_index_in_dim(
            saved, jnp.where(live_in, inp, cur_s), mic, axis=0)
        # last stage emits microbatch m = t - (P-1)
        m = t - (P - 1)
        mc = jnp.clip(m, 0, M - 1)
        write = (m >= 0) & (idx == P - 1)
        cur = lax.dynamic_index_in_dim(outputs, mc, axis=0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, out, cur), mc, axis=0)
        return (out, outputs, saved), None

    out0 = _zb_pvary(jnp.zeros_like(x_microbatches), axis)
    state0 = _zb_pvary(jnp.zeros_like(x_microbatches[0]), axis)
    (_, outputs, saved), _ = lax.scan(step, (state0, out0, out0),
                                      jnp.arange(T))
    outputs = _replicate_from_last(outputs, axis)
    return outputs, (stage_params, saved)


def _zb_bwd(stage_fn, axis, res, g):
    stage_params, saved = res
    P = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    M = saved.shape[0]
    T_b = 2 * M + P - 1
    start = P - 1 - idx  # this rank's first dgrad tick

    def dgrad(x, ct):
        _, vjp_x = jax.vjp(lambda xx: stage_fn(stage_params, xx), x)
        return vjp_x(ct)[0]

    def wgrad(x, ct):
        _, vjp_p = jax.vjp(lambda pp: stage_fn(pp, x), stage_params)
        return vjp_p(ct)[0]

    wacc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                         stage_params)

    def step(carry, u):
        dx_prev, ct_buf, wacc, dx_inputs = carry
        # activation cotangents flow upstream (rank r+1 -> r); the last
        # rank injects the loss cotangent for its current microbatch
        ring = _ppermute(dx_prev, axis,
                            [(i, i - 1) for i in range(1, P)])
        m_d = u - start
        mdc = jnp.clip(m_d, 0, M - 1)
        live_d = (m_d >= 0) & (m_d < M)
        g_inj = jnp.take(g, mdc, axis=0)
        ct_in = jnp.where(idx == P - 1, g_inj, ring)
        x_d = lax.dynamic_index_in_dim(saved, mdc, axis=0, keepdims=False)
        dx = lax.cond(live_d, lambda: dgrad(x_d, ct_in),
                      lambda: jnp.zeros_like(dx_prev))
        # stash the cotangent for this microbatch's deferred wgrad
        cur_ct = lax.dynamic_index_in_dim(ct_buf, mdc, axis=0,
                                          keepdims=False)
        ct_buf = lax.dynamic_update_index_in_dim(
            ct_buf, jnp.where(live_d, ct_in, cur_ct), mdc, axis=0)
        # deferred wgrad fills the former bubble ticks
        m_w = u - start - M
        mwc = jnp.clip(m_w, 0, M - 1)
        live_w = (m_w >= 0) & (m_w < M)
        x_w = lax.dynamic_index_in_dim(saved, mwc, axis=0, keepdims=False)
        ct_w = lax.dynamic_index_in_dim(ct_buf, mwc, axis=0,
                                        keepdims=False)
        wacc = lax.cond(
            live_w,
            lambda w: jax.tree.map(
                lambda a, d: a + d.astype(a.dtype), w, wgrad(x_w, ct_w)),
            lambda w: w, wacc)
        # rank 0's dx is the cotangent of x_microbatches[m]
        cur_dx = lax.dynamic_index_in_dim(dx_inputs, mdc, axis=0,
                                          keepdims=False)
        dx_inputs = lax.dynamic_update_index_in_dim(
            dx_inputs, jnp.where(live_d & (idx == 0), dx, cur_dx), mdc,
            axis=0)
        return (dx, ct_buf, wacc, dx_inputs), None

    zeros_m = _zb_pvary(jnp.zeros_like(saved), axis)
    dx0 = _zb_pvary(jnp.zeros_like(saved[0]), axis)
    wacc0 = _zb_pvary(wacc0, axis)
    (_, _, wacc, dx_inputs), _ = lax.scan(
        step, (dx0, zeros_m, wacc0, zeros_m), jnp.arange(T_b))
    # x_microbatches is replicated over pp; only rank 0 contributed — psum
    # broadcasts its cotangent everywhere (zeros elsewhere)
    dx_inputs = lax.psum(dx_inputs, axis)
    dparams = jax.tree.map(lambda p, w: w.astype(p.dtype), stage_params,
                           wacc)
    return dparams, dx_inputs


_zb.defvjp(_zb_fwd, _zb_bwd)
