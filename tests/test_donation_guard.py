"""Donation guard (ISSUE 2 satellite): the sharded train step must donate
params + optimizer state, so the overlap path's extra buffers (fp32
accumulators, EF residuals) can't silently double HBM — without donation
XLA keeps the input AND output copies of every param/moment live across
the step boundary.

Asserted via the compiled executable's input/output aliasing (the
compiled-HLO form of jit's donate_argnums) rather than donation warnings,
which the CPU backend does not always emit."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import comm_overlap as co
from paddle_tpu.distributed.sharding.group_sharded import \
    build_sharded_train_step


def _job():
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(64, 32).astype(np.float32)),
              "b": jnp.zeros((32,), jnp.float32)}
    xs = jnp.asarray(rng.randn(16, 64).astype(np.float32))
    ys = jnp.asarray(rng.randn(16, 32).astype(np.float32))

    def loss_fn(p, x, y):
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    return params, xs, ys, loss_fn


def _aliased_bytes(compiled):
    """Donated input bytes of a compiled executable: prefer
    memory_analysis (exact), fall back to parsing input_output_alias out
    of the compiled HLO (always present when donation took effect)."""
    try:
        ma = compiled.memory_analysis()
        if ma is not None and getattr(ma, "alias_size_in_bytes", 0):
            return int(ma.alias_size_in_bytes)
    except Exception:
        pass
    txt = compiled.as_text()
    return (1 << 20) if "input_output_alias" in txt else 0


def _param_state_bytes(p, st):
    return sum(x.nbytes for x in jax.tree.leaves((p, st)))


def test_sharded_train_step_donates_params_and_state():
    mesh = dist.build_mesh({"sharding": 8})
    params, xs, ys, loss_fn = _job()
    opt = paddle.optimizer.AdamW(1e-3)
    step, place, compile_for = build_sharded_train_step(
        loss_fn, opt, mesh, level="os_g", data_axes=("sharding",))
    p, st = place(params)
    jstep, batch_sharding = compile_for(p)
    xs_s = jax.device_put(xs, batch_sharding)
    ys_s = jax.device_put(ys, batch_sharding)
    compiled = jstep.lower(p, st, xs_s, ys_s,
                           jnp.float32(1e-3)).compile()
    aliased = _aliased_bytes(compiled)
    assert aliased > 0, "params/opt state are NOT donated"
    # donation must actually take: inputs are consumed by the call
    out = jstep(p, st, xs_s, ys_s, jnp.float32(1e-3))
    jax.block_until_ready(out)
    assert all(x.is_deleted() for x in jax.tree.leaves(p)), \
        "donated params still alive after the step"


def test_sharded_microbatched_overlap_step_still_donates():
    """The overlap path adds fp32 scan accumulators; donation of params +
    state must survive it (the whole point of the guard)."""
    mesh = dist.build_mesh({"sharding": 8})
    params, xs, ys, loss_fn = _job()
    opt = paddle.optimizer.AdamW(1e-3)
    step, place, compile_for = build_sharded_train_step(
        loss_fn, opt, mesh, level="os_g", data_axes=("sharding",),
        microbatches=4)
    p, st = place(params)
    jstep, batch_sharding = compile_for(p)
    compiled = jstep.lower(p, st, jax.device_put(xs, batch_sharding),
                           jax.device_put(ys, batch_sharding),
                           jnp.float32(1e-3)).compile()
    assert _aliased_bytes(compiled) > 0


def test_fp8_train_step_donates_params_state_and_meta():
    """ISSUE 3 satellite: the fp8 train step adds an fp8_meta carry
    (scales + amax history); params, optimizer state AND the meta must
    all stay donated — the delayed-scaling bookkeeping may not cost a
    second resident copy of anything."""
    from paddle_tpu.quantization import fp8 as f8
    params, xs, ys, loss_fn = _job()
    opt = paddle.optimizer.AdamW(1e-3)

    def fp8_loss(p, scales, x, y):
        return jnp.mean(
            (f8.fp8_dot(x, p["w"], scales["gemm"]) + p["b"] - y) ** 2)

    meta = f8.init_fp8_meta(("gemm",))
    step = f8.make_fp8_train_step(fp8_loss, opt)
    state = jax.jit(opt.init_state)(params)
    lr = jnp.float32(1e-3)
    compiled = step.lower(params, state, meta, xs, ys, lr).compile()
    assert _aliased_bytes(compiled) > 0, \
        "fp8 step does NOT donate params/opt state/fp8_meta"
    out = step(params, state, meta, xs, ys, lr)
    jax.block_until_ready(out)
    assert all(x.is_deleted()
               for x in jax.tree.leaves((params, state, meta))), \
        "donated fp8 step inputs still alive after the step"


def test_hybrid_mp_overlap_steps_donate():
    """ISSUE 5 satellite: the seq-parallel and ring-collective-matmul
    step variants must keep donating params + optimizer state — the mp
    overlap exists to SHRINK activation memory, so silently losing
    donation (doubling params/moments) would more than cancel it."""
    from paddle_tpu.models import gpt as G
    mesh = dist.build_mesh({"dp": 2, "pp": 2, "mp": 2})
    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                      num_heads=4, max_seq_len=16, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (8, 16)))
    labels = jnp.asarray(np.random.RandomState(1).randint(0, 64, (8, 16)))
    for mode in ("seq_parallel", "collective_matmul"):
        opt = paddle.optimizer.AdamW(1e-3)
        from paddle_tpu.models.hybrid_engine import build_train_step
        from paddle_tpu.models.gpt import (hybrid_loss_fn,
                                           hybrid_param_specs,
                                           init_hybrid_params)
        from paddle_tpu.distributed.comm_overlap import MpOverlapConfig
        sp = MpOverlapConfig(mode)

        def lf(p, t, l, sp=sp):
            return hybrid_loss_fn(p, t, l, cfg, num_microbatches=2, sp=sp)

        step, shard, init = build_train_step(
            lf, hybrid_param_specs(cfg), mesh, opt,
            example_params=jax.eval_shape(
                lambda: init_hybrid_params(cfg, jax.random.PRNGKey(0))),
            mp_overlap=sp)
        p = shard(init_hybrid_params(cfg, jax.random.PRNGKey(0)))
        st = init(p)
        compiled = step.lower(p, st, tokens, labels,
                              jnp.float32(1e-3)).compile()
        assert _aliased_bytes(compiled) > 0, \
            f"{mode} step does NOT donate params/opt state"


def test_hybrid_overlap_step_memory_sane():
    """hybrid engine + EF residuals: compiled peak stays within a small
    multiple of params+state+grads (no silent HBM doubling from the
    overlap buffers)."""
    mesh = dist.build_mesh({"dp": 8})
    params, xs, ys, loss_fn = _job()
    specs = {"w": P(), "b": P()}
    from paddle_tpu.models.hybrid_engine import build_train_step
    opt = paddle.optimizer.AdamW(1e-3)
    step, shard, init = build_train_step(
        loss_fn, specs, mesh, opt,
        comm_overlap=co.CommOverlapConfig(bucket_mb=1e-4, quantize="int8"),
        example_params=jax.eval_shape(lambda: params))
    p = shard(params)
    st = init(p)
    compiled = step.lower(p, st, xs, ys, jnp.float32(1e-3)).compile()
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is None or not getattr(ma, "temp_size_in_bytes", 0):
        import pytest
        pytest.skip("backend exposes no memory analysis")
    budget = 8 * _param_state_bytes(p, st) + xs.nbytes + ys.nbytes
    assert ma.temp_size_in_bytes + ma.output_size_in_bytes < 4 * budget


# ---------------------------------------------------------------------------
# the hybrid train step owns its state (PR 53): the model builders donate
# (params, opt_state) unless the caller keeps its inputs
# ---------------------------------------------------------------------------
def _hybrid_job(family):
    if family == "gpt":
        from paddle_tpu.models import gpt as M
        cfg = M.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                          num_heads=4, max_seq_len=16, dtype=jnp.float32)
    else:
        from paddle_tpu.models import llama as M
        cfg = M.LlamaConfig(vocab_size=64, hidden_size=32, num_layers=4,
                            num_heads=4, num_kv_heads=2,
                            intermediate_size=64, max_seq_len=16,
                            dtype=jnp.float32)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 64, (8, 16)))
    labels = jnp.asarray(rng.randint(0, 64, (8, 16)))
    return M, cfg, tokens, labels


def _hybrid_build(M, cfg, **kw):
    """(step, the host tree, its sharded params, the state) on the dp2 x
    pp2 x mp2 CPU mesh, with the extras that ride opt_state when `kw` asks
    for them."""
    mesh = dist.build_mesh({"dp": 2, "pp": 2, "mp": 2})
    opt = paddle.optimizer.AdamW(1e-3)
    step, shard, init = M.build_hybrid_train_step(
        cfg, mesh, opt, num_microbatches=2, **kw)
    host = M.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    params = shard(host)
    return step, host, params, init(params)


def _buffers(tree):
    return [s.data.unsafe_buffer_pointer() for x in jax.tree.leaves(tree)
            for s in x.addressable_shards]


def _aliased_args(compiled):
    """The flat argument numbers that the compiled text aliases to an
    output (`input_output_alias` of the module's first line)."""
    header = compiled.as_text().split("\n", 1)[0]
    return sorted(int(i) for i in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header))


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_hybrid_step_owns_its_state(family):
    """A default-built hybrid step consumes the trees it is given and the
    compiled text aliases EVERY parameter and slot leaf to an output; the
    tree shard_params was given stays the caller's."""
    M, cfg, tokens, labels = _hybrid_job(family)
    step, host, params, state = _hybrid_build(M, cfg)
    # no buffer is named twice (jit would refuse the donation), and none
    # is the host tree's (a replicated leaf's shard on the device the
    # host array lives on is that array's buffer unless shard_params
    # copies it)
    ours = _buffers((params, state))
    assert len(set(ours)) == len(ours)
    assert not set(ours) & set(_buffers(host))
    lr = jnp.float32(1e-3)
    compiled = step.lower(params, state, tokens, labels, lr).compile()
    assert _aliased_args(compiled) == list(
        range(len(jax.tree.leaves((params, state)))))
    # and the aliased bytes are a device's whole share of the state
    first = jax.tree.leaves(params)[0].sharding.mesh.devices.flat[0]
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        s.data.nbytes for x in jax.tree.leaves((params, state))
        for s in x.addressable_shards if s.device == first)
    out = step(params, state, tokens, labels, lr)
    jax.block_until_ready(out)
    assert all(x.is_deleted() for x in jax.tree.leaves((params, state)))
    assert not any(x.is_deleted() for x in jax.tree.leaves(host))


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_hybrid_step_keeps_inputs_when_asked(family):
    """donate=False: no aliasing in the compiled text and the same trees
    feed the step again."""
    M, cfg, tokens, labels = _hybrid_job(family)
    step, _, params, state = _hybrid_build(M, cfg, donate=False)
    lr = jnp.float32(1e-3)
    compiled = step.lower(params, state, tokens, labels, lr).compile()
    assert "input_output_alias" not in compiled.as_text()
    _, _, a = step(params, state, tokens, labels, lr)
    _, _, b = step(params, state, tokens, labels, lr)
    assert not any(x.is_deleted() for x in jax.tree.leaves((params, state)))
    assert float(a) == float(b)


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_hybrid_donated_and_kept_builds_train_bitwise_alike(family):
    """One program text but for the aliasing: three rebinding steps of the
    donated build give the bits of the undonated build."""
    M, cfg, tokens, labels = _hybrid_job(family)
    lr = jnp.float32(1e-3)
    runs, texts = {}, {}
    for donate in (True, False):
        step, _, params, state = _hybrid_build(M, cfg, donate=donate)
        texts[donate] = step.lower(params, state, tokens, labels,
                                   lr).as_text()
        losses = []
        for _ in range(3):
            params, state, loss = step(params, state, tokens, labels, lr)
            losses.append(np.asarray(loss))
        runs[donate] = (losses, jax.tree.map(np.asarray, (params, state)))
    assert "jax.buffer_donor = true, " in texts[True]
    assert texts[True].replace("jax.buffer_donor = true, ", "") == \
        texts[False]
    assert [l.tobytes() for l in runs[True][0]] == \
        [l.tobytes() for l in runs[False][0]]
    for a, b in zip(jax.tree.leaves(runs[True][1]),
                    jax.tree.leaves(runs[False][1])):
        assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("rides", ["telemetry", "fp8", "zero1", "comm_ef"])
def test_what_rides_opt_state_is_donated_with_it(rides):
    """The telemetry ring, the fp8 meta, ZeRO's dp-sharded slots and the
    error-feedback residuals go where opt_state goes: every leaf of the
    carry aliases an output and is consumed by the step."""
    from paddle_tpu.models import gpt as M
    _, cfg, tokens, labels = _hybrid_job("gpt")
    from paddle_tpu.observability import TelemetryConfig
    kw = {"telemetry": {"telemetry": TelemetryConfig(interval=4)},
          "fp8": {"fp8": True},
          "zero1": {"zero_stage": 1},
          "comm_ef": {"comm_overlap": co.CommOverlapConfig(
              bucket_mb=1e-4, quantize="int8")}}[rides]
    step, _, params, state = _hybrid_build(M, cfg, **kw)
    ours = _buffers((params, state))
    assert len(set(ours)) == len(ours)
    lr = jnp.float32(1e-3)
    compiled = step.lower(params, state, tokens, labels, lr).compile()
    assert _aliased_args(compiled) == list(
        range(len(jax.tree.leaves((params, state)))))
    new_params, new_state, loss = step(params, state, tokens, labels, lr)
    assert np.isfinite(float(loss))
    assert all(x.is_deleted() for x in jax.tree.leaves((params, state)))
    # and the step after takes what this one gave back
    _, _, loss = step(new_params, new_state, tokens, labels, lr)
    assert np.isfinite(float(loss))


def test_run_resilient_refuses_to_keep_a_consumed_state(tmp_path):
    """The resilient loop falls back to the state a rejected step was
    given; a step that donated it is told so, by name, at the rejection
    (not by a deleted-array error one step later)."""
    from paddle_tpu.distributed.resilience import run_resilient
    from paddle_tpu.enforce import PreconditionNotMetError
    poison = jax.jit(lambda w: w * jnp.nan, donate_argnums=0)

    def step_fn(st, i):
        w = poison(st["w"])
        return {"w": w}, jnp.sum(w)

    with pytest.raises(PreconditionNotMetError, match="donate=False"):
        run_resilient(step_fn, {"w": jnp.ones((4,))}, steps=2,
                      ckpt_dir=str(tmp_path), ckpt_every=0, resume=False)
