"""Benchmark: GPT pretraining throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
A chip entry: it refuses to run when jax finds no TPU — a CPU timing is
never written under a device metric's name.

Config (BASELINE.json configs[2] class): GPT-3 1.3B — L=24, H=2048,
16 heads (head_dim 128: full-width MXU contractions), vocab 32768,
seq 1024, batch 8. bf16 params + bf16 Adam moments (update in fp32 —
optimizer.py moment_dtype), full per-block rematerialization, buffer
donation keeps one copy of params/state resident.

Metric: tokens/sec/chip for the full train step (fwd + bwd + AdamW).
vs_baseline = achieved_MFU / 0.45 (the north-star MFU target from
BASELINE.json; the reference publishes no absolute numbers).

Not measured on the current installation: see PERF.md. chip_smoke.py is
the quick proof that this step runs on the chip.
"""

import functools
import json
import time

import numpy as np


FLAGSHIP = dict(vocab_size=32768, hidden_size=2048, num_layers=24,
                num_heads=16, max_seq_len=1024, batch=8, seq=1024)
SECONDARY = dict(vocab_size=32768, hidden_size=1024, num_layers=16,
                 num_heads=16, max_seq_len=1024, batch=16, seq=1024)


def _config_hash(c):
    import hashlib
    return hashlib.sha1(json.dumps(c, sort_keys=True).encode()).hexdigest()[:8]


def _run_config(jax, paddle, G, conf, iters):
    import jax.numpy as jnp

    batch, seq = conf["batch"], conf["seq"]
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=conf["max_seq_len"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)

    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4,
        moment_dtype=jnp.bfloat16)
    state = jax.jit(opt.init_state)(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: G.dense_loss(p, tokens, labels, cfg))(params)
        params, state = opt.apply(params, grads, state, 1e-4)
        return params, state, loss

    # fixed pre-built batch in the timed loop: the frozen config_hash
    # series stays measured EXACTLY as in prior rounds (pure step time,
    # no per-iteration host batch synthesis). The prefetch_to_device
    # input pipeline is exercised/timed in _run_overlap_config instead.
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))

    # warmup/compile, timed SEPARATELY (compile_s) so steady-state step
    # time — the metric overlap work moves — is never masked or inflated
    # by warmup
    tc0 = time.perf_counter()
    params, state, loss = step(params, state, tokens, labels)
    float(loss)
    compile_s = time.perf_counter() - tc0

    t0 = time.perf_counter()
    for _ in range(iters):
        params, state, loss = step(params, state, tokens, labels)
    float(loss)  # forces completion of the whole chain
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * iters / dt

    # analytic FLOPs/token + peak from the observability subsystem (the
    # one copy of the 6N + 12LHS accounting; exact-N from the live params
    # keeps this frozen series bit-identical to prior rounds)
    from paddle_tpu.observability import flops as _flops
    n_params = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params))
    flops_per_token = _flops.gpt_flops_per_token(cfg, seq,
                                                 params=params)["model"]
    mfu = _flops.mfu(tokens_per_sec, flops_per_token,
                     _flops.peak_flops(jax.devices()))
    return tokens_per_sec, mfu, n_params, compile_s


def _run_overlap_config(jax, paddle, G, conf, iters):
    """Bucketed/overlapped + quantized dp grad sync vs the monolithic
    pmean, on a dp mesh over every local device, with the comms share of
    the step measured directly (same step with dp sync skipped)."""
    import jax.numpy as jnp
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.comm_overlap import CommOverlapConfig
    from paddle_tpu.io import prefetch_to_device
    from paddle_tpu.models.hybrid_engine import build_train_step
    from jax.sharding import PartitionSpec as P

    n_dev = len(jax.devices())
    mesh = dist.build_mesh({"dp": n_dev})
    batch, seq = conf["batch"], conf["seq"]
    batch = max(batch, n_dev)  # at least one sample per dp rank
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=conf["max_seq_len"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    specs = jax.tree.map(lambda _: P(), params)
    example = jax.eval_shape(lambda: params)

    def loss_fn(p, tokens, labels):
        return G.dense_loss(p, tokens, labels, cfg)

    class _NoSync:  # measurement probe: same step minus the dp collectives
        def __init__(self, inner):
            self._inner = inner
            self._skips_grad_sync = True

        def __getattr__(self, item):
            return getattr(self._inner, item)

    rng = np.random.RandomState(0)

    def timed(comm_overlap, no_sync=False):
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-4,
            moment_dtype=jnp.bfloat16)
        if no_sync:
            opt = _NoSync(opt)
        step, shard, init = build_train_step(
            loss_fn, specs, mesh, opt, example_params=example,
            comm_overlap=comm_overlap)
        p = shard(params)
        st = init(p)
        feed = prefetch_to_device(
            ((rng.randint(0, cfg.vocab_size, (batch, seq)),
              rng.randint(0, cfg.vocab_size, (batch, seq)))
             for _ in range(iters + 2)))
        tokens, labels = next(feed)
        tc0 = time.perf_counter()
        p, st, loss = step(p, st, tokens, labels, jnp.float32(1e-4))
        float(loss)
        compile_s = time.perf_counter() - tc0
        t0 = time.perf_counter()
        for _ in range(iters):
            tokens, labels = next(feed)
            p, st, loss = step(p, st, tokens, labels, jnp.float32(1e-4))
        float(loss)
        return (time.perf_counter() - t0) / iters, compile_s

    t_mono, compile_mono = timed(None)
    t_nosync, _ = timed(None, no_sync=True)
    t_bucket, compile_bucket = timed(CommOverlapConfig(bucket_mb=4.0))
    t_int8, _ = timed(CommOverlapConfig(bucket_mb=4.0, quantize="int8"))
    comms_fraction = max(0.0, 1.0 - t_nosync / t_mono)
    toks = batch * seq / t_bucket
    return {
        "config_hash": _config_hash(conf),
        "devices": n_dev,
        "tokens_per_sec_bucketed": round(toks, 1),
        "step_ms": {"monolithic": round(t_mono * 1e3, 2),
                    "no_dp_sync": round(t_nosync * 1e3, 2),
                    "bucketed": round(t_bucket * 1e3, 2),
                    "int8_ef": round(t_int8 * 1e3, 2)},
        "comms_fraction": round(comms_fraction, 4),
        "compile_s": {"monolithic": round(compile_mono, 2),
                      "bucketed": round(compile_bucket, 2)},
    }


def _run_fp8_config(jax, paddle, G, conf, iters, parity_steps=50):
    """bf16 vs delayed-scaling fp8 GEMMs on the dense single-chip path
    (FLAGS_fp8 / quantization/fp8.py): steady-state step time for both,
    plus loss parity over `parity_steps` training steps from the same
    init/batch (the acceptance gate: <= 2e-2 relative at the last step).
    On CPU the float8 dtypes are emulated, so step-time there measures
    bookkeeping overhead only — the MXU speedup needs hardware."""
    import jax.numpy as jnp
    from paddle_tpu.quantization import fp8 as f8

    batch, seq = conf["batch"], conf["seq"]
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=conf["max_seq_len"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    params0 = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))

    def make_opt():
        return paddle.optimizer.AdamW(
            learning_rate=1e-4,
            moment_dtype=jnp.bfloat16)

    def run(fp8, steps):
        opt = make_opt()
        # fresh param buffers per run — both steps donate their carries
        params = jax.tree.map(jnp.copy, params0)
        state = jax.jit(opt.init_state)(params)
        if fp8:
            meta = f8.init_fp8_meta(G.GPT_FP8_SITES, cfg.num_layers)
            step = f8.make_fp8_train_step(
                lambda p, s, t, l: G.dense_loss(p, t, l, cfg, fp8=s), opt)
            carry = (params, state, meta)

            def one(carry):
                p, st, m = carry
                p, st, m, loss = step(p, st, m, tokens, labels,
                                      jnp.float32(1e-4))
                return (p, st, m), loss
        else:
            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def step(p, st, t, l):
                loss, grads = jax.value_and_grad(
                    lambda p: G.dense_loss(p, t, l, cfg))(p)
                p, st = opt.apply(p, grads, st, 1e-4)
                return p, st, loss
            carry = (params, state)

            def one(carry):
                p, st = carry
                p, st, loss = step(p, st, tokens, labels)
                return (p, st), loss

        tc0 = time.perf_counter()
        carry, loss = one(carry)
        losses = [float(loss)]  # forces completion
        compile_s = time.perf_counter() - tc0
        # exactly `steps` total steps regardless of iters: the timed
        # window is capped so the parity gate always measures the step
        # count it reports
        timed = min(iters, steps - 1)
        t0 = time.perf_counter()
        for _ in range(timed):
            carry, loss = one(carry)
        float(loss)
        dt = (time.perf_counter() - t0) / max(timed, 1)
        for _ in range(steps - 1 - timed):
            carry, loss = one(carry)
        losses.append(float(loss))
        return dt, compile_s, losses

    t_bf16, compile_bf16, l_bf16 = run(False, parity_steps)
    t_fp8, compile_fp8, l_fp8 = run(True, parity_steps)
    rel = abs(l_fp8[-1] - l_bf16[-1]) / max(abs(l_bf16[-1]), 1e-9)
    return {
        "config_hash": _config_hash(conf),
        "step_ms": {"bf16": round(t_bf16 * 1e3, 2),
                    "fp8": round(t_fp8 * 1e3, 2)},
        "speedup": round(t_bf16 / t_fp8, 3),
        "compile_s": {"bf16": round(compile_bf16, 2),
                      "fp8": round(compile_fp8, 2)},
        "loss_final": {"bf16": round(l_bf16[-1], 4),
                       "fp8": round(l_fp8[-1], 4)},
        "loss_rel_diff": round(rel, 5),
        "loss_parity_ok": bool(rel <= 2e-2),
        "parity_steps": parity_steps,
    }


def _run_mp_overlap_config(jax, paddle, G, conf, iters):
    """Tensor-parallel mp-axis overlap (FLAGS_mp_seq_parallel /
    FLAGS_mp_collective_matmul): hybrid-engine step time for the
    allreduce baseline vs sequence-parallel vs ring collective-matmul on
    a dp x mp mesh, plus the activation-memory delta (compiled
    temp_size) that sequence parallelism exists to buy. Needs an mp
    degree dividing the device count (skipped on one chip)."""
    import jax.numpy as jnp
    import paddle_tpu.distributed as dist

    n_dev = len(jax.devices())
    mp = next((m for m in (4, 2) if n_dev % m == 0), None)
    if mp is None:
        return {"skipped": f"needs a device count divisible by 2 for an "
                           f"mp axis, have {n_dev}"}
    dp = n_dev // mp
    mesh = dist.build_mesh({"dp": dp, "pp": 1, "mp": mp})
    batch, seq = conf["batch"], conf["seq"]
    # 2 microbatches per dp rank, batch divisible by both
    batch = 2 * dp * max(1, batch // (2 * dp))
    seq = (seq // mp) * mp
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=max(conf["max_seq_len"], seq),
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    lr = jnp.float32(1e-4)

    def timed(mode):
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-4,
            moment_dtype=jnp.bfloat16)
        step, shard, init = G.build_hybrid_train_step(
            cfg, mesh, opt, num_microbatches=2, mp_overlap=mode)
        p = shard(params)
        st = init(p)
        # ONE AOT compile serves both the memory_analysis and the timed
        # loop (jit's own call cache would compile the same program a
        # second time)
        tc0 = time.perf_counter()
        compiled = step.lower(p, st, tokens, labels, lr).compile()
        compile_s = time.perf_counter() - tc0
        # activation/temp memory of the compiled step: what the
        # seq-sharded residual stream + 1/mp saved activations buy
        temp = int(compiled.memory_analysis().temp_size_in_bytes)
        p, st, loss = compiled(p, st, tokens, labels, lr)  # warmup
        float(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            p, st, loss = compiled(p, st, tokens, labels, lr)
        float(loss)
        return (time.perf_counter() - t0) / iters, compile_s, temp

    t_ar, c_ar, m_ar = timed(None)
    t_sp, c_sp, m_sp = timed("seq_parallel")
    t_cm, c_cm, m_cm = timed("collective_matmul")
    return {
        "config_hash": _config_hash(conf),
        "devices": n_dev,
        "mesh": {"dp": n_dev // mp, "pp": 1, "mp": mp},
        "step_ms": {"allreduce": round(t_ar * 1e3, 2),
                    "seq_parallel": round(t_sp * 1e3, 2),
                    "collective_matmul": round(t_cm * 1e3, 2)},
        "compile_s": {"allreduce": round(c_ar, 2),
                      "seq_parallel": round(c_sp, 2),
                      "collective_matmul": round(c_cm, 2)},
        "temp_bytes": {"allreduce": m_ar, "seq_parallel": m_sp,
                       "collective_matmul": m_cm},
        "activation_delta_bytes": m_ar - m_sp,
    }


def _run_flash_training_config(jax, paddle, G, conf, iters):
    """Training-grade flash attention (FLAGS_flash_attention): hybrid
    step time + compiled temp bytes for the composed-einsum baseline vs
    the fused kernel on a dp x mp mesh, the analytic attention-FLOPs
    share (einsum vs flash executed passes — flash runs MORE flops and
    buys O(S) memory), and a long-S planner run showing the
    activation-HBM prune delta the flash axis exists for. On the CPU
    smoke the kernel runs in interpreter mode — step times measure the
    interpreter, not the MXU; the memory and planner rows are the
    meaningful CPU signals."""
    import jax.numpy as jnp
    import paddle_tpu.distributed as dist
    from paddle_tpu.observability import flops as FL

    n_dev = len(jax.devices())
    mp = next((m for m in (2, 4) if n_dev % m == 0
               and conf["num_heads"] % m == 0), None)
    if mp is None:
        return {"skipped": f"needs an mp degree dividing devices "
                           f"({n_dev}) and heads ({conf['num_heads']})"}
    dp = n_dev // mp
    mesh = dist.build_mesh({"dp": dp, "pp": 1, "mp": mp})
    batch, seq = conf["batch"], conf["seq"]
    batch = 2 * dp * max(1, batch // (2 * dp))
    seq = max(128, (seq // 128) * 128)  # kernel lane tiles
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=max(conf["max_seq_len"], seq),
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    lr = jnp.float32(1e-4)

    def timed(flash):
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-4,
            moment_dtype=jnp.bfloat16)
        step, shard, init = G.build_hybrid_train_step(
            cfg, mesh, opt, num_microbatches=2, flash_attention=flash)
        p = shard(params)
        st = init(p)
        tc0 = time.perf_counter()
        compiled = step.lower(p, st, tokens, labels, lr).compile()
        compile_s = time.perf_counter() - tc0
        temp = int(compiled.memory_analysis().temp_size_in_bytes)
        p, st, loss = compiled(p, st, tokens, labels, lr)  # warmup
        float(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            p, st, loss = compiled(p, st, tokens, labels, lr)
        float(loss)
        return (time.perf_counter() - t0) / iters, compile_s, temp

    t_e, c_e, m_e = timed(None)
    t_f, c_f, m_f = timed(True)

    # analytic attention share: executed passes per token, einsum vs
    # flash (observability.flops.attention_flops_per_token — the same
    # term the planner scores the flash axis with)
    a_e = FL.attention_flops_per_token(
        num_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        seq_len=seq, impl="einsum", remat="full")
    a_f = FL.attention_flops_per_token(
        num_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        seq_len=seq, impl="flash", remat="full")
    total = FL.gpt_flops_per_token(cfg, seq, params=params,
                                   remat="full")["hardware"]

    # planner: at long S under the v5e 16 GB budget the einsum twin's
    # rematted-scores term OOM-prunes configs the flash estimate admits
    from paddle_tpu.distributed.auto_tuner import planner as PL
    pcfg = G.gpt_1p3b()
    long_seq = 4096
    spec = PL.ModelSpec.from_config(pcfg, "gpt")
    cm = PL.CostModel(spec, PL.KNOWN_PROFILES["tpu-v5e"],
                      global_batch=8, seq=long_seq)
    c_base = PL.PlanCandidate(dp=1, mp=8)
    c_fl = PL.PlanCandidate(dp=1, mp=8, flash_attention=True)
    p_base, p_fl = cm.predict(c_base), cm.predict(c_fl)
    rep = PL.plan(pcfg, world=8, global_batch=8, seq=long_seq,
                  family="gpt", profile=PL.KNOWN_PROFILES["tpu-v5e"])
    n_fl = sum(1 for s in rep.ranked if s.candidate.flash_attention)
    n_es = len(rep.ranked) - n_fl
    pruned_hbm_es = sum(
        1 for c, r in rep.pruned
        if "analytic HBM" in r and not c.flash_attention)
    pruned_hbm_fl = sum(
        1 for c, r in rep.pruned
        if "analytic HBM" in r and c.flash_attention)
    return {
        "config_hash": _config_hash(conf),
        "devices": n_dev,
        "mesh": {"dp": dp, "pp": 1, "mp": mp},
        "seq": seq,
        "step_ms": {"einsum": round(t_e * 1e3, 2),
                    "flash": round(t_f * 1e3, 2)},
        "compile_s": {"einsum": round(c_e, 2), "flash": round(c_f, 2)},
        "temp_bytes": {"einsum": m_e, "flash": m_f},
        "temp_bytes_delta": m_e - m_f,
        "attn_flops": {
            "einsum_hw_per_token": a_e["hardware"],
            "flash_hw_per_token": a_f["hardware"],
            "flash_over_einsum": round(a_f["hardware"] / a_e["hardware"],
                                       4),
            "einsum_share_of_step": round(a_e["hardware"] / total, 4),
        },
        "plan_long_seq": {
            "model": "gpt1p3b", "seq": long_seq, "hbm_gb": 16.0,
            "act_gb": {"einsum": round(p_base.hbm["act"] / 1e9, 3),
                       "flash": round(p_fl.hbm["act"] / 1e9, 3)},
            "step_s": {"einsum": round(p_base.step_s, 4),
                       "flash": round(p_fl.step_s, 4)},
            "valid": {"einsum": n_es, "flash": n_fl},
            "hbm_pruned": {"einsum": pruned_hbm_es,
                           "flash": pruned_hbm_fl},
        },
    }


def _run_moe_config(jax, paddle, G, conf, iters):
    """GPT-MoE through the hybrid engine on a dp x ep x mp mesh
    (FLAGS_moe_index_dispatch / FLAGS_moe_quantize_a2a / FLAGS_moe_overlap):
    dense-dispatch baseline vs zero-flop index dispatch vs the
    int8-EF quantized + chunk-overlapped all-to-all, with the analytic
    dispatch-flop delta and per-rank a2a wire bytes stated alongside."""
    import jax.numpy as jnp
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.comm_overlap import MoeDispatchConfig
    from paddle_tpu.observability import ep_a2a_wire_bytes
    from paddle_tpu.observability import flops as _flops

    n_dev = len(jax.devices())
    if n_dev < 4 or n_dev % 4 != 0:
        return {"skipped": f"needs a device count divisible by 4 for a "
                           f"dp x ep2 x mp2 mesh, have {n_dev}"}
    ep, mp = 2, 2
    dp = n_dev // (ep * mp)
    mesh = dist.build_mesh({"dp": dp, "ep": ep, "pp": 1, "mp": mp})
    batch, seq = conf["batch"], conf["seq"]
    batch = dp * ep * max(1, batch // (dp * ep))
    E = 8
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=conf["max_seq_len"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
        moe_num_experts=E, moe_capacity_factor=2.0)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    lr = jnp.float32(1e-4)
    b_rank = batch // (dp * ep)
    T = b_rank * seq
    # the ONE copy of the MoE flop math (planner + bench share it;
    # tests assert it equals the former inline formulas bit-for-bit)
    moe_fl = _flops.gpt_moe_flops_per_token(cfg, tokens_per_rank=T, mp=mp)
    C = int(moe_fl["capacity"])
    H = cfg.hidden_size
    dt = 2

    def timed(dispatch, **kw):
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-4,
            moment_dtype=jnp.bfloat16)
        step, shard, init = G.build_hybrid_train_step(
            cfg, mesh, opt, num_microbatches=1, moe_dispatch=dispatch,
            **kw)
        p = shard(params)
        st = init(p)
        tc0 = time.perf_counter()
        p, st, loss = step(p, st, tokens, labels, lr)
        float(loss)
        compile_s = time.perf_counter() - tc0
        t0 = time.perf_counter()
        for _ in range(iters):
            p, st, loss = step(p, st, tokens, labels, lr)
        float(loss)
        return (time.perf_counter() - t0) / iters, compile_s

    t_dense, c_dense = timed(None)
    t_index, c_index = timed(MoeDispatchConfig(index=True))
    t_qovl, c_qovl = timed(
        MoeDispatchConfig(index=True, quantize=True, overlap=True,
                          chunks=2),
        moe_ef_tokens=(b_rank, seq))

    # per-rank expert-GEMM flops/step: each rank's local expert shard
    # processes all E*C capacity slots of its ep group after the a2a
    # (padding slots do real MXU work), 2 GEMMs of H x FF/mp each,
    # fwd + 2x bwd, L2 MoE layers (observability.flops owns the math)
    expert_flops = moe_fl["expert_gemm_flops_per_rank_step"]
    L2 = cfg.num_layers // 2
    peak = _flops.peak_flops(jax.devices())
    payload = float(E * C * H)
    return {
        "config_hash": _config_hash(conf),
        "mesh": {"dp": dp, "ep": ep, "pp": 1, "mp": mp},
        "experts": E, "capacity_per_rank": C,
        "step_ms": {"dense_dispatch": round(t_dense * 1e3, 2),
                    "index_dispatch": round(t_index * 1e3, 2),
                    "int8_ef_overlapped_a2a": round(t_qovl * 1e3, 2)},
        "compile_s": {"dense_dispatch": round(c_dense, 2),
                      "index_dispatch": round(c_index, 2),
                      "int8_ef_overlapped_a2a": round(c_qovl, 2)},
        "expert_gemm_mfu_pct": {
            "index_dispatch": round(
                100.0 * expert_flops / (t_index * peak), 2),
            "int8_ef_overlapped_a2a": round(
                100.0 * expert_flops / (t_qovl * peak), 2)},
        # the 2*T*E*C*D one-hot einsum the index dispatch deletes —
        # PER dispatch AND combine, fwd (backward re-runs both)
        "dense_dispatch_flops_per_moe_layer":
            moe_fl["dense_dispatch_flops_per_moe_layer"],
        "a2a_bytes_per_step_per_rank": {
            "wire_dtype": "bf16",
            "unquantized_wire": ep_a2a_wire_bytes(
                ep, payload_elems=payload, n_layer_executions=float(L2),
                itemsize=dt),
            "int8_wire": ep_a2a_wire_bytes(
                ep, payload_elems=payload, n_layer_executions=float(L2),
                itemsize=dt, quantize=True)},
    }


def _run_telemetry_config(jax, paddle, G, conf, iters,
                          comms_fraction=None):
    """Step accounting through the observability StepTimer: compile vs
    steady split, per-phase (data-wait vs device step) ms breakdown, MFU
    from the analytic FLOPs model, and the measured comms fraction from
    the overlap probe — the 'where does step time go' section."""
    import jax.numpy as jnp
    from paddle_tpu.io import prefetch_to_device
    from paddle_tpu.observability import StepTimer, flops as _flops

    batch, seq = conf["batch"], conf["seq"]
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=conf["max_seq_len"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    fpt = _flops.gpt_flops_per_token(cfg, seq, params=params)
    fpt_hw = _flops.gpt_flops_per_token(cfg, seq, params=params,
                                        remat="full")
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, moment_dtype=jnp.bfloat16)
    state = jax.jit(opt.init_state)(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: G.dense_loss(p, tokens, labels, cfg))(params)
        params, state = opt.apply(params, grads, state, 1e-4)
        return params, state, loss

    timer = StepTimer(tokens_per_step=batch * seq,
                      flops_per_token=fpt["model"],
                      peak_flops=_flops.peak_flops(jax.devices()))
    rng = np.random.RandomState(0)
    feed = prefetch_to_device(
        ((rng.randint(0, cfg.vocab_size, (batch, seq)),
          rng.randint(0, cfg.vocab_size, (batch, seq)))
         for _ in range(iters + 1)))
    for _ in range(iters + 1):
        with timer.phase("data"):
            tokens, labels = next(feed)
        with timer.step():  # first completed step records compile_s
            params, state, loss = step(params, state, jnp.asarray(tokens),
                                       jnp.asarray(labels))
            float(loss)
    if comms_fraction is not None:
        timer.set_comms_fraction(comms_fraction)
    report = timer.report()
    report["config_hash"] = _config_hash(conf)
    report["flops_per_token"] = {"model": fpt["model"],
                                 "hardware_full_remat": fpt_hw["hardware"]}
    return report


def _run_zero_stages_config(jax, paddle, G, conf, iters):
    """ZeRO stage axis (FLAGS_zero_stage): per-stage hybrid step time on
    the dp4 x mp2 smoke mesh, the spec-derived per-chip params/opt bytes
    (grads are transient in the fused program; stage 2's dp-sharded
    accounting shows up in the planner's HBM rule), and the analytic
    per-step zero3 param-AG wire bytes fp32 vs int8 — the structural
    unlock this section tracks is params/chip scaling ~1/dp at rest."""
    import time

    import jax.numpy as jnp
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.hbm_audit import per_device_bytes
    from paddle_tpu.models.hybrid_engine import zero_dims
    from paddle_tpu.observability.metrics import zero3_ag_wire_bytes

    batch, seq = conf["batch"], conf["seq"]
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=conf["max_seq_len"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    mesh = dist.build_mesh({"dp": 4, "pp": 1, "mp": 2})
    params0 = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    pshape = jax.eval_shape(
        lambda: G.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))

    out = {"config_hash": _config_hash(conf),
           "mesh": {"dp": 4, "mp": 2}, "stages": {}}
    losses = {}
    for stage in (0, 1, 2, 3):
        opt = paddle.optimizer.AdamW(learning_rate=1e-3)
        step, shard_params, init_state = G.build_hybrid_train_step(
            cfg, mesh, opt, num_microbatches=1, telemetry=None,
            zero_stage=stage)
        p = shard_params(params0)
        s = init_state(p)
        p, s, loss = step(p, s, tokens, labels, jnp.float32(1e-3))
        float(loss)  # compile + settle
        t0 = time.perf_counter()
        for _ in range(iters):
            p, s, loss = step(p, s, tokens, labels, jnp.float32(1e-3))
        losses[stage] = float(loss)
        dt = (time.perf_counter() - t0) / iters
        param_b = per_device_bytes(pshape, init_state.param_specs, mesh)
        sshape = jax.eval_shape(opt.init_state, pshape)
        opt_b = per_device_bytes(sshape, init_state.state_specs, mesh)
        out["stages"][f"zero{stage}"] = {
            "step_ms": round(dt * 1e3, 2),
            "per_chip_param_bytes": int(param_b),
            "per_chip_opt_bytes": int(opt_b),
        }
    # stage-3 parity gate: the bench never reports a broken program
    assert abs(losses[3] - losses[0]) < 5e-4 * max(abs(losses[0]), 1), \
        losses
    r0 = out["stages"]["zero0"]
    r3 = out["stages"]["zero3"]
    out["param_bytes_ratio_zero3_vs_plain"] = round(
        r3["per_chip_param_bytes"] / r0["per_chip_param_bytes"], 4)

    # analytic per-step zero3 AG wire, fp vs int8 (the EQuARX ~2x-vs-bf16
    # operating point applied to the param gather)
    specs = G.hybrid_param_specs(cfg)
    zd = zero_dims(specs, pshape, mesh, "dp")
    item = jnp.dtype(cfg.param_dtype).itemsize
    # the ONE shard-product rule (hbm_audit) applied per dp-shardable
    # leaf: bytes local to the mp/pp shards, full over dp
    blk = sum(per_device_bytes(l, sp, mesh)
              for l, sp, z in zip(jax.tree.leaves(pshape["blocks"]),
                                  jax.tree.leaves(specs["blocks"]),
                                  jax.tree.leaves(zd["blocks"]))
              if z >= 0)
    other = sum(per_device_bytes(pshape[k], specs[k], mesh)
                for k in ("wte", "wpe", "lnf_g", "lnf_b", "head_w")
                if zd[k] >= 0)
    out["zero3_ag_wire_bytes_per_step"] = {
        "fp": int(zero3_ag_wire_bytes(4, block_param_bytes=blk,
                                      n_stage_executions=1.0,
                                      other_param_bytes=other)),
        "int8": int(zero3_ag_wire_bytes(4, block_param_bytes=blk,
                                        n_stage_executions=1.0,
                                        other_param_bytes=other,
                                        quantize=True,
                                        param_itemsize=item)),
    }
    return out


def _run_numerics_config(jax, paddle, G, conf, iters):
    """Numerics observability (FLAGS_numerics): flags-on vs flags-off
    hybrid step time on the dp4 x mp2 smoke mesh — the overhead of the
    in-program tensor-health series (per-layer grad norms + activation
    rms/absmax riding the telemetry ring, host poll every interval
    included in the timed loop). Target: < 3% step-time overhead; also
    reports the registered series count and a sample of the decoded
    per-layer stats so rounds can see the path is live."""
    import time

    import jax.numpy as jnp
    import paddle_tpu.distributed as dist
    from paddle_tpu import observability as obs

    batch, seq = max(conf["batch"], 8), conf["seq"]  # dp4 divisibility
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=conf["max_seq_len"],
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    mesh = dist.build_mesh({"dp": 4, "pp": 1, "mp": 2})
    params0 = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    interval = 10

    def timed(telemetry, numerics):
        step, shard_params, init_state = G.build_hybrid_train_step(
            cfg, mesh, paddle.optimizer.AdamW(learning_rate=1e-3),
            num_microbatches=1, telemetry=telemetry, numerics=numerics)
        # host AFTER the build: the engine registers the numerics series
        # onto the config
        host = (obs.TelemetryHost(telemetry) if telemetry is not None
                else None)
        p = shard_params(params0)
        s = init_state(p)
        p, s, loss = step(p, s, tokens, labels, jnp.float32(1e-3))
        float(loss)  # compile + settle
        n = max(iters, 2) * interval
        t0 = time.perf_counter()
        for i in range(n):
            p, s, loss = step(p, s, tokens, labels, jnp.float32(1e-3))
            if host is not None:
                host.poll(s, i)
        float(loss)
        return (time.perf_counter() - t0) / n * 1e3, float(loss), host

    off_ms, off_loss, _ = timed(None, None)
    tcfg = obs.TelemetryConfig(interval=interval, strict=False)
    on_ms, on_loss, host = timed(tcfg, True)
    overhead = (on_ms - off_ms) / off_ms * 100.0
    sample = {k: round(host.series[k][-1], 5)
              for k in list(tcfg.extra)[:4]}
    return {
        "config_hash": _config_hash(conf),
        "mesh": {"dp": 4, "mp": 2},
        "interval": interval,
        "n_series": tcfg.n_series,
        "step_ms_off": round(off_ms, 3),
        "step_ms_on": round(on_ms, 3),
        "overhead_pct": round(overhead, 2),
        "target_pct": 3.0,
        "fetches": host.fetch_count,
        "sample_series": sample,
        # the two programs train identically up to the telemetry carry
        "loss_delta": abs(on_loss - off_loss),
    }


def _run_planner_config(jax, G, conf):
    """Auto-parallel planner end-to-end (distributed.auto_tuner): plan the
    bench shape over the local mesh, then run a 4-point measured sweep —
    the planner's top-1, two mid-surface configs and a deliberately-bad
    pipeline config — through build_hybrid_train_step(**engine_kwargs),
    calibrate the cost model on the first three (rate / per-collective
    launch / per-step overhead) and report plan wall time, top-1
    predicted-vs-measured step ms and the ranking-order check. Mesh-shape
    hops between sweep points carry the params through the PR-7
    elastic-reshard path (warm_hop) so reshard-on-load is exercised
    across every mesh change."""
    import tempfile
    import jax.numpy as jnp
    from paddle_tpu.distributed import auto_tuner as AT
    from paddle_tpu.distributed.auto_tuner.sweep import (ranking_agreement,
                                                         run_sweep)

    n_dev = len(jax.devices())
    if n_dev < 8:
        return {"skipped": f"needs 8 devices for the sweep meshes, have "
                           f"{n_dev}"}
    batch, seq = max(conf["batch"], 16), conf["seq"]
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=max(conf["max_seq_len"], seq),
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)

    t0 = time.perf_counter()
    report = AT.plan(cfg, world=8, global_batch=batch, seq=seq,
                     family="gpt")
    plan_s = time.perf_counter() - t0
    top1 = report.top(1)[0]
    spec = report.spec
    P = AT.PlanCandidate
    bad_pp = 4 if cfg.num_layers % 4 == 0 else 2
    sweep = [top1.candidate,
             P(dp=8, micro_batches=1),
             P(dp=2, mp=2, pp=2, micro_batches=2),
             P(dp=4, mp=2, micro_batches=1),
             # deliberately bad: max bubble at M=1 on the deepest legal
             # pipeline for this layer count
             P(dp=8 // bad_pp, pp=bad_pp, micro_batches=1)]
    # dedupe + constraint-check while keeping the top-1 first; 4 points
    # (3 calibration anchors + the bad config as the held-out check)
    from paddle_tpu.distributed.auto_tuner.planner import check_candidate
    seen, cands = set(), []
    for c in sweep:
        if c not in seen and check_candidate(
                c, spec, world=8, global_batch=batch, seq=seq) is None:
            seen.add(c)
            cands.append(c)
    cands = cands[:3] + [sweep[-1]] if len(cands) > 4 else cands
    cm = AT.CostModel(spec, report.profile, global_batch=batch, seq=seq)
    with tempfile.TemporaryDirectory(prefix="planner_hop_") as hop_dir:
        rows, cal = run_sweep(cfg, cands, cost_model=cm, family="gpt",
                              global_batch=batch, seq=seq, iters=3,
                              repeats=2, anchors=cands[:3],
                              warm_hop_dir=hop_dir)
    agr = ranking_agreement(rows, noise_rel=0.2)
    return {
        "config_hash": _config_hash(conf),
        "plan_s": round(plan_s, 2),
        "n_generated": report.n_generated,
        "n_valid": len(report.ranked),
        "n_pruned": len(report.pruned),
        "top1": top1.row(),
        "sweep": [{"candidate": str(r["candidate"]),
                   "measured_ms": round(r["measured_s"] * 1e3, 2),
                   "predicted_ms": round(r["predicted_s"] * 1e3, 2),
                   "anchor": bool(r.get("anchor"))} for r in rows],
        "top1_predicted_vs_measured": round(
            rows[0]["predicted_s"] / rows[0]["measured_s"], 3),
        "ranking_order_ok": agr["ok"],
        "ranking_checked_pairs": agr["checked_pairs"],
        "calibrated": {
            "rate_flops": cal.rate,
            "collective_launch_us": round(cal.t_launch * 1e6, 1),
            "step_overhead_ms": round(cal.step_overhead_s * 1e3, 2)},
        "warm_hop": "params reshard-loaded across mesh hops "
                    "(checkpoint.reshard)",
    }


def _run_profile_attribution_config(jax, G, conf, iters=3):
    """Measurement-loop section (observability.profile_reader): capture
    attributed profile windows of 3 planner configs + one deliberately
    bad-overlap config, report the measured compute / exposed-comm /
    overhead split next to the planner's predicted split, the
    census-vs-analytic wire-byte ratio, and the derived measured
    HardwareProfile JSON that `auto_tuner plan --profile` consumes.

    Documented tolerance (the slow-tier gate asserts the same bounds):
    census/analytic wire bytes in [0.5, 2.5] — the census counts remat
    REPLAYS of forward collectives and engine-internal reductions
    (grad-norm, loss) that the useful-work wire model deliberately
    excludes, so mp configs sit ~1.3-1.6x over; the bad-overlap config
    is exempt from the ratio but must attribute the WORST exposed comm."""
    import jax.numpy as jnp
    from paddle_tpu.distributed.auto_tuner import planner as PL
    from paddle_tpu.distributed.auto_tuner.sweep import profile_candidate
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.observability import profile_reader as PR

    n_dev = len(jax.devices())
    if n_dev < 8:
        return {"skipped": f"needs 8 devices, have {n_dev}"}
    batch, seq = max(conf["batch"], 16), conf["seq"]
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=max(conf["max_seq_len"], seq),
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    spec = PL.ModelSpec.from_config(cfg, "gpt")
    base_prof = PL.profile_for()
    cm = PL.CostModel(spec, base_prof, global_batch=batch, seq=seq)

    # shared backend rates: one microbench, every window priced the same
    flat = build_mesh({"dp": 8})
    bw, launch = PR.measure_collective_rates(flat)
    rates = PR.MeasuredRates(rate_flops=PR.measure_compute_rate(),
                             ici_gbs=bw, launch_s=launch)

    P = PL.PlanCandidate
    plan_configs = [(P(dp=8), "dp:monolithic"),
                    (P(dp=8, comm_bucket_mb=4.0), "dp:bucketed"),
                    (P(dp=4, mp=2), "mp:allreduce")]
    # the bad-overlap config the ratio gate exempts: ring
    # collective-matmul pays 4*(mp-1) collectives per GEMM pair for
    # overlap this backend cannot deliver (the round-6 CPU-proxy worst)
    bad = P(dp=2, mp=4, mp_overlap="collective_matmul")
    host_params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    rows, windows = [], []
    for cand, mode in plan_configs + [(bad, None)]:
        win = profile_candidate(cfg, cand, global_batch=batch, seq=seq,
                                steps=iters, rates=rates, mode=mode,
                                host_params=host_params)
        pred = cm.predict(cand)
        analytic_wire = sum(pred.wire.values())
        rows.append({
            "candidate": str(cand), "mode": mode,
            "bad_overlap": mode is None,
            "measured": {
                "step_ms": round(win.step_time_s * 1e3, 2),
                "compute_ms": round(win.compute_s * 1e3, 2),
                "exposed_comm_ms": round(win.exposed_comm_s * 1e3, 3),
                "hidden_comm_ms": round(win.hidden_comm_s * 1e3, 3),
                "overhead_ms": round(win.overhead_s * 1e3, 2),
                "hidable_fraction": round(win.hidable_fraction, 3),
                "wire_mb": round(win.census.total_wire_bytes / 1e6, 3),
                "n_collectives": round(win.census.n_collectives),
            },
            "predicted": {
                "step_ms": round(pred.step_s * 1e3, 2),
                "compute_ms": round(pred.compute_s * 1e3, 2),
                "exposed_comm_ms": round(pred.exposed_comm_s * 1e3, 3),
                "wire_mb": round(analytic_wire / 1e6, 3),
                "n_collectives": pred.n_collectives,
            },
            "wire_ratio_census_over_analytic": round(
                win.census.total_wire_bytes / max(analytic_wire, 1.0), 3),
        })
        windows.append(win)
    worst = max(rows, key=lambda r: r["measured"]["exposed_comm_ms"])
    prof = PR.derive_hardware_profile(windows, base=base_prof)
    # close the loop: the derived profile drives a full plan
    report = PL.plan(cfg, world=8, global_batch=batch, seq=seq,
                     family="gpt", profile=prof)
    return {
        "config_hash": _config_hash(conf),
        "rates": {"gemm_gflops": round(rates.rate_flops / 1e9, 2),
                  "ici_gbs": round(rates.ici_gbs, 3),
                  "collective_launch_us": round(rates.launch_s * 1e6, 1)},
        "configs": rows,
        "bad_overlap_attributes_worst": worst["bad_overlap"],
        "tolerance_note": "census/analytic wire ratio documented "
                          "[0.5, 2.5]; bad-overlap config exempt but "
                          "must attribute worst exposed comm",
        "hardware_profile": PL.profile_to_json(prof),
        "plan_with_measured_profile_top1":
            report.top(1)[0].row() if report.ranked else None,
    }


def _run_serving_config(jax, G):
    """The serving engine's sections at serving_bench's chip scenario
    (the 125M-shape model), as the standalone
    `benchmarks/serving_bench.py` measures them."""
    from benchmarks.serving_bench import (run_overload_comparison,
                                          run_prefix_spec_comparison,
                                          run_router_comparison, scenario)

    cfg, _, _, _, mk = scenario(True)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    report = {"config": f"batch 8, chunk {mk['chunk']}, decode burst "
                        f"{mk['decode_burst']}"}
    # ISSUE 13: offered load at ~2x measured capacity, shedding on vs
    # off — admitted p99 TTFT vs SLO, shed rate, goodput
    report["overload"] = run_overload_comparison(
        params, cfg, mk, 8, n_req=64)
    # ISSUE 16: 2-replica fleet with one replica killed mid-run vs the
    # uninterrupted fleet — goodput cost of a journaled failover, with
    # bitwise-equal outputs (the exactly-once contract)
    report["router"] = run_router_comparison(
        params, cfg, mk, 8, n_req=48)
    # ISSUE 17: prefix page sharing admission multiplier at a fixed pool
    # + speculative-decoding tokens/decode-step (replay + ngram
    # proposers), both bitwise vs plain greedy decode
    report["prefix_spec"] = run_prefix_spec_comparison(params, cfg, mk, 8)
    return report


def main():
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.device import device_tag, require_tpu
    from paddle_tpu.flags import REPO_JIT_CACHE_DIR
    from paddle_tpu.models import gpt as G

    # a chip entry: no CPU shapes under a device metric's name
    require_tpu("bench.py")
    paddle.set_flags({"jit_cache_dir": REPO_JIT_CACHE_DIR})
    flagship, secondary, iters = dict(FLAGSHIP), dict(SECONDARY), 12
    overlap_conf, overlap_iters = dict(SECONDARY), 8

    toks, mfu, _, compile_s = _run_config(jax, paddle, G, flagship, iters)
    out = {
        "device": device_tag(),
        "metric": "gpt1p3b_tokens_per_sec_per_chip",
        "value": round(toks, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
        # frozen flagship series (VERDICT r2 weak-2): same hash ==
        # round-over-round comparable
        "config_hash": _config_hash(flagship),
        "mfu_pct": round(mfu * 100, 1),
        "compile_s": round(compile_s, 2),
    }
    toks2, mfu2, _, compile2 = _run_config(jax, paddle, G, secondary, iters)
    out["secondary"] = {"config_hash": _config_hash(secondary),
                        "tokens_per_sec": round(toks2, 1),
                        "mfu_pct": round(mfu2 * 100, 1),
                        "compile_s": round(compile2, 2),
                        # H=1024/16 heads gives head_dim 64: a 64-deep
                        # attention contraction, half the MXU's width
                        "mfu_note": "head_dim 64 (flagship row is d=128)"}
    # bucketed-overlap + int8 dp gradient sync (FLAGS_comm_bucket_mb /
    # FLAGS_comm_quantize): per-phase comms fraction + step times
    out["overlap"] = _run_overlap_config(jax, paddle, G, overlap_conf,
                                         overlap_iters)
    # mp-axis tensor-parallel overlap (FLAGS_mp_seq_parallel /
    # FLAGS_mp_collective_matmul): allreduce vs seq-parallel vs ring
    # collective-matmul step time + activation-memory delta
    mp_conf = dict(SECONDARY)
    out["mp_overlap"] = _run_mp_overlap_config(jax, paddle, G, mp_conf,
                                               overlap_iters)
    # training-grade flash attention (FLAGS_flash_attention): einsum vs
    # fused-kernel step time + compiled temp bytes, the analytic
    # attention-FLOPs share, and the long-S planner HBM-prune delta
    out["flash_training"] = _run_flash_training_config(
        jax, paddle, G, mp_conf, overlap_iters)
    # delayed-scaling fp8 GEMMs (FLAGS_fp8): bf16 vs fp8 step time +
    # 50-step loss-parity gate on the dense single-chip path
    fp8_conf = dict(SECONDARY)
    out["fp8"] = _run_fp8_config(jax, paddle, G, fp8_conf,
                                 iters)
    # GPT-MoE in the hybrid engine (FLAGS_moe_*): dense vs index
    # dispatch vs the int8-EF quantized + overlapped all-to-all, with
    # the analytic dispatch-flop delta and a2a wire bytes
    moe_conf = dict(SECONDARY)
    out["moe"] = _run_moe_config(jax, paddle, G, moe_conf, overlap_iters)
    # ZeRO stage axis (FLAGS_zero_stage): per-stage hybrid step time,
    # per-chip param/opt bytes (stage 3 params scale ~1/dp at rest) and
    # the analytic zero3 param-AG wire fp32 vs int8
    out["zero_stages"] = _run_zero_stages_config(
        jax, paddle, G, dict(SECONDARY),
        overlap_iters)
    # step accounting (observability.StepTimer): compile/steady split,
    # data-vs-step phase breakdown, analytic-FLOPs MFU and the measured
    # comms_fraction — where the step time goes, round over round
    tele_conf = dict(SECONDARY)
    out["telemetry"] = _run_telemetry_config(
        jax, paddle, G, tele_conf, iters,
        comms_fraction=out["overlap"]["comms_fraction"])
    # numerics observability (FLAGS_numerics): flags-on step-time
    # overhead of the in-program tensor-health series (target < 3%) +
    # a decoded per-layer sample proving the path is live
    out["numerics"] = _run_numerics_config(
        jax, paddle, G, tele_conf, iters)
    # auto-parallel planner (distributed.auto_tuner): plan time, top-1
    # predicted vs measured step ms on this host's mesh, ranking-order
    # check over a 4-point sweep with reshard warm hops between mesh
    # shapes.
    planner_conf = dict(SECONDARY)
    out["planner"] = _run_planner_config(jax, G, planner_conf)
    # measurement loop (observability.profile_reader): attributed
    # compute/exposed-comm split per config vs the planner's predicted
    # split, census-vs-analytic wire ratio, and the derived measured
    # HardwareProfile JSON `auto_tuner plan --profile` consumes
    out["profile_attribution"] = _run_profile_attribution_config(
        jax, G, planner_conf)
    # the serving engine under overload, behind the router, and with
    # prefix sharing and speculation (benchmarks/serving_bench.py owns
    # the harness)
    out["serving"] = _run_serving_config(jax, G)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
