"""Optimizer golden tests vs torch (reference pattern: test_adam_op.py etc.)."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.nn.clip import ClipGradByGlobalNorm, ClipGradByNorm


def _run_steps(opt_cls, torch_cls, steps=5, atol=1e-5, pt_kw=None, th_kw=None):
    import torch
    w0 = np.random.randn(4, 3).astype(np.float32)
    g = [np.random.randn(4, 3).astype(np.float32) for _ in range(steps)]

    params = {"w": paddle.to_tensor(w0)}
    opt = opt_cls(learning_rate=0.1, **(pt_kw or {}))
    state = opt.init_state(params)
    for gi in g:
        params, state = opt.apply(params, {"w": paddle.to_tensor(gi)}, state)

    tw = torch.nn.Parameter(torch.tensor(w0))
    topt = torch_cls([tw], lr=0.1, **(th_kw or {}))
    for gi in g:
        topt.zero_grad()
        tw.grad = torch.tensor(gi)
        topt.step()
    assert np.allclose(np.asarray(params["w"]), tw.detach().numpy(), atol=atol), \
        np.abs(np.asarray(params["w"]) - tw.detach().numpy()).max()


def test_sgd_matches_torch():
    import torch
    _run_steps(paddle.optimizer.SGD, torch.optim.SGD)


def test_momentum_matches_torch():
    import torch
    _run_steps(paddle.optimizer.Momentum, torch.optim.SGD,
               pt_kw={"momentum": 0.9}, th_kw={"momentum": 0.9})


def test_adam_matches_torch():
    import torch
    _run_steps(paddle.optimizer.Adam, torch.optim.Adam, atol=1e-5)


def test_adamw_matches_torch():
    import torch
    _run_steps(paddle.optimizer.AdamW, torch.optim.AdamW, atol=1e-5,
               pt_kw={"weight_decay": 0.05}, th_kw={"weight_decay": 0.05})


def test_fused_multi_tensor_matches_per_leaf():
    """The multi-tensor path (reference use_multi_tensor /
    fused_adam_kernel.cu) is elementwise-identical to the per-leaf loop:
    mixed dtypes, master weights, frozen (None-grad) leaves."""
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    params = {
        "w_bf16": jnp.asarray(rng.randn(32, 16), jnp.bfloat16),
        "b_f32": jnp.asarray(rng.randn(16), jnp.float32),
        "frozen": jnp.asarray(rng.randn(4), jnp.float32),
        "nested": {"k": jnp.asarray(rng.randn(8, 8), jnp.float32)},
    }
    grads = {
        "w_bf16": jnp.asarray(rng.randn(32, 16), jnp.bfloat16),
        "b_f32": jnp.asarray(rng.randn(16), jnp.float32),
        "frozen": None,
        "nested": {"k": jnp.asarray(rng.randn(8, 8), jnp.float32)},
    }
    for cls, kw in ((paddle.optimizer.Adam, {"weight_decay": 0.02}),
                    (paddle.optimizer.AdamW, {"weight_decay": 0.05}),
                    (paddle.optimizer.Adam, {"multi_precision": True})):
        o_fused = cls(learning_rate=0.1, use_multi_tensor=True, **kw)
        o_leaf = cls(learning_rate=0.1, use_multi_tensor=False, **kw)
        pf, sf = params, o_fused.init_state(params)
        pl_, sl = params, o_leaf.init_state(params)
        for _ in range(3):
            pf, sf = o_fused.apply(pf, grads, sf)
            pl_, sl = o_leaf.apply(pl_, grads, sl)
        for k in ("w_bf16", "b_f32", "frozen"):
            np.testing.assert_array_equal(
                np.asarray(pf[k], np.float32), np.asarray(pl_[k], np.float32),
                err_msg=f"{cls.__name__} {kw} {k}")
        np.testing.assert_array_equal(np.asarray(pf["nested"]["k"]),
                                      np.asarray(pl_["nested"]["k"]))
        for k in ("moment1", "moment2"):
            np.testing.assert_array_equal(
                np.asarray(sf["slots"]["w_bf16"][k], np.float32),
                np.asarray(sl["slots"]["w_bf16"][k], np.float32))


def test_fused_multi_tensor_gates():
    """Ineligible configs raise under use_multi_tensor=True and silently
    keep the per-leaf loop under auto."""
    import jax.numpy as jnp
    p = {"w": jnp.ones((4, 4))}
    g = {"w": jnp.ones((4, 4))}
    with pytest.raises(ValueError, match="use_multi_tensor"):
        paddle.optimizer.AdamW(0.1, use_multi_tensor=True,
                               apply_decay_param_fun=lambda n: False)
    from paddle_tpu.framework.selected_rows import SelectedRows
    import jax.numpy as _jnp
    opt = paddle.optimizer.Adam(0.1, use_multi_tensor=True, lazy_mode=True)
    with pytest.raises(ValueError, match="use_multi_tensor"):
        opt.apply(p, g, opt.init_state(p))
    # NAdam/RAdam override the update math — never fused
    from paddle_tpu.optimizer.optimizer import _FUSED_TYPES
    assert paddle.optimizer.NAdam not in _FUSED_TYPES
    # default is OFF (reference default; measured slower on TPU) — a
    # name-aware config works fine without the kwarg
    dflt = paddle.optimizer.AdamW(0.1, apply_decay_param_fun=lambda n: True)
    dflt.apply(p, g, dflt.init_state(p))


def test_eager_step_api():
    net = nn.Linear(3, 2)
    opt = paddle.optimizer.SGD(0.5, parameters=net.parameters())
    w_before = np.asarray(net.weight.value).copy()
    for p in net.parameters():
        p.grad = np.ones(p.shape, np.float32)
    opt.step()
    opt.clear_grad()
    assert np.allclose(np.asarray(net.weight.value), w_before - 0.5, atol=1e-6)
    assert net.weight.grad is None


def test_global_norm_clip():
    g = {"a": paddle.to_tensor(np.full((4,), 3.0, np.float32)),
         "b": paddle.to_tensor(np.full((4,), 4.0, np.float32))}
    clip = ClipGradByGlobalNorm(1.0)
    out = clip(g)
    import jax
    total = np.sqrt(sum(float((np.asarray(v) ** 2).sum()) for v in out.values()))
    assert abs(total - 1.0) < 1e-5


def test_lr_schedulers():
    from paddle_tpu.optimizer import lr
    s = lr.StepDecay(0.1, step_size=2, gamma=0.5)
    vals = []
    for _ in range(5):
        vals.append(s())
        s.step()
    assert np.allclose(vals, [0.1, 0.1, 0.05, 0.05, 0.025])

    warm = lr.LinearWarmup(0.1, warmup_steps=4, start_lr=0.0, end_lr=0.1)
    got = []
    for _ in range(5):
        got.append(warm())
        warm.step()
    assert got[0] == 0.0 and abs(got[-1] - 0.1) < 1e-9

    cos = lr.CosineAnnealingDecay(0.1, T_max=10)
    assert abs(cos() - 0.1) < 1e-9

    noam = lr.NoamDecay(d_model=512, warmup_steps=100)
    for _ in range(100):
        noam.step()
    peak = noam()
    for _ in range(200):
        noam.step()
    assert noam() < peak


def test_scheduler_with_optimizer():
    from paddle_tpu.optimizer import lr
    sched = lr.StepDecay(0.1, step_size=1, gamma=0.1)
    net = nn.Linear(2, 2)
    opt = paddle.optimizer.SGD(sched, parameters=net.parameters())
    assert abs(opt.get_lr() - 0.1) < 1e-9
    sched.step()
    assert abs(opt.get_lr() - 0.01) < 1e-9


def test_optimizer_state_dict():
    net = nn.Linear(3, 2)
    opt = paddle.optimizer.Adam(0.01, parameters=net.parameters())
    for p in net.parameters():
        p.grad = np.ones(p.shape, np.float32)
    opt.step()
    sd = opt.state_dict()
    assert sd["step_count"] == 1
    opt2 = paddle.optimizer.Adam(0.01, parameters=net.parameters())
    opt2.set_state_dict(sd)
    assert opt2._step_count == 1


def test_lbfgs_rosenbrock():
    """L-BFGS converges on the Rosenbrock function where SGD crawls."""
    import jax.numpy as jnp
    from paddle_tpu.optimizer import minimize_lbfgs

    def rosen(p):
        x, y = p["x"], p["y"]
        return (1 - x) ** 2 + 100.0 * (y - x ** 2) ** 2

    params = {"x": jnp.asarray(-1.2), "y": jnp.asarray(1.0)}
    out, loss = minimize_lbfgs(rosen, params, max_iter=100)
    assert loss < 1e-6, loss
    assert abs(float(out["x"]) - 1.0) < 1e-3
    assert abs(float(out["y"]) - 1.0) < 1e-3


def test_lbfgs_class_surface():
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.optimizer import LBFGS

    layer = nn.Linear(4, 1, bias_attr=False)
    X = jnp.asarray(np.random.RandomState(0).randn(32, 4).astype(np.float32))
    w_true = jnp.asarray([[1.0], [-2.0], [0.5], [3.0]])
    y = X @ w_true
    opt = LBFGS(parameters=layer.parameters(), max_iter=50)

    def closure(values):
        (w,) = values
        return jnp.mean((X @ w - y) ** 2)

    loss = opt.step(closure)
    assert loss < 1e-8
    np.testing.assert_allclose(np.asarray(layer.weight), np.asarray(w_true),
                               atol=1e-3)


def test_gradient_merge_matches_large_batch():
    """k accumulation steps with avg == one step on the concatenated batch
    (reference: gradient_merge pass semantics)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.optimizer import GradientMergeOptimizer

    k = 4
    params = {"w": jnp.ones((4,))}
    inner_a = paddle.optimizer.SGD(0.1)
    gm = GradientMergeOptimizer(paddle.optimizer.SGD(0.1), k_steps=k)
    state = gm.init_state(params)
    grads = [jnp.asarray(np.random.RandomState(i).randn(4), jnp.float32)
             for i in range(k)]

    p = params
    apply = jax.jit(gm.apply)
    for i, g in enumerate(grads):
        p, state = apply(p, {"w": g}, state, 0.1)
        if i < k - 1:  # params unchanged until the merge step
            np.testing.assert_array_equal(np.asarray(p["w"]),
                                          np.asarray(params["w"]))
    mean_g = sum(np.asarray(g) for g in grads) / k
    ref, _ = inner_a.apply(params, {"w": jnp.asarray(mean_g)},
                           inner_a.init_state(params), 0.1)
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(ref["w"]),
                               rtol=1e-6)
    assert int(state["count"]) == 0  # cycle reset


def test_gradient_merge_multiple_cycles():
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.optimizer import GradientMergeOptimizer

    gm = GradientMergeOptimizer(paddle.optimizer.SGD(1.0), k_steps=2,
                                avg=False)
    p = {"w": jnp.zeros(())}
    s = gm.init_state(p)
    for step in range(6):
        p, s = gm.apply(p, {"w": jnp.asarray(1.0)}, s, 1.0)
    # 3 merge cycles, each applying summed grad 2.0 with lr 1.0
    assert float(p["w"]) == -6.0


def test_gradient_merge_eager_step_and_state_dict():
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.optimizer import GradientMergeOptimizer

    layer = nn.Linear(4, 1, bias_attr=False)
    w0 = np.asarray(layer.weight)
    gm = GradientMergeOptimizer(
        paddle.optimizer.SGD(0.5, parameters=layer.parameters()), k_steps=2)
    layer.weight.grad = jnp.ones((4, 1))
    gm.step()
    np.testing.assert_array_equal(np.asarray(layer.weight), w0)  # held
    sd = gm.state_dict()
    assert sd["gm_count"] == 1  # mid-cycle state is checkpointable
    layer.weight.grad = jnp.full((4, 1), 3.0)
    gm.step()  # merge fires: mean grad = 2.0, lr 0.5
    np.testing.assert_allclose(np.asarray(layer.weight), w0 - 1.0, rtol=1e-6)
    assert gm.state_dict()["gm_count"] == 0

    # mid-cycle restore resumes the accumulation
    gm2 = GradientMergeOptimizer(
        paddle.optimizer.SGD(0.5, parameters=layer.parameters()), k_steps=2)
    gm2.set_state_dict(sd)
    assert gm2._eager_count == 1


def test_gradient_merge_grad_clip_lands_on_inner():
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import (DistributedStrategy,
                                              HybridParallelClipGrad)
    from paddle_tpu.optimizer import GradientMergeOptimizer
    s = DistributedStrategy()
    s.gradient_merge = True
    s.gradient_merge_configs = {"k_steps": 2}
    fleet.init(is_collective=True, strategy=s)
    inner = paddle.optimizer.SGD(0.1,
                                 grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    dopt = fleet.distributed_optimizer(inner)
    # the swap must reach the inner optimizer (the one that applies clip)
    assert isinstance(inner._grad_clip, HybridParallelClipGrad)


def test_gradient_merge_accumulates_fp32_for_bf16_grads():
    """ISSUE 2 satellite regression: merged grads accumulate in fp32
    regardless of param/grad dtype. k bf16 micrograds of ~1/k magnitude
    summed in bf16 would lose the low bits each add (bf16 has 8 mantissa
    bits); the fp32 accumulator must reproduce the one-big-batch update
    to fp32 accuracy."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.optimizer import GradientMergeOptimizer

    k = 16
    rng = np.random.RandomState(0)
    # zero params + lr 1.0: the merged param IS the (negated) merged
    # gradient, so accumulator precision is directly observable
    params = {"w": jnp.zeros((256,), jnp.bfloat16)}
    grads = [jnp.asarray((1e-3 * (1 + 0.5 * np.sin(i)) *
                          rng.randn(256)).astype(np.float32))
             for i in range(k)]

    gm = GradientMergeOptimizer(paddle.optimizer.SGD(1.0), k_steps=k)
    state = gm.init_state(params)
    assert all(l.dtype == jnp.float32
               for l in jax.tree.leaves(state["acc"]))
    p = params
    for g in grads:
        # bf16 wire grads (the dp reduce-dtype case)
        p, state = gm.apply(p, {"w": g.astype(jnp.bfloat16)}, state, 1.0)

    mean_g = np.mean([np.asarray(g.astype(jnp.bfloat16), np.float32)
                      for g in grads], axis=0)
    got = np.asarray(p["w"], np.float32)

    # what a bf16 accumulator would have produced instead
    acc16 = jnp.zeros((256,), jnp.bfloat16)
    for g in grads:
        acc16 = acc16 + g.astype(jnp.bfloat16)
    bf16_err = np.abs(np.asarray(acc16, np.float32) / k + (-mean_g)).max()

    # fp32 accumulation: only the ONE final bf16 param store rounds —
    # strictly tighter than k accumulated bf16 truncations
    fp32_err = np.abs(got + mean_g).max()
    assert fp32_err <= 2e-5, fp32_err
    assert bf16_err > 2e-6  # the failure mode the fp32 accumulator avoids
    assert fp32_err < bf16_err, (fp32_err, bf16_err)


def test_state_specs_for_wrapper_without_example():
    """Fallback path must handle wrapper state structures too."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu.models.hybrid_engine import state_specs_for
    from paddle_tpu.optimizer import GradientMergeOptimizer
    specs = {"w": P("mp", None), "b": P()}
    gm = GradientMergeOptimizer(paddle.optimizer.AdamW(1e-3), k_steps=2)
    sspec = state_specs_for(gm, specs)
    assert sspec["acc"]["w"] == P("mp", None)
    assert sspec["count"] == P()
    assert sspec["inner"]["slots"]["w"]["moment1"] == P("mp", None)


def test_adam_moment_dtype_bf16():
    """TPU extension: bf16 moment storage (update still in fp32) — the
    single-chip state-memory lever that fits 1.3B on one v5e (the
    train-1p3b-seq2k cell runs with it)."""
    import jax
    import jax.numpy as jnp
    params = {"w": jnp.ones((8, 8), jnp.bfloat16)}
    grads = {"w": jnp.full((8, 8), 0.5, jnp.bfloat16)}
    opt = paddle.optimizer.AdamW(1e-2, moment_dtype=jnp.bfloat16)
    state = opt.init_state(params)
    assert state["slots"]["w"]["moment1"].dtype == jnp.bfloat16
    assert state["slots"]["w"]["moment2"].dtype == jnp.bfloat16
    p2, s2 = jax.jit(opt.apply)(params, grads, state, 1e-2)
    # dtypes preserved across steps (jit carry structure stays stable)
    assert p2["w"].dtype == jnp.bfloat16
    assert s2["slots"]["w"]["moment1"].dtype == jnp.bfloat16
    p3, s3 = jax.jit(opt.apply)(p2, grads, s2, 1e-2)
    assert float(jnp.mean(p3["w"])) < float(jnp.mean(p2["w"])) < 1.0
    # default stays fp32
    opt32 = paddle.optimizer.AdamW(1e-2)
    assert opt32.init_state(params)["slots"]["w"]["moment1"].dtype == jnp.float32


def test_bf16_moments_track_ema_via_stochastic_rounding():
    """With beta2=0.999 the per-step m2 update (~0.1%) is below bf16's ulp;
    nearest-rounding would freeze m2. The stochastic-rounding store must
    keep the EMA tracking in expectation (regression test)."""
    import jax
    import jax.numpy as jnp
    p = {"w": jnp.ones((64, 64), jnp.bfloat16)}
    opt = paddle.optimizer.AdamW(1e-3, moment_dtype=jnp.bfloat16)
    opt32 = paddle.optimizer.AdamW(1e-3)
    s16, s32 = opt.init_state(p), opt32.init_state(p)
    g = {"w": jnp.full((64, 64), 0.1, jnp.bfloat16)}
    apply16 = jax.jit(opt.apply)
    apply32 = jax.jit(opt32.apply)
    p16, p32 = p, p
    for _ in range(300):
        p16, s16 = apply16(p16, g, s16, 1e-3)
        p32, s32 = apply32(p32, g, s32, 1e-3)
    m2_16 = float(jnp.mean(s16["slots"]["w"]["moment2"].astype(jnp.float32)))
    m2_32 = float(jnp.mean(s32["slots"]["w"]["moment2"]))
    # fp32 EMA after 300 steps of g=0.1: 0.01*(1-0.999^300) ≈ 0.00259.
    # A frozen bf16 EMA would stall near its first representable plateau
    # (well under half the fp32 value); SR must keep it within 20%.
    assert m2_32 > 0
    assert abs(m2_16 - m2_32) / m2_32 < 0.2, (m2_16, m2_32)


def test_selected_rows_lazy_adam():
    """SelectedRows sparse grads + Adam(lazy_mode=True): only touched rows
    move (reference: phi/core/selected_rows.h + LazyAdam)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import SelectedRows
    table = jnp.ones((8, 4), jnp.float32)
    params = {"emb": table}
    opt = paddle.optimizer.AdamW(1e-2, lazy_mode=True, weight_decay=0.0)
    state = opt.init_state(params)
    g = SelectedRows(jnp.asarray([1, 5]), jnp.ones((2, 4)), 8)
    grads = {"emb": g}
    p2, s2 = jax.jit(opt.apply)(params, grads, state, 1e-2)
    moved = np.where(np.abs(np.asarray(p2["emb"]) - 1.0).sum(-1) > 0)[0]
    np.testing.assert_array_equal(moved, [1, 5])  # ONLY touched rows
    m1 = np.asarray(s2["slots"]["emb"]["moment1"])
    assert np.all(m1[[0, 2, 3, 4, 6, 7]] == 0) and np.all(m1[[1, 5]] != 0)
    # dense fallback without lazy_mode: all rows get decoupled decay etc.
    opt2 = paddle.optimizer.AdamW(1e-2, lazy_mode=False)
    p3, _ = jax.jit(opt2.apply)(params, grads, opt2.init_state(params), 1e-2)
    assert np.abs(np.asarray(p3["emb"]) - 1.0).sum() > 0
    # round-trips: to_dense/from_dense/coalesced
    np.testing.assert_allclose(np.asarray(g.to_dense()).sum(), 8.0)
    sr2 = SelectedRows(jnp.asarray([1, 1]), jnp.ones((2, 4)), 8).coalesced()
    np.testing.assert_array_equal(np.asarray(sr2.rows), [1])
    np.testing.assert_allclose(np.asarray(sr2.value), 2.0)


def test_selected_rows_clip_and_bf16_moments():
    """Review regressions: global-norm clip scales VALUES not row indices;
    bf16 moment2 stores keep stochastic rounding on the sparse path."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import SelectedRows
    from paddle_tpu.nn.clip import ClipGradByGlobalNorm, global_norm
    g = SelectedRows(jnp.asarray([1, 5]), jnp.full((2, 4), 10.0), 8)
    clip = ClipGradByGlobalNorm(1.0)
    out = clip({"emb": g})["emb"]
    np.testing.assert_array_equal(np.asarray(out.rows), [1, 5])  # untouched
    np.testing.assert_allclose(float(global_norm({"e": out})), 1.0,
                               rtol=1e-5)
    # lazy adam + clip end to end under jit
    params = {"emb": jnp.ones((8, 4))}
    opt = paddle.optimizer.AdamW(1e-2, lazy_mode=True,
                                 grad_clip=ClipGradByGlobalNorm(1.0),
                                 moment_dtype=jnp.bfloat16)
    state = opt.init_state(params)
    p2, s2 = jax.jit(opt.apply)(params, {"emb": g}, state, 1e-2)
    moved = np.where(np.abs(np.asarray(p2["emb"]) - 1.0).sum(-1) > 0)[0]
    np.testing.assert_array_equal(moved, [1, 5])
    assert s2["slots"]["emb"]["moment2"].dtype == jnp.bfloat16


def test_lars_optimizer():
    """LARS layer-wise trust ratio (reference: lars_momentum kernel):
    update magnitude scales with ||w||/||g|| per layer."""
    import jax
    import jax.numpy as jnp
    params = {"big": jnp.ones((4, 4)) * 10.0, "small": jnp.ones((4, 4))}
    grads = {"big": jnp.ones((4, 4)), "small": jnp.ones((4, 4))}
    opt = paddle.optimizer.Lars(learning_rate=1.0, momentum=0.0,
                                lars_coeff=0.001, lars_weight_decay=0.0)
    state = opt.init_state(params)
    p2, s2 = jax.jit(opt.apply)(params, grads, state, 1.0)
    d_big = float(jnp.abs(p2["big"] - params["big"]).mean())
    d_small = float(jnp.abs(p2["small"] - params["small"]).mean())
    # trust ratio ∝ ||w||: the 10x-larger layer moves ~10x more
    assert 8.0 < d_big / d_small < 12.0, (d_big, d_small)
    # loss decreases on a quadratic
    w = {"w": jnp.full((8,), 5.0)}
    opt2 = paddle.optimizer.Lars(0.5, momentum=0.9)
    st = opt2.init_state(w)
    for _ in range(50):
        g = {"w": 2 * w["w"]}
        w, st = opt2.apply(w, g, st, 0.5)
    assert float(jnp.abs(w["w"]).max()) < 5.0


def test_lars_exclusions_and_kwarg_guard():
    """Review regressions: exclude_from_weight_decay is honored (excluded
    params get plain momentum, no trust scaling), and weight_decay= is
    rejected instead of silently ignored."""
    import jax
    import jax.numpy as jnp
    with pytest.raises(TypeError, match="lars_weight_decay"):
        paddle.optimizer.Lars(0.1, weight_decay=1e-4)
    params = {"conv_w": jnp.ones((4, 4)) * 10.0,
              "batch_norm_scale": jnp.ones((4,)) * 10.0}
    grads = {"conv_w": jnp.ones((4, 4)), "batch_norm_scale": jnp.ones((4,))}
    opt = paddle.optimizer.Lars(1.0, momentum=0.0, lars_coeff=0.001,
                                exclude_from_weight_decay=["batch_norm"])
    p2, _ = jax.jit(opt.apply)(params, grads, opt.init_state(params), 1.0)
    # excluded: plain momentum SGD step of lr*g = 1.0 exactly
    np.testing.assert_allclose(
        np.asarray(params["batch_norm_scale"] - p2["batch_norm_scale"]),
        1.0, rtol=1e-6)
    # included: trust-ratio-scaled (coeff * ||w||/||g|| ~ 0.01x)
    d = float(jnp.abs(p2["conv_w"] - params["conv_w"]).mean())
    assert d < 0.1
