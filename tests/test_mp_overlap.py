"""Sequence-parallel TP + ring collective-matmul tests (ISSUE 5).

Covers: primitive-level fwd/bwd parity of the collective_matmul ops,
bitwise-identical lowered HLO with the feature off, multi-step loss
parity of seq-parallel and collective-matmul vs the allreduce baseline
on the dp2·pp2·mp2 virtual mesh (50-step acceptance run in the slow
tier), the mp=1 degenerate case, the fp8+zero1 compose, the
ring-vs-fp8 refusal, the per-mode HLO collective-mix assertion (guards
against silent fallback to the replicated path), telemetry comms_bytes
vs the analytic wire model, and the mp_ops axis/shape validation.

Parity tolerance note: seq-parallel REDUCES the LayerNorm/bias grads
over mp (per-shard partial sums + psum) where the baseline computes one
full-sequence reduction per rank — the same sums reassociated, so fp32
losses agree to ulp-level absolute differences (measured ≤2e-8 abs /
≤2e-6 rel over 50 steps as the toy overfits toward 0.02 loss) but not
always bit-for-bit. The bitwise guarantee of this PR is the OFF path:
with the flags off the compiled step is byte-identical HLO
(test_mp_overlap_off_is_bitwise_noop).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.enforce import EnforceNotMet, InvalidArgumentError
from paddle_tpu.distributed.comm_overlap import collective_matmul as cm
from paddle_tpu.distributed.fleet.layers.mpu import mp_ops
from paddle_tpu.models import gpt as G
from paddle_tpu.utils import shard_map

from hlo_utils import collective_counts

CFG = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
                  max_seq_len=16, dtype=jnp.float32)
LR = jnp.float32(1e-2)


def _data(batch=8, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randint(0, CFG.vocab_size, (batch, seq))),
            jnp.asarray(rng.randint(0, CFG.vocab_size, (batch, seq))))


def _run_gpt(mesh, mode, steps, cfg=CFG, **kw):
    opt = paddle.optimizer.AdamW(1e-2)
    step, shard, init = G.build_hybrid_train_step(
        cfg, mesh, opt, num_microbatches=2, mp_overlap=mode, **kw)
    p = shard(G.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    s = init(p)
    tokens, labels = _data()
    losses = []
    for _ in range(steps):
        p, s, loss = step(p, s, tokens, labels, LR)
        losses.append(float(loss))
    return losses


def _max_rel(a, b):
    return max(abs(x - y) / max(abs(x), 1e-12) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# Primitive level
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("ring", [False, True], ids=["fused", "ring"])
def test_ag_matmul_and_matmul_rs_match_dense(ring):
    """Forward and backward of both entry points vs the dense reference,
    on an mp=4 sub-mesh (ring partial-sum order differs from the fused
    collectives — parity within fp32 reassociation noise)."""
    mesh = dist.build_mesh({"mp": 4, "x": 2})
    rng = np.random.RandomState(0)
    B, S, H, F = 2, 8, 6, 12
    x = jnp.asarray(rng.randn(B, S, H).astype(np.float32))
    w = jnp.asarray(rng.randn(H, F).astype(np.float32))

    def ag_grads(xl, wl):
        def loss(xl, wl):
            # per-rank loss over this rank's F shard; the total is the
            # mp-sum (x axis replicates the identical computation)
            return jnp.sum(cm.ag_matmul(xl, wl, "mp", ring=ring) ** 2)
        return (lax.psum(loss(xl, wl), "mp"),) + jax.grad(
            loss, argnums=(0, 1))(xl, wl)

    l, gx, gw = jax.jit(shard_map(
        ag_grads, mesh=mesh,
        in_specs=(P(None, "mp", None), P(None, "mp")),
        out_specs=(P(), P(None, "mp", None), P(None, "mp"))))(x, w)
    l_ref, (gx_ref, gw_ref) = (
        jnp.sum((x @ w) ** 2),
        jax.grad(lambda x, w: jnp.sum((x @ w) ** 2), argnums=(0, 1))(x, w))
    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               atol=1e-4)

    z = jnp.asarray(rng.randn(B, S, F).astype(np.float32))
    w2 = jnp.asarray(rng.randn(F, H).astype(np.float32))

    def rs_grads(zl, wl):
        def loss(zl, wl):
            return jnp.sum(cm.matmul_rs(zl, wl, "mp", ring=ring) ** 2)
        # the per-rank seq-shard losses sum to the dense loss
        return (lax.psum(loss(zl, wl), "mp"),) + jax.grad(
            loss, argnums=(0, 1))(zl, wl)

    l2, gz, gw2 = jax.jit(shard_map(
        rs_grads, mesh=mesh,
        in_specs=(P(None, None, "mp"), P("mp", None)),
        out_specs=(P(), P(None, None, "mp"), P("mp", None))))(z, w2)
    l2_ref = jnp.sum((z @ w2) ** 2)
    gz_ref, gw2_ref = jax.grad(
        lambda z, w: jnp.sum((z @ w) ** 2), argnums=(0, 1))(z, w2)
    np.testing.assert_allclose(float(l2), float(l2_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gz), np.asarray(gz_ref),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw2), np.asarray(gw2_ref),
                               atol=1e-4)


def test_scatter_ag_rs_seq_roundtrip_and_grads():
    mesh = dist.build_mesh({"mp": 4, "x": 2})
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 8, 4).astype(np.float32))

    def fn(xr):
        # replicated -> scatter -> gather is the identity (values)
        y = cm.ag_seq(cm.scatter_seq(xr, "mp"), "mp")

        # per-rank loss on the shard: grad = scatter-bwd(2*chunk) =
        # all_gather of the per-chunk grads = exactly 2x on every rank
        def loss(xr):
            return jnp.sum(cm.scatter_seq(xr, "mp") ** 2)

        return y, jax.grad(loss)(xr)

    y, g = jax.jit(shard_map(fn, mesh=mesh, in_specs=(P(),),
                             out_specs=(P(), P())))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(x), atol=1e-6)


# ---------------------------------------------------------------------------
# Engine level: off = bitwise no-op, on = loss parity
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mesh8():
    return dist.build_mesh({"dp": 2, "pp": 2, "mp": 2})


def test_mp_overlap_off_is_bitwise_noop(mesh8):
    """FLAGS off + mp_overlap='auto' must lower to byte-identical HLO as
    an explicit mp_overlap=None build (the telemetry no-op pattern)."""
    paddle.set_flags({"FLAGS_mp_seq_parallel": False,
                      "FLAGS_mp_collective_matmul": False})
    tokens, labels = _data()

    def build(mode):
        step, shard, init = G.build_hybrid_train_step(
            CFG, mesh8, paddle.optimizer.AdamW(1e-2), num_microbatches=2,
            mp_overlap=mode)
        p = shard(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
        return step, p, init(p)

    step_none, p, s = build(None)
    base = step_none.lower(p, s, tokens, labels, LR).as_text()
    step_auto, _, _ = build("auto")
    assert step_auto.lower(p, s, tokens, labels, LR).as_text() == base

    # and ON genuinely changes the program
    step_sp, _, _ = build("seq_parallel")
    assert step_sp.lower(p, s, tokens, labels, LR).as_text() != base


def test_seq_parallel_and_ring_loss_parity(mesh8):
    """8-step fp32 loss parity of both sp modes vs the allreduce baseline
    on dp2·pp2·mp2 (50-step acceptance run: test_parity_50_steps, slow
    tier). Tolerance: see module docstring."""
    base = _run_gpt(mesh8, None, 8)
    sp = _run_gpt(mesh8, "seq_parallel", 8)
    ring = _run_gpt(mesh8, "collective_matmul", 8)
    assert base[0] == sp[0] == ring[0], "forward must match exactly"
    assert _max_rel(base, sp) < 1e-6, (base, sp)
    assert _max_rel(base, ring) < 1e-6, (base, ring)


@pytest.mark.slow
def test_parity_50_steps(mesh8):
    """ISSUE 5 acceptance: 50-step loss parity on the virtual 8-device
    mesh for both modes, fp32. The toy overfits to ~0.02 loss by step
    50, so the ulp-level grad reassociation (module docstring) shows up
    as ~1e-6 relative there — rtol 1e-5 with a small atol floor."""
    base = _run_gpt(mesh8, None, 50)
    sp = _run_gpt(mesh8, "seq_parallel", 50)
    ring = _run_gpt(mesh8, "collective_matmul", 50)
    np.testing.assert_allclose(sp, base, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ring, base, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_parity_bf16(mesh8):
    """bf16 compute dtype: step 0 (identical params) must match exactly
    and step 1 to ~1e-4 — a WRONG gradient (e.g. the unsummed SP-param
    grads this suite exists to catch) shows up at step 1 at 3e-3.
    Beyond that, bf16 QUANTIZES the fp32 ulp noise (a 1e-8 param
    difference crosses bf16 rounding boundaries, measured ~3e-3 by step
    2 on this overfitting toy), so longer bf16 horizons only get a
    sanity band — the 50-step acceptance run is the fp32 one."""
    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                      num_heads=4, max_seq_len=16, dtype=jnp.bfloat16)
    base = _run_gpt(mesh8, None, 3, cfg=cfg)
    sp = _run_gpt(mesh8, "seq_parallel", 3, cfg=cfg)
    assert base[0] == sp[0], (base, sp)
    assert abs(base[1] - sp[1]) / abs(base[1]) < 5e-4, (base, sp)
    assert _max_rel(base, sp) < 2e-2, (base, sp)


def _f32_ulps(a, b):
    bits = lambda x: int(np.float32(x).view(np.int32))
    return abs(bits(a) - bits(b))


def test_mp1_degenerate():
    """mp=1 mesh: every sp collective degenerates to identity/local
    matmul, so the three modes compute the same math. They are NOT the
    same program, though: the sp modes still trace their sequence
    slicing (dynamic_slice at axis_index * S) and the ring its one-chunk
    loop, the lowered text differs in thousands of lines, and XLA fuses
    — and so associates the float32 sums of — each differently. Step 0
    (before any update feeds a difference back) is exact; afterwards the
    losses agree to a few float32 ulps (measured on jax 0.9.0: sp
    bitwise, ring 1 ulp at step 2). Bound: 4 ulps."""
    mesh = dist.build_mesh({"dp": 4, "pp": 2, "mp": 1})
    base = _run_gpt(mesh, None, 3)
    sp = _run_gpt(mesh, "seq_parallel", 3)
    ring = _run_gpt(mesh, "collective_matmul", 3)
    assert base[0] == sp[0] == ring[0], (base, sp, ring)
    for other in (sp, ring):
        ulps = [_f32_ulps(x, y) for x, y in zip(base, other)]
        assert max(ulps) <= 4, (base, other, ulps)


@pytest.mark.slow
def test_fp8_zero1_compose(mesh8):
    """seq-parallel composes with fp8 delayed scaling + ZeRO-1: the site
    GEMMs see the gathered full-sequence input (same values as the
    allreduce path's replicated input), so the fp8 trajectories track —
    step 0 exactly, then to quantization-amplified reassociation noise
    (a grad ulp shifts an amax, which shifts next step's scales;
    measured ≤2e-4 over 4 steps)."""
    base = _run_gpt(mesh8, None, 4, fp8=True, zero1_dp=True)
    sp = _run_gpt(mesh8, "seq_parallel", 4, fp8=True, zero1_dp=True)
    assert base[0] == sp[0], (base, sp)
    assert _max_rel(base, sp) < 5e-4, (base, sp)


def test_ring_refuses_fp8(mesh8):
    with pytest.raises(EnforceNotMet, match="amax"):
        G.build_hybrid_train_step(
            CFG, mesh8, paddle.optimizer.AdamW(1e-2), num_microbatches=2,
            mp_overlap="collective_matmul", fp8=True)
    from paddle_tpu.models import llama as L
    lcfg = L.llama_tiny(dtype=jnp.float32)
    with pytest.raises(EnforceNotMet, match="amax"):
        L.build_hybrid_train_step(
            lcfg, mesh8, paddle.optimizer.AdamW(1e-2), num_microbatches=2,
            mp_overlap="collective_matmul", fp8=True)


# ---------------------------------------------------------------------------
# HLO collective mix (guards against silent fallback to the replicated
# path — loss parity alone cannot distinguish the modes)
# ---------------------------------------------------------------------------
def test_hlo_collective_mix(mesh8):
    tokens, labels = _data()

    def counts(mode):
        step, shard, init = G.build_hybrid_train_step(
            CFG, mesh8, paddle.optimizer.AdamW(1e-2), num_microbatches=2,
            mp_overlap=mode)
        p = shard(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
        s = init(p)
        return collective_counts(
            step.lower(p, s, tokens, labels, LR).as_text())

    base, sp, ring = counts(None), counts("seq_parallel"), \
        counts("collective_matmul")
    # baseline: pure all-reduce TP — no AG/RS anywhere, only the pp
    # pipeline's two ppermutes
    assert base["all_gather"] == 0 and base["reduce_scatter"] == 0, base
    # seq-parallel: the per-layer ACTIVATION all-reduce pairs become
    # AG+RS. (Raw all-reduce op COUNTS are not a clean discriminator
    # here: sp adds [H]-sized grad psums for the replicated-but-SP
    # ln/bias params — more ops, vastly fewer bytes — so the mode
    # signature is the AG/RS/permute mix.)
    assert sp["all_gather"] > 0 and sp["reduce_scatter"] > 0, sp
    # collective matmul: the AG/RS pairs become ppermute rings (the
    # baseline's permutes are the pp pipeline's — the ring adds more)
    assert ring["collective_permute"] > sp["collective_permute"], (sp, ring)
    assert ring["all_gather"] < sp["all_gather"], (sp, ring)
    assert ring["reduce_scatter"] < sp["reduce_scatter"], (sp, ring)


def test_llama_hlo_collective_mix(mesh8):
    from paddle_tpu.models import llama as L
    lcfg = L.LlamaConfig(vocab_size=64, hidden_size=32, num_layers=4,
                         num_heads=4, num_kv_heads=2, intermediate_size=64,
                         max_seq_len=16, dtype=jnp.float32)
    tokens, labels = _data()

    def counts(mode):
        step, shard, init = L.build_hybrid_train_step(
            lcfg, mesh8, paddle.optimizer.AdamW(1e-2), num_microbatches=2,
            mp_overlap=mode)
        p = shard(L.init_hybrid_params(lcfg, jax.random.PRNGKey(0)))
        s = init(p)
        return collective_counts(
            step.lower(p, s, tokens, labels, LR).as_text())

    base, sp, ring = counts(None), counts("seq_parallel"), \
        counts("collective_matmul")
    assert sp["all_reduce"] < base["all_reduce"], (base, sp)
    assert sp["all_gather"] > base["all_gather"], (base, sp)
    assert ring["collective_permute"] > sp["collective_permute"], (sp, ring)


@pytest.mark.slow
def test_llama_seq_parallel_parity(mesh8):
    from paddle_tpu.models import llama as L
    lcfg = L.LlamaConfig(vocab_size=64, hidden_size=32, num_layers=4,
                         num_heads=4, num_kv_heads=2, intermediate_size=64,
                         max_seq_len=16, dtype=jnp.float32)
    tokens, labels = _data()

    def run(mode, steps=8):
        opt = paddle.optimizer.AdamW(1e-2)
        step, shard, init = L.build_hybrid_train_step(
            lcfg, mesh8, opt, num_microbatches=2, mp_overlap=mode)
        p = shard(L.init_hybrid_params(lcfg, jax.random.PRNGKey(0)))
        s = init(p)
        out = []
        for _ in range(steps):
            p, s, loss = step(p, s, tokens, labels, LR)
            out.append(float(loss))
        return out

    base, sp, ring = run(None), run("seq_parallel"), \
        run("collective_matmul")
    assert _max_rel(base, sp) < 1e-6, (base, sp)
    assert _max_rel(base, ring) < 1e-6, (base, ring)


# ---------------------------------------------------------------------------
# Telemetry: comms_bytes matches the analytic wire model
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", [None, "seq_parallel",
                                  "collective_matmul"])
def test_telemetry_comms_matches_analytic(mode):
    """dp=1 mesh (zero dp-sync bytes) so comms_bytes isolates the mp
    path; expected value re-derived here from the documented wire model
    — the engine must deposit exactly this constant every step."""
    import paddle_tpu.observability as obs
    mesh = dist.build_mesh({"dp": 1, "pp": 2, "mp": 4})
    tcfg = obs.TelemetryConfig(interval=2)
    step, shard, init = G.build_hybrid_train_step(
        CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=2,
        mp_overlap=mode, telemetry=tcfg)
    p = shard(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s = init(p)
    tokens, labels = _data(batch=4)
    host = obs.TelemetryHost(tcfg)
    for i in range(2):
        p, s, loss = step(p, s, tokens, labels, jnp.float32(1e-3))
        host.poll(s, i)

    mp, pp, M = 4, 2, 2
    b, S, H = 4, 16, CFG.hidden_size
    dt = 4  # fp32 activations
    a_blk = (b // M) * S * H * dt
    a_full = b * S * H * dt
    executed = (M + pp - 1) * (CFG.num_layers // pp)
    expected = obs.mp_wire_bytes(
        "allreduce" if mode is None else mode, mp,
        gemm_pair_bytes=2.0 * executed * a_blk,
        allreduce_bytes=2.0 * a_full + 4.0 * b * S * 4,
        scatter_bytes=a_full)
    got = host.series["comms_bytes"][-1]
    assert got == pytest.approx(expected, rel=1e-6), (got, expected)
    if mode is not None:
        assert tcfg.static["mp_mode"] == mode
        # sp modes pay the embed scatter's backward all-gather on top of
        # the (byte-identical) GEMM-pair and boundary terms
        f = (mp - 1) / mp
        base_expected = obs.mp_wire_bytes(
            "allreduce", mp, gemm_pair_bytes=2.0 * executed * a_blk,
            allreduce_bytes=2.0 * a_full + 4.0 * b * S * 4,
            scatter_bytes=a_full)
        assert expected == pytest.approx(base_expected + f * a_full)


def test_telemetry_comms_analytic_vpp():
    """The executed-block count is schedule-aware: the interleaved
    pipeline runs V*M+P-1 ticks of ONE L/(P*V)-layer chunk (not M+P-1
    ticks of the whole stage — the 1F1B formula overstates vpp wire
    bytes by the bubble difference)."""
    import paddle_tpu.observability as obs
    mesh = dist.build_mesh({"dp": 1, "pp": 2, "mp": 4})
    tcfg = obs.TelemetryConfig(interval=1)
    step, shard, init = G.build_hybrid_train_step(
        CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=2,
        virtual_pp=2, mp_overlap="seq_parallel", telemetry=tcfg)
    p = shard(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s = init(p)
    tokens, labels = _data(batch=4)
    host = obs.TelemetryHost(tcfg)
    p, s, _ = step(p, s, tokens, labels, jnp.float32(1e-3))
    host.poll(s, 0)

    mp, pp, M, V = 4, 2, 2, 2
    b, S, H, dt = 4, 16, CFG.hidden_size, 4
    a_blk = (b // M) * S * H * dt
    a_full = b * S * H * dt
    l_local = CFG.num_layers // pp
    executed = (V * M + pp - 1) * (l_local / V)
    expected = obs.mp_wire_bytes(
        "seq_parallel", mp, gemm_pair_bytes=2.0 * executed * a_blk,
        allreduce_bytes=2.0 * a_full + 4.0 * b * S * 4,
        scatter_bytes=a_full)
    assert host.series["comms_bytes"][-1] == pytest.approx(expected,
                                                           rel=1e-6)


# ---------------------------------------------------------------------------
# Validation (ISSUE 5 small fix)
# ---------------------------------------------------------------------------
def test_mp_ops_axis_validation():
    """c_identity / mp_allreduce / the sp entry points raise a typed
    InvalidArgumentError (not an opaque jax trace error) when the named
    axis is not in scope."""
    x = jnp.ones((2, 4, 8))
    for fn in (lambda: mp_ops.c_identity(x, "mp"),
               lambda: mp_ops.mp_allreduce(x, "mp"),
               lambda: mp_ops.c_split(x, "mp"),
               lambda: mp_ops.c_concat(x, "mp"),
               lambda: mp_ops.ag_matmul(x, jnp.ones((8, 4)), "mp"),
               lambda: mp_ops.matmul_rs(x, jnp.ones((8, 4)), "mp")):
        with pytest.raises(InvalidArgumentError, match="not in scope"):
            fn()
    # ...and a wrong NAME inside shard_map is equally typed
    mesh = dist.build_mesh({"mp": 8})

    def local(x):
        return mp_ops.mp_allreduce(x, "model")  # no such axis

    with pytest.raises(InvalidArgumentError, match="not in scope"):
        jax.jit(shard_map(local, mesh=mesh, in_specs=(P(),),
                          out_specs=P()))(x)

    # ...including on DIFFERENTIATED paths, where custom_vjp fwd rules
    # replace the primal (c_concat routes its fwd through the validated
    # entry)
    def local_grad(x):
        return jax.grad(
            lambda x: jnp.sum(mp_ops.c_concat(x, "model") ** 2))(x)

    with pytest.raises(InvalidArgumentError, match="not in scope"):
        jax.jit(shard_map(local_grad, mesh=mesh, in_specs=(P(),),
                          out_specs=P()))(x)


def test_mp_ops_shape_validation():
    mesh = dist.build_mesh({"mp": 8})
    x = jnp.ones((2, 4, 6))  # 6 not divisible by 8

    def split_bad(x):
        return mp_ops.c_split(x, "mp", dim=-1)

    with pytest.raises(EnforceNotMet, match="divisible"):
        jax.jit(shard_map(split_bad, mesh=mesh, in_specs=(P(),),
                          out_specs=P("mp")))(x)

    def rs_bad(x):
        return mp_ops.matmul_rs(x, jnp.ones((6, 6)), "mp")  # S=4 % 8 != 0

    with pytest.raises(EnforceNotMet, match="divisible"):
        jax.jit(shard_map(rs_bad, mesh=mesh, in_specs=(P(),),
                          out_specs=P(None, "mp", None)))(x)
