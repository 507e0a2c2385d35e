"""Pallas flash attention in the training hot path (ISSUE 12).

Covers: direct kernel fwd/bwd parity vs the composed einsum (fp32 +
bf16), flags-off bitwise-identical lowered HLO, multi-step hybrid-loss
parity on the dp2·pp2·mp2 virtual mesh (50-step acceptance run in the
slow tier), bitwise equality of the remat modes (full replay vs
FLASH_REMAT_NAMES selective reuse), the jaxpr-level kernel-presence /
scores-absence assertions (a silent fallback to the composed path cannot
pass), the compose matrix (sp/ring mp-overlap × zero1 × {1F1B, ZBH1,
VPP} × fp8 GEMMs), the sep context-parallel legs (ring vs Ulysses,
single-process), MoE + llama legs, the sep/ulysses refusals, and the
planner's flash axis (validity, prune reasons, long-S activation-HBM
drop, honest compute cost).

Parity tolerance note: the fused kernel computes the same softmax
attention as the composed path but with online (tiled) normalization —
fp32 trajectories agree to reassociation noise (measured ≤1e-6 rel over
8 steps, the first steps usually bit-equal on this toy), never
guaranteed bit-for-bit. The bitwise guarantees of this PR are (a) the
OFF path — flash_attention=None/flags-off compiles byte-identical HLO —
and (b) ACROSS REMAT MODES with flash on: replaying the deterministic
kernel equals reusing its saved residuals exactly.

CPU tier-1 runs the kernels in interpreter mode (kernels.pallas._common)
— the whole matrix is testable off-TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.enforce import EnforceNotMet
from paddle_tpu.kernels.pallas import flash_attention as fa
from paddle_tpu.kernels.pallas import flash_training as ft
from paddle_tpu.models import gpt as G
from paddle_tpu.models import llama as L

from hlo_utils import attention_scores_dots, pallas_call_count

CFG = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
                  max_seq_len=64, dtype=jnp.float32)
SEQ = 64
LR = jnp.float32(1e-2)


def _data(batch=8, seq=SEQ, vocab=None, seed=0):
    rng = np.random.RandomState(seed)
    v = vocab or CFG.vocab_size
    return (jnp.asarray(rng.randint(0, v, (batch, seq))),
            jnp.asarray(rng.randint(0, v, (batch, seq))))


def _run_gpt(mesh, flash, steps, cfg=CFG, microbatches=2, **kw):
    opt = paddle.optimizer.AdamW(1e-2)
    step, shard, init = G.build_hybrid_train_step(
        cfg, mesh, opt, num_microbatches=microbatches,
        flash_attention=flash, **kw)
    p = shard(G.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    s = init(p)
    tokens, labels = _data(vocab=cfg.vocab_size)
    losses = []
    for _ in range(steps):
        p, s, loss = step(p, s, tokens, labels, LR)
        losses.append(float(loss))
    return losses


def _max_rel(a, b):
    return max(abs(x - y) / max(abs(x), 1e-12) for x, y in zip(a, b))


def _composed(q, k, v):
    """The reference O(S²) causal attention (gpt._attention math)."""
    import math
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    S = logits.shape[-1]
    logits = jnp.where(jnp.tril(jnp.ones((S, S), bool)), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# Kernel level
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol_o,tol_g",
                         [(jnp.float32, 2e-5, 2e-4),
                          (jnp.bfloat16, 2e-2, 5e-2)],
                         ids=["fp32", "bf16"])
def test_flash_matches_composed_fwd_bwd(dtype, tol_o, tol_g):
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(2, 128, 4, 16)).astype(dtype)
               for _ in range(3))

    def loss_flash(q, k, v):
        return jnp.sum(ft.attention(q, k, v, ft.FlashAttentionConfig())
                       .astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_composed(q, k, v).astype(jnp.float32) ** 2)

    o_f = ft.attention(q, k, v, ft.FlashAttentionConfig())
    o_r = _composed(q, k, v)
    np.testing.assert_allclose(np.asarray(o_f, np.float32),
                               np.asarray(o_r, np.float32), atol=tol_o)
    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_r):
        scale = max(float(jnp.abs(b.astype(jnp.float32)).max()), 1.0)
        np.testing.assert_allclose(np.asarray(a, np.float32) / scale,
                                   np.asarray(b, np.float32) / scale,
                                   atol=tol_g)


def test_resolve_and_flags():
    """resolve_flash_attention mirrors the fp8/mp_overlap resolution
    contract, and 'auto' reads FLAGS_flash_attention / FLAGS_flash_sep."""
    assert ft.resolve_flash_attention(None) is None
    assert ft.resolve_flash_attention(False) is None
    assert ft.resolve_flash_attention("auto") is None  # flags default off
    assert ft.resolve_flash_attention(True).sep is None
    assert ft.resolve_flash_attention("ring").sep == "ring"
    cfg = ft.FlashAttentionConfig(block_q=256)
    assert ft.resolve_flash_attention(cfg) is cfg
    paddle.set_flags({"FLAGS_flash_attention": True,
                      "FLAGS_flash_sep": "ulysses"})
    try:
        r = ft.resolve_flash_attention("auto")
        assert r is not None and r.sep == "ulysses"
        # a sep flag WITHOUT the flash flag is a loud misconfiguration,
        # not a silent einsum fallback
        paddle.set_flags({"FLAGS_flash_attention": False})
        with pytest.raises(EnforceNotMet, match="flash_sep"):
            ft.resolve_flash_attention("auto")
    finally:
        paddle.set_flags({"FLAGS_flash_attention": False,
                          "FLAGS_flash_sep": ""})
    with pytest.raises(EnforceNotMet):
        ft.FlashAttentionConfig(sep="nope")


# ---------------------------------------------------------------------------
# Engine level: off = bitwise no-op, on = parity + kernel presence
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mesh8():
    return dist.build_mesh({"dp": 2, "pp": 2, "mp": 2})


def _build(mesh, flash, cfg=CFG, **kw):
    opt = paddle.optimizer.AdamW(1e-2)
    step, shard, init = G.build_hybrid_train_step(
        cfg, mesh, opt, num_microbatches=2, flash_attention=flash, **kw)
    p = shard(G.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    return step, p, init(p)


def test_flash_off_is_bitwise_noop(mesh8):
    """FLAGS off + flash_attention='auto' must lower to byte-identical
    HLO as an explicit flash_attention=None build (the mp_overlap/
    telemetry no-op pattern) — and ON genuinely changes the program."""
    tokens, labels = _data()
    step_none, p, s = _build(mesh8, None)
    base = step_none.lower(p, s, tokens, labels, LR).as_text()
    step_auto, _, _ = _build(mesh8, "auto")
    assert step_auto.lower(p, s, tokens, labels, LR).as_text() == base
    step_on, _, _ = _build(mesh8, True)
    assert step_on.lower(p, s, tokens, labels, LR).as_text() != base


def test_flash_kernel_present_einsum_scores_absent(mesh8):
    """The anti-silent-fallback gate: flash on ⇒ pallas_call eqns in the
    traced step and ZERO rank-4 (S, S) scores dots; flash off ⇒ the
    reverse. (Compiled-TPU text would additionally show tpu_custom_call —
    hlo_utils.pallas_custom_call_count; interpret-mode CPU lowering has
    no custom-call marker, hence the jaxpr-level counters.)"""
    tokens, labels = _data()
    step_off, p, s = _build(mesh8, None)
    assert pallas_call_count(step_off, p, s, tokens, labels, LR) == 0
    assert attention_scores_dots(step_off, p, s, tokens, labels, LR,
                                 seq=SEQ) > 0
    step_on, _, _ = _build(mesh8, True)
    assert pallas_call_count(step_on, p, s, tokens, labels, LR) > 0
    assert attention_scores_dots(step_on, p, s, tokens, labels, LR,
                                 seq=SEQ) == 0


def test_flash_hybrid_loss_parity(mesh8):
    """8-step fp32 loss parity of the flash hybrid step vs the einsum
    baseline on dp2·pp2·mp2 (50-step acceptance run: slow tier)."""
    base = _run_gpt(mesh8, None, 8)
    fl = _run_gpt(mesh8, True, 8)
    assert _max_rel(base, fl) < 1e-5, (base, fl)


def test_remat_modes_bitwise_equal():
    """Full remat (replay the flash forward kernel) and selective remat
    (reuse the FLASH_REMAT_NAMES-saved out/lse residuals) must agree
    BITWISE — the kernel is deterministic, so replay == reuse exactly.
    The saved-residual mode provably skips the replay: its traced
    backward contains one fewer pallas_call."""
    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=SEQ, dtype=jnp.float32)
    tokens, labels = _data(batch=4, vocab=cfg.vocab_size)
    p = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    plan = ft.FlashAttentionConfig()

    def vg(remat_save):
        return jax.jit(jax.value_and_grad(
            lambda p: G.dense_loss(p, tokens, labels, cfg,
                                   remat_save=remat_save, flash=plan)))

    l_full, g_full = vg(())(p)
    l_sel, g_sel = vg(("attn_out", "qkv"))(p)
    assert float(l_full) == float(l_sel)
    eq = jax.tree.map(lambda a, b: bool((a == b).all()), g_full, g_sel)
    assert all(jax.tree.leaves(eq)), eq
    n_full = pallas_call_count(vg(()), p)
    n_sel = pallas_call_count(vg(("attn_out", "qkv")), p)
    assert n_sel < n_full, (n_sel, n_full)


@pytest.mark.parametrize("attention", ["kernel", "composed"])
def test_dense_block_keeps_what_its_attention_made(attention, monkeypatch,
                                                   capsys):
    """dense_loss with every default (no flash= plan, the benchmark's
    call): a block keeps `qkv` and ONE copy of the attention output. Where
    the registry op takes its Pallas arm (forced as test_chip_compile
    forces it; the kernel runs in the interpreter here) that copy is the
    kernel's `flash_out`, with `flash_lse` beside it, so the backward
    kernels start without a second forward kernel; on the composed path
    it is `attn_out` and there is no lse. Never both. Loss and gradients
    are remat=False's, bitwise, either way, and the kernel path traces as
    many kernels as remat=False does (forward, dq, dkv: no replay)."""
    from paddle_tpu.ops import registry
    monkeypatch.setattr(registry, "_on_tpu", lambda: attention == "kernel")
    seq, batch = 128, 2             # the kernel's gate wants S % 128 == 0
    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=seq, dtype=jnp.float32)
    nl, nh, hd, H = (cfg.num_layers, cfg.num_heads, cfg.head_dim,
                     cfg.hidden_size)
    tokens, labels = _data(batch=batch, seq=seq, vocab=cfg.vocab_size)
    p = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))

    def loss(p, **kw):
        return G.dense_loss(p, tokens, labels, cfg, **kw)

    jax.ad_checkpoint.print_saved_residuals(loss, p)
    kept = [line.split()[0] for line in capsys.readouterr().out.splitlines()
            if "output of scan" in line]
    shapes = {"qkv": f"f32[{nl},{batch},{seq},{3 * H}]",
              "attn_out": f"f32[{nl},{batch},{seq},{nh},{hd}]",
              # head_dim 8 is not lane-native: the kernel's own layout
              "flash_out": f"f32[{nl},{batch * nh},{seq},{hd}]",
              "flash_lse": f"f32[{nl},{batch * nh},{seq}]",
              "carry": f"f32[{nl},{batch},{seq},{H}]"}
    want = (("qkv", "flash_out", "flash_lse") if attention == "kernel"
            else ("qkv", "attn_out"))
    assert sorted(kept) == sorted(shapes[n] for n in want + ("carry",)), kept

    vg = jax.value_and_grad(loss)
    vg_plain = jax.value_and_grad(lambda p: loss(p, remat=False))
    n_kernels = pallas_call_count(vg, p)
    assert n_kernels == (pallas_call_count(vg_plain, p)
                         if attention == "kernel" else 0), n_kernels
    # one primitive at a time: what the CPU compiler would fuse differently
    # in the two programs is no part of the comparison
    with jax.disable_jit():
        (l, g), (l0, g0) = vg(p), vg_plain(p)
    assert float(l) == float(l0)
    eq = jax.tree.map(lambda a, b: bool((a == b).all()), g, g0)
    assert all(jax.tree.leaves(eq)), eq


# ---------------------------------------------------------------------------
# Compose matrix: sp/ring × zero1 × {1F1B, ZBH1, VPP} × fp8
# ---------------------------------------------------------------------------
def test_compose_sp_zero1(mesh8):
    """Fast-tier compose gate: flash under seq-parallel TP + ZeRO-1
    tracks its own einsum baseline (attention consumes the gathered full
    sequence; heads stay local under TP)."""
    kw = dict(mp_overlap="seq_parallel", zero1_dp=True)
    base = _run_gpt(mesh8, None, 4, **kw)
    fl = _run_gpt(mesh8, True, 4, **kw)
    assert _max_rel(base, fl) < 1e-5, (base, fl)


@pytest.mark.slow
@pytest.mark.parametrize("kw", [
    dict(mp_overlap="seq_parallel"),
    dict(mp_overlap="collective_matmul"),
    dict(mp_overlap="collective_matmul", zero1_dp=True),
    dict(schedule="ZBH1"),
    dict(schedule="ZBH1", mp_overlap="seq_parallel", zero1_dp=True),
    dict(virtual_pp=2),
    dict(virtual_pp=2, mp_overlap="seq_parallel"),
    dict(fp8=True),
    dict(fp8=True, mp_overlap="seq_parallel", zero1_dp=True),
], ids=["sp", "ring", "ring-zero1", "zbh1", "zbh1-sp-zero1", "vpp",
        "vpp-sp", "fp8", "fp8-sp-zero1"])
def test_compose_matrix(mesh8, kw):
    """Each leg: the flash step vs ITS OWN einsum baseline under the same
    flags, 4 steps fp32 (fp8 legs: quantization-amplified tolerance —
    an attention-output ulp shifts an amax, which shifts next-step
    scales)."""
    tol = 5e-4 if kw.get("fp8") else 1e-5
    base = _run_gpt(mesh8, None, 4, **kw)
    fl = _run_gpt(mesh8, True, 4, **kw)
    assert _max_rel(base, fl) < tol, (kw, base, fl)


@pytest.mark.slow
def test_flash_50_step_trajectory(mesh8):
    """ISSUE 12 acceptance: the flash hybrid trajectory tracks the einsum
    baseline over 50 steps on dp2·pp2·mp2, fp32 (measured ~1e-6 rel as
    the toy overfits — the ≤2e-2 acceptance band is vast headroom; kept
    loose because kernel-vs-composed reassociation noise is chaotic)."""
    base = _run_gpt(mesh8, None, 50)
    fl = _run_gpt(mesh8, True, 50)
    assert _max_rel(base, fl) < 2e-2, (base, fl)


@pytest.mark.slow
def test_flash_bf16_tracks(mesh8):
    """bf16 compute dtype: step 0 must agree to kernel-vs-composed
    rounding (the composed path also accumulates in fp32, so only the
    online-softmax reassociation differs), later steps to the bf16
    quantization band (the mp_overlap bf16 pattern)."""
    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                      num_heads=4, max_seq_len=SEQ, dtype=jnp.bfloat16)
    base = _run_gpt(mesh8, None, 3, cfg=cfg)
    fl = _run_gpt(mesh8, True, 3, cfg=cfg)
    assert abs(base[0] - fl[0]) / abs(base[0]) < 2e-3, (base, fl)
    assert _max_rel(base, fl) < 2e-2, (base, fl)


@pytest.mark.slow
def test_moe_flash_parity():
    """GPT-MoE on the ep mesh: flash threads through the MoE block's
    attention sublayer (_moe_block_fn) — parity vs the MoE einsum
    baseline."""
    cfg = G.gpt_moe_tiny(dtype=jnp.float32)
    mesh = dist.build_mesh({"dp": 2, "ep": 2, "pp": 1, "mp": 2})
    tokens, labels = _data(vocab=cfg.vocab_size)

    def run(flash):
        opt = paddle.optimizer.AdamW(1e-2)
        step, shard, init = G.build_hybrid_train_step(
            cfg, mesh, opt, flash_attention=flash)
        p = shard(G.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
        s = init(p)
        out = []
        for _ in range(4):
            p, s, l = step(p, s, tokens, labels, LR)
            out.append(float(l))
        return out

    base = run(None)
    fl = run(True)
    assert _max_rel(base, fl) < 1e-4, (base, fl)


def test_llama_flash_parity(mesh8):
    """Llama (GQA: 4 q heads, 2 kv heads over mp2 — one kv head per
    rank, KV indexed not repeated): flash vs the registry baseline."""
    cfg = L.llama_tiny(dtype=jnp.float32)
    tokens, labels = _data(vocab=cfg.vocab_size)

    def run(flash):
        opt = paddle.optimizer.AdamW(1e-2)
        step, shard, init = L.build_hybrid_train_step(
            cfg, mesh8, opt, num_microbatches=2, flash_attention=flash)
        p = shard(L.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
        s = init(p)
        out = []
        for _ in range(3):
            p, s, l = step(p, s, tokens, labels, LR)
            out.append(float(l))
        return out

    base = run(None)
    fl = run(True)
    assert _max_rel(base, fl) < 1e-4, (base, fl)


# ---------------------------------------------------------------------------
# sep context parallelism (ring / Ulysses), single-process
# ---------------------------------------------------------------------------
def test_sep_ring_vs_ulysses_parity():
    """ISSUE 12 sep leg: the same global problem on a dp2·sep2·mp2 mesh
    (sequence sharded over sep) under ring AND Ulysses context
    parallelism must track a sep-free flash baseline on dp4·mp2 — the
    global loss is the same mean over the same tokens either way."""
    mesh_sep = dist.build_mesh({"dp": 2, "sep": 2, "pp": 1, "mp": 2})
    mesh_dp4 = dist.build_mesh({"dp": 4, "pp": 1, "mp": 2})
    base = _run_gpt(mesh_dp4, True, 4)
    ring = _run_gpt(mesh_sep, "ring", 4)
    uly = _run_gpt(mesh_sep, "ulysses", 4)
    assert _max_rel(base, ring) < 1e-5, (base, ring)
    assert _max_rel(base, uly) < 1e-5, (base, uly)


@pytest.mark.slow
def test_llama_sep_ring_parity():
    """Llama sep ring (RoPE tables sliced to each rank's GLOBAL
    positions; rotated K blocks travel the ring)."""
    cfg = L.llama_tiny(dtype=jnp.float32)
    tokens, labels = _data(vocab=cfg.vocab_size)
    mesh_sep = dist.build_mesh({"dp": 2, "sep": 2, "pp": 1, "mp": 2})
    mesh_dp4 = dist.build_mesh({"dp": 4, "pp": 1, "mp": 2})

    def run(mesh, flash):
        opt = paddle.optimizer.AdamW(1e-2)
        step, shard, init = L.build_hybrid_train_step(
            cfg, mesh, opt, num_microbatches=2, flash_attention=flash)
        p = shard(L.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
        s = init(p)
        out = []
        for _ in range(4):
            p, s, l = step(p, s, tokens, labels, LR)
            out.append(float(l))
        return out

    base = run(mesh_dp4, True)
    ring = run(mesh_sep, "ring")
    assert _max_rel(base, ring) < 1e-5, (base, ring)


def test_sep_refusals(mesh8):
    """sep needs the mesh axis; is not composed with mp sequence
    parallelism (both shard the sequence) or MoE; ulysses needs
    heads/mp divisible by the sep degree."""
    opt = paddle.optimizer.AdamW(1e-2)
    with pytest.raises(EnforceNotMet, match="mesh axis"):
        G.build_hybrid_train_step(CFG, mesh8, opt, num_microbatches=2,
                                  flash_attention="ring")
    mesh_sep = dist.build_mesh({"dp": 2, "sep": 2, "pp": 1, "mp": 2})
    with pytest.raises(EnforceNotMet, match="sequence"):
        G.build_hybrid_train_step(CFG, mesh_sep, opt, num_microbatches=2,
                                  flash_attention="ring",
                                  mp_overlap="seq_parallel")
    moe_cfg = G.gpt_moe_tiny(dtype=jnp.float32)
    mesh_moe = dist.build_mesh({"dp": 1, "ep": 2, "sep": 2, "pp": 1,
                                "mp": 2})
    with pytest.raises(EnforceNotMet, match="MoE"):
        G.build_hybrid_train_step(moe_cfg, mesh_moe, opt,
                                  flash_attention="ring")
    # 4 heads / mp2 = 2 local heads; sep4 cannot take a head shard
    mesh_s4 = dist.build_mesh({"dp": 1, "sep": 4, "pp": 1, "mp": 2})
    with pytest.raises(EnforceNotMet, match="ulysses"):
        G.build_hybrid_train_step(CFG, mesh_s4, opt, num_microbatches=1,
                                  flash_attention="ulysses")
    lcfg = L.llama_tiny()
    with pytest.raises(EnforceNotMet, match="ulysses"):
        # kv heads 2 / mp2 = 1 per rank; sep2 cannot shard it
        mesh_l = dist.build_mesh({"dp": 2, "sep": 2, "pp": 1, "mp": 2})
        L.build_hybrid_train_step(lcfg, mesh_l, opt, num_microbatches=1,
                                  flash_attention="ulysses")
    # the GLOBAL sequence must fit the position table: dynamic_slice
    # would silently CLAMP an out-of-range start and hand later sep
    # ranks the first ranks' position rows — must refuse at trace
    step, shard, init = G.build_hybrid_train_step(
        CFG, mesh_sep, opt, num_microbatches=2, flash_attention="ring")
    p = shard(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s = init(p)
    big_t, big_l = _data(seq=2 * CFG.max_seq_len)  # global = 2x table
    with pytest.raises(EnforceNotMet, match="max_seq_len"):
        step(p, s, big_t, big_l, LR)


# ---------------------------------------------------------------------------
# Planner: the flash_attention axis
# ---------------------------------------------------------------------------
def test_planner_flash_axis_validity_and_prunes():
    from paddle_tpu.distributed.auto_tuner import planner as PL
    spec = PL.ModelSpec.from_config(G.gpt_tiny(), "gpt")
    ok = PL.PlanCandidate(dp=4, mp=2, flash_attention=True)
    assert PL.check_candidate(ok, spec, world=8, global_batch=8,
                              seq=256) is None
    kw = ok.engine_kwargs(family="gpt")
    assert kw["flash_attention"] is True
    assert "flash" in str(ok)
    # seq not a lane multiple -> pruned with a stated reason
    r = PL.check_candidate(ok, spec, world=8, global_batch=8, seq=192)
    assert r and "128" in r
    # head_dim > 256 -> pruned
    big = G.GPTConfig(vocab_size=1024, hidden_size=1024, num_layers=2,
                      num_heads=2, max_seq_len=256)
    spec_big = PL.ModelSpec.from_config(big, "gpt")
    r = PL.check_candidate(PL.PlanCandidate(dp=4, mp=2,
                                            flash_attention=True),
                           spec_big, world=8, global_batch=8, seq=256)
    assert r and "head_dim" in r
    # the default enumeration emits flash-aware candidates
    cands, _ = PL.generate_plan_candidates(spec, 8, global_batch=8,
                                           seq=256)
    assert any(c.flash_attention for c in cands)
    assert any(not c.flash_attention for c in cands)


def test_planner_flash_hbm_drops_and_compute_honest():
    """The acceptance property: at long S the flash candidate's
    activation-HBM estimate drops vs the einsum estimate (O(S) vs O(S²)
    rematted scores), while its predicted compute is HIGHER (the
    two-kernel backward re-derives scores tiles) — flash wins the
    ranking exactly where memory binds, not by fiat."""
    from paddle_tpu.distributed.auto_tuner import planner as PL
    cfg = G.gpt_1p3b()
    spec = PL.ModelSpec.from_config(cfg, "gpt")
    cm = PL.CostModel(spec, PL.KNOWN_PROFILES["tpu-v5e"],
                      global_batch=8, seq=4096)
    base = PL.PlanCandidate(dp=1, mp=8)
    fl = PL.PlanCandidate(dp=1, mp=8, flash_attention=True)
    pb, pf = cm.predict(base), cm.predict(fl)
    assert pf.hbm["act"] < pb.hbm["act"]
    assert pf.compute_s > pb.compute_s
    assert pf.hbm["params"] == pb.hbm["params"]
    # the flops delta matches the analytic attention model exactly
    from paddle_tpu.observability import flops as F
    a_e = F.attention_flops_per_token(num_layers=cfg.num_layers,
                                      hidden_size=cfg.hidden_size,
                                      seq_len=4096, impl="einsum")
    a_f = F.attention_flops_per_token(num_layers=cfg.num_layers,
                                      hidden_size=cfg.hidden_size,
                                      seq_len=4096, impl="flash")
    want = (8 * 4096) * (a_f["hardware"] - a_e["hardware"]) / 8 \
        * ((1 + 1 - 1) / 1)
    assert abs((pf.compute_units - pb.compute_units) - want) \
        <= 1e-6 * want
    # and at a tight budget the einsum twin is pruned while flash fits
    budget = pf.hbm_bytes * 1.02 / 1e9
    rep = PL.plan(cfg, world=8, global_batch=8, seq=4096, family="gpt",
                  hbm_gb=budget,
                  micro_batch_options=(1,), schedules=("1f1b",),
                  vpp_options=(1,), zero_stage_options=(0,),
                  comm_bucket_options=(0.0,), mp_overlap_options=(None,))
    kept = {str(s.candidate) for s in rep.ranked}
    assert str(fl) in kept
    assert str(base) not in kept
    assert any("analytic HBM" in reason and str(c) == str(base)
               for c, reason in rep.pruned)


def test_flash_engine_kwargs_round_trip():
    """A planner-emitted flash candidate builds and steps through the
    real engine (the PR 9 round-trip pattern)."""
    from paddle_tpu.distributed.auto_tuner import planner as PL
    c = PL.PlanCandidate(dp=2, pp=2, mp=2, micro_batches=2,
                         flash_attention=True)
    spec = PL.ModelSpec.from_config(CFG, "gpt")
    assert PL.check_candidate(c, spec, world=8, global_batch=8,
                              seq=SEQ + 64) is None
    mesh = c.build_mesh()
    kw = c.engine_kwargs(family="gpt")
    opt = paddle.optimizer.AdamW(1e-2)
    step, shard, init = G.build_hybrid_train_step(CFG, mesh, opt, **kw)
    p = shard(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s = init(p)
    tokens, labels = _data()
    p, s, loss = step(p, s, tokens, labels, LR)
    assert np.isfinite(float(loss))
