"""Auto-parallel planner tests (reference analog: test/auto_tuner/ + the
semi-auto spmd_rules coverage).

Covers: candidate generation over the REAL hybrid-engine surface (the old
tuner's "sharding"/"sep" vocabulary is gone), engine_kwargs round-trips
through build_hybrid_train_step for every family, the shared MoE flop math
(bit-for-bit the frozen formulas), cost-model rankings against this
repo's RECORDED ground truth (PR 2 bucketed-overlap and PR 5 mp-overlap
directions on the TPU profile; the CPU-mesh op-count ordering
allreduce < sp < ring on the CPU profile), analytic-OOM-vs-compiled
``memory_analysis`` agreement, the CLI, and (slow tier) the
predicted-vs-measured CPU sweep with the documented tolerances.
"""

import io
import json
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import auto_tuner as AT
from paddle_tpu.distributed.auto_tuner import (AutoTuner, CostModel,
                                               KNOWN_PROFILES, ModelSpec,
                                               PlanCandidate, plan)
from paddle_tpu.distributed.auto_tuner.planner import check_candidate
from paddle_tpu.models import gpt as G
from paddle_tpu.models import llama as LL

GB, SEQ = 16, 128


def _tiny_gpt(**kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("param_dtype", jnp.float32)
    return G.gpt_tiny(**kw)


def _spec(cfg=None, family="gpt"):
    return ModelSpec.from_config(cfg if cfg is not None else _tiny_gpt(),
                                 family)


def _check(c, spec, world=8, gb=GB, seq=SEQ):
    return check_candidate(c, spec, world=world, global_batch=gb, seq=seq)


# ---------------------------------------------------------------------------
# Generation + constraints (the engine's real vocabulary).
# ---------------------------------------------------------------------------
def test_generate_covers_factorizations_on_real_axes():
    spec = _spec()
    cands, _ = AT.generate_plan_candidates(spec, 8, global_batch=GB,
                                           seq=SEQ)
    assert cands
    dims = {(c.dp, c.mp, c.pp) for c in cands}
    assert (8, 1, 1) in dims and (2, 2, 2) in dims and (2, 4, 1) in dims
    for c in cands:
        assert c.world == 8
        # the vocabulary the hybrid engine actually mounts — the stale
        # "sharding"/"sep" axes are gone for good
        assert set(c.mesh_dims()) == {"dp", "ep", "pp", "mp"}


def test_constraint_prune_reasons():
    spec = _spec()  # L=4, heads=4, vocab=1024
    c = PlanCandidate
    assert "heads" in _check(c(dp=1, mp=8), spec)
    assert "layers" in _check(c(dp=1, pp=8), spec)
    assert "micro_batches" in _check(c(dp=8, micro_batches=3), spec)
    assert "divisible by dp*ep" in _check(c(dp=8, micro_batches=1), spec,
                                          gb=12)
    assert "mp_overlap needs mp > 1" in _check(
        c(dp=8, mp_overlap="seq_parallel"), spec)
    assert "divisible by" in _check(
        c(dp=2, mp=4, mp_overlap="seq_parallel"), spec, seq=126)
    assert _check(c(dp=2, mp=4, mp_overlap="seq_parallel"), spec) is None
    # fp8 compose rules (one copy: the engine's own refusals)
    assert "1F1B" in _check(c(dp=2, pp=2, mp=2, vpp=2,
                              schedule="interleaved", micro_batches=2,
                              fp8=True), spec)
    assert "amax" in _check(c(dp=2, mp=4, fp8=True,
                              mp_overlap="collective_matmul"), spec)
    assert "comm_overlap" in _check(c(dp=8, fp8=True, comm_bucket_mb=4.0),
                                    spec)
    # degenerate schedules
    assert "pp > 1" in _check(c(dp=8, schedule="zbh1"), spec)
    # dense model refuses the moe surface
    assert "ep must be 1" in _check(c(dp=4, ep=2), spec)


def test_constraint_prune_reasons_moe_and_llama():
    mspec = _spec(G.gpt_moe_tiny(dtype=jnp.float32,
                                 param_dtype=jnp.float32))
    c = PlanCandidate
    assert "expert count" in _check(c(dp=4, ep=2), _spec(
        G.gpt_moe_tiny(moe_num_experts=9, dtype=jnp.float32,
                       param_dtype=jnp.float32)))
    assert "1F1B" in _check(c(dp=2, ep=2, pp=2, schedule="zbh1"), mspec)
    assert "pp=1" in _check(c(dp=2, ep=2, pp=2, micro_batches=2,
                              moe_quantize=True), mspec)
    assert _check(c(dp=4, ep=2, moe_quantize=True, moe_overlap=True),
                  mspec) is None
    lspec = _spec(LL.llama_tiny(dtype=jnp.float32,
                                param_dtype=jnp.float32), "llama")
    assert "llama" in _check(c(dp=2, pp=2, mp=2, micro_batches=2,
                               schedule="zbh1"), lspec)
    assert "comm_overlap" in _check(c(dp=8, comm_bucket_mb=4.0), lspec)
    assert "MoE" in _check(c(dp=4, ep=2), lspec)
    assert _check(c(dp=2, pp=2, mp=2, micro_batches=2), lspec) is None


# ---------------------------------------------------------------------------
# engine_kwargs round-trips: emitted configs build AND step unmodified.
# ---------------------------------------------------------------------------
def _round_trip(cfg, cand, family="gpt", gb=GB, seq=SEQ):
    spec = _spec(cfg, family)
    assert _check(cand, spec, gb=gb, seq=seq) is None
    M = G if family == "gpt" else LL
    mesh = cand.build_mesh()
    step, shard, init = M.build_hybrid_train_step(
        cfg, mesh, paddle.optimizer.AdamW(1e-3),
        **cand.engine_kwargs(family=family, global_batch=gb, seq=seq))
    p = shard(M.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    st = init(p)
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, cfg.vocab_size, (gb, seq)))
    p, st, loss = step(p, st, tok, tok, jnp.float32(1e-3))
    assert np.isfinite(float(loss))
    return float(loss)


def test_round_trip_hybrid_zero1_bucketed():
    _round_trip(_tiny_gpt(), PlanCandidate(dp=2, mp=2, pp=2,
                                           micro_batches=2, zero_stage=1,
                                           comm_bucket_mb=4.0))


def test_round_trip_zbh1_seq_parallel():
    _round_trip(_tiny_gpt(), PlanCandidate(dp=2, mp=2, pp=2,
                                           micro_batches=2,
                                           schedule="zbh1",
                                           mp_overlap="seq_parallel"))


def test_round_trip_interleaved_vpp():
    _round_trip(_tiny_gpt(), PlanCandidate(dp=4, pp=2, vpp=2,
                                           schedule="interleaved",
                                           micro_batches=4))


def test_round_trip_moe_overlapped():
    cfg = G.gpt_moe_tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    _round_trip(cfg, PlanCandidate(dp=2, ep=2, mp=2, micro_batches=1,
                                   moe_index=True, moe_overlap=True))


def test_round_trip_llama():
    cfg = LL.llama_tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    _round_trip(cfg, PlanCandidate(dp=2, mp=2, pp=2, micro_batches=2),
                family="llama")


def test_gpt1p3b_topk_all_valid():
    """The acceptance surface: every emitted top-k config for gpt1p3b on
    the 8-dev virtual mesh passes the engine's own constraint checks and
    constructs its kwargs (the slow tier AOT-compiles the top-1)."""
    cfg = G.gpt_1p3b()
    rep = plan(cfg, world=8, global_batch=8, seq=2048, family="gpt",
               profile=KNOWN_PROFILES["tpu-v5e"])
    assert len(rep.ranked) >= 5
    for s in rep.top(5):
        assert check_candidate(s.candidate, rep.spec, world=8,
                               global_batch=8, seq=2048) is None
        kw = s.candidate.engine_kwargs(family="gpt", global_batch=8,
                                       seq=2048)
        assert kw["telemetry"] is None and "schedule" in kw
        assert s.prediction.hbm_bytes <= rep.profile.hbm_gb * 1e9


# ---------------------------------------------------------------------------
# The shared MoE flop math, held bit-for-bit to formulas frozen here.
# ---------------------------------------------------------------------------
def test_moe_flops_matches_bench_math_bit_for_bit():
    from paddle_tpu.incubate.distributed.models.moe.gate import \
        compute_capacity
    from paddle_tpu.observability import gpt_moe_flops_per_token
    cfg = G.GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                      num_heads=4, max_seq_len=128,
                      moe_num_experts=8, moe_capacity_factor=2.0,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    E, H, FF, L2 = 8, 64, cfg.ffn_hidden, cfg.num_layers // 2
    for T, mp in ((4 * 64, 2), (512, 1), (96, 4)):
        m = gpt_moe_flops_per_token(cfg, tokens_per_rank=T, mp=mp)
        C = compute_capacity(T, E, 1, cfg.moe_capacity_factor)
        assert m["capacity"] == C
        # the formulas written out, frozen
        assert m["expert_gemm_flops_per_rank_step"] == \
            12.0 * E * C * H * (FF // mp) * L2
        assert m["dense_dispatch_flops_per_moe_layer"] == \
            2.0 * 2 * T * E * C * H
    with pytest.raises(ValueError):
        gpt_moe_flops_per_token(_tiny_gpt(), tokens_per_rank=64)


# ---------------------------------------------------------------------------
# Cost-model rankings vs the RECORDED ground truth.
# ---------------------------------------------------------------------------
def test_tpu_ranking_mp_overlap_beats_baseline_and_bucketed_beats_mono():
    """On the TPU profile the model must reproduce the recorded
    directions: seq-parallel and ring collective-matmul beat plain
    allreduce TP (PR 5 — the mp wire is the exposed-comm term behind the
    43.3% multichip MFU), and bucketed dp sync beats the monolithic
    pmean (PR 2 — 13450 -> 14318 tok/s/chip)."""
    cfg = G.gpt_1p3b()
    spec = ModelSpec.from_config(cfg, "gpt")
    cm = CostModel(spec, KNOWN_PROFILES["tpu-v5e"], global_batch=16,
                   seq=2048)
    ar = cm.predict(PlanCandidate(dp=2, mp=4)).step_s
    sp = cm.predict(PlanCandidate(dp=2, mp=4,
                                  mp_overlap="seq_parallel")).step_s
    ring = cm.predict(PlanCandidate(
        dp=2, mp=4, mp_overlap="collective_matmul")).step_s
    assert ring < sp < ar
    mono = cm.predict(PlanCandidate(dp=8)).step_s
    bkt = cm.predict(PlanCandidate(dp=8, comm_bucket_mb=4.0)).step_s
    assert bkt < mono


def test_cpu_ranking_matches_round6_proxy_op_count_ordering():
    """The CPU profile (overlap_capable=False, per-collective launch
    dominant) must reproduce the CPU-mesh ordering allreduce <
    seq_parallel < ring: on the virtual-device mesh the modes rank by
    op count, not wire."""
    cfg = _tiny_gpt()
    spec = ModelSpec.from_config(cfg, "gpt")
    cm = CostModel(spec, KNOWN_PROFILES["cpu"], global_batch=GB, seq=SEQ)
    ar = cm.predict(PlanCandidate(dp=2, mp=4)).step_s
    sp = cm.predict(PlanCandidate(dp=2, mp=4,
                                  mp_overlap="seq_parallel")).step_s
    ring = cm.predict(PlanCandidate(
        dp=2, mp=4, mp_overlap="collective_matmul")).step_s
    assert ar < sp < ring


def test_bubble_and_schedule_structure():
    spec = _spec()
    cm = CostModel(spec, KNOWN_PROFILES["tpu-v5e"], global_batch=GB,
                   seq=SEQ)
    p1 = cm.predict(PlanCandidate(dp=4, pp=2, micro_batches=2))
    p2 = cm.predict(PlanCandidate(dp=4, pp=2, micro_batches=4))
    assert p1.bubble_frac == pytest.approx(1 / 3)
    assert p2.bubble_frac == pytest.approx(1 / 5)
    assert p2.compute_s < p1.compute_s
    v = cm.predict(PlanCandidate(dp=4, pp=2, vpp=2,
                                 schedule="interleaved", micro_batches=4))
    assert v.bubble_frac == pytest.approx(1 / 9)
    # the factor-V bubble cut shows in compute; the model also charges
    # VPP its real cost — more boundary ppermute wire ((V*M+P-1) vs
    # (M+P-1) ticks), so step_s may rank either way at toy shapes
    assert v.compute_s < p2.compute_s
    assert v.wire["pp"] > p2.wire["pp"]


def test_hbm_model_monotonic_in_zero1_mp_and_sp():
    spec = _spec()
    cm = CostModel(spec, KNOWN_PROFILES["cpu"], global_batch=GB, seq=SEQ)
    base, parts = cm.hbm_bytes(PlanCandidate(dp=8))
    z1, z1_parts = cm.hbm_bytes(PlanCandidate(dp=8, zero_stage=1))
    assert z1_parts["opt"] < parts["opt"] and z1 < base
    mp1, _ = cm.hbm_bytes(PlanCandidate(dp=4, mp=2))
    assert mp1 < base
    b, bp = cm.hbm_bytes(PlanCandidate(dp=2, mp=4, micro_batches=1))
    s, sp_ = cm.hbm_bytes(PlanCandidate(dp=2, mp=4, micro_batches=1,
                                        mp_overlap="seq_parallel"))
    assert sp_["act"] < bp["act"]  # the seq-sharded residual stream


def test_hbm_budget_prunes_with_reason():
    rep = plan(_tiny_gpt(), world=8, global_batch=GB, seq=SEQ,
               family="gpt", profile=KNOWN_PROFILES["cpu"],
               hbm_gb=1e-4)
    assert not rep.ranked
    assert any("analytic HBM" in r for _, r in rep.pruned)


def test_oom_prune_agrees_with_compiled_memory_analysis():
    """The acceptance case: the planner's analytic OOM decision matches
    compiled ``memory_analysis`` on the virtual 8-dev mesh for one admit
    and one reject budget (each chosen with 2x margin on BOTH models, so
    agreement is a property of the models, not the budget)."""
    from paddle_tpu.distributed.hbm_audit import audit_plan_compile
    cfg = _tiny_gpt()
    cand = PlanCandidate(dp=2, mp=2, pp=2, micro_batches=2)
    spec = ModelSpec.from_config(cfg, "gpt")
    cm = CostModel(spec, KNOWN_PROFILES["cpu"], global_batch=GB, seq=SEQ)
    analytic, _ = cm.hbm_bytes(cand)
    audit = audit_plan_compile(cand, cfg, family="gpt", global_batch=GB,
                               seq=SEQ)
    # the step donates its state, so it is resident once: arguments,
    # temp, and the few outputs that alias no argument
    compiled = audit["resident_bytes"]
    assert audit["alias_bytes"] > 0.9 * audit["per_device_param_bytes"]
    assert compiled > 0
    # the two models agree within an order of magnitude at this shape
    assert 0.1 < analytic / compiled < 10.0
    for budget_b, admit in ((2.0 * max(analytic, compiled), True),
                            (0.5 * min(analytic, compiled), False)):
        planner_admits = analytic <= budget_b
        compiled_admits = compiled <= budget_b
        assert planner_admits == compiled_admits == admit
        rep = plan(cfg, world=8, global_batch=GB, seq=SEQ, family="gpt",
                   profile=KNOWN_PROFILES["cpu"], hbm_gb=budget_b / 1e9)
        in_ranked = any(s.candidate == cand for s in rep.ranked)
        assert in_ranked == admit, (budget_b, admit)


# ---------------------------------------------------------------------------
# CLI.
# ---------------------------------------------------------------------------
def test_cli_plan_table():
    from paddle_tpu.distributed.auto_tuner.__main__ import main
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(["plan", "--model", "gpt_tiny", "--mesh", "2x4",
                   "--global-batch", "16", "--seq", "128", "--top", "3"])
    out = buf.getvalue()
    assert rc == 0
    assert "step_ms" in out and "MFU%" in out and "bubble" in out
    assert "pruned" in out and "engine kwargs" in out


def test_cli_plan_json():
    from paddle_tpu.distributed.auto_tuner.__main__ import main
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(["plan", "--model", "gpt_moe_tiny", "--mesh", "8",
                   "--global-batch", "16", "--seq", "128", "--top", "4",
                   "--json"])
    assert rc == 0
    d = json.loads(buf.getvalue())
    assert d["n_valid"] > 0 and d["n_pruned"] > 0
    for row in d["ranked"]:
        assert {"candidate", "step_ms", "mfu_pct", "comm_frac",
                "bubble_frac", "hbm_gb"} <= set(row)
    assert all({"candidate", "reason"} <= set(r) for r in d["pruned"])


def test_unknown_mp_overlap_is_pruned_not_crashed():
    spec = _spec()
    c = PlanCandidate(dp=2, mp=4, mp_overlap="ring")  # typo'd mode
    reason = _check(c, spec)
    assert reason is not None and "mp_overlap" in reason
    assert "ring" in str(c)  # __str__ stays total on unchecked candidates


def test_launcher_no_model_info_keeps_unprunable_configs():
    """With no model information the trial loop must sweep the RAW mesh
    factorizations — a fabricated proxy model would silently drop e.g.
    mp=8 for a user whose real model has 8+ heads."""
    from paddle_tpu.distributed.launch.auto_tune import _candidates_for
    cands = _candidates_for({"max_trials": 3}, 8)
    assert any(c.mp == 8 for c in cands)
    assert any(c.pp == 8 for c in cands)
    # with model dims present, real constraints apply again
    cands = _candidates_for({"num_layers": 4, "num_heads": 4,
                             "hidden_size": 32, "vocab_size": 64,
                             "global_batch": 8, "seq_len": 16,
                             "analytic_rank": False}, 8)
    assert cands and all(4 % c.mp == 0 for c in cands)


def test_launcher_candidate_path_initializes_no_jax_backend():
    """The launch parent must never acquire a backend before trial
    subprocesses spawn — on a TPU host jax.devices() would lock libtpu
    and every trial would fail to initialize the chip. Fresh process:
    all three _candidates_for branches, then assert zero live backends."""
    import subprocess
    import sys
    code = (
        "from paddle_tpu.distributed.launch.auto_tune import "
        "_candidates_for\n"
        "from jax._src import xla_bridge\n"
        "_candidates_for({'max_trials': 3}, 8)\n"
        "_candidates_for({'model': 'gpt_tiny', 'global_batch': 16,"
        " 'seq_len': 128, 'top_k': 4}, 8)\n"
        "_candidates_for({'num_heads': 4, 'num_layers': 4,"
        " 'global_batch': 8, 'seq_len': 16, 'analytic_rank': False}, 8)\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=240,
                   cwd="/root/repo")


# ---------------------------------------------------------------------------
# Trial driver + warm reshard hop.
# ---------------------------------------------------------------------------
def test_autotuner_trial_driver_picks_best_and_records_failures():
    def trial(c):
        if c.mp == 4:
            raise RuntimeError("oom")
        return 100.0 * c.dp + c.micro_batches

    spec = _spec()
    cands, _ = AT.generate_plan_candidates(
        spec, 4, global_batch=8, seq=SEQ, micro_batch_options=(1, 2),
        zero_stage_options=(0,), comm_bucket_options=(0.0,),
        mp_overlap_options=(None,), vpp_options=(1,),
        schedules=("1f1b",))
    tuner = AutoTuner(trial)
    best = tuner.tune(cands)
    assert best.dp == 4 and best.micro_batches == 2
    failed = [h for h in tuner.history if h["error"]]
    assert failed and all(h["candidate"].mp == 4 for h in failed)
    assert "FAILED" in tuner.summary()
    assert tuner.best["candidate"] == best


def test_warm_hop_reshard_preserves_params_across_mesh_change():
    """The PR-7 residue wired into the sweep: params saved on one
    candidate's mesh reshard-load bitwise onto a DIFFERENT mesh shape."""
    from paddle_tpu.distributed.auto_tuner.sweep import (
        reshard_params_hop, save_params_for_hop)
    import tempfile
    cfg = _tiny_gpt()
    a = PlanCandidate(dp=8)
    b = PlanCandidate(dp=2, mp=2, pp=2, micro_batches=2)
    host = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    _, shard_a, init_a = G.build_hybrid_train_step(
        cfg, a.build_mesh(), paddle.optimizer.AdamW(1e-3),
        **a.engine_kwargs(family="gpt"))
    pa = shard_a(host)
    with tempfile.TemporaryDirectory() as d:
        saved = save_params_for_hop(pa, init_a.layout_extra, d + "/hop")
        _, shard_b, init_b = G.build_hybrid_train_step(
            cfg, b.build_mesh(), paddle.optimizer.AdamW(1e-3),
            **b.engine_kwargs(family="gpt"))
        pb = shard_b(host)
        loaded = reshard_params_hop(saved, pb, init_b.layout_extra)
    flat_h = jax.tree.leaves(host)
    flat_l = jax.tree.leaves(jax.device_get(loaded))
    for h, l in zip(flat_h, flat_l):
        np.testing.assert_array_equal(np.asarray(h), np.asarray(l))


# ---------------------------------------------------------------------------
# Slow tier: the measured validation.
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_sweep_predicted_vs_measured_cpu_smoke():
    """The bench-validation acceptance gate on the CPU smoke mesh:
    measure 7 configs spanning mp_overlap / comm_overlap / schedule /
    micro_batches / a deliberately-bad pipeline, calibrate the cost model
    on 3 anchors (rate, per-collective launch, per-step overhead), then

    * the predicted ranking is ORDER-CORRECT: every pair where both the
      predicted and the measured times differ by > 20% must be ordered
      the same way (near-ties on either side make no adjudicable claim);
    * predicted step-time ratios (vs the first anchor) are within the
      DOCUMENTED tolerance of measured: 40% relative for the normal
      configs (the CPU backend's efficiency varies with GEMM size in
      ways the TPU-shaped model does not chase — README "Auto-parallel
      planner"); the deliberately-bad bubble config is instead required
      to be BOTH predicted and measured strictly worst — the decision
      the planner exists to make.
    """
    from paddle_tpu.distributed.auto_tuner.sweep import (ranking_agreement,
                                                         run_sweep)
    cfg = G.GPTConfig(vocab_size=512, hidden_size=64, num_layers=8,
                      num_heads=4, max_seq_len=128, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    spec = ModelSpec.from_config(cfg, "gpt")
    cm = CostModel(spec, KNOWN_PROFILES["cpu"], global_batch=16, seq=128)
    P = PlanCandidate
    cands = [
        P(dp=8, micro_batches=1),
        P(dp=2, mp=2, pp=2, micro_batches=2),
        P(dp=2, mp=2, pp=2, micro_batches=2,
          mp_overlap="seq_parallel"),
        P(dp=2, mp=2, pp=2, micro_batches=4),
        P(dp=2, pp=4, micro_batches=1),       # deliberately bad
        P(dp=2, mp=2, pp=2, micro_batches=2, schedule="zbh1"),
        P(dp=2, mp=2, pp=2, micro_batches=2, comm_bucket_mb=4.0),
    ]
    for c in cands:
        assert _check(c, spec) is None, str(c)
    rows, cal = run_sweep(cfg, cands, cost_model=cm, family="gpt",
                          global_batch=16, seq=128, iters=5, repeats=4,
                          anchors=cands[:3])
    agr = ranking_agreement(rows, noise_rel=0.25)
    assert agr["ok"], agr
    assert agr["checked_pairs"] >= 4
    bad = cands[4]
    base = rows[0]
    for r in rows:
        if r["candidate"] == bad:
            continue
        ratio_err = abs((r["predicted_s"] / base["predicted_s"])
                        / (r["measured_s"] / base["measured_s"]) - 1.0)
        assert ratio_err <= 0.4, (str(r["candidate"]), ratio_err)
    # the deliberately-bad config: the planner's prediction AND the
    # measurement both put it strictly last
    worst_pred = max(rows, key=lambda r: r["predicted_s"])["candidate"]
    worst_meas = max(rows, key=lambda r: r["measured_s"])["candidate"]
    assert worst_pred == bad and worst_meas == bad


@pytest.mark.slow
def test_gpt1p3b_top1_aot_compiles_on_virtual_mesh():
    """The flagship acceptance leg: the planner's top-1 for gpt1p3b on
    the 8-dev virtual mesh AOT-compiles through the full hybrid step
    (memory_analysis returns real bytes) without materializing 1.3B
    params — the hbm_audit pattern."""
    from paddle_tpu.distributed.hbm_audit import audit_plan_compile
    cfg = G.gpt_1p3b()
    rep = plan(cfg, world=8, global_batch=8, seq=2048, family="gpt",
               profile=KNOWN_PROFILES["tpu-v5e"])
    top1 = rep.top(1)[0]
    audit = audit_plan_compile(top1.candidate, cfg, family="gpt",
                               global_batch=8, seq=2048)
    assert audit["per_device_param_bytes"] > 0
    assert audit.get("temp_bytes", 0) > 0
    # the analytic model and the compiled plan agree on the admit side
    assert top1.prediction.hbm_bytes <= rep.profile.hbm_gb * 1e9
