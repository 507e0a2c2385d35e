"""Numerics observability (ISSUE 15): in-program tensor-health telemetry,
host-side anomaly detection and spike-triggered forensics.

Anchor contracts:

* **flags-off bitwise** — with FLAGS_numerics off, both model builders'
  compiled hybrid steps are BYTE-IDENTICAL to builds with numerics=None
  (lowered-HLO text asserted, gpt AND llama);
* **spike acceptance** — an injected loss spike (faults grammar site
  ``numerics/spike``) in a resilient run yields EXACTLY one
  ``numerics_anomaly`` JSONL event plus one bounded flight-recorder
  bundle whose ``numerics.json`` carries the per-layer stats;
* **EF honesty** — the ``num_ef_*`` series equal norms recomputed on the
  host from the fetched ``opt_state`` residual carries, on all three
  wires (dp comm_ef / MoE moe_ef / zero3_ef).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import observability as obs
from paddle_tpu.distributed.comm_overlap import (CommOverlapConfig,
                                                 MoeDispatchConfig)
from paddle_tpu.distributed.comm_overlap.zero3 import Zero3Config
from paddle_tpu.models import gpt as G
from paddle_tpu.models import llama as Lm
from paddle_tpu.observability.numerics import (DetectorConfig,
                                               NumericsConfig,
                                               NumericsGuard,
                                               NumericsMonitor,
                                               numerics_spike_check)

CFG = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4, num_heads=2,
                  max_seq_len=32, dtype=jnp.float32, param_dtype=jnp.float32)
LCFG = Lm.LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=2, max_seq_len=32, dtype=jnp.float32,
                      param_dtype=jnp.float32)
LR = jnp.float32(1e-3)


def _data(batch=8, seq=16, seed=0, vocab=64):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randint(0, vocab, (batch, seq))),
            jnp.asarray(rng.randint(0, vocab, (batch, seq))))


def _host_norm(tree):
    return float(np.sqrt(sum(
        float(np.sum(np.square(np.asarray(l, np.float64))))
        for l in jax.tree.leaves(jax.device_get(tree)))))


# ---------------------------------------------------------------------------
# flags-off bitwise no-op (both builders)
# ---------------------------------------------------------------------------
def test_numerics_off_is_bitwise_noop_gpt():
    mesh = dist.build_mesh({"dp": 2, "pp": 2, "mp": 2})
    tokens, labels = _data()
    step0, sh, ini = G.build_hybrid_train_step(
        CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=2,
        telemetry=None, numerics=None)
    p = sh(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s = ini(p)
    base = step0.lower(p, s, tokens, labels, LR).as_text()

    paddle.set_flags({"FLAGS_numerics": False})
    step1, _, ini1 = G.build_hybrid_train_step(
        CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=2,
        telemetry=None, numerics="auto")
    assert step1.lower(p, s, tokens, labels, LR).as_text() == base
    assert ini1.telemetry_config is None

    # and ON genuinely changes the program (a vacuous guard would pass)
    tcfg = obs.TelemetryConfig(interval=4, strict=False)
    step2, sh2, ini2 = G.build_hybrid_train_step(
        CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=2,
        telemetry=tcfg, numerics=True)
    p2 = sh2(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s2 = ini2(p2)
    assert step2.lower(p2, s2, tokens, labels, LR).as_text() != base
    assert any(n.startswith("num_gnorm_l") for n in tcfg.extra)


def test_numerics_off_is_bitwise_noop_llama():
    mesh = dist.build_mesh({"dp": 2, "pp": 2, "mp": 2})
    tokens, labels = _data()
    step0, sh, ini = Lm.build_hybrid_train_step(
        LCFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=2,
        telemetry=None, numerics=None)
    p = sh(Lm.init_hybrid_params(LCFG, jax.random.PRNGKey(0)))
    s = ini(p)
    base = step0.lower(p, s, tokens, labels, LR).as_text()
    paddle.set_flags({"FLAGS_numerics": False})
    step1, _, _ = Lm.build_hybrid_train_step(
        LCFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=2,
        telemetry=None, numerics="auto")
    assert step1.lower(p, s, tokens, labels, LR).as_text() == base


def test_numerics_flag_implies_telemetry_config():
    """FLAGS_numerics alone (telemetry flag off) must auto-create the
    carry and publish the resolved config on init_state."""
    mesh = dist.build_mesh({"dp": 2, "pp": 2, "mp": 2})
    paddle.set_flags({"FLAGS_numerics": True, "FLAGS_telemetry": False})
    try:
        step, sh, ini = G.build_hybrid_train_step(
            CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=2)
        tcfg = ini.telemetry_config
        assert tcfg is not None and not tcfg.strict
        assert tcfg.static["numerics"]["num_layers"] == CFG.num_layers
        assert f"num_gnorm_l{CFG.num_layers - 1}" in tcfg.extra
        p = sh(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
        s = ini(p)
        assert "telemetry" in s
    finally:
        paddle.set_flags({"FLAGS_numerics": False})


# ---------------------------------------------------------------------------
# per-layer series: decode, consistency, independent recompute
# ---------------------------------------------------------------------------
def test_per_layer_series_decode_and_bound():
    mesh = dist.build_mesh({"dp": 2, "pp": 2, "mp": 2})
    tokens, labels = _data()
    tcfg = obs.TelemetryConfig(interval=2, strict=False)
    step, sh, ini = G.build_hybrid_train_step(
        CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=2,
        telemetry=tcfg, numerics=True)
    p = sh(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s = ini(p)
    host = obs.TelemetryHost(tcfg)
    for i in range(4):
        p, s, loss = step(p, s, tokens, labels, LR)
        host.poll(s, i)
    for i in range(CFG.num_layers):
        assert all(v > 0 for v in host.series[f"num_gnorm_l{i}"])
        assert all(v > 0 for v in host.series[f"num_act_rms_l{i}"])
        assert all(v > 0 for v in host.series[f"num_act_absmax_l{i}"])
        # absmax dominates rms by construction
        assert (host.series[f"num_act_absmax_l{i}"][-1]
                >= host.series[f"num_act_rms_l{i}"][-1])
    # the layer norms decompose the BLOCKS' share of the global norm:
    # sum of squares can never exceed the global grad norm squared
    lsq = sum(host.series[f"num_gnorm_l{i}"][-1] ** 2
              for i in range(CFG.num_layers))
    assert lsq <= host.series["grad_norm"][-1] ** 2 * (1 + 1e-4)


def test_per_layer_gnorm_matches_independent_grads():
    """The num_gnorm_l<i> series equal per-layer norms recomputed from an
    INDEPENDENT jax.grad of the same loss (global numpy arithmetic on
    the fetched dp-averaged grads — none of the engine's
    replication/psum accounting)."""
    from paddle_tpu.utils import shard_map as _sm
    mesh = dist.build_mesh({"dp": 2, "pp": 2, "mp": 2})
    tokens, labels = _data()
    tcfg = obs.TelemetryConfig(interval=1, strict=False)
    step, sh, ini = G.build_hybrid_train_step(
        CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=2,
        telemetry=tcfg, numerics=True,
        donate=False)   # p0 feeds the independent grad after the step
    p0 = sh(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s0 = ini(p0)
    host = obs.TelemetryHost(tcfg)
    _, s1, _ = step(p0, s0, tokens, labels, LR)
    host.poll(s1, 0)

    specs = G.hybrid_param_specs(CFG)

    def ref(p, t, l):
        g = jax.grad(lambda q: G.hybrid_loss_fn(q, t, l, CFG, 2))(p)
        return jax.tree.map(lambda x: lax.pmean(x, "dp"), g)

    grads = jax.jit(_sm(ref, mesh=mesh,
                        in_specs=(specs, P("dp"), P("dp")),
                        out_specs=specs))(p0, tokens, labels)
    blocks = jax.device_get(grads["blocks"])
    per = np.zeros((CFG.num_layers,), np.float64)
    for leaf in jax.tree.leaves(blocks):
        a = np.asarray(leaf, np.float64)
        per += np.sum(np.square(a), axis=tuple(range(1, a.ndim)))
    ref_norms = np.sqrt(per)
    got = np.array([host.series[f"num_gnorm_l{i}"][0]
                    for i in range(CFG.num_layers)])
    np.testing.assert_allclose(got, ref_norms, rtol=2e-4)


def test_per_layer_gnorm_covers_zbh1_without_act_series():
    """ZBH1 has no aux channel: the builder must drop the act series but
    keep the engine-side per-layer grad norms."""
    mesh = dist.build_mesh({"dp": 2, "pp": 2, "mp": 2})
    tokens, labels = _data()
    tcfg = obs.TelemetryConfig(interval=2, strict=False)
    step, sh, ini = G.build_hybrid_train_step(
        CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=2,
        schedule="ZBH1", telemetry=tcfg, numerics=True)
    assert not any(n.startswith("num_act_") for n in tcfg.extra)
    p = sh(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s = ini(p)
    host = obs.TelemetryHost(tcfg)
    for i in range(2):
        p, s, _ = step(p, s, tokens, labels, LR)
        host.poll(s, i)
    assert all(host.series[f"num_gnorm_l{i}"][-1] > 0
               for i in range(CFG.num_layers))


# ---------------------------------------------------------------------------
# EF residual series vs independently recomputed norms (all three wires)
# ---------------------------------------------------------------------------
def test_ef_comm_series_matches_host_recompute():
    mesh = dist.build_mesh({"dp": 4, "pp": 1, "mp": 2})
    tokens, labels = _data()
    tcfg = obs.TelemetryConfig(interval=1, strict=False)
    step, sh, ini = G.build_hybrid_train_step(
        CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=1,
        telemetry=tcfg, numerics=True,
        comm_overlap=CommOverlapConfig(bucket_mb=1e-4, quantize="int8"))
    p = sh(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s = ini(p)
    host = obs.TelemetryHost(tcfg)
    for i in range(3):
        p, s, _ = step(p, s, tokens, labels, LR)
        host.poll(s, i)
    ref = _host_norm(s["comm_ef"])
    got = host.series["num_ef_comm"][-1]
    assert ref > 0
    np.testing.assert_allclose(got, ref, rtol=1e-4)


def test_ef_zero3_series_matches_host_recompute():
    mesh = dist.build_mesh({"dp": 4, "pp": 1, "mp": 2})
    tokens, labels = _data()
    tcfg = obs.TelemetryConfig(interval=1, strict=False)
    step, sh, ini = G.build_hybrid_train_step(
        CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=1,
        telemetry=tcfg, numerics=True, zero_stage=3,
        zero3=Zero3Config(quantize=True))
    p = sh(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s = ini(p)
    host = obs.TelemetryHost(tcfg)
    for i in range(3):
        p, s, _ = step(p, s, tokens, labels, LR)
        host.poll(s, i)
    ref = _host_norm(s["zero3_ef"])
    got = host.series["num_ef_zero3"][-1]
    assert ref > 0
    np.testing.assert_allclose(got, ref, rtol=1e-4)


def test_ef_moe_series_matches_host_recompute():
    mcfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                       num_heads=4, max_seq_len=16, dtype=jnp.float32,
                       moe_num_experts=4, moe_capacity_factor=8.0,
                       moe_aux_weight=1e-2)
    mesh = dist.build_mesh({"dp": 2, "ep": 2, "pp": 1, "mp": 2})
    tokens, labels = _data(batch=8, seq=16)
    tcfg = obs.TelemetryConfig(interval=1, strict=False)
    step, sh, ini = G.build_hybrid_train_step(
        mcfg, mesh, paddle.optimizer.AdamW(1e-2), num_microbatches=1,
        telemetry=tcfg, numerics=True,
        moe_dispatch=MoeDispatchConfig(index=True, quantize=True),
        moe_ef_tokens=(2, 16))
    p = sh(G.init_hybrid_params(mcfg, jax.random.PRNGKey(0)))
    s = ini(p)
    host = obs.TelemetryHost(tcfg)
    for i in range(3):
        p, s, _ = step(p, s, tokens, labels, jnp.float32(1e-2))
        host.poll(s, i)
    ref = _host_norm(s["moe_ef"])
    got = host.series["num_ef_moe"][-1]
    assert ref > 0
    np.testing.assert_allclose(got, ref, rtol=1e-4)
    # per-layer grad norms cover the MoE pair stacking ([L/2] indices)
    L2 = mcfg.num_layers // 2
    assert all(host.series[f"num_gnorm_l{i}"][-1] > 0 for i in range(L2))
    assert f"num_gnorm_l{L2}" not in host.series


# ---------------------------------------------------------------------------
# fp8 site health
# ---------------------------------------------------------------------------
def test_fp8_site_health_unit():
    """Pure-function contract: sat = amax/(scale*fmax), headroom is the
    clamped log2 margin; role 'g' uses the e5m2 max."""
    from paddle_tpu.observability.numerics import (HEADROOM_CLAMP,
                                                   fp8_site_health)
    from paddle_tpu.quantization.fp8 import E4M3_MAX, E5M2_MAX
    amax = {"s": {"x": jnp.float32(448.0), "w": jnp.float32(2.0),
                  "g": jnp.float32(0.0)}}
    scales = {"s": {"x": jnp.float32(2.0 / E4M3_MAX),
                    "w": jnp.float32(2.0 / E4M3_MAX),
                    "g": jnp.float32(1.0 / E5M2_MAX)}}
    out = fp8_site_health(amax, scales)
    # x role saturates 224x over its 2.0 cap; the site max reports it
    np.testing.assert_allclose(float(out["num_fp8_sat_s"]), 224.0,
                               rtol=1e-5)
    # headroom is the min over roles: the saturating x role, log2(1/224)
    np.testing.assert_allclose(float(out["num_fp8_headroom_s"]),
                               -np.log2(224.0), rtol=1e-5)
    # an unexercised site (amax 0 everywhere) clamps instead of inf
    out0 = fp8_site_health({"s": {"x": jnp.float32(0.0)}},
                           {"s": {"x": jnp.float32(1.0)}})
    assert float(out0["num_fp8_headroom_s"]) == HEADROOM_CLAMP
    assert float(out0["num_fp8_sat_s"]) == 0.0


def test_fp8_site_series_present_in_hybrid():
    mesh = dist.build_mesh({"dp": 4, "pp": 1, "mp": 2})
    tokens, labels = _data()
    tcfg = obs.TelemetryConfig(interval=2, strict=False)
    step, sh, ini = G.build_hybrid_train_step(
        CFG, mesh, paddle.optimizer.AdamW(1e-3), num_microbatches=1,
        telemetry=tcfg, numerics=True, fp8=True)
    p = sh(G.init_hybrid_params(CFG, jax.random.PRNGKey(0)))
    s = ini(p)
    host = obs.TelemetryHost(tcfg)
    for i in range(4):
        p, s, _ = step(p, s, tokens, labels, LR)
        host.poll(s, i)
    for site in G.GPT_FP8_SITES:
        assert host.series[f"num_fp8_sat_{site}"][-1] > 0
        assert np.isfinite(host.series[f"num_fp8_headroom_{site}"]).all()


# ---------------------------------------------------------------------------
# engine-level numerics (no model): EF series on a toy job
# ---------------------------------------------------------------------------
def test_engine_level_numerics_without_blocks():
    from paddle_tpu.models.hybrid_engine import build_train_step
    mesh = dist.build_mesh({"dp": 8})
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(64, 32).astype(np.float32)),
              "b": jnp.zeros((32,), jnp.float32)}
    specs = {"w": P(), "b": P()}
    xs = jnp.asarray(rng.randn(16, 64).astype(np.float32))
    ys = jnp.asarray(rng.randn(16, 32).astype(np.float32))

    def loss_fn(p, x, y):
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    tcfg = obs.TelemetryConfig(interval=1)
    step, sh, ini = build_train_step(
        loss_fn, specs, mesh, paddle.optimizer.AdamW(1e-3),
        example_params=params, telemetry=tcfg,
        numerics=NumericsConfig(),  # no per-layer series
        comm_overlap=CommOverlapConfig(bucket_mb=1e-4, quantize="int8"))
    assert tuple(tcfg.extra) == ("num_ef_comm",)
    p = sh(params)
    s = ini(p)
    host = obs.TelemetryHost(tcfg)
    for i in range(2):
        p, s, _ = step(p, s, xs, ys, LR)
        host.poll(s, i)
    np.testing.assert_allclose(host.series["num_ef_comm"][-1],
                               _host_norm(s["comm_ef"]), rtol=1e-4)


# ---------------------------------------------------------------------------
# host-side monitor: detectors, episodes, actions
# ---------------------------------------------------------------------------
def _mon(tmp_path, name="mon.jsonl", **kw):
    cfg = DetectorConfig(**{**dict(window=16, min_history=4,
                                   spike_factor=4.0, clear_obs=3), **kw})
    log = obs.EventLog(str(tmp_path / name))
    return NumericsMonitor(cfg, event_log=log), log


def _events(log):
    log.close()
    return [json.loads(l) for l in open(log.path, encoding="utf-8")]


def test_monitor_one_anomaly_per_episode_and_rearm(tmp_path):
    mon, log = _mon(tmp_path)
    for i in range(8):
        mon.note_loss(i, 1.0 + 0.01 * i)
    mon.note_loss(8, 50.0)           # spike -> opens the episode
    mon.note_loss(9, 60.0)           # still anomalous -> same episode
    for i in range(10, 13):
        mon.note_loss(i, 1.0)        # 3 healthy -> episode closes
    mon.note_loss(13, 70.0)          # re-armed -> a SECOND episode
    ev = _events(log)
    kinds = [e["event"] for e in ev]
    assert kinds.count("numerics_anomaly") == 2
    assert kinds.count("numerics_recovered") == 1
    first = next(e for e in ev if e["event"] == "numerics_anomaly")
    assert first["reason"] == "loss_spike" and first["step"] == 8
    assert len(mon.anomalies) == 2


def test_monitor_nonfinite_and_gradnorm_detectors(tmp_path):
    mon, log = _mon(tmp_path)
    mon.ingest_row(0, {"nonfinite_count": 4.0})
    for i in range(1, 9):
        mon.ingest_row(i, {"grad_norm": 1.0})
    mon.ingest_row(9, {"grad_norm": 9.0})
    ev = [e for e in _events(log) if e["event"] == "numerics_anomaly"]
    assert ev[0]["reasons"] == ["nonfinite"]
    assert any(r == "grad_norm_spike" for e in ev for r in e["reasons"])


def test_monitor_ef_growth_detector(tmp_path):
    mon, log = _mon(tmp_path)
    for i in range(6):
        mon.ingest_row(i, {"num_ef_comm": 1e-3})
    # EF blows up 100x over the rolling median
    mon.ingest_row(6, {"num_ef_comm": 0.1})
    reasons = [r for e in _events(log) if e["event"] == "numerics_anomaly"
               for r in e["reasons"]]
    assert any(r.startswith("ef_growth:num_ef_comm") for r in reasons)


def test_monitor_fp8_saturation_rate_detector(tmp_path):
    mon, log = _mon(tmp_path)
    for i in range(6):
        mon.ingest_row(i, {"num_fp8_sat_qkv": 0.5})
    # saturating >half the recent window crosses the rate threshold;
    # later anomalous observations extend the SAME episode silently
    # (one event per episode — merged reasons live in the snapshot)
    for i in range(6, 14):
        mon.ingest_row(i, {"num_fp8_sat_qkv": 1.5})
    reasons = [r for e in _events(log) if e["event"] == "numerics_anomaly"
               for r in e["reasons"]]
    assert any(r.startswith("fp8_saturation:num_fp8_sat_qkv")
               for r in reasons)
    assert sum(1 for e in _events_list(log.path)
               if e["event"] == "numerics_anomaly") == 1


def _events_list(path):
    return [json.loads(l) for l in open(path, encoding="utf-8")]


def test_monitor_duplicate_steps_ignored(tmp_path):
    """Ring rows lag the per-step host loss — the same step seen twice
    must not double-feed the detectors' history."""
    mon, _ = _mon(tmp_path)
    for i in range(6):
        mon.note_loss(i, 1.0)
    mon.ingest_row(5, {"loss": 999.0})  # stale duplicate of step 5
    assert mon._hist["loss"][-1] == 1.0
    assert not mon.anomalies


def test_monitor_action_arming_and_budget(tmp_path):
    mon, _ = _mon(tmp_path, action="rollback", confirm=2, max_rollbacks=1)
    for i in range(6):
        mon.note_loss(i, 1.0)
    mon.note_loss(6, 50.0)
    assert mon.consume_action() is None      # 1 hit < confirm
    mon.note_loss(7, 50.0)
    assert mon.consume_action() == "rollback"
    mon.note_loss(8, 50.0)
    assert mon.consume_action() is None      # budget spent
    assert mon.rollbacks == 1
    mon.on_rollback()
    assert mon.snapshot()["episode"] is None


def test_monitor_snapshot_bounded(tmp_path):
    mon, _ = _mon(tmp_path, window=8)
    for i in range(50):
        mon.ingest_row(i, {"loss": 1.0, "num_gnorm_l0": 0.5})
    snap = mon.snapshot()
    assert len(snap["series"]["loss"]) <= 8
    assert len(snap["steps"]) <= 8
    assert "num_gnorm_l0" in snap["series"]


# ---------------------------------------------------------------------------
# driver integration: spike acceptance, skip, rollback
# ---------------------------------------------------------------------------
def test_spike_check_acceptance(tmp_path):
    """The ISSUE acceptance row (shared with the __graft_entry__ dryrun
    leg): injected spike -> exactly one numerics_anomaly event + one
    bundle with per-layer numerics.json."""
    out = numerics_spike_check(str(tmp_path),
                               mesh_shape={"dp": 4, "pp": 1, "mp": 2})
    assert out["layers"] == 2
    assert any(r.startswith("loss_spike") for r in out["reasons"])


def _driver_job(tmp_path, action, *, steps=14, spike_at=10, confirm=1,
                ckpt_every=0, spike_clause=None):
    from paddle_tpu.distributed.resilience import run_resilient
    log = obs.EventLog(str(tmp_path / "drv.jsonl"))
    guard = NumericsGuard(
        obs.TelemetryConfig(interval=4, strict=False),
        NumericsMonitor(DetectorConfig(window=16, min_history=4,
                                       spike_factor=4.0, clear_obs=3,
                                       action=action, confirm=confirm),
                        event_log=log),
        event_log=log)
    calls = []

    def step_fn(st, i):
        calls.append(i)
        return {"x": st["x"] + 1.0}, float(1.0 + 0.001 * i)

    prev = paddle.get_flags(["FLAGS_fault_inject"])
    prev_log = obs.set_event_log(log)  # driver lifecycle events too
    paddle.set_flags({"FLAGS_fault_inject":
                      spike_clause or f"numerics/spike:{spike_at}"})
    try:
        state, info = run_resilient(
            step_fn, {"x": jnp.zeros((2,), jnp.float32)}, steps=steps,
            ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=ckpt_every,
            numerics=guard)
    finally:
        paddle.set_flags(prev)
        obs.set_event_log(prev_log)
        log.close()
    return state, info, calls, [
        json.loads(l) for l in open(log.path, encoding="utf-8")], guard


def test_driver_numerics_skip_rejects_step(tmp_path):
    state, info, calls, ev, guard = _driver_job(tmp_path, "skip")
    assert info["numerics_skips"] == 1
    assert any(e["event"] == "resilience_numerics_skip" for e in ev)
    assert any(e["event"] == "numerics_anomaly" for e in ev)
    # one step's state transition was rejected
    assert float(state["x"][0]) == info["completed_steps"] - 1


def test_driver_numerics_rollback_restarts_from_checkpoint(tmp_path):
    state, info, calls, ev, guard = _driver_job(tmp_path, "rollback",
                                                ckpt_every=4, spike_at=10)
    assert info["numerics_rollbacks"] == 1
    rb = next(e for e in ev if e["event"] == "resilience_numerics_rollback")
    assert rb["to_step"] == 8
    # steps 8.. replayed after the rollback at the spike step
    assert calls.count(8) == 2
    assert info["completed_steps"] == 14
    assert any(e["event"] == "numerics_anomaly" for e in ev)


def test_driver_rollback_without_checkpoint_degrades(tmp_path):
    state, info, calls, ev, guard = _driver_job(tmp_path, "rollback",
                                                ckpt_every=0)
    assert info["numerics_rollbacks"] == 0
    assert any(e["event"] == "resilience_numerics_rollback_unavailable"
               for e in ev)
    assert info["completed_steps"] == 14


def test_maybe_trigger_grammar():
    from paddle_tpu.distributed.resilience import faults
    paddle.set_flags({"FLAGS_fault_inject": "numerics/spike:3"})
    try:
        hits = [faults.maybe_trigger("numerics/spike") for _ in range(5)]
        assert hits == [False, False, True, False, False]
        # disarmed: always False, no counting overhead
        paddle.set_flags({"FLAGS_fault_inject": ""})
        assert faults.maybe_trigger("numerics/spike") is False
    finally:
        paddle.set_flags({"FLAGS_fault_inject": ""})


# ---------------------------------------------------------------------------
# serving: KV-pool page-scale drift (FLAGS_numerics, quantized pools)
# ---------------------------------------------------------------------------
def test_serving_kv_scale_drift_gauges(tmp_path):
    from paddle_tpu.inference.serving import ServingEngine
    scfg = G.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                       num_heads=4, max_seq_len=128, dtype=jnp.float32)
    params = G.init_hybrid_params(scfg, jax.random.PRNGKey(0))
    log = obs.EventLog(str(tmp_path / "serve.jsonl"))
    prev = obs.set_event_log(log)
    paddle.set_flags({"FLAGS_numerics": True,
                      "FLAGS_telemetry_interval": 2})
    try:
        eng = ServingEngine(params, scfg, max_batch=2, block_size=8,
                            num_blocks=24, max_blocks_per_seq=8, chunk=8,
                            adaptive_mix=False, kv_cache_dtype="int8")
        # long enough that generation spans several engine steps (the
        # fused burst emits ~8 tokens/step) so mid-run polls see LIVE
        # pages, then run past completion so the pool drains
        eng.add_request(list(range(1, 9)), max_new_tokens=40)
        for _ in range(10):
            eng.step()
    finally:
        paddle.set_flags({"FLAGS_numerics": False})
        obs.set_event_log(prev)
        log.close()
    ev = [json.loads(l) for l in open(log.path, encoding="utf-8")]
    kv = [e for e in ev if e["event"] == "numerics_kv"]
    assert kv and all(e["role"] == "serving" for e in kv)
    # mid-generation polls saw live written pages with real scales...
    hot = [e for e in kv if e["kv_pages_live"] > 0]
    assert hot and hot[0]["kv_scale_max"] > 0
    assert hot[0]["kv_scale_mean"] > 0
    # ...and liveness comes from the POOL accounting, not stale scales:
    # once the request finished and freed its pages, the poll reports a
    # dead pool even though the scale buffers still hold old values
    assert kv[-1]["kv_pages_live"] == 0
    assert kv[-1]["kv_scale_max"] == 0
    snap = eng.snapshot()["kv_scales"]
    assert {k: kv[-1][k] for k in snap} == snap


# ---------------------------------------------------------------------------
# satellites: rotated-stream merge + prom grad-norm export
# ---------------------------------------------------------------------------
def test_merge_event_streams_reads_rotated_segment(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    log = obs.EventLog(path, max_mb=2e-3)  # ~2 KB cap -> fast rotation
    n = 0
    while log.rotations == 0:  # fill exactly past ONE rotation
        log.emit("tick", i=n, pad="x" * 64)
        n += 1
    for _ in range(3):         # a few live-generation records on top
        log.emit("tick", i=n, pad="x" * 64)
        n += 1
    log.close()
    assert os.path.exists(path + ".1"), "log never rotated"
    merged = obs.merge_event_streams(path)
    ticks = [e["i"] for e in merged if e["event"] == "tick"]
    # the rotated generation's records lead the timeline — the capped
    # log's oldest half is no longer silently dropped from the merge
    assert ticks == sorted(ticks)
    assert ticks[0] == 0 and ticks[-1] == n - 1 and len(ticks) == n
    assert any(e["event"] == "jsonl_rotated" for e in merged)
    # the live file ALONE starts mid-history — the .1 read is what
    # restored the front
    live = [json.loads(l) for l in open(path, encoding="utf-8")]
    assert min(e["i"] for e in live if e["event"] == "tick") > 0


def test_telemetry_host_prom_export():
    mesh = dist.build_mesh({"dp": 8})
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(64, 32).astype(np.float32))}
    specs = {"w": P()}
    xs = jnp.asarray(rng.randn(16, 64).astype(np.float32))
    ys = jnp.asarray(rng.randn(16, 32).astype(np.float32))
    from paddle_tpu.models.hybrid_engine import build_train_step
    tcfg = obs.TelemetryConfig(interval=5)
    step, sh, ini = build_train_step(
        lambda p, x, y: jnp.mean((x @ p["w"] - y) ** 2), specs, mesh,
        paddle.optimizer.AdamW(1e-3), telemetry=tcfg)
    reg = obs.PromRegistry()
    host = obs.TelemetryHost(tcfg, prom=reg)
    p = sh(params)
    s = ini(p)
    for i in range(10):
        p, s, loss = step(p, s, xs, ys, LR)
        host.poll(s, i)
    assert reg.get("train_grad_norm") == pytest.approx(
        host.series["grad_norm"][-1])
    assert reg.get("train_loss") == pytest.approx(
        host.series["loss"][-1])
    # per-step summary window: 10 observations, live quantiles work
    snap = reg.snapshot()
    assert snap["train_grad_norm_step_count"] == 10.0
    assert reg.quantile("train_grad_norm_step", 0.95) > 0


def test_host_watermark_survives_skipped_steps():
    """A numerics skip keeps a carry whose ring count lags the polled
    (discarded) sibling: the host's ingest watermark must neither
    re-decode overlapping rows as duplicates nor wedge flush()."""
    from paddle_tpu.models.hybrid_engine import build_train_step
    mesh = dist.build_mesh({"dp": 8})
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(64, 32).astype(np.float32))}
    specs = {"w": P()}
    xs = jnp.asarray(rng.randn(16, 64).astype(np.float32))
    ys = jnp.asarray(rng.randn(16, 32).astype(np.float32))
    tcfg = obs.TelemetryConfig(interval=2)
    step, sh, ini = build_train_step(
        lambda p, x, y: jnp.mean((x @ p["w"] - y) ** 2), specs, mesh,
        paddle.optimizer.AdamW(1e-3), telemetry=tcfg,
        donate=False)   # a skipped step's input carry feeds the next one
    host = obs.TelemetryHost(tcfg)
    p = sh(params)
    st = ini(p)
    for i in range(6):
        p, st_new, _ = step(p, st, xs, ys, LR)
        host.poll(st_new, i)
        if i != 3:          # i == 3: the guard said "skip" — keep st
            st = st_new
    assert host.steps == sorted(set(host.steps)), host.steps
    assert host.flush(st) is None  # nothing left; must NOT go negative
    assert host.steps == sorted(set(host.steps)), host.steps


def test_aggregator_exports_per_host_grad_norm(tmp_path):
    from paddle_tpu.observability.aggregate import TelemetryAggregator
    agg = TelemetryAggregator(rank=0, world_size=1)
    agg.prom is not None
    payload = {"host": 3, "role": "trainer", "ts": 0.0,
               "window_ms": [10.0, 11.0],
               "prom": {"train_grad_norm": 0.75}}
    agg.aggregate({0: payload})
    assert agg.prom.get("grad_norm_host3") == 0.75
