"""The measurement loop (ISSUE 11): profile capture + attribution, fleet
telemetry with straggler detection, and the hang flight recorder.

Anchor contracts:

* **census ground truth** — the while-trip-aware compiled-HLO census
  counts loop-body collectives trip times (XLA's cost_analysis does
  not), and on a dp-only hybrid step its wire bytes match the planner's
  analytic dp model almost exactly;
* **profile -> planner loop** — a profile captured by the new pipeline
  on the CPU smoke mesh feeds ``auto_tuner plan --profile <json>`` end
  to end, and measured hide overrides change CostModel scoring;
* **straggler detection** — synthetic skewed windows flag exactly the
  slow host; a two-rank aggregation through the store emits the
  ``straggler_detected`` event into the JSONL log;
* **flight recorder** — an injected ``watchdog/hang`` stall makes the
  watchdog fire and leaves a bundle containing the telemetry ring tail,
  recent events and the open spans; with the flag off the recorder is
  inert and compiled programs are untouched (bitwise HLO).
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import observability as obs
from paddle_tpu.distributed.auto_tuner import planner as PL
from paddle_tpu.distributed.auto_tuner.sweep import profile_candidate
from paddle_tpu.observability import profile_reader as PR
from paddle_tpu.utils import shard_map


# ---------------------------------------------------------------------------
# HLO census
# ---------------------------------------------------------------------------
def _scan_psum_program(mesh, length=3):
    def f(x, w):
        def body(c, _):
            return jax.lax.psum(c @ w, "mp") * 0.5, ()
        out, _ = jax.lax.scan(body, x, None, length=length)
        return jax.lax.pmean(jnp.sum(out), "dp")

    return jax.jit(shard_map(f, mesh=mesh, in_specs=(P("dp", "mp"),
                                                     P("mp", None)),
                             out_specs=P()))


def test_hlo_census_counts_loop_collectives():
    """Collectives inside a lax.scan count trip times; dot FLOPs too.
    The outer pmean adds one more all-reduce at multiplier 1."""
    mesh = dist.build_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
    j = _scan_psum_program(mesh, length=3)
    x = jnp.ones((8, 16))
    w = jnp.ones((16, 8))
    text = j.lower(x, w).compile().as_text()
    c = PR.hlo_census(text, default_group=4)
    assert c.collectives["all_reduce"]["count"] == 4  # 3 in-loop + 1
    # in-loop payload [4,8] f32 = 128 B over a 2-group: 2*128*(1/2) each;
    # the outer scalar pmean adds 2*4*(1/2)
    assert c.collectives["all_reduce"]["wire_bytes"] == 3 * 128 + 4
    # dot [4,8]x[8,8] = 512 flops, 3 trips
    assert c.dot_flops == 3 * 512
    assert not c.notes


def test_hlo_census_beats_cost_analysis_on_loops():
    """The reason the census exists: XLA cost_analysis reports loop
    bodies once, the census multiplies by the trip count."""
    mesh = dist.build_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
    j = _scan_psum_program(mesh, length=5)
    x = jnp.ones((8, 16))
    w = jnp.ones((16, 8))
    compiled = j.lower(x, w).compile()
    c = PR.hlo_census(compiled.as_text(), default_group=4)
    ca = compiled.cost_analysis()
    ca_flops = float((ca if isinstance(ca, dict) else ca[0])["flops"])
    assert c.dot_flops == 5 * 512
    assert ca_flops < c.dot_flops  # cost_analysis undercounts the loop


_HAND_HLO = """HloModule m, entry_computation_layout={()->f32[]}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%body (p: (s32[], f32[4,8], f32[8,8])) -> (s32[], f32[4,8], f32[8,8]) {
  %p = (s32[], f32[4,8]{1,0}, f32[8,8]{1,0}) parameter(0)
  %x = f32[4,8]{1,0} get-tuple-element(%p), index=1
  %w = f32[8,8]{1,0} get-tuple-element(%p), index=2
  %i = s32[] get-tuple-element(%p), index=0
  %d = f32[4,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = (f32[4,8]{1,0}, f32[4]{0}, f32[4]{0}, f32[4]{0}, f32[4]{0}, /*index=5*/f32[4]{0}) all-reduce(%d, %d, %d, %d, %d, %d), replica_groups={{0,1},{2,3}}, to_apply=%add
  ROOT %t = (s32[], f32[4,8]{1,0}, f32[8,8]{1,0}) tuple(%i, %d, %w)
}

%cond (p: (s32[], f32[4,8], f32[8,8])) -> pred[] {
  %p = (s32[], f32[4,8]{1,0}, f32[8,8]{1,0}) parameter(0)
  %c = s32[] constant(99)
  ROOT %lt = pred[] compare(%c, %c), direction=LT
}

ENTRY %main () -> f32[] {
  %init = (s32[], f32[4,8]{1,0}, f32[8,8]{1,0}) tuple()
  %w0 = (s32[], f32[4,8]{1,0}, f32[8,8]{1,0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %z = f32[] constant(0)
}
"""


def test_hlo_census_reads_the_installed_printer():
    """The three spellings that broke the reader on the installed XLA:
    dot operands printed by name only (shape from the operand's own
    definition), ``/*index=N*/`` markers inside a combined collective's
    tuple shape, and the trip count XLA prints on the while's own line
    (which beats the constant in the condition)."""
    c = PR.hlo_census(_HAND_HLO, default_group=4)
    assert c.dot_flops == 7 * 2 * (4 * 8) * 8
    assert c.collectives["all_reduce"]["count"] == 7
    payload = 4 * 8 * 4 + 5 * 4 * 4  # f32[4,8] + five f32[4]
    assert c.collectives["all_reduce"]["wire_bytes"] == 7 * payload
    assert not c.notes


def test_attribution_math():
    """attribute_window: exposed clamps to [0, wire], hidden is the
    remainder, residual beyond compute+wire lands in overhead."""
    census = PR.Census(
        collectives={"all_reduce": {"count": 4.0, "wire_bytes": 4e6}},
        dot_flops=2e9, n_while=0, notes=[])
    rates = PR.MeasuredRates(rate_flops=1e12, ici_gbs=1.0, launch_s=1e-3)
    # wire = 4e6/1e9 + 4*1e-3 = 8 ms; compute = 2 ms
    att = PR.attribute_window(census, 0.006, rates)
    assert att["compute_s"] == pytest.approx(0.002)
    assert att["total_wire_s"] == pytest.approx(0.008)
    assert att["exposed_comm_s"] == pytest.approx(0.004)
    assert att["hidden_comm_s"] == pytest.approx(0.004)
    assert att["overhead_s"] == pytest.approx(0.0)
    assert att["hidable_fraction"] == pytest.approx(0.5)
    # step longer than compute + wire: the excess is overhead, nothing
    # is hidden
    att2 = PR.attribute_window(census, 0.015, rates)
    assert att2["exposed_comm_s"] == pytest.approx(0.008)
    assert att2["hidden_comm_s"] == pytest.approx(0.0)
    assert att2["overhead_s"] == pytest.approx(0.005)


def test_cost_model_hide_overrides():
    """Measured hide overrides WIN over the table and the
    overlap_capable zeroing (they are the measurement)."""
    import dataclasses
    from paddle_tpu.models.gpt import gpt_tiny
    cfg = gpt_tiny()
    spec = PL.ModelSpec.from_config(cfg, "gpt")
    cand = PL.PlanCandidate(dp=4, mp=2)
    base = PL.KNOWN_PROFILES["cpu"]  # overlap_capable=False
    cm0 = PL.CostModel(spec, base, global_batch=16, seq=64)
    exp0, wire0 = cm0.exposed_comm_s(cand)
    prof = dataclasses.replace(base, hide={"mp:allreduce": 1.0},
                               source="measured")
    cm1 = PL.CostModel(spec, prof, global_batch=16, seq=64)
    exp1, wire1 = cm1.exposed_comm_s(cand)
    assert wire1 == wire0  # wire model unchanged
    bw = base.ici_gbs * 1e9
    # the mp term is fully hidden now; dp stays exposed
    assert exp1 == pytest.approx(wire0["dp"] / bw, rel=1e-9)
    assert exp1 < exp0
    assert cm1.hide_fractions(cand)["mp"] == 1.0
    assert cm0.hide_fractions(cand)["mp"] == 0.0


def test_capture_profile_and_plan_cli(tmp_path):
    """Acceptance: `auto_tuner plan --profile <json>` runs end-to-end on
    a profile produced by the capture pipeline on the CPU smoke mesh."""
    mesh = dist.build_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
    j = _scan_psum_program(mesh, length=4)
    x = jnp.ones((8, 16))
    w = jnp.ones((16, 8))
    win = PR.capture_step_profile(j, (x, w), steps=2, label="smoke",
                                  mode="mp:allreduce", mesh=mesh)
    assert win.steps == 2 and win.step_time_s > 0
    assert win.census.collectives["all_reduce"]["count"] == 5
    prof = PR.derive_hardware_profile([win],
                                      base=PL.KNOWN_PROFILES["cpu"])
    assert prof.source == "measured"
    assert "mp:allreduce" in prof.hide
    path = str(tmp_path / "measured.json")
    PR.save_profile_json(path, prof, [win])
    # the CLI consumes it directly
    from paddle_tpu.distributed.auto_tuner.__main__ import main as cli
    import io
    import contextlib
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli(["plan", "--model", "gpt_tiny", "--mesh", "2x4",
                  "--profile", path, "--json"])
    assert rc == 0
    report = json.loads(buf.getvalue())
    assert report["profile"]["source"] == "measured"
    assert report["profile"]["hide"]["mp:allreduce"] == pytest.approx(
        win.hidable_fraction, abs=1e-3)
    assert report["ranked"], report


def test_profile_reader_golden_dp2mp2():
    """Satellite golden: attribute a known dp2·mp2 CPU-smoke hybrid step
    — the census wire bytes must agree with the planner's analytic wire
    model within the documented tolerance (census counts remat replays
    of forward collectives + engine-internal reductions the useful-work
    model excludes, so the ratio sits in [0.9, 2.5])."""
    from paddle_tpu.models import gpt as G
    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=16, dtype=jnp.float32)
    cand = PL.PlanCandidate(dp=2, mp=2)
    rates = PR.MeasuredRates(rate_flops=1e11, ici_gbs=1.0, launch_s=1e-4)
    win = profile_candidate(cfg, cand, global_batch=8, seq=16, steps=2,
                            rates=rates, mode="mp:allreduce")
    spec = PL.ModelSpec.from_config(cfg, "gpt")
    cm = PL.CostModel(spec, PL.KNOWN_PROFILES["cpu"], global_batch=8,
                      seq=16)
    analytic = sum(cm.predict(cand).wire.values())
    ratio = win.census.total_wire_bytes / analytic
    assert 0.9 <= ratio <= 2.5, (
        f"census {win.census.total_wire_bytes:.0f} B vs analytic "
        f"{analytic:.0f} B (ratio {ratio:.2f}) outside the documented "
        f"[0.9, 2.5] tolerance")
    # attribution is complete: the step splits into compute + exposed +
    # hidden + overhead exactly
    total = win.compute_s + win.exposed_comm_s + win.overhead_s
    assert total == pytest.approx(win.step_time_s, rel=1e-6)
    assert win.census.collectives["all_reduce"]["count"] > 0


@pytest.mark.slow
def test_profile_attribution_gate():
    """The slow-tier acceptance gate: measured exposed-comm attribution
    vs the analytic wire models across 3 planner configs + one
    deliberately-bad-overlap config on the CPU smoke mesh. Documented
    tolerance: census/analytic wire-byte ratio in [0.5, 2.5] for the
    scored configs; the bad config is exempt from the ratio but MUST
    attribute the worst exposed comm."""
    from paddle_tpu.models import gpt as G
    cfg = G.GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                      num_heads=4, max_seq_len=128, dtype=jnp.float32)
    B, S = 16, 128
    spec = PL.ModelSpec.from_config(cfg, "gpt")
    cm = PL.CostModel(spec, PL.KNOWN_PROFILES["cpu"], global_batch=B,
                      seq=S)
    flat = dist.build_mesh({"dp": 8})
    bw, launch = PR.measure_collective_rates(flat)
    rates = PR.MeasuredRates(rate_flops=PR.measure_compute_rate(),
                             ici_gbs=bw, launch_s=launch)
    Pc = PL.PlanCandidate
    gated = [(Pc(dp=8), "dp:monolithic"),
             (Pc(dp=8, comm_bucket_mb=4.0), "dp:bucketed"),
             (Pc(dp=4, mp=2), "mp:allreduce")]
    # the bad-overlap config: ring collective-matmul pays 4*(mp-1)
    # collectives per GEMM pair for overlap this backend cannot deliver —
    # the worst config on the CPU mesh, where modes rank by op count
    bad = Pc(dp=2, mp=4, mp_overlap="collective_matmul")
    host_params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    windows, exposed = [], {}
    for cand, mode in gated + [(bad, None)]:
        win = profile_candidate(cfg, cand, global_batch=B, seq=S,
                                steps=3, rates=rates, mode=mode,
                                host_params=host_params)
        windows.append(win)
        exposed[str(cand)] = win.exposed_comm_s
        analytic = sum(cm.predict(cand).wire.values())
        ratio = win.census.total_wire_bytes / max(analytic, 1.0)
        if mode is not None:  # the bad config is ratio-exempt
            assert 0.5 <= ratio <= 2.5, (cand, ratio)
    # the bad-overlap config must attribute the WORST exposed comm
    assert exposed[str(bad)] == max(exposed.values()), exposed
    # the derived measured profile drives a full plan end-to-end
    prof = PR.derive_hardware_profile(windows,
                                      base=PL.KNOWN_PROFILES["cpu"])
    report = PL.plan(cfg, world=8, global_batch=B, seq=S, family="gpt",
                     profile=prof)
    assert report.ranked
    assert report.profile.source == "measured"


# ---------------------------------------------------------------------------
# Fleet telemetry + stragglers
# ---------------------------------------------------------------------------
def test_detect_stragglers_synthetic():
    fast = [10.0 + 0.1 * i for i in range(16)]
    slow = [25.0 + 0.1 * i for i in range(16)]
    det = obs.detect_stragglers({0: fast, 1: fast, 2: slow, 3: []},
                                factor=1.5)
    assert det["stragglers"] == [2]
    assert det["missing"] == [3]
    assert det["skew"] == pytest.approx(
        det["hosts"][2]["median_ms"] / det["fleet_median_ms"])
    assert det["hosts"][2]["p95_ms"] >= det["hosts"][2]["median_ms"]
    # under a looser factor nothing is flagged
    det2 = obs.detect_stragglers({0: fast, 1: fast, 2: slow}, factor=3.0)
    assert det2["stragglers"] == []


def test_detect_stragglers_no_false_flags_on_uniform_noise():
    rng = np.random.RandomState(0)
    windows = {h: list(10.0 + rng.rand(32)) for h in range(4)}
    det = obs.detect_stragglers(windows, factor=1.5)
    assert det["stragglers"] == []
    assert det["skew"] < 1.2


def test_aggregator_two_ranks_through_store(tmp_path):
    """Two aggregator instances (world 2) over a shared in-process
    store: rank 0's aggregate flags the slowed host, exports per-host
    p50/p95 gauges and emits straggler_detected into the JSONL log."""
    from paddle_tpu.distributed.store import TCPStore
    store0 = TCPStore(port=0, world_size=2, is_master=True)
    store1 = TCPStore(port=store0.port, world_size=2)
    log = obs.EventLog(str(tmp_path / "fleet.jsonl"))
    a0 = obs.TelemetryAggregator(rank=0, world_size=2, store=store0,
                                 host=0, window=8, interval=4,
                                 straggler_factor=1.5, event_log=log)
    a1 = obs.TelemetryAggregator(rank=1, world_size=2, store=store1,
                                 host=1, window=8, interval=4,
                                 straggler_factor=1.5)
    report = None
    for i in range(4):
        a0.note_step(10.0)
        a1.note_step(30.0)
        a1.tick(i)  # publish first so rank 0's gather never waits
        r = a0.tick(i)
        if r is not None:
            report = r
    assert report is not None
    assert report["stragglers"] == [1]
    assert report["skew"] == pytest.approx(3.0)
    assert a0.prom.get("step_ms_p95_host1") == pytest.approx(30.0)
    assert a0.prom.get("step_ms_p50_host0") == pytest.approx(10.0)
    assert a0.prom.get("stragglers") == 1
    assert a0.prom.get("step_time_skew") == pytest.approx(3.0)
    # the prom snapshot crossed the wire with the payload
    assert "step_ms_p95" in report["prom"][1]
    log.close()
    recs = [json.loads(l) for l in
            open(log.path, encoding="utf-8").read().splitlines()]
    ev = [r for r in recs if r["event"] == "straggler_detected"]
    assert len(ev) == 1  # flagged once per episode, not per round
    assert ev[0]["straggler_host"] == 1
    assert ev[0]["fleet_median_ms"] == pytest.approx(
        report["fleet_median_ms"])


def test_aggregator_in_run_resilient(tmp_path):
    """Single-process wiring: run_resilient(aggregator=) feeds step
    times and lands the final fleet report in info['fleet']."""
    from paddle_tpu.distributed.resilience import run_resilient
    agg = obs.TelemetryAggregator(rank=0, world_size=1, host=0,
                                  window=8, interval=2,
                                  straggler_factor=1.5)
    state = {"w": jnp.zeros((4,))}

    def step_fn(st, i):
        return {"w": st["w"] + 1.0}, jnp.float32(1.0)

    _, info = run_resilient(step_fn, state, steps=6,
                            ckpt_dir=str(tmp_path / "ck"), ckpt_every=0,
                            resume=False, aggregator=agg)
    assert info["fleet"] is not None
    assert info["fleet"]["stragglers"] == []
    assert 0 in info["fleet"]["hosts"]
    assert agg.prom.get("step_ms_count") is None  # histogram, not gauge
    snap = agg.prom.snapshot()
    assert snap["step_ms_count"] == 6.0


@pytest.mark.slow
def test_two_process_fleet_telemetry():
    """The mp_smoke fleet leg: 2 spawned processes aggregate over a real
    TCP store; the slowed rank must be flagged. Skips where the platform
    cannot run the spawned cluster."""
    from paddle_tpu.distributed import mp_smoke
    try:
        out = mp_smoke.fleet_telemetry_check(8, timeout=120, steps=8,
                                             slow_ms=80.0)
    except mp_smoke.ClusterUnsupported as e:
        pytest.skip(str(e))
    assert out["stragglers"] == [1]
    assert out["skew"] > 1.35


# ---------------------------------------------------------------------------
# prom histogram + quantiles
# ---------------------------------------------------------------------------
def test_prom_histogram_render_and_quantile():
    reg = obs.PromRegistry(namespace="t")
    for v in (0.002, 0.02, 0.02, 0.2):
        reg.histogram_observe("lat", v, buckets=(0.01, 0.1, 1.0))
    text = reg.render()
    assert '# TYPE t_lat histogram' in text
    assert 't_lat_bucket{le="0.01"} 1' in text
    assert 't_lat_bucket{le="0.1"} 3' in text
    assert 't_lat_bucket{le="1"} 4' in text
    assert 't_lat_bucket{le="+Inf"} 4' in text
    assert "t_lat_count 4" in text
    assert reg.quantile("lat", 0.5) == pytest.approx(0.02)
    assert reg.quantile("lat", 0.95) == pytest.approx(0.2)
    assert reg.get("lat") == pytest.approx((0.002 + 0.04 + 0.2) / 4)


def test_prom_summary_window_quantile_recent_only():
    """The window forgets: a slow startup wave stops biasing p95 once
    enough recent observations displace it (the TTFT/SLO fix)."""
    reg = obs.PromRegistry(namespace="t")
    for _ in range(4):
        reg.summary_observe("ttft", 5.0, window=8)
    for _ in range(8):
        reg.summary_observe("ttft", 0.1, window=8)
    assert reg.quantile("ttft", 0.95) == pytest.approx(0.1)
    # the lifetime mean still remembers — that is exactly why adaptive
    # control must not read it
    assert reg.get("ttft") > 1.0
    snap = reg.snapshot()
    assert snap["ttft_count"] == 12.0
    assert snap["ttft_p95"] == pytest.approx(0.1)


def test_serving_pick_burst_reads_window_p95():
    """The adaptive mix reads the registry's recent-window p95, not the
    lifetime mean: once recent TTFTs sit below the SLO the burst
    recovers even though the mean stays above it."""
    from paddle_tpu.inference.serving import ServingEngine
    eng = ServingEngine.__new__(ServingEngine)  # scheduler-only surface
    eng.adaptive_mix = True
    eng.decode_burst = 8
    eng.ttft_slo_s = 1.0
    eng._ttft_window = 4
    eng._prom = obs.PromRegistry(namespace="paddle_tpu_serving")
    for _ in range(6):  # slow startup wave
        eng._prom.summary_observe("ttft_seconds", 5.0, window=4)
    assert eng._pick_burst(1) < eng.decode_burst  # over SLO: shortened
    for _ in range(4):  # recent window recovers
        eng._prom.summary_observe("ttft_seconds", 0.05, window=4)
    with_pressure = eng._pick_burst(1)
    eng._prom.gauge_set("queue_depth", 0)
    assert eng._pick_burst(0) == eng.decode_burst  # no pressure: full
    assert with_pressure >= 8 // 4  # only the prefill pressure divides


# ---------------------------------------------------------------------------
# events: rotation, host/role, merge
# ---------------------------------------------------------------------------
def test_eventlog_rotation_and_attribution_fields(tmp_path):
    # size one record first, then cap for EXACTLY one rotation over 10
    # records, so the .1 generation + live file together hold every line
    probe = obs.EventLog(str(tmp_path / "probe.jsonl"), host=3)
    probe.emit("tick", i=0, pad="x" * 64)
    probe.close()
    rec_bytes = os.path.getsize(probe.path)
    path = str(tmp_path / "ev.jsonl")
    log = obs.EventLog(path, role="trainer", host=3,
                       max_mb=6.5 * rec_bytes / (1 << 20))
    for i in range(10):
        log.emit("tick", i=i, pad="x" * 64)
    log.close()
    assert log.rotations == 1
    assert os.path.exists(path + ".1")  # rotated generation
    recs = [json.loads(l) for l in
            open(path, encoding="utf-8").read().splitlines()]
    assert recs[0]["event"] == "jsonl_rotated"
    assert recs[0]["rotated_to"] == path + ".1"
    for r in recs:
        assert r["host"] == 3 and r["role"] == "trainer"
    # no line was lost across the rotation
    old = [json.loads(l) for l in
           open(path + ".1", encoding="utf-8").read().splitlines()]
    ticks = [r["i"] for r in old + recs if r["event"] == "tick"]
    assert ticks == list(range(10))


def test_eventlog_tail(tmp_path):
    log = obs.EventLog(str(tmp_path / "t.jsonl"))
    for i in range(20):
        log.emit("e", i=i)
    tail = log.tail(5)
    assert [r["i"] for r in tail] == [15, 16, 17, 18, 19]
    log.close()


def test_merge_event_streams(tmp_path):
    """One role-tagged timeline over a trainer loop and a serving
    engine's streams — ordered by ts, every record attributable."""
    trainer = obs.EventLog(str(tmp_path / "trainer.jsonl"),
                           role="trainer")
    serving = obs.EventLog(str(tmp_path / "serving.jsonl"),
                           role="serving")
    trainer.emit("step", i=0)
    serving.emit("serving_admit", rid=0)
    trainer.emit("step", i=1)
    serving.emit("serving_complete", rid=0)
    merged = obs.merge_event_streams(
        trainer, serving, out_path=str(tmp_path / "merged.jsonl"))
    assert len(merged) == 4
    assert [r["ts"] for r in merged] == sorted(r["ts"] for r in merged)
    roles = {r["event"]: r["role"] for r in merged}
    assert roles["step"] == "trainer"
    assert roles["serving_admit"] == "serving"
    written = [json.loads(l) for l in
               open(tmp_path / "merged.jsonl",
                    encoding="utf-8").read().splitlines()]
    assert written == merged


def test_merge_event_streams_training_plus_serving_engine(tmp_path):
    """The RL-loop pre-work end to end: a real training step loop and a
    real ServingEngine write separate logs; the merged stream carries
    both halves role-tagged."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import gpt as G
    t_log = obs.EventLog(str(tmp_path / "train.jsonl"), role="trainer")
    prev = obs.set_event_log(t_log)
    try:
        w = jnp.zeros((8, 8))
        x = jnp.ones((4, 8))

        @jax.jit
        def step(w):
            return w - 0.1 * jax.grad(
                lambda w: jnp.sum((x @ w) ** 2))(w)

        for i in range(3):
            w = step(w)
            obs.emit_event("train_step", step=i)
        # swap the process log to the serving stream and run the engine
        s_log = obs.EventLog(str(tmp_path / "serve.jsonl"),
                             role="serving")
        obs.set_event_log(s_log)
        cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                          num_heads=4, max_seq_len=64, dtype=jnp.float32)
        params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(params, cfg, max_batch=2, block_size=16,
                            num_blocks=16, chunk=8, decode_burst=2)
        eng.add_request(np.arange(4) % 64, max_new_tokens=3)
        eng.run(max_steps=20)
    finally:
        obs.set_event_log(prev)
    merged = obs.merge_event_streams(t_log, s_log)
    roles = {(r["role"], r["event"]) for r in merged}
    assert ("trainer", "train_step") in roles
    assert ("serving", "serving_admit") in roles
    assert ("serving", "serving_complete") in roles


def test_serving_steps_land_on_span_timeline(tmp_path):
    """The small fix: serving shows up on the same host timeline as
    training — engine steps open RecordEvent spans the profiler's
    collector sees."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import gpt as G
    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=64, dtype=jnp.float32)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, max_batch=2, block_size=16,
                        num_blocks=16, chunk=8, decode_burst=2)
    eng.add_request(np.arange(4) % 64, max_new_tokens=3)
    with obs.capture_spans() as cap:
        eng.run(max_steps=20)
    names = {e.name for e in cap.events}
    assert "serving_step" in names
    assert "serving_unified_dispatch" in names
    path = obs.write_chrome_trace(str(tmp_path / "t.json"), cap.events)
    trace = json.load(open(path))
    assert any(ev["name"] == "serving_step"
               for ev in trace["traceEvents"])
    # the engine's first steps show their start-up work: a first-call bar
    # with its burst size around the three compile bars, each named
    first = [ev for ev in trace["traceEvents"]
             if ev["name"] == "startup_program_first_call"]
    assert first and all(ev["cat"] == "Startup" and ev["args"]["k"] >= 1
                         for ev in first)
    inside = [ev for ev in trace["traceEvents"] if ev["cat"] == "Compile"
              and first[0]["ts"] <= ev["ts"]
              and ev["ts"] + ev["dur"] <= first[0]["ts"] + first[0]["dur"]]
    assert {ev["name"] for ev in inside} == {
        "compile_trace", "compile_lower", "compile_backend"}
    assert all(ev["args"]["fun"] for ev in inside)


# ---------------------------------------------------------------------------
# faults: the hang clause
# ---------------------------------------------------------------------------
def test_fault_hang_clause_stalls_then_continues():
    from paddle_tpu.distributed.resilience import faults
    faults.configure("x/slow:2:hang0.2")
    try:
        t0 = time.perf_counter()
        faults.maybe_fail("x/slow")          # hit 1: no stall
        assert time.perf_counter() - t0 < 0.15
        t0 = time.perf_counter()
        faults.maybe_fail("x/slow")          # hit 2: stalls, no raise
        assert time.perf_counter() - t0 >= 0.2
        faults.maybe_fail("x/slow")          # one-shot: done
        assert faults.hits()["x/slow"] == 3
    finally:
        faults.configure("")


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def _drive_telemetry_host():
    """A TelemetryHost with two decoded rows, so crash bundles have a
    real ring tail."""
    tcfg = obs.TelemetryConfig(interval=2)
    host = obs.TelemetryHost(tcfg)
    buf = obs.init_buffer(tcfg)
    buf = obs.update_buffer(buf, tcfg, {"loss": jnp.float32(1.5)})
    buf = obs.update_buffer(buf, tcfg, {"loss": jnp.float32(1.25)})
    host.poll({"telemetry": buf}, 1)
    return host


def test_flight_recorder_bundle_on_injected_hang(tmp_path):
    """Acceptance: an injected watchdog/hang fault produces a bundle
    containing the telemetry tail, recent events and open spans — and
    the run CONTINUES after the stall (a hang is not a crash)."""
    from paddle_tpu.distributed.resilience import run_resilient
    log = obs.EventLog(str(tmp_path / "ev.jsonl"))
    prev_log = obs.set_event_log(log)
    rec = obs.FlightRecorder(str(tmp_path / "crash"), max_events=50,
                             keep=4, min_interval_s=0.0)
    prev_rec = obs.set_flight_recorder(rec)
    tele_host = _drive_telemetry_host()
    paddle.set_flags({"FLAGS_fault_inject": "watchdog/hang:2:hang1.2"})
    try:
        from paddle_tpu.distributed.watchdog import CommWatchdog
        state = {"w": jnp.zeros((4,))}

        def step_fn(st, i):
            return {"w": st["w"] + 1.0}, jnp.float32(float(i))

        wd = CommWatchdog(poll_interval=0.1)
        _, info = run_resilient(step_fn, state, steps=3,
                                ckpt_dir=str(tmp_path / "ck"),
                                ckpt_every=0, resume=False, watchdog=wd,
                                step_timeout=0.3)
        assert info["completed_steps"] == 3      # stalled, not aborted
        assert info["watchdog"]["timeout_count"] == 1
    finally:
        paddle.set_flags({"FLAGS_fault_inject": ""})
        obs.set_flight_recorder(prev_rec)
        obs.set_event_log(prev_log)
        wd.stop()
    assert rec.last_bundle is not None
    bundle = rec.last_bundle
    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    assert manifest["reason"].startswith("watchdog_timeout")
    assert "resilient_step" in manifest["reason"]
    assert manifest["watchdog"]["active"] >= 1    # the hung span, open
    # recent events: the run lifecycle up to the hang is in the tail
    tail = [json.loads(l) for l in
            open(os.path.join(bundle, "events_tail.jsonl"),
                 encoding="utf-8").read().splitlines()]
    assert any(r["event"] == "resilience_run_start" for r in tail)
    # open spans: the wedged step's watchdog span with its age
    spans = json.load(open(os.path.join(bundle, "open_spans.json")))
    pend = {s["tag"]: s["age_s"] for s in spans["watchdog_pending"]}
    assert "resilient_step" in pend and pend["resilient_step"] >= 0.3
    # telemetry ring tail: the live host's decoded rows (other tests'
    # hosts may still be alive in the registry — find ours)
    tele = json.load(open(os.path.join(bundle, "telemetry_tail.json")))
    assert any(ring["series"]["loss"] == [1.5, 1.25]
               for ring in tele.values())
    # the report carries the thread-stack dump
    report = open(os.path.join(bundle, "report.txt")).read()
    assert "exceeded its deadline" in report
    # the dump announced itself in the JSONL stream
    recs = [json.loads(l) for l in
            open(log.path, encoding="utf-8").read().splitlines()]
    assert any(r["event"] == "flight_recorder_dump" for r in recs)
    del tele_host


def test_flight_recorder_sigterm_dump(tmp_path):
    """The resilience SIGTERM path dumps a bundle during the drain."""
    import signal
    from paddle_tpu.distributed.resilience import run_resilient
    rec = obs.FlightRecorder(str(tmp_path / "crash"), min_interval_s=0.0)
    prev_rec = obs.set_flight_recorder(rec)
    try:
        state = {"w": jnp.zeros((2,))}

        def step_fn(st, i):
            if i == 1:
                os.kill(os.getpid(), signal.SIGTERM)
            return st, jnp.float32(1.0)

        _, info = run_resilient(step_fn, state, steps=10,
                                ckpt_dir=str(tmp_path / "ck"),
                                ckpt_every=0, resume=False, grace_s=5.0)
        assert info["preempted"]
    finally:
        obs.set_flight_recorder(prev_rec)
    assert rec.last_bundle is not None
    manifest = json.load(
        open(os.path.join(rec.last_bundle, "manifest.json")))
    assert manifest["reason"] == "sigterm"


def test_flight_recorder_bounded(tmp_path):
    """The crash dir stays bounded: keep-N pruning + rate limit."""
    rec = obs.FlightRecorder(str(tmp_path / "crash"), keep=2,
                             min_interval_s=0.0)
    for i in range(5):
        assert rec.dump(f"r{i}") is not None
    bundles = [e for e in os.listdir(tmp_path / "crash")
               if e.startswith("flight_")]
    assert len(bundles) == 2
    limited = obs.FlightRecorder(str(tmp_path / "crash2"), keep=2,
                                 min_interval_s=60.0)
    assert limited.dump("a") is not None
    assert limited.dump("b") is None  # rate-limited


def test_flight_recorder_off_is_inert_and_hlo_unchanged(tmp_path):
    """Telemetry-off no-op stays intact (the established HLO-assert
    pattern): arming the flight recorder flag changes NOTHING in a
    compiled program — it is host-side only — and with the flag empty
    maybe_dump is a no-op."""
    from paddle_tpu.observability.flight_recorder import maybe_dump
    assert obs.get_flight_recorder() is None  # flag empty
    assert maybe_dump("nothing") is None

    @jax.jit
    def step(w):
        return w * 2.0
    w = jnp.ones((8,))
    base = step.lower(w).as_text()
    paddle.set_flags(
        {"FLAGS_flight_recorder_dir": str(tmp_path / "crash")})
    try:
        assert obs.get_flight_recorder() is not None
        armed = step.lower(w).as_text()
    finally:
        paddle.set_flags({"FLAGS_flight_recorder_dir": ""})
    assert base == armed


def test_active_spans_registry():
    from paddle_tpu.profiler import RecordEvent, active_spans
    ev = RecordEvent("hanging_op")
    ev.begin()
    try:
        spans = active_spans()
        mine = [s for s in spans if s["name"] == "hanging_op"]
        assert mine and mine[0]["age_s"] >= 0.0
    finally:
        ev.end()
    assert not [s for s in active_spans() if s["name"] == "hanging_op"]
