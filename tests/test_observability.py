"""Observability subsystem (ISSUE 4): in-program telemetry, step/MFU
accounting, JSONL events, Prometheus scrape, chrome-trace spans.

The two contract tests that anchor the subsystem:

* **no-op guarantee** — with telemetry off the hybrid engine's compiled
  train step is BITWISE identical to one built with no telemetry arg at
  all (asserted on the lowered HLO text), and donation still covers the
  whole carry when it is on;
* **one fetch per interval** — a 50-step run with interval 10 costs
  exactly 5 device fetches and yields complete loss / grad-norm /
  comms-bytes series in the JSONL log.
"""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import observability as obs
from paddle_tpu.distributed.comm_overlap import CommOverlapConfig
from paddle_tpu.models.hybrid_engine import build_train_step


def _job(seed=0):
    rng = np.random.RandomState(seed)
    params = {"w": jnp.asarray(rng.randn(64, 32).astype(np.float32)),
              "b": jnp.zeros((32,), jnp.float32)}
    specs = {"w": P(), "b": P()}
    xs = jnp.asarray(rng.randn(16, 64).astype(np.float32))
    ys = jnp.asarray(rng.randn(16, 32).astype(np.float32))

    def loss_fn(p, x, y):
        loss = jnp.mean((x @ p["w"] + p["b"] - y) ** 2)
        obs.observe("train/aux", loss * 2.0)
        return loss

    return params, specs, xs, ys, loss_fn


# ---------------------------------------------------------------------------
# no-op + overhead contracts
# ---------------------------------------------------------------------------
def test_telemetry_off_is_bitwise_noop():
    """FLAGS_telemetry=off must leave the compiled train step bitwise
    unchanged: same lowered HLO text as a build with telemetry=None, with
    observe() calls present in the loss."""
    mesh = dist.build_mesh({"dp": 8})
    params, specs, xs, ys, loss_fn = _job()
    opt = paddle.optimizer.AdamW(1e-3)
    lr = jnp.float32(1e-3)

    step_none, shard, init = build_train_step(loss_fn, specs, mesh, opt,
                                              telemetry=None)
    p = shard(params)
    st = init(p)
    base = step_none.lower(p, st, xs, ys, lr).as_text()

    paddle.set_flags({"FLAGS_telemetry": False})
    step_auto, _, _ = build_train_step(loss_fn, specs, mesh,
                                       paddle.optimizer.AdamW(1e-3),
                                       telemetry="auto")
    assert step_auto.lower(p, st, xs, ys, lr).as_text() == base

    # and ON genuinely changes the program (the guard would be vacuous if
    # a telemetry build accidentally compiled to the same thing)
    tcfg = obs.TelemetryConfig(interval=4, extra=("train/aux",))
    step_on, shard_on, init_on = build_train_step(
        loss_fn, specs, mesh, paddle.optimizer.AdamW(1e-3), telemetry=tcfg)
    p_on = shard_on(params)
    st_on = init_on(p_on)
    assert "telemetry" in st_on
    assert step_on.lower(p_on, st_on, xs, ys, lr).as_text() != base


def test_telemetry_50_steps_one_fetch_per_interval(tmp_path):
    """Acceptance gate: 50 steps at interval 10 -> exactly 5 host fetches,
    and the JSONL log carries complete grad-norm, comms-bytes and loss
    series."""
    mesh = dist.build_mesh({"dp": 8})
    params, specs, xs, ys, loss_fn = _job()
    tcfg = obs.TelemetryConfig(interval=10, extra=("train/aux",))
    step, shard, init = build_train_step(
        loss_fn, specs, mesh, paddle.optimizer.AdamW(1e-3), telemetry=tcfg)
    p = shard(params)
    st = init(p)

    log_path = str(tmp_path / "telemetry.jsonl")
    with obs.EventLog(log_path) as log:
        host = obs.TelemetryHost(tcfg, event_log=log)
        losses = []
        for i in range(50):
            p, st, loss = step(p, st, xs, ys, jnp.float32(1e-3))
            losses.append(float(loss))
            host.poll(st, i)

    assert host.fetch_count == 5
    assert len(host.steps) == 50 and host.steps == list(range(50))
    # series decode exactly (loss bitwise — same value the step returned)
    np.testing.assert_array_equal(np.float32(host.series["loss"]),
                                  np.float32(losses))
    assert all(v > 0 for v in host.series["grad_norm"])
    assert all(v == host.series["comms_bytes"][0] > 0
               for v in host.series["comms_bytes"])
    assert all(v == 0 for v in host.series["nonfinite_count"])
    np.testing.assert_allclose(host.series["train/aux"],
                               [2 * v for v in losses], rtol=1e-5)

    events = [json.loads(l) for l in open(log_path)]
    kinds = [e["event"] for e in events]
    assert kinds[0] == "telemetry_run" and kinds.count("telemetry") == 5
    merged = {}
    for e in events:
        if e["event"] == "telemetry":
            for k, v in e["series"].items():
                merged.setdefault(k, []).extend(v)
    for needed in ("loss", "grad_norm", "comms_bytes"):
        assert len(merged[needed]) == 50, needed


@pytest.mark.parametrize("kw", [
    dict(zero1_dp=True),
    dict(comm_overlap=CommOverlapConfig(bucket_mb=1e-4)),
    dict(comm_overlap=CommOverlapConfig(bucket_mb=1e-4, microbatches=2)),
    dict(comm_overlap=CommOverlapConfig(bucket_mb=1e-4, quantize="int8")),
    dict(comm_overlap=CommOverlapConfig(bucket_mb=1e-4), zero1_dp=True),
], ids=["zero1", "overlap", "overlap_mb2", "overlap_int8",
        "overlap_zero1"])
def test_telemetry_composes_with_sync_paths(kw):
    """The buffer rides every grad-sync flavor; loss series tracks the
    step's returned loss and the comms-bytes constant reflects the path
    (int8 shrinks it, microbatches multiply it)."""
    mesh = dist.build_mesh({"dp": 8})
    params, specs, xs, ys, loss_fn = _job()
    tcfg = obs.TelemetryConfig(interval=4, extra=("train/aux",))
    step, shard, init = build_train_step(
        loss_fn, specs, mesh, paddle.optimizer.AdamW(1e-3), telemetry=tcfg,
        example_params=jax.eval_shape(lambda: params), **kw)
    p = shard(params)
    st = init(p)
    host = obs.TelemetryHost(tcfg)
    losses = []
    for i in range(4):
        p, st, loss = step(p, st, xs, ys, jnp.float32(1e-3))
        losses.append(float(loss))
        host.poll(st, i)
    assert host.fetch_count == 1
    np.testing.assert_allclose(host.series["loss"], losses, rtol=1e-6)
    assert host.series["grad_norm"][-1] > 0
    assert host.series["comms_bytes"][0] > 0
    ocfg = kw.get("comm_overlap")
    if ocfg is not None and ocfg.quantize:
        assert host.series["comms_bytes"][0] < 4000  # int8 wire, not fp32
    if ocfg is not None:
        assert tcfg.static["comm_buckets_bytes"]  # per-bucket plan bytes


def test_telemetry_buffer_donated_with_carry():
    """donate=True must alias the whole carry INCLUDING the telemetry
    buffer — the bookkeeping may not cost a second resident copy."""
    mesh = dist.build_mesh({"dp": 8})
    params, specs, xs, ys, loss_fn = _job()
    tcfg = obs.TelemetryConfig(interval=4, extra=("train/aux",))
    step, shard, init = build_train_step(
        loss_fn, specs, mesh, paddle.optimizer.AdamW(1e-3), telemetry=tcfg,
        donate=True)
    p = shard(params)
    st = init(p)
    compiled = step.lower(p, st, xs, ys, jnp.float32(1e-3)).compile()
    try:
        ma = compiled.memory_analysis()
        aliased = int(getattr(ma, "alias_size_in_bytes", 0)) if ma else 0
    except Exception:
        aliased = 0
    if not aliased:
        aliased = (1 << 20) if "input_output_alias" in compiled.as_text() \
            else 0
    assert aliased > 0, "carry not donated"
    out = step(p, st, xs, ys, jnp.float32(1e-3))
    jax.block_until_ready(out)
    assert all(x.is_deleted() for x in jax.tree.leaves(st["telemetry"])), \
        "telemetry buffer survived donation"


def test_observe_unregistered_series_raises():
    mesh = dist.build_mesh({"dp": 8})
    params, specs, xs, ys, _ = _job()

    def loss_fn(p, x, y):
        loss = jnp.mean((x @ p["w"] + p["b"] - y) ** 2)
        obs.observe("not/registered", loss)
        return loss

    step, shard, init = build_train_step(
        loss_fn, specs, mesh, paddle.optimizer.AdamW(1e-3),
        telemetry=obs.TelemetryConfig(interval=2))
    p = shard(params)
    st = init(p)
    with pytest.raises(KeyError, match="not/registered"):
        step(p, st, xs, ys, jnp.float32(1e-3))


def test_flag_driven_config_is_nonstrict_and_reads_extra():
    """FLAGS_telemetry=1 must never crash a model that observe()s a
    series nobody registered: the flag-driven config warns + drops
    unknown names, and FLAGS_telemetry_extra registers them."""
    mesh = dist.build_mesh({"dp": 8})
    params, specs, xs, ys, loss_fn = _job()  # observes "train/aux"
    paddle.set_flags({"FLAGS_telemetry": True,
                      "FLAGS_telemetry_interval": 4})
    try:
        tcfg = obs.telemetry_from_flags()
        assert tcfg is not None and not tcfg.strict
        step, shard, init = build_train_step(
            loss_fn, specs, mesh, paddle.optimizer.AdamW(1e-3),
            telemetry="auto")
        p = shard(params)
        st = init(p)
        with pytest.warns(UserWarning, match="train/aux"):
            p, st, loss = step(p, st, xs, ys, jnp.float32(1e-3))  # no crash

        paddle.set_flags({"FLAGS_telemetry_extra": "train/aux"})
        tcfg = obs.telemetry_from_flags()
        assert tcfg.extra == ("train/aux",)
        step2, shard2, init2 = build_train_step(
            loss_fn, specs, mesh, paddle.optimizer.AdamW(1e-3),
            telemetry="auto")
        p2 = shard2(params)
        st2 = init2(p2)
        host = obs.TelemetryHost(tcfg)
        for i in range(4):
            p2, st2, loss = step2(p2, st2, xs, ys, jnp.float32(1e-3))
            host.poll(st2, i)
        assert len(host.series["train/aux"]) == 4
    finally:
        paddle.set_flags({"FLAGS_telemetry": False,
                          "FLAGS_telemetry_interval": 10,
                          "FLAGS_telemetry_extra": ""})


def test_config_static_rewritten_per_build():
    """Reusing one TelemetryConfig across builds must not leak the
    previous engine's bucket/mesh metadata into the next run's header."""
    params, specs, xs, ys, loss_fn = _job()
    example = jax.eval_shape(lambda: params)
    tcfg = obs.TelemetryConfig(interval=4, extra=("train/aux",))
    build_train_step(loss_fn, specs, dist.build_mesh({"dp": 8}),
                     paddle.optimizer.AdamW(1e-3), telemetry=tcfg,
                     example_params=example,
                     comm_overlap=CommOverlapConfig(bucket_mb=1e-4))
    assert "comm_buckets_bytes" in tcfg.static
    build_train_step(loss_fn, specs,
                     dist.build_mesh({"dp": 4, "mp": 2}),
                     paddle.optimizer.AdamW(1e-3), telemetry=tcfg)
    assert "comm_buckets_bytes" not in tcfg.static
    assert tcfg.static["mesh"] == {"dp": 4, "mp": 2}


def test_observe_is_inert_without_collection():
    # no active collection: observe must not record or fail
    obs.observe("anything", 1.0)
    with obs.collecting() as sink:
        obs.observe("a", jnp.float32(1))
        obs.observe("a", jnp.float32(2))  # repeats sum
        obs.observe("b", 3.0)
    d = obs.metrics.obs_dict(sink)
    assert float(d["a"]) == 3.0 and float(d["b"]) == 3.0
    obs.observe("anything", 1.0)  # scope closed again


# ---------------------------------------------------------------------------
# ring buffer / host decode units
# ---------------------------------------------------------------------------
def test_ring_buffer_update_and_wraparound():
    tcfg = obs.TelemetryConfig(interval=3)
    buf = obs.init_buffer(tcfg)
    for i in range(5):
        buf = obs.update_buffer(buf, tcfg, {"loss": float(i)})
    assert int(buf["count"]) == 5
    col = list(tcfg.series).index("loss")
    # rows hold steps [3, 4, 2] at positions [0, 1, 2]
    np.testing.assert_array_equal(np.asarray(buf["data"])[:, col],
                                  [3.0, 4.0, 2.0])
    with pytest.raises(KeyError):
        obs.update_buffer(buf, tcfg, {"nope": 1.0})


def test_fp8_series_present_with_fp8_plan():
    """fp8 + telemetry: amax/scale drift series are non-zero from the
    first step (the hybrid gpt path builds the plan)."""
    from paddle_tpu.models import gpt as G
    mesh = dist.build_mesh({"dp": 2, "pp": 1, "mp": 4})
    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=16, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    tcfg = obs.TelemetryConfig(interval=2)
    step, shard, init = G.build_hybrid_train_step(
        cfg, mesh, paddle.optimizer.AdamW(1e-3), fp8=True, telemetry=tcfg)
    p = shard(G.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    st = init(p)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 64, (8, 16)))
    labs = jnp.asarray(rng.randint(0, 64, (8, 16)))
    host = obs.TelemetryHost(tcfg)
    for i in range(2):
        p, st, _ = step(p, st, toks, labs, jnp.float32(1e-3))
        host.poll(st, i)
    assert host.series["fp8_amax_max"][-1] > 0
    assert host.series["fp8_scale_max"][-1] > 0


# ---------------------------------------------------------------------------
# flops / StepTimer
# ---------------------------------------------------------------------------
def test_gpt_flops_matches_legacy_inline_math():
    """The bench's frozen series depends on this staying bit-identical to
    the formula previously inlined there: 6*(N - emb) + 12*L*H*S."""
    from paddle_tpu.models import gpt as G
    cfg = G.GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                      num_heads=4, max_seq_len=128, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    seq = 128
    n_params = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params))
    n_emb = (int(np.prod(params["wte"].shape))
             + int(np.prod(params["wpe"].shape)))
    legacy = 6 * (n_params - n_emb) + 12 * cfg.num_layers * cfg.hidden_size * seq
    got = obs.gpt_flops_per_token(cfg, seq, params=params)
    assert got["model"] == legacy
    # remat-aware hardware flops: none < selective < full; fwd = model/3
    # exactly when there is no attention term to skew it
    full = obs.gpt_flops_per_token(cfg, seq, params=params, remat="full")
    sel = obs.gpt_flops_per_token(cfg, seq, params=params,
                                  remat="selective")
    assert got["hardware"] == got["model"]
    assert got["model"] < sel["hardware"] < full["hardware"]
    with pytest.raises(ValueError):
        obs.gpt_flops_per_token(cfg, seq, remat="bogus")


def test_llama_flops_analytic_gqa():
    from paddle_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=64)
    got = obs.llama_flops_per_token(cfg, 64)
    h, L = 64, 2
    kv = 2 * (64 // 4)
    n = L * (h * h + 2 * h * kv + h * h + 3 * h * cfg.intermediate_size) \
        + h * 256
    assert got["model"] == 6 * n + 12 * L * h * 64


def test_mfu_and_collective_seconds():
    assert obs.mfu(100.0, 1e10, peak=1e12) == pytest.approx(1.0)
    # ring all-reduce: 2(n-1)/n * bytes / bw
    t = obs.collective_seconds(8e9, 8, bandwidth_gbs=100.0)
    assert t == pytest.approx(2 * 7 / 8 * 8e9 / 100e9)
    assert obs.collective_seconds(8e9, 1, 100.0) == 0.0
    with pytest.raises(ValueError):
        obs.collective_seconds(1.0, 2, 1.0, op="gossip")


def test_step_timer_compile_steady_split():
    import time
    timer = obs.StepTimer(tokens_per_step=100, flops_per_token=1e6,
                          peak_flops=1e12)
    for i in range(4):
        with timer.step():
            time.sleep(0.03 if i == 0 else 0.005)
        with timer.phase("data"):
            time.sleep(0.001)
    rep = timer.report()
    assert rep["compile_s"] >= 0.03
    assert rep["steady_steps"] == 3
    assert 0 < rep["step_ms"]["min"] <= rep["step_ms"]["avg"] \
        <= rep["step_ms"]["max"] < 30.0
    assert rep["phases_ms"]["data"]["count"] == 4
    assert rep["tokens_per_sec"] > 0 and rep["mfu_pct"] > 0
    timer.set_comms_fraction(0.25)
    assert timer.report()["comms_fraction"] == 0.25


def test_step_timer_comms_fraction_from_plan():
    import time
    from paddle_tpu.distributed.comm_overlap.bucketing import \
        build_bucket_plan
    plan = build_bucket_plan(
        [jax.ShapeDtypeStruct((1024,), jnp.float32)], 0.0)
    timer = obs.StepTimer()
    with timer.step():
        pass
    with timer.step():
        time.sleep(0.01)
    frac = timer.comms_fraction_from_plan(plan, axis_size=8,
                                          bandwidth_gbs=1e-3)
    assert frac is not None and 0 < frac <= 1.0
    assert timer.report()["comms_fraction_source"] == "plan_estimate"


# ---------------------------------------------------------------------------
# events / trace / prometheus
# ---------------------------------------------------------------------------
def test_event_log_jsonl_schema_and_span(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    from paddle_tpu.profiler.utils import collector
    with obs.EventLog(path) as log:
        log.emit("hello", a=1, b="x", arr=jnp.float32(2.5))
        collector.enabled = True
        with log.span("phase1"):
            pass
        spans = collector.drain()
        collector.enabled = False
    lines = [json.loads(l) for l in open(path)]
    assert [e["event"] for e in lines] == ["hello", "span_begin",
                                          "span_end"]
    assert lines[0]["a"] == 1 and lines[0]["arr"] == 2.5
    assert "ts" in lines[0] and "pid" in lines[0]
    assert lines[2]["duration_s"] >= 0
    # the span also landed in the profiler's collector (unified traces)
    assert [s.name for s in spans] == ["phase1"]


def test_global_event_log_binds_to_flag(tmp_path):
    path = str(tmp_path / "global.jsonl")
    paddle.set_flags({"FLAGS_telemetry_jsonl": path})
    try:
        log = obs.get_event_log()
        assert log is not None and log.path == path
        log.emit("flag_bound")
        assert obs.get_event_log() is log  # cached while flag unchanged
    finally:
        paddle.set_flags({"FLAGS_telemetry_jsonl": ""})
        obs.set_event_log(None)
    assert json.loads(open(path).readline())["event"] == "flag_bound"
    assert obs.get_event_log() is None


def test_write_chrome_trace(tmp_path):
    with obs.capture_spans() as cap:
        with obs.span("alpha"):
            pass
    path = obs.write_chrome_trace(str(tmp_path / "t.json"), cap.events,
                                  extra=[{"name": "inst", "ph": "i",
                                          "ts": 0}])
    data = json.load(open(path))
    names = [e["name"] for e in data["traceEvents"]]
    assert "alpha" in names and "inst" in names


def test_prom_registry_render_and_types():
    reg = obs.PromRegistry(namespace="t")
    reg.counter_inc("hits", 2, help="hit count")
    reg.gauge_set("depth", 3.5)
    reg.gauge_max("peak", 1.0)
    reg.gauge_max("peak", 0.5)  # keeps max
    reg.summary_observe("lat", 0.25)
    reg.summary_observe("lat", 0.75)
    txt = reg.render()
    assert "# TYPE t_hits counter" in txt and "t_hits 2" in txt
    assert "t_depth 3.5" in txt
    assert "t_peak 1" in txt
    assert "t_lat_sum 1" in txt and "t_lat_count 2" in txt
    assert reg.get("lat") == pytest.approx(0.5)
    assert reg.get("t_depth") == 3.5 and reg.get("missing") is None
    with pytest.raises(ValueError):
        reg.counter_inc("depth")  # type clash


# ---------------------------------------------------------------------------
# serving scrape
# ---------------------------------------------------------------------------
def test_serving_prometheus_scrape_after_request(tmp_path):
    """Acceptance gate: after a request completes the ServingEngine serves
    a Prometheus scrape with non-zero TTFT and pool utilization (peak),
    and logs admits/completions to the JSONL event log."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import gpt as G
    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=64, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    path = str(tmp_path / "serve.jsonl")
    prev = obs.set_event_log(obs.EventLog(path))
    try:
        eng = ServingEngine(params, cfg, max_batch=2, num_blocks=32,
                            chunk=8, decode_burst=4)
        eng.add_request(np.arange(5, dtype=np.int32), 6)
        out = eng.run()
        assert len(out[0]) == 6
    finally:
        log = obs.set_event_log(prev)
        log.close()

    reg = eng.prom
    assert reg.get("ttft_seconds") > 0
    assert reg.get("kv_pool_utilization_peak") > 0
    assert reg.get("tokens_total") == 6
    assert reg.get("requests_completed_total") == 1
    assert reg.get("tokens_per_sec") > 0

    srv = eng.serve_metrics(port=0)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10
        ).read().decode()
    finally:
        srv.stop()
        eng._metrics_server = None
    assert "paddle_tpu_serving_ttft_seconds_sum" in body
    assert "paddle_tpu_serving_kv_pool_utilization_peak" in body

    kinds = [json.loads(l)["event"] for l in open(path)]
    assert "serving_admit" in kinds and "serving_complete" in kinds


# ---------------------------------------------------------------------------
# resilience events + fit report
# ---------------------------------------------------------------------------
def test_resilient_runner_logs_lifecycle_events(tmp_path):
    from paddle_tpu.distributed.resilience import run_resilient
    path = str(tmp_path / "res.jsonl")
    paddle.set_flags({"FLAGS_telemetry_jsonl": path})
    try:
        def step_fn(state, i):
            return {"x": state["x"] + 1}, 0.5

        state, info = run_resilient(step_fn, {"x": np.zeros((2,))},
                                    steps=5, ckpt_dir=str(tmp_path / "ck"),
                                    ckpt_every=2)
    finally:
        paddle.set_flags({"FLAGS_telemetry_jsonl": ""})
        obs.set_event_log(None)
    assert info["completed_steps"] == 5
    events = [json.loads(l) for l in open(path)]
    kinds = [e["event"] for e in events]
    assert kinds[0] == "resilience_run_start"
    assert kinds[-1] == "resilience_run_end"
    commits = [e for e in events if e["event"] == "resilience_commit"]
    assert [c["step"] for c in commits] == [2, 4, 5]


def test_model_fit_telemetry_report():
    from paddle_tpu import nn
    from paddle_tpu.io import TensorDataset
    rng = np.random.RandomState(0)
    X = rng.randn(32, 8).astype(np.float32)
    y = (X.sum(1) > 0).astype(np.int64)
    ds = TensorDataset([X, y])
    net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 2))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.Adam(0.01,
                                        parameters=net.parameters()),
                  nn.CrossEntropyLoss())
    paddle.set_flags({"FLAGS_telemetry": True})
    try:
        model.fit(ds, batch_size=16, epochs=1, verbose=0, shuffle=False)
    finally:
        paddle.set_flags({"FLAGS_telemetry": False})
    rep = model.last_fit_telemetry
    assert rep["compile_s"] > 0
    assert rep["steady_steps"] == 1  # 2 batches: 1 compile + 1 steady
    assert rep["phases_ms"]["data"]["count"] >= 1


# ---------------------------------------------------------------------------
# the one peak table (PR 23): unknown TPU kinds are errors, not defaults
# ---------------------------------------------------------------------------
class _FakeDev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_peak_table_known_kinds():
    from paddle_tpu.distributed.auto_tuner import planner as PL
    from paddle_tpu.observability import flops
    v5e = [_FakeDev("tpu", "TPU v5 lite")]
    assert flops.peak_flops(v5e) == flops.CHIP_PEAKS["TPU v5 lite"][1]
    assert PL.profile_for(v5e).name == "tpu-v5e"
    # the planner's profiles read the same table — no second copy
    for name, prof in PL.KNOWN_PROFILES.items():
        want = (flops.CPU_NOMINAL_PEAK if name == "cpu"
                else flops.PROFILE_PEAKS[name])
        assert prof.peak_flops == want, name
    assert flops.peak_flops([_FakeDev("cpu", "cpu")]) == \
        flops.CPU_NOMINAL_PEAK


@pytest.mark.parametrize("dev", [_FakeDev("tpu", "TPU v9 mega"),
                                 _FakeDev("tpu", ""),
                                 _FakeDev("gpu", "H100")],
                         ids=["unknown-tpu", "blank-kind", "other-platform"])
def test_unknown_device_kind_raises(dev):
    from paddle_tpu.distributed.auto_tuner import planner as PL
    from paddle_tpu.observability import flops
    with pytest.raises(ValueError, match="CHIP_PEAKS"):
        flops.peak_flops([dev])
    with pytest.raises(ValueError, match="CHIP_PEAKS"):
        PL.profile_for([dev])
