"""Multi-replica serving router (ISSUE 16): health-driven least-loaded
dispatch, journaled failover with exactly-once delivery and bitwise
greedy outputs, quarantine + doubling-backoff probes, drain-respawn on
the same journal, the fleet /metrics + /healthz front door, the new
fault sites (router/dispatch, replica/spawn, replica/heartbeat), the
journal fsync policy and the router.json flight-recorder section."""

import json
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.inference.router import (ReplicaSet, Router,
                                         router_failover_check,
                                         router_spawn_check)
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import gpt as G
from paddle_tpu.models.generation import gpt_generate
from paddle_tpu.observability import EventLog, set_event_log

CFG = G.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                  max_seq_len=128, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return G.init_hybrid_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    paddle.set_flags({"FLAGS_fault_inject": ""})


def golden(params, prompt, n):
    out = gpt_generate(params, CFG, jnp.asarray(prompt, jnp.int32)[None], n)
    return np.asarray(out)[0, len(prompt):].tolist()


def mk_factory(params, **kw):
    base = dict(max_batch=2, block_size=8, num_blocks=24,
                max_blocks_per_seq=8, chunk=8, decode_burst=2,
                adaptive_mix=False)
    base.update(kw)
    return lambda: ServingEngine(params, CFG, **base)


def reqs(n_req=4, seed=0):
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, 97, (k,)) for k in (7, 5, 6, 8)[:n_req]]
    news = (4, 5, 3, 4)[:n_req]
    return prompts, news


def drive(router, max_steps=500):
    for _ in range(max_steps):
        if not router.has_work():
            break
        router.step()
    return router


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
def test_least_loaded_dispatch_splits_fleet_and_stays_bitwise(params):
    """Placement alternates across equally-loaded replicas, and the
    fleet's greedy outputs are bitwise-identical to gpt_generate —
    placement-independent by construction."""
    prompts, news = reqs()
    rs = ReplicaSet.in_process(mk_factory(params), n=2)
    router = Router(rs)
    lids = [router.submit(p, n) for p, n in zip(prompts, news)]
    router.step()  # one dispatch round
    owners = [router.owner[lid] for lid in lids]
    assert owners == [0, 1, 0, 1], owners
    results, info = router.run()
    assert all(s == "done" for s in info["statuses"].values()), info
    for lid, (p, n) in enumerate(zip(prompts, news)):
        assert results[lid] == golden(params, p, n), lid
    assert router.failovers == 0


def test_router_queue_max_sheds_at_front_door(params, tmp_path):
    """Fleet-level backpressure: arrivals past router_queue_max are shed
    LOUDLY (status, reason-tagged event, counter) at submit."""
    log_path = str(tmp_path / "ev.jsonl")
    set_event_log(EventLog(log_path))
    try:
        prompts, news = reqs(3)
        rs = ReplicaSet.in_process(mk_factory(params), n=1)
        router = Router(rs, queue_max=2)
        lids = [router.submit(p, n) for p, n in zip(prompts, news)]
        assert router.statuses[lids[2]] == "shed"
        assert router.sheds == 1
        results, info = router.run()
        assert info["statuses"][lids[0]] == "done"
        assert info["statuses"][lids[1]] == "done"
        assert results[lids[2]] == []
    finally:
        set_event_log(None)
    evs = [json.loads(ln) for ln in open(log_path) if ln.strip()]
    shed = [e for e in evs if e.get("event") == "router_shed"]
    assert len(shed) == 1 and shed[0]["reason"] == "router_queue_full"
    assert shed[0]["role"] == "router"


def test_replica_cap_bounds_per_replica_queue(params):
    """replica_cap is the per-replica bound: dispatch never assigns a
    replica more in-flight work than the cap; the excess waits at the
    (bounded) fleet door until capacity frees up."""
    prompts, news = reqs()
    rs = ReplicaSet.in_process(mk_factory(params), n=2)
    router = Router(rs, replica_cap=1)
    for p, n in zip(prompts, news):
        router.submit(p, n)
    router.step()
    assert max(len(r.assigned) for r in rs) <= 1
    assert len(router.queue) == 2  # backpressure: held, not dropped
    _, info = router.run()
    assert all(s == "done" for s in info["statuses"].values()), info


# ---------------------------------------------------------------------------
# failover: the in-process acceptance
# ---------------------------------------------------------------------------
def test_failover_bitwise_exactly_once_healthz(params, tmp_path):
    """Acceptance (ISSUE 16, in-process leg): killing 1 of 2 replicas
    mid-generation completes every in-flight request on the survivor
    with exactly-once delivery and bitwise greedy outputs; fleet
    /healthz stays 200 throughout; exactly one router_failover event;
    full capacity (both replicas ready) after recovery."""
    out = router_failover_check(str(tmp_path))
    assert out["failovers"] == 1
    assert out["requeued"] >= 1
    assert out["tokens_pre_failover"] > 0  # the kill landed MID-stream
    assert out["healthz_polls"] > 0


def test_heartbeat_trigger_fails_over(params, tmp_path):
    """An armed replica/heartbeat trigger makes the router treat a
    perfectly healthy replica as wedged: its in-flight work replays on
    the survivor, outputs stay bitwise — liveness failover without
    anyone dying."""
    log_path = str(tmp_path / "ev.jsonl")
    set_event_log(EventLog(log_path))
    try:
        prompts, news = reqs()
        rs = ReplicaSet.in_process(mk_factory(params), n=2)
        router = Router(rs)
        lids = [router.submit(p, n) for p, n in zip(prompts, news)]
        faults.configure("replica/heartbeat")  # 1st check = replica 0
        results, info = router.run()
    finally:
        faults.configure("")
        set_event_log(None)
    assert router.failovers == 1
    assert all(s == "done" for s in info["statuses"].values()), info
    for lid, (p, n) in enumerate(zip(prompts, news)):
        assert results[lid] == golden(params, p, n), lid
    evs = [json.loads(ln) for ln in open(log_path) if ln.strip()]
    fo = [e for e in evs if e.get("event") == "router_failover"]
    assert len(fo) == 1 and fo[0]["reason"] == "heartbeat_timeout"
    assert fo[0]["replica"] == 0


# ---------------------------------------------------------------------------
# quarantine + probes
# ---------------------------------------------------------------------------
def test_consecutive_dispatch_failures_quarantine_then_probe(params,
                                                             tmp_path):
    """router/dispatch failing every attempt quarantines the replica at
    max_failures; after the backoff a probe respawns it and the held
    queue drains — nothing is lost across the quarantine window."""
    log_path = str(tmp_path / "ev.jsonl")
    set_event_log(EventLog(log_path))
    try:
        prompts, news = reqs(1)
        rs = ReplicaSet.in_process(mk_factory(params), n=1)
        router = Router(rs, max_failures=2, backoff_s=0.05)
        lid = router.submit(prompts[0], news[0])
        faults.configure("router/dispatch:p1.0")
        router.step()
        assert rs[0].state == "quarantined"
        assert router.statuses[lid] == "pending"  # held, not dropped
        faults.configure("")
        deadline = time.monotonic() + 30.0
        while router.has_work() and time.monotonic() < deadline:
            router.step()
            time.sleep(0.01)
    finally:
        faults.configure("")
        set_event_log(None)
    assert router.statuses[lid] == "done"
    assert rs[0].state == "ready"
    assert router.delivered[lid] == golden(params, prompts[0], news[0])
    evs = [json.loads(ln) for ln in open(log_path) if ln.strip()]
    kinds = [e["event"] for e in evs]
    assert kinds.count("router_dispatch_failed") >= 2
    assert kinds.count("router_quarantine") == 1
    probes = [e for e in evs if e.get("event") == "router_probe"]
    assert any(e["ok"] for e in probes)


def test_spawn_fault_quarantines_with_doubling_backoff(params):
    """replica/spawn failing at start quarantines that replica
    immediately (it never came up); the fleet serves from the survivor
    meanwhile, and a later successful probe restores full capacity."""
    faults.configure("replica/spawn")  # 1st spawn = replica 0's
    rs = ReplicaSet.in_process(mk_factory(params), n=2)
    router = Router(rs, backoff_s=0.05)
    faults.configure("")
    assert rs[0].state == "quarantined"
    assert rs[1].state == "ready"
    assert router.fleet_health() == "ready"  # one survivor suffices
    prompts, news = reqs(2)
    lids = [router.submit(p, n) for p, n in zip(prompts, news)]
    deadline = time.monotonic() + 30.0
    while ((router.has_work() or rs[0].state != "ready")
           and time.monotonic() < deadline):
        router.step()
        time.sleep(0.01)
    assert all(router.statuses[lid] == "done" for lid in lids)
    assert rs.states() == ["ready", "ready"]  # full capacity recovered
    assert rs[0].respawns >= 1


def test_failed_probe_doubles_backoff(params):
    """Every failed quarantine probe doubles the next backoff — the
    router never hot-loops respawning a replica that cannot come up."""
    faults.configure("replica/spawn:p1.0")  # EVERY spawn fails
    try:
        rs = ReplicaSet.in_process(mk_factory(params), n=1)
        router = Router(rs, backoff_s=0.01)
        assert rs[0].state == "quarantined"
        backoffs = [rs[0].backoff_s]
        deadline = time.monotonic() + 10.0
        while len(backoffs) < 3 and time.monotonic() < deadline:
            router.step()
            if rs[0].backoff_s != backoffs[-1]:
                backoffs.append(rs[0].backoff_s)
            time.sleep(0.005)
        assert len(backoffs) >= 3, backoffs
        assert backoffs[1] == pytest.approx(backoffs[0] * 2)
        assert backoffs[2] == pytest.approx(backoffs[1] * 2)
    finally:
        faults.configure("")


# ---------------------------------------------------------------------------
# front door: /metrics + /healthz + flight recorder
# ---------------------------------------------------------------------------
def test_fleet_metrics_and_healthz_aggregate(params):
    """One stable front door: router gauges (replica_state_<i>,
    per-replica depth, failover counters) ride /metrics; /healthz is 200
    iff >=1 replica is ready and 503 once the whole fleet is out."""
    rs = ReplicaSet.in_process(mk_factory(params), n=2)
    router = Router(rs)
    server = router.serve_metrics(port=0)
    try:
        prompts, news = reqs(2)
        for p, n in zip(prompts, news):
            router.submit(p, n)
        drive(router)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=5
        ).read().decode()
        assert "paddle_tpu_router_replica_state_0 1" in body
        assert "paddle_tpu_router_replica_state_1 1" in body
        assert "paddle_tpu_router_replicas_ready 2" in body
        assert "paddle_tpu_router_router_dispatches_total" in body
        code = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/healthz", timeout=5).getcode()
        assert code == 200
        # the WHOLE fleet out -> the front door must go 503
        for rep in rs:
            router._quarantine(rep, "test")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz", timeout=5)
        assert ei.value.code == 503
    finally:
        server.stop()


def test_flight_recorder_bundle_has_router_json(params, tmp_path):
    """A fleet incident leaves forensics: any flight-recorder dump made
    while a router lives carries router.json with per-replica lifecycle
    + per-request watermarks."""
    import gc
    import os
    from paddle_tpu.observability.flight_recorder import (FlightRecorder,
                                                          maybe_dump,
                                                          set_flight_recorder)
    rec = FlightRecorder(str(tmp_path), min_interval_s=0.0)
    prev = set_flight_recorder(rec)
    try:
        rs = ReplicaSet.in_process(mk_factory(params), n=1)
        router = Router(rs)
        prompts, news = reqs(1)
        router.submit(prompts[0], news[0])
        router.step()
        gc.collect()  # purge dead routers (ref cycles) from the registry
        bundle = maybe_dump("router_test")
        assert bundle is not None
        with open(os.path.join(bundle, "router.json")) as f:
            rj = json.load(f)
        (snap,) = rj.values()
        assert snap["fleet_health"] == "ready"
        assert snap["replicas"][0]["state"] == "ready"
        assert snap["requests"]["0"]["status"] in ("running", "done")
    finally:
        set_flight_recorder(prev)


# ---------------------------------------------------------------------------
# cross-process acceptance (the spawn leg)
# ---------------------------------------------------------------------------
def test_spawned_fleet_kill_failover_bitwise(params, tmp_path):
    """Acceptance (ISSUE 16 satellite, cross-process): replica 0
    hard-killed by serving/step:3:kill (os._exit in the worker) — every
    request completes on replica 1 with exactly-once delivery (pre-kill
    journal + post-failover journal concatenate to golden), bitwise
    greedy outputs, zero leaked pages on the survivor, /healthz 200
    throughout, replica 0 respawned onto the same journal."""
    out = router_spawn_check(str(tmp_path))
    assert out["tokens_pre_kill"] > 0
    assert out["tokens_post_failover"] > 0
    assert out["failovers"] == 1
    assert out["survivor_free_blocks"] == out["survivor_pool_blocks"]


# ---------------------------------------------------------------------------
# one process per chip (PR 23): CPU-pinned children are refused on a TPU
# parent instead of quietly serving from the CPU
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("path", ["spawned_replica", "kill_replay"])
def test_cpu_children_refused_when_parent_is_on_tpu(path, tmp_path,
                                                    monkeypatch):
    from paddle_tpu.inference import resilient
    from paddle_tpu.inference.router import SpawnedReplica
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="InProcessReplica"):
        if path == "spawned_replica":
            SpawnedReplica(0, str(tmp_path)).start()
        else:
            resilient.kill_replay_check(str(tmp_path))
    # nothing was started
    assert not list(tmp_path.rglob("out.*.log"))


def test_cpu_parent_keeps_its_cpu_children(monkeypatch):
    """The CPU pin stays when the parent itself runs on the CPU — the
    tier-1 spawn tests depend on it."""
    from paddle_tpu.inference import resilient
    assert jax.default_backend() == "cpu"
    resilient.refuse_cpu_children_on_tpu("test")  # no raise
