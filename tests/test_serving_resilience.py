"""Serving resilience (ISSUE 13): deadlines + cancellation, admission
control + load shedding, preempt-and-requeue, the crash-recovering
``run_serving_resilient`` replay driver (exactly-once delivery, retry
budgets, nonfinite circuit breaker, SIGTERM drain), fault/forensics
wiring (serving fault sites, flight-recorder serving snapshot, /healthz)
and the flags-off inertness contract."""

import json
import os
import signal
import time
import urllib.request
import urllib.error

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.resilience import FaultInjected, faults
from paddle_tpu.flags import flag, set_flags
from paddle_tpu.inference.resilient import (ServingJournal,
                                            kill_replay_check,
                                            run_serving_resilient)
from paddle_tpu.inference.serving import (NonFiniteSampleError,
                                          ServingEngine)
from paddle_tpu.models import gpt as G
from paddle_tpu.models.generation import gpt_generate

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


def mk(params, **kw):
    base = dict(max_batch=2, block_size=8, num_blocks=24,
                max_blocks_per_seq=8, chunk=8, adaptive_mix=False)
    base.update(kw)
    return ServingEngine(params, CFG, **base)


def drive(eng):
    """Step to completion, returning {rid: Request} for every terminal
    request step() reported."""
    reported = {}
    for _ in range(10000):
        if not eng.has_work():
            break
        for r in eng.step():
            reported[r.rid] = r
    return reported


# ---------------------------------------------------------------------------
# deadlines + cancellation
# ---------------------------------------------------------------------------
def test_deadline_sheds_stale_queued(params):
    """An expired deadline sheds a QUEUED request before it ever runs;
    the sibling is untouched and completes its golden output."""
    rng = np.random.RandomState(0)
    p1, p2 = rng.randint(0, 97, (9,)), rng.randint(0, 97, (8,))
    eng = mk(params, max_batch=1)
    r1 = eng.add_request(p1, 5)
    r2 = eng.add_request(p2, 4, deadline_s=0.0)  # expired on arrival
    rep = drive(eng)
    assert rep[r2].status == "shed" and rep[r2].error == "deadline"
    assert rep[r2].output == []
    assert rep[r1].status == "ok"
    assert rep[r1].output == golden(params, p1, 5)
    assert eng.prom.get("requests_shed_total") == 1.0


def test_deadline_cancels_inflight_and_frees_pages(params):
    """Deadline expiry MID-GENERATION cancels the request: partial output
    kept, pages freed and re-admittable (no leak)."""
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, 97, (8,))
    eng = mk(params, max_batch=1)
    free0 = len(eng.free_blocks)
    rid = eng.add_request(prompt, 40, deadline_s=3600.0)
    # run until it has emitted at least one token, then force expiry
    # (deterministic: no wall-clock race)
    reported = {}
    for _ in range(100):
        if eng.slots[0] is not None and eng.slots[0].output:
            break
        for r in eng.step():
            reported[r.rid] = r
    assert eng.slots[0] is not None and eng.slots[0].output
    emitted = len(eng.slots[0].output)
    eng.slots[0].deadline = time.perf_counter() - 1.0
    for r in eng.step():
        reported[r.rid] = r
    r = reported[rid]
    assert r.status == "cancelled" and r.error == "deadline"
    assert len(r.output) >= emitted > 0
    assert r.output == golden(params, prompt, 40)[:len(r.output)]
    assert len(eng.free_blocks) == free0  # pages accounted
    assert eng.prom.get("requests_cancelled_total") == 1.0
    assert not eng.has_work()


def test_earliest_deadline_first_admission(params):
    """With deadlines present the queue admits EDF: a later-submitted,
    tighter-deadline request starts (and finishes) first."""
    rng = np.random.RandomState(2)
    pa, pb = rng.randint(0, 97, (8,)), rng.randint(0, 97, (8,))
    eng = mk(params, max_batch=1)
    ra = eng.add_request(pa, 4, deadline_s=3600.0)
    rb = eng.add_request(pb, 4, deadline_s=60.0)  # tighter, submitted later
    order = []
    for _ in range(1000):
        if not eng.has_work():
            break
        order += [r.rid for r in eng.step() if r.status == "ok"]
    assert order == [rb, ra]


def test_no_deadlines_keeps_fifo_admission(params):
    rng = np.random.RandomState(3)
    pa, pb = rng.randint(0, 97, (8,)), rng.randint(0, 97, (8,))
    eng = mk(params, max_batch=1)
    ra = eng.add_request(pa, 4)
    rb = eng.add_request(pb, 4)
    order = []
    for _ in range(1000):
        if not eng.has_work():
            break
        order += [r.rid for r in eng.step() if r.status == "ok"]
    assert order == [ra, rb]


# ---------------------------------------------------------------------------
# admission control + load shedding
# ---------------------------------------------------------------------------
def test_queue_max_sheds_at_submit(params):
    eng = mk(params, max_batch=1, queue_max=1)
    rng = np.random.RandomState(4)
    p = rng.randint(0, 97, (8,))
    r1 = eng.add_request(p, 4)          # queued (slot taken at next step)
    r2 = eng.add_request(p, 4)          # queue full -> shed at submit
    res = eng.run()
    assert res.statuses[r2] == "shed"
    assert res[r2] == []
    assert res.statuses[r1] == "ok"
    assert eng.prom.get("requests_shed_total") == 1.0


def test_overload_shed_keeps_slot_horizon(params):
    """With the TTFT window p95 above the SLO headroom, the queue is
    trimmed to the NEWEST max_batch arrivals — the aged head has already
    burned its latency budget; fresh admissions are what keep admitted
    p99 inside the SLO."""
    eng = mk(params, max_batch=2, shed=True, ttft_slo_s=0.01)
    # prime the recent TTFT window above the SLO (the policy's input is
    # the engine's own prom registry)
    for _ in range(8):
        eng.prom.summary_observe("ttft_seconds", 1.0, window=16)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 97, (8,)) for _ in range(6)]
    rids = [eng.add_request(p, 3) for p in prompts]  # 6 > 2*max_batch
    rep = drive(eng)
    statuses = [rep[r].status for r in rids]
    assert statuses[:4] == ["shed"] * 4        # aged head shed
    assert statuses[4:] == ["ok", "ok"]        # newest arrivals admitted
    assert all(rep[r].error == "overload" for r in rids[:4])
    assert eng.prom.get("requests_shed_total") == 4.0


def test_no_shed_below_slo(params):
    """p95 under the SLO: the same queue drains normally (shed policy is
    driven by the measured window, not queue depth alone)."""
    eng = mk(params, max_batch=2, shed=True, ttft_slo_s=10.0)
    for _ in range(8):
        eng.prom.summary_observe("ttft_seconds", 0.001, window=16)
    rng = np.random.RandomState(6)
    rids = [eng.add_request(rng.randint(0, 97, (8,)), 3) for _ in range(6)]
    rep = drive(eng)
    assert all(rep[r].status == "ok" for r in rids)


# ---------------------------------------------------------------------------
# preempt-and-requeue
# ---------------------------------------------------------------------------
def test_preempt_decode_victim_and_requeue(params):
    """Pool exhaustion with an urgent head: the decode victim is evicted
    (pages freed), re-enqueued with its emitted prefix, and BOTH requests
    finish with greedy outputs identical to their goldens (preempted
    recompute is token-identical). No pages leak."""
    rng = np.random.RandomState(7)
    pv = rng.randint(0, 97, (8,))       # victim: long decode, 4 blocks
    ph = rng.randint(0, 97, (8,))       # head: also needs 4 blocks
    eng = mk(params, max_batch=2, num_blocks=7,
             preempt=True, preempt_wait_steps=1)
    free0 = len(eng.free_blocks)        # 6 usable
    rv = eng.add_request(pv, 24)        # (8+24)/8 = 4 blocks
    rh = eng.add_request(ph, 24)        # 4 > remaining 2 -> blocked
    rep = drive(eng)
    assert rep[rv].status == "ok" and rep[rh].status == "ok"
    assert rep[rv].output == golden(params, pv, 24)
    assert rep[rh].output == golden(params, ph, 24)
    assert rep[rv].preemptions >= 1     # the victim really was evicted
    assert eng.prom.get("requests_preempted_total") >= 1.0
    assert len(eng.free_blocks) == free0


def test_preempt_off_head_waits(params):
    """Same pressure with preempt off: the head waits (no starvation,
    no eviction) and both still finish."""
    rng = np.random.RandomState(8)
    pv, ph = rng.randint(0, 97, (8,)), rng.randint(0, 97, (8,))
    eng = mk(params, max_batch=2, num_blocks=7, preempt=False)
    rv = eng.add_request(pv, 24)
    rh = eng.add_request(ph, 24)
    rep = drive(eng)
    assert rep[rv].preemptions == 0
    assert rep[rv].output == golden(params, pv, 24)
    assert rep[rh].output == golden(params, ph, 24)
    assert eng.prom.get("requests_preempted_total") is None


# ---------------------------------------------------------------------------
# satellite hardening: callback errors, leftover reporting
# ---------------------------------------------------------------------------
def test_on_token_callback_error_fails_only_that_request(params):
    rng = np.random.RandomState(9)
    p1, p2 = rng.randint(0, 97, (9,)), rng.randint(0, 97, (8,))

    def boom(rid, tok):
        raise RuntimeError("user callback bug")

    eng = mk(params)
    free0 = len(eng.free_blocks)
    r1 = eng.add_request(p1, 6, on_token=boom)
    r2 = eng.add_request(p2, 5)
    rep = drive(eng)
    assert rep[r1].status == "failed"
    assert "callback" in rep[r1].error
    assert rep[r2].status == "ok"
    assert rep[r2].output == golden(params, p2, 5)  # sibling unharmed
    assert len(eng.free_blocks) == free0            # poisoned pages freed
    assert eng.prom.get("callback_errors_total") == 1.0


def test_run_budget_exhaustion_reports_leftover(params):
    rng = np.random.RandomState(10)
    p = rng.randint(0, 97, (8,))
    eng = mk(params, max_batch=1)
    r1 = eng.add_request(p, 40)
    res = eng.run(max_steps=1)
    assert res.leftover == [r1]                     # loud, not lost
    assert eng.prom.get("run_steps_exhausted_total") == 1.0
    res2 = eng.run()                                # finishing run
    assert res2[r1] == golden(params, p, 40)
    assert res2.leftover == []


# ---------------------------------------------------------------------------
# fault sites + forensics
# ---------------------------------------------------------------------------
def test_serving_fault_sites_fire(params):
    rng = np.random.RandomState(11)
    eng = mk(params)
    eng.add_request(rng.randint(0, 97, (8,)), 3)
    paddle.set_flags({"FLAGS_fault_inject": "serving/step:1"})
    with pytest.raises(FaultInjected):
        eng.step()
    paddle.set_flags({"FLAGS_fault_inject": "serving/dispatch:1"})
    with pytest.raises(FaultInjected):
        eng.step()
    paddle.set_flags({"FLAGS_fault_inject": ""})


def test_pool_exhausted_site_counts_blocked_admissions(params):
    rng = np.random.RandomState(12)
    eng = mk(params, max_batch=2, num_blocks=7)
    eng.add_request(rng.randint(0, 97, (8,)), 24)   # 4 of 6 usable
    eng.add_request(rng.randint(0, 97, (8,)), 24)   # blocked
    # arm an unrelated site so the (otherwise disarmed) registry counts
    paddle.set_flags({"FLAGS_fault_inject": "never/fires:999"})
    eng.step()
    assert faults.hits().get("serving/pool_exhausted", 0) >= 1
    paddle.set_flags({"FLAGS_fault_inject": ""})


def test_flight_recorder_bundle_has_serving_snapshot(params, tmp_path):
    from paddle_tpu.observability.flight_recorder import (FlightRecorder,
                                                          maybe_dump,
                                                          set_flight_recorder)
    rng = np.random.RandomState(13)
    eng = mk(params, max_batch=1)
    eng.add_request(rng.randint(0, 97, (8,)), 24)   # stays in-flight
    eng.add_request(rng.randint(0, 97, (8,)), 8)    # stays queued
    eng.step()
    rec = FlightRecorder(str(tmp_path))
    prev = set_flight_recorder(rec)
    import gc
    gc.collect()   # purge dead engines (ref cycles) from the registry
    try:
        bundle = maybe_dump("serving_test")
    finally:
        set_flight_recorder(prev)
    assert bundle is not None
    snap = json.load(open(os.path.join(bundle, "serving.json")))
    (eng_snap,) = snap.values()
    assert eng_snap["health"] == "ready"
    assert eng_snap["slots"][0]["status"] == "ok"
    assert len(eng_snap["queue"]) == 1
    assert 0.0 < eng_snap["pool_utilization"] <= 1.0


def test_healthz_rides_metrics_server(params):
    rng = np.random.RandomState(14)
    eng = mk(params, max_batch=1)
    srv = eng.serve_metrics(port=0)
    try:
        def get(path):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}{path}") as r:
                    return r.status, r.read().decode()
            except urllib.error.HTTPError as e:
                return e.code, e.read().decode()
        code, body = get("/healthz")
        assert code == 503 and json.loads(body)["state"] == "loading"
        eng.add_request(rng.randint(0, 97, (8,)), 2)
        eng.run()
        code, body = get("/healthz")
        assert code == 200 and json.loads(body)["state"] == "ready"
        eng.drain()
        code, body = get("/healthz")
        assert code == 503 and json.loads(body)["state"] == "draining"
        code, body = get("/metrics")                # metrics unaffected
        assert code == 200 and "requests_total" in body
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# run_serving_resilient: rebuild + replay
# ---------------------------------------------------------------------------
def _workload(rng_seed=0, n=4):
    rng = np.random.RandomState(rng_seed)
    prompts = [rng.randint(0, 97, (k,)) for k in (9, 13, 6, 11)[:n]]
    news = [6, 4, 7, 5][:n]
    return prompts, news


def test_rebuild_and_replay_bitwise_exactly_once(params):
    """An injected step failure mid-workload: the driver rebuilds the
    engine, replays prompt+prefix, and greedy outputs are BITWISE equal
    to the uninterrupted run with every on_token delivered exactly once
    and zero leaked pages."""
    prompts, news = _workload()
    goldens = [golden(params, p, n) for p, n in zip(prompts, news)]
    paddle.set_flags({"FLAGS_fault_inject": "serving/step:3"})
    seen = {i: [] for i in range(4)}
    reqs = [{"prompt": p, "max_new_tokens": n,
             "on_token": lambda lid, t: seen[lid].append(t)}
            for p, n in zip(prompts, news)]
    results, info = run_serving_resilient(
        lambda: mk(params), reqs, retry_backoff_s=0.001)
    paddle.set_flags({"FLAGS_fault_inject": ""})
    assert info["rebuilds"] == 1
    assert [results[i] for i in range(4)] == goldens
    assert all(seen[i] == goldens[i] for i in range(4))  # exactly-once
    assert all(s == "done" for s in info["statuses"].values())
    assert info["free_blocks"] == info["pool_blocks"]    # no page leak


def test_retry_budget_exhausts_to_failed(params):
    """An engine that fails every step: requests making no progress
    exhaust their retry budget and are FAILED (bounded rebuilds), not
    retried forever."""
    prompts, news = _workload(n=2)

    calls = {"n": 0}

    def make_bad():
        eng = mk(params)
        orig = eng.step

        def step():
            calls["n"] += 1
            raise RuntimeError("poisoned step")
        eng.step = step
        del orig
        return eng

    results, info = run_serving_resilient(
        make_bad, [{"prompt": p, "max_new_tokens": n}
                   for p, n in zip(prompts, news)],
        max_retries=1, retry_backoff_s=0.001)
    assert all(s == "failed" for s in info["statuses"].values())
    assert set(info["failed"]) == {0, 1}
    # failure 1 baselines progress, 2 charges, 3 exhausts — bounded
    assert info["rebuilds"] == 3


def test_nonfinite_circuit_breaker_fails_poisoned_request(params):
    """NonFiniteSampleError carries the poisoned rid: that request is
    failed IMMEDIATELY (no retry), its siblings replay to their goldens."""
    prompts, news = _workload(n=3)
    goldens = [golden(params, p, n) for p, n in zip(prompts, news)]
    poisoned = {"armed": True}

    def make_engine():
        eng = mk(params)
        orig = eng._check_tok

        def check(r, tok):
            if poisoned["armed"] and r.rid == 0:
                poisoned["armed"] = False  # only the FIRST engine's rid 0
                raise NonFiniteSampleError(r.rid, -1)
            return orig(r, tok)
        eng._check_tok = check
        return eng

    results, info = run_serving_resilient(
        make_engine, [{"prompt": p, "max_new_tokens": n}
                      for p, n in zip(prompts, news)],
        retry_backoff_s=0.001)
    assert info["statuses"][0] == "failed"
    assert 0 in info["failed"] and "out-of-range" in info["failed"][0]
    assert info["rebuilds"] == 1
    for lid in (1, 2):
        assert info["statuses"][lid] == "done"
        assert results[lid] == goldens[lid]


def test_sigterm_drain_finishes_inflight_requeues_queued(params, tmp_path):
    """SIGTERM mid-run: admission stops, the in-flight request finishes
    inside the grace window, the queued one is REQUEUED — and a successor
    driver pointed at the same journal completes it with delivery
    exactly-once across the two runs."""
    prompts, news = _workload(n=2)
    goldens = [golden(params, p, n) for p, n in zip(prompts, news)]
    jpath = str(tmp_path / "journal.jsonl")
    seen = {0: [], 1: []}

    fired = {"done": False}

    def on_token(lid, tok):
        seen[lid].append(tok)
        if not fired["done"]:
            fired["done"] = True
            os.kill(os.getpid(), signal.SIGTERM)  # the preemption notice

    reqs = [{"prompt": p, "max_new_tokens": n, "on_token": on_token}
            for p, n in zip(prompts, news)]
    results, info = run_serving_resilient(
        lambda: mk(params, max_batch=1), reqs, grace_s=30.0,
        journal_path=jpath)
    assert info["preempted"] is True
    assert info["statuses"][0] == "done"       # fit in the grace window
    assert results[0] == goldens[0]
    assert info["statuses"][1] == "requeued"   # never started; not lost
    assert results[1] == []

    # successor process (same journal): resumes ONLY the requeued work
    results2, info2 = run_serving_resilient(
        lambda: mk(params, max_batch=1), reqs, journal_path=jpath)
    assert info2["statuses"] == {0: "done", 1: "done"}
    assert results2[0] == goldens[0] and results2[1] == goldens[1]
    assert seen[0] == goldens[0] and seen[1] == goldens[1]  # exactly-once


def test_spawned_kill_and_replay_bitwise(params, tmp_path):
    """Acceptance (ISSUE 13): worker hard-killed by serving/step:4:kill
    (os._exit — a real crash), respawned onto the same journal; outputs
    bitwise-identical to the uninterrupted spawn, exactly-once delivery
    across the process boundary, zero leaked KV pages."""
    out = kill_replay_check(str(tmp_path))
    assert out["tokens_pre_kill"] > 0
    assert out["free_blocks"] == out["pool_blocks"]


def test_journal_tolerates_torn_tail(tmp_path):
    """A crash mid-flush leaves one partial final line: the loader must
    drop the torn tail instead of crashing every respawn at startup."""
    p = str(tmp_path / "j.jsonl")
    j = ServingJournal(p)
    j.append(0, 7)
    j.append(0, 9)
    j.close()
    with open(p, "a", encoding="utf-8") as f:
        f.write('{"lid": 0, "tok": 1')  # torn mid-record
    j2 = ServingJournal(p)
    assert j2.delivered == {0: [7, 9]}  # intact prefix, tear dropped
    j2.close()


def test_journal_fsync_cadence_counts_appends(tmp_path):
    """FLAGS_serving_journal_fsync=N fsyncs every N appends: the
    crash-window contract is 'at most N-1 clean records plus one torn
    tail can vanish on host power loss'. N=0 keeps the flush-only fast
    path (process-crash durable, host-crash best-effort)."""
    import paddle_tpu as paddle
    p = str(tmp_path / "j.jsonl")
    j = ServingJournal(p, fsync=2)
    assert j.fsync_every == 2
    for k in range(5):
        j.append(0, k)  # 5 appends -> sync at 2 and 4, 1 pending
    assert j._appends_since_sync == 1
    j.close()  # close() drains the pending tail through fsync
    assert ServingJournal(p).delivered == {0: [0, 1, 2, 3, 4]}
    # the flag is the default when no explicit fsync arg is given
    paddle.set_flags({"FLAGS_serving_journal_fsync": 7})
    try:
        assert ServingJournal(str(tmp_path / "k.jsonl")).fsync_every == 7
    finally:
        paddle.set_flags({"FLAGS_serving_journal_fsync": 0})
    assert ServingJournal(str(tmp_path / "l.jsonl")).fsync_every == 0


def test_journal_fsynced_tolerates_torn_tail(tmp_path):
    """Regression (ISSUE 16): even under the fsync policy a host crash
    can tear the record AFTER the last sync point — the loader keeps
    every durable record and drops only the tear, exactly as in the
    flush-only mode."""
    p = str(tmp_path / "j.jsonl")
    j = ServingJournal(p, fsync=1)
    j.stamp(0, 11.0)
    j.append(0, 7)
    j.append(0, 9)
    j.mark(1, "done")
    j.close()
    with open(p, "a", encoding="utf-8") as f:
        f.write('{"lid": 0, "tok": 1')  # torn mid-record past the sync
    j2 = ServingJournal(p, fsync=1)
    assert j2.delivered == {0: [7, 9]}
    assert j2.statuses == {1: "done"}
    assert j2.t0 == {0: 11.0}
    j2.close()


def test_overload_trim_keeps_most_urgent_with_deadlines(params):
    """With deadlines in the queue (which _admit keeps EDF-sorted), the
    overload trim keeps the EARLIEST-deadline requests — not the
    positional tail, which after the EDF sort would be the least urgent."""
    eng = mk(params, max_batch=2, shed=True, ttft_slo_s=0.01)
    for _ in range(8):
        eng.prom.summary_observe("ttft_seconds", 1.0, window=16)
    rng = np.random.RandomState(17)
    # submit with DESCENDING urgency reversed: latest submitted = most
    # urgent, so keep-newest and keep-most-urgent disagree positionally
    # only after the EDF sort
    rids = [eng.add_request(rng.randint(0, 97, (8,)), 3,
                            deadline_s=3600.0 - 100.0 * k)
            for k in range(6)]
    rep = drive(eng)
    statuses = {r: rep[r].status for r in rids}
    # most urgent = the two LAST submitted (tightest deadlines) survive
    assert statuses[rids[4]] == "ok" and statuses[rids[5]] == "ok"
    assert sum(1 for s in statuses.values() if s == "shed") == 4


def test_preempted_victim_dropped_from_queue_is_cancelled(params):
    """A preempted-and-requeued victim already delivered tokens: if it is
    then dropped from the queue (deadline/overload), it must report
    'cancelled' (ran, partial output kept) — never 'shed' (never-ran),
    or a consumer resubmitting shed work would double-deliver the
    prefix."""
    rng = np.random.RandomState(18)
    prompt = rng.randint(0, 97, (8,))
    eng = mk(params, max_batch=1)
    rid = eng.add_request(prompt, 40)
    while eng.slots[0] is None or not eng.slots[0].output:
        eng.step()
    r = eng.slots[0]
    eng._preempt(r)                    # requeued with a delivered prefix
    r.deadline = time.perf_counter() - 1.0
    (dropped,) = [x for x in eng.step() if x.rid == rid]
    assert dropped.status == "cancelled"
    assert dropped.output              # the prefix is preserved
    assert not eng.has_work()


def test_journal_roundtrip(tmp_path):
    p = str(tmp_path / "j.jsonl")
    j = ServingJournal(p)
    j.stamp(0, 123.0)
    j.append(0, 7)
    j.append(0, 9)
    j.mark(1, "failed")
    j.close()
    j2 = ServingJournal(p)
    assert j2.delivered == {0: [7, 9]}
    assert j2.statuses == {1: "failed"}
    assert j2.t0 == {0: 123.0}
    j2.close()


def test_sheds_visible_as_events_and_metrics(params, tmp_path):
    """Acceptance: sheds are COUNTED prom metrics + JSONL events (reason
    + rid), not silent drops."""
    from paddle_tpu.observability import EventLog, set_event_log
    log_path = str(tmp_path / "serving.jsonl")
    prev = set_event_log(EventLog(log_path))
    try:
        rng = np.random.RandomState(16)
        eng = mk(params, max_batch=1, queue_max=1)
        eng.add_request(rng.randint(0, 97, (8,)), 3)
        shed_rid = eng.add_request(rng.randint(0, 97, (8,)), 3)
        eng.run()
    finally:
        set_event_log(prev)
    recs = [json.loads(ln) for ln in open(log_path)]
    sheds = [r for r in recs if r["event"] == "serving_shed"]
    assert len(sheds) == 1
    assert sheds[0]["rid"] == shed_rid
    assert sheds[0]["reason"] == "queue_full"
    assert sheds[0]["role"] == "serving"
    assert eng.prom.get("requests_shed_total") == 1.0


def _pct(v, q):
    return round(float(np.percentile(v, q)), 3)


def _lat_stats(lat):
    return {"mean": round(float(np.mean(lat)), 3), "p50": _pct(lat, 50),
            "p95": _pct(lat, 95), "p99": _pct(lat, 99)}


def run_overload_comparison(params, cfg, mk, batch, *, n_req: int = 64,
                            load_factor: float = 2.0,
                            slo_factor: float = 3.0, seed: int = 0):
    """Driver of ``test_overload_shedding_preserves_admitted_slo``
    (ISSUE 13): offered load ~``load_factor``x the engine's measured
    capacity, shedding ON (bounded queue + SLO-driven shed) vs OFF —
    admitted-request TTFT percentiles, shed rate and goodput. The point
    the numbers make: without shedding EVERY request's
    TTFT grows with the backlog (p99 collapses), with shedding the engine
    sacrifices a counted fraction of arrivals so the ADMITTED requests
    keep meeting the SLO.

    Calibration: one closed wave of exactly ``batch`` requests (all slots
    busy, no queue) measures the per-wave service time T_req ->
    capacity ~ batch/T_req req/s, SLO = ``slo_factor`` x T_req. The shed
    engine runs the PURE SLO policy (queue_max=0 — no static bound): the
    TTFT window-p95 crossing the SLO headroom is what trims the queue,
    so the mechanism under test is the one doing the work."""
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, (int(rng.choice((8, 16))),))
               for _ in range(n_req)]
    news = rng.randint(8, 17, (n_req,)).tolist()

    def make_engine(**kw):
        return ServingEngine(params, cfg, max_batch=batch,
                             adaptive_mix=False, **mk, **kw)

    # calibrate: warm the programs, then time one full-batch closed wave
    eng = make_engine()
    for p, n in zip(prompts[:batch], news[:batch]):
        eng.add_request(p, n)
    eng.run()                                   # compile wave
    t0 = time.perf_counter()
    for p, n in zip(prompts[:batch], news[:batch]):
        eng.add_request(p, n)
    eng.run()
    t_req = max(time.perf_counter() - t0, 1e-6)
    slo_s = slo_factor * t_req
    interval = t_req / (load_factor * batch)    # 2x offered request rate

    def open_loop(**kw):
        eng = make_engine(**kw)
        for p, n in zip(prompts[:batch], news[:batch]):
            eng.add_request(p, n)
        eng.run()                               # fresh-engine compile wave
        reported = {}
        t0 = time.perf_counter()
        i = 0
        while i < n_req or eng.has_work():
            now = time.perf_counter() - t0
            while i < n_req and now >= i * interval:
                eng.add_request(prompts[i], news[i])
                i += 1
                now = time.perf_counter() - t0
            if eng.has_work():
                for r in eng.step():
                    reported[r.rid] = r
            elif i < n_req:
                time.sleep(max(i * interval - now, 0.0))
        wall = max(time.perf_counter() - t0, 1e-9)
        admitted = [r for r in reported.values() if r.status == "ok"]
        shed = [r for r in reported.values() if r.status == "shed"]
        ttfts = [r.ttft_s for r in admitted if r.ttft_s is not None]
        # SLO-goodput: tokens of requests that MET the TTFT SLO — the
        # number a latency-bound service actually sells. An unbounded
        # queue "completes everything" but past the SLO, which counts
        # for nothing here.
        in_slo = [r for r in admitted
                  if r.ttft_s is not None and r.ttft_s <= slo_s]
        out = {"admitted": len(admitted), "shed": len(shed),
               "shed_rate": round(len(shed) / max(len(reported), 1), 3),
               "goodput_tokens_per_sec": round(
                   sum(len(r.output) for r in admitted) / wall, 1),
               "slo_goodput_tokens_per_sec": round(
                   sum(len(r.output) for r in in_slo) / wall, 1),
               "requests_meeting_slo": len(in_slo),
               "wall_s": round(wall, 3)}
        if ttfts:
            out["ttft_s"] = _lat_stats(ttfts)
            out["p99_within_slo"] = bool(_pct(ttfts, 99) <= slo_s)
        return out

    shed_on = open_loop(shed=True, ttft_slo_s=slo_s)
    shed_off = open_loop()
    # t_req_s and ttft_slo_s are for the assertion messages
    return {"t_req_s": round(t_req, 3), "ttft_slo_s": round(slo_s, 3),
            "shed_on": shed_on, "shed_off": shed_off}


def test_overload_shedding_preserves_admitted_slo(params):
    """Acceptance (slow): at ~2x offered load the shedding engine keeps
    admitted-request p99 TTFT within its SLO while the no-shed baseline
    blows through it (the backlog grows with every arrival)."""
    mk_args = dict(block_size=16, num_blocks=192, max_blocks_per_seq=16,
                   chunk=16, decode_burst=16)
    out = run_overload_comparison(params, CFG, mk_args, batch=4,
                                  n_req=48)
    on, off = out["shed_on"], out["shed_off"]
    assert on["p99_within_slo"] is True, out
    assert off["p99_within_slo"] is False, out
    assert on["shed"] > 0 and off["shed"] == 0
    assert on["ttft_s"]["p99"] < off["ttft_s"]["p99"]
    # the number a latency-bound service sells: tokens delivered to
    # requests that MET the SLO
    assert (on["slo_goodput_tokens_per_sec"]
            > off["slo_goodput_tokens_per_sec"]), out


# ---------------------------------------------------------------------------
# flags-off inertness (the telemetry/mp_overlap pattern)
# ---------------------------------------------------------------------------
def test_resilience_flags_default_off():
    assert int(flag("serving_queue_max")) == 0
    assert bool(flag("serving_shed")) is False
    assert bool(flag("serving_preempt")) is False


def test_flags_off_engine_is_bitwise_inert(params):
    """The resilience layer is host-side scheduler state ONLY: an engine
    with the whole surface enabled (bounded queue, shed, preempt,
    deadlines in play) lowers byte-identical HLO to the default engine,
    and a default-flag engine produces byte-identical outputs to the
    pre-resilience behavior on a plain workload."""
    e_def = mk(params)
    e_res = mk(params, queue_max=8, shed=True, preempt=True,
               ttft_slo_s=0.5)
    rng = np.random.RandomState(14)
    texts = []
    for eng in (e_def, e_res):
        eng.add_request(rng.randint(0, 97, (9,)), 4)
        b = eng._pack_ragged(eng._admit())
        texts.append(eng._build_unified(b.K).lower(
            *eng._upload_ragged(b)).as_text())
        eng.cancel_all()
        eng.step()      # reports the cancellation; both engines start clean
    assert texts[0] == texts[1]
    # byte-identical step behavior: same workload, same outputs, and the
    # resilience-enabled engine (nothing triggering) changes nothing
    rng = np.random.RandomState(15)
    prompts = [rng.randint(0, 97, (n,)) for n in (9, 8)]

    def run(eng):
        rids = [eng.add_request(p, 4, deadline_s=3600.0 if eng is e_res
                                else None) for p in prompts]
        res = eng.run()
        return [res[r] for r in rids]

    assert run(e_def) == run(e_res)
