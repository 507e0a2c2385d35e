"""Crash-recovering serving driver: ``run_serving_resilient`` (ISSUE 13).

The serving twin of ``distributed.resilience.run_resilient``: the engine
is treated as a *disposable executor* and the driver owns the durable
request state, so any engine-step failure — a poisoned compiled program,
a device reset, a hard process kill — costs a rebuild-and-replay instead
of stranding every in-flight request:

* **request replay** — the driver records every token it delivered (the
  emitted-count watermark, optionally journaled to disk flushed-per-line);
  after a rebuild each unfinished request is re-submitted with
  ``prompt + delivered-prefix`` so the fresh engine re-prefills the
  context and decoding continues exactly where it stopped. Greedy replay
  is token-identical to the uninterrupted run, and the watermark makes
  ``on_token`` delivery exactly-once across retries (a token is journaled
  before the callback sees it, then rides the replay prompt — never the
  callback — after a crash).
* **per-request retry budgets with backoff** — a step failure charges
  only the requests that made NO progress since the previous failure;
  a request that exhausts ``max_retries`` is failed and not resubmitted,
  and each consecutive failure doubles the rebuild backoff.
* **nonfinite circuit breaker** — :class:`~.serving.NonFiniteSampleError`
  (the engine's out-of-range-token gate) carries the poisoned rid: that
  request is failed IMMEDIATELY, with no retry, instead of poisoning
  every rebuild forever.
* **SIGTERM drain** — the preemption notice stops admission
  (``engine.drain()``), sheds the queue back to the driver as *requeued*
  work, lets in-flight requests finish inside ``FLAGS_preempt_grace_s``,
  and cancels (pages freed, prefix preserved in the journal) whatever
  does not fit the grace window — a successor process pointed at the same
  journal resumes them.
* **health** — ``metrics_port`` starts one stable /metrics + /healthz
  endpoint whose readiness (``loading/ready/draining/degraded``) follows
  the driver across engine rebuilds.

``kill_replay_check`` is the spawn-based acceptance harness (the
``resilience_worker`` pattern): a worker process is hard-killed by an
armed ``serving/step:N:kill`` fault mid-workload, respawned onto the same
journal, and its outputs must be bitwise-identical to an uninterrupted
run with zero duplicate deliveries and zero leaked KV pages. It is run by
both tests/test_serving_resilience.py and the ``__graft_entry__`` dryrun.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from .serving import NonFiniteSampleError, ServingEngine

__all__ = ["run_serving_resilient", "ServingJournal", "kill_replay_check"]

_TERMINAL = ("done", "failed", "shed", "cancelled")


def _emit(event: str, **fields):
    from ..observability import emit_event
    emit_event(event, role="serving", **fields)


class ServingJournal:
    """Append-only, flushed-per-line delivery journal — the emitted-count
    watermark that survives process death. One JSONL line per delivered
    token (``{"lid": i, "tok": t}``), plus terminal status marks
    (``{"lid": i, "status": ...}``) and first-submit wall-clock stamps
    (``{"lid": i, "t0": unix}``) so deadlines keep their original epoch
    across restarts. ``path=None`` keeps the watermark in memory only
    (single-process rebuilds).

    Durability (ISSUE 16): flush-per-line covers PROCESS death — every
    appended line reaches the kernel page cache before the user callback
    sees the token, so a kill -9 / ``os._exit`` never replays a delivered
    token. A HOST crash (kernel panic, power loss) can still lose the
    un-synced tail: ``fsync`` (default ``FLAGS_serving_journal_fsync``)
    bounds that window by fsyncing every N appends — at most N-1 whole
    records plus one torn final line (dropped by the loader) can vanish;
    N=1 trades per-token fsync latency for a zero-record window."""

    def __init__(self, path: Optional[str] = None, *,
                 fsync: Optional[int] = None):
        if fsync is None:
            from ..flags import flag
            fsync = int(flag("serving_journal_fsync"))
        self.path = path
        self.fsync_every = max(int(fsync), 0)
        self._appends_since_sync = 0
        self.delivered: Dict[int, List[int]] = {}
        self.statuses: Dict[int, str] = {}
        self.t0: Dict[int, float] = {}
        self._fh = None
        if path and os.path.exists(path):
            with open(path, "r", encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        # torn tail: a crash mid-flush leaves one partial
                        # final line — drop it (and anything after: the
                        # file is append-only, nothing follows a tear)
                        # instead of making every respawn crash at load
                        break
                    lid = int(rec["lid"])
                    if "tok" in rec:
                        self.delivered.setdefault(lid, []).append(
                            int(rec["tok"]))
                    elif "status" in rec:
                        self.statuses[lid] = str(rec["status"])
                    elif "t0" in rec:
                        self.t0[lid] = float(rec["t0"])
        if path:
            self._fh = open(path, "a", encoding="utf-8")

    def _write(self, rec: Dict[str, Any]):
        if self._fh is not None:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
            if self.fsync_every:
                self._appends_since_sync += 1
                if self._appends_since_sync >= self.fsync_every:
                    os.fsync(self._fh.fileno())
                    self._appends_since_sync = 0

    def append(self, lid: int, tok: int):
        self.delivered.setdefault(lid, []).append(int(tok))
        self._write({"lid": lid, "tok": int(tok)})

    def mark(self, lid: int, status: str):
        self.statuses[lid] = status
        self._write({"lid": lid, "status": status})

    def stamp(self, lid: int, t0: float):
        if lid not in self.t0:
            self.t0[lid] = float(t0)
            self._write({"lid": lid, "t0": float(t0)})

    def close(self):
        if self._fh is not None:
            if self.fsync_every:
                os.fsync(self._fh.fileno())
            self._fh.close()
            self._fh = None


class _PromProxy:
    """render()-able view over the CURRENT engine's registry, so one
    metrics server (one stable port) survives engine rebuilds — and the
    driver's exit (the registry is small host state; holding it does not
    pin the dead engine's params/KV pools)."""

    def __init__(self, holder: Dict[str, Any]):
        self._holder = holder

    def render(self) -> str:
        prom = self._holder.get("prom")
        return prom.render() if prom is not None else ""


def run_serving_resilient(
        make_engine: Callable[[], ServingEngine],
        requests: Sequence[Dict[str, Any]], *,
        max_steps: int = 1_000_000,
        max_retries: int = 2,
        retry_backoff_s: float = 0.05,
        grace_s: Optional[float] = None,
        journal_path: Optional[str] = None,
        metrics_port: Optional[int] = None):
    """Drive `requests` to completion through disposable engines built by
    ``make_engine()``. Each request is a dict: ``prompt`` (int sequence)
    and ``max_new_tokens`` required; ``temperature``, ``eos_id``,
    ``deadline_s`` and ``on_token`` optional. The stable request id (the
    ``lid``) is the list index — ``on_token(lid, tok)`` and the returned
    results are keyed by it, across any number of rebuilds/restarts.

    Returns ``(results, info)``: results maps every lid to its delivered
    tokens (partial for cancelled/requeued requests); info records
    rebuilds, per-lid statuses (``done/failed/shed/cancelled/requeued``),
    drain/preemption details and the final engine's pool accounting
    (``free_blocks`` vs ``pool_blocks`` — equal means zero leaked pages).
    """
    from ..flags import flag
    from ..distributed.resilience.driver import SigtermGuard
    from ..observability.flight_recorder import maybe_dump

    if grace_s is None:
        grace_s = float(flag("preempt_grace_s"))
    requests = list(requests)
    journal = ServingJournal(journal_path)
    statuses: Dict[int, str] = {}
    retries: Dict[int, int] = {}
    progress_at_fail: Dict[int, int] = {}
    for lid in range(len(requests)):
        statuses[lid] = journal.statuses.get(lid, "pending")
        retries[lid] = 0
    info: Dict[str, Any] = {"rebuilds": 0, "steps": 0, "preempted": False,
                            "requeued": [], "failed": {},
                            "journal": journal_path}
    holder: Dict[str, Any] = {"engine": None, "draining": False}
    server = None
    if metrics_port is not None:
        from ..observability.prom import MetricsServer

        def _health():
            if holder["draining"]:
                return "draining"
            eng = holder.get("engine")
            return eng.health if eng is not None else "loading"
        server = MetricsServer(_PromProxy(holder), port=metrics_port,
                               health_fn=_health)
        info["metrics_server"] = server

    def _deliver(lid, _rid, tok):
        # journal-first: the watermark advances BEFORE the user callback,
        # so a crash can never replay a token the journal already owns
        journal.append(lid, tok)
        cb = requests[lid].get("on_token")
        if cb is not None:
            cb(lid, tok)

    def _submit(engine) -> Dict[int, int]:
        """(Re-)submit every unfinished request with its delivered prefix
        folded into the prompt; returns {engine rid: lid}."""
        rid_map: Dict[int, int] = {}
        now = time.time()
        for lid, spec in enumerate(requests):
            # 'requeued' is terminal for THIS driver run (handed back to
            # the caller / a successor on the same journal) — resubmitting
            # it into a draining engine would just spin until the grace
            # deadline
            if statuses[lid] in _TERMINAL or statuses[lid] == "requeued":
                continue
            pre = journal.delivered.get(lid, [])
            rem = int(spec["max_new_tokens"]) - len(pre)
            if rem <= 0:
                statuses[lid] = "done"
                journal.mark(lid, "done")
                continue
            eos = spec.get("eos_id")
            if eos is not None and pre and pre[-1] == eos:
                statuses[lid] = "done"
                journal.mark(lid, "done")
                continue
            journal.stamp(lid, now)
            deadline_s = spec.get("deadline_s")
            if deadline_s is not None:
                # keep the ORIGINAL submission epoch across restarts
                deadline_s = max(
                    float(deadline_s) - (now - journal.t0[lid]), 0.0)
            prompt = np.asarray(spec["prompt"], np.int32)
            if pre:
                prompt = np.concatenate(
                    [prompt, np.asarray(pre, np.int32)])
            rid = engine.add_request(
                prompt, rem, spec.get("temperature", 0.0), eos,
                on_token=(lambda r, t, lid=lid: _deliver(lid, r, t)),
                deadline_s=deadline_s)
            rid_map[rid] = lid
        return rid_map

    def _fail(lid, err):
        statuses[lid] = "failed"
        info["failed"][lid] = err
        journal.mark(lid, "failed")
        _emit("serving_request_failed", lid=lid, error=err)

    consec_failures = 0
    drain_deadline = None
    engine = None
    rid_map: Dict[int, int] = {}
    try:
        with SigtermGuard() as sig:
            while True:
                if all(s in _TERMINAL or s == "requeued"
                       for s in statuses.values()):
                    break
                if engine is None:
                    engine = make_engine()
                    holder["engine"] = engine
                    holder["prom"] = engine.prom
                    rid_map = _submit(engine)
                    if holder["draining"]:
                        # rebuilt mid-drain: the resubmitted requests are
                        # exactly the in-flight work the grace window is
                        # FOR, so they must re-admit — report draining
                        # without blocking admission (cancel_all at the
                        # grace deadline still caps everything)
                        engine.set_health("draining")
                if sig.triggered and not holder["draining"]:
                    # preemption notice: stop admitting, shed the queue
                    # back to the driver, finish what fits in the grace
                    # window (cancel the rest at the deadline below)
                    holder["draining"] = True
                    info["preempted"] = True
                    drain_deadline = time.monotonic() + grace_s
                    engine.drain()
                    for r in engine.shed_queue("draining"):
                        lid = rid_map.get(r.rid)
                        if lid is not None:
                            statuses[lid] = "requeued"
                    _emit("serving_sigterm_drain", grace_s=grace_s,
                          running=sum(s is not None for s in engine.slots))
                    maybe_dump("serving_sigterm",
                               extra={"engine": engine.snapshot()})
                if (drain_deadline is not None
                        and time.monotonic() > drain_deadline):
                    for r in engine.cancel_all("drain_deadline"):
                        lid = rid_map.get(r.rid)
                        if lid is not None and statuses[lid] not in \
                                _TERMINAL:
                            statuses[lid] = "requeued"
                    break
                if not engine.has_work():
                    break
                try:
                    finished = engine.step()
                except NonFiniteSampleError as e:
                    # circuit breaker: the poisoned request is FAILED, not
                    # retried — its siblings replay on a fresh engine
                    lid = rid_map.get(e.rid)
                    if lid is not None:
                        _fail(lid, repr(e))
                    info["rebuilds"] += 1
                    _emit("serving_engine_rebuild", error=repr(e),
                          poisoned_lid=lid, rebuilds=info["rebuilds"])
                    engine = holder["engine"] = None
                    continue
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    consec_failures += 1
                    info["rebuilds"] += 1
                    # retry budgets: charge only requests that made NO
                    # progress since the last failure — a request that
                    # never advances exhausts its budget and is failed
                    for rid, lid in rid_map.items():
                        if statuses[lid] in _TERMINAL:
                            continue
                        got = len(journal.delivered.get(lid, []))
                        if got == progress_at_fail.get(lid, -1):
                            retries[lid] += 1
                            if retries[lid] > max_retries:
                                _fail(lid, f"retry budget exhausted "
                                           f"({max_retries}) after: {e!r}")
                        progress_at_fail[lid] = got
                    _emit("serving_engine_rebuild", error=repr(e),
                          rebuilds=info["rebuilds"])
                    maybe_dump("serving_step_failure",
                               extra={"error": repr(e),
                                      "rebuilds": info["rebuilds"]})
                    time.sleep(min(
                        retry_backoff_s * (2 ** (consec_failures - 1)),
                        2.0))
                    engine = holder["engine"] = None
                    continue
                consec_failures = 0
                info["steps"] += 1
                for r in finished:
                    lid = rid_map.get(r.rid)
                    if lid is None or statuses[lid] in _TERMINAL:
                        continue
                    if r.status == "ok":
                        statuses[lid] = "done"
                        journal.mark(lid, "done")
                    elif holder["draining"] and r.status in ("shed",
                                                             "cancelled"):
                        statuses[lid] = "requeued"  # successor resumes it
                    elif r.status == "failed":
                        _fail(lid, r.error or "failed")
                    else:
                        statuses[lid] = r.status
                        journal.mark(lid, r.status)
                if info["steps"] >= max_steps:
                    break
    finally:
        journal.close()
        # the metrics-server thread outlives this call: drop the engine
        # reference (don't pin params + KV pools for the process
        # lifetime) and stop answering ready — a router must not route
        # to a replica whose driver has exited
        holder["draining"] = True
        holder["engine"] = None
    info["statuses"] = dict(statuses)
    info["requeued"] = sorted(lid for lid, s in statuses.items()
                              if s == "requeued")
    info["leftover"] = sorted(lid for lid, s in statuses.items()
                              if s == "pending")
    if engine is not None:
        # free_pages(): cached-free prefix pages are reclaimable, not
        # leaked — the zero-leak gate must count them as free
        info["free_blocks"] = engine.free_pages()
        info["pool_blocks"] = engine._num_blocks - 1
    results = {lid: list(journal.delivered.get(lid, []))
               for lid in range(len(requests))}
    _emit("serving_run_end", rebuilds=info["rebuilds"],
          steps=info["steps"], preempted=info["preempted"],
          failed=sorted(info["failed"]), requeued=info["requeued"])
    return results, info


# -- spawn-based acceptance harness (the resilience_worker pattern) ----------
def refuse_cpu_children_on_tpu(what: str) -> None:
    """Spawned serving workers pin ``JAX_PLATFORMS=cpu`` — a chip belongs
    to one process, and the parent holds it. That is right when the
    parent itself runs on the CPU (the tests). On a TPU parent it would
    quietly serve from the CPU, so refuse instead."""
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} starts CPU-pinned child processes, but this process "
            "runs on the TPU and a chip belongs to one process: the "
            "children would silently serve from the CPU. Run the replicas "
            "in this process, each on its own device "
            "(paddle_tpu.inference.router.InProcessReplica).")


def kill_replay_check(workdir: str, *, timeout: float = 300.0
                      ) -> Dict[str, Any]:
    """Hard-kill-and-replay acceptance (ISSUE 13): spawn the replay
    worker three times — an uninterrupted golden run, a run hard-killed
    by an armed ``serving/step:4:kill`` fault (os._exit, no cleanup), and
    a respawn onto the SAME journal. Asserts the resumed outputs are
    bitwise-identical to the golden run, every token was delivered
    exactly once across the two processes, and the final engine leaked
    zero KV pages. Returns a summary dict (consumed by the dryrun and the
    tier-1 test)."""
    import subprocess
    import sys
    from ..distributed.resilience.faults import FAULT_EXIT_CODE

    refuse_cpu_children_on_tpu("kill_replay_check")
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    def spawn(jdir, fault=""):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   FLAGS_fault_inject=fault,
                   PYTHONPATH=repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        # a spawned worker must not inherit the parent's dryrun device
        # count / multiprocess env
        env.pop("XLA_FLAGS", None)
        args = [sys.executable, "-m", "paddle_tpu.inference.replay_worker",
                jdir]
        p = subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True)
        out, err = p.communicate(timeout=timeout)
        return p.returncode, out, err

    def result(out):
        for line in out.splitlines():
            if line.startswith("RESULT "):
                rec = json.loads(line[len("RESULT "):])
                rec["outputs"] = {int(k): v
                                  for k, v in rec["outputs"].items()}
                rec["delivered"] = {int(k): v
                                    for k, v in rec["delivered"].items()}
                return rec
        raise AssertionError(f"no RESULT line in: {out!r}")

    g_dir = os.path.join(workdir, "golden")
    k_dir = os.path.join(workdir, "killed")
    os.makedirs(g_dir, exist_ok=True)
    os.makedirs(k_dir, exist_ok=True)

    rc, out, err = spawn(g_dir)
    assert rc == 0, (rc, err)
    golden = result(out)
    assert golden["rebuilds"] == 0

    rc, out_k, err_k = spawn(k_dir, fault="serving/step:4:kill")
    assert rc == FAULT_EXIT_CODE, (rc, out_k, err_k)
    pre = {}  # tokens the killed process delivered before dying
    with open(os.path.join(k_dir, "journal.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "tok" in rec:
                pre.setdefault(int(rec["lid"]), []).append(int(rec["tok"]))
    assert any(pre.values()), "kill fired before any delivery"

    rc, out_r, err_r = spawn(k_dir)  # respawn onto the same journal
    assert rc == 0, (rc, err_r)
    resumed = result(out_r)

    # bitwise parity with the uninterrupted run
    assert resumed["outputs"] == golden["outputs"], (
        resumed["outputs"], golden["outputs"])
    # exactly-once delivery across the process boundary: pre-kill
    # deliveries + post-resume deliveries concatenate to the golden
    # outputs with no duplicates and no gaps
    for lid, out_g in golden["outputs"].items():
        both = pre.get(lid, []) + resumed["delivered"].get(lid, [])
        assert both == out_g, (lid, pre.get(lid), resumed["delivered"])
    # zero leaked KV pages after the replay (free_blocks is None when the
    # driver exited without a live engine — that must FAIL the gate, not
    # pass it vacuously as None == None)
    assert resumed["free_blocks"] is not None, resumed
    assert resumed["free_blocks"] == resumed["pool_blocks"], resumed
    assert all(s == "done" for s in resumed["statuses"].values()), resumed
    return {"outputs": len(golden["outputs"]),
            "tokens_pre_kill": sum(len(v) for v in pre.values()),
            "tokens_post_resume": sum(len(v)
                                      for v in resumed["delivered"]
                                      .values()),
            "free_blocks": resumed["free_blocks"],
            "pool_blocks": resumed["pool_blocks"]}
