"""Multi-replica serving: a fault-tolerant fleet router (ISSUE 16).

PR 13 hardened ONE serving replica (deadlines, shedding, preemption,
journaled replay, /healthz); this module is the layer above it — the
unit of production serving is a FLEET, and replica death is an expected
event the router absorbs, not an outage:

* **health-driven dispatch** — placement reads each replica's health
  state and prom snapshot (queue depth + running, recent-window TTFT
  p95, KV-pool utilization) and routes to the least-loaded ready
  replica. Every replica's own queue is bounded (its engine's admission
  control); the router adds a fleet-level bound on top
  (``FLAGS_router_queue_max``) so when every replica sheds, arrivals
  shed at the front door too instead of building an unbounded backlog.
* **journaled failover** — every replica rides its own PR 13
  :class:`~.resilient.ServingJournal`; a token is journaled *before*
  the client callback sees it. On replica death (process exit, step
  failure, heartbeat timeout, an armed fault site) the router requeues
  that replica's in-flight requests onto survivors with the delivered
  prefix folded into the prompt and ``max_new_tokens`` reduced by the
  watermark — token delivery stays exactly-once and greedy outputs stay
  bitwise-identical to an uninterrupted run (a greedy request's output
  is a pure function of its own prompt, independent of placement).
* **quarantine + respawn** — ``FLAGS_router_max_failures`` consecutive
  dispatch/step failures quarantine a replica: it is drained (SIGTERM
  grace for spawned replicas, drain+cancel for in-process ones) and
  probed with doubling backoff; a successful probe respawns it on the
  SAME journal (the PR 13 successor-resume path, driven automatically).
* **fleet front door** — :meth:`Router.serve_metrics` starts ONE stable
  /metrics + /healthz: ready iff ≥1 replica is ready, gauges
  ``replica_state_<i>`` / per-replica depth, counters
  ``router_failovers_total`` / ``router_requeued_total``, reason-tagged
  ``router_*`` JSONL events, and a ``router.json`` flight-recorder
  section so a fleet incident leaves forensics.

Two replica kinds share the lifecycle (``starting → ready ⇄
quarantined → dead``, ``draining`` on the way down):
:class:`InProcessReplica` builds engines from a factory (tier-1 tests,
the dryrun leg); :class:`SpawnedReplica` drives a
``paddle_tpu.inference.router_worker`` process per replica over a tiny
file protocol — an append-only ``inbox.<gen>.jsonl`` of request lines
(the generation bumps on every respawn so a successor never re-reads
work the router already reassigned), the worker's journal as the
delivery channel (the router tails it), and a ``health.json`` heartbeat.

``router_failover_check`` / ``router_spawn_check`` are the acceptance
harnesses run by tests/test_router.py and the ``__graft_entry__``
dryrun.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .resilient import ServingJournal
from .serving import NonFiniteSampleError

__all__ = ["Router", "ReplicaSet", "InProcessReplica", "SpawnedReplica",
           "router_failover_check", "router_spawn_check"]

_TERMINAL = ("done", "failed", "shed", "cancelled")
# numeric codes for the replica_state_<i> prom gauge
STATE_CODES = {"starting": 0, "ready": 1, "draining": 2, "quarantined": 3,
               "dead": 4}


def _faults():
    from ..distributed.resilience import faults
    return faults


def _emit(event: str, **fields):
    from ..observability import emit_event
    emit_event(event, role="router", **fields)


class _ReplicaBase:
    """Lifecycle state + router-side bookkeeping shared by both replica
    kinds. ``assigned`` is the router's view of in-flight work — the
    orphan set a failover requeues."""

    kind = "?"

    def __init__(self, idx: int, journal_path: Optional[str] = None):
        self.idx = idx
        self.state = "starting"
        self.journal_path = journal_path
        self.assigned: Dict[int, Dict[str, Any]] = {}
        self.consec_failures = 0
        self.quarantine_until = 0.0
        self.backoff_s: Optional[float] = None
        self.respawns = 0
        self.last_error: Optional[str] = None

    # -- overridden per kind -------------------------------------------------
    def start(self) -> None:
        raise NotImplementedError

    def submit(self, lid: int, spec: Dict[str, Any], prompt: np.ndarray,
               rem: int, deliver: Callable[[int, int], None]) -> None:
        raise NotImplementedError

    def poll(self, deliver) -> Tuple[List[Tuple[int, str, Optional[str]]],
                                     Optional[str]]:
        """Advance/observe the replica; returns (finished, death_reason)
        where finished is [(lid, status, error)] and death_reason is a
        non-None string when the replica must be failed over."""
        raise NotImplementedError

    def load(self) -> Tuple[float, float, float]:
        """Placement key: (pending requests, TTFT recent p95, pool
        utilization) — lower is better on every axis."""
        return (float(len(self.assigned)), 0.0, 0.0)

    def extra_stats(self) -> Dict[str, float]:
        """Sharing/speculation health counters for the fleet /metrics
        (ISSUE 17) — empty where the engine is out of process."""
        return {}

    def heartbeat_age(self) -> float:
        return 0.0

    def stop(self, grace_s: float, reason: str) -> None:
        raise NotImplementedError

    def drain(self, deliver) -> List[Tuple[int, str, Optional[str]]]:
        """Final observation pass after ``stop()`` — where delivery is
        asynchronous (a journal tail), pick up every durable record the
        replica wrote before it died. No-op where delivery is
        synchronous."""
        del deliver
        return []

    def pending(self) -> int:
        return len(self.assigned)

    def snapshot(self) -> Dict[str, Any]:
        return {"idx": self.idx, "kind": self.kind, "state": self.state,
                "pending": self.pending(),
                "consec_failures": self.consec_failures,
                "respawns": self.respawns,
                "journal": self.journal_path,
                "last_error": self.last_error}


class InProcessReplica(_ReplicaBase):
    """A replica backed by an in-process :class:`ServingEngine` built
    from a factory — the tier-1/test form. Its journal may be
    memory-only (``journal_path=None``): the process IS the failure
    domain, so the watermark only has to survive the engine, not the
    host."""

    kind = "inproc"

    def __init__(self, idx: int, make_engine: Callable[[], Any],
                 journal_path: Optional[str] = None):
        super().__init__(idx, journal_path)
        self._make_engine = make_engine
        self.engine = None
        self.journal = ServingJournal(journal_path)
        self._rid_map: Dict[int, int] = {}

    def start(self) -> None:
        _faults().maybe_fail("replica/spawn")
        self.engine = self._make_engine()
        self._rid_map = {}
        self.state = "ready"

    def submit(self, lid, spec, prompt, rem, deliver) -> None:
        rid = self.engine.add_request(
            prompt, rem, spec.get("temperature", 0.0), spec.get("eos_id"),
            on_token=(lambda r, t, lid=lid: deliver(lid, t)),
            deadline_s=spec.get("deadline_s"))
        self._rid_map[rid] = lid

    def poll(self, deliver):
        if self.engine is None or not self.engine.has_work():
            return [], None
        try:
            finished = self.engine.step()
        except NonFiniteSampleError as e:
            # circuit breaker parity with run_serving_resilient: the
            # poisoned request is FAILED (never requeued — it would
            # poison every survivor too); its siblings fail over
            lid = self._rid_map.get(e.rid)
            done = ([(lid, "failed", repr(e))] if lid is not None else [])
            return done, f"nonfinite:{e.rid}"
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            return [], repr(e)
        out = []
        for r in finished:
            lid = self._rid_map.get(r.rid)
            if lid is not None:
                out.append((lid, "done" if r.status == "ok" else r.status,
                            r.error))
        return out, None

    def load(self):
        eng = self.engine
        if eng is None:
            return (float(len(self.assigned)), 0.0, 0.0)
        s = eng.load_stats()
        return (s["pending"], s["ttft_p95"], s["pool_utilization"])

    def extra_stats(self):
        eng = self.engine
        if eng is None:
            return {}
        s = eng.load_stats()
        return {k: s[k] for k in ("kv_pages_shared", "kv_cow_copies_total",
                                  "spec_proposed_total",
                                  "spec_accepted_total") if k in s}

    def stop(self, grace_s, reason) -> None:
        # in-process drain: no SIGTERM to send — stop admission, shed the
        # queue and cancel in-flight (pages freed; the journal keeps every
        # delivered prefix for the failover resubmission)
        eng, self.engine = self.engine, None
        self._rid_map = {}
        if eng is not None:
            try:
                eng.drain()
                eng.shed_queue(reason)
                eng.cancel_all(reason)
            except Exception:
                pass

    def free_pool(self) -> Tuple[Optional[int], Optional[int]]:
        if self.engine is None:
            return None, None
        # free_pages() counts cached-free prefix pages as reclaimable —
        # the zero-leak failover gate must not read them as leaked
        return self.engine.free_pages(), self.engine._num_blocks - 1


class SpawnedReplica(_ReplicaBase):
    """A replica backed by a ``router_worker`` process — the real path.

    File protocol under ``workdir/replica<i>/``:

    * ``inbox.<gen>.jsonl`` — router appends one request line per
      dispatch plus a ``{"close": true}`` sentinel; the generation bumps
      on every (re)spawn so a respawned worker NEVER re-reads work the
      router already reassigned to survivors (the double-delivery hole a
      shared inbox would open).
    * ``journal.jsonl``     — the worker's :class:`ServingJournal`, and
      the delivery channel: the worker journals each token BEFORE the
      router can observe it; the router tails complete lines only (a
      torn tail from a mid-write kill is left for the next poll). The
      SAME file rides across respawns — the successor-resume contract.
    * ``health.json``       — heartbeat, atomically replaced each worker
      loop; staleness past ``FLAGS_router_heartbeat_timeout_s`` is
      treated as death.
    * ``out.<gen>.log`` / ``err.<gen>.log`` — worker stdio (files, not
      pipes: nothing blocks on an unread pipe mid-run); the final
      ``RESULT {json}`` line carries the pool-leak accounting.
    """

    kind = "spawn"

    def __init__(self, idx: int, workdir: str, *, fault: str = ""):
        self.rdir = os.path.join(workdir, f"replica{idx}")
        os.makedirs(self.rdir, exist_ok=True)
        super().__init__(idx, os.path.join(self.rdir, "journal.jsonl"))
        self._fault = fault  # armed for the FIRST spawn only
        self.gen = 0
        self.proc = None
        self._inbox = None
        self._journal_off = 0
        self._spawn_ts = 0.0
        self.exit_code: Optional[int] = None  # current generation
        self.exit_codes: List[int] = []       # every dead generation's rc
        self._statuses: Dict[int, str] = {}

    def start(self) -> None:
        import subprocess
        import sys
        from .resilient import refuse_cpu_children_on_tpu
        refuse_cpu_children_on_tpu("SpawnedReplica")
        _faults().maybe_fail("replica/spawn")
        self.gen += 1
        self._close_inbox_handle()
        self._inbox = open(os.path.join(self.rdir,
                                        f"inbox.{self.gen}.jsonl"),
                           "a", encoding="utf-8")
        repo = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   FLAGS_fault_inject=self._fault,
                   PYTHONPATH=repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        env.pop("XLA_FLAGS", None)  # no inherited dryrun device counts
        self._fault = ""  # a respawn must not re-arm the injected crash
        args = [sys.executable, "-m", "paddle_tpu.inference.router_worker",
                self.rdir, "--gen", str(self.gen)]
        out = open(os.path.join(self.rdir, f"out.{self.gen}.log"), "w")
        err = open(os.path.join(self.rdir, f"err.{self.gen}.log"), "w")
        self.proc = subprocess.Popen(args, env=env, stdout=out, stderr=err)
        out.close()
        err.close()
        self._spawn_ts = time.time()
        self.exit_code = None
        # 'starting' until the FIRST fresh heartbeat: a cold worker is
        # still compiling — failover traffic must land on warm survivors,
        # not queue behind a respawn's startup
        self.state = "starting"

    def heartbeat_fresh(self) -> bool:
        """True once THIS generation's worker has written a heartbeat
        (a stale file left by the previous generation does not count)."""
        try:
            with open(os.path.join(self.rdir, "health.json"),
                      encoding="utf-8") as f:
                rec = json.load(f)
            return float(rec.get("ts", 0.0)) >= self._spawn_ts - 1.0
        except Exception:
            return False

    def _close_inbox_handle(self):
        if self._inbox is not None:
            try:
                self._inbox.close()
            except Exception:
                pass
            self._inbox = None

    def submit(self, lid, spec, prompt, rem, deliver) -> None:
        del deliver  # delivery rides the journal tail, not a callback
        rec = {"lid": int(lid), "prompt": [int(t) for t in prompt],
               "max_new_tokens": int(rem),
               "temperature": float(spec.get("temperature", 0.0)),
               "eos_id": spec.get("eos_id"),
               "deadline_s": spec.get("deadline_s")}
        self._inbox.write(json.dumps(rec) + "\n")
        self._inbox.flush()

    def send_close(self) -> None:
        if self._inbox is not None:
            self._inbox.write('{"close": true}\n')
            self._inbox.flush()

    def _read_tail(self, deliver):
        finished: List[Tuple[int, str, Optional[str]]] = []
        # tail COMPLETE journal lines appended since the last read —
        # journal-first in the worker means every token seen here was
        # durable before the client callback fires in this process
        try:
            with open(self.journal_path, "r", encoding="utf-8") as f:
                f.seek(self._journal_off)
                data = f.read()
        except OSError:
            data = ""
        end = data.rfind("\n")
        if end >= 0:
            for line in data[:end].splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn interior line (mid-write kill)
                lid = int(rec.get("lid", -1))
                if "tok" in rec:
                    deliver(lid, int(rec["tok"]))
                elif "status" in rec:
                    st = str(rec["status"])
                    self._statuses[lid] = st
                    if lid in self.assigned and st in _TERMINAL:
                        finished.append((lid, st, None))
            self._journal_off += end + 1
        return finished

    def poll(self, deliver):
        finished = self._read_tail(deliver)
        rc = self.proc.poll() if self.proc is not None else None
        if rc is not None:
            self.exit_code = rc
            self.exit_codes.append(rc)
            return finished, f"process_exit rc={rc}"
        return finished, None

    def drain(self, deliver):
        # the worker keeps journaling between a poll's tail read and the
        # moment its death is observed (and a heartbeat-timed-out worker
        # may still be writing) — once stop() has made the journal final,
        # this picks up those durable records so the failover watermark
        # counts every token the client was (or will be) handed
        return self._read_tail(deliver)

    def heartbeat_age(self) -> float:
        try:
            with open(os.path.join(self.rdir, "health.json"),
                      encoding="utf-8") as f:
                rec = json.load(f)
            return max(0.0, time.time() - float(rec.get("ts", 0.0)))
        except Exception:
            # no heartbeat yet: age from spawn (startup/compile counts
            # against the timeout — a worker that never comes up is dead)
            return max(0.0, time.time() - self._spawn_ts)

    def stop(self, grace_s, reason) -> None:
        import signal
        self._close_inbox_handle()
        p, self.proc = self.proc, None
        if p is None or p.poll() is not None:
            return
        try:
            p.send_signal(signal.SIGTERM)  # drain: finish what fits
            p.wait(timeout=grace_s)
        except Exception:
            try:
                p.kill()
                p.wait(timeout=5.0)
            except Exception:
                pass

    def wait(self, timeout: float) -> Optional[int]:
        if self.proc is None:
            return self.exit_code
        try:
            self.exit_code = self.proc.wait(timeout=timeout)
        except Exception:
            return None
        return self.exit_code

    def result(self) -> Optional[Dict[str, Any]]:
        """Parse the worker's final ``RESULT {json}`` line (pool-leak
        accounting) from the current generation's stdout log."""
        try:
            with open(os.path.join(self.rdir, f"out.{self.gen}.log"),
                      encoding="utf-8") as f:
                for line in f:
                    if line.startswith("RESULT "):
                        return json.loads(line[len("RESULT "):])
        except OSError:
            return None
        return None


class ReplicaSet:
    """An ordered fleet of replicas plus construction helpers."""

    def __init__(self, replicas: Sequence[_ReplicaBase]):
        self.replicas = list(replicas)

    @classmethod
    def in_process(cls, make_engine: Callable[[], Any], n: int = 2, *,
                   journal_dir: Optional[str] = None) -> "ReplicaSet":
        reps = []
        for i in range(n):
            jp = (os.path.join(journal_dir, f"replica{i}.jsonl")
                  if journal_dir else None)
            reps.append(InProcessReplica(i, make_engine, jp))
        return cls(reps)

    @classmethod
    def spawned(cls, workdir: str, n: int = 2, *,
                faults: Optional[Dict[int, str]] = None) -> "ReplicaSet":
        faults = faults or {}
        return cls([SpawnedReplica(i, workdir, fault=faults.get(i, ""))
                    for i in range(n)])

    def __len__(self):
        return len(self.replicas)

    def __iter__(self):
        return iter(self.replicas)

    def __getitem__(self, i):
        return self.replicas[i]

    def states(self) -> List[str]:
        return [r.state for r in self.replicas]

    def ready(self) -> List[_ReplicaBase]:
        return [r for r in self.replicas if r.state == "ready"]


class Router:
    """Fault-tolerant request router over a :class:`ReplicaSet`.

    ``submit`` enqueues; ``step`` is one scheduling round (probe
    quarantined replicas, dispatch least-loaded, advance/observe every
    ready replica, heartbeat-check, harvest); ``run`` drives every
    submitted request to a terminal status. Tokens reach ``on_token``
    exactly once, already journaled by the owning replica."""

    def __init__(self, replica_set: ReplicaSet, *,
                 max_failures: Optional[int] = None,
                 queue_max: Optional[int] = None,
                 replica_cap: int = 0,
                 heartbeat_timeout_s: Optional[float] = None,
                 backoff_s: Optional[float] = None,
                 grace_s: Optional[float] = None):
        from ..flags import flag
        from ..observability import PromRegistry
        from ..observability.flight_recorder import register_router
        self.replica_set = replica_set
        self.max_failures = int(max_failures if max_failures is not None
                                else flag("router_max_failures"))
        self.queue_max = int(queue_max if queue_max is not None
                             else flag("router_queue_max"))
        self.replica_cap = int(replica_cap)
        self.heartbeat_timeout_s = float(
            heartbeat_timeout_s if heartbeat_timeout_s is not None
            else flag("router_heartbeat_timeout_s"))
        self.backoff0_s = float(backoff_s if backoff_s is not None
                                else flag("router_quarantine_backoff_s"))
        self.grace_s = float(grace_s if grace_s is not None
                             else flag("preempt_grace_s"))
        self.requests: List[Dict[str, Any]] = []
        self.queue: List[int] = []          # lids awaiting dispatch
        self.statuses: Dict[int, str] = {}
        self.delivered: Dict[int, List[int]] = {}
        self.errors: Dict[int, str] = {}
        self.owner: Dict[int, int] = {}     # lid -> replica idx (current)
        self.steps = 0
        self.failovers = 0
        self.requeues = 0
        self.sheds = 0
        self._prom = PromRegistry(namespace="paddle_tpu_router")
        self._server = None
        for r in self.replica_set:
            self._try_start(r, probe=False)
        self._refresh_gauges()
        register_router(self)

    # -- front door ----------------------------------------------------------
    @property
    def prom(self):
        return self._prom

    def fleet_health(self) -> str:
        """Fleet readiness: ready iff at least one replica is ready —
        ONE dead replica must not flip the front door to 503."""
        return "ready" if self.replica_set.ready() else "degraded"

    def serve_metrics(self, port: int = 0):
        from ..observability.prom import MetricsServer
        self._server = MetricsServer(self._prom, port=port,
                                     health_fn=self.fleet_health)
        return self._server

    def submit(self, prompt, max_new_tokens: int, temperature: float = 0.0,
               eos_id: Optional[int] = None, on_token=None,
               deadline_s: Optional[float] = None) -> int:
        """Enqueue a request; returns its stable fleet-wide lid. The
        router queue is the fleet-level backpressure bound: past
        ``queue_max`` the arrival is SHED loudly (event + counter), the
        same contract as one engine's bounded queue."""
        lid = len(self.requests)
        spec = {"prompt": np.asarray(prompt, np.int32),
                "max_new_tokens": int(max_new_tokens),
                "temperature": temperature, "eos_id": eos_id,
                "on_token": on_token, "deadline_s": deadline_s}
        self.requests.append(spec)
        self.delivered[lid] = []
        if self.queue_max and len(self.queue) >= self.queue_max:
            self.statuses[lid] = "shed"
            self.sheds += 1
            self._prom.counter_inc("router_shed_total",
                                   help="arrivals shed at the fleet door")
            _emit("router_shed", lid=lid, reason="router_queue_full",
                  queue_depth=len(self.queue))
            return lid
        self.statuses[lid] = "pending"
        self.queue.append(lid)
        return lid

    # -- delivery ------------------------------------------------------------
    def _deliver(self, rep: _ReplicaBase, lid: int, tok: int):
        # in-process replicas journal-first HERE; spawned replicas
        # already journaled in the worker before the tail read saw it
        if isinstance(rep, InProcessReplica):
            rep.journal.append(lid, tok)
        self.delivered[lid].append(int(tok))
        cb = self.requests[lid].get("on_token")
        if cb is not None:
            cb(lid, tok)

    # -- dispatch ------------------------------------------------------------
    def _eligible(self) -> List[_ReplicaBase]:
        out = []
        for r in self.replica_set.ready():
            if self.replica_cap and r.pending() >= self.replica_cap:
                continue
            out.append(r)
        return out

    def _dispatch(self):
        while self.queue:
            cands = self._eligible()
            if not cands:
                return  # fleet backpressure: hold in the bounded queue
            rep = min(cands, key=lambda r: (*r.load(), r.idx))
            lid = self.queue[0]
            spec = self.requests[lid]
            pre = self.delivered[lid]
            rem = spec["max_new_tokens"] - len(pre)
            eos = spec.get("eos_id")
            if rem <= 0 or (eos is not None and pre and pre[-1] == eos):
                self.queue.pop(0)
                self._finish(lid, "done", None)
                continue
            prompt = spec["prompt"]
            if pre:
                prompt = np.concatenate(
                    [prompt, np.asarray(pre, np.int32)])
            try:
                _faults().maybe_fail("router/dispatch")
                rep.submit(lid, spec, prompt, rem,
                           lambda l, t, rep=rep: self._deliver(rep, l, t))
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                self._charge_failure(rep, f"dispatch: {e!r}")
                continue
            self.queue.pop(0)
            self.statuses[lid] = "running"
            self.owner[lid] = rep.idx
            rep.assigned[lid] = spec
            rep.consec_failures = 0
            self._prom.counter_inc("router_dispatches_total",
                                   help="requests handed to a replica")

    # -- failure handling ----------------------------------------------------
    def _charge_failure(self, rep: _ReplicaBase, reason: str):
        """One consecutive-failure charge; quarantine past the budget."""
        rep.consec_failures += 1
        rep.last_error = reason
        _emit("router_dispatch_failed", replica=rep.idx, reason=reason,
              consec_failures=rep.consec_failures)
        if rep.consec_failures >= self.max_failures:
            self._quarantine(rep, reason)

    def _failover(self, rep: _ReplicaBase, reason: str):
        """Replica death: requeue its journaled in-flight requests onto
        survivors, watermark preserved (the delivered prefix rides the
        next dispatch's prompt — exactly-once by construction). The
        replica is stopped FIRST and its journal drained before the
        watermarks are taken: tokens journaled between the detecting
        poll's tail read and the death (or by a heartbeat-timed-out
        worker still writing) must count, or a survivor would
        re-generate them and the client would see them twice."""
        rep.stop(self.grace_s, "failover")
        for lid, status, err in rep.drain(
                lambda l, t, rep=rep: self._deliver(rep, l, t)):
            rep.assigned.pop(lid, None)
            self._finish(lid, status, err, replica=rep)
        orphans = sorted(lid for lid in rep.assigned
                         if self.statuses.get(lid) not in _TERMINAL)
        pre_counts = {lid: len(self.delivered[lid]) for lid in orphans}
        rep.assigned.clear()
        for lid in reversed(orphans):
            self.statuses[lid] = "pending"
            self.queue.insert(0, lid)  # orphans keep their original order
        self.failovers += 1
        self.requeues += len(orphans)
        self._prom.counter_inc("router_failovers_total",
                               help="replica deaths absorbed by requeue")
        self._prom.counter_inc("router_requeued_total", len(orphans),
                               help="in-flight requests replayed onto "
                                    "survivors")
        _emit("router_failover", replica=rep.idx, reason=reason,
              orphans=orphans, watermarks=pre_counts)
        from ..observability.flight_recorder import maybe_dump
        maybe_dump("router_failover",
                   extra={"replica": rep.idx, "reason": reason,
                          "orphans": orphans})
        rep.consec_failures += 1
        rep.last_error = reason
        if rep.consec_failures >= self.max_failures:
            self._quarantine(rep, reason)
        else:
            self._try_start(rep, probe=False)

    def _quarantine(self, rep: _ReplicaBase, reason: str):
        rep.stop(self.grace_s, "quarantined")
        rep.state = "quarantined"
        rep.backoff_s = (self.backoff0_s if rep.backoff_s is None
                         else min(rep.backoff_s * 2.0, 30.0))
        rep.quarantine_until = time.monotonic() + rep.backoff_s
        _emit("router_quarantine", replica=rep.idx, reason=reason,
              backoff_s=rep.backoff_s,
              consec_failures=rep.consec_failures)

    def _try_start(self, rep: _ReplicaBase, *, probe: bool) -> bool:
        try:
            rep.start()
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            rep.last_error = repr(e)
            rep.consec_failures += 1
            if probe:
                # failed probe: stay quarantined, backoff doubles
                rep.backoff_s = min((rep.backoff_s or self.backoff0_s)
                                    * 2.0, 30.0)
                rep.quarantine_until = time.monotonic() + rep.backoff_s
                _emit("router_probe", replica=rep.idx, ok=False,
                      error=repr(e), backoff_s=rep.backoff_s)
            else:
                self._quarantine(rep, f"start: {e!r}")
            return False
        if probe or rep.consec_failures:
            rep.respawns += 1
            self._prom.counter_inc("router_respawns_total",
                                   help="replicas respawned onto their "
                                        "journal")
            _emit("router_probe", replica=rep.idx, ok=True,
                  respawns=rep.respawns)
        if probe:
            # a successful probe proved the replica can come back: fresh
            # failure budget. A plain restart does NOT reset the count —
            # 'consecutive' survives crash-restart loops, only a
            # successful dispatch clears it.
            rep.consec_failures = 0
        rep.backoff_s = None
        return True

    # -- the scheduling round ------------------------------------------------
    def step(self):
        self.steps += 1
        now = time.monotonic()
        # 0) promote warmed-up replicas; catch startup deaths
        for rep in self.replica_set:
            if rep.state != "starting":
                continue
            if isinstance(rep, SpawnedReplica):
                rc = rep.proc.poll() if rep.proc is not None else -1
                if rc is not None:
                    rep.exit_code = rc
                    rep.exit_codes.append(rc)
                    self._failover(rep, f"process_exit rc={rc} (startup)")
                elif rep.heartbeat_fresh():
                    rep.state = "ready"
            else:
                rep.state = "ready"
        # 1) probe quarantined replicas whose backoff expired
        for rep in self.replica_set:
            if rep.state == "quarantined" and now >= rep.quarantine_until:
                self._try_start(rep, probe=True)
        # 2) health-driven dispatch
        self._dispatch()
        # 3) advance/observe every ready replica (round-robin: one engine
        #    step per in-process replica per round keeps interleaving —
        #    and any armed global fault-site hit counter — deterministic)
        for rep in self.replica_set:
            if rep.state != "ready":
                continue
            finished, death = rep.poll(
                lambda l, t, rep=rep: self._deliver(rep, l, t))
            for lid, status, err in finished:
                rep.assigned.pop(lid, None)
                self._finish(lid, status, err, replica=rep)
            if death is not None:
                self._failover(rep, death)
        # 4) heartbeat: a wedged replica is failed over like a dead one
        for rep in self.replica_set:
            if rep.state != "ready":
                continue
            if (_faults().maybe_trigger("replica/heartbeat")
                    or rep.heartbeat_age() > self.heartbeat_timeout_s):
                self._failover(rep, "heartbeat_timeout")
        self._refresh_gauges()

    def _finish(self, lid: int, status: str, err: Optional[str],
                replica: Optional[_ReplicaBase] = None):
        if self.statuses.get(lid) in _TERMINAL:
            return
        if status in ("shed", "cancelled") and replica is not None:
            # the replica dropped it (deadline/overload/drain) without
            # finishing: the fleet still owns the request — requeue with
            # the watermark rather than surfacing a replica-local shed
            if self.statuses.get(lid) not in _TERMINAL:
                self.statuses[lid] = "pending"
                self.queue.append(lid)
                self.requeues += 1
                self._prom.counter_inc("router_requeued_total")
                return
        self.statuses[lid] = status
        if err:
            self.errors[lid] = err
        if status == "done":
            for rep in self.replica_set:
                if isinstance(rep, InProcessReplica) and \
                        rep.journal.statuses.get(lid) is None and \
                        self.owner.get(lid) == rep.idx:
                    rep.journal.mark(lid, "done")
        self._prom.counter_inc(f"router_{status}_total",
                               help="requests by terminal status")

    def _refresh_gauges(self):
        self._prom.gauge_set("router_queue_depth", len(self.queue),
                             help="requests waiting at the fleet door")
        self._prom.gauge_set("replicas_ready",
                             len(self.replica_set.ready()),
                             help="replicas currently routable")
        for rep in self.replica_set:
            i = rep.idx
            self._prom.gauge_set(f"replica_state_{i}",
                                 STATE_CODES.get(rep.state, -1),
                                 help="0=starting 1=ready 2=draining "
                                      "3=quarantined 4=dead")
            pend, ttft, util = rep.load()
            self._prom.gauge_set(f"replica_queue_depth_{i}", pend)
            self._prom.gauge_set(f"replica_pool_utilization_{i}", util)
            if ttft:
                self._prom.gauge_set(f"replica_ttft_p95_{i}", ttft)
            for name, v in rep.extra_stats().items():
                # kv_pages_shared / kv_cow_copies_total /
                # spec_proposed_total / spec_accepted_total (ISSUE 17) —
                # accepted/proposed is the fleet speculation health rate
                self._prom.gauge_set(f"replica_{name}_{i}", v)

    def has_work(self) -> bool:
        return (bool(self.queue)
                or any(self.statuses.get(lid) == "running"
                       for lid in range(len(self.requests))))

    def run(self, max_steps: int = 100000, *, poll_s: float = 0.005,
            deadline_s: Optional[float] = None
            ) -> Tuple[Dict[int, List[int]], Dict[str, Any]]:
        """Drive every submitted request to a terminal status; returns
        ``(results, info)`` shaped like ``run_serving_resilient`` —
        results maps lid to its delivered tokens."""
        t_end = (time.monotonic() + deadline_s
                 if deadline_s is not None else None)
        spawned = any(isinstance(r, SpawnedReplica)
                      for r in self.replica_set)
        for _ in range(max_steps):
            if not self.has_work():
                break
            if t_end is not None and time.monotonic() > t_end:
                break
            before = sum(len(v) for v in self.delivered.values())
            self.step()
            after = sum(len(v) for v in self.delivered.values())
            if spawned and after == before:
                time.sleep(poll_s)  # workers self-step; don't spin hot
        info = {"steps": self.steps, "failovers": self.failovers,
                "requeued": self.requeues, "sheds": self.sheds,
                "statuses": dict(self.statuses),
                "replica_states": self.replica_set.states(),
                "leftover": sorted(
                    lid for lid, s in self.statuses.items()
                    if s not in _TERMINAL)}
        results = {lid: list(self.delivered.get(lid, []))
                   for lid in range(len(self.requests))}
        _emit("router_run_end", **{k: info[k] for k in
                                   ("steps", "failovers", "requeued",
                                    "sheds", "leftover")})
        return results, info

    def close(self, *, timeout: float = 60.0):
        """Drain the fleet down: close every spawned inbox (the worker
        exits once its work is done), wait, and stop the front door."""
        for rep in self.replica_set:
            if isinstance(rep, SpawnedReplica):
                rep.send_close()
        for rep in self.replica_set:
            if isinstance(rep, SpawnedReplica):
                if rep.wait(timeout) is None:
                    rep.stop(self.grace_s, "close")
                rep.state = "dead"
        if self._server is not None:
            self._server.stop()
            self._server = None

    def snapshot(self) -> Dict[str, Any]:
        """Host-state fleet snapshot for flight-recorder bundles
        (``router.json``): per-replica lifecycle + failure counters,
        queue, per-lid status/watermark."""
        return {
            "fleet_health": self.fleet_health(),
            "steps": self.steps, "failovers": self.failovers,
            "requeued": self.requeues, "sheds": self.sheds,
            "queue": list(self.queue),
            "replicas": [r.snapshot() for r in self.replica_set],
            "requests": {
                lid: {"status": self.statuses.get(lid),
                      "delivered": len(self.delivered.get(lid, [])),
                      "owner": self.owner.get(lid)}
                for lid in range(len(self.requests))},
        }


# -- acceptance harnesses ----------------------------------------------------
def router_failover_check(workdir: str, *, n_replicas: int = 2,
                          fault: str = "serving/step:5"
                          ) -> Dict[str, Any]:
    """In-process acceptance (tier-1 + dryrun leg): a 2-replica fleet,
    replica 0's engine killed mid-generation by an armed ``serving/step``
    fault (raise form — the hit counter is global, so with strict
    round-robin stepping hit 5 lands on replica 0's 3rd step, after it
    has delivered tokens). Asserts every request completes with greedy
    outputs bitwise-identical to ``gpt_generate``, exactly-once delivery,
    EXACTLY one ``router_failover`` event, fleet /healthz 200 at every
    poll, and full capacity (every replica ready) after recovery."""
    import urllib.request
    import jax.numpy as jnp
    from ..models.generation import gpt_generate
    from ..observability import EventLog, get_event_log, set_event_log
    from .replay_worker import workload
    from .serving import ServingEngine

    cfg, params, prompts, news = workload()

    def make_engine():
        # decode_burst=2 stretches each request across several engine
        # steps so the armed serving/step hit lands MID-generation (a
        # full burst would finish the whole workload before it fires)
        return ServingEngine(params, cfg, max_batch=2, block_size=8,
                             num_blocks=24, max_blocks_per_seq=8, chunk=8,
                             decode_burst=2, adaptive_mix=False)

    golden = {}
    for lid, (p, n) in enumerate(zip(prompts, news)):
        out = gpt_generate(params, cfg, jnp.asarray(p, jnp.int32)[None], n)
        golden[lid] = np.asarray(out)[0, len(p):].tolist()

    log_path = os.path.join(workdir, "router_events.jsonl")
    prev_log = get_event_log()
    set_event_log(EventLog(log_path))
    faults = _faults()
    faults.configure(fault)
    healthz_polls = 0
    try:
        rs = ReplicaSet.in_process(make_engine, n=n_replicas,
                                   journal_dir=workdir)
        router = Router(rs)
        server = router.serve_metrics(port=0)
        delivered_cb: Dict[int, List[int]] = {i: [] for i in golden}
        for lid, (p, n) in enumerate(zip(prompts, news)):
            router.submit(p, n, on_token=lambda l, t: delivered_cb[l]
                          .append(int(t)))
        url = f"http://127.0.0.1:{server.port}/healthz"
        tokens_at_failover = None
        while router.has_work():
            router.step()
            code = urllib.request.urlopen(url, timeout=5).getcode()
            assert code == 200, f"fleet /healthz flapped: {code}"
            healthz_polls += 1
            if router.failovers and tokens_at_failover is None:
                tokens_at_failover = sum(len(v) for v in
                                         router.delivered.values())
        results = {lid: router.delivered[lid] for lid in golden}
        router.close()
    finally:
        faults.configure("")
        set_event_log(prev_log)

    assert results == golden, (results, golden)
    assert delivered_cb == golden, "on_token delivery not exactly-once"
    assert router.failovers == 1, router.failovers
    with open(log_path, encoding="utf-8") as f:
        evs = [json.loads(ln) for ln in f if ln.strip()]
    fo = [e for e in evs if e.get("event") == "router_failover"]
    assert len(fo) == 1, fo
    assert all(s == "ready" for s in router.replica_set.states()), \
        router.replica_set.states()
    # zero leaked pages on every live engine after the full fleet run
    for rep in router.replica_set:
        free, total = rep.free_pool()
        if free is not None:
            assert free == total, (rep.idx, free, total)
    total_tokens = sum(len(v) for v in golden.values())
    return {"requests": len(golden), "tokens": total_tokens,
            "tokens_pre_failover": tokens_at_failover or 0,
            "failovers": router.failovers, "requeued": router.requeues,
            "healthz_polls": healthz_polls,
            "failed_replica": fo[0].get("replica")}


def router_spawn_check(workdir: str, *, timeout: float = 300.0
                       ) -> Dict[str, Any]:
    """Cross-process acceptance (ISSUE 16 satellite): a 2-replica SPAWNED
    fleet, replica 0 hard-killed (``serving/step:3:kill`` — os._exit in
    the worker, a real crash) mid-generation. Every request must complete
    on replica 1 with exactly-once delivery (pre-kill journal tokens +
    post-failover tokens concatenate to golden, no dupes/gaps), bitwise
    greedy outputs, zero leaked KV pages on the survivor, fleet /healthz
    200 throughout, and replica 0 respawned to ready on the same
    journal."""
    import subprocess
    import sys
    import urllib.error
    import urllib.request
    from ..distributed.resilience.faults import FAULT_EXIT_CODE
    from .replay_worker import workload

    cfg, params, prompts, news = workload()
    # golden comes from a SPAWNED uninterrupted run (the kill_replay_check
    # pattern), not in-process generation: the fleet workers are clean
    # processes, while the calling process may carry arbitrary global
    # jax/flag state — in-process numerics need not match theirs bitwise.
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    g_dir = os.path.join(workdir, "golden")
    os.makedirs(g_dir, exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu", FLAGS_fault_inject="",
               PYTHONPATH=repo + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.inference.replay_worker", g_dir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    g_out, g_err = proc.communicate(timeout=timeout)
    assert proc.returncode == 0, (proc.returncode, g_err)
    golden: Dict[int, List[int]] = {}
    for line in g_out.splitlines():
        if line.startswith("RESULT "):
            rec = json.loads(line[len("RESULT "):])
            golden = {int(k): v for k, v in rec["delivered"].items()}
    assert golden, ("no RESULT from golden run", g_out, g_err)

    rs = ReplicaSet.spawned(workdir, n=2,
                            faults={0: "serving/step:3:kill"})
    # generous heartbeat budget: this check asserts the SCRIPTED kill is
    # the death cause — on a loaded CI box a live worker can stall past
    # the default timeout and get SIGTERM-drained first, which is
    # legitimate router behavior but not what is under test here
    router = Router(rs, heartbeat_timeout_s=60.0)
    server = router.serve_metrics(port=0)
    url = f"http://127.0.0.1:{server.port}/healthz"
    t_end = time.monotonic() + timeout
    # warm the whole fleet up BEFORE submitting: dispatch is health-driven
    # (cold replicas are not routable), so submitting against a
    # half-warmed fleet would send everything to whichever worker
    # heartbeated first — including the armed one's workload
    while not all(s == "ready" for s in rs.states()):
        assert time.monotonic() < t_end, (
            "fleet never warmed up", router.snapshot())
        router.step()
        time.sleep(0.05)
    for lid, (p, n) in enumerate(zip(prompts, news)):
        router.submit(p, n)
    healthz_polls = 0
    while router.has_work():
        assert time.monotonic() < t_end, (
            "spawned fleet did not converge", router.snapshot())
        router.step()
        try:
            code = urllib.request.urlopen(url, timeout=5).getcode()
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 200, f"fleet /healthz flapped: {code}"
        healthz_polls += 1
        time.sleep(0.02)
    # recovery to FULL capacity: keep stepping (healthz still 200 — one
    # ready survivor suffices) until the respawned replica 0 heartbeats
    # its way back to ready
    while not all(s == "ready" for s in rs.states()):
        assert time.monotonic() < t_end, (
            "fleet did not recover full capacity", router.snapshot())
        router.step()
        code = urllib.request.urlopen(url, timeout=5).getcode()
        assert code == 200, f"fleet /healthz flapped post-run: {code}"
        healthz_polls += 1
        time.sleep(0.05)
    results = {lid: router.delivered[lid] for lid in golden}

    # bitwise parity + exactly-once at the client
    assert results == golden, (results, golden)
    assert router.failovers >= 1
    r0, r1 = rs[0], rs[1]
    assert FAULT_EXIT_CODE in r0.exit_codes, r0.exit_codes
    # exactly-once ACROSS the process boundary: replica 0's journal holds
    # only pre-kill tokens (the respawned generation got no reassigned
    # work — the inbox generation bump guarantees it); replica 1's holds
    # the rest. Their per-lid concatenation must equal golden exactly.
    def journal_toks(rep):
        toks: Dict[int, List[int]] = {}
        with open(rep.journal_path, encoding="utf-8") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    break
                if "tok" in rec:
                    toks.setdefault(int(rec["lid"]), []).append(
                        int(rec["tok"]))
        return toks
    pre, post = journal_toks(r0), journal_toks(r1)
    assert any(pre.values()), "kill fired before any delivery"
    for lid, out_g in golden.items():
        both = pre.get(lid, []) + post.get(lid, [])
        assert both == out_g, (lid, pre.get(lid), post.get(lid), out_g)
    # full capacity after respawn: BOTH replicas ready, r0 on gen 2
    assert all(s == "ready" for s in rs.states()), rs.states()
    assert r0.gen == 2 and r0.respawns >= 1, (r0.gen, r0.respawns)
    router.close(timeout=timeout)
    res1 = r1.result()
    assert res1 is not None, "survivor produced no RESULT"
    assert res1["free_blocks"] == res1["pool_blocks"], res1
    return {"requests": len(golden),
            "tokens_pre_kill": sum(len(v) for v in pre.values()),
            "tokens_post_failover": sum(len(v) for v in post.values()),
            "failovers": router.failovers, "requeued": router.requeues,
            "healthz_polls": healthz_polls,
            "survivor_free_blocks": res1["free_blocks"],
            "survivor_pool_blocks": res1["pool_blocks"]}
