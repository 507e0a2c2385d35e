"""Deterministic, flag-gated fault injection.

Recovery code that is never executed is broken code: every resilience path
in this package (crash-safe commit, store retry, preemption drain) carries
named injection points — ``faults.maybe_fail("ckpt/after_chunk_write")`` —
that are inert unless ``FLAGS_fault_inject`` arms them. Tests use them to
prove the recovery paths actually run (reference analog: the chaos hooks
the reference exercises via test/legacy_test/test_dist_base.py subprocess
kills; here the kill point is addressable and deterministic).

Spec grammar (``FLAGS_fault_inject``, comma-separated clauses)::

    site                fire on the 1st hit of `site`, raising FaultInjected
    site:3              fire on the 3rd hit (deterministic, fires once)
    site:3:kill         hard-exit (os._exit(FAULT_EXIT_CODE)) on the 3rd hit
    site:p0.25          fire each hit with prob 0.25 — per-site RNG seeded
                        from FLAGS_fault_inject_seed, so the same seed+spec
                        replays the identical failure schedule
    site:p0.25:kill     probabilistic hard-exit
    site:2:hang5        HANG: on the 2nd hit, block in time.sleep for 5
                        seconds then CONTINUE normally (no exception) —
                        the wedged-step simulator the watchdog/flight-
                        recorder tests arm (``hang`` alone sleeps 30 s)

Sites currently planted (grep for ``maybe_fail`` /
``maybe_corrupt_file`` to enumerate):

* ``ckpt/torn_chunk``         — TEARS the just-landed .distcp file
  (truncates it to half) before dying: simulates a storage layer that
  acked the fsync but lost the tail — the mid-save case atomic_write
  alone cannot model (``maybe_corrupt_file``)
* ``ckpt/after_chunk_write``  — data file durable, metadata not yet written
* ``ckpt/before_metadata_write`` — before the atomic 0.metadata replace
* ``ckpt/before_commit``      — staging dir complete, not yet renamed
* ``ckpt/after_rename``       — final dir exists, COMMITTED marker missing
* ``store/connect`` ``store/get`` ``store/set`` ``store/wait`` — transient
  store faults (raised as TransientStoreError so the retry path engages)
* ``loop/before_step``        — the resilient train driver's step boundary
* ``watchdog/hang``           — INSIDE the driver's watchdog span, before
  the step runs: arm with a ``hangN`` clause to wedge the step past its
  budget so the watchdog fires and the flight recorder dumps, then let
  the run continue (the hang is a stall, not a crash)
* ``serving/step``            — first thing in ``ServingEngine.step()``:
  the kill-and-replay leg arms ``serving/step:3:kill`` to hard-kill the
  serving process mid-workload (the ``run_serving_resilient`` driver
  must rebuild + replay), and a ``hangN`` clause wedges the engine like
  a stuck device would
* ``serving/dispatch``        — immediately before each compiled serving
  program is invoked (the unified ragged step)
* ``router/dispatch``         — in the fleet router, immediately before a
  request is handed to the chosen replica: a ``raise`` clause makes that
  dispatch fail (the request requeues, the replica's consecutive-failure
  count charges toward ``FLAGS_router_max_failures`` quarantine), ``kill``
  hard-exits the router process itself (ISSUE 16)
* ``replica/spawn``           — in the router's replica start/probe path,
  before the engine is built (in-process) or the worker process spawned:
  arming it proves the quarantine + doubling-backoff probe loop runs
  (ISSUE 16)
* ``replica/heartbeat``       — a ``maybe_trigger`` QUERY site in the
  router's per-replica heartbeat check: the scheduled hit makes the
  router treat that replica's heartbeat as timed out — the
  journaled-failover path runs without anyone actually dying, the
  watchdog-hang pattern applied to liveness (ISSUE 16)
* ``serving/pool_exhausted``  — the admission loop found the queue head
  pool-blocked (no free KV pages): fires each blocked attempt, so tests
  can prove head-of-line pressure (and the preempt path) actually ran
* ``numerics/spike``          — a ``maybe_trigger`` QUERY site in the
  resilient driver's step loop: when armed (e.g.
  ``numerics/spike:12``), the scheduled hit makes the driver scale its
  HOST-OBSERVED loss by 1e6 — a synthetic loss/grad spike exercising
  the numerics anomaly detectors + flight-recorder forensics end to
  end with the device state untouched (ISSUE 15; the watchdog/hang
  pattern applied to value corruption instead of stalls)
"""

from __future__ import annotations

import os
import random
import threading
import zlib
from typing import Dict, Optional

__all__ = ["FaultInjected", "maybe_fail", "maybe_trigger",
           "maybe_corrupt_file", "configure", "reset", "hits",
           "FAULT_EXIT_CODE"]

FAULT_EXIT_CODE = 41  # distinguishable from python crashes (1) / signals


class FaultInjected(RuntimeError):
    """Raised by an armed injection point (default failure mode)."""


class _Clause:
    __slots__ = ("site", "nth", "prob", "kill", "hang_s", "fired", "rng")

    def __init__(self, site: str, nth: Optional[int], prob: Optional[float],
                 kill: bool, hang_s: Optional[float] = None):
        self.site = site
        self.nth = nth
        self.prob = prob
        self.kill = kill
        self.hang_s = hang_s
        self.fired = False
        self.rng: Optional[random.Random] = None


_LOCK = threading.Lock()
_ARMED: Dict[str, _Clause] = {}
_COUNTS: Dict[str, int] = {}
# Fast-path gate: maybe_fail is a single comparison when disarmed. None
# means "not yet configured" — the first maybe_fail pulls the spec from
# FLAGS_fault_inject (env overrides land there before this package can be
# imported; see flags._bind_fault_inject).
_ENABLED: Optional[bool] = None


def configure(spec: str) -> None:
    """(Re)arm injection points from a spec string; '' disarms everything.
    Bound to FLAGS_fault_inject via its on_set hook, so both the env var and
    paddle.set_flags take effect. Counters reset on every configure."""
    global _ENABLED
    armed: Dict[str, _Clause] = {}
    for clause in (spec or "").split(","):
        clause = clause.strip()
        if not clause:
            continue
        parts = clause.split(":")
        site = parts[0]
        nth: Optional[int] = 1
        prob: Optional[float] = None
        kill = False
        hang_s: Optional[float] = None
        for p in parts[1:]:
            if p == "kill":
                kill = True
            elif p == "raise":
                kill = False
            elif p.startswith("hang"):
                hang_s = float(p[4:]) if p[4:] else 30.0
            elif p.startswith("p"):
                prob, nth = float(p[1:]), None
            else:
                nth = int(p)
        armed[site] = _Clause(site, nth, prob, kill, hang_s)
    with _LOCK:
        _ARMED.clear()
        _ARMED.update(armed)
        _COUNTS.clear()
        _ENABLED = bool(armed)


def reset() -> None:
    """Clear hit counters and one-shot state, keeping the armed spec."""
    with _LOCK:
        _COUNTS.clear()
        for cl in _ARMED.values():
            cl.fired = False
            cl.rng = None


def hits() -> Dict[str, int]:
    """Per-site hit counts since the last configure/reset (only tracked
    while any clause is armed — the disarmed fast path counts nothing)."""
    with _LOCK:
        return dict(_COUNTS)


def _site_rng(site: str) -> random.Random:
    # per-site stream: same FLAGS_fault_inject_seed => same schedule,
    # independent of how other sites interleave
    from ...flags import flag
    seed = int(flag("fault_inject_seed"))
    return random.Random((zlib.crc32(site.encode()) << 32) ^ seed)


def maybe_corrupt_file(site: str, path: str, exc=FaultInjected) -> None:
    """Torn-write injection point: like ``maybe_fail`` but, on the
    scheduled hit, first TRUNCATES `path` to half its bytes — the file is
    left torn on disk exactly as a lying storage layer would, then the
    clause's failure mode (raise / hard-exit) fires. Disarmed: one
    comparison, the file is never touched."""
    if _ENABLED is None:
        from ...flags import flag
        configure(flag("fault_inject"))
    if not _ENABLED:
        return

    def tear():
        size = os.path.getsize(path)
        with open(path, "rb+") as f:
            f.truncate(size // 2)
    _fire(site, exc, before=tear)


def maybe_fail(site: str, exc=FaultInjected) -> None:
    """Injection point. No-op (one comparison) unless FLAGS_fault_inject
    arms `site`; then raises `exc` or hard-exits on the scheduled hit."""
    if _ENABLED is None:
        from ...flags import flag
        configure(flag("fault_inject"))
    if not _ENABLED:
        return
    _fire(site, exc)


def maybe_trigger(site: str) -> bool:
    """QUERY-style injection point for sites whose failure mode is a
    corrupted VALUE rather than an exception (a numerics spike, a
    degraded reading): counts a hit and returns True on the scheduled
    firing instead of raising — the caller then perturbs its own state.
    ``kill`` clauses keep their hard-exit semantics; ``hangN`` clauses
    stall-then-continue and return False (a hang is not a corruption).
    Disarmed: one comparison, always False."""
    if _ENABLED is None:
        from ...flags import flag
        configure(flag("fault_inject"))
    if not _ENABLED:
        return False
    return _fire(site, None, trigger_only=True)


def _fire(site: str, exc, before=None, trigger_only=False) -> bool:
    with _LOCK:
        n = _COUNTS.get(site, 0) + 1
        _COUNTS[site] = n
        cl = _ARMED.get(site)
        if cl is None:
            return
        if cl.prob is not None:
            if cl.rng is None:
                cl.rng = _site_rng(site)
            fire = cl.rng.random() < cl.prob
        else:
            fire = (not cl.fired) and n == cl.nth
            cl.fired = cl.fired or fire
        kill = cl.kill
        hang_s = cl.hang_s
    if not fire:
        return False
    if before is not None:
        before()  # e.g. tear the file THEN die, like real torn storage
    if hang_s is not None:
        # a STALL, not a crash: wedge here (outside the lock) long enough
        # for the watchdog to fire, then resume normally — the injected
        # hang a flight-recorder test diagnoses from the bundle alone
        import time
        time.sleep(hang_s)
        return False
    if kill:
        os._exit(FAULT_EXIT_CODE)  # crash without cleanup: no atexit drain,
        #                            no buffered IO flush — a real SIGKILL
    if trigger_only:
        return True  # the caller owns the corruption (maybe_trigger)
    raise exc(f"[fault-injection] {site} (hit {n})")
