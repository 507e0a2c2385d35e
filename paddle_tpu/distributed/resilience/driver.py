"""Preemption-aware resilient train loop.

What real TPU fleets do daily — preempted VMs, SIGTERMed workers, hung
rendezvous, a stray NaN — is handled here once so train scripts don't each
reinvent it (reference analog: comm_task_manager watchdog escalation +
the elastic launcher's checkpoint-restart contract):

* every step runs inside a ``CommWatchdog`` span, with optional escalation
  (``abort_on_timeout``) that interrupts a hung step, takes a final commit
  and raises ``WatchdogTimeout`` instead of silently wedging the job;
* checkpoints auto-commit on a cadence through the crash-safe two-phase
  protocol (`commit.commit_checkpoint`);
* SIGTERM (the cloud preemption notice) is caught: the loop finishes the
  in-flight step, drains async writers and takes ONE final synchronous
  commit inside ``FLAGS_preempt_grace_s``. Multi-process assumption: the
  platform preempts the WHOLE job (every rank gets SIGTERM, as Cloud TPU
  pod maintenance does) and ranks run step-synchronized, so all ranks
  reach the final commit barrier for the same step; a rank whose final
  barrier still times out logs the error and exits without a checkpoint
  rather than hanging past the grace window;
* on restart, ``latest_checkpoint`` discovery resumes the loop exactly
  where the last commit left it;
* a non-finite loss skips the step (the grad-scaler found_inf discipline,
  extended to the loop level) and aborts with a per-leaf diagnostic after
  ``FLAGS_max_consecutive_nonfinite`` consecutive skips.
"""

from __future__ import annotations

import _thread
import math
import signal
import sys
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from ..watchdog import CommWatchdog
from .commit import checkpoint_step, commit_checkpoint, latest_checkpoint

__all__ = ["run_resilient", "SigtermGuard", "NonFiniteLossError",
           "WatchdogTimeout"]


class NonFiniteLossError(RuntimeError):
    """Too many consecutive non-finite steps; message carries the per-leaf
    nan/inf breakdown of the last rejected state."""


class WatchdogTimeout(RuntimeError):
    """A step overran its watchdog budget and abort_on_timeout escalated."""


class SigtermGuard:
    """Installs a SIGTERM handler that records the preemption notice
    without killing the process; the training loop polls ``triggered`` at
    step boundaries. Restores the previous handler on exit. A no-op (never
    triggered) off the main thread, where CPython forbids signal.signal."""

    def __init__(self, extra_signals: Tuple[int, ...] = ()):
        self._signals = (signal.SIGTERM,) + tuple(extra_signals)
        self._previous: Dict[int, Any] = {}
        self.triggered = False
        self.trigger_time: Optional[float] = None

    def _handler(self, signum, frame):
        del signum, frame
        self.triggered = True
        if self.trigger_time is None:
            self.trigger_time = time.monotonic()

    def __enter__(self):
        if threading.current_thread() is threading.main_thread():
            for sig in self._signals:
                self._previous[sig] = signal.signal(sig, self._handler)
        return self

    def __exit__(self, *exc):
        for sig, prev in self._previous.items():
            signal.signal(sig, prev)
        self._previous.clear()
        return False


def _loss_value(loss) -> Optional[float]:
    if loss is None:
        return None
    try:
        return float(loss)
    except TypeError:
        return None


def _kept(state: Dict, why: str) -> Dict:
    """The state a rejected step falls back to, if it is still there: a
    step_fn that DONATED it (the hybrid builders' default) left nothing to
    keep."""
    import jax
    from ...enforce import PreconditionNotMetError, enforce
    enforce(not any(isinstance(v, jax.Array) and v.is_deleted()
                    for v in jax.tree.leaves(state)),
            f"{why}: the loop keeps the state the step was given, but "
            "step_fn consumed it (a donated train step). Build the step "
            "with donate=False under run_resilient",
            op="run_resilient", error=PreconditionNotMetError)
    return state


def drain_then_commit(wd: CommWatchdog, grace_s: float, commit_fn
                      ) -> Optional[BaseException]:
    """The shared preemption endgame (driver loop + FitResilience): inside
    one watchdog span budgeted at grace_s, flush in-flight async writers
    (logging, not masking, their failures) and take one synchronous commit.
    Returns the commit error instead of raising — the process is already
    dying, and a barrier timeout must not prevent an orderly exit."""
    from ..checkpoint import wait_async_save
    try:
        with wd.watch("preempt_final_commit", timeout=grace_s):
            try:
                wait_async_save()
            except Exception as e:  # the final commit still runs
                sys.stderr.write(f"[resilience] async drain failed during "
                                 f"preemption: {e!r}\n")
            commit_fn()
        return None
    except KeyboardInterrupt:
        raise  # escalation handling is the caller's business
    except BaseException as e:
        sys.stderr.write(f"[resilience] final preemption commit failed "
                         f"(exiting WITHOUT a new checkpoint): {e!r}\n")
        return e


def run_resilient(step_fn: Callable[[Dict, int], Tuple[Dict, Any]],
                  state: Dict, *, steps: int, ckpt_dir: str,
                  ckpt_every: int = 0,
                  store=None, watchdog: Optional[CommWatchdog] = None,
                  step_timeout: Optional[float] = None,
                  abort_on_timeout: bool = False,
                  max_consecutive_nonfinite: Optional[int] = None,
                  grace_s: Optional[float] = None,
                  keep_n: Optional[int] = None,
                  resume: bool = True,
                  layout_extra: Optional[Dict[str, Any]] = None,
                  aggregator=None, numerics=None,
                  on_step: Optional[Callable[[int, Optional[float]], None]]
                  = None) -> Tuple[Dict, Dict[str, Any]]:
    """Drive ``step_fn(state, step) -> (new_state, loss)`` for ``steps``
    steps with checkpoint-restart fault tolerance. Returns
    ``(final_state, info)``; info records resume/preemption/watchdog
    details. `state` must be a (nested) dict of arrays/scalars — the same
    contract as ``save_state_dict``. step_fn must leave the state it was
    given alive: a rejected step (non-finite loss, a numerics skip) falls
    back to it. A hybrid train step donates its inputs by default, so
    build it with ``donate=False`` for this loop.

    aggregator: a fleet :class:`observability.TelemetryAggregator` — the
    loop feeds it every step's wall time (loss forced, so it measures
    execution, not dispatch) and drives its publish/gather cadence; rank
    0's gauges then carry per-host step-time p50/p95 and straggler flags
    (``straggler_detected`` JSONL events). The final fleet report lands
    in ``info["fleet"]``.

    numerics: a :class:`observability.numerics.NumericsGuard` (ISSUE
    15) — after every step the loop feeds it the host-observed loss and
    the new state (the guard polls the telemetry ring on its interval
    cadence and runs the anomaly detectors; one ``numerics_anomaly``
    event + flight-recorder bundle per episode). A CONFIRMED episode
    can act per FLAGS_numerics_action: "skip" rejects the diverging
    step (the found_inf discipline at episode level —
    ``resilience_numerics_skip`` events, ``info["numerics_skips"]``);
    "rollback" reloads the LAST COMMITTED checkpoint and re-trains
    forward from its step (``resilience_numerics_rollback``,
    ``info["numerics_rollbacks"]``; bounded by the monitor's
    max_rollbacks). The ``numerics/spike`` faults-grammar site in this
    loop injects a synthetic host-observed loss spike for end-to-end
    detection tests.

    Crash forensics: when FLAGS_flight_recorder_dir is set, a watchdog
    timeout (the CommWatchdog dumps from its own monitor thread), the
    SIGTERM drain and the non-finite abort each leave a bounded
    flight-recorder bundle (telemetry ring tail, recent JSONL events,
    open spans, heartbeat ages).

    Elastic resume (FLAGS_ckpt_reshard): commits record the topology
    layout (schema v2), and resume compares it against THIS run's `state`
    template — whose arrays' shardings describe the new mesh. On a
    mismatch (mesh shape, partition specs, zero1 on<->off, pp/vpp
    relayout, changed comm plan) the checkpoint is RESHARDED onto the new
    topology instead of failing: params/optimizer state reassemble from
    the chunk index, stacked-block leaves permute across (pp, vpp)
    layouts, and the engine carries follow their remap policies
    (fp8_meta follows its layers, comm_ef resets with a JSONL event when
    the plan changed, telemetry reinitializes). `layout_extra` carries
    the model-level hints both ends need (the hybrid engine attaches the
    dict to the init_state it returns: ``init_state.layout_extra``).
    """
    from ...flags import flag
    from . import faults

    if max_consecutive_nonfinite is None:
        max_consecutive_nonfinite = int(flag("max_consecutive_nonfinite"))
    if grace_s is None:
        grace_s = float(flag("preempt_grace_s"))

    def _emit(event: str, **fields):
        """Crash-forensics JSONL (observability.events): every lifecycle
        decision the loop takes — resume/skip/commit/SIGTERM/abort — lands
        as one flushed line when FLAGS_telemetry_jsonl is set."""
        from ...observability import emit_event
        emit_event(event, **fields)

    wd = watchdog or CommWatchdog(poll_interval=0.2)
    own_wd = watchdog is None
    escalation = {"pending": False}
    prev_on_timeout = wd.on_timeout
    # interrupt_main targets the MAIN thread: escalating from a driver
    # running elsewhere would bomb unrelated main-thread code and never
    # unstick our own loop
    on_main = threading.current_thread() is threading.main_thread()

    def _on_timeout(span, report):
        prev_on_timeout(span, report)
        if abort_on_timeout and on_main and not escalation["pending"]:
            escalation["pending"] = True
            _thread.interrupt_main()  # unstick the step at the next
            #                           interruptible host point
    wd.on_timeout = _on_timeout
    wd.start()

    info: Dict[str, Any] = {"resumed_from": None, "preempted": False,
                            "watchdog_abort": False, "nonfinite_skips": 0,
                            "numerics_skips": 0, "numerics_rollbacks": 0,
                            "final_checkpoint": None}
    start_step = 0
    if resume:
        # with_metadata: discovery's integrity validation already decoded
        # the metadata — reuse it instead of unpickling a second time
        ckpt, md = latest_checkpoint(ckpt_dir, with_metadata=True)
        if ckpt is not None:
            from ..checkpoint import (layout_mismatch, load_metadata,
                                      load_resharded, load_state_dict)
            # the template is mutated in place, which keeps structure-only
            # subtrees (empty dicts) that the returned nested dict drops
            template = {"step": 0, "state": state}
            mismatch = None
            if flag("ckpt_reshard"):
                if md is None:
                    md = load_metadata(ckpt)
                mismatch = layout_mismatch(md, template,
                                           layout_extra=layout_extra)
                if mismatch:
                    # topology changed since the commit: reshard instead
                    # of tripping over a carry shape error mid-restart
                    _emit("resilience_reshard_resume", checkpoint=ckpt,
                          mismatch={k: v for k, v in mismatch.items()})
                    loaded = load_resharded(template, ckpt, metadata=md,
                                            layout_extra=layout_extra)
            if not mismatch:
                loaded = load_state_dict(template, ckpt, metadata=md)
            state, start_step = template["state"], int(loaded["step"])
            info["resumed_from"] = ckpt
            info["resharded"] = bool(mismatch)
            assert start_step == checkpoint_step(ckpt)
            _emit("resilience_resume", checkpoint=ckpt, step=start_step,
                  resharded=bool(mismatch))
    _emit("resilience_run_start", steps=steps, start_step=start_step,
          ckpt_dir=ckpt_dir, ckpt_every=ckpt_every)

    def _commit(next_step, **kw):
        path = commit_checkpoint({"step": next_step, "state": state},
                                 ckpt_dir, next_step, store=store,
                                 keep_n=keep_n, layout_extra=layout_extra,
                                 **kw)
        info["final_checkpoint"] = path
        _emit("resilience_commit", step=next_step, path=path)
        return path

    progress = {"done": start_step, "nonfinite": 0}

    def _loop(sig):
        """One pass over the remaining steps; mutates `state`/`progress`.
        Factored out so run_resilient can wrap the WHOLE loop — headers and
        bookkeeping included — in one KeyboardInterrupt net: the escalation
        interrupt may land at any bytecode, not just inside step_fn."""
        nonlocal state
        for i in range(progress["done"], steps):
            if sig.triggered:
                info["preempted"] = True
                return
            faults.maybe_fail("loop/before_step")
            t_step0 = time.perf_counter()
            with wd.watch("resilient_step", timeout=step_timeout):
                # the wedged-step injection point (hangN clause): stalls
                # INSIDE the watchdog span so the timeout + flight
                # recorder fire, then the step proceeds normally
                faults.maybe_fail("watchdog/hang")
                new_state, loss = step_fn(state, i)
            loss_val = _loss_value(loss)
            if loss_val is not None and faults.maybe_trigger(
                    "numerics/spike"):
                # synthetic loss/grad spike: perturbs only the DRIVER's
                # view of the loss (device state untouched) so the
                # numerics detection + forensics loop can be exercised
                # deterministically (ISSUE 15)
                loss_val = loss_val * 1e6 if loss_val != 0.0 else 1e6
                _emit("numerics_spike_injected", step=i, loss=loss_val)
            guard_action = None
            if numerics is not None:
                guard_action = numerics.after_step(new_state, i, loss_val)
            step_ms = (time.perf_counter() - t_step0) * 1e3
            if loss_val is not None and not math.isfinite(loss_val):
                # found_inf discipline at loop level: reject the step,
                # keep the last good state
                state = _kept(state, "non-finite loss")
                progress["nonfinite"] += 1
                info["nonfinite_skips"] += 1
                _emit("resilience_nonfinite_skip", step=i, loss=loss_val,
                      consecutive=progress["nonfinite"])
                if progress["nonfinite"] >= max_consecutive_nonfinite:
                    from ...amp.grad_scaler import nonfinite_report
                    from ...observability.flight_recorder import maybe_dump
                    maybe_dump("nonfinite_abort", watchdog=wd,
                               extra={"step": i, "loss": loss_val})
                    raise NonFiniteLossError(
                        f"{progress['nonfinite']} consecutive non-finite "
                        f"steps (last loss={loss_val} at step {i}); "
                        f"per-leaf diagnostic of the rejected state:\n"
                        f"{nonfinite_report(new_state)}")
            else:
                progress["nonfinite"] = 0
                if guard_action == "skip":
                    # confirmed-divergence skip: keep the last good state
                    # (the found_inf discipline at episode level)
                    state = _kept(state, "numerics skip")
                    info["numerics_skips"] += 1
                    _emit("resilience_numerics_skip", step=i,
                          loss=loss_val)
                else:
                    state = new_state
            if guard_action == "rollback":
                ckpt, md = latest_checkpoint(ckpt_dir, with_metadata=True)
                if ckpt is None:
                    # nothing committed yet: nothing to roll back to —
                    # record it, REFUND the monitor's rollback budget
                    # (charged at arm time) and keep training; a later
                    # confirmation re-arms once a commit exists
                    numerics.on_rollback_unavailable()
                    _emit("resilience_numerics_rollback_unavailable",
                          step=i)
                else:
                    from ..checkpoint import load_state_dict
                    template = {"step": 0, "state": state}
                    loaded = load_state_dict(template, ckpt, metadata=md)
                    state = template["state"]
                    progress["done"] = int(loaded["step"])
                    info["numerics_rollbacks"] += 1
                    _emit("resilience_numerics_rollback", step=i,
                          to_step=progress["done"], checkpoint=ckpt)
                    # detectors reset + the telemetry host rewinds to
                    # the restored carry's ring count so replayed rows
                    # re-enter detection
                    numerics.on_rollback(state)
                    return "rollback"  # restart the loop from the ckpt
            progress["done"] = i + 1
            if aggregator is not None:
                # float(loss) above forced the step, so this is executed
                # wall time — what the straggler detector must see
                aggregator.note_step(step_ms)
                aggregator.tick(i)
            if on_step is not None:
                on_step(i, loss_val)
            if (ckpt_every and progress["done"] % ckpt_every == 0
                    and not sig.triggered):
                _commit(progress["done"])
            if sig.triggered:
                info["preempted"] = True
                return

    try:
        with SigtermGuard() as sig:
            try:
                # a numerics rollback rewinds progress["done"] to the
                # checkpoint's step and restarts the pass (bounded by
                # the guard monitor's max_rollbacks budget)
                while _loop(sig) == "rollback":
                    pass
                done = progress["done"]
                if (not info["preempted"] and done > start_step
                        and ckpt_every and done % ckpt_every):
                    # clean end of run between cadence points: commit the
                    # tail. Inside the interrupt net: a late escalation
                    # interrupt (step overran its budget but completed just
                    # as the watchdog fired) may land HERE mid-commit — the
                    # commit is crash-safe and the handler below redoes it.
                    _commit(done)
            except KeyboardInterrupt:
                if not escalation["pending"]:
                    raise  # a genuine Ctrl-C, not our escalation
                info["watchdog_abort"] = True
                info["preempted"] = True
            done = progress["done"]
            if info["preempted"]:
                _emit("resilience_sigterm", step=done,
                      watchdog_abort=info["watchdog_abort"])
                from ...observability.flight_recorder import maybe_dump
                maybe_dump("watchdog_abort" if info["watchdog_abort"]
                           else "sigterm", watchdog=wd,
                           extra={"step": done})
                # preemption drain: flush in-flight async writers, then one
                # final SYNCHRONOUS commit inside the grace budget
                t0 = time.monotonic()
                try:
                    err = drain_then_commit(
                        wd, grace_s,
                        lambda: _commit(done, barrier_timeout=grace_s))
                except KeyboardInterrupt:
                    if not escalation["pending"]:
                        raise
                    # the single escalation interrupt landed during the
                    # drain instead of the loop; the commit is crash-safe
                    # and no further interrupt can fire — retry once
                    info["watchdog_abort"] = True
                    err = drain_then_commit(
                        wd, grace_s,
                        lambda: _commit(done, barrier_timeout=grace_s))
                if err is not None:
                    info["final_commit_error"] = repr(err)
                info["grace_used_s"] = time.monotonic() - t0
    finally:
        wd.on_timeout = prev_on_timeout
        if own_wd:
            wd.stop()
    done = progress["done"]

    info["completed_steps"] = done
    info["watchdog"] = wd.stats()
    if numerics is not None:
        # drain the partial tail interval so an end-of-run anomaly still
        # reaches the detectors/forensics
        try:
            numerics.flush(state)
        except Exception as e:
            sys.stderr.write(f"[resilience] numerics flush failed: "
                             f"{e!r}\n")
        info["numerics_anomalies"] = len(numerics.monitor.anomalies)
    if aggregator is not None:
        info["fleet"] = aggregator.last_report
    _emit("resilience_run_end", completed_steps=done,
          preempted=info["preempted"],
          watchdog_abort=info["watchdog_abort"],
          nonfinite_skips=info["nonfinite_skips"],
          final_checkpoint=info["final_checkpoint"])
    if info["watchdog_abort"]:
        raise WatchdogTimeout(
            f"step {done} exceeded its {step_timeout}s budget; final "
            f"checkpoint committed at {info['final_checkpoint']}"
            + (f" (final commit FAILED: {info['final_commit_error']})"
               if "final_commit_error" in info else ""))
    return state, info
