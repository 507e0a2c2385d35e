"""Profiler (reference: python/paddle/profiler/profiler.py — ProfilerState
:89, targets :110, scheduler windows make_scheduler, export_chrome_tracing
:227; statistics tables profiler_statistic.py).

TPU design: the device timeline comes from jax.profiler (XPlane → TensorBoard
/ Perfetto); this Profiler adds the reference's scheduling state machine,
host-span summary tables, and a self-contained chrome-trace export so users
get the familiar workflow (start/step/stop, summary()) without extra tools.
"""

from __future__ import annotations

import enum
import json
import os
import time
from collections import defaultdict
from typing import Callable, Iterable, List, Optional, Sequence

from .utils import HostEvent, RecordEvent, collector

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "make_scheduler",
           "export_chrome_tracing", "RecordEvent", "SummaryView"]


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # last record step of a window


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0
                   ) -> Callable[[int], ProfilerState]:
    """Window state machine (reference semantics): skip_first CLOSED steps,
    then cycles of closed→ready→record; repeat=0 cycles forever."""
    assert closed >= 0 and ready >= 0 and record > 0
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        cycle = s // period
        if repeat and cycle >= repeat:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD  # record everything until stop()


def export_chrome_tracing(dir_name: Optional[str] = None,
                          worker_name: Optional[str] = None) -> Callable:
    """on_trace_ready callback writing chrome://tracing JSON. dir_name
    defaults to FLAGS_profiler_dir."""
    if dir_name is None:
        from ..flags import flag
        dir_name = flag("profiler_dir")

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name,
                            f"{name}_step{prof.step_num}.json")
        events = [ev.chrome() for ev in prof._recorded]
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        prof.last_export_path = path

    return handler


class SummaryView:
    """Aggregated per-name host-span stats (reference: profiler_statistic
    summary tables)."""

    def __init__(self, events: Sequence[HostEvent]):
        from .utils import Stat
        agg = defaultdict(Stat)
        for e in events:
            agg[e.name].add(e.duration)
        self.rows = {k: {"calls": s.count, "total": s.total, "min": s.min,
                         "max": s.max, "avg": s.avg}
                     for k, s in agg.items()}

    def __str__(self):
        if not self.rows:
            return "(no events recorded)"
        w = max(len(k) for k in self.rows)
        lines = [f"{'Name'.ljust(w)}  Calls     Total(ms)   Avg(ms)   "
                 f"Min(ms)   Max(ms)"]
        for k, r in sorted(self.rows.items(), key=lambda kv: -kv[1]["total"]):
            lines.append(
                f"{k.ljust(w)}  {r['calls']:5d}  {r['total']*1e3:10.3f}  "
                f"{r['avg']*1e3:8.3f}  {r['min']*1e3:8.3f}  "
                f"{r['max']*1e3:8.3f}")
        return "\n".join(lines)


class Profiler:
    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 profile_memory: bool = False, with_flops: bool = False,
                 timer_only: bool = False):
        del profile_memory, with_flops
        self.targets = list(targets or [ProfilerTarget.CPU,
                                        ProfilerTarget.TPU])
        if scheduler is None:
            self.scheduler = _default_scheduler
        elif callable(scheduler):
            self.scheduler = scheduler
        else:  # (start, end) tuple shorthand, reference behavior
            start, end = scheduler
            self.scheduler = make_scheduler(closed=max(start, 0), ready=0,
                                            record=end - start, repeat=1)
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self._recorded: List[HostEvent] = []   # current window only
        self._history: List[HostEvent] = []    # finished windows (summary)
        self._jax_trace_dir: Optional[str] = None
        self.last_export_path: Optional[str] = None

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        self.state = self.scheduler(self.step_num)
        self._apply_state()
        return self

    def stop(self):
        if self.state in (ProfilerState.RECORD,
                          ProfilerState.RECORD_AND_RETURN):
            self._finish_window()
        collector.enabled = False
        self._stop_jax_trace()
        self.state = ProfilerState.CLOSED

    def step(self):
        prev = self.state
        if prev in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._recorded.extend(collector.drain())
        self.step_num += 1
        self.state = self.scheduler(self.step_num)
        if prev == ProfilerState.RECORD_AND_RETURN or (
                prev == ProfilerState.RECORD
                and self.state not in (ProfilerState.RECORD,
                                       ProfilerState.RECORD_AND_RETURN)):
            self._finish_window()
        self._apply_state()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- internals -----------------------------------------------------------
    def _apply_state(self):
        recording = self.state in (ProfilerState.RECORD,
                                   ProfilerState.RECORD_AND_RETURN)
        if recording and not collector.enabled:
            collector.clear()
            collector.enabled = True
            if not self.timer_only:
                self._start_jax_trace()
        elif not recording and collector.enabled:
            collector.enabled = False
            self._stop_jax_trace()

    def _start_jax_trace(self):
        if ProfilerTarget.TPU not in self.targets:
            return
        try:
            import tempfile
            import jax.profiler
            self._jax_trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_prof_")
            jax.profiler.start_trace(self._jax_trace_dir)
        except Exception:
            self._jax_trace_dir = None

    def _stop_jax_trace(self):
        if self._jax_trace_dir is not None:
            try:
                import jax.profiler
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_trace_dir = None

    def _finish_window(self):
        self._recorded.extend(collector.drain())
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)  # sees only this window's events
        # windows export independently; summary() still sees everything
        self._history.extend(self._recorded)
        self._recorded = []

    # -- reporting -----------------------------------------------------------
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms") -> SummaryView:
        del sorted_by, op_detail, thread_sep, time_unit
        return SummaryView(self._history + self._recorded)
