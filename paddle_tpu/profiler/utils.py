"""Host-side event recording (reference: RecordEvent spans emitted by the
C++ HostTracer, paddle/fluid/platform/profiler/host_tracer.cc; Python
surface python/paddle/profiler/utils.py RecordEvent).

TPU design: device-side tracing belongs to jax.profiler (XPlane/Perfetto);
host spans are collected in-process so the Profiler can build the summary
tables and a chrome trace without any vendor tooling. Every span is ALSO a
jax.profiler.TraceAnnotation, whoever started the profiler session (this
package's Profiler, `jax.profiler.start_trace`/`start_server`, a
benchmark): the program's spans sit on the host plane of the same
`.xplane.pb` as the device operations, on their clock, with their
attributes as the event's stats. With no session a TraceAnnotation is a
flag check.
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..flags import flag as _flag

__all__ = ["RecordEvent", "HostEvent", "EventCollector", "collector", "Stat",
           "active_spans", "open_span_names", "record_interval",
           "KEPT_TYPES"]

# Event types the collector keeps whether or not a session is on: the
# program's start-up and jax's compilations (observability/startup.py).
KEPT_TYPES = ("Compile", "Startup")


class Stat:
    """count/total/min/max/avg accumulator shared by the timer and the
    profiler summary."""

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = 0.0

    def add(self, v: float):
        self.count += 1
        self.total += v
        self.min = min(self.min, v)
        self.max = max(self.max, v)

    @property
    def avg(self) -> float:
        return self.total / self.count if self.count else 0.0


@dataclass
class HostEvent:
    name: str
    start: float          # perf_counter seconds
    end: float
    tid: int
    event_type: str = "UserDefined"
    span_id: int = 0      # unique in the process
    parent: Optional[int] = None   # span_id of the span open on this
    #                                thread when this one began
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def duration(self) -> float:
        return self.end - self.start

    def chrome(self) -> Dict[str, Any]:
        """This span as one chrome://tracing complete event."""
        return {"name": self.name, "ph": "X", "cat": self.event_type,
                "ts": self.start * 1e6, "dur": self.duration * 1e6,
                "pid": os.getpid(), "tid": self.tid,
                "args": {"span_id": self.span_id, "parent": self.parent,
                         **self.attrs}}


class EventCollector:
    """Process-global host event sink; enabled by an active Profiler.
    Events of a type in KEPT_TYPES are also kept in a ring of their own,
    session or none: the newest `keep` of them, `kept_total` counted."""

    def __init__(self, keep: int = 4096):
        self._events: List[HostEvent] = []
        self._lock = threading.Lock()
        self.enabled = False
        self._kept: collections.deque = collections.deque(maxlen=keep)
        self.kept_total = 0

    def kept(self) -> List[HostEvent]:
        """The ring, oldest first."""
        with self._lock:
            return list(self._kept)

    def add(self, ev: HostEvent):
        if ev.event_type in KEPT_TYPES:
            with self._lock:
                self._kept.append(ev)
                self.kept_total += 1
        if not self.enabled:
            if _flag("enable_host_event_recorder_hook"):
                with self._lock:
                    self._events.append(ev)
                return
            return
        with self._lock:
            self._events.append(ev)

    def drain(self) -> List[HostEvent]:
        with self._lock:
            out, self._events = self._events, []
        return out

    def clear(self):
        self.drain()


collector = EventCollector()

# Open (begun, not yet ended) RecordEvent spans, keyed by span identity.
# Always tracked — one dict insert/remove per span — because the hang
# flight recorder must see what was in flight when a pod wedges, which is
# exactly when no profiler session is active.
_OPEN_SPANS: dict = {}
_OPEN_LOCK = threading.Lock()
_SPAN_IDS = itertools.count(1)
_STACK = threading.local()     # .spans: this thread's open span ids


def _open_ids() -> list:
    """This thread's stack of open span ids."""
    stack = getattr(_STACK, "spans", None)
    if stack is None:
        stack = _STACK.spans = []
    return stack


def active_spans():
    """Snapshot of currently-open host spans as
    ``[{"name", "age_s", "tid", "event_type"}, ...]``, oldest first — the
    flight recorder's 'what was running when we hung' view."""
    now = time.perf_counter()
    with _OPEN_LOCK:
        spans = list(_OPEN_SPANS.values())
    out = [{"name": name, "age_s": round(now - start, 6), "tid": tid,
            "event_type": etype}
           for (name, start, tid, etype) in spans]
    out.sort(key=lambda s: -s["age_s"])
    return out


def open_span_names() -> List[str]:
    """Names of the spans open on the calling thread."""
    tid = threading.get_ident()
    with _OPEN_LOCK:
        return [s[0] for s in _OPEN_SPANS.values() if s[2] == tid]


def record_interval(name: str, start: float, end: float,
                    event_type: str = "UserDefined", **attrs) -> HostEvent:
    """A span whose start and end (perf_counter seconds) the caller kept
    itself and which has already ended: a phase of a request's life, known
    only when the next one begins. It goes to the collector with no
    parent; a TraceAnnotation cannot be back-dated, so a profiler session
    does not see it."""
    ev = HostEvent(name, start, end, threading.get_ident(), event_type,
                   span_id=next(_SPAN_IDS), attrs=attrs)
    collector.add(ev)
    return ev


class RecordEvent:
    """Context manager/decorator recording one host span.

    Usage: ``with profiler.RecordEvent("forward", step=3): ...``. A span
    records its name, start, end, the span that was open on its thread
    when it began (``HostEvent.parent``) and its keyword attributes
    (``HostEvent.attrs``; numbers or strings). It is a
    jax.profiler.TraceAnnotation too, so any profiler session shows it
    beside the device operations, attributes as the event's stats. What
    is learnt only while the span is open joins them through ``set``."""

    def __init__(self, name: str, event_type: str = "UserDefined", **attrs):
        self.name = name
        self.event_type = event_type
        self.attrs = attrs
        self._start: Optional[float] = None
        self._jax_ctx = None
        self._id = 0
        self._parent: Optional[int] = None

    def begin(self):
        stack = _open_ids()
        self._id = next(_SPAN_IDS)
        self._parent = stack[-1] if stack else None
        stack.append(self._id)
        self._start = time.perf_counter()
        with _OPEN_LOCK:
            _OPEN_SPANS[id(self)] = (self.name, self._start,
                                     threading.get_ident(), self.event_type)
        self._jax_ctx = TraceAnnotation(self.name, **self.attrs)
        self._jax_ctx.__enter__()

    def set(self, **attrs):
        """Attributes learnt while the span is open: merged into its own,
        and into the open annotation's stats. After ``end()`` the span is
        recorded as it was, and this does nothing."""
        if self._start is None:
            return
        self.attrs.update(attrs)
        self._jax_ctx.set_metadata(**attrs)

    def end(self):
        if self._start is None:
            return
        end = time.perf_counter()
        self._jax_ctx.__exit__(None, None, None)
        self._jax_ctx = None
        with _OPEN_LOCK:
            _OPEN_SPANS.pop(id(self), None)
        stack = _open_ids()
        if self._id in stack:     # begin()/end() pairs need not nest, and
            stack.remove(self._id)   # end() may run on another thread
        collector.add(HostEvent(self.name, self._start, end,
                                threading.get_ident(), self.event_type,
                                self._id, self._parent, self.attrs))
        self._start = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with RecordEvent(self.name or fn.__qualname__, self.event_type,
                             **self.attrs):
                return fn(*a, **kw)
        return wrapped
