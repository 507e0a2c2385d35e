"""The program's record of its own start-up: what happens before the first
useful step, by name.

One listener on jax's own compile events, registered when this package is
imported, turns each trace, lowering and backend compilation into one
back-dated ``HostEvent`` (``trace.COMPILE_SPANS``, ``event_type="Compile"``)
that carries jax's name of the function and, on a backend compilation,
whether the persistent cache held the program (``trace.COMPILE_CACHE``).
Three live spans (``trace.STARTUP_SPANS``, ``event_type="Startup"``) mark
the start-up work the program does itself: its import, an engine's
construction, the first call of each compiled variant of the serving step.
The collector keeps both types in a ring of their own whether or not a
profiler session is on (``profiler.utils.KEPT_TYPES``); with a session on
they reach it as every span does.

``startup_record(until_s)`` reads the ring: seconds by kind, counts, and
the longest compilations. Its zero is the process's start as the operating
system gives it, so ``until_s`` is an age of the process.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax
from jax import monitoring

from .. import _IMPORT_T0
from ..profiler.utils import (HostEvent, RecordEvent, collector,
                              open_span_names, record_interval)
from .trace import (COMPILE_CACHE, COMPILE_SPANS, FIRST_CALL_ATTRS,
                    SERVING_SPANS, STARTUP_SPANS)

__all__ = ["startup_record", "compiled_since", "take_recompiles",
           "note_import", "FirstCall", "NO_SPAN", "RECOMPILES", "PROCESS_T0",
           "BREAKDOWN_KEYS"]

# jax's duration events (jax/_src/dispatch.py), each fired with `fun_name=`
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": COMPILE_SPANS.trace,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": COMPILE_SPANS.lower,
    "/jax/core/compile/backend_compile_duration": COMPILE_SPANS.backend}
# and what fires inside a backend compile that asks the persistent cache
# (jax/_src/compiler.py compile_or_get_cached; with no cache directory jax
# still "asks", of nothing: that is no question). `cache_misses` is not
# here: jax fires it only where an entry is WRITTEN, which the size and
# time thresholds suppress.
_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_HIT_FIGURES = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_us",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_us"}
_STAGE_OF = {name: stage for stage, name in COMPILE_SPANS._asdict().items()}

# Every `jit` that a trace, or a lowering (a kernel's body), meets is traced
# inside it and reported like it, and every function of jax.numpy is one:
# thousands an engine step. An event with another stage open around it on
# its thread carries `nested=1` and adds to no sum (its seconds are its
# outermost stage's, so the sums never count a second twice); a nested trace
# is kept at all only from this length on (a kernel's body under its own
# `jit`: it names where a long trace or lowering went), so the ring holds a
# start-up.
_NESTED_TRACE_MIN_S = 1e-3

_asking = threading.local()     # .cache: this thread's open question;
#                                 .depth: stages open on this thread
_seen = [0]                     # stage events the listener was handed
# Backend compilations that fired inside the dispatch of a serving variant
# that had run before, until the engine that dispatched takes them.
RECOMPILES: collections.deque = collections.deque(maxlen=256)
_recompiles_lock = threading.Lock()
# the outside figures `StepTimer.report()` carries beside its `compile_s`
BREAKDOWN_KEYS = ("trace_s", "lower_s", "cache_load_s", "compiled_s",
                  "uncached_s")


def _process_start() -> float:
    """The process's start on `perf_counter`: /proc/self/stat's field 22
    (clock ticks after boot) against CLOCK_BOOTTIME; where that cannot be
    read, the first line of `paddle_tpu/__init__.py`."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()     # from field 3
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return _IMPORT_T0
    return min(time.perf_counter() - age, _IMPORT_T0) if age >= 0 \
        else _IMPORT_T0


PROCESS_T0 = _process_start()
_import_event: Optional[HostEvent] = None   # the ring may let it go


def note_import(end: float):
    """`startup_import`: from the first line of `paddle_tpu/__init__.py`
    to `end`, its last."""
    global _import_event
    _import_event = record_interval(STARTUP_SPANS.import_, _IMPORT_T0, end,
                                    "Startup")


# -- the listener -----------------------------------------------------------
def _on_event(event: str, **kw):
    if event == _ASKED:
        _asking.cache = ({"at": time.perf_counter()}
                         if jax.config.jax_compilation_cache_dir else None)
    elif event == _HIT:
        cache = getattr(_asking, "cache", None)
        if cache is not None:
            cache["hit"] = True


def _verdict(start: float) -> Dict[str, Any]:
    """`cache` of the backend compile that began at `start` on this thread
    and ends now, with jax's two figures on a hit."""
    cache, _asking.cache = getattr(_asking, "cache", None), None
    if cache is None or cache["at"] < start - 0.01:
        return {"cache": COMPILE_CACHE.off}
    if not cache.get("hit"):
        return {"cache": COMPILE_CACHE.compiled}
    return {"cache": COMPILE_CACHE.hit,
            **{k: cache.get(k, 0) for k in _HIT_FIGURES.values()}}


def _on_scalar(event: str, value, **kw):
    if event in _STAGES:    # jax reports a span's start as a scalar
        _asking.depth = getattr(_asking, "depth", 0) + 1


def _on_duration(event: str, duration: float, **kw):
    name = _STAGES.get(event)
    if name is None:
        figure = _HIT_FIGURES.get(event)
        cache = getattr(_asking, "cache", None)
        if figure is not None and cache is not None:
            cache[figure] = int(round(duration * 1e6))
        return
    _seen[0] += 1
    depth = _asking.depth = max(getattr(_asking, "depth", 1) - 1, 0)
    if (depth and name == COMPILE_SPANS.trace
            and duration < _NESTED_TRACE_MIN_S):
        return
    end = time.perf_counter()
    attrs = {"fun": str(kw.get("fun_name", ""))}
    if depth:
        attrs["nested"] = 1
    recompile = False
    if name == COMPILE_SPANS.backend:
        attrs.update(_verdict(end - duration))
        spans = open_span_names()
        recompile = (SERVING_SPANS.dispatch in spans
                     and STARTUP_SPANS.program not in spans)
        if recompile:
            attrs["recompile"] = 1
    ev = record_interval(name, end - duration, end, "Compile", **attrs)
    if recompile:
        with _recompiles_lock:
            RECOMPILES.append(ev)


monitoring.register_event_listener(_on_event)
monitoring.register_scalar_listener(_on_scalar)
monitoring.register_event_duration_secs_listener(_on_duration)


# -- reading the ring -------------------------------------------------------
def _union_s(events) -> float:
    """Seconds some event of `events` covers, thread by thread: an inner
    `jit` is traced inside its caller's trace, and a second is counted
    once."""
    total = 0.0
    by_tid = collections.defaultdict(list)
    for e in events:
        by_tid[e.tid].append((e.start, e.end))
    for spans in by_tid.values():
        spans.sort()
        lo, hi = spans[0]
        for s, e in spans[1:]:
            if s > hi:
                total += hi - lo
                lo, hi = s, e
            else:
                hi = max(hi, e)
        total += hi - lo
    return total


def startup_record(until_s: Optional[float] = None) -> Dict[str, Any]:
    """What the kept events that ENDED within `until_s` seconds of the
    process's start say of it (all of them, if None). Seconds, each the
    union of its events' intervals on a thread, the events with no other
    stage open around them (a kernel's body traced inside a lowering is
    that lowering's): `import_s`; `engine_build_s` and `first_call_s` (the
    other two live spans, which CONTAIN compile events); `trace_s`,
    `lower_s`; backend compilations by what the persistent cache did:
    `cache_load_s` (it held the program), `compiled_s` (it was asked and
    did not), `uncached_s` (it was never asked, whatever the machine
    holds). Counts: `programs` (backend compilations, nested or not) =
    `cache_hits` + `compiled` + `uncached`; `recompiles`; `events` read,
    `dropped` (the ring's cap let them go, oldest first) and `seen` (stage
    events jax handed the listener in the process's life so far, the
    short nested traces it did not keep included). `longest`: the
    ten longest compile events (`fun`, `stage`, `seconds`, `cache` on a
    backend one, `nested` on one inside another stage)."""
    cut = float("inf") if until_s is None else PROCESS_T0 + until_s
    kept = collector.kept()
    dropped = collector.kept_total - len(kept)
    if _import_event is not None and all(e is not _import_event
                                         for e in kept):
        kept.insert(0, _import_event)   # the ring had let it go
    events = [e for e in kept if e.end <= cut]
    named = collections.defaultdict(list)   # the outermost, by name
    for e in events:
        if not e.attrs.get("nested"):
            named[e.name].append(e)
    programs = [e for e in events if e.name == COMPILE_SPANS.backend]
    backend = collections.defaultdict(list)
    for e in named[COMPILE_SPANS.backend]:
        backend[e.attrs["cache"]].append(e)
    results = collections.Counter(e.attrs["cache"] for e in programs)
    compiles = sorted((e for e in events if e.event_type == "Compile"),
                      key=lambda e: -e.duration)
    return {
        "import_s": _union_s(named[STARTUP_SPANS.import_]),
        "engine_build_s": _union_s(named[STARTUP_SPANS.engine]),
        "first_call_s": _union_s(named[STARTUP_SPANS.program]),
        "trace_s": _union_s(named[COMPILE_SPANS.trace]),
        "lower_s": _union_s(named[COMPILE_SPANS.lower]),
        "cache_load_s": _union_s(backend[COMPILE_CACHE.hit]),
        "compiled_s": _union_s(backend[COMPILE_CACHE.compiled]),
        "uncached_s": _union_s(backend[COMPILE_CACHE.off]),
        "programs": len(programs),
        "cache_hits": results[COMPILE_CACHE.hit],
        "compiled": results[COMPILE_CACHE.compiled],
        "uncached": results[COMPILE_CACHE.off],
        "recompiles": sum(1 for e in programs if e.attrs.get("recompile")),
        "events": len(events),
        "dropped": dropped,
        "seen": _seen[0],
        "longest": [
            {"fun": e.attrs.get("fun", ""), "stage": _STAGE_OF[e.name],
             "seconds": e.duration,
             **{k: e.attrs[k] for k in ("cache", "nested") if k in e.attrs}}
            for e in compiles[:10]]}


def compiled_since(t: float) -> Dict[str, Any]:
    """The outermost compile events of the calling thread that ended at or
    after `t` (perf_counter): the time they cover by stage in whole
    microseconds (`trace_us`, `lower_us`, `backend_us`), `results` (backend
    compilations counted by `cache`), and the `fun` and `cache` of the
    longest backend compilation ("" where there was none)."""
    tid = threading.get_ident()
    mine = [e for e in collector.kept() if e.event_type == "Compile"
            and e.tid == tid and e.end >= t and not e.attrs.get("nested")]
    by_name = collections.defaultdict(list)
    for e in mine:
        by_name[e.name].append(e)
    backend = by_name[COMPILE_SPANS.backend]
    program = max(backend, key=lambda e: e.duration, default=None)
    return {
        **{stage + "_us": int(round(1e6 * _union_s(by_name[name])))
           for stage, name in COMPILE_SPANS._asdict().items()},
        "results": dict(collections.Counter(e.attrs["cache"]
                                            for e in backend)),
        "fun": program.attrs["fun"] if program else "",
        "cache": program.attrs["cache"] if program else ""}


def take_recompiles() -> List[HostEvent]:
    """The calling thread's entries of RECOMPILES, taken out of it."""
    tid = threading.get_ident()
    with _recompiles_lock:
        mine = [e for e in RECOMPILES if e.tid == tid]
        for e in mine:
            RECOMPILES.remove(e)
    return mine


# -- the first call of a compiled variant -----------------------------------
NO_SPAN = contextlib.nullcontext()     # around a call of a known variant


class FirstCall(RecordEvent):
    """`startup_program_first_call`: the live span around the first call
    of one variant (`k`, `spec`) of the serving step, which traces, lowers
    and compiles or loads it. Where the call returns, `note(k, spec,
    since)` says what `compiled_since` the span's start read, and the span
    closes with it (FIRST_CALL_ATTRS)."""

    def __init__(self, note, k, spec):
        super().__init__(STARTUP_SPANS.program, "Startup",
                         **dict(zip(FIRST_CALL_ATTRS, (k, int(spec)))))
        self._note = note

    def __exit__(self, exc_type, *exc):
        if exc_type is None:
            done = self._note(self.attrs["k"], self.attrs["spec"],
                              self._start)
            self.set(**{a: done[a] for a in FIRST_CALL_ATTRS[2:]})
        return super().__exit__(exc_type, *exc)
