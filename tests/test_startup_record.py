"""The program's record of its own start-up (observability/startup.py):
jax's compile events as named, back-dated events, the ring that keeps them
without a profiler session, and `startup_record`. What an ENGINE adds (the
first-call span, the two prom series, `program_compiled`) is held where an
engine is already built: `tests/test_program_tracing.py`, `mixed_engine`.
"""

import threading

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import startup
from paddle_tpu.observability.trace import (COMPILE_ATTRS, COMPILE_CACHE,
                                            COMPILE_SPANS, STARTUP_SPANS)
from paddle_tpu.profiler.utils import (EventCollector, HostEvent, KEPT_TYPES,
                                       collector)

from chipbench import harness


def _probe():
    """A NEW function each call, the same program each time: jax's own
    caches key on the function, the persistent cache on the program."""
    def startup_probe(x):
        return jnp.tanh(x) * 3 + 1
    return jax.jit(startup_probe)


def _since(n0, name):
    """The events of one name the ring got after its `n0`-th."""
    kept = collector.kept()
    return [e for e in kept[len(kept) - (collector.kept_total - n0):]
            if e.name == name]


@pytest.fixture
def persistent_cache(tmp_path):
    """jax's persistent cache in `tmp_path`, both thresholds open."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = [getattr(jax.config, n) for n in names]
    cc.reset_cache()
    for n, v in zip(names, (str(tmp_path), 0.0, -1)):
        jax.config.update(n, v)
    yield tmp_path
    for n, v in zip(names, before):
        jax.config.update(n, v)
    cc.reset_cache()


def test_a_compilation_is_three_named_events_and_says_what_the_cache_did(
        persistent_cache):
    x = jnp.ones((4,))
    n0 = collector.kept_total
    _probe()(x)
    first = {n: _since(n0, n) for n in COMPILE_SPANS}
    assert [len(v) for v in first.values()] == [1, 1, 1]
    trace, lower, backend = (first[n][0] for n in COMPILE_SPANS)
    assert trace.attrs["fun"] == "startup_probe"
    assert lower.attrs["fun"] == backend.attrs["fun"] == "jit(startup_probe)"
    assert backend.attrs["cache"] == COMPILE_CACHE.compiled
    for e in (trace, lower, backend):
        assert e.event_type == "Compile" and e.duration > 0
        assert set(e.attrs) <= set(COMPILE_ATTRS)
        assert e.tid == threading.get_ident()
    # back-dated on the spans' clock, in the order they happened
    assert trace.end <= lower.end <= backend.start + 1e-3

    n1 = collector.kept_total
    _probe()(x)     # the same program from another function: a hit
    hit, = _since(n1, COMPILE_SPANS.backend)
    assert hit.attrs["cache"] == COMPILE_CACHE.hit
    assert hit.attrs["retrieval_us"] > 0 and "saved_us" in hit.attrs


def test_without_a_cache_directory_a_compilation_is_uncached():
    if jax.config.jax_compilation_cache_dir:
        pytest.skip("the environment places a persistent cache")
    x = jnp.ones((5,))      # the array's own program is not the probe's
    n0 = collector.kept_total
    _probe()(x)
    backend, = _since(n0, COMPILE_SPANS.backend)
    assert backend.attrs == {"fun": "jit(startup_probe)",
                             "cache": COMPILE_CACHE.off}


def test_a_trace_inside_another_stage_is_nested_or_dropped():
    @jax.jit
    def inner(x):
        return x + 1

    @jax.jit
    def outer(x):
        return inner(x) * 2
    x = jnp.ones((6,))
    n0 = collector.kept_total
    outer(x)
    traces = _since(n0, COMPILE_SPANS.trace)
    assert [e.attrs["fun"] for e in traces if "nested" not in e.attrs] == \
        ["outer"]
    # a short inner trace is not kept; a long one is, marked
    assert all(e.attrs.get("nested") == 1 and e.duration >= 1e-3
               for e in traces if e.attrs["fun"] != "outer")


def test_the_ring_keeps_them_with_no_session_and_a_session_gets_them_too():
    assert not collector.enabled
    collector.clear()
    x, y = jnp.ones((7,)), jnp.ones((8,))
    n0 = collector.kept_total
    _probe()(x)
    with obs.span("not_kept"):
        pass
    assert collector.kept_total - n0 == 3
    assert {e.event_type for e in collector.kept()} <= set(KEPT_TYPES)
    assert collector.drain() == []      # no session: nothing else is held
    with obs.capture_spans() as cap:
        _probe()(y)
    got = [e.name for e in cap.events if e.event_type == "Compile"]
    assert sorted(got) == sorted(COMPILE_SPANS)
    assert collector.kept_total - n0 == 6
    chrome = [e.chrome() for e in cap.events
              if e.name == COMPILE_SPANS.backend]
    assert chrome[0]["cat"] == "Compile"
    assert chrome[0]["args"]["fun"] == "jit(startup_probe)"


def test_the_ring_is_capped_and_counts_what_it_let_go():
    ring = EventCollector(keep=4)
    for i in range(6):
        ring.add(HostEvent(COMPILE_SPANS.trace, i, i + 0.5, 1, "Compile"))
    ring.add(HostEvent("serving_step", 9.0, 9.5, 1))
    assert [e.start for e in ring.kept()] == [2, 3, 4, 5]
    assert ring.kept_total == 6 and ring.drain() == []


def _record_of(monkeypatch, events, until_s=None):
    ring = EventCollector()
    t0 = startup.PROCESS_T0
    for name, start, end, attrs in events:
        etype = "Startup" if name in STARTUP_SPANS else "Compile"
        attrs = dict(attrs)
        ring.add(HostEvent(name, t0 + start, t0 + end, attrs.pop("tid", 1),
                           etype, attrs=attrs))
    monkeypatch.setattr(startup, "collector", ring)
    monkeypatch.setattr(startup, "_import_event", None)
    return startup.startup_record(until_s)


def test_the_record_cuts_by_end_time_and_counts_a_second_once(monkeypatch):
    T, L, B = COMPILE_SPANS
    events = [
        (STARTUP_SPANS.import_, 0.1, 1.1, {}),
        (T, 2.0, 4.0, {"fun": "step"}),
        (T, 2.5, 3.0, {"fun": "kernel", "nested": 1}),     # inside `step`
        (T, 3.5, 4.5, {"fun": "other"}),                   # overlaps it
        (T, 2.0, 3.0, {"fun": "elsewhere", "tid": 2}),     # another thread
        (L, 5.0, 6.0, {"fun": "jit(step)"}),
        (B, 6.0, 6.5, {"fun": "jit(step)", "cache": "hit",
                       "retrieval_us": 400000, "saved_us": 9}),
        (B, 7.0, 9.0, {"fun": "jit(cold)", "cache": "compiled"}),
        (B, 9.0, 9.25, {"fun": "jit(eager)", "cache": "off"}),
        (B, 5.2, 5.4, {"fun": "jit(in_lower)", "cache": "compiled",
                       "nested": 1}),
        (B, 8.0, 12.0, {"fun": "jit(late)", "cache": "compiled",
                        "tid": 2})]
    r = _record_of(monkeypatch, events)
    assert r["import_s"] == pytest.approx(1.0)
    assert r["trace_s"] == pytest.approx(2.5 + 1.0)    # union, a thread each
    assert r["lower_s"] == pytest.approx(1.0)
    assert (r["cache_load_s"], r["compiled_s"], r["uncached_s"]) == \
        pytest.approx((0.5, 2.0 + 4.0, 0.25))
    assert (r["programs"], r["cache_hits"], r["compiled"], r["uncached"]) \
        == (5, 1, 3, 1)
    assert r["events"] == len(events) and r["dropped"] == 0
    assert r["longest"][0] == {"fun": "jit(late)", "stage": "backend",
                               "seconds": pytest.approx(4.0),
                               "cache": "compiled"}
    assert len(r["longest"]) == 10
    # `jit(late)` began before the cut and ended behind it: not set-up's
    cut = _record_of(monkeypatch, events, until_s=9.1)
    assert cut["compiled_s"] == pytest.approx(2.0) and cut["compiled"] == 2
    assert cut["uncached"] == 0 and cut["trace_s"] == r["trace_s"]


def test_the_live_record_has_the_import_and_no_negative_part():
    r = obs.startup_record()
    assert r["import_s"] > 0
    assert all(v >= 0 for k, v in r.items() if k != "longest")
    assert r["programs"] == r["cache_hits"] + r["compiled"] + r["uncached"]
    # the record's zero is the process's start: before the import began
    import paddle_tpu
    assert startup.PROCESS_T0 <= paddle_tpu._IMPORT_T0
    assert obs.startup_record(until_s=0.0)["events"] == 0


def test_the_step_timer_carries_the_inside_figures():
    t, x = obs.StepTimer(), jnp.ones((9,))
    with t.step():
        _probe()(x)
    rep = t.report()
    assert rep["compile_s"] > 0
    assert tuple(rep["compile_breakdown"]) == startup.BREAKDOWN_KEYS
    assert all(v >= 0 for v in rep["compile_breakdown"].values())


def test_the_benchmarks_reader_cuts_the_record_at_the_set_up(capsys):
    run = {"facts": {}, "trace": None, "e2e": {"setup_s": 1e9}}
    value = harness.read_metric("setup_compiled_s", run)
    assert value == obs.startup_record()["compiled_s"]
    assert capsys.readouterr().out.count("[startup] {") == 1
    # a set-up that built no program has nothing to put down to a cache
    assert harness.read_metric("setup_compiled_s",
                               dict(run, e2e={"setup_s": -1.0})) is None
    assert harness.read_metric("setup_compiled_s",
                               {"facts": {}, "trace": None}) is None
