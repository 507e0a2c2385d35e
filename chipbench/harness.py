"""What every runner shares: finding a cell's files by name, the look for
the chips, the compile cache, the compile counter, the profiler trace, and
the one result line."""

import importlib
import json
import math
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
HOST_SPANS = ("traced_window", "train_step", "engine_step", "client_loop")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*a):
    print(*a, flush=True)


def mark(ctx, what):
    """A line of the set-up's own timeline, in seconds since the start."""
    import time
    log(f"[setup +{time.perf_counter() - ctx['t0']:.1f}s] {what}")


# -- finding things by name -------------------------------------------------
def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(spec, name):
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                     f"(have: {[c['name'] for c in spec['workloads']]})")


def metrics_of(spec, cell, group):
    """The `end_to_end` or `per_layer` entries this cell reports. An
    end-to-end metric without `workloads` belongs to every cell; a
    per-layer one without it to every cell that reports what it moves."""
    e2e = [m for m in spec["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if group == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def runner_of(traffic):
    return importlib.import_module(f"chipbench.runners.{traffic['runner']}")


def read_metric(name, run):
    """A per-layer metric's value through its own reader, or None."""
    meta = load_json("metrics", name + ".json")
    reader = importlib.import_module(f"chipbench.readers.{meta['reader']}")
    return reader.read(run, **meta.get("params", {}))


# -- the device -------------------------------------------------------------
def require_chips(chips, allow_cpu=False):
    """Exit, printing no result, unless jax's devices are `chips` TPU
    chips. `allow_cpu` is the tests' rehearsal switch and is not reachable
    from the command line."""
    import jax
    devs = jax.devices()
    if allow_cpu:
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, jax found platform "
                         f"{devs[0].platform!r}; refusing to run on it")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, jax "
                         f"found {len(devs)}")
    return devs[:chips]


def setup_compile_cache():
    """The persistent compile cache: where JAX_COMPILATION_CACHE_DIR says,
    else the fixed git-ignored directory inside the checkout that the
    program's own chip entries use. Every program is cached, however
    quickly it compiled, so that a second run compiles nothing."""
    import paddle_tpu as paddle
    from paddle_tpu.flags import REPO_JIT_CACHE_DIR
    paddle.set_flags({"jit_cache_dir": REPO_JIT_CACHE_DIR,
                      "jit_cache_min_compile_time_secs": 0.0})
    import jax
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


class CompileCounter:
    """Counts jax's backend compilations (cache loads included: either
    way a program was not ready when it was called)."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **kw):
        if name == COMPILE_EVENT:
            self.count += 1


def memory_peak_bytes(devices):
    """Peak of live buffers on the fullest chip (the program's own
    reservation is not in it; PERF.md, PR 23)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)   # the CPU of a rehearsal reports none


# -- the trace --------------------------------------------------------------
class Trace:
    """One profiler session into a fixed git-ignored directory of the
    checkout. Python-level tracing is off: it would log every call."""

    def __init__(self, cell_name):
        self.dir = os.path.join(ROOT, ".chipbench_trace", cell_name)
        self.on = False
        self._span = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True
        self._span = jax.profiler.TraceAnnotation("traced_window")
        self._span.__enter__()

    def stop(self):
        """Ends the session and returns the reduced form."""
        import jax
        from chipbench import trace_reduce
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False
        return trace_reduce.from_xplane(
            trace_reduce.newest_xplane(self.dir), HOST_SPANS)


def span(name, on):
    """A host span in the profiler's trace when tracing, else nothing."""
    import contextlib
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


# -- the result -------------------------------------------------------------
def check_lines(checks):
    """Prints each number compared beside its limit; True when all hold."""
    ok = True
    for name, value, limit in checks:
        good = value is not None and math.isfinite(value) and value <= limit
        ok &= good
        log(f"[check] {name} value={value!r} limit={limit!r} "
            f"{'ok' if good else 'FAILED'}")
    return bool(checks) and ok


def result_line(spec, cell, run, trace_flag):
    """The one JSON object that ends a run's standard output."""
    import jax
    from chipbench import trace_reduce
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run["devices"]),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    out = {"correct": check_lines(run["checks"]),
           "attempted": run["attempted"], "failed": run["failed"]}
    metrics = {}
    if trace_flag:
        trace = run["trace"]
        t0, t1 = trace_reduce.traced_window(trace)
        device["busy_s"] = trace_reduce.busy_seconds(trace, t0, t1)
        device["window_s"] = (t1 - t0) / 1e9
        for m in metrics_of(spec, cell, "per_layer"):
            value = read_metric(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                            "idle_gaps": trace_reduce.idle_gaps(trace)}
    else:
        for m in metrics_of(spec, cell, "end_to_end"):
            metrics[m["name"]] = {"value": run["e2e"][m["name"]],
                                  "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    return json.dumps(out)
