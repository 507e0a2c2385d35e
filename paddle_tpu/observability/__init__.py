"""Unified observability: in-program telemetry, step/MFU accounting and
exportable traces across training and serving.

The subsystem the perf work steers by (ISSUE 4): the bench's single
tokens/s + MFU pair says nothing about WHERE step time goes; this package
makes the split measurable without leaving the compiled program:

* :mod:`.metrics` — ``observe(name, scalar)`` inside jitted code; a
  fixed-shape ring buffer rides the train-step carry (like
  ``opt_state["fp8_meta"]``) and the host fetches it once per
  ``FLAGS_telemetry_interval`` steps. Built-in series: loss, grad
  global-norm, nonfinite counts, dp-collective wire bytes (from the
  comm_overlap bucket plans), FP8 amax/scale drift. Strict no-op
  (bitwise-identical program) when ``FLAGS_telemetry`` is off.
* :mod:`.step_timer` / :mod:`.flops` — compile vs steady-state split,
  per-phase breakdown, analytic GPT/Llama FLOPs (fwd/bwd/remat-aware)
  for MFU, comms fraction measured or estimated from bucket plans.
* :mod:`.events` — flushed-per-line, size-capped JSONL event log with
  host/role-tagged records (crash forensics; the resilient runner logs
  resumes/skips/commits/SIGTERM through it) + ``merge_event_streams``
  for one role-tagged timeline over trainer + serving logs.
* :mod:`.trace` — chrome-trace spans unified with ``paddle_tpu.profiler``.
* :mod:`.startup` — the program's record of its own start-up: jax's
  traces, lowerings, compilations and cache loads by name, and the three
  spans of the program's own start-up work, kept without a profiler
  session; ``startup_record()`` reads it.
* :mod:`.prom` — Prometheus text-format scrape surface (counters,
  gauges, summaries, bucketed histograms, recent-window p50/p95
  quantiles) for the serving engine and the fleet view.
* :mod:`.profile_reader` — the MEASUREMENT half (ISSUE 11): capture a
  windowed profile of a compiled step (while-trip-aware compiled-HLO op
  census + micro-benchmarked rates), attribute per-op time into compute
  vs hidden/exposed collective time by kind, and derive a measured
  ``HardwareProfile`` JSON the auto-parallel planner consumes directly.
* :mod:`.aggregate` — fleet telemetry: per-process step-time windows +
  prom snapshots gathered through the distributed store into rank-0
  gauges, with straggler detection (``straggler_detected`` events).
* :mod:`.flight_recorder` — hang flight recorder: watchdog timeouts and
  resilience SIGTERM/abort paths dump a bounded crash bundle (telemetry
  ring tail, recent events, open spans, heartbeat ages, active profile
  window).

Entry points: ``models.hybrid_engine.build_train_step(telemetry=)``,
``Model.fit``, ``distributed.resilience.run_resilient`` and
``inference.ServingEngine``. See README "Observability".
"""

from .aggregate import TelemetryAggregator, detect_stragglers
from .events import (EventLog, emit_event, get_event_log,
                     merge_event_streams, set_event_log)
from .flight_recorder import (FlightRecorder, get_flight_recorder,
                              set_flight_recorder)
from .flops import (collective_seconds, gpt_flops_per_token,
                    gpt_moe_flops_per_token, llama_flops_per_token, mfu,
                    param_count, peak_flops, plan_wire_bytes,
                    transformer_flops_per_token)
from .metrics import (BUILTIN_SERIES, TelemetryConfig, TelemetryHost,
                      buffer_specs, collecting, ep_a2a_wire_bytes,
                      init_buffer, mp_comm_scope, mp_wire_bytes,
                      note_ep_comm, note_mp_comm, note_zero3_comm, observe,
                      telemetry_from_flags, update_buffer,
                      zero3_ag_wire_bytes)
from . import numerics
from .numerics import (DetectorConfig, NumericsConfig, NumericsGuard,
                       NumericsMonitor, numerics_from_flags,
                       resolve_numerics)
from .profile_reader import (MeasuredRates, ProfileWindow,
                             capture_step_profile, derive_hardware_profile,
                             hlo_census, load_profile_json,
                             measure_collective_rates, measure_compute_rate,
                             save_profile_json)
from .prom import MetricsServer, PromRegistry, serve_registry
from . import startup
from .startup import startup_record
from .step_timer import StepTimer
from .trace import capture_spans, span, write_chrome_trace

__all__ = [
    "TelemetryConfig", "TelemetryHost", "telemetry_from_flags", "observe",
    "collecting", "BUILTIN_SERIES", "init_buffer", "buffer_specs",
    "update_buffer", "mp_wire_bytes", "note_mp_comm", "mp_comm_scope",
    "ep_a2a_wire_bytes", "note_ep_comm",
    "zero3_ag_wire_bytes", "note_zero3_comm",
    "StepTimer",
    "gpt_flops_per_token", "gpt_moe_flops_per_token",
    "llama_flops_per_token",
    "transformer_flops_per_token", "param_count", "mfu", "peak_flops",
    "collective_seconds", "plan_wire_bytes",
    "EventLog", "emit_event", "get_event_log", "set_event_log",
    "merge_event_streams",
    "PromRegistry", "MetricsServer", "serve_registry",
    "span", "capture_spans", "write_chrome_trace",
    "startup", "startup_record",
    "hlo_census", "capture_step_profile", "derive_hardware_profile",
    "save_profile_json", "load_profile_json", "measure_compute_rate",
    "measure_collective_rates", "MeasuredRates", "ProfileWindow",
    "TelemetryAggregator", "detect_stragglers",
    "FlightRecorder", "get_flight_recorder", "set_flight_recorder",
    "numerics", "NumericsConfig", "NumericsMonitor", "NumericsGuard",
    "DetectorConfig", "numerics_from_flags", "resolve_numerics",
]
