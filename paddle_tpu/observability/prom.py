"""Prometheus text-format scrape surface (serving + fleet telemetry).

A dependency-free subset of the Prometheus client: counters, gauges,
summaries (sum+count pairs) and bucketed histograms rendered in text
exposition format 0.0.4, plus a tiny threaded HTTP server exposing
``/metrics``. The serving engine keeps a :class:`PromRegistry` per
process and updates it inside ``ServingEngine.step``; the fleet
:class:`~paddle_tpu.observability.aggregate.TelemetryAggregator` gathers
per-process ``snapshot()``s into rank-0 gauges; ops point a scraper (or
curl) at the port.

Observation metrics (summaries and histograms) additionally keep a
bounded *recent window* of raw observations so callers can read live
quantiles (``quantile(name, 0.95)``) instead of the lifetime mean — a
summary's mean never decays, so one slow startup wave would otherwise
bias adaptive control (the serving TTFT/SLO mix) forever.

No pull-time device work: every metric is a host float updated on the
engine's own schedule, so a scrape can never add a TPU dispatch.
"""

from __future__ import annotations

import threading
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional, Sequence, Tuple

__all__ = ["PromRegistry", "MetricsServer", "serve_registry",
           "DEFAULT_BUCKETS", "nearest_rank"]


def nearest_rank(sorted_vals: Sequence[float], q: float) -> float:
    """Nearest-rank quantile of a non-empty SORTED sequence (q in
    [0, 1]): the ceil(q*N)-th order statistic — the median of 2 values
    is the LOWER one. The ONE copy shared by the registry's window
    quantiles and the fleet straggler detector (aggregate.percentile),
    so adaptive serving control and straggler verdicts can never compute
    different quantiles for the same q."""
    import math
    q = min(max(float(q), 0.0), 1.0)
    idx = min(max(math.ceil(q * len(sorted_vals)) - 1, 0),
              len(sorted_vals) - 1)
    return sorted_vals[idx]

_TYPES = ("counter", "gauge", "summary", "histogram")

# latency-ish default buckets (seconds or ms — caller's unit), upper
# bounds of the cumulative le="" series; +Inf is implicit
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
    5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0)


class _Metric:
    __slots__ = ("name", "mtype", "help", "value", "sum", "count",
                 "buckets", "bucket_counts", "window", "by_label")

    def __init__(self, name: str, mtype: str, help_: str,
                 buckets: Optional[Sequence[float]] = None,
                 window: int = 64):
        self.name = name
        self.mtype = mtype
        self.help = help_
        self.value = 0.0   # counter/gauge (a labelled counter: the total)
        self.by_label: Dict[Tuple[Tuple[str, str], ...], float] = {}
        self.sum = 0.0     # summary/histogram
        self.count = 0
        self.buckets: Tuple[float, ...] = ()
        self.bucket_counts: list = []
        if mtype == "histogram":
            self.buckets = tuple(sorted(buckets or DEFAULT_BUCKETS))
            self.bucket_counts = [0] * len(self.buckets)
        # recent raw observations (summary/histogram) for live quantiles
        self.window: Optional[deque] = (
            deque(maxlen=max(int(window), 1))
            if mtype in ("summary", "histogram") else None)


class PromRegistry:
    def __init__(self, namespace: str = "paddle_tpu", window: int = 64):
        self.namespace = namespace
        self.window = max(int(window), 1)
        self._metrics: Dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, mtype: str, help_: str,
             buckets: Optional[Sequence[float]] = None,
             window: Optional[int] = None) -> _Metric:
        assert mtype in _TYPES, mtype
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = _Metric(
                    name, mtype, help_, buckets=buckets,
                    window=window if window is not None else self.window)
            elif m.mtype != mtype:
                raise ValueError(f"metric {name} is a {m.mtype}, "
                                 f"not {mtype}")
            return m

    # -- update surface ------------------------------------------------------
    def counter_inc(self, name: str, amount: float = 1.0, help: str = "",
                    labels: Optional[Dict[str, str]] = None):
        """`labels` splits the counter into one series a label set
        (``name{reason="spec"}``); the metric's own value stays the
        total over them."""
        m = self._get(name, "counter", help)
        with self._lock:
            m.value += amount
            if labels:
                key = tuple(sorted(labels.items()))
                m.by_label[key] = m.by_label.get(key, 0.0) + amount

    def gauge_set(self, name: str, value: float, help: str = ""):
        m = self._get(name, "gauge", help)
        with self._lock:
            m.value = float(value)

    def gauge_max(self, name: str, value: float, help: str = ""):
        """Set-if-greater — peak gauges (e.g. peak pool utilization)."""
        m = self._get(name, "gauge", help)
        with self._lock:
            m.value = max(m.value, float(value))

    def summary_observe(self, name: str, value: float, help: str = "",
                        window: Optional[int] = None):
        m = self._get(name, "summary", help, window=window)
        with self._lock:
            m.sum += float(value)
            m.count += 1
            m.window.append(float(value))

    def histogram_observe(self, name: str, value: float, help: str = "",
                          buckets: Optional[Sequence[float]] = None,
                          window: Optional[int] = None):
        """Bucketed histogram observation (cumulative le="" series in the
        exposition). `buckets` fixes the upper bounds at first touch;
        later calls reuse the metric's buckets."""
        m = self._get(name, "histogram", help, buckets=buckets,
                      window=window)
        v = float(value)
        with self._lock:
            m.sum += v
            m.count += 1
            m.window.append(v)
            for i, ub in enumerate(m.buckets):
                if v <= ub:  # per-bucket count; render() cumulates
                    m.bucket_counts[i] += 1
                    break

    def _metric(self, name: str) -> Optional[_Metric]:
        prefix = f"{self.namespace}_"
        if self.namespace and name.startswith(prefix):
            name = name[len(prefix):]
        return self._metrics.get(name)

    def get(self, name: str,
            labels: Optional[Dict[str, str]] = None) -> Optional[float]:
        """Current value (summaries/histograms: mean of observations;
        a labelled counter: its total, or with `labels` that series, 0
        where it was never touched); None if the metric was never
        touched. Accepts the bare or namespaced name."""
        m = self._metric(name)
        if m is None:
            return None
        if labels:
            return m.by_label.get(tuple(sorted(labels.items())), 0.0)
        if m.mtype in ("summary", "histogram"):
            return m.sum / m.count if m.count else None
        return m.value

    def quantile(self, name: str, q: float) -> Optional[float]:
        """Recent-window quantile of a summary/histogram (q in [0, 1],
        nearest-rank over the last `window` raw observations). None when
        the metric does not exist, has no observations yet, or is not an
        observation type. This is the live-control read — the serving
        adaptive mix and the fleet router want p95-of-recent, not the
        lifetime mean the summary exposes."""
        m = self._metric(name)
        if m is None or m.window is None or not m.window:
            return None
        with self._lock:
            vals = sorted(m.window)
        return nearest_rank(vals, q)

    def snapshot(self) -> Dict[str, float]:
        """Flat {name: value} view for cross-process aggregation (the
        fleet TelemetryAggregator ships this through the distributed
        store). Counters/gauges export their value; observation metrics
        export `<name>_count`, `<name>_mean` and recent-window
        `<name>_p50`/`<name>_p95`."""
        with self._lock:
            metrics = list(self._metrics.values())
        out: Dict[str, float] = {}
        for m in metrics:
            if m.mtype in ("counter", "gauge"):
                out[m.name] = m.value
                continue
            out[m.name + "_count"] = float(m.count)
            if m.count:
                out[m.name + "_mean"] = m.sum / m.count
            for q, tag in ((0.5, "_p50"), (0.95, "_p95")):
                v = self.quantile(m.name, q)
                if v is not None:
                    out[m.name + tag] = v
        return out

    # -- exposition ----------------------------------------------------------
    def render(self) -> str:
        lines = []
        with self._lock:
            metrics = sorted(self._metrics.values(), key=lambda m: m.name)
        ns = self.namespace
        for m in metrics:
            full = f"{ns}_{m.name}" if ns else m.name
            if m.help:
                lines.append(f"# HELP {full} {m.help}")
            lines.append(f"# TYPE {full} {m.mtype}")
            if m.mtype == "summary":
                lines.append(f"{full}_sum {_fmt(m.sum)}")
                lines.append(f"{full}_count {m.count}")
            elif m.mtype == "histogram":
                cum = 0
                for ub, c in zip(m.buckets, m.bucket_counts):
                    cum += c
                    lines.append(f'{full}_bucket{{le="{_fmt(ub)}"}} {cum}')
                lines.append(f'{full}_bucket{{le="+Inf"}} {m.count}')
                lines.append(f"{full}_sum {_fmt(m.sum)}")
                lines.append(f"{full}_count {m.count}")
            elif m.by_label:
                for key, v in sorted(m.by_label.items()):
                    tags = ",".join(f'{k}="{val}"' for k, val in key)
                    lines.append(f"{full}{{{tags}}} {_fmt(v)}")
            else:
                lines.append(f"{full} {_fmt(m.value)}")
        return "\n".join(lines) + "\n"


def _fmt(v: float) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


class MetricsServer:
    """Threaded /metrics endpoint over a registry (or any render()-able).
    port=0 binds an ephemeral port; read it back from ``.port``.

    health_fn: optional zero-arg callable returning a readiness state
    string (the serving engine's ``loading/ready/draining/degraded``) —
    when set, the server also answers ``/healthz`` with a JSON body
    ``{"state": ...}``: HTTP 200 iff the state is ``ready``, 503
    otherwise, so a fleet router/load-balancer can gate traffic on it
    without parsing metrics."""

    def __init__(self, registry: PromRegistry, port: int = 0,
                 host: str = "127.0.0.1", health_fn=None):
        reg = registry

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server API)
                if health_fn is not None and \
                        self.path.rstrip("/") == "/healthz":
                    try:
                        state = str(health_fn())
                    except Exception:  # readiness must never 500 opaquely
                        state = "degraded"
                    body = ('{"state": "%s"}' % state).encode("utf-8")
                    self.send_response(200 if state == "ready" else 503)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if self.path.rstrip("/") not in ("", "/metrics"):
                    self.send_response(404)
                    self.end_headers()
                    return
                body = reg.render().encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # quiet
                del a

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="paddle-tpu-metrics",
                                        daemon=True)
        self._thread.start()

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def serve_registry(registry: PromRegistry,
                   port: Optional[int] = None,
                   health_fn=None) -> Optional[MetricsServer]:
    """Start a scrape endpoint; port None reads
    FLAGS_telemetry_prometheus_port (0 = disabled -> None). health_fn
    adds the /healthz readiness route (see MetricsServer)."""
    if port is None:
        from ..flags import flag
        port = int(flag("telemetry_prometheus_port"))
        if port <= 0:
            return None
    return MetricsServer(registry, port=max(port, 0), health_fn=health_fn)
