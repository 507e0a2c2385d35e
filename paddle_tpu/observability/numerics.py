"""Numerics observability: in-program tensor health + host-side anomaly
detection and spike-triggered forensics (ISSUE 15).

The stack trains through five low-precision surfaces (fp8 delayed
scaling, three int8 error-feedback wires, an int8/fp8 KV pool) whose
whole correctness story is *bounded dither* — yet a saturating fp8
scale, a growing EF residual or a loss spike is invisible until a run
diverges. PR 10 built the *performance* measurement loop; this module is
its *numerics* twin, split the same way:

* **In-program stats** (device side, riding the telemetry ring exactly
  as every other builtin does — flags-off lowers byte-identical HLO):
  :class:`NumericsConfig` is the plan a model builder threads into
  ``hybrid_engine.build_train_step(numerics=)``. The ENGINE then
  registers the series below onto the telemetry config and computes the
  engine-side ones; the MODELS deposit the activation stats from their
  block scans through the pipeline aux channel + ``observe()``:

  - ``num_gnorm_l<i>``       per-stacked-layer gradient norm (engine,
    replication-aware — the global-norm clip's accounting per layer
    index; storage order under vpp, both MoE families summed per pair);
  - ``num_act_rms_l<i>`` / ``num_act_absmax_l<i>``  per-layer block-
    output activation rms / absmax (models; mean over microbatches,
    max over data shards — 1F1B dense path);
  - ``num_ef_comm`` / ``num_ef_moe`` / ``num_ef_zero3``  global norms of
    the three error-feedback residual carries (engine, from the same
    ``opt_state`` namespaces the wires thread);
  - ``num_fp8_sat_<site>`` / ``num_fp8_headroom_<site>``  per-GEMM-site
    scale saturation ratio (this step's observed amax over the scale's
    representable cap — > 1 means values clipped) and log2 headroom
    (engine, read from the delayed-scaling ``fp8_meta`` observations).

* **Host side**: :class:`NumericsMonitor` — windowed anomaly detectors
  (loss/grad-norm spike vs rolling median, per-layer grad/act spikes,
  EF-residual growth, fp8 saturation rate, nonfinite onset) emitting ONE
  reason-tagged ``numerics_anomaly`` JSONL event per episode and one
  bounded flight-recorder bundle gaining ``numerics.json`` (last-K
  per-layer stats + detector state). :class:`NumericsGuard` bundles a
  TelemetryHost + monitor into the ``run_resilient(numerics=)`` hook
  that can skip-step or rollback-to-last-checkpoint on confirmed
  divergence (``FLAGS_numerics_action``).

The serving side's numerics twin (KV-pool page-scale drift) lives in
``inference.serving`` — host-side gauges off the same ``FLAGS_numerics``
switch.
"""

from __future__ import annotations

import dataclasses
import math
from collections import deque
from statistics import median as _median
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["NumericsConfig", "numerics_from_flags", "resolve_numerics",
           "numerics_series", "EF_SERIES", "fp8_site_health",
           "DetectorConfig", "detector_from_flags", "NumericsMonitor",
           "NumericsGuard", "numerics_spike_check"]

# opt_state EF namespace -> telemetry series name (the engine registers
# the subset whose plans are live; tests fetch the carries and assert the
# series against independently recomputed norms)
EF_SERIES: Dict[str, str] = {"comm_ef": "num_ef_comm",
                             "moe_ef": "num_ef_moe",
                             "zero3_ef": "num_ef_zero3"}

# clamp on the log2 headroom series: an all-zero amax observation (site
# not yet exercised) would otherwise print log2(cap/tiny) ~ 40+
HEADROOM_CLAMP = 32.0


@dataclasses.dataclass
class NumericsConfig:
    """In-program tensor-health plan a model builder hands the engine.

    num_layers: stacked-block layer count (global dim-0 of the params'
        ``block_key`` subtree — ``cfg.num_layers`` for the dense models,
        layer PAIRS for GPT-MoE). 0 disables the per-layer series.
    act: the model deposits per-layer activation rms/absmax from its
        block scan (the builders enable this on the plain-1F1B dense
        path, where the pipeline aux channel exists; per-layer GRAD
        norms are engine-side and work under every schedule).
    block_key / pp_axis: where the stacked block subtree lives and which
        mesh axis shards its layer dim (dim 0) — the engine all-gathers
        the per-layer vector over it so the replicated telemetry row is
        identical on every rank.
    """
    num_layers: int = 0
    act: bool = False
    block_key: str = "blocks"
    pp_axis: str = "pp"

    def meta(self) -> Dict[str, Any]:
        return {"num_layers": int(self.num_layers), "act": bool(self.act),
                "block_key": self.block_key, "pp_axis": self.pp_axis}


def numerics_from_flags() -> bool:
    from ..flags import flag
    return bool(flag("numerics"))


def resolve_numerics(arg, *, num_layers: int, act: bool,
                     block_key: str = "blocks",
                     pp_axis: str = "pp") -> Optional[NumericsConfig]:
    """ONE resolution of a model builder's numerics= argument ("auto"
    reads FLAGS_numerics; bool forces; an explicit NumericsConfig wins)
    — gpt and llama both route through here so the flag semantics can
    never drift between families. None/off resolves to None and the
    build compiles bitwise-identically to one without the argument."""
    if isinstance(arg, NumericsConfig):
        return arg
    if arg is None:
        return None
    on = numerics_from_flags() if arg == "auto" else bool(arg)
    if not on:
        return None
    return NumericsConfig(num_layers=int(num_layers), act=bool(act),
                          block_key=block_key, pp_axis=pp_axis)


def numerics_series(ncfg: NumericsConfig, *,
                    ef_namespaces: Sequence[str] = (),
                    fp8_sites: Sequence[str] = ()) -> Tuple[str, ...]:
    """The telemetry series a numerics build registers — derived from the
    config + the engine's live plans alone, so the host decodes buffers
    with no side channel (the BUILTIN_SERIES discipline)."""
    names: List[str] = []
    for i in range(int(ncfg.num_layers)):
        names.append(f"num_gnorm_l{i}")
    if ncfg.act:
        for i in range(int(ncfg.num_layers)):
            names.append(f"num_act_rms_l{i}")
            names.append(f"num_act_absmax_l{i}")
    for ns in ef_namespaces:
        names.append(EF_SERIES[ns])
    for s in fp8_sites:
        names.append(f"num_fp8_sat_{s}")
        names.append(f"num_fp8_headroom_{s}")
    return tuple(names)


def fp8_site_health(amax_obs, scales, axes=()) -> Dict[str, Any]:
    """Per-site fp8 scale health from this step's amax observations vs
    the delayed scales the step USED (runs inside the compiled step; the
    engine merges the result into the telemetry row).

    axes: mesh axes to pmax the saturation / pmin the headroom over —
    the engine passes EVERY mesh axis. The amax observations are
    deliberately never pmax'd over the stacked pipeline axis (that
    would mix different layers' amaxes into the scale update), so each
    pp rank's local reduction only covers ITS layer stack; the
    replicated telemetry row must be rank-identical, and a clip on
    another rank's layers must still surface.

    * ``num_fp8_sat_<site>``: max over roles (and stacked layers) of
      observed_amax / (scale x fmax) — the fraction of the quantizer's
      representable range the step's largest value needed. > 1 means the
      e4m3/e5m2 cast CLIPPED this step (delayed scaling saturates for
      one step on a fresh outlier by design; a sustained rate is the
      anomaly, which the monitor detects).
    * ``num_fp8_headroom_<site>``: min over roles/layers of
      log2(scale x fmax / amax) — bits of range headroom left (clamped
      to +-HEADROOM_CLAMP; unexercised sites read the clamp).

    Pipelined observation note: the hybrid path's scale cotangents SUM
    over the pipeline's T ticks (an additive upper bound, quantization
    .fp8.update_fp8_meta) — saturation reads proportionally high there;
    the detectors compare against each series' own rolling history, so
    the constant factor cancels.
    """
    import jax.numpy as jnp
    from jax import lax
    from ..quantization.fp8 import role_fmax
    tiny = 1e-12
    axes = tuple(axes)
    out: Dict[str, Any] = {}
    for site, roles in amax_obs.items():
        sat, hr = [], []
        for role, a in roles.items():
            cap = (scales[site][role].astype(jnp.float32)
                   * role_fmax(role))
            af = jnp.maximum(a.astype(jnp.float32), 0.0)
            sat.append(jnp.max(af / jnp.maximum(cap, tiny)))
            hr.append(jnp.min(jnp.clip(
                jnp.log2(jnp.maximum(cap, tiny)
                         / jnp.maximum(af, tiny)),
                -HEADROOM_CLAMP, HEADROOM_CLAMP)))
        s = jnp.max(jnp.stack(sat))
        h = jnp.min(jnp.stack(hr))
        if axes:
            s = lax.pmax(s, axes)
            h = -lax.pmax(-h, axes)
        out[f"num_fp8_sat_{site}"] = s
        out[f"num_fp8_headroom_{site}"] = h
    return out


# ---------------------------------------------------------------------------
# Host side: windowed anomaly detection + forensics + the driver hook.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DetectorConfig:
    """Windowed-detector knobs (host side only — nothing here touches
    compiled programs).

    window: rolling history per series (also the last-K depth of the
        ``numerics.json`` forensics snapshot).
    min_history: observations a series needs before its spike/growth
        detector arms (a cold start must not flag the first fetch).
    spike_factor: loss/grad-norm/activation spike threshold — fire when
        the new value exceeds the rolling MEDIAN by this factor (median,
        not mean: one prior spike must not mask the next).
    ef_growth_factor: EF-residual growth threshold vs rolling median.
    sat_threshold / sat_rate: an fp8 site is anomalous when more than
        ``sat_rate`` of its recent window observed saturation ratio
        > ``sat_threshold``.
    clear_obs: consecutive healthy observations that END an episode
        (one ``numerics_anomaly`` event + one bundle per episode).
    action: what a CONFIRMED episode asks the resilient driver to do —
        "none" (observe only), "skip" (reject diverging steps, the
        found_inf discipline) or "rollback" (reload the last committed
        checkpoint and re-train forward).
    confirm: anomalous observations inside one episode before the
        action fires (a single spiky fetch stays forensics-only).
    max_rollbacks: rollbacks the monitor will ever request (a bad data
        shard would otherwise loop the driver forever).
    """
    window: int = 32
    min_history: int = 8
    spike_factor: float = 4.0
    ef_growth_factor: float = 8.0
    sat_threshold: float = 1.0
    sat_rate: float = 0.5
    clear_obs: int = 16
    action: str = "none"
    confirm: int = 2
    max_rollbacks: int = 1


def detector_from_flags() -> DetectorConfig:
    from ..flags import flag
    return DetectorConfig(window=int(flag("numerics_window")),
                          spike_factor=float(flag("numerics_spike_factor")),
                          action=str(flag("numerics_action")))


class NumericsMonitor:
    """Windowed anomaly detection over the numerics telemetry series.

    Feed it per-step host losses (:meth:`note_loss`) and decoded
    telemetry rows (:meth:`ingest_row` — the :class:`NumericsGuard` does
    both). Each observation runs the detectors against that series' own
    rolling history; the FIRST anomalous observation opens an *episode*:
    one reason-tagged ``numerics_anomaly`` JSONL event, one bounded
    flight-recorder bundle (which gains ``numerics.json`` — the monitor
    registers weakly, so EVERY bundle from any crash path includes the
    numerics state). Further anomalous observations extend the episode
    silently; ``clear_obs`` consecutive healthy ones close it (with a
    ``numerics_recovered`` event) and re-arm detection.

    Duplicate-step protection: telemetry rows arrive one interval late,
    so the same step's loss may be seen twice (driver + ring) — each
    series ignores observations at or before its last-seen step.
    """

    def __init__(self, cfg: Optional[DetectorConfig] = None,
                 event_log=None):
        self.cfg = cfg or detector_from_flags()
        self._event_log = event_log
        k = max(int(self.cfg.window), 4)
        self._hist: Dict[str, deque] = {}
        self._last_step: Dict[str, int] = {}
        self._steps: deque = deque(maxlen=k)
        self.anomalies: List[Dict[str, Any]] = []
        self.rollbacks = 0
        self._episode: Optional[Dict[str, Any]] = None
        self._healthy = 0
        self._pending_action: Optional[str] = None
        from .flight_recorder import register_numerics_monitor
        register_numerics_monitor(self)

    # -- ingestion -----------------------------------------------------------
    def note_loss(self, step: int, loss: float) -> None:
        """Per-step host-observed loss (the driver's float(loss) —
        per-step granularity, one interval earlier than the ring)."""
        self._observe(int(step), {"loss": float(loss)})

    def ingest_row(self, step: int, values: Dict[str, float]) -> None:
        """One decoded telemetry row (series name -> value at `step`)."""
        self._observe(int(step), {k: float(v) for k, v in values.items()})

    # -- detectors -----------------------------------------------------------
    def _detect(self, name: str, v: float,
                hist: Sequence[float]) -> Optional[str]:
        cfg = self.cfg
        if not math.isfinite(v):
            return f"nonfinite_value:{name}"
        if name == "nonfinite_count":
            return "nonfinite" if v > 0 else None
        spike_kind = None
        if name in ("loss", "grad_norm"):
            spike_kind = f"{name}_spike"
        elif name.startswith("num_gnorm_l"):
            spike_kind = f"layer_grad_spike:{name}"
        elif name.startswith("num_act_absmax_l"):
            spike_kind = f"act_spike:{name}"
        if spike_kind is not None:
            if len(hist) < max(cfg.min_history, 1):
                return None
            med = _median(hist)
            if v > max(med * cfg.spike_factor, med + 1e-9):
                return spike_kind
            return None
        if name.startswith("num_ef_"):
            if len(hist) < max(cfg.min_history, 1):
                return None
            med = _median(hist)
            if v > 1e-12 and v > max(med * cfg.ef_growth_factor,
                                     med + 1e-12):
                return f"ef_growth:{name}"
            return None
        if name.startswith("num_fp8_sat_"):
            recent = list(hist)[-(cfg.window - 1):] + [v]
            if len(recent) < cfg.min_history:
                return None
            rate = sum(1 for x in recent
                       if x > cfg.sat_threshold) / len(recent)
            if rate >= cfg.sat_rate:
                return f"fp8_saturation:{name}"
            return None
        return None

    def _observe(self, step: int, values: Dict[str, float]) -> None:
        reasons: List[str] = []
        trig: Dict[str, float] = {}
        # unique observed steps (the ring's rows lag the per-step host
        # loss, so the same step arrives twice — forensics readers
        # correlating snapshot windows must not see duplicates)
        if step not in self._steps:
            self._steps.append(step)
        for name, v in values.items():
            if step <= self._last_step.get(name, -1):
                continue  # duplicate (ring row behind the host loss)
            self._last_step[name] = step
            h = self._hist.setdefault(
                name, deque(maxlen=max(int(self.cfg.window), 4)))
            r = self._detect(name, v, h)
            h.append(v)
            if r is not None:
                reasons.append(r)
                trig[name] = v
        if reasons:
            self._healthy = 0
            if self._episode is None:
                self._open_episode(step, reasons, trig)
            else:
                ep = self._episode
                ep["hits"] += 1
                ep["last_step"] = step
                for r in reasons:
                    if r not in ep["reasons"]:
                        ep["reasons"].append(r)
            self._arm_action()
        else:
            self._healthy += 1
            if (self._episode is not None
                    and self._healthy >= self.cfg.clear_obs):
                self._emit("numerics_recovered",
                           step=step,
                           first_step=self._episode["step"],
                           reasons=self._episode["reasons"])
                self._episode = None

    def _open_episode(self, step: int, reasons: List[str],
                      trig: Dict[str, float]) -> None:
        self._episode = {"step": step, "last_step": step, "hits": 1,
                         "reasons": list(reasons), "values": dict(trig)}
        anomaly = {"step": step, "reasons": list(reasons),
                   "values": dict(trig)}
        self.anomalies.append(anomaly)
        self._emit("numerics_anomaly", step=step,
                   reason=reasons[0], reasons=reasons, values=trig)
        from .flight_recorder import maybe_dump
        anomaly["bundle"] = maybe_dump(
            "numerics_anomaly",
            extra={"step": step, "reasons": reasons, "values": trig})

    def _arm_action(self) -> None:
        cfg = self.cfg
        ep = self._episode
        if cfg.action == "none" or ep is None:
            return
        if ep["hits"] < cfg.confirm or ep.get("actioned"):
            return
        if cfg.action == "rollback" and self.rollbacks >= cfg.max_rollbacks:
            return
        ep["actioned"] = True
        self._pending_action = cfg.action
        if cfg.action == "rollback":
            self.rollbacks += 1

    def consume_action(self) -> Optional[str]:
        """The driver's per-step query: "skip"/"rollback" once armed by a
        confirmed episode, else None. Skip re-arms on the next anomalous
        observation of the same episode; rollback is budgeted by
        max_rollbacks across the whole run."""
        a, self._pending_action = self._pending_action, None
        if a == "skip" and self._episode is not None:
            # keep skipping while the episode stays confirmed — the next
            # anomalous observation re-arms anyway; a healthy one clears
            self._episode["actioned"] = False
        return a

    def on_rollback(self) -> None:
        """The driver rolled the run back: histories describe a future
        that no longer exists — reset windows and close the episode."""
        self._hist.clear()
        self._last_step.clear()
        self._steps.clear()
        self._episode = None
        self._healthy = 0
        self._pending_action = None

    def refund_rollback(self) -> None:
        """The driver found NO checkpoint to roll back to: return the
        budget (charged at arm time) and un-action the episode so a
        later confirmation — once a commit exists — can re-arm."""
        self.rollbacks = max(self.rollbacks - 1, 0)
        if self._episode is not None:
            self._episode["actioned"] = False

    # -- forensics -----------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Bounded host state for flight-recorder bundles
        (``numerics.json``): last-K values of every tracked series
        (per-layer stats included), detector config + episode state, the
        anomaly history."""
        return {
            "config": dataclasses.asdict(self.cfg),
            "steps": list(self._steps),
            "series": {k: [float(v) for v in d]
                       for k, d in sorted(self._hist.items())},
            "episode": self._episode,
            "healthy_streak": self._healthy,
            "rollbacks": self.rollbacks,
            "anomalies": self.anomalies[-8:],
        }

    def _emit(self, event: str, **fields) -> None:
        log = self._event_log
        if log is None:
            from .events import get_event_log
            log = get_event_log()
        if log is not None:
            log.emit(event, **fields)


class NumericsGuard:
    """The ``run_resilient(numerics=)`` hook: one object bundling the
    telemetry fetch (TelemetryHost), the anomaly monitor and the
    action policy. Build the ENGINE first (it registers the numerics
    series onto the telemetry config — ``init_state.telemetry_config``
    is the resolved config for flag-driven builds), then::

        guard = NumericsGuard(init_state.telemetry_config)
        run_resilient(step_fn, state, ..., numerics=guard)

    ``after_step`` feeds the per-step loss, polls the ring on the
    interval cadence, runs the detectors and returns the confirmed
    action ("skip"/"rollback") or None. `prom` additionally exports the
    decoded grad-norm/loss as live gauges (TelemetryHost's export)."""

    def __init__(self, telemetry, monitor: Optional[NumericsMonitor]
                 = None, *, prom=None, event_log=None):
        from .metrics import TelemetryHost
        self.host = TelemetryHost(telemetry, event_log=event_log,
                                  prom=prom)
        self.monitor = monitor or NumericsMonitor(event_log=event_log)

    @staticmethod
    def _carrier(state):
        """The engine opt-state carry holding the telemetry buffer —
        the driver's state dict nests it one level down."""
        if not isinstance(state, dict):
            return None
        if "telemetry" in state:
            return state
        for v in state.values():
            if isinstance(v, dict) and "telemetry" in v:
                return v
        return None

    def _feed(self, new: Optional[Dict[str, List[float]]]) -> None:
        if not new:
            return
        n = len(next(iter(new.values())))
        steps = self.host.steps[-n:]
        for j, s in enumerate(steps):
            self.monitor.ingest_row(s, {k: v[j] for k, v in new.items()})

    def after_step(self, state, step: int,
                   loss: Optional[float] = None) -> Optional[str]:
        if loss is not None:
            self.monitor.note_loss(step, float(loss))
        carrier = self._carrier(state)
        if carrier is not None:
            self._feed(self.host.poll(carrier, step))
        return self.monitor.consume_action()

    def flush(self, state) -> None:
        carrier = self._carrier(state)
        if carrier is not None:
            self._feed(self.host.flush(carrier))

    def on_rollback(self, state=None) -> None:
        """The driver restored `state` from a checkpoint: reset the
        detectors AND rewind the host to the restored carry's ring
        count — without the rewind the ingest watermark (set while
        polling the abandoned timeline) would silently drop every
        replayed row and leave the detectors blind exactly when
        re-divergence must be caught."""
        self.monitor.on_rollback()
        carrier = self._carrier(state) if state is not None else None
        if carrier is not None:
            import jax
            self.host.rewind(int(jax.device_get(
                carrier["telemetry"]["count"])))

    def on_rollback_unavailable(self) -> None:
        """The driver had no checkpoint to roll back to."""
        self.monitor.refund_rollback()


# ---------------------------------------------------------------------------
# The CI/dryrun leg: spike-injected run -> detection + bundle asserted.
# ---------------------------------------------------------------------------
def numerics_spike_check(workdir: str, *, steps: int = 20,
                         spike_at: int = 14,
                         mesh_shape: Optional[Dict[str, int]] = None
                         ) -> Dict[str, Any]:
    """End-to-end numerics acceptance (shared by the ``__graft_entry__``
    dryrun leg and tier-1): a gpt-tiny hybrid run with numerics
    telemetry on, driven by ``run_resilient`` with a NumericsGuard,
    while the ``numerics/spike`` faults-grammar site injects one
    host-observed loss spike at step `spike_at`. Asserts EXACTLY one
    ``numerics_anomaly`` JSONL event and one flight-recorder bundle
    whose ``numerics.json`` carries the per-layer stats. Single-process
    (the degraded form of the 2-proc leg — the detectors and forensics
    are host-local either way). Returns a summary dict."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.resilience import run_resilient
    from paddle_tpu.models import gpt as G
    from .events import EventLog, set_event_log
    from .flight_recorder import FlightRecorder, set_flight_recorder
    from .metrics import TelemetryConfig

    mesh_shape = mesh_shape or {"dp": 2, "pp": 1, "mp": 1}
    mesh = dist.build_mesh(mesh_shape)
    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=2, max_seq_len=32, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    tcfg = TelemetryConfig(interval=4, strict=False)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3)
    # run_resilient falls back to the state a rejected step was given
    step, shard_params, init_state = G.build_hybrid_train_step(
        cfg, mesh, opt, num_microbatches=1, telemetry=tcfg,
        numerics=True, donate=False)
    p = shard_params(G.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    s = init_state(p)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)))

    def step_fn(st, i):
        del i
        np_, ns_, loss = step(st["params"], st["opt"], tokens, labels,
                              jnp.float32(1e-3))
        return {"params": np_, "opt": ns_}, loss

    log_path = os.path.join(workdir, "numerics.jsonl")
    crash_dir = os.path.join(workdir, "crash")
    log = EventLog(log_path)
    prev_log = set_event_log(log)
    prev_rec = set_flight_recorder(FlightRecorder(crash_dir))
    prev_fault = paddle.get_flags(["FLAGS_fault_inject"])
    try:
        paddle.set_flags(
            {"FLAGS_fault_inject": f"numerics/spike:{spike_at}"})
        guard = NumericsGuard(
            tcfg, NumericsMonitor(
                DetectorConfig(window=16, min_history=4, spike_factor=4.0,
                               clear_obs=4),
                event_log=log),
            event_log=log)
        state, info = run_resilient(
            step_fn, {"params": p, "opt": s}, steps=steps,
            ckpt_dir=os.path.join(workdir, "ckpt"), ckpt_every=0,
            numerics=guard)
    finally:
        paddle.set_flags(prev_fault)
        set_event_log(prev_log)
        set_flight_recorder(prev_rec)
        log.close()

    import json
    events = [json.loads(l) for l in open(log_path, encoding="utf-8")]
    anomalies = [e for e in events if e["event"] == "numerics_anomaly"]
    assert len(anomalies) == 1, \
        f"expected exactly one numerics_anomaly event, got {anomalies}"
    assert any(r.startswith("loss_spike") for r in anomalies[0]["reasons"])
    bundles = sorted(d for d in os.listdir(crash_dir)
                     if d.startswith("flight_"))
    assert len(bundles) == 1, bundles
    nj = os.path.join(crash_dir, bundles[0], "numerics.json")
    assert os.path.exists(nj), "bundle missing numerics.json"
    with open(nj, encoding="utf-8") as f:
        forensic = json.load(f)
    mon = next(iter(forensic.values()))
    per_layer = [k for k in mon["series"] if k.startswith("num_gnorm_l")]
    assert len(per_layer) == cfg.num_layers, mon["series"].keys()
    assert mon["anomalies"], "monitor snapshot lost the anomaly"
    assert info["completed_steps"] == steps
    return {"anomaly_step": anomalies[0]["step"],
            "reasons": anomalies[0]["reasons"],
            "layers": len(per_layer),
            "bundle": bundles[0]}
