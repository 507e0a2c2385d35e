"""Step accounting: compile vs steady-state, per-phase breakdown, MFU.

One reusable instrument for a training loop's own clock (the benchmark
has its own: ``chipbench/runners/_train.py``):

* the FIRST completed step is recorded as ``compile_s`` (jit trace +
  XLA compile + the step itself), every later step as steady state;
  ``report()`` puts the process's own figures beside it
  (``compile_breakdown``, from :func:`.startup.startup_record`);
* named phases (``with timer.phase("data"): ...``) attribute wall time
  inside or around the step — a per-phase ms breakdown of the host's
  time;
* ``report()`` derives tokens/s and MFU from an analytic FLOPs model
  (:mod:`.flops`) and carries a comms fraction either measured (the
  no-sync probe) or estimated from a comm_overlap bucket
  plan + link bandwidth.

The timer never touches the device: callers must end a step only after
forcing completion (``float(loss)``) or the numbers measure dispatch, not
execution.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Optional

from ..profiler.utils import Stat

__all__ = ["StepTimer"]


class StepTimer:
    def __init__(self, *, tokens_per_step: Optional[int] = None,
                 flops_per_token: Optional[float] = None,
                 peak_flops: Optional[float] = None):
        self.tokens_per_step = tokens_per_step
        self.flops_per_token = flops_per_token
        self.peak = peak_flops
        self.compile_s: Optional[float] = None
        self.steady = Stat()
        self.phases: Dict[str, Stat] = {}
        self._comms_fraction: Optional[float] = None
        self._comms_source: Optional[str] = None

    # -- timing spans --------------------------------------------------------
    @contextlib.contextmanager
    def step(self):
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
        if self.compile_s is None:
            self.compile_s = dt
        else:
            self.steady.add(dt)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.phases.setdefault(name, Stat()).add(time.perf_counter() - t0)

    # -- comms fraction ------------------------------------------------------
    def set_comms_fraction(self, fraction: float, source: str = "measured"):
        """Record the share of steady step time spent in (unoverlapped) dp
        collectives — e.g. ``1 - t_nosync/t_full`` from a no-sync probe."""
        self._comms_fraction = max(float(fraction), 0.0)
        self._comms_source = source

    def comms_fraction_from_plan(self, plan, axis_size: int,
                                 bandwidth_gbs: float, *,
                                 microbatches: int = 1,
                                 wire_itemsize: Optional[int] = None,
                                 op: str = "allreduce") -> Optional[float]:
        """Analytic comms fraction from a comm_overlap BucketPlan: total
        per-step wire time over measured steady step time (an upper bound
        — overlap hides some of it). Needs at least one steady step."""
        from .flops import collective_seconds, plan_wire_bytes
        if not self.steady.count:
            return None
        per_bucket = plan_wire_bytes(plan, wire_itemsize=wire_itemsize)
        t = sum(collective_seconds(b, axis_size, bandwidth_gbs, op)
                for b in per_bucket) * max(int(microbatches), 1)
        frac = min(t / self.steady.avg, 1.0)
        self.set_comms_fraction(frac, source="plan_estimate")
        return frac

    # -- derived metrics -----------------------------------------------------
    @property
    def tokens_per_sec(self) -> Optional[float]:
        if self.tokens_per_step is None or not self.steady.count:
            return None
        return self.tokens_per_step / self.steady.avg

    @property
    def mfu(self) -> Optional[float]:
        tps = self.tokens_per_sec
        if tps is None or self.flops_per_token is None:
            return None
        from .flops import mfu as _mfu
        return _mfu(tps, self.flops_per_token, self.peak)

    def report(self) -> Dict[str, Any]:
        from .startup import BREAKDOWN_KEYS, startup_record
        record = startup_record()
        out: Dict[str, Any] = {
            "compile_s": (round(self.compile_s, 3)
                          if self.compile_s is not None else None),
            # the process's traces, lowerings, cache loads and compilations
            # so far: the inside of the first step's outside timing
            "compile_breakdown": {k: round(record[k], 3)
                                  for k in BREAKDOWN_KEYS},
            "steady_steps": self.steady.count,
            "step_ms": {
                "avg": round(self.steady.avg * 1e3, 3),
                "min": round((0.0 if not self.steady.count
                              else self.steady.min) * 1e3, 3),
                "max": round(self.steady.max * 1e3, 3),
            },
            "phases_ms": {
                name: {"avg": round(s.avg * 1e3, 3),
                       "total": round(s.total * 1e3, 3),
                       "count": s.count}
                for name, s in sorted(self.phases.items())
            },
        }
        tps = self.tokens_per_sec
        if tps is not None:
            out["tokens_per_sec"] = round(tps, 1)
        m = self.mfu
        if m is not None:
            out["mfu_pct"] = round(m * 100, 2)
        if self._comms_fraction is not None:
            out["comms_fraction"] = round(self._comms_fraction, 4)
            out["comms_fraction_source"] = self._comms_source
        return out
