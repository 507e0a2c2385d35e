"""Device-profile capture + attribution: the measurement half of the
observability loop (T3 framing, arXiv:2401.16677 — attribute collective
time from the OBSERVED program instead of assuming overlap).

The planner (``distributed.auto_tuner``) scores configs with analytic
wire models discounted by *hidable fractions* that, until now, were
hard-coded T3-style table entries, and converts bytes to seconds with
table ICI rates. This module closes that loop: capture a windowed profile
around N steps of a real compiled program and attribute where the time
went —

* **op census from the compiled HLO** (the CPU-tier proxy; on device the
  same census is the ground map a jax.profiler trace refines): every
  collective (all-reduce / all-gather / reduce-scatter /
  collective-permute / all-to-all) with its payload bytes and replica
  group size, and every ``dot`` with its FLOPs — both multiplied through
  ``while`` loop bodies by their parsed trip counts, which XLA's own
  ``cost_analysis`` does NOT do (a pipelined train step is ~all loops);
* **micro-benchmarked rates**: the effective collective launch cost +
  link bandwidth (two-size psum solve) and the achievable GEMM rate,
  measured on the live backend rather than read from a table;
* **attribution**: compute seconds = census FLOPs / measured rate; total
  wire seconds per collective kind = bytes / measured bandwidth + count x
  launch; the measured step wall time then splits each collective into
  *hidden* (concurrent with compute) vs *exposed* time:
  ``exposed = clamp(step - compute, 0, total_wire)``,
  ``hidden = total_wire - exposed``, with any residual beyond
  compute + wire attributed as (host/dispatch) overhead.

From per-mode capture windows, :func:`derive_hardware_profile` builds a
measured :class:`~paddle_tpu.distributed.auto_tuner.planner.
HardwareProfile` — effective ici_gbs, per-collective launch cost,
per-mode hidable fractions — serialized as JSON that
``auto_tuner plan --profile measured.json`` and :class:`CostModel`
consume directly, so planner calibration stops being step-time-only and
gains per-term ground truth.

An open capture window is visible to the hang flight recorder
(:func:`active_profile_window`) so a pod that wedges mid-profile leaves
the half-collected window in the crash bundle.
"""

from __future__ import annotations

import dataclasses
import json
import re
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["COLLECTIVE_KINDS", "Census", "hlo_census", "MeasuredRates",
           "measure_compute_rate", "measure_collective_rates",
           "ProfileWindow", "capture_step_profile",
           "derive_hardware_profile", "save_profile_json",
           "load_profile_json", "active_profile_window"]

COLLECTIVE_KINDS = ("all_reduce", "all_gather", "reduce_scatter",
                    "collective_permute", "all_to_all")

# compiled-HLO spellings; -start matches async forms once (-done never
# has a payload-bearing "= shapes op(" assignment of its own kind name
# followed by "(") — see tests/hlo_utils.py for the lowered-text variants
_OP_SPELLING = {
    "all-reduce": "all_reduce", "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "collective-permute": "collective_permute",
    "all-to-all": "all_to_all",
}
_COLL_RE = re.compile(
    r"= (?P<shapes>[^=]*?) (?P<op>all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|all-to-all)(?P<start>-start)?\(")
_SHAPE_RE = re.compile(r"(?P<dtype>[a-z][a-z0-9]*)\[(?P<dims>[0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{(?P<first>[0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_WHILE_RE = re.compile(
    r"while\(.*?condition=%?(?P<cond>[\w.\-]+), body=%?(?P<body>[\w.\-]+)",
    re.DOTALL)
_CALLED_RE = re.compile(
    r"(?:to_apply|calls|true_computation|false_computation|"
    r"branch_computations)=\{?%?(?P<names>[\w.\-]+(?:,\s*%?[\w.\-]+)*)")
_TRIP_RE = re.compile(r"=\s*[su]\d+\[\]\s*constant\((\d+)\)")
# XLA prints operands by NAME only (``dot(%a, %b)``): the lhs shape comes
# from the operand's own definition in the same computation (_DEF_RE)
_DOT_RE = re.compile(
    r"= (?P<shape>[a-z][a-z0-9]*\[[0-9,]*\])\S* dot\("
    r"%?(?P<lhs>[\w.\-]+)[,)].*?"
    r"lhs_contracting_dims=\{(?P<cdims>[0-9,]*)\}")
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+) = "
    r"(?P<shape>[a-z][a-z0-9]*\[[0-9,]*\])", re.MULTILINE)
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_KNOWN_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _itemsize(dtype: str) -> int:
    m = re.search(r"(\d+)", dtype)
    if not m:
        return 1  # pred / token
    bits = int(m.group(1))
    if dtype.startswith("c"):  # complex: c64/c128 are total bits
        return bits // 8
    return max(bits // 8, 1)


def _shape_bytes(token_dtype: str, dims: str) -> float:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return float(n) * _itemsize(token_dtype)


def _wire_bytes(kind: str, result_bytes: float, k: int) -> float:
    """Per-rank ring-accounting wire bytes of one collective op from its
    RESULT payload bytes and replica-group size k (all-gather results are
    full gathered size, reduce-scatter results are the shard)."""
    if k <= 1:
        return 0.0
    f = (k - 1) / k
    if kind == "all_reduce":
        return 2.0 * result_bytes * f        # RS + AG of the payload
    if kind == "all_gather":
        return result_bytes * f              # result = gathered size
    if kind == "reduce_scatter":
        return result_bytes * (k - 1)        # result = one shard
    if kind == "collective_permute":
        return result_bytes                  # each rank forwards once
    return result_bytes * f                  # all_to_all


@dataclasses.dataclass
class Census:
    """Compiled-HLO op census with while-loop multiplicity applied:
    per-kind collective {count, wire_bytes} and total dot FLOPs, all per
    device per step."""
    collectives: Dict[str, Dict[str, float]]
    dot_flops: float
    n_while: int
    notes: List[str]

    @property
    def n_collectives(self) -> float:
        return sum(v["count"] for v in self.collectives.values())

    @property
    def total_wire_bytes(self) -> float:
        return sum(v["wire_bytes"] for v in self.collectives.values())

    def to_json(self) -> Dict[str, Any]:
        return {"collectives": {k: dict(v)
                                for k, v in self.collectives.items()},
                "dot_flops": self.dot_flops, "n_while": self.n_while,
                "notes": list(self.notes)}


def _split_computations(text: str) -> Tuple[Optional[str], Dict[str, str]]:
    """(entry_name, {computation_name: body_text}) from compiled HLO
    module text. Computations start at column 0 as
    ``[ENTRY ]%name (args) -> result {`` and end at a column-0 ``}``."""
    comps: Dict[str, List[str]] = {}
    entry = None
    current: Optional[str] = None
    for line in text.splitlines():
        if current is None:
            m = re.match(r"^(ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\{\s*$",
                         line)
            if m:
                current = m.group("name")
                comps[current] = []
                if m.group(1):
                    entry = current
        else:
            if line.startswith("}"):
                current = None
            else:
                comps[current].append(line)
    return entry, {k: "\n".join(v) for k, v in comps.items()}


def _computation_multipliers(entry: Optional[str],
                             comps: Dict[str, str],
                             notes: List[str]) -> Dict[str, float]:
    """Execution multiplicity of each computation: ENTRY runs once; a
    while body runs its parsed trip count times (nested whiles multiply);
    to_apply/calls/branch computations inherit the caller's multiplier.
    Unknown trip counts fall back to 1 with a note — the census then
    UNDERCOUNTS, which the attribution records rather than hides."""
    mult: Dict[str, float] = {}
    if entry is None:
        # no ENTRY marker (lowered/StableHLO text): treat every
        # computation as executing once
        notes.append("no ENTRY computation found; multipliers default 1")
        return {name: 1.0 for name in comps}
    pending: List[Tuple[str, float]] = [(entry, 1.0)]
    while pending:
        name, m = pending.pop()
        if name not in comps:
            continue
        mult[name] = mult.get(name, 0.0) + m
        body_text = comps[name]
        consumed = set()
        for w in _WHILE_RE.finditer(body_text):
            cond, body = w.group("cond"), w.group("body")
            # XLA's own analysis, printed on the while's line, wins over
            # the largest-constant-in-the-condition heuristic
            eol = body_text.find("\n", w.end())
            known = _KNOWN_TRIP_RE.search(
                body_text, w.end(), eol if eol > 0 else len(body_text))
            trips = ([int(known.group(1))] if known else
                     [int(t) for t in _TRIP_RE.findall(comps.get(cond, ""))])
            trip = float(max(trips)) if trips else 1.0
            if not trips:
                notes.append(f"while body {body}: trip count not found "
                             f"in {cond}; assuming 1")
            pending.append((body, m * trip))
            pending.append((cond, m * (trip + 1)))
            consumed.add(body)
            consumed.add(cond)
        for c in _CALLED_RE.finditer(body_text):
            for callee in re.split(r",\s*%?", c.group("names")):
                if callee and callee not in consumed:
                    pending.append((callee, m))
    return mult


def hlo_census(text: str, *, default_group: int = 1) -> Census:
    """Census a compiled HLO module: collectives by kind with per-rank
    wire bytes (replica-group sizes parsed per op; `default_group` covers
    ops without groups) and total dot FLOPs — each multiplied by its
    enclosing while loops' trip counts. This is the CPU-tier profile
    proxy: XLA's cost_analysis reports loop bodies ONCE, so a pipelined
    or layer-scanned train step needs the trip-aware census."""
    notes: List[str] = []
    # long tuple shapes carry ``/*index=5*/`` markers whose "=" would cut
    # a combined (many-operand) collective out of _COLL_RE's match
    text = _COMMENT_RE.sub("", text)
    entry, comps = _split_computations(text)
    if not comps:
        # not module text at all — census the flat text at multiplier 1
        comps = {"<flat>": text}
        entry = None
        notes.append("unrecognized module structure; flat census")
    mult = _computation_multipliers(entry, comps, notes)
    coll = {k: {"count": 0.0, "wire_bytes": 0.0} for k in COLLECTIVE_KINDS}
    dot_flops = 0.0
    n_while = 0
    for name, body in comps.items():
        m = mult.get(name, 0.0)
        if m <= 0.0:
            continue
        n_while += len(_WHILE_RE.findall(body))
        for c in _COLL_RE.finditer(body):
            kind = _OP_SPELLING[c.group("op")]
            shapes = _SHAPE_RE.findall(c.group("shapes"))
            if c.group("start") and len(shapes) >= 2 and len(shapes) % 2 == 0:
                # async-start results alias (operand, result) pairs;
                # count the result half once
                shapes = shapes[len(shapes) // 2:]
            payload = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
            line_end = body.find("\n", c.start())
            line = body[c.start():line_end if line_end > 0 else len(body)]
            g = _GROUPS_RE.search(line)
            if g:
                k = len([x for x in g.group("first").split(",") if x])
            else:
                gi = _GROUPS_IOTA_RE.search(line)
                k = int(gi.group(2)) if gi else default_group
            coll[kind]["count"] += m
            coll[kind]["wire_bytes"] += m * _wire_bytes(kind, payload, k)
        defs = None
        for d in _DOT_RE.finditer(body):
            out_dt, out_dims = _SHAPE_RE.match(d.group("shape")).groups()
            out_elems = 1
            for x in out_dims.split(","):
                if x:
                    out_elems *= int(x)
            if defs is None:
                defs = {m_.group("name"): m_.group("shape")
                        for m_ in _DEF_RE.finditer(body)}
            lhs = _SHAPE_RE.match(defs.get(d.group("lhs"), ""))
            if lhs is None:
                notes.append(f"dot in {name}: lhs operand shape not "
                             "found; its FLOPs are not counted")
                continue
            lhs_shape = [int(x) for x in lhs.group("dims").split(",") if x]
            contract = 1
            for ci in d.group("cdims").split(","):
                if ci:
                    contract *= lhs_shape[int(ci)]
            dot_flops += m * 2.0 * out_elems * contract
    return Census(collectives={k: v for k, v in coll.items()
                               if v["count"] > 0},
                  dot_flops=dot_flops, n_while=n_while, notes=notes)


# ---------------------------------------------------------------------------
# Micro-benchmarked backend rates.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class MeasuredRates:
    """Backend rates measured on the live mesh: achievable GEMM flops/s
    per device, effective link bandwidth and per-collective launch cost
    (the two-size psum solve)."""
    rate_flops: float
    ici_gbs: float
    launch_s: float

    def to_json(self) -> Dict[str, float]:
        return dataclasses.asdict(self)


def _best_of(fn, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def measure_compute_rate(n: int = 384, dtype=None,
                         repeats: int = 3) -> float:
    """Achievable dense-GEMM flops/s of ONE device: time an [n,n]@[n,n]
    matmul (best-of-`repeats`, post-warmup). The measured-rate leg of the
    attribution — 'compute seconds' divides census FLOPs by this."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    a = jnp.ones((n, n), dtype)
    f = jax.jit(lambda x: x @ x)
    jax.block_until_ready(f(a))  # compile + warm
    t = _best_of(lambda: jax.block_until_ready(f(a)), repeats)
    return 2.0 * n ** 3 / max(t, 1e-9)


def measure_collective_rates(mesh=None, *, axis: Optional[str] = None,
                             sizes: Tuple[int, int] = (1 << 10, 1 << 21),
                             repeats: int = 3) -> Tuple[float, float]:
    """(ici_gbs, launch_s) of the live mesh from a two-size psum solve:
    ``t = launch + wire/bw`` at a tiny and a large payload gives both the
    per-collective dispatch cost and the effective link bandwidth. Uses
    the mesh's first axis of size > 1 (or `axis`); a degenerate mesh
    (1 device) returns table-free defaults (inf bandwidth, measured
    dispatch floor)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..utils import shard_map
    if mesh is None:
        from ..distributed.topology import build_mesh
        mesh = build_mesh({"x": len(jax.devices())})
    if axis is None:
        axis = next((a for a in mesh.axis_names if mesh.shape[a] > 1),
                    None)
    if axis is None:
        return float("inf"), 1e-6
    k = mesh.shape[axis]
    times = {}
    for elems in sizes:
        x = jnp.ones((elems,), jnp.float32)
        f = jax.jit(shard_map(lambda v: jax.lax.psum(v, axis), mesh=mesh,
                              in_specs=P(), out_specs=P()))
        jax.block_until_ready(f(x))
        times[elems] = _best_of(lambda: jax.block_until_ready(f(x)),
                                repeats)
    small, large = sizes
    w = {e: 2.0 * e * 4 * (k - 1) / k for e in sizes}  # psum wire bytes
    dt = times[large] - times[small]
    if dt <= 0:
        # launch-dominated at both sizes (tiny meshes / fast memcpy):
        # bandwidth unresolvable — report the floor and the launch
        return float("inf"), max(min(times.values()), 1e-9)
    bw = (w[large] - w[small]) / dt
    launch = max(times[small] - w[small] / bw, 1e-9)
    return bw / 1e9, launch


# ---------------------------------------------------------------------------
# The capture window + attribution.
# ---------------------------------------------------------------------------
_ACTIVE_LOCK = threading.Lock()
_ACTIVE_WINDOW: Optional[Dict[str, Any]] = None


def active_profile_window() -> Optional[Dict[str, Any]]:
    """Snapshot of the capture window currently open (None otherwise) —
    the flight recorder includes it in crash bundles so a hang mid-
    profile keeps the half-collected measurements."""
    with _ACTIVE_LOCK:
        return dict(_ACTIVE_WINDOW) if _ACTIVE_WINDOW is not None else None


@dataclasses.dataclass
class ProfileWindow:
    """One attributed capture window: N measured steps of one compiled
    program, split into compute vs per-kind collective time with each
    collective's hidden/exposed share."""
    label: str
    mode: Optional[str]
    steps: int
    step_time_s: float                      # median of the window
    step_times_s: List[float]
    compute_s: float
    flops_per_step: float
    cost_analysis_flops: Optional[float]
    wire_s: Dict[str, float]                # per collective kind, total
    exposed_s: Dict[str, float]             # per kind, exposed share
    total_wire_s: float
    exposed_comm_s: float
    hidden_comm_s: float
    overhead_s: float
    hidable_fraction: float
    rates: MeasuredRates
    census: Census

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["rates"] = self.rates.to_json()
        d["census"] = self.census.to_json()
        return d


def attribute_window(census: Census, step_time_s: float,
                     rates: MeasuredRates, *,
                     flops_per_step: Optional[float] = None
                     ) -> Dict[str, Any]:
    """The attribution arithmetic (shared by capture and tests): census +
    measured rates + observed step wall time -> compute seconds, per-kind
    total wire seconds, hidden vs exposed split, residual overhead."""
    flops = census.dot_flops if flops_per_step is None else flops_per_step
    compute_s = flops / max(rates.rate_flops, 1e-9)
    bw = rates.ici_gbs * 1e9
    wire_s = {k: (v["wire_bytes"] / bw if bw > 0 else 0.0)
              + v["count"] * rates.launch_s
              for k, v in census.collectives.items()}
    total_wire = sum(wire_s.values())
    exposed_total = min(max(step_time_s - compute_s, 0.0), total_wire)
    hidden_total = total_wire - exposed_total
    overhead = max(step_time_s - compute_s - exposed_total, 0.0)
    share = (exposed_total / total_wire) if total_wire > 0 else 0.0
    exposed = {k: v * share for k, v in wire_s.items()}
    return {"compute_s": compute_s, "wire_s": wire_s,
            "total_wire_s": total_wire, "exposed_comm_s": exposed_total,
            "hidden_comm_s": hidden_total, "overhead_s": overhead,
            "exposed_s": exposed,
            "hidable_fraction": (hidden_total / total_wire
                                 if total_wire > 0 else 0.0),
            "flops_per_step": flops}


def capture_step_profile(jitted_step, args: Sequence[Any], *,
                         steps: int = 5, label: str = "step",
                         mode: Optional[str] = None, mesh=None,
                         rates: Optional[MeasuredRates] = None,
                         flops_per_step: Optional[float] = None
                         ) -> ProfileWindow:
    """Capture + attribute a window of `steps` executions of a jitted
    step function (called with the same `args` each time — the step must
    not donate its inputs).

    The compiled HLO is censused (collectives by kind/bytes/groups, dot
    FLOPs, while-trip aware), backend rates are micro-benchmarked unless
    `rates` is passed (pass one shared MeasuredRates when capturing
    several windows — the solve costs a few collective dispatches), the
    median step wall time is measured post-warmup, and the window is
    attributed into compute vs hidden/exposed collective time
    (:func:`attribute_window`). `mode` labels what the window measured
    ("mp:seq_parallel", "dp:bucketed", ...) so
    :func:`derive_hardware_profile` can map its hidable fraction onto the
    planner's overlap-discount table.

    flops_per_step: trust an analytic model (observability.flops) over
    the dot census — e.g. for programs dominated by non-dot compute.
    """
    import jax
    global _ACTIVE_WINDOW
    lowered = jitted_step.lower(*args)
    compiled = lowered.compile()
    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text()
    census = hlo_census(text, default_group=len(jax.devices()))
    ca_flops: Optional[float] = None
    try:
        ca = compiled.cost_analysis()
        d = ca if isinstance(ca, dict) else ca[0]
        ca_flops = float(d.get("flops", 0.0))
    except Exception:
        pass
    if rates is None:
        bw, launch = measure_collective_rates(mesh)
        rates = MeasuredRates(rate_flops=measure_compute_rate(),
                              ici_gbs=bw, launch_s=launch)
    with _ACTIVE_LOCK:
        _ACTIVE_WINDOW = {"label": label, "mode": mode, "steps": steps,
                          "started_ts": time.time(), "step_times_s": []}
    try:
        jax.block_until_ready(jitted_step(*args))  # warm (compile cached)
        samples: List[float] = []
        for _ in range(max(steps, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(jitted_step(*args))
            samples.append(time.perf_counter() - t0)
            with _ACTIVE_LOCK:
                _ACTIVE_WINDOW["step_times_s"] = list(samples)
    finally:
        with _ACTIVE_LOCK:
            _ACTIVE_WINDOW = None
    med = sorted(samples)[len(samples) // 2]
    att = attribute_window(census, med, rates,
                           flops_per_step=flops_per_step)
    return ProfileWindow(
        label=label, mode=mode, steps=len(samples), step_time_s=med,
        step_times_s=[round(s, 6) for s in samples],
        compute_s=att["compute_s"],
        flops_per_step=att["flops_per_step"],
        cost_analysis_flops=ca_flops,
        wire_s=att["wire_s"], exposed_s=att["exposed_s"],
        total_wire_s=att["total_wire_s"],
        exposed_comm_s=att["exposed_comm_s"],
        hidden_comm_s=att["hidden_comm_s"],
        overhead_s=att["overhead_s"],
        hidable_fraction=att["hidable_fraction"],
        rates=rates, census=census)


# ---------------------------------------------------------------------------
# Measured HardwareProfile derivation + JSON io.
# ---------------------------------------------------------------------------
def derive_hardware_profile(windows: Sequence[ProfileWindow], *,
                            base=None, name: Optional[str] = None):
    """A measured HardwareProfile from attributed capture windows:
    effective ici_gbs and per-collective launch cost come from the
    windows' micro-benchmarked rates, gemm_efficiency from the measured
    GEMM rate against the base profile's peak, and each window labeled
    with a `mode` contributes its hidable fraction to the profile's
    ``hide`` override table (the keys CostModel consults instead of the
    hard-coded T3 constants). `base` defaults to the detected backend
    profile."""
    import dataclasses as dc
    from ..distributed.auto_tuner.planner import profile_for
    if base is None:
        base = profile_for()
    if not windows:
        return base
    rates = windows[0].rates
    bw = rates.ici_gbs if rates.ici_gbs != float("inf") else base.ici_gbs
    eff = min(max(rates.rate_flops / base.peak_flops, 1e-6), 1.0)
    hide = dict(base.hide or {})
    for w in windows:
        if w.mode:
            hide[str(w.mode)] = round(float(w.hidable_fraction), 4)
    overlap = any(h > 0.25 for h in hide.values())
    return dc.replace(
        base, name=name or f"measured:{base.name}", ici_gbs=float(bw),
        collective_launch_s=float(rates.launch_s), gemm_efficiency=eff,
        overlap_capable=bool(overlap or base.overlap_capable),
        hide=hide, source="measured")


def save_profile_json(path: str, profile,
                      windows: Sequence[ProfileWindow] = ()) -> str:
    """Serialize a (measured) HardwareProfile plus its capture windows —
    the artifact ``auto_tuner plan --profile <path>`` consumes."""
    import os
    from ..distributed.auto_tuner.planner import profile_to_json
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    payload = {"hardware_profile": profile_to_json(profile),
               "windows": [w.to_json() for w in windows]}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
    return path


def load_profile_json(path: str):
    """Load a HardwareProfile (+ windows metadata) saved by
    :func:`save_profile_json` (also accepts a bare profile dict)."""
    from ..distributed.auto_tuner.planner import profile_from_json
    with open(path, "r", encoding="utf-8") as f:
        payload = json.load(f)
    if "hardware_profile" in payload:
        payload = payload["hardware_profile"]
    return profile_from_json(payload)
