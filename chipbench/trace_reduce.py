"""From a profiler trace to numbers: device busy and idle time, time by
operation name, and idle gaps named by what the host was doing.

The reduction works on a small neutral form, so that it can be tested on a
recorded slice kept beside the tests:

    {"devices": {"<plane name>": [[name, start_ns, dur_ns], ...]},
     "async":   {"<plane name>": [[name, start_ns, dur_ns], ...]},
     "host": [[span name, start_ns, dur_ns], ...]}

`from_xplane` makes that form from the `.xplane.pb` the JAX profiler
writes: one entry per TPU device plane, holding the events of its "XLA Ops"
line (the operations as they ran), and the benchmark's own
`TraceAnnotation` spans from the host plane.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"            # the operations as they ran, nested
ASYNC_LINE = "Async XLA Ops"    # start-to-done spans of asynchronous ones
# An event is named "<stem>|<kind>|<result type>", from the HLO text the
# profiler gives (see `short_name`).
COLLECTIVE = re.compile(
    r"\|(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)")
CUSTOM_CALL = re.compile(r"\|tpu_custom_call\|")   # a Pallas kernel
CONTAINER = re.compile(r"\|(while|conditional|call)\|")
_HLO = re.compile(r"^%?(?P<stem>[^ ]+) = (?P<rest>.*)$", re.S)
_KIND = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_TYPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def from_xplane(path, host_spans):
    """Neutral form of one trace file. `host_spans` are the names of the
    benchmark's own spans to keep from the host plane."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "async": {}, "host": []}
    keep = set(host_spans)
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                part = {OPS_LINE: "devices", ASYNC_LINE: "async"}.get(
                    line.name)
                if part is not None:
                    out[part][plane.name] = [
                        [short_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)] for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in keep:
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


def short_name(hlo):
    """"%copy.100 = bf16[24,16]{...} copy(...)" -> "copy.100|copy|bf16[24,16]".
    A custom call to a Pallas kernel gets the kind `tpu_custom_call`."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:80]
    rest = m["rest"]
    kind = _KIND.search(rest)
    kind = kind.group(1) if kind else "?"
    if kind == "custom-call" and 'custom_call_target="tpu_custom_call"' in rest:
        kind = "tpu_custom_call"
    typ = _TYPE.search(rest)
    return f"{m['stem']}|{kind}|{typ.group(0) if typ else ''}"


def _union_ns(intervals):
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _clip(events, t0, t1):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def window_of(trace):
    """[t0, t1] in ns: from the first device event to the last."""
    starts = [s for ev in trace["devices"].values() for _, s, _ in ev]
    ends = [s + d for ev in trace["devices"].values() for _, s, d in ev]
    return (min(starts), max(ends)) if starts else (0, 0)


def busy_seconds(trace, t0=None, t1=None):
    """Seconds in which an operation ran, mean over the devices."""
    if t0 is None:
        t0, t1 = window_of(trace)
    per_dev = [_union_ns([(a, b) for _, a, b in _clip(ev, t0, t1)])
               for ev in trace["devices"].values()]
    return sum(per_dev) / len(per_dev) / 1e9 if per_dev else 0.0


def pattern_seconds(trace, pattern, t0=None, t1=None):
    """Seconds in which an operation whose name matches ran or, for an
    asynchronous one, was in flight (union, so overlapping events are not
    counted twice), mean over the devices."""
    if t0 is None:
        t0, t1 = window_of(trace)
    per_dev = [_union_ns([(a, b) for part in ("devices", "async")
                          for n, a, b in _clip(
                              trace.get(part, {}).get(dev, []), t0, t1)
                          if pattern.search(n)])
               for dev in trace["devices"]]
    return sum(per_dev) / len(per_dev) / 1e9 if per_dev else 0.0


def top_ops(trace, n=10):
    """[[name, seconds]]: the operations that took most device time,
    summed over their events and averaged over the devices."""
    total = {}
    for ev in trace["devices"].values():
        for name, _, d in ev:
            if not CONTAINER.search(name):   # a loop's time is its body's
                total[name] = total.get(name, 0) + d
    k = max(len(trace["devices"]), 1)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def idle_gaps(trace, n=10):
    """[[host span, seconds]]: the longest gaps between operations on the
    first device, each named by the benchmark's span that covers the
    gap's middle ("outside_spans" when none does)."""
    if not trace["devices"]:
        return []
    events = sorted(trace["devices"][sorted(trace["devices"])[0]],
                    key=lambda e: e[1])
    gaps, end = [], None
    for _, s, d in events:
        if end is not None and s > end:
            gaps.append((s - end, (s + end) // 2))
        end = s + d if end is None else max(end, s + d)
    out = []
    for length, mid in sorted(gaps, reverse=True)[:n]:
        # the innermost (shortest) covering span names the gap
        cover = [(d, name) for name, s, d in trace["host"]
                 if s <= mid <= s + d]
        out.append([min(cover)[1] if cover else "outside_spans",
                    length / 1e9])
    return out


def traced_window(trace):
    """[t0, t1] in ns of the benchmark's `traced_window` span, which is on
    the device events' clock; without one, first to last device event."""
    spans = [(s, s + d) for name, s, d in trace["host"]
             if name == "traced_window"]
    if spans:
        return max(spans, key=lambda se: se[1] - se[0])
    return window_of(trace)
