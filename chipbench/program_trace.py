"""The program's own names in the run's trace: every host span with its
attributes, and every device operation with the `jax.named_scope` path it
was traced under and, for a Pallas kernel, the kernel's name.

`harness.Trace.stop()` keeps only the benchmark's spans and no stats, so
this module opens the run's `.xplane.pb` a second time (once per process)
and keeps a form like `trace_reduce`'s, one field wider:

    {"devices": {"<plane>": [[name, start_ns, dur_ns, path], ...]},
     "async":   {"<plane>": [[name, start_ns, dur_ns, path], ...]},
     "host": [[span name, start_ns, dur_ns, {attribute: value}], ...]}

`name` is `trace_reduce.short_name` of the event; `path` is the
operation's `op_name` ("jit(step)/transpose(jvp())/while/body/attn/qkv/
dot_general"), which the profiler stores as the `tf_op` stat of the
event's METADATA, where `jax.profiler.ProfileData` does not look. So the
file is read here with a small decoder of the protobuf wire format
(tsl/profiler/protobuf/xplane.proto), nothing imported.

A program that names nothing (the parent of the PR that brought this)
gives paths without scopes and a host list without its spans; every
reader built on this form then returns None.
"""

import glob
import os
import re
import struct

from chipbench import harness, trace_reduce

_cache = {}


# -- the wire format --------------------------------------------------------
def _fields(buf):
    """(field number, value) of one message: an int for a varint or a
    fixed-width field, bytes for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, v
        elif wire == 2:
            ln = shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, buf[i:i + ln]
            i += ln
        elif wire == 1:
            yield key >> 3, int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            yield key >> 3, int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names):
    """(name, value) of one XStat."""
    name = value = None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v)
        elif f == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            value = stat_names.get(v)
    return name, value


def _plane(buf):
    """One XPlane: (name, [(line name, timestamp_ns, [event bytes])],
    {metadata id: (name, {stat: value})})."""
    name, lines, raw_meta, stat_names = "", [], [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            raw_meta.append(v)
        elif f == 5:
            for g, w in _fields(v):
                if g == 2:
                    sid = sname = None
                    for h, x in _fields(w):
                        if h == 1:
                            sid = x
                        elif h == 2:
                            sname = bytes(x).decode()
                    stat_names[sid] = sname
    return name, lines, raw_meta, stat_names


def _event_metadata(raw_meta, stat_names):
    meta = {}
    for entry in raw_meta:
        for g, w in _fields(entry):
            if g != 2:
                continue
            mid, mname, stats = None, "", {}
            for h, x in _fields(w):
                if h == 1:
                    mid = x
                elif h == 2:
                    mname = bytes(x).decode("utf-8", "replace")
                elif h == 5:
                    k, val = _stat(x, stat_names)
                    stats[k] = val
            meta[mid] = (mname, stats)
    return meta


def _line(buf):
    name, ts, events = "", 0, []
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            ts = _signed(v)
        elif f == 4:
            events.append(v)
    return name, ts, events


def _event(buf, ts):
    """(metadata id, start_ns, dur_ns, [stat bytes])."""
    mid = off = dur = 0
    stats = []
    for f, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            off = _signed(v)
        elif f == 3:
            dur = _signed(v)
        elif f == 4:
            stats.append(v)
    return mid, ts + off // 1000, dur // 1000, stats


def from_xplane(path):
    """The form above of one trace file."""
    with open(path, "rb") as f:
        space = memoryview(f.read())    # slices of it copy nothing
    out = {"devices": {}, "async": {}, "host": []}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, lines, raw_meta, stat_names = _plane(plane)
        device = trace_reduce.DEVICE_PLANE.match(name)
        if not device and not name.startswith("/host:"):
            continue
        meta = _event_metadata(raw_meta, stat_names)
        short = {}
        for raw in lines:
            lname, ts, events = _line(raw)
            if device:
                part = {trace_reduce.OPS_LINE: "devices",
                        trace_reduce.ASYNC_LINE: "async"}.get(lname)
                if part is None:
                    continue
                rows = out[part][name] = []
                for ev in events:
                    mid, start, dur, _ = _event(ev, ts)
                    if mid not in short:
                        hlo, stats = meta.get(mid, ("", {}))
                        short[mid] = (trace_reduce.short_name(hlo),
                                      stats.get("tf_op") or "")
                    rows.append([short[mid][0], start, dur, short[mid][1]])
            else:
                for ev in events:
                    mid, start, dur, stats = _event(ev, ts)
                    attrs = dict(_stat(s, stat_names) for s in stats)
                    out["host"].append([meta.get(mid, ("", {}))[0], start,
                                        dur, attrs])
    return out


def of(run):
    """The run's trace in this module's form: what a test put under
    `run["program_trace"]`, else the newest `.xplane.pb` that
    `harness.Trace` left under the checkout, read once; None when the run
    was not traced."""
    if "program_trace" in run:
        return run["program_trace"]
    if not run.get("trace"):
        return None
    files = glob.glob(os.path.join(harness.ROOT, ".chipbench_trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    if path not in _cache:
        _cache.clear()
        _cache[path] = from_xplane(path)
    return _cache[path]


# -- reading the form -------------------------------------------------------
def window(pt):
    """[t0, t1] in ns of the benchmark's `traced_window` span."""
    return trace_reduce.traced_window(
        {"host": [h[:3] for h in pt["host"] if h[0] == "traced_window"],
         "devices": {k: [e[:3] for e in v[:1] + v[-1:]]
                     for k, v in pt["devices"].items()}})


def busy_ns(pt, t0, t1):
    """ns inside [t0, t1] in which an operation ran, mean over the devices
    (what `trace_reduce.busy_seconds` gives for the reduced form)."""
    per_dev = [trace_reduce._union_ns(
        [(max(s, t0), min(s + d, t1)) for _, s, d, _ in events
         if s < t1 and s + d > t0]) for events in pt["devices"].values()]
    return sum(per_dev) / len(per_dev) if per_dev else 0.0


def spans(pt, name, t0, t1):
    """The host spans of one name that lie wholly inside [t0, t1]."""
    return [h for h in pt["host"]
            if h[0] == name and h[1] >= t0 and h[1] + h[2] <= t1]


_SEGMENT = re.compile(r"[^/()]+")


def scope_of(path, scopes):
    """The innermost of `scopes` on an operation's `op_name` path, or
    None. A path's segments are split at "/" and at the brackets of
    jax's transforms ("transpose(jvp(attn))" holds the segment "attn")."""
    found = None
    for seg in _SEGMENT.findall(path):
        if seg in scopes:
            found = seg
    return found


def leaf_ops(pt, t0, t1):
    """(plane, name, start, end, path) of every operation that is not a
    container (a loop's time is its body's), clipped to [t0, t1]."""
    for plane, events in pt["devices"].items():
        for name, s, d, path in events:
            if trace_reduce.CONTAINER.search(name):
                continue
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                yield plane, name, a, b, path


def kernel_of(name):
    """"ragged_paged_attn.7|tpu_custom_call|bf16[...]" -> the kernel's
    name "ragged_paged_attn"; None for anything but a Pallas kernel."""
    if not trace_reduce.CUSTOM_CALL.search(name):
        return None
    return re.sub(r"\.\d+$", "", name.split("|", 1)[0])
