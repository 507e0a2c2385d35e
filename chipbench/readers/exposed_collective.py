"""The share of the traced window in which a collective runs or is in
flight on a chip while no other operation runs there: the part of
`collective_pct` that compute does not hide. Mean over the chips; from the
reduced form of the trace alone."""
from chipbench import trace_reduce


def read(run):
    trace = run.get("trace")
    if not trace or not trace["devices"]:
        return None
    t0, t1 = trace_reduce.traced_window(trace)
    if t1 <= t0:
        return None
    exposed = 0
    for dev, ops in trace["devices"].items():
        coll, other = [], []
        for name, a, b in trace_reduce._clip(
                ops + trace.get("async", {}).get(dev, []), t0, t1):
            if trace_reduce.COLLECTIVE.search(name):
                coll.append((a, b))
            elif not trace_reduce.CONTAINER.search(name):
                other.append((a, b))
        both = trace_reduce._union_ns(coll + other)
        exposed += both - trace_reduce._union_ns(other)
    return 100.0 * exposed / len(trace["devices"]) / (t1 - t0)
