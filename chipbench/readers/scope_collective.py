"""Time in which a collective that the program issued under one of the
named scopes (`coll_mp`, `coll_dp`: the mesh axis it crosses) runs or is in
flight, as a share of the traced window. Mean over the chips."""
from chipbench import program_trace, trace_reduce


def read(run, scopes):
    pt = program_trace.of(run)
    if not pt or not pt["devices"]:
        return None
    t0, t1 = program_trace.window(pt)
    want = set(scopes)
    total, seen = 0, False
    for dev in pt["devices"]:
        spans = []
        for name, s, d, path in pt["devices"][dev] + pt["async"].get(dev, []):
            if trace_reduce.COLLECTIVE.search(name) and \
                    program_trace.scope_of(path, want):
                seen = True
                a, b = max(s, t0), min(s + d, t1)
                if b > a:
                    spans.append((a, b))
        total += trace_reduce._union_ns(spans)
    if not seen or t1 <= t0:
        return None     # a program that scopes no collective
    return 100.0 * total / len(pt["devices"]) / (t1 - t0)
