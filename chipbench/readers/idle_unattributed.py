"""The share of the device's idle time, in the traced window, that no span
of the program accounts for: a gap between operations counts as accounted
for when one of the named spans covers its middle. Mean over the devices."""
from chipbench import program_trace


def read(run, spans):
    pt = program_trace.of(run)
    if not pt or not pt["devices"]:
        return None
    t0, t1 = program_trace.window(pt)
    cover = sorted((h[1], h[1] + h[2]) for name in spans
                   for h in program_trace.spans(pt, name, t0, t1))
    if not cover:
        return None     # a program that opens none of these spans
    idle = loose = 0
    for events in pt["devices"].values():
        busy = sorted((max(s, t0), min(s + d, t1)) for _, s, d, _ in events
                      if s < t1 and s + d > t0)
        end = t0
        for a, b in busy + [(t1, t1)]:
            if a > end:
                mid = (a + end) // 2
                idle += a - end
                if not any(s <= mid <= e for s, e in cover):
                    loose += a - end
            end = max(end, b)
    return 100.0 * loose / idle if idle else None
