"""Host time the program spent in some of its own spans, in milliseconds
per step, over the steps that lie wholly inside the traced window."""
from chipbench import program_trace


def read(run, spans, per):
    pt = program_trace.of(run)
    if not pt:
        return None
    t0, t1 = program_trace.window(pt)
    steps = program_trace.spans(pt, per, t0, t1)
    if not steps:
        return None
    a = min(s[1] for s in steps)
    b = max(s[1] + s[2] for s in steps)
    inside = [h for name in spans for h in program_trace.spans(pt, name, a, b)]
    if not inside:
        return None     # a program that opens none of these spans
    return sum(h[2] for h in inside) / len(steps) / 1e6
