"""A statistic of one attribute over the program's spans of one name that
lie wholly inside the traced window: `stat` is "p<q>" (a percentile, the
yardstick's interpolation), "mean" or "sum"; `attrs` lists the attributes
to add up span by span before the statistic is taken (one name reads that
attribute alone); `scale` turns the program's unit into the metric's
(0.001: microseconds to milliseconds). A span that lacks one of the
attributes is not counted, so a program that opens the span without them
(the parent of the PR that brought them) reads None."""
from chipbench import program_trace, yardstick


def read(run, span, attrs, stat, scale=1.0):
    pt = program_trace.of(run)
    if not pt:
        return None
    t0, t1 = program_trace.window(pt)
    values = [sum(h[3][a] for a in attrs)
              for h in program_trace.spans(pt, span, t0, t1)
              if all(a in h[3] for a in attrs)]
    if not values:
        return None
    if stat == "sum":
        value = sum(values)
    elif stat == "mean":
        value = sum(values) / len(values)
    else:
        value = yardstick.percentile(values, float(stat[1:]))
    return value * scale
