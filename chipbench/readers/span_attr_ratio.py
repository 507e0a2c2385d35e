"""A share, in percent, read from the attributes of the program's spans of
one name that lie wholly inside the traced window. The numerator is the
sum of attribute `num` or, with `num_equals`, the count of the spans whose
`num` has that value; the denominator is the sum of attribute `den` times
`den_times`, or without `den` the count of the spans. Spans that lack an
attribute named here are not counted, so a program that opens the span
without it (the parent of the PR that brought it) reads None."""
from chipbench import program_trace


def read(run, span, num, den=None, num_equals=None, den_times=1.0):
    pt = program_trace.of(run)
    if not pt:
        return None
    t0, t1 = program_trace.window(pt)
    need = [num] + ([den] if den else [])
    hs = [h[3] for h in program_trace.spans(pt, span, t0, t1)
          if all(a in h[3] for a in need)]
    top = (sum(a[num] for a in hs) if num_equals is None
           else sum(a[num] == num_equals for a in hs))
    bottom = (sum(a[den] for a in hs) if den else len(hs)) * den_times
    return 100.0 * top / bottom if bottom else None
