"""What a kernel had to do, counted by the program, over the time the
kernel took on the device, as a share of one of the chip's published
peaks (`peak`: "flops" or "hbm_bytes_per_s").

`kernel_hbm` with more than one count: the program's `span` carries the
counts in the attributes `per_unit` names, each turned into operations (or
bytes) by its own factor and added up. A step's kernels run between the
start of its `span` and the end of its `until` span, so counts and kernel
time are both taken from the first such start to the last such end inside
the traced window. A span that lacks one of the attributes (the parent of
the PR that brought them) reads None."""
from chipbench import program_trace, yardstick


def read(run, span, per_unit, until, kernel, peak):
    pt = program_trace.of(run)
    if not pt or not pt["devices"]:
        return None
    t0, t1 = program_trace.window(pt)
    starts = program_trace.spans(pt, span, t0, t1)
    ends = program_trace.spans(pt, until, t0, t1)
    if not starts or not ends:
        return None     # a program that opens no such spans
    a = min(h[1] for h in starts)
    b = max(h[1] + h[2] for h in ends)
    inside = [h[3] for h in starts if h[1] + h[2] <= b]
    if not all(attr in h for h in inside for attr in per_unit):
        return None
    work = sum(h[attr] * each for h in inside
               for attr, each in per_unit.items())
    ns = sum(y - x for _, name, x, y, _ in program_trace.leaf_ops(pt, a, b)
             if program_trace.kernel_of(name) == kernel)
    if not work or not ns:
        return None
    rate = work / (ns / len(pt["devices"]) / 1e9)
    return 100.0 * rate / yardstick.chip_peak(
        run["devices"][0].device_kind, peak)
