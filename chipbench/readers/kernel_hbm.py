"""The bytes a kernel had to read, counted by the program, over the time the
kernel took on the device, as a share of the chip's published HBM rate.

The program's `span` carries the count in attribute `attr` (for ragged
paged attention: KV positions attended in the step), `bytes_per_unit`
turns it into bytes, and `kernel` names the Pallas kernel. A step's
kernels run between the start of its `span` and the end of its `until`
span (the host blocked on the result), so spans and kernel time are both
taken from the first such start to the last such end inside the traced
window."""
from chipbench import program_trace, yardstick


def read(run, span, attr, until, kernel, bytes_per_unit):
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
    units = sum(h[3].get(attr, 0) for h in starts if h[1] + h[2] <= b)
    ns = sum(y - x for _, name, x, y, _ in program_trace.leaf_ops(pt, a, b)
             if program_trace.kernel_of(name) == kernel)
    if not units or not ns:
        return None
    rate = units * bytes_per_unit / (ns / len(pt["devices"]) / 1e9)
    return 100.0 * rate / yardstick.chip_peak(
        run["devices"][0].device_kind, "hbm_bytes_per_s")
