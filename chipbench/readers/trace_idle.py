"""The share of the traced window in which no operation ran on the device,
mean over the devices, from the profiler's trace."""
from chipbench import trace_reduce


def read(run):
    trace = run.get("trace")
    if not trace or not trace["devices"]:
        return None
    t0, t1 = trace_reduce.traced_window(trace)
    if t1 <= t0:
        return None
    busy = trace_reduce.busy_seconds(trace, t0, t1)
    return 100.0 * (1.0 - busy / ((t1 - t0) / 1e9))
