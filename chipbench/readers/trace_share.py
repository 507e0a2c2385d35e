"""Device time in operations of one kind, as a share of the device's busy
time or of the traced window, from the profiler's trace."""
from chipbench import trace_reduce

KINDS = {"custom_call": trace_reduce.CUSTOM_CALL,
         "collective": trace_reduce.COLLECTIVE}


def read(run, kind, of):
    trace = run.get("trace")
    if not trace or not trace["devices"]:
        return None
    t0, t1 = trace_reduce.traced_window(trace)
    part = trace_reduce.pattern_seconds(trace, KINDS[kind], t0, t1)
    whole = (trace_reduce.busy_seconds(trace, t0, t1) if of == "busy"
             else (t1 - t0) / 1e9)
    return 100.0 * part / whole if whole > 0 else None
