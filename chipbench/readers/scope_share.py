"""Device time in the operations of some of the program's named scopes, as
a share of the device's busy time in the traced window.

`of` lists the scopes that divide the program between this cell's share
metrics; an operation belongs to the innermost of them on its `op_name`
path. `scopes` are the ones this metric counts; an empty list counts the
operations under none of `of` (work no line of the program asked for by
name). Containers (loops, calls) are left out: their time is their
bodies'."""
from chipbench import program_trace


def read(run, scopes, of):
    pt = program_trace.of(run)
    if not pt or not pt["devices"]:
        return None
    t0, t1 = program_trace.window(pt)
    want, known = set(scopes), set(of)
    part = named = 0
    for _, _, a, b, path in program_trace.leaf_ops(pt, t0, t1):
        scope = program_trace.scope_of(path, known)
        if scope is not None:
            named += b - a
        if (scope in want) if want else (scope is None):
            part += b - a
    busy = program_trace.busy_ns(pt, t0, t1)
    if not named or busy <= 0:
        return None     # a program that names none of these scopes
    return 100.0 * part / len(pt["devices"]) / busy
