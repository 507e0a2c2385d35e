"""One figure of the program's own record of its start-up
(`paddle_tpu.observability.startup_record`: its traces, lowerings, cache
loads and compilations by name), cut where the run's set-up ended: the
record's zero is the process's start, `setup_s` counts from the runner's
first line, and the quarter second between them is the interpreter's own
start. A compile behind the cut is the window's (`compiles_in_window`).
The whole record goes to the run's log once, as one `[startup]` line. A
program without the record (the parent of the PR that brought it) reads
None, and so does a set-up in which no program was compiled or loaded
(the tests' dummy runner): there is nothing to put down to a cache."""
import json

from chipbench import harness

_logged = False


def read(run, key):
    global _logged
    setup_s = run.get("e2e", {}).get("setup_s")
    if setup_s is None:
        return None
    from paddle_tpu import observability
    record_of = getattr(observability, "startup_record", None)
    if record_of is None:
        return None
    record = record_of(until_s=setup_s + 0.25)
    if not record["programs"]:
        return None
    if not _logged:
        _logged = True
        longest = [dict(e, seconds=round(e["seconds"], 3))
                   for e in record["longest"][:5]]
        harness.log("[startup]", json.dumps(
            {**{k: round(v, 3) if isinstance(v, float) else v
                for k, v in record.items() if k != "longest"},
             "setup_s": round(setup_s, 3), "longest": longest}))
    return record.get(key)
