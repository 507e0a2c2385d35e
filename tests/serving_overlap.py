"""Driver shared by the one-step-in-flight tests (ISSUE 31): the same
arrivals through two engines of one seed, one called `step()` alone (a
step stays in flight between calls) and one `step(); settle()` every
iteration — the synchronous order the engine had before."""

import numpy as np

from paddle_tpu import observability as obs
from paddle_tpu.observability.trace import SERVING_SPANS


class Driven:
    """What one engine did with a script of arrivals."""

    def __init__(self, eng, sync):
        self.eng, self.sync = eng, sync
        self.reqs = {}          # rid -> Request
        self.order = []         # rids in submission order
        self.admitted_at = {}   # rid -> call index it first held a slot
        self.in_flight = []     # the dispatch spans' attribute, in order
        self.state = None       # at the checkpoint, settled
        self.calls = 0

    def outputs(self):
        return [(self.reqs[rid].status, list(self.reqs[rid].output))
                for rid in self.order]

    def settles(self, reason):
        return self.eng.prom.get("overlap_settles_total",
                                 labels={"reason": reason})


def settled_state(eng):
    """Everything the device and the allocator hold, read the way an
    outsider may: the reads settle."""
    live = sorted(int(b) for b in np.unique(eng.tables) if b)
    state = {"lens": np.array(eng.lens), "tables": eng.tables.copy(),
             "live_pages": live, "free_pages": eng.free_pages(),
             "k": np.asarray(eng.k_pools), "v": np.asarray(eng.v_pools)}
    if eng.k_scales is not None:
        state["ks"] = np.asarray(eng.k_scales)
        state["vs"] = np.asarray(eng.v_scales)
    if eng.ssm_state is not None:
        state["ssm"] = np.asarray(eng.ssm_state)
        state["tail"] = np.asarray(eng.conv_tail)
    return state


def drive(eng, script, sync, hooks=None, checkpoint=None, max_calls=400):
    """script: {call index: [add_request keywords, ...]}; hooks: {call
    index: f(eng, run)} run before that call's step; checkpoint: after
    that many calls, settle and keep `settled_state`."""
    run = Driven(eng, sync)
    hooks = hooks or {}
    last = max(list(script) + list(hooks))
    with obs.capture_spans() as cap:
        while run.calls <= last or eng.has_work():
            for kw in script.get(run.calls, ()):
                rid = eng.add_request(**kw)
                run.reqs[rid] = eng.queue[-1]
                run.order.append(rid)
            if run.calls in hooks:
                hooks[run.calls](eng, run)
            eng.step()
            if sync:
                eng.settle()
            # committed progress, read without settling
            for s in eng.snapshot()["slots"]:
                if s is not None:
                    run.admitted_at.setdefault(s["rid"], run.calls)
            run.calls += 1
            if run.calls == checkpoint:
                eng.settle()
                run.state = settled_state(eng)
            assert run.calls < max_calls, "the script never drained"
    run.in_flight = [e.attrs["in_flight"] for e in cap.events
                     if e.name == SERVING_SPANS.dispatch]
    return run


def both(make, script, **kw):
    """(one step in flight, synchronous order) over the same script."""
    return (drive(make(), script, sync=False, **kw),
            drive(make(), script, sync=True, **kw))


def assert_same_state(a, b):
    assert a.keys() == b.keys()
    for name in a:
        if isinstance(a[name], np.ndarray):
            np.testing.assert_array_equal(a[name], b[name], err_msg=name)
        else:
            assert a[name] == b[name], name
