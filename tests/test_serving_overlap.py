"""One step in flight (ISSUE 31): `step()` dispatches step n+1 before it
fetches step n. Token for token, and at a settled checkpoint buffer for
buffer, that is the synchronous order (`step(); settle()`), in every
engine mode; where the host's decision needs the result in flight the
engine settles by itself, and an EOS costs one wasted row-step and never a
wrong token."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import gpt as G
from paddle_tpu.models.generation import gpt_generate

from serving_overlap import assert_same_state, both, drive

CFG = G.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                  max_seq_len=128, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return G.init_hybrid_params(CFG, jax.random.PRNGKey(0))


def golden(params, prompt, n):
    out = gpt_generate(params, CFG, jnp.asarray(prompt, jnp.int32)[None], n)
    return np.asarray(out)[0, len(prompt):].tolist()


def maker(params, **kw):
    base = dict(max_batch=4, block_size=8, num_blocks=40,
                max_blocks_per_seq=8, chunk=8, adaptive_mix=False,
                pool_audit=True, seed=11)
    base.update(kw)
    return lambda: ServingEngine(params, CFG, **base)


def prompts(n, lo=5, hi=21, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG.vocab_size, (int(s),))
            for s in rng.randint(lo, hi, n)]


def arrivals(ps, news, at, **kw):
    script = {}
    for p, n, t in zip(ps, news, at):
        script.setdefault(t, []).append(
            dict(prompt=p, max_new_tokens=n, **kw))
    return script


# -- the same schedule: state for state at a settled checkpoint -------------
SAME_SCHEDULE = {
    "greedy-k1": (dict(decode_burst=1), {}),
    "burst-k8": (dict(decode_burst=8), {}),
    "sampled": (dict(decode_burst=4), dict(temperature=0.8)),
    "int8": (dict(decode_burst=4, int8=True, kv_cache_dtype="int8"), {}),
}


@pytest.mark.parametrize("case", sorted(SAME_SCHEDULE))
def test_one_step_in_flight_is_the_synchronous_order(params, case):
    """Four slots, four requests arriving over the first calls, none
    waiting for a slot or a page: both orders dispatch the same packed
    steps, so after N calls (settled) lens, tables, pools and scales are
    equal bit for bit, and every output token for token, sampled rows
    too (the key is split once a dispatch, in the same order)."""
    eng_kw, req_kw = SAME_SCHEDULE[case]
    ps = prompts(4, seed=3)
    news = [21, 38, 17, 29]
    script = arrivals(ps, news, at=[0, 0, 2, 3], **req_kw)
    flight, sync = both(maker(params, **eng_kw), script, checkpoint=5)
    assert flight.outputs() == sync.outputs()
    assert all(status == "ok" and len(out) == n
               for (status, out), n in zip(flight.outputs(), news))
    assert_same_state(flight.state, sync.state)
    assert flight.state["lens"].any()       # rows were mid-decode there
    if case in ("greedy-k1", "burst-k8"):
        assert [out for _, out in flight.outputs()] == [
            golden(params, p, n) for p, n in zip(ps, news)]
    # every dispatch found the step before still in flight, but the first
    # and the one after this test's own settle at the checkpoint; nothing
    # made the engine settle before the last step
    eng = flight.eng
    want = [1] * eng.dispatches
    want[0] = want[5] = 0
    assert flight.in_flight == want
    assert eng.prom.get("steps_overlapped_total") == eng.dispatches - 2
    assert eng.prom.get("overlap_settles_total") == 2   # checkpoint, tail
    assert flight.settles("tail") == 1
    assert eng.prom.get("overlap_wasted_rows_total") == 0
    assert eng.dispatches == eng.engine_steps == flight.calls
    assert sync.in_flight == [0] * sync.eng.dispatches
    # one cache entry a program: `prev_tok` comes from the step before or
    # from the constructor and compiles nothing twice
    for e in (eng, sync.eng):
        assert e.compiled_cache_entries() == len(e._unified_cache)
    assert eng.free_pages() == eng._num_blocks - 1
    assert not eng.lens.any()


# -- an EOS is learnt one step late -----------------------------------------
@pytest.mark.parametrize("burst,at", [(1, 2), (8, 3)],
                         ids=["eos-in-pass-1", "eos-inside-a-burst"])
def test_an_eos_costs_one_wasted_row_step_and_no_token(params, burst, at):
    """One slot, a request whose EOS falls at its token `at` (pass 1 of a
    K = 1 step; the fourth of the eight tokens of a K = 8 step), a second
    one queued behind it. The row rides one more step, its tokens are
    dropped, and the slot is the next request's two calls later than in
    the synchronous order (a count-finished row's: one call later)."""
    a, b = prompts(2, lo=9, hi=10, seed=4)
    g = golden(params, a, 12)
    eos = g[at]
    assert eos not in g[:at]
    seen = []
    script = {0: [dict(prompt=a, max_new_tokens=12, eos_id=eos,
                       on_token=lambda rid, t: seen.append(t)),
                  dict(prompt=b, max_new_tokens=5)]}
    flight, sync = both(maker(params, max_batch=1, decode_burst=burst),
                        script)
    assert flight.outputs() == sync.outputs()
    assert flight.outputs()[0] == ("ok", g[:at + 1])
    assert flight.outputs()[1] == ("ok", golden(params, b, 5))
    assert seen == 2 * g[:at + 1]       # streamed once an engine, no more
    assert flight.eng.prom.get("overlap_wasted_rows_total") == 1
    assert sync.eng.prom.get("overlap_wasted_rows_total") == 0
    ra, rb = flight.order
    assert flight.admitted_at[rb] == sync.admitted_at[rb] + 2
    assert flight.eng.free_pages() == flight.eng._num_blocks - 1


def test_a_count_finished_row_is_not_packed_again(params):
    """`max_new_tokens` is known to the host: the row is left out of the
    next step, nothing is wasted, and its slot is reused one call later
    than in the synchronous order."""
    a, b = prompts(2, lo=9, hi=10, seed=4)
    script = {0: [dict(prompt=a, max_new_tokens=6),
                  dict(prompt=b, max_new_tokens=5)]}
    flight, sync = both(maker(params, max_batch=1, decode_burst=1), script)
    assert flight.outputs() == sync.outputs()
    assert flight.eng.prom.get("overlap_wasted_rows_total") == 0
    rb = flight.order[1]
    assert flight.admitted_at[rb] == sync.admitted_at[rb] + 1
    # the slot's hand-over is the one call without a dispatch
    assert flight.in_flight.count(0) == 2
    assert flight.eng.engine_steps == flight.eng.dispatches + 1


# -- where the host's decision needs the result, the engine settles ---------
def test_shared_prefix_pages_register_at_the_walk(params):
    """Three requests behind one 2-page prefix: the owner's pages are
    registered when its prefill has been WALKED (a call later), the
    siblings then share them; same tokens, no page leaked, the audit on."""
    rng = np.random.RandomState(0)
    common = rng.randint(0, 97, (16,))
    ps = [np.concatenate([common, rng.randint(0, 97, (4,))])
          for _ in range(3)]
    script = arrivals(ps, [7, 6, 8], at=[0, 1, 1])
    flight, sync = both(maker(params, prefix_share=True, decode_burst=2),
                        script)
    assert flight.outputs() == sync.outputs()
    assert [out for _, out in flight.outputs()] == [
        golden(params, p, n) for p, n in zip(ps, [7, 6, 8])]
    for run in (flight, sync):
        assert run.eng.prom.get("kv_prefix_hits_total") == 2
        assert run.eng.free_pages() == run.eng._num_blocks - 1
        assert run.eng.load_stats()["kv_pages_shared"] == 0.0


def test_a_preemption_is_decided_on_settled_state(params):
    """A pool that forces a victim: the engine settles before it chooses
    one and folds its output into its prompt; both requests end golden."""
    rng = np.random.RandomState(7)
    ps = [rng.randint(0, 97, (8,)) for _ in range(2)]
    script = arrivals(ps, [24, 24], at=[0, 0])
    flight, sync = both(maker(params, max_batch=2, num_blocks=7,
                              preempt=True, preempt_wait_steps=1,
                              decode_burst=2), script)
    assert flight.outputs() == sync.outputs()
    assert [out for _, out in flight.outputs()] == [
        golden(params, p, 24) for p in ps]
    assert flight.settles("preempt") >= 1
    for run in (flight, sync):
        assert run.eng.prom.get("requests_preempted_total") >= 1
        assert run.eng.free_pages() == run.eng._num_blocks - 1


def test_cancel_of_a_running_request_settles_first(params):
    """`cancel` between two steps: the request keeps every token of the
    step that was in flight, as in the synchronous order, and its pages
    return."""
    ps = prompts(2, seed=5)

    def cancel(eng, run):
        got = eng.cancel(run.order[0], "user")
        assert got is run.reqs[run.order[0]]

    script = arrivals(ps, [30, 11], at=[0, 0])
    flight, sync = both(maker(params, decode_burst=2), script,
                        hooks={4: cancel})
    assert flight.outputs() == sync.outputs()
    status, out = flight.outputs()[0]
    assert status == "cancelled" and 0 < len(out) < 30
    assert out == golden(params, ps[0], 30)[:len(out)]
    assert flight.outputs()[1] == ("ok", golden(params, ps[1], 11))
    assert flight.settles("cancel") == 1
    assert flight.eng.free_pages() == flight.eng._num_blocks - 1


def test_a_deadline_that_expires_mid_generation_settles_first(params):
    ps = prompts(2, seed=6)

    def expire(eng, run):
        run.reqs[run.order[0]].deadline = time.perf_counter() - 1.0

    script = {0: [dict(prompt=ps[0], max_new_tokens=30, deadline_s=1e6),
                  dict(prompt=ps[1], max_new_tokens=9)]}
    flight, sync = both(maker(params, decode_burst=2), script,
                        hooks={5: expire})
    assert flight.outputs() == sync.outputs()
    status, out = flight.outputs()[0]
    assert status == "cancelled" and 0 < len(out) < 30
    assert flight.settles("expire") == 1
    assert flight.eng.free_pages() == flight.eng._num_blocks - 1


def test_speculative_drafts_settle_every_step(params):
    """The proposer reads `Request.output`: with drafts on, every pack
    waits for the step before (as fast as it was, no faster) and accepts
    what it accepted in the synchronous order."""
    rng = np.random.RandomState(8)
    ps = [np.tile(rng.randint(0, 97, (5,)), 4) for _ in range(2)]
    script = arrivals(ps, [14, 10], at=[0, 1])
    flight, sync = both(maker(params, spec_decode_k=2), script)
    assert flight.outputs() == sync.outputs()
    assert [out for _, out in flight.outputs()] == [
        golden(params, p, n) for p, n in zip(ps, [14, 10])]
    eng = flight.eng
    assert eng.spec_proposed == sync.eng.spec_proposed > 0
    assert eng.spec_accepted == sync.eng.spec_accepted
    assert flight.in_flight == [0] * eng.dispatches
    assert flight.settles("spec") == eng.dispatches - 1
    assert eng.prom.get("steps_overlapped_total") == 0
    assert eng.dispatches == eng.engine_steps


def test_settle_and_the_settling_reads(params):
    """`settle()` is a no-op with nothing in flight; an outside read of
    `slots` settles (and counts as `observer`), `snapshot()` and
    `load_stats()` do not and show committed progress."""
    eng = maker(params, decode_burst=2)()
    eng.settle()
    assert eng.prom.get("overlap_settles_total") == 0
    rid = eng.add_request(prompts(1, seed=9)[0], 12)
    for _ in range(4):
        eng.step()
    assert eng.has_work() and eng._flight is not None
    before = eng.snapshot()["slots"][0]
    eng.load_stats()
    assert eng._flight is not None              # neither read settled
    r = eng.slots[0]                            # this one does
    assert eng._flight is None and r.rid == rid
    assert eng.prom.get("overlap_settles_total",
                        labels={"reason": "observer"}) == 1
    after = eng.snapshot()["slots"][0]
    assert after["emitted"] == len(r.output) == before["emitted"] + 2
    assert int(eng.lens[0]) == len(r.prompt) + len(r.output) - 1
    assert 'overlap_settles_total{reason="observer"} 1' in eng.metrics_text()
    out = eng.run()
    assert out[rid] == golden(params, r.prompt, 12)


def test_a_run_that_exhausts_its_budget_returns_settled(params):
    eng = maker(params, decode_burst=1)()
    rid = eng.add_request(prompts(1, seed=10)[0], 20)
    res = eng.run(max_steps=5)
    assert res.leftover == [rid] and eng._flight is None
    r = eng._slots[0]
    assert int(eng._lens[0]) == len(r.prompt) + len(r.output) - 1
    done = eng.run()
    assert done[rid] == golden(params, r.prompt, 20)


def test_the_warm_up_the_benchmark_runs_ends_with_nothing_in_flight(params):
    """`chipbench.runners.serve_closed._warm_up` (not this repo's to edit)
    overrides `_pick_burst` on the instance, cancels everything, takes ONE
    step and requires an idle engine."""
    from chipbench.runners.serve_closed import _warm_up
    eng = maker(params, decode_burst=4, num_blocks=64)()
    _warm_up(eng, CFG.vocab_size, 8)
    assert not eng.has_work() and eng._flight is None
    assert sorted(k for k, _ in eng._unified_cache) == [1, 2, 4]
    assert eng.free_pages() == eng._num_blocks - 1


def test_the_last_step_is_not_held_back_a_call(params):
    """Nothing queued and every row scheduled to end in the step just
    dispatched: the engine lands it in the same call (`tail`), so a lone
    request's last tokens do not wait for a call that has nothing to
    dispatch."""
    run = drive(maker(params)(), {0: [dict(prompt=np.arange(6),
                                           max_new_tokens=3)]}, sync=False)
    eng = run.eng
    assert eng.dispatches == eng.engine_steps == run.calls
    assert run.settles("tail") == 1 and not eng.has_work()
