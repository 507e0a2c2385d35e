"""Prefix page sharing + speculative decoding (ISSUE 17): refcounted KV
pool invariants (share, copy-on-write on divergence, free-at-zero,
quantized-pool scale inheritance), n>1 fan-out sharing, the exact-match
speculative verify path (bitwise-greedy under perfect / garbage / n-gram
proposers), composition with deadlines, preemption, the crash-replay
driver and the multi-replica router (zero leaked pages on failover), and
the flags-off byte-identical-program contract.

Every engine here runs with ``pool_audit=True``: the refcount /
free-list / cached-free partition is re-verified on every slot release,
so a sharing bug fails loudly inside the test instead of leaking."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import flag, set_flags
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.inference.speculative import (ReplayCache,
                                              make_ngram_proposer,
                                              ngram_propose)
from paddle_tpu.models import gpt as G
from paddle_tpu.models.generation import gpt_generate

CFG = G.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                  max_seq_len=128, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return G.init_hybrid_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _restore_flags():
    keep = {k: flag(k) for k in ("serving_prefix_share",
                                 "serving_spec_decode_k",
                                 "serving_pool_audit")}
    yield
    set_flags(keep)
    paddle.set_flags({"FLAGS_fault_inject": ""})


def golden(params, prompt, n):
    out = gpt_generate(params, CFG, jnp.asarray(prompt, jnp.int32)[None], n)
    return np.asarray(out)[0, len(prompt):].tolist()


def mk(params, **kw):
    base = dict(max_batch=2, block_size=8, num_blocks=24,
                max_blocks_per_seq=8, chunk=8, adaptive_mix=False,
                pool_audit=True)
    base.update(kw)
    return ServingEngine(params, CFG, **base)


def drive(eng):
    reported = {}
    for _ in range(10000):
        if not eng.has_work():
            break
        for r in eng.step():
            reported[r.rid] = r
    return reported


# ---------------------------------------------------------------------------
# refcounted pool: share, COW, free-at-zero
# ---------------------------------------------------------------------------
def test_shared_system_prompt_pages_refcounted(params):
    """Three requests opening with the same 16-token (2-page) system
    prompt: after the first registers the pages, the others REFERENCE
    them (refcount > 1 observable mid-run), outputs stay golden, and the
    drained pool returns every page."""
    rng = np.random.RandomState(0)
    common = rng.randint(0, 97, (16,))
    prompts = [np.concatenate([common, rng.randint(0, 97, (4,))])
               for _ in range(3)]
    # burst=1: decode spans steps, so the shared refcounts are
    # observable at step boundaries (a full burst finishes in one)
    eng = mk(params, max_batch=3, prefix_share=True,
             decode_burst=1)
    # prime: the first request registers the prompt's full pages
    r0 = eng.add_request(prompts[0], 4)
    res0 = eng.run()
    assert res0[r0] == golden(params, prompts[0], 4)
    rids = [eng.add_request(p, 6) for p in prompts[1:]]
    peak_shared = 0
    outs = {}
    while eng.has_work():
        for r in eng.step():
            outs[r.rid] = r.output
        peak_shared = max(peak_shared, int((eng.refcount > 1).sum()))
    assert peak_shared == 2, peak_shared   # both system-prompt pages
    for rid, p in zip(rids, prompts[1:]):
        assert outs[rid] == golden(params, p, 6)
    assert eng.free_pages() == eng._num_blocks - 1   # free-at-zero
    assert eng.load_stats()["kv_pages_shared"] == 0.0


def test_fanout_identical_prompts_cow_on_divergence(params):
    """n>1 fan-out: three IDENTICAL page-aligned prompts against a
    primed prefix cache. All three branches resume from the cached
    pages; the first claimant is the sole holder of the last page and
    writes in place, each FURTHER branch's recompute would land inside
    the now-shared last page, so it copies-on-write first — exactly one
    COW per extra branch — and every branch's greedy output is bitwise
    the single-request golden."""
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, 97, (16,))      # exactly 2 full pages
    g = golden(params, prompt, 6)
    eng = mk(params, max_batch=3, prefix_share=True)
    r0 = eng.add_request(prompt, 6)
    assert eng.run()[r0] == g               # primes the 2-page cache
    rids = [eng.add_request(prompt, 6) for _ in range(3)]
    res = eng.run()
    assert [res[r] for r in rids] == [g, g, g]
    assert eng.cow_copies == 2, eng.cow_copies
    assert eng.free_pages() == eng._num_blocks - 1


def test_shared_pages_survive_first_finisher(params):
    """Free returns a page only at refcount 0: the short branch finishes
    first, the long branch keeps decoding off the still-referenced
    shared pages and stays golden."""
    rng = np.random.RandomState(2)
    common = rng.randint(0, 97, (16,))
    p_short = np.concatenate([common, rng.randint(0, 97, (4,))])
    p_long = np.concatenate([common, rng.randint(0, 97, (4,))])
    eng = mk(params, max_batch=2, prefix_share=True,
             decode_burst=1)
    r0 = eng.add_request(p_short, 2)
    eng.run()
    rs = eng.add_request(p_short, 2)
    rl = eng.add_request(p_long, 16)
    seen_survivor = False
    outs = {}
    while eng.has_work():
        for r in eng.step():
            outs[r.rid] = r.output
        if rs in outs and rl not in outs:
            # short branch done, long branch mid-decode: the shared
            # prompt pages must still be live (held by the survivor)
            assert eng.free_pages() < eng._num_blocks - 1
            seen_survivor = True
    assert seen_survivor
    assert outs[rl] == golden(params, p_long, 16)
    assert eng.free_pages() == eng._num_blocks - 1
    del r0


def test_prefix_cache_evicts_lru_under_pressure(params):
    """Cached-free prefix pages are a soft reserve: when the free list
    runs dry they are evicted (LRU) for fresh allocations — distinct
    workloads keep running golden through a pool sized below the total
    cache footprint, and nothing leaks."""
    rng = np.random.RandomState(3)
    eng = mk(params, max_batch=1, num_blocks=9,
             prefix_share=True)
    for i in range(6):
        p = rng.randint(0, 97, (16,))       # 2 full pages cached each
        rid = eng.add_request(p, 4)
        assert eng.run()[rid] == golden(params, p, 4)
    assert eng.free_pages() == eng._num_blocks - 1


def test_quantized_pool_sharing_and_cow_bitwise(params):
    """int8 KV pool: shared pages carry their per-page scales, and a COW
    copy inherits the source page's running absmax — sharing and fan-out
    reproduce the no-share int8 engine bitwise."""
    rng = np.random.RandomState(4)
    common = rng.randint(0, 97, (16,))
    prompts = [np.concatenate([common, rng.randint(0, 97, (4,))]),
               common.copy(), common.copy()]
    news = [6, 5, 5]

    def run(share):
        eng = mk(params, max_batch=2,
                 kv_cache_dtype="int8", prefix_share=share)
        r0 = eng.add_request(prompts[0], news[0])
        eng.run()
        rids = [eng.add_request(p, n) for p, n in zip(prompts[1:],
                                                      news[1:])]
        res = eng.run()
        leak = eng._num_blocks - 1 - eng.free_pages()
        del r0
        return [res[r] for r in rids], eng.cow_copies, leak

    base, cow_off, _ = run(False)
    shared, cow_on, leak = run(True)
    assert shared == base
    assert cow_off == 0 and cow_on >= 1, (cow_off, cow_on)
    assert leak == 0


# ---------------------------------------------------------------------------
# speculative decoding: exact-match acceptance = bitwise greedy
# ---------------------------------------------------------------------------
def _proposer_matrix(params, prompt, n):
    g = golden(params, prompt, n)
    full = list(prompt) + g

    def perfect(ctx, k):
        done = len(ctx)
        return full[done:done + k]

    def garbage(ctx, k):
        return [(int(ctx[-1]) + 7) % 97] * k

    return g, {"perfect": perfect, "garbage": garbage,
               "ngram": ngram_propose}


@pytest.mark.parametrize("kind", ["perfect", "garbage", "ngram"])
def test_spec_greedy_bitwise_vs_plain(params, kind):
    """Exact-match acceptance makes the proposer a pure speed knob:
    brilliant, useless, or n-gram drafts all emit BITWISE the plain
    greedy output."""
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, 97, (9,))
    g, props = _proposer_matrix(params, prompt, 12)
    eng = mk(params, spec_decode_k=3,
             proposer=props[kind], decode_burst=1)
    rid = eng.add_request(prompt, 12)
    assert eng.run()[rid] == g
    assert eng.spec_proposed > 0
    if kind == "perfect":
        assert eng.spec_accepted == eng.spec_proposed


def test_spec_perfect_proposer_multiplies_tokens_per_step(params):
    """A fully-accepted k=3 draft emits up to 4 tokens per dispatch: the
    perfect proposer must finish in well under half the plain engine's
    steps (this is the throughput claim, measured in steps, not wall)."""
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, 97, (8,))
    g, props = _proposer_matrix(params, prompt, 16)

    def steps(**kw):
        eng = mk(params, decode_burst=1, **kw)
        rid = eng.add_request(prompt, 16)
        assert eng.run()[rid] == g
        return eng.engine_steps

    plain = steps()
    spec = steps(spec_decode_k=3, proposer=props["perfect"])
    assert spec * 2 < plain, (spec, plain)


def test_spec_replay_cache_proposer_accepts_repeat_traffic(params):
    """ReplayCache: a second wave of identical requests proposes from the
    first wave's recorded outputs and accepts ~everything."""
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 97, (n,)) for n in (8, 11)]
    cache = ReplayCache()
    eng = mk(params, spec_decode_k=3, proposer=cache,
             decode_burst=1)
    rids = [eng.add_request(p, 10) for p in prompts]
    res = eng.run()
    for p, rid in zip(prompts, rids):
        assert res[rid] == golden(params, p, 10)
        cache.record(p, res[rid])
    p0, a0 = eng.spec_proposed, eng.spec_accepted
    rids2 = [eng.add_request(p, 10) for p in prompts]
    res2 = eng.run()
    assert [res2[r] for r in rids2] == [res[r] for r in rids]
    assert eng.spec_accepted - a0 == eng.spec_proposed - p0 > 0


def test_spec_one_dispatch_per_step_preserved(params):
    """Speculation must not break the single-dispatch contract: the
    verify pass rides the ONE unified program (no extra dispatches), and
    every compiled entry is one of the engine's unified variants."""
    rng = np.random.RandomState(8)
    eng = mk(params, spec_decode_k=3, decode_burst=1)
    eng.add_request(rng.randint(0, 97, (9,)), 10)
    eng.run()
    assert eng.dispatches == eng.engine_steps > 0
    assert eng.compiled_cache_entries() == len(eng._unified_cache) > 0


def test_spec_counters_in_stats_and_metrics(params):
    rng = np.random.RandomState(9)
    # a constant proposer guarantees spec_proposed > 0 (n-gram on a
    # random prompt may legitimately never fire)
    eng = mk(params, spec_decode_k=3, prefix_share=True,
             decode_burst=1, proposer=lambda ctx, k: [1] * k)
    eng.add_request(rng.randint(0, 97, (9,)), 8)
    eng.run()
    stats = eng.load_stats()
    for k in ("kv_pages_shared", "kv_cow_copies_total",
              "spec_proposed_total", "spec_accepted_total"):
        assert k in stats, k
        assert k in eng.snapshot(), k
    assert stats["spec_proposed_total"] == float(eng.spec_proposed) > 0
    text = eng.metrics_text()
    assert "spec_proposed_total" in text
    assert "kv_pages_shared" in text


# ---------------------------------------------------------------------------
# composition: deadlines, preemption, crash-replay, router failover
# ---------------------------------------------------------------------------
def test_spec_with_deadline_shed(params):
    """Speculation composes with the deadline scheduler: an
    expired-on-arrival request sheds, the live one still decodes
    speculatively to its golden."""
    rng = np.random.RandomState(10)
    p1, p2 = rng.randint(0, 97, (8,)), rng.randint(0, 97, (8,))
    eng = mk(params, max_batch=1, spec_decode_k=3,
             decode_burst=1)
    r1 = eng.add_request(p1, 8)
    r2 = eng.add_request(p2, 8, deadline_s=0.0)
    rep = drive(eng)
    assert rep[r2].status == "shed"
    assert rep[r1].status == "ok"
    assert rep[r1].output == golden(params, p1, 8)


def test_spec_and_share_with_preempt_requeue(params):
    """Pool exhaustion with sharing + speculation on: the decode victim
    is evicted (shared pages decref'd, not freed under a survivor), the
    requeued recompute is token-identical, and no pages leak."""
    rng = np.random.RandomState(11)
    pv = rng.randint(0, 97, (8,))
    ph = rng.randint(0, 97, (8,))
    eng = mk(params, max_batch=2, num_blocks=7,
             preempt=True, preempt_wait_steps=1, spec_decode_k=3,
             prefix_share=True, decode_burst=1)
    rv = eng.add_request(pv, 24)
    rh = eng.add_request(ph, 24)
    rep = drive(eng)
    assert rep[rv].output == golden(params, pv, 24)
    assert rep[rh].output == golden(params, ph, 24)
    assert rep[rv].preemptions >= 1
    assert eng.free_pages() == eng._num_blocks - 1


def test_spec_and_share_with_crash_replay_bitwise(params):
    """The resilient replay driver rebuilds a speculating, sharing
    engine after an injected step fault: replayed requests re-propose
    and still deliver bitwise goldens exactly once, zero leaked pages."""
    from paddle_tpu.inference.resilient import run_serving_resilient
    rng = np.random.RandomState(12)
    common = rng.randint(0, 97, (8,))
    prompts = [np.concatenate([common, rng.randint(0, 97, (n,))])
               for n in (1, 3, 5, 7)]
    news = [6, 4, 7, 5]
    goldens = [golden(params, p, n) for p, n in zip(prompts, news)]
    paddle.set_flags({"FLAGS_fault_inject": "serving/step:3"})
    seen = {i: [] for i in range(4)}
    reqs = [{"prompt": p, "max_new_tokens": n,
             "on_token": lambda lid, t: seen[lid].append(t)}
            for p, n in zip(prompts, news)]
    results, info = run_serving_resilient(
        lambda: mk(params, spec_decode_k=3,
                   prefix_share=True, decode_burst=1),
        reqs, retry_backoff_s=0.001)
    paddle.set_flags({"FLAGS_fault_inject": ""})
    assert info["rebuilds"] == 1
    assert [results[i] for i in range(4)] == goldens
    assert all(seen[i] == goldens[i] for i in range(4))
    assert info["free_blocks"] == info["pool_blocks"]


def test_router_failover_with_shared_pages_zero_leak(params):
    """ISSUE 17 router contract: a replica death while requests SHARE
    prefix pages must decref on requeue, not double-free — every request
    completes bitwise on the survivor, exactly one failover, and every
    live replica drains to a full pool."""
    from paddle_tpu.distributed.resilience import faults
    from paddle_tpu.inference.router import ReplicaSet, Router
    rng = np.random.RandomState(13)
    common = rng.randint(0, 97, (8,))       # one full shared page
    prompts = [np.concatenate([common, rng.randint(0, 97, (n,))])
               for n in (1, 3, 5, 7)]
    news = [6, 4, 7, 5]
    goldens = {i: golden(params, p, n)
               for i, (p, n) in enumerate(zip(prompts, news))}

    def make_engine():
        return mk(params, decode_burst=2, prefix_share=True,
                  spec_decode_k=2)

    router = Router(ReplicaSet.in_process(make_engine, n=2))
    lids = [router.submit(p, n) for p, n in zip(prompts, news)]
    faults.configure("serving/step:5")
    try:
        while router.has_work():
            router.step()
    finally:
        faults.configure("")
    assert {i: router.delivered[lid]
            for i, lid in enumerate(lids)} == goldens
    assert router.failovers == 1, router.failovers
    for rep in router.replica_set:
        free, total = rep.free_pool()
        if free is not None:
            assert free == total, (rep.idx, free, total)
    router.close()


# ---------------------------------------------------------------------------
# flags: off is byte-identical, on resolves
# ---------------------------------------------------------------------------
def test_flags_off_unified_program_byte_identical(params):
    """Defaults off: a flag-resolved engine compiles the SAME unified
    program bytes as an explicit share-off, spec-off engine — the
    tentpole is invisible until switched on."""
    assert flag("serving_prefix_share") is False
    assert int(flag("serving_spec_decode_k")) == 0
    e_auto = mk(params)
    e_off = mk(params, prefix_share=False, spec_decode_k=0)
    assert e_auto.prefix_share is False and e_auto.spec_k == 0
    R, T = e_auto.max_batch, e_auto.token_budget
    nb = e_auto.tables.shape[1]
    args = (params, jnp.zeros((T,), jnp.int32), jnp.zeros((T,), jnp.int32),
            jnp.full((T,), T, jnp.int32), jnp.zeros((R,), jnp.int32),
            jnp.zeros((R,), jnp.int32), jnp.zeros((R,), jnp.int32),
            jnp.zeros((R, nb), jnp.int32), jnp.zeros((R,), bool),
            jnp.zeros((R,), bool), jnp.zeros((R,), jnp.int32),
            jnp.full((R,), -1, jnp.int32), jnp.zeros((R,), jnp.float32),
            jnp.zeros((R,), jnp.int32),                      # prev_tok
            jax.random.PRNGKey(0), e_auto.k_pools, e_auto.v_pools)
    assert (e_auto._unified(1).lower(*args).as_text()
            == e_off._unified(1).lower(*args).as_text())


def test_flags_resolve_share_spec_audit(params):
    set_flags({"serving_prefix_share": True, "serving_spec_decode_k": 4,
               "serving_pool_audit": True})
    eng = ServingEngine(params, CFG, max_batch=2, block_size=8,
                        num_blocks=24, max_blocks_per_seq=8, chunk=8,
                        adaptive_mix=False)
    assert eng.prefix_share is True
    assert eng.spec_k == 4
    assert eng.pool_audit is True
    set_flags({"serving_prefix_share": False, "serving_spec_decode_k": 0,
               "serving_pool_audit": False})
    eng2 = mk(params, pool_audit=None)
    assert eng2.prefix_share is False and eng2.spec_k == 0
    assert eng2.pool_audit is False


def test_pool_audit_detects_refcount_corruption(params):
    """The audit actually bites: a manufactured refcount mismatch fails
    the next release loudly instead of leaking."""
    rng = np.random.RandomState(14)
    eng = mk(params, prefix_share=True)
    eng.add_request(rng.randint(0, 97, (9,)), 4)
    eng.run()
    eng.refcount[3] += 1                    # corrupt
    with pytest.raises(RuntimeError, match="audit"):
        eng._audit_pool()


# ---------------------------------------------------------------------------
# proposers: pure-function contracts
# ---------------------------------------------------------------------------
def test_ngram_propose_prefers_longest_recent_match():
    ctx = [1, 2, 3, 9, 1, 2, 3]
    assert ngram_propose(ctx, 2) == [9, 1]   # trigram 1,2,3 -> follows 9
    assert ngram_propose([5, 6, 7], 3) == []         # no earlier match
    # cycle reuse: the [4,4,4] suffix matches at 0, one token follows
    assert ngram_propose([4, 4, 4, 4], 2) == [4]
    assert ngram_propose(ctx, 0) == []
    bound = make_ngram_proposer(max_ngram=2, min_ngram=2)
    assert bound([1, 2, 9, 3, 2, 9], 1) == [3]


def test_replay_cache_prefix_match_and_divergence():
    c = ReplayCache(max_entries=2)
    c.record([1, 2], [3, 4, 5])
    assert c([1, 2], 2) == [3, 4]
    assert c([1, 2, 3], 3) == [4, 5]        # mid-output resume
    assert c([1, 2, 9], 2) == []            # diverged -> no proposal
    assert c([7, 7], 2) == []               # unknown prompt
    c.record([8], [1])
    c.record([9], [2])                      # evicts the oldest entry
    assert c([1, 2], 1) == []
