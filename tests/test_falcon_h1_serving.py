"""Falcon-H1 on the serving path (ISSUE 28): the engine's own step, pools,
allocator and burst scan serving a block that runs GQA attention and a
Mamba-2 mixer side by side, held against the plain reference
(`chipbench/reference/falcon_h1.py`) on LOGITS, at a toy size of the same
structure: 2 layers, a query group of 2, 2 state groups of 2 mixer heads,
conv 4, scan chunk = engine chunk.

How the logits are read: `ragged_step._sample` is wrapped to hand every
pass's [R, V] logits to the host (an ordered debug callback), and the
engine's walk is wrapped to file each emitted token's row under its
request. The reference then gets prompt + served tokens in one forward.

Tolerances. Engine and reference both compute in float32 here, so what
separates them is the order of the sums: the chunked scan against the
step-by-step recurrence, paged online softmax against a dense softmax,
XLA's default CPU matmul against `highest`. Logits are O(1) (largest
|logit| about 1.2); the largest difference seen over all cases below is
3e-7, and LOGIT_ATOL is 13x that. A recurrent state kept in bfloat16
(8 mantissa bits, rounded at every token) moves logits by 1.7e-5 to
2.7e-5 and fails it; a state that is not zeroed for a slot's next request
moves them by 8e-4.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.enforce import EnforceNotMet  # noqa: E402
from paddle_tpu.inference import ragged_step as RS  # noqa: E402
from paddle_tpu.inference.serving import ServingEngine  # noqa: E402
from paddle_tpu.kernels.pallas import ssm  # noqa: E402
from paddle_tpu.models import falcon_h1 as FH  # noqa: E402
from paddle_tpu.models import gpt as G  # noqa: E402
from paddle_tpu.observability.trace import SERVING_SPANS  # noqa: E402

from chipbench import weights_falcon_h1 as WF  # noqa: E402
from chipbench.reference import falcon_h1 as R  # noqa: E402

W = dict(vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
         num_kv_heads=2, head_dim=16, ffn_hidden=128, rope_theta=1e11,
         rms_norm_eps=1e-5, ssm_heads=4, ssm_head_dim=16, ssm_groups=2,
         ssm_state=32, ssm_conv=4, ssm_chunk=8)
# toy multipliers: the published ones are tuned to widths 80x these and
# would leave the mixer's share of a toy logit too small to test
M = dict(embedding_multiplier=5.656854249492381,
         attention_in_multiplier=1.0, key_multiplier=0.5,
         attention_out_multiplier=0.5, ssm_in_multiplier=0.5,
         ssm_multipliers=[0.7, 0.5, 0.6, 0.9, 0.7], ssm_out_multiplier=0.4,
         mlp_multipliers=[0.5, 0.3], lm_head_multiplier=2.0)
LOGIT_ATOL = 4e-6
ENGINE = dict(max_batch=4, block_size=8, num_blocks=40,
              max_blocks_per_seq=8, chunk=8, decode_burst=4)


def toy_cfg(**kw):
    base = dict(W, **{k: tuple(v) if isinstance(v, list) else v
                      for k, v in M.items()},
                dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(kw)
    return FH.FalconH1Config(**base)


@pytest.fixture(scope="module")
def params():
    return WF.make_params(W, 3, jnp.float32)


class Logits:
    """Every emitted token's logits row, by request."""

    def __init__(self, monkeypatch):
        self.passes, self.by_rid, self._walk, self._packed = [], {}, {}, 0
        real = RS._sample

        def spy(logits, temps, key):
            jax.debug.callback(lambda a: self.passes.append(np.asarray(a)),
                               logits, ordered=True)
            return real(logits, temps, key)
        monkeypatch.setattr(RS, "_sample", spy)

    def watch(self, eng):
        walk, emit, pack = eng._walk_ragged, eng._emit, eng._pack_ragged
        first = {}      # a packed step -> its first pass's place (a step
        #                 is walked while the next one's passes arrive)

        def _pack(fresh):
            b = pack(fresh)
            if b is not None:
                first[id(b)] = self._packed
                self._packed += b.K
            return b

        def _walk(b, *a):
            self._walk = {"base": first.pop(id(b)), "n": {}}
            return walk(b, *a)

        def _emit(r, tok):
            t = self._walk["n"].get(r.rid, 0)
            self._walk["n"][r.rid] = t + 1
            self.by_rid.setdefault(r.rid, []).append(
                self.passes[self._walk["base"] + t][r.slot])
            return emit(r, tok)
        eng._walk_ragged, eng._emit, eng._pack_ragged = _walk, _emit, _pack
        return eng


@pytest.fixture
def logits(monkeypatch):
    return Logits(monkeypatch)


def worst_gap(params, logits, prompt, output, rid, sound=True):
    """Largest |engine logit - reference logit| over a request's served
    positions, the reference given prompt + served tokens at once. A
    sound engine also served the reference's own greedy tokens."""
    seq = np.concatenate([prompt, np.asarray(output, np.int32)])
    ref = np.asarray(R.forward(params, jnp.asarray(seq), W, M))
    got = np.stack(logits.by_rid[rid])
    assert got.shape[0] == len(output)
    want = ref[len(prompt) - 1:len(seq) - 1]
    if sound:
        assert (want.argmax(-1) == np.asarray(output)).all()
    return float(np.abs(got - want).max())


def prompts(n, lo=3, hi=30, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, W["vocab_size"], int(s), dtype=np.int32)
            for s in rng.integers(lo, hi, n)]


# -- engine against reference -------------------------------------------------
@pytest.mark.parametrize("burst", [1, 4])
def test_chunked_prefill_then_paged_decode(params, logits, burst):
    """21 prompt tokens in chunks of 8, 8 and 5 continuing from the slot's
    state, then 12 tokens through pages and state, K passes a step."""
    eng = logits.watch(ServingEngine(params, toy_cfg(),
                                     **dict(ENGINE, decode_burst=burst)))
    prompt = prompts(1, 21, 22)[0]
    rid = eng.add_request(prompt, 12)
    out = eng.run()
    assert len(out[rid]) == 12
    assert worst_gap(params, logits, prompt, out[rid], rid) < LOGIT_ATOL
    assert eng.dispatches == eng.engine_steps      # one program a step


def test_mixed_steps_decode_rows_beside_prefill_chunks(params, logits):
    """Requests arrive while others decode, so passes carry one-token rows
    and chunks together; six requests share four slots, so slots are
    reused too."""
    eng = logits.watch(ServingEngine(params, toy_cfg(), **ENGINE))
    ps = prompts(6, seed=1)
    rids, outs = [], {}
    mixed = 0
    for p in ps:
        rids.append(eng.add_request(p, 9))
        for _ in range(2):
            # committed progress, read without settling the step in flight
            live = [r for r in eng.snapshot()["slots"] if r is not None]
            dec = sum(r["prefill_done"] >= r["prompt_len"] for r in live)
            mixed += bool(eng.queue or dec < len(live)) and dec > 0
            for r in eng.step():
                outs[r.rid] = r.output
    outs.update(eng.run())
    assert mixed >= 3
    for p, rid in zip(ps, rids):
        assert worst_gap(params, logits, p, outs[rid], rid) < LOGIT_ATOL


def test_a_slot_reused_by_a_second_request_starts_from_zero(params, logits):
    eng = logits.watch(ServingEngine(params, toy_cfg(),
                                     **dict(ENGINE, max_batch=1)))
    a, b = prompts(2, 10, 20, seed=2)
    ra = eng.add_request(a, 6)
    out = eng.run()
    state_after_a = np.asarray(eng.ssm_state)
    assert np.abs(state_after_a).max() > 0      # the slot's state is dirty
    rb = eng.add_request(b, 6)
    out.update(eng.run())
    assert worst_gap(params, logits, a, out[ra], ra) < LOGIT_ATOL
    assert worst_gap(params, logits, b, out[rb], rb) < LOGIT_ATOL
    assert eng.prom.get("ssm_state_resets_total") == 2


def test_a_state_that_is_not_reset_is_caught(params, logits, monkeypatch):
    """The same two requests with the in-program reset taken out: the
    second request's logits leave the tolerance."""
    scan, conv = FH.ssm_scan, FH.ssm_conv
    monkeypatch.setattr(FH, "ssm_scan", lambda *a, **kw: scan(
        *a[:8], jnp.zeros_like(a[8]), **kw))
    monkeypatch.setattr(FH, "ssm_conv", lambda *a, **kw: conv(
        *a[:9], jnp.zeros_like(a[9]), **kw))
    eng = logits.watch(ServingEngine(params, toy_cfg(),
                                     **dict(ENGINE, max_batch=1)))
    a, b = prompts(2, 10, 20, seed=2)
    eng.add_request(a, 6)
    eng.run()
    rb = eng.add_request(b, 6)
    out = eng.run()
    assert worst_gap(params, logits, b, out[rb], rb,
                     sound=False) > 100 * LOGIT_ATOL


def test_a_preempted_request_resumes_with_its_state_rebuilt(params, logits):
    """Preempted mid-decode (pages and slot released), the request comes
    back with its served tokens folded into the prompt and re-prefills
    from position 0; what it serves after is what an undisturbed run
    serves."""
    eng = logits.watch(ServingEngine(params, toy_cfg(), **ENGINE))
    a, b = prompts(2, 12, 20, seed=3)
    ra, rb = eng.add_request(a, 14), eng.add_request(b, 14)
    while len(eng.slots[0].output if eng.slots[0] else ()) < 5:
        eng.step()
    victim = eng.slots[0]
    assert victim.rid == ra and not victim.done
    eng._preempt(victim)
    out = eng.run()
    assert victim.preemptions == 1 and len(out[ra]) == 14
    assert worst_gap(params, logits, a, out[ra], ra) < LOGIT_ATOL
    assert worst_gap(params, logits, b, out[rb], rb) < LOGIT_ATOL


def test_a_bfloat16_state_fails_the_tolerance(params, logits):
    eng = logits.watch(ServingEngine(params, toy_cfg(),
                                     ssm_state_dtype="bfloat16", **ENGINE))
    assert eng.ssm_state.dtype == jnp.bfloat16
    prompt = prompts(1, 21, 22)[0]
    rid = eng.add_request(prompt, 12)
    out = eng.run()
    gap = worst_gap(params, logits, prompt, out[rid], rid, sound=False)
    assert gap > 3 * LOGIT_ATOL, gap


def test_an_int8_kv_pool_beside_the_float32_state_serves_the_same(params):
    """The quantized pool's append and scales ride the same carry as the
    state; at this size its rounding flips no greedy token."""
    served = []
    for kv in ("auto", "int8"):
        eng = ServingEngine(params, toy_cfg(), kv_cache_dtype=kv, **ENGINE)
        rids = [eng.add_request(p, 10) for p in prompts(3, 10, 30, seed=5)]
        out = eng.run()
        served.append([list(out[r]) for r in rids])
    assert served[0] == served[1]


# -- the engine's side ---------------------------------------------------------
def test_the_pool_is_sized_by_kv_heads_and_the_state_by_slots(params):
    cfg = toy_cfg()
    eng = ServingEngine(params, cfg, **ENGINE)
    assert eng.k_pools.shape == (2, cfg.num_kv_heads, 40, 8, 16)
    assert eng.ssm_state.shape == (2, 4, 4, 16, 32)
    assert eng.ssm_state.dtype == jnp.float32
    assert eng.conv_tail.shape == (2, 3, 4, cfg.conv_dim)
    eng.add_request(np.arange(5, dtype=np.int32), 2)
    eng.run()
    assert eng.prom.get("ssm_state_bytes") == \
        eng.ssm_state.nbytes + eng.conv_tail.nbytes
    # the GPT engine keeps none, and its pool is sized as before
    gcfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                       num_heads=4, max_seq_len=64, dtype=jnp.float32)
    geng = ServingEngine(G.init_hybrid_params(gcfg, jax.random.PRNGKey(0)),
                         gcfg, max_batch=2, block_size=8,
                         num_blocks=8, chunk=8)
    assert geng.ssm_state is None and geng.k_pools.shape[1] == 4


def test_positions_pass_no_table(params):
    """RoPE at the row's position: a sequence longer than any GPT position
    table of the toy tests (64) is served, and still matches."""
    cfg = toy_cfg()
    assert not hasattr(cfg, "max_seq_len")
    pos = jnp.asarray([0, 63, 64, 5000])
    assert (FH.Serving.positions(pos, cfg) == pos).all()


@pytest.mark.parametrize("kw,word", [
    (dict(ragged=False), "PR 30"), (dict(int8=True), "int8"),
    (dict(prefix_share=True), "prefix_share"),
    (dict(spec_decode_k=2), "spec_decode_k"), (dict(mesh=True), "mesh"),
    (dict(chunk=16), "scan chunk")])
def test_what_the_hybrid_cannot_be_served_with_raises_at_construction(
        params, kw, word):
    if "mesh" in kw:
        from jax.sharding import Mesh
        kw = dict(mesh=Mesh(np.array(jax.devices()[:2]), ("mp",)))
    with pytest.raises(EnforceNotMet, match=word):
        ServingEngine(params, toy_cfg(), **dict(ENGINE, **kw))


# -- one step in flight (ISSUE 31) --------------------------------------------
def test_one_step_in_flight_is_the_synchronous_order(params):
    """The same arrivals through `step()` alone and through `step();
    settle()`: every output token for token, and at a settled checkpoint
    the recurrent state and conv tail bit for bit beside lens, tables and
    pools."""
    from serving_overlap import assert_same_state, both
    ps = prompts(4, seed=5)
    news = [19, 30, 14, 23]
    script = {}
    for p, n, at in zip(ps, news, [0, 0, 2, 3]):
        script.setdefault(at, []).append(dict(prompt=p, max_new_tokens=n))
    flight, sync = both(lambda: ServingEngine(params, toy_cfg(), **ENGINE),
                        script, checkpoint=6)
    assert flight.outputs() == sync.outputs()
    assert [len(out) for _, out in flight.outputs()] == news
    assert_same_state(flight.state, sync.state)
    assert np.abs(flight.state["ssm"]).max() > 0
    assert flight.state["lens"].any()
    eng = flight.eng
    want = [1] * eng.dispatches
    want[0] = want[6] = 0       # the first; the one after the checkpoint
    assert flight.in_flight == want
    assert eng.dispatches == eng.engine_steps
    assert eng.compiled_cache_entries() == len(eng._unified_cache)
    assert eng.prom.get("ssm_state_resets_total") == \
        sync.eng.prom.get("ssm_state_resets_total") == 4


def test_an_observer_between_steps_reads_a_settled_state(params,
                                                         monkeypatch):
    """The benchmark's runner (`serve_closed_h1.states_in_flight`, not this
    repo's to edit) reads, after a `step()` and with no engine call
    between: `slots`, then a request's `output`, `lens`, `ssm_state`.
    With a step in flight the buffer is a burst ahead of the walked
    tokens: the first of those reads settles, and the state covers
    exactly `lens` tokens of prompt + output. `snapshot()`, which the
    runner calls after every step of its window, fetches nothing and
    reports committed progress."""
    from chipbench.runners.serve_closed_h1 import (state_errors,
                                                   states_in_flight)
    eng = ServingEngine(params, toy_cfg(), **ENGINE)     # bursts of 4
    rids = [eng.add_request(p, 40) for p in prompts(3, seed=6)]
    fetches = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: fetches.append(1) or real(x))
    for _ in range(7):
        eng.step()
        n = len(fetches)
        snap = eng.snapshot()
        assert len(fetches) == n and eng._flight is not None
        for s, r in zip(snap["slots"], eng._slots):
            if r is not None:
                assert (s["emitted"], s["prefill_done"]) == (
                    len(r.output), r.prefill_done)
    behind = {r.rid: len(r.output) for r in eng._slots if r is not None}
    held = states_in_flight(eng, 8, seed=0, fresh=set(rids[-1:]))
    assert eng._flight is None
    assert eng.prom.get("overlap_settles_total",
                        labels={"reason": "observer"}) == 1
    assert len(held) == 3
    for slot, tokens, _ in held:
        r = eng._slots[slot]
        assert len(r.output) == behind[r.rid] + 4       # the burst landed
        assert len(tokens) == len(r.prompt) + len(r.output) - 1
    errs = state_errors(params, {"widths": W, "multipliers": M}, held, 96)
    assert max(errs) < 1e-5, errs
    # a state a burst ahead of the tokens it is held against is not close
    stale = [(slot, tokens[:-4], state) for slot, tokens, state in held]
    assert min(state_errors(params, {"widths": W, "multipliers": M},
                            stale, 96)) > 1e-2


def test_the_dispatch_span_counts_state_rows_and_tokens(params):
    eng = ServingEngine(params, toy_cfg(), **dict(ENGINE, decode_burst=2))
    eng.add_request(np.arange(11, dtype=np.int32) % 96, 6)
    eng.add_request(np.arange(3, dtype=np.int32), 6)
    with obs.capture_spans() as cap:
        eng.step()      # two prefill rows: 8 + 3 tokens, one completes
        eng.step()      # a decode row (burst of 2) beside a chunk of 3
    d = [e.attrs for e in cap.events
         if e.name == SERVING_SPANS.dispatch]
    assert (d[0]["ssm_scan_rows"], d[0]["ssm_update_rows"],
            d[0]["ssm_tokens"]) == (2, 0, 11)
    k = d[1]["k"]       # both rows sample in step 2: each runs k passes
    assert (d[1]["ssm_scan_rows"], d[1]["ssm_update_rows"],
            d[1]["ssm_tokens"]) == (2, 2 * (k - 1), 4 + 2 * (k - 1))


# -- the kernels ----------------------------------------------------------------
@pytest.fixture
def aliasing(monkeypatch):
    """The kernels under the interpreter that keeps a TPU's memory: an
    aliased output IS its input's buffer (plain ``interpret=True`` copies
    it, so a block visited twice reads the old state both times there),
    blocks move only when their index changes, and an output block that
    is left and come back to raises."""
    monkeypatch.setattr(ssm, "_interpret", pltpu.InterpretParams)


def scan_against_the_recurrence(q_lens, reset, chunk):
    """Each row's chunk from a non-zero state (zero where it resets)
    against the reference's step-by-step recurrence; idle rows and other
    layers bit for bit as they were. float32 both sides; 2e-5 of values
    O(1-10) is the chunked sums' reordering over 128 terms."""
    H, G_, P, N, L, layer = 4, 2, 16, 32, 3, 1
    q_lens = np.minimum(np.asarray(q_lens, np.int32), chunk)
    reset = np.asarray(reset, np.int32)
    R_ = len(q_lens)
    rng = np.random.default_rng(chunk)
    x = rng.normal(size=(R_, chunk, H, P)).astype(np.float32)
    B = rng.normal(size=(R_, chunk, G_, N)).astype(np.float32)
    C = rng.normal(size=(R_, chunk, G_, N)).astype(np.float32)
    dt = rng.uniform(1e-3, 0.1, size=(R_, chunk, H)).astype(np.float32)
    A = -rng.uniform(1, 16, size=(H,)).astype(np.float32)
    S = rng.normal(size=(L, R_, H, P, N)).astype(np.float32)
    live = np.arange(chunk)[None, :] < q_lens[:, None]
    dtm = np.where(live[..., None], dt, 0.0).astype(np.float32)
    y, S1 = ssm.ssm_scan(
        jnp.asarray(x.reshape(R_, chunk, H * P)),
        jnp.asarray(B.reshape(R_, chunk, G_ * N)),
        jnp.asarray(C.reshape(R_, chunk, G_ * N)), jnp.asarray(dtm),
        jnp.asarray(np.cumsum(dtm * A, axis=1)), jnp.asarray(S), layer,
        jnp.asarray(q_lens), jnp.asarray(reset), groups=G_)
    y, S1 = np.asarray(y).reshape(R_, chunk, H, P), np.asarray(S1)
    assert np.array_equal(S1[[0, 2]], S[[0, 2]])    # other layers untouched
    for r, n in enumerate(q_lens):
        if n == 0:
            assert np.array_equal(S1[layer, r], S[layer, r])
            continue
        S0 = S[layer, r] * (0.0 if reset[r] else 1.0)
        with jax.default_matmul_precision("highest"):
            want_y, want_S = R.selective_scan(
                jnp.asarray(x[r, :n]), jnp.asarray(B[r, :n]),
                jnp.asarray(C[r, :n]), jnp.asarray(dt[r, :n]),
                jnp.asarray(A), jnp.asarray(S0))
        np.testing.assert_allclose(y[r, :n], want_y, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(S1[layer, r], want_S, atol=2e-5,
                                   rtol=2e-5)


@pytest.mark.parametrize("chunk", [128, 1], ids=["chunk128", "token"])
def test_the_scan_kernel_against_the_recurrence(chunk):
    """Ragged rows of 1, 7 and the whole chunk, an idle row, and a row that
    resets."""
    scan_against_the_recurrence([1, 7, chunk, 0, 20], [0, 0, 0, 0, 1], chunk)


@pytest.mark.parametrize("q_lens,reset,chunk", [
    ([1, 1, 0, 0, 0], [0, 0, 0, 0, 0], 1),
    ([0, 7, 0, 128, 0, 0], [0, 0, 0, 0, 0, 0], 128),
    ([0, 20, 3, 0], [0, 1, 0, 0], 128),
    ([0, 0, 0], [0, 0, 0], 1),
    ([1, 7, 128, 20], [0, 0, 0, 1], 128),
], ids=["decode-then-idle", "chunks-then-idle", "reset-then-short-then-idle",
        "all-idle", "none-idle"])
def test_the_scan_kernel_visits_each_state_once(aliasing, q_lens, reset,
                                                chunk):
    """The update is not idempotent and the state is aliased: the last
    active row, followed by idle rows (every burst pass beside a prefill
    in progress), must come out advanced once, not once a grid step.
    Here the last active row does NOT reset (a reset row would hide a
    second visit: it starts from zero each time)."""
    scan_against_the_recurrence(q_lens, reset, chunk)


@pytest.mark.parametrize("memory", ["copied", "aliased"])
def test_the_conv_kernel_against_the_plain_conv_with_a_tail(memory, request):
    """Rows of 5, 1, 0, 2, 3 and 9 tokens packed into one buffer, each
    continuing from its slot's last three inputs (one row resetting):
    exact products, float32 sums of 4 terms, so 1e-6."""
    if memory == "aliased":
        request.getfixturevalue("aliasing")
    K, Cc, L, layer, T = 4, 24, 2, 1, 24
    q_lens = np.asarray([5, 1, 0, 2, 3, 9], np.int32)
    R_ = len(q_lens)
    rng = np.random.default_rng(1)
    tail = rng.normal(size=(L, K - 1, R_, Cc)).astype(np.float32)
    w = rng.normal(size=(K, Cc)).astype(np.float32)
    b = rng.normal(size=(Cc,)).astype(np.float32)
    x = rng.normal(size=(T, Cc)).astype(np.float32)
    row_of, off_of = np.zeros(T, np.int32), np.full(T, T, np.int32)
    starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    for r, n in enumerate(q_lens):
        row_of[starts[r]:starts[r] + n] = r
        off_of[starts[r]:starts[r] + n] = np.arange(n)
    reset = np.asarray([1, 0, 0, 0, 0, 0], np.int32)
    y, t1 = ssm.ssm_conv(*(jnp.asarray(a) for a in (x, w, b, tail)), layer,
                         *(jnp.asarray(a) for a in (row_of, off_of, starts,
                                                    q_lens, reset)))
    y, t1 = np.asarray(y), np.asarray(t1)
    assert np.array_equal(t1[0], tail[0])
    for r, n in enumerate(q_lens):
        old = tail[layer, :, r] * (0.0 if reset[r] else 1.0)
        full = np.concatenate([old, x[starts[r]:starts[r] + n]], 0)
        np.testing.assert_array_equal(t1[layer, :, r], full[-(K - 1):]
                                      if n else tail[layer, :, r])
        if n:
            want = R.silu(R.causal_conv(jnp.asarray(full), jnp.asarray(w),
                                        jnp.asarray(b)))[K - 1:]
            np.testing.assert_allclose(y[starts[r]:starts[r] + n], want,
                                       atol=1e-6, rtol=1e-6)
