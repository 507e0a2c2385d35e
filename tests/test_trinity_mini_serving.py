"""Trinity-Mini on the serving path (ISSUE 54): window and full attention
layers side by side in one engine — a window mask in the ragged kernel
(pages wholly behind the window are never read), a second page lifetime in
the allocator (a window layer's pages are given back as the window slides,
reused from step n+2), a sigmoid router with a choice-only bias — held
against the plain reference (`chipbench/reference/trinity_mini.py`) at a
toy size that keeps every mechanism: one dense window layer + S S S F,
hidden 64, 4/2 heads of 16, 8 experts top-2 + a shared one, a window of 8
at pages of 4 and chunks of 8 (a ring of 2 + 2 + 2 = 6 pages a row).

Engine and reference both compute in float32 here: what separates them is
the order of the sums (paged online soft-max against a blocked one, the
grouped expert product against a scan with masks). Logits are O(1) (the
embedding's sqrt(hidden)); the largest difference seen over the cases
below is 4e-6 and LOGIT_ATOL is ~10x that. A window one page off moves
them by 1e-2 and more, a freed page that is read by 1e20 and more.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from paddle_tpu.enforce import EnforceNotMet  # noqa: E402
from paddle_tpu.inference.serving import ServingEngine  # noqa: E402
from paddle_tpu.models import trinity_mini as TM  # noqa: E402

from chipbench import weights_trinity_mini as WT  # noqa: E402
from chipbench.reference import trinity_mini as R  # noqa: E402
from test_falcon_h1_serving import Logits  # noqa: E402

W = dict(vocab_size=96, hidden_size=64, num_layers=5, num_dense_layers=1,
         global_attn_every=4, num_heads=4, num_kv_heads=2, head_dim=16,
         sliding_window=8, rope_theta=10000.0, rms_norm_eps=1e-5,
         intermediate_size=96, num_experts=8, experts_per_tok=2, moe_ffn=32,
         shared_ffn=32, route_scale=2.826, experts_held=(0, 8))
LOGIT_ATOL = 5e-5
ENGINE = dict(max_batch=2, block_size=4, num_blocks=32,
              max_blocks_per_seq=12, chunk=8, decode_burst=2,
              token_budget=2 + 16, pool_audit=True)
RING = 6    # ceil(8 / 4) + ceil(8 / 4) + 2


def toy_cfg(**kw):
    return TM.TrinityMiniConfig(**dict(
        W, dtype=jnp.float32, param_dtype=jnp.float32, **kw))


@pytest.fixture(scope="module")
def params():
    return WT.make_params(W, 3, jnp.float32)


@pytest.fixture
def logits(monkeypatch):
    return Logits(monkeypatch)


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, W["vocab_size"], n,
                                                dtype=np.int32)


def submit(eng, prompt, new, **kw):
    rid = eng.add_request(prompt, new, keep_routing=True, **kw)
    return next(r for r in eng.queue if r.rid == rid)


def worst_gap(params, logits, r, window=None):
    """Largest |engine logit - reference logit| over a request's served
    positions: the reference is given prompt + served tokens at once, and
    follows the engine's picks (they are compared apart)."""
    seq = np.concatenate([r.prompt, np.asarray(r.output, np.int32)])
    n = len(seq) - 1
    pad = np.zeros((40,), np.int32)     # one shape: one compile
    pad[:len(seq)] = seq
    x, own, _ = R.hidden(params, jnp.asarray(pad), W, block=8, window=window)
    want = np.asarray(R.head_logits(params, x))[len(r.prompt) - 1:n]
    got = np.stack(logits.by_rid[r.rid])
    flips = int((np.sort(np.asarray(own)[:n], -1)
                 != np.sort(r.routing[:n], -1)).any(-1).sum())
    return float(np.abs(got - want).max()), flips


class Watch:
    """Holds the window lifetime to its contract at every step: the ring's
    bound a row, freed pages poisoned where they lie so that a read of
    one shows (that settles every step: `test_window_lifetime.py` holds
    the step in flight to "no page handed out before step n+2"). The poison is 1e30 and
    not NaN: a page handed out again is written a few positions at a
    time, and the kernel multiplies the rest of it by a probability of 0
    (0 x NaN is NaN, on any page of any pool; the kernel's own test below
    holds whole PAGES that must not be read to NaN)."""

    def __init__(self, eng):
        self.eng, self.most, self.handed_out_again = eng, 0, 0
        given_back = set()
        slide = eng._slide_windows

        def _slide(q_lens, pos0, lens_after):
            before = set(eng.wfree_blocks)
            out = slide(q_lens, pos0, lens_after)
            after = set(eng.wfree_blocks)
            self.handed_out_again += len((before - after) & given_back)
            given_back.update(after - before)   # nothing in flight: free
            self.most = max(self.most, int((eng._whi - eng._wlo).max()))
            return out
        eng._slide_windows = _slide

    def step(self):
        eng = self.eng
        out = eng.step()
        if eng.wfree_blocks:
            free = np.asarray(eng.wfree_blocks)     # settles: no step in
            eng.wk_pools = eng.wk_pools.at[:, :, free].set(1e30)    # flight
            eng.wv_pools = eng.wv_pools.at[:, :, free].set(1e30)
        return out


# -- (a) engine against reference ----------------------------------------------
def test_window_and_full_layers_on_logits_picks_and_pages(params, logits):
    """Three requests over two slots: a prompt of more than three windows
    prefilled in chunks of 8 (the ring slides two pages a chunk) beside a
    short one, decoded 2 passes a step past further page boundaries, the
    third in the slot and the pages a finished one gave back; every freed
    window page is poisoned where it lies. Served logits against the
    reference's full forward, prefill's first token and decode alike; and
    the same comparison FAILS with the reference's window one page off."""
    eng = logits.watch(ServingEngine(params, toy_cfg(), **ENGINE))
    watch = Watch(eng)
    reqs = [submit(eng, prompt_of(n, seed=n), new)
            for n, new in ((27, 7), (9, 6), (19, 5))]
    while eng.has_work():
        watch.step()
    assert all(r.status == "ok" and len(r.output) == r.max_new_tokens
               for r in reqs)
    for r in reqs:
        gap, flips = worst_gap(params, logits, r)
        assert gap < LOGIT_ATOL and flips == 0, (r.rid, gap, flips)
    # the contract of the second lifetime
    assert watch.most <= RING and watch.handed_out_again > 0
    assert eng.window_pages_freed > 0
    assert eng.free_pages() == ENGINE["num_blocks"] - 1         # no leak,
    assert eng.free_pages(window=True) == eng._num_wblocks - 1  # either pool
    assert eng.prom.get("window_pages_freed_total") == eng.window_pages_freed
    assert 0 < eng.prom.get("kv_window_pool_utilization_peak") <= 1
    snap = eng.snapshot()
    assert snap["free_window_blocks"] == eng._num_wblocks - 1
    assert snap["window_pool_utilization"] == 0.0
    # a window one page off (4 positions more, or fewer) is another model
    gap, _ = worst_gap(params, logits, reqs[0], window=8 + 4)
    assert gap > 100 * LOGIT_ATOL, gap


def test_a_page_given_back_too_early_is_caught(params, logits):
    """The sliding free one page ahead of the window: the page's keys are
    still inside it, the poison is read, and the served logits show it."""
    eng = logits.watch(ServingEngine(params, toy_cfg(),
                                     **dict(ENGINE, decode_burst=1)))
    eng._window -= eng.bs       # the HOST's window only: the kernel's and
    watch = Watch(eng)          # the model's stay 8
    r = submit(eng, prompt_of(27, seed=27), 3)
    while eng.has_work():
        watch.step()
    gap, _ = worst_gap(params, logits, r)
    assert not gap < 100 * LOGIT_ATOL, gap


# -- (d) the router ----------------------------------------------------------------
def test_the_bias_moves_the_choice_and_not_the_weight():
    cfg = toy_cfg()
    rng = np.random.default_rng(0)
    logit = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(8,)) * 0.3, jnp.float32)
    w, ids = TM.route(logit, bias, cfg)
    s = np.asarray(jax.nn.sigmoid(logit))
    own, _ = R.route(jnp.asarray(s), bias, 2)
    assert (np.sort(np.asarray(ids), -1) == np.sort(np.asarray(own), -1)).all()
    plain = np.asarray(TM.route(logit, jnp.zeros((8,)), cfg)[1])
    assert (np.sort(plain, -1) != np.sort(np.asarray(ids), -1)).any()
    # the weights are the scores at the picks, renormalised and scaled:
    # the bias is not in them
    at = np.take_along_axis(s, np.asarray(ids), -1)
    np.testing.assert_allclose(
        np.asarray(w), at / at.sum(-1, keepdims=True) * 2.826, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.826, rtol=1e-6)
    raw = TM.route(logit, bias, toy_cfg(route_norm=False))[0]
    np.testing.assert_allclose(np.asarray(raw), at * 2.826, rtol=1e-6)


def test_the_expert_layer_against_the_reference(params):
    """`moe_layer` (plan, grouped product, combine, shared expert) against
    the reference's scan over experts, picks and weights alike."""
    cfg = toy_cfg()
    f = jnp.asarray(np.random.default_rng(1).normal(size=(19, 64)),
                    jnp.float32)
    p = {k: v[0, 1] for k, v in params["blocks"][0].items()}
    y, ids, stats = TM.moe_layer(p, f, params["experts"][0], 1, cfg)
    e = {k: v[1] for k, v in params["experts"][0].items()}
    with jax.default_matmul_precision("highest"):
        want, own, _ = R.expert_layer(p, e, f, W)
    assert (np.sort(np.asarray(ids), -1) == np.sort(np.asarray(own), -1)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-6)
    assert int(stats[1]) == 19 * 2 and int(stats[0]) <= 8


# -- (e) what it cannot be served with -------------------------------------------------
@pytest.mark.parametrize("kw,word", [
    ({"prefix_share": True}, "prefix_share"),
    ({"spec_decode_k": 2}, "spec_decode_k"),
    ({"mesh": object()}, "a mesh"),
    ({"int8": True}, "int8 weights"),
    ({"kv_cache_dtype": "int8"}, "kv_cache_dtype")])
def test_what_it_cannot_be_served_with_raises_at_construction(params, kw,
                                                              word):
    with pytest.raises(EnforceNotMet, match=word):
        ServingEngine(params, toy_cfg(), **dict(ENGINE, **kw))


def test_the_configuration_refuses_a_depth_that_is_no_whole_period():
    with pytest.raises(EnforceNotMet, match="whole periods"):
        toy_cfg(num_layers=6)
    with pytest.raises(EnforceNotMet, match="window layers"):
        toy_cfg(num_dense_layers=4, num_layers=8)


def test_the_pools_hold_their_own_layers(params):
    eng = ServingEngine(params, toy_cfg(), **ENGINE)
    assert eng.k_pools.shape[0] == 1 and eng.wk_pools.shape[0] == 4
    assert eng.wtables.shape == (2, RING)
    assert eng.wk_pools.shape[2] == 2 * RING + 1
