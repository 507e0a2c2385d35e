"""The Trinity-Mini cell's own pieces at toy size on the CPU: the plain
reference against a second, layer-by-layer write-up (numpy float64, loops
over positions and heads) and its blocked attention against the one-line
form; the seeded weights made twice; the real traffic mix's arithmetic; and
the runner driven end to end in a temporary copy to which a toy cell is
ADDED (bfloat16; every mechanism kept: 1 dense window layer + window,
window, window, full; a window of 16 at pages of 8 and chunks of 16, so a
ring of 6; 8 experts top-2 + a shared one). Three controls and three broken
timed paths must each come out as not correct; `router_bf16` is run and
reported."""

import json
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import traffic as T
from chipbench import weights_trinity_mini as W
from chipbench.reference import trinity_mini as R
from chipbench.tests import rehearsal as Rh

CELL = "toy-trin"
REAL = "serve-trinity-mini-26b-mix"


def _data(name):
    with open(os.path.join(Rh.HERE, "data", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = Rh.make_copy(str(tmp_path_factory.mktemp("chipbench_trin")))
    for name, kind in (("tiny-trin", "configs"), ("tiny-trinmix", "traffic")):
        shutil.copy(os.path.join(Rh.HERE, "data", name + ".json"),
                    os.path.join(root, "chipbench", kind, name + ".json"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-trin", "source": "tests only", "reduced": [],
        "file": "chipbench/configs/tiny-trin.json", "why": "tests only"})
    spec["workloads"].append({"name": CELL, "config": "tiny-trin",
                              "traffic": "tiny-trinmix", "chips": 1,
                              "why": "tests only"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)
    return root


def _checks(out):
    return {ln.split()[1]: ln.strip().endswith(" ok")
            for ln in out.splitlines() if ln.startswith("[check]")}


# -- the reference ---------------------------------------------------------------
def _second_write_up(params, tokens, w):
    """The forward pass written a second time: numpy float64, a loop over
    layers, positions and heads, masks by comparison of positions."""
    f8 = lambda a: np.asarray(a, np.float64)     # noqa: E731
    S, H, D = len(tokens), w["hidden_size"], w["head_dim"]
    hq, hkv, eps = w["num_heads"], w["num_kv_heads"], w["rms_norm_eps"]

    def rms(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * f8(g)

    def silu(x):
        return x / (1 + np.exp(-x))

    def ffn(f, g, u, d):
        return (silu(f @ f8(g)) * (f @ f8(u))) @ f8(d)

    def rot(x, pos):    # [heads, D] at one position
        inv = w["rope_theta"] ** (-np.arange(0, D, 2) / D)
        c, s = np.cos(pos * inv), np.sin(pos * inv)
        a, b = x[:, :D // 2], x[:, D // 2:]
        return np.concatenate([a * c - b * s, b * c + a * s], -1)

    x = f8(params["embed"])[tokens] * np.sqrt(H)
    for where, run, at, windowed in R.layer_kinds(w):
        if where == "prologue":
            p = {k: v[at] for k, v in params["prologue"].items()}
        else:
            p = {k: v[at] for k, v in params["blocks"][run].items()}
            n = w["global_attn_every"] - 1 if run == 0 else 1
            e = {k: f8(v[at[0] * n + at[1]])
                 for k, v in params["experts"][run].items()}
        u = rms(x, p["ln1_g"])
        q = rms((u @ f8(p["q_w"])).reshape(S, hq, D), p["q_norm"])
        k = rms((u @ f8(p["k_w"])).reshape(S, hkv, D), p["k_norm"])
        v = (u @ f8(p["v_w"])).reshape(S, hkv, D)
        if windowed:
            q = np.stack([rot(q[i], i) for i in range(S)])
            k = np.stack([rot(k[i], i) for i in range(S)])
        o = np.zeros((S, hq, D))
        for i in range(S):
            first = max(0, i - w["sliding_window"] + 1) if windowed else 0
            for h in range(hq):
                s = k[first:i + 1, h // (hq // hkv)] @ q[i, h] / np.sqrt(D)
                pr = np.exp(s - s.max())
                o[i, h] = (pr / pr.sum()) @ v[first:i + 1, h // (hq // hkv)]
        gate = 1 / (1 + np.exp(-(u @ f8(p["g_w"]))))
        a = x + rms((o.reshape(S, -1) * gate) @ f8(p["o_w"]),
                    p["post_attn_g"])
        f = rms(a, p["ln2_g"])
        if where == "prologue":
            y = ffn(f, p["gate_w"], p["up_w"], p["down_w"])
        else:
            sc = 1 / (1 + np.exp(-(f @ f8(p["router_w"]))))
            y = ffn(f, p["shared_gate_w"], p["shared_up_w"],
                    p["shared_down_w"])
            for i in range(S):
                picks = np.argsort(-(sc[i] + f8(p["expert_bias"])),
                                   kind="stable")[:w["experts_per_tok"]]
                wt = sc[i, picks] / (sc[i, picks].sum() + 1e-20) \
                    * w["route_scale"]
                for j, t in zip(picks, wt):
                    y[i] += t * ffn(f[i], e["gate_w"][j], e["up_w"][j],
                                    e["down_w"][j])
        x = a + rms(y, p["post_mlp_g"])
    return rms(x, params["lnf_g"]) @ f8(params["head_w"])


def test_the_reference_against_a_second_layer_by_layer_write_up():
    w = _data("tiny-trin")["widths"]
    params = W.make_params(w, 5, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, w["vocab_size"], 48)
    got = np.asarray(R.forward(params, jnp.asarray(tokens, jnp.int32), w,
                               block=16))
    want = _second_write_up(params, tokens, w)
    assert np.abs(want).max() > 0.5     # logits of order 1: the embedding
    np.testing.assert_allclose(got, want, atol=2e-4)   # sqrt(H), f32 sums
    # the window is felt (48 positions against a window of 16) ...
    wide = np.asarray(R.forward(params, jnp.asarray(tokens, jnp.int32), w,
                                block=16, window=17))
    assert np.abs(wide - want)[20:].max() > 1e-3
    # ... and one block of 48 is the three blocks of 16
    one = np.asarray(R.forward(params, jnp.asarray(tokens, jnp.int32), w))
    np.testing.assert_allclose(one, got, atol=2e-5)


def test_the_blocked_attention_is_the_one_line_form():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(64, 4, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(64, 2, 16)), jnp.float32)
            for _ in range(2))
    for window in (None, 16, 21):
        want = np.asarray(R.attention_dense(q, k, v, window))
        got = np.concatenate([np.asarray(R.attention_blocked(
            q[i:i + 16], k, v, i, 16, window)) for i in range(0, 64, 16)])
        np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_seeded_weights_made_twice_are_the_same_bits():
    w = _data("tiny-trin")["widths"]
    a, b = (W.make_params(w, 2 ** 31 + 7, jnp.bfloat16) for _ in range(2))
    other = W.make_params(w, 2 ** 31 + 8, jnp.bfloat16)
    flat = lambda t: [np.asarray(x) for x in    # noqa: E731
                      __import__("jax").tree.leaves(t)]
    assert all((x == y).all() for x, y in zip(flat(a), flat(b)))
    assert not all((x == y).all() for x, y in zip(flat(a), flat(other)))
    bias = a["blocks"][0]["expert_bias"]
    assert bias.dtype == jnp.float32 and bias.shape == (1, 3, 8)
    assert 0.003 < float(jnp.std(bias)) < 0.03


# -- the traffic -------------------------------------------------------------------
def test_the_generator_offers_every_seed_the_same_work():
    """The real mix: 64 pairs on the log-uniform grids, the full pool
    sized for the whole multiset resident, the window pool 64 rings of 20,
    dealt so that every 16 consecutive requests take each quarter of the
    prompt grid four times, whatever the seed."""
    with open(os.path.join(Rh.REPO, "chipbench", "traffic",
                           "trinmix.json")) as f:
        p = json.load(f)
    pairs = T.length_multiset(p)
    prompts = sorted(a for a, _ in pairs)
    assert len(pairs) == 64 and 512 <= prompts[0] and prompts[-1] <= 32768
    assert 7000 < sum(prompts) / 64 < 8500 and prompts[48] > 11000
    e = p["engine"]
    pages = sum(-(-(a + b) // e["block_size"]) for a, b in pairs)
    assert pages == 4047 and e["num_blocks"] == 1 + int(pages * 1.05 + 0.65)
    ring = -(-2048 // e["block_size"]) + -(-e["chunk"] // e["block_size"]) + 2
    assert ring == 20 and e["num_window_blocks"] == 64 * ring + 1
    assert max(a + b for a, b in pairs) <= e["max_blocks_per_seq"] * 128
    assert max(a + b for a, b in pairs) <= p["pad_to"]
    assert e["token_budget"] == e["max_batch"] + 4 * e["chunk"]
    quarter = {a: i // 16 for i, a in enumerate(prompts)}
    totals = set()
    for seed in (1, 2 ** 31 + 5):
        gen = T.ClosedLoop(p, 200192, seed)
        dealt = [gen.next_request() for _ in range(128)]
        for g in range(8):
            took = [quarter[len(q)] for q, _ in dealt[16 * g:16 * g + 16]]
            assert sorted(took) == sorted(list(range(4)) * 4)
        totals.add((sum(len(q) for q, _ in dealt[:64]),
                    sum(a for _, a in dealt[:64])))
    assert len(totals) == 1


# -- the runner, end to end ------------------------------------------------------
def test_the_runner_end_to_end_and_its_metrics(copy):
    rc, last, out = Rh.run_cell(copy, CELL, seconds=10.0, trace=0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "compiles in window 0" in out and "preemptions 0" in out
    assert set(_checks(out)) == {
        "served_logit_gap_max", "served_logit_gap_mean",
        "route_clear_mismatches", "route_flip_share",
        "route_flip_share_first"}
    line = next(ln for ln in out.splitlines() if ln.startswith("[window]"))
    words = line.replace(";", " ").split()
    tokens = (int(words[words.index("output") - 1])
              + int(words[words.index("prompt") - 1]))
    span = float(line.split(" engine steps in ")[1].split(" s;")[0])
    assert last["metrics"]["serve_tok_s"]["value"] == pytest.approx(
        tokens / span, rel=1e-3)
    assert int(line.split("given back ")[1].split(",")[0]) > 0
    rc, last, out = Rh.run_cell(copy, CELL, seconds=10.0, trace=1, seed=8)
    assert rc == 0, out[-3000:]
    got = last["metrics"]
    assert {"engine_step_p50_ms.serve", "burst_k_mean.serve",
            "pool_peak_pct.serve", "win_pool_peak_pct.trinmix",
            "moe_touched_pct", "moe_load_max_over_mean",
            "host_pack_ms.serve", "prefill_starved_pct.serve"} <= set(got)
    assert 0 < got["win_pool_peak_pct.trinmix"]["value"] <= 100
    assert 0 < got["moe_touched_pct"]["value"] <= 100
    # no chip, no device trace: nothing under a device metric's name
    assert not any(k.startswith(("device_idle_pct", "win_attn_", "attn_",
                                 "moe_grouped", "moe_expert", "moe_route",
                                 "dense_", "unscoped_", "kv_write_"))
                   for k in got)


@pytest.mark.parametrize("control,felt", [
    ("weights_fp8", "route_flip_share"),
    ("cache_fp8", "route_flip_share"),
    ("window_4096", "served_logit_gap_max")])
def test_a_control_is_not_correct_by_the_limit_that_feels_it(copy, control,
                                                             felt):
    rc, last, out = Rh.run_cell(copy, CELL, "--control", control,
                                seconds=10.0, seed=1)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, _checks(out)
    assert _checks(out)[felt] is False


def test_the_bfloat16_router_is_run_and_reported(copy):
    """Whether or not it separates (PERF.md section 7): the control runs
    to its end and prints its checks."""
    rc, last, out = Rh.run_cell(copy, CELL, "--control", "router_bf16",
                                seconds=10.0, seed=1)
    assert rc == 0, out[-3000:]
    assert len(_checks(out)) == 5 and last["failed"] == 0


BROKEN = {
    "a-token-altered": (
        "emit = S.ServingEngine._emit\n"
        "def wrong(self, r, tok):\n"
        "    return emit(self, r, (tok + 1) % 96 if len(r.output) % 7 == 3"
        " else tok)\n"
        "S.ServingEngine._emit = wrong\n"),
    "the-bias-left-out-of-the-choice": (
        "route = TM.route\n"
        "TM.route = lambda logits, bias, cfg: route(logits, bias * 0, cfg)\n"),
    "the-full-layer-rotated-too": (
        "qkv = TM.Serving.qkv\n"
        "TM.Serving.qkv = staticmethod(lambda p, x, pos, cfg, mp_axis=None, "
        "kind='attention': qkv(p, x, pos, cfg, mp_axis, kind='window'))\n"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_timed_path_is_not_correct(copy, fault):
    """Each fault in the PROGRAM alone (the reference is not the
    program's): a served token replaced on its way out, the router's bias
    dropped from the choice, the full layer given the window layers'
    rotation."""
    patch = ("from paddle_tpu.models import trinity_mini as TM\n"
             "from paddle_tpu.inference import serving as S\n"
             + BROKEN[fault])
    rc, last, out = Rh.run_cell(copy, CELL, patch=patch, seconds=10.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, _checks(out)
