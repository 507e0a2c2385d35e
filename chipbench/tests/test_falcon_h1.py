"""The Falcon-H1 cell's own pieces at toy size on the CPU: the plain
reference against a hand-rolled recurrence, and the runner driven end to
end in a temporary copy to which a toy cell is ADDED; the `state_bf16`
control and a broken timed path (the state not reset when a slot is taken
again) must come out as not correct."""

import json
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_falcon_h1 as WF
from chipbench.reference import falcon_h1 as R
from chipbench.tests import rehearsal as Rh

CELL = "toy-h1"
REAL = "serve-falconh1-34b-chat"


def _toy(name):
    with open(os.path.join(Rh.HERE, "data", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = Rh.make_copy(str(tmp_path_factory.mktemp("chipbench_h1")))
    for name, kind in (("tiny-h1", "configs"), ("tiny-h1chat", "traffic")):
        shutil.copy(os.path.join(Rh.HERE, "data", name + ".json"),
                    os.path.join(root, "chipbench", kind, name + ".json"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-h1", "source": "tests only", "reduced": [],
        "file": "chipbench/configs/tiny-h1.json", "why": "tests only"})
    spec["workloads"].append({"name": CELL, "config": "tiny-h1",
                              "traffic": "tiny-h1chat", "chips": 1,
                              "why": "tests only"})
    # beside the real cell wherever that one is listed; the entries without
    # a list reach a cell that reports `serve_tok_s` by themselves
    for m in spec["end_to_end"] + spec["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)
    return root


def _checks(out):
    return {ln.split()[1]: ln.strip().endswith(" ok")
            for ln in out.splitlines() if ln.startswith("[check]")}


def test_reference_against_a_hand_rolled_recurrence():
    """One layer's mixer maths, written a second time with loops over
    time, heads and taps in numpy float64, against the reference's block
    (float32 at `highest`: 2e-5 of values O(1) is its rounding over a
    64-wide feed-forward and 24 steps)."""
    cfg = _toy("tiny-h1")
    w, m = dict(cfg["widths"], num_layers=1), cfg["multipliers"]
    params = WF.make_params(w, 11, jnp.float32)
    p = {k: np.asarray(v[0], np.float64) for k, v in params["blocks"].items()}
    S, H = 24, w["hidden_size"]
    x = np.random.default_rng(0).normal(size=(S, H))
    got, got_state = R.block({k: v[0] for k, v in params["blocks"].items()},
                             jnp.asarray(x, jnp.float32), w, m, n=20)
    Hm, P, G, N, K = (w["ssm_heads"], w["ssm_head_dim"], w["ssm_groups"],
                      w["ssm_state"], w["ssm_conv"])
    d, gn, D = Hm * P, G * N, w["head_dim"]

    def rms(v, g):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5) * g

    def silu(v):
        return v / (1 + np.exp(-v))

    u = rms(x, p["ln1_g"])
    # attention, one query at a time
    hq, hkv = w["num_heads"], w["num_kv_heads"]
    inv = w["rope_theta"] ** (-np.arange(0, D, 2) / D)

    def rope(v, t):
        ang = t * inv
        c, s = np.cos(ang), np.sin(ang)
        a, b = v[:D // 2], v[D // 2:]
        return np.concatenate([a * c - b * s, b * c + a * s])

    q = (u @ p["q_w"]).reshape(S, hq, D)
    k = (u @ p["k_w"] * m["key_multiplier"]).reshape(S, hkv, D)
    v = (u @ p["v_w"]).reshape(S, hkv, D)
    att = np.zeros((S, hq, D))
    for t in range(S):
        for h in range(hq):
            kv = h // (hq // hkv)
            sc = np.array([rope(q[t, h], t) @ rope(k[s, kv], s)
                           for s in range(t + 1)]) / np.sqrt(D)
            pr = np.exp(sc - sc.max())
            att[t, h] = (pr / pr.sum()) @ v[:t + 1, kv]
    a = att.reshape(S, hq * D) @ p["o_w"] * m["attention_out_multiplier"]
    # mixer, one step of time after another
    mz, mx, mb, mc, mdt = m["ssm_multipliers"]
    zx = (u * m["ssm_in_multiplier"]) @ p["ssm_in_w"]
    z, xbc, dt = zx[:, :d] * mz, zx[:, d:2 * d + 2 * gn], zx[:, 2 * d + 2 * gn:]
    xbc = xbc * np.concatenate([np.full(d, mx), np.full(gn, mb),
                                np.full(gn, mc)])
    conv = np.zeros_like(xbc)
    for t in range(S):
        conv[t] = p["conv_b"] + sum(p["conv_w"][j] * xbc[t - (K - 1) + j]
                                    for j in range(K) if t - (K - 1) + j >= 0)
    conv = silu(conv)
    dt = np.log1p(np.exp(dt * mdt + p["dt_bias"]))
    state = np.zeros((Hm, P, N))
    y = np.zeros((S, Hm, P))
    for t in range(S):
        for h in range(Hm):
            g = h // (Hm // G)
            xs = conv[t, h * P:(h + 1) * P]
            B = conv[t, d + g * N:d + (g + 1) * N]
            C = conv[t, d + gn + g * N:d + gn + (g + 1) * N]
            if t < 20:      # the state handed back is the one after 20
                state[h] = (np.exp(-dt[t, h] * np.exp(p["A_log"][h]))
                            * state[h] + dt[t, h] * np.outer(xs, B))
            y[t, h] = state[h] @ C + p["D"][h] * xs
    y = (y.reshape(S, d) * silu(z)).reshape(S, G, d // G)
    y = (y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5)
         ).reshape(S, d) * p["ssm_norm_g"]
    x1 = x + a + y @ p["ssm_out_w"] * m["ssm_out_multiplier"]
    f = rms(x1, p["ln2_g"])
    want = x1 + ((f @ p["up_w"]) * silu(f @ p["gate_w"]
                                        * m["mlp_multipliers"][0])
                 ) @ p["down_w"] * m["mlp_multipliers"][1]
    # positions from n = 20 on run with a zero step in the reference: only
    # the first 20 positions and the state are comparable
    np.testing.assert_allclose(np.asarray(got)[:20], want[:20], atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got_state), state, atol=2e-6,
                               rtol=2e-5)


def test_the_runner_end_to_end_and_its_metrics(copy):
    rc, last, out = Rh.run_cell(copy, CELL, seconds=10.0, trace=0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "compiles in window 0" in out
    assert set(_checks(out)) == {"served_logit_gap_max",
                                 "served_logit_gap_mean",
                                 "state_rel_err_max"}
    rc, last, out = Rh.run_cell(copy, CELL, seconds=10.0, trace=1, seed=8)
    assert rc == 0, out[-3000:]
    assert {"engine_step_p50_ms.serve", "burst_k_mean.serve",
            "ttft_p50_ms.serve", "pool_peak_pct.serve"} \
        <= set(last["metrics"])
    # no chip, no device trace: nothing under a device metric's name (the
    # table's slot fill is counted from the host's own dispatch spans)
    assert "attn_slot_fill_pct.h1chat" in last["metrics"]
    assert not any(k.startswith(("device_idle_pct", "ssm_", "attn_time_pct",
                                 "attn_hbm_pct"))
                   for k in last["metrics"])


def test_the_bfloat16_state_control_is_not_correct(copy):
    rc, last, out = Rh.run_cell(copy, CELL, "--control", "state_bf16",
                                seconds=10.0, seed=1)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, _checks(out)
    assert _checks(out)["state_rel_err_max"] is False


def test_the_float8_weights_control_is_not_correct_by_the_gaps(copy):
    """The gaps' own control: the state check alone would let it by on
    some seeds (2.8e-3 under the toy limit of 3e-3 on seed 2)."""
    rc, last, out = Rh.run_cell(copy, CELL, "--control", "weights_fp8",
                                seconds=10.0, seed=2)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, _checks(out)
    assert _checks(out)["served_logit_gap_max"] is False
    assert _checks(out)["served_logit_gap_mean"] is False


def test_the_states_checked_are_drawn_over_all_slots():
    """The highest live slot always (the end of a pass's row list, where
    a scan that revisits a block lands), one request the window admitted
    always, the rest by the seed: over a few seeds every slot is seen."""
    import numpy as np
    from types import SimpleNamespace as NS
    from chipbench.runners import serve_closed_h1 as H

    def req(slot, done=True):
        return NS(slot=slot, rid=100 + slot, prompt=[1, 2, 3], folded=0,
                  prefill_done=3 if done else 1, output=[4, 5])
    slots = [req(i) for i in range(16)]
    slots[3], slots[15] = None, req(15, done=False)     # free; in prefill
    eng = NS(slots=slots, lens=np.full(16, 4),
             ssm_state=np.zeros((2, 16, 1, 1, 1), np.float32))
    seen = set()
    for seed in range(12):
        held = H.states_in_flight(eng, 4, seed, fresh={105, 106})
        got = [slot for slot, tokens, state in held]
        assert len(got) == 4 and 14 in got and {5, 6} & set(got), got
        assert not {3, 15} & set(got)
        assert got == [s for s, _, _ in
                       H.states_in_flight(eng, 4, seed, fresh={105, 106})]
        assert all(len(t) == 4 for _, t, _ in held)
        seen |= set(got)
    assert len(seen) >= 10, seen


def test_a_state_that_is_not_reset_is_not_correct(copy):
    """The timed path broken where a slot is taken again: the in-program
    reset sees no row starting at position 0."""
    patch = (
        "from paddle_tpu.models import falcon_h1 as FH\n"
        "import jax.numpy as jnp\n"
        "_scan, _conv = FH.ssm_scan, FH.ssm_conv\n"
        "FH.ssm_scan = lambda *a, **kw: _scan(*a[:8], "
        "jnp.zeros_like(a[8]), **kw)\n"
        "FH.ssm_conv = lambda *a, **kw: _conv(*a[:9], "
        "jnp.zeros_like(a[9]), **kw)\n")
    rc, last, out = Rh.run_cell(copy, CELL, patch=patch, seconds=10.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, _checks(out)
