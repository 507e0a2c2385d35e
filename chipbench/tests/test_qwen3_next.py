"""The Qwen3-Next cell's own pieces at toy size on the CPU: the plain
reference's delta rule against a hand-rolled loop, and the runner driven
end to end in a temporary copy to which a toy cell is ADDED (bfloat16, the
pattern kept: 3 linear layers + 1 attention layer, 16 experts top-4 of
which 8 held). The three controls and five broken timed paths must each
come out as not correct, by the limit that feels it."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import qwen3_next as R
from chipbench.tests import rehearsal as Rh

CELL = "toy-q3n"
REAL = "serve-qwen3next-80b-chat"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = Rh.make_copy(str(tmp_path_factory.mktemp("chipbench_q3n")))
    for name, kind in (("tiny-q3n", "configs"), ("tiny-q3nchat", "traffic")):
        shutil.copy(os.path.join(Rh.HERE, "data", name + ".json"),
                    os.path.join(root, "chipbench", kind, name + ".json"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-q3n", "source": "tests only", "reduced": [],
        "file": "chipbench/configs/tiny-q3n.json", "why": "tests only"})
    spec["workloads"].append({"name": CELL, "config": "tiny-q3n",
                              "traffic": "tiny-q3nchat", "chips": 1,
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


def test_the_references_delta_rule_against_a_hand_rolled_loop():
    """The recurrence written a second time in numpy float64, loops over
    time and heads, against `delta_rule` (float32: 1e-5 of values O(1))."""
    S, H, dk, dv = 20, 3, 8, 6
    rng = np.random.default_rng(0)
    q, k = rng.normal(size=(2, S, H, dk))
    v = rng.normal(size=(S, H, dv))
    g = -rng.uniform(0.01, 0.5, size=(S, H))
    beta = rng.uniform(0.1, 0.9, size=(S, H))
    with jax.default_matmul_precision("highest"):
        o, state = R.delta_rule(*(jnp.asarray(a, jnp.float32)
                                  for a in (q, k, v, g, beta)))
    want_state = np.zeros((H, dk, dv))
    for t in range(S):
        for h in range(H):
            Sh = want_state[h] * np.exp(g[t, h])
            d = beta[t, h] * (v[t, h] - Sh.T @ k[t, h])
            want_state[h] = Sh + np.outer(k[t, h], d)
            np.testing.assert_allclose(o[t, h], want_state[h].T @ q[t, h],
                                       atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5, rtol=1e-5)


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
        "route_flip_share_first", "state_rel_err_max", "state_rel_err_first"}
    rc, last, out = Rh.run_cell(copy, CELL, seconds=10.0, trace=1, seed=8)
    assert rc == 0, out[-3000:]
    assert {"engine_step_p50_ms.serve", "burst_k_mean.serve",
            "pool_peak_pct.serve", "moe_touched_pct",
            "moe_load_max_over_mean"} <= set(last["metrics"])
    assert 0 < last["metrics"]["moe_touched_pct"]["value"] <= 100
    assert last["metrics"]["moe_load_max_over_mean"]["value"] >= 1
    # no chip, no device trace: nothing under a device metric's name
    assert not any(k.startswith(("device_idle_pct", "gdn_", "attn_",
                                 "moe_grouped", "moe_expert", "moe_route"))
                   for k in last["metrics"])


@pytest.mark.parametrize("control,felt", [
    ("weights_fp8", "served_logit_gap_mean"),
    ("state_bf16", "state_rel_err_first"),
    ("router_bf16", "route_flip_share_first")])
def test_a_control_is_not_correct_by_the_limit_that_feels_it(copy, control,
                                                             felt):
    rc, last, out = Rh.run_cell(copy, CELL, "--control", control,
                                seconds=10.0, seed=1)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, _checks(out)
    assert _checks(out)[felt] is False


BROKEN = {
    "an-experts-output-left-out": (
        "combine = QN.M.combine\n"
        "QN.M.combine = lambda y, w, p: combine(y, w.at[:, 0].set(0.0), p)\n"),
    "the-top-picks-cut-by-one": (
        "plan = QN.M.plan\n"
        "QN.M.plan = lambda ids, lo, hi: plan(ids.at[:, -1].set(-1), lo, hi)"
        "\n"),
    "the-shared-gate-left-out": (
        "shared = QN.shared_expert\n"
        "QN.shared_expert = lambda p, f, cfg: shared(dict(p, shared_sg_w="
        "0 * p['shared_sg_w']), f, cfg) * 2.0\n"),
    "the-state-not-reset": (
        "scan, conv = QN.gdn_scan, QN.ssm_conv\n"
        "QN.gdn_scan = lambda *a: scan(*a[:8], jnp.zeros_like(a[8]))\n"
        "QN.ssm_conv = lambda *a: conv(*a[:9], jnp.zeros_like(a[9]))\n"),
    "the-attention-gate-left-out": (
        "qkv = QN.Serving.qkv\n"
        "def ungated(*a, **kw):\n"
        "    q, k, v, gate = qkv(*a, **kw)\n"
        "    return q, k, v, jnp.full_like(gate, 30.0)\n"
        "QN.Serving.qkv = staticmethod(ungated)\n"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_timed_path_is_not_correct(copy, fault):
    """Each fault in the PROGRAM alone (the reference is not the
    program's): a pick's weighted output dropped, the last pick computed
    as if not held while still reported, the shared expert's gate taken
    as 1 (sigmoid(0) x 2), the slot's state kept for its next request,
    the attention output's gate taken as 1."""
    patch = ("from paddle_tpu.models import qwen3_next as QN\n"
             "import jax.numpy as jnp\n" + BROKEN[fault])
    rc, last, out = Rh.run_cell(copy, CELL, patch=patch, seconds=10.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, _checks(out)
