"""The DeepSeek-V2 cell's own pieces at toy size on the CPU: the runner
driven end to end in a temporary copy to which a toy cell is ADDED
(bfloat16; every mechanism kept: 1 dense + 2 expert layers, latent
attention with YaRN, 16 experts in 4 groups of which 2 are kept and one is
held, 4 shared documents). The two controls and three broken timed paths
must each come out as not correct, and the runner's token count must leave
the prefix hits out."""

import json
import os
import shutil

import numpy as np
import pytest

from chipbench import traffic_docqa as TD
from chipbench.tests import rehearsal as Rh

CELL = "toy-dsv2"
REAL = "serve-deepseekv2-236b-docqa"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = Rh.make_copy(str(tmp_path_factory.mktemp("chipbench_dsv2")))
    for name, kind in (("tiny-dsv2", "configs"),
                       ("tiny-dsv2docqa", "traffic")):
        shutil.copy(os.path.join(Rh.HERE, "data", name + ".json"),
                    os.path.join(root, "chipbench", kind, name + ".json"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-dsv2", "source": "tests only", "reduced": [],
        "file": "chipbench/configs/tiny-dsv2.json", "why": "tests only"})
    spec["workloads"].append({"name": CELL, "config": "tiny-dsv2",
                              "traffic": "tiny-dsv2docqa", "chips": 1,
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


def _window(out):
    line = next(ln for ln in out.splitlines() if ln.startswith("[window]"))
    words = line.replace(";", " ").replace("(", " ").split()
    return {"output": int(words[words.index("output") - 1]),
            "run": int(words[words.index("prompt") - 1]),
            "uncut": int(words[words.index("with") - 1])}


def test_the_generator_offers_every_seed_the_same_work():
    """The real mix: 16 documents on their grid, 64 triples in which every
    document appears 4 times, dealt in groups that hold every document
    once, whatever the seed."""
    with open(os.path.join(Rh.REPO, "chipbench", "traffic",
                           "dsv2docqa.json")) as f:
        p = json.load(f)
    lens = TD.document_lengths(p)
    assert len(lens) == 16 and 8192 <= min(lens) and max(lens) <= 32768
    assert sum(-(-n // 128) for n in lens) == 2223
    e = p["engine"]
    assert e["num_blocks"] == 2223 + 64 * 5 + 1 + 272
    assert max(lens) + 256 + 256 <= e["max_blocks_per_seq"] * 128
    triples = TD.triple_multiset(p)
    assert sorted(t[0] for t in triples) == sorted(list(range(16)) * 4)
    assert max(lens[d] % 128 + q + a for d, q, a in triples) <= 5 * 128
    docs = [np.zeros(n, np.int32) for n in lens]
    totals = set()
    for seed in (1, 2 ** 31 + 5):
        gen = TD.DocQA(p, 12800, seed, docs)
        dealt = [gen.next_request() for _ in range(128)]
        for g in range(8):      # every 16 requests: every document once
            assert sorted(gen.document_of[16 * g:16 * g + 16]) == \
                list(range(16))
        totals.add((sum(len(q) for q, _ in dealt[:64]),
                    sum(a for _, a in dealt[:64])))
    assert len(totals) == 1


def test_the_runner_end_to_end_and_its_metrics(copy):
    rc, last, out = Rh.run_cell(copy, CELL, seconds=10.0, trace=0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "compiles in window 0" in out and "preemptions 0" in out
    assert "cached pages evicted" in out
    assert set(_checks(out)) == {
        "served_logit_gap_max", "served_logit_gap_mean",
        "route_clear_mismatches", "route_flip_share",
        "route_flip_share_first", "latent_rel_err_max",
        "latent_rel_err_first"}
    # the token count leaves the prefix hits out: what ran is far less
    # than the prompts, and the rate is made of what ran
    w = _window(out)
    assert 0 < w["run"] < 0.6 * w["uncut"]
    span = float(out.split(" engine steps in ")[1].split(" s;")[0])
    assert last["metrics"]["serve_tok_s"]["value"] == pytest.approx(
        (w["output"] + w["run"]) / span, rel=1e-3)
    rc, last, out = Rh.run_cell(copy, CELL, seconds=10.0, trace=1, seed=8)
    assert rc == 0, out[-3000:]
    got = last["metrics"]
    assert {"engine_step_p50_ms.serve", "burst_k_mean.serve",
            "pool_peak_pct.serve", "moe_touched_pct.dsv2docqa",
            "moe_load_max_over_mean.dsv2docqa",
            "moe_local_token_pct.dsv2docqa", "prefix_hit_pct.dsv2docqa",
            "cache_evictions_in_window.dsv2docqa",
            "preemptions_in_window.dsv2docqa"} <= set(got)
    assert 0 < got["moe_touched_pct.dsv2docqa"]["value"] <= 100
    assert 0 < got["moe_local_token_pct.dsv2docqa"]["value"] <= 100
    assert 40 < got["prefix_hit_pct.dsv2docqa"]["value"] < 100
    assert got["cache_evictions_in_window.dsv2docqa"]["value"] >= 0
    # no chip, no device trace: nothing under a device metric's name
    assert not any(k.startswith(("device_idle_pct", "mla_attn_", "mla_proj",
                                 "moe_grouped", "moe_expert", "moe_route"))
                   for k in got)


@pytest.mark.parametrize("control,felt", [
    ("weights_fp8", "route_flip_share"),
    ("cache_fp8", "latent_rel_err_first")])
def test_a_control_is_not_correct_by_the_limit_that_feels_it(copy, control,
                                                             felt):
    rc, last, out = Rh.run_cell(copy, CELL, "--control", control,
                                seconds=10.0, seed=1)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, _checks(out)
    assert _checks(out)[felt] is False


BROKEN = {
    "a-token-altered": (
        "emit = S.ServingEngine._emit\n"
        "def wrong(self, r, tok):\n"
        "    return emit(self, r, (tok + 1) % 96 if len(r.output) % 7 == 3"
        " else tok)\n"
        "S.ServingEngine._emit = wrong\n"),
    "the-rotary-key-left-unrotated": (
        "rope = DS._rope\n"
        "DS._rope = lambda x, pos, f: x if x.ndim == 2 else rope(x, pos, f)"
        "\n"),
    "the-shared-expert-left-out": (
        "ffn = DS._gated_ffn\n"
        "DS._gated_ffn = lambda f, g, u, d, dt: ffn(f, g, u, d, dt) * "
        "(0.0 if g.shape[-1] == 64 and g.ndim == 2 and u.shape[0] == 64 "
        "and d.shape[0] == 64 else 1.0)\n"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_timed_path_is_not_correct(copy, fault):
    """Each fault in the PROGRAM alone (the reference is not the
    program's): a served token replaced on its way out, the cache's rotary
    key stored as it left the projection, the shared expert's output
    dropped."""
    patch = ("from paddle_tpu.models import deepseek_v2 as DS\n"
             "from paddle_tpu.inference import serving as S\n"
             "import jax.numpy as jnp\n" + BROKEN[fault])
    rc, last, out = Rh.run_cell(copy, CELL, patch=patch, seconds=10.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, _checks(out)


def test_a_token_count_with_the_hits_in_it_is_caught(copy):
    """The runner's count against the yardstick's uncut one: patched to
    the uncut count the rate rises by the hits, which the test of the
    metrics above would refuse."""
    patch = ("from chipbench.runners import serve_docqa as SD\n"
             "from chipbench import yardstick as Y\n"
             "SD.prefilled_in_window = lambda first, hit, a, b: "
             "Y.prefill_tokens_in_window(first, a, b)\n")
    rc, last, out = Rh.run_cell(copy, CELL, patch=patch, seconds=10.0)
    assert rc == 0, out[-3000:]
    w = _window(out)
    assert w["run"] == w["uncut"]       # the hits are in: the count is off
