"""Every runner driven end to end on the CPU at toy size, in a temporary
copy to which the toy cells are ADDED as new files and new entries; the
controls and the broken timed paths must come out as not correct."""

import json
import os

import pytest

from chipbench.tests import rehearsal as Rh


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return Rh.make_copy(str(tmp_path_factory.mktemp("chipbench_copy")))


def _checks(out):
    return {ln.split()[1]: ln.strip().endswith(" ok")
            for ln in out.splitlines() if ln.startswith("[check]")}


def _assert_line(last, metrics):
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(metrics) <= set(last["metrics"]), last["metrics"]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(isinstance(m["value"], float) and "unit" in m
               for m in last["metrics"].values())


@pytest.mark.parametrize("cell,e2e,layer", [
    ("toy-train", "train_tok_s", "step_p50_ms.train"),
    ("toy-hybrid", "train_tok_s", "step_p50_ms.train"),
    ("toy-serve", "tpot_p95_ms", "engine_step_p50_ms.chat"),
])
def test_runner_end_to_end(copy, cell, e2e, layer):
    seconds = 14.0 if cell == "toy-serve" else 3.0
    rc, last, out = Rh.run_cell(copy, cell, seconds=seconds, trace=0)
    assert rc == 0, out[-3000:]
    _assert_line(last, [e2e, "setup_s"])
    assert last["correct"] is True, out[-3000:]
    assert "compiles in window 0" in out
    rc, last, out = Rh.run_cell(copy, cell, seconds=seconds, trace=1, seed=8)
    assert rc == 0, out[-3000:]
    _assert_line(last, [layer])
    assert "breakdown" in last and "busy_s" in last["device"]
    # no chip, no device trace: the trace's readers found nothing to read
    assert not any(k.startswith(("device_idle_pct", "pallas_time_pct",
                                 "mfu_pct")) for k in last["metrics"])


BROKEN = {
    # a step that returns its state unchanged
    "unchanged": ("toy-train", (
        "import paddle_tpu as paddle\n"
        "paddle.optimizer.AdamW.apply = "
        "lambda self, params, grads, state, lr=None: (params, state)\n"),
        {"first_grad_norm_gap", "moved_norm_gap"}),
    # half of the batch left out of the loss
    "half_batch": ("toy-train", (
        "from paddle_tpu.models import gpt as G\n"
        "_loss = G.dense_loss\n"
        "G.dense_loss = lambda p, t, l, cfg, **kw: "
        "_loss(p, t[:2], l[:2], cfg, **kw)\n"),
        {"loss_gap_step1"}),
    # a token altered where it is produced
    "token": ("toy-serve", (
        "from paddle_tpu.inference import serving as S\n"
        "_chk = S.ServingEngine._check_tok\n"
        "S.ServingEngine._check_tok = lambda self, r, tok: "
        "(_chk(self, r, tok) + (len(r.output) == 3)) % 512\n"),
        {"served_logit_gap_max"}),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_timed_path_is_not_correct(copy, fault):
    cell, patch, must_fail = BROKEN[fault]
    rc, last, out = Rh.run_cell(copy, cell, patch=patch,
                                seconds=14.0 if cell == "toy-serve" else 3.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False
    checks = _checks(out)
    assert must_fail <= {k for k, ok in checks.items() if not ok}, checks


@pytest.mark.parametrize("cell,control,seconds", [
    ("toy-train", "fp8", 3.0), ("toy-hybrid", "fp8", 3.0),
    ("toy-serve-wide", "int8", 12.0)])
def test_the_lower_precision_control_is_not_correct(copy, cell, control,
                                                    seconds):
    """The program's own lower-precision path in the program's place. The
    toy limits were set as the cells' are: from sound runs and control
    runs over several seeds (see the toy traffic files)."""
    rc, last, out = Rh.run_cell(copy, cell, "--control", control,
                                seconds=seconds, seed=1)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, _checks(out)
    if cell == "toy-serve-wide":    # and the sound program passes them
        rc, last, out = Rh.run_cell(copy, cell, seconds=seconds, seed=1)
        assert rc == 0 and last["correct"] is True, _checks(out)


def test_new_config_traffic_metric_and_runner_are_files_and_entries(copy):
    """A dummy of each, added to the copy without editing a file in it."""
    root = os.path.join(copy, "chipbench")
    new = {
        "configs/dummy-config.json": json.dumps(
            {"name": "dummy-config", "source": "tests", "reduced": [],
             "deployment": {"chips": 1}}),
        "traffic/dummy-mix.json": json.dumps(
            {"runner": "dummy_runner", "answer": 42.0}),
        "metrics/dummy_metric.x.json": json.dumps(
            {"name": "dummy_metric.x", "layer": "entry", "unit": "count",
             "moves": "dummy_e2e", "reader": "dummy_reader",
             "params": {"key": "answer"}}),
        "readers/dummy_reader.py":
            "def read(run, key):\n    return run['facts'].get(key)\n",
        "runners/dummy_runner.py": (
            "def run(ctx):\n"
            "    import time\n"
            "    return {'devices': ctx['devices'], 'attempted': 1,\n"
            "            'failed': 0, 'memory_peak_bytes': 0,\n"
            "            'checks': [('exact', 0.0, 0.0)],\n"
            "            'trace': {'devices': {}, 'async': {}, 'host': []},\n"
            "            'e2e': {'dummy_e2e': ctx['traffic']['answer'],\n"
            "                    'setup_s': time.perf_counter() - ctx['t0']},\n"
            "            'facts': {'answer': ctx['traffic']['answer']}}\n"),
    }
    for rel, text in new.items():
        path = os.path.join(root, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "dummy-config", "source": "tests",
                            "file": "chipbench/configs/dummy-config.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": "dummy-cell", "config": "dummy-config",
                              "traffic": "dummy-mix", "chips": 1,
                              "why": "tests"})
    spec["end_to_end"].append({"name": "dummy_e2e", "unit": "count",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["dummy-cell"]})
    spec["per_layer"].append({"name": "dummy_metric.x", "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": "entry", "moves": "dummy_e2e",
                              "workloads": ["dummy-cell"]})
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    rc, last, out = Rh.run_cell(copy, "dummy-cell", seconds=1.0)
    assert rc == 0 and last["correct"] is True, out[-2000:]
    assert last["metrics"]["dummy_e2e"]["value"] == 42.0
    assert set(last["metrics"]) == {"dummy_e2e", "setup_s"}
    rc, last, out = Rh.run_cell(copy, "dummy-cell", seconds=1.0, trace=1)
    assert rc == 0, out[-2000:]
    assert last["metrics"] == {"dummy_metric.x": {"value": 42.0,
                                                  "unit": "count"}}
