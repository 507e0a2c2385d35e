"""BENCHMARK.json against the contract's limits, and the files it names."""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from chipbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = harness.load_spec()
CELLS = [c["name"] for c in SPEC["workloads"]]
EMPTY_RUN = {"facts": {}, "trace": None}


def _meta(name):
    return harness.load_json("metrics", name + ".json")


def _reported(cell, group="per_layer"):
    """The entries of a group that a cell reports, found as the harness
    does."""
    return harness.metrics_of(SPEC, harness.cell_of(SPEC, cell), group)


def test_keys_names_and_units_are_legal():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"] and 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"][1].startswith("chipbench/")
    names = []
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in SPEC[group]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]) and len(e["why"]) <= 200
            names.append((group, e["name"]))
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        names.append(("metric", m["name"]))
    assert len(names) == len(set(names))
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_cells_find_their_files_by_name():
    configs = {c["name"]: c for c in SPEC["configs"]}
    seen = set()
    for cell in SPEC["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert (cell["config"], cell["traffic"]) not in seen
        seen.add((cell["config"], cell["traffic"]))
        entry = configs[cell["config"]]
        assert entry["file"] == f"chipbench/configs/{cell['config']}.json"
        config = harness.load_json("configs", cell["config"] + ".json")
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"]
        assert config["deployment"]["chips"] == cell["chips"]
        traffic = harness.load_json("traffic", cell["traffic"] + ".json")
        assert hasattr(harness.runner_of(traffic), "run")
        e2e = harness.metrics_of(SPEC, cell, "end_to_end")
        assert {"setup_s"} < {m["name"] for m in e2e}
        assert harness.metrics_of(SPEC, cell, "per_layer")
    assert {c["config"] for c in SPEC["workloads"]} == set(configs)
    four = [c for c in SPEC["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 4)


def test_widths_are_the_published_ones():
    w = harness.load_json("configs", "gpt3-1p3b.json")["widths"]
    assert (w["hidden_size"], w["num_layers"], w["num_heads"],
            w["ffn_hidden"], w["max_seq_len"], w["vocab_size"]) == \
        (2048, 24, 16, 8192, 2048, 50304)
    c = harness.load_json("configs", "gpt3-6p7b.json")
    w = c["widths"]
    assert (w["hidden_size"], w["num_heads"], w["ffn_hidden"],
            w["max_seq_len"], w["vocab_size"]) == \
        (4096, 32, 16384, 2048, 50304)
    assert c["reduced"] == ["num_layers"] and w["num_layers"] % 2 == 0


def test_every_per_layer_metric_has_its_file_and_reader():
    """An entry's cells are the ones it lists or, without a list, every
    cell that reports the end-to-end metric it moves; a listed cell
    reports that metric too, and the harness hands the entry to exactly
    those cells."""
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    reported = {c: _reported(c) for c in CELLS}
    for m in SPEC["per_layer"]:
        meta = _meta(m["name"])
        assert (meta["layer"], meta["unit"], meta["moves"]) == \
            (m["layer"], m["unit"], m["moves"])
        reader = importlib.import_module(
            f"chipbench.readers.{meta['reader']}")
        assert callable(reader.read)
        report_moved = set(e2e[m["moves"]].get("workloads", CELLS))
        cells = set(m.get("workloads", report_moved))
        assert cells and cells <= report_moved <= set(CELLS), m["name"]
        assert cells == {c for c in CELLS if m in reported[c]}, m["name"]
    files = {f[:-5] for f in os.listdir(os.path.join(harness.HERE, "metrics"))}
    assert files == {m["name"] for m in SPEC["per_layer"]}


def test_a_reader_with_nothing_to_read_returns_nothing():
    for m in SPEC["per_layer"]:
        assert harness.read_metric(m["name"], EMPTY_RUN) is None


# What keeps the table from filling up by copy: an entry that every cell
# reporting what it moves can read carries no list, so a later cell gets it
# unasked, and a copy of it under the new cell's own suffix is then the
# second name of one reading in that cell. Rules over what a cell reports,
# never over the table's names or size: an added entry trips none of them
# unless it is such a copy.
@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reads_nothing_under_two_names(cell):
    """A cell's entries differ pairwise in what they read: reader and
    params, whatever the entries are called."""
    names = {}
    for m in _reported(cell):
        meta = _meta(m["name"])
        names.setdefault(json.dumps([meta["reader"], meta.get("params", {})],
                                    sort_keys=True), []).append(m["name"])
    assert not [ns for ns in names.values() if len(ns) > 1]


@pytest.mark.parametrize("cell", CELLS)
def test_every_entry_a_cell_reports_has_its_file_and_reader(cell):
    """As the result line needs them: the file by the entry's name, the
    reader by the file's; and what the entry moves is an end-to-end metric
    of this very cell."""
    moved = {m["name"] for m in _reported(cell, "end_to_end")}
    for m in _reported(cell):
        meta = _meta(m["name"])
        assert meta["name"] == m["name"] and m["moves"] in moved, m["name"]
        reader = importlib.import_module(
            f"chipbench.readers.{meta['reader']}")
        assert callable(reader.read), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_entries_read_nothing_from_an_empty_run(cell):
    """A run that recorded nothing gives a result line without the
    metric, never a 0 under a share's or a roofline's name."""
    entries = _reported(cell)
    assert entries
    assert [m["name"] for m in entries
            if harness.read_metric(m["name"], EMPTY_RUN) is not None] == []


def test_run_refuses_any_platform_but_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "train-1p3b-seq2k", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(ln.lstrip().startswith("{") for ln in p.stdout.splitlines())
