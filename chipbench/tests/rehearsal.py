"""The tests' rehearsal switch: a temporary copy of the benchmark with toy
cells ADDED (new files and new BENCHMARK.json entries, nothing edited), run
on the CPU with the look for a chip skipped. Lives with the tests only."""

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

TOY_CELLS = [
    {"name": "toy-train", "config": "tiny-gpt", "traffic": "tiny-train",
     "chips": 1, "why": "tests only"},
    {"name": "toy-hybrid", "config": "tiny-gpt", "traffic": "tiny-hybrid",
     "chips": 4, "why": "tests only"},
    {"name": "toy-serve", "config": "tiny-gpt", "traffic": "tiny-serve",
     "chips": 1, "why": "tests only"},
    {"name": "toy-serve-wide", "config": "tiny-gpt-wide",
     "traffic": "tiny-serve-wide", "chips": 1, "why": "tests only"},
]
GROUP = {"toy-train": ".train", "toy-hybrid": ".train", "toy-serve": ".chat",
         "toy-serve-wide": ".chat"}


def make_copy(dst):
    """Copies BENCHMARK.json and chipbench/ to `dst` and adds the toy
    cells. Returns the copy's root."""
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for name, kind in (("tiny-gpt", "configs"), ("tiny-gpt-wide", "configs"),
                       ("tiny-train", "traffic"), ("tiny-hybrid", "traffic"),
                       ("tiny-serve", "traffic"),
                       ("tiny-serve-wide", "traffic")):
        shutil.copy(os.path.join(HERE, "data", name + ".json"),
                    os.path.join(dst, "chipbench", kind, name + ".json"))
    for name in ("tiny-gpt", "tiny-gpt-wide"):
        spec["configs"].append({
            "name": name, "source": "tests only", "reduced": [],
            "file": f"chipbench/configs/{name}.json", "why": "tests only"})
    spec["workloads"] += TOY_CELLS
    for cell in TOY_CELLS:
        moved = {".train": "train_tok_s",
                 ".chat": "tpot_p95_ms"}[GROUP[cell["name"]]]
        for m in spec["end_to_end"]:
            if m["name"] == moved:
                m["workloads"].append(cell["name"])
        for m in spec["per_layer"]:
            if m["name"].endswith(GROUP[cell["name"]]):
                m["workloads"].append(cell["name"])
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    return dst


def run_cell(root, workload, *extra, seconds=2.0, seed=7, trace=0,
             patch="", timeout=900):
    """Runs one cell of the copy at `root` in a child process on the CPU
    (4 virtual devices), the look for a chip skipped. `patch` is Python
    run in the child before the cell, for tests that break the timed path.
    Returns (exit code, parsed last line or None, all output)."""
    code = (
        "import sys; sys.path.insert(0, %r); sys.path.insert(1, %r)\n"
        "import time; t0 = time.perf_counter()\n"
        "%s\n"
        "from chipbench import run\n"
        "run.main(%r, allow_cpu=True, t0=t0)\n"
    ) % (root, REPO, patch,
         ["--workload", workload, "--seed", str(seed), "--seconds",
          str(seconds), "--trace", str(trace), *extra])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                       capture_output=True, text=True, timeout=timeout)
    out = p.stdout + p.stderr
    last = None
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    if p.returncode == 0 and lines:
        last = json.loads(lines[-1])
    return p.returncode, last, out
