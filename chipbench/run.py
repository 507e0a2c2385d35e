"""chipbench: one cell, once, in one process.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration, traffic mix and
per-layer metrics by name under chipbench/, refuses anything but the TPU
chips the cell asks for, and ends its standard output with one JSON line.
"""

import time

_T0 = time.perf_counter()   # the process's start, for setup_s

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None, allow_cpu=False, t0=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="run the cell's lower-precision control instead "
                         "(the builder's and the tests' use; the driver "
                         "never passes it)")
    args = ap.parse_args(argv)

    from chipbench import harness
    spec = harness.load_spec()
    cell = harness.cell_of(spec, args.workload)
    config = harness.load_json("configs", cell["config"] + ".json")
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    devices = harness.require_chips(cell["chips"], allow_cpu)
    if not allow_cpu:
        harness.log("[env] compile cache:", harness.setup_compile_cache())
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "control": args.control,
           "devices": devices, "t0": _T0 if t0 is None else t0,
           "compiles": harness.CompileCounter(),
           "tracer": harness.Trace(cell["name"]) if args.trace else None}
    run = harness.runner_of(traffic).run(ctx)
    line = harness.result_line(spec, cell, run, bool(args.trace))
    print(line, flush=True)
    return line


if __name__ == "__main__":
    main()
