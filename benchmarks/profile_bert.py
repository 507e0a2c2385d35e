"""Profile the packed BERT-base step on the TPU and print a per-fusion
time breakdown (top ops + category sums). Round-4 tool for the ≥35% MFU
push — identifies where the step's ms actually go.

Usage: python benchmarks/profile_bert.py [--iters 6]
"""

import glob
import gzip
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import functools
import numpy as np


def run_and_trace(iters=6):
    import jax
    from paddle_tpu.device import require_tpu
    require_tpu("benchmarks/profile_bert.py")
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from benchmarks.configs_bench import _bert_job
    from paddle_tpu.models.bert import bert_pretrain_loss, pack_sequences
    from paddle_tpu.nn import functional_call

    (cfg, model, params, buffers, opt, state, rng, seqs, lens, t_real,
     flops, B, S) = _bert_job(jax, jnp, paddle)
    ids, seg, pos, _, _ = pack_sequences(seqs, S)
    Bp = ids.shape[0]
    real = seg >= 0
    mlm_labels = jnp.asarray(
        np.where((rng.rand(Bp, S) < 0.15) & real,
                 rng.randint(0, cfg.vocab_size, (Bp, S)), -100))
    nsp_labels = jnp.asarray(rng.randint(0, 2, (Bp,)))
    ids, seg, pos = jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(pos)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, ids, seg, pos, mlm_labels, nsp_labels):
        def loss_fn(p):
            (mlm, nsp), _ = functional_call(
                model, p, buffers, ids, pack_segment_ids=seg,
                position_ids=pos)
            return bert_pretrain_loss(mlm, nsp, mlm_labels, nsp_labels)
        l, g = jax.value_and_grad(loss_fn)(params)
        params, state = opt.apply(params, g, state, 1e-4)
        return params, state, l

    args = (ids, seg, pos, mlm_labels, nsp_labels)
    carry = step(params, state, *args)
    float(carry[-1])  # warm
    tdir = tempfile.mkdtemp(prefix="bert_prof_")
    jax.profiler.start_trace(tdir)
    for _ in range(iters):
        carry = step(*carry[:-1], *args)
    float(carry[-1])
    jax.profiler.stop_trace()
    return tdir, iters, flops


CATS = [
    ("flash", ("flash", "_attn")),
    ("matmul/fusion-dot", ("dot", "convolution")),
    ("convert/opt", ("convert",)),
    ("dynamic-slice/update", ("dynamic",)),
    ("scatter/gather", ("scatter", "gather")),
    ("reduce", ("reduce",)),
    ("copy/transpose", ("copy", "transpose")),
]


def parse(tdir, iters, flops):
    from paddle_tpu.observability.flops import peak_flops
    paths = glob.glob(os.path.join(
        tdir, "**", "*.trace.json.gz"), recursive=True)
    if not paths:
        print("no trace found under", tdir)
        return
    with gzip.open(paths[0], "rt") as f:
        trace = json.load(f)
    ev = trace["traceEvents"]
    # ONLY the per-device "XLA Ops" lane: the "XLA Modules" and "Steps"
    # lanes nest the same device time, so summing every TPU-pid event
    # would double/triple count it
    tpu_pids = set()
    thread_names = {}
    for e in ev:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            nm = e["args"].get("name", "")
            if "TPU" in nm or "/device:" in nm:
                tpu_pids.add(e["pid"])
        elif e.get("name") == "thread_name":
            thread_names[(e["pid"], e["tid"])] = e["args"].get("name", "")
    per_op = {}
    total = 0.0
    for e in ev:
        if e.get("ph") != "X" or e.get("pid") not in tpu_pids:
            continue
        if thread_names.get((e["pid"], e.get("tid"))) != "XLA Ops":
            continue
        dur = e.get("dur", 0) / 1e3  # us -> ms
        nm = e.get("name", "")
        if dur <= 0:
            continue
        per_op[nm] = per_op.get(nm, 0.0) + dur
        total += dur
    per_step = {k: v / iters for k, v in per_op.items()}
    top = sorted(per_step.items(), key=lambda kv: -kv[1])[:35]
    print(f"== total device time/step: {total/iters:.2f} ms "
          f"(useful {flops/1e12:.2f} TF -> "
          f"{flops / (total / iters / 1e3) / peak_flops() * 100:.1f}% MFU "
          "if device-bound)")
    print("== top ops (ms/step):")
    for k, v in top:
        print(f"  {v:8.3f}  {k[:110]}")
    print("== categories (ms/step):")
    seen = set()
    for cat, keys in CATS:
        s = 0.0
        for k, v in per_step.items():
            lk = k.lower()
            if any(x in lk for x in keys) and k not in seen:
                s += v
                seen.add(k)
        print(f"  {s:8.3f}  {cat}")
    rest = sum(v for k, v in per_step.items() if k not in seen)
    print(f"  {rest:8.3f}  other")


if __name__ == "__main__":
    iters = 6
    if "--iters" in sys.argv:
        iters = int(sys.argv[sys.argv.index("--iters") + 1])
    tdir, iters, flops = run_and_trace(iters)
    parse(tdir, iters, flops)
    print("trace dir:", tdir)
