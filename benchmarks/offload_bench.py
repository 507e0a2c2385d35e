"""Bigger-than-HBM single-chip training via host offload.

Three tiers, all on one 16 GB v5e:

* ``--size 2.85b`` (moments offload, VERDICT r2 #3): a 2.76B-param GPT
  (H=2560, L=34, 20 heads) trains with Adam moments parked in pinned_host
  and streamed through HBM one leaf at a time — HBM holds params + grads +
  activations only.

* ``--size 6.7b`` (param streaming, VERDICT r3 #1): the GPT-3 6.7B
  north-star shape (H=4096, L=32, heads=32, vocab 50304) — its bf16 params
  alone (~13.4 GB) don't fit next to activations, so the PARAMS themselves
  live in pinned_host and stream through HBM one block at a time, forward
  and backward, with the optimizer update fused into the backward
  (distributed/sharding/param_stream.py; reference:
  group_sharded_stage3.py:85 param slicing + gather-on-use + offload).

* ``--size llama7b`` (param streaming, round 4): Llama-2 7B — BASELINE
  config 3's REAL shape (rounds 1-3 proxied it at 1.12B because 7B
  exceeded HBM) — through the same streamed trainer via
  models/llama.streamed_fns.

Run on the TPU: `python benchmarks/offload_bench.py --size 6.7b` — prints
one JSON line; refuses to run when jax finds no TPU. All tiers are host-link-bound by design; the point is
capability (the shape trains at all), not throughput.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_moments_offload():
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.sharding.group_sharded import (
        build_sharded_train_step)
    from paddle_tpu.models import gpt as G

    cfg = G.GPTConfig(vocab_size=32768, hidden_size=2560, num_layers=34,
                      num_heads=20, max_seq_len=1024,
                      dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    batch, seq, iters = 4, 1024, 3

    mesh = dist.build_mesh({"sharding": len(jax.devices())})
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 moment_dtype=jnp.bfloat16)

    def loss_fn(p, tokens, labels):
        # full remat: this tier's contract is minimum activation memory
        # (HBM holds params + grads + activations only)
        return G.dense_loss(p, tokens, labels, cfg, remat_save=())

    _, place, compile_for = build_sharded_train_step(
        loss_fn, opt, mesh, level="os", data_axes="sharding", offload=True)

    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params))
    params, state = place(params)
    jstep, bspec = compile_for(params)

    rng = np.random.RandomState(0)
    tokens = jax.device_put(rng.randint(0, cfg.vocab_size, (batch, seq)),
                            bspec)
    labels = jax.device_put(rng.randint(0, cfg.vocab_size, (batch, seq)),
                            bspec)

    params, state, loss = jstep(params, state, tokens, labels,
                                jnp.float32(1e-4))
    float(loss)  # force completion
    t0 = time.perf_counter()
    for _ in range(iters):
        params, state, loss = jstep(params, state, tokens, labels,
                                    jnp.float32(1e-4))
    l_final = float(loss)
    dt = (time.perf_counter() - t0) / iters

    kinds = {leaf.sharding.memory_kind for leaf in jax.tree.leaves(state)
             if getattr(leaf, "ndim", 0) >= 1}
    assert np.isfinite(l_final), l_final
    print(json.dumps({
        "metric": "offload_2p7b_single_chip_step_time",
        "value": round(dt, 3), "unit": "s/step",
        "tokens_per_sec": round(batch * seq / dt, 1),
        "n_params_b": round(n_params / 1e9, 2),
        "state_memory": sorted(kinds),
        "config": f"GPT {n_params/1e9:.2f}B bf16, seq {seq}, batch {batch}, "
                  "Adam moments parked in pinned_host, streamed per leaf",
    }))


def run_param_stream(model: str = "gpt", clip: float = 0.0):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed.sharding.param_stream import (
        build_param_streamed_train_step, park)

    if model == "llama":
        from paddle_tpu.models import llama as G
        cfg = G.llama2_7b(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        batch, seq, iters = 2, 2048, 2
        name = "llama2_7b"
    else:
        from paddle_tpu.models import gpt as G
        cfg = G.gpt_6p7b(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        # the step is host-link-bound, so batch 4 should cost about the
        # same transfer time as batch 2 (not measured on the current
        # installation)
        batch, seq, iters = 4, 2048, 2
        name = "gpt3_6p7b"

    grad_clip = (paddle.nn.ClipGradByGlobalNorm(clip) if clip > 0 else None)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 moment_dtype=jnp.bfloat16,
                                 grad_clip=grad_clip)
    place, init_state, step = build_param_streamed_train_step(
        *G.streamed_fns(cfg), opt)

    t_init = time.perf_counter()
    hparams = G.init_streamed_params(cfg, jax.random.PRNGKey(0), park=park)
    hstate = init_state(hparams)
    n_params = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(hparams))
    init_s = time.perf_counter() - t_init

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))

    hparams, hstate, loss = step(hparams, hstate, tokens, labels, 1e-4)
    l0 = float(loss)  # warmup incl. all 5 program compiles
    t0 = time.perf_counter()
    for _ in range(iters):
        hparams, hstate, loss = step(hparams, hstate, tokens, labels, 1e-4)
    l_final = float(loss)
    dt = (time.perf_counter() - t0) / iters

    kinds = {leaf.sharding.memory_kind for leaf in jax.tree.leaves(hparams)}
    assert np.isfinite(l_final), (l0, l_final)
    assert kinds == {"pinned_host"}, kinds
    print(json.dumps({
        "metric": f"offload_{name}_param_stream_step_time",
        "value": round(dt, 3), "unit": "s/step",
        "tokens_per_sec": round(batch * seq / dt, 1),
        "n_params_b": round(n_params / 1e9, 2),
        "loss_first_to_last": [round(l0, 3), round(l_final, 3)],
        "init_s": round(init_s, 1),
        "param_memory": sorted(kinds),
        "grad_clip": (f"global_norm({clip})" if clip > 0 else "none"),
        "config": f"{name} {n_params/1e9:.2f}B bf16 (H={cfg.hidden_size}, "
                  f"L={cfg.num_layers}, heads={cfg.num_heads}, "
                  f"vocab={cfg.vocab_size}), seq {seq}, batch {batch}; "
                  "params+moments in pinned_host, streamed per block "
                  "fwd+bwd, update fused into backward"
                  + (", two-pass global-norm clip" if clip > 0 else ""),
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", choices=["2.85b", "6.7b", "llama7b"],
                    default="2.85b")
    ap.add_argument("--clip", type=float, default=0.0,
                    help="ClipGradByGlobalNorm threshold (0 = off); the "
                         "GPT-3 recipe uses 1.0 — engages the two-pass "
                         "streamed backward")
    args = ap.parse_args()
    from paddle_tpu.device import require_tpu
    require_tpu("benchmarks/offload_bench.py")
    if args.size == "2.85b":
        if args.clip > 0:
            ap.error("--clip applies to the param-streamed tiers "
                     "(--size 6.7b/llama7b); the 2.85b moments-offload "
                     "tier clips through the optimizer's own apply()")
        run_moments_offload()
    elif args.size == "llama7b":
        run_param_stream(model="llama", clip=args.clip)
    else:
        run_param_stream(clip=args.clip)


if __name__ == "__main__":
    main()
