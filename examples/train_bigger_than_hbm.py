"""Example: train a model whose parameters don't fit the chip's HBM.

The param-streaming tier (distributed/sharding/param_stream.py) keeps
params AND optimizer moments in host memory (pinned_host) and streams one
transformer block at a time through HBM — forward and backward, with the
Adam update fused into the backward so gradients never exist model-wide.
This is how GPT-3 6.7B and Llama-2 7B are meant to train on a single
16 GB v5e (not measured on the current installation; reference analogue: GroupShardedStage3 param slicing with
gather-on-use + offload, group_sharded_stage3.py:85).

Run (CPU demo shapes):   python examples/train_bigger_than_hbm.py
Real thing (one v5e):    python examples/train_bigger_than_hbm.py --model gpt-6.7b
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny",
                    choices=["tiny", "gpt-6.7b", "llama-7b"])
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed.sharding.param_stream import (
        build_param_streamed_train_step, park)

    if args.model == "llama-7b":
        from paddle_tpu.models import llama as M
        cfg = M.llama2_7b(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        batch, seq = 2, 2048
    elif args.model == "gpt-6.7b":
        from paddle_tpu.models import gpt as M
        cfg = M.gpt_6p7b(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        batch, seq = 4, 2048
    else:
        from paddle_tpu.models import gpt as M
        cfg = M.gpt_tiny(dtype=jnp.float32)
        batch, seq = 2, 64

    on_tpu = any(d.platform.lower() != "cpu" for d in jax.devices())
    moments = jnp.bfloat16 if on_tpu else None

    # 1. optimizer must follow the per-leaf protocol (AdamW-family)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, moment_dtype=moments)

    # 2. the model as three segment functions over a segmented param tree
    place, init_state, step = build_param_streamed_train_step(
        *M.streamed_fns(cfg), opt)

    # 3. init ONE segment at a time, parking each in pinned_host
    hparams = M.init_streamed_params(cfg, jax.random.PRNGKey(0), park=park)
    hstate = init_state(hparams)
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(hparams))
    print(f"{n/1e9:.2f}B params resident in "
          f"{jax.tree.leaves(hparams)[0].sharding.memory_kind}")

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))

    for i in range(args.steps):
        t0 = time.perf_counter()
        hparams, hstate, loss = step(hparams, hstate, tokens, labels, 1e-4)
        print(f"step {i}: loss {float(loss):.3f} "
              f"({time.perf_counter() - t0:.2f} s)")


if __name__ == "__main__":
    main()
