"""Profile the flagship GPT-1.3B train step (bench.py config) on the TPU
and print the per-op breakdown — same tooling as profile_bert.py.

Usage: python benchmarks/profile_gpt.py [--iters 3]
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import functools

import numpy as np


def run_and_trace(iters=3):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.device import require_tpu
    require_tpu("benchmarks/profile_gpt.py")
    from paddle_tpu.models import gpt as G
    from bench import FLAGSHIP

    conf = FLAGSHIP
    cfg = G.GPTConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_layers"], num_heads=conf["num_heads"],
        max_seq_len=conf["max_seq_len"], dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    opt = paddle.optimizer.AdamW(1e-4, moment_dtype=jnp.bfloat16)
    state = jax.jit(opt.init_state)(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: G.dense_loss(p, tokens, labels, cfg))(params)
        params, state = opt.apply(params, grads, state, 1e-4)
        return params, state, loss

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (conf["batch"], conf["seq"])))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (conf["batch"], conf["seq"])))
    params, state, loss = step(params, state, tokens, labels)
    float(loss)
    tdir = tempfile.mkdtemp(prefix="gpt_prof_")
    jax.profiler.start_trace(tdir)
    for _ in range(iters):
        params, state, loss = step(params, state, tokens, labels)
    float(loss)
    jax.profiler.stop_trace()
    # useful flops: 6*N_matmul*tokens + 12*L*H*S^2 (causal halves the
    # attention term; keep the convention bench.py uses for MFU)
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params))
    emb = cfg.vocab_size * cfg.hidden_size
    toks = conf["batch"] * conf["seq"]
    flops = (6.0 * (n - emb) * toks
             + 12.0 * cfg.num_layers * cfg.hidden_size * conf["batch"]
             * conf["seq"] ** 2)
    return tdir, iters, flops


if __name__ == "__main__":
    iters = 3
    if "--iters" in sys.argv:
        iters = int(sys.argv[sys.argv.index("--iters") + 1])
    tdir, iters, flops = run_and_trace(iters)
    from profile_bert import parse
    parse(tdir, iters, flops)
    print("trace dir:", tdir)
