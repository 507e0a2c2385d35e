"""One chip: the donated plain-`jit` `dense_loss` + AdamW step, the call
sequence of `chip_smoke._run_dense` at the cell's own sizes."""

import functools
import gc

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import gpt as G

from chipbench import harness, traffic as T, weights as W
from chipbench.runners import _train


def gpt_config(config):
    w = config["widths"]
    dt = jnp.dtype(config["dtype"])
    return G.GPTConfig(
        vocab_size=w["vocab_size"], hidden_size=w["hidden_size"],
        num_layers=w["num_layers"], num_heads=w["num_heads"],
        ffn_hidden=w["ffn_hidden"], max_seq_len=w["max_seq_len"],
        dtype=dt, param_dtype=dt)


def adamw(config):
    o = config["optimizer"]
    return paddle.optimizer.AdamW(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        moment_dtype=jnp.dtype(o["moment_dtype"]))


class DenseCell:
    def __init__(self, ctx):
        config = ctx["config"]
        cfg, opt, lr = gpt_config(config), adamw(config), \
            config["optimizer"]["lr"]
        self.params = W.make_params(config["widths"], ctx["seed"],
                                    config["dtype"])
        self.state = jax.jit(opt.init_state)(self.params)
        self.batches = [tuple(jnp.asarray(a) for a in b)
                        for b in T.train_batches(
                            ctx["traffic"], cfg.vocab_size, ctx["seed"])]
        if ctx["control"] == "fp8":
            from paddle_tpu.quantization import fp8 as F8
            self.meta = F8.init_fp8_meta(G.GPT_FP8_SITES, cfg.num_layers)
            inner = F8.make_fp8_train_step(
                lambda p, s, t, l: G.dense_loss(p, t, l, cfg, fp8=s), opt)

            def step(params, state, tokens, labels):
                params, state, self.meta, loss = inner(
                    params, state, self.meta, tokens, labels, lr)
                return params, state, loss
            self._step = step
            return
        assert ctx["control"] is None, ctx["control"]

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, state, tokens, labels):
            loss, grads = jax.value_and_grad(
                lambda p: G.dense_loss(p, tokens, labels, cfg))(params)
            params, state = opt.apply(params, grads, state, lr)
            return params, state, loss
        self._step = step

    def step(self, i):
        tokens, labels = self.batches[i % len(self.batches)]
        self.params, self.state, loss = self._step(
            self.params, self.state, tokens, labels)
        return loss

    def free(self):
        self.params = self.state = self._step = self.batches = None
        self.meta = None
        gc.collect()


def run(ctx):
    harness.mark(ctx, 'imports done, chip held')
    return _train.drive(ctx, DenseCell(ctx))
