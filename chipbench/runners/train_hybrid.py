"""Four chips, one process: `G.build_hybrid_train_step` on the mesh the
configuration states (dp x mp), the layout PR 23 proved. The builder cannot
donate, so parameters and moments are resident twice."""

import gc

import jax.numpy as jnp

import paddle_tpu.distributed as dist
from paddle_tpu.models import gpt as G

from chipbench import harness, traffic as T, weights as W
from chipbench.runners import _train
from chipbench.runners.train_dense import adamw, gpt_config


class HybridCell:
    def __init__(self, ctx):
        config, traffic = ctx["config"], ctx["traffic"]
        cfg = gpt_config(config)
        mesh = dist.build_mesh(dict(config["deployment"]["mesh"]),
                               devices=list(ctx["devices"]))
        assert ctx["control"] in (None, "fp8"), ctx["control"]
        self._step, shard_params, init_state = G.build_hybrid_train_step(
            cfg, mesh, adamw(config),
            num_microbatches=traffic["microbatches"],
            fp8=(ctx["control"] == "fp8"))
        self.params = shard_params(
            W.make_params(config["widths"], ctx["seed"], config["dtype"]))
        self.state = init_state(self.params)
        self.lr = jnp.float32(config["optimizer"]["lr"])
        self.batches = [tuple(jnp.asarray(a) for a in b)
                        for b in T.train_batches(traffic, cfg.vocab_size,
                                                 ctx["seed"])]

    def step(self, i):
        tokens, labels = self.batches[i % len(self.batches)]
        self.params, self.state, loss = self._step(
            self.params, self.state, tokens, labels, self.lr)
        return loss

    def free(self):
        self.params = self.state = self._step = self.batches = None
        gc.collect()


def run(ctx):
    harness.mark(ctx, 'imports done, chips held')
    return _train.drive(ctx, HybridCell(ctx))
