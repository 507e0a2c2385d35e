"""Plain reference of the training step: the GPT loss, its gradients and
AdamW, in float32, layer by layer so that it fits beside nothing else on
the chips of the cell.

Stored parameters keep the storage type the configuration states (bfloat16)
and are rounded to it, to nearest, after every update; all arithmetic is
float32 at `highest`. The Adam moments are float32 and never stored on the
device: each step's gradients go to the host, and step t rebuilds its
moments from the t gradients so far, which for the three steps followed is
cheaper than carrying two moment trees. Where a cell spans several chips,
rows are spread over them and the parameters are copied to each: the same
plain functions, partitioned by the compiler.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench.reference import gpt as R

_F32 = jnp.float32


@functools.partial(jax.jit, static_argnames=("n_heads",))
def _layer_fwd(p, x, *, n_heads):
    return R.block(p, x, n_heads)


@functools.partial(jax.jit, static_argnames=("n_heads",), donate_argnums=(2,))
def _layer_bwd(p, x, dy, *, n_heads):
    _, vjp = jax.vjp(lambda p_, x_: R.block(p_, x_, n_heads), p, x)
    dp, dx = vjp(dy)
    return dx, jax.tree.map(lambda a: a.astype(_F32), dp)


@jax.jit
def _head_grad(p, x, labels):
    loss, (dp, dx) = jax.value_and_grad(R.head_loss, argnums=(0, 1))(
        p, x, labels)
    return loss, dx, jax.tree.map(lambda a: a.astype(_F32), dp)


@jax.jit
def _embed_grad(p, tokens, dx):
    _, vjp = jax.vjp(lambda p_: R.embed(p_, tokens), p)
    return jax.tree.map(lambda a: a.astype(_F32), vjp(dx)[0])


@functools.partial(jax.jit, static_argnames=("hyper",))
def _adamw(p, grads, *, hyper):
    """p after step t = len(grads), from the t gradients so far (oldest
    first). Returns the new stored leaf."""
    lr, b1, b2, eps, wd = hyper
    t = len(grads)
    m = sum((1 - b1) * b1 ** (t - 1 - k) * g for k, g in enumerate(grads))
    v = sum((1 - b2) * b2 ** (t - 1 - k) * jnp.square(g)
            for k, g in enumerate(grads))
    upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    pf = p.astype(_F32)
    return (pf - lr * upd - lr * wd * pf).astype(p.dtype)


@jax.jit
def sq(tree):
    return jax.tree.map(lambda a: jnp.sum(jnp.square(a.astype(_F32))), tree)


@jax.jit
def sq_diff(a, b):
    return jax.tree.map(
        lambda x, y: jnp.sum(jnp.square(x.astype(_F32) - y.astype(_F32))),
        a, b)


class RefTrainer:
    """Follows the first steps of a cell from the same seeded weights."""

    def __init__(self, params, n_heads, hyper, devices):
        self.n_heads, self.hyper = n_heads, tuple(float(h) for h in hyper)
        mesh = Mesh(np.array(devices), ("rows",))
        self._rows = NamedSharding(mesh, P("rows"))
        self._all = NamedSharding(mesh, P())
        put = functools.partial(jax.device_put, device=self._all)
        L = params["blocks"]["qkv_w"].shape[0]
        self.layers = [put(jax.tree.map(lambda a: a[i], params["blocks"]))
                       for i in range(L)]
        self.embed = put({k: params[k] for k in ("wte", "wpe")})
        self.head = put({k: params[k] for k in ("lnf_g", "lnf_b", "head_w")})
        self._first = self._groups()
        self._hist = []     # per step: host gradients, one tree per group

    def _groups(self):
        return self.layers + [self.embed, self.head]

    def step(self, tokens, labels, last=False):
        """One step on [B, S] int arrays. Returns (loss, {leaf: |grad|})
        with the gradient norm of each stacked leaf as the optimizer gets
        it. Each group of leaves (a layer, the embeddings, the head) is
        updated as soon as its gradient exists, and the gradient then
        leaves the device; `last` says no later step will want it."""
        tokens = jax.device_put(jnp.asarray(tokens), self._rows)
        labels = jax.device_put(jnp.asarray(labels), self._rows)
        xs = [jax.jit(R.embed)(self.embed, tokens)]
        for p in self.layers:
            xs.append(_layer_fwd(p, xs[-1], n_heads=self.n_heads))
        n = len(self.layers)
        squares, host = [None] * (n + 2), [None] * (n + 2)

        def settle(gi, p, g):
            squares[gi] = sq(g)
            past = [h[gi] for h in self._hist]
            new = jax.tree.map(
                lambda leaf, *gs: _adamw(leaf, [jnp.asarray(x) for x in gs],
                                         hyper=self.hyper), p, *past, g)
            if not last:
                host[gi] = jax.tree.map(np.asarray, g)
            return new

        loss, dx, g = _head_grad(self.head, xs.pop(), labels)
        self.head = settle(n + 1, self.head, g)
        for i in reversed(range(n)):
            dx, g = _layer_bwd(self.layers[i], xs.pop(), dx,
                               n_heads=self.n_heads)
            self.layers[i] = settle(i, self.layers[i], g)
        g = _embed_grad(self.embed, tokens, dx)
        self.embed = settle(n, self.embed, g)
        self._hist.append(host)
        return float(loss), self._norms(squares)

    @staticmethod
    def _norms(sq_groups):
        """Sum the per-layer squares into the stacked leaves' norms."""
        total = {}
        n_layers = len(sq_groups) - 2
        for gi, group in enumerate(sq_groups):
            for k, v in group.items():
                name = f"blocks.{k}" if gi < n_layers else k
                total[name] = total.get(name, 0.0) + float(v)
        return {k: v ** 0.5 for k, v in total.items()}

    def moved(self):
        """{leaf: |stored now - stored at the start|}."""
        return self._norms([sq_diff(a, b)
                            for a, b in zip(self._groups(), self._first)])
