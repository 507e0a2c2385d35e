"""Seeded weights for every cell: made on the device, one compiled call a
leaf, in the type the configuration stores them in.

The tree has the layout of the program's stacked GPT parameters (blocks
stacked on a leading [L] axis) but is made here, so the reference can make
the same values again from the seed without taking anything the program
produced. Every leaf has a key of its own (`fold_in(key(seed), index)`), so
one leaf can be made again alone, to measure how far a trained leaf moved.
Biases and LayerNorm vectors are drawn too (the program's own initialiser
sets them to 0 and 1): a check against the reference should feel every leaf.
"""

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02
BLOCK_LEAVES = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
                "ln2_g", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")


def leaf_table(widths):
    """[(path, shape, mean, std)] in a fixed order; `widths` is the
    configuration file's `widths` group."""
    H, L = widths["hidden_size"], widths["num_layers"]
    FF, V, S = widths["ffn_hidden"], widths["vocab_size"], widths["max_seq_len"]
    out_std = STD / math.sqrt(2 * L)
    block = {"ln1_g": ((L, H), 1.0, STD), "ln1_b": ((L, H), 0.0, STD),
             "qkv_w": ((L, H, 3 * H), 0.0, STD),
             "qkv_b": ((L, 3 * H), 0.0, STD),
             "proj_w": ((L, H, H), 0.0, out_std),
             "proj_b": ((L, H), 0.0, STD),
             "ln2_g": ((L, H), 1.0, STD), "ln2_b": ((L, H), 0.0, STD),
             "fc1_w": ((L, H, FF), 0.0, STD), "fc1_b": ((L, FF), 0.0, STD),
             "fc2_w": ((L, FF, H), 0.0, out_std),
             "fc2_b": ((L, H), 0.0, STD)}
    table = [(("wte",), (V, H), 0.0, STD), (("wpe",), (S, H), 0.0, STD)]
    table += [(("blocks", k), *block[k]) for k in BLOCK_LEAVES]
    table += [(("lnf_g",), (H,), 1.0, STD), (("lnf_b",), (H,), 0.0, STD),
              (("head_w",), (H, V), 0.0, STD)]
    return table


def seed_key(seed):
    """A jax key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def make_leaf(key, index, mean, std, *, shape, dtype):
    draw = jax.random.normal(jax.random.fold_in(key, index), shape,
                             jnp.float32)
    return (mean + std * draw).astype(dtype)


def _set(tree, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def make_params(widths, seed, dtype=jnp.bfloat16):
    """The whole tree, every leaf through the one compiled `make_leaf` of
    its shape (dispatched without waiting), so that a leaf made again
    alone is the same bits: a tree fused into one program is not."""
    key, tree = seed_key(seed), {}
    for i, (path, shape, mean, std) in enumerate(leaf_table(widths)):
        _set(tree, path, make_leaf(key, i, mean, std, shape=shape,
                                   dtype=jnp.dtype(dtype)))
    return tree


def leaf_name(path):
    return ".".join(path)


def get(tree, path):
    for k in path:
        tree = tree[k]
    return tree
