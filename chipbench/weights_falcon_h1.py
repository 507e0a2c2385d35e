"""Seeded weights of the Falcon-H1 tree: made on the device, one compiled
call a leaf (`weights.make_leaf`'s way: a leaf made again alone is the same
bits), in the type the configuration stores them in.

The tree has the layout the program's `models.falcon_h1` reads, but is
made here, so the reference makes the same values again from the seed.
Everything is N(0, 0.02) except what the Mamba-2 reference initialiser
draws otherwise: `A_log` = log U[1, 16], `dt_bias` the inverse softplus of
a log-uniform step in [1e-3, 0.1], `D` = 1 + N(0, 0.02), and the conv's
taps and bias U(-1/sqrt(K), 1/sqrt(K)) (torch's Conv1d default, which
that initialiser keeps: under N(0, 0.02) taps the conv shrinks x, B and C
25-fold and the state's term falls to 5e-4 of the skip term D x, so that
no check of the logits could feel the state at all). Norm gains are
1 + N(0, 0.02) so that a check feels them. The configuration file lists
all of this under `assumed.weights`.
"""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.weights import STD, _set, make_leaf, seed_key

BLOCK_LEAVES = ("ln1_g", "q_w", "k_w", "v_w", "o_w", "ssm_in_w", "conv_w",
                "conv_b", "dt_bias", "A_log", "D", "ssm_norm_g", "ssm_out_w",
                "ln2_g", "gate_w", "up_w", "down_w")
DT_RANGE = (1e-3, 0.1)
A_RANGE = (1.0, 16.0)


def leaf_table(widths):
    """[(path, shape, law)] in a fixed order; law is ("normal", mean, std),
    ("conv_uniform", bound), ("log_uniform_A",) or ("inv_softplus_dt",)."""
    w = widths
    H, L, FF, V = (w["hidden_size"], w["num_layers"], w["ffn_hidden"],
                   w["vocab_size"])
    D, hq, hkv = w["head_dim"], w["num_heads"], w["num_kv_heads"]
    Hm, d = w["ssm_heads"], w["ssm_heads"] * w["ssm_head_dim"]
    gn = w["ssm_groups"] * w["ssm_state"]
    K, conv = w["ssm_conv"], d + 2 * gn

    def n(mean=0.0):
        return ("normal", mean, STD)

    block = {"ln1_g": ((L, H), n(1.0)), "q_w": ((L, H, hq * D), n()),
             "k_w": ((L, H, hkv * D), n()), "v_w": ((L, H, hkv * D), n()),
             "o_w": ((L, hq * D, H), n()),
             "ssm_in_w": ((L, H, 2 * d + 2 * gn + Hm), n()),
             "conv_w": ((L, K, conv), ("conv_uniform", K ** -0.5)),
             "conv_b": ((L, conv), ("conv_uniform", K ** -0.5)),
             "dt_bias": ((L, Hm), ("inv_softplus_dt",)),
             "A_log": ((L, Hm), ("log_uniform_A",)),
             "D": ((L, Hm), n(1.0)), "ssm_norm_g": ((L, d), n(1.0)),
             "ssm_out_w": ((L, d, H), n()), "ln2_g": ((L, H), n(1.0)),
             "gate_w": ((L, H, FF), n()), "up_w": ((L, H, FF), n()),
             "down_w": ((L, FF, H), n())}
    table = [(("embed",), (V, H), n())]
    table += [(("blocks", k), *block[k]) for k in BLOCK_LEAVES]
    table += [(("lnf_g",), (H,), n(1.0)), (("head_w",), (H, V), n())]
    return table


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "law"))
def _make_uniform_leaf(key, index, *, shape, dtype, law):
    u = jax.random.uniform(jax.random.fold_in(key, index), shape,
                           jnp.float32)
    if law[0] == "conv_uniform":
        return ((2.0 * u - 1.0) * law[1]).astype(dtype)
    if law[0] == "log_uniform_A":   # A = -exp(A_log), A_log = log U[1, 16]
        return jnp.log(A_RANGE[0] + u * (A_RANGE[1] - A_RANGE[0])
                       ).astype(dtype)
    lo, hi = (math.log(v) for v in DT_RANGE)
    step = jnp.exp(lo + u * (hi - lo))
    return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)


def make_params(widths, seed, dtype=jnp.bfloat16):
    key, tree = seed_key(seed), {}
    dtype = jnp.dtype(dtype)
    for i, (path, shape, law) in enumerate(leaf_table(widths)):
        if law[0] == "normal":
            leaf = make_leaf(key, i, law[1], law[2], shape=shape,
                             dtype=dtype)
        else:
            leaf = _make_uniform_leaf(key, i, shape=shape, dtype=dtype,
                                      law=law)
        _set(tree, path, leaf)
    return tree
