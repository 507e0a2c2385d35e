"""Seeded weights of the Qwen3-Next tree: made on the device, one compiled
call a leaf (`weights.make_leaf`'s way: a leaf made again alone is the same
bits), in the type the configuration stores them in.

The tree has the layout the program's `models.qwen3_next` reads (`blocks`
and `experts` one dict a run of the layer pattern: linear, attention), but
is made here, so the reference makes the same values again from the seed.
Matrices are N(0, 0.02); the zero-centred RMS gains N(0, 0.02) and the
delta rule's plain output gain `norm_w` 1 + N(0, 0.02), so that a check
feels them; and so that the recurrent state is felt (PERF.md, PR 28:
under small taps the conv shrinks q, k and v and no check can feel the
state): conv taps U(-1/2, 1/2), `A_log` = log U[1, 16], `dt_bias` the
inverse softplus of a log-uniform step in [1e-3, 0.1]. The configuration
file lists all of this under `assumed.weights`. Only the experts the
configuration holds are made (`experts_held`), and only its rows of the
vocabulary.
"""

import jax.numpy as jnp

from chipbench.weights import STD, make_leaf, seed_key
from chipbench.weights_falcon_h1 import _make_uniform_leaf

LINEAR_LEAVES = ("ln1_g", "in_qkvz_w", "in_ba_w", "conv_w", "A_log",
                 "dt_bias", "norm_w", "out_w")
ATTENTION_LEAVES = ("ln1_g", "q_w", "k_w", "v_w", "q_norm", "k_norm", "o_w")
MOE_LEAVES = ("ln2_g", "router_w", "shared_gate_w", "shared_up_w",
              "shared_down_w", "shared_sg_w")
EXPERT_LEAVES = ("gate_w", "up_w", "down_w")


def leaf_table(widths):
    """[(path, shape, law)] in a fixed order; law is ("normal", mean, std),
    ("conv_uniform", bound), ("log_uniform_A",) or ("inv_softplus_dt",).
    A path's second entry is the run: 0 the linear layers, 1 attention."""
    w = widths
    H, V = w["hidden_size"], w["vocab_size"]
    interval = w["full_attention_interval"]
    P = w["num_layers"] // interval
    hq, hkv, D = w["num_heads"], w["num_kv_heads"], w["head_dim"]
    Hk, Hv = w["linear_key_heads"], w["linear_value_heads"]
    kd, vd = Hk * w["linear_key_dim"], Hv * w["linear_value_dim"]
    E, F, Fs = w["num_experts"], w["moe_ffn"], w["shared_ffn"]
    held = w["experts_held"][1] - w["experts_held"][0]

    def n(mean=0.0):
        return ("normal", mean, STD)

    def moe(c):
        return {"ln2_g": ((P, c, H), n()), "router_w": ((P, c, H, E), n()),
                "shared_gate_w": ((P, c, H, Fs), n()),
                "shared_up_w": ((P, c, H, Fs), n()),
                "shared_down_w": ((P, c, Fs, H), n()),
                "shared_sg_w": ((P, c, H), n())}

    c = interval - 1
    linear = {"ln1_g": ((P, c, H), n()),
              "in_qkvz_w": ((P, c, H, 2 * kd + 2 * vd), n()),
              "in_ba_w": ((P, c, H, 2 * Hv), n()),
              "conv_w": ((P, c, w["linear_conv"], 2 * kd + vd),
                         ("conv_uniform", 0.5)),
              "A_log": ((P, c, Hv), ("log_uniform_A",)),
              "dt_bias": ((P, c, Hv), ("inv_softplus_dt",)),
              "norm_w": ((P, c, w["linear_value_dim"]), n(1.0)),
              "out_w": ((P, c, vd, H), n()), **moe(c)}
    attention = {"ln1_g": ((P, 1, H), n()),
                 "q_w": ((P, 1, H, hq * 2 * D), n()),
                 "k_w": ((P, 1, H, hkv * D), n()),
                 "v_w": ((P, 1, H, hkv * D), n()),
                 "q_norm": ((P, 1, D), n()), "k_norm": ((P, 1, D), n()),
                 "o_w": ((P, 1, hq * D, H), n()), **moe(1)}
    table = [(("embed",), (V, H), n())]
    table += [(("blocks", 0, k), *linear[k])
              for k in LINEAR_LEAVES + MOE_LEAVES]
    table += [(("blocks", 1, k), *attention[k])
              for k in ATTENTION_LEAVES + MOE_LEAVES]
    for r, count in ((0, c), (1, 1)):
        table += [(("experts", r, k),
                   (P * count, held) + ((F, H) if k == "down_w" else (H, F)),
                   n()) for k in EXPERT_LEAVES]
    table += [(("lnf_g",), (H,), n()), (("head_w",), (H, V), n())]
    return table


def make_params(widths, seed, dtype=jnp.bfloat16):
    key, dtype = seed_key(seed), jnp.dtype(dtype)
    tree = {"blocks": ({}, {}), "experts": ({}, {})}
    for i, (path, shape, law) in enumerate(leaf_table(widths)):
        if law[0] == "normal":
            leaf = make_leaf(key, i, law[1], law[2], shape=shape,
                             dtype=dtype)
        else:
            leaf = _make_uniform_leaf(key, i, shape=shape, dtype=dtype,
                                      law=law)
        if len(path) == 1:
            tree[path[0]] = leaf
        else:
            tree[path[0]][path[1]][path[2]] = leaf
    return tree
