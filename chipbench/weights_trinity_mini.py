"""Seeded weights of the Trinity-Mini tree: made on the device, one compiled
call a leaf (`weights.make_leaf`'s way: a leaf made again alone is the same
bits), in the type the configuration stores them in.

The tree has the layout the program's `models.trinity_mini` reads
(`prologue`: the leading dense window layers, leaves `[dense, ...]`;
`blocks` and `experts` one dict a run of the pattern, 0 the window layers,
1 the full layer, leaves `[periods, run, ...]` and
`[periods * run, held, ...]`), but is made here, so the reference makes the
same values again from the seed. Matrices are N(0, 0.02), every RMS gain
1 + N(0, 0.02), as `weights_qwen3_next` makes them, and `expert_bias` is
N(0, 0.01) in float32, so that the router's CHOICE feels it: the router's
logits have a standard deviation of 0.02 x sqrt(2048) = 0.9, its sigmoid
scores near the eighth pick lie ~0.01 apart. The configuration file lists
this under `assumed`.
"""

import jax.numpy as jnp

from chipbench.weights import STD, make_leaf, seed_key

BIAS_STD = 0.01
ATTENTION_LEAVES = ("ln1_g", "post_attn_g", "q_w", "g_w", "k_w", "v_w",
                    "q_norm", "k_norm", "o_w", "ln2_g", "post_mlp_g")
DENSE_LEAVES = ("gate_w", "up_w", "down_w")
MOE_LEAVES = ("router_w", "expert_bias", "shared_gate_w", "shared_up_w",
              "shared_down_w")
EXPERT_LEAVES = ("gate_w", "up_w", "down_w")


def leaf_table(widths):
    """[(path, shape, mean, std, float32?)] in a fixed order. A `blocks` or
    `experts` path's second entry is the run: 0 window, 1 full."""
    w = widths
    H, V, nd = w["hidden_size"], w["vocab_size"], w["num_dense_layers"]
    g = w["global_attn_every"]
    P = (w["num_layers"] - nd) // g
    hq, hkv, D = w["num_heads"], w["num_kv_heads"], w["head_dim"]
    FF, Fs, F, E = (w["intermediate_size"], w["shared_ffn"], w["moe_ffn"],
                    w["num_experts"])
    held = w["experts_held"][1] - w["experts_held"][0]
    attention = {"ln1_g": ((H,), 1.0), "post_attn_g": ((H,), 1.0),
                 "q_w": ((H, hq * D), 0.0), "g_w": ((H, hq * D), 0.0),
                 "k_w": ((H, hkv * D), 0.0), "v_w": ((H, hkv * D), 0.0),
                 "q_norm": ((D,), 1.0), "k_norm": ((D,), 1.0),
                 "o_w": ((hq * D, H), 0.0), "ln2_g": ((H,), 1.0),
                 "post_mlp_g": ((H,), 1.0)}
    dense = {"gate_w": (H, FF), "up_w": (H, FF), "down_w": (FF, H)}
    moe = {"router_w": (H, E), "expert_bias": (E,), "shared_gate_w": (H, Fs),
           "shared_up_w": (H, Fs), "shared_down_w": (Fs, H)}
    table = [(("embed",), (V, H), 0.0, STD, False)]
    table += [(("prologue", k), (nd,) + attention[k][0], attention[k][1],
               STD, False) for k in ATTENTION_LEAVES]
    table += [(("prologue", k), (nd,) + dense[k], 0.0, STD, False)
              for k in DENSE_LEAVES]
    for r, count in ((0, g - 1), (1, 1)):
        table += [(("blocks", r, k), (P, count) + attention[k][0],
                   attention[k][1], STD, False) for k in ATTENTION_LEAVES]
        table += [(("blocks", r, k), (P, count) + moe[k], 0.0,
                   BIAS_STD if k == "expert_bias" else STD,
                   k == "expert_bias") for k in MOE_LEAVES]
    for r, count in ((0, g - 1), (1, 1)):
        table += [(("experts", r, k),
                   (P * count, held) + ((F, H) if k == "down_w" else (H, F)),
                   0.0, STD, False) for k in EXPERT_LEAVES]
    table += [(("lnf_g",), (H,), 1.0, STD, False),
              (("head_w",), (H, V), 0.0, STD, False)]
    return table


def make_params(widths, seed, dtype=jnp.bfloat16):
    key, dtype = seed_key(seed), jnp.dtype(dtype)
    tree = {"prologue": {}, "blocks": ({}, {}), "experts": ({}, {})}
    for i, (path, shape, mean, std, f32) in enumerate(leaf_table(widths)):
        leaf = make_leaf(key, i, mean, std, shape=shape,
                         dtype=jnp.dtype(jnp.float32) if f32 else dtype)
        if len(path) == 1:
            tree[path[0]] = leaf
        elif path[0] == "prologue":
            tree["prologue"][path[1]] = leaf
        else:
            tree[path[0]][path[1]][path[2]] = leaf
    return tree
