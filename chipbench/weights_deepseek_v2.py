"""Seeded weights of the DeepSeek-V2 tree: made on the device, one compiled
call a leaf (`weights.make_leaf`'s way: a leaf made again alone is the same
bits), in the type the configuration stores them in.

The tree has the layout the program's `models.deepseek_v2` reads
(`prologue`: the leading dense layers, leaves `[first_k_dense, ...]`;
`blocks` and `experts` one dict each, leaves `[layers, 1, ...]` and
`[layers, held, ...]`), but is made here, so the reference makes the same
values again from the seed. Matrices are N(0, 0.02), every RMS gain
1 + N(0, 0.02), so that a check feels them. With these the router's
logits have a standard deviation of 0.02 x sqrt(hidden) (1.4 at 5120): its
soft-max over 160 is far from flat and `router_w` is not scaled. The
configuration file lists this under `assumed.weights`. Only the experts the
configuration holds are made (`experts_held`), and only its rows of the
vocabulary.
"""

import jax.numpy as jnp

from chipbench.weights import STD, make_leaf, seed_key

ATTENTION_LEAVES = ("ln1_g", "dq_w", "q_norm_g", "uq_w", "dkv_w",
                    "kv_norm_g", "uk_w", "uv_w", "o_w", "ln2_g")
DENSE_LEAVES = ("gate_w", "up_w", "down_w")
MOE_LEAVES = ("router_w", "shared_gate_w", "shared_up_w", "shared_down_w")
EXPERT_LEAVES = ("gate_w", "up_w", "down_w")


def leaf_table(widths):
    """[(path, shape, mean)] in a fixed order."""
    w = widths
    H, V, P = w["hidden_size"], w["vocab_size"], w["first_k_dense"]
    L = w["num_layers"] - P
    hd, C, rope = w["num_heads"], w["kv_lora_rank"], w["qk_rope_head_dim"]
    nope, v, rq = w["qk_nope_head_dim"], w["v_head_dim"], w["q_lora_rank"]
    FF, Fs, F, E = (w["intermediate_size"], w["shared_ffn"], w["moe_ffn"],
                    w["num_experts"])
    held = w["experts_held"][1] - w["experts_held"][0]
    attention = {"ln1_g": ((H,), 1.0), "dq_w": ((H, rq), 0.0),
                 "q_norm_g": ((rq,), 1.0),
                 "uq_w": ((rq, hd * (nope + rope)), 0.0),
                 "dkv_w": ((H, C + rope), 0.0), "kv_norm_g": ((C,), 1.0),
                 "uk_w": ((hd, nope, C), 0.0), "uv_w": ((hd, C, v), 0.0),
                 "o_w": ((hd * v, H), 0.0), "ln2_g": ((H,), 1.0)}
    dense = {"gate_w": (H, FF), "up_w": (H, FF), "down_w": (FF, H)}
    moe = {"router_w": (H, E), "shared_gate_w": (H, Fs),
           "shared_up_w": (H, Fs), "shared_down_w": (Fs, H)}
    table = [(("embed",), (V, H), 0.0)]
    table += [(("prologue", k), (P,) + attention[k][0], attention[k][1])
              for k in ATTENTION_LEAVES]
    table += [(("prologue", k), (P,) + dense[k], 0.0) for k in DENSE_LEAVES]
    table += [(("blocks", k), (L, 1) + attention[k][0], attention[k][1])
              for k in ATTENTION_LEAVES]
    table += [(("blocks", k), (L, 1) + moe[k], 0.0) for k in MOE_LEAVES]
    table += [(("experts", k),
               (L, held) + ((F, H) if k == "down_w" else (H, F)), 0.0)
              for k in EXPERT_LEAVES]
    table += [(("lnf_g",), (H,), 1.0), (("head_w",), (H, V), 0.0)]
    return table


def make_params(widths, seed, dtype=jnp.bfloat16):
    key, dtype = seed_key(seed), jnp.dtype(dtype)
    tree = {"prologue": {}, "blocks": ({},), "experts": ({},)}
    for i, (path, shape, mean) in enumerate(leaf_table(widths)):
        leaf = make_leaf(key, i, mean, STD, shape=shape, dtype=dtype)
        if len(path) == 1:
            tree[path[0]] = leaf
        elif path[0] == "prologue":
            tree["prologue"][path[1]] = leaf
        else:
            tree[path[0]][0][path[1]] = leaf
    return tree
