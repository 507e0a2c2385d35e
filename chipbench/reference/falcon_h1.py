"""Plain reference of the Falcon-H1 block (tiiuae, `model_type:
falcon_h1`; the equations are ISSUE 28's, from the published `config.json`
and the `falcon_h1` implementation in `transformers`): float32
`jax.numpy`, matrix products at `highest` precision, a sequential scan
over time for the recurrent state, no chunking, no kernels, no cache, and
nothing imported from the program.

Every block runs causal GQA attention (rotate-half RoPE on q and k) and a
Mamba-2 mixer side by side on one normed input, adds both to the residual
stream, then a gated feed-forward. `w` is the configuration file's
`widths` group, `m` its `multipliers`. Parameters may arrive in any
storage type; they are widened to float32 here, which is exact.

A caller that pads a sequence may pass its true length `n`: the steps of
the positions from `n` on are set to 0, so that the recurrent state handed
back is the one after token n - 1 (causality keeps the padding from every
position before it either way).

Departures from the published implementation, none of which changes the
mathematics: `mamba_use_mlp: true` is read as "the block has its
feed-forward", and `mlp_expansion_factor` as unused beside
`intermediate_size` (the configuration file lists both under `assumed`).
"""

import math

import jax
import jax.numpy as jnp

from chipbench.reference.gpt import highest

_F32 = jnp.float32


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(_F32)


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta):
    """Rotate-half RoPE at positions 0..S-1; x: [S, heads, D]."""
    S, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=_F32) / D)
    ang = jnp.arange(S, dtype=_F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + rot * sin


def attention(q, k, v):
    """Causal softmax attention; q: [S, hq, D], k, v: [S, hkv, D], query
    head h reading KV head h // (hq // hkv)."""
    S, hq, D = q.shape
    g = hq // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)


def causal_conv(x, w, b):
    """Depthwise causal conv over time; x: [S, C], w: [K, C] with w[K-1]
    on the token itself, zeros before the sequence."""
    K = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), _F32), x], 0)
    return b + sum(w[j] * padded[j:j + x.shape[0]] for j in range(K))


def selective_scan(xs, B, C, dt, A, state=None):
    """S_t = exp(dt_t A) S_{t-1} + dt_t xs_t (outer) B_t; y_t = S_t C_t,
    one step of time after another. xs: [S, heads, P]; B, C: [S, G, N];
    dt: [S, heads]; A: [heads]; head h reads group h // (heads // G).
    Returns (y [S, heads, P], the state after the last step)."""
    heads, G = xs.shape[1], B.shape[1]
    B, C = (jnp.repeat(a, heads // G, axis=1) for a in (B, C))
    if state is None:
        state = jnp.zeros((heads, xs.shape[2], B.shape[2]), _F32)

    def step(S, t):
        x_t, B_t, C_t, dt_t = t
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    state, y = jax.lax.scan(step, state, (xs, B, C, dt))
    return y, state


@highest
def block(p, x, w, m, n=None):
    """One layer on its own (unstacked) parameters; x: [S, H] float32.
    Returns (x, the recurrent state [heads, P, N] after token n - 1, or
    after the last when n is None)."""
    p = {k: v.astype(_F32) for k, v in p.items()}
    S = x.shape[0]
    eps = w["rms_norm_eps"]
    D, hq, hkv = w["head_dim"], w["num_heads"], w["num_kv_heads"]
    Hm, P, G, N = (w["ssm_heads"], w["ssm_head_dim"], w["ssm_groups"],
                   w["ssm_state"])
    d, gn = Hm * P, G * N
    u = rms_norm(x, p["ln1_g"], eps)
    # attention
    ua = u * m["attention_in_multiplier"]
    q = rope((ua @ p["q_w"]).reshape(S, hq, D), w["rope_theta"])
    k = rope((ua @ p["k_w"] * m["key_multiplier"]).reshape(S, hkv, D),
             w["rope_theta"])
    v = (ua @ p["v_w"]).reshape(S, hkv, D)
    a = attention(q, k, v).reshape(S, hq * D) @ p["o_w"] \
        * m["attention_out_multiplier"]
    # Mamba-2 mixer
    mz, mx, mb, mc, mdt = m["ssm_multipliers"]
    mup = jnp.concatenate([jnp.full((n,), s, _F32) for n, s in (
        (d, mz), (d, mx), (gn, mb), (gn, mc), (Hm, mdt))])
    zxbcdt = (u * m["ssm_in_multiplier"]) @ p["ssm_in_w"] * mup
    z, xbc, dt = zxbcdt[:, :d], zxbcdt[:, d:2 * d + 2 * gn], \
        zxbcdt[:, 2 * d + 2 * gn:]
    xbc = silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs = xbc[:, :d].reshape(S, Hm, P)
    Bm = xbc[:, d:d + gn].reshape(S, G, N)
    Cm = xbc[:, d + gn:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    if n is not None:
        dt = jnp.where(jnp.arange(S)[:, None] < n, dt, 0.0)
    y, state = selective_scan(xs, Bm, Cm, dt, -jnp.exp(p["A_log"]))
    y = (y + p["D"][None, :, None] * xs).reshape(S, d)
    y = (y * silu(z)).reshape(S, G, d // G)
    y = (y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
         ).reshape(S, d) * p["ssm_norm_g"]
    x = x + a + y @ p["ssm_out_w"] * m["ssm_out_multiplier"]
    # feed-forward
    f = rms_norm(x, p["ln2_g"], eps)
    gate, scale = m["mlp_multipliers"]
    return x + ((f @ p["up_w"]) * silu(f @ p["gate_w"] * gate)) \
        @ p["down_w"] * scale, state


def embed(p, tokens, m):
    return jnp.take(p["embed"], tokens, axis=0).astype(_F32) \
        * m["embedding_multiplier"]


def hidden(params, tokens, w, m, n=None):
    """(The final-normed stream [S, H] of one sequence, every layer's
    recurrent state [L, heads, P, N] after token n - 1), layer by layer."""
    def body(x, p):
        return block(p, x, w, m, n)
    x, states = jax.lax.scan(body, embed(params, tokens, m),
                             params["blocks"])
    return rms_norm(x, params["lnf_g"], w["rms_norm_eps"]), states


@highest
def head_logits(params, x, m, cols=None):
    """Logits of `cols` (a slice of the vocabulary) or of all of it."""
    head = params["head_w"] if cols is None else params["head_w"][:, cols]
    return x @ head.astype(_F32) * m["lm_head_multiplier"]


def forward(params, tokens, w, m):
    """Logits [S, V] of one sequence (toy sizes: the whole head at once)."""
    return head_logits(params, hidden(params, tokens, w, m)[0], m)


def best_and_picked(params, x, picked, m, blocks=8):
    """For each position of x: [S, H], the largest logit and the logit of
    `picked` [S], with the head widened to float32 a block of the
    vocabulary at a time (whole, it is the largest array of the model)."""
    V = params["head_w"].shape[1]
    size = -(-V // blocks)
    best = jnp.full((x.shape[0],), -jnp.inf, _F32)
    mine = jnp.zeros((x.shape[0],), _F32)
    for lo in range(0, V, size):
        hi = min(lo + size, V)
        logits = head_logits(params, x, m, slice(lo, hi))
        best = jnp.maximum(best, logits.max(-1))
        inside = (picked >= lo) & (picked < hi)
        at = jnp.take_along_axis(
            logits, jnp.clip(picked - lo, 0, hi - lo - 1)[:, None], -1)[:, 0]
        mine = jnp.where(inside, at, mine)
    return best, mine
