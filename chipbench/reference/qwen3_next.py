"""Plain reference of Qwen3-Next (Qwen, `model_type: qwen3_next`; the
equations are ISSUE 36's, from the published `config.json` and the
`qwen3_next` implementation in `transformers`): float32 `jax.numpy`,
matrix products at `highest` precision, the gated delta rule one token
after another (NOT the chunked form), full causal attention, the experts
as a loop over the held ones with masks, no kernels, no cache, no
batching, and nothing imported from the program.

Layer i (0-based) is gated softmax attention iff (i + 1) % interval == 0,
else Gated DeltaNet; every layer ends in the expert layer. `w` is the
configuration file's `widths` group. The tree is the program's: `blocks`
and `experts` one dict a run of the pattern (linear, attention), leaves
`[periods, run, ...]` and `[periods * run, held, ...]`. Parameters may
arrive in any storage type; they are widened to float32 here, which is
exact.

The share: `w["experts_held"] = [lo, hi)`. The router keeps its width,
its k picks and the renormalisation over all k; the layer adds the
weighted outputs of the picks it holds and the whole shared expert.

`routing` ([S, L, k] expert numbers, or None) replaces the reference's own
top-k pick where it is given (an entry < 0 means "pick yourself"); the
WEIGHTS are still its own float32 probabilities, renormalised over those
ids. With seeded weights the k-th and (k+1)-th probabilities lie a few
percent apart and the bfloat16 stream's noise flips one pick in some 5%
of (position, layer) pairs, each flip moving the logits a hundred times
further than a lower precision does: so the comparison of logits follows
the routing the timed path reported, and the picks are compared on their
own (`own` and `margin` of the result).

A caller that pads a sequence may pass its true length `n`: positions
from `n` on move no state.

Left out, as in the program: the multi-token-prediction module.
"""

import jax
import jax.numpy as jnp

from chipbench.reference.gpt import highest

_F32 = jnp.float32


def rms_norm(x, g, eps):
    """Zero-centred gain: the stored g starts at 0."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta, rot):
    """Rotate-half RoPE at positions 0..S-1 on dims [0, rot) of each head;
    x: [S, heads, D]."""
    S = x.shape[0]
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=_F32) / rot)
    ang = jnp.arange(S, dtype=_F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    xr = x[..., :rot]
    turned = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([xr * cos + turned * sin, x[..., rot:]], -1)


def attention(q, k, v):
    """Causal softmax attention; q: [S, hq, D], k, v: [S, hkv, D]."""
    S, hq, D = q.shape
    g = hq // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * D ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)


def causal_conv(x, w):
    """Depthwise causal conv over time, no bias; x: [S, C], w: [K, C] with
    w[K-1] on the token itself, zeros before the sequence."""
    K = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), _F32), x], 0)
    return sum(w[j] * padded[j:j + x.shape[0]] for j in range(K))


def delta_rule(q, k, v, g, beta):
    """One token after another, per head: S = S exp(g_t);
    d = beta_t (v_t - S^T k_t); S = S + outer(k_t, d); o_t = S^T q_t.
    q, k: [S, H, dk]; v: [S, H, dv]; g, beta: [S, H]. Returns
    (o [S, H, dv], the state [H, dk, dv] after the last token)."""
    state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), _F32)

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def gated_ffn(f, gate_w, up_w, down_w):
    return (silu(f @ gate_w) * (f @ up_w)) @ down_w


def experts(p, e, f, w, routing=None, router_dtype=_F32):
    """The expert layer on f: [S, H]; p: the layer's block leaves, e: its
    held experts' (gate_w, up_w, down_w). Returns (y, own picks [S, k],
    margin [S] = (p_k - p_(k+1)) / p_k of the reference's own router)."""
    k = w["experts_per_tok"]
    lo, hi = w["experts_held"]
    probs = jax.nn.softmax(
        (f.astype(router_dtype) @ p["router_w"].astype(router_dtype)), -1
    ).astype(_F32)
    top, own = jax.lax.top_k(probs, k + 1)
    margin = (top[:, k - 1] - top[:, k]) / top[:, k - 1]
    own = own[:, :k]
    ids = own if routing is None else jnp.where(routing >= 0, routing, own)
    weights = jnp.take_along_axis(probs, ids, axis=1)
    weights = weights / jnp.sum(weights, -1, keepdims=True)

    def one(y, t):
        gate_w, up_w, down_w, number = t
        mine = jnp.sum(jnp.where(ids == number, weights, 0.0), -1)  # [S]
        out = gated_ffn(f, gate_w.astype(_F32), up_w.astype(_F32),
                        down_w.astype(_F32))
        return y + mine[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(f),
                        (e["gate_w"], e["up_w"], e["down_w"],
                         jnp.arange(lo, hi)))
    shared = gated_ffn(f, p["shared_gate_w"], p["shared_up_w"],
                       p["shared_down_w"])
    return y + jax.nn.sigmoid(f @ p["shared_sg_w"][:, None]) * shared, \
        own, margin


def linear_mixer(p, u, w, n=None):
    """A Gated DeltaNet layer's mixer on the normed u: [S, H]. Returns
    (out [S, H], the state [Hv, dk, dv] after token n - 1)."""
    S = u.shape[0]
    Hk, Hv = w["linear_key_heads"], w["linear_value_heads"]
    dk, dv = w["linear_key_dim"], w["linear_value_dim"]
    rep = Hv // Hk
    qkvz = (u @ p["in_qkvz_w"]).reshape(S, Hk, 2 * dk + 2 * rep * dv)
    ba = (u @ p["in_ba_w"]).reshape(S, Hk, 2 * rep)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + rep * dv]
    z = qkvz[..., 2 * dk + rep * dv:].reshape(S, Hv, dv)
    b, a = ba[..., :rep].reshape(S, Hv), ba[..., rep:].reshape(S, Hv)
    mixed = silu(causal_conv(jnp.concatenate(
        [q.reshape(S, Hk * dk), k.reshape(S, Hk * dk),
         v.reshape(S, Hv * dv)], -1), p["conv_w"]))
    q = mixed[:, :Hk * dk].reshape(S, Hk, dk)
    k = mixed[:, Hk * dk:2 * Hk * dk].reshape(S, Hk, dk)
    v = mixed[:, 2 * Hk * dk:].reshape(S, Hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    if n is not None:
        live = jnp.arange(S)[:, None] < n
        g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q), rep, axis=1) * dk ** -0.5
    k = jnp.repeat(l2(k), rep, axis=1)
    o, state = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + w["rms_norm_eps"])
    y = (o * p["norm_w"] * silu(z)).reshape(S, Hv * dv)
    return y @ p["out_w"], state


def attention_mixer(p, u, w):
    S = u.shape[0]
    hq, hkv, D = w["num_heads"], w["num_kv_heads"], w["head_dim"]
    rot = int(D * w["partial_rotary_factor"])
    eps = w["rms_norm_eps"]
    qg = (u @ p["q_w"]).reshape(S, hq, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = (u @ p["k_w"]).reshape(S, hkv, D)
    v = (u @ p["v_w"]).reshape(S, hkv, D)
    q = rope(rms_norm(q, p["q_norm"], eps), w["rope_theta"], rot)
    k = rope(rms_norm(k, p["k_norm"], eps), w["rope_theta"], rot)
    a = attention(q, k, v) * jax.nn.sigmoid(gate)
    return a.reshape(S, hq * D) @ p["o_w"]


@highest
def layer(kind, p, e, x, w, n=None, routing=None, router_dtype=_F32):
    """One layer on its own (unstacked) leaves; x: [S, H] float32.
    Returns (x, state or None, own picks, margin)."""
    p = {k: v.astype(_F32) for k, v in p.items()}
    u = rms_norm(x, p["ln1_g"], w["rms_norm_eps"])
    state = None
    if kind == "linear":
        mixed, state = linear_mixer(p, u, w, n)
    else:
        mixed = attention_mixer(p, u, w)
    x = x + mixed
    f = rms_norm(x, p["ln2_g"], w["rms_norm_eps"])
    y, own, margin = experts(p, e, f, w, routing, router_dtype)
    return x + y, state, own, margin


def hidden(params, tokens, w, n=None, routing=None, router_dtype=_F32):
    """(The final-normed stream [S, H] of one sequence; every linear
    layer's state [L_lin, Hv, dk, dv] after token n - 1; the reference's
    own picks [S, L, k]; their margins [S, L]), layer by layer."""
    interval = w["full_attention_interval"]
    runs = (("linear", interval - 1), ("attention", 1))
    x = jnp.take(params["embed"], tokens, axis=0).astype(_F32)
    states, owns, margins, number = [], [], [], 0
    for period in range(w["num_layers"] // interval):
        for r, (kind, count) in enumerate(runs):
            for j in range(count):
                p = {k: v[period, j] for k, v in params["blocks"][r].items()}
                e = {k: v[period * count + j]
                     for k, v in params["experts"][r].items()}
                x, state, own, margin = layer(
                    kind, p, e, x, w, n,
                    None if routing is None else routing[:, number],
                    router_dtype)
                if state is not None:
                    states.append(state)
                owns.append(own)
                margins.append(margin)
                number += 1
    return (rms_norm(x, params["lnf_g"].astype(_F32), w["rms_norm_eps"]),
            jnp.stack(states), jnp.stack(owns, 1), jnp.stack(margins, 1))


@highest
def head_logits(params, x, cols=None):
    """Logits of `cols` (a slice of the vocabulary) or of all of it."""
    head = params["head_w"] if cols is None else params["head_w"][:, cols]
    return x @ head.astype(_F32)


def forward(params, tokens, w, routing=None):
    """Logits [S, V] of one sequence (toy sizes: the whole head at once)."""
    return head_logits(params, hidden(params, tokens, w, None, routing)[0])


def best_and_picked(params, x, picked, blocks=8):
    """For each position of x: [S, H], the largest logit and the logit of
    `picked` [S], the head widened to float32 a block of the vocabulary
    at a time."""
    V = params["head_w"].shape[1]
    size = -(-V // blocks)
    best = jnp.full((x.shape[0],), -jnp.inf, _F32)
    mine = jnp.zeros((x.shape[0],), _F32)
    for lo in range(0, V, size):
        hi = min(lo + size, V)
        logits = head_logits(params, x, slice(lo, hi))
        best = jnp.maximum(best, logits.max(-1))
        inside = (picked >= lo) & (picked < hi)
        at = jnp.take_along_axis(
            logits, jnp.clip(picked - lo, 0, hi - lo - 1)[:, None], -1)[:, 0]
        mine = jnp.where(inside, at, mine)
    return best, mine
