"""Plain reference of Trinity-Mini (Arcee, `model_type: afmoe`; the
equations are ISSUE 54's, from the published `config.json` and, where its
keys do not spell a thing out, the family's modelling code: the
configuration file lists those under `assumed`): float32 `jax.numpy`,
matrix products at `highest` precision, attention by an explicit mask, no
kernels, no paged cache, no batching, and nothing imported from the
program.

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g
    h0 = E[tokens] * sqrt(hidden)                            (mup_enabled)
    a  = h + RMS(Attn(RMS(h; ln1)); post_attn);  h' = a + RMS(FFN(RMS(a; ln2)); post_mlp)
    Attn(u): q = u Wq (hq heads of D), k = u Wk, v = u Wv (hkv heads),
      gate = u Wg; q, k RMS-normed per head; a WINDOW layer rotates q, k
      (rotate-half over the whole head, theta) and masks i - window < j <= i;
      a FULL layer has no position encoding and masks j <= i;
      softmax(q k^T / sqrt(D)) v, hq / hkv query heads a KV head;
      out = (attn * sigmoid(gate)) Wo
    FFN, dense layers [0, num_dense_layers): (silu(f Wgate) * (f Wup)) Wdown
    FFN, expert layers: s = sigmoid(f Wr); picks = top-k of s + b;
      w = s[picks] / (sum s[picks] + 1e-20) * route_scale;
      y = shared(f) + sum_i w_i expert_{p_i}(f)
    logits = RMS(h_L; lnf) Whead

Departures from a one-line transcription, none of which changes a value
beyond the order of float32 sums: a sequence is worked in BLOCKS of
positions (a 33k-token prompt's [heads, S, S] scores are 0.14 TB), the
soft-max over key blocks is the running (online) form (a window layer
starts at the first key block its block of queries can see), and the
experts are a scan over the held ones with masks. `attention_dense` is the
one-line form, for the tests that hold the blocked one to it.

The tree is the program's (`models/trinity_mini.py`'s docstring), made by
`chipbench/weights_trinity_mini.py`. `w` is the configuration file's
`widths` group.

`routing` ([S, L_routed, k] expert numbers, or None) replaces the
reference's own picks where given (an entry < 0: "pick yourself"); the
weights are still its own float32 scores at those ids. `own` and `margin`
of the result are the reference's own picks and how clear they were:
(c_k - c_(k+1)) / c_k over the choice values c = s + b.
"""

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.gpt import highest

_F32 = jnp.float32


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def silu(x):
    return x * jax.nn.sigmoid(x)


def gated_ffn(f, gate_w, up_w, down_w):
    return (silu(f @ gate_w) * (f @ up_w)) @ down_w


def rope(x, pos, theta):
    """Rotate-half over the whole last dim at positions `pos` [S];
    x: [S, heads, D]."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=_F32) / D)
    ang = pos.astype(_F32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + turned * sin


def attention_dense(q, k, v, window=None):
    """The one-line form: q: [S, hq, D]; k, v: [S, hkv, D] -> [S, hq, D];
    `window`: query i sees keys j with i - window < j <= i."""
    S, hq, D = q.shape
    g = hq // k.shape[1]
    s = jnp.einsum("qhgd,khd->hgqk", q.reshape(S, -1, g, D), k) * D ** -0.5
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    ok = j <= i
    if window is not None:
        ok &= j > i - window
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1)
    return jnp.einsum("hgqk,khd->qhgd", p, v).reshape(S, hq, D)


def attention_blocked(q, k, v, first, block, window=None):
    """One block of queries (positions first ..) against the key blocks it
    can see: the running soft-max. q: [block, hq, D]; k, v: [S, hkv, D];
    `first` traced."""
    B, hq, D = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qpos = first + jnp.arange(B)
    qg = q.reshape(B, hkv, g, D)

    def step(j, carry):
        m, l, acc = carry
        kk = jax.lax.dynamic_slice_in_dim(k, j * B, B)
        vv = jax.lax.dynamic_slice_in_dim(v, j * B, B)
        s = jnp.einsum("qhgd,khd->hgqk", qg, kk) * D ** -0.5
        kpos = (j * B + jnp.arange(B))[None, :]
        ok = kpos <= qpos[:, None]
        if window is not None:
            ok &= kpos > qpos[:, None] - window
        s = jnp.where(ok, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        # a key block wholly outside a query's window leaves its row at
        # -inf: nothing to rescale yet
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
        p = jnp.exp(s - safe[..., None])
        return (m_new, l * alpha + p.sum(-1),
                acc * alpha[..., None] + jnp.einsum("hgqk,khd->hgqd", p, vv))

    lo = 0 if window is None else jnp.maximum(first - (window - 1), 0) // B
    m, l, acc = jax.lax.fori_loop(
        lo, first // B + 1, step,
        (jnp.full((hkv, g, B), -jnp.inf, _F32), jnp.zeros((hkv, g, B), _F32),
         jnp.zeros((hkv, g, B, D), _F32)))
    return (acc / l[..., None]).transpose(2, 0, 1, 3).reshape(B, hq, D)


def route(scores, bias, k):
    """(own picks [S, k], margin [S]) of the top-k of scores + bias."""
    top, own = jax.lax.top_k(scores + bias, k + 1)
    return own[:, :k], (top[:, k - 1] - top[:, k]) / top[:, k - 1]


def expert_layer(p, e, f, w, routing=None, router_dtype=_F32):
    """The expert layer on f: [S, H]. Returns (y, own picks, margin)."""
    lo, hi = w["experts_held"]
    scores = jax.nn.sigmoid(
        (f.astype(router_dtype) @ p["router_w"].astype(router_dtype)
         ).astype(_F32))
    own, margin = route(scores, p["expert_bias"], w["experts_per_tok"])
    ids = own if routing is None else jnp.where(routing >= 0, routing, own)
    weights = jnp.take_along_axis(scores, ids, axis=1)
    if w.get("route_norm", True):
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    weights = weights * w["route_scale"]

    def one(y, t):
        gate_w, up_w, down_w, number = t
        mine = jnp.sum(jnp.where(ids == number, weights, 0.0), -1)  # [S]
        out = gated_ffn(f, gate_w.astype(_F32), up_w.astype(_F32),
                        down_w.astype(_F32))
        return y + mine[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(f),
                        (e["gate_w"], e["up_w"], e["down_w"],
                         jnp.arange(lo, hi)))
    y = y + gated_ffn(f, p["shared_gate_w"], p["shared_up_w"],
                      p["shared_down_w"])
    return y, own, margin


@functools.partial(jax.jit, static_argnames=("block", "dense", "windowed",
                                             "rdt", "wkey"))
@highest
def layer(p, e, x, routing, n, *, block, dense, windowed, rdt, wkey):
    """One layer on its own (unstacked) leaves; x: [S, H] float32, S whole
    blocks, of which the first `n` positions are the sequence (blocks past
    it are not worked: their rows come back as they were). Returns (x, own
    picks [S, k], margin [S]) (dense layers: zeros for the picks)."""
    w = dict(wkey)
    S, H = x.shape
    B, nblocks = block, -(-n // block)
    hq, hkv, D = w["num_heads"], w["num_kv_heads"], w["head_dim"]
    eps = w["rms_norm_eps"]
    window = w["sliding_window"] if windowed else None
    p = {k: v.astype(_F32) for k, v in p.items()}

    def rows(a, i):
        return jax.lax.dynamic_slice_in_dim(a, i * B, B)

    def put(a, i, v):
        return jax.lax.dynamic_update_slice_in_dim(a, v, i * B, 0)

    # -- every position's queries, keys, values and gate ---------------------
    def project(i, carry):
        q, k, v, gate = carry
        u = rms_norm(rows(x, i), p["ln1_g"], eps)
        qi = rms_norm((u @ p["q_w"]).reshape(B, hq, D), p["q_norm"], eps)
        ki = rms_norm((u @ p["k_w"]).reshape(B, hkv, D), p["k_norm"], eps)
        if windowed:
            pos = i * B + jnp.arange(B)
            qi = rope(qi, pos, w["rope_theta"])
            ki = rope(ki, pos, w["rope_theta"])
        return (put(q, i, qi), put(k, i, ki),
                put(v, i, (u @ p["v_w"]).reshape(B, hkv, D)),
                put(gate, i, u @ p["g_w"]))

    q, k, v, gate = jax.lax.fori_loop(
        0, nblocks, project,
        (jnp.zeros((S, hq, D), _F32), jnp.zeros((S, hkv, D), _F32),
         jnp.zeros((S, hkv, D), _F32), jnp.zeros((S, hq * D), _F32)))

    kk = w["experts_per_tok"]

    def rest(i, carry):
        x, own, margin = carry
        o = attention_blocked(rows(q, i), k, v, i * B, B, window)
        o = (o.reshape(B, hq * D) * jax.nn.sigmoid(rows(gate, i))) @ p["o_w"]
        a = rows(x, i) + rms_norm(o, p["post_attn_g"], eps)
        f = rms_norm(a, p["ln2_g"], eps)
        if dense:
            y = gated_ffn(f, p["gate_w"], p["up_w"], p["down_w"])
            return (put(x, i, a + rms_norm(y, p["post_mlp_g"], eps)), own,
                    margin)
        y, o_, m_ = expert_layer(
            p, e, f, w, None if routing is None else rows(routing, i),
            jnp.dtype(rdt))
        return (put(x, i, a + rms_norm(y, p["post_mlp_g"], eps)),
                put(own, i, o_), put(margin, i, m_))

    return jax.lax.fori_loop(
        0, nblocks, rest,
        (x, jnp.zeros((S, kk), jnp.int32), jnp.zeros((S,), _F32)))


def layer_kinds(w):
    """[(prologue?, run, index in its run's stack, windowed)] in layer
    order."""
    nd, g = w["num_dense_layers"], w["global_attn_every"]
    kinds = [("prologue", None, j, True) for j in range(nd)]
    for period in range((w["num_layers"] - nd) // g):
        kinds += [("blocks", 0, (period, j), True) for j in range(g - 1)]
        kinds += [("blocks", 1, (period, 0), False)]
    return kinds


def hidden(params, tokens, w, routing=None, n=None, block=None,
           router_dtype=_F32, window=None):
    """(The final-normed stream [S, H] of one sequence; the reference's
    own picks [S, L_routed, k]; their margins [S, L_routed]). `tokens`:
    [S], S whole `block`s (default: one block of S); `n`: the sequence's
    true length (default S); `window` overrides the configuration's (the
    tests' off-by-one)."""
    S = tokens.shape[0]
    block = S if block is None else block
    assert S % block == 0, (S, block)
    n = S if n is None else n
    if window is not None:
        w = dict(w, sliding_window=window)
    wkey = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in w.items()))
    rdt = jnp.dtype(router_dtype).name
    x = jnp.take(params["embed"], tokens, axis=0).astype(_F32)
    if w.get("mup_enabled", True):
        x = x * w["hidden_size"] ** 0.5
    g = w["global_attn_every"]
    owns, margins, routed = [], [], 0
    for where, run, at, windowed in layer_kinds(w):
        if where == "prologue":
            p = {k: v[at] for k, v in params["prologue"].items()}
            x, _, _ = layer(p, None, x, None, n, block=block, dense=True,
                            windowed=True, rdt=rdt, wkey=wkey)
            continue
        period, j = at
        p = {k: v[period, j] for k, v in params["blocks"][run].items()}
        count = g - 1 if run == 0 else 1
        e = {k: v[period * count + j]
             for k, v in params["experts"][run].items()}
        x, own, margin = layer(
            p, e, x, None if routing is None else routing[:, routed], n,
            block=block, dense=False, windowed=windowed, rdt=rdt, wkey=wkey)
        owns.append(own)
        margins.append(margin)
        routed += 1
    return (rms_norm(x, params["lnf_g"].astype(_F32), w["rms_norm_eps"]),
            jnp.stack(owns, 1), jnp.stack(margins, 1))


@highest
def head_logits(params, x, cols=None):
    """Logits of `cols` (a slice of the vocabulary) or of all of it."""
    head = params["head_w"] if cols is None else params["head_w"][:, cols]
    return x @ head.astype(_F32)


def forward(params, tokens, w, routing=None, block=None, window=None):
    """Logits [S, V] of one sequence (toy sizes: the whole head at once)."""
    return head_logits(params, hidden(params, tokens, w, routing,
                                      block=block, window=window)[0])


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
