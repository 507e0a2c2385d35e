"""Plain reference of DeepSeek-V2 (deepseek-ai, `model_type: deepseek_v2`;
the equations are ISSUE 51's, from the published `config.json` and
`modeling_deepseek.py`): float32 `jax.numpy`, matrix products at `highest`
precision, the NAIVE form of latent attention (every head's key and value
are expanded from the compressed vector; nothing is absorbed), no kernels,
no paged cache, and nothing imported from the program.

    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g
    x = x + Attn(RMS(x; ln1));   x = x + FFN(RMS(x; ln2))
    c_q = RMS(h W_DQ);  [q_nope_i | q_rope_i] = c_q W_UQ  (head i: [nope | rope])
    [c | k_r] = h W_DKV,  c = RMS(c);  q_rope_i, k_r rotated (YaRN, rotate-half
    pairing: dim j with dim j + rope/2);  k_nope_i = c W_UK_i,  v_i = c W_UV_i
    s_i = (q_nope_i . k_nope_i + q_rope_i . k_r) * scale;  causal soft-max
    out = concat_i(p_i v_i) W_O
    FFN: dense gated in layers [0, first_k_dense); else
    s = softmax(h W_r); a group's score its best expert's; the best topk_group
    groups kept; top experts_per_tok of what is left, weights
    routed_scaling_factor * s_e; + the shared gated FFN.

Departures from a one-line transcription, none of which changes a value
beyond the order of float32 sums: a sequence is worked in BLOCKS of
positions (a 33k-token document's [heads, S, S] scores are 0.5 TB), the
soft-max over key blocks is the running (online) form, heads are taken a
group at a time, and the experts are a scan over the held ones with masks.
`attention_dense` is the one-line form, for the tests that hold the
blocked one to it.

The tree is the program's (`models/deepseek_v2.py`'s docstring), made by
`chipbench/weights_deepseek_v2.py`. `w` is the configuration file's
`widths` group. The share: `w["experts_held"] = [lo, hi)`: the router keeps
its width, groups and picks; the layer adds the picks it holds and the
whole shared expert.

`routing` ([S, L_routed, k] expert numbers, or None) replaces the
reference's own picks where given (an entry < 0: "pick yourself"); the
weights are still its own float32 probabilities at those ids. `own` and
`margin` of the result are the reference's own picks and how clear they
were: the smaller of (p_k - p_(k+1)) / p_k among the kept groups' experts
and (g_m - g_(m+1)) / g_m among the groups' scores.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.gpt import highest

_F32 = jnp.float32
HEAD_GROUPS = 4     # heads are attended a group at a time
LATENT_KEEP = 16    # positions a stretch of kept cache entries holds


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def silu(x):
    return x * jax.nn.sigmoid(x)


def gated_ffn(f, gate_w, up_w, down_w):
    return (silu(f @ gate_w) * (f @ up_w)) @ down_w


def yarn_inv_freq(w):
    """rope/2 frequencies (float64): the published
    `yarn_find_correction_range` / `yarn_linear_ramp_mask` blend."""
    dim, base = w["qk_rope_head_dim"], w["rope_theta"]
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / w["rope_factor"]

    def correction_dim(turns):
        return (dim * math.log(w["rope_original_max"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(w["rope_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(w["rope_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                         / (high - low), 0.0, 1.0)
    return inter * (1.0 - mask) + extra * mask


def attn_scale(w):
    m = 0.1 * w["rope_mscale_all_dim"] * math.log(w["rope_factor"]) + 1.0 \
        if w["rope_factor"] > 1.0 else 1.0
    return (w["qk_nope_head_dim"] + w["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, pos, w):
    """Rotate-half over the last dim at positions `pos` [S]; x: [S, D] or
    [S, heads, D]. The cos/sin factor mscale / mscale_all_dim is 1."""
    ang = pos.astype(_F32)[:, None] * jnp.asarray(yarn_inv_freq(w), _F32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def attention_dense(q_nope, q_rope, k_nope, k_r, v, scale):
    """The one-line form: q_nope, k_nope: [S, h, nope]; q_rope: [S, h, r];
    k_r: [S, r]; v: [S, h, dv] -> [S, h, dv]."""
    S = q_nope.shape[0]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
         + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)


def attention_blocked(q_nope, q_rope, k_nope, k_r, v, scale, first, block):
    """One block of queries (positions first ..) against the key blocks
    [0, first / block]: the running soft-max. q_*: [block, h, .];
    k_nope, v: [S, h, .]; k_r: [S, r]; `first` traced."""
    B, h, dv = block, q_nope.shape[1], v.shape[2]
    qpos = first + jnp.arange(B)

    def step(j, carry):
        m, l, acc = carry
        kn = jax.lax.dynamic_slice_in_dim(k_nope, j * B, B)
        kr = jax.lax.dynamic_slice_in_dim(k_r, j * B, B)
        vv = jax.lax.dynamic_slice_in_dim(v, j * B, B)
        s = (jnp.einsum("qhd,khd->hqk", q_nope, kn)
             + jnp.einsum("qhd,kd->hqk", q_rope, kr)) * scale
        ok = (j * B + jnp.arange(B))[None, :] <= qpos[:, None]
        s = jnp.where(ok[None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        return (m_new, l * alpha + p.sum(-1),
                acc * alpha[..., None] + jnp.einsum("hqk,khd->hqd", p, vv))

    m, l, acc = jax.lax.fori_loop(
        0, first // B + 1, step,
        (jnp.full((h, B), -jnp.inf, _F32), jnp.zeros((h, B), _F32),
         jnp.zeros((h, B, dv), _F32)))
    return (acc / l[..., None]).transpose(1, 0, 2)           # [B, h, dv]


def route(probs, w):
    """(own picks [S, k], margin [S]) of the group-limited greedy router
    on probs [S, E]."""
    S, E = probs.shape
    G, keep, k = w["n_group"], w["topk_group"], w["experts_per_tok"]
    best = probs.reshape(S, G, E // G).max(-1)
    g_top, groups = jax.lax.top_k(best, min(keep + 1, G))
    kept = (jax.nn.one_hot(groups[:, :keep], G, dtype=jnp.int32).sum(1)
            > 0)
    masked = jnp.where(jnp.repeat(kept, E // G, axis=1), probs, 0.0)
    top, own = jax.lax.top_k(masked, k + 1)
    margin = (top[:, k - 1] - top[:, k]) / top[:, k - 1]
    if keep < G:
        margin = jnp.minimum(
            margin, (g_top[:, keep - 1] - g_top[:, keep])
            / g_top[:, keep - 1])
    return own[:, :k], margin


def expert_layer(p, e, f, w, routing=None, router_dtype=_F32, shared=True):
    """The expert layer on f: [S, H]. Returns (y, own picks, margin)."""
    lo, hi = w["experts_held"]
    probs = jax.nn.softmax(
        f.astype(router_dtype) @ p["router_w"].astype(router_dtype), -1
    ).astype(_F32)
    own, margin = route(probs, w)
    ids = own if routing is None else jnp.where(routing >= 0, routing, own)
    weights = (jnp.take_along_axis(probs, ids, axis=1)
               * w["routed_scaling_factor"])

    def one(y, t):
        gate_w, up_w, down_w, number = t
        mine = jnp.sum(jnp.where(ids == number, weights, 0.0), -1)  # [S]
        out = gated_ffn(f, gate_w.astype(_F32), up_w.astype(_F32),
                        down_w.astype(_F32))
        return y + mine[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(f),
                        (e["gate_w"], e["up_w"], e["down_w"],
                         jnp.arange(lo, hi)))
    if shared:
        y = y + gated_ffn(f, p["shared_gate_w"], p["shared_up_w"],
                          p["shared_down_w"])
    return y, own, margin


@functools.partial(jax.jit, static_argnames=("block", "dense", "rdt", "wkey"))
@highest
def layer(p, e, x, routing, n, keep, *, block, dense, rdt, wkey):
    """One layer on its own (unstacked) leaves; x: [S, H] float32, S whole
    blocks, of which the first `n` positions are the sequence (blocks past
    it are not worked: their rows come back as they were). Returns (x,
    own picks [S, k], margin [S], the cache entries [c | k_r] of the
    len(keep) stretches of LATENT_KEEP positions that start at `keep`)
    (dense layers: zeros for the picks)."""
    w = dict(wkey)
    S, H = x.shape
    B, nblocks = block, -(-n // block)
    hd, C = w["num_heads"], w["kv_lora_rank"]
    nope, rp, dv = (w["qk_nope_head_dim"], w["qk_rope_head_dim"],
                    w["v_head_dim"])
    eps, scale = w["rms_norm_eps"], attn_scale(w)
    big = {k: v for k, v in p.items() if v.ndim >= 2}      # widened in use
    p = {k: (v if k in big else v.astype(_F32)) for k, v in p.items()}

    def f32(name):
        return big[name].astype(_F32)

    def rows(a, i):
        return jax.lax.dynamic_slice_in_dim(a, i * B, B)

    def put(a, i, v):
        return jax.lax.dynamic_update_slice_in_dim(a, v, i * B, 0)

    # -- every position's low-rank query and cache entry -----------------
    def down(i, carry):
        c_q, c, k_r = carry
        h = rms_norm(rows(x, i), p["ln1_g"], eps)
        ckr = h @ f32("dkv_w")
        pos = i * B + jnp.arange(B)
        return (put(c_q, i, rms_norm(h @ f32("dq_w"), p["q_norm_g"], eps)),
                put(c, i, rms_norm(ckr[:, :C], p["kv_norm_g"], eps)),
                put(k_r, i, rope(ckr[:, C:], pos, w)))

    c_q, c, k_r = jax.lax.fori_loop(
        0, nblocks, down,
        (jnp.zeros((S, w["q_lora_rank"]), _F32), jnp.zeros((S, C), _F32),
         jnp.zeros((S, rp), _F32)))

    entries = jnp.concatenate([c, k_r], -1)
    kept = jax.vmap(lambda at: jax.lax.dynamic_slice_in_dim(
        entries, at, LATENT_KEEP))(keep)

    # -- attention, a group of heads at a time ------------------------------
    groups = HEAD_GROUPS if hd % HEAD_GROUPS == 0 else 1
    hg = hd // groups
    attn = jnp.zeros((S, H), _F32)
    for g in range(groups):
        uq = f32("uq_w").reshape(-1, hd, nope + rp)[:, g * hg:(g + 1) * hg]
        uk = f32("uk_w")[g * hg:(g + 1) * hg]                # [hg, nope, C]
        uv = f32("uv_w")[g * hg:(g + 1) * hg]                # [hg, C, dv]
        ow = f32("o_w").reshape(hd, dv, H)[g * hg:(g + 1) * hg]

        def expand(i, carry):
            k_nope, v = carry
            ci = rows(c, i)
            return (put(k_nope, i, jnp.einsum("sc,hdc->shd", ci, uk)),
                    put(v, i, jnp.einsum("sc,hcv->shv", ci, uv)))

        k_nope, v = jax.lax.fori_loop(
            0, nblocks, expand, (jnp.zeros((S, hg, nope), _F32),
                                 jnp.zeros((S, hg, dv), _F32)))

        def attend(i, attn):
            q = jnp.einsum("sr,rhd->shd", rows(c_q, i), uq)
            q_rope = rope(q[..., nope:], i * B + jnp.arange(B), w)
            o = attention_blocked(q[..., :nope], q_rope, k_nope, k_r, v,
                                  scale, i * B, B)
            return put(attn, i, rows(attn, i)
                       + jnp.einsum("shv,hvo->so", o, ow))

        attn = jax.lax.fori_loop(0, nblocks, attend, attn)

    # -- the rest of the block -----------------------------------------------
    k = w["experts_per_tok"]

    def rest(i, carry):
        x, own, margin = carry
        xi = rows(x, i) + rows(attn, i)
        f = rms_norm(xi, p["ln2_g"], eps)
        if dense:
            return (put(x, i, xi + gated_ffn(f, f32("gate_w"), f32("up_w"),
                                             f32("down_w"))), own, margin)
        y, o, m = expert_layer(
            {**p, **{n_: f32(n_) for n_ in big}}, e, f, w,
            None if routing is None else rows(routing, i),
            jnp.dtype(rdt))
        return put(x, i, xi + y), put(own, i, o), put(margin, i, m)

    return jax.lax.fori_loop(
        0, nblocks, rest,
        (x, jnp.zeros((S, k), jnp.int32), jnp.zeros((S,), _F32))) + (kept,)


def hidden(params, tokens, w, routing=None, n=None, block=None,
           router_dtype=_F32, keep=None):
    """(The final-normed stream [S, H] of one sequence; the reference's
    own picks [S, L_routed, k]; their margins [S, L_routed]). `tokens`:
    [S], S whole `block`s (default: one block of S); `n`: the sequence's
    true length (default S). With `keep` (first positions of stretches of
    LATENT_KEEP positions) a fourth result: every layer's cache entries
    [c | k_r] there, [L, len(keep), LATENT_KEEP, C + rope]."""
    S = tokens.shape[0]
    block = S if block is None else block
    assert S % block == 0, (S, block)
    n = S if n is None else n
    wkey = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in w.items()))
    rdt = jnp.dtype(router_dtype).name
    x = jnp.take(params["embed"], tokens, axis=0).astype(_F32)
    P = w["first_k_dense"]
    at = jnp.asarray([0] if keep is None else keep, jnp.int32)
    owns, margins, latents = [], [], []
    for j in range(P):
        p = {k: v[j] for k, v in params["prologue"].items()}
        x, _, _, kept = layer(p, None, x, None, n, at, block=block,
                              dense=True, rdt=rdt, wkey=wkey)
        latents.append(kept)
    blocks, experts = params["blocks"][0], params["experts"][0]
    for j in range(w["num_layers"] - P):
        p = {k: v[j, 0] for k, v in blocks.items()}
        e = {k: v[j] for k, v in experts.items()}
        x, own, margin, kept = layer(
            p, e, x, None if routing is None else routing[:, j], n, at,
            block=block, dense=False, rdt=rdt, wkey=wkey)
        owns.append(own)
        margins.append(margin)
        latents.append(kept)
    out = (rms_norm(x, params["lnf_g"].astype(_F32), w["rms_norm_eps"]),
           jnp.stack(owns, 1), jnp.stack(margins, 1))
    return out if keep is None else out + (jnp.stack(latents),)


@highest
def head_logits(params, x, cols=None):
    """Logits of `cols` (a slice of the vocabulary) or of all of it."""
    head = params["head_w"] if cols is None else params["head_w"][:, cols]
    return x @ head.astype(_F32)


def forward(params, tokens, w, routing=None, block=None):
    """Logits [S, V] of one sequence (toy sizes: the whole head at once)."""
    return head_logits(params, hidden(params, tokens, w, routing,
                                      block=block)[0])


def best_and_picked(params, x, picked, blocks=4):
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
