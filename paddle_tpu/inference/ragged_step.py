"""Single-dispatch ragged serving step (ISSUE 6 tentpole): the serving
engine's one step (serving.py module doc). ONE compiled program advances
EVERY slot — decode rows and chunked-prefill rows ride one PACKED ragged
token buffer with per-row ``(slot, q_len, kv_len)`` descriptors, so

  * the QKV/projection/FFN GEMMs batch over ``sum(q_lens)`` real tokens
    (a decode row contributes 1 row of GEMM work, not a padded chunk);
  * attention is the unified Pallas ragged-paged kernel
    (`kernels.pallas.ragged_paged_attention`) over the shared block pool,
    descriptors riding scalar prefetch;
  * prefill KV is appended to the pool from INSIDE the program (int8
    pools quantize on append with per-page running-absmax scales,
    `quantization.kv_cache`);
  * sampling happens in-program at each row's last valid position, and a
    K-1-step decode-burst `lax.scan` continues freshly-sampled rows —
    K tokens per dispatch, including the token that completes a prefill
    (better TTFT).

Layout contract (host side, `ServingEngine._pack_ragged`): the packed
buffer holds each active row's tokens contiguously at ``starts[r]``;
``row_of/off_of`` map packed positions back to (row, chunk offset) and
tail padding points past every row's ``q_len`` (masked everywhere).
Attention reads and writes the packed buffer itself: the kernel takes
the ``[T, H_q, D]`` queries with ``starts`` and ``q_lens``, each row
copies its own positions in and out, and padding comes back zero — no
``[R, c_att]`` tile of queries or of outputs exists in the step. The
per-row window ``tile_idx [R, c_att]`` remains for the two consumers that
still want a row's tokens as a tile: the quantized append (which
requantizes whole pages, `append_tokens_quantized`) and a recurrent
mixer's chunk scan (``plan["tile_idx"]``, `models/falcon_h1.py`).

The KV pool's contract (the one place it is stated; the kernels and
`quantization.kv_cache` refer here). The K and the V pool are ONE buffer
each, ``[L, H_kv, NB, bs, D]`` (+ ``[L, H_kv, NB]`` f32 scales when
quantized), from `unified_step`'s donated argument to its aliased
result:

  * it rides the layer scan's and the burst scan's CARRY, never ``xs`` /
    ``ys`` — a scan that takes the pool as ``xs`` slices a layer's page
    set out of the stack and stacks a fresh one back, 2 x 3.2 GB a pass;
  * it is never sliced by layer: the attention kernel and the append
    take the whole pool and a ``layer`` scalar (scalar prefetch,
    dereferenced in the append's index maps and in the attention
    kernel's page copies, which read the pool where it lies in HBM);
  * it is written only in place and in the layout the kernel reads: new
    rows by `kernels.pallas.kv_append` (aliased in/out; each aligned
    sublane tile a pass writes comes in by one copy and goes back by
    one); the quantized append and the copy-on-write
    copy through the page-flat view ``pool.reshape(L*H*NB, bs, D)``
    (`kv_cache.page_rows`) — collapsing leading dims is a bitcast in the
    tiled layout, so a scatter on it updates in place. A
    multi-dimensional ``pool.at[li, :, blk, off].set`` lets the compiler
    pick the scatter's operand layout and copy the whole pool to and
    fro, inside the layer loop (PERF.md, PR 27).

A recurrent model's per-slot state (`unified_step`'s ``ssm_state`` and
``conv_tail``; `kernels.pallas.ssm`, `kernels.pallas.gdn`) keeps the same
contract: one donated buffer each, on both scans' carry, the layer by
scalar prefetch, written in place by the mixer's kernels.

A LATENT cache (``serving_model(cfg).latent``, `models/deepseek_v2.py`)
keeps the same contract on pages without heads: the two pools are the
compressed vector's ``[L, 1, NB, bs, C]`` and the shared rotary key's
``[L, 1, NB, bs, Rd]`` (512 wide, and the 64-wide key in a page of 128
lanes: two pools so that each page is whole lane tiles of its own width;
the head axis of 1 keeps the page-flat view, the copy-on-write above and
the allocator as they are), written by `kernels.pallas.latent_append` on
`kv_append`'s work list and read where they lie by `mla_attention` (its
items once a pass too); a layer of kind "latent" takes
the model's `latent_qkv` in place of `qkv`. A model's PROLOGUE
(``prologue(cfg)``: a kind and a count) is a run of leading layers under
``params["prologue"]``, scanned before the periods with no experts; the
pool's entries count them first.

A WINDOWED attention layer (kind "window", `models/trinity_mini.py`:
query i attends the keys j with ``i - window < j <= i``) keeps its K and V
in a SECOND pair of pools with a table and a LIFETIME of their own
(``win`` of `ragged_pass` and `unified_step`: ``(wtables, wkp, wvp)``):
the pages of a layer that attends everything live as long as the request,
a window layer's are given back by the host as the window slides
(`inference.serving`), so its table is a RING of ``nbw`` entries, page j of
a row in entry ``j % nbw``. The window pools keep the contract above
(donated, on both scans' carry, the layer by scalar prefetch, one entry a
window layer in its own numbering); `kv_append` writes each layer through
its own lifetime's table, the work list built once a lifetime and pass; the
attention kernel takes ``window=`` and reads no page that lies wholly
behind every query's window.

A model whose layers are not all of one kind gives its PATTERN
(``serving_model(cfg).pattern``: one period as runs of "attention",
"window", "parallel", "linear" or "latent" layers). `ragged_pass` scans periods and, inside
one, each run; the pool then holds one entry an ATTENTION layer and the
state one a layer with a MIXER, each numbered in its own order, and a
run's stacked expert weights are taken whole like the pool (the layer
and the expert by scalar prefetch). A pattern of one layer (GPT: one
"attention"; Falcon-H1: one "parallel") is the scan over layers it was.

`tests/test_chip_compile.py` holds the compiled step to it: no
pool-sized (or state-sized) copy, slice or update, temp under 1 GiB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..observability.trace import SCOPES
from ..kernels.pallas.kv_append import append_tile, kv_append, tile_work
from ..kernels.pallas.latent_append import latent_append
from ..kernels.pallas.mla_attention import mla_items, mla_paged_attention
from ..kernels.pallas.ragged_paged_attention import ragged_paged_attention
from ..quantization.kv_cache import (append_tokens_quantized, page_rows,
                                     reset_page_scales)
from .serving import _sample, serving_model

__all__ = ["ragged_pass", "unified_step"]


def ragged_pass(params, tokens, row_of, off_of, starts, pos0, q_lens,
                tables, temps, key, kp, vp, ks, vs, ssm=None, win=None, *,
                cfg, bs, c_att, mp_axis=None, all_greedy=False):
    """One transformer forward over the packed ragged batch + per-row
    sampling. tokens/row_of/off_of: [T] packed (off_of >= q_len marks
    padding); starts/pos0/q_lens/temps: [R]; tables: [R, nb]; pools:
    [L, H_kv, NB, bs, D] (+ [L, H_kv, NB] scales when quantized).
    ssm: a recurrent model's (state, tail) or None; it rides the layer
    scan's carry beside the pools and is the fifth entry of the returned
    pools tuple. win: a model with windowed layers' (wtables [R, nbw],
    wkp, wvp): the ring tables and the two pools of the window layers'
    lifetime ([L_win, H_kv, NBw, bs, D]), or None; the pools ride the
    carry too and (wkp, wvp) is the sixth entry of the returned tuple
    (None without them).
    Returns (tok [R], (kp, vp, ks, vs, ssm, win) updated — ks, vs None when
    the pool is not quantized); with ``all_greedy``
    the head runs over EVERY packed position and the return gains a
    ``greedy_t [T]`` argmax vector between tok and the pools — the
    speculative-decoding verify signal (draft token i is accepted iff it
    equals the model's own argmax one position earlier). A model with
    routed experts adds, last, what its router chose: (ids [L, T, k],
    stats [L, columns]) as the model's `moe_layer` gives them a layer (L:
    the layers WITH a router; the model's `route_stats` names the
    columns)."""
    T = tokens.shape[0]
    quantized = ks is not None
    model = serving_model(cfg)
    pos_t = model.positions(pos0[row_of] + off_of, cfg)
    x = model.embed(params, tokens[None], pos_t[None], cfg)  # [1, T, H]
    kv_lens = pos0 + q_lens
    # the (page, tile) list the in-place append walks (unquantized pools;
    # quantized ones requantize whole pages, `append_tokens_quantized`)
    if not quantized:
        tile = append_tile(kp.dtype, bs)
        work = tile_work(starts, pos0, q_lens, tables, bs=bs, tile=tile,
                         c_att=c_att, T=T)
        if win is not None:     # the second lifetime's list, from its ring
            work_w = tile_work(starts, pos0, q_lens, win[0], bs=bs,
                               tile=tile, c_att=c_att, T=T, ring=True)
    # a row's tokens as a [c_att] window of the packed buffer, for the quantized
    # append and a recurrent mixer (both mask the clamped duplicates past q_len)
    tile_idx = jnp.clip(
        starts[:, None] + jnp.minimum(jnp.arange(c_att)[None, :],
                                      jnp.maximum(q_lens - 1, 0)[:, None]),
        0, T - 1)                                            # [R, c_att]
    scale = 1.0 / (cfg.head_dim ** 0.5)
    extra = {}
    if model.latent or getattr(model, "mask_padding", False):
        extra = {"real": off_of < q_lens[row_of]}   # padding is not routed
    if model.latent:    # attention's items: once a pass
        items = mla_items(tables, q_lens, kv_lens, bs=bs, c_att=c_att, T=T)
    if ssm is not None:
        # a row that starts at position 0 starts from a zero state
        plan = {"row_of": row_of, "off_of": off_of, "starts": starts,
                "q_lens": q_lens, "tile_idx": tile_idx,
                "reset": (pos0 == 0) & (q_lens > 0)}

    # one period of the model's layer pattern, as runs of one kind of
    # layer: "attention" (queries, the K/V append, paged attention; what
    # `qkv` hands back fourth goes to `block_math`), "parallel" (attention
    # with a recurrent mixer beside it on that fourth return) or "linear"
    # (the mixer alone, on the residual stream). The scan is over PERIODS;
    # the pool holds one entry an attention layer, the state one a layer
    # with a mixer, each numbered in its own order
    runs = model.pattern(cfg)
    routed = model.routed
    single = len(runs) == 1 and runs[0][1] == 1 and not routed
    n_att = sum(n for kind, n in runs if kind not in ("linear", "window"))
    n_mix = sum(n for kind, n in runs
                if kind not in ("attention", "latent", "window"))
    n_win = sum(n for kind, n in runs if kind == "window")
    wtables = None if win is None else win[0]

    def layer_body(kind, experts, flat, att, mix):
        """One layer of `kind`; flat/att/mix: its number among all layers
        of its run's kind (the experts' leading index), among the layers
        with attention (the pool's; a window layer's among the window
        layers, its own pools'), among those with a mixer (the
        state's)."""
        def body(carry, p):
            x, kp, vp, ks, vs, ssm, wp = carry
            attn_p = None
            if kind == "linear":
                mixed, ssm = model.mixer(p, x, ssm, mix, plan, cfg)
            elif kind == "window":
                q, k, v, mixed = model.qkv(p, x, pos_t[None], cfg, mp_axis,
                                           kind=kind)
                with jax.named_scope(SCOPES.kv_write):
                    wp = kv_append(*wp, k[0], v[0], att, work_w, tile=tile)
                with jax.named_scope(SCOPES.window_attn):
                    attn_p = ragged_paged_attention(
                        q[0], *wp, wtables, starts, q_lens, kv_lens, scale,
                        None, None, att, c_att=c_att,
                        window=model.window(cfg))[None]      # [1,T,h,D]
            elif kind == "latent":
                qa, qr, c, k_r = model.latent_qkv(p, x, pos_t[None], cfg)
                mixed = None
                with jax.named_scope(SCOPES.kv_write):
                    kp, vp = latent_append(kp, vp, c, k_r, att, work,
                                           tile=tile)
                with jax.named_scope(SCOPES.mla_attn):
                    attn_p = mla_paged_attention(
                        qa, qr, kp, vp, tables, starts, q_lens, kv_lens,
                        model.attn_scale(cfg), att, c_att=c_att,
                        work=items)[None]                    # [1,T,h,C]
            else:
                q, k, v, u = model.qkv(p, x, pos_t[None], cfg, mp_axis)
                mixed = u                                    # [1,T,h,D]
                if kind == "parallel":
                    mixed, ssm = model.mixer(p, u, ssm, mix, plan, cfg)
                with jax.named_scope(SCOPES.kv_write):
                    if quantized:
                        kp, ks = append_tokens_quantized(
                            kp, ks, k[0][tile_idx], pos0, q_lens, tables,
                            bs, att)
                        vp, vs = append_tokens_quantized(
                            vp, vs, v[0][tile_idx], pos0, q_lens, tables,
                            bs, att)
                    else:
                        kp, vp = kv_append(kp, vp, k[0], v[0], att, work,
                                           tile=tile)
                with jax.named_scope(SCOPES.ragged_attn):
                    attn_p = ragged_paged_attention(
                        q[0], kp, vp, tables, starts, q_lens, kv_lens,
                        scale, ks, vs, att, c_att=c_att)[None]  # [1,T,h,D]
            route = None
            if routed:
                x, route = model.block_math(p, x, attn_p, mixed, cfg,
                                            mp_axis, experts=experts,
                                            layer=flat, **extra)
            else:
                x = model.block_math(p, x, attn_p, mixed, cfg, mp_axis)
            return (x, kp, vp, ks, vs, ssm, wp), route
        return body

    def period_body(carry, xs):
        ps, period = xs
        if single:      # one layer a period: the period IS the layer
            return layer_body(runs[0][0], None, period, period, period)(
                carry, ps)
        routes, att0, mix0, win0 = [], 0, 0, 0
        for r, (kind, n) in enumerate(runs):
            def run_body(carry, pj, r=r, kind=kind, n=n, att0=att0,
                         mix0=mix0, win0=win0):
                p, j = pj
                return layer_body(
                    kind, params["experts"][r] if routed else None,
                    period * n + j,
                    (pro_win + period * n_win + win0 + j if kind == "window"
                     else pro_att + period * n_att + att0 + j),
                    period * n_mix + mix0 + j)(carry, p)
            carry, route = lax.scan(
                run_body, carry, (ps[r], jnp.arange(n, dtype=jnp.int32)))
            routes.append(route)
            att0 += n if kind not in ("linear", "window") else 0
            mix0 += n if kind != "attention" else 0
            win0 += n if kind == "window" else 0
        # the period's routing in layer order: (ids [n, T, k], stats [n, .])
        route = (jax.tree.map(lambda *a: jnp.concatenate(a), *routes)
                 if routed else None)
        return carry, route

    carry = (x, kp, vp, ks, vs, ssm, None if win is None else win[1:])
    kind, n_pro = model.prologue(cfg)
    # the prologue's layers are the first entries of their lifetime's pool
    pro_att, pro_win = (0, n_pro) if kind == "window" else (n_pro, 0)
    if n_pro:   # the leading layers, no experts; the pool's first entries
        carry, _ = lax.scan(
            lambda carry, pj: layer_body(kind, None, pj[1], pj[1], pj[1])(
                carry, pj[0]),
            carry, (params["prologue"], jnp.arange(n_pro, dtype=jnp.int32)))
    blocks = params["blocks"]
    periods = jax.tree.leaves(blocks)[0].shape[0]
    xs = (blocks, jnp.arange(periods, dtype=jnp.int32))
    (x, *pools), route = lax.scan(period_body, carry, xs)
    if routed:      # [periods, layers a period, ...] -> [layers, ...]
        route = jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), route)
    with jax.named_scope(SCOPES.head):
        x = model.final_norm(params, x, cfg)
    last_idx = jnp.clip(starts + jnp.maximum(q_lens, 1) - 1, 0, T - 1)
    if all_greedy:
        # spec verify: the head GEMM widens from [R, V] to [T, V] so the
        # model's argmax is known at every draft position in ONE pass
        logits_all = model.head_logits(params, x[0], cfg, mp_axis)  # [T, V]
        with jax.named_scope(SCOPES.sample):
            greedy_t = jnp.argmax(logits_all, axis=-1).astype(jnp.int32)
        logits = logits_all[last_idx]                            # [R, V]
    else:
        logits = model.head_logits(params, x[0][last_idx], cfg, mp_axis)
    tok = _sample(logits, temps, key)
    if all_greedy:
        return tok, greedy_t, pools
    if routed:
        return tok, pools, route
    return tok, pools


def unified_step(params, tokens, row_of, off_of, starts, pos0, q_lens,
                 tables, fresh, sample0, remaining, eos_ids, temps,
                 prev_tok, key, kp, vp, ks, vs, cow_src=None, cow_dst=None,
                 reset_tables=None, ssm_state=None, conv_tail=None,
                 wtables=None, wkp=None, wvp=None, *, cfg, bs, c_att, K,
                 spec=False, mp_axis=None):
    """ONE compiled program per engine step: the ragged pass (prefill
    chunks + first decode token for every row) followed by K-1 decode
    micro-steps for every sampling row. fresh: [R] bool — slots admitted
    this step (their tables' page scales reset in-program, so recycled
    blocks never inherit a stale quantization range); sample0: [R] bool —
    rows whose pass-1 token counts (decode rows + prefills completing
    this step); remaining: [R] tokens each row may still emit INCLUDING
    pass-1's (0 for mid-prefill rows); eos_ids: [R] (-1 = none);
    temps: [R] (0 = greedy).

    The next token stays on the device (ISSUE 31): prev_tok [R] is the
    last token each slot emitted, as the previous call returned it
    (``last_tok``), never fetched. A packed position whose ``tokens``
    entry is negative (the host's sentinel, -1: "this row's input is
    what the step before sampled, and I have not seen it yet") takes
    ``prev_tok[row_of]``; every other position keeps the token the host
    wrote (prompt tokens, a first decode step after a settle, drafts),
    so the same program serves a step packed behind one still in flight
    and one packed from settled state. ``last_tok`` comes back after
    ``lens``: per row the last token it emitted in this call (pass 1, or
    the last burst pass it was active in), else its ``prev_tok`` carried
    through. Both are R int32s that never leave the device.

    Prefix sharing (ISSUE 17) appends three OPTIONAL trailing args so the
    flags-off trace — and hence the compiled HLO — is byte-identical:
    cow_src/cow_dst [R] pair up copy-on-write page copies executed
    before any append (idle pairs point 0 -> 0, a scratch-block no-op);
    reset_tables [R, nb] replaces ``tables`` in the fresh-row scale
    reset with inherited (shared) entries zeroed, so admitting a request
    onto cached pages never wipes the canonical pages' quantization
    scales. Scale order matters: reset first, COW copy after, so a COW
    destination inherits its source page's running absmax.

    A model with a recurrent mixer (``serving_model(cfg).recurrent``)
    appends two more: ssm_state [L, R, heads, P, N] and conv_tail
    [L, K-1, R, channels], its per-SLOT state (row r is slot r). They
    keep the pool's contract, stated above: donated, on the scans' carry,
    written in place by the mixer's kernels; a row whose pass starts at
    position 0 starts from zeros. Both come back after ``lens``.

    A model with windowed layers appends three more: wtables [R, nbw],
    the ring tables of the window layers' lifetime, and that lifetime's
    two pools wkp, wvp [L_win, H_kv, NBw, bs, D], donated and aliased like
    the others; both pools come back after ``last_tok`` (after the
    recurrent state, where there is one).

    A model with routed experts (``routed``) returns what its router
    chose, last: ids0 [L, T, k] int16, the picks of every packed position
    of pass 1 and layer; ids_burst [K-1, L, R, k], the burst passes' (row
    r's one position); stats [K, L, columns] int32, per pass and layer
    what the model's `route_stats` names (`kernels.pallas.moe.PASS_STATS`:
    held experts touched, the assignments to them, the largest number one
    of them got, tiles walked and their height). They are fetched with the
    tokens: no sync of their own.

    Returns (toks [K, R], kp, vp, ks, vs, lens [R], last_tok [R]); with
    ``spec=True`` (K must be 1) the return gains ``greedy_all [T]`` after
    toks — the model's argmax at every packed position, from which the
    host accepts the longest exactly-matching draft prefix."""
    assert not (spec and K > 1), "spec verify subsumes the burst"
    R = pos0.shape[0]
    tokens = jnp.where(tokens < 0, prev_tok[row_of], tokens)
    quantized = ks is not None
    if quantized:
        rt = tables if reset_tables is None else reset_tables
        with jax.named_scope(SCOPES.kv_write):
            ks = reset_page_scales(ks, rt, fresh)
            vs = reset_page_scales(vs, rt, fresh)
    if cow_src is not None:
        with jax.named_scope(SCOPES.cow):
            src = page_rows(kp.shape, None, cow_src).reshape(-1)
            dst = page_rows(kp.shape, None, cow_dst).reshape(-1)

            def copy_pages(a):  # pool [L,H,NB,bs,D] or scales [L,H,NB]
                flat = a.reshape((-1,) + a.shape[3:])
                return flat.at[dst].set(flat[src]).reshape(a.shape)

            kp, vp = copy_pages(kp), copy_pages(vp)
            if quantized:
                ks, vs = copy_pages(ks), copy_pages(vs)
    ssm = None if ssm_state is None else (ssm_state, conv_tail)
    wp = None if wtables is None else (wkp, wvp)
    key, sub = jax.random.split(key)
    out = ragged_pass(params, tokens, row_of, off_of, starts,
                      pos0, q_lens, tables, temps, sub,
                      kp, vp, ks, vs, ssm,
                      None if wp is None else (wtables, *wp), cfg=cfg, bs=bs,
                      c_att=c_att, mp_axis=mp_axis, all_greedy=spec)
    routed = serving_model(cfg).routed
    if spec:
        tok0, greedy_all, (kp, vp, ks, vs, ssm, wp) = out
    elif routed:
        tok0, (kp, vp, ks, vs, ssm, wp), (ids0, stats0) = out
    else:
        tok0, (kp, vp, ks, vs, ssm, wp) = out
    tok0 = jnp.where(sample0, tok0, 0)
    last_tok = jnp.where(sample0, tok0, prev_tok)
    lens = pos0 + q_lens
    rem = remaining - sample0.astype(remaining.dtype)
    alive = sample0 & ~(tok0 == eos_ids)
    ar = jnp.arange(R, dtype=jnp.int32)
    zero = jnp.zeros((R,), jnp.int32)

    def micro(carry, _):
        tok, last, kp, vp, ks, vs, ssm, wp, lens, rem, alive, key = carry
        active = alive & (rem > 0)
        ql = active.astype(jnp.int32)
        key, sub = jax.random.split(key)
        tok2, (kp, vp, ks, vs, ssm, wp), *route = ragged_pass(
            params, tok, ar, zero, ar, lens, ql, tables, temps, sub,
            kp, vp, ks, vs, ssm,
            None if wp is None else (wtables, *wp), cfg=cfg, bs=bs, c_att=1,
            mp_axis=mp_axis)
        tok2 = jnp.where(active, tok2, 0)
        last = jnp.where(active, tok2, last)
        lens = lens + ql
        rem = rem - ql
        alive = alive & ~(active & (tok2 == eos_ids))
        return (tok2, last, kp, vp, ks, vs, ssm, wp, lens, rem, alive,
                key), (tok2, *route)

    if K > 1:
        carry = (tok0, last_tok, kp, vp, ks, vs, ssm, wp, lens, rem, alive,
                 key)
        with jax.named_scope(SCOPES.burst):
            (_, last_tok, kp, vp, ks, vs, ssm, wp, lens, _, _, _), \
                (toks, *route) = lax.scan(micro, carry, jnp.arange(K - 1))
        all_toks = jnp.concatenate([tok0[None], toks], axis=0)
    else:
        all_toks = tok0[None]
    if spec:
        return all_toks, greedy_all, kp, vp, ks, vs, lens, last_tok
    out = (all_toks, kp, vp, ks, vs, lens, last_tok)
    if ssm is not None:
        out += tuple(ssm)
    if wp is not None:
        out += tuple(wp)
    if routed:
        if K > 1:
            (ids_burst, stats_burst), = route
            stats0 = jnp.concatenate([stats0[None], stats_burst])
        else:
            ids_burst = jnp.zeros((0, ids0.shape[0], R, ids0.shape[2]),
                                  ids0.dtype)
            stats0 = stats0[None]
        out += (ids0, ids_burst, stats0)
    return out
