"""The recurrent-state path of a Gated DeltaNet (linear attention) layer on
the serving step's ragged batch (Pallas): the gated delta rule

    S_t = exp(g_t) S_{t-1} + k_t (outer) beta_t (v_t - (exp(g_t) S_{t-1})^T k_t)
    o_t = S_t^T q_t

per value head, with the state S [d_k, d_v] in float32, one a SLOT and a
layer. Row r of the batch IS engine slot r, as in `kernels/pallas/ssm.py`,
whose grid, row list and contract this kernel shares (the state is ONE
donated buffer ``[L, slots, heads, d_k, d_v]``, never sliced by layer,
written in place: `inference/ragged_step.py` states it).

A row takes one of three arms, chosen from its prefetched length:

  * one token (every decode row): the recurrence itself on the vector
    units (k S and S q a multiply and a sum over the state's sublanes,
    the correction a broadcast product; the token's q and k come
    normalised and transposed, `_single`) — the state's bytes and little
    else;
  * up to `SUB` tokens, or a chunk: the chunked form over a sub-chunk of
    n positions (n = `SUB`, or `SC` = 64, one sub-chunk after another),
    identical in exact arithmetic: with G the running sum of g inside the
    sub-chunk, D_ij = exp(G_i - G_j), kb = k beta, vb = v beta,

        A  = -(kb k^T o D), strictly lower
        T  = (I - A)^-1
        W  = T (kb exp(G)),  U = T vb,  v' = U - W S
        o  = (q exp(G)) S + ((q k^T o D), lower with diagonal) v'
        S' = exp(G_n) S + (k exp(G_n - G))^T v'

    A is nilpotent (A^n = 0), so T = (I + A)(I + A^2)(I + A^4)... in
    log2(n) squarings: the forward substitution's n dependent steps as
    2 log2(n) - 1 products, all float32 at `highest`.

Positions past a row's length carry g = 0 and beta = 0 and move nothing.
q and k arrive as the conv left them and are L2-normalised here, per key
head (x rsqrt(sum x^2 + 1e-6); q also scaled by d_k^-0.5); a value head h
reads key head h // (value heads / key heads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret as _interpret
from .ssm import _active_rows
from ...observability.trace import KERNELS

__all__ = ["gdn_scan"]

SUB = 16    # the short arm's positions: one bf16 sublane tile
SC = 64     # a sub-chunk of the chunk arm
_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_HI = jax.lax.Precision.HIGHEST
_EPS = 1e-6


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=_F32)


def _head_block(heads, rep):
    """Value heads a grid step handles: the largest divisor of the heads
    that is at most 8 and whole key heads (8 heads are 512 KB of float32
    state at 128 x 128)."""
    return max(d for d in range(rep, min(8, heads) + 1, rep)
               if heads % d == 0)


def _l2(x, scale=1.0):
    return x * (jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _EPS)
                * scale)


def _column(cols, j):
    """Column j (traced) of cols [n, w] as [n, 1]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    return jnp.sum(jnp.where(lane == j, cols, 0.0), axis=1, keepdims=True)


def _heads(HB, body):
    jax.lax.fori_loop(0, HB, lambda i, c: (body(i), c)[1], None)


def _single(qk_ref, lane0, v_ref, s_in, s_out, o_ref, zero, g_of, beta_of,
            *, HB, rep):
    """The one-token arm: the recurrence as it is written, on the vector
    units. The token's q and k arrive normalised and TRANSPOSED (d_k on
    the sublanes, as the state has it; a key head a lane, the block's
    first at `lane0`), so k S and
    S q are a multiply and a sum over sublanes and the rank-1 correction
    a broadcast product: on the MXU each was a product with one live row
    that loaded the head's state as weights, six passes at `highest`."""
    qk = qk_ref[0]                                      # [2, dk, Hk]

    def key_head(ik):
        q, k = _column(qk[0], lane0 + ik), _column(qk[1], lane0 + ik)
        for j in range(rep):            # the value heads that read it
            i = ik * rep + j
            v = v_ref[0, i, :SUB, :].astype(_F32)[:1]           # [1, dv]
            S = jnp.where(zero, 0.0, s_in[0, 0, i].astype(_F32))
            S = S * jnp.exp(jnp.full((1, S.shape[1]), g_of(i), _F32))
            d = jnp.full((1, S.shape[1]), beta_of(i), _F32) * (
                v - jnp.sum(S * k, 0, keepdims=True))
            S = S + k * d
            o_ref[0, i, :1, :] = jnp.sum(S * q, 0, keepdims=True)
            s_out[0, 0, i] = S.astype(s_out.dtype)

    _heads(HB // rep, key_head)


def _positions(n, s, q_ref, k_ref, v_ref, col_ref, row_ref, src, s_out,
               o_ref, zero, end_of, *, HB, rep):
    """The chunked form over positions [s * SC, s * SC + n) of the tiles,
    from the state in `src` (the block as it came, or as the sub-chunk
    before left it)."""
    dk = q_ref.shape[-1]
    lo = s * SC
    ii = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = (ii == jj).astype(_F32)

    def head(i):
        ik = jax.lax.div(i, rep)
        q = _l2(q_ref[0, ik, lo:lo + n, :].astype(_F32), dk ** -0.5)
        k = _l2(k_ref[0, ik, lo:lo + n, :].astype(_F32))
        v = v_ref[0, i, lo:lo + n, :].astype(_F32)
        cols = col_ref[0, 0, lo:lo + n, :]
        beta, cum = _column(cols, i), _column(cols, HB + i)     # [n, 1]
        cum_row = row_ref[0, 0, s, pl.ds(HB + i, 1), :n]        # [1, n]
        decay = jnp.exp(jnp.minimum(cum - cum_row, 0.0))
        kk = _dot(k, k, _NT)
        A = jnp.where(ii > jj, -(beta * kk * decay), 0.0)
        Tm, Pw = eye + A, A
        m = 2
        while m < n:
            Pw = _dot(Pw, Pw)
            Tm = Tm + _dot(Tm, Pw)
            m *= 2
        S = src[0, 0, i].astype(_F32)
        if src is not s_out:
            S = jnp.where(zero, 0.0, S)
        grow = jnp.exp(cum)                                     # [n, 1]
        W = _dot(Tm, k * (beta * grow))
        U = _dot(Tm, v * beta)
        vp = U - _dot(W, S)
        qk = jnp.where(ii >= jj, _dot(q, k, _NT) * decay, 0.0)
        o_ref[0, i, lo:lo + n, :] = _dot(q * grow, S) + _dot(qk, vp)
        end = end_of(i, s)
        keep = jnp.exp(jnp.full((1, S.shape[1]), end, _F32))
        S = keep * S + _dot(k * jnp.exp(jnp.minimum(end - cum, 0.0)), vp,
                            _TN)
        s_out[0, 0, i] = S.astype(s_out.dtype)

    _heads(HB, head)


def _kernel(layer_ref, rows_ref, n_ref, qlens_ref, reset_ref, cend_ref,
            beta0_ref, q_ref, k_ref, v_ref, col_ref, row_ref, qk_ref, s_in,
            o_ref, s_out, *, C, HB, H, rep, nsub):
    w, n = pl.program_id(0), n_ref[0]
    r = rows_ref[w]
    # past the list's end: the last real step's blocks, and no work
    ql = jnp.where(w < n, qlens_ref[r], -1)
    zero = reset_ref[r] > 0
    hb = pl.program_id(1)
    head0 = r * H + hb * HB

    def end_of(i, s):   # a head's running sum of g at a sub-chunk's end
        return cend_ref[(head0 + i) * nsub + s]

    refs = dict(v_ref=v_ref, s_out=s_out, o_ref=o_ref, zero=zero, HB=HB,
                rep=rep)

    @pl.when(ql == 1)
    def _one():
        _single(qk_ref, hb * (HB // rep), s_in=s_in,
                g_of=lambda i: end_of(i, 0),
                beta_of=lambda i: beta0_ref[head0 + i], **refs)

    if C > 1:
        short = min(SUB, C)
        chunk = functools.partial(_positions, q_ref=q_ref, k_ref=k_ref,
                                  col_ref=col_ref, row_ref=row_ref,
                                  end_of=end_of, **refs)

        @pl.when((ql > 1) & (ql <= short))
        def _short():
            chunk(short, 0, src=s_in)

        for s in range(nsub if C > short else 0):
            @pl.when(ql > max(short, s * SC))
            def _chunk(s=s):
                chunk(min(SC, C), s, src=s_in if s == 0 else s_out)

    # a list with no real entry still writes the block it stayed on back
    @pl.when(n == 0)
    def _keep():
        s_out[...] = s_in[...]


def gdn_scan(q, k, v, g, beta, state, layer, q_lens, reset):
    """One pass of the gated delta rule over every row's chunk.
    q, k: [R, C, Hk, dk] as the conv left them (normalised here); v:
    [R, C, Hv, dv]; g (log decay, <= 0), beta: [R, C, Hv] f32, both 0
    past a row's length; state: [L, R, Hv, dk, dv]; q_lens, reset: [R] (a
    reset row starts from a zero state). Returns (o [R, C, Hv, dv] f32 —
    rows and positions without a token hold nothing defined —, state
    aliased to the one given, holding each row's state after its last
    token)."""
    R, C, Hk, dk = q.shape
    _, _, H, dv = v.shape
    rep = H // Hk
    HB = _head_block(H, rep)
    nhb = H // HB
    Cp = -(-C // SUB) * SUB
    if Cp > SC:
        Cp = -(-Cp // SC) * SC
    if Cp > C:
        pad = ((0, 0), (0, Cp - C), (0, 0), (0, 0))
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
        g, beta = (jnp.pad(a, pad[:3]) for a in (g, beta))
    sc = min(SC, Cp)
    nsub = Cp // sc
    cum = jnp.cumsum(g.astype(_F32).reshape(R, nsub, sc, H), axis=2)
    cend = cum[:, :, -1, :].transpose(0, 2, 1).reshape(R * H * nsub)
    both = jnp.concatenate(
        [beta.astype(_F32).reshape(R, Cp, nhb, HB),
         cum.reshape(R, Cp, nhb, HB)], axis=-1)             # [R,Cp,nhb,2HB]
    col = both.transpose(0, 2, 1, 3)                        # [R,nhb,Cp,2HB]
    row = both.reshape(R, nsub, sc, nhb, 2 * HB).transpose(0, 3, 1, 4, 2)
    # the one-token arm's operands: each row's FIRST position, q and k
    # normalised and with d_k leading (the state's sublanes), and its beta
    qk0 = jnp.stack([_l2(q[:, 0].astype(_F32), dk ** -0.5),
                     _l2(k[:, 0].astype(_F32))], 1).transpose(0, 1, 3, 2)
    beta0 = beta[:, 0].astype(_F32).reshape(R * H)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))  # head-major
    rows, n = _active_rows(q_lens)

    # index maps: grid step (w, hb) works on row rows[w], head block hb;
    # past the n real rows it stays on the last real step's blocks
    def at(w, hb, rows, n):
        return rows[w], jnp.where(w < n[0], hb, nhb - 1)

    def tiles_idx(w, hb, layer, rows, n, *_):
        r, hb = at(w, hb, rows, n)
        return (r, hb, 0, 0)

    def row_idx(w, hb, layer, rows, n, *_):
        r, hb = at(w, hb, rows, n)
        return (r, hb, 0, 0, 0)

    def first_idx(w, hb, layer, rows, n, *_):
        return (rows[w], 0, 0, 0)

    def state_idx(w, hb, layer, rows, n, *_):
        r, hb = at(w, hb, rows, n)
        return (layer[0], r, hb, 0, 0)

    state_block = pl.BlockSpec((1, 1, HB, dk, dv), state_idx)
    prefetch = (jnp.asarray(layer, jnp.int32).reshape(1), rows, n,
                q_lens.astype(jnp.int32), reset.astype(jnp.int32), cend,
                beta0)
    kernel = functools.partial(_kernel, C=Cp if C > 1 else 1, HB=HB, H=H,
                               rep=rep, nsub=nsub)
    common = dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(R, nhb),
            in_specs=[pl.BlockSpec((1, HB // rep, Cp, dk), tiles_idx),
                      pl.BlockSpec((1, HB // rep, Cp, dk), tiles_idx),
                      pl.BlockSpec((1, HB, Cp, dv), tiles_idx),
                      pl.BlockSpec((1, 1, Cp, 2 * HB), tiles_idx),
                      pl.BlockSpec((1, 1, nsub, 2 * HB, sc), row_idx),
                      pl.BlockSpec((1, 2, dk, Hk), first_idx),
                      state_block],
            out_specs=[pl.BlockSpec((1, HB, Cp, dv), tiles_idx),
                       state_block]),
        out_shape=[jax.ShapeDtypeStruct((R, H, Cp, dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={len(prefetch) + 6: 1},
        interpret=_interpret())
    # one body, two names: a pass of single tokens is the state update
    if C == 1:
        call = pl.pallas_call(kernel, name=KERNELS.gdn_state_update, **common)
    else:
        call = pl.pallas_call(kernel, name=KERNELS.gdn_chunk_scan, **common)
    o, state = call(*prefetch, q, k, v, col, row, qk0, state)
    return o.transpose(0, 2, 1, 3)[:, :C], state
