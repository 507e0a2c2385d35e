"""The recurrent-state path of a Mamba-2 mixer on the serving step's ragged
batch (Pallas): the causal depthwise conv with its per-slot tail, and the
selective-state scan with its per-slot state.

Both work on what `inference/ragged_step.py` packs: row r of the batch IS
engine slot r, a row is 1 token (decode) or up to a chunk of tokens
(prefill, continuing where the slot's state stands), and rows with no
token this pass are not touched.

The state's contract is the KV pool's (`inference/ragged_step.py` states
it): the recurrent state is ONE buffer ``[L, slots, heads, P, N]`` and the
conv tail ONE buffer ``[L, K-1, slots, channels]`` from the step's donated
argument to its aliased result; they ride the scans' carry, are never
sliced by layer (``layer`` rides scalar prefetch into the index maps) and
are written only in place, by these kernels.

`ssm_scan` is the chunked (SSD) form of

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,   y_t = S_t C_t

over a row's chunk, from the slot's state S_0: with cum_t the running sum
of dt A inside the chunk,

    y_t = exp(cum_t) (S_0 C_t) + sum_{s<=t} exp(cum_t - cum_s) dt_s (C_t.B_s) x_s
    S_end = exp(cum_end) S_0 + sum_s exp(cum_end - cum_s) dt_s x_s (outer) B_s

all as matrix products over the chunk (float32 operands at `highest`: the
state is float32 and a bfloat16 pass would round it). Positions past a
row's length carry dt = 0, so they move neither state nor cum. A row of
at most `SUB` tokens (every decode row) takes the same formulas over its
first `SUB` positions only, so it costs its state's bytes and no more.
The grid walks a LIST of the rows that have tokens (scalar prefetch, like
`kv_append`'s tile list) and the count n of them. A grid step past the
n-th stays on the last real step's blocks and skips the body: the update
is not idempotent (the state is aliased, so a second visit would start
from the S_end the first wrote), and a block that does not change is
neither fetched again nor written back before the grid ends. So an idle
slot's state is never touched, and an active one's is read once and
written once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret as _interpret
from ...observability.trace import KERNELS

__all__ = ["ssm_conv", "ssm_scan"]

SUB = 16    # the short path's positions: one bf16 sublane tile
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_HI = jax.lax.Precision.HIGHEST


def _head_block(heads, groups):
    """Heads a grid step of the scan handles: the largest divisor of the
    heads in a B/C group that is at most 8 (a block reads one group; 8
    heads are 1 MB of float32 state at P 128, N 256)."""
    per_group = heads // groups
    return max(d for d in range(1, min(8, per_group) + 1)
               if per_group % d == 0)


def _active_rows(q_lens):
    """([R] int32, [1] int32): the rows with tokens first (in order), the
    tail of the list repeating the last of them (row 0 when there is
    none), and how many there are."""
    R = q_lens.shape[0]
    on = q_lens > 0
    order = jnp.argsort(~on, stable=True).astype(jnp.int32)
    n = jnp.sum(on.astype(jnp.int32))
    rows = order[jnp.minimum(jnp.arange(R), jnp.maximum(n - 1, 0))]
    return rows, n.reshape(1)


# -- the conv ---------------------------------------------------------------
def _conv_kernel(layer_ref, x_ref, w_ref, *refs, K):
    # the bias is an operand only where the conv has one
    *b_ref, tok_ref, row_ref, tail_in, y_ref, tail_out = refs
    T, R = x_ref.shape[0], row_ref.shape[0]
    x = x_ref[...]
    row_of, off_of = tok_ref[:, 0:1], tok_ref[:, 1:2]            # [T, 1]
    starts, q_lens, reset = (row_ref[:, 0:1], row_ref[:, 1:2],
                             row_ref[:, 2:3])                    # [R, 1]
    old = [jnp.where(reset > 0, jnp.zeros_like(x[:R]), tail_in[0, k])
           for k in range(K - 1)]                                # [R, cb]
    src = jnp.concatenate([x] + old, axis=0)             # [T + (K-1)R, cb]
    col = jax.lax.broadcasted_iota(jnp.int32, (T, src.shape[0]), 1)
    t = jax.lax.broadcasted_iota(jnp.int32, (T, src.shape[0]), 0)
    acc = x.astype(_F32) * w_ref[K - 1:K, :].astype(_F32)
    if b_ref:
        acc = acc + b_ref[0][...].astype(_F32)
    for j in range(1, K):
        # the input j positions back: in the packed buffer when the row
        # has it this pass, else in the row's tail (plane K-1 + off - j)
        back = jnp.where(off_of >= j, t - j,
                         T + (K - 1 + off_of - j) * R + row_of)
        prev = jax.lax.dot_general(
            (col == back).astype(x.dtype), src, (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)
        acc = acc + prev * w_ref[K - 1 - j:K - j, :].astype(_F32)
    y_ref[...] = (acc * jax.nn.sigmoid(acc)).astype(y_ref.dtype)
    # the new tail: the row's last K-1 inputs, old ones moving up where
    # the row brought fewer
    tcol = jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
    for k in range(K - 1):
        at = q_lens - (K - 1) + k                                # [R, 1]
        new = jax.lax.dot_general(
            ((tcol == starts + at) & (at >= 0)).astype(x.dtype), x,
            (((1,), (0,)), ((), ())), preferred_element_type=_F32)
        for k2 in range(k, K - 1):      # at < 0: old plane q_len + k
            new = jnp.where(q_lens + k == k2, old[k2].astype(_F32), new)
        tail_out[0, k] = new.astype(tail_out.dtype)


def ssm_conv(x, w, b, tail, layer, row_of, off_of, starts, q_lens, reset,
             *, block=512):
    """silu(causal depthwise conv) of the packed rows x: [T, C] with taps
    w: [K, C] (w[K-1] on the token itself) and bias b: [C], or None for a
    conv without one; a row's first
    tokens read the K-1 inputs before them from ``tail[layer]``
    ([L, K-1, R, C], plane K-2 the newest; taken as 0 where ``reset``),
    which leaves holding the row's last K-1 inputs. row_of/off_of: [T]
    (off_of >= q_len marks padding); starts/q_lens/reset: [R].
    Returns (y [T, C], tail aliased to the one given)."""
    T, C = x.shape
    K = w.shape[0]
    R = tail.shape[2]
    cb = block if C % block == 0 else C
    tok = jnp.stack([row_of, off_of], axis=1).astype(jnp.int32)
    row = jnp.stack([starts, q_lens, reset.astype(jnp.int32)],
                    axis=1).astype(jnp.int32)

    def tail_idx(c, layer):
        return (layer[0], 0, 0, c)

    tail_block = pl.BlockSpec((1, K - 1, R, cb), tail_idx)
    has_bias = b is not None
    y, tail = pl.pallas_call(
        functools.partial(_conv_kernel, K=K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(C // cb,),
            in_specs=[pl.BlockSpec((T, cb), lambda c, *_: (0, c)),
                      pl.BlockSpec((K, cb), lambda c, *_: (0, c))]
            + [pl.BlockSpec((1, cb), lambda c, *_: (0, c))] * has_bias
            + [pl.BlockSpec((T, 2), lambda c, *_: (0, 0)),
               pl.BlockSpec((R, 3), lambda c, *_: (0, 0)), tail_block],
            out_specs=[pl.BlockSpec((T, cb), lambda c, *_: (0, c)),
                       tail_block]),
        out_shape=[jax.ShapeDtypeStruct((T, C), x.dtype),
                   jax.ShapeDtypeStruct(tail.shape, tail.dtype)],
        input_output_aliases={5 + has_bias: 1},
        interpret=_interpret(),
        name=KERNELS.ssm_conv,
    )(jnp.asarray(layer, jnp.int32).reshape(1), x, w,
      *([b.reshape(1, C)] if has_bias else []), tok, row, tail)
    return y, tail


# -- the scan ---------------------------------------------------------------
def _scan_positions(n, x_ref, b_ref, c_ref, col_ref, row_ref, s_in, s_out,
                    y_ref, zero, cum_end_of, *, HB, P):
    """The chunk formulas over the block's first n positions. `cum_end_of`
    gives a head's cum at the row's end as a scalar (from SMEM: a [1, 1]
    vector does not broadcast over a [P, N] state in the compiler)."""
    Bm = b_ref[0, :n, :].astype(_F32)                            # [n, N]
    Cm = c_ref[0, :n, :].astype(_F32)
    G = jax.lax.dot_general(Cm, Bm, _NT, precision=_HI,
                            preferred_element_type=_F32)         # [n, n]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
              <= jax.lax.broadcasted_iota(jnp.int32, (n, n), 0))
    for i in range(HB):
        dt_col = col_ref[0, 0, :n, i:i + 1]                      # [n, 1]
        cum_col = col_ref[0, 0, :n, HB + i:HB + i + 1]
        dt_row = row_ref[0, 0, i:i + 1, :n]                      # [1, n]
        cum_row = row_ref[0, 0, HB + i:HB + i + 1, :n]
        decay = jnp.where(causal,
                          jnp.exp(jnp.minimum(cum_col - cum_row, 0.0)), 0.0)
        xh = x_ref[0, :n, i * P:(i + 1) * P].astype(_F32)        # [n, P]
        S0 = jnp.where(zero, 0.0, s_in[0, 0, i].astype(_F32))    # [P, N]
        y = jax.lax.dot_general(G * decay * dt_row, xh,
                                (((1,), (0,)), ((), ())), precision=_HI,
                                preferred_element_type=_F32)
        y = y + jnp.exp(cum_col) * jax.lax.dot_general(
            Cm, S0, _NT, precision=_HI, preferred_element_type=_F32)
        cum_end = cum_end_of(i)
        keep = jnp.exp(jnp.full((1, S0.shape[1]), cum_end, _F32))
        S1 = keep * S0 + jax.lax.dot_general(
            xh * (jnp.exp(cum_end - cum_col) * dt_col), Bm, _TN,
            precision=_HI, preferred_element_type=_F32)
        y_ref[0, :n, i * P:(i + 1) * P] = y.astype(y_ref.dtype)
        s_out[0, 0, i] = S1.astype(s_out.dtype)


def _scan_kernel(layer_ref, rows_ref, n_ref, qlens_ref, reset_ref, cend_ref,
                 x_ref, b_ref, c_ref, col_ref, row_ref, s_in, y_ref, s_out, *,
                 C, HB, P, H):
    w, n = pl.program_id(0), n_ref[0]
    r = rows_ref[w]
    # past the list's end: the last real step's blocks, and no work
    ql = jnp.where(w < n, qlens_ref[r], -1)
    zero = reset_ref[r] > 0
    head0 = r * H + pl.program_id(1) * HB
    run = functools.partial(
        _scan_positions, x_ref=x_ref, b_ref=b_ref, c_ref=c_ref,
        col_ref=col_ref, row_ref=row_ref, s_in=s_in, s_out=s_out,
        y_ref=y_ref, zero=zero, cum_end_of=lambda i: cend_ref[head0 + i],
        HB=HB, P=P)
    short = min(SUB, C)

    @pl.when((ql > 0) & (ql <= short))
    def _short():
        run(short)

    if C > short:
        @pl.when(ql > short)
        def _chunk():
            run(C)

    # a list with no real entry still writes the block it stayed on back
    @pl.when(n == 0)
    def _keep():
        s_out[...] = s_in[...]


def ssm_scan(x, B, Cm, dt, cum, state, layer, q_lens, reset, *, groups):
    """One pass of the selective-state recurrence over every row's chunk.
    x: [R, C, heads*P]; B, Cm: [R, C, groups*N]; dt: [R, C, heads] f32,
    0 past a row's length; cum: [R, C, heads] f32, the running sum of
    dt*A along C; state: [L, R, heads, P, N]; q_lens, reset: [R] (a reset
    row starts from a zero state). Returns (y [R, C, heads*P] f32 — rows
    and positions without a token hold nothing defined —, state aliased
    to the one given, holding each row's state after its last token)."""
    R, C, _ = x.shape
    _, _, H, P, N = state.shape
    HB = _head_block(H, groups)
    nhb, per_group = H // HB, (H // groups) // HB
    if C % SUB:     # the decode pass: pad its one position to a tile
        pad = ((0, 0), (0, SUB - C % SUB), (0, 0))
        x, B, Cm, dt = (jnp.pad(a, pad) for a in (x, B, Cm, dt))
        cum = jnp.pad(cum, pad, mode="edge")    # constant past the end
    Cp = x.shape[1]
    both = jnp.concatenate([dt.reshape(R, Cp, nhb, HB),
                            cum.reshape(R, Cp, nhb, HB)], axis=-1)
    col = both.transpose(0, 2, 1, 3)                # [R, nhb, Cp, 2*HB]
    row = both.transpose(0, 2, 3, 1)                # [R, nhb, 2*HB, Cp]
    rows, n = _active_rows(q_lens)

    # index maps: grid step (w, hb) works on row rows[w], head block hb;
    # past the n real rows it stays on the last real step's blocks
    def at(w, hb, rows, n):
        return rows[w], jnp.where(w < n[0], hb, nhb - 1)

    def heads_idx(w, hb, layer, rows, n, *_):   # x, y: [R, Cp, heads*P]
        r, hb = at(w, hb, rows, n)
        return (r, 0, hb)

    def group_idx(w, hb, layer, rows, n, *_):   # B, C: the block's group
        r, hb = at(w, hb, rows, n)
        return (r, 0, hb // per_group)

    def steps_idx(w, hb, layer, rows, n, *_):   # col, row: [R, nhb, ., .]
        r, hb = at(w, hb, rows, n)
        return (r, hb, 0, 0)

    def state_idx(w, hb, layer, rows, n, *_):
        r, hb = at(w, hb, rows, n)
        return (layer[0], r, hb, 0, 0)

    state_block = pl.BlockSpec((1, 1, HB, P, N), state_idx)
    prefetch = (jnp.asarray(layer, jnp.int32).reshape(1), rows, n,
                q_lens.astype(jnp.int32), reset.astype(jnp.int32),
                cum[:, -1].reshape(R * H))
    kernel = functools.partial(_scan_kernel, C=Cp, HB=HB, P=P, H=H)
    common = dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(R, nhb),
            in_specs=[pl.BlockSpec((1, Cp, HB * P), heads_idx),
                      pl.BlockSpec((1, Cp, N), group_idx),
                      pl.BlockSpec((1, Cp, N), group_idx),
                      pl.BlockSpec((1, 1, Cp, 2 * HB), steps_idx),
                      pl.BlockSpec((1, 1, 2 * HB, Cp), steps_idx),
                      state_block],
            out_specs=[pl.BlockSpec((1, Cp, HB * P), heads_idx),
                       state_block]),
        out_shape=[jax.ShapeDtypeStruct((R, Cp, H * P), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={len(prefetch) + 5: 1},
        interpret=_interpret())
    # one body, two names: a pass of single tokens is the state update
    if Cp == SUB:
        call = pl.pallas_call(kernel, name=KERNELS.ssm_state_update, **common)
    else:
        call = pl.pallas_call(kernel, name=KERNELS.ssm_chunk_scan, **common)
    y, state = call(*prefetch, x, B, Cm, col, row, state)
    return y[:, :C], state
