"""Absorbed multi-head latent attention over the paged latent pools (Pallas).

A latent cache holds, a token and layer, one compressed vector ``c`` [C]
and one rotary key ``r`` [Rd] for ALL heads (`latent_append`; the pool's
contract is stated in inference/ragged_step.py). In the ABSORBED form a
head's key up-projection is folded into its query and its value
up-projection is applied after the sum, so the page is both the keys and
the values and no per-head key or value ever exists:

    s[h, t] = (qa[h] . c[t] + qr[h] . r[t]) * scale        qa = q_nope W_UK^T
    u[h]    = sum_t softmax_t(s[h, :]) c[t]                 o[h] = u[h] W_UV

so the heads of ONE token are the rows of one MXU tile (128 heads: one
``[128, C + Rd] x [C + Rd, bs]`` product a page, then ``[128, bs] x [bs,
C]``): a page's 1,152 B a token meet 2 x 128 x (2 C + Rd) flop, which is
the chip's ridge. Cost follows the descriptors, as in
`ragged_paged_attention`:

  * the pools are the engine's WHOLE buffers, ``[L, 1, NB, bs, C]`` and
    ``[L, 1, NB, bs, Rd]``, in HBM (``pl.ANY``); the layer rides scalar
    prefetch beside the block tables; a page is one copy of each pool's
    ``[bs, .]`` tile into one of two VMEM buffers (``kp`` pages a buffer:
    a step of the page loop is one product over ``kp x bs`` keys), the
    next step's copies in flight while this one is attended;
  * queries come and go PACKED: ``qa [T, H, C]``, ``qr [T, H, Rd]`` and
    the output ``[T, H, C]``, row r's positions at ``[starts[r], starts[r]
    + q_lens[r])``. A token's ``[H, .]`` is whole tiles, so a token is one
    copy in and one out, and only the tokens a row owns move. The output
    is aliased to a zeroed operand: a position no row owns reads zero;
  * the grid walks a WORK LIST of query tiles (`_items`: row, first chunk
    position, tokens), n of at most W; a step past n does nothing. A row
    of one token (every decode row) is one item on the DECODE arm: 128
    rows a product. A prefill chunk is ``ceil(q_len / tq)`` items on the
    CHUNK arm: ``tq`` tokens' heads folded into ``tq x H`` rows of one
    product, each item streaming the row's pages up to its own last
    position (a chunk against a long prefix is compute-bound: its pages
    come in ``tq x H`` rows' worth of arithmetic apart);
  * pages every token of the item sees whole take no mask; the causal
    mask (``key position <= query position``) and the item's raggedness
    (``token < n``) apply on the pages at the boundary only;
  * online soft-max, float32 scores, statistics and accumulator.

Naming rule: every ``pallas_call`` that does latent attention for the
serving step is named ``KERNELS.mla_paged_attn``; the benchmark's
``mla_attn_hbm_pct`` and ``mla_attn_mxu_pct`` divide the step's bytes and
operations by the device time of kernels of exactly that name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import LANES as _LANES
from ._common import interpret as _interpret
from ...observability.trace import KERNELS

__all__ = ["mla_paged_attention"]

_NEG_INF = -1e30


def _items(q_lens, *, tq, c_att, T):
    """The query tiles a pass runs: their count n, then [W] vectors of
    (row, first chunk position, tokens). W is the static bound; entries
    past n are never read."""
    R = q_lens.shape[0]
    W = R if c_att == 1 else min(R * -(-c_att // tq), R + T // tq)
    count = -(-q_lens // tq)
    ends = jnp.cumsum(count)
    w = jnp.arange(W, dtype=jnp.int32)
    row = jnp.minimum(jnp.searchsorted(ends, w, side="right"), R - 1)
    c0 = (w - (ends[row] - count[row])) * tq
    n_tok = jnp.clip(q_lens[row] - c0, 0, tq)
    return tuple(a.astype(jnp.int32)
                 for a in (ends[-1].reshape(1), row, c0, n_tok))


def _mla_kernel(tables_ref, starts_ref, pos0_ref, layer_ref, n_ref, row_ref,
                c0_ref, ntok_ref, qa_hbm, qr_hbm, c_hbm, r_hbm, _, o_hbm,
                qa_buf, qr_buf, cbuf, rbuf, obuf, m_sc, l_sc, acc_sc, psem,
                qsem, osem, *, scale, bs, tq, kp):
    w = pl.program_id(0)
    H = qa_buf.shape[1]
    nb = tables_ref.shape[1]
    layer = layer_ref[0]

    def arm(nt):
        """One item on `nt` token slots (1: the decode arm; tq: the chunk
        arm): the tokens' heads are the rows of every product."""
        rows = nt * H
        r, c0, n = row_ref[w], c0_ref[w], ntok_ref[w]
        at = starts_ref[r] + c0          # the item's first packed position
        first = pos0_ref[r] + c0         # ... and its position in the row

        def page_copies(j, slot):
            """The `kp` pages of step j, one copy of each pool a page. A
            page past the item's last is the last again: its keys lie
            behind every query of the item, which the mask hides."""
            copies = []
            for i in range(kp):
                page = tables_ref[r, jax.lax.min(j * kp + i, pages - 1)]
                rows = pl.ds(i * bs, bs)
                copies += [
                    pltpu.make_async_copy(c_hbm.at[layer, 0, page],
                                          cbuf.at[slot, rows],
                                          psem.at[0, slot]),
                    pltpu.make_async_copy(r_hbm.at[layer, 0, page],
                                          rbuf.at[slot, rows],
                                          psem.at[1, slot])]
            return copies

        def token_copies(i):
            return (pltpu.make_async_copy(qa_hbm.at[at + i], qa_buf.at[i],
                                          qsem.at[0]),
                    pltpu.make_async_copy(qr_hbm.at[at + i], qr_buf.at[i],
                                          qsem.at[1]))

        def each_token(act):
            for i in range(nt):     # static: nt is 1 or tq
                if nt == 1:
                    act(i)
                else:
                    pl.when(i < n)(functools.partial(act, i))

        # steps [0, whole) of `kp` pages lie before the item's FIRST
        # position: every token sees them whole; steps [whole, total) hold
        # the boundary
        KB = kp * bs
        pages = jax.lax.clamp(1, jax.lax.div(first + n + bs - 1, bs), nb)
        total = jax.lax.div(pages + kp - 1, kp)
        whole = jax.lax.min(jax.lax.div(first + 1, KB), total)
        for copy in page_copies(0, 0):
            copy.start()

        def fetch(i):
            for copy in token_copies(i):
                copy.start()

        def fetched(i):
            for copy in token_copies(i):
                copy.wait()

        each_token(fetch)
        each_token(fetched)
        m_sc[:rows] = jnp.full((rows, _LANES), _NEG_INF, jnp.float32)
        l_sc[:rows] = jnp.zeros((rows, _LANES), jnp.float32)
        acc_sc[:rows] = jnp.zeros((rows, acc_sc.shape[1]), jnp.float32)

        def page_step(masked, j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < total)
            def _next_page():
                for copy in page_copies(j + 1, 1 - slot):
                    copy.start()

            for copy in page_copies(j, slot):
                copy.wait()
            qa = qa_buf[:nt].reshape(rows, qa_buf.shape[2])
            qr = qr_buf[:nt].reshape(rows, qr_buf.shape[2])
            c, rk = cbuf[slot], rbuf[slot]
            nt_dims = (((1,), (1,)), ((), ()))
            s = (jax.lax.dot_general(qa, c, nt_dims,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr, rk, nt_dims,
                                       preferred_element_type=jnp.float32)
                 ) * scale                                   # [rows, KB]
            if masked:
                tok = jax.lax.div(
                    jax.lax.broadcasted_iota(jnp.int32, (rows, KB), 0), H)
                col = jax.lax.broadcasted_iota(jnp.int32, (rows, KB), 1)
                ok = (tok < n) & (j * KB + col <= first + tok)
                s = jnp.where(ok, s, _NEG_INF)
            m_prev = m_sc[:rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(ok, p, 0.0)
            l_sc[:rows] = jnp.broadcast_to(
                l_sc[:rows, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
                (rows, _LANES))
            m_sc[:rows] = jnp.broadcast_to(m_new, (rows, _LANES))
            acc_sc[:rows] = acc_sc[:rows] * alpha + jnp.dot(
                p.astype(c.dtype), c, preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, whole, functools.partial(page_step, False),
                          None)
        jax.lax.fori_loop(whole, total, functools.partial(page_step, True),
                          None)
        l = l_sc[:rows, :1]
        dead = (l == 0.0) | (m_sc[:rows, :1] <= _NEG_INF * 0.5)
        inv = jnp.where(dead, 0.0, 1.0 / jnp.maximum(l, 1e-37))
        obuf[:nt] = (acc_sc[:rows] * inv).astype(obuf.dtype).reshape(
            (nt,) + obuf.shape[1:])

        def out_copy(i):
            return pltpu.make_async_copy(obuf.at[i], o_hbm.at[at + i],
                                         osem.at[0])
        each_token(lambda i: out_copy(i).start())
        each_token(lambda i: out_copy(i).wait())

    live = w < n_ref[0]
    if tq == 1:
        pl.when(live)(lambda: arm(1))
    else:
        one = ntok_ref[w] <= 1
        pl.when(live & one)(lambda: arm(1))
        pl.when(live & jnp.logical_not(one))(lambda: arm(tq))


def mla_paged_attention(qa, qr, c_pool, r_pool, block_tables, starts, q_lens,
                        kv_lens, scale: float, layer=0, *, c_att: int,
                        tq: int = 8, kp: int = 4):
    """qa: [T, H, C], the step's PACKED absorbed queries (``q_nope
    W_UK^T``), qr: [T, H, Rd], their rotary parts — row r's chunk occupies
    positions [starts[r], starts[r] + q_lens[r]); ``c_att`` (static) is
    the longest chunk a row may hold; pools: [L, 1, NB, bs, C] and
    [L, 1, NB, bs, Rd] with ``layer`` the (traced) layer to attend over;
    block_tables: [R, nb]; q_lens: [R] (0 = inactive row); kv_lens: [R],
    the TOTAL length including this chunk (query c sits at position
    kv_lens - q_lens + c); ``tq``: tokens a chunk-arm item folds into one
    product's rows; ``kp``: pages a step of the page loop attends at once
    (one product over ``kp x bs`` keys), at most a table's width. Both are
    the values measured on the chip at bs = 128 (PERF.md, PR 51: 4 pages a
    step against 1 is 45.9% against 25% of the decode arm's roofline; 8
    are untried) → [T, H, C] in qa's dtype, packed as qa is: ``u[t, h]
    = sum_keys softmax(...) c[key]``, every position no row owns zero."""
    T, H, C = qa.shape
    Rd = qr.shape[2]
    R = block_tables.shape[0]
    _, _, _, bs, _ = c_pool.shape
    Cc = min(c_att, T)
    tq = 1 if Cc == 1 else min(tq, Cc)
    kp = min(kp, block_tables.shape[1])
    q_lens = q_lens.astype(jnp.int32)
    n, row, c0, n_tok = _items(q_lens, tq=tq, c_att=Cc, T=T)
    W = row.shape[0]
    prefetch = [block_tables.astype(jnp.int32), starts.astype(jnp.int32),
                (kv_lens - q_lens).astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1), n, row, c0, n_tok]
    rows = tq * H
    item = qa.dtype.itemsize
    vmem = (2 * tq * H * C * item + tq * H * max(Rd, _LANES) * item
            + 2 * kp * bs * (C + max(Rd, _LANES)) * c_pool.dtype.itemsize
            + 4 * rows * (C + 2 * _LANES))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(W,),
        in_specs=[hbm, hbm, hbm, hbm, hbm],
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((tq, H, C), qa.dtype),
            pltpu.VMEM((tq, H, Rd), qr.dtype),
            pltpu.VMEM((2, kp * bs, C), c_pool.dtype),
            pltpu.VMEM((2, kp * bs, Rd), r_pool.dtype),
            pltpu.VMEM((tq, H, C), qa.dtype),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, C), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, bs=bs, tq=tq, kp=kp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qa.shape, qa.dtype),
        input_output_aliases={len(prefetch) + 4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the buffers above and as much again for the score tiles
            vmem_limit_bytes=min(max(3 * vmem, 32 << 20), 96 << 20)),
        interpret=_interpret(),
        name=KERNELS.mla_paged_attn,
    )(*prefetch, qa, qr, c_pool, r_pool, jnp.zeros_like(qa))
