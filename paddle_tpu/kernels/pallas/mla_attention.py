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
  * the grid walks a WORK LIST of items (`mla_items`, made once a pass and
    not once a layer), n of at most W; a step past n does nothing. Items
    are of two kinds. A CHUNK item is ``tq`` tokens of one prefill chunk
    (``ceil(q_len / tq)`` of them a row, a last one of one token aside),
    their heads folded into ``tq x H`` rows of one product, streaming the
    row's pages up to the item's own last position (a chunk against a
    long prefix is compute-bound: its pages come in ``tq x H`` rows' worth
    of arithmetic apart). A GROUP item is a few rows' LAST tokens, one
    token a member: every decode row is a member of one;
  * what decides a group (`decode_groups`, a function of the tables and
    the lengths alone): rows of one token whose tables name the same
    first ``kp`` pages, in row order, at most `_GROUP_ROWS`' largest to a
    group (more rows on one prefix make a second group); its SHARED pages
    are the leading table columns on which every member agrees with the
    first, cut to the pages wholly before every member's query position
    and to whole steps of ``kp``. A row with no companion (another
    document, a chunk's last token) is a group of one, its shared pages
    its own;
  * a group item runs in two phases on ONE set of statistics. Phase A
    walks the shared pages once, through the first member's table, the
    members' heads folded into ``g x H`` rows of each product, no mask
    (every key lies behind every query): a page is copied from HBM and
    loaded into the MXU once for the group, not once a row. Phase B walks
    each member's own pages, those from the shared count to its position,
    on its own ``H`` rows of the same running maximum, sum and
    accumulator, the causal mask on the steps at the boundary: the online
    soft-max carries from A to B a row, so there is no second pass and no
    merge, and the next step's copies stay in flight across the phases
    and from one member to the next. One normalisation and one copy out a
    member. The products' row counts are static, so phase A is compiled
    for the group sizes `_GROUP_ROWS` and a group takes the smallest that
    holds it;
  * a chunk item's pages every token sees whole take no mask; the causal
    mask (``key position <= query position``) and the item's raggedness
    (``token < n``) apply on the pages at the boundary only;
  * online soft-max, float32 scores, statistics and accumulator.

What sharing is worth (PERF.md, PR 52: one layer alone on the chip at the
docqa cell's shapes, 64 decode rows over 17.9k positions of 16 documents,
share of the MXU peak for the counted pairs): a product pays a fixed cost
a page tile it loads whatever streams through it, so rows alone (128 rows
a product) run at 45-48%, groups of 2 / 3 / 4 / 5 at 60 / 67 / 71.5 /
71.7%, of 8 at 73% (the chunk arm's 1,024 rows: 78%): it saturates at
four to five rows, hence `_GROUP_ROWS`. Four rows a document: 3.43 ->
2.28 ms a layer; tables with no common head: 3.41, the parent's. Eight
pages a step read 2-3% under four with groups (13% with rows alone) and
are not taken: twice the page buffers for ~1% of the cell's step.

Naming rule: every ``pallas_call`` that does latent attention for the
serving step is named ``KERNELS.mla_paged_attn``; the benchmark's
``mla_attn_hbm_pct`` and ``mla_attn_mxu_pct`` divide the step's bytes and
operations (a row's whole context, whoever shares it) by the device time
of kernels of exactly that name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import LANES as _LANES
from ._common import interpret as _interpret
from ...observability.trace import KERNELS

__all__ = ["decode_groups", "shared_pages", "mla_items",
           "mla_paged_attention"]

_NEG_INF = -1e30
# pages a step of the page loop attends, and tokens a chunk item folds.
# Measured on the chip at bs = 128 (PERF.md, PR 51: 4 pages a step against 1
# is 45.9% against 25% of a lone decode row's roofline)
KP, TQ = 4, 8
# the member counts phase A's product is compiled for; the largest is the
# most members a group has. Measured (PERF.md, PR 52): on the cell's mix of
# 2-6 rows a document 1-5 is 1.7% behind all of 1-8, 1-4 4.0%, (1, 2, 4, 8)
# 16% (a group padded to the next size pays for rows that are not there)
_GROUP_ROWS = (1, 2, 3, 4, 5)


def decode_groups(tables, q_lens, kv_lens, *, bs, kp, cap, xp=jnp):
    """Which rows attend their leading pages together. Per row [R]: its
    group's first member (itself: a group of one), its place among the
    members, the group's size, and the group's shared STEPS (of ``kp``
    pages: what phase A walks). A row's item is its LAST token's, at
    position ``kv_lens - 1``: only rows of one token (``q_lens == 1``)
    find companions. Written once for the device's work list (``xp`` =
    jax.numpy) and the host's count of it (numpy)."""
    R, nb = tables.shape
    ar = xp.arange(R)
    # pages wholly before the row's query position: the page that holds it
    # stays the row's own, so every member has a phase B
    before = xp.clip((kv_lens - 1) // bs, 0, nb - 1)
    cand = (q_lens == 1) & (before >= kp)
    head = tables[:, :kp]
    same = ((head[:, None] == head[None]).all(-1) & cand[:, None] & cand[None]
            ) | (ar[:, None] == ar[None])
    rank = (same & (ar[None] < ar[:, None])).sum(1)
    group = same & ((rank // cap)[:, None] == (rank // cap)[None])
    leader = group.argmax(1)
    agree = xp.cumprod((tables == tables[leader]).astype(xp.int32),
                       axis=1).sum(1)
    shared = xp.where(group, xp.minimum(agree, before)[None], nb).min(1)
    return leader, rank % cap, group.sum(1), shared // kp


def shared_pages(tables, q_lens, kv_lens, *, bs):
    """The (row, page) pairs of a pass that phase A of a group of two or
    more rows attends, as the kernel groups them by itself (host side:
    numpy arrays in, an int out)."""
    if (q_lens == 1).sum() < 2:
        return 0
    kp = min(KP, tables.shape[1])
    _, _, size, steps = decode_groups(tables, q_lens, kv_lens, bs=bs, kp=kp,
                                      cap=_group_cap(TQ), xp=np)
    return int((steps * kp)[(q_lens == 1) & (size > 1)].sum())


def mla_items(block_tables, q_lens, kv_lens, *, bs, c_att, T, tq=TQ, kp=KP):
    """The items a pass runs, the same for every layer: their count n,
    then [W] vectors of (row, first chunk position, tokens, members, shared
    steps) and the members' rows [W * cap]. ``tokens > 1`` is a chunk item
    of `row`; else the item is the group that `row` leads. W is the static
    bound; entries past n are never read."""
    R, nb = block_tables.shape
    tc, cap, kp = _chunk_tokens(c_att, T, tq), _group_cap(tq), min(kp, nb)
    q_lens = q_lens.astype(jnp.int32)
    leader, place, size, steps = decode_groups(
        block_tables, q_lens, kv_lens.astype(jnp.int32), bs=bs, kp=kp,
        cap=cap)
    ar = jnp.arange(R, dtype=jnp.int32)
    # a row's last tile of ONE token is its group item's, not a chunk's
    last1 = (q_lens % tc == 1) if tc > 1 else (q_lens == 1)
    chunks = -(-q_lens // tc) - last1 if tc > 1 else jnp.zeros_like(q_lens)
    count = chunks + (last1 & (leader == ar))
    W = R if tc == 1 else min(R * -(-min(c_att, T) // tc), R + T // tc)
    ends = jnp.cumsum(count)
    w = jnp.arange(W, dtype=jnp.int32)
    row = jnp.minimum(jnp.searchsorted(ends, w, side="right"), R - 1)
    k = w - (ends[row] - count[row])
    chunk = k < chunks[row]
    c0 = jnp.where(chunk, k * tc, q_lens[row] - 1)
    n_tok = jnp.where(chunk, jnp.clip(q_lens[row] - c0, 0, tc), 1)
    members = jnp.zeros((R, cap), jnp.int32).at[leader, place].set(ar)[row]
    return tuple(a.astype(jnp.int32) for a in (
        ends[-1].reshape(1), row, c0, n_tok, size[row], steps[row],
        members.reshape(-1)))


def _chunk_tokens(c_att, T, tq):
    """Tokens a chunk item folds (two at least: a tile of one token is a
    group item's); 1 where no row holds a chunk and no item is one."""
    return 1 if min(c_att, T) == 1 else max(2, min(tq, c_att, T))


def _group_cap(tq):
    """The most members of a group: the largest product phase A has."""
    return min(tq, max(_GROUP_ROWS))


def _mla_kernel(tables_ref, starts_ref, pos0_ref, layer_ref, n_ref, row_ref,
                c0_ref, ntok_ref, size_ref, steps_ref, member_ref, qa_hbm,
                qr_hbm, c_hbm, r_hbm, _, o_hbm, qa_buf, qr_buf, cbuf, rbuf,
                obuf, m_sc, l_sc, acc_sc, psem, qsem, osem, *, scale, bs, tc,
                sizes, kp):
    w = pl.program_id(0)
    H = qa_buf.shape[1]
    nb = tables_ref.shape[1]
    layer = layer_ref[0]
    KB = kp * bs
    cap = sizes[-1]

    def extent(last):
        """(pages, steps) a row's table holds up to position `last`."""
        pages = jax.lax.clamp(1, jax.lax.div(last, bs) + 1, nb)
        return pages, jax.lax.div(pages + kp - 1, kp)

    def page_copies(r, pages, j, slot):
        """The `kp` pages of step j of row r, one copy of each pool a
        page. A page past the row's last is the last again: its keys lie
        behind every query of the item, which the mask hides."""
        copies = []
        for i in range(kp):
            page = tables_ref[r, jax.lax.min(j * kp + i, pages - 1)]
            rows = pl.ds(i * bs, bs)
            copies += [
                pltpu.make_async_copy(c_hbm.at[layer, 0, page],
                                      cbuf.at[slot, rows], psem.at[0, slot]),
                pltpu.make_async_copy(r_hbm.at[layer, 0, page],
                                      rbuf.at[slot, rows], psem.at[1, slot])]
        return copies

    def start(copies):
        for copy in copies:
            copy.start()

    def wait(copies):
        for copy in copies:
            copy.wait()

    def token_in(at, i):
        """The packed token `at` into slot i of the query buffers."""
        return (pltpu.make_async_copy(qa_hbm.at[at], qa_buf.at[i],
                                      qsem.at[0]),
                pltpu.make_async_copy(qr_hbm.at[at], qr_buf.at[i],
                                      qsem.at[1]))

    def token_out(at, i):
        return (pltpu.make_async_copy(obuf.at[i], o_hbm.at[at], osem.at[0]),)

    def rows_of(i, nt):
        """The statistics' rows of the `nt` tokens from slot i."""
        at = i * H if isinstance(i, int) else pl.multiple_of(i * H, H)
        return pl.ds(at, nt * H)

    def reset(i, nt):
        rows = rows_of(i, nt)
        m_sc[rows] = jnp.full((nt * H, _LANES), _NEG_INF, jnp.float32)
        l_sc[rows] = jnp.zeros((nt * H, _LANES), jnp.float32)
        acc_sc[rows] = jnp.zeros((nt * H, acc_sc.shape[1]), jnp.float32)

    def attend(r, pages, j, slot, ahead, i, nt, mask):
        """Step j of row r's pages, waited for in buffer `slot`, against
        the `nt` tokens from slot i of the query buffers (their heads the
        product's rows), `ahead` having started what comes next into the
        other buffer. mask: None where every key of the step lies behind
        every query, else (first position, tokens that are real)."""
        ahead(1 - slot)
        wait(page_copies(r, pages, j, slot))
        rows, at = nt * H, rows_of(i, nt)
        qa = qa_buf[pl.ds(i, nt)].reshape(rows, qa_buf.shape[2])
        qr = qr_buf[pl.ds(i, nt)].reshape(rows, qr_buf.shape[2])
        c, rk = cbuf[slot], rbuf[slot]
        nt_dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qa, c, nt_dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, rk, nt_dims,
                                   preferred_element_type=jnp.float32)
             ) * scale                                       # [rows, KB]
        if mask is not None:
            first, n = mask
            tok = jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, (rows, KB), 0), H)
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, KB), 1)
            ok = (tok < n) & (j * KB + col <= first + tok)
            s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_sc[at, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(ok, p, 0.0)
        l_sc[at] = jnp.broadcast_to(
            l_sc[at, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            (rows, _LANES))
        m_sc[at] = jnp.broadcast_to(m_new, (rows, _LANES))
        acc_sc[at] = acc_sc[at] * alpha + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)

    def walk(lo, hi, step):
        jax.lax.fori_loop(lo, hi, lambda j, carry: step(j) or carry, 0)

    def finish(i, nt):
        at = rows_of(i, nt)
        l = l_sc[at, :1]
        dead = (l == 0.0) | (m_sc[at, :1] <= _NEG_INF * 0.5)
        inv = jnp.where(dead, 0.0, 1.0 / jnp.maximum(l, 1e-37))
        obuf[pl.ds(i, nt)] = (acc_sc[at] * inv).astype(obuf.dtype).reshape(
            (nt,) + obuf.shape[1:])

    def each(count, limit, act):
        """act(i) for the i < count of a static `limit`."""
        for i in range(limit):
            if limit == 1:
                act(i)
            else:
                pl.when(i < count)(functools.partial(act, i))

    def chunk_item():
        """`tc` token slots of one row's chunk: steps [0, whole) of `kp`
        pages lie before the item's FIRST position, every token sees them
        whole; steps [whole, total) hold the boundary."""
        r, c0, n = row_ref[w], c0_ref[w], ntok_ref[w]
        at = starts_ref[r] + c0          # the item's first packed position
        first = pos0_ref[r] + c0         # ... and its position in the row
        pages, total = extent(first + n - 1)
        whole = jax.lax.min(jax.lax.div(first + 1, KB), total)
        start(page_copies(r, pages, 0, 0))
        each(n, tc, lambda i: start(token_in(at + i, i)))
        each(n, tc, lambda i: wait(token_in(at + i, i)))
        reset(0, tc)

        def step(mask, j):
            def ahead(slot):
                pl.when(j + 1 < total)(
                    lambda: start(page_copies(r, pages, j + 1, slot)))
            attend(r, pages, j, jax.lax.rem(j, 2), ahead, 0, tc, mask)

        walk(0, whole, functools.partial(step, None))
        walk(whole, total, functools.partial(step, (first, n)))
        finish(0, tc)
        each(n, tc, lambda i: start(token_out(at + i, i)))
        each(n, tc, lambda i: wait(token_out(at + i, i)))

    def group_item():
        """Up to `cap` rows' last tokens, slot m of the buffers member m's:
        phase A, the `sa` steps all members share, on the group's rows at
        once; phase B, each member's own steps [sa, its total) on its own
        rows. Member 0 leads: phase A reads ITS table, and its own steps
        come first, so step sa follows step sa - 1 on one table."""
        c0, g, sa = c0_ref[w], size_ref[w], steps_ref[w]

        def member(m):
            r = member_ref[w * cap + m]
            first = pos0_ref[r] + c0
            return (r, first, starts_ref[r] + c0) + extent(first)

        r0, _, _, pages0, _ = member(0)
        start(page_copies(r0, pages0, 0, 0))
        each(g, cap, lambda m: start(token_in(member(m)[2], m)))

        def arrived(m):
            wait(token_in(member(m)[2], m))
            reset(m, 1)

        each(g, cap, arrived)
        lo = 0
        for rows in sizes:
            def shared(j, rows=rows):
                attend(r0, pages0, j, jax.lax.rem(j, 2),
                       lambda slot: start(page_copies(r0, pages0, j + 1,
                                                      slot)),
                       0, rows, None)
            pl.when((lo < g) & (g <= rows))(
                functools.partial(walk, 0, sa, shared))
            lo = rows

        def own(m, off):
            r, first, _, pages, total = member(m)
            whole = jax.lax.min(jax.lax.div(first + 1, KB), total)

            def step(mask, j):
                def ahead(slot):
                    pl.when(j + 1 < total)(
                        lambda: start(page_copies(r, pages, j + 1, slot)))

                    @pl.when((j + 1 == total) & (m + 1 < g))
                    def _next_member():
                        r2, _, _, pages2, _ = member(m + 1)
                        start(page_copies(r2, pages2, sa, slot))
                attend(r, pages, j, jax.lax.rem(off + j, 2), ahead, m, 1,
                       mask)

            walk(sa, whole, functools.partial(step, None))
            walk(whole, total, functools.partial(step, (first, 1)))
            return off + total - sa

        jax.lax.fori_loop(0, g, own, 0)

        def leave(m):
            finish(m, 1)
            start(token_out(member(m)[2], m))

        each(g, cap, leave)
        each(g, cap, lambda m: wait(token_out(member(m)[2], m)))

    live = w < n_ref[0]
    if tc == 1:
        pl.when(live)(group_item)
    else:
        one = ntok_ref[w] <= 1
        pl.when(live & one)(group_item)
        pl.when(live & jnp.logical_not(one))(chunk_item)


def mla_paged_attention(qa, qr, c_pool, r_pool, block_tables, starts, q_lens,
                        kv_lens, scale: float, layer=0, *, c_att: int,
                        tq: int = TQ, kp: int = KP, work=None):
    """qa: [T, H, C], the step's PACKED absorbed queries (``q_nope
    W_UK^T``), qr: [T, H, Rd], their rotary parts — row r's chunk occupies
    positions [starts[r], starts[r] + q_lens[r]); ``c_att`` (static) is
    the longest chunk a row may hold; pools: [L, 1, NB, bs, C] and
    [L, 1, NB, bs, Rd] with ``layer`` the (traced) layer to attend over;
    block_tables: [R, nb]; q_lens: [R] (0 = inactive row); kv_lens: [R],
    the TOTAL length including this chunk (query c sits at position
    kv_lens - q_lens + c); ``tq``: tokens a chunk item folds into one
    product's rows (a group item folds up to `_GROUP_ROWS`' largest rows,
    and no more than ``tq``); ``kp``: pages a step of the page loop
    attends at once (one product over ``kp x bs`` keys), at most a table's
    width. Both are the values measured on the chip at bs = 128 (the
    module's `KP`, `TQ`). ``work``: the pass's items
    as `mla_items` lists them from the same tables, lengths, ``tq`` and
    ``kp`` (made here when not given: a caller with several layers makes
    them once). Rows of one token whose tables share leading pages attend
    those pages together (the module docstring's group item); which rows
    do is decided from the tables alone and changes no result → [T, H, C]
    in qa's dtype, packed as qa is: ``u[t, h] = sum_keys softmax(...)
    c[key]``, every position no row owns zero."""
    T = qa.shape[0]
    bs = c_pool.shape[3]
    kp = min(kp, block_tables.shape[1])
    q_lens = q_lens.astype(jnp.int32)
    if work is None:
        work = mla_items(block_tables, q_lens, kv_lens, bs=bs, c_att=c_att,
                         T=T, tq=tq, kp=kp)
    prefetch = (block_tables.astype(jnp.int32), starts.astype(jnp.int32),
                (kv_lens - q_lens).astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1), *work)
    cap = _group_cap(tq)
    return _attend(prefetch, qa, qr, c_pool, r_pool, scale=scale,
                   tc=_chunk_tokens(c_att, T, tq), kp=kp,
                   sizes=tuple(sorted({s for s in _GROUP_ROWS if s < cap}
                                      | {cap})), interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("scale", "tc", "kp", "sizes", "interpret"))
def _attend(prefetch, qa, qr, c_pool, r_pool, *, scale, tc, kp, sizes,
            interpret):
    """The call itself, under its own `jit`: a step calls the kernel from
    several places (the prologue's scan and the periods', pass 1 and the
    burst, one program a burst size) and each call traces and lowers the
    kernel's body in Python, 0.4-0.8 s a call; with its own `jit` the body
    is traced once a shape for the process and lowered once a program
    (PERF.md, PR 52: set-up's warm-up phase read +24 s without it)."""
    T, H, C = qa.shape
    Rd = qr.shape[2]
    bs = c_pool.shape[3]
    W = prefetch[5].shape[0]
    slots = max(tc, sizes[-1])
    rows = slots * H
    item = qa.dtype.itemsize
    vmem = (2 * slots * H * C * item + slots * H * max(Rd, _LANES) * item
            + 2 * kp * bs * (C + max(Rd, _LANES)) * c_pool.dtype.itemsize
            + 4 * rows * (C + 2 * _LANES))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(W,),
        in_specs=[hbm, hbm, hbm, hbm, hbm],
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((slots, H, C), qa.dtype),
            pltpu.VMEM((slots, H, Rd), qr.dtype),
            pltpu.VMEM((2, kp * bs, C), c_pool.dtype),
            pltpu.VMEM((2, kp * bs, Rd), r_pool.dtype),
            pltpu.VMEM((slots, H, C), qa.dtype),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, C), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, bs=bs, tc=tc,
                          sizes=sizes, kp=kp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qa.shape, qa.dtype),
        input_output_aliases={len(prefetch) + 4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the buffers above and as much again for the score tiles
            vmem_limit_bytes=min(max(3 * vmem, 32 << 20), 96 << 20)),
        interpret=interpret,
        name=KERNELS.mla_paged_attn,
    )(*prefetch, qa, qr, c_pool, r_pool, jnp.zeros_like(qa))
