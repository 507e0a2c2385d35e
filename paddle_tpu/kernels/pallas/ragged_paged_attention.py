"""Unified ragged prefill+decode paged attention for TPU (Pallas).

One kernel serves a MIXED batch over the block-paged KV pool: each row of
the ragged batch is either a decode step (q_len = 1) or a chunked-prefill
slice (q_len = chunk, causal within the chunk, attending to every prior
KV page), with per-row ``(q_len, kv_len)`` descriptors riding SCALAR
PREFETCH next to the block tables — so the serving engine's whole step is
ONE compiled dispatch instead of a prefill program plus a decode program
(Ragged Paged Attention, arXiv:2604.15464; reference block kernels
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu).

The kernel's cost follows ``(starts, q_lens, kv_lens, tables)``, not the
static shapes ``(R, H_kv, nb, c_att)`` (paged_attention.py is the
decode-only kernel `models/generation.py` calls; the serving engine does
not):

  * the pool is the serving engine's WHOLE buffer, layer-major then
    head-major: ``[L, H_kv, num_blocks, bs, D]`` (+ ``[L, H_kv,
    num_blocks]`` scales), and the layer to attend over is a scalar that
    rides scalar prefetch — the caller never slices a layer out of the
    pool, so the compiled step holds no pool-shaped copy (the pool's
    contract is stated once, in inference/ragged_step.py; the scales are
    small, and the wrapper hands SMEM the one layer's). A 4-D ``[H_kv,
    num_blocks, bs, D]`` pool is the same call with ``L = 1``,
    ``layer = 0`` (decided from ``k_pool.ndim``);
  * grid ``(R,)``: one step a row, 64 a layer at the cells' batch where
    ``(R, H_kv, nb)`` was 16,384, each 0.15-0.3 us whether or not it
    carried work. The pool stays in HBM (``pl.ANY``); a row walks its own
    ``cdiv(kv_len, bs)`` pages in a loop, and each page is ONE strided
    copy of EVERY KV head's ``[bs, D]`` tile (``pool.at[layer, :, page]``,
    H_kv contiguous 32 KB pieces at 128 x 128 bf16) into one of two VMEM
    buffers, the next page's copy (the wide arm's next block's P copies)
    in flight while this one is attended.
    The row's last page or block starts the NEXT live row's first, so the
    stream does not drain between rows (grid steps run in order:
    ``dimension_semantics`` is ``arbitrary``). A table slot nobody owns
    costs nothing — no step, no fetch; an empty row (q_len = 0) costs its
    grid step and nothing else. Bytes and time both follow the
    descriptors;
  * queries come and go PACKED, as the step has them: ``q`` and the
    output are the ``[T, H_q, D]`` buffer that leaves `model.qkv` and
    enters `model.block_math`, row r at positions ``[starts[r], starts[r]
    + q_lens[r])``, rows in any order (the host lays decode rows by slot,
    then prefill rows). Both stay in HBM beside the pool. A live row
    copies its own positions into VMEM with ONE copy of static size (the
    narrow arm its ``8 // g`` positions, the wide arm the chunk), begun
    early enough to end inside the buffer and started where its first
    page copy is started, and writes back exactly its ``q_len`` positions
    in copies of static size (whole blocks of 8 positions, then single
    ones: at most ``c_att // 8 + 7``, waited for when the next live row's
    output is due). The output is aliased to a zeroed operand, so a position no
    row owns reads zero. No ``[R, c_att, H_q, D]`` tile of queries or of
    outputs exists on either side of the call (at the GPT cells' shapes
    the gather that built them wrote 33.5 MB a layer and the kernel moved
    0.5 MB a row in and out, live or not, for 0.8 MB of queries; PERF.md,
    PR 34). A row stages its queries head-major in VMEM once, folding the
    GQA group into the rows of one ``[g * c, D]`` MXU operand a KV head
    (row f is query head f // c of the group at chunk position f % c),
    and un-folds its output the same way; in-kernel masking applies
    causality (``col_pos <= kv_len - q_len + c``) and, on the narrow arm,
    raggedness (``c < q_len``; the wide arm's rows past ``q_len`` hold
    what an earlier row left there, mix with no other row and are never
    written back), so decode rows and prefill chunks share the grid with
    no inter-row padding;
  * a row's arithmetic is sized by its own ``q_len``, in two arms chosen
    from the prefetched descriptor (``pl.when``, static slices in each):
    a row whose folded queries fit one sublane tile (``q_len <= 8 // g``:
    every decode row, for MHA a short verify row too) stages and attends
    its first ``8 // g`` chunk positions only, every head of the page in
    ONE ``[H_kv, 8, bs]`` soft-max update (the heads' products are
    independent, so the MXU and the vector units pipeline across them).
    Any other row (a prefill chunk) takes the WIDE arm, whose cost is the
    vector units' walks over ``[rows, columns]`` f32 tiles, not its
    products (PERF.md, PR 57), so it makes those walks as few as it can:
    it fetches its pages a BLOCK of P at a time (P strided copies into the
    P slices of one ``[H_kv, P * bs, D]`` buffer; P from the static
    shapes, `_block_pages`) and makes one soft-max update a (block, KV
    head) over ``P * bs`` columns, so the running maximum and sum, their
    two broadcast stores and the accumulator's rescale are paid once a
    block, not once a page; a block takes the MASKED update only where an
    edge crosses it (`_edge_block`: its last position past the row's first
    query, so the chunk's own positions and the ragged end, or under a
    window its first position behind the last query's window), every
    other block the same update with no comparison and no select, decided
    from the prefetched descriptors; the staged queries are cast to the
    operand dtype once a row; the folded rows are walked in sub-tiles
    (`_tile_rows`), two updates side by side (`_UNROLL`). What is
    computed is what the narrow arm computes: operands in the pool's
    dtype, f32 scores scaled in f32, f32 statistics and accumulator, the
    same keys seen by the same queries. Both arms write the row's own
    ``q_len`` positions and no other. `wide_arm_pages` counts on the host,
    by the same rules, the pages the wide arm walks and those in masked
    blocks (the dispatch span's ``chunk_pages``, ``chunk_masked_pages``);
  * optional int8 / fp8 KV: pools stored quantized with per-(head, page)
    scales in the module's absmax convention (quantization/: dequant =
    q·s/qmax), dequantized IN-KERNEL: the page's values widen exactly to
    the query's dtype and each head's products take that head's scalar
    ``s/qmax`` from SMEM — decode is bandwidth-bound, so the kernel
    streams half the HBM bytes per step and a fixed pool budget admits
    ~2x the sequences;
  * online softmax (f32 scores, statistics and accumulator) in VMEM
    scratch, exactly like the training flash kernel.

Naming rule: every ``pallas_call`` that does attention for the ragged
step is named ``KERNELS.ragged_paged_attn``. The benchmark's
``attn_hbm_pct`` divides the step's KV bytes by the device time of kernels
of exactly that name: attention time under another name, or attention
arithmetic moved out of Pallas into XLA operations, makes it over-read.

Interpreter mode runs the same kernel on CPU (tier-1 parity tests).
Page-size guidance is unchanged from paged_attention.py: pick
block_size >= 128 on real TPUs; a page is the unit of every copy.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import LANES as _LANES
from ._common import interpret as _interpret
from ...observability.trace import KERNELS

__all__ = ["ragged_paged_attention", "wide_arm_pages"]

_NEG_INF = -1e30
# the narrow arm's tile height: one f32 sublane tile of folded queries
_NARROW = 8
# the wide arm's soft-max update, as step 0 timed it on the chip (PERF.md,
# PR 57: its cost is a soft-max UPDATE's latencies, ~1 us whatever the
# update's width, so few large updates, and two at a time so that one's
# products run beside the other's exponent): at most `_BLOCK_PAGES` pages a
# block (16 loses under a window, where every other block then holds an
# edge), a block's two K and two V buffers within `_BLOCK_BYTES` (4 pages at
# GPT's 16 KV heads, where 8 slowed the decode rows beside them), the folded
# rows in sub-tiles of at most `_TILE_ROWS`, `_UNROLL` updates side by side
_BLOCK_PAGES = 8
_BLOCK_BYTES = 8 << 20
_TILE_ROWS = 1024
_UNROLL = 2


def _block_pages(hkv, bs, D, itemsize):
    """Pages a soft-max update of the wide arm: a power of two, as many as
    keep the block buffers ([2, H_kv, P * bs, D] of K and of V) within
    `_BLOCK_BYTES`, at most `_BLOCK_PAGES`."""
    P = 1
    while (2 * P <= _BLOCK_PAGES
           and 4 * hkv * 2 * P * bs * D * itemsize <= _BLOCK_BYTES):
        P *= 2
    return P


def _tile_rows(g, ch):
    """Rows of a sub-tile of the wide arm's `g * ch` folded rows: whole
    chunks of the group's heads (ch * k, k a divisor of g) or a whole
    fraction of one chunk, so a sub-tile's chunk positions are one run;
    all the rows where neither fits `_TILE_ROWS`."""
    if g * ch <= _TILE_ROWS:
        return g * ch
    if ch <= _TILE_ROWS:
        k = max(k for k in range(1, g + 1)
                if g % k == 0 and ch * k <= _TILE_ROWS)
        return ch * k
    fits = [t for t in range(16, _TILE_ROWS + 1, 16) if ch % t == 0]
    return max(fits) if fits else g * ch


def _edge_block(j, q_len, kv_len, P, bs, window):
    """Whether the block of P pages at page j can hold a key that some
    query of the row does not see: its last position is past the row's
    FIRST query (the chunk's own positions, the ragged end, a slice past
    the last page) or, under a window, its first position is behind the row's LAST
    query's window (the trailing edge). Scalars in the kernel, arrays on
    the host."""
    edge = (j + P) * bs - 1 > kv_len - q_len
    if window is not None:
        edge = edge | (j * bs <= kv_len - 1 - window)
    return edge


def wide_arm_pages(q_lens, kv_lens, *, hq, hkv, bs, D, itemsize, c_att,
                   window=None):
    """What the wide arm walks in one call, counted on the host (numpy)
    by the kernel's own rules from the same descriptors: (pages, masked),
    the (row, page) pairs of the rows that take the wide arm and those of
    them in a block that takes the masked update."""
    g = hq // hkv
    if g * c_att <= _NARROW:
        return 0, 0
    cn = min(_NARROW // g, c_att)
    P = _block_pages(hkv, bs, D, itemsize)
    q_lens, kv_lens = (np.asarray(a, np.int64) for a in (q_lens, kv_lens))
    wide = q_lens > (cn if cn < c_att else 0)
    pages = masked = 0
    for ql, kl in zip(q_lens[wide], kv_lens[wide]):
        n = max(-(-kl // bs), 1)
        j0 = 0 if window is None else max(kl - ql - (window - 1), 0) // bs
        jb = np.arange(j0, n, P)
        edge = _edge_block(jb, ql, kl, P, bs, window)
        pages += int(n - j0)
        masked += int(np.minimum(P, n - jb)[edge].sum())
    return pages, masked


def _ragged_kernel(*refs, scale, bs, hq, C, P, RT, quantized, qmax, window):
    # scalar prefetch (the quantized pools' two scale tables last), the
    # operands where they lie in HBM (the fourth is the zeroed buffer the
    # output aliases), the output, scratch (last `qb`, the staged queries
    # in the operand dtype, where they are staged in another)
    (tables_ref, starts_ref, qlens_ref, kvlens_ref, layer_ref,
     next_ref), refs = refs[:6], refs[6:]
    (ks_ref, vs_ref), refs = (refs[:2], refs[2:]) if quantized else (
        (None, None), refs)
    (q_hbm, k_hbm, v_hbm, _, o_hbm, kbuf, vbuf, sem, io_sem, stream, qin,
     obuf, qs, ot, m_sc, l_sc, acc_sc, *cast) = refs
    qb = cast[0] if cast else qs
    r = pl.program_id(0)
    R, nb = tables_ref.shape
    T = q_hbm.shape[0]
    hkv = qs.shape[0]
    g = hq // hkv
    ql = qlens_ref[r]
    kl = kvlens_ref[r]
    layer = layer_ref[0]
    cn = min(_NARROW // g, C)   # chunk positions the narrow arm holds
    W = P * bs                  # key positions a block of the wide arm holds

    def first_page(row):
        """The first page `row` reads: 0, or under a window the page that
        holds the oldest position its first query attends (the pages
        before it are neither copied nor waited on)."""
        if window is None:
            return 0
        behind = kvlens_ref[row] - qlens_ref[row] - (window - 1)
        return jax.lax.div(jax.lax.max(behind, 0), bs)

    def pages(row):
        """One past `row`'s last page (scalars go through lax, not the
        jitted jnp helpers: non-negative operands need no sign fix-up)."""
        n = jax.lax.div(kvlens_ref[row] + bs - 1, bs)
        if window is None:
            return jax.lax.clamp(1, n, nb)
        return jax.lax.max(n, 1)    # a ring's pages are counted past its width

    def page_of(row, j):
        """`row`'s j-th page; under a window its table is a RING, page j
        in entry j % nb (the pages behind the window were given back)."""
        if window is None:
            return tables_ref[row, j]
        return tables_ref[row, jax.lax.rem(j, nb)]

    def page_copies(row, j, slot, wide, act):
        """Every KV head's tile of `row`'s j-th page: K and V, one strided
        copy each into buffer `slot`, started or waited for (`act`). The
        wide arm takes a BLOCK: pages [j, j + P) side by side in the
        buffer's P slices, as many of them as the row has (the slices
        past its last page keep what an earlier block left there: keys
        the mask hides, and values that are finite, since `vbuf` starts
        as zeros and holds nothing but pages since). A loop, not P copies
        written out: every line of the kernel is traced and lowered in
        every program's set-up."""
        def one(p, _):
            page = page_of(row, j + p)
            at = pl.ds(pl.multiple_of(p * bs, bs), bs)
            act(pltpu.make_async_copy(k_hbm.at[layer, :, page],
                                      kbuf.at[slot, :, at], sem.at[0, slot]))
            act(pltpu.make_async_copy(v_hbm.at[layer, :, page],
                                      vbuf.at[slot, :, at], sem.at[1, slot]))

        if not wide or P == 1:
            return one(0, None)
        jax.lax.fori_loop(0, jax.lax.min(P, pages(row) - j), one, None)

    def query_begin(row, ch):
        """Where the copy of `row`'s `ch` positions begins: at the row's
        start, or earlier where that keeps it inside the buffer."""
        return jax.lax.min(starts_ref[row], T - ch)

    def query_copy(row, ch):
        """`row`'s first `ch` packed positions into `qin`: one copy of
        static size (the row's positions sit `starts - query_begin` rows
        down; what it over-reads is a neighbour's or padding, rows the
        write-back never emits)."""
        return pltpu.make_async_copy(
            q_hbm.at[pl.ds(query_begin(row, ch), ch)],
            qin.at[pl.ds(0, ch)], io_sem.at[0])

    def start(row, j, slot, wide):
        page_copies(row, j, slot, wide, lambda copy: copy.start())

    def start_row(row, slot):
        """`row`'s first page (or block) and its queries, at the size its
        arm reads."""
        def begin(ch, wide):
            start(row, first_page(row), slot, wide)
            query_copy(row, ch).start()

        if 0 < cn < C:
            narrow = qlens_ref[row] <= cn
            pl.when(narrow)(lambda: begin(cn, False))
            pl.when(jnp.logical_not(narrow))(lambda: begin(C, True))
        else:
            begin(C, g * C > _NARROW)

    def output_copies(n, at, act):
        """`n` positions of `obuf` to packed positions [at, at + n) of the
        output, in copies of static size: whole blocks of 8 positions,
        then single ones. A row writes its own positions and no other
        (rows are not packed in row order, so nothing repairs an
        overshoot). Two loops, no conditional a size: every line of the
        kernel is traced and lowered in every program's set-up."""
        def piece(off, size):
            act(pltpu.make_async_copy(
                obuf.at[pl.ds(off, size)],
                o_hbm.at[pl.ds(at + off, size)], io_sem.at[1]))

        whole = jax.lax.div(n, _NARROW) if C >= _NARROW else 0
        if C >= _NARROW:
            jax.lax.fori_loop(
                0, whole, lambda i, _: piece(i * _NARROW, _NARROW), None)
        jax.lax.fori_loop(whole * _NARROW, n, lambda i, _: piece(i, 1), None)

    def land_output():
        """Wait for the writes still in flight (stream[2] positions at
        stream[3]) before `obuf` is filled again or the call ends."""
        output_copies(stream[2], stream[3], lambda copy: copy.wait())
        stream[2] = 0

    # `stream`: the buffer the next page copy lands in, whether this row's
    # first page and queries are already in flight (started by the row
    # before), and the output still being written (positions, start)
    @pl.when(r == 0)
    def _first_row():
        stream[0] = 0
        stream[1] = 0
        stream[2] = 0
        if P > 1:   # a short block's unused slices are multiplied by p = 0
            def clear(i, _):
                vbuf[i // hkv, i % hkv] = jnp.zeros(vbuf.shape[2:],
                                                    vbuf.dtype)
            jax.lax.fori_loop(0, 2 * hkv, clear, None)

    def arm(ch, wide):
        """The row's first `ch` chunk positions, folded group-major into
        `rows` = g * ch rows a KV head (row f is query head f // ch of the
        group at chunk position f % ch). The narrow arm makes one soft-max
        update a page, every head in it; the wide arm one a BLOCK of P
        pages, head by head, its rows in sub-tiles of RT."""
        rows = max(g * ch, _NARROW)
        per = P if wide else 1      # pages a step of the row's stream
        # the row's queries, head-major: staged once, read every page. One
        # [H_q, D] tile a chunk position, a loop as long as the row's own
        # q_len (unrolled, the chunk's C x H_q single-row moves were most
        # of the kernel's compile time)
        if g * ch < rows:
            qs[:, :rows] = jnp.zeros((hkv, rows, qs.shape[2]), qs.dtype)

        @pl.when(stream[1] == 0)
        def _own_first_copies():
            start(r, first_page(r), stream[0], wide)
            query_copy(r, ch).start()

        query_copy(r, ch).wait()
        down = starts_ref[r] - query_begin(r, ch)

        def stage(c, carry):
            tile = qin[down + c].astype(qs.dtype)
            for j in range(hq):
                qs[j // g, pl.ds(j % g * ch + c, 1), :] = tile[j:j + 1]
            return carry

        jax.lax.fori_loop(0, ql, stage, None)
        if wide and qb is not qs:
            # cast once a row, not once a (page, head): a packed dtype has
            # no single-row stores, so the staging above is in 32 bits
            qb[:, :rows] = qs[:, :rows].astype(qb.dtype)
        m_sc[:, :rows] = jnp.full((hkv, rows, _LANES), _NEG_INF, jnp.float32)
        l_sc[:, :rows] = jnp.zeros((hkv, rows, _LANES), jnp.float32)
        acc_sc[:, :rows] = jnp.zeros((hkv, rows) + acc_sc.shape[2:],
                                     jnp.float32)
        n, j0 = pages(r), first_page(r)
        steps = jax.lax.div(n - j0 + per - 1, per)
        base = stream[0]            # page j0 lands in buffer stream[0]
        nxt = next_ref[r]

        if not wide:
            # row f of the folded tile is chunk position c = f % ch; its
            # absolute query position is kv_len - q_len + c (the chunk
            # holds the LAST q_len tokens of the sequence)
            c = jax.lax.rem(
                jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0), ch)
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
            last_col = kl - ql + c

            def head_scales(ref, page):
                """ref[h, page] / qmax, a [hkv, 1, 1] vector built from
                the SMEM scalars."""
                head = jax.lax.broadcasted_iota(jnp.int32, (hkv, 1, 1), 0)
                vec = jnp.zeros((hkv, 1, 1), jnp.float32)
                for h in range(hkv):
                    vec = jnp.where(head == h, ref[h, page], vec)
                return vec / qmax

            def attend(j, slot):
                """One soft-max update of every head over page j: the
                heads are the batch dimension of the two contractions."""
                ok = (c < ql) & (j * bs + col <= last_col)
                if window is not None:  # query i attends i - window < j <= i
                    ok = ok & (j * bs + col > last_col - window)
                ok = ok[None]
                page = page_of(r, j)
                q = qs[:, :rows, :].astype(qin.dtype)   # [hkv, rows, D]
                k = kbuf[slot, :, :bs]                  # [hkv, bs, D]
                v = vbuf[slot, :, :bs]
                sk = scale
                if quantized:
                    # absmax dequantisation (x = q * s / qmax) on the
                    # products: the values widen exactly, one scale a
                    # (head, page)
                    k, v = k.astype(q.dtype), v.astype(q.dtype)
                    sk = scale * head_scales(ks_ref, page)
                s = jax.lax.dot_general(
                    q, k, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32) * sk
                s = jnp.where(ok, s, _NEG_INF)          # [hkv, rows, bs]
                m_prev = m_sc[:, :rows, :1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=2, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
                l_sc[:, :rows] = jnp.broadcast_to(
                    l_sc[:, :rows, :1] * alpha
                    + jnp.sum(p, axis=2, keepdims=True), (hkv, rows, _LANES))
                m_sc[:, :rows] = jnp.broadcast_to(m_new, (hkv, rows, _LANES))
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)
                if quantized:
                    pv = pv * head_scales(vs_ref, page)
                acc_sc[:, :rows] = acc_sc[:, :rows] * alpha + pv
        else:
            # sub-tile t holds folded rows [t * RT, (t + 1) * RT): chunk
            # positions c0 + (t * RT) % ch. Query c sees key position
            # j * bs + col where col - c <= kv_len - q_len - j * bs (and,
            # under a window, > that - window): `d` is the loop's one
            # constant tile, an edge two scalars a (block, sub-tile).
            # Rows past q_len hold what a row before left there; no
            # update mixes rows and the write-back never emits them
            nt = rows // RT
            U = math.gcd(_UNROLL, hkv * nt)
            d = (jax.lax.broadcasted_iota(jnp.int32, (RT, W), 1)
                 - jax.lax.rem(
                     jax.lax.broadcasted_iota(jnp.int32, (RT, W), 0), ch))
            slice_of = jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, (1, W), 1), bs)

            def page_scales(ref, h, j):
                """ref[h, page] / qmax over the block's columns: [1, W],
                a scale a (head, page) inside a block of pages."""
                vec = jnp.zeros((1, W), jnp.float32)
                for p in range(P):  # past the last page: any real page
                    page = page_of(r, jax.lax.min(j + p, n - 1))
                    vec = jnp.where(slice_of == p, ref[h, page], vec)
                return vec / qmax

            def update(i, j, slot, masked):
                """One soft-max update of head i // nt, sub-tile i % nt,
                over the block at page j: `masked` (static) where an edge
                crosses the block, else every key is seen by every
                query and nothing is compared or selected."""
                h, t = (i, 0) if nt == 1 else (jax.lax.div(i, nt),
                                               jax.lax.rem(i, nt))
                at = (pl.ds(0, rows) if nt == 1
                      else pl.ds(pl.multiple_of(t * RT, RT), RT))
                q = qb[h, at, :]                        # [RT, D]
                k = kbuf[slot, h]                       # [W, D]
                v = vbuf[slot, h]
                sk = scale
                if quantized:
                    k, v = k.astype(q.dtype), v.astype(q.dtype)
                    sk = scale * page_scales(ks_ref, h, j)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sk    # [RT, W]
                if masked:
                    seen = kl - ql - j * bs
                    if RT % ch:     # the sub-tile's first chunk position
                        seen = seen + jax.lax.rem(t * RT, ch)
                    ok = d <= seen
                    if window is not None:
                        ok = ok & (d > seen - window)
                    s = jnp.where(ok, s, _NEG_INF)
                m_prev = m_sc[h, at, :1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                if masked:
                    p = jnp.where(ok, p, 0.0)
                l_sc[h, at] = jnp.broadcast_to(
                    l_sc[h, at, :1] * alpha
                    + jnp.sum(p, axis=1, keepdims=True), (RT, _LANES))
                m_sc[h, at] = jnp.broadcast_to(m_new, (RT, _LANES))
                if quantized:
                    p = p * page_scales(vs_ref, h, j)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc_sc[h, at] = acc_sc[h, at] * alpha + pv

            def attend(j, slot):
                """The block at page j, every head and sub-tile: masked
                only where it can hold a masked key."""
                edge = _edge_block(j, ql, kl, P, bs, window)
                for masked, on in ((True, edge),
                                   (False, jnp.logical_not(edge))):
                    def some(i, _, masked=masked):
                        for u in range(U):  # side by side in one block
                            update(i * U + u, j, slot, masked)

                    pl.when(on)(functools.partial(
                        jax.lax.fori_loop, 0, hkv * nt // U, some, None))

        def step(i, carry):
            slot = jax.lax.rem(base + i, 2)
            j = j0 + i * per

            @pl.when(i + 1 < steps)
            def _next_pages():
                start(r, j + per, 1 - slot, wide)

            @pl.when((i + 1 == steps) & (nxt < R))
            def _next_row():
                start_row(nxt, 1 - slot)

            page_copies(r, j, slot, wide, lambda copy: copy.wait())
            attend(j, slot)
            return carry

        jax.lax.fori_loop(0, steps, step, None)
        stream[0] = jax.lax.rem(base + steps, 2)
        stream[1] = (nxt < R).astype(jnp.int32)
        l = l_sc[:, :rows, :1]
        dead = (l == 0.0) | (m_sc[:, :rows, :1] <= _NEG_INF * 0.5)
        inv = jnp.where(dead, 0.0, 1.0 / jnp.maximum(l, 1e-37))
        acc_sc[:, :rows] = acc_sc[:, :rows] * inv

    def write_back(ch):
        """The row's output, un-folded from the arm's `ch`-position tiles
        into `obuf`, on its way to the row's packed positions. One body
        for both arms (`ch` is a scalar then): every line of the kernel is
        traced and lowered in every program's set-up."""
        land_output()

        def emit(c, carry):     # the [H_q, D] tile of position c
            for j in range(hq):
                ot[j:j + 1, :] = acc_sc[j // g, pl.ds(j % g * ch + c, 1), :]
            obuf[c] = ot[...].astype(obuf.dtype)
            return carry

        jax.lax.fori_loop(0, ql, emit, None)
        output_copies(ql, starts_ref[r], lambda copy: copy.start())
        stream[2] = ql
        stream[3] = starts_ref[r]

    # A row's arithmetic is sized by its own q_len: folded queries that
    # fit one sublane tile run on 8 rows, every head of a page in one
    # update; any other row on the whole chunk, a head and a block of
    # pages an update.
    if 0 < cn < C:
        narrow = ql <= cn
        pl.when((ql > 0) & narrow)(lambda: arm(cn, False))
        pl.when((ql > 0) & jnp.logical_not(narrow))(lambda: arm(C, True))
        pl.when(ql > 0)(lambda: write_back(
            jax.lax.select(narrow, jnp.int32(cn), jnp.int32(C))))
    else:
        @pl.when(ql > 0)
        def _one_arm():
            arm(C, g * C > _NARROW)
            write_back(C)
    pl.when(r == R - 1)(land_output)


def ragged_paged_attention(q, k_pool, v_pool, block_tables, starts, q_lens,
                           kv_lens, scale: float, k_scales=None,
                           v_scales=None, layer=0, *, c_att: int,
                           window=None):
    """q: [T, H_q, D], the step's PACKED queries — row r's chunk occupies
    positions [starts[r], starts[r] + q_lens[r]), rows in any order, no two
    overlapping; ``c_att`` (static) is the longest chunk a row may hold:
    it sizes the kernel's VMEM and picks the arms;
    pools: [L, H_kv, num_blocks, bs, D] (float, or int8 with k_scales /
    v_scales: [L, H_kv, num_blocks] f32 per-page absmax scales) and
    ``layer`` the (traced) int32 index of the layer to attend over — or
    one layer's [H_kv, num_blocks, bs, D] pool (+ [H_kv, num_blocks]
    scales), which is the L = 1 form of the same call;
    block_tables: [R, nb] int32; starts: [R] int32; q_lens: [R] int32
    (0 = inactive row, whose ``starts`` is not read); kv_lens: [R] int32 —
    TOTAL kv length including this chunk (query c sits at absolute
    position kv_lens - q_lens + c) → [T, H_q, D], packed as q is: a row's
    positions hold its output, every other position reads zero.
    ``window`` (static; None: causal only): query i attends the keys j with
    ``i - window < j <= i``. ``block_tables`` is then a RING: page j of a
    row is entry ``j % nb`` (`inference.serving` gives the pages behind the
    window back), and a row reads from the page that holds position
    ``kv_lens - q_lens - (window - 1)`` on; earlier pages are neither
    copied nor waited on, so their entries may hold anything."""
    T, hq, D = q.shape
    R = block_tables.shape[0]
    C = min(c_att, T)       # no row holds more positions than the buffer
    # a position is copied whole, so its [H_q, D] must be whole tiles: a
    # packed dtype tiles H_q by the power of two above it, up to 8 (Mosaic
    # refuses to slice [T, 20, 128] bf16 by position); the heads added
    # are copied with the others and never staged
    tiled = (1 if q.dtype.itemsize >= 4
             else min(8, 1 << max(hq - 1, 1).bit_length()))
    hp = -(-hq // tiled) * tiled
    if hp > hq:
        q = jnp.pad(q, ((0, 0), (0, hp - hq), (0, 0)))
    if k_pool.ndim == 4:  # one layer's pool: a free leading-1 reshape
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    elif k_scales is not None:
        # the layer's [H_kv, num_blocks] scales alone go to SMEM: all
        # layers' (2 x 0.75 MB at 512 pages) do not fit its 1 MB
        k_scales, v_scales = (jax.lax.dynamic_index_in_dim(
            s, layer, keepdims=False) for s in (k_scales, v_scales))
    _, hkv, _, bs, _ = k_pool.shape
    g = hq // hkv
    quantized = k_scales is not None
    # quantized pools dequantize in-kernel in the absmax convention
    # (quantization/kv_cache.py): int8 grid tops at 127, e4m3 at 448
    qmax = (448.0 if k_pool.dtype == jnp.dtype(jnp.float8_e4m3fn)
            else 127.0)
    CG8 = max(_NARROW, -(-C * g // 8) * 8)  # a head's folded tile
    q_lens = q_lens.astype(jnp.int32)
    # the next row that has work (R past the last): a row starts that
    # row's first copies beside its own last page's arithmetic
    rows = jnp.arange(R, dtype=jnp.int32)
    later_live = (rows[None, :] > rows[:, None]) & (q_lens > 0)[None, :]
    next_live = jnp.min(jnp.where(later_live, rows[None, :], R), axis=1)

    # staged in 32 bits: a packed dtype has no single-row loads or stores
    stage = jnp.float32 if q.dtype.itemsize < 4 else q.dtype

    prefetch = [block_tables.astype(jnp.int32), starts.astype(jnp.int32),
                q_lens, kv_lens.astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1), next_live]
    if quantized:
        prefetch += [k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32)]
    # the wide arm (rows whose folded queries pass one sublane tile) takes
    # its pages a block at a time, its rows a sub-tile at a time
    wide = g * C > _NARROW
    P = _block_pages(hkv, bs, D, k_pool.dtype.itemsize) if wide else 1
    RT = _tile_rows(g, C)
    tile = hkv * CG8 * D                    # a row's folded query tile
    block = 2 * hkv * P * bs * D * k_pool.dtype.itemsize    # two buffers
    cast = wide and stage != q.dtype
    vmem = (2 * C * hp * D * q.dtype.itemsize + 2 * block
            + 4 * tile * (2 + 2 * _LANES // D)
            + cast * tile * q.dtype.itemsize)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(R,),
        in_specs=[hbm, hbm, hbm, hbm],
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((2, hkv, P * bs, D), k_pool.dtype),
            pltpu.VMEM((2, hkv, P * bs, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),      # queries in, output out
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((C, hp, D), q.dtype),
            pltpu.VMEM((C, hp, D), q.dtype),
            pltpu.VMEM((hkv, CG8, D), stage),
            pltpu.VMEM((hp, D), jnp.float32),
            pltpu.VMEM((hkv, CG8, _LANES), jnp.float32),
            pltpu.VMEM((hkv, CG8, _LANES), jnp.float32),
            pltpu.VMEM((hkv, CG8, D), jnp.float32),
            # the wide arm's operand: the staged queries cast once a row
        ] + [pltpu.VMEM((hkv, CG8, D), q.dtype)] * cast,
    )
    # the output starts as zeros and is written in place: positions that
    # belong to no row (the buffer's tail padding) read zero
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, scale=scale, bs=bs, hq=hq, C=C,
                          P=P, RT=RT, quantized=quantized, qmax=qmax,
                          window=None if window is None else int(window)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        input_output_aliases={len(prefetch) + 3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the buffers above and as much again for the score tiles (a
            # sub-tile's [RT, P * bs] f32 scores, probabilities and mask)
            vmem_limit_bytes=min(max(2 * vmem, 32 << 20), 96 << 20)),
        interpret=_interpret(),
        name=KERNELS.ragged_paged_attn,
    )(*prefetch, q, k_pool, v_pool, jnp.zeros_like(q))
    return out[:, :hq]
