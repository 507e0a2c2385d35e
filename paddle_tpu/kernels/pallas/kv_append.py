"""In-place append of a step's new K and V rows to the paged pools (Pallas).

The serving step writes ``sum(q_lens)`` new token rows a layer into the
K and the V pool (inference/ragged_step.py states the pool's contract:
one ``[L, H_kv, NB, bs, D]`` buffer each, donated, never sliced by layer,
never copied). As an XLA scatter on the flat row view that is one index a
(head, token) — 3,072 rows a call at the benchmark's geometry, which the
chip works through at ~73 ns a row (PERF.md, PR 27). A DMA a token row is
not possible: a one-row slice of the pool's tiled HBM layout is refused
by the compiler ("must be aligned to tiling").

So the append works on what the layout offers, the aligned sublane TILE
(16 rows of bf16, 8 of f32): the rows a ragged row adds are contiguous
positions, so they touch ``ceil`` of ``q_len / tile`` tiles, a decode row
one. The caller lists those tiles and counts them (`tile_work`: n of at
most W); the kernel walks the n listed tiles and no further, so a pass
pays for what it writes. The pools stay in HBM (aliased in and out); a
tile's ``[H, tile, D]`` of K and of V come into one of `_SLOTS` VMEM
buffers by a strided copy each (``layer`` and the listed page and tile by
scalar prefetch), the new rows are merged in, and the buffer goes back
where it came from — the rest of the pool untouched. Four tiles' reads
and two tiles' writes are in flight around the merge: one pass's tiles
are disjoint (distinct rows own distinct pages, a row's tiles are
distinct), so they move in any order, and with one tile's copies in
flight at a time a tile costs twice its bytes' time (PERF.md, PR 48). The
new rows reach their sublanes through a one-hot ``[tile, T] x [T, 2*H*D]``
product on the MXU (exact: one 1.0 a row, f32 accumulation), so nothing
in the kernel slices a packed dtype at a dynamic sublane offset.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret as _interpret
from ...observability.trace import KERNELS

__all__ = ["append_tile", "tile_work", "kv_append"]


# tiles in VMEM at a time: four on their way in, two on their way out. Ten
# move no faster, four take a quarter longer a tile, three 70% (PERF.md,
# PR 48)
_SLOTS = 6


def append_tile(dtype, bs):
    """Rows of the aligned sublane tile the append reads and writes."""
    return min(bs, 32 // jnp.dtype(dtype).itemsize)


def tile_work(starts, pos0, q_lens, tables, *, bs, tile, c_att, T,
              ring=False):
    """The tiles this pass's rows touch: their count n, then scalar-
    prefetch vectors [W] of (page, tile-in-page, packed index of the
    tile's row 0, first and one-past-last row of the tile that is new).
    starts/pos0/q_lens: [R] (row r's ``q_lens[r] <= c_att`` tokens sit at
    packed ``starts[r]..`` and land at positions ``pos0[r]..``); tables:
    [R, nb] (``ring``: page j of a row is entry ``j % nb``, the table of
    a lifetime whose pages are given back behind a window). W is the
    static bound on n; the walk ends at n, so what the entries past it
    hold is never read."""
    R, nb = tables.shape
    W = min(R * (1 + (c_att + tile - 2) // tile),
            R + (T + (tile - 2) * R) // tile)
    first = pos0 // tile
    count = jnp.where(q_lens > 0, (pos0 + q_lens - 1) // tile - first + 1, 0)
    ends = jnp.cumsum(count)
    w = jnp.arange(W, dtype=jnp.int32)
    row = jnp.minimum(jnp.searchsorted(ends, w, side="right"), R - 1)
    pos = (first[row] + w - (ends[row] - count[row])) * tile
    page = tables[row, (pos // bs) % nb if ring
                  else jnp.clip(pos // bs, 0, nb - 1)]
    lo = jnp.clip(pos0[row] - pos, 0, tile)
    hi = jnp.clip(pos0[row] + q_lens[row] - pos, 0, tile)
    tok0 = starts[row] + pos - pos0[row]
    return tuple(a.astype(jnp.int32)
                 for a in (ends[-1], page, (pos % bs) // tile, tok0, lo, hi))


def _append_kernel(layer_ref, n_ref, page_ref, sub_ref, tok0_ref, lo_ref,
                   hi_ref, val, k_hbm, v_hbm, k_out, v_out, buf, rsem, wsem,
                   *, tile, precision):
    layer, n = layer_ref[0], n_ref[0]
    T = val.shape[0]
    _, _, H, _, D = buf.shape
    ahead = _SLOTS - 2      # reads in flight; two writes drain behind them

    def tile_of(pool, w):
        rows = pl.ds(pl.multiple_of(sub_ref[w] * tile, tile), tile)
        return pool.at[layer, :, page_ref[w], rows]          # [H, tile, D]

    def reads(w):
        slot = jax.lax.rem(w, _SLOTS)
        return [pltpu.make_async_copy(tile_of(pool, w), buf.at[slot, j],
                                      rsem.at[slot, j])
                for j, pool in enumerate((k_hbm, v_hbm))]

    def writes(w):
        slot = jax.lax.rem(w, _SLOTS)
        return [pltpu.make_async_copy(buf.at[slot, j], tile_of(pool, w),
                                      wsem.at[slot, j])
                for j, pool in enumerate((k_out, v_out))]

    def start(copies):
        for c in copies:
            c.start()

    def wait(copies):
        for c in copies:
            c.wait()

    def first(w, carry):
        start(reads(w))
        return carry

    def merge(w, carry):
        # tile w - 2 has left its buffer, which tile w + ahead takes
        @pl.when(w >= 2)
        def _():
            wait(writes(w - 2))

        @pl.when(w + ahead < n)
        def _():
            start(reads(w + ahead))
        wait(reads(w))
        lo, hi = lo_ref[w], hi_ref[w]
        i = jax.lax.broadcasted_iota(jnp.int32, (tile, T), 0)
        t = jax.lax.broadcasted_iota(jnp.int32, (tile, T), 1)
        pick = ((t == tok0_ref[w] + i) & (i >= lo) & (i < hi))
        new = jax.lax.dot_general(
            pick.astype(val.dtype), val[...],
            (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)          # [tile, 2*H*D]
        r = jax.lax.broadcasted_iota(jnp.int32, (tile, D), 0)
        fresh = (r >= lo) & (r < hi)
        slot = jax.lax.rem(w, _SLOTS)
        for j in range(2):
            for h in range(H):
                at = (j * H + h) * D
                buf[slot, j, h] = jnp.where(
                    fresh, new[:, at:at + D].astype(buf.dtype),
                    buf[slot, j, h])
        start(writes(w))
        return carry

    def last(w, carry):
        wait(writes(w))
        return carry

    jax.lax.fori_loop(0, jnp.minimum(n, ahead), first, None)
    jax.lax.fori_loop(0, n, merge, None)
    jax.lax.fori_loop(jnp.maximum(n - 2, 0), n, last, None)


def kv_append(k_pool, v_pool, k, v, layer, work, *, tile):
    """Write the packed rows k, v: [T, H, D] into ``layer`` of the pools
    [L, H, NB, bs, D] at the n tiles ``work`` lists (`tile_work`). Returns
    the two pools, aliased to the ones given."""
    T, H, D = k.shape
    n, *tiles = work
    val = jnp.concatenate([k.reshape(T, H * D), v.reshape(T, H * D)],
                          axis=1).astype(k_pool.dtype)
    exact = (jax.lax.Precision.HIGHEST
             if k_pool.dtype == jnp.dtype(jnp.float32) else None)
    prefetch = (jnp.asarray(layer, jnp.int32).reshape(1), n.reshape(1),
                *tiles)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_append_kernel, tile=tile, precision=exact),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(1,),
            in_specs=[pl.BlockSpec(val.shape, lambda i, *_: (0, 0)),
                      hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((_SLOTS, 2, H, tile, D), k_pool.dtype),
                pltpu.SemaphoreType.DMA((_SLOTS, 2)),
                pltpu.SemaphoreType.DMA((_SLOTS, 2))]),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={len(prefetch) + 1: 0, len(prefetch) + 2: 1},
        interpret=_interpret(),
        name=KERNELS.kv_append,
    )(*prefetch, val, k_pool, v_pool)
