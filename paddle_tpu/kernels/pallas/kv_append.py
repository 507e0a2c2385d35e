"""In-place append of a step's new K and V rows to the paged pools (Pallas).

The serving step writes ``sum(q_lens)`` new token rows a layer into the
K and the V pool (inference/ragged_step.py states the pool's contract:
one ``[L, H_kv, NB, bs, D]`` buffer each, donated, never sliced by layer,
never copied). As an XLA scatter on the flat row view that is one index a
(head, token) — 3,072 rows a call at the benchmark's geometry, which the
chip works through at ~73 ns a row, 9% of a step against this kernel's
0.9% (PERF.md, PR 27). A DMA a token row is not possible: a one-row
slice of the pool's tiled HBM layout is refused by the compiler ("must
be aligned to tiling").

So the append works on what the layout offers, the aligned sublane TILE
(16 rows of bf16, 8 of f32): the rows a ragged row adds are contiguous
positions, so they touch ``ceil`` of ``q_len / tile`` tiles, a decode row
one. The caller lists those tiles (`tile_work`); the grid walks the list,
one ``[H, tile, D]`` block of K and of V a step through aliased in/out
BlockSpecs whose index maps dereference ``layer`` and the listed (page,
tile) by scalar prefetch — read, merge, write back, the rest of the pool
untouched. The new rows reach their sublanes through a one-hot
``[tile, T] x [T, 2*H*D]`` product on the MXU (exact: one 1.0 a row, f32
accumulation), so nothing in the kernel slices a packed dtype at a
dynamic sublane offset. List entries past the last real one repeat it:
the block index does not change, so Pallas neither re-fetches nor writes
back, and an idle entry costs a grid step.
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


def append_tile(dtype, bs):
    """Rows of the aligned sublane tile the append reads and writes."""
    return min(bs, 32 // jnp.dtype(dtype).itemsize)


def tile_work(starts, pos0, q_lens, tables, *, bs, tile, c_att, T):
    """The tiles this pass's rows touch, as scalar-prefetch vectors [W]:
    (page, tile-in-page, packed index of the tile's row 0, first and
    one-past-last row of the tile that is new). starts/pos0/q_lens: [R]
    (row r's ``q_lens[r] <= c_att`` tokens sit at packed ``starts[r]..``
    and land at positions ``pos0[r]..``); tables: [R, nb]. W is the
    static bound on the count; entries past the real ones repeat the
    last and are marked empty (first = last = 0)."""
    R, nb = tables.shape
    W = min(R * (1 + (c_att + tile - 2) // tile),
            R + (T + (tile - 2) * R) // tile)
    first = pos0 // tile
    count = jnp.where(q_lens > 0, (pos0 + q_lens - 1) // tile - first + 1, 0)
    ends = jnp.cumsum(count)
    n = ends[-1]
    w = jnp.arange(W, dtype=jnp.int32)
    wc = jnp.minimum(w, jnp.maximum(n - 1, 0))
    row = jnp.minimum(jnp.searchsorted(ends, wc, side="right"), R - 1)
    pos = (first[row] + wc - (ends[row] - count[row])) * tile
    page = tables[row, jnp.clip(pos // bs, 0, nb - 1)]
    real = w < n
    lo = jnp.where(real, jnp.clip(pos0[row] - pos, 0, tile), 0)
    hi = jnp.where(real, jnp.clip(pos0[row] + q_lens[row] - pos, 0, tile), 0)
    tok0 = starts[row] + pos - pos0[row]
    return tuple(a.astype(jnp.int32)
                 for a in (page, (pos % bs) // tile, tok0, lo, hi))


def _append_kernel(layer_ref, page_ref, sub_ref, tok0_ref, lo_ref, hi_ref,
                   val_ref, k_in, v_in, k_out, v_out, *, H, D, tile,
                   precision):
    w = pl.program_id(0)
    lo, hi = lo_ref[w], hi_ref[w]

    @pl.when(hi > lo)
    def _merge():
        T = val_ref.shape[0]
        i = jax.lax.broadcasted_iota(jnp.int32, (tile, T), 0)
        t = jax.lax.broadcasted_iota(jnp.int32, (tile, T), 1)
        pick = ((t == tok0_ref[w] + i) & (i >= lo) & (i < hi))
        new = jax.lax.dot_general(
            pick.astype(val_ref.dtype), val_ref[...],
            (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)          # [tile, 2*H*D]
        r = jax.lax.broadcasted_iota(jnp.int32, (tile, D), 0)
        fresh = (r >= lo) & (r < hi)
        for h in range(H):
            k_out[0, h, 0] = jnp.where(
                fresh, new[:, h * D:(h + 1) * D].astype(k_out.dtype),
                k_in[0, h, 0])
            v_out[0, h, 0] = jnp.where(
                fresh, new[:, (H + h) * D:(H + h + 1) * D].astype(
                    v_out.dtype), v_in[0, h, 0])

    # a list with no real entry still writes its (repeated) block back
    @pl.when((hi <= lo) & (w == 0))
    def _keep():
        k_out[...] = k_in[...]
        v_out[...] = v_in[...]


def kv_append(k_pool, v_pool, k, v, layer, work, *, tile):
    """Write the packed rows k, v: [T, H, D] into ``layer`` of the pools
    [L, H, NB, bs, D] at the tiles ``work`` lists (`tile_work`). Returns
    the two pools, aliased to the ones given."""
    T, H, D = k.shape
    val = jnp.concatenate([k.reshape(T, H * D), v.reshape(T, H * D)],
                          axis=1).astype(k_pool.dtype)

    def pool_idx(w, layer, page, sub, *_):
        return (layer[0], 0, page[w], sub[w], 0)

    block = pl.BlockSpec((1, H, 1, tile, D), pool_idx)
    exact = (jax.lax.Precision.HIGHEST
             if k_pool.dtype == jnp.dtype(jnp.float32) else None)
    prefetch = (jnp.asarray(layer, jnp.int32).reshape(1),) + tuple(work)
    return pl.pallas_call(
        functools.partial(_append_kernel, H=H, D=D, tile=tile,
                          precision=exact),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(work[0].shape[0],),
            in_specs=[pl.BlockSpec((T, 2 * H * D), lambda w, *_: (0, 0)),
                      block, block],
            out_specs=[block, block]),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={len(prefetch) + 1: 0, len(prefetch) + 2: 1},
        interpret=_interpret(),
        name=KERNELS.kv_append,
    )(*prefetch, val, k_pool, v_pool)
