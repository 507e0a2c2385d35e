"""In-place append of a step's new LATENT rows to the paged latent pools
(Pallas): `kv_append`'s walk on pages that have no heads.

A latent cache keeps, a token and layer, ONE compressed vector ``c`` (all
heads' keys and values are products of it) and ONE rotary key ``r`` that
all heads share. The two live in two pools under the pool's contract
(inference/ragged_step.py): ``[L, 1, NB, bs, C]`` and ``[L, 1, NB, bs,
Rd]`` (512, and the 64-wide key in 128 lanes: both whole lane tiles, what
a copy of a slice of the tiled layout needs; the head axis of 1 keeps the
page-flat view, the copy-on-write and the engine's allocator the ones the
K and V pools have).
The walk is `kernels.pallas.kv_append`'s, on its work list (`tile_work`:
the n aligned sublane tiles a pass writes, of at most W): a tile's
``[tile, C]`` and ``[tile, Rd]`` come into one of `_SLOTS` VMEM buffers by
one copy each, the pass's new rows are merged in through the one-hot
product, and the buffers go back where they came from, four tiles' reads
and two tiles' writes in flight around the merge.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret as _interpret
from .kv_append import _SLOTS
from ...observability.trace import KERNELS

__all__ = ["latent_append"]


def _append_kernel(layer_ref, n_ref, page_ref, sub_ref, tok0_ref, lo_ref,
                   hi_ref, val, c_hbm, r_hbm, c_out, r_out, cbuf, rbuf, rsem,
                   wsem, *, tile, precision):
    layer, n = layer_ref[0], n_ref[0]
    T = val.shape[0]
    C = cbuf.shape[-1]
    ahead = _SLOTS - 2      # reads in flight; two writes drain behind them

    def tile_of(pool, w):
        rows = pl.ds(pl.multiple_of(sub_ref[w] * tile, tile), tile)
        return pool.at[layer, 0, page_ref[w], rows]             # [tile, D]

    def reads(w):
        slot = jax.lax.rem(w, _SLOTS)
        return [pltpu.make_async_copy(tile_of(pool, w), buf.at[slot],
                                      rsem.at[slot, j])
                for j, (pool, buf) in enumerate(((c_hbm, cbuf),
                                                 (r_hbm, rbuf)))]

    def writes(w):
        slot = jax.lax.rem(w, _SLOTS)
        return [pltpu.make_async_copy(buf.at[slot], tile_of(pool, w),
                                      wsem.at[slot, j])
                for j, (pool, buf) in enumerate(((c_out, cbuf),
                                                 (r_out, rbuf)))]

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
        # tile w - 2 has left its buffers, which tile w + ahead takes
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
            preferred_element_type=jnp.float32)          # [tile, C + Rd]
        slot = jax.lax.rem(w, _SLOTS)
        for buf, at in ((cbuf, 0), (rbuf, C)):
            D = buf.shape[-1]
            r = jax.lax.broadcasted_iota(jnp.int32, (tile, D), 0)
            buf[slot] = jnp.where((r >= lo) & (r < hi),
                                  new[:, at:at + D].astype(buf.dtype),
                                  buf[slot])
        start(writes(w))
        return carry

    def last(w, carry):
        wait(writes(w))
        return carry

    jax.lax.fori_loop(0, jnp.minimum(n, ahead), first, None)
    jax.lax.fori_loop(0, n, merge, None)
    jax.lax.fori_loop(jnp.maximum(n - 2, 0), n, last, None)


def latent_append(c_pool, r_pool, c, r, layer, work, *, tile):
    """Write the packed rows c: [T, C], r: [T, Rd] into ``layer`` of the
    pools [L, 1, NB, bs, C] and [L, 1, NB, bs, Rd] at the n tiles ``work``
    lists (`kv_append.tile_work`). Returns the two pools, aliased to the
    ones given."""
    T, C = c.shape
    Rd = r.shape[1]
    n, *tiles = work
    val = jnp.concatenate([c, r], axis=1).astype(c_pool.dtype)
    exact = (jax.lax.Precision.HIGHEST
             if c_pool.dtype == jnp.dtype(jnp.float32) else None)
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
                pltpu.VMEM((_SLOTS, tile, C), c_pool.dtype),
                pltpu.VMEM((_SLOTS, tile, Rd), r_pool.dtype),
                pltpu.SemaphoreType.DMA((_SLOTS, 2)),
                pltpu.SemaphoreType.DMA((_SLOTS, 2))]),
        out_shape=[jax.ShapeDtypeStruct(c_pool.shape, c_pool.dtype),
                   jax.ShapeDtypeStruct(r_pool.shape, r_pool.dtype)],
        input_output_aliases={len(prefetch) + 1: 0, len(prefetch) + 2: 1},
        interpret=_interpret(),
        name=KERNELS.latent_append,
    )(*prefetch, val, c_pool, r_pool)
