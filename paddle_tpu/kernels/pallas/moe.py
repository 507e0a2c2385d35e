"""The routed experts of a sparse layer on the serving step's packed batch
(Pallas): every token's assignments to the experts THIS chip holds, grouped
by expert, through the gated feed-forward

    y = (silu(x @ Wg_e) * (x @ Wu_e)) @ Wd_e

one expert after another, so that an expert no token chose never leaves
HBM (a decode pass of 64 rows touches ~183 of 256 held experts; each is
6.3 MB at 2048 x 512 x 3 in bfloat16).

`plan` sorts the pass's (token, pick) pairs by held expert and `tiles` lays
each expert's group out in whole TILES of `tm` rows (rows past a group's count
are padding: zero inputs, outputs nobody reads). The kernel's grid walks
the tiles: tile w is rows [w tm, (w + 1) tm) of the padded buffer and
belongs to expert ``tile_expert[w]`` (scalar prefetch, dereferenced in the
weights' index maps beside ``layer``: the stacked weights
``[layers, experts, ., .]`` are never sliced, by layer or by expert;
consecutive tiles of one expert do not fetch it again). The static grid
is the bound `n_tiles_max`; a step past the last real tile stays on its
blocks and does nothing. The token rows are gathered into the padded
buffer before the call and the weighted outputs gathered back after it
(`combine`), both in XLA: at most tm - 1 padding rows an expert.

The tile's HEIGHT `tm` is the plan's, and it follows the pass
(`tile_rows`): the pipeline fetches one grid step ahead, so the next
expert's weights stream in only behind an expert's LAST tile and every
tile before it is exposed, at a cost that grows slowly with its rows (the
weights it latches are the same). Where a pass gives an expert many rows
(a prefill pass of 1,088 tokens x top-8 over 128 experts: 68) tiles of 16
walk every expert four or five times and leave the kernel at half its
HBM roofline, and where it gives an expert a few (a decode pass) a taller
tile is only padding. The height is a function of the pass's static
shapes alone: the rows an expert can expect, tokens x picks / experts.

An expert's WIDTH F is a second, inner grid axis: a step takes
``[H, BLOCK_F]`` of the gate and the up matrix and ``[BLOCK_F, H]`` of the
down matrix (at 5120 x 1536 an expert's three matrices are 47 MB, which no
VMEM holds twice over; three 5120 x 512 blocks, double-buffered, are
31 MB), the tile's float32 output stays where it is while the width's
blocks add to it, and it is written back when the walk leaves the tile.
An F of one block (2048 x 512) is the walk it was, and so is a wider
expert whose three matrices still fit VMEM twice over (2048 x 1024, 25 MB:
one block, so an expert's consecutive tiles fetch it once). A step past
the last real tile stays on the LAST block of the width too.

No assignment is ever dropped: there is no capacity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret as _interpret
from ...observability.trace import KERNELS

__all__ = ["TM_MIN", "TM_MAX", "PASS_STATS", "tile_rows", "tile_rows_of",
           "n_tiles_max", "plan", "tiles", "pass_stats", "grouped_ffn",
           "combine"]

TM_MIN = 16     # the least rows of a tile: one bf16 sublane tile
TM_MAX = 128    # the most: the MXU's rows
BLOCK_F = 512    # the most of an expert's width a grid step takes, unless
#                  the whole width fits `WHOLE_F_BYTES` double-buffered
WHOLE_F_BYTES = 32 << 20
_F32 = jnp.float32


def tile_rows(tokens, picks, experts):
    """The tile height for a pass of `tokens` positions with `picks`
    experts each out of the router's `experts`: the rows an expert can
    expect, r = tokens x picks / experts, down to a whole number of
    sublane tiles, between TM_MIN and TM_MAX. All three are static, so the
    height is the traced program's and nothing chooses it at run time."""
    r = tokens * picks // experts
    return max(TM_MIN, min(TM_MAX, r // TM_MIN * TM_MIN))


def n_tiles_max(assignments, experts, tm):
    """The most tiles of `tm` rows `assignments` pairs over `experts`
    groups can fill: each group's last tile may be partial."""
    return experts + -(-assignments // tm)


def tile_rows_of(p):
    """The tile height `tiles` laid the plan `p` out with (its shapes
    say)."""
    return p["token_of_row"].shape[0] // p["tile_expert"].shape[0]


def plan(ids, lo, hi):
    """Group the picks ids: [T, k] (expert numbers over ALL the router's
    experts) that fall on the held experts [lo, hi). Returns the groups,
    for `tiles` to lay out: ``held`` [T, k] bool; ``key`` [T k], a pair's
    held-expert index (hi - lo where not held); ``order`` [T k], the pairs
    sorted by it; ``counts`` [hi - lo], the assignments each held expert
    got."""
    T, k = ids.shape
    E, N = hi - lo, T * k
    held = (ids >= lo) & (ids < hi)
    key = jnp.where(held, ids - lo, E).reshape(N).astype(jnp.int32)
    counts = jnp.sum((key[:, None] == jnp.arange(E)[None, :])
                     .astype(jnp.int32), axis=0)                    # [E]
    order = jnp.argsort(key, stable=True).astype(jnp.int32)          # [N]
    return {"held": held, "key": key, "order": order, "counts": counts}


def tiles(groups, tm):
    """Lay `plan`'s groups out in tiles of `tm` rows (`tile_rows`).
    Returns the plan `grouped_ffn` and `combine` take, a dict: ``held``
    and ``counts`` as they were; ``pos`` [T, k], the pick's row in the
    padded buffer (0 where not held); ``token_of_row`` [G * tm] (T marks
    padding); ``tile_expert`` [G] (held-expert index, the last real
    tile's past the end) and ``n_tiles`` [1]."""
    assert TM_MIN <= tm <= TM_MAX and tm % TM_MIN == 0, tm
    held, key, order, counts = (groups[n] for n in
                                ("held", "key", "order", "counts"))
    (T, k), E, N = held.shape, counts.shape[0], key.shape[0]
    G = n_tiles_max(N, E, tm)
    group_end = jnp.cumsum(counts)
    group_start = group_end - counts
    group_tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(group_tiles)
    tile_start = tile_end - group_tiles
    n_tiles = tile_end[-1]
    # a sorted pair's row: its group's first tile, then its rank
    sk = jnp.minimum(key[order], E - 1)
    row_sorted = tile_start[sk] * tm + jnp.arange(N) - group_start[sk]
    pos = row_sorted[jnp.argsort(order)].reshape(T, k)
    # a padded row's token: its tile's expert, its rank in the group
    w = jnp.arange(G, dtype=jnp.int32)
    wc = jnp.minimum(w, jnp.maximum(n_tiles - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, wc, side="right"), E - 1
    ).astype(jnp.int32)
    rank = ((w - tile_start[tile_expert]) * tm)[:, None] \
        + jnp.arange(tm)[None, :]                                   # [G, tm]
    real = (w < n_tiles)[:, None] & (rank < counts[tile_expert][:, None])
    at = jnp.clip(group_start[tile_expert][:, None] + rank, 0, N - 1)
    token_of_row = jnp.where(real, order[at] // k, T).reshape(G * tm)
    return {"held": held, "pos": jnp.where(held, pos, 0),
            "token_of_row": token_of_row, "tile_expert": tile_expert,
            "n_tiles": n_tiles.reshape(1).astype(jnp.int32),
            "counts": counts}


# what `pass_stats` says of a pass and layer, in column order: held experts
# with an assignment, assignments to held experts, the most one held expert
# got, real tiles walked, and the tiles' height
PASS_STATS = ("touched", "assignments", "load_max", "tiles", "tile_rows")


def pass_stats(p):
    """The counts of `tiles`' plan `p`, [len(PASS_STATS)] int32 in that
    order."""
    counts = p["counts"]
    return jnp.stack([jnp.sum((counts > 0).astype(jnp.int32)),
                      jnp.sum(counts), jnp.max(counts), p["n_tiles"][0],
                      tile_rows_of(p)]).astype(jnp.int32)


def _ffn_kernel(layer_ref, expert_ref, n_ref, x_ref, g_ref, u_ref, d_ref,
                y_ref, *, nf):
    f = pl.program_id(1)

    @pl.when(pl.program_id(0) < n_ref[0])
    def _tile():
        x = x_ref[...]
        a = jnp.dot(x, g_ref[0, 0], preferred_element_type=_F32)
        b = jnp.dot(x, u_ref[0, 0], preferred_element_type=_F32)
        h = (a * jax.nn.sigmoid(a) * b).astype(x.dtype)
        part = jnp.dot(h, d_ref[0, 0], preferred_element_type=_F32)
        if nf == 1:
            y_ref[...] = part
        else:   # the output block stays in VMEM over the width's blocks
            @pl.when(f == 0)
            def _first():
                y_ref[...] = part

            @pl.when(f > 0)
            def _add():
                y_ref[...] += part


def grouped_ffn(x, gate_w, up_w, down_w, layer, p):
    """x: [T, H], the pass's normed tokens; gate_w, up_w:
    [layers, E, H, F], down_w: [layers, E, F, H], the held experts of
    every layer; p: `tiles`' dict. A grid step takes at most BLOCK_F of
    an expert's width (F must be whole blocks). Returns y_pad
    [G * tm, H] float32 (tm: the plan's tile height): row
    ``p['pos'][t, j]`` holds expert ``ids[t, j]``'s output for token t
    (unweighted); rows of tiles past the last real one hold nothing
    defined."""
    T, H = x.shape
    _, E, _, F = gate_w.shape
    # an expert's whole width where its three matrices fit VMEM twice over
    # (2048 x 1024: 25 MB): consecutive tiles of one expert then stay on
    # its blocks and fetch nothing, where a width in blocks walks the
    # expert's blocks again for every tile
    whole = 2 * 3 * H * F * gate_w.dtype.itemsize <= WHOLE_F_BYTES
    FB = F if whole else min(BLOCK_F, F)
    assert F % FB == 0, (F, FB)
    nF = F // FB
    G, tm = p["tile_expert"].shape[0], tile_rows_of(p)
    x_pad = jnp.concatenate([x, jnp.zeros((1, H), x.dtype)])[
        p["token_of_row"]]                                    # [G * tm, H]

    def tile_idx(w, f, layer, expert, n):
        return (jnp.minimum(w, jnp.maximum(n[0] - 1, 0)), 0)

    def block_of(w, f, n):      # past the last real tile: stay put
        return jnp.where(w < n[0], f, nF - 1)

    def wide_idx(w, f, layer, expert, n):
        return (layer[0], expert[w], 0, block_of(w, f, n))

    def down_idx(w, f, layer, expert, n):
        return (layer[0], expert[w], block_of(w, f, n), 0)

    weights = 2 * 3 * H * FB * gate_w.dtype.itemsize    # double-buffered
    return pl.pallas_call(
        functools.partial(_ffn_kernel, nf=nF),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(G, nF),
            in_specs=[pl.BlockSpec((tm, H), tile_idx),
                      pl.BlockSpec((1, 1, H, FB), wide_idx),
                      pl.BlockSpec((1, 1, H, FB), wide_idx),
                      pl.BlockSpec((1, 1, FB, H), down_idx)],
            out_specs=pl.BlockSpec((tm, H), tile_idx)),
        out_shape=jax.ShapeDtypeStruct((G * tm, H), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(max(2 * weights, 32 << 20), 96 << 20)),
        interpret=_interpret(),
        name=KERNELS.moe_grouped_ffn,
    )(jnp.asarray(layer, jnp.int32).reshape(1), p["tile_expert"],
      p["n_tiles"], x_pad, gate_w, up_w, down_w)


def combine(y_pad, weights, p):
    """sum_j weights[t, j] * (expert ids[t, j]'s output for token t) over
    the held picks; y_pad: `grouped_ffn`'s result, weights: [T, k] f32.
    A pick that is not held adds nothing (and reads nothing: a row no
    tile wrote may hold anything). Returns [T, H] float32."""
    picked = y_pad[p["pos"]]                                  # [T, k, H]
    return jnp.sum(jnp.where(p["held"][..., None],
                             weights[..., None] * picked, 0.0), axis=1)
