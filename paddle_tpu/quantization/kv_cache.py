"""Quantized paged KV-cache pool (int8, stretch fp8-e4m3 storage).

Decode attention is bandwidth-bound: per generated token the kernel
streams every referenced KV page. Storing the pool int8 halves those
bytes AND doubles the sequences a fixed HBM budget admits — the two wins
ISSUE 6 targets. The machinery reuses the module-wide quantization
convention (``__init__.quantize_to_int8``: scale = absmax, dequant =
q·scale/127); scales live per (layer, kv_head, page) so one SMEM scalar
dequantizes a whole ``[bs, D]`` page tile inside the ragged kernel.
The pool is the engine's one ``[L, H_kv, NB, bs, D]`` buffer (scales
``[L, H_kv, NB]``): donated, never sliced by layer, written here only
through its page-flat views — the contract is stated in
inference/ragged_step.py and `page_rows` is the one place that numbers
the flat views' rows.

Append semantics (deterministic, functional — runs INSIDE the serving
program): pages accept tokens incrementally, so a page's scale is a
running absmax. When a new token raises it, the page's existing int8
contents are REQUANTIZED to the grown scale (q' = round(q·s_old/s_new))
in the same scatter that writes the new tokens — a one-page
read-modify-write riding next to an attention read of ceil(len/bs)
pages, i.e. amortized noise. Freed pages get their scales reset to zero
in-program when their blocks are re-admitted (`reset_page_scales`), so a
recycled block never inherits a stale (precision-crushing) range.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["kv_cache_dtype", "kv_pool_blocks_for_budget", "page_rows",
           "append_tokens_quantized", "reset_page_scales",
           "KV_CACHE_DTYPES"]

# storage dtypes the pool supports; "auto" in the engine resolves to the
# model compute dtype (unquantized)
KV_CACHE_DTYPES = ("auto", "bf16", "f32", "int8", "fp8_e4m3")

_EPS = 1e-8


def kv_cache_dtype(name):
    """Resolve a `kv_cache_dtype` flag/arg value to (jnp dtype, quantized:
    bool). `auto` is resolved by the caller (engine) to the model dtype."""
    from ..enforce import enforce_in
    enforce_in(name, set(KV_CACHE_DTYPES) - {"auto"}, op="kv_cache_dtype",
               kv_cache_dtype=name)
    if name == "int8":
        return jnp.int8, True
    if name == "fp8_e4m3":
        # fp8 storage keeps the same per-page absmax scales (e4m3 has no
        # shared exponent window wide enough for raw activations)
        return jnp.float8_e4m3fn, True
    return {"bf16": jnp.bfloat16, "f32": jnp.float32}[name], False


def _qmax(dtype):
    return 127.0 if dtype == jnp.int8 else 448.0  # e4m3 finite max


def kv_pool_blocks_for_budget(budget_bytes: int, num_layers: int,
                              num_kv_heads: int, block_size: int,
                              head_dim: int, dtype) -> int:
    """How many pool blocks a fixed HBM byte budget admits (k + v pools
    plus, for quantized dtypes, their f32 per-page scales). This is the
    capacity half of the int8-KV win: itemsize 1 vs 2 ≈ 2x the blocks."""
    item = jnp.dtype(dtype).itemsize
    per_block = 2 * num_layers * num_kv_heads * block_size * head_dim * item
    if jnp.dtype(dtype) in (jnp.dtype(jnp.int8),
                            jnp.dtype(jnp.float8_e4m3fn)):
        per_block += 2 * num_layers * num_kv_heads * 4  # k+v scale entries
    return int(budget_bytes // per_block)


def page_rows(shape, layer, blk):
    """Row numbers of pages ``blk`` (any int shape) in the page-flat view
    ``pool.reshape(L*H*NB, bs, D)`` / ``scales.reshape(L*H*NB)`` of a
    ``shape = [L, H, NB, ...]`` pool, for every head of ``layer`` (a
    traced scalar → [H, *blk.shape]) or of every layer (``layer=None`` →
    [L*H, *blk.shape]). Page-wise pool writes go through these flat
    views: a reshape of leading dims is a bitcast in the tiled layout,
    and a scatter on it keeps the default layout the attention kernel
    reads — a multi-dimensional ``pool.at[li, :, blk]`` lets the compiler
    pick another one and copy the whole pool to and fro (PERF.md, PR 27)."""
    L, H, NB = shape[:3]
    if layer is None:
        lh = jnp.arange(L * H, dtype=jnp.int32)
    else:
        lh = layer * H + jnp.arange(H, dtype=jnp.int32)
    return lh.reshape((-1,) + (1,) * blk.ndim) * NB + blk[None]


def reset_page_scales(scales, tables, fresh):
    """Zero the per-page scales of every block in a freshly-admitted
    row's table, in-program (no extra dispatch). scales: [L, H, NB];
    tables: [R, nb] int32; fresh: [R] bool — rows admitted this step.
    Non-fresh rows route to block 0 (the reserved scratch block), whose
    scale is meaningless by construction. A [NB] hit mask and one
    elementwise select: in place, in whatever layout the scales have."""
    idx = jnp.where(fresh[:, None], tables, 0).reshape(-1)
    hit = jnp.zeros((scales.shape[-1],), jnp.bool_).at[idx].set(True)
    return jnp.where(hit, 0.0, scales)


def append_tokens_quantized(pool, scales, val, pos0, q_lens, tables, bs,
                            layer=0):
    """Quantize-on-append into the paged pool with per-(head, page)
    running-absmax scales.

    pool: [L, H, NB, bs, D] int8/fp8 — the WHOLE pool, of which only
    ``layer``'s (a traced int32 scalar) pages are touched — with scales
    [L, H, NB] f32; or one layer's [H, NB, bs, D] pool + [H, NB] scales
    (the L = 1 form, decided from ``pool.ndim``). val: [R, C, H, D]
    float chunk tiles (row r's tokens occupy columns [0, q_lens[r]) and
    land at positions pos0[r]..pos0[r]+q_lens[r]-1); tables: [R, nb].
    Returns (pool', scales') in the shapes given. Pages are read and
    written through the page-flat views (`page_rows`), so the update is
    in place and in the kernel's layout. Rows with q_len = 0 are exact
    no-ops on their own pages (ratio-1 requantize); idle rows' writes
    land in the reserved scratch block 0.
    """
    in_shape = pool_shape = pool.shape
    if pool.ndim == 4:
        pool_shape, layer = (1,) + in_shape, 0
    R, C, H, D = val.shape
    nb = tables.shape[1]
    qmax = _qmax(pool.dtype)
    # a C-token span starting anywhere touches at most this many pages
    PT = min(nb, (C + bs - 2) // bs + 1)
    p0b = pos0 // bs
    slot = p0b[:, None] + jnp.arange(PT)[None, :]              # [R, PT]
    # slots past the table's end (a chunk landing in the last page) route
    # to the reserved scratch block 0 like the unquantized path — clipping
    # to nb-1 would alias the row's REAL last block and the duplicate
    # scatter entry (whose winner XLA leaves unspecified) could overwrite
    # the freshly appended tokens with requantized stale contents
    blk = jnp.where(slot < nb,
                    jnp.take_along_axis(tables, jnp.clip(slot, 0, nb - 1),
                                        axis=1), 0)
    # which chunk token (if any) lands in each page cell
    gpos = slot[:, :, None] * bs + jnp.arange(bs)[None, None, :]
    tok = gpos - pos0[:, None, None]                           # [R, PT, bs]
    valid = (tok >= 0) & (tok < q_lens[:, None, None])
    tok_c = jnp.clip(tok, 0, C - 1)
    # vals_sel[r, u, o] = val[r, tok_c[r, u, o]] — [R, PT, bs, H, D]
    vals_sel = jnp.take_along_axis(
        val[:, None], tok_c[:, :, :, None, None], axis=2)
    av = jnp.where(valid[..., None, None],
                   jnp.abs(vals_sel.astype(jnp.float32)), 0.0)
    vmax = av.max(axis=(2, 4))                                 # [R, PT, H]
    rows = page_rows(pool_shape, layer, blk)                   # [H, R, PT]
    flat_s = scales.reshape(-1)
    pool = pool.reshape((-1,) + pool_shape[3:])                # [LHNB,bs,D]
    # grow the touched pages' scales (scatter-max: associative, so pages
    # hit by several tokens — or several idle rows at scratch — are safe)
    new_scales = flat_s.at[rows].max(jnp.moveaxis(vmax, 2, 0))
    s_new = new_scales[rows]                                   # [H, R, PT]
    s_old = flat_s[rows]
    ratio = jnp.where(s_new > 0, s_old / jnp.maximum(s_new, _EPS), 1.0)
    pages = pool[rows]                                         # [H,R,PT,bs,D]
    is_int = pool.dtype == jnp.dtype(jnp.int8)
    requant = pages.astype(jnp.float32) * ratio[..., None, None]
    vt = jnp.moveaxis(vals_sel, 3, 0).astype(jnp.float32)      # [H,R,PT,bs,D]
    q_new = vt * qmax / jnp.maximum(s_new, _EPS)[..., None, None]
    if is_int:  # fp8 storage keeps fractions; int8 rounds to the grid
        requant = jnp.round(requant)
        q_new = jnp.round(q_new)
    q_new = jnp.clip(q_new, -qmax, qmax)
    merged = jnp.where(valid[None, :, :, :, None], q_new, requant)
    pool = pool.at[rows].set(merged.astype(pool.dtype))
    return pool.reshape(in_shape), new_scales.reshape(scales.shape)
