"""Unified ragged prefill+decode paged attention for TPU (Pallas).

One kernel serves a MIXED batch over the block-paged KV pool: each row of
the ragged batch is either a decode step (q_len = 1) or a chunked-prefill
slice (q_len = chunk, causal within the chunk, attending to every prior
KV page), with per-row ``(q_len, kv_len)`` descriptors riding SCALAR
PREFETCH next to the block tables — so the serving engine's whole step is
ONE compiled dispatch instead of a prefill program plus a decode program
(Ragged Paged Attention, arXiv:2604.15464; reference block kernels
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu).

Design (extends paged_attention.py, which stays as the decode-only
baseline the two-program engine path compiles):

  * the pool is the serving engine's WHOLE buffer, layer-major then
    head-major: ``[L, H_kv, num_blocks, bs, D]`` (+ ``[L, H_kv,
    num_blocks]`` scales), and the layer to attend over is a scalar that
    rides scalar prefetch — the caller never slices a layer out of the
    pool, so the compiled step holds no pool-shaped copy (the pool's
    contract is stated once, in inference/ragged_step.py; the scales are
    small, and the wrapper hands SMEM the one layer's). One (layer,
    head, block) tile is a contiguous ``[bs, D]`` VMEM block; the K/V
    BlockSpec index maps dereference ``layer[0]`` and ``tables[r, j]`` so
    only referenced blocks stream. A 4-D ``[H_kv, num_blocks, bs, D]``
    pool is the same call with ``L = 1``, ``layer = 0`` (decided from
    ``k_pool.ndim``);
  * grid ``(R, H_kv, nb)``: rows × kv heads × table slots. Per-row
    ``kv_len`` clamps past-end steps to the last used block (Pallas skips
    the re-fetch when consecutive steps map to the same block) and the
    compute body is predicated off — a decode row costs its own blocks,
    never the batch max;
  * the q tile folds (chunk, GQA group) into one ``[C*g, D]`` MXU
    operand; in-kernel masking applies BOTH raggedness (``c < q_len``)
    and causality (``col_pos <= kv_len - q_len + c``), so decode rows and
    prefill chunks share the grid with no inter-row padding;
  * optional int8 KV: pools stored int8 with per-(head, page) scales in
    the module's absmax convention (quantization/: dequant = q·s/127),
    dequantized IN-KERNEL right after the VMEM fetch — decode is
    bandwidth-bound, so the kernel streams half the HBM bytes per step
    and a fixed pool budget admits ~2x the sequences;
  * online softmax in VMEM scratch, exactly like the training flash
    kernel; empty rows (q_len = 0) emit zeros.

Interpreter mode runs the same kernel on CPU (tier-1 parity tests).
Page-size guidance is unchanged from paged_attention.py: pick
block_size >= 128 on real TPUs; tiny vLLM-style pages drown in grid
overhead.
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

__all__ = ["ragged_paged_attention"]

_NEG_INF = -1e30


def _ragged_kernel(*refs, scale, bs, nb, g, quantized, qmax):
    if quantized:
        (tables_ref, qlens_ref, kvlens_ref, layer_ref, ks_ref, vs_ref,
         q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc) = refs
    else:
        (tables_ref, qlens_ref, kvlens_ref, layer_ref,
         q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc) = refs
        ks_ref = vs_ref = None
    r = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    ql = qlens_ref[r]
    kl = kvlens_ref[r]
    used = (kl + bs - 1) // bs

    @pl.when((j < used) & (ql > 0))
    def _compute():
        q = q_ref[0, 0]  # [CG, D] — (chunk, group) folded, c-major
        k = k_ref[0, 0, 0]  # [bs, D] (int8 when quantized)
        v = v_ref[0, 0, 0]
        if quantized:
            page = tables_ref[r, j]
            k_deq = k.astype(jnp.float32) * (ks_ref[h, page] / qmax)
            v_deq = v.astype(jnp.float32) * (vs_ref[h, page] / qmax)
        else:
            k_deq, v_deq = k, v
        s = jax.lax.dot_general(
            q, k_deq, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [CG, bs]
        # row f of the folded tile is chunk position c = f // g; its
        # absolute query position is kv_len - q_len + c (the chunk holds
        # the LAST q_len tokens of the sequence)
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = (c < ql) & (col <= kl - ql + c)
        s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        l_sc[:] = l_sc[:] * alpha[:, None] + jnp.sum(p, axis=1)[:, None]
        m_sc[:] = jnp.broadcast_to(m_new[:, None], m_sc.shape)
        pv = jax.lax.dot_general(
            p.astype(v_deq.dtype), v_deq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_sc[:] = acc_sc[:] * alpha[:, None] + pv

    @pl.when(j == nb - 1)
    def _finalize():
        l = l_sc[:, 0]
        dead = (l == 0.0) | (m_sc[:, 0] <= _NEG_INF * 0.5)
        inv = jnp.where(dead, 0.0, 1.0 / jnp.maximum(l, 1e-37))
        o_ref[0, 0] = (acc_sc[:] * inv[:, None]).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pool, v_pool, block_tables, q_lens,
                           kv_lens, scale: float,
                           k_scales=None, v_scales=None, layer=0):
    """q: [R, C, H_q, D] — row r's chunk occupies columns [0, q_lens[r]);
    pools: [L, H_kv, num_blocks, bs, D] (float, or int8 with k_scales /
    v_scales: [L, H_kv, num_blocks] f32 per-page absmax scales) and
    ``layer`` the (traced) int32 index of the layer to attend over — or
    one layer's [H_kv, num_blocks, bs, D] pool (+ [H_kv, num_blocks]
    scales), which is the L = 1 form of the same call;
    block_tables: [R, nb] int32; q_lens: [R] int32 (0 = inactive row);
    kv_lens: [R] int32 — TOTAL kv length including this chunk (query c
    sits at absolute position kv_lens - q_lens + c) → [R, C, H_q, D]."""
    R, C, hq, D = q.shape
    if k_pool.ndim == 4:  # one layer's pool: a free leading-1 reshape
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    elif k_scales is not None:
        # the layer's [H_kv, num_blocks] scales alone go to SMEM: all
        # layers' (2 x 0.75 MB at 512 pages) do not fit its 1 MB
        k_scales, v_scales = (jax.lax.dynamic_index_in_dim(
            s, layer, keepdims=False) for s in (k_scales, v_scales))
    _, hkv, _, bs, _ = k_pool.shape
    nb = block_tables.shape[1]
    g = hq // hkv
    quantized = k_scales is not None
    # quantized pools dequantize in-kernel in the absmax convention
    # (quantization/kv_cache.py): int8 grid tops at 127, e4m3 at 448
    qmax = (448.0 if k_pool.dtype == jnp.dtype(jnp.float8_e4m3fn)
            else 127.0)
    CG = C * g
    CG8 = max(8, -(-CG // 8) * 8)  # sublane-align the folded tile
    # [R, C, hkv, g, D] -> [R, hkv, C*g, D], chunk-major rows (c = f // g)
    qt = q.reshape(R, C, hkv, g, D).transpose(0, 2, 1, 3, 4)
    qt = qt.reshape(R, hkv, CG, D)
    if CG8 != CG:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, CG8 - CG), (0, 0)))

    def q_idx(r, h, j, *prefetch):
        return (r, h, 0, 0)

    def kv_idx(r, h, j, *prefetch):
        tables, qlens, kvlens = prefetch[:3]
        # clamp past-end steps to the last used block: the index repeats,
        # so Pallas skips the re-fetch and the tail costs nothing
        used_last = jnp.maximum((kvlens[r] + bs - 1) // bs - 1, 0)
        return (prefetch[3][0], h, tables[r, jnp.minimum(j, used_last)],
                0, 0)

    prefetch = [block_tables, q_lens.astype(jnp.int32),
                kv_lens.astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1)]
    if quantized:
        prefetch += [k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(R, hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, CG8, D), q_idx),
            pl.BlockSpec((1, 1, 1, bs, D), kv_idx),
            pl.BlockSpec((1, 1, 1, bs, D), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, CG8, D), q_idx),
        scratch_shapes=[
            pltpu.VMEM((CG8, _LANES), jnp.float32),
            pltpu.VMEM((CG8, _LANES), jnp.float32),
            pltpu.VMEM((CG8, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, scale=scale, bs=bs, nb=nb, g=g,
                          quantized=quantized, qmax=qmax),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, hkv, CG8, D), q.dtype),
        interpret=_interpret(),
        name=KERNELS.ragged_paged_attn,
    )(*prefetch, qt, k_pool, v_pool)
    out = out[:, :, :CG].reshape(R, hkv, C, g, D)
    return out.transpose(0, 2, 1, 3, 4).reshape(R, C, hq, D)
