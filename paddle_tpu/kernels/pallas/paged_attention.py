"""Paged (block-table) decode attention for TPU (Pallas).

TPU-native replacement for the reference's paged-KV decode kernel
(reference: paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu
and masked_multihead_attention_kernel.cu — vLLM-style block pool + per
sequence block tables).

Design:
  * pools live head-major: [H_kv, num_blocks, block_size, D] so one
    (head, block) tile is a contiguous [block_size, D] VMEM block;
  * block_tables/seq_lens ride as SCALAR PREFETCH (SMEM): the K/V
    BlockSpec index maps dereference ``tables[b, j]`` directly, so the
    kernel streams ONLY the blocks a sequence references — the round-1
    gather (`k_pool[block_tables]`) materialized the whole logical
    [B, T, H, D] cache in HBM every decode step;
  * past-end grid steps clamp their index map to the sequence's last used
    block: Pallas skips the re-fetch when consecutive steps map to the
    same block, so padded table tails cost neither bandwidth nor compute
    (the compute body is predicated off);
  * GQA native: the grid runs per KV head; the g = H_q/H_kv query heads
    of the group ride one [g, D] tile (padded to 8 sublanes);
  * online softmax across table blocks in VMEM scratch, exactly like the
    training flash kernel; fully-empty sequences emit zeros.

Decode is bandwidth-bound: the win is reading seq_len tokens of KV once,
instead of gather-writing + re-reading max_len tokens.

Page-size guidance: block_size=128 (the lane width) makes a page one
full tile, streamed once, where a dense cache at capacity is read whole
and an XLA gather reads AND writes it. Tiny vLLM-style pages (16) pay one
grid step for an eighth of a tile each, so the grid's fixed cost
dominates — pick block_size ≥ 128.
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

__all__ = ["paged_decode_attention"]

_NEG_INF = -1e30


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_sc, l_sc, acc_sc, *, scale, bs, nb):
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    ln = lens_ref[b]
    used = (ln + bs - 1) // bs

    @pl.when(j < used)
    def _compute():
        q = q_ref[0, 0]  # [g8, D]
        k = k_ref[0, 0]  # [bs, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [g8, bs]
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < ln, s, _NEG_INF)
        m_prev = m_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(pos < ln, p, 0.0)
        l_sc[:] = l_sc[:] * alpha[:, None] + jnp.sum(p, axis=1)[:, None]
        m_sc[:] = jnp.broadcast_to(m_new[:, None], m_sc.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_sc[:] = acc_sc[:] * alpha[:, None] + pv

    @pl.when(j == nb - 1)
    def _finalize():
        l = l_sc[:, 0]
        dead = (l == 0.0) | (m_sc[:, 0] <= _NEG_INF * 0.5)
        inv = jnp.where(dead, 0.0, 1.0 / jnp.maximum(l, 1e-37))
        o_ref[0, 0] = (acc_sc[:] * inv[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, seq_lens,
                           scale: float):
    """q: [B, H_q, D]; pools: [H_kv, num_blocks, bs, D];
    block_tables: [B, nb] int32; seq_lens: [B] int32 → [B, H_q, D]."""
    B, hq, D = q.shape
    hkv, _, bs, _ = k_pool.shape
    nb = block_tables.shape[1]
    g = hq // hkv
    g8 = max(8, -(-g // 8) * 8)  # pad the head group to sublane multiple
    qg = q.reshape(B, hkv, g, D)
    if g8 != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g8 - g), (0, 0)))

    def q_idx(b, h, j, tables, lens):
        return (b, h, 0, 0)

    def kv_idx(b, h, j, tables, lens):
        # clamp past-end steps to the last used block: the index repeats,
        # so Pallas skips the re-fetch and the tail costs nothing
        used_last = jnp.maximum((lens[b] + bs - 1) // bs - 1, 0)
        return (h, tables[b, jnp.minimum(j, used_last)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g8, D), q_idx),
            pl.BlockSpec((1, 1, bs, D), kv_idx),
            pl.BlockSpec((1, 1, bs, D), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, g8, D), q_idx),
        scratch_shapes=[
            pltpu.VMEM((g8, _LANES), jnp.float32),
            pltpu.VMEM((g8, _LANES), jnp.float32),
            pltpu.VMEM((g8, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bs=bs, nb=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hkv, g8, D), q.dtype),
        interpret=_interpret(),
        name=KERNELS.paged_attn,
    )(block_tables, seq_lens, qg, k_pool, v_pool)
    return out[:, :, :g].reshape(B, hq, D)
