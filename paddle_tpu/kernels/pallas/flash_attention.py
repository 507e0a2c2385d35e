"""Tiled flash attention for TPU (Pallas).

TPU-native replacement for the reference's CUDA FlashAttention-2 integration
(reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu — dense :68 and
varlen :213 entry points; third_party/flashattn; Python surface
python/paddle/nn/functional/flash_attention.py:242,976,1098).

Design (FlashAttention-2 style, mapped onto the TPU memory hierarchy):
  * grid = (batch*heads, q_blocks, k_blocks); the k axis is innermost so the
    online-softmax state (m, l, acc) carries across k steps in VMEM scratch —
    the scores matrix never exists in HBM (O(S) memory instead of O(S^2)).
  * QK^T and PV run on the MXU with fp32 accumulation
    (preferred_element_type); rescaling on the VPU.
  * fully-masked blocks are skipped (predicated with pl.when) from the
    *structure* of the mask — causal diagonal and sliding-window band — not
    from a dense mask tensor; structured masks that can't be block-skipped
    (segments, flashmask rows, additive bias) are applied elementwise inside
    the tile, still O(S) HBM.
  * backward = two kernels (dkv with q innermost; dq with k innermost) using
    the saved logsumexp and a precomputed delta = rowsum(dO * O), per the
    FlashAttention-2 backward recurrence.

Mask/variant support (all inside the kernel — nothing falls back to an
O(S^2) composed path):
  * causal, bottom-right aligned when sq != sk (matches the composed
    reference and FlashAttention-2 semantics);
  * GQA/MQA native: key/value may carry fewer heads (H % H_kv == 0); KV
    blocks are *indexed* per query-head group via BlockSpec index maps — KV
    is never repeated in HBM (reference repeats via expand before the CUDA
    kernel when num_heads differ);
  * packed-varlen segment ids (q/kv position → sequence id; cross-segment
    scores masked) — the TPU analogue of the reference's cu_seqlens varlen
    kernel (flash_attn_kernel.cu:213);
  * sliding window (left, right) with block-level skipping;
  * flashmask start/end row indices per key column ([B, 1|H, Sk] each; key
    j masked for queries start<=q<end — the reference's
    flashmask_attention O(S) mask representation);
  * additive bias [1|B, 1|H, Sq, Sk] (covers bool masks converted to 0/-inf);
  * dropout via the in-kernel TPU PRNG: the forward draws the keep mask from
    (seed, head, q_block, k_block) and the backward re-derives the identical
    mask from the same counters — no O(S^2) mask tensor is ever saved
    (reference: philox seed/offset round-tripped through the CUDA kernel).

Layouts: public API takes paddle convention [B, S, H, D]; kernels run on
[B*H, S, D] (queries) and [B*H_kv, S, D] (keys/values).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from ...enforce import enforce
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import LANES as _LANES
from ._common import interpret as _interpret
from ...observability.trace import KERNELS

__all__ = ["flash_attention", "supported", "FLASH_REMAT_NAMES"]

# checkpoint_name tags on the differentiation residuals (the kernel output
# and its logsumexp) — the FP8_REMAT_NAMES pattern: inert under plain
# jax.checkpoint (the always-checkpointed pipeline stages replay the flash
# KERNEL, O(S) HBM, never a composed einsum), but a selective-remat policy
# (dense_forward remat_save + these names) keeps (out, lse) so the backward
# reuses the flash forward instead of re-running it.
FLASH_REMAT_NAMES = ("flash_out", "flash_lse")

_NEG_INF = -1e30


def _pick_block(s: int, target: int = 1024) -> int:
    """Largest power-of-two-ish divisor of s up to `target`. Bigger q/k
    tiles amortize the softmax rescale over more columns; the fp32 scores
    tile at 1024x1024 (4 MB) still fits VMEM comfortably (the order 1024 >
    512 > 256 is not measured on the current installation)."""
    b = min(target, s)
    while s % b:
        b //= 2
    return max(b, 1)


def _block_target(has_extras: bool) -> int:
    # 1024 tiles fit VMEM even WITH the extra per-tile inputs (bias 2 MB
    # bf16 + dropout bits 4 MB beside the 4 MB fp32 scores); their speed
    # against 512 on the masked paths is not measured on the current
    # installation
    del has_extras
    return 1024


def supported(query, key, value, attn_mask=None, dropout_p=0.0,
              is_causal=False, *args, **kwargs) -> bool:
    """Gate for registry dispatch. The tiled kernel handles dense/causal/
    masked/GQA attention with dropout; remaining fallbacks: rank != 4,
    head_dim > 256, sequence lengths not multiples of 128 (pad upstream),
    or a mask that isn't [1|B, 1|H, Sq, Sk]."""
    if getattr(query, "ndim", 0) != 4 or key.ndim != 4 or value.ndim != 4:
        return False
    b, sq, h, d = query.shape
    kb, sk, h_kv, kd = key.shape
    if kb != b or kd != d or tuple(value.shape) != tuple(key.shape):
        return False
    if h_kv == 0 or h % h_kv != 0:
        return False
    if d > 256:
        return False
    if not (sq % 128 == 0 and sk % 128 == 0):
        return False
    if attn_mask is not None:
        if getattr(attn_mask, "ndim", 0) != 4:
            return False
        mb, mh, msq, msk = attn_mask.shape
        if (msq, msk) != (sq, sk) or mb not in (1, b) or mh not in (1, h):
            return False
    seg = kwargs.get("segment_ids")
    if seg is not None and tuple(getattr(seg, "shape", ())) != (b, sq):
        return False
    if dropout_p and not 0.0 <= float(dropout_p) < 1.0:
        return False
    return True


# ---------------------------------------------------------------------------
# in-kernel mask application / block skipping
# ---------------------------------------------------------------------------

def _mask_scores(s, i, j, *, block_q, block_k, causal, offset, window,
                 bias=None, qseg=None, kseg=None, fm_start=None, fm_end=None):
    """Apply bias + structured masks to a scores tile. i/j are q/k block
    ids; offset aligns causal bottom-right for sq != sk."""
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    need_pos = causal or window is not None or fm_start is not None
    qpos = kpos = None
    if need_pos:
        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
    masked = None

    def _or(a, b):
        return b if a is None else a | b

    if causal:
        masked = _or(masked, kpos > qpos + offset)
    if window is not None:
        left, right = window
        if left is not None:
            masked = _or(masked, kpos < qpos + offset - left)
        if right is not None:
            masked = _or(masked, kpos > qpos + offset + right)
    if qseg is not None:
        # qseg arrives as a [bq, 1] COLUMN (sublane-major, pre-broadcast
        # outside the kernel); kseg as a [bk] lane vector. A [bq]
        # lane-vector qseg here would need an in-tile cross-lane
        # transpose in every tile of the backward.
        masked = _or(masked, qseg != kseg[None, :])
    if fm_start is not None:
        masked = _or(masked, (qpos >= fm_start[None, :])
                     & (qpos < fm_end[None, :]))
    if masked is not None:
        s = jnp.where(masked, _NEG_INF, s)
    return s


def _seg_block_overlap(masks):
    """Dynamic per-tile gate for packed-varlen inputs: False iff the q and
    k segment-id RANGES in this tile are disjoint — every (q, k) pair then
    has qseg != kseg, the tile is fully masked, and skipping its matmuls/
    softmax entirely is exact. Range overlap is conservative for arbitrary
    id layouts; for first-fit packing (ids ascend within a row,
    models/bert.py pack_sequences) it skips ~1 - sum(len_i^2)/S^2 of the
    tiles — the TPU analogue of the reference varlen kernel launching
    per-sequence (flash_attn_kernel.cu cu_seqlens). Pad tails (-1) keep
    their current semantics: all-pad x all-pad tiles still run."""
    _, qseg_ref, kseg_ref, _, _ = masks
    if qseg_ref is None:
        return None
    qcol = qseg_ref[0][:, :1]   # [bq, 1] sublane column
    klane = kseg_ref[0, 0]      # [bk] lane vector
    return ((jnp.min(qcol) <= jnp.max(klane))
            & (jnp.max(qcol) >= jnp.min(klane)))


def _block_run(i, j, *, block_q, block_k, causal, offset, window):
    """True iff block (i, j) can contain any unmasked score, from the
    causal diagonal and window band alone (segments/flashmask/bias are
    handled elementwise)."""
    run = None
    q_lo = i * block_q
    q_hi = i * block_q + block_q - 1
    k_lo = j * block_k
    k_hi = j * block_k + block_k - 1

    def _and(a, b):
        return b if a is None else jnp.logical_and(a, b)

    if causal:
        run = _and(run, k_lo <= q_hi + offset)
    if window is not None:
        left, right = window
        if left is not None:
            run = _and(run, k_hi >= q_lo + offset - left)
        if right is not None:
            run = _and(run, k_lo <= q_hi + offset + right)
    return True if run is None else run


def _last_k_block(i, num_k, *, block_q, block_k, causal, offset, window):
    """Index of the last k block that runs for q block i (finalize point)."""
    if not causal and (window is None or window[1] is None):
        return num_k - 1
    hi = i * block_q + block_q - 1 + offset
    if causal and window is not None and window[1] is not None:
        hi = hi + 0  # causal is the tighter bound (right >= 0)
    elif window is not None and window[1] is not None and not causal:
        hi = hi + window[1]
    return jnp.clip(hi // block_k, 0, num_k - 1)


def _dropout_keep(seed_ref, bh, i, j, num_q, num_k, shape, dropout_p):
    """Deterministic keep-mask for tile (bh, i, j): forward and backward
    re-derive identical bits from the same counters. Mosaic allows at most
    two seed words, so the tile coordinates fold into one id."""
    tile = (bh * num_q + i) * num_k + j
    pltpu.prng_seed(seed_ref[0], tile)
    bits = pltpu.prng_random_bits(shape)
    thresh = min(int(dropout_p * 2.0 ** 32), 2 ** 32 - 1)
    return bits.astype(jnp.uint32) >= jnp.uint32(thresh)


def _unpack_refs(refs, *, n_main, has_bias, has_seg, has_fm, dropout_p):
    """Split positional pallas refs into (seed, main tensors, mask refs,
    outputs+scratch) by the active feature flags — ONE walk shared by all
    three kernels so the layouts cannot drift from the input assembly in
    _fwd/_bwd_impl."""
    idx = 0
    seed = None
    if dropout_p:
        seed = refs[idx]; idx += 1
    main = refs[idx:idx + n_main]; idx += n_main
    bias = qseg = kseg = fms = fme = None
    if has_bias:
        bias = refs[idx]; idx += 1
    if has_seg:
        qseg, kseg = refs[idx:idx + 2]; idx += 2
    if has_fm:
        fms, fme = refs[idx:idx + 2]; idx += 2
    return seed, main, (bias, qseg, kseg, fms, fme), refs[idx:]


def _mask_ref_args(masks):
    """Materialize the per-tile mask operands for _mask_scores."""
    bias_ref, qseg_ref, kseg_ref, fms_ref, fme_ref = masks
    return dict(
        bias=bias_ref[0, 0] if bias_ref is not None else None,
        # [bq, 1] column slice of the lane-broadcast q-side ids
        qseg=qseg_ref[0][:, :1] if qseg_ref is not None else None,
        kseg=kseg_ref[0, 0] if kseg_ref is not None else None,
        fm_start=fms_ref[0, 0] if fms_ref is not None else None,
        fm_end=fme_ref[0, 0] if fme_ref is not None else None)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, sm_scale, causal, offset, window, block_q, block_k,
                num_k, has_bias, has_seg, has_fm, dropout_p, save_lse):
    seed_ref, (q_ref, k_ref, v_ref), masks, rest = _unpack_refs(
        refs, n_main=3, has_bias=has_bias, has_seg=has_seg, has_fm=has_fm,
        dropout_p=dropout_p)
    if save_lse:
        o_ref, lse_ref = rest[0], rest[1]
        acc_sc, m_sc, l_sc = rest[2:5]
    else:
        o_ref, lse_ref = rest[0], None
        acc_sc, m_sc, l_sc = rest[1:4]

    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    run = _block_run(i, j, block_q=block_q, block_k=block_k, causal=causal,
                     offset=offset, window=window)
    if has_seg:
        run = jnp.logical_and(run, _seg_block_overlap(masks))

    @pl.when(run)
    def _compute():
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        s = _mask_scores(
            s, i, j, block_q=block_q, block_k=block_k, causal=causal,
            offset=offset, window=window, **_mask_ref_args(masks))
        m_prev = m_sc[:, 0]                      # [bq]
        m_cur = jnp.max(s, axis=1)               # [bq]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)          # [bq]
        p = jnp.exp(s - m_new[:, None])          # [bq, bk] f32
        l_sc[:] = (l_sc[:] * alpha[:, None]
                   + jnp.sum(p, axis=1)[:, None])
        m_sc[:] = jnp.broadcast_to(m_new[:, None], m_sc.shape)
        if dropout_p:
            keep = _dropout_keep(seed_ref, b, i, j, pl.num_programs(1),
                                  num_k, p.shape, dropout_p)
            p_use = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
        else:
            p_use = p
        pv = jax.lax.dot_general(
            p_use.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_sc[:] = acc_sc[:] * alpha[:, None] + pv

    j_last = _last_k_block(i, num_k, block_q=block_q, block_k=block_k,
                           causal=causal, offset=offset, window=window)

    @pl.when(j == j_last)
    def _finalize():
        l = l_sc[:, 0]
        # a row is dead if no block ran for it (l == 0) OR every score was
        # elementwise-masked to _NEG_INF (m never rose above it; p = exp(0)
        # makes l = block_k there, so l alone can't detect it) — emit zeros,
        # matching the composed path's fully-masked-row contract
        dead = (l == 0.0) | (m_sc[:, 0] <= _NEG_INF * 0.5)
        inv = jnp.where(dead, 0.0, 1.0 / jnp.maximum(l, 1e-37))
        o_ref[0] = (acc_sc[:] * inv[:, None]).astype(o_ref.dtype)
        if lse_ref is not None:
            # dead rows keep lse ~ _NEG_INF so the backward can re-detect
            # them (p must be zero there, not exp(0))
            lse = m_sc[:, 0] + jnp.log(jnp.maximum(l, 1e-37))
            lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


def _bias_index(fwd_grid, bias_shape, h, h_kv, g, nq):
    """Index map for the [1|B, 1|H, Sq, Sk] bias under a given grid
    convention. fwd_grid: True for (bh, i, j) grids (fwd/dq), False for the
    dkv grid (bh_kv, j, t)."""
    mb, mh = bias_shape[0], bias_shape[1]
    if fwd_grid:
        def idx(b, i, j):
            bi = b // h if mb > 1 else 0
            hi = b % h if mh > 1 else 0
            return (bi, hi, i, j)
    else:
        def idx(bkv, j, t):
            bi = bkv // h_kv if mb > 1 else 0
            hi = ((bkv % h_kv) * g + t // nq) if mh > 1 else 0
            return (bi, hi, t % nq, j)
    return idx


def _build_specs(*, grid_kind, h, h_kv, g, nq, block_q, block_k, d,
                 bias_shape, has_seg, has_fm, dropout_p, fm_mh=None):
    """in_specs tail (bias/segments/flashmask) shared by fwd/dq/dkv, plus
    the optional SMEM seed spec at the head."""
    fwd_grid = grid_kind in ("fwd", "dq")
    head = []
    if dropout_p:
        head.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    tail = []
    if bias_shape is not None:
        tail.append(pl.BlockSpec(
            (1, 1, block_q, block_k),
            _bias_index(fwd_grid, bias_shape, h, h_kv, g, nq)))
    if has_seg:
        # q-side ids ride lane-BROADCAST as [B, Sq, LANES] (the lse/delta
        # pattern) so the kernel reads a sublane-major [bq, 1] column with
        # no in-tile transpose; k-side ids ride lane-major [B, 1, Sk]
        if fwd_grid:
            qidx = lambda b, i, j: (b // h, i, 0)
            kidx = lambda b, i, j: (b // h, 0, j)
        else:
            qidx = lambda bkv, j, t: (bkv // h_kv, t % nq, 0)
            kidx = lambda bkv, j, t: (bkv // h_kv, 0, j)
        tail.append(pl.BlockSpec((1, block_q, _LANES), qidx))
        tail.append(pl.BlockSpec((1, 1, block_k), kidx))
    if has_fm:
        # flashmask arrays ride flattened as [B*Hm, 1, Sk] (same tiling rule)
        mh = fm_mh
        if fwd_grid:
            def fm_idx(b, i, j):
                return (b // h * mh + (b % h if mh > 1 else 0), 0, j)
        else:
            def fm_idx(bkv, j, t):
                hi = ((bkv % h_kv) * g + t // nq) if mh > 1 else 0
                return (bkv // h_kv * mh + hi, 0, j)
        tail.append(pl.BlockSpec((1, 1, block_k), fm_idx))
        tail.append(pl.BlockSpec((1, 1, block_k), fm_idx))
    return head, tail


def _sds(shape, dtype, vma=None):
    """ShapeDtypeStruct with an optional varying-mesh-axes set — required
    when the kernel runs inside shard_map with check_vma=True (the ring
    attention path passes its mesh axis here)."""
    if vma is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))


def _prep_mask_operands(qseg, kseg, fm_start, fm_end):
    """Reshape mask operands to their kernel ride layouts (q segments
    lane-broadcast [B,Sq,LANES], k segments [B,1,Sk], flashmask
    [B*Hm,1,Sk]) — shared by _fwd and _bwd_impl."""
    fm_mh = None
    if qseg is not None:
        qseg = jnp.broadcast_to(qseg[:, :, None],
                                (*qseg.shape, _LANES))
        kseg = kseg[:, None, :]
    if fm_start is not None:
        fm_mh = fm_start.shape[1]
        fm_start = fm_start.reshape(-1, 1, fm_start.shape[-1])
        fm_end = fm_end.reshape(-1, 1, fm_end.shape[-1])
    return qseg, kseg, fm_start, fm_end, fm_mh


def _mask_input_list(bias, qseg, kseg, fm_start, fm_end):
    """Input-list tail matching _build_specs' tail ordering exactly."""
    inputs = []
    if bias is not None:
        inputs.append(bias)
    if qseg is not None:
        inputs += [qseg, kseg]
    if fm_start is not None:
        inputs += [fm_start, fm_end]
    return inputs


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, *, h, h_kv,
         bias=None, qseg=None, kseg=None, fm_start=None, fm_end=None,
         window=None, dropout_p=0.0, seed=None, save_lse=True, vma=None,
         native=False):
    """q: [B*H, Sq, D]; k/v: [B*H_kv, Sk, D]. With native=True the main
    tensors arrive HEAD-NATIVE as [B, Sq, H*D] / [B, Sk, H_kv*D] (a free
    reshape of the model's [B, S, H, D]) and each program's (1, block, d)
    tile is lane-sliced out of the fused head dim by the index map — no
    host-side [B,S,H,D] -> [B*H,S,D] transpose copy ever happens. Only
    legal when d % 128 == 0 (Mosaic lane-block divisibility); the kernel
    body is identical either way."""
    if native:
        b_n, sq, hd = q.shape
        d = hd // h
        bh = b_n * h
    else:
        bh, sq, d = q.shape
    sk = k.shape[1]
    g = h // h_kv
    nq, nk = sq // block_q, sk // block_k
    offset = sk - sq
    grid = (bh, nq, nk)
    qseg, kseg, fm_start, fm_end, fm_mh = _prep_mask_operands(
        qseg, kseg, fm_start, fm_end)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, offset=offset,
        window=window, block_q=block_q, block_k=block_k, num_k=nk,
        has_bias=bias is not None, has_seg=qseg is not None,
        has_fm=fm_start is not None, dropout_p=dropout_p, save_lse=save_lse)

    if native:
        kv_idx = lambda b, i, j: (b // h, j, (b % h) // g)
        in_specs = [
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b // h, i, b % h)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
        ]
    else:
        kv_idx = lambda b, i, j: (b // h * h_kv + (b % h) // g, j, 0)
        in_specs = [
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
        ]
    head, tail = _build_specs(
        grid_kind="fwd", h=h, h_kv=h_kv, g=g, nq=nq, block_q=block_q,
        block_k=block_k, d=d, bias_shape=None if bias is None else bias.shape,
        has_seg=qseg is not None, has_fm=fm_start is not None,
        dropout_p=dropout_p, fm_mh=fm_mh)
    in_specs = head + in_specs + tail

    inputs = ([seed] if dropout_p else []) + [q, k, v] + _mask_input_list(
        bias, qseg, kseg, fm_start, fm_end)

    if native:
        ospec = pl.BlockSpec((1, block_q, d),
                             lambda b, i, j: (b // h, i, b % h))
        oshape = (bh // h, sq, h * d)
    else:
        ospec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
        oshape = (bh, sq, d)
    lspec = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0))
    if save_lse:
        out_specs = [ospec, lspec]
        out_shape = [_sds(oshape, q.dtype, vma),
                     _sds((bh, sq, _LANES), jnp.float32, vma)]
    else:
        out_specs = ospec
        out_shape = _sds(oshape, q.dtype, vma)
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name=KERNELS.flash_fwd,
    )(*inputs)
    if save_lse:
        out, lse = res
        return out, lse[:, :, 0]
    return res, None


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dkv_kernel(*refs, sm_scale, causal, offset, window, block_q, block_k,
                num_q, num_t, h, h_kv, g, has_bias, has_seg, has_fm,
                dropout_p):
    seed_ref, main, masks, rest = _unpack_refs(
        refs, n_main=6, has_bias=has_bias, has_seg=has_seg, has_fm=has_fm,
        dropout_p=dropout_p)
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = main
    dk_ref, dv_ref, dk_sc, dv_sc = rest

    bkv = pl.program_id(0)
    j = pl.program_id(1)   # k block
    t = pl.program_id(2)   # (q head in group) * num_q + q block — innermost
    i = t % num_q
    bh_q = bkv // h_kv * h + (bkv % h_kv) * g + t // num_q

    @pl.when(t == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    run = _block_run(i, j, block_q=block_q, block_k=block_k, causal=causal,
                     offset=offset, window=window)
    if has_seg:
        run = jnp.logical_and(run, _seg_block_overlap(masks))

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        kk = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]      # [bq]
        # delta = rowsum(dO * O) computed in-VMEM: a [bq] reduce over d is
        # ~free here, while the precomputed lane-replicated delta tensor
        # cost a [bh, sq, 128] fp32 broadcast + two big HBM reads per layer
        delta = jnp.sum(o_ref[0].astype(jnp.float32)
                        * do.astype(jnp.float32), axis=-1)
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        s = _mask_scores(
            s, i, j, block_q=block_q, block_k=block_k, causal=causal,
            offset=offset, window=window, **_mask_ref_args(masks))
        p = jnp.exp(s - lse[:, None])  # [bq, bk], undropped softmax
        # fully-masked (dead) rows carry lse ~ _NEG_INF: exp(s - lse) would
        # be exp(0) = 1 there — zero them so no gradient leaks through
        p = jnp.where((lse <= _NEG_INF * 0.5)[:, None], 0.0, p)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_p:
            keep = _dropout_keep(seed_ref, bh_q, i, j, num_q,
                                 pl.num_programs(1), p.shape, dropout_p)
            inv_keep = 1.0 / (1.0 - dropout_p)
            p_drop = jnp.where(keep, p * inv_keep, 0.0)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        else:
            p_drop = p
        # dv += D(P)^T dO
        dv_sc[:] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dS = P*(dP∘M/keep - delta)*scale
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_sc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == num_t - 1)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _dq_kernel(*refs, sm_scale, causal, offset, window, block_q, block_k,
               num_k, has_bias, has_seg, has_fm, dropout_p,
               bias_grad=False):
    seed_ref, main, masks, rest = _unpack_refs(
        refs, n_main=6, has_bias=has_bias, has_seg=has_seg, has_fm=has_fm,
        dropout_p=dropout_p)
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = main
    if bias_grad:
        dq_ref, db_ref, dq_sc = rest
    else:
        dq_ref, dq_sc = rest
        db_ref = None

    b = pl.program_id(0)
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # k block (innermost: carry dq)

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    run = _block_run(i, j, block_q=block_q, block_k=block_k, causal=causal,
                     offset=offset, window=window)
    if has_seg:
        run = jnp.logical_and(run, _seg_block_overlap(masks))

    if bias_grad:
        @pl.when(jnp.logical_not(run))
        def _zero_db():
            # block-skipped tiles (outside causal/window bands) still own
            # their slice of the dbias output — make it zeros, not garbage
            db_ref[0] = jnp.zeros_like(db_ref[0])

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        kk = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]
        delta = jnp.sum(o_ref[0].astype(jnp.float32)
                        * do.astype(jnp.float32), axis=-1)  # [bq], in-VMEM
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _mask_scores(
            s, i, j, block_q=block_q, block_k=block_k, causal=causal,
            offset=offset, window=window, **_mask_ref_args(masks))
        p = jnp.exp(s - lse[:, None])
        p = jnp.where((lse <= _NEG_INF * 0.5)[:, None], 0.0, p)  # dead rows
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_p:
            keep = _dropout_keep(seed_ref, b, i, j, pl.num_programs(1),
                                  num_k, p.shape, dropout_p)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_p)), 0.0)
        # dS without the sm_scale factor IS the additive-bias gradient
        # (s = qk*scale + bias): emit it per tile — every mask/dropout
        # effect is already inside p/dp, so dbias composes with all of them
        ds_raw = p * (dp - delta[:, None])
        if bias_grad:
            db_ref[0] = ds_raw.astype(db_ref.dtype)
        ds = ds_raw * sm_scale
        dq_sc[:] += jax.lax.dot_general(
            ds.astype(kk.dtype), kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    j_last = _last_k_block(i, num_k, block_q=block_q, block_k=block_k,
                           causal=causal, offset=offset, window=window)

    @pl.when(j == j_last)
    def _finalize():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_impl(q, k, v, out, lse, do, sm_scale, causal, block_q, block_k, *,
              h, h_kv, bias=None, qseg=None, kseg=None, fm_start=None,
              fm_end=None, window=None, dropout_p=0.0, seed=None, vma=None,
              bias_grad=False, native=False):
    if native:
        b_n, sq, hd = q.shape
        d = hd // h
        bh, bh_kv = b_n * h, b_n * h_kv
        sk = k.shape[1]
    else:
        bh, sq, d = q.shape
        bh_kv, sk, _ = k.shape
    g = h // h_kv
    nq, nk = sq // block_q, sk // block_k
    offset = sk - sq
    # delta = rowsum(dO * O) is computed inside the kernels (o rides the
    # same BlockSpec as do — 4 bytes/elem bf16 read vs a lane-replicated
    # [bh, sq, 128] fp32 broadcast + two reads)
    lse_r = jnp.broadcast_to(lse[:, :, None], (bh, sq, _LANES))

    qseg, kseg, fm_start, fm_end, fm_mh = _prep_mask_operands(
        qseg, kseg, fm_start, fm_end)
    bias_shape = None if bias is None else bias.shape
    has_seg = qseg is not None
    has_fm = fm_start is not None
    extra_inputs = _mask_input_list(bias, qseg, kseg, fm_start, fm_end)
    seed_inputs = [seed] if dropout_p else []

    # ---- dk/dv: grid (B*H_kv, k blocks, group*q blocks) — the q-head
    # group is folded into the innermost axis so GQA reductions accumulate
    # in the VMEM scratch rather than racing on an HBM block.
    num_t = g * nq
    if native:
        qspec = pl.BlockSpec(
            (1, block_q, d),
            lambda bkv, j, t: (bkv // h_kv, t % nq,
                               (bkv % h_kv) * g + t // nq))
        kspec = pl.BlockSpec((1, block_k, d),
                             lambda bkv, j, t: (bkv // h_kv, j, bkv % h_kv))
    else:
        qspec = pl.BlockSpec(
            (1, block_q, d),
            lambda bkv, j, t: (bkv // h_kv * h + (bkv % h_kv) * g + t // nq,
                               t % nq, 0))
        kspec = pl.BlockSpec((1, block_k, d), lambda bkv, j, t: (bkv, j, 0))
    rspec = pl.BlockSpec(
        (1, block_q, _LANES),
        lambda bkv, j, t: (bkv // h_kv * h + (bkv % h_kv) * g + t // nq,
                           t % nq, 0))
    head, tail = _build_specs(
        grid_kind="dkv", h=h, h_kv=h_kv, g=g, nq=nq, block_q=block_q,
        block_k=block_k, d=d, bias_shape=bias_shape, has_seg=has_seg,
        has_fm=has_fm, dropout_p=dropout_p, fm_mh=fm_mh)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal, offset=offset,
            window=window, block_q=block_q, block_k=block_k, num_q=nq,
            num_t=num_t, h=h, h_kv=h_kv, g=g, has_bias=bias is not None,
            has_seg=has_seg, has_fm=has_fm, dropout_p=dropout_p),
        grid=(bh_kv, nk, num_t),
        in_specs=head + [qspec, kspec, kspec, qspec, qspec, rspec] + tail,
        out_specs=[kspec, kspec],
        out_shape=[
            _sds(k.shape, k.dtype, vma),
            _sds(v.shape, v.dtype, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
        name=KERNELS.flash_bwd_dkv,
    )(*seed_inputs, q, k, v, out, do, lse_r, *extra_inputs)

    # ---- dq: grid (B*H, q blocks, k blocks)
    if native:
        qspec2 = pl.BlockSpec((1, block_q, d),
                              lambda b, i, j: (b // h, i, b % h))
        kspec2 = pl.BlockSpec((1, block_k, d),
                              lambda b, i, j: (b // h, j, (b % h) // g))
    else:
        kv_idx = lambda b, i, j: (b // h * h_kv + (b % h) // g, j, 0)
        qspec2 = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
        kspec2 = pl.BlockSpec((1, block_k, d), kv_idx)
    rspec2 = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0))
    head, tail = _build_specs(
        grid_kind="dq", h=h, h_kv=h_kv, g=g, nq=nq, block_q=block_q,
        block_k=block_k, d=d, bias_shape=bias_shape, has_seg=has_seg,
        has_fm=has_fm, dropout_p=dropout_p, fm_mh=fm_mh)
    emit_db = bias_grad and bias is not None
    dq_ospec = qspec2
    dq_oshape = _sds(q.shape, q.dtype, vma)
    if emit_db:
        # in-kernel dbias: each (b, i, j) tile writes its slice of the
        # full-resolution [B*H, Sq, Sk] gradient once (fp32); broadcast
        # bias shapes reduce OUTSIDE the kernel. Strictly cheaper than the
        # old composed recompute (no second QK/PV matmul pass) and composes
        # with dropout/segments/window/flashmask since ds already does.
        out_specs = [dq_ospec, pl.BlockSpec((1, block_q, block_k),
                                            lambda b, i, j: (b, i, j))]
        out_shape = [dq_oshape, _sds((bh, sq, sk), jnp.float32, vma)]
    else:
        out_specs, out_shape = dq_ospec, dq_oshape
    res = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal, offset=offset,
            window=window, block_q=block_q, block_k=block_k, num_k=nk,
            has_bias=bias is not None, has_seg=has_seg, has_fm=has_fm,
            dropout_p=dropout_p, bias_grad=emit_db),
        grid=(bh, nq, nk),
        in_specs=head + [qspec2, kspec2, kspec2, qspec2, qspec2, rspec2]
        + tail,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name=KERNELS.flash_bwd_dq,
    )(*seed_inputs, q, k, v, out, do, lse_r, *extra_inputs)
    if emit_db:
        dq, db_full = res
        return dq, dk, dv, db_full
    return res, dk, dv, None


# ---------------------------------------------------------------------------
# public API ([B, S, H, D] layout, custom_vjp)
# ---------------------------------------------------------------------------

def _prep(x):
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def _unprep(x, b, h):
    bh, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)


_STATIC = (7, 8, 9, 10, 11, 12, 13)  # causal, sm_scale, block_q, block_k,
#                                       window, dropout_p, bias_grad


@functools.partial(jax.custom_vjp, nondiff_argnums=_STATIC)
def _flash(query, key, value, bias, q_seg, kv_seg, seed,
           causal, sm_scale, block_q, block_k, window, dropout_p,
           bias_grad=False):
    out, _ = _flash_fwd_impl(query, key, value, bias, q_seg, kv_seg, seed,
                             causal, sm_scale, block_q, block_k, window,
                             dropout_p, save_lse=False)
    return out


def _flash_fwd_impl(query, key, value, bias, q_seg, kv_seg, seed,
                    causal, sm_scale, block_q, block_k, window, dropout_p,
                    save_lse):
    b, sq, h, d = query.shape
    h_kv = key.shape[2]
    fm_start = fm_end = None
    if bias is not None and isinstance(bias, tuple):
        bias, fm_start, fm_end = bias
    # head-native lane slicing needs d % 128 == 0 (Mosaic lane blocks);
    # smaller heads pay the [B,S,H,D] -> [B*H,S,D] transpose copy
    native = d % 128 == 0
    if native:
        q = query.reshape(b, sq, h * d)
        k = key.reshape(b, key.shape[1], h_kv * d)
        v = value.reshape(b, value.shape[1], h_kv * d)
    else:
        q, k, v = _prep(query), _prep(key), _prep(value)
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, h=h,
                    h_kv=h_kv, bias=bias, qseg=q_seg, kseg=kv_seg,
                    fm_start=fm_start, fm_end=fm_end, window=window,
                    dropout_p=dropout_p, seed=seed, save_lse=save_lse,
                    native=native)
    out4 = out.reshape(b, sq, h, d) if native else _unprep(out, b, h)
    return out4, (q, k, v, out, lse, b, h, h_kv, native)


def _flash_fwd(query, key, value, bias, q_seg, kv_seg, seed,
               causal, sm_scale, block_q, block_k, window, dropout_p,
               bias_grad=False):
    out, res = _flash_fwd_impl(query, key, value, bias, q_seg, kv_seg, seed,
                               causal, sm_scale, block_q, block_k, window,
                               dropout_p, save_lse=True)
    q, k, v, out_r, lse, b, h, h_kv, native = res
    # tag the forward's residuals (FLASH_REMAT_NAMES) so a selective-remat
    # policy can keep them: (out, lse) saved => the backward kernels run
    # without replaying the forward kernel (q/k/v are cheap reshapes of the
    # projection outputs the model tags itself, e.g. dense_block's "qkv")
    out_r = checkpoint_name(out_r, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    # re-derive the public [B, Sq, H, D] output FROM the tagged residual:
    # downstream primal uses and the backward then both hang off the SAVED
    # value — reshaping the untagged kernel output instead would leave the
    # recompute side needing the original var and re-running the kernel
    out = (out_r.reshape(b, out_r.shape[1], h, -1) if native
           else _unprep(out_r, b, h))
    res = (q, k, v, out_r, lse, b, h, h_kv, native)
    return out, res + (bias, q_seg, kv_seg, seed)


def _flash_bwd(causal, sm_scale, block_q, block_k, window, dropout_p,
               bias_grad, res, g):
    q, k, v, out, lse, b, h, h_kv, native, bias, q_seg, kv_seg, seed = res
    fm_start = fm_end = None
    is_fm = bias is not None and isinstance(bias, tuple)
    if is_fm:
        bias, fm_start, fm_end = bias
    bsq, d4 = g.shape[1], g.shape[3]
    do = g.reshape(b, bsq, h * d4) if native else _prep(g)
    dq, dk, dv, db_full = _bwd_impl(
        q, k, v, out, lse, do, sm_scale, causal, block_q, block_k, h=h,
        h_kv=h_kv, bias=bias, qseg=q_seg, kseg=kv_seg, fm_start=fm_start,
        fm_end=fm_end, window=window, dropout_p=dropout_p, seed=seed,
        bias_grad=bias_grad, native=native)
    dbias = None
    if bias is not None or is_fm:
        if bias_grad and bias is not None:
            # in-kernel dbias: the dq kernel emitted the full-resolution
            # [B*H, Sq, Sk] dS; reduce to the (possibly broadcast) bias
            # shape here
            ds = db_full.reshape(b, h, *db_full.shape[-2:])
            if bias.shape[0] == 1:
                ds = ds.sum(axis=0, keepdims=True)
            if bias.shape[1] == 1:
                ds = ds.sum(axis=1, keepdims=True)
            db = ds.astype(bias.dtype)
            dbias = (db, jnp.zeros_like(fm_start),
                     jnp.zeros_like(fm_end)) if is_fm else db
        else:
            # constant-mask contract (padding masks, flashmask rows) — the
            # reference flash kernels likewise emit no mask gradient. Pass
            # bias_grad=True for a LEARNED bias (in-kernel dS emission);
            # flashmask rows are integer indices and always get zeros, so
            # bias_grad with flashmask-but-no-bias degrades to that.
            dbias = jax.tree_util.tree_map(jnp.zeros_like,
                                           (bias, fm_start, fm_end)
                                           if is_fm else bias)
    if native:
        sk = k.shape[1]
        return (dq.reshape(b, bsq, h, d4), dk.reshape(b, sk, h_kv, d4),
                dv.reshape(b, sk, h_kv, d4), dbias, None, None, None)
    return (_unprep(dq, b, h), _unprep(dk, b, h_kv), _unprep(dv, b, h_kv),
            dbias, None, None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(query, key, value, causal=False, sm_scale=None,
                    block_q=None, block_k=None, *, bias=None,
                    q_segment_ids=None, kv_segment_ids=None,
                    startend_row_indices=None, window=None,
                    dropout_p=0.0, dropout_seed=None, bias_grad=False):
    """Fused attention. query: [B, Sq, H, D]; key/value: [B, Sk, H_kv, D]
    with H % H_kv == 0 (GQA/MQA native — KV heads are indexed, not
    repeated) → [B, Sq, H, D].

    bias: additive [1|B, 1|H, Sq, Sk] (use 0/-1e30 for bool masks). Treated
    as a constant by the vjp (no dbias).
    q_segment_ids/kv_segment_ids: int32 [B, Sq]/[B, Sk] packed-varlen ids;
    scores across different ids are masked.
    startend_row_indices: (start, end) int32 [B, 1|H, Sk] flashmask pair —
    key column j is masked for queries start[j] <= q < end[j].
    window: (left, right) ints or None — sliding window around the
    (bottom-right aligned) diagonal.
    dropout_p/dropout_seed: attention-probability dropout drawn from the
    in-kernel PRNG; seed is an int32 [1] array (required when p > 0).

    The primal (inference) path skips the logsumexp residual entirely — no
    extra HBM traffic; it is produced only when jax needs the vjp."""
    b, sq, h, d = query.shape
    sk = key.shape[1]
    scale = float(sm_scale) if sm_scale is not None else 1.0 / math.sqrt(d)
    has_extras = (bias is not None or q_segment_ids is not None
                  or startend_row_indices is not None or dropout_p > 0)
    target = _block_target(has_extras)
    bq = block_q or _pick_block(sq, target)
    bk = block_k or _pick_block(sk, target)
    if bias is not None or q_segment_ids is not None \
            or startend_row_indices is not None:
        # bias/segment/flashmask BlockSpecs put the block size in the lane
        # dim, so Mosaic needs 128-multiples there (seqs are already %128)
        bq = bq if bq % 128 == 0 else 128
        bk = bk if bk % 128 == 0 else 128
    if window is not None:
        left, right = window
        window = (None if left is None or left < 0 else int(left),
                  None if right is None or right < 0 else int(right))
        if window == (None, None):
            window = None
    enforce(not (dropout_p > 0 and dropout_seed is None),
            "dropout_p > 0 requires dropout_seed", op="flash_attention")
    if q_segment_ids is not None:
        q_segment_ids = q_segment_ids.astype(jnp.int32)
        kv_segment_ids = kv_segment_ids.astype(jnp.int32)
    packed_bias = bias
    if startend_row_indices is not None:
        fm_start, fm_end = startend_row_indices
        packed_bias = (bias, fm_start.astype(jnp.int32),
                       fm_end.astype(jnp.int32))
    return _flash(query, key, value, packed_bias, q_segment_ids,
                  kv_segment_ids, dropout_seed, bool(causal), scale, bq, bk,
                  window, float(dropout_p), bool(bias_grad))
