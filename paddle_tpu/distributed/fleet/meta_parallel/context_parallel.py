"""Context (sequence) parallelism: ring attention and Ulysses all-to-all.

The reference snapshot has no ring attention — its long-context answers are
Megatron-SP (sequence_parallel_utils.py), the SEP axis (segment_parallel.py:26,
sequence split for the non-attention parts) and long-seq CUDA kernels
(flash_attn varlen / flashmask, SURVEY §5 "Long-context"). On TPU, true
context parallelism over the ICI ring is the idiomatic design (SURVEY §5:
"ring attention over ICI ... or Ulysses all-to-all"), so this module is the
SEP axis done TPU-first:

* ``ring_attention`` — q stays local, k/v blocks rotate around the mesh axis
  with lax.ppermute; per-step contributions merge through their logsumexp,
  so no device ever materializes full-sequence K/V or scores. The rotation
  is a lax.scan: XLA overlaps each step's ppermute (ICI) with the block
  matmuls (MXU). Two tiers (round 2):

  - impl="tiled" (default where shapes allow): each ring step runs the
    Pallas flash kernel on the visiting K/V block — scores stay tiled in
    VMEM, O(block) not O(S_local^2) HBM — and a hand-written custom_vjp
    runs the REVERSE ring for the backward: dk/dv accumulators travel
    with the rotating blocks and arrive home after n steps, dq
    accumulates locally; per (q-shard, kv-block) tile the flash backward
    kernels run with the *global* logsumexp/delta (standard ring-attention
    backward). lax.switch picks full/diagonal/skip per step from the
    block's global position, so causal rings skip past-diagonal blocks
    entirely.
  - impl="einsum": the round-1 XLA-composed online-softmax ring (kept for
    shapes the kernel can't take: S_local not a lane multiple on TPU).

* ``ulysses_attention`` — all-to-all swaps the sequence shard for a head
  shard ([B, S/n, H, D] -> [B, S, H/n, D]), runs ordinary full attention on
  the local heads (Pallas flash kernel on TPU), and swaps back. Cheaper than
  the ring when heads divide the axis (two all-to-alls vs n ppermutes) but
  caps the parallel degree at num_heads.

Both are per-shard functions: call them inside shard_map with the sequence
dim sharded over `axis`.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from ....enforce import InvalidArgumentError
from jax import lax

__all__ = ["ring_attention", "ulysses_attention"]

_NEG_INF = -1e30


def ring_attention(q, k, v, axis: str = "sep", causal: bool = False,
                   sm_scale: Optional[float] = None, remat: bool = True,
                   impl: str = "auto"):
    """Blockwise ring attention over mesh axis `axis`.

    q/k/v: this rank's sequence shard, [B, S_local, H, D] (paddle layout).
    Returns [B, S_local, H, D]. Global sequence order is the concatenation
    of shards by rank; causal masking uses global positions.

    impl: "tiled" (Pallas flash tiles + hand-written ring vjp), "einsum"
    (XLA-composed online softmax), or "auto" (tiled where the kernel takes
    the shape: D <= 256, and S_local % 128 == 0 on real TPU).
    """
    B, S, H, D = q.shape
    H_kv = k.shape[2]
    if H % max(H_kv, 1) != 0:
        raise InvalidArgumentError(
            f"ring attention GQA needs q heads divisible by kv heads "
            f"(got q {H}, kv {H_kv})")
    if impl == "auto":
        lanes_ok = S % 128 == 0 or jax.default_backend() == "cpu"
        impl = "tiled" if D <= 256 and lanes_ok else "einsum"
    if impl == "tiled":
        scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
        return _ring_tiled(q, k, v, axis, bool(causal), float(scale))
    g = H // H_kv  # grouped einsum handles GQA without repeating KV
    n = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    q32 = (q * scale).astype(q.dtype)
    q_pos = rank * S + jnp.arange(S)  # [S] global positions of local queries

    # kv blocks rotate "backward" (rank r sends to r+1), so after t steps
    # this rank holds the block originating at rank - t (mod n): every rank
    # sees every block after n steps.
    perm = [(i, (i + 1) % n) for i in range(n)]

    # chunk the visiting block's key dim so the per-step scores tensor is
    # [B, H, Sq, chunk] instead of [B, H, Sq, S_local] — removes the
    # O(S_local^2) HBM wall this tier had (VERDICT r2 weak-3); S_local is
    # padded up to a chunk multiple and pad keys masked by position.
    n_chunks = -(-S // 512)
    chunk = -(-S // n_chunks)  # balanced: pad waste < n_chunks elements
    S_pad = n_chunks * chunk
    k_off = jnp.arange(chunk)

    def chunk_step(q32, k_blk, v_blk, src, m, l, acc, c):
        k_c = lax.dynamic_slice_in_dim(k_blk, c * chunk, chunk, axis=1)
        v_c = lax.dynamic_slice_in_dim(v_blk, c * chunk, chunk, axis=1)
        if g > 1:
            # GQA: grouped einsum — each kv head serves its g query heads
            # via index sharing, never a repeated KV copy (round 4; the
            # tier previously raised and forced the tiled path)
            qr = q32.reshape(B, S, H_kv, g, D)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k_c,
                           preferred_element_type=jnp.float32)
            s = s.reshape(B, H, S, chunk)               # [B,H,Sq,chunk]
        else:
            s = jnp.einsum("bqhd,bkhd->bhqk", q32, k_c,
                           preferred_element_type=jnp.float32)
        idx = c * chunk + k_off
        valid = idx < S                                 # pad keys are dead
        if causal:
            k_pos = src * S + idx
            valid = valid[None, :] & (k_pos[None, :] <= q_pos[:, None])
        else:
            valid = jnp.broadcast_to(valid[None, :], (S, chunk))
        s = jnp.where(valid[None, None], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)                     # [B,H,Sq]
        m_new = jnp.maximum(m, m_cur)
        # fully-masked rows keep m = -inf; guard the shift to avoid inf-inf
        shift = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
        p = jnp.exp(s - shift[..., None])
        p = jnp.where(valid[None, None], p, 0.0)
        alpha = jnp.where(m <= _NEG_INF, 0.0, jnp.exp(m - shift))
        l = l * alpha + jnp.sum(p, axis=-1)
        if g > 1:
            pr = p.reshape(B, H_kv, g, S, chunk).astype(v_c.dtype)
            pv = jnp.einsum("bhgqk,bkhd->bqhgd", pr, v_c,
                            preferred_element_type=jnp.float32)
            pv = pv.reshape(B, S, H, D)
        else:
            pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_c.dtype), v_c,
                            preferred_element_type=jnp.float32)
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return m_new, l, acc

    def body(carry, t):
        k_blk, v_blk, m, l, acc = carry
        src = (rank - t) % n
        # python loop (few, static chunks): an inner lax.scan would be a
        # closed_call, which shard_map can't evaluate eagerly under remat
        for c in range(S_pad // chunk):
            m, l, acc = chunk_step(q32, k_blk, v_blk, src, m, l, acc, c)
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        return (k_blk, v_blk, m, l, acc), None

    if remat:
        body = jax.checkpoint(body)

    if S_pad != S:
        # the rotating block carries its pad tail (chunk-multiple length);
        # pad keys are masked by position inside chunk_step
        k = jnp.pad(k, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    m0 = _pvary(jnp.full((B, H, S), _NEG_INF, jnp.float32), axis)
    l0 = _pvary(jnp.zeros((B, H, S), jnp.float32), axis)
    acc0 = _pvary(jnp.zeros((B, S, H, D), jnp.float32), axis)
    (k_blk, v_blk, m, l, acc), _ = lax.scan(
        body, (k, v, m0, l0, acc0), jnp.arange(n))
    inv = jnp.where(l == 0.0, 0.0, 1.0 / jnp.maximum(l, 1e-37))
    out = acc * inv.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# tiled ring: Pallas flash tiles per step + hand-written ring backward
# ---------------------------------------------------------------------------

def _ring_perm(axis):
    n = lax.axis_size(axis)
    return [(i, (i + 1) % n) for i in range(n)]


def _tile_modes(rank, t, n):
    """0 = full (block is globally before the local queries), 1 = diagonal
    (same-rank block: causal within), 2 = skip (block is entirely after)."""
    src = (rank - t) % n
    return jnp.where(src < rank, 0, jnp.where(src == rank, 1, 2))


def _expand_kv(x3, h, h_kv):
    """[B*H_kv, S, D] -> [B*H, S, D] by repeating each kv head over its
    query group (composed fallback only — the kernel indexes instead)."""
    b = x3.shape[0] // h_kv
    g = h // h_kv
    return jnp.repeat(x3.reshape(b, h_kv, *x3.shape[1:]), g,
                      axis=1).reshape(b * h, *x3.shape[1:])


def _reduce_kv_grad(g3, h, h_kv):
    """[B*H, S, D] per-query-head kv grads -> [B*H_kv, S, D] group sums."""
    b = g3.shape[0] // h
    g = h // h_kv
    return g3.reshape(b, h_kv, g, *g3.shape[1:]).sum(axis=2).reshape(
        b * h_kv, *g3.shape[1:])


def _tile_fwd(q3, k3, v3, causal, scale, h, h_kv, vma):
    """One (q-shard × kv-block) tile: (o f32, lse f32). Pallas flash kernel
    compiled (GQA native via kv index maps); a composed per-tile reference
    on CPU (pallas interpret mode can't run under shard_map's varying-axis
    checking)."""
    from ....kernels.pallas import flash_attention as _fa
    if not _fa._interpret():
        blk = _fa._pick_block(q3.shape[1])
        o, lse = _fa._fwd(q3, k3, v3, scale, causal, blk, blk, h=h,
                          h_kv=h_kv, save_lse=True, vma=vma)
        return o.astype(jnp.float32), lse
    if h_kv != h:
        k3 = _expand_kv(k3, h, h_kv)
        v3 = _expand_kv(v3, h, h_kv)
    s = jnp.einsum("bqd,bkd->bqk", q3, k3,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape[1], s.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool), sk - sq)[None],
                      s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    lse = jnp.where(m <= _NEG_INF * 0.5, _NEG_INF,
                    m + jnp.log(jnp.maximum(l, 1e-37)))
    inv = jnp.where(l == 0.0, 0.0, 1.0 / jnp.maximum(l, 1e-37))
    o = jnp.einsum("bqk,bkd->bqd", p.astype(v3.dtype), v3,
                   preferred_element_type=jnp.float32) * inv[..., None]
    return o, lse


def _tile_bwd(q3, k3, v3, out3, lse, do3, causal, scale, h, h_kv, vma):
    """Per-tile (dq, dk, dv) with the GLOBAL lse (p = exp(s - lse_global)
    is the globally-normalized tile probability)."""
    from ....kernels.pallas import flash_attention as _fa
    if not _fa._interpret():
        blk = _fa._pick_block(q3.shape[1])
        dq, dk, dv, _ = _fa._bwd_impl(q3, k3, v3, out3, lse, do3, scale,
                                      causal, blk, blk, h=h, h_kv=h_kv,
                                      vma=vma)
        return dq, dk, dv
    kv_shape = k3.shape
    if h_kv != h:
        k3 = _expand_kv(k3, h, h_kv)
        v3 = _expand_kv(v3, h, h_kv)
    s = jnp.einsum("bqd,bkd->bqk", q3, k3,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape[1], s.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool), sk - sq)[None],
                      s, _NEG_INF)
    p = jnp.exp(s - lse[..., None])
    p = jnp.where((lse <= _NEG_INF * 0.5)[..., None], 0.0, p)
    do32 = do3.astype(jnp.float32)
    delta = jnp.sum(do32 * out3.astype(jnp.float32), axis=-1)  # [BH,S]
    dv = jnp.einsum("bqk,bqd->bkd", p, do32)
    dp = jnp.einsum("bqd,bkd->bqk", do32, v3.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bqk,bkd->bqd", ds, k3.astype(jnp.float32))
    dk = jnp.einsum("bqk,bqd->bkd", ds, q3.astype(jnp.float32))
    if h_kv != h:
        dk = _reduce_kv_grad(dk, h, h_kv)
        dv = _reduce_kv_grad(dv, h, h_kv)
        assert dk.shape == kv_shape, (dk.shape, kv_shape)
    return dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype)


def _ring_fwd_step(q3, k3, v3, mode, scale, h, h_kv, axis):
    """One visiting block, switched on the block's causal mode."""
    bh, s, d = q3.shape
    vma = (axis,)

    def full(args):
        o, lse = _tile_fwd(*args, False, scale, h, h_kv, vma)
        return o, lse

    def diag(args):
        o, lse = _tile_fwd(*args, True, scale, h, h_kv, vma)
        return o, lse

    def skip(args):
        # outputs must match the compute branches' varying-axis type
        return (_pvary(jnp.zeros((bh, s, d), jnp.float32), axis),
                _pvary(jnp.full((bh, s), _NEG_INF, jnp.float32), axis))

    if mode is None:  # non-causal ring: every block is a full tile
        return full((q3, k3, v3))
    return lax.switch(mode, [full, diag, skip], (q3, k3, v3))


def _ring_bwd_step(q3, k3, v3, out3, lse, do3, mode, scale, h, h_kv, axis):
    """One visiting block of the reverse ring."""
    vma = (axis,)

    def full(args):
        return _tile_bwd(*args, False, scale, h, h_kv, vma)

    def diag(args):
        return _tile_bwd(*args, True, scale, h, h_kv, vma)

    def skip(args):
        q3, k3, v3, _, _, _ = args
        return (jnp.zeros_like(q3), jnp.zeros_like(k3), jnp.zeros_like(v3))

    if mode is None:  # non-causal ring: every block is a full tile
        return full((q3, k3, v3, out3, lse, do3))
    return lax.switch(mode, [full, diag, skip],
                      (q3, k3, v3, out3, lse, do3))


def _merge_lse(acc, lse, o_b, lse_b):
    """Merge a block's normalized output into the running one through
    logsumexp weights. _NEG_INF (finite) keeps empty/empty merges NaN-free;
    rows that never see a key keep lse ~ _NEG_INF and zero output."""
    lse_c = jnp.logaddexp(lse, lse_b)
    w = jnp.exp(lse - lse_c)[..., None]
    w_b = jnp.exp(lse_b - lse_c)[..., None]
    return acc * w + o_b * w_b, lse_c


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_tiled(q, k, v, axis, causal, scale):
    out, _ = _ring_tiled_fwd(q, k, v, axis, causal, scale)
    return out


def _ring_tiled_fwd(q, k, v, axis, causal, scale):
    from ....kernels.pallas.flash_attention import _prep, _unprep
    n = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    B, S, H, D = q.shape
    H_kv = k.shape[2]
    q3, k3, v3 = _prep(q), _prep(k), _prep(v)

    def body(carry, t):
        k_blk, v_blk, acc, lse = carry
        mode = _tile_modes(rank, t, n) if causal else None
        o_b, lse_b = _ring_fwd_step(q3, k_blk, v_blk, mode, scale,
                                    H, H_kv, axis)
        acc, lse = _merge_lse(acc, lse, o_b, lse_b)
        k_blk = lax.ppermute(k_blk, axis, _ring_perm(axis))
        v_blk = lax.ppermute(v_blk, axis, _ring_perm(axis))
        return (k_blk, v_blk, acc, lse), None

    acc0 = _pvary(jnp.zeros(q3.shape, jnp.float32), axis)
    lse0 = _pvary(jnp.full(q3.shape[:2], _NEG_INF, jnp.float32), axis)
    (_, _, acc, lse), _ = lax.scan(body, (k3, v3, acc0, lse0),
                                   jnp.arange(n))
    out3 = acc.astype(q.dtype)
    return _unprep(out3, B, H), (q3, k3, v3, out3, lse, B, H, H_kv)


def _ring_tiled_bwd(axis, causal, scale, res, g):
    from ....kernels.pallas.flash_attention import _prep, _unprep
    q3, k3, v3, out3, lse, B, H, H_kv = res
    do3 = _prep(g)
    n = lax.axis_size(axis)
    rank = lax.axis_index(axis)

    def body(carry, t):
        k_blk, v_blk, dk_blk, dv_blk, dq_acc = carry
        mode = _tile_modes(rank, t, n) if causal else None
        dq_c, dk_c, dv_c = _ring_bwd_step(q3, k_blk, v_blk, out3, lse, do3,
                                          mode, scale, H, H_kv, axis)
        dq_acc = dq_acc + dq_c.astype(jnp.float32)
        dk_blk = dk_blk + dk_c.astype(jnp.float32)
        dv_blk = dv_blk + dv_c.astype(jnp.float32)
        # dk/dv accumulators travel WITH their block; after n rotations the
        # block (and its completed gradient) is home again
        perm = _ring_perm(axis)
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        dk_blk = lax.ppermute(dk_blk, axis, perm)
        dv_blk = lax.ppermute(dv_blk, axis, perm)
        return (k_blk, v_blk, dk_blk, dv_blk, dq_acc), None

    z = _pvary(jnp.zeros(k3.shape, jnp.float32), axis)
    dq0 = _pvary(jnp.zeros(q3.shape, jnp.float32), axis)
    (_, _, dk3, dv3, dq3), _ = lax.scan(
        body, (k3, v3, z, z, dq0), jnp.arange(n))
    return (_unprep(dq3.astype(q3.dtype), B, H),
            _unprep(dk3.astype(k3.dtype), B, H_kv),
            _unprep(dv3.astype(v3.dtype), B, H_kv))


_ring_tiled.defvjp(_ring_tiled_fwd, _ring_tiled_bwd)


def _pvary(x, axis):
    """Mark a freshly-created constant as device-varying over `axis`
    (shard_map's varying-axis type system)."""
    return lax.pcast(x, (axis,), to="varying")


def ulysses_attention(q, k, v, axis: str = "sep", causal: bool = False,
                      sm_scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None):
    """DeepSpeed-Ulysses style sequence parallelism: trade the sequence
    shard for a head shard with one all-to-all each way.

    q/k/v: [B, S_local, H, D] with H divisible by the axis size.
    A custom `attn_fn` is called as attn_fn(q, k, v, causal) on the
    head-sharded full-sequence arrays (sm_scale is pre-folded into q).
    """
    n = lax.axis_size(axis)
    B, S, H, D = q.shape
    H_kv = k.shape[2]
    if H % n != 0 or H_kv % n != 0:
        raise InvalidArgumentError(
            f"ulysses needs q heads ({H}) AND kv heads ({H_kv}) divisible "
            f"by the axis size ({n}) — the all-to-all trades the sequence "
            "shard for a head shard on both; repeat kv heads upstream or "
            "use ring attention for H_kv < n")

    def to_heads(x):
        # split heads across ranks, gather the full sequence
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)  # [B, S*n, H/n, D]

    def to_seq(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)  # [B, S_local, H, D]

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    if sm_scale is not None:
        # fold a custom scale into q (inner attention uses 1/sqrt(D))
        qh = qh * (sm_scale * math.sqrt(D))
    if attn_fn is None:
        from ....nn import functional as F
        out = F.scaled_dot_product_attention(qh, kh, vh, is_causal=causal)
    else:
        out = attn_fn(qh, kh, vh, causal)
    return to_seq(out)
