"""Pallas kernel parity tests (interpret mode on the CPU mesh).

Mirrors the reference's OpTest golden-value pattern (SURVEY §4.1): each fused
kernel is compared against the XLA-composed reference implementation, forward
and gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.kernels.pallas.flash_attention as fa_mod
from paddle_tpu.kernels.pallas.flash_attention import flash_attention
from paddle_tpu.kernels.pallas.rms_norm import rms_norm as pallas_rms_norm
from paddle_tpu.kernels.pallas.rope import apply_rope
from paddle_tpu.nn.functional.flash_attention import _sdpa_reference


def _rand(*shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype=dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(128, 128), (256, 256)])
def test_flash_attention_forward(causal, sq, sk):
    b, h, d = 2, 3, 64
    q = _rand(b, sq, h, d, seed=1) * 0.3
    k = _rand(b, sk, h, d, seed=2) * 0.3
    v = _rand(b, sk, h, d, seed=3)
    out = flash_attention(q, k, v, causal, None, 128, 128)
    ref = _sdpa_reference(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
    b, s, h, d = 1, 128, 2, 64
    q = _rand(b, s, h, d, seed=4) * 0.3
    k = _rand(b, s, h, d, seed=5) * 0.3
    v = _rand(b, s, h, d, seed=6)

    def loss_pallas(q, k, v):
        o = flash_attention(q, k, v, causal, None, 64, 64)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = _sdpa_reference(q, k, v, is_causal=causal)
        return jnp.sum(o * o)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-4, atol=3e-4)


def test_flash_attention_supported_gate():
    q = jnp.zeros((2, 128, 4, 64))
    kv = jnp.zeros((2, 128, 2, 64))  # GQA: 2 kv heads for 4 q heads
    assert fa_mod.supported(q, q, q)
    assert fa_mod.supported(q, kv, kv)
    assert fa_mod.supported(q, q, q, dropout_p=0.1)  # in-kernel PRNG
    assert fa_mod.supported(q, q, q,
                            attn_mask=jnp.zeros((2, 1, 128, 128)))
    assert fa_mod.supported(q, q, q,
                            attn_mask=jnp.zeros((1, 4, 128, 128), bool))
    # still rejected: rank-2 masks, non-128-multiple seqs, bad head split
    assert not fa_mod.supported(q, q, q, attn_mask=jnp.zeros((128, 128)))
    assert not fa_mod.supported(jnp.zeros((2, 100, 4, 64)), q, q)
    assert not fa_mod.supported(q, jnp.zeros((2, 128, 3, 64)),
                                jnp.zeros((2, 128, 3, 64)))


def test_rms_norm_parity():
    x = _rand(6, 256, seed=7)
    w = _rand(256, seed=8) * 0.1 + 1.0

    def ref(x, w):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * w

    y = pallas_rms_norm(x, w, 1e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, w)),
                               rtol=1e-5, atol=1e-5)

    gp = jax.grad(lambda x, w: jnp.sum(jnp.sin(pallas_rms_norm(x, w, 1e-6))),
                  argnums=(0, 1))(x, w)
    gr = jax.grad(lambda x, w: jnp.sum(jnp.sin(ref(x, w))),
                  argnums=(0, 1))(x, w)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def test_rms_norm_3d_batch():
    x = _rand(2, 4, 128, seed=9)
    w = jnp.ones((128,))
    y = pallas_rms_norm(x, w, 1e-6)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(x * jax.lax.rsqrt(ms + 1e-6)),
                               rtol=1e-5, atol=1e-5)


def test_rope_parity_and_grad():
    b, s, h, d = 2, 16, 4, 64
    x = _rand(b, s, h, d, seed=10)
    inv = 1.0 / (10000 ** (jnp.arange(0, d, 2) / d))
    ang = jnp.arange(s)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def ref(x):
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        c = cos[None, :, None, :]
        sn = sin[None, :, None, :]
        return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)

    y = apply_rope(x, cos, sin)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x)),
                               rtol=1e-5, atol=1e-5)
    gp = jax.grad(lambda x: jnp.sum(jnp.cos(apply_rope(x, cos, sin))))(x)
    gr = jax.grad(lambda x: jnp.sum(jnp.cos(ref(x))))(x)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=1e-5, atol=1e-5)


def test_registry_dispatch_routes_to_pallas(monkeypatch):
    # force the TPU branch of OpSchema.dispatch on CPU (kernels run in
    # interpret mode there) to exercise the full registry → pallas plumbing
    import paddle_tpu.ops.registry as registry
    import paddle_tpu.nn.functional as F
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    q = _rand(1, 128, 2, 64, seed=12) * 0.3
    out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    ref = _sdpa_reference(q, q, q, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    x = _rand(4, 256, seed=13)
    w = jnp.ones((256,))
    y = F.rms_norm(x, w)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(x * jax.lax.rsqrt(ms + 1e-6)),
                               rtol=1e-5, atol=1e-5)


def test_fused_rope_incubate_surface(monkeypatch):
    import paddle_tpu.ops.registry as registry
    from paddle_tpu.incubate.nn.functional import (
        fused_rotary_position_embedding, swiglu)
    b, s, h, d = 2, 16, 2, 32
    q = _rand(b, s, h, d, seed=14)
    k = _rand(b, s, h, d, seed=15)
    qr, kr, vr = fused_rotary_position_embedding(q, k)
    assert vr is None and qr.shape == q.shape
    # pallas path (interpret) must match the XLA reference path
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    qp, kp, _ = fused_rotary_position_embedding(q, k)
    np.testing.assert_allclose(np.asarray(qp), np.asarray(qr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(kp), np.asarray(kr),
                               rtol=1e-5, atol=1e-5)
    # swiglu split convention
    x = _rand(4, 64, seed=16)
    out = swiglu(x)
    x1, x2 = np.split(np.asarray(x), 2, axis=-1)
    np.testing.assert_allclose(np.asarray(out),
                               x1 / (1 + np.exp(-x1)) * x2, rtol=1e-5)


def test_registry_dispatch_falls_back_on_cpu():
    # on CPU the dispatcher must use the XLA reference path (pallas gated
    # to TPU); correctness of the dispatch plumbing:
    import paddle_tpu.nn.functional as F
    q = _rand(1, 8, 2, 16, seed=11)
    out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    ref = _sdpa_reference(q, q, q, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


# ---------------------------------------------------------------- variants
# (round-2: masked/varlen/GQA/window/flashmask run IN the kernel)

def _repeat_kv(x, g):
    b, s, hkv, d = x.shape
    return jnp.repeat(x, g, axis=2)


@pytest.mark.parametrize("h,h_kv", [(4, 2), (4, 1)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_gqa(h, h_kv, causal):
    b, s, d = 2, 128, 64
    q = _rand(b, s, h, d, seed=21) * 0.3
    k = _rand(b, s, h_kv, d, seed=22) * 0.3
    v = _rand(b, s, h_kv, d, seed=23)

    out = flash_attention(q, k, v, causal, None, 64, 64)
    ref = _sdpa_reference(q, _repeat_kv(k, h // h_kv),
                          _repeat_kv(v, h // h_kv), is_causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    gp = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal, None, 64, 64) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(_sdpa_reference(
        q, _repeat_kv(k, h // h_kv), _repeat_kv(v, h // h_kv),
        is_causal=causal) ** 2), argnums=(0, 1, 2))(q, k, v)
    # grad through jnp.repeat already folds the group back to h_kv heads
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-4, atol=3e-4)


def test_flash_attention_causal_rectangular():
    """sq != sk causal is bottom-right aligned (decode-style)."""
    b, h, d = 1, 2, 64
    q = _rand(b, 128, h, d, seed=24) * 0.3
    k = _rand(b, 256, h, d, seed=25) * 0.3
    v = _rand(b, 256, h, d, seed=26)
    out = flash_attention(q, k, v, True, None, 64, 64)
    ref = _sdpa_reference(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("mask_kind", ["bool", "additive"])
def test_flash_attention_bias_mask(mask_kind):
    b, s, h, d = 2, 128, 2, 64
    q = _rand(b, s, h, d, seed=27) * 0.3
    k = _rand(b, s, h, d, seed=28) * 0.3
    v = _rand(b, s, h, d, seed=29)
    rs = np.random.RandomState(30)
    if mask_kind == "bool":
        m = rs.rand(b, 1, s, s) > 0.3
        bias = jnp.where(jnp.asarray(m), 0.0, -1e30).astype(q.dtype)
        ref_mask = jnp.asarray(m)
    else:
        bias = jnp.asarray(rs.randn(1, h, s, s).astype(np.float32))
        ref_mask = bias
    out = flash_attention(q, k, v, False, None, 64, 64, bias=bias)
    ref = _sdpa_reference(q, k, v, attn_mask=ref_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
    # grads flow through q/k/v (bias is a constant on the fast path)
    gp = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, False, None, 64, 64, bias=bias) ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(_sdpa_reference(
        q, k, v, attn_mask=ref_mask) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=3e-4, atol=3e-4)


def test_flash_attention_segment_ids():
    """Packed-varlen: cross-segment attention masked, in kernel."""
    b, s, h, d = 1, 256, 2, 64
    q = _rand(b, s, h, d, seed=31) * 0.3
    k = _rand(b, s, h, d, seed=32) * 0.3
    v = _rand(b, s, h, d, seed=33)
    seg = jnp.asarray(np.repeat([0, 1, 2, 3], 64)[None], jnp.int32)
    out = flash_attention(q, k, v, True, None, 64, 64,
                          q_segment_ids=seg, kv_segment_ids=seg)
    mask = (seg[0][:, None] == seg[0][None, :])[None, None]
    cm = jnp.tril(jnp.ones((s, s), bool))[None, None]
    ref = _sdpa_reference(q, k, v, attn_mask=mask & cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    gp = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, True, None, 64, 64, q_segment_ids=seg,
        kv_segment_ids=seg) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(_sdpa_reference(
        q, k, v, attn_mask=mask & cm) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-4, atol=3e-4)


def test_flash_attention_segment_skip_misaligned():
    """Segment boundaries that do NOT align with tile boundaries: the
    dynamic range-overlap tile skip (_seg_block_overlap) must stay exact —
    partially-overlapping tiles run, fully-disjoint ones skip, and -1 pad
    tails keep the composed path's semantics."""
    b, s, h, d = 1, 384, 2, 64
    q = _rand(b, s, h, d, seed=41) * 0.3
    k = _rand(b, s, h, d, seed=42) * 0.3
    v = _rand(b, s, h, d, seed=43)
    # lengths chosen so some 64-wide tiles hold a SINGLE id: tile range
    # pairs like [0,0] x [2,2] are disjoint and actually take the skip
    # branch (with every segment shorter than a tile, all ranges overlap
    # and the gate would never fire). 300 real tokens + 84 pad (-1).
    seg_np = np.full((s,), -1, np.int32)
    off = 0
    for sid, ln in enumerate([140, 40, 120]):
        seg_np[off:off + ln] = sid
        off += ln
    # sanity: at block 64 there must exist a fully-disjoint tile pair
    t = seg_np.reshape(s // 64, 64)
    lo, hi = t.min(1), t.max(1)
    assert any(hi[i] < lo[j] or hi[j] < lo[i]
               for i in range(len(lo)) for j in range(len(lo)) if i != j)
    seg = jnp.asarray(seg_np[None])
    out = flash_attention(q, k, v, False, None, 64, 64,
                          q_segment_ids=seg, kv_segment_ids=seg)
    mask = (seg[0][:, None] == seg[0][None, :])[None, None]
    ref = _sdpa_reference(q, k, v, attn_mask=mask)
    real = np.asarray(seg_np >= 0)
    np.testing.assert_allclose(np.asarray(out)[:, real],
                               np.asarray(ref)[:, real],
                               rtol=2e-4, atol=2e-4)
    gp = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, False, None, 64, 64, q_segment_ids=seg,
        kv_segment_ids=seg)[:, real] ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(_sdpa_reference(
        q, k, v, attn_mask=mask)[:, real] ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2)])
def test_flash_attention_head_native_d128(h, hkv):
    """d % 128 == 0 takes the HEAD-NATIVE lane-sliced path: [B, S, H, D]
    is viewed as [B, S, H*D] and each program's tile is lane-indexed out
    of the fused head dim (no transpose copy). Exercises the native
    BlockSpec index maps in all three kernels (fwd/dq/dkv), incl. GQA —
    every other flash test uses d=64, which runs only the legacy branch."""
    b, s, d = 2, 256, 128
    q = _rand(b, s, h, d, seed=51) * 0.3
    k = _rand(b, s, hkv, d, seed=52) * 0.3
    v = _rand(b, s, hkv, d, seed=53)
    out = flash_attention(q, k, v, True, None, 128, 128)
    rep = h // hkv
    ref = _sdpa_reference(q, jnp.repeat(k, rep, axis=2),
                          jnp.repeat(v, rep, axis=2), is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    gp = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, True, None, 128, 128) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(_sdpa_reference(
        q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
        is_causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


def test_flash_attention_window():
    b, s, h, d = 1, 256, 2, 64
    q = _rand(b, s, h, d, seed=34) * 0.3
    k = _rand(b, s, h, d, seed=35) * 0.3
    v = _rand(b, s, h, d, seed=36)
    left = 96
    out = flash_attention(q, k, v, True, None, 64, 64, window=(left, None))
    rows = jnp.arange(s)[:, None]
    cols = jnp.arange(s)[None, :]
    wm = ((cols >= rows - left) & (cols <= rows))[None, None]
    ref = _sdpa_reference(q, k, v, attn_mask=wm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    gp = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, True, None, 64, 64, window=(left, None)) ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(
        _sdpa_reference(q, k, v, attn_mask=wm) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=3e-4, atol=3e-4)


def test_flash_attention_flashmask_rows():
    """O(S) flashmask start/end rows applied in kernel: key column j is
    masked for queries start[j] <= q < end[j]."""
    b, s, h, d = 1, 256, 2, 64
    q = _rand(b, s, h, d, seed=37) * 0.3
    k = _rand(b, s, h, d, seed=38) * 0.3
    v = _rand(b, s, h, d, seed=39)
    rs = np.random.RandomState(40)
    start = rs.randint(0, s, size=(b, 1, s)).astype(np.int32)
    end = np.minimum(start + rs.randint(1, 64, size=(b, 1, s)), s).astype(
        np.int32)
    fm = (jnp.asarray(start), jnp.asarray(end))
    out = flash_attention(q, k, v, True, None, 64, 64,
                          startend_row_indices=fm)
    rows = jnp.arange(s)[None, None, :, None]
    st = jnp.asarray(start)[:, :, None, :]
    en = jnp.asarray(end)[:, :, None, :]
    allowed = (rows < st) | (rows >= en)
    cm = jnp.tril(jnp.ones((s, s), bool))[None, None]
    ref = _sdpa_reference(q, k, v, attn_mask=allowed & cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    gp = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, True, None, 64, 64, startend_row_indices=fm) ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(_sdpa_reference(
        q, k, v, attn_mask=allowed & cm) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=3e-4, atol=3e-4)


def test_flash_attention_dropout():
    """In-kernel PRNG dropout: deterministic per seed, ~p zeros, and the
    backward regenerates the identical mask (grads finite & consistent)."""
    b, s, h, d = 1, 128, 2, 64
    q = _rand(b, s, h, d, seed=41) * 0.3
    k = _rand(b, s, h, d, seed=42) * 0.3
    v = jnp.ones((b, s, h, d), jnp.float32)
    seed = jnp.asarray([1234], jnp.int32)
    try:
        out1 = flash_attention(q, k, v, False, None, 64, 64,
                               dropout_p=0.5, dropout_seed=seed)
    except Exception as e:  # pragma: no cover - interpret-mode PRNG gap
        pytest.skip(f"in-kernel PRNG unavailable in this mode: {e}")
    out2 = flash_attention(q, k, v, False, None, 64, 64,
                           dropout_p=0.5, dropout_seed=seed)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    out3 = flash_attention(q, k, v, False, None, 64, 64, dropout_p=0.5,
                           dropout_seed=jnp.asarray([99], jnp.int32))
    assert not np.allclose(np.asarray(out1), np.asarray(out3))
    # with v=1, undropped rows sum to 1; E[out] stays ~1 under 1/keep scaling
    assert 0.9 < float(jnp.mean(out1)) < 1.1
    g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, False, None, 64, 64, dropout_p=0.5,
        dropout_seed=seed) ** 2))(q)
    assert np.all(np.isfinite(np.asarray(g)))


def test_unpadded_and_flashmask_dispatch(monkeypatch):
    """flash_attn_unpadded / flashmask_attention route through the Pallas
    kernel on TPU (forced here; interpret on CPU) and match their composed
    reference implementations; dispatch_stats records the fast-path hit."""
    import paddle_tpu.ops.registry as registry
    import paddle_tpu.nn.functional as F
    from paddle_tpu.ops import dispatch_stats, get_op
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    dispatch_stats(reset=True)

    cu = jnp.asarray([0, 100, 180, 256], jnp.int32)
    q = _rand(256, 4, 64, seed=50) * 0.3
    k = _rand(256, 2, 64, seed=51) * 0.3
    v = _rand(256, 2, 64, seed=52)
    out, _ = F.flash_attn_unpadded(q, k, v, cu, cu, 100, 100, causal=True)
    ref, _ = get_op("flash_attn_unpadded").fn(
        q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1),
        cu, cu, 100, 100, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
    # non-128-multiple totals are padded inside the fast path
    cu2 = jnp.asarray([0, 60, 130, 200], jnp.int32)
    q2 = _rand(200, 2, 64, seed=53) * 0.3
    out2, _ = F.flash_attn_unpadded(q2, q2, q2, cu2, cu2, 70, 70,
                                    causal=True)
    ref2, _ = get_op("flash_attn_unpadded").fn(q2, q2, q2, cu2, cu2, 70, 70,
                                               causal=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               rtol=3e-4, atol=3e-4)

    b, s, h = 1, 256, 2
    q3 = _rand(b, s, h, 64, seed=54) * 0.3
    rs = np.random.RandomState(55)
    start = jnp.asarray(rs.randint(0, s, size=(b, 1, s, 1)), jnp.int32)
    out3, _ = F.flashmask_attention(q3, q3, q3, start, causal=True)
    ref3, _ = get_op("flashmask_attention").fn(q3, q3, q3, start,
                                               causal=True)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(ref3),
                               rtol=3e-4, atol=3e-4)

    stats = dispatch_stats()
    assert stats["flash_attn_unpadded"]["pallas"] == 2
    assert stats["flash_attn_unpadded"]["reference"] == 0
    assert stats["flashmask_attention"]["pallas"] == 1


def test_fully_masked_rows_zero_on_both_paths():
    """causal with sq > sk leaves early query rows with no visible keys
    (bottom-right alignment): both the kernel and the composed fallback
    must emit zeros there, not a uniform average of V."""
    b, h, d = 1, 2, 64
    q = _rand(b, 256, h, d, seed=60) * 0.3
    k = _rand(b, 128, h, d, seed=61) * 0.3
    v = _rand(b, 128, h, d, seed=62)
    out = flash_attention(q, k, v, True, None, 128, 128)
    ref = _sdpa_reference(q, k, v, is_causal=True)
    # rows 0..127 see no keys (offset = -128)
    assert float(jnp.abs(out[:, :128]).max()) == 0.0
    assert float(jnp.abs(ref[:, :128]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_mask_is_constant_no_grad_flow():
    """No gradient flows into attn_mask on the composed path (shared
    contract with the kernel, whose vjp returns zeros for the bias)."""
    q = _rand(1, 8, 2, 16, seed=63)
    bias = _rand(1, 1, 8, 8, seed=64)
    g = jax.grad(lambda b: jnp.sum(
        _sdpa_reference(q, q, q, attn_mask=b) ** 2))(bias)
    assert float(jnp.abs(g).max()) == 0.0


def test_segment_fully_masked_rows_zero():
    """Rows whose segment matches NO kv position must emit zeros (and zero
    grads), matching the composed path — regression: finite _NEG_INF made
    p=exp(0) and the kernel returned a uniform average of V."""
    b, s, h, d = 1, 256, 2, 64
    q = _rand(b, s, h, d, seed=70) * 0.3
    k = _rand(b, s, h, d, seed=71) * 0.3
    v = _rand(b, s, h, d, seed=72)
    qseg = jnp.asarray(np.r_[np.zeros(128), np.full(128, 7)][None], jnp.int32)
    kseg = jnp.zeros((1, s), jnp.int32)  # segment 7 matches nothing
    out = flash_attention(q, k, v, False, None, 128, 128,
                          q_segment_ids=qseg, kv_segment_ids=kseg)
    assert float(jnp.abs(out[:, 128:]).max()) == 0.0
    mask = (qseg[0][:, None] == kseg[0][None, :])[None, None]
    ref = _sdpa_reference(q, k, v, attn_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
    # gradients of dead rows must not leak into k/v
    gp = jax.grad(lambda k, v: jnp.sum(flash_attention(
        q, k, v, False, None, 128, 128, q_segment_ids=qseg,
        kv_segment_ids=kseg) ** 2), argnums=(0, 1))(k, v)
    gr = jax.grad(lambda k, v: jnp.sum(_sdpa_reference(
        q, k, v, attn_mask=mask) ** 2), argnums=(0, 1))(k, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-4, atol=3e-4)


def test_flashmask_window_rectangular_alignment():
    """Composed flashmask window must be bottom-right aligned like the
    kernel when Sq != Sk (regression: top-left aligned wm)."""
    from paddle_tpu.ops import get_op
    b, h, d = 1, 2, 64
    sq, sk, w = 128, 256, 32
    q = _rand(b, sq, h, d, seed=73) * 0.3
    kv = _rand(b, sk, h, d, seed=74) * 0.3
    out, _ = get_op("flashmask_attention").fn(q, kv, kv, None, causal=True,
                                              window_size=w)
    rows = jnp.arange(sq)[:, None]
    cols = jnp.arange(sk)[None, :]
    off = sk - sq
    m = ((cols <= rows + off) & (cols >= rows + off - w))[None, None]
    ref = _sdpa_reference(q, kv, kv, attn_mask=m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.tpu
def test_flash_dropout_bwd_mask_consistency_tpu():
    """Compiled-only: the backward re-derives the forward's keep mask.
    With a fixed seed, out is linear in v; d/dv of sum(out) recovers the
    column-sums of the dropped probability matrix, so sum(out(v=1)) must
    equal <grad_v, 1> exactly."""
    # decided in the test body, never at import: every xdist worker must
    # collect the same tests
    if jax.default_backend() == "cpu":
        pytest.skip("in-kernel PRNG has no CPU lowering (run with "
                    "PADDLE_TPU_TESTS=1 -m tpu -p no:xdist on a TPU)")
    b, s, h, d = 1, 256, 2, 64
    q = _rand(b, s, h, d, seed=75) * 0.3
    k = _rand(b, s, h, d, seed=76) * 0.3
    seed = jnp.asarray([77], jnp.int32)
    f = lambda v: jnp.sum(flash_attention(
        q, k, v, False, None, 128, 128, dropout_p=0.5,
        dropout_seed=seed).astype(jnp.float32))
    ones = jnp.ones((b, s, h, d), jnp.float32)
    gv = jax.grad(f)(ones)
    np.testing.assert_allclose(float(f(ones)), float(jnp.sum(gv)),
                               rtol=1e-3)


def test_flashmask_four_column_golden():
    """4-column flashmask (VERDICT r2 #5; reference
    flash_attention.py:1330-1332): per key column, LT rows [r1, r2) and UT
    rows [r3, r4) masked, triangles strict."""
    from paddle_tpu.nn import functional as F

    b, s, h = 1, 32, 2
    rng = np.random.RandomState(60)
    q = jnp.asarray(rng.randn(b, s, h, 16).astype(np.float32)) * 0.3
    r1 = rng.randint(0, s, size=(b, 1, s, 1))
    r2 = np.minimum(r1 + rng.randint(1, 8, size=r1.shape), s)
    r3 = rng.randint(0, s, size=r1.shape)
    r4 = np.minimum(r3 + rng.randint(1, 8, size=r1.shape), s)
    idx = jnp.asarray(np.concatenate([r1, r2, r3, r4], axis=-1), jnp.int32)

    out, _ = F.flashmask_attention(q, q, q, idx, causal=False)

    rows = np.arange(s)[:, None]
    cols = np.arange(s)[None, :]
    lt, ut = rows > cols, rows < cols
    banned = ((lt & (rows >= r1[0, 0, :, 0][None, :])
               & (rows < r2[0, 0, :, 0][None, :]))
              | (ut & (rows >= r3[0, 0, :, 0][None, :])
                 & (rows < r4[0, 0, :, 0][None, :])))
    keep = jnp.asarray(~banned)[None, None]
    ref = _sdpa_reference(q, q, q, attn_mask=keep)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flashmask_two_column_bidirectional_golden():
    """C=2 causal=False: LT rows >= r1 masked, UT rows < r2 masked."""
    from paddle_tpu.nn import functional as F

    b, s, h = 1, 32, 2
    rng = np.random.RandomState(61)
    q = jnp.asarray(rng.randn(b, s, h, 16).astype(np.float32)) * 0.3
    r1 = rng.randint(1, s, size=(b, 1, s, 1))
    r2 = rng.randint(0, s, size=r1.shape)
    idx = jnp.asarray(np.concatenate([r1, r2], axis=-1), jnp.int32)

    out, _ = F.flashmask_attention(q, q, q, idx, causal=False)

    rows = np.arange(s)[:, None]
    cols = np.arange(s)[None, :]
    lt, ut = rows > cols, rows < cols
    banned = (lt & (rows >= r1[0, 0, :, 0][None, :])) | \
             (ut & (rows < r2[0, 0, :, 0][None, :]))
    keep = jnp.asarray(~banned)[None, None]
    ref = _sdpa_reference(q, q, q, attn_mask=keep)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_learned_bias_grad():
    """bias_grad=True produces the real additive-bias gradient (in-kernel
    dS emission); default stays the constant-mask zero-grad contract."""
    b, s, h, d = 1, 256, 2, 64
    q = _rand(b, s, h, d, seed=70) * 0.3
    k = _rand(b, s, h, d, seed=71) * 0.3
    v = _rand(b, s, h, d, seed=72)
    bias = _rand(b, h, s, s, seed=73) * 0.1

    def loss_fast(bias):
        return jnp.sum(flash_attention(q, k, v, True, None, 64, 64,
                                       bias=bias, bias_grad=True) ** 2)

    def loss_ref(bias):
        logits = (jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
                  / np.sqrt(d) + bias)
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
        return jnp.sum(out ** 2)

    g_fast = jax.grad(loss_fast)(bias)
    g_ref = jax.grad(loss_ref)(bias)
    np.testing.assert_allclose(np.asarray(g_fast), np.asarray(g_ref),
                               rtol=3e-4, atol=3e-4)

    # default contract: zero bias grad (constant mask)
    g_zero = jax.grad(lambda bb: jnp.sum(flash_attention(
        q, k, v, True, None, 64, 64, bias=bb) ** 2))(bias)
    assert float(jnp.abs(g_zero).max()) == 0.0


def test_flash_bias_grad_broadcast_shapes():
    """In-kernel dbias reduces to broadcast bias shapes: [1, H, S, S] and
    [1, 1, S, S] (VERDICT r3 #7 done-condition shapes)."""
    b, s, h, d = 2, 256, 2, 64
    q = _rand(b, s, h, d, seed=80) * 0.3
    k = _rand(b, s, h, d, seed=81) * 0.3
    v = _rand(b, s, h, d, seed=82)

    for bias_shape in ((1, h, s, s), (1, 1, s, s)):
        bias = _rand(*bias_shape, seed=83) * 0.1

        def loss_fast(bias):
            return jnp.sum(flash_attention(q, k, v, False, None, 128, 128,
                                           bias=bias, bias_grad=True) ** 2)

        def loss_ref(bias):
            logits = (jnp.einsum("bqhd,bkhd->bhqk", q, k)
                      .astype(jnp.float32) / np.sqrt(d) + bias)
            p = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
            return jnp.sum(out ** 2)

        g_fast = jax.grad(loss_fast)(bias)
        g_ref = jax.grad(loss_ref)(bias)
        assert g_fast.shape == bias_shape
        np.testing.assert_allclose(np.asarray(g_fast), np.asarray(g_ref),
                                   rtol=4e-4, atol=4e-4,
                                   err_msg=str(bias_shape))


def test_flash_bias_grad_with_dropout_and_window():
    """The old composed-dbias gate is gone: learned-bias gradients now
    compose with dropout (mask re-derived in-kernel) and sliding windows
    (skipped blocks emit zero tiles)."""
    b, s, h, d = 1, 256, 2, 64
    q = _rand(b, s, h, d, seed=90) * 0.3
    k = _rand(b, s, h, d, seed=91) * 0.3
    v = _rand(b, s, h, d, seed=92)
    bias = _rand(b, h, s, s, seed=93) * 0.1

    # dropout: fwd/bwd masks must agree — check E[grad] sanity via p→0
    # limit (in-kernel PRNG: TPU or Mosaic interpret only)
    try:
        seed = jnp.asarray([123], jnp.int32)
        g_p0 = jax.grad(lambda bb: jnp.sum(flash_attention(
            q, k, v, False, None, 128, 128, bias=bb, dropout_p=1e-7,
            dropout_seed=seed, bias_grad=True) ** 2))(bias)
        g_ref = jax.grad(lambda bb: jnp.sum(flash_attention(
            q, k, v, False, None, 128, 128, bias=bb,
            bias_grad=True) ** 2))(bias)
        np.testing.assert_allclose(np.asarray(g_p0), np.asarray(g_ref),
                                   rtol=1e-3, atol=1e-3)
    except NotImplementedError as e:
        if "prng" not in str(e):
            raise

    # window: parity vs composed with the same band mask
    win = (64, 0)
    g_win = jax.grad(lambda bb: jnp.sum(flash_attention(
        q, k, v, False, None, 64, 64, bias=bb, window=win,
        bias_grad=True) ** 2))(bias)

    def loss_ref_win(bias):
        logits = (jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
                  / np.sqrt(d) + bias)
        rows = jnp.arange(s)[:, None]
        cols = jnp.arange(s)[None, :]
        keep = (cols >= rows - 64) & (cols <= rows)
        logits = jnp.where(keep[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
        return jnp.sum(out ** 2)

    g_wref = jax.grad(loss_ref_win)(bias)
    np.testing.assert_allclose(np.asarray(g_win), np.asarray(g_wref),
                               rtol=4e-4, atol=4e-4)


# -- KPS portable primitives (round 4; reference paddle/phi/kernels/
# primitive/ — SURVEY §2.2) ---------------------------------------------------
def test_kps_elementwise_primitive():
    from paddle_tpu.kernels.pallas.primitives import elementwise

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 256).astype(np.float32))
    y = jnp.asarray(rng.randn(64, 256).astype(np.float32))
    out = elementwise(lambda a, b: a * b + 1.0, x, y)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x * y + 1.0),
                               rtol=1e-5, atol=1e-5)
    # unary + 3-D view
    x3 = jnp.asarray(rng.randn(4, 16, 128).astype(np.float32))
    out3 = elementwise(jnp.tanh, x3)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(jnp.tanh(x3)),
                               rtol=1e-6, atol=1e-6)


def test_kps_row_reduce_primitive():
    from paddle_tpu.kernels.pallas.primitives import row_reduce

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(32, 512).astype(np.float32))
    np.testing.assert_allclose(np.asarray(row_reduce(jnp.add, 0.0, x)),
                               np.asarray(x).sum(-1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(row_reduce(jnp.maximum, -np.inf, x)),
        np.asarray(x).max(-1), rtol=1e-6)
    # multi-tile column streaming + 3-D view
    x3 = jnp.asarray(rng.randn(2, 8, 4096).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(row_reduce(jnp.add, 0.0, x3, block_cols=1024)),
        np.asarray(x3).sum(-1), rtol=1e-4, atol=1e-4)
    from paddle_tpu.enforce import InvalidArgumentError
    with pytest.raises(InvalidArgumentError, match="lane"):
        row_reduce(jnp.add, 0.0, jnp.ones((4, 100)))


def test_kps_online_softmax_update():
    from paddle_tpu.kernels.pallas.primitives import online_softmax_update

    rng = np.random.RandomState(2)
    s1 = jnp.asarray(rng.randn(8, 64).astype(np.float32))
    s2 = jnp.asarray(rng.randn(8, 64).astype(np.float32))
    v1 = jnp.asarray(rng.randn(64, 16).astype(np.float32))
    v2 = jnp.asarray(rng.randn(64, 16).astype(np.float32))

    m = jnp.full((8,), -1e30)
    l = jnp.zeros((8,))
    acc = jnp.zeros((8, 16))
    m, l, acc, _ = online_softmax_update(s1, m, l, acc, v1)
    m, l, acc, _ = online_softmax_update(s2, m, l, acc, v2)
    out = acc / l[:, None]

    s = jnp.concatenate([s1, s2], axis=1)
    v = jnp.concatenate([v1, v2], axis=0)
    ref = jax.nn.softmax(s, axis=-1) @ v
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kps_fused_layer_norm_fwd_bwd():
    from paddle_tpu.kernels.pallas.primitives import layer_norm

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(6, 32, 256).astype(np.float32))
    g = jnp.asarray(rng.rand(256).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(256).astype(np.float32) * 0.1)

    def composed(x, g, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b

    y = layer_norm(x, g, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(composed(x, g, b)),
                               rtol=1e-5, atol=1e-5)

    def loss_fused(x, g, b):
        return jnp.sum(layer_norm(x, g, b) ** 2)

    def loss_ref(x, g, b):
        return jnp.sum(composed(x, g, b) ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, g, b)
    for a, bb, nm in zip(gf, gr, ("dx", "dg", "db")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=2e-4, atol=2e-4, err_msg=nm)
