"""Single-dispatch ragged serving (ISSUE 6): unified prefill+decode
kernel parity vs the composed einsum path, the one-dispatch-per-step
contract, the one engine however it is asked for, pool-pressure
scheduling, the quantized KV pool (capacity + determinism), TP int8
weights, and the telemetry-driven adaptive prefill/decode mix."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.flags import flag, set_flags
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import gpt as G
from paddle_tpu.models.generation import gpt_generate

CFG = G.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                  max_seq_len=128, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return G.init_hybrid_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _restore_serving_flags():
    keep = {k: flag(k) for k in ("serving_kv_cache_dtype",
                                 "serving_adaptive_mix")}
    yield
    set_flags(keep)


def golden(params, prompt, n):
    out = gpt_generate(params, CFG, jnp.asarray(prompt, jnp.int32)[None], n)
    return np.asarray(out)[0, len(prompt):].tolist()


def mk(params, **kw):
    # fixed mix by default: an adaptive engine lazily compiles one
    # unified program PER burst length the scheduler picks — interpret-
    # mode compiles dominate tier-1 wall time. The adaptive policy has
    # its own explicit tests below.
    base = dict(max_batch=2, block_size=8, num_blocks=24,
                max_blocks_per_seq=8, chunk=8, adaptive_mix=False)
    base.update(kw)
    return ServingEngine(params, CFG, **base)


# what `mk` builds when it is told nothing else (the flags' defaults)
_MK_DEFAULTS = dict(decode_burst=8, kv_cache_dtype="auto", max_batch=2,
                    adaptive_mix=False, mesh=None, int8=False)


@pytest.fixture(scope="module")
def engines(params):
    """`engines(**kw)` is `mk(params, **kw)`, built ONCE a geometry and
    handed to every test that asks for the same one: most of a serving test
    is tracing and compiling the same unified step again (ROADMAP Queue 3
    item 1). A test takes an engine with no work in it and leaves it so
    (`run()` drains it): its pages are free again, its compiled steps stay.
    What it counts over its life (steps, dispatches, micro-steps) a test
    reads as a difference. A test that needs a FRESH engine (new pools, new
    compiles, a patched program) still calls `mk`."""
    built = {}

    def get(**kw):
        kw = {k: v for k, v in kw.items()
              if k not in _MK_DEFAULTS or _MK_DEFAULTS[k] != v}
        key = tuple(sorted(kw.items()))
        if key not in built:
            built[key] = mk(params, **kw)
        eng = built[key]
        assert not eng.has_work() and not eng.queue
        return eng
    return get


# ---------------------------------------------------------------------------
# kernel: parity vs the composed (gather + masked softmax) reference
# ---------------------------------------------------------------------------
@pytest.fixture(params=["copied", "aliased"])
def interpreter(request, monkeypatch):
    """The kernel under both interpreters. Its output is written in place
    (aliased to a zeroed operand, a row's positions by its own copies):
    plain ``interpret=True`` copies the aliased operand and runs a copy the
    moment it is started; ``pltpu.InterpretParams()`` keeps a TPU's memory
    and semaphores, so a copy that is never waited for, or a row that
    writes beside its own positions, shows there (PERF.md, PR 28)."""
    if request.param == "aliased":
        from jax.experimental.pallas import tpu as pltpu
        from paddle_tpu.kernels.pallas import ragged_paged_attention as mod
        monkeypatch.setattr(mod, "_interpret", pltpu.InterpretParams)
    return request.param


def _pack(q_lens, order=None, tail=0, gap=0):
    """Where each row's chunk starts in the packed buffer when the rows are
    laid in `order` (row order unless given), `gap` unowned positions after
    each, `tail` positions of padding at the end: (starts [R], T)."""
    starts, cursor = np.zeros(len(q_lens), np.int32), 0
    for r in (range(len(q_lens)) if order is None else order):
        if q_lens[r]:
            starts[r] = cursor
            cursor += int(q_lens[r]) + gap
    return starts, cursor - (gap if cursor else 0) + tail


def _composed_reference(q, kp, vp, tables, starts, q_lens, kv_lens, scale):
    """Independent einsum re-derivation of the ragged kernel's contract:
    per-row gather of referenced blocks, causal-within-chunk masking, a
    row's output at its own packed positions and zero everywhere else."""
    T, hq, D = q.shape
    hkv, _, bs, _ = kp.shape
    g = hq // hkv
    out = np.zeros((T, hq, D), np.float32)
    kp, vp, q = np.asarray(kp, np.float32), np.asarray(vp, np.float32), \
        np.asarray(q, np.float32)
    for r in range(len(q_lens)):
        ql, kl = int(q_lens[r]), int(kv_lens[r])
        if ql == 0:
            continue
        ks = np.concatenate([kp[:, tables[r, j]]
                             for j in range(tables.shape[1])], axis=1)
        vs = np.concatenate([vp[:, tables[r, j]]
                             for j in range(tables.shape[1])], axis=1)
        for c in range(ql):
            qpos = kl - ql + c
            for h in range(hq):
                kh = ks[h // g][:qpos + 1]
                s = (q[starts[r] + c, h] @ kh.T) * scale
                p = np.exp(s - s.max())
                p /= p.sum()
                out[starts[r] + c, h] = p @ vs[h // g][:qpos + 1]
    return out


def _owned(starts, q_lens, T):
    """[T] bool: the packed positions that belong to a row."""
    own = np.zeros(T, bool)
    for s, n in zip(starts, q_lens):
        own[s:s + n] = True
    return own


# (q_lens, kv_lens) a batch; bs = 8, C = 6, five table slots (40 positions)
# or as many as the longest row fills
_KERNEL_BATCHES = {
    # row 0 decode, row 1 prefill chunk mid-sequence, row 2 EMPTY
    # (finished slot), row 3 fresh prefill
    "mixed": ([1, 6, 0, 3], [19, 11, 0, 3]),
    # both arms and the empty row in one call: q_len 0, 1 (a decode row),
    # 2-8 (verify rows; the narrow arm while q_len * g <= 8) and the full
    # chunk, the narrow rows before, between and after the wide ones
    "arms": ([1, 0, 2, 6, 4, 1, 6], [9, 0, 17, 6, 30, 33, 40]),
    # kv_len at 1, bs, bs + 1, and into the table's last page (its first
    # position, its last)
    "edges": ([1, 1, 1, 1, 6, 3], [1, 8, 9, 33, 40, 40]),
    # the wide arm's BLOCKS (8 pages = 64 positions an update at these
    # sizes; a table of 19 pages): a chunk whose context is three blocks
    # with a ragged last one (19 pages: 8 + 8 + 3), one that ends exactly
    # on a block's edge (16 pages), one shorter than a block, each between
    # decode rows: the stream's hand-over from a paged row to a blocked one
    # and back
    "blocks": ([1, 6, 1, 6, 6, 1], [70, 150, 9, 128, 20, 130]),
}


def _own_pages(kv_lens, bs, nb):
    """A table a row: the pages its context fills, numbered from 1 on."""
    tables = np.zeros((len(kv_lens), nb), np.int32)
    blk = 1
    for r, kv_len in enumerate(kv_lens):
        for j in range(-(-int(kv_len) // bs)):
            tables[r, j] = blk
            blk += 1
    return tables


def _attend(q, kp, vp, tables, starts, q_lens, kv_lens, scale, *rest,
            c_att):
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    return np.asarray(ragged_paged_attention(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(starts),
        jnp.asarray(q_lens), jnp.asarray(kv_lens), scale, *rest,
        c_att=c_att), np.float32)


@pytest.mark.parametrize("batch", sorted(_KERNEL_BATCHES))
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (20, 4)])
def test_ragged_kernel_matches_composed_reference(hq, hkv, batch,
                                                  interpreter):
    """MHA, GQA, and a 5-query group whose decode row (5 folded rows) takes
    the 8-row arm while its 2-query row (10) takes the whole tile; rows
    packed in row order, three positions of padding behind them."""
    rng = np.random.RandomState(0)
    q_lens, kv_lens = (np.array(a, np.int32) for a in _KERNEL_BATCHES[batch])
    C, D, bs = 6, 16, 8
    pages = -(-kv_lens // bs)       # the table as wide as the longest row
    nb, NB = max(5, int(pages.max())), max(32, int(pages.sum()) + 1)
    kp = jnp.asarray(rng.randn(hkv, NB, bs, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(hkv, NB, bs, D).astype(np.float32))
    tables = _own_pages(kv_lens, bs, nb)
    starts, T = _pack(q_lens, tail=3)
    q = jnp.asarray(rng.randn(T, hq, D).astype(np.float32))
    scale = 1.0 / np.sqrt(D)
    out = _attend(q, kp, vp, tables, starts, q_lens, kv_lens, scale, c_att=C)
    ref = _composed_reference(q, kp, vp, tables, starts, q_lens, kv_lens,
                              scale)
    rel = np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-9)
    assert rel <= 1e-2, rel  # acceptance: <=1e-2 rel (exceeds it: fp32)
    assert np.abs(out - ref).max() < 1e-5
    # positions that belong to no row (the tail padding) read zero
    assert (out[~_owned(starts, q_lens, T)] == 0).all()


@pytest.mark.parametrize("hq,hkv,tile_rows", [(4, 4, None), (20, 4, None),
                                              (20, 4, 64)])
def test_ragged_kernel_arms_agree_on_a_decode_row(hq, hkv, tile_rows,
                                                  monkeypatch):
    """The same decode rows through a c_att = 1 call (the burst passes'
    form: T = R, starts = arange(R), one arm, an 8-row tile) and as
    q_len = 1 rows of a c_att = 128 call beside a full chunk (pass 1: the
    narrow arm of a 128 * g-row tile) give the same output. With sub-tiles
    of 64 rows the chunk row's 640 folded rows are walked in ten, each
    half a chunk of one query head: a sub-tile's first chunk position is
    in its mask."""
    if tile_rows:
        from paddle_tpu.kernels.pallas import ragged_paged_attention as mod
        monkeypatch.setattr(mod, "_TILE_ROWS", tile_rows)
        assert mod._tile_rows(hq // hkv, 128) == tile_rows
    rng = np.random.RandomState(5)
    R, C, D, bs, nb, NB = 4, 128, 16, 16, 9, 24
    kp = jnp.asarray(rng.randn(hkv, NB, bs, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(hkv, NB, bs, D).astype(np.float32))
    q_lens = np.array([1, 128, 0, 1], np.int32)
    kv_lens = np.array([37, 140, 0, 16], np.int32)
    tables = _own_pages(kv_lens, bs, nb)
    # decode rows by slot, then the chunk row: as `_pack_ragged` lays them
    starts, T = _pack(q_lens, order=[0, 3, 1], tail=2)
    q = jnp.asarray(rng.randn(T, hq, D).astype(np.float32))
    scale = 1.0 / np.sqrt(D)
    wide = _attend(q, kp, vp, tables, starts, q_lens, kv_lens, scale,
                   c_att=C)
    decode = np.array([1, 0, 0, 1], np.int32)       # the chunk row sits out
    q_one = jnp.zeros((R, hq, D), jnp.float32).at[jnp.asarray([0, 3])].set(
        q[jnp.asarray(starts[[0, 3]])])
    one = _attend(q_one, kp, vp, tables, np.arange(R, dtype=np.int32),
                  decode, kv_lens * decode, scale, c_att=1)
    for r in (0, 3):
        np.testing.assert_allclose(wide[starts[r]], one[r], rtol=0,
                                   atol=1e-6)
    assert (one[[1, 2]] == 0).all()
    ref = _composed_reference(q, kp, vp, tables, starts, q_lens, kv_lens,
                              scale)
    assert np.abs(wide - ref).max() < 1e-5


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_ragged_kernel_quantized_scales_are_per_head(kv_dtype):
    """A quantized pool whose (head, page) scales differ widely by head:
    the kernel dequantizes each head's products with that head's scale (in
    both arms, whole-pool form, layer > 0), and a scale taken from the
    neighbouring head would miss by far more than the tolerance."""
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    rng = np.random.RandomState(9)
    L, hq, hkv, NB, bs, D, C, nb = 2, 8, 4, 20, 8, 16, 12, 10
    if kv_dtype == "int8":
        qmax, store = 127.0, jnp.int8
        grid = rng.randint(-127, 128, (2, L, hkv, NB, bs, D))
    else:
        qmax, store = 448.0, jnp.float8_e4m3fn
        grid = rng.uniform(-448, 448, (2, L, hkv, NB, bs, D))
    kp, vp = (jnp.asarray(a, jnp.float32).astype(store) for a in grid)
    per_head = 4.0 ** np.arange(hkv)                # 1, 4, 16, 64
    ks = (rng.rand(L, hkv, NB) + 0.5) * per_head[None, :, None]
    vs = (rng.rand(L, hkv, NB) + 0.5) * per_head[None, ::-1, None]
    q_lens = np.array([1, 12, 0, 3], np.int32)      # narrow, wide, -, narrow
    # the wide row's 10 pages are two blocks of its arm (8 + 2): a scale a
    # (head, page) INSIDE one soft-max update
    kv_lens = np.array([20, 76, 0, 9], np.int32)
    tables = _own_pages(kv_lens, bs, nb)
    starts, T = _pack(q_lens, order=[0, 3, 1], tail=1)
    q = jnp.asarray(rng.randn(T, hq, D).astype(np.float32))
    scale = 1.0 / np.sqrt(D)
    out = np.asarray(jax.jit(ragged_paged_attention,
                             static_argnames=("scale", "c_att"))(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(starts),
        jnp.asarray(q_lens), jnp.asarray(kv_lens), scale=scale,
        k_scales=jnp.asarray(ks, jnp.float32),
        v_scales=jnp.asarray(vs, jnp.float32), layer=jnp.int32(1), c_att=C))

    def reference(ks, vs):
        kf = np.asarray(kp[1], np.float32) * ks[1][..., None, None] / qmax
        vf = np.asarray(vp[1], np.float32) * vs[1][..., None, None] / qmax
        return _composed_reference(q, kf, vf, tables, starts, q_lens,
                                   kv_lens, scale)

    ref = reference(ks, vs)
    live = _owned(starts, q_lens, T)
    tol = 1e-4 * np.abs(ref).max()
    assert np.abs(out - ref)[live].max() < tol
    assert (out[~live] == 0).all()
    for wrong in (reference(np.roll(ks, 1, axis=1), vs),
                  reference(ks, np.roll(vs, 1, axis=1))):
        assert np.abs(out - wrong)[live].max() > 100 * tol


def test_ragged_kernel_bf16_rel_tolerance(interpreter):
    rng = np.random.RandomState(3)
    C, hq, hkv, D, bs, nb, NB = 4, 4, 4, 16, 8, 4, 12
    kp = jnp.asarray(rng.randn(hkv, NB, bs, D)).astype(jnp.bfloat16)
    vp = jnp.asarray(rng.randn(hkv, NB, bs, D)).astype(jnp.bfloat16)
    q_lens = np.array([1, 4, 2], np.int32)
    kv_lens = np.array([9, 12, 2], np.int32)
    tables = _own_pages(kv_lens, bs, nb)
    starts, T = _pack(q_lens, tail=1)
    q = jnp.asarray(rng.randn(T, hq, D)).astype(jnp.bfloat16)
    scale = 1.0 / np.sqrt(D)
    out = _attend(q, kp, vp, tables, starts, q_lens, kv_lens, scale, c_att=C)
    ref = _composed_reference(q.astype(jnp.float32), kp.astype(jnp.float32),
                              vp.astype(jnp.float32), tables, starts, q_lens,
                              kv_lens, scale)
    rel = np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-9)
    assert rel <= 1e-2, rel  # acceptance bound, bf16
    assert (out[-1] == 0).all()


def test_ragged_kernel_int8_pool_close():
    from paddle_tpu.quantization.kv_cache import append_tokens_quantized
    rng = np.random.RandomState(1)
    hkv, NB, bs, D, R, C, nb = 2, 10, 8, 16, 2, 8, 4
    tables = np.zeros((R, nb), np.int32)
    tables[0, :2] = [1, 2]
    tables[1, :2] = [3, 4]
    kf = rng.randn(R, C, hkv, D).astype(np.float32)
    vf = rng.randn(R, C, hkv, D).astype(np.float32)
    pos0 = np.array([0, 0], np.int32)
    q_lens = np.array([8, 5], np.int32)
    kp = jnp.zeros((hkv, NB, bs, D), jnp.int8)
    ks = jnp.zeros((hkv, NB), jnp.float32)
    vp, vs = jnp.zeros_like(kp), jnp.zeros_like(ks)
    kp, ks = append_tokens_quantized(kp, ks, jnp.asarray(kf),
                                     jnp.asarray(pos0), jnp.asarray(q_lens),
                                     jnp.asarray(tables), bs)
    vp, vs = append_tokens_quantized(vp, vs, jnp.asarray(vf),
                                     jnp.asarray(pos0), jnp.asarray(q_lens),
                                     jnp.asarray(tables), bs)
    starts, T = _pack(q_lens)
    q = jnp.asarray(rng.randn(T, hkv, D).astype(np.float32))
    scale = 1.0 / np.sqrt(D)
    out = _attend(q, kp, vp, tables, starts, q_lens, q_lens, scale, ks, vs,
                  c_att=C)
    # reference over the EXACT float tokens: int8 storage error only
    kpf = jnp.zeros((hkv, NB, bs, D), jnp.float32)
    vpf = jnp.zeros_like(kpf)
    for r in range(R):
        for t in range(int(q_lens[r])):
            b, o = tables[r, t // bs], t % bs
            kpf = kpf.at[:, b, o].set(kf[r, t])
            vpf = vpf.at[:, b, o].set(vf[r, t])
    ref = _composed_reference(q, kpf, vpf, tables, starts, q_lens, q_lens,
                              scale)
    assert np.abs(out - ref).max() < 0.08


def test_quantized_append_into_last_table_page():
    """Regression: a chunk landing in the row's LAST table slot makes the
    append's page window overhang the table end. The overflow entry must
    route to scratch block 0 — clipping it onto the real last block made
    a duplicate scatter index whose (unspecified-order) requant-only
    write could replace the freshly appended tokens."""
    from paddle_tpu.quantization.kv_cache import append_tokens_quantized
    rng = np.random.RandomState(3)
    hkv, NB, bs, D, nb = 2, 6, 8, 16, 2
    tables = np.array([[1, 2]], np.int32)       # row full: 2 of 2 slots
    C = bs                                      # chunk fills the page
    kf = rng.randn(1, C, hkv, D).astype(np.float32)
    pos0 = np.array([bs], np.int32)             # starts in the last slot
    q_lens = np.array([C], np.int32)
    kp = jnp.zeros((hkv, NB, bs, D), jnp.int8)
    ks = jnp.zeros((hkv, NB), jnp.float32)
    kp, ks = append_tokens_quantized(kp, ks, jnp.asarray(kf),
                                     jnp.asarray(pos0), jnp.asarray(q_lens),
                                     jnp.asarray(tables), bs)
    deq = (np.asarray(kp[:, 2], np.float32)
           * np.asarray(ks[:, 2])[:, None, None] / 127.0)
    want = np.moveaxis(kf[0], 1, 0)             # [hkv, bs, D]
    err = np.abs(deq - want).max()
    assert err < 0.05, err                      # int8 grid error only


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_ragged_kernel_whole_pool_layer_index(kv_dtype):
    """The engine's form of the call: the whole [L, H, NB, bs, D] pool and
    a traced layer index give bit for bit what the 4-D call gives on that
    layer's pool — and the quantized append on the whole pool touches
    that layer's pages alone."""
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    from paddle_tpu.quantization.kv_cache import append_tokens_quantized
    rng = np.random.RandomState(11)
    L, hkv, NB, bs, D, R, C, nb = 3, 2, 10, 8, 16, 3, 8, 4
    tables = np.zeros((R, nb), np.int32)
    tables[0, :2], tables[1, :2], tables[2, :1] = [1, 2], [3, 4], [5]
    tables = jnp.asarray(tables)
    starts, T = _pack([8, 5, 0], tail=2)
    starts = jnp.asarray(starts)
    q_lens = jnp.asarray(np.array([8, 5, 0], np.int32))
    kv_lens = jnp.asarray(np.array([16, 5, 0], np.int32))
    q = jnp.asarray(rng.randn(T, hkv, D).astype(np.float32))
    scale = 1.0 / np.sqrt(D)
    if kv_dtype == "int8":
        kp = jnp.asarray(rng.randint(-127, 128, (L, hkv, NB, bs, D)),
                         jnp.int8)
        vp = jnp.asarray(rng.randint(-127, 128, (L, hkv, NB, bs, D)),
                         jnp.int8)
        ks = jnp.asarray(rng.rand(L, hkv, NB).astype(np.float32) + 0.5)
        vs = jnp.asarray(rng.rand(L, hkv, NB).astype(np.float32) + 0.5)
        val = jnp.asarray(rng.randn(R, C, hkv, D).astype(np.float32))
        pos0 = kv_lens - q_lens
        kp5, ks5 = jax.jit(append_tokens_quantized, static_argnums=6)(
            kp, ks, val, pos0, q_lens, tables, bs, jnp.int32(1))
        kp4, ks4 = append_tokens_quantized(kp[1], ks[1], val, pos0, q_lens,
                                           tables, bs)
        np.testing.assert_array_equal(kp5[1], kp4)
        np.testing.assert_array_equal(ks5[1], ks4)
        for other in (0, 2):    # the append strays into no other layer
            np.testing.assert_array_equal(kp5[other], kp[other])
            np.testing.assert_array_equal(ks5[other], ks[other])
        kp, ks = kp5, ks5
    else:
        kp = jnp.asarray(rng.randn(L, hkv, NB, bs, D)).astype(jnp.bfloat16)
        vp = jnp.asarray(rng.randn(L, hkv, NB, bs, D)).astype(jnp.bfloat16)
        q = q.astype(jnp.bfloat16)
        ks = vs = None

    @jax.jit
    def whole(layer):
        return ragged_paged_attention(q, kp, vp, tables, starts, q_lens,
                                      kv_lens, scale, ks, vs, layer, c_att=C)

    for layer in (1, 2):
        one = ragged_paged_attention(
            q, kp[layer], vp[layer], tables, starts, q_lens, kv_lens, scale,
            None if ks is None else ks[layer],
            None if vs is None else vs[layer], c_att=C)
        np.testing.assert_array_equal(
            np.asarray(whole(jnp.int32(layer)), np.float32),
            np.asarray(one, np.float32))
    assert not np.array_equal(np.asarray(whole(jnp.int32(1)), np.float32),
                              np.asarray(whole(jnp.int32(2)), np.float32))


def _layout_case(g, kv_dtype, q_lens, kv_lens, order=None, tail=0, gap=0,
                 c_att=12, seed=21):
    """The kernel on a packed layout, for a query group of `g` (2 KV heads)
    over a bf16 or an int8 pool, and what the reference makes of the same
    pool: (run, reference, starts, T, tolerance), where run(q_lens) and
    reference(q_lens) attend the SAME packed buffer (compiled once) with
    some rows' q_len changed."""
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    rng = np.random.RandomState(seed)
    hkv, D, bs, nb, NB = 2, 16, 8, 6, 40
    q_lens, kv_lens = (np.asarray(a, np.int32) for a in (q_lens, kv_lens))
    tables = _own_pages(kv_lens, bs, nb)
    starts, T = _pack(q_lens, order=order, tail=tail, gap=gap)
    q = jnp.asarray(rng.randn(T, g * hkv, D)).astype(jnp.bfloat16)
    scale = 1.0 / np.sqrt(D)
    if kv_dtype == "int8":
        kp, vp = (jnp.asarray(rng.randint(-127, 128, (hkv, NB, bs, D)),
                              jnp.int8) for _ in range(2))
        ks, vs = (jnp.asarray(rng.rand(hkv, NB).astype(np.float32) + 0.5)
                  for _ in range(2))
        kf = np.asarray(kp, np.float32) * np.asarray(ks)[..., None, None] / 127
        vf = np.asarray(vp, np.float32) * np.asarray(vs)[..., None, None] / 127
    else:
        kp, vp = (jnp.asarray(rng.randn(hkv, NB, bs, D)).astype(jnp.bfloat16)
                  for _ in range(2))
        ks = vs = None
        kf, vf = np.asarray(kp, np.float32), np.asarray(vp, np.float32)
    kernel = jax.jit(lambda n: ragged_paged_attention(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(starts), n,
        jnp.asarray(kv_lens), scale, ks, vs, c_att=c_att))

    def run(n=q_lens):
        return np.asarray(kernel(jnp.asarray(n, jnp.int32)), np.float32)

    def reference(n=q_lens):
        return _composed_reference(q, kf, vf, tables, starts, n, kv_lens,
                                   scale)

    # bf16 queries and output
    return run, reference, starts, T, 1e-2 * np.abs(reference()).max()


# packed layouts the serving step makes (c_att = 12, pages of 8); the edge
# of the narrow arm is 8 // g chunk positions: 8 for MHA, 1 for g = 5
_LAYOUTS = {
    # `_pack_ragged` lays decode rows by slot, then prefill rows: rows are
    # NOT in row order and `starts` is not monotone
    "out_of_row_order": lambda edge: dict(
        q_lens=[1, 12, 1, 5, 1], kv_lens=[9, 30, 17, 5, 40],
        order=[0, 2, 4, 3, 1], tail=2),
    # finished slots between live ones cost their grid step and nothing
    # else: no query copy, no output write
    "empty_rows_between": lambda edge: dict(
        q_lens=[0, 1, 0, 0, 7, 0, 1, 0], kv_lens=[0, 20, 0, 0, 15, 0, 8, 0],
        tail=1),
    # a short chunk row whose last position is the buffer's last: the
    # static-size query copy must not leave the buffer
    "chunk_ends_the_buffer": lambda edge: dict(
        q_lens=[1, 12, 5], kv_lens=[11, 12, 29], order=[0, 1, 2]),
    # ... and a decode row on the buffer's last position (the narrow copy)
    "decode_ends_the_buffer": lambda edge: dict(
        q_lens=[12, 5, 1], kv_lens=[36, 5, 33], order=[0, 1, 2]),
    # a verify row at the narrow arm's edge and one past it
    "verify_rows_at_the_arms_edge": lambda edge: dict(
        q_lens=[edge, edge + 1, 1, edge + 1, edge],
        kv_lens=[20, 21, 3, edge + 1, 40], order=[2, 0, 1, 4, 3], tail=3),
    # positions between rows that belong to no row
    "gaps_between_rows": lambda edge: dict(
        q_lens=[3, 1, 12], kv_lens=[3, 25, 20], order=[1, 0, 2], gap=2,
        tail=4),
}


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("g", [1, 5])
def test_ragged_kernel_packed_layouts(g, kv_dtype, layout, interpreter):
    """The kernel reads a row's queries from, and writes its output to,
    the row's own positions of the packed buffer — wherever the host put
    them — and every position no row owns reads back exactly zero."""
    case = _LAYOUTS[layout](max(8 // g, 1))
    run, reference, starts, T, tol = _layout_case(g, kv_dtype, **case)
    out = run()
    assert np.isfinite(out).all()
    assert np.abs(out - reference()).max() < tol
    own = _owned(starts, case["q_lens"], T)
    assert own.sum() == sum(case["q_lens"])
    assert (out[~own] == 0).all()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("g", [1, 5])
def test_ragged_kernel_row_writes_its_own_positions_only(g, kv_dtype,
                                                         interpreter):
    """Take one row out of the batch (q_len 0): its positions read zero
    and every other row's output is bit for bit what it was — a row's
    write touches no neighbour's position, before it or behind it, on
    either arm."""
    case = dict(q_lens=[1, 12, 1, 5, 1], kv_lens=[9, 30, 17, 5, 40],
                order=[0, 2, 4, 3, 1], tail=2)
    run, reference, starts, T, tol = _layout_case(g, kv_dtype, **case)
    full = run()
    for gone in (2, 3, 1):     # a decode row, a short chunk, a full chunk
        q_lens = list(case["q_lens"])
        n, q_lens[gone] = q_lens[gone], 0
        out = run(q_lens)
        assert np.abs(out - reference(q_lens)).max() < tol
        s = int(starts[gone])
        assert (out[s:s + n] == 0).all()
        keep = np.ones(T, bool)
        keep[s:s + n] = False
        np.testing.assert_array_equal(out[keep], full[keep])


def _append_rows(case, bs, tile, rng):
    """(R, c_att, T, q_lens, pos0) of one pass, by what it asks of the
    append's work list (n tiles of at most W)."""
    if case == "docs":      # the docs cell's pass: decode rows, two chunks
        R, c_att, T = 64, 128, 192
        q_lens = np.zeros(R, np.int32)
        q_lens[:11] = 1
        q_lens[20], q_lens[41] = 128, 53
        pos0 = np.where(q_lens == 1, rng.randint(0, 2 * bs - 1, R), 0)
        pos0[20], pos0[41] = bs - 5, 3
        return R, c_att, T, q_lens, pos0.astype(np.int32)
    if case == "full":      # the bound's own worst case, n = W: every row
        R, c_att = 5, 2     # two tokens astride a tile edge
        q_lens = np.full(R, 2, np.int32)
        pos0 = tile * rng.randint(1, 3 * bs // tile, R) - 1
        return R, c_att, 2 * R, q_lens, pos0.astype(np.int32)
    R, c_att, T = 5, 12, 24
    q_lens = {"empty": [0, 0, 0, 0, 0],     # n = 0: no row has a token
              "one": [0, 0, 0, 1, 0],       # n = 1
              # decode rows, a chunk crossing tiles and a page, an empty
              # row, a decode row on a page's last position
              "mixed": [1, 12, 0, 7, 1],
              "poisoned": [1, 12, 0, 7, 1]}[case]
    pos0 = [rng.randint(0, 3 * bs), rng.randint(0, 2 * bs), 0,
            rng.randint(0, bs), bs - 1]
    return R, c_att, T, np.array(q_lens, np.int32), np.array(pos0, np.int32)


@pytest.fixture(params=["copied", "aliased"])
def append_interpreter(request, monkeypatch):
    """`kv_append` under both interpreters: its pools are aliased in and
    out and written by its own copies, which only
    ``pltpu.InterpretParams()`` runs as the chip does (see
    `interpreter`)."""
    if request.param == "aliased":
        from jax.experimental.pallas import tpu as pltpu
        from paddle_tpu.kernels.pallas import kv_append as mod
        monkeypatch.setattr(mod, "_interpret", pltpu.InterpretParams)
    return request.param


@pytest.mark.parametrize("dtype,bs,D,case", [
    (dtype, bs, D, case)
    for dtype, bs, D in [("float32", 8, 16), ("float32", 16, 16),
                         ("bfloat16", 16, 16), ("bfloat16", 32, 16),
                         ("bfloat16", 16, 128), ("float32", 16, 256)]
    for case in ("mixed", "empty", "one", "full", "poisoned")] + [
        ("bfloat16", 128, 128, "docs"), ("float32", 128, 256, "docs")])
def test_kv_append_writes_the_rows_and_nothing_else(dtype, bs, D, case,
                                                    append_interpreter):
    """The in-place append (kernels/pallas/kv_append.py) against a plain
    loop, bit for bit, every other element of every layer untouched: the
    work list empty, of one tile, full to its bound W, of a pass's usual
    mix, at the docs cell's shape (a chunk row beside decode rows), and
    with every entry PAST the count n pointing at other live tiles, which
    a walk that went on would overwrite."""
    from paddle_tpu.kernels.pallas.kv_append import (append_tile, kv_append,
                                                     tile_work)
    dtype = jnp.dtype(dtype)
    tile = append_tile(dtype, bs)
    L, H, nb = (2, 2, 2) if case == "docs" else (3, 2, 4)
    for seed in range(3 if case == "mixed" else 1):
        rng = np.random.RandomState(seed)
        R, c_att, T, q_lens, pos0 = _append_rows(case, bs, tile, rng)
        NB = R * nb + 1
        tables = np.arange(1, NB, dtype=np.int32).reshape(R, nb)
        starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(
            np.int32)
        n, *tiles = jax.jit(lambda *a: tile_work(
            *a, jnp.asarray(tables), bs=bs, tile=tile, c_att=c_att, T=T))(
                jnp.asarray(starts), jnp.asarray(pos0), jnp.asarray(q_lens))
        n = int(n)
        if case == "poisoned":
            page, sub, tok0, lo, hi = (np.array(a) for a in tiles)
            assert 0 < n < len(page)
            # the tail lists tiles the pass wrote and ones it did not, all
            # of their rows marked new
            page[n:] = np.resize(np.concatenate([page[:n], tables[2]]),
                                 len(page) - n)
            sub[n:], tok0[n:], lo[n:], hi[n:] = 0, 0, 0, tile
            tiles = [jnp.asarray(a) for a in (page, sub, tok0, lo, hi)]
        kp, vp = (jnp.asarray(rng.randn(L, H, NB, bs, D)).astype(dtype)
                  for _ in range(2))
        k, v = (jnp.asarray(rng.randn(T, H, D)).astype(dtype)
                for _ in range(2))
        want = [np.asarray(a, np.float32).copy() for a in (kp, vp)]
        seen = set()
        for r in range(R):
            for c in range(q_lens[r]):
                page, off = tables[r, (pos0[r] + c) // bs], (pos0[r] + c) % bs
                seen.add((int(page), int(off) // tile))
                for pool, val in zip(want, (k, v)):
                    pool[1, :, page, off] = np.asarray(
                        val, np.float32)[starts[r] + c]
        assert n == len(seen)
        if case in ("empty", "one", "full"):
            assert n == {"empty": 0, "one": 1, "full": len(tiles[0])}[case]
        got = jax.jit(lambda kp, vp, k, v, *work: kv_append(
            kp, vp, k, v, jnp.int32(1), work, tile=tile))(
                kp, vp, k, v, jnp.int32(n), *tiles)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g, np.float32), w)


@pytest.mark.parametrize("bs,tile", [(8, 8), (16, 8), (16, 16), (128, 16)])
def test_tile_work_lists_each_tile_a_pass_writes_once(bs, tile):
    """`tile_work` against a plain loop over the rows: its count n is the
    number of distinct (page, tile) pairs the new positions lie in, and
    its first n entries are those pairs, each once, with the rows of the
    tile that are new and the packed index they come from."""
    from paddle_tpu.kernels.pallas.kv_append import tile_work
    R, nb, c_att, T = 9, 8, 40, 96
    tables = np.arange(1, R * nb + 1, dtype=np.int32).reshape(R, nb)
    work = jax.jit(lambda *a: tile_work(
        *a, jnp.asarray(tables), bs=bs, tile=tile, c_att=c_att, T=T))
    for seed in range(8):
        rng = np.random.RandomState(seed)
        q_lens = np.where(rng.rand(R) < 0.5, 1, rng.randint(0, c_att + 1, R))
        while q_lens.sum() > T:
            q_lens[np.argmax(q_lens)] //= 2
        pos0 = np.array([rng.randint(0, nb * bs - q + 1) for q in q_lens])
        starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]])
        want = {}       # (page, tile in page) -> {row of the tile: packed t}
        for r in range(R):
            for c in range(q_lens[r]):
                p = pos0[r] + c
                want.setdefault((tables[r, p // bs], p % bs // tile),
                                {})[p % tile] = starts[r] + c
        n, page, sub, tok0, lo, hi = (np.asarray(a) for a in work(
            *(jnp.asarray(a, jnp.int32) for a in (starts, pos0, q_lens))))
        assert n == len(want) <= len(page)
        got = {(page[w], sub[w]): {i: tok0[w] + i
                                   for i in range(lo[w], hi[w])}
               for w in range(n)}
        assert len(got) == n and got == want


# ---------------------------------------------------------------------------
# engine: single-dispatch contract + flags-off bitwise baseline
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_cache_dtype", ["f32", "int8"])
def test_step_writes_only_its_own_pages(params, kv_cache_dtype):
    """The pool is written in place through its flat views
    (ragged_step.py): one step may change, in every layer, the pages of
    the rows it ran (up to the tokens they hold now) and the scratch
    block 0 — every other page of every layer is bit-identical
    afterwards. A row number that strays shows here."""
    rng = np.random.RandomState(12)
    eng = mk(params, kv_cache_dtype=kv_cache_dtype)
    shape = eng.k_pools.shape                   # [L, H, NB, bs, D]
    if kv_cache_dtype == "int8":
        noise = [rng.randint(-127, 128, shape).astype(np.int8)
                 for _ in range(2)]
    else:
        noise = [rng.randn(*shape).astype(np.float32) for _ in range(2)]
    eng.k_pools = jnp.asarray(noise[0], eng.k_pools.dtype)
    eng.v_pools = jnp.asarray(noise[1], eng.v_pools.dtype)
    before = [np.asarray(eng.k_pools).copy(), np.asarray(eng.v_pools).copy()]
    eng.add_request(rng.randint(0, CFG.vocab_size, (11,)), 4)
    eng.add_request(rng.randint(0, CFG.vocab_size, (5,)), 4)
    eng.step()
    mine = {0}
    written = {}                                # page -> tokens it holds
    for slot, req in enumerate(eng.slots):
        if req is None:
            continue
        n = int(eng.lens[slot])
        for j in range(-(-n // eng.bs)):
            page = int(eng.tables[slot, j])
            mine.add(page)
            written[page] = min(eng.bs, n - j * eng.bs)
    assert written, "the step ran no row"
    others = [b for b in range(shape[2]) if b not in mine]
    for was, pool in zip(before, (eng.k_pools, eng.v_pools)):
        now = np.asarray(pool)
        np.testing.assert_array_equal(now[:, :, others], was[:, :, others])
        for page, n in written.items():
            for layer in range(shape[0]):       # every layer wrote its own
                assert not np.array_equal(now[layer, :, page, :n],
                                          was[layer, :, page, :n])
            if kv_cache_dtype != "int8":        # int8 requantizes the page
                np.testing.assert_array_equal(now[:, :, page, n:],
                                              was[:, :, page, n:])


def test_one_dispatch_per_step_and_program_cache(params, engines):
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, CFG.vocab_size, (n,)) for n in (5, 13, 9, 16)]
    news = [6, 3, 9, 4]
    eng = engines()
    rids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
    res = eng.run()
    # exactly ONE compiled dispatch per engine step
    assert eng.dispatches == eng.engine_steps > 0
    # and no hidden programs: every traced-cache entry is one of the
    # unified-step programs the engine built (one per burst length used)
    assert eng.compiled_cache_entries() == len(eng._unified_cache) > 0
    for rid, p, n in zip(rids, prompts, news):
        assert res[rid] == golden(params, p, n), rid


def test_the_engine_is_one_however_it_is_asked_for(params):
    """`ragged` selects nothing (PR 30): left out, or True as the
    benchmark's runners still pass it, the unified step lowers to the
    same text; anything else raises at construction."""
    rng = np.random.RandomState(2)
    texts = []
    for kw in ({}, {"ragged": True}):
        eng = mk(params, **kw)
        eng.add_request(rng.randint(0, CFG.vocab_size, (9,)), 6)
        b = eng._pack_ragged(eng._admit())
        texts.append(eng._build_unified(b.K).lower(
            *eng._upload_ragged(b)).as_text())
    assert texts[0] == texts[1]
    for bad in (False, "auto", 0):
        with pytest.raises(ValueError, match="PR 30"):
            mk(params, ragged=bad)


# ---------------------------------------------------------------------------
# engine: ragged goldens (streaming, eos, temperature-0 determinism)
# ---------------------------------------------------------------------------
def test_ragged_streaming_and_eos(params, engines):
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, CFG.vocab_size, (9,))
    g = golden(params, prompt, 10)
    eos = g[3]
    seen = []
    eng = engines()
    rid = eng.add_request(prompt, 10, eos_id=eos,
                          on_token=lambda r, t: seen.append((r, t)))
    res = eng.run()
    assert res[rid] == g[:4]
    assert [t for _, t in seen] == res[rid]


@pytest.mark.parametrize("seed", [0, 2 ** 31 - 1])
def test_the_steps_one_call_key_split_is_the_eager_split(seed):
    """The step splits its key in one compiled call; the keys are bit
    for bit those of the eager `key, sub = jax.random.split(key)`."""
    from paddle_tpu.inference.serving import _split_key
    key = jax.random.PRNGKey(seed)
    for _ in range(3):
        want_key, want_sub = jax.random.split(key)
        key, sub = _split_key(key)
        np.testing.assert_array_equal(np.asarray(key), np.asarray(want_key))
        np.testing.assert_array_equal(np.asarray(sub), np.asarray(want_sub))


# ---------------------------------------------------------------------------
# pool-pressure scheduling
# ---------------------------------------------------------------------------
def test_admission_waits_when_pages_exhausted(params):
    """Free pages run out -> the queue WAITS (no admission), and admits
    as soon as _finish returns blocks."""
    rng = np.random.RandomState(6)
    # 9 blocks: scratch + 8 usable; each request needs 2 (8+4 over bs=8).
    # adaptive mix: under queue pressure bursts shorten, so no request
    # can finish inside step 1 — the full-pool wait is observable
    eng = mk(params, max_batch=2, num_blocks=5,
             adaptive_mix=True)
    p1 = rng.randint(0, CFG.vocab_size, (8,))
    p2 = rng.randint(0, CFG.vocab_size, (8,))
    p3 = rng.randint(0, CFG.vocab_size, (8,))
    eng.add_request(p1, 4)
    eng.add_request(p2, 4)
    eng.add_request(p3, 4)
    eng.step()
    # pool holds 4 usable blocks = exactly two 2-block requests
    assert sum(s is not None for s in eng.slots) == 2
    assert len(eng.queue) == 1
    assert len(eng.free_blocks) == 0
    res = eng.run()
    assert len(res) == 3  # run() drained; p3 admitted after a finish
    assert eng.has_work() is False
    assert len(eng.free_blocks) == 4  # everything returned


def test_blocks_freed_and_reused_after_finish(params):
    rng = np.random.RandomState(7)
    eng = mk(params, num_blocks=9, max_blocks_per_seq=4)
    total_free = len(eng.free_blocks)
    prompts = [rng.randint(0, CFG.vocab_size, (8,)) for _ in range(6)]
    rids = [eng.add_request(p, 4) for p in prompts]
    res = eng.run()
    assert len(res) == 6
    assert len(eng.free_blocks) == total_free
    for rid, p in zip(rids, prompts):
        assert res[rid] == golden(params, p, 4)


def test_request_larger_than_pool_refused(params):
    """never-fits on the ragged path: rejected per-request (naming the
    pool cap in Request.error), sibling completes in the same run
    (ISSUE 13 satellite)."""
    rng = np.random.RandomState(22)
    sib = rng.randint(0, CFG.vocab_size, (8,))
    eng = mk(params, num_blocks=3, max_blocks_per_seq=8)
    bad = eng.add_request(np.zeros(20, np.int32), 10)  # needs 4 > 2 usable
    good = eng.add_request(sib, 4)                     # needs 2: fits
    reported = {}
    while eng.has_work():
        for r in eng.step():
            reported[r.rid] = r
    bad_r, good_r = reported[bad], reported[good]
    assert bad_r.status == "failed" and "pool capacity" in bad_r.error
    assert good_r.status == "ok"
    assert good_r.output == golden(params, sib, 4)


# ---------------------------------------------------------------------------
# quantized KV pool
# ---------------------------------------------------------------------------
def _capacity_cfg():
    return G.GPTConfig(vocab_size=97, hidden_size=64, num_layers=2,
                       num_heads=4, max_seq_len=128, dtype=jnp.float32)


def test_int8_kv_admits_2x_sequences_at_fixed_budget():
    """Acceptance: int8 KV admits >=1.9x the concurrent sequences of
    bf16 at a fixed pool byte budget."""
    cfg = _capacity_cfg()
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(8)
    budget = 9 * (2 * cfg.num_layers * cfg.num_heads * 16 * cfg.head_dim * 2)

    def admitted(kv):
        eng = ServingEngine(params, cfg, max_batch=16, block_size=16,
                            kv_pool_bytes=budget, max_blocks_per_seq=4,
                            chunk=8, kv_cache_dtype=kv)
        for _ in range(16):
            eng.add_request(rng.randint(0, cfg.vocab_size, (20,)), 8)
        eng._admit()
        return sum(s is not None for s in eng.slots)

    n_bf16 = admitted("bf16")
    n_int8 = admitted("int8")
    assert n_int8 / n_bf16 >= 1.9, (n_int8, n_bf16)


def _int8_run(eng, prompts, news):
    rids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
    res = eng.run()
    return [res[r] for r in rids]


def test_int8_kv_outputs_deterministic(params, engines):
    """Acceptance: the quantized-KV run is bitwise-deterministic across
    repeats: a FRESH engine (new pools, new compiles) serves what the
    module's int8 engine serves, whatever that one has served before, and
    serves it again."""
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, CFG.vocab_size, (n,)) for n in (9, 13)]
    news = [6, 6]
    fresh = mk(params, kv_cache_dtype="int8")
    q1 = _int8_run(fresh, prompts, news)
    q2 = _int8_run(engines(kv_cache_dtype="int8"), prompts, news)
    assert q1 == q2 == _int8_run(fresh, prompts, news)


def test_int8_kv_outputs_close_to_float(engines):
    """int8 storage error stays token-level small vs the float pool
    (slow tier; the kernel-level bound is the fast-tier
    test_ragged_kernel_int8_pool_close)."""
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, CFG.vocab_size, (n,)) for n in (9, 13)]
    news = [6, 6]
    fp = _int8_run(engines(), prompts, news)
    q1 = _int8_run(engines(kv_cache_dtype="int8"), prompts, news)
    total = sum(len(o) for o in fp)
    agree = sum(a == b for o1, o2 in zip(fp, q1)
                for a, b in zip(o1, o2))
    assert agree / total >= 0.75, (fp, q1)
    for o1, o2 in zip(fp, q1):
        assert o1[0] == o2[0]  # first token (largest margin) agrees


def test_fp8_kv_pool_runs(params):
    rng = np.random.RandomState(10)
    prompt = rng.randint(0, CFG.vocab_size, (9,))
    eng = mk(params, kv_cache_dtype="fp8_e4m3")
    rid = eng.add_request(prompt, 6)
    res = eng.run()
    g = golden(params, prompt, 6)
    assert len(res[rid]) == 6
    assert res[rid][0] == g[0]


def test_page_scale_reset_on_block_reuse(params):
    """Recycled blocks must not inherit a stale quantization range: run
    a LARGE-logit request through a tiny pool, then a fresh request that
    reuses its blocks — outputs must match a clean engine bitwise."""
    rng = np.random.RandomState(11)
    p1 = rng.randint(0, CFG.vocab_size, (8,))
    p2 = rng.randint(0, CFG.vocab_size, (8,))
    eng = mk(params, kv_cache_dtype="int8", max_batch=1,
             num_blocks=5)
    r1 = eng.add_request(p1, 4)
    r2 = eng.add_request(p2, 4)   # reuses r1's freed blocks
    res = eng.run()
    clean = mk(params, kv_cache_dtype="int8", max_batch=1,
               num_blocks=5)
    rc = clean.add_request(p2, 4)
    assert clean.run()[rc] == res[r2], (res[r1], res[r2])


# ---------------------------------------------------------------------------
# TP: ragged path + the int8-weight satellite (exact parity)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _mesh4():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:4]), ("mp",))


def test_tp_ragged_matches_generate(params):
    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, CFG.vocab_size, (n,)) for n in (9, 14, 5)]
    news = [6, 4, 8]
    eng = mk(params, mesh=_mesh4())
    rids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
    res = eng.run()
    assert eng.dispatches == eng.engine_steps
    for rid, p, n in zip(rids, prompts, news):
        assert res[rid] == golden(params, p, n), rid


def test_tp_int8_weights_parity_smoke(engines):
    """Fast-tier satellite gate: int8 W8A8 weights under TP reproduce
    the dense int8 engine exactly (one request; the multi-request run
    is in the slow tier)."""
    rng = np.random.RandomState(18)
    prompt = rng.randint(0, CFG.vocab_size, (9,))

    def run(mesh):
        eng = engines(int8=True, mesh=mesh)
        rid = eng.add_request(prompt, 5)
        return eng.run()[rid]

    assert run(None) == run(_mesh4())


def test_tp_int8_weights_match_dense_int8_exactly(engines):
    """Satellite: int8 weights under TP serving — per-output-channel
    scales shard with the weight shards; the row-parallel sites share
    the activation scale (pmax) and psum the INT32 accumulator, so the
    sharded engine reproduces the dense int8 engine EXACTLY."""
    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, CFG.vocab_size, (n,)) for n in (9, 13, 6)]
    news = [6, 5, 7]

    def run(mesh):
        eng = engines(int8=True, mesh=mesh)
        rids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
        res = eng.run()
        return [res[r] for r in rids]

    assert run(None) == run(_mesh4())


def test_tp_int8_kv_pool(params, engines):
    """int8 KV + TP compose on the ragged path (scales head-sharded)."""
    rng = np.random.RandomState(14)
    prompt = rng.randint(0, CFG.vocab_size, (9,))
    dense = engines(kv_cache_dtype="int8")
    rd = dense.add_request(prompt, 6)
    tp = mk(params, kv_cache_dtype="int8", mesh=_mesh4())
    rt = tp.add_request(prompt, 6)
    assert dense.run()[rd] == tp.run()[rt]


# ---------------------------------------------------------------------------
# adaptive prefill/decode mix (telemetry-driven)
# ---------------------------------------------------------------------------
def test_adaptive_mix_shortens_bursts_under_pressure(params, engines):
    rng = np.random.RandomState(15)
    prompts = [rng.randint(0, CFG.vocab_size, (6,)) for _ in range(6)]
    news = [8] * 6

    def mean_burst(adaptive):
        eng = engines(decode_burst=8, adaptive_mix=adaptive)
        micro0, steps0 = eng.decode_microsteps, eng.engine_steps
        rids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
        res = eng.run()
        for rid, p, n in zip(rids, prompts, news):
            assert res[rid] == golden(params, p, n)
        return ((eng.decode_microsteps - micro0)
                / (eng.engine_steps - steps0))

    # queue pressure (6 requests, 2 slots) -> shorter bursts than fixed
    assert mean_burst(True) < mean_burst(False)


def test_adaptive_mix_full_burst_when_idle(params, engines):
    rng = np.random.RandomState(16)
    eng = engines(max_batch=2, decode_burst=8, adaptive_mix=True)
    micro0, steps0 = eng.decode_microsteps, eng.engine_steps
    prompt = rng.randint(0, CFG.vocab_size, (5,))
    rid = eng.add_request(prompt, 9)
    res = eng.run()
    assert res[rid] == golden(params, prompt, 9)
    # after prefill completes the queue is empty -> full bursts ran:
    # 9 tokens in few steps (prefill step + one full burst step)
    assert eng.engine_steps - steps0 <= 3
    assert eng.decode_microsteps - micro0 >= 8


def test_dispatch_metrics_exported(engines):
    rng = np.random.RandomState(17)
    eng = engines()
    eng.add_request(rng.randint(0, CFG.vocab_size, (5,)), 4)
    eng.run()
    text = eng.metrics_text()
    assert "dispatches_per_step" in text
    assert "dispatches_total" not in text   # no reader: gone in PR 38
    assert eng._prom.get("dispatches_per_step") == \
        eng.dispatches / eng.engine_steps


# ---------------------------------------------------------------------------
# `_qkv` (ISSUE 43): the product stays a 2-D GEMM behind a barrier; what it
# returns is what the parent's formula returned
# ---------------------------------------------------------------------------
def parent_qkv(p, x, cfg, mp_axis=None):
    """`serving._qkv` as it stood before the barrier, written out: the
    product reshaped to [B, S, heads, 3, D] and indexed."""
    from paddle_tpu.inference.serving import _mm
    B, S, _ = x.shape
    h = G._ln(x, p["ln1_g"], p["ln1_b"])
    qkv = (_mm(h.astype(cfg.dtype), p, "qkv_w", cfg)
           + p["qkv_b"].astype(cfg.dtype))
    heads = qkv.shape[-1] // (3 * cfg.head_dim)
    qkv = qkv.reshape(B, S, heads, 3, cfg.head_dim)
    return qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]


@pytest.mark.parametrize("how", ["dense", "int8_weights", "mp2"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_qkv_is_the_parents_formula(dtype, how):
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.inference.serving import _qkv, quantize_serving_params
    from paddle_tpu.utils import shard_map
    cfg = dataclasses.replace(CFG, dtype=jnp.dtype(dtype),
                              param_dtype=jnp.dtype(dtype))
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(3))
    # a bias and gains that are not their initial 0 and 1
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    blocks = dict(params["blocks"])
    for k, name in zip(keys, ("qkv_b", "ln1_g", "ln1_b")):
        blocks[name] = (blocks[name] + 0.3 * jax.random.normal(
            k, blocks[name].shape)).astype(blocks[name].dtype)
    params = dict(params, blocks=blocks)
    if how == "int8_weights":
        params = quantize_serving_params(params)
    p = jax.tree.map(lambda a: a[1], params["blocks"])      # one layer
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, cfg.hidden_size),
                          jnp.float32).astype(cfg.dtype)
    want = jax.jit(lambda p, x: parent_qkv(p, x, cfg))(p, x)
    if how == "mp2":
        # column-parallel: each rank holds two whole heads of the four
        mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
        cols = {"qkv_w": P(None, "mp"), "qkv_b": P("mp")}
        heads = P(None, None, "mp", None)
        got = jax.jit(shard_map(
            lambda p, x: _qkv(p, x, cfg, "mp"), mesh=mesh,
            in_specs=({k: cols.get(k, P()) for k in p}, P()),
            out_specs=(heads, heads, heads)))(p, x)
    else:
        got = jax.jit(lambda p, x: _qkv(p, x, cfg))(p, x)
    for name, g, w in zip("qkv", got, want):
        assert g.shape == (1, 24, cfg.num_heads, cfg.head_dim), name
        assert g.dtype == w.dtype == cfg.dtype, name
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32), name)


@pytest.mark.parametrize("int8", [False, True], ids=["dense", "int8_weights"])
def test_served_tokens_are_the_parents(params, monkeypatch, int8):
    """Prompts longer and shorter than a chunk, decode bursts, sampling at
    a temperature from a fixed seed: token for token what the engine
    serves with the parent's formula in `_qkv`'s place. The weights are
    sharpened until the tokens feel their attention: with k and v
    exchanged the same engine serves other tokens."""
    from paddle_tpu.inference import serving
    blocks = dict(params["blocks"])
    blocks["qkv_w"] = blocks["qkv_w"] * 8.0
    blocks["qkv_b"] = 0.5 * jax.random.normal(jax.random.PRNGKey(1),
                                              blocks["qkv_b"].shape)
    sharp = dict(params, blocks=blocks)
    rng = np.random.RandomState(43)
    prompts = [rng.randint(0, CFG.vocab_size, (n,)) for n in (19, 5, 11)]
    news, temps = [9, 12, 7], [0.0, 0.8, 1.3]

    def served():
        eng = mk(sharp, seed=43, decode_burst=4, int8=int8)
        rids = [eng.add_request(p, n, temperature=t)
                for p, n, t in zip(prompts, news, temps)]
        res = eng.run()
        return [res[r] for r in rids]

    def exchanged(p, x, cfg, mp_axis=None):
        q, k, v = parent_qkv(p, x, cfg)
        return q, v, k

    change = served()
    assert [len(o) for o in change] == news
    monkeypatch.setattr(serving, "_qkv", parent_qkv)
    assert served() == change
    monkeypatch.setattr(serving, "_qkv", exchanged)
    assert served() != change
