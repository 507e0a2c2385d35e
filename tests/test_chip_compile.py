"""Main-path Pallas kernels compile for the real chip, without the chip.

The TPU compiler is installed on chip-less machines and compiles for a
DESCRIBED topology (`v5e:2x2`), so these tests catch what interpret mode
cannot: misaligned slices, VMEM over-use, unpartitionable kernels.

Rules this file follows (on-chip-measurement guide, section 2):

* the topology is described inside the module-scoped ``topo`` fixture —
  never at import, in a ``skipif``/``parametrize`` argument or in
  conftest — so every xdist worker collects the same tests and only the
  worker that runs this file loads the TPU library;
* every compile happens in the test's own process (no children);
* all such tests live in this ONE file;
* kernel modules bind ``interpret`` by name and would lower the
  interpreter under ``JAX_PLATFORMS=cpu``; the ``compiled_kernels``
  fixture steers those module attributes with monkeypatch;
* the persistent compilation cache is off around them (a described-chip
  executable can be written to it but not read back without a chip).

A compile that passes is not a chip run and is never reported as one.
"""

import functools
import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

_KERNEL_MODULES = ("flash_attention", "layer_norm", "rms_norm", "rope",
                   "primitives", "fused_adam", "paged_attention",
                   "ragged_paged_attention", "kv_append", "ssm", "gdn",
                   "moe", "mla_attention", "latent_append")

# a small serving pool's geometry: GPT-1.3B heads, 128-token pages
HEADS, HEAD_DIM, PAGE, NUM_PAGES, PAGES_PER_SEQ = 16, 128, 128, 64, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch, no_persistent_cache):
    """Make every kernel module lower the real kernel, not the
    interpreter (they read ``_interpret()`` at trace time)."""
    for name in _KERNEL_MODULES:
        mod = importlib.import_module(f"paddle_tpu.kernels.pallas.{name}")
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel was not lowered for the chip"
    return compiled, text


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_1p3b(one_chip, compiled_kernels, grad):
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention
    x = _sds(one_chip, (8, 1024, HEADS, HEAD_DIM), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    _, text = _compile(fn, x, x, x)
    # forward alone is one kernel; the backward adds dq and dk/dv passes
    assert text.count("tpu_custom_call") >= (3 if grad else 1)


def test_layer_norm_fwd_bwd(one_chip, compiled_kernels):
    from paddle_tpu.kernels.pallas.layer_norm import layer_norm
    x = _sds(one_chip, (8192, 2048), jnp.bfloat16)
    w = _sds(one_chip, (2048,), jnp.bfloat16)

    def loss(x, w, b):
        return jnp.sum(layer_norm(x, w, b).astype(jnp.float32))

    _, text = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, w, w)
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("stochastic_rounding", [False, True],
                         ids=["nearest", "sr"])
def test_fused_adam_bf16_leaf(one_chip, compiled_kernels,
                              stochastic_rounding):
    from paddle_tpu.kernels.pallas import fused_adam
    leaf = _sds(one_chip, (2048, 8192), jnp.bfloat16)
    scalar = _sds(one_chip, (), jnp.int32)
    assert fused_adam.supported(leaf, leaf, {"moment1": leaf,
                                             "moment2": leaf})

    def update(p, g, m1, m2, step):
        rng = (jax.random.key(step.astype(jnp.uint32), impl="rbg")
               if stochastic_rounding else None)
        return fused_adam.adam_update(
            p, g, {"moment1": m1, "moment2": m2}, 1e-4, step, rng,
            beta1=0.9, beta2=0.999, epsilon=1e-8, decoupled=0.01)

    _compile(update, leaf, leaf, leaf, leaf, scalar)
    new_p, slot = jax.eval_shape(update, leaf, leaf, leaf, leaf, scalar)
    assert new_p.dtype == jnp.bfloat16
    assert slot["moment2"].dtype == jnp.bfloat16


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_ragged_paged_attention(one_chip, compiled_kernels, kv_dtype):
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    rows, chunk = 8, 128
    q = _sds(one_chip, (rows + chunk, HEADS, HEAD_DIM), jnp.bfloat16)
    pool = _sds(one_chip, (HEADS, NUM_PAGES, PAGE, HEAD_DIM),
                jnp.int8 if kv_dtype == "int8" else jnp.bfloat16)
    tables = _sds(one_chip, (rows, PAGES_PER_SEQ), jnp.int32)
    lens = _sds(one_chip, (rows,), jnp.int32)
    scale = HEAD_DIM ** -0.5
    if kv_dtype == "int8":
        scales = _sds(one_chip, (HEADS, NUM_PAGES), jnp.float32)

        def fn(q, kp, vp, tables, starts, q_lens, kv_lens, ks, vs):
            return ragged_paged_attention(q, kp, vp, tables, starts, q_lens,
                                          kv_lens, scale, ks, vs,
                                          c_att=chunk)
        _compile(fn, q, pool, pool, tables, lens, lens, lens, scales, scales)
    else:
        def fn(q, kp, vp, tables, starts, q_lens, kv_lens):
            return ragged_paged_attention(q, kp, vp, tables, starts, q_lens,
                                          kv_lens, scale, c_att=chunk)
        _compile(fn, q, pool, pool, tables, lens, lens, lens)


# the serving cells' attention shapes (PERF.md section 4): rows, packed
# positions, chunk, query heads, the whole pool [L, H_kv, pages, PAGE,
# HEAD_DIM], table width; and a GPT pass on a chip of the 4-way `mesh`
# path, which holds a quarter of the heads (a [4, 128] position is less
# than one bf16 tile)
_CELL_ATTENTION = {
    "gpt-pass1": (64, 192, 128, 16, (24, 16, 256), 16),
    "gpt-burst": (64, 64, 1, 16, (24, 16, 256), 16),
    "gpt-pass1-mp4": (64, 192, 128, 4, (24, 4, 256), 16),
    "falconh1-pass1": (64, 192, 128, 20, (6, 4, 640), 8),
    "falconh1-burst": (64, 64, 1, 20, (6, 4, 640), 8),
    # Trinity-Mini's pass 1 (PR 57): 32 query heads on 4 KV heads fold a
    # chunk of 256 into 2,048 rows a KV head, attended a block of pages an
    # update; the full layer's pool and table, then a window layer's ring
    "trinity-pass1-full": (64, 1088, 256, 32, (1, 4, 4251), 262),
    "trinity-pass1-window": (64, 1088, 256, 32, (4, 4, 1281), 20,
                             {"window": 2048}),
    # Qwen3-Next's: 16 query heads on 2 KV heads of 256
    "q3n-pass1": (64, 192, 128, 16, (1, 2, 640), 8, {"head_dim": 256}),
}


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("cell", sorted(_CELL_ATTENTION))
def test_ragged_paged_attention_at_the_cells_shapes(one_chip,
                                                    compiled_kernels, cell,
                                                    kv_dtype):
    """The kernel as the serving cells call it: the packed queries, the
    whole pool, a traced layer, every KV head of a page in one copy (16 x
    32 KB for GPT, 4 x 32 KB for Falcon-H1 with its 640-row folded query
    tile), the wide arm's blocks of pages (8 MB of block buffers for GPT's
    16 KV heads, Trinity-Mini's 2,048-row folded tile in 256-row
    sub-tiles), a row's own positions copied in and out at any offset. A
    VMEM overrun or a refused slice shows here, without the chip."""
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    (rows, tokens, chunk, hq, (layers, hkv, pages), table,
     *other) = _CELL_ATTENTION[cell]
    other = other[0] if other else {}
    head_dim, window = other.get("head_dim", HEAD_DIM), other.get("window")
    quant = kv_dtype == "int8"
    q = _sds(one_chip, (tokens, hq, head_dim), jnp.bfloat16)
    pool = _sds(one_chip, (layers, hkv, pages, PAGE, head_dim),
                jnp.int8 if quant else jnp.bfloat16)
    scales = (_sds(one_chip, (layers, hkv, pages), jnp.float32)
              if quant else None)
    tables = _sds(one_chip, (rows, table), jnp.int32)
    lens = _sds(one_chip, (rows,), jnp.int32)
    layer = _sds(one_chip, (), jnp.int32)

    def fn(q, kp, vp, tables, starts, q_lens, kv_lens, ks, vs, layer):
        return ragged_paged_attention(q, kp, vp, tables, starts, q_lens,
                                      kv_lens, head_dim ** -0.5, ks, vs,
                                      layer, c_att=chunk, window=window)

    compiled, text = _compile(fn, q, pool, pool, tables, lens, lens, lens,
                              scales, scales, layer)
    assert "ragged_paged_attn" in text
    # the pool is read where it lies: nothing pool-sized is staged
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 26


def test_paged_decode_attention(one_chip, compiled_kernels):
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_decode_attention)
    batch = 8
    q = _sds(one_chip, (batch, HEADS, HEAD_DIM), jnp.bfloat16)
    pool = _sds(one_chip, (HEADS, NUM_PAGES, PAGE, HEAD_DIM), jnp.bfloat16)
    tables = _sds(one_chip, (batch, PAGES_PER_SEQ), jnp.int32)
    lens = _sds(one_chip, (batch,), jnp.int32)

    def fn(q, kp, vp, tables, lens):
        return paged_decode_attention(q, kp, vp, tables, lens,
                                      HEAD_DIM ** -0.5)

    _compile(fn, q, pool, pool, tables, lens)


# ---------------------------------------------------------------------------
# the serving step at the benchmark cells' geometry: the KV pool stays ONE
# buffer, written in place (inference/ragged_step.py states the contract)
# ---------------------------------------------------------------------------
LAYERS, ROWS, TOKENS, TABLE, C_ATT = 24, 64, 192, 16, 128
_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
# what moved a layer's page set or the whole pool in the parent's step
_POOL_MOVERS = {"copy", "copy-start", "dynamic-slice", "dynamic-update-slice"}


def _moved(text, hit, by_shape=False):
    """Copies, slices, updates and loop fusions of the compiled text whose
    result's element count `hit` accepts (with `by_shape`, whose result's
    dimensions, leading 1s dropped: where an activation has as many
    elements as a buffer, the count cannot tell them apart). Views (`bitcast`), the
    while/tuple plumbing, the in-place scatter and the copy-on-write page
    gather (`kCustom` fusions) may carry that size."""
    found = []
    for line in text.splitlines():
        m = _HLO_RESULT.match(line)
        if not m or not m.group(1):
            continue
        n = math.prod(int(d) for d in m.group(1).split(","))
        if by_shape:
            n = _no_leading_ones(int(d) for d in m.group(1).split(","))
        op = m.group(2)
        if hit(n) and (op in _POOL_MOVERS
                       or op == "fusion" and "kind=kCustom" not in line):
            found.append(line.strip()[:160])
    return found


def _no_leading_ones(dims):
    dims = tuple(dims)
    while len(dims) > 1 and dims[0] == 1:
        dims = dims[1:]
    return dims


def _outside_fusions(text):
    """The compiled text without its fused computations: what is left are
    the operations the chip runs one by one (the entry, loop bodies,
    calls). An operand that a GEMM slices out of a stack for itself is an
    instruction of the GEMM's fused computation and is not among them."""
    fused = set(re.findall(r" fusion\([^\n]*calls=%?([\w.\-]+)", text))
    kept, skip = [], False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            skip = line.split()[0].lstrip("%") in fused
        if not skip:
            kept.append(line)
    return "\n".join(kept)


def _weight_copies(text, blocks):
    """{shape: lines} of the results of a block's weight matrix's shape
    outside any fusion's body: the stacked leaf, one run's or one layer's
    share of it (leading 1s dropped; a matrix is a million elements or
    more). A GEMM that takes its layer where it lies in the stack leaves
    none; one whose weight the compiler wants in another layout slices the
    layer out and copies it first, or copies the whole stack once a step
    (PERF.md, PR 43)."""
    shapes = set()
    for leaf in jax.tree.leaves(blocks):
        for i in range(leaf.ndim - 1):
            if leaf.ndim >= 3 and math.prod(leaf.shape[i:]) >= 2 ** 20:
                shapes.add(_no_leading_ones(leaf.shape[i:]))
    assert shapes, "no weight matrix among the blocks"
    outside = _outside_fusions(text)
    found = {s: _moved(outside, {s}.__contains__, by_shape=True)
             for s in shapes}
    return {s: lines for s, lines in found.items() if lines}


_HLO_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


def _query_tiles(text, rows, c_att, heads):
    """Every array of the compiled text, results and operands alike, that
    holds `rows` x `c_att` positions of `heads` query heads: the per-row
    query or output tiles the attention kernel no longer takes
    ([R, c_att, H_q, D], or flat [R * c_att, H_q, D] as the gather made
    them). The minor dimension tells them from a weight of as many
    elements ([2048, 8192] is 64 x 128 x 16 x 128 too)."""
    n = rows * c_att * heads * HEAD_DIM
    found = set()
    for dims in _HLO_SHAPE.findall(text):
        dims = [int(d) for d in dims.split(",")]
        if math.prod(dims) == n and dims[-1] == HEAD_DIM:
            found.add(tuple(dims))
    return sorted(found)


def _pool_copies(text, pages):
    """What moved a whole number of layers' page sets ([H, pages, PAGE, D]:
    the smallest pool-shaped copy the parent made), up to a pool."""
    layer_set = HEADS * pages * PAGE * HEAD_DIM
    return _moved(text, lambda n: n % layer_set == 0
                  and n <= LAYERS * layer_set)


def _expert_tiles(text):
    """{(tile height, tiles of the static grid)} of the compiled step's
    `moe_grouped_ffn` calls, one pair a distinct pass: a call's result is
    the padded buffer f32[G tm, H], its second operand `tile_expert`
    s32[G], G = held experts + ceil((token, pick) pairs / tm)."""
    found = set()
    for line in text.splitlines():
        if "moe_grouped_ffn" in line and "custom-call(" in line:
            rows = int(re.search(r"= f32\[(\d+),\d+\]", line).group(1))
            grid = int(re.search(
                r"operand_layout_constraints=\{s32\[1\]\{0\}, "
                r"s32\[(\d+)\]", line).group(1))
            assert rows % grid == 0, (rows, grid)
            found.add((rows // grid, grid))
    return found


def _gpt_serving_params(one_chip, int8_weights=False):
    """(cfg, params as shapes on the described chip) of GPT-3 1.3B, with
    `int8_weights` as `ServingEngine(int8=True)` holds them."""
    from paddle_tpu.inference.serving import quantize_serving_params
    from paddle_tpu.models import gpt as G
    cfg = G.GPTConfig(vocab_size=50304, hidden_size=HEADS * HEAD_DIM,
                      num_layers=LAYERS, num_heads=HEADS, ffn_hidden=8192,
                      max_seq_len=2048, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16)

    def init():
        params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
        return quantize_serving_params(params) if int8_weights else params

    return cfg, jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                             jax.eval_shape(init))


@functools.lru_cache(maxsize=None)   # two tests read each compiled step
def _compile_unified_step(one_chip, K, kv_dtype, pages, share=False,
                          int8_weights=False):
    """(compiled step, params)."""
    from paddle_tpu.inference import ragged_step as RS
    cfg, params = _gpt_serving_params(one_chip, int8_weights)

    def i32(*shape):
        return _sds(one_chip, shape, jnp.int32)

    def flags():
        return _sds(one_chip, (ROWS,), jnp.bool_)

    quant = kv_dtype == "int8"
    pool = _sds(one_chip, (LAYERS, HEADS, pages, PAGE, HEAD_DIM),
                jnp.int8 if quant else jnp.bfloat16)
    scales = (_sds(one_chip, (LAYERS, HEADS, pages), jnp.float32)
              if quant else None)
    args = [params, i32(TOKENS), i32(TOKENS), i32(TOKENS), i32(ROWS),
            i32(ROWS), i32(ROWS), i32(ROWS, TABLE), flags(), flags(),
            i32(ROWS), i32(ROWS), _sds(one_chip, (ROWS,), jnp.float32),
            i32(ROWS),      # prev_tok: the slots' last tokens, device-kept
            _sds(one_chip, (2,), jnp.uint32), pool, pool, scales, scales]
    if share:   # cow_src, cow_dst, reset_tables
        args += [i32(ROWS), i32(ROWS), i32(ROWS, TABLE)]
    step = functools.partial(RS.unified_step, cfg=cfg, bs=PAGE,
                             c_att=C_ATT, K=K)
    return jax.jit(step, donate_argnums=(15, 16, 17, 18) if quant
                   else (15, 16)).lower(*args).compile(), params


@pytest.mark.parametrize("K,kv_dtype,pages,share", [
    (1, "bf16", 256, False), (8, "bf16", 256, False),
    (1, "int8", 256, False), (8, "int8", 256, False),
    (1, "bf16", 256, True), (8, "bf16", 320, False),
    (1, "int8", 512, False),
], ids=["k1-bf16", "k8-bf16", "k1-int8", "k8-int8", "k1-bf16-cow",
        "k8-bf16-320pages", "k1-int8-512pages"])
def test_unified_step_keeps_pool_in_place(one_chip, compiled_kernels, K,
                                          kv_dtype, pages, share):
    """The guard against the pool copies coming back (PERF.md, PR 27), and
    the per-row query tiles (PR 34):
    the parent's step at this geometry held ten pool-shaped copies,
    slices and updates, 7.0 GiB of temp at K = 1 and 7.6 GiB at K = 8,
    and did not compile at 320 pages. An int8 pool of the same bytes
    (512 pages) has to fit too: its scales go to SMEM a layer at a time."""
    compiled, _ = _compile_unified_step(one_chip, K, kv_dtype, pages, share)
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel was not lowered for the chip"
    assert _pool_copies(text, pages) == []
    # attention reads and writes the packed [T, H_q, D] buffer (PERF.md,
    # PR 34): the parent's step gathered it to [64 x 128, 16, 128] a layer,
    # the kernel moved a 0.5 MB tile in and out for each of the 64 rows,
    # and a second gather picked 192 positions back. No such array is left
    # — and the reader finds one where there is one: the packed buffer
    # itself, and the tile of K and V the quantized append still takes
    assert _query_tiles(text, 1, TOKENS, HEADS) != []
    tiles = _query_tiles(text, ROWS, C_ATT, HEADS)
    assert (tiles != []) == (kv_dtype == "int8"), tiles
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 30, mem.temp_size_in_bytes
    # both pools (and scales) alias their arguments: donated in, out in place
    item = 1 if kv_dtype == "int8" else 2
    pool_bytes = LAYERS * HEADS * pages * PAGE * HEAD_DIM * item
    assert mem.alias_size_in_bytes >= 2 * pool_bytes


# ---------------------------------------------------------------------------
# Falcon-H1-34B's serving step at its cell's geometry (ISSUE 28): six
# layers at the published widths, 64 slots, 640 pages of 4 KV heads, and
# the recurrent state [6, 64, 32, 128, 256] f32 (1.6 GB) beside them.
H1_LAYERS, H1_ROWS, H1_PAGES, H1_TABLE = 6, 64, 640, 8


def _state_copies(text, shapes):
    """What moved a buffer of one of the `shapes`' sizes: the state or the
    pool, one layer's or all."""
    return _moved(text, {math.prod(s) for s in shapes}.__contains__)


@functools.lru_cache(maxsize=None)   # two tests read each compiled step
def _compile_falcon_h1_step(one_chip, K):
    """(compiled step, params, cfg, pool, state and tail shapes)."""
    from paddle_tpu.inference import ragged_step as RS
    from paddle_tpu.models import falcon_h1 as FH
    cfg = FH.FalconH1Config(num_layers=H1_LAYERS)
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: FH.init_params(cfg, jax.random.PRNGKey(0))))
    tokens = H1_ROWS + cfg.ssm_chunk

    def i32(*shape):
        return _sds(one_chip, shape, jnp.int32)

    def flags():
        return _sds(one_chip, (H1_ROWS,), jnp.bool_)

    pool_shape = (H1_LAYERS, cfg.num_kv_heads, H1_PAGES, PAGE, cfg.head_dim)
    state_shape, tail_shape = FH.state_shapes(cfg, H1_ROWS)
    pool = _sds(one_chip, pool_shape, jnp.bfloat16)
    args = [params, i32(tokens), i32(tokens), i32(tokens), i32(H1_ROWS),
            i32(H1_ROWS), i32(H1_ROWS), i32(H1_ROWS, H1_TABLE), flags(),
            flags(), i32(H1_ROWS), i32(H1_ROWS),
            _sds(one_chip, (H1_ROWS,), jnp.float32), i32(H1_ROWS),
            _sds(one_chip, (2,), jnp.uint32), pool, pool, None, None, None,
            None, None, _sds(one_chip, state_shape, jnp.float32),
            _sds(one_chip, tail_shape, jnp.bfloat16)]
    step = functools.partial(RS.unified_step, cfg=cfg, bs=PAGE,
                             c_att=cfg.ssm_chunk, K=K)
    compiled = jax.jit(step, donate_argnums=(15, 16, 22, 23)
                       ).lower(*args).compile()
    return compiled, params, cfg, pool_shape, state_shape, tail_shape


@pytest.mark.parametrize("K", [1, 8], ids=["k1", "k8"])
def test_falcon_h1_step_keeps_state_and_pool_in_place(one_chip,
                                                      compiled_kernels, K):
    """The state's contract is the pool's: one donated buffer each for the
    recurrent state and the conv tail, on the scans' carry, written only
    by the mixer's kernels. No state-sized or pool-sized copy, slice or
    update outside them (one layer's slots or all six), temp under 1 GiB,
    and everything donated comes back aliased."""
    compiled, _, cfg, pool_shape, state_shape, tail_shape = (
        _compile_falcon_h1_step(one_chip, K))
    tokens = H1_ROWS + cfg.ssm_chunk
    text = compiled.as_text()
    for kernel in ("ssm_conv", "ssm_chunk_scan", "ragged_paged_attn",
                   "kv_append") + (("ssm_state_update",) if K > 1 else ()):
        assert kernel in text, f"{kernel} was not lowered for the chip"
    assert _state_copies(text, [state_shape, state_shape[1:], pool_shape,
                                pool_shape[1:], tail_shape]) == []
    # nor a tile of queries a row: 64 x 128 positions of 20 heads (the
    # packed buffer, padded to 24 heads for the kernel's copies, is there)
    assert _query_tiles(text, H1_ROWS, cfg.ssm_chunk, cfg.num_heads) == []
    assert _query_tiles(text, 1, tokens, 24) != []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 30, mem.temp_size_in_bytes
    donated = (2 * math.prod(pool_shape) * 2 + math.prod(state_shape) * 4
               + math.prod(tail_shape) * 2)
    assert mem.alias_size_in_bytes >= donated


# ---------------------------------------------------------------------------
# Qwen3-Next-80B-A3B's serving step at its cell's geometry (ISSUE 36): one
# period of the layer pattern (three Gated DeltaNet layers, one gated
# attention layer) at the published widths, 256 of 512 experts held, 64
# slots; the pool holds ONE layer [1, 2, 640, 128, 256] (D = 256), the
# state three [3, 64, 32, 128, 128] f32, and the run's stacked expert
# weights (1.6 GB a layer) are taken whole like the pool.
Q3N_ROWS, Q3N_PAGES, Q3N_TABLE = 64, 640, 8


@functools.lru_cache(maxsize=None)   # two tests read each compiled step
def _compile_qwen3_next_step(one_chip, K):
    """(compiled step, params, pool, state and tail shapes)."""
    from paddle_tpu.inference import ragged_step as RS
    from paddle_tpu.models import qwen3_next as QN
    cfg = QN.Qwen3NextConfig(vocab_size=75968, num_layers=4,
                             experts_held=(0, 256))
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: QN.init_params(cfg, jax.random.PRNGKey(0))))
    tokens = Q3N_ROWS + cfg.ssm_chunk

    def i32(*shape):
        return _sds(one_chip, shape, jnp.int32)

    def flags():
        return _sds(one_chip, (Q3N_ROWS,), jnp.bool_)

    pool_shape = (1, cfg.num_kv_heads, Q3N_PAGES, PAGE, cfg.head_dim)
    state_shape, tail_shape = QN.state_shapes(cfg, Q3N_ROWS)
    pool = _sds(one_chip, pool_shape, jnp.bfloat16)
    args = [params, i32(tokens), i32(tokens), i32(tokens), i32(Q3N_ROWS),
            i32(Q3N_ROWS), i32(Q3N_ROWS), i32(Q3N_ROWS, Q3N_TABLE), flags(),
            flags(), i32(Q3N_ROWS), i32(Q3N_ROWS),
            _sds(one_chip, (Q3N_ROWS,), jnp.float32), i32(Q3N_ROWS),
            _sds(one_chip, (2,), jnp.uint32), pool, pool, None, None, None,
            None, None, _sds(one_chip, state_shape, jnp.float32),
            _sds(one_chip, tail_shape, jnp.bfloat16)]
    step = functools.partial(RS.unified_step, cfg=cfg, bs=PAGE,
                             c_att=cfg.ssm_chunk, K=K)
    compiled = jax.jit(step, donate_argnums=(15, 16, 22, 23)
                       ).lower(*args).compile()
    return compiled, params, pool_shape, state_shape, tail_shape


@pytest.mark.parametrize("K", [1, 8], ids=["q3n-pass1", "q3n-burst"])
def test_qwen3_next_step_keeps_state_pool_and_experts_in_place(
        one_chip, compiled_kernels, K):
    """The period scan keeps the contract: no copy, slice or update the
    size of the state (one layer's slots or all three), of the pool, of
    the conv tail or of a layer's expert matrices; temp under 1 GiB;
    everything donated comes back aliased; `ragged_paged_attn` and
    `kv_append` lower at D = 256 with 2 KV heads."""
    compiled, _, pool_shape, state_shape, tail_shape = (
        _compile_qwen3_next_step(one_chip, K))
    assert state_shape == (3, 64, 32, 128, 128)
    assert tail_shape == (3, 3, 64, 8192)
    text = compiled.as_text()
    for kernel in ("ssm_conv", "gdn_chunk_scan", "moe_grouped_ffn",
                   "ragged_paged_attn", "kv_append") + (
                       ("gdn_state_update",) if K > 1 else ()):
        assert kernel in text, f"{kernel} was not lowered for the chip"
    # by shape: the chunk scan's [64, 128, 32, 128] tiles have as many
    # elements as a layer's state, the packed [192, 16, 512] queries as a
    # layer's conv tail. A layer's expert matrices: [256, 2048, 512]
    # (gate, up), [256, 512, 2048] (down); a run's: three of them
    buffers = [state_shape, state_shape[1:], pool_shape, tail_shape,
               tail_shape[1:]]
    for expert in ((256, 2048, 512), (256, 512, 2048)):
        buffers += [expert, (3,) + expert]
    assert _moved(text, {_no_leading_ones(b) for b in buffers}.__contains__,
                  by_shape=True) == []
    # a pass gives an expert 1.25-3.75 rows: tiles of 16 (192 and 64
    # tokens x top-10 over the 256 held of 512)
    assert _expert_tiles(text) == {(16, 256 + 120)} | (
        {(16, 256 + 40)} if K > 1 else set())
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 30, mem.temp_size_in_bytes
    donated = (2 * math.prod(pool_shape) * 2 + math.prod(state_shape) * 4
               + math.prod(tail_shape) * 2)
    assert mem.alias_size_in_bytes >= donated


# ---------------------------------------------------------------------------
# the DeepSeek-V2 cell's step (ISSUE 51): published widths, layer 0 dense +
# layers 1-5 with group 0's 20 experts, an eighth of the vocabulary, 64
# slots, 2,816 latent pages, tables of 264, chunks of 256, prefix sharing on
# ---------------------------------------------------------------------------
DSV2_ROWS, DSV2_PAGES, DSV2_TABLE, DSV2_CHUNK = 64, 2816, 264, 256


def _compile_deepseek_v2_step(one_chip, K):
    """(compiled step, params, the two pools' shapes)."""
    from paddle_tpu.inference import ragged_step as RS
    from paddle_tpu.models import deepseek_v2 as DS
    cfg = DS.DeepseekV2Config(vocab_size=12800, num_layers=6,
                              experts_held=(0, 20))
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: DS.init_params(cfg, jax.random.PRNGKey(0))))
    R, tokens = DSV2_ROWS, DSV2_ROWS + DSV2_CHUNK

    def i32(*shape):
        return _sds(one_chip, shape, jnp.int32)

    def flags():
        return _sds(one_chip, (R,), jnp.bool_)

    shapes = [(6, h, DSV2_PAGES, PAGE, d)
              for h, d in DS.Serving.pool_shapes(cfg)]
    pools = [_sds(one_chip, shape, jnp.bfloat16) for shape in shapes]
    args = [params, i32(tokens), i32(tokens), i32(tokens), i32(R), i32(R),
            i32(R), i32(R, DSV2_TABLE), flags(), flags(), i32(R), i32(R),
            _sds(one_chip, (R,), jnp.float32), i32(R),
            _sds(one_chip, (2,), jnp.uint32), *pools, None, None,
            i32(R), i32(R), i32(R, DSV2_TABLE)]     # the copy-on-write's
    step = functools.partial(RS.unified_step, cfg=cfg, bs=PAGE,
                             c_att=DSV2_CHUNK, K=K)
    compiled = jax.jit(step, donate_argnums=(15, 16)).lower(*args).compile()
    return compiled, params, shapes


# what the step is KNOWN to move of a weight's shape, as found (PERF.md
# section 7, PR 51), for the PR that repairs it to empty and so that nothing
# joins it unseen. A layer's W_UK [128, 128, 512] and W_UV [128, 512, 128]
# are NOT in it: the batched products that absorb them slice their layer out
# of the stack inside their own fusions. At K > 1 the stacks of the two
# matrices whose width is no whole lane tile (W_DKV's 576, the router's 160)
# are copied once a step, before the loop over the K passes: 37.7 MB read
# and written, ~0.09 ms of a step of 8 passes at the chip's 819 GB/s
_DSV2_KNOWN = {1: set(), 8: {(5, 1, 5120, 576), (5, 1, 5120, 160)}}


@pytest.mark.parametrize("K", [1, 8], ids=["dsv2-pass1", "dsv2-burst"])
def test_deepseek_v2_step_keeps_pool_and_experts_in_place(
        one_chip, compiled_kernels, K):
    """The prologue and the period scan keep the contract on the latent
    pools: no copy, slice or update the size of a pool (all layers' or one
    layer's pages) or of a layer's expert matrices; temp under 1 GiB;
    both pools come back aliased; `mla_paged_attn`, `latent_append` and
    the width-tiled `moe_grouped_ffn` lower at the published widths. A
    2-D GEMM takes its layer's weight where it lies (the barrier of
    ISSUE 43 around W_UQ's and W_DKV's products), W_UK and W_UV where
    they lie too; what is still moved of a weight's shape is
    `_DSV2_KNOWN`, no more and no less."""
    compiled, params, shapes = _compile_deepseek_v2_step(one_chip, K)
    assert shapes == [(6, 1, 2816, 128, 512), (6, 1, 2816, 128, 128)]
    text = compiled.as_text()
    for kernel in ("mla_paged_attn", "latent_append", "moe_grouped_ffn"):
        assert kernel in text, f"{kernel} was not lowered for the chip"
    buffers = [s[i:] for s in shapes for i in (0, 2)]
    for expert in ((20, 5120, 1536), (20, 1536, 5120)):
        buffers += [expert, (5,) + expert]
    assert _moved(text, {_no_leading_ones(b) for b in buffers}.__contains__,
                  by_shape=True) == []
    found = _weight_copies(text, (params["blocks"], params["prologue"]))
    assert set(found) == _DSV2_KNOWN[K], found
    # a pass gives an expert 2.4-12 rows: tiles of 16 (320 and 64 tokens
    # x top-6 over the 20 held of 160)
    assert _expert_tiles(text) == {(16, 20 + 120)} | (
        {(16, 20 + 24)} if K > 1 else set())
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 30, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= sum(2 * math.prod(s) for s in shapes)


# ---------------------------------------------------------------------------
# the Trinity-Mini cell's step (ISSUE 54): published widths, one dense window
# layer + window, window, window, full, all 128 experts and the whole
# vocabulary, 64 slots, 4,251 full-lifetime pages with tables of 262, 1,281
# window-lifetime pages with rings of 20, chunks of 256 under a budget of
# 1,088 packed tokens
# ---------------------------------------------------------------------------
TRIN_ROWS, TRIN_TOKENS, TRIN_CHUNK = 64, 1088, 256
TRIN_PAGES, TRIN_TABLE, TRIN_WPAGES, TRIN_RING = 4251, 262, 1281, 20


def _compile_trinity_step(one_chip, K):
    """(compiled step, params, the two lifetimes' pool shapes)."""
    from paddle_tpu.inference import ragged_step as RS
    from paddle_tpu.models import trinity_mini as TM
    cfg = TM.TrinityMiniConfig(num_layers=5, num_dense_layers=1)
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: TM.init_params(cfg, jax.random.PRNGKey(0))))
    R, tokens = TRIN_ROWS, TRIN_TOKENS

    def i32(*shape):
        return _sds(one_chip, shape, jnp.int32)

    def flags():
        return _sds(one_chip, (R,), jnp.bool_)

    shapes = [(1, cfg.num_kv_heads, TRIN_PAGES, PAGE, cfg.head_dim),
              (4, cfg.num_kv_heads, TRIN_WPAGES, PAGE, cfg.head_dim)]
    full, win = (_sds(one_chip, shape, jnp.bfloat16) for shape in shapes)
    args = [params, i32(tokens), i32(tokens), i32(tokens), i32(R), i32(R),
            i32(R), i32(R, TRIN_TABLE), flags(), flags(), i32(R), i32(R),
            _sds(one_chip, (R,), jnp.float32), i32(R),
            _sds(one_chip, (2,), jnp.uint32), full, full] + [None] * 7 + [
            i32(R, TRIN_RING), win, win]
    step = functools.partial(RS.unified_step, cfg=cfg, bs=PAGE,
                             c_att=TRIN_CHUNK, K=K)
    compiled = jax.jit(step, donate_argnums=(15, 16, 25, 26)
                       ).lower(*args).compile()
    return compiled, params, shapes


@pytest.mark.parametrize("K", [1, 8], ids=["trin-pass1", "trin-burst"])
def test_trinity_step_keeps_both_lifetimes_pools_and_experts_in_place(
        one_chip, compiled_kernels, K):
    """The prologue and the period scan keep the contract on BOTH
    lifetimes' pools: no copy, slice or update the size of a pool (all its
    layers' or one layer's pages) or of a layer's expert matrices; temp
    under 1 GiB; all four pools come back aliased; `ragged_paged_attn`
    lowers under a window (a ring table of 20) and without one, and
    `moe_grouped_ffn` with an expert's whole width of 1,024 in one
    block, in tiles of 64 rows where pass 1 gives an expert 68 (1,088
    tokens x top-8 over 128) and of 16 where a burst pass gives it 4."""
    compiled, params, shapes = _compile_trinity_step(one_chip, K)
    text = compiled.as_text()
    for kernel in ("ragged_paged_attn", "kv_append", "moe_grouped_ffn"):
        assert kernel in text, f"{kernel} was not lowered for the chip"
    assert _expert_tiles(text) == {(64, 128 + 136)} | (
        {(16, 128 + 32)} if K > 1 else set())
    buffers = [s[i:] for s in shapes for i in (0, 1)]
    for expert in ((128, 2048, 1024), (128, 1024, 2048)):
        buffers += [expert, (3,) + expert]
    assert _moved(text, {_no_leading_ones(b) for b in buffers}.__contains__,
                  by_shape=True) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 30, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= sum(2 * math.prod(s) * 2
                                          for s in shapes)


# ---------------------------------------------------------------------------
# every serving step above: a GEMM takes its layer's weight where it lies in
# the scan's stack (ISSUE 43)
# ---------------------------------------------------------------------------
# case -> (compile helper, its arguments, the K = 8 program's temp bound in
# MiB or None, the weight shapes the step is KNOWN to move). The parent's
# GPT K = 8 step held a re-laid copy of the whole qkv stack in its temp
# (576.0 of 579.1 MiB); the rehearsal reads 2.0 and 2.3 MiB now, the bounds
# are twice that (an int8 POOL's requantizing tiles are 391 MiB: no bound
# there). Falcon-H1's and Qwen3-Next's seams still have what GPT's had:
# recorded here as found (PERF.md section 7, PR 43), for the PR that repairs
# them to empty, and so that no further weight joins them unseen
_H1_Q, _H1_IN = (5120, 2560), (5120, 9248)          # q_w, the mixer's in_w
_Q3N_QKVZ, _Q3N_Q, _Q3N_K = (2048, 12288), (2048, 8192), (2048, 512)
_GPT, _H1, _Q3N = (_compile_unified_step, _compile_falcon_h1_step,
                   _compile_qwen3_next_step)
_WEIGHT_STEPS = {
    "gpt-k1-bf16": (_GPT, (1, "bf16", 256), None, set()),
    "gpt-k8-bf16": (_GPT, (8, "bf16", 256), 4, set()),
    "gpt-k1-int8": (_GPT, (1, "int8", 256), None, set()),
    "gpt-k8-int8": (_GPT, (8, "int8", 256), None, set()),
    "gpt-k1-int8-weights": (_GPT, (1, "bf16", 256, False, True), None,
                            set()),
    "gpt-k8-int8-weights": (_GPT, (8, "bf16", 256, False, True), 5, set()),
    "falcon-h1-k1": (_H1, (1,), None, {_H1_Q}),
    "falcon-h1-k8": (_H1, (8,), None, {_H1_Q, (6,) + _H1_Q, (6,) + _H1_IN}),
    "qwen3-next-k1": (_Q3N, (1,), None,
                      {_Q3N_QKVZ, (3,) + _Q3N_QKVZ, (512, 2048)}),
    "qwen3-next-k8": (_Q3N, (8,), None,
                      {_Q3N_QKVZ, (3,) + _Q3N_QKVZ, (512, 2048), _Q3N_Q,
                       _Q3N_K}),
}


@pytest.mark.parametrize("case", sorted(_WEIGHT_STEPS))
def test_serving_step_takes_block_weights_in_place(one_chip,
                                                   compiled_kernels, case):
    """No operation of its own has a result of a block weight's shape: the
    parent's GPT step sliced `[1,2048,6144]` out of `qkv_w` and copied it
    to the layout of a product the compiler had folded `_qkv`'s reshape
    into, a layer at a time (K = 1: 12% of the docs cell's device time) or
    the whole `[24,2048,6144]` stack once a step (K > 1: 0.57 GiB of
    temp). The other three GEMMs of a block never did."""
    build, args, temp_mib, known = _WEIGHT_STEPS[case]
    compiled, params = build(one_chip, *args)[:2]
    found = _weight_copies(compiled.as_text(), params["blocks"])
    assert set(found) == known, found
    if temp_mib is not None:
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < temp_mib * 2 ** 20, temp


# ---------------------------------------------------------------------------
# every serving step above with an unquantized pool: the append walks the
# tiles its work list COUNTS (ISSUE 48)
# ---------------------------------------------------------------------------
# case -> (compile helper, its arguments, W of each pass's work list: the
# bound on the tiles R rows of at most c_att of the pass's T tokens touch)
_APPEND_STEPS = {
    "gpt-k1": (_GPT, (1, "bf16", 256), {132}),
    "gpt-k8": (_GPT, (8, "bf16", 256), {132, 64}),
    "falcon-h1-k1": (_H1, (1,), {132}),
    "falcon-h1-k8": (_H1, (8,), {132, 64}),
    "q3n-pass1": (_Q3N, (1,), {132}),
    "q3n-burst": (_Q3N, (8,), {132, 64}),
}


@pytest.mark.parametrize("case", sorted(_APPEND_STEPS))
def test_kv_append_takes_its_trip_count_as_an_operand(one_chip,
                                                      compiled_kernels,
                                                      case):
    """Every `kv_append` of the compiled step takes, before its five
    vectors of the bound's length W, the layer and the COUNT of the tiles
    the pass writes, and hands the pools back aliased. The parent's call
    had no count: it walked a grid of W = 132 (pass 1) or 64 (a burst
    pass) steps whatever the pass wrote."""
    build, args, bounds = _APPEND_STEPS[case]
    text = build(one_chip, *args)[0].as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "/kv_append/" in line]
    assert calls, "kv_append was not lowered for the chip"
    seen = set()
    for line in calls:
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                             line).group(1)
        ints = re.findall(r"s32\[(\d+)\]", operands)
        assert ints[:2] == ["1", "1"] and ints[2:] == [ints[2]] * 5, ints
        seen.add(int(ints[2]))
        assert "output_to_operand_aliasing={{0}: (8, {}), {1}: (9, {})}" \
            in line
    assert seen == bounds


# ---------------------------------------------------------------------------
# the hybrid training cell's step (GPT-3 6.7B widths, six layers, dp 2 x
# pp 1 x mp 2, two microbatches): ONE pipeline stage is no pipeline
# ---------------------------------------------------------------------------
def _gemm_fusions(text):
    """The fusions of a compiled TPU program that hold a GEMM, by
    instruction name (a loop's body is in the text once, however often it
    runs; a clone the compiler makes to re-materialise is `.rematN`)."""
    bodies, name = set(), None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[0].lstrip("%")
        elif " convolution(" in line:
            bodies.add(name)
    return [inst for inst, callee in re.findall(
        r"%?([\w.\-]+) = [^\n]* fusion\([^\n]*calls=%?([\w.\-]+)", text)
        if callee in bodies]


def _stacked_gradient_gemms(text):
    """{fused computation: it holds an add} of the GEMM fusions that write
    a layer's weight gradient into its stacked `bf16[6, ., .]` buffer."""
    out, name, body = {}, None, []
    for line in text.splitlines() + ["}"]:
        if line.endswith("{") and not line.startswith(" "):
            name, body = line.split()[0].lstrip("%"), []
        elif line.startswith("}") and name:
            joined = "\n".join(body)
            if (" convolution(" in joined and re.search(
                    r"ROOT \S+ = bf16\[6,\d+,\d+\]\S* dynamic-update-slice\(",
                    joined)):
                out[name] = " add(" in joined
            name = None
        elif name:
            body.append(line)
    return out


def _by_computation(text, entry):
    """The instructions of ENTRY (`entry`), or of every other computation
    (the loops' bodies and the fused computations)."""
    out, inside = [], False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            inside = line.startswith("ENTRY")
        elif inside == entry:
            out.append(line)
    return out


def test_hybrid_cell_step_runs_no_pass_twice(topo, compiled_kernels,
                                             monkeypatch):
    """The compiled step of `train-6p7b-dp2mp2` runs no GEMM, kernel or
    collective twice: fifteen GEMMs in its text (qkv, proj, fc1, fc2 and
    the head, each once forward and twice backward; the pipeline form had
    nineteen with its stage replay, and the compiler added the head's
    three more times when the loss sat in a loop's body), the flash
    forward kernel once, no `collective-permute`, and one dp all-reduce of
    the stacked gradients after the microbatch scan, not one a microbatch.
    Since PR 47 a microbatch's gradient joins the sum where the backward
    makes it: the four weight-gradient GEMMs that write a layer into a
    stacked buffer add to what the buffer holds, divided by the dp ranks
    already, and no loop body copies a whole stack or makes a pass over
    one (the parent added on the microbatch scan's carry and divided
    behind the all-reduce). No collective is asynchronous: nothing asks the
    compiler for a switch (with the two that make an all-reduce
    asynchronous the compiler clones GEMMs as carriers and the step loses
    3.5% on the chip, PERF.md PR 47). And the summed stack and a
    microbatch's gradient are ONE buffer (PR 47). Since PR 53 the step,
    built as the cell builds it (every default), OWNS its state: all of
    the argument state aliases an output (4.575 GiB a chip), and no
    parameter or moment is copied whole in front of `fused_adam`, which
    updates a leaf in place (without donation the compiler copied the 17
    stacks and the `wte` leaf, 3.98 GiB a chip and a step, PERF.md PR 53).
    Arguments + temp are 8.13 GiB where arguments + outputs + temp were
    11.76 (temp alone reads 3.55 with aliasing, 2.61 without: the sum is
    what fell)."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.models import gpt as G
    from paddle_tpu.ops import registry
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    cfg = G.GPTConfig(vocab_size=50304, hidden_size=4096, num_layers=6,
                      num_heads=32, ffn_hidden=16384, max_seq_len=2048,
                      dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    mesh = dist.build_mesh({"dp": 2, "pp": 1, "mp": 2},
                           devices=list(topo.devices))
    opt = paddle.optimizer.AdamW(1e-4, moment_dtype=jnp.bfloat16)
    step, _, init = G.build_hybrid_train_step(cfg, mesh, opt,
                                              num_microbatches=2)

    def sharded(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs)
    ex = jax.eval_shape(
        lambda: G.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((4, 2048), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp")))
    lr = jax.ShapeDtypeStruct((), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    compiled = step.lower(sharded(ex, init.param_specs),
                          sharded(init.abstract(ex), init.state_specs),
                          tok, tok, lr).compile()
    text = compiled.as_text()
    assert len(_gemm_fusions(text)) == 3 * 5
    assert len(re.findall(r"%flash_fwd[.\d]* = ", text)) == 1
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text
    assert "collective-permute" not in text
    assert "async-collective" not in text
    # four mp all-reduces of [1, 2048, 4096] a block pass (two forward,
    # two backward), two around the embedding and the head
    assert len(re.findall(r"= bf16\[1,2048,4096\]\S* all-reduce", text)) == 6
    # the stacked fc1 gradient, [6, 4096, 8192] a chip (the compiler may
    # read it as [6 x 4096, 8192]), is reduced over dp once a step (1.64 GB
    # of gradients on the wire, as the parent's)
    stacked = [l for l in text.splitlines() if re.search(
        r"= bf16\[(6,4096|24576),8192\]\S* all-reduce", l)]
    assert len(stacked) == 1, stacked
    # fc1, fc2, proj and qkv write a layer of their stacked gradient once,
    # adding to the sum as they write
    gemms = _stacked_gradient_gemms(text)
    assert list(gemms.values()) == [True] * 4, gemms
    in_a_loop = [l for l in _by_computation(text, entry=False) if re.search(
        r"= bf16\[6,\d{4},\d{4}\]\S* (copy\(|fusion\(.*kind=kLoop)", l)]
    assert not in_a_loop, in_a_loop
    # the step owns its state: every argument but the batch and the
    # learning rate (a dp rank's tokens and labels, [2, 2048] int32 each,
    # and a scalar) aliases an output, and no entry parameter that is a
    # layer stack or the `wte` leaf is copied
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= ma.argument_size_in_bytes - 64 * 1024
    assert ma.alias_size_in_bytes > 4.5 * 2 ** 30
    whole_leaves = [l for l in _by_computation(text, entry=True) if re.search(
        r"= bf16\[(6,\d+,\d+|25152,4096)\]\S* copy\(%param", l)]
    assert not whole_leaves, whole_leaves
    assert (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            < 8.5 * 2 ** 30)


# ---------------------------------------------------------------------------
# the dense training cell's step (GPT-3 1.3B, 4 x 2048, one chip, donated)
# ---------------------------------------------------------------------------
def test_dense_cell_step_runs_flash_forward_once(one_chip, compiled_kernels,
                                                 monkeypatch):
    """The compiled step of `train-1p3b-seq2k` (the runner's `jit` of
    `value_and_grad(G.dense_loss)` + `AdamW.apply`, every default) runs
    flash attention's forward kernel ONCE a layer: a block keeps the
    kernel's `(out, lse)` beside `qkv`, so the backward kernels start from
    what was saved. Seventeen GEMMs in its text: the fifteen a step needs
    (qkv, proj, fc1, fc2 and the head, each once forward and twice
    backward) plus the replays of fc1 and proj under the block's
    checkpoint, which memory does not allow keeping yet at four rows (the
    `[24,4,2048,8192]` fc1 stack is 3 GiB: "Used 17.62G of 15.75G hbm";
    keeping `proj` alone makes the compiler clone the fc1 replay as
    `.rematN`, PERF.md PR 39). No GEMM may sit in such a clone."""
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt as G
    from paddle_tpu.ops import registry
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    cfg = G.GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                      num_heads=16, ffn_hidden=8192, max_seq_len=2048,
                      dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    opt = paddle.optimizer.AdamW(1e-4, moment_dtype=jnp.bfloat16)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: G.dense_loss(p, tokens, labels, cfg))(params)
        params, state = opt.apply(params, grads, state, 1e-4)
        return params, state, loss

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)
    params = jax.eval_shape(
        lambda: G.init_hybrid_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(opt.init_state, params)
    tok = _sds(one_chip, (4, 2048), jnp.int32)
    text = step.lower(on_chip(params), on_chip(state), tok,
                      tok).compile().as_text()
    assert len(re.findall(r"%flash_fwd[.\d]* = ", text)) == 1
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text
    gemms = _gemm_fusions(text)
    assert len(gemms) == 3 * 5 + 2, gemms
    assert not [g for g in gemms if ".remat" in g], gemms
