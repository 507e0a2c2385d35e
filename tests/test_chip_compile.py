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

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

_KERNEL_MODULES = ("flash_attention", "layer_norm", "rms_norm", "rope",
                   "primitives", "fused_adam", "paged_attention",
                   "ragged_paged_attention")

# the serving smoke's pool geometry (chip_smoke.py): GPT-1.3B heads,
# 128-token pages
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
    q = _sds(one_chip, (rows, chunk, HEADS, HEAD_DIM), jnp.bfloat16)
    pool = _sds(one_chip, (HEADS, NUM_PAGES, PAGE, HEAD_DIM),
                jnp.int8 if kv_dtype == "int8" else jnp.bfloat16)
    tables = _sds(one_chip, (rows, PAGES_PER_SEQ), jnp.int32)
    lens = _sds(one_chip, (rows,), jnp.int32)
    scale = HEAD_DIM ** -0.5
    if kv_dtype == "int8":
        scales = _sds(one_chip, (HEADS, NUM_PAGES), jnp.float32)

        def fn(q, kp, vp, tables, q_lens, kv_lens, ks, vs):
            return ragged_paged_attention(q, kp, vp, tables, q_lens,
                                          kv_lens, scale, ks, vs)
        _compile(fn, q, pool, pool, tables, lens, lens, scales, scales)
    else:
        def fn(q, kp, vp, tables, q_lens, kv_lens):
            return ragged_paged_attention(q, kp, vp, tables, q_lens,
                                          kv_lens, scale)
        _compile(fn, q, pool, pool, tables, lens, lens)


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
