"""The plain reference against the program's own forward, toy size."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.reference import gpt as R

WIDTHS = {"vocab_size": 1024, "hidden_size": 128, "num_layers": 4,
          "num_heads": 4, "ffn_hidden": 512, "max_seq_len": 256}


def test_reference_block_against_dense_forward_at_gpt_tiny():
    from paddle_tpu.models import gpt as G
    cfg = G.gpt_tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    assert (cfg.vocab_size, cfg.hidden_size, cfg.num_layers, cfg.num_heads,
            cfg.ffn_hidden, cfg.max_seq_len) == tuple(WIDTHS.values())
    params = W.make_params(WIDTHS, 5, jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 1024, (2, 96), dtype=np.int32))
    with jax.default_matmul_precision("highest"):
        want = G.dense_forward(params, tokens, cfg, remat=False)
    got = R.forward(params, tokens, n_heads=4)
    assert got.shape == (2, 96, 1024)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_weights_come_from_the_seed_leaf_by_leaf():
    a = W.make_params(WIDTHS, 2 ** 31 + 9, jnp.bfloat16)
    b = W.make_params(WIDTHS, 2 ** 31 + 9, jnp.bfloat16)
    c = W.make_params(WIDTHS, 10, jnp.bfloat16)
    key = W.seed_key(2 ** 31 + 9)
    for i, (path, shape, mean, std) in enumerate(W.leaf_table(WIDTHS)):
        leaf = W.get(a, path)
        assert leaf.shape == shape and leaf.dtype == jnp.bfloat16
        assert (leaf == W.get(b, path)).all()
        assert not (leaf == W.get(c, path)).all()
        alone = W.make_leaf(key, i, mean, std, shape=shape,
                            dtype=jnp.dtype(jnp.bfloat16))
        assert (leaf == alone).all()
    g = np.asarray(a["blocks"]["ln1_g"], np.float32)
    assert abs(g.mean() - 1.0) < 0.01 and 0.01 < g.std() < 0.03
