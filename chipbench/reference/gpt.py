"""Plain reference of the GPT-3 block (Brown et al. 2020): float32
`jax.numpy`, matrix products at `highest` precision, no kernels, no cache,
no batching tricks, and nothing imported from the program.

Pre-LayerNorm block with learned positions, biases everywhere, tanh GELU
and an untied output head. The fused QKV projection is head-major: its 3H
output channels are [head, (q|k|v), head_dim], the layout the configuration
files state (`qkv_layout`). Parameters may arrive in any storage type; they
are widened to float32 here, which is exact.
"""

import functools
import math

import jax
import jax.numpy as jnp

EPS = 1e-5
_F32 = jnp.float32


def highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def layer_norm(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * g.astype(_F32) + b.astype(_F32)


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(q, k, v):
    """Causal softmax attention, [B, S, heads, D]."""
    S, D = q.shape[1], q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


@highest
def block(p, x, n_heads):
    """One layer on its own (unstacked) parameters; x is [B, S, H] float32."""
    p = {k: v.astype(_F32) for k, v in p.items()}
    B, S, H = x.shape
    h = layer_norm(x, p["ln1_g"], p["ln1_b"])
    qkv = (h @ p["qkv_w"] + p["qkv_b"]).reshape(B, S, n_heads, 3,
                                                 H // n_heads)
    a = attention(qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2])
    x = x + a.reshape(B, S, H) @ p["proj_w"] + p["proj_b"]
    h = layer_norm(x, p["ln2_g"], p["ln2_b"])
    return x + gelu(h @ p["fc1_w"] + p["fc1_b"]) @ p["fc2_w"] + p["fc2_b"]


def embed(p, tokens):
    S = tokens.shape[1]
    return (jnp.take(p["wte"], tokens, axis=0).astype(_F32)
            + p["wpe"][:S].astype(_F32)[None])


@highest
def head_logits(p, x):
    return layer_norm(x, p["lnf_g"], p["lnf_b"]) @ p["head_w"].astype(_F32)


def head_loss(p, x, labels):
    """Mean next-token cross entropy over every row and position."""
    logp = jax.nn.log_softmax(head_logits(p, x), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


@functools.partial(jax.jit, static_argnames=("n_heads",))
def forward(params, tokens, *, n_heads):
    """Logits [B, S, V] of a whole stacked tree, layer by layer."""
    def body(x, p):
        return block(p, x, n_heads), None
    x, _ = jax.lax.scan(body, embed(params, tokens), params["blocks"])
    return head_logits(params, x)
