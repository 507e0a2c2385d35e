"""Serving-tier tests (VERDICT r2 #4): continuous batching + chunked
prefill over the paged pool must reproduce the one-shot gpt_generate
goldens exactly (greedy), stream tokens, and recycle blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (ServingEngine,
                                          generate_static_batch)
from paddle_tpu.models import gpt as G
from paddle_tpu.models.generation import gpt_generate

CFG = G.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                  max_seq_len=128, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return G.init_hybrid_params(CFG, jax.random.PRNGKey(0))


def golden(params, prompt, n):
    out = gpt_generate(params, CFG, jnp.asarray(prompt, jnp.int32)[None], n)
    return np.asarray(out)[0, len(prompt):].tolist()


def test_single_request_matches_generate(params):
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, CFG.vocab_size, (11,))
    eng = ServingEngine(params, CFG, max_batch=2, block_size=8,
                        num_blocks=32, max_blocks_per_seq=8, chunk=4)
    rid = eng.add_request(prompt, max_new_tokens=7)
    res = eng.run()
    assert res[rid] == golden(params, prompt, 7)


def test_concurrent_ragged_requests_match_generate(params):
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, CFG.vocab_size, (n,))
               for n in (5, 13, 9, 16, 3)]
    news = [6, 3, 9, 4, 8]
    eng = ServingEngine(params, CFG, max_batch=2, block_size=8,
                        num_blocks=24, max_blocks_per_seq=8, chunk=8)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
    res = eng.run()
    for rid, p, n in zip(rids, prompts, news):
        assert res[rid] == golden(params, p, n), rid


def test_streaming_callback_order(params):
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, CFG.vocab_size, (6,))
    seen = []
    eng = ServingEngine(params, CFG, max_batch=1, block_size=8,
                        num_blocks=16, max_blocks_per_seq=4, chunk=8)
    rid = eng.add_request(prompt, 5, on_token=lambda r, t: seen.append((r, t)))
    res = eng.run()
    assert [t for _, t in seen] == res[rid]
    assert all(r == rid for r, _ in seen)


def test_blocks_recycled_across_many_requests(params):
    """More total work than the pool could ever hold at once — finishing
    requests must return their blocks (admit/evict)."""
    rng = np.random.RandomState(3)
    eng = ServingEngine(params, CFG, max_batch=2, block_size=8,
                        num_blocks=9, max_blocks_per_seq=4, chunk=8)
    total_free = len(eng.free_blocks)
    prompts = [rng.randint(0, CFG.vocab_size, (8,)) for _ in range(6)]
    rids = [eng.add_request(p, 4) for p in prompts]
    res = eng.run()
    assert len(res) == 6
    assert len(eng.free_blocks) == total_free  # everything returned
    for rid, p in zip(rids, prompts):
        assert res[rid] == golden(params, p, 4)


def test_eos_stops_early(params):
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, CFG.vocab_size, (9,))
    g = golden(params, prompt, 10)
    eos = g[3]
    eng = ServingEngine(params, CFG, max_batch=1, block_size=8,
                        num_blocks=16, max_blocks_per_seq=8, chunk=8)
    rid = eng.add_request(prompt, 10, eos_id=eos)
    res = eng.run()
    assert res[rid] == g[:4]


def test_oversized_request_rejected_per_request(params):
    """A request that can NEVER fit is rejected per-request
    (status='failed', naming the binding cap) — it must not abort the
    engine step and strand its queued siblings (ISSUE 13 satellite)."""
    rng = np.random.RandomState(21)
    sib = rng.randint(0, CFG.vocab_size, (9,))
    eng = ServingEngine(params, CFG, max_batch=1, block_size=8,
                        num_blocks=16, max_blocks_per_seq=2, chunk=8)
    bad = eng.add_request(np.zeros(20, np.int32), 10)
    good = eng.add_request(sib, 5)
    res = eng.run()
    assert res.statuses[bad] == "failed"
    assert res[bad] == []
    assert res.statuses[good] == "ok"
    assert res[good] == golden(params, sib, 5)  # sibling unharmed


def test_tp_sharded_decode_matches_generate(params):
    """Megatron-TP serving over an mp mesh axis (VERDICT r3 #8): sharded
    qkv/proj/fc + head-sharded KV pools + vocab-parallel logits must
    reproduce the single-device goldens exactly."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, CFG.vocab_size, (n,)) for n in (9, 14, 5)]
    news = [6, 4, 8]
    eng = ServingEngine(params, CFG, max_batch=2, block_size=8,
                        num_blocks=24, max_blocks_per_seq=8, chunk=8,
                        mesh=mesh, mp_axis="mp")
    rids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
    res = eng.run()
    for rid, p, n in zip(rids, prompts, news):
        assert res[rid] == golden(params, p, n), rid


def test_int8_serving_close_to_fp(params):
    """W8A8 serving (int8=True): weights quantized per output channel,
    activations per call — generated tokens track the fp engine closely
    (greedy, short decodes; W8A8 error can flip late low-margin tokens,
    so assert high agreement rather than exact match)."""
    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, CFG.vocab_size, (n,)) for n in (9, 13)]
    news = [6, 6]

    def run(int8):
        eng = ServingEngine(params, CFG, max_batch=2, block_size=8,
                            num_blocks=24, max_blocks_per_seq=8, chunk=8,
                            int8=int8)
        rids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
        res = eng.run()
        return [res[r] for r in rids]

    fp = run(False)
    q8 = run(True)
    total = sum(len(o) for o in fp)
    agree = sum(a == b for o1, o2 in zip(fp, q8)
                for a, b in zip(o1, o2))
    assert agree / total >= 0.75, (fp, q8)
    # first token (largest margin) must agree per request
    for o1, o2 in zip(fp, q8):
        assert o1[0] == o2[0]


def test_static_batch_mixed_prompt_lengths(params):
    """The static baseline buckets mixed-length prompts by length and pads
    to the bucket max; equal-length groups still match goldens exactly."""
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, CFG.vocab_size, (n,)) for n in (8, 8, 12, 12)]
    news = [5, 4, 6, 3]
    outs = generate_static_batch(params, CFG, prompts, news, batch_size=2)
    for p, n, o in zip(prompts, news, outs):
        assert o == golden(params, p, n)


def test_static_batch_baseline_matches_generate(params):
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, CFG.vocab_size, (8,)) for _ in range(4)]
    news = [3, 6, 2, 5]
    outs = generate_static_batch(params, CFG, prompts, news, batch_size=2)
    for p, n, o in zip(prompts, news, outs):
        assert o == golden(params, p, n)
