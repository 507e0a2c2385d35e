"""The benchmark's own arithmetic."""

import pytest

from chipbench import harness, yardstick as Y


def _deliveries(step_s, ks, start=0.0):
    """Delivery times of one request that gets ks[i] tokens at the end of
    step i, steps `step_s` apart."""
    times, t = [], start
    for k in ks:
        t += step_s
        times += [t] * k
    return times


def test_tpot_k1_and_k8_are_step_time_over_k():
    one = {1: _deliveries(0.3, [1] * 64)}
    eight = {1: _deliveries(0.3, [8] * 8)}
    s1 = Y.tpot_samples_ms(one, 0.0, 100.0, span=16)
    s8 = Y.tpot_samples_ms(eight, 0.0, 100.0, span=16)
    assert len(s1) == 64 - 16 and len(s8) == 64 - 16
    assert all(x == pytest.approx(300.0) for x in s1)
    assert all(x == pytest.approx(300.0 / 8) for x in s8)


def test_tpot_mixed_bursts_give_no_plateau():
    # K alternates 8, 1: a gap between single tokens would be 0 or a whole
    # step; the wait for 16 tokens takes many values in between
    req = {1: _deliveries(0.25, [8, 1] * 20)}
    s = Y.tpot_samples_ms(req, 0.0, 100.0, span=16)
    assert len(s) == 180 - 16
    assert len({round(x, 6) for x in s}) >= 3
    assert min(s) >= 250.0 / 8 and max(s) < 250.0
    assert Y.percentile(s, 95) < max(s) + 1e-9


def test_tpot_default_span_is_a_paragraph():
    assert Y.TPOT_SPAN == 64
    req = {1: _deliveries(0.25, [8, 1] * 40)}       # 360 tokens
    s = Y.tpot_samples_ms(req, 0.0, 100.0)
    assert len(s) == 360 - 64
    # 64 tokens take 14 or 15 steps here: no plateau 20 ms wide
    assert max(s) - min(s) < 250.0 / 64 * 1.01 and len(set(s)) >= 2


def test_tpot_cut_by_either_end_of_the_window():
    times = _deliveries(0.1, [1] * 40)          # 0.1 .. 4.0
    inside = Y.tpot_samples_ms({1: times}, 0.95, 3.05, span=16)
    # token i at (i+1)/10; both i-16 and i inside [0.95, 3.05]: i-16 >= 9, i <= 29
    assert len(inside) == 29 - 25 + 1
    assert Y.tpot_samples_ms({1: times}, 0.0, 1.0, span=16) == []
    assert Y.tpot_samples_ms({1: times[:16]}, 0.0, 10.0, span=16) == []
    two = Y.tpot_samples_ms({1: times, 2: times}, 0.95, 3.05, span=16)
    assert len(two) == 2 * len(inside)          # pooled over requests


def _snap(*slots):
    return {"slots": [None if s is None else
                      {"rid": s[0], "prefill_done": s[1], "emitted": s[2]}
                      for s in slots]}


def test_prefill_tokens_from_two_snapshots():
    # rid 1: 300 of 1000 done at the start, first token inside -> 700
    # rid 2: admitted inside, first token inside -> 500
    # rid 3: 128 done at the start, still prefilling at the end at 640 -> 512
    # rid 4: admitted inside, 256 done at the end -> 256
    # rid 5: decoding at the start (emitted > 0): nothing
    start = _snap((1, 300, 0), (3, 128, 0), (5, 900, 7), None)
    end = _snap((3, 640, 0), (4, 256, 0), (5, 900, 40), (2, 500, 3))
    got = Y.prefill_tokens_in_window({1: 1000, 2: 500}, start, end)
    assert got == 700 + 500 + 512 + 256
    assert Y.prefill_tokens_in_window({}, _snap(), _snap()) == 0


def test_percentile_matches_numpy():
    import numpy as np
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
    for q in (0, 50, 95, 100):
        assert Y.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert Y.percentile([], 95) is None


@pytest.mark.parametrize("name", ["gpt3-1p3b", "gpt3-6p7b"])
def test_flops_copy_agrees_with_the_programs(name):
    import jax.numpy as jnp
    from paddle_tpu.models import gpt as G
    from paddle_tpu.observability import flops as F
    w = harness.load_json("configs", name + ".json")["widths"]
    cfg = G.GPTConfig(vocab_size=w["vocab_size"],
                      hidden_size=w["hidden_size"],
                      num_layers=w["num_layers"], num_heads=w["num_heads"],
                      ffn_hidden=w["ffn_hidden"],
                      max_seq_len=w["max_seq_len"], dtype=jnp.bfloat16)
    want = F.gpt_flops_per_token(cfg, 2048)["model"]
    assert Y.gpt_flops_per_token(w, 2048) == pytest.approx(want, rel=1e-12)
    assert Y.chip_peak("TPU v5 lite") == F.CHIP_PEAKS["TPU v5 lite"][1]
    with pytest.raises(KeyError):
        Y.chip_peak("cpu")
    assert Y.mfu_pct(13000.0, want, 1, "TPU v5 lite") == pytest.approx(
        100 * F.mfu(13000.0, want, peak=197e12))
