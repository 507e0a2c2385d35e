"""The benchmark's own arithmetic: peaks, model FLOPs, percentiles, and the
two serving metrics. Copied here so that no later PR can move the yardstick
(originals: `paddle_tpu/observability/flops.py` `gpt_flops_per_token`,
`CHIP_PEAKS`, `mfu`; see PERF.md, Open questions)."""

import math

# Per-chip bf16 matrix peak in FLOP/s, HBM bytes/s, keyed by the
# `device_kind` jax reports. Source: Google Cloud TPU documentation, "TPU
# v5e" system architecture page (197 TFLOP/s bf16, 819 GB/s, 16 GB). A device
# that is not in the table is an error, never a default.
CHIP_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def chip_peak(device_kind, what="flops"):
    if device_kind not in CHIP_PEAKS:
        raise KeyError(f"no published peak for device_kind {device_kind!r}: "
                       "add it, with its source, to chipbench/yardstick.py")
    return CHIP_PEAKS[device_kind][what]


def gpt_flops_per_token(widths, seq_len):
    """Model FLOPs per trained token, forward and backward, nothing
    recomputed: 6 per matrix parameter (blocks and the untied head,
    embeddings excluded) plus 12 * L * H * S for the attention products;
    the causal halving is not applied (the convention of the program's
    own accounting, kept so the two agree)."""
    H, L = widths["hidden_size"], widths["num_layers"]
    FF, V = widths["ffn_hidden"], widths["vocab_size"]
    n = L * (4 * H * H + 2 * H * FF) + H * V
    return 6.0 * n + 12.0 * L * H * seq_len


def mfu_pct(tokens_per_s, flops_per_token, chips, device_kind):
    return 100.0 * tokens_per_s * flops_per_token / (
        chips * chip_peak(device_kind))


def percentile(values, q):
    """q in [0, 100], linear interpolation between order statistics (the
    rule numpy's default uses); None when there is nothing to read."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# -- serving ----------------------------------------------------------------
# A reader's wait is taken over this many tokens (a paragraph). Over 16
# tokens (a line) the wait is a whole number of engine steps, 8 to 12 of
# them at today's step of 0.33 s, and its 95th percentile hops between
# plateaus 20 ms apart from seed to seed: 3.8% between quartiles over six
# seeds where this span reads 1.2% (my chip runs, PR 25; PERF.md).
TPOT_SPAN = 64


def tpot_samples_ms(deliveries, t0, t1, span=TPOT_SPAN):
    """The time a reader waits for the next `span` tokens, per token.

    `deliveries` maps a request to the times at which each of its output
    tokens was handed over, in order (tokens of one engine step share a
    time). One sample for every token i >= span whose delivery, and that
    of token i - span, both lie in [t0, t1]: (t[i] - t[i-span]) / span, in
    milliseconds. Pooled over all requests."""
    out = []
    for times in deliveries.values():
        for i in range(span, len(times)):
            a, b = times[i - span], times[i]
            if a >= t0 and b <= t1:
                out.append((b - a) / span * 1e3)
    return out


def prefill_tokens_in_window(first_token_prompts, at_start, at_end):
    """Prompt tokens prefilled between two `ServingEngine.snapshot()`s.

    first_token_prompts: {rid: prompt length} of every request whose first
    token arrived inside the window (its prefill ended there). Less what
    the snapshot at the window's start shows as already prefilled for
    requests that had no token yet; plus the progress, at the window's
    end, of requests still without a token."""
    def unfinished(snap):
        return {r["rid"]: r["prefill_done"] for r in snap["slots"]
                if r is not None and r["emitted"] == 0}
    before, after = unfinished(at_start), unfinished(at_end)
    total = sum(first_token_prompts.values())
    total -= sum(done for rid, done in before.items()
                 if rid in first_token_prompts or rid in after)
    total += sum(after.values())
    return total
