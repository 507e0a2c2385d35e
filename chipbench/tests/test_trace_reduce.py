"""The trace reduction, on a slice recorded on the chip and on hand-made
traces."""

import json
import os
import re

import pytest

from chipbench import trace_reduce as TR

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace-v5e-serve-slice.json")) as f:
        return json.load(f)


def _union(intervals):
    """An independent union: sweep over sorted end points."""
    points = sorted([(s, 1) for s, e in intervals]
                    + [(e, -1) for s, e in intervals])
    depth, total, last = 0, 0, None
    for t, step in points:
        if depth > 0:
            total += t - last
        depth += step
        last = t
    return total


def test_recorded_busy_is_the_union_of_its_operations(recorded):
    ev = recorded["devices"]["/device:TPU:0"]
    t0, t1 = TR.window_of(recorded)
    assert t0 == min(e[1] for e in ev) and t1 == max(e[1] + e[2] for e in ev)
    want = _union([(s, s + d) for _, s, d in ev]) / 1e9
    assert TR.busy_seconds(recorded) == pytest.approx(want, rel=1e-12)
    assert 0 < want <= (t1 - t0) / 1e9      # nested events count once


def test_recorded_pattern_sum_finds_the_pallas_kernel(recorded):
    ev = recorded["devices"]["/device:TPU:0"]
    want = _union([(s, s + d) for n, s, d in ev
                   if "|tpu_custom_call|" in n]) / 1e9
    got = TR.pattern_seconds(recorded, TR.CUSTOM_CALL)
    assert got == pytest.approx(want, rel=1e-12) and got > 0
    # a plain XLA custom call is not a Pallas kernel
    assert any(re.search(r"\|custom-call\|", n) for n, _, _ in ev)
    assert TR.pattern_seconds(recorded, TR.COLLECTIVE) == 0.0


def test_recorded_gaps_are_named_by_the_benchmarks_span(recorded):
    gaps = TR.idle_gaps(recorded, 3)
    assert gaps[0][0] == "engine_step" and gaps[0][1] > gaps[1][1] > 0
    t0, t1 = TR.traced_window(recorded)
    span = [h for h in recorded["host"] if h[0] == "traced_window"][0]
    assert (t0, t1) == (span[1], span[1] + span[2])


def test_top_ops_leave_out_the_loop_that_contains_them(recorded):
    names = [n for n, _ in TR.top_ops(recorded, 10)]
    assert names[0].startswith("closed_call.4|tpu_custom_call|")
    assert not any("|while|" in n for n in names)


HAND = {
    "devices": {
        "/device:TPU:0": [["a|fusion|f32[8]", 0, 10], ["b|fusion|f32[8]", 5, 10],
                          ["all-reduce.1|all-reduce|f32[8]", 30, 10],
                          ["k|tpu_custom_call|bf16[8]", 60, 20]],
        "/device:TPU:1": [["a|fusion|f32[8]", 0, 40]],
    },
    "async": {"/device:TPU:1": [
        ["all-gather-start.2|all-gather-start|f32[8]", 50, 30]]},
    "host": [["traced_window", 0, 100], ["train_step", 0, 28],
             ["client_loop", 41, 14]],
}


def test_hand_made_union_patterns_and_clipping():
    # device 0: [0,15) + [30,40) + [60,80) = 45; device 1: 40; mean 42.5 ns
    assert TR.busy_seconds(HAND) == pytest.approx(42.5e-9)
    # clipped to [10, 70): 5 + 10 + 10 = 25 and 30
    assert TR.busy_seconds(HAND, 10, 70) == pytest.approx(27.5e-9)
    # collectives: 10 ns on device 0, 30 ns in flight on device 1
    assert TR.pattern_seconds(HAND, TR.COLLECTIVE, 0, 100) == \
        pytest.approx(20e-9)
    assert TR.pattern_seconds(HAND, TR.CUSTOM_CALL, 0, 100) == \
        pytest.approx(10e-9)
    assert TR.traced_window(HAND) == (0, 100)


def test_hand_made_gaps():
    gaps = TR.idle_gaps(HAND, 5)
    # device 0 is idle over [15,30) and [40,60)
    assert gaps == [["client_loop", 20e-9], ["train_step", 15e-9]]


def test_short_name():
    hlo = ('%closed_call.4 = bf16[64,16,128,128]{3,2,1,0:T(8,128)(2,1)S(1)} '
           'custom-call(s32[64,16]{1,0:T(8,128)S(1)} %copy-done.16), '
           'custom_call_target="tpu_custom_call", operand_layout={}')
    assert TR.short_name(hlo) == \
        "closed_call.4|tpu_custom_call|bf16[64,16,128,128]"
    loop = ('%while.2 = (s32[]{:T(128)}, bf16[1,192,2048]{2,1,0:T(8,128)}) '
            'while((s32[]{:T(128)}, bf16[1,192,2048]{2,1,0}) %tuple.56), '
            'condition=%c, body=%b')
    assert TR.short_name(loop) == "while.2|while|s32[]"
    assert TR.CONTAINER.search(TR.short_name(loop))
    assert TR.short_name("%all-reduce.7 = f32[128]{0} all-reduce(f32[128]{0}"
                         " %x), replica_groups={}") == \
        "all-reduce.7|all-reduce|f32[128]"
