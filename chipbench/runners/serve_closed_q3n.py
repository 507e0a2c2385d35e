"""One chip: `ServingEngine(ragged=True)` serving a Qwen3-Next configuration
under `serve_closed`'s closed loop. The clients, the loop and the warm-up
are that module's; the window is `serve_closed.run`'s, line for line, as in
`serve_closed_h1` (it is not a function there, and a file the benchmark has
is not this PR's to edit: PERF.md section 7 says the window now exists in
three runners). What differs is the model (the program's `qwen3_next`
configuration with its share of the experts and of the vocabulary, this
benchmark's seeded tree and plain reference), that every request asks for
its routing (`keep_routing`), and `correct`, which has three parts:

 (i)  each served token's logit against the reference's best at its
      position, the reference FOLLOWING the routing the timed path
      reported for that request (its weights are its own float32
      probabilities over those picks): with seeded weights one flipped
      pick moves the logits a hundred times further than a lower
      precision does, so the picks are held apart and compared in (ii);
 (ii) the program's ten picks against the reference's own ten at every
      (position, layer) of those requests: `route_clear_mismatches`
      counts the pairs that differ although the reference's margin
      (p_10 - p_11) / p_10 exceeds the traffic file's `route_margin` and
      must be 0; `route_flip_share`, all differing pairs over all pairs,
      stays under a measured limit, and so does the FIRST layer's
      (`route_flip_share_first`: its router sees the least noise from the
      bfloat16 stream above it, so its own precision shows there);
 (iii) the recurrent state the timed path left in the slots of requests
      still decoding when the window closed, against the state the
      reference reaches over the same tokens and the same routing
      (`state_rel_err_max` over all three linear layers, and the first
      layer's alone, `state_rel_err_first`, for the same reason).

Three lower-precision readings have to come out as not correct, each by
the limit that feels it: `--control weights_fp8` (matrices rounded through
float8: the gaps of (i)), `--control router_bf16` (the router's product
and softmax in bfloat16: the picks of (ii)), `--control state_bf16` (the
engine's recurrent state in bfloat16: (iii))."""

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

# the program's module first: a program without it fails here, at the
# import, before anything holds the chip
from paddle_tpu.models import qwen3_next as QN
from paddle_tpu.inference import ServingEngine

from chipbench import harness, traffic as T, yardstick as Y
from chipbench import weights_qwen3_next as W
from chipbench.reference import qwen3_next as R
from chipbench.runners.serve_closed import Loop, _warm_up
from chipbench.runners.serve_closed_h1 import _through_fp8

CONTROLS = (None, "state_bf16", "weights_fp8", "router_bf16")
# every matrix; the norms' gains, the conv's taps and the per-head vectors
# stay as they are
FP8_LEAVES = ("embed", "head_w", "in_qkvz_w", "in_ba_w", "out_w", "q_w",
              "k_w", "v_w", "o_w", "router_w", "shared_gate_w",
              "shared_up_w", "shared_down_w", "gate_w", "up_w", "down_w")


def weights_through_fp8(tree):
    """In place, a leaf at a time: the tree is half of the chip."""
    if isinstance(tree, tuple):
        for sub in tree:
            weights_through_fp8(sub)
        return tree
    for k, v in tree.items():
        if isinstance(v, (dict, tuple)):
            weights_through_fp8(v)
        elif k in FP8_LEAVES:
            tree[k] = _through_fp8(v)
    return tree


def q3n_config(config, control=None):
    return QN.Qwen3NextConfig(
        **config["widths"], dtype=jnp.dtype(config["dtype"]),
        param_dtype=jnp.dtype(config["dtype"]),
        router_dtype=jnp.bfloat16 if control == "router_bf16"
        else jnp.float32)


def sample_requests(ended, n, seed):
    """The longest request that ended in the window and n - 1 more drawn
    from the seed: `serve_closed._sample`'s choice, the requests
    themselves (their routing is wanted too)."""
    if not ended:
        return []
    ended = sorted(ended, key=lambda r: r.rid)
    longest = max(ended, key=lambda r: len(r.prompt) + len(r.output))
    rest = [r for r in ended if r is not longest]
    rng = np.random.default_rng([int(seed), 5])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in pick]


def states_in_flight(eng, most, seed, fresh):
    """(slot, tokens consumed, their routing, the slot's recurrent state
    [L_lin, heads, dk, dv]) of up to `most` requests that are decoding:
    `serve_closed_h1.states_in_flight`'s choice (always the highest slot,
    always one the window admitted where there is one, the rest drawn
    from the seed), with the routing of the tokens consumed."""
    live = [r for r in eng.slots
            if r is not None and r.prefill_done >= len(r.prompt)]
    rng = np.random.default_rng(seed)
    picked, rest = live[-1:], live[:-1]
    new = [i for i, r in enumerate(rest) if r.rid in fresh]
    if new:
        picked.append(rest.pop(new[rng.integers(len(new))]))
    picked += [rest[i] for i in rng.permutation(len(rest))]
    out = []
    for r in sorted(picked[:most], key=lambda r: r.slot):
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.output[r.folded:], np.int32)])
        n = int(eng.lens[r.slot])
        out.append((r.slot, seq[:n], r.routing[:n].copy(),
                    np.asarray(eng.ssm_state[:, r.slot], np.float32)))
    return out


def _padded(tokens, routing, pad_to):
    """A sequence and its routing at the reference's fixed length
    (positions past the end pick for themselves: -1)."""
    seq = np.zeros((pad_to,), np.int32)
    seq[:len(tokens)] = tokens
    route = np.full((pad_to,) + routing.shape[1:], -1, np.int32)
    route[:len(routing)] = routing
    return jnp.asarray(seq), jnp.asarray(route)


def against_reference(params, config, samples, held, pad_to, margin_min):
    """The three comparisons. samples: requests that ended; held:
    `states_in_flight`'s. Returns (gaps a request, (differing pairs,
    clear mismatches, pairs), state errors a slot)."""
    w = config["widths"]

    @jax.jit
    def served(params, tokens, routing):
        x, _, own, margin = R.hidden(params, tokens, w, None, routing)
        best, picked = R.best_and_picked(params, x[:-1], tokens[1:])
        return best - picked, own, margin

    @jax.jit
    def states(params, tokens, routing, n):
        return R.hidden(params, tokens, w, n, routing)[1]

    gaps, clear, pairs, widest = [], 0, 0, 0.0
    differ = np.zeros((w["num_layers"],), np.int64)
    for r in samples:
        prompt, output = np.asarray(r.prompt), np.asarray(r.output, np.int32)
        n = len(prompt) + len(output)
        assert (r.routing[:n - 1] >= 0).all(), "a position without routing"
        g, own, margin = served(params, *_padded(
            np.concatenate([prompt, output]), r.routing[:n - 1], pad_to))
        gaps.append(np.asarray(g)[len(prompt) - 1:n - 1])
        own, margin = np.asarray(own)[:n - 1], np.asarray(margin)[:n - 1]
        other = (np.sort(own, -1) != np.sort(r.routing[:n - 1], -1)).any(-1)
        differ += other.sum(0)                          # a layer
        clear += int((other & (margin > margin_min)).sum())
        pairs += other.shape[0]                         # a layer
        widest = max(widest, float(np.max(margin, where=other, initial=0.0)))
    errs = []
    for _, tokens, routing, got in held:
        want = np.asarray(states(params, *_padded(tokens, routing, pad_to),
                                 len(tokens)))
        errs.append([float(np.linalg.norm(g - w) / np.linalg.norm(w))
                     for g, w in zip(got, want)] +      # a linear layer,
                    [float(np.linalg.norm(got - want)   # then all of them
                           / np.linalg.norm(want))])
    return gaps, (differ, clear, pairs, widest), \
        np.asarray(errs, np.float64).reshape(len(errs), -1)


def run(ctx):
    config, traffic = ctx["config"], ctx["traffic"]
    widths, seconds, tracer = config["widths"], ctx["seconds"], ctx["tracer"]
    assert ctx["control"] in CONTROLS, ctx["control"]
    cfg = q3n_config(config, ctx["control"])
    harness.mark(ctx, "imports done, chip held")
    params = W.make_params(widths, ctx["seed"], config["dtype"])
    if ctx["control"] == "weights_fp8":
        params = weights_through_fp8(params)
    eng = ServingEngine(
        params, cfg, ragged=True, seed=ctx["seed"] % 2 ** 31,
        ssm_state_dtype=("bfloat16" if ctx["control"] == "state_bf16"
                         else "float32"),
        **traffic["engine"])
    # every request keeps its routing: the loop and the warm-up are
    # `serve_closed`'s and know no such argument
    eng.add_request = functools.partial(eng.add_request, keep_routing=True)
    del params
    harness.mark(ctx, "weights and engine made")
    _warm_up(eng, cfg.vocab_size, traffic["engine"]["chunk"])
    harness.mark(ctx, "every program variant ran once")

    # -- the ramp, still set-up ---------------------------------------------
    loop = Loop(eng, T.ClosedLoop(traffic, cfg.vocab_size, ctx["seed"]))
    while time.perf_counter() - loop.t_start < traffic["ramp_s"]:
        loop.step()

    # -- the window ---------------------------------------------------------
    pauses, began = [], [0.0]

    def on_gc(phase, info):     # the collector's pauses, for the log
        if phase == "start":
            began[0] = time.perf_counter()
        else:
            pauses.append((began[0], time.perf_counter() - began[0],
                           info["generation"]))
    gc.callbacks.append(on_gc)

    def moe_counters():
        return (eng.moe_experts_touched, eng.moe_passes,
                float(eng._moe_load.sum()))

    compiles0 = ctx["compiles"].count
    steps0, disp0, micro0 = (eng.engine_steps, eng.dispatches,
                             eng.decode_microsteps)
    moe0 = moe1 = moe_counters()
    preempt0 = eng.prom.get("requests_preempted_total") or 0.0
    n_spans0, n_busy0 = len(loop.step_spans), len(loop.busy)
    snap0 = snap1 = eng.snapshot()
    trace_from = seconds - float(traffic.get("trace_s", 8.0))
    t_w0 = t_last = time.perf_counter()
    setup_s = t_w0 - ctx["t0"]
    counters1 = (steps0, disp0, micro0)
    while True:
        if tracer and not tracer.on and \
                time.perf_counter() - t_w0 >= trace_from:
            tracer.start()
        t1 = loop.step(traced=bool(tracer and tracer.on))
        if t1 - t_w0 > seconds:
            break
        t_last, snap1 = t1, eng.snapshot()
        counters1 = (eng.engine_steps, eng.dispatches, eng.decode_microsteps)
        moe1 = moe_counters()
    trace = tracer.stop() if tracer else None
    gc.callbacks.remove(on_gc)
    compiles = ctx["compiles"].count - compiles0
    peak = harness.memory_peak_bytes(ctx["devices"])
    pool_peak = eng.prom.get("kv_pool_utilization_peak") or 0.0
    preempted = (eng.prom.get("requests_preempted_total") or 0.0) - preempt0
    held = states_in_flight(
        eng, traffic["check_states"], ctx["seed"],
        {rid for made, rid in loop.submitted if made >= t_w0})

    # -- what the window handed over ----------------------------------------
    def inside(t):
        return t_w0 <= t <= t_last
    out_tokens = sum(inside(t) for ts in loop.deliveries.values()
                     for t in ts)
    first_in = {rid: len(loop.req[rid][0])
                for rid, ts in loop.deliveries.items()
                if ts and inside(ts[0])}
    prefilled = Y.prefill_tokens_in_window(first_in, snap0, snap1)
    span_s = t_last - t_w0
    tpot = Y.tpot_samples_ms(loop.deliveries, t_w0, t_last)
    ttft_ms = [(ts[0] - loop.first_due[rid]) * 1e3
               for rid, ts in loop.deliveries.items()
               if ts and inside(ts[0])]
    attempted = [rid for made, rid in loop.submitted if inside(made)]
    done = {r.rid: r for t, r in loop.finished}
    failed = sum(1 for rid in attempted
                 if rid in done and done[rid].status != "ok")
    ok_done = [r for t, r in loop.finished
               if inside(t) and r.status == "ok"]
    spans = [s for s in loop.step_spans[n_spans0:] if s[1] <= t_last]
    full = [round(d * 1e3, 1) for t, d, g in pauses if inside(t) and g == 2]
    young = [d * 1e3 for t, d, g in pauses if inside(t) and g < 2]
    lo, hi = widths["experts_held"]
    slots = (hi - lo) * widths["num_layers"] * max(moe1[1] - moe0[1], 1)
    harness.log(f"[window] {len(spans)} engine steps in {span_s:.2f} s; "
                f"{out_tokens} output + {prefilled} prompt tokens; "
                f"{len(tpot)} tpot samples; {len(ok_done)} requests ended; "
                f"setup_s {setup_s:.2f}; compiles in window {compiles}; "
                f"state resets {eng.ssm_resets}; preemptions {preempted}; "
                f"experts touched {moe1[0] - moe0[0]} of {slots}; "
                f"collector pauses: full {full} ms, young "
                f"{sum(young):.1f} ms in {len(young)}")

    # -- the reference, once the engine is gone ------------------------------
    samples = sample_requests(ok_done, traffic["check_requests"],
                              ctx["seed"])
    del eng, loop.eng
    gc.collect()
    t_ref = time.perf_counter()
    ref_params = W.make_params(widths, ctx["seed"], config["dtype"])
    gaps, (differ, clear, pairs, widest), errs = against_reference(
        ref_params, config, samples, held, traffic["pad_to"],
        traffic["route_margin"])
    flat = np.concatenate(gaps) if gaps else np.zeros((0,))
    layers = widths["num_layers"]
    by_layer = differ / max(pairs, 1)       # the flip share of each layer
    harness.log(f"[reference] {len(samples)} requests, {flat.size} served "
                f"tokens, {pairs} positions x {layers} layers of which "
                f"{int(differ.sum())} differ (a layer: "
                f"{[round(float(x), 4) for x in by_layer]}; widest margin "
                f"{widest:.4f}) and {clear} clearly; {len(errs)} states in "
                f"flight, slots {[h[0] for h in held]} "
                f"({[len(h[1]) for h in held]} tokens), error a linear "
                f"layer and of all: {np.round(errs, 5).tolist()}, "
                f"{time.perf_counter() - t_ref:.1f} s")
    limits = traffic["limits"]
    # the FIRST layer's router and state see the least noise from the
    # bfloat16 stream above them (its input is the embedding's own
    # bfloat16 values): their precision is what a comparison can resolve,
    # so each has a limit of its own beside the one over all layers
    checks = [("served_logit_gap_max",
               float(flat.max()) if flat.size else None,
               limits["served_logit_gap_max"]),
              ("served_logit_gap_mean",
               float(flat.mean()) if flat.size else None,
               limits["served_logit_gap_mean"]),
              ("route_clear_mismatches", float(clear) if pairs else None,
               limits["route_clear_mismatches"]),
              ("route_flip_share",
               float(differ.sum()) / (pairs * layers) if pairs else None,
               limits["route_flip_share"]),
              ("route_flip_share_first",
               float(by_layer[0]) if pairs else None,
               limits["route_flip_share_first"]),
              ("state_rel_err_max",
               float(errs[:, -1].max()) if len(errs) else None,
               limits["state_rel_err_max"]),
              ("state_rel_err_first",
               float(errs[:, 0].max()) if len(errs) else None,
               limits["state_rel_err_first"])]
    passes = max(moe1[1] - moe0[1], 1)
    return {
        "devices": ctx["devices"], "checks": checks, "trace": trace,
        "attempted": len(attempted), "failed": failed,
        "memory_peak_bytes": peak,
        "e2e": {"serve_tok_s": (out_tokens + prefilled) / span_s,
                "setup_s": setup_s},
        "facts": {"engine_step_ms": [(b - a) * 1e3 for a, b in spans],
                  "gen_late_ms": [ms for (made, _), ms in
                                  zip(loop.submitted, loop.late_ms)
                                  if inside(made)],
                  "ttft_ms": ttft_ms, "tpot_ms": tpot,
                  "slot_busy_pct": [100.0 * b / traffic["engine"]["max_batch"]
                                    for b in loop.busy[n_busy0:]],
                  "pool_peak_pct": 100.0 * pool_peak,
                  "engine_steps": counters1[0] - steps0,
                  "dispatches": counters1[1] - disp0,
                  "decode_microsteps": counters1[2] - micro0,
                  "compiles_in_window": compiles,
                  "live_peak_bytes": peak,
                  "moe_touched_pct": 100.0 * (moe1[0] - moe0[0]) / slots,
                  "moe_load_max_over_mean":
                      (moe1[2] - moe0[2]) / (widths["num_layers"] * passes),
                  "preemptions": preempted},
    }
