"""One chip: `ServingEngine(ragged=True)` serving a Trinity-Mini
configuration (window and full attention layers side by side: two page
lifetimes; a sigmoid router with a choice-only bias over 128 experts, all
held) under `serve_closed`'s closed loop. The clients, the loop and the
warm-up are that module's; the window is `serve_closed.run`'s, line for
line, as in the three runners before it (a file the benchmark has is not
this PR's to edit). What differs:

  * `serve_tok_s` counts the prompt tokens the program RAN plus the output
    tokens handed over, as `serve_docqa` counts them (no prefix is shared
    here, so a request's whole prompt is run);
  * the facts of the second lifetime: the window pool's peak, the pages
    given back, the settles the pool forced (0: the pool is sized so);
  * `correct`, two parts on what the timed path produced, for
    `check_requests` requests that ended in the window, one of each class
    of prompt length in the traffic file's `check_classes` (the longest
    that ended first, the others from the seed):
    (i)  each served token's logit against the reference's best at its
         position, the reference FOLLOWING the routing the timed path
         reported (its weights are its own float32 scores at those picks);
    (ii) the program's eight picks against the reference's own at every
         (position, layer): `route_clear_mismatches` (pairs that differ
         although the reference's margin exceeds the traffic file's
         `route_margin`; must be 0), `route_flip_share` (all differing
         pairs over all pairs) and the FIRST expert layer's.

Controls that have to come out as not correct: `--control weights_fp8`
(every matrix rounded through float8), `--control cache_fp8` (K and V
rounded through e4m3 on their way into BOTH pools), `--control
window_4096` (the program is given a window of 4,096, the reference
2,048). `--control router_bf16` (the router's product and scores in
bfloat16) is run and reported whether or not it separates."""

import dataclasses
import functools
import gc
import time

import jax.numpy as jnp
import numpy as np

# the program's module first: a program without it fails here, at the
# import, before anything holds the chip
from paddle_tpu.models import trinity_mini as TM
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference import ragged_step as RS

from chipbench import harness, traffic as T, yardstick as Y
from chipbench import weights_trinity_mini as W
from chipbench.reference import trinity_mini as R
from chipbench.runners.serve_closed import Loop, _warm_up
from chipbench.runners.serve_closed_h1 import _through_fp8

CONTROLS = (None, "weights_fp8", "cache_fp8", "window_4096", "router_bf16")


def weights_through_fp8(tree):
    """Every matrix, in place, a leaf at a time; the norms' gains and the
    router's bias stay."""
    if isinstance(tree, tuple):
        for sub in tree:
            weights_through_fp8(sub)
        return tree
    for k, v in tree.items():
        if isinstance(v, (dict, tuple)):
            weights_through_fp8(v)
        elif k == "embed" or k.endswith("_w"):
            tree[k] = _through_fp8(v)
    return tree


def cache_through_fp8():
    """The timed path's append takes K and V through e4m3 (no scale: both
    are O(1), k normed per head), into the full and the window pools
    alike."""
    append = RS.kv_append

    def rounded(k_pool, v_pool, k, v, *a, **kw):
        def fp8(x):
            return x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        return append(k_pool, v_pool, fp8(k), fp8(v), *a, **kw)
    RS.kv_append = rounded


def trinity_config(config, control=None):
    cfg = TM.TrinityMiniConfig(
        **config["widths"], dtype=jnp.dtype(config["dtype"]),
        param_dtype=jnp.dtype(config["dtype"]),
        router_dtype=jnp.bfloat16 if control == "router_bf16"
        else jnp.dtype(config["router_dtype"]))
    if control == "window_4096":
        cfg = dataclasses.replace(cfg, sliding_window=4096)
    return cfg


def sample_requests(ended, classes, seed):
    """One request that ended in the window for each class [lo, hi] of
    prompt length, no request twice: the first class's LONGEST, the
    others' from the seed; a class nobody ended in gives its place to the
    longest request left."""
    ended = sorted(ended, key=lambda r: r.rid)
    rng = np.random.default_rng([int(seed), 5])
    picked = []
    for n, (lo, hi) in enumerate(classes):
        left = [r for r in ended if r not in picked]
        inside = [r for r in left if lo <= len(r.prompt) <= hi]
        if not left:
            break
        if n == 0 or not inside:
            picked.append(max(inside or left, key=lambda r: len(r.prompt)))
        else:
            picked.append(inside[rng.integers(len(inside))])
    return picked


def against_reference(params, widths, samples, pad_to, block, margin_min):
    """(gaps a request, (differing pairs a layer, clear mismatches, pairs,
    widest margin of a differing pair))."""
    gaps, clear, pairs, widest = [], 0, 0, 0.0
    layers = widths["num_layers"] - widths["num_dense_layers"]
    differ = np.zeros((layers,), np.int64)
    for r in samples:
        prompt, output = np.asarray(r.prompt), np.asarray(r.output, np.int32)
        n = len(prompt) + len(output)
        tokens = np.zeros((pad_to,), np.int32)
        tokens[:n] = np.concatenate([prompt, output])
        routing = np.full((pad_to,) + r.routing.shape[1:], -1, np.int32)
        routing[:n - 1] = r.routing[:n - 1]
        assert (routing[:n - 1] >= 0).all(), "a position without routing"
        x, own, margin = R.hidden(params, jnp.asarray(tokens), widths,
                                  jnp.asarray(routing), n=n, block=block)
        served = slice(len(prompt) - 1, n - 1)
        best, picked = R.best_and_picked(
            params, x[served], jnp.asarray(tokens[len(prompt):n]))
        gaps.append(np.asarray(best - picked))
        own, margin = np.asarray(own)[:n - 1], np.asarray(margin)[:n - 1]
        other = (np.sort(own, -1) != np.sort(r.routing[:n - 1], -1)).any(-1)
        differ += other.sum(0)
        clear += int((other & (margin > margin_min)).sum())
        pairs += other.shape[0]
        widest = max(widest, float(np.max(margin, where=other, initial=0.0)))
    return gaps, (differ, clear, pairs, widest)


def run(ctx):
    config, traffic = ctx["config"], ctx["traffic"]
    widths, seconds, tracer = config["widths"], ctx["seconds"], ctx["tracer"]
    control = ctx["control"]
    assert control in CONTROLS, control
    cfg = trinity_config(config, control)
    harness.mark(ctx, "imports done, chip held")
    params = W.make_params(widths, ctx["seed"], config["dtype"])
    if control == "weights_fp8":
        params = weights_through_fp8(params)
    if control == "cache_fp8":
        cache_through_fp8()
    engine = dict(traffic["engine"])
    if control == "window_4096":    # its ring is wider: the pool by default
        del engine["num_window_blocks"]
    eng = ServingEngine(params, cfg, ragged=True, seed=ctx["seed"] % 2 ** 31,
                        **engine)
    # every request keeps its routing: the loop and the warm-up are
    # `serve_closed`'s and know no such argument
    eng.add_request = functools.partial(eng.add_request, keep_routing=True)
    del params
    harness.mark(ctx, "weights and engine made")
    _warm_up(eng, cfg.vocab_size, engine["chunk"])
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

    def counters():
        return (eng.moe_experts_touched, eng.moe_passes,
                float(eng._moe_load.sum()), eng.window_pages_freed,
                eng.prom.get("requests_preempted_total") or 0.0,
                eng.prom.get("overlap_settles_total",
                             {"reason": "window"}) or 0.0)

    compiles0 = ctx["compiles"].count
    steps0, disp0, micro0 = (eng.engine_steps, eng.dispatches,
                             eng.decode_microsteps)
    moe0 = moe1 = counters()
    n_spans0, n_busy0 = len(loop.step_spans), len(loop.busy)
    snap0 = snap1 = eng.snapshot()
    trace_from = seconds - float(traffic.get("trace_s", 8.0))
    t_w0 = t_last = time.perf_counter()
    setup_s = t_w0 - ctx["t0"]
    counters1 = (steps0, disp0, micro0)
    # per dispatched step: packed q tokens, prompt tokens granted, decode
    # rows, pages the pack gave back
    work, seen = [], None
    while True:
        if tracer and not tracer.on and \
                time.perf_counter() - t_w0 >= trace_from:
            tracer.start()
        t1 = loop.step(traced=bool(tracer and tracer.on))
        flight = eng._flight    # the step this call dispatched, if any
        if flight is not None and flight is not seen:
            seen = flight
            work.append((t1, flight.q_tokens, sum(flight.grants.values()),
                         len(flight.dec), flight.K, flight.model_attrs))
        if t1 - t_w0 > seconds:
            break
        t_last, snap1 = t1, eng.snapshot()
        counters1 = (eng.engine_steps, eng.dispatches, eng.decode_microsteps)
        moe1 = counters()
    trace = tracer.stop() if tracer else None
    gc.callbacks.remove(on_gc)
    compiles = ctx["compiles"].count - compiles0
    peak = harness.memory_peak_bytes(ctx["devices"])
    pool_peak = eng.prom.get("kv_pool_utilization_peak") or 0.0
    win_peak = eng.prom.get("kv_window_pool_utilization_peak") or 0.0
    freed, preempted, settles = (moe1[3] - moe0[3], moe1[4] - moe0[4],
                                 moe1[5] - moe0[5])

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
    layers = widths["num_layers"] - widths["num_dense_layers"]
    passes = max(moe1[1] - moe0[1], 1)
    slots = (hi - lo) * layers * passes
    step_ms = np.asarray([(b - a) * 1e3 for a, b in spans] or [0.0])
    mine = [w for w in work if inside(w[0])]
    n_work = max(len(mine), 1)
    harness.log(f"[steps] {len(spans)} in the window, wall ms a step: mean "
                f"{step_ms.mean():.2f}, p50 {np.median(step_ms):.2f}, p95 "
                f"{np.percentile(step_ms, 95):.2f}, max {step_ms.max():.2f}; "
                f"a step packs {sum(w[1] for w in mine) / n_work:.1f} q "
                f"tokens, {sum(w[2] for w in mine) / n_work:.1f} of them "
                f"prompt tokens, {sum(w[3] for w in mine) / n_work:.1f} "
                f"decode rows, K {sum(w[4] for w in mine) / n_work:.2f}, "
                f"attends {sum(w[5]['kv_layer_tokens'] for w in mine) / n_work:.0f} "
                f"positions over the layers, gives "
                f"{sum(w[5]['win_pages_freed'] for w in mine) / n_work:.2f} "
                f"window pages back ({sum(w[5]['win_pages_freed'] > 0 for w in mine)} "
                f"of {len(mine)} steps gave some back, "
                f"{sum(w[2] > 0 for w in mine)} prefilled)")
    harness.log(f"[window] {len(spans)} engine steps in {span_s:.2f} s; "
                f"{out_tokens} output + {prefilled} prompt tokens; "
                f"{len(tpot)} tpot samples; {len(ok_done)} requests ended; "
                f"setup_s {setup_s:.2f}; compiles in window {compiles}; "
                f"preemptions {preempted}; window pages given back {freed}, "
                f"settles for the window pool {settles}; pool peaks: full "
                f"{100 * pool_peak:.1f}%, window {100 * win_peak:.1f}%; "
                f"experts touched {moe1[0] - moe0[0]} of {slots} "
                f"({100.0 * (moe1[0] - moe0[0]) / slots:.1f}%), load max "
                f"over mean {(moe1[2] - moe0[2]) / (layers * passes):.2f}; "
                f"collector pauses: full {full} ms, young "
                f"{sum(young):.1f} ms in {len(young)}")

    # -- the reference, once the engine is gone ------------------------------
    samples = sample_requests(ok_done, traffic["check_classes"][
        :traffic["check_requests"]], ctx["seed"])
    del eng, loop.eng
    gc.collect()
    t_ref = time.perf_counter()
    ref_params = W.make_params(widths, ctx["seed"], config["dtype"])
    gaps, (differ, clear, pairs, widest) = against_reference(
        ref_params, widths, samples, traffic["pad_to"],
        traffic["reference_block"], traffic["route_margin"])
    flat = np.concatenate(gaps) if gaps else np.zeros((0,))
    by_layer = differ / max(pairs, 1)       # the flip share of each layer
    harness.log(f"[reference] {len(samples)} requests, prompts "
                f"{[len(r.prompt) for r in samples]}, {flat.size} served "
                f"tokens (widest gap a request "
                f"{[round(float(g.max()), 4) for g in gaps]}), {pairs} "
                f"positions x {layers} layers of which {int(differ.sum())} "
                f"differ (a layer: {[round(float(x), 4) for x in by_layer]}; "
                f"widest margin {widest:.4f}) and {clear} clearly, "
                f"{time.perf_counter() - t_ref:.1f} s")
    limits = traffic["limits"]
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
               limits["route_flip_share_first"])]
    return {
        "devices": ctx["devices"], "checks": checks, "trace": trace,
        "attempted": len(attempted), "failed": failed,
        "memory_peak_bytes": peak,
        "e2e": {"serve_tok_s": (out_tokens + prefilled) / span_s,
                "setup_s": setup_s},
        "facts": {"engine_step_ms": step_ms.tolist() if spans else [],
                  "gen_late_ms": [ms for (made, _), ms in
                                  zip(loop.submitted, loop.late_ms)
                                  if inside(made)],
                  "ttft_ms": ttft_ms, "tpot_ms": tpot,
                  "slot_busy_pct": [100.0 * b / engine["max_batch"]
                                    for b in loop.busy[n_busy0:]],
                  "pool_peak_pct": 100.0 * pool_peak,
                  "win_pool_peak_pct": 100.0 * win_peak,
                  "engine_steps": counters1[0] - steps0,
                  "dispatches": counters1[1] - disp0,
                  "decode_microsteps": counters1[2] - micro0,
                  "compiles_in_window": compiles,
                  "live_peak_bytes": peak,
                  "moe_touched_pct": 100.0 * (moe1[0] - moe0[0]) / slots,
                  "moe_load_max_over_mean":
                      (moe1[2] - moe0[2]) / (layers * passes),
                  "window_pages_freed": freed,
                  "window_settles": settles,
                  "preemptions": preempted},
    }
