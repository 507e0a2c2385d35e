"""One chip: `ServingEngine(ragged=True)` serving a Falcon-H1 configuration
under `serve_closed`'s closed loop. The clients, the loop, the warm-up and
the sampling of requests to check are that module's; what differs is the
model (the program's `falcon_h1` configuration, this benchmark's seeded
tree and plain reference) and one more comparison: beside each served
token's logit against the reference's best, the recurrent state the timed
path left in the slots of requests still decoding when the window closed,
against the state the reference reaches over the same tokens. The window
itself is `serve_closed.run`'s, line for line (it is not a function there,
and a file the benchmark has is not this PR's to edit).

Two lower-precision readings have to come out as not correct, each by the
limit that feels it: `--control state_bf16` keeps the engine's recurrent
state in bfloat16 (the state error feels it, the logits do not), and
`--control weights_fp8` serves from weight matrices rounded through
float8 (the served-logit gaps feel it, over every slot and layer)."""

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

# the program's module first: a program without it fails here, at the
# import, before anything holds the chip
from paddle_tpu.models import falcon_h1 as FH
from paddle_tpu.inference import ServingEngine

from chipbench import harness, traffic as T, yardstick as Y
from chipbench import weights_falcon_h1 as W
from chipbench.reference import falcon_h1 as R
from chipbench.runners.serve_closed import Loop, _sample, _warm_up

CONTROLS = (None, "state_bf16", "weights_fp8")
FP8_LEAVES = ("embed", "q_w", "k_w", "v_w", "o_w", "ssm_in_w", "ssm_out_w",
              "gate_w", "up_w", "down_w", "head_w")


@jax.jit
def _to_fp8(w):
    f = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(f)) / 448.0
    return (f / scale).astype(jnp.float8_e4m3fn), scale


@functools.partial(jax.jit, static_argnames="dtype")
def _from_fp8(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _through_fp8(w):
    """w rounded to float8 (e4m3, one scale a tensor) and back: two
    compiled calls, because inside one the compiler elides the round
    trip (as `weights.make_leaf` found for bfloat16)."""
    return _from_fp8(*_to_fp8(w), dtype=w.dtype)


def weights_through_fp8(tree):
    """In place, a leaf at a time: the tree is two thirds of the chip."""
    for k, v in tree.items():
        if isinstance(v, dict):
            weights_through_fp8(v)
        elif k in FP8_LEAVES:
            tree[k] = _through_fp8(v)
    return tree


def h1_config(config):
    w, m = config["widths"], config["multipliers"]
    return FH.FalconH1Config(
        **w, **{k: tuple(v) if isinstance(v, list) else v
                for k, v in m.items()},
        dtype=jnp.dtype(config["dtype"]),
        param_dtype=jnp.dtype(config["dtype"]))


def served_gaps(params, config, samples, pad_to):
    """`serve_closed.served_gaps` for this model: one padded forward of the
    plain reference a request, the head a block of the vocabulary at a
    time."""
    w, m = config["widths"], config["multipliers"]

    @jax.jit
    def gaps(params, tokens):
        x, _ = R.hidden(params, tokens, w, m)
        best, picked = R.best_and_picked(params, x[:-1], tokens[1:], m)
        return best - picked

    out = []
    for prompt, output in samples:
        seq = np.zeros((pad_to,), np.int32)
        n = len(prompt) + len(output)
        seq[:len(prompt)] = prompt
        seq[len(prompt):n] = output
        g = np.asarray(gaps(params, jnp.asarray(seq)))
        out.append(g[len(prompt) - 1:n - 1])
    return out


def states_in_flight(eng, most, seed, fresh):
    """(slot, tokens consumed, the slot's recurrent state [L, heads, P, N])
    of up to `most` requests that are decoding: the state covers the slot's
    `lens` tokens (the token sampled last is pending, not yet consumed).
    Always the one in the highest slot (the end of a pass's row list),
    always one the window admitted where there is one (`fresh`, their
    rids: the timed path reset its state), the rest drawn from the seed
    over all the others."""
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
        out.append((r.slot, seq[:n], np.asarray(eng.ssm_state[:, r.slot],
                                                np.float32)))
    return out


def state_errors(params, config, held, pad_to):
    """||engine state - reference state|| / ||reference state|| over all
    layers, a request."""
    w, m = config["widths"], config["multipliers"]

    @jax.jit
    def states(params, tokens, n):
        return R.hidden(params, tokens, w, m, n)[1]

    out = []
    for _, tokens, got in held:
        seq = np.zeros((pad_to,), np.int32)
        seq[:len(tokens)] = tokens
        want = np.asarray(states(params, jnp.asarray(seq), len(tokens)))
        out.append(float(np.linalg.norm(got - want)
                         / np.linalg.norm(want)))
    return out


def run(ctx):
    config, traffic = ctx["config"], ctx["traffic"]
    widths, seconds, tracer = config["widths"], ctx["seconds"], ctx["tracer"]
    cfg = h1_config(config)
    assert ctx["control"] in CONTROLS, ctx["control"]
    harness.mark(ctx, "imports done, chip held")
    params = W.make_params(widths, ctx["seed"], config["dtype"])
    if ctx["control"] == "weights_fp8":
        params = weights_through_fp8(params)
    eng = ServingEngine(
        params, cfg, ragged=True, seed=ctx["seed"] % 2 ** 31,
        ssm_state_dtype=("bfloat16" if ctx["control"] == "state_bf16"
                         else "float32"),
        **traffic["engine"])
    del params
    harness.mark(ctx, "weights and engine made")
    _warm_up(eng, cfg.vocab_size, traffic["engine"]["chunk"])
    harness.mark(ctx, "every program variant ran once")

    # -- the ramp, still set-up ---------------------------------------------
    loop = Loop(eng, T.ClosedLoop(traffic, cfg.vocab_size, ctx["seed"]))
    while time.perf_counter() - loop.t_start < traffic["ramp_s"]:
        loop.step()

    # -- the window ---------------------------------------------------------
    compiles0 = ctx["compiles"].count
    steps0, disp0, micro0 = (eng.engine_steps, eng.dispatches,
                             eng.decode_microsteps)
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
    trace = tracer.stop() if tracer else None
    compiles = ctx["compiles"].count - compiles0
    peak = harness.memory_peak_bytes(ctx["devices"])
    pool_peak = eng.prom.get("kv_pool_utilization_peak") or 0.0
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
    harness.log(f"[window] {len(spans)} engine steps in {span_s:.2f} s; "
                f"{out_tokens} output + {prefilled} prompt tokens; "
                f"{len(tpot)} tpot samples; {len(ok_done)} requests ended; "
                f"setup_s {setup_s:.2f}; compiles in window {compiles}; "
                f"state resets {eng.ssm_resets}")

    # -- the reference, once the engine is gone ------------------------------
    samples = _sample(ok_done, traffic["check_requests"], ctx["seed"])
    del eng, loop.eng
    gc.collect()
    t_ref = time.perf_counter()
    ref_params = W.make_params(widths, ctx["seed"], config["dtype"])
    pad_to = traffic["pad_to"]
    gaps = served_gaps(ref_params, config, samples, pad_to)
    errs = state_errors(ref_params, config, held, pad_to)
    flat = np.concatenate(gaps) if gaps else np.zeros((0,))
    harness.log(f"[reference] {len(samples)} requests, {flat.size} served "
                f"tokens, {len(errs)} states in flight, slots "
                f"{[slot for slot, _, _ in held]} "
                f"({[len(t) for _, t, _ in held]} tokens): "
                f"{[round(e, 6) for e in errs]}, "
                f"{time.perf_counter() - t_ref:.1f} s")
    limits = traffic["limits"]
    checks = [("served_logit_gap_max",
               float(flat.max()) if flat.size else None,
               limits["served_logit_gap_max"]),
              ("served_logit_gap_mean",
               float(flat.mean()) if flat.size else None,
               limits["served_logit_gap_mean"]),
              ("state_rel_err_max", max(errs) if errs else None,
               limits["state_rel_err_max"])]
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
                  "live_peak_bytes": peak},
    }
