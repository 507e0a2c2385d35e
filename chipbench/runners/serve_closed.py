"""One chip: `ServingEngine(ragged=True)` under a closed loop, a fixed
number of clients each sending its next request the moment the last one
ends. One process, one thread: the loop submits what is due, then calls
`eng.step()`; tokens of a step are handed over when the step returns."""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.inference import ServingEngine

from chipbench import harness, traffic as T, weights as W, yardstick as Y
from chipbench.reference import gpt as R
from chipbench.runners.train_dense import gpt_config


class Loop:
    """The clients, the engine, and the log of what was handed over."""

    def __init__(self, eng, gen):
        self.eng, self.gen = eng, gen
        self.due = {seat: gen.start_of(seat) for seat in range(gen.clients)}
        self.seat_of, self.req, self._seated = {}, {}, set()
        self.deliveries, self.first_due = {}, {}
        self.submitted, self.late_ms = [], []
        self.finished, self._fresh = [], []
        self.step_spans, self.busy = [], []
        self.t_start = time.perf_counter()

    def _on_token(self, rid, tok):
        self._fresh.append(rid)

    def submit_due(self, now):
        for seat, due in list(self.due.items()):
            if now - self.t_start < due:
                continue
            del self.due[seat]
            prompt, answer = self.gen.next_request(
                seat_first=seat not in self._seated)
            self._seated.add(seat)
            rid = self.eng.add_request(prompt, answer,
                                       on_token=self._on_token)
            made = time.perf_counter()
            self.seat_of[rid] = seat
            self.req[rid] = (prompt, answer)
            self.deliveries[rid] = []
            self.first_due[rid] = self.t_start + due
            self.submitted.append((made, rid))
            self.late_ms.append((made - (self.t_start + due)) * 1e3)

    def step(self, traced=False):
        with harness.span("client_loop", traced):
            self.submit_due(time.perf_counter())
        t0 = time.perf_counter()
        with harness.span("engine_step", traced):
            ended = self.eng.step()
        t1 = time.perf_counter()
        self.step_spans.append((t0, t1))
        self.busy.append(self.eng.prom.get("running_requests") or 0.0)
        for rid in self._fresh:
            self.deliveries[rid].append(t1)
        self._fresh = []
        for r in ended:
            self.finished.append((t1, r))
            seat = self.seat_of.get(r.rid)
            if seat is not None:    # its client sends again at once
                self.due[seat] = t1 - self.t_start
        return t1


def _warm_up(eng, vocab, chunk):
    """One step of every unified-program variant the cell can reach: each
    burst size, with a prefill row in the step and without. The choice of
    `_pick_burst` is overridden from outside for these steps only."""
    rng = np.random.default_rng(0)
    eng.add_request(rng.integers(0, vocab, chunk, dtype=np.int32), 10 ** 6)
    for k in eng._burst_sizes(eng.decode_burst):
        eng._pick_burst = lambda n_prefilling, k=k: k
        eng.add_request(rng.integers(0, vocab, 2 * chunk, dtype=np.int32),
                        2 * k + 2)
        for _ in range(5):
            eng.step()
    del eng._pick_burst
    eng.cancel_all("warm-up")
    eng.step()
    assert not eng.has_work()


def served_gaps(params, n_heads, samples, pad_to):
    """For each sampled request, how far each served token's logit lies
    below the reference's best at its position, given the served prefix.
    One padded forward of the plain reference a request."""
    @jax.jit
    def gaps(params, tokens):
        logits = R.forward(params, tokens[None], n_heads=n_heads)[0]
        picked = jnp.take_along_axis(logits[:-1], tokens[1:, None], -1)[:, 0]
        return jnp.max(logits[:-1], -1) - picked

    out = []
    for prompt, output in samples:
        seq = np.zeros((pad_to,), np.int32)
        n = len(prompt) + len(output)
        seq[:len(prompt)] = prompt
        seq[len(prompt):n] = output
        g = np.asarray(gaps(params, jnp.asarray(seq)))
        out.append(g[len(prompt) - 1:n - 1])
    return out


def run(ctx):
    config, traffic = ctx["config"], ctx["traffic"]
    widths, seconds, tracer = config["widths"], ctx["seconds"], ctx["tracer"]
    cfg = gpt_config(config)
    assert ctx["control"] in (None, "int8"), ctx["control"]
    harness.mark(ctx, "imports done, chip held")
    params = W.make_params(widths, ctx["seed"], config["dtype"])
    eng = ServingEngine(params, cfg, ragged=True, seed=ctx["seed"] % 2 ** 31,
                        int8=(ctx["control"] == "int8"), **traffic["engine"])
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
    serve_tok_s = (out_tokens + prefilled) / span_s
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
                f"setup_s {setup_s:.2f}; compiles in window {compiles}")

    # -- the reference, once the engine is gone ------------------------------
    samples = _sample(ok_done, traffic["check_requests"], ctx["seed"])
    del eng, loop.eng
    gc.collect()
    t_ref = time.perf_counter()
    ref_params = W.make_params(widths, ctx["seed"], config["dtype"])
    gaps = served_gaps(ref_params, widths["num_heads"], samples,
                       widths["max_seq_len"])
    flat = np.concatenate(gaps) if gaps else np.zeros((0,))
    harness.log(f"[reference] {len(samples)} requests, {flat.size} served "
                f"tokens, {time.perf_counter() - t_ref:.1f} s")
    limits = traffic["limits"]
    checks = [("served_logit_gap_max",
               float(flat.max()) if flat.size else None,
               limits["served_logit_gap_max"]),
              ("served_logit_gap_mean",
               float(flat.mean()) if flat.size else None,
               limits["served_logit_gap_mean"])]
    n_steps = counters1[0] - steps0
    return {
        "devices": ctx["devices"], "checks": checks, "trace": trace,
        "attempted": len(attempted), "failed": failed,
        "memory_peak_bytes": peak,
        "e2e": {"serve_tok_s": serve_tok_s, "setup_s": setup_s,
                "tpot_p95_ms": Y.percentile(tpot, 95)},
        "facts": {"engine_step_ms": [(b - a) * 1e3 for a, b in spans],
                  "gen_late_ms": [ms for (made, _), ms in
                                  zip(loop.submitted, loop.late_ms)
                                  if inside(made)],
                  "ttft_ms": ttft_ms, "tpot_ms": tpot,
                  "slot_busy_pct": [100.0 * b / traffic["engine"]["max_batch"]
                                    for b in loop.busy[n_busy0:]],
                  "pool_peak_pct": 100.0 * pool_peak,
                  "engine_steps": n_steps,
                  "dispatches": counters1[1] - disp0,
                  "decode_microsteps": counters1[2] - micro0,
                  "compiles_in_window": compiles,
                  "live_peak_bytes": peak},
    }


def _sample(ended, n, seed):
    """(prompt, output) of the longest request that ended in the window and
    of n - 1 more drawn from the seed."""
    if not ended:
        return []
    ended = sorted(ended, key=lambda r: r.rid)
    longest = max(ended, key=lambda r: len(r.prompt) + len(r.output))
    rest = [r for r in ended if r is not longest]
    rng = np.random.default_rng([int(seed), 5])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [(np.asarray(r.prompt), np.asarray(r.output, np.int32))
            for r in [longest] + [rest[i] for i in pick]]
