"""One chip: `ServingEngine(ragged=True, prefix_share=True)` serving a
DeepSeek-V2 configuration (a latent paged cache, a leading dense layer, a
group-limited router of which this chip holds one group) to a closed loop
of short questions about a few long documents (`traffic_docqa`). The
clients' loop and the warm-up are `serve_closed`'s; the window is
`serve_closed.run`'s, line for line, as in the three runners before it.
What differs:

  * SET-UP prefills every document once (a request of the document alone,
    one token asked), so its full pages are registered and stay resident,
    cached-free; in the window the document part of every prompt is a
    prefix hit and only the question (and the document's partial last
    page) is prefilled, against the long latent prefix;
  * `serve_tok_s` counts what the program COMPUTED: a request's prompt
    LESS its prefix hit (`Request.prefix_hit_tokens`, the engine's own
    record) plus the output tokens handed over (`prefilled_in_window`: the
    yardstick's two-snapshot count with the hits taken out; a 17k-token
    hit is not 17k served tokens);
  * preemptions and evictions of cached pages
    (`ServingEngine.cache_evictions`: a document's pages, or a finished
    question's own full pages, which are cached too) are facts of the
    window: the cell is sized so that neither happens;
  * `correct` has two parts, on what the timed path produced:
    (i)  each served token's logit against the reference's best at its
         position, the reference FOLLOWING the routing the timed path
         reported (the request's own for the positions it ran, the
         document's set-up request's for its hit);
    (ii) the program's picks against the reference's own at every
         (position, layer) the request ran: `route_clear_mismatches` (pairs
         that differ although the reference's margin exceeds the traffic
         file's `route_margin`, must be 0), `route_flip_share` (all
         differing pairs over all pairs) and the FIRST expert layer's;
    (iii) the CACHE itself: the latent pages the timed path wrote for the
         sampled requests' documents (a few stretches of 16 positions a
         document, every layer, read from the pools before the engine is
         freed) against the reference's own [c | k_r] at those positions:
         `latent_rel_err_max` over the layers and `latent_rel_err_first`,
         layer 0's, whose input is the embedding's own bfloat16 values.
         With seeded weights attention over 8k-33k positions is a broad
         average, so the logits hardly feel the page's precision; the page
         does.

Two lower-precision readings have to come out as not correct:
`--control weights_fp8` (every matrix rounded through float8) and
`--control cache_fp8` (the latent page, the compressed vector and the
rotary key, rounded through float8 e4m3 on its way into the pool: the
mechanism's own)."""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

# the program's module first: a program without it fails here, at the
# import, before anything holds the chip
from paddle_tpu.models import deepseek_v2 as DS
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference import ragged_step as RS

from chipbench import harness, traffic_docqa as TD, yardstick as Y
from chipbench import weights_deepseek_v2 as W
from chipbench.reference import deepseek_v2 as R
from chipbench.runners.serve_closed import Loop, _warm_up
from chipbench.runners.serve_closed_h1 import _through_fp8

CONTROLS = (None, "weights_fp8", "cache_fp8")


def weights_through_fp8(tree):
    """Every matrix, in place, a leaf at a time; the norms' gains stay."""
    if isinstance(tree, tuple):
        for sub in tree:
            weights_through_fp8(sub)
        return tree
    for k, v in tree.items():
        if isinstance(v, (dict, tuple)):
            weights_through_fp8(v)
        elif not k.endswith("_g"):
            tree[k] = _through_fp8(v)
    return tree


def cache_through_fp8():
    """The timed path's append takes the latent through e4m3 (no scale:
    the compressed vector is normed, the rotary key O(1))."""
    append = RS.latent_append

    def rounded(c_pool, r_pool, c, r, *a, **kw):
        def fp8(x):
            return x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        return append(c_pool, r_pool, fp8(c), fp8(r), *a, **kw)
    RS.latent_append = rounded


def dsv2_config(config):
    return DS.DeepseekV2Config(
        **config["widths"],
        dtype=jnp.dtype(config["dtype"]),
        param_dtype=jnp.dtype(config["dtype"]))


def prefilled_in_window(first_in, hit, at_start, at_end):
    """`yardstick.prefill_tokens_in_window` with every request's prefix
    hit taken out: prompt tokens the program RAN between two snapshots.
    first_in: {rid: prompt length} of the requests whose first token
    arrived inside the window; hit: {rid: tokens found in shared pages}."""
    def unfinished(snap):
        return {r["rid"]: max(r["prefill_done"] - hit.get(r["rid"], 0), 0)
                for r in snap["slots"]
                if r is not None and r["emitted"] == 0}
    before, after = unfinished(at_start), unfinished(at_end)
    total = sum(n - hit.get(rid, 0) for rid, n in first_in.items())
    total -= sum(done for rid, done in before.items()
                 if rid in first_in or rid in after)
    return total + sum(after.values())


def prefill_documents(eng, docs):
    """Every document through the engine once, alone; returns its routing
    [len, L, k] (the positions of its full pages are what later hits
    inherit)."""
    made = []
    for doc in docs:
        rid = eng.add_request(doc, 1, keep_routing=True)
        made.append(next(r for r in eng.queue if r.rid == rid))
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
    assert all(r.status == "ok" and len(r.output) == 1 for r in made)
    pages = [[eng._prefix_cache[h] for h in eng._chain_of(r)] for r in made]
    return [r.routing[:len(r.prompt)] for r in made], pages, steps


def cached_entries(eng, pages, rope, stretches, seed):
    """`stretches` stretches of R.LATENT_KEEP positions of a document whose
    full pages are `pages` (the first, the rest from the seed): (their
    first positions, the pools' [L, stretches, keep, C + rope] there)."""
    keep, bs = R.LATENT_KEEP, eng.bs
    rng = np.random.default_rng([int(seed), 7])
    rest = np.arange(1, len(pages) * bs // keep)
    at = np.concatenate([[0], np.sort(rng.choice(
        rest, stretches - 1, replace=len(rest) < stretches - 1)) * keep]
    ).astype(np.int64)
    page, off = np.asarray(pages)[at // bs], at % bs
    rows = off[:, None] + np.arange(keep)[None, :]
    c = eng.k_pools[:, 0, page[:, None], rows]
    r = eng.v_pools[:, 0, page[:, None], rows][..., :rope]
    return at, np.asarray(jnp.concatenate([c, r], -1), np.float32)


def sample_requests(ended, doc_of, doc_lens, n, seed):
    """The request on the longest document that has one, and n - 1 more
    in the seed's order, each on a document no earlier sample is on while
    such a request is left (the reference runs a whole document a
    sample: two samples on one document would pay for it twice and hold
    the same cached pages to it twice)."""
    if not ended:
        return []
    ended = sorted(ended, key=lambda r: r.rid)
    longest = max(ended, key=lambda r: (doc_lens[doc_of[r.rid]], -r.rid))
    rest = [r for r in ended if r is not longest]
    rng = np.random.default_rng([int(seed), 5])
    order = [rest[i] for i in rng.permutation(len(rest))]
    samples, again = [longest], []
    for r in order:
        if doc_of[r.rid] in {doc_of[s.rid] for s in samples}:
            again.append(r)
        else:
            samples.append(r)
    return (samples + again)[:n]


def against_reference(params, widths, samples, doc_routing, doc_of, cached,
                      pad_to, block, margin_min):
    """(gaps a request, (differing pairs a layer, clear mismatches, pairs,
    widest margin of a differing pair), the cached entries' error a
    request and layer); cached: `cached_entries` of each sample's
    document."""
    gaps, clear, pairs, widest, errs = [], 0, 0, 0.0, []
    layers = widths["num_layers"] - widths["first_k_dense"]
    differ = np.zeros((layers,), np.int64)
    for r in samples:
        prompt, output = np.asarray(r.prompt), np.asarray(r.output, np.int32)
        n, hit = len(prompt) + len(output), r.prefix_hit_tokens
        tokens = np.zeros((pad_to,), np.int32)
        tokens[:n] = np.concatenate([prompt, output])
        routing = np.full((pad_to,) + r.routing.shape[1:], -1, np.int32)
        routing[:n - 1] = r.routing[:n - 1]
        routing[:hit] = doc_routing[doc_of[r.rid]][:hit]
        assert (routing[:n - 1] >= 0).all(), "a position without routing"
        at, got = cached[r.rid]
        x, own, margin, want = R.hidden(
            params, jnp.asarray(tokens), widths, jnp.asarray(routing), n=n,
            block=block, keep=at)
        want = np.asarray(want)
        errs.append([float(np.linalg.norm(g - w) / np.linalg.norm(w))
                     for g, w in zip(got, want)])
        served = slice(len(prompt) - 1, n - 1)
        best, picked = R.best_and_picked(
            params, x[served], jnp.asarray(tokens[len(prompt):n]))
        gaps.append(np.asarray(best - picked))
        ran = slice(hit, n - 1)     # the positions THIS request ran
        own, margin = np.asarray(own)[ran], np.asarray(margin)[ran]
        other = (np.sort(own, -1) != np.sort(r.routing[ran], -1)).any(-1)
        differ += other.sum(0)
        clear += int((other & (margin > margin_min)).sum())
        pairs += other.shape[0]
        widest = max(widest, float(np.max(margin, where=other, initial=0.0)))
    return gaps, (differ, clear, pairs, widest), np.asarray(
        errs, np.float64).reshape(len(errs), -1)


def run(ctx):
    config, traffic = ctx["config"], ctx["traffic"]
    widths, seconds, tracer = config["widths"], ctx["seconds"], ctx["tracer"]
    assert ctx["control"] in CONTROLS, ctx["control"]
    cfg = dsv2_config(config)
    harness.mark(ctx, "imports done, chip held")
    params = W.make_params(widths, ctx["seed"], config["dtype"])
    if ctx["control"] == "weights_fp8":
        params = weights_through_fp8(params)
    if ctx["control"] == "cache_fp8":
        cache_through_fp8()
    eng = ServingEngine(params, cfg, ragged=True, prefix_share=True,
                        seed=ctx["seed"] % 2 ** 31, **traffic["engine"])
    del params
    harness.mark(ctx, "weights and engine made")
    _warm_up(eng, cfg.vocab_size, traffic["engine"]["chunk"])
    harness.mark(ctx, "every program variant ran once")
    docs = TD.documents(traffic, cfg.vocab_size, ctx["seed"])
    doc_routing, doc_pages, steps = prefill_documents(eng, docs)
    harness.mark(ctx, f"{len(docs)} documents, {sum(map(len, docs))} tokens, "
                      f"prefilled in {steps} steps; "
                      f"{len(eng._cached_free)} pages cached")

    # every request keeps its routing, and the runner keeps the request
    # (its prefix hit is the engine's record): the loop and the warm-up
    # are `serve_closed`'s and know neither
    requests, add = {}, eng.add_request

    def add_request(prompt, answer, **kw):
        rid = add(prompt, answer, keep_routing=True, **kw)
        requests[rid] = next((r for r in reversed(eng.queue)
                              if r.rid == rid), None)
        return rid
    eng.add_request = add_request

    # -- the ramp, still set-up ---------------------------------------------
    gen = TD.DocQA(traffic, cfg.vocab_size, ctx["seed"], docs)
    loop = Loop(eng, gen)
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
                float(eng._moe_load.sum()), eng.moe_local_tokens,
                eng.moe_tokens, eng.cache_evictions,
                eng.prom.get("requests_preempted_total") or 0.0)

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
    work, work1, seen = [0, 0], (0, 0), None    # packed q and kv tokens
    while True:
        if tracer and not tracer.on and \
                time.perf_counter() - t_w0 >= trace_from:
            tracer.start()
        t1 = loop.step(traced=bool(tracer and tracer.on))
        flight = eng._flight    # the step this call dispatched, if any
        if flight is not None and flight is not seen:
            seen = flight
            work[0] += flight.q_tokens
            work[1] += flight.kv_tokens
        if t1 - t_w0 > seconds:
            break
        work1 = tuple(work)
        t_last, snap1 = t1, eng.snapshot()
        counters1 = (eng.engine_steps, eng.dispatches, eng.decode_microsteps)
        moe1 = counters()
    trace = tracer.stop() if tracer else None
    gc.callbacks.remove(on_gc)
    compiles = ctx["compiles"].count - compiles0
    peak = harness.memory_peak_bytes(ctx["devices"])
    pool_peak = eng.prom.get("kv_pool_utilization_peak") or 0.0
    evicted, preempted = moe1[5] - moe0[5], moe1[6] - moe0[6]

    # -- what the window handed over ----------------------------------------
    def inside(t):
        return t_w0 <= t <= t_last
    out_tokens = sum(inside(t) for ts in loop.deliveries.values()
                     for t in ts)
    first_in = {rid: len(loop.req[rid][0])
                for rid, ts in loop.deliveries.items()
                if ts and inside(ts[0])}
    hit = {rid: r.prefix_hit_tokens for rid, r in requests.items()
           if r is not None}
    prefilled = prefilled_in_window(first_in, hit, snap0, snap1)
    uncut = Y.prefill_tokens_in_window(first_in, snap0, snap1)
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
    layers = widths["num_layers"] - widths["first_k_dense"]
    passes = max(moe1[1] - moe0[1], 1)
    slots = (hi - lo) * layers * passes
    admitted = [rid for rid in attempted if rid in hit]
    prompt_tokens = sum(len(loop.req[rid][0]) for rid in admitted)
    harness.log(f"[work] q_tokens {work1[0]} kv_tokens {work1[1]} packed in "
                f"the window's dispatches")
    step_ms = np.asarray([(b - a) * 1e3 for a, b in spans] or [0.0])
    # a slow host or chip shows here, in the run itself: the steps' count
    # and their wall time, beside the work they were given
    harness.log(f"[steps] {len(spans)} in the window, wall ms a step: mean "
                f"{step_ms.mean():.2f}, p50 {np.median(step_ms):.2f}, p95 "
                f"{np.percentile(step_ms, 95):.2f}, max {step_ms.max():.2f}; "
                f"{work1[1] / max(len(spans), 1):.0f} kv tokens and "
                f"{work1[0] / max(len(spans), 1):.1f} q tokens a step, "
                f"{1e6 * step_ms.sum() / max(work1[1], 1):.2f} ns a kv token")
    harness.log(f"[window] {len(spans)} engine steps in {span_s:.2f} s; "
                f"{out_tokens} output + {prefilled} prompt tokens run "
                f"({uncut} with their prefix hits); {len(tpot)} tpot "
                f"samples; {len(ok_done)} requests ended; setup_s "
                f"{setup_s:.2f}; compiles in window {compiles}; "
                f"preemptions {preempted}; cached pages evicted {evicted}; "
                f"experts touched {moe1[0] - moe0[0]} of {slots}; "
                f"collector pauses: full {full} ms, young "
                f"{sum(young):.1f} ms in {len(young)}")

    # -- the reference, once the engine is gone ------------------------------
    doc_of = dict(zip((rid for _, rid in loop.submitted), gen.document_of))
    samples = sample_requests(ok_done, doc_of, list(map(len, docs)),
                              traffic["check_requests"], ctx["seed"])
    cached = {r.rid: cached_entries(
        eng, doc_pages[doc_of[r.rid]], widths["qk_rope_head_dim"],
        traffic["check_stretches"], ctx["seed"] + r.rid) for r in samples}
    del eng, loop.eng, add
    gc.collect()
    t_ref = time.perf_counter()
    ref_params = W.make_params(widths, ctx["seed"], config["dtype"])
    gaps, (differ, clear, pairs, widest), errs = against_reference(
        ref_params, widths, samples, doc_routing, doc_of, cached,
        traffic["pad_to"], traffic["reference_block"],
        traffic["route_margin"])
    flat = np.concatenate(gaps) if gaps else np.zeros((0,))
    by_layer = differ / max(pairs, 1)       # the flip share of each layer
    harness.log(f"[reference] {len(samples)} requests on documents "
                f"{[doc_of[r.rid] for r in samples]}, {flat.size} served "
                f"tokens, {pairs} positions x {layers} layers of which "
                f"{int(differ.sum())} differ (a layer: "
                f"{[round(float(x), 4) for x in by_layer]}; widest margin "
                f"{widest:.4f}) and {clear} clearly; the cached entries' "
                f"error a request and layer {np.round(errs, 5).tolist()}, "
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
               limits["route_flip_share_first"]),
              ("latent_rel_err_max",
               float(errs.max()) if errs.size else None,
               limits["latent_rel_err_max"]),
              ("latent_rel_err_first",
               float(errs[:, 0].max()) if errs.size else None,
               limits["latent_rel_err_first"])]
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
                      (moe1[2] - moe0[2]) / (layers * passes),
                  "moe_local_token_pct": 100.0 * (moe1[3] - moe0[3])
                      / max(moe1[4] - moe0[4], 1),
                  "prefix_hit_pct": (100.0 * sum(hit[rid] for rid in admitted)
                                     / prompt_tokens if prompt_tokens
                                     else None),
                  "cache_evictions": evicted,
                  "preemptions": preempted},
    }
