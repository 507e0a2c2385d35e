"""Serving bench: continuous batching + chunked prefill vs static batching
(VERDICT r2 #4, widened per r3 #8: >=64 requests, MIXED prompt lengths)
with per-request latency percentiles (p50/p95/p99), plus the engine under
overload, behind the router, and with prefix sharing and speculation.

Workload: 64 requests, prompt lengths drawn from {32, 48, 64, 96}, ragged
output lengths U[8, 96] — the variance that makes static batches idle at
the barrier. The static baseline is the STRONGEST version: requests
bucketed by prompt length, each batch padded only to its own max.
Model: GPT ~125M-shape (bf16 on TPU); `--shape gpt1p3b` runs the
flagship 1.3B shape on-chip (VERDICT weak #2 — the regime where decode
is genuinely weight-bound).

Run: `python benchmarks/serving_bench.py` — one JSON line. bench.py
imports the `run_*` sections directly.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _device():
    """Every result names the device it ran on: a CPU run of these
    helpers (tier-1 drives them for counts and parity) can then never be
    read as a device metric."""
    from paddle_tpu.device import device_tag
    return device_tag()


def _pct(v, q):
    return round(float(np.percentile(v, q)), 3)


def _lat_stats(lat):
    return {"mean": round(float(np.mean(lat)), 3), "p50": _pct(lat, 50),
            "p95": _pct(lat, 95), "p99": _pct(lat, 99)}


def run_overload_comparison(params, cfg, mk, batch, *, n_req: int = 64,
                            load_factor: float = 2.0,
                            slo_factor: float = 3.0, seed: int = 0):
    """Overload section (ISSUE 13): offered load ~``load_factor``x the
    engine's measured capacity, shedding ON (bounded queue + SLO-driven
    shed) vs OFF — admitted-request TTFT percentiles, shed rate and
    goodput. The point the numbers make: without shedding EVERY request's
    TTFT grows with the backlog (p99 collapses), with shedding the engine
    sacrifices a counted fraction of arrivals so the ADMITTED requests
    keep meeting the SLO.

    Calibration: one closed wave of exactly ``batch`` requests (all slots
    busy, no queue) measures the per-wave service time T_req ->
    capacity ~ batch/T_req req/s, SLO = ``slo_factor`` x T_req. The shed
    engine runs the PURE SLO policy (queue_max=0 — no static bound): the
    TTFT window-p95 crossing the SLO headroom is what trims the queue,
    so the mechanism under test is the one doing the work."""
    import jax
    from paddle_tpu.inference.serving import ServingEngine

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, (int(rng.choice((8, 16))),))
               for _ in range(n_req)]
    news = rng.randint(8, 17, (n_req,)).tolist()

    def make_engine(**kw):
        return ServingEngine(params, cfg, max_batch=batch,
                             adaptive_mix=False, **mk, **kw)

    # calibrate: warm the programs, then time one full-batch closed wave
    eng = make_engine()
    for p, n in zip(prompts[:batch], news[:batch]):
        eng.add_request(p, n)
    eng.run()                                   # compile wave
    t0 = time.perf_counter()
    for p, n in zip(prompts[:batch], news[:batch]):
        eng.add_request(p, n)
    eng.run()
    t_req = max(time.perf_counter() - t0, 1e-6)
    slo_s = slo_factor * t_req
    interval = t_req / (load_factor * batch)    # 2x offered request rate

    def open_loop(**kw):
        eng = make_engine(**kw)
        for p, n in zip(prompts[:batch], news[:batch]):
            eng.add_request(p, n)
        eng.run()                               # fresh-engine compile wave
        reported = {}
        t0 = time.perf_counter()
        i = 0
        while i < n_req or eng.has_work():
            now = time.perf_counter() - t0
            while i < n_req and now >= i * interval:
                eng.add_request(prompts[i], news[i])
                i += 1
                now = time.perf_counter() - t0
            if eng.has_work():
                for r in eng.step():
                    reported[r.rid] = r
            elif i < n_req:
                time.sleep(max(i * interval - now, 0.0))
        wall = max(time.perf_counter() - t0, 1e-9)
        admitted = [r for r in reported.values() if r.status == "ok"]
        shed = [r for r in reported.values() if r.status == "shed"]
        ttfts = [r.ttft_s for r in admitted if r.ttft_s is not None]
        # SLO-goodput: tokens of requests that MET the TTFT SLO — the
        # number a latency-bound service actually sells. An unbounded
        # queue "completes everything" but past the SLO, which counts
        # for nothing here.
        in_slo = [r for r in admitted
                  if r.ttft_s is not None and r.ttft_s <= slo_s]
        out = {"admitted": len(admitted), "shed": len(shed),
               "shed_rate": round(len(shed) / max(len(reported), 1), 3),
               "goodput_tokens_per_sec": round(
                   sum(len(r.output) for r in admitted) / wall, 1),
               "slo_goodput_tokens_per_sec": round(
                   sum(len(r.output) for r in in_slo) / wall, 1),
               "requests_meeting_slo": len(in_slo),
               "wall_s": round(wall, 3)}
        if ttfts:
            out["ttft_s"] = _lat_stats(ttfts)
            out["p99_within_slo"] = bool(_pct(ttfts, 99) <= slo_s)
        return out

    shed_on = open_loop(shed=True, ttft_slo_s=slo_s)
    shed_off = open_loop()
    return {
        "device": _device(),
        "offered_load_x_capacity": load_factor,
        "t_req_s": round(t_req, 3),
        "ttft_slo_s": round(slo_s, 3),
        "config": f"{n_req} reqs, arrival interval {interval * 1e3:.1f} "
                  f"ms ({load_factor}x the measured {batch}-slot "
                  "capacity), shed policy: TTFT window-p95 vs SLO "
                  f"({slo_factor}x T_req, headroom 0.5, queue trimmed "
                  "to the newest max_batch)",
        "shed_on": shed_on,
        "shed_off": shed_off,
    }


def run_router_comparison(params, cfg, mk, batch, *, n_req: int = 32,
                          n_replicas: int = 2, seed: int = 0):
    """Router section (ISSUE 16): a 2-replica fleet under full offered
    load (closed loop, every request queued at t0 — ~2x one replica's
    capacity), one replica killed mid-run vs the same fleet left alone.
    The kill is a one-shot ``serving/step`` fault armed once ~1/3 of the
    tokens are out, so the death lands mid-generation with journaled
    prefixes in flight; the router's failover replays those requests
    onto the survivor and respawns the casualty. The numbers the section
    makes: goodput under a replica death stays a FRACTION of the
    uninterrupted fleet's (not zero, not halved forever), every request
    still completes, and the outputs are bitwise the uninterrupted
    run's — the exactly-once contract priced in tokens/s."""
    from paddle_tpu.distributed.resilience import faults
    from paddle_tpu.inference.router import ReplicaSet, Router
    from paddle_tpu.inference.serving import ServingEngine

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, (int(rng.choice((8, 16))),))
               for _ in range(n_req)]
    news = rng.randint(8, 17, (n_req,)).tolist()
    total = sum(news)

    def make_engine():
        return ServingEngine(params, cfg, max_batch=batch,
                             adaptive_mix=False, **mk)

    def run_fleet(kill_at_tokens=None):
        router = Router(ReplicaSet.in_process(make_engine, n=n_replicas))
        # per-fleet compile wave: every replica sees work before the clock
        for p, n in zip(prompts[:n_replicas * batch],
                        news[:n_replicas * batch]):
            router.submit(p, n)
        while router.has_work():
            router.step()
        lids = [router.submit(p, n) for p, n in zip(prompts, news)]
        killed = False
        t0 = time.perf_counter()
        try:
            while router.has_work():
                router.step()
                if (kill_at_tokens is not None and not killed
                        and sum(len(router.delivered[lid])
                                for lid in lids) >= kill_at_tokens):
                    # one-shot: the next engine poll hard-fails that
                    # replica -> journaled failover onto the survivor
                    faults.configure("serving/step")
                    killed = True
        finally:
            faults.configure("")
        wall = max(time.perf_counter() - t0, 1e-9)
        toks = sum(len(router.delivered[lid]) for lid in lids)
        out = {"wall_s": round(wall, 3),
               "goodput_tokens_per_sec": round(toks / wall, 1),
               "completed": sum(1 for lid in lids
                                if router.statuses[lid] == "done"),
               "requests": n_req,
               "failovers": router.failovers,
               "requeued": router.requeues}
        results = {i: list(router.delivered[lid])
                   for i, lid in enumerate(lids)}
        return out, results

    uninterrupted, res_u = run_fleet()
    disrupted, res_k = run_fleet(kill_at_tokens=total // 3)
    return {
        "device": _device(),
        "config": f"{n_replicas} in-process replicas x {batch} slots, "
                  f"{n_req} reqs closed-loop, kill = one-shot "
                  "serving/step fault armed after ~1/3 of tokens; "
                  "failover replays journaled in-flight requests onto "
                  "the survivor, casualty respawns on its journal",
        "uninterrupted": uninterrupted,
        "replica_killed": disrupted,
        "goodput_ratio_killed_vs_uninterrupted": round(
            disrupted["goodput_tokens_per_sec"]
            / max(uninterrupted["goodput_tokens_per_sec"], 1e-9), 3),
        "outputs_bitwise_equal": res_u == res_k,
    }


def run_prefix_spec_comparison(params, cfg, mk, batch, *, seed=0):
    """Prefix sharing + speculative decoding section (ISSUE 17), two legs:

    (a) Admission multiplier at a FIXED pool: every request opens with the
    same 4-page system prompt, the pool is sized so the unshared engine
    can only hold ~4 residents (5 pages each), and the metric is PEAK
    concurrently-resident requests with sharing on vs off. Sharing turns
    the 4 prompt pages into one refcounted copy, so each extra resident
    costs 1 fresh tail page instead of 5 — the multiplier is the capacity
    a fleet gets back from templated traffic without buying HBM.

    (b) Tokens per decode step with speculation: same greedy workload,
    ``decode_burst=1`` on both sides so one engine step = one model
    forward per row. The headline proposer is :class:`ReplayCache` primed
    by an identical first wave (repeat/retry traffic — the same workload
    prefix sharing multiplies); the draft-free n-gram proposer runs
    alongside. Exact-match acceptance keeps every variant bitwise equal
    to plain decode — speculation only moves tokens/step, never text."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.inference.speculative import ReplayCache

    bs = mk["block_size"]
    rng = np.random.RandomState(seed)

    # ---- leg (a): admission at a fixed pool, sharing on vs off -------
    sys_prompt = rng.randint(0, cfg.vocab_size, (4 * bs,))
    n_req = 24
    prompts = [np.concatenate(
        [sys_prompt, rng.randint(0, cfg.vocab_size, (bs // 2,))]
    ).astype(np.int32) for _ in range(n_req)]
    max_new = bs // 2
    pages_per_req = (4 * bs + bs // 2 + max_new + bs - 1) // bs   # = 5
    usable = 4 * pages_per_req + 1   # unshared engine caps at 4 residents
    slots = 16

    def residency(share):
        eng = ServingEngine(
            params, cfg, max_batch=slots, adaptive_mix=False,
            block_size=bs, num_blocks=usable + 1,
            max_blocks_per_seq=mk["max_blocks_per_seq"], chunk=mk["chunk"],
            # burst=1 so a resident decodes across many engine steps —
            # peak residency is then observable at step boundaries
            decode_burst=1,
            token_budget=slots * (1 + mk["chunk"]),
            prefix_share=share, pool_audit=True)
        # primer wave: ONE request registers the system prompt's full
        # pages in the prefix cache (and compiles the programs); with
        # sharing off it is just a warmup
        eng.add_request(prompts[0], max_new)
        eng.run()
        rids = [eng.add_request(p, max_new) for p in prompts]
        outs, peak, shared_peak = {}, 0, 0
        while eng.has_work():
            for r in eng.step():
                outs[r.rid] = r.output
            # the engine's own gauge: reading `slots` would settle the
            # step in flight every iteration
            peak = max(peak, int(eng.prom.get("running_requests") or 0))
            shared_peak = max(shared_peak, int((eng.refcount > 1).sum()))
        return (peak, shared_peak, [outs[rid] for rid in rids],
                usable - eng.free_pages())

    peak_off, _, outs_off, leak_off = residency(False)
    peak_on, shared_on, outs_on, leak_on = residency(True)

    # ---- leg (b): tokens per decode step, speculation on vs off ------
    rng2 = np.random.RandomState(seed + 1)
    prompts2 = [rng2.randint(0, cfg.vocab_size, (bs,)).astype(np.int32)
                for _ in range(batch)]
    new2 = 2 * bs
    total2 = batch * new2

    def mk_eng(k=0, proposer=None):
        return ServingEngine(
            params, cfg, max_batch=batch, adaptive_mix=False,
            block_size=bs, num_blocks=mk["num_blocks"],
            max_blocks_per_seq=mk["max_blocks_per_seq"], chunk=mk["chunk"],
            decode_burst=1, token_budget=batch * (1 + mk["chunk"]),
            spec_decode_k=k, proposer=proposer)

    def wave(eng, record_into=None):
        rids = [eng.add_request(p, new2) for p in prompts2]
        outs = {}
        s0 = eng.engine_steps
        p0, a0 = eng.spec_proposed, eng.spec_accepted
        t0 = time.perf_counter()
        while eng.has_work():
            for r in eng.step():
                outs[r.rid] = r.output
        dt = time.perf_counter() - t0
        if record_into is not None:
            for p, rid in zip(prompts2, rids):
                record_into.record(p, outs[rid])
        return ([outs[rid] for rid in rids], eng.engine_steps - s0, dt,
                eng.spec_proposed - p0, eng.spec_accepted - a0)

    eng_plain = mk_eng()
    wave(eng_plain)                                   # compile wave
    outs_plain, steps_plain, dt_plain, _, _ = wave(eng_plain)

    cache = ReplayCache()
    eng_rep = mk_eng(k=3, proposer=cache)
    wave(eng_rep, record_into=cache)   # wave 1 primes the replay cache
    outs_rep, steps_rep, dt_rep, prop_r, acc_r = wave(eng_rep)

    eng_ng = mk_eng(k=3)                     # default prompt-lookup/ngram
    wave(eng_ng)
    outs_ng, steps_ng, dt_ng, prop_n, acc_n = wave(eng_ng)

    def spec_stats(steps, dt, prop=None, acc=None):
        out = {"tokens_per_decode_step":
               round(total2 / (steps * batch), 2),
               "engine_steps": steps, "wall_s": round(dt, 3)}
        if prop is not None:
            out.update(proposed=int(prop), accepted=int(acc),
                       acceptance_rate=round(acc / max(prop, 1), 3))
        return out

    return {
        "device": _device(),
        "prefix_sharing": {
            "config": f"{n_req} reqs sharing a {4 * bs}-token system "
                      f"prompt ({pages_per_req} pages/req unshared), "
                      f"pool {usable} pages, {slots} slots, prefix "
                      "cache primed by one completed request",
            "peak_resident_requests": {"share_off": peak_off,
                                       "share_on": peak_on},
            "admission_multiplier": round(peak_on / max(peak_off, 1), 2),
            "peak_shared_pages": shared_on,
            "outputs_match_share_off": outs_on == outs_off,
            "pages_leaked": {"share_off": int(leak_off),
                             "share_on": int(leak_on)},
        },
        "speculative": {
            "config": f"{batch} reqs x {new2} greedy tokens, k=3, "
                      "decode_burst=1 both sides (1 engine step = 1 "
                      "forward/row); replay = history proposer primed "
                      "by an identical first wave, ngram = draft-free "
                      "prompt lookup",
            "plain": spec_stats(steps_plain, dt_plain),
            "replay": spec_stats(steps_rep, dt_rep, prop_r, acc_r),
            "ngram": spec_stats(steps_ng, dt_ng, prop_n, acc_n),
            "step_reduction_replay_vs_plain":
                round(steps_plain / max(steps_rep, 1), 2),
            "outputs_bitwise_plain": {"replay": outs_rep == outs_plain,
                                      "ngram": outs_ng == outs_plain},
        },
    }


def scenario(on_tpu: bool, big: bool = False, shape: str = "auto"):
    """Workload + engine geometry per platform/shape. Returns
    (cfg, n_req, plens, out_hi, mk) — shared by main() and bench.py's
    serving section so the two agree. on_tpu=False is the tiny float32
    shape tier-1 tests drive on the CPU (counts and parity only — a CPU
    timing is never a device metric)."""
    import jax.numpy as jnp
    from paddle_tpu.models import gpt as G

    if shape == "gpt1p3b":
        # flagship 1.3B serving shape (VERDICT weak #2): decode is
        # weight-bound here — 2.6 GB of bf16 weights stream per decode
        # microstep vs ~25 MB of KV pages at ctx 512
        cfg = G.GPTConfig(vocab_size=32768, hidden_size=2048,
                          num_layers=24, num_heads=16, max_seq_len=1024,
                          dtype=jnp.bfloat16 if on_tpu else jnp.float32,
                          param_dtype=(jnp.bfloat16 if on_tpu
                                       else jnp.float32))
        n_req, plens, out_hi = 32, (128, 256, 512), 128
    elif on_tpu and big:
        # high-raggedness scenario (VERDICT r4 ask-10): 128 requests with
        # LONG mixed prompts — the regime where the paged kernel streams
        # only the blocks a sequence references while a dense baseline
        # reads every padded row
        cfg = G.GPTConfig(vocab_size=32768, hidden_size=768, num_layers=12,
                          num_heads=12, max_seq_len=1024,
                          dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        n_req, plens, out_hi = 128, (64, 128, 256, 512), 128
    elif on_tpu:
        cfg = G.GPTConfig(vocab_size=32768, hidden_size=768, num_layers=12,
                          num_heads=12, max_seq_len=512, dtype=jnp.bfloat16,
                          param_dtype=jnp.bfloat16)
        n_req, plens, out_hi = 64, (32, 48, 64, 96), 96
    else:
        cfg = G.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                          num_heads=4, max_seq_len=128, dtype=jnp.float32)
        n_req, plens, out_hi = 8, (8, 16), 16

    if shape == "gpt1p3b":
        mk = dict(block_size=32, num_blocks=200, max_blocks_per_seq=20,
                  chunk=128, decode_burst=32)
    elif big:
        # bigger pool for 512-token prompts; blocks sized so the pool
        # still fits comfortably next to the 125M params; the big
        # scenario also doubles the work per dispatch (chunk 128 prefill,
        # 32-token decode bursts)
        mk = dict(block_size=32, num_blocks=320, max_blocks_per_seq=24,
                  chunk=128, decode_burst=32)
    elif on_tpu:
        mk = dict(block_size=16, num_blocks=192, max_blocks_per_seq=16,
                  chunk=32, decode_burst=16)
    else:
        # CPU smoke: shorter chunk — the interpreter-mode ragged kernel's
        # pass-1 tile is c_att=chunk rows, and the 8-16-token smoke
        # prompts never fill a 32 chunk anyway
        mk = dict(block_size=16, num_blocks=192, max_blocks_per_seq=16,
                  chunk=16, decode_burst=16)
    return cfg, n_req, plens, out_hi, mk


def main(big: bool = False, shape: str = "auto"):
    import jax
    from paddle_tpu.inference.serving import (ServingEngine,
                                              generate_static_batch)
    from paddle_tpu.models import gpt as G

    from paddle_tpu.device import require_tpu
    # main() is a chip entry; tier-1 calls the run_* helpers directly at
    # scenario(on_tpu=False)
    require_tpu("benchmarks/serving_bench.py")
    cfg, n_req, plens, out_hi, mk = scenario(True, big=big, shape=shape)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (int(rng.choice(plens)),))
               for _ in range(n_req)]
    news = rng.randint(8, out_hi + 1, (n_req,)).tolist()
    total_tokens = sum(news)
    batch = 8

    def make_engine():
        return ServingEngine(params, cfg, max_batch=batch, **mk)

    def run_continuous():
        eng = make_engine()
        for p, n in zip(prompts, news):
            eng.add_request(p, n)
        eng.run()  # warm compile happens inside; time a fresh engine below
        eng2 = make_engine()
        rids = [eng2.add_request(p, n) for p, n in zip(prompts, news)]
        done_at = {}
        t0 = time.perf_counter()
        while eng2.has_work():
            for r in eng2.step():
                done_at[r.rid] = time.perf_counter() - t0
        lat = [done_at[rid] for rid in rids]
        return time.perf_counter() - t0, lat

    def run_static():
        generate_static_batch(params, cfg, prompts, news, batch)  # warm
        # per-request completion = its BATCH GROUP's finish time (every
        # request in a static group waits for the group's longest)
        order = sorted(range(n_req), key=lambda i: len(prompts[i]))
        lat = [0.0] * n_req
        t0 = time.perf_counter()
        for i in range(0, n_req, batch):
            idxs = order[i:i + batch]
            generate_static_batch(
                params, cfg, [prompts[j] for j in idxs],
                [news[j] for j in idxs], batch, sort_by_len=False)
            now = time.perf_counter() - t0
            for j in idxs:
                lat[j] = now
        return time.perf_counter() - t0, lat

    dt_s, lat_s = run_static()
    dt_c, lat_c = run_continuous()

    # per-decoded-token KV bytes: the paged kernel streams only the blocks
    # a sequence references (ceil(len/bs) rounded up to block_size); a
    # dense padded cache reads max_seq_len rows for every slot every step
    bs_kv = mk["block_size"]
    paged_rows = sum(
        ((len(p) + t) // bs_kv + 1) * bs_kv
        for p, n in zip(prompts, news) for t in range(n))
    dense_rows = total_tokens * cfg.max_seq_len
    out = {
        "device": _device(),
        "metric": ("serving_continuous_vs_static_big_ragged" if big
                   else "serving_continuous_vs_static"),
        "value": round(total_tokens / dt_c, 1),
        "unit": "generated tokens/s (continuous batching)",
        "static_tokens_per_sec": round(total_tokens / dt_s, 1),
        "speedup": round(dt_s / dt_c, 2),
        "kv_read_rows_paged_vs_dense": round(paged_rows / dense_rows, 3),
        "latency_s": {
            "continuous": _lat_stats(lat_c),
            "static": _lat_stats(lat_s),
        },
        "config": f"{n_req} reqs, prompts {plens} mixed, outputs "
                  f"U[8,{out_hi}], batch {batch}, chunked prefill "
                  f"{mk['chunk']}, decode bursts up to "
                  f"{mk['decode_burst']}, one ragged dispatch a step; "
                  "static baseline bucketed by prompt length; latency = "
                  "submit-all-at-t0 to request completion",
        # ISSUE 13: offered load at ~2x capacity, shedding on vs off —
        # admitted-request TTFT percentiles, shed rate, goodput
        "overload": run_overload_comparison(
            params, cfg, mk, batch, n_req=64),
        # ISSUE 16: 2-replica fleet, one replica killed mid-run vs the
        # uninterrupted fleet — goodput cost of a journaled failover
        "router": run_router_comparison(
            params, cfg, mk, batch, n_req=48),
        # ISSUE 17: prefix page sharing (admission multiplier at a fixed
        # pool) + speculative decoding (tokens per decode step, bitwise
        # vs plain)
        "prefix_spec": run_prefix_spec_comparison(params, cfg, mk, batch),
    }
    if shape == "gpt1p3b":
        out["metric"] += "_gpt1p3b"
    print(json.dumps(out))


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--big", action="store_true",
                    help="128 requests, prompts up to 512 (high-"
                         "raggedness profile)")
    ap.add_argument("--shape", default="auto",
                    choices=("auto", "gpt1p3b"),
                    help="gpt1p3b: flagship 1.3B serving shape "
                         "(weight-bound decode; VERDICT weak #2)")
    args = ap.parse_args()
    main(big=args.big, shape=args.shape)
