"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the two hot paths through the entry points a user calls, in ONE
process, at the full width and depth of GPT-3 1.3B (hidden 2048, 24 layers,
16 heads of 128, vocab 32768, seq 1024, bf16 params + bf16 Adam moments;
weights random from a seed):

  train   G.init_hybrid_params -> jitted, donated G.dense_loss + AdamW step,
          5 steps on a seeded batch
  serve   inference.ServingEngine answering 8 seeded requests
          of mixed prompt lengths, twice (fresh engine each time)

    python chip_smoke.py             # one chip: train + serve
    python chip_smoke.py --chips 4   # four chips, ONLY: the hybrid dp x mp
                                     # step on one mesh from one process,
                                     # and the one-device dense step it is
                                     # compared with

It refuses to run (non-zero exit, no result line) when jax finds no TPU, and
no phase's failure is caught: any assertion or exception is a non-zero exit.
The last line of stdout is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.

Everything printed before that line is smoke information (compile seconds,
step seconds, peak bytes, dispatch counts, served tokens) — not benchmark
results.
"""

import argparse
import functools
import gc
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.device import device_tag, require_tpu
from paddle_tpu.flags import REPO_JIT_CACHE_DIR
from paddle_tpu.inference import ServingEngine
from paddle_tpu.kernels.pallas import _common
from paddle_tpu.models import gpt as G
from paddle_tpu.observability.flops import peak_flops
from paddle_tpu.ops.registry import dispatch_stats
from paddle_tpu.utils.timing import dispatch_rtt_s

# GPT-3 1.3B as bench.py's FLAGSHIP: widths and depth are never cut
WIDTHS = dict(vocab_size=32768, hidden_size=2048, num_layers=24,
              num_heads=16, max_seq_len=1024)
SEQ = 1024
TRAIN_BATCH = 8
LR = 1e-4
SEED = 0

# serving geometry: 128-token pages (ragged_paged_attention.py's guidance
# for real TPUs; the flag default of 16 is the CPU-test size)
SERVE = dict(max_batch=8, block_size=128, num_blocks=64,
             max_blocks_per_seq=8, chunk=128, decode_burst=8)
PROMPT_LENS = (16, 48, 100, 160, 256, 300, 400, 512)
NEW_TOKENS = 32

# four-chip option. Batch 4, not 8: G.build_hybrid_train_step cannot ask
# for donation, so params + moments are resident twice (3.76 GiB of
# arguments + 3.76 GiB of outputs per device at dp2 x mp2) and the batch-8
# program's 9.11 GiB did not load into the 8.22 GiB left (my chip run,
# PR 23; see CHANGES.md). The dense step it is compared with runs the
# same batch.
HYBRID = dict(dims={"dp": 2, "pp": 1, "mp": 2}, batch=4, microbatches=2,
              steps=3)
# |hybrid loss - dense loss| per step. bf16 params and activations (8
# mantissa bits), an mp-split contraction order, per-shard stochastic
# rounding of the bf16 moments: a few 1e-3 on a loss of ~10; 0.05 leaves
# room without passing a wrong sharding once the weights have moved.
HYBRID_LOSS_TOL = 0.05


def log(*a):
    print(*a, flush=True)


def _cfg():
    return G.GPTConfig(**WIDTHS, dtype=jnp.bfloat16,
                       param_dtype=jnp.bfloat16)


def _batch(cfg, batch):
    rng = np.random.RandomState(SEED)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, SEQ)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, SEQ)))
    return tokens, labels


def _mem(dev, key="peak_bytes_in_use"):
    return int(dev.memory_stats()[key])


def _mem_line(tag, devices):
    """bytes_in_use / peak_bytes_in_use per device at a phase boundary —
    shows what a phase left behind and what its peak added."""
    log(f"[mem] {tag}: " + "; ".join(
        f"dev{d.id} in_use {_mem(d, 'bytes_in_use')} peak {_mem(d)}"
        for d in devices))


def _adamw():
    return paddle.optimizer.AdamW(learning_rate=LR,
                                  moment_dtype=jnp.bfloat16)


def _run_dense(cfg, batch, steps, check_lowering):
    """bench.py's flagship step (plain jit, params and state donated):
    init from SEED, compile once (AOT, so the lowered text can be read),
    run `steps` steps. Returns (losses, compile_s, step_s)."""
    opt = _adamw()

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: G.dense_loss(p, tokens, labels, cfg))(params)
        params, state = opt.apply(params, grads, state, LR)
        return params, state, loss

    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(SEED))
    state = jax.jit(opt.init_state)(params)
    tokens, labels = _batch(cfg, batch)

    dispatch_stats(reset=True)
    t0 = time.perf_counter()
    lowered = step.lower(params, state, tokens, labels)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    if check_lowering:
        stats = dispatch_stats()
        log(f"[train] op-registry dispatch counts (per trace): {stats}")
        sdpa = stats.get("scaled_dot_product_attention")
        assert sdpa and sdpa["pallas"] >= 1 and sdpa["reference"] == 0, (
            f"scaled_dot_product_attention fell back to reference: {stats}")
        text = lowered.as_text()
        n_adam = len(re.findall(r'kernel_name = "fused_adam"', text))
        n_calls = text.count("tpu_custom_call")
        log(f"[train] lowered step: {n_calls} tpu_custom_call, "
            f"{n_adam} of them fused_adam")
        assert n_adam >= 1, "no fused-Adam tpu_custom_call in the step"
        ma = compiled.memory_analysis()
        log(f"[train] compiled memory_analysis: arguments "
            f"{ma.argument_size_in_bytes} B, temp {ma.temp_size_in_bytes} B, "
            f"aliased {ma.alias_size_in_bytes} B")

    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, state, loss = compiled(params, state, tokens, labels)
        jax.block_until_ready((params, state, loss))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    del params, state, compiled, lowered
    gc.collect()
    return losses, compile_s, step_s


def train_phase(dev):
    log(f"[train] GPT-1.3B {WIDTHS}, batch {TRAIN_BATCH} x seq {SEQ}, "
        "bf16 params + bf16 Adam moments, donated dense_loss + AdamW step")
    losses, compile_s, step_s = _run_dense(_cfg(), TRAIN_BATCH, 5,
                                           check_lowering=True)
    log(f"[train] compile_s {compile_s:.2f}")
    log("[train] step_s " + " ".join(f"{s:.4f}" for s in step_s))
    log("[train] losses " + " ".join(f"{x:.4f}" for x in losses))
    log(f"[train] peak_bytes_in_use {_mem(dev)} of "
        f"{_mem(dev, 'bytes_limit')} B")
    _mem_line("after train, buffers freed", [dev])
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"


def _reference_rank(cfg, params, prompt, token):
    """Rank of the engine's first generated token under the training
    forward (G.dense_forward) on the same prompt: 0 = its argmax."""
    logits = jax.jit(lambda p, t: G.dense_forward(p, t, cfg))(
        params, jnp.asarray(prompt)[None])
    last = np.asarray(logits[0, -1].astype(jnp.float32))
    return int((last > last[token]).sum())


def serve_phase(dev):
    cfg = _cfg()
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(SEED))
    rng = np.random.RandomState(SEED + 1)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in PROMPT_LENS]
    _mem_line("serve params on device", [dev])
    log(f"[serve] ServingEngine {SERVE}, prompts "
        f"{PROMPT_LENS}, {NEW_TOKENS} new tokens each, greedy")

    def run_once(tag):
        t_build = time.perf_counter()
        eng = ServingEngine(params, cfg, seed=SEED,
                            pool_audit=True, **SERVE)
        first = {}

        def on_token(rid, tok):
            first.setdefault(rid, time.perf_counter())

        t0 = time.perf_counter()
        rids = [eng.add_request(p, NEW_TOKENS, on_token=on_token)
                for p in prompts]
        res = eng.run()
        wall = time.perf_counter() - t0
        outs = [list(res[r]) for r in rids]
        ttft = sorted(first[r] - t0 for r in rids)
        n_tok = sum(len(o) for o in outs)
        log(f"[serve:{tag}] {len(outs)} requests, {n_tok} tokens in "
            f"{wall:.2f} s incl. compile ({n_tok / wall:.1f} tok/s), "
            f"engine build {t0 - t_build:.2f} s, TTFT min/median/max "
            f"{ttft[0]:.3f}/{ttft[len(ttft) // 2]:.3f}/{ttft[-1]:.3f} s, "
            f"{eng.engine_steps} engine steps, {eng.dispatches} dispatches")
        assert all(res.statuses[r] == "ok" for r in rids), res.statuses
        for o in outs:
            assert len(o) == NEW_TOKENS, [len(x) for x in outs]
            assert all(0 <= t < cfg.vocab_size for t in o), o
        assert eng.engine_steps > 0
        assert eng.dispatches / eng.engine_steps == 1.0, (
            eng.dispatches, eng.engine_steps)  # the ragged contract
        leaked = eng._num_blocks - 1 - eng.free_pages()
        assert leaked == 0, f"{leaked} KV pages leaked at drain"
        _mem_line(f"serve:{tag} drained, engine alive", [dev])
        del eng
        gc.collect()
        return outs

    log(f"[serve] measured dispatch+fetch round trip "
        f"{dispatch_rtt_s() * 1e3:.3f} ms")
    first_run = run_once("cold")
    second_run = run_once("repeat")
    assert first_run == second_run, "repeat run produced different tokens"
    log("[serve] repeat on a fresh engine: identical tokens")
    # the engine against the training forward on two of the prompts
    for i in (0, 4):
        rank = _reference_rank(cfg, params, prompts[i], first_run[i][0])
        log(f"[serve] request {i} (prompt {PROMPT_LENS[i]}): first token "
            f"{first_run[i][0]} has rank {rank} under G.dense_forward")
        assert rank < 3, (
            f"engine's first token is rank {rank} under the reference")
    log(f"[serve] peak_bytes_in_use {_mem(dev)} (process lifetime)")


def hybrid_phase():
    """Four chips, one process: the hybrid engine's step on a dp x mp mesh
    against the one-device dense step (same seed, same batch)."""
    devices = jax.devices()
    cfg = _cfg()
    dims, batch = HYBRID["dims"], HYBRID["batch"]
    steps, M = HYBRID["steps"], HYBRID["microbatches"]
    mesh = dist.build_mesh(dims)
    log(f"[hybrid] mesh {dims} over {[d.id for d in mesh.devices.flat]}, "
        f"batch {batch} (8 does not fit without donation), "
        f"{M} microbatches, {steps} steps")
    step, shard_params, init_state = G.build_hybrid_train_step(
        cfg, mesh, _adamw(), num_microbatches=M)
    params = shard_params(G.init_hybrid_params(cfg,
                                               jax.random.PRNGKey(SEED)))
    state = init_state(params)
    tokens, labels = _batch(cfg, batch)
    lr = jnp.float32(LR)
    _mem_line("hybrid params + state sharded", devices)

    t0 = time.perf_counter()
    compiled = step.lower(params, state, tokens, labels, lr).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    kinds = ("all-reduce", "all-gather", "reduce-scatter",
             "collective-permute", "all-to-all")
    counts = {k: len(re.findall(rf" {k}(?:-start)?\(", text)) for k in kinds}
    ma = compiled.memory_analysis()
    log(f"[hybrid] compile_s {compile_s:.2f}; collectives in the compiled "
        f"step {counts}; {text.count('tpu_custom_call')} tpu_custom_call")
    log(f"[hybrid] per-device memory_analysis: arguments "
        f"{ma.argument_size_in_bytes} B, outputs {ma.output_size_in_bytes} B,"
        f" temp {ma.temp_size_in_bytes} B, aliased {ma.alias_size_in_bytes} B")
    # mp splits every block's contractions, dp the batch: both reduce
    # with all-reduce in this engine (no zero stage, no seq-parallel)
    assert counts["all-reduce"] > 0, counts

    hybrid_losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, state, loss = compiled(params, state, tokens, labels, lr)
        jax.block_until_ready((params, state, loss))
        step_s.append(time.perf_counter() - t0)
        hybrid_losses.append(float(loss))
    peaks = {d.id: _mem(d) for d in devices}
    log("[hybrid] step_s " + " ".join(f"{s:.4f}" for s in step_s))
    log("[hybrid] losses " + " ".join(f"{x:.4f}" for x in hybrid_losses))
    log(f"[hybrid] peak_bytes_in_use per device {peaks}")
    # params + moments are ~8 GB in all; a device holding under 1 GB
    # would mean the step ran somewhere else
    assert all(p > 1 << 30 for p in peaks.values()), peaks
    del params, state, compiled
    gc.collect()
    _mem_line("after hybrid, buffers freed", devices)

    dense_losses, dense_compile_s, dense_step_s = _run_dense(
        cfg, batch, steps, check_lowering=False)
    log(f"[dense@device0] compile_s {dense_compile_s:.2f}; step_s "
        + " ".join(f"{s:.4f}" for s in dense_step_s))
    log("[dense@device0] losses " + " ".join(f"{x:.4f}"
                                             for x in dense_losses))
    diffs = [abs(a - b) for a, b in zip(hybrid_losses, dense_losses)]
    log("[hybrid] |hybrid - dense| per step "
        + " ".join(f"{d:.5f}" for d in diffs)
        + f" (tolerance {HYBRID_LOSS_TOL})")
    assert all(np.isfinite(hybrid_losses + dense_losses))
    assert max(diffs) <= HYBRID_LOSS_TOL, (hybrid_losses, dense_losses)
    assert hybrid_losses[-1] < hybrid_losses[0], hybrid_losses


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the hybrid step across four chips "
                         "and the one-device step it is compared with")
    args = ap.parse_args()

    dev = require_tpu("chip_smoke.py")  # SystemExit before any phase
    n_dev = len(jax.devices())
    assert n_dev == args.chips, (
        f"--chips {args.chips} but jax sees {n_dev} devices")
    # one fixed cache directory: JAX_COMPILATION_CACHE_DIR when the
    # machine sets it (the flag's hook then leaves jax's config alone),
    # <repo>/.jax_cache otherwise
    paddle.set_flags({"jit_cache_dir": REPO_JIT_CACHE_DIR})
    from importlib.metadata import version
    log(f"[env] jax {version('jax')}, jaxlib {version('jaxlib')}, libtpu "
        f"{version('libtpu')}, device {dev.platform} '{dev.device_kind}' "
        f"x {n_dev}, bf16 peak {peak_flops():.3g} FLOP/s (flops.CHIP_PEAKS)")
    log(f"[env] compile cache dir: {jax.config.jax_compilation_cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r})")
    assert _common.interpret() is False, "Pallas kernels would interpret"

    t0 = time.perf_counter()
    if args.chips == 4:
        hybrid_phase()
    else:
        train_phase(dev)
        serve_phase(dev)
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device_tag()}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
