"""What the two training runners share: the first steps that the reference
follows, the measured window, and the comparison.

A runner builds ONE object (the compiled step with its parameters and
optimizer state), set-up drives it through its first steps from the seed,
and the window goes on with the same object.
"""

import statistics
import time

import jax
import jax.numpy as jnp

from chipbench import harness, traffic as T, weights as W
from chipbench.reference.train import RefTrainer, sq, sq_diff

FOLLOWED = 3    # steps the reference follows


def _by_leaf(widths, tree):
    return {W.leaf_name(path): W.get(tree, path)
            for path, *_ in W.leaf_table(widths)}


def grad_norms(widths, state, beta1):
    """|first gradient| of every leaf as the optimizer got it, from its
    state after one step: moment1 = (1 - beta1) * gradient."""
    slots = state.get("opt", state)["slots"]   # fp8 builds wrap the state
    m1 = jax.tree.map(lambda s: s["moment1"], slots,
                      is_leaf=lambda s: isinstance(s, dict)
                      and "moment1" in s)
    return {k: float(v) ** 0.5 / (1 - beta1)
            for k, v in _by_leaf(widths, sq(m1)).items()}


def moved_norms(widths, params, seed):
    """|parameters now - parameters from the seed|, leaf by leaf, making
    each first leaf again alone so no second tree is ever resident."""
    key = W.seed_key(seed)
    out = {}
    for i, (path, _, mean, std) in enumerate(W.leaf_table(widths)):
        leaf = W.get(params, path)
        first = W.make_leaf(key, i, mean, std, shape=leaf.shape,
                            dtype=leaf.dtype)
        out[W.leaf_name(path)] = float(sq_diff(leaf, first)) ** 0.5
    return out


def worst_leaf_gap(ours, ref):
    """The largest | |ours| - |ref| | over the leaves, each against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some leaves hardly move)."""
    floor = statistics.median(ref.values())
    gaps = sorted(((abs(ours[k] - ref[k]) / max(ref[k], floor, 1e-30), k)
                   for k in ref), reverse=True)
    harness.log("[compare] median leaf %.6g; worst leaves: " % floor
                + "; ".join(f"{k} ours {ours[k]:.6g} reference {ref[k]:.6g}"
                            for _, k in gaps[:4]))
    return gaps[0][0]


def drive(ctx, cell_obj):
    """Set-up's first steps, the window, then the reference. `cell_obj`
    has .step(i) -> loss (a device scalar; parameters and state move on
    inside), .params, .state, .free()."""
    config, traffic = ctx["config"], ctx["traffic"]
    widths, hyper = config["widths"], config["optimizer"]
    seconds, tracer = ctx["seconds"], ctx["tracer"]
    tokens_per_step = traffic["batch"] * traffic["seq"]

    def full_step(i):
        loss = cell_obj.step(i)
        jax.block_until_ready((cell_obj.params, cell_obj.state, loss))
        return loss

    harness.mark(ctx, "weights, state and step built")
    losses, first_grad = [], None
    for i in range(FOLLOWED):
        losses.append(float(full_step(i)))
        if i == 0:
            harness.mark(ctx, "first step ran (compiled or loaded)")
            first_grad = grad_norms(widths, cell_obj.state, hyper["beta1"])
    moved = moved_norms(widths, cell_obj.params, ctx["seed"])
    harness.mark(ctx, f"first losses {losses}")

    # -- the window ---------------------------------------------------------
    compiles0 = ctx["compiles"].count
    spans, k, loss = [], FOLLOWED, None
    trace_from = seconds - float(traffic.get("trace_s", 8.0))
    t_w0 = time.perf_counter()
    setup_s = t_w0 - ctx["t0"]
    while True:
        t0 = time.perf_counter()
        if tracer and not tracer.on and t0 - t_w0 >= trace_from:
            tracer.start()
            t0 = time.perf_counter()
        with harness.span("train_step", bool(tracer and tracer.on)):
            loss = full_step(k)
        t1 = time.perf_counter()
        if t1 - t_w0 > seconds:
            break
        spans.append((t0, t1))
        k += 1
    trace = tracer.stop() if tracer else None
    compiles = ctx["compiles"].count - compiles0
    peak = harness.memory_peak_bytes(ctx["devices"])
    finite = bool(jnp.isfinite(loss))
    n = len(spans)
    tok_s = tokens_per_step * n / (spans[-1][1] - spans[0][0])
    harness.log(f"[window] {n} steps, train_tok_s {tok_s:.1f}, setup_s "
                f"{setup_s:.2f}, compiles in window {compiles}")

    # -- the reference, once the program's state is gone --------------------
    cell_obj.free()
    t_ref = time.perf_counter()
    ref = RefTrainer(W.make_params(widths, ctx["seed"]), widths["num_heads"],
                     [hyper[k_] for k_ in ("lr", "beta1", "beta2", "epsilon",
                                           "weight_decay")], ctx["devices"])
    batches = T.train_batches(traffic, widths["vocab_size"], ctx["seed"])
    ref_losses, ref_grad = [], None
    for i in range(FOLLOWED):
        tok, lab = batches[i % len(batches)]
        l, g = ref.step(tok, lab, last=(i == FOLLOWED - 1))
        ref_losses.append(l)
        ref_grad = ref_grad or g
    ref_moved = ref.moved()
    harness.log(f"[reference] {time.perf_counter() - t_ref:.1f} s, losses "
                f"{ref_losses}")
    limits = traffic["limits"]
    checks = [(f"loss_gap_step{i + 1}", abs(a - b) / abs(b),
               limits["loss_gap"])
              for i, (a, b) in enumerate(zip(losses, ref_losses))]
    checks.append(("first_grad_norm_gap",
                   worst_leaf_gap(first_grad, ref_grad),
                   limits["first_grad_norm_gap"]))
    checks.append(("moved_norm_gap", worst_leaf_gap(moved, ref_moved),
                   limits["moved_norm_gap"]))
    step_ms = [(b - a) * 1e3 for a, b in spans]
    return {
        "devices": ctx["devices"], "checks": checks, "trace": trace,
        "attempted": n, "failed": 0 if finite else n,
        "memory_peak_bytes": peak,
        "e2e": {"train_tok_s": tok_s, "setup_s": setup_s},
        "facts": {"step_ms": step_ms, "compiles_in_window": compiles,
                  "live_peak_bytes": peak,
                  # rate over the steps' own time: what a traced run, whose
                  # profiler stalls between steps, can still state
                  "step_tok_s": tokens_per_step * n / (sum(step_ms) / 1e3),
                  "flops_widths": widths, "seq": traffic["seq"],
                  "chips": len(ctx["devices"]),
                  "device_kind": ctx["devices"][0].device_kind},
    }
