"""Measured bench sweep for the auto-parallel planner.

Closes the loop the ISSUE demands: the planner's analytic ranking is only
trustworthy if a real sweep confirms it. ``run_sweep`` builds and steps
each PlanCandidate through ``build_hybrid_train_step(**engine_kwargs)``
on the live mesh (the CPU smoke mesh in CI, a pod slice on hardware),
times steady-state steps, calibrates the cost model's
(rate, collective-launch) pair on anchor candidates
(:meth:`planner.CostModel.calibrate` — the "measured-or-peak" leg), and
reports predicted vs measured step times. ``ranking_agreement`` is the
order-correctness check: for every candidate pair whose MEASURED times
differ by more than the noise margin, the predicted order must match.

Mesh-shape hops between sweep points can carry a warm parameter state
through the PR-7 elastic-reshard path (``warm_hop=True``): the previous
candidate's params are saved once with schema-v2 layout metadata and
reshard-loaded onto the next candidate's mesh instead of re-initializing
— the "use it to drive bench sweeps across mesh shapes" residue of
ROADMAP item 5.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .planner import CostModel, PlanCandidate

__all__ = ["measure_candidate", "run_sweep", "ranking_agreement",
           "reshard_params_hop", "profile_candidate"]


def _builder(family: str):
    if family == "gpt":
        from ...models import gpt as M
    else:
        from ...models import llama as M
    return M


def measure_candidate(cfg, cand: PlanCandidate, *, family: str = "gpt",
                      global_batch: int, seq: int, iters: int = 3,
                      repeats: int = 2, host_params=None,
                      warm_from: Optional[Dict[str, Any]] = None,
                      optimizer=None) -> Dict[str, Any]:
    """Build + step one candidate; returns measured seconds/step
    (best-of-``repeats`` mean over ``iters`` steps), compile seconds, and
    (for warm hops) the live state handles.

    host_params: host/replicated param tree reused across candidates so
    every sweep point trains the same weights; warm_from: a dict from a
    previous point's ``reshard_params_hop`` save (overrides host_params
    through the reshard path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle

    M = _builder(family)
    mesh = cand.build_mesh()
    opt = optimizer if optimizer is not None \
        else paddle.optimizer.AdamW(learning_rate=1e-4)
    kw = cand.engine_kwargs(family=family, global_batch=global_batch,
                            seq=seq)
    step, shard_params, init_state = M.build_hybrid_train_step(
        cfg, mesh, opt, **kw)
    if host_params is None:
        host_params = M.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    with mesh:
        p = shard_params(host_params)
        if warm_from is not None:
            p = reshard_params_hop(warm_from, p, init_state.layout_extra)
        st = init_state(p)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (global_batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (global_batch, seq)))
    lr = jnp.float32(1e-4)
    t0 = time.perf_counter()
    p, st, loss = step(p, st, tokens, labels, lr)
    float(loss)
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        for _ in range(iters):
            p, st, loss = step(p, st, tokens, labels, lr)
        float(loss)
        best = min(best, (time.perf_counter() - t0) / iters)
    return {"step_s": best, "compile_s": compile_s, "loss": float(loss),
            "params": p, "state": st,
            "layout_extra": init_state.layout_extra}


def profile_candidate(cfg, cand: PlanCandidate, *, family: str = "gpt",
                      global_batch: int, seq: int, steps: int = 3,
                      rates=None, mode: Optional[str] = None,
                      host_params=None, optimizer=None):
    """Build one candidate and capture an ATTRIBUTED profile window of
    its compiled step (observability.profile_reader): while-trip-aware
    HLO census, measured rates, compute vs hidden/exposed collective
    split. `mode` labels what the window measures in the planner's
    HIDE_KEYS vocabulary ("dp:monolithic", "mp:allreduce", ...) so
    derive_hardware_profile can map its hidable fraction; pass one
    shared MeasuredRates across a multi-config capture. The bench's
    profile_attribution section and the slow-tier attribution gate share
    this harness."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from ...observability.profile_reader import capture_step_profile

    M = _builder(family)
    mesh = cand.build_mesh()
    opt = optimizer if optimizer is not None \
        else paddle.optimizer.AdamW(learning_rate=1e-4)
    kw = cand.engine_kwargs(family=family, global_batch=global_batch,
                            seq=seq)
    # capture_step_profile calls the step with the SAME args every time:
    # the window's step may not consume them
    step, shard_params, init_state = M.build_hybrid_train_step(
        cfg, mesh, opt, donate=False, **kw)
    if host_params is None:
        host_params = M.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    with mesh:
        p = shard_params(host_params)
        st = init_state(p)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (global_batch, seq)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (global_batch, seq)))
    return capture_step_profile(
        step, (p, st, tokens, labels, jnp.float32(1e-4)), steps=steps,
        label=str(cand), mode=mode, mesh=mesh, rates=rates)


def reshard_params_hop(saved: Dict[str, Any], target_params,
                       target_layout_extra=None):
    """Load a previous sweep point's params onto THIS candidate's mesh
    through checkpoint.reshard (PR 7): ``saved`` is the dict returned by
    :func:`save_params_for_hop`. Returns the resharded param tree shaped
    and sharded like ``target_params``."""
    from ..checkpoint.reshard import load_resharded
    sd = {"params": target_params}
    out = load_resharded(sd, saved["path"],
                         layout_extra=target_layout_extra)
    return out["params"]


def save_params_for_hop(params, layout_extra, path: str) -> Dict[str, Any]:
    """Save one sweep point's live params with schema-v2 layout metadata
    so the next mesh shape can reshard-load them (FLAGS_ckpt_reshard is
    forced on for this save only)."""
    from ...flags import flag, set_flags
    from ..checkpoint import save_state_dict
    prev = flag("ckpt_reshard")
    set_flags({"ckpt_reshard": True})
    try:
        save_state_dict({"params": params}, path, layout="auto",
                        layout_extra=layout_extra)
    finally:
        set_flags({"ckpt_reshard": prev})
    return {"path": path}


def run_sweep(cfg, candidates: Sequence[PlanCandidate], *,
              cost_model: CostModel, family: str = "gpt",
              global_batch: int, seq: int, iters: int = 3,
              repeats: int = 2,
              anchors: Optional[Sequence[PlanCandidate]] = None,
              warm_hop_dir: Optional[str] = None
              ) -> Tuple[List[Dict[str, Any]], CostModel]:
    """Measure every candidate, calibrate the cost model on ``anchors``
    (default: the first three candidates — rate, per-collective launch
    overhead and fixed per-step overhead; see CostModel.calibrate), and
    return
    ``([{candidate, measured_s, predicted_s, compile_s}, ...],
    calibrated_model)``. predicted_s comes from the CALIBRATED model —
    the predicted-vs-measured numbers the tolerance gate compares.

    warm_hop_dir: carry the params between mesh shapes through the
    elastic-reshard path instead of re-sharding the host tree (one save
    per hop; exercises reshard-on-load across every mesh change in the
    sweep)."""
    import os
    import jax

    host_params = _builder(family).init_hybrid_params(
        cfg, jax.random.PRNGKey(0))
    rows: List[Dict[str, Any]] = []
    warm = None
    for i, cand in enumerate(candidates):
        m = measure_candidate(cfg, cand, family=family,
                              global_batch=global_batch, seq=seq,
                              iters=iters, repeats=repeats,
                              host_params=host_params, warm_from=warm)
        rows.append({"candidate": cand, "measured_s": m["step_s"],
                     "compile_s": m["compile_s"], "loss": m["loss"]})
        if warm_hop_dir is not None and i + 1 < len(candidates):
            path = os.path.join(warm_hop_dir, f"hop_{i}")
            warm = save_params_for_hop(m["params"], m["layout_extra"],
                                       path)
        del m
    anchors = list(anchors) if anchors is not None else \
        [r["candidate"] for r in rows[:3]]
    meas = {r["candidate"]: r["measured_s"] for r in rows}
    cal = cost_model.calibrate([(a, meas[a]) for a in anchors
                                if a in meas])
    for r in rows:
        r["predicted_s"] = cal.predict(r["candidate"]).step_s
        r["anchor"] = r["candidate"] in anchors
    return rows, cal


def ranking_agreement(rows: Sequence[Dict[str, Any]], *,
                      noise_rel: float = 0.15) -> Dict[str, Any]:
    """Order-correctness of predicted vs measured step times: every pair
    where BOTH the measured times and the predicted times differ by more
    than ``noise_rel`` (relative to the smaller) must be ordered the same
    way. Pairs inside the margin on either side are ties — the model
    makes no distinguishing claim there (predicted near-ties) or the
    measurement cannot adjudicate (measured near-ties) — and never count
    for or against. Returns {"ok", "checked_pairs", "violations"}."""
    viol = []
    checked = 0
    for i in range(len(rows)):
        for j in range(i + 1, len(rows)):
            mi, mj = rows[i]["measured_s"], rows[j]["measured_s"]
            pi, pj = rows[i]["predicted_s"], rows[j]["predicted_s"]
            if abs(mi - mj) <= noise_rel * min(mi, mj):
                continue
            if abs(pi - pj) <= noise_rel * min(pi, pj):
                continue
            checked += 1
            if (mi < mj) != (pi < pj):
                viol.append((str(rows[i]["candidate"]),
                             str(rows[j]["candidate"])))
    return {"ok": not viol, "checked_pairs": checked, "violations": viol}
