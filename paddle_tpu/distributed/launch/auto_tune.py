"""Launcher-wired auto-tuner (reference: launch/main.py auto-tuner mode —
``--auto_tuner_json`` drives subprocess trials of the user's own training
script, reading one metric back per trial, then launches the real job
with the winner).

The candidate vocabulary is the auto_tuner planner's
:class:`PlanCandidate` — the REAL hybrid-engine surface (dp/mp/pp/ep,
schedule, vpp, micro_batches, zero_stage, comm_bucket_mb, mp_overlap, ...).
With ``FLAGS_auto_parallel_plan`` (default on) and a model named in the
tuner json, the analytic planner generates, HBM-prunes and RANKS the
candidates first, so only the top ``FLAGS_auto_parallel_topk`` pay for a
real subprocess trial; without model information the trial loop sweeps
the constraint-valid factorizations unranked.

Trial protocol (what the training script sees):
  PADDLE_AUTO_TUNER_CANDIDATE = JSON dict of the PlanCandidate fields
  PADDLE_AUTO_TUNER_TRIAL     = "1" (run a few steps, then exit 0)
  PADDLE_AUTO_TUNER_METRIC_FILE = path — write ONE float (higher=better)

Script-side helpers: ``candidate_from_env()`` parses the candidate into a
PlanCandidate (``cand.build_mesh()`` / ``cand.engine_kwargs()`` feed it
straight into ``build_hybrid_train_step``); ``report_metric(value)``
writes the metric file.
"""

from __future__ import annotations
from ...enforce import InvalidArgumentError

import dataclasses
import json
import os
import tempfile
from typing import List, Optional

from ..auto_tuner import (AutoTuner, ModelSpec, PlanCandidate,
                          generate_plan_candidates, model_config_by_name,
                          plan as plan_candidates)

__all__ = ["run_auto_tune", "candidate_from_env", "report_metric"]


def candidate_from_env() -> Optional[PlanCandidate]:
    raw = os.environ.get("PADDLE_AUTO_TUNER_CANDIDATE")
    if not raw:
        return None
    d = json.loads(raw)
    return PlanCandidate(**d)


def is_trial() -> bool:
    return os.environ.get("PADDLE_AUTO_TUNER_TRIAL") == "1"


def report_metric(value: float) -> None:
    path = os.environ.get("PADDLE_AUTO_TUNER_METRIC_FILE")
    if path:
        with open(path, "w") as f:
            f.write(repr(float(value)))


def _candidate_env(cand: PlanCandidate) -> str:
    return json.dumps(dataclasses.asdict(cand), sort_keys=True)


def _launcher_profile(cfg_json: dict):
    """Hardware profile for the launcher's analytic ranking WITHOUT
    touching jax.devices(): the launcher must never initialize a backend
    — on a TPU host that would lock libtpu before the trial subprocesses
    spawn and every trial would fail to acquire the chip. Resolution:
    explicit json "profile" name > env sniff (JAX_PLATFORMS=cpu) >
    tpu-v5e. That last guess is DELIBERATE and the one place a chip is
    assumed without asking the device: asking would load the TPU library
    in the launcher. It only orders trials (each trial measures itself on
    the real device, where an unknown kind raises); name the profile in
    the json to rank for another chip. (The planner math is
    trace/shape-only and never initializes a backend either.)"""
    from ..auto_tuner import KNOWN_PROFILES
    name = cfg_json.get("profile")
    if name is None:
        plat = os.environ.get("JAX_PLATFORMS", "")
        name = "cpu" if plat.startswith("cpu") else "tpu-v5e"
    if name not in KNOWN_PROFILES:
        # a measured-profile JSON path (observability.profile_reader
        # capture artifact) — still backend-free: just a file read
        from ..auto_tuner.planner import resolve_profile
        return resolve_profile(name)
    return KNOWN_PROFILES[name]


def _candidates_for(cfg_json: dict, world: int) -> List[PlanCandidate]:
    """Candidate list for the trial loop: planner-ranked top-k when the
    json names a model (or carries shape fields to build one) and
    FLAGS_auto_parallel_plan is on; constraint-valid factorizations
    unranked when the flag is off; and with NO model information at all,
    the raw mesh factorization x micro-batch sweep with no pruning —
    a fabricated proxy model would silently drop configs (e.g. mp=8 on
    an 8-head model) that are valid for the user's real one."""
    from ...flags import flag

    model = cfg_json.get("model")
    dim_keys = ("num_layers", "num_heads", "hidden_size", "vocab_size")
    micro_opts = tuple(cfg_json.get("micro_batch_options", (1, 2, 4, 8)))
    if model is None and not any(k in cfg_json for k in dim_keys):
        out = []
        for dp in (d for d in range(1, world + 1) if world % d == 0):
            for mp in (m for m in range(1, world // dp + 1)
                       if (world // dp) % m == 0):
                pp = world // (dp * mp)
                for mb in micro_opts:
                    out.append(PlanCandidate(dp=dp, mp=mp, pp=pp,
                                             micro_batches=mb))
        return out
    if model is not None:
        cfg, family = model_config_by_name(model)
    else:
        from ...models.gpt import GPTConfig
        import jax.numpy as jnp
        cfg = GPTConfig(
            vocab_size=cfg_json.get("vocab_size", 1024),
            hidden_size=cfg_json.get("hidden_size", 64),
            num_layers=cfg_json.get("num_layers", 4),
            num_heads=cfg_json.get("num_heads", 4),
            max_seq_len=max(cfg_json.get("seq_len", 128), 128),
            dtype=jnp.float32, param_dtype=jnp.float32)
        family = "gpt"
    gb = int(cfg_json.get("global_batch", max(8, world)))
    seq = int(cfg_json.get("seq_len", cfg.max_seq_len))
    gen_kw = {"micro_batch_options": micro_opts}
    if bool(cfg_json.get("analytic_rank", flag("auto_parallel_plan"))):
        report = plan_candidates(
            cfg, world=world, global_batch=gb, seq=seq, family=family,
            profile=_launcher_profile(cfg_json),
            hbm_gb=(cfg_json.get("hbm_gb")
                    or float(flag("auto_parallel_hbm_gb")) or None),
            **gen_kw)
        top_k = int(cfg_json.get("top_k", flag("auto_parallel_topk")))
        return [s.candidate for s in report.top(top_k)]
    spec = ModelSpec.from_config(cfg, family)
    cands, _ = generate_plan_candidates(spec, world, global_batch=gb,
                                        seq=seq, **gen_kw)
    return cands


def run_auto_tune(ctx) -> Optional[str]:
    """Run the candidate search with the user's own training script as the
    trial body. Returns the winning candidate env string (or None)."""
    from .controllers import CollectiveController

    if ctx.args.nnodes != 1:
        # per-node sweeps would race to different winners and hand ranks
        # inconsistent meshes; a store-synchronized multi-node sweep is
        # future work (the reference's auto-tuner is likewise driven from
        # one launcher)
        raise InvalidArgumentError(
            "--auto_tune currently supports single-node jobs only "
            "(nnodes=1); run the sweep on one node and pass the winning "
            "candidate to the multi-node job via "
            "PADDLE_AUTO_TUNER_CANDIDATE")

    cfg = {}
    if ctx.args.auto_tuner_json:
        with open(ctx.args.auto_tuner_json) as f:
            cfg = json.load(f)
    world = ctx.args.nnodes * ctx.nproc
    cands = _candidates_for(cfg, world)

    def run_trial(cand: PlanCandidate) -> Optional[float]:
        fd, metric_file = tempfile.mkstemp(prefix="autotune_")
        os.close(fd)
        try:
            trial_ctx = _clone(ctx)
            trial_ctx.envs.update({
                "PADDLE_AUTO_TUNER_CANDIDATE": _candidate_env(cand),
                "PADDLE_AUTO_TUNER_TRIAL": "1",
                "PADDLE_AUTO_TUNER_METRIC_FILE": metric_file,
            })
            trial_ctx.args.job_id = (f"{ctx.args.job_id}-tune-"
                                     f"{str(cand).replace(' ', '_')}")
            rc = CollectiveController(trial_ctx).run()
            if rc != 0:
                return None
            with open(metric_file) as f:
                raw = f.read().strip()
            return float(raw) if raw else None
        finally:
            os.unlink(metric_file)

    tuner = AutoTuner(run_trial,
                      max_trials=cfg.get("max_trials"),
                      max_time_s=cfg.get("max_time_s"))
    best = tuner.tune(cands)
    print(tuner.summary())
    if best is None:
        return None
    print(f"auto-tuner winner: {best}")
    return _candidate_env(best)


def _clone(ctx):
    """Fresh Context for a trial: same argv surface, isolated env/args so
    trial job_ids and env markers don't leak into the real run."""
    import argparse

    new = object.__new__(type(ctx))
    new.args = argparse.Namespace(**vars(ctx.args))
    new.node = ctx.node
    new.nproc = ctx.nproc
    new.envs = dict(ctx.envs)
    return new
