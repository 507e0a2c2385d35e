"""Two-process validation workload (`python -m
paddle_tpu.distributed.mp_smoke`).

The multi-host analogue of the driver's single-process dry run: spawned as 2
jax processes x N/2 virtual CPU devices each (reference pattern:
test/legacy_test/test_dist_base.py:1206 _run_cluster), it builds the hybrid
ICI/DCN mesh (dp across processes, mp intra-process), runs a few hybrid
dp x mp train steps, and prints the loss curve as JSON for the launcher to
compare against the identical single-process run.

`run_training(mesh, steps)` is imported by the parent for the golden run;
`spawn_and_check(n_devices)` is the launcher half used by
__graft_entry__.dryrun_multichip and by tests.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

import numpy as np

__all__ = ["run_training", "run_training_resilient", "run_training_fleet",
           "spawn_cluster", "spawn_and_check", "elastic_restart_check",
           "fleet_telemetry_check", "main", "ClusterUnsupported"]


class ClusterUnsupported(RuntimeError):
    """The platform cannot run the spawned multi-process cluster at all
    (as opposed to the workload failing): the jax build lacks
    cross-process CPU collectives, the coordinator cannot bind, etc.
    Tests catch this to SKIP with the reason instead of erroring."""


# worker-output signatures that mean "this platform can't do multiprocess
# jax", not "the workload is broken" — deliberately NARROW: rendezvous
# timeouts / hangs / init-path tracebacks stay hard failures (a deadlock
# regression must not report as "platform cannot spawn")
_UNSUPPORTED_MARKERS = (
    "Multiprocess computations aren't implemented",
    "multi-process computations are not supported",
)

# env that would leak the parent's jax/launcher identity into workers
_SCRUB_ENV = ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_NUM_PROCESSES",
              "JAX_PROCESS_ID", "JAX_COORDINATOR_ADDRESS",
              "PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
              "PADDLE_TRAINER_ENDPOINTS", "PADDLE_CURRENT_ENDPOINT",
              "PADDLE_LOCAL_RANK", "PADDLE_VIRTUAL_DEVICES_PER_PROC")


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def spawn_cluster(argv, nproc: int, devices_per_proc: int,
                  sentinel: str, extra_env=None, timeout: float = 300.0,
                  ok_returncodes=(0,)):
    """Spawn `nproc` jax worker processes of `argv` (2-process rendezvous on
    a fresh port, `devices_per_proc` virtual CPU devices each), wait, and
    return the JSON payload following `sentinel` on each worker's stdout —
    the shared launcher half of the reference subprocess-spawn pattern
    (test_dist_base.py:1206 _run_cluster).

    ok_returncodes: exit codes that count as success — the elastic leg
    EXPECTS its workers to die with faults.FAULT_EXIT_CODE mid-run.
    Workers that died on purpose print no sentinel; their slot in the
    returned list is None."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    repo = _repo_root()
    procs, outs = [], []
    for pid in range(nproc):
        env = {k: v for k, v in os.environ.items() if k not in _SCRUB_ENV}
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PADDLE_VIRTUAL_DEVICES_PER_PROC": str(devices_per_proc),
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "JAX_NUM_PROCESSES": str(nproc),
            "JAX_PROCESS_ID": str(pid),
            "PADDLE_TRAINER_ID": str(pid),
            "PADDLE_TRAINERS_NUM": str(nproc),
            "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""),
        })
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            argv, env=env, cwd=repo, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    timed_out = False
    try:
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                # kill and STILL collect the output: when one rank dies
                # with the unsupported marker mid-rendezvous, its peer
                # sometimes wedges in the dead rendezvous instead of
                # crashing — the marker (in the dead sibling's output)
                # is what distinguishes that from a real deadlock
                timed_out = True
                p.kill()
                out, _ = p.communicate()
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for out in outs:
        for marker in _UNSUPPORTED_MARKERS:
            if marker in out:
                raise ClusterUnsupported(
                    f"platform cannot run the {nproc}-process cluster "
                    f"({marker!r}):\n{out[-1500:]}")
    if timed_out:
        # no unsupported marker anywhere: a genuine hang stays a HARD
        # failure (a deadlock regression must not report as a skip)
        raise RuntimeError(
            f"worker(s) exceeded the {timeout}s timeout with no "
            f"unsupported-platform marker:\n" +
            "\n".join(o[-1500:] for o in outs))
    for p, out in zip(procs, outs):
        if p.returncode not in ok_returncodes:
            raise RuntimeError(f"worker failed (rc={p.returncode}):\n"
                               f"{out[-4000:]}")
    results = []
    for out in outs:
        line = next((l for l in out.splitlines()
                     if l.startswith(sentinel)), None)
        results.append(None if line is None
                       else json.loads(line[len(sentinel):]))
    return results


def run_training(mesh, steps: int = 4, return_params: bool = False,
                 num_microbatches: int = 1, schedule: str = "1F1B",
                 zero1: bool = False, virtual_pp: int = 1,
                 moe: bool = False, zero_stage: int = 0):
    """Seed-deterministic tiny-GPT hybrid train loop over `mesh` (axes dp /
    pp / mp, plus ep for the MoE leg); every process computes identical
    host inputs. The ONE copy of the parity workload — the launcher
    golden, the spawned workers and the reference-pattern tests
    (tests/mp_worker.py) all import it, so they can never drift apart.

    moe=True runs the GPT-MoE config (switch FFN on alternating layers,
    experts sharded over the mesh's 'ep' axis, index dispatch) so the
    dispatch/combine all-to-alls cross whatever boundary the mesh puts
    the ep axis on."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt as G

    if moe:
        cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                          num_heads=4, max_seq_len=16, dtype=jnp.float32,
                          moe_num_experts=4, moe_capacity_factor=4.0)
    else:
        # the interleaved schedule needs num_layers % (pp * vpp) == 0
        nl = 2 * max(int(virtual_pp), 1)
        cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=nl,
                          num_heads=4, max_seq_len=16, dtype=jnp.float32)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    # zero1 mode also carries the axes-aware global-norm clip so the
    # cross-process parity covers the whole round-5 stage-1 path
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-2,
        grad_clip=(paddle.nn.ClipGradByGlobalNorm(0.5)
                   if (zero1 or zero_stage) else None))
    kw = {}
    if moe:
        from .comm_overlap import MoeDispatchConfig
        kw["moe_dispatch"] = MoeDispatchConfig(index=True)
    stage = int(zero_stage) if zero_stage else (1 if zero1 else 0)
    step, shard_params, init_state = G.build_hybrid_train_step(
        cfg, mesh, opt, num_microbatches=num_microbatches,
        schedule=schedule, zero_stage=stage, virtual_pp=virtual_pp, **kw)
    params = shard_params(params)
    state = init_state(params)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)))
    losses = []
    for _ in range(steps):
        params, state, loss = step(params, state, tokens, labels,
                                   jnp.float32(1e-2))
        losses.append(float(jax.device_get(loss)))
    return (losses, params) if return_params else losses


def run_training_resilient(mesh, steps: int, ckpt_dir: str):
    """The SAME seed-deterministic tiny-GPT workload as `run_training`,
    driven through the resilient runner with a per-step crash-safe commit
    and elastic layout metadata (FLAGS_ckpt_reshard) — the one copy of the
    elastic-restart workload shared by the 2-process workers, the
    single-process resume and the golden run. Returns (losses, info):
    losses keyed by global step (a resumed run only reports the steps it
    actually executed)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt as G
    from .resilience import run_resilient

    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=16, dtype=jnp.float32)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2)
    # run_resilient falls back to the state a rejected step was given
    step, shard_params, init_state = G.build_hybrid_train_step(
        cfg, mesh, opt, donate=False)
    params = shard_params(params)
    state = {"params": params, "opt": init_state(params)}
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)))

    def step_fn(st, i):
        del i
        p, s, loss = step(st["params"], st["opt"], tokens, labels,
                          jnp.float32(1e-2))
        return {"params": p, "opt": s}, loss

    losses = {}
    _, info = run_resilient(step_fn, state, steps=steps, ckpt_dir=ckpt_dir,
                            ckpt_every=1,
                            layout_extra=init_state.layout_extra,
                            on_step=lambda i, l: losses.__setitem__(i, l))
    return losses, info


def run_training_fleet(mesh, steps: int, store, rank: int, world: int,
                       slow_ms: float = 0.0, interval: int = 4):
    """The seed-deterministic tiny-GPT workload driven through
    ``run_resilient(aggregator=)`` with a fleet TelemetryAggregator over
    the shared TCP store — the fleet-telemetry leg's one workload copy.
    ``slow_ms`` injects a per-step stall on THIS rank (the synthetic
    straggler the detector must flag). Returns run_resilient's info;
    rank 0's ``info["fleet"]`` carries the last aggregate report
    (per-host medians/p95, skew, stragglers)."""
    import tempfile
    import time as _time
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt as G
    from paddle_tpu.observability import TelemetryAggregator
    from .resilience import run_resilient

    cfg = G.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=16, dtype=jnp.float32)
    params = G.init_hybrid_params(cfg, jax.random.PRNGKey(0))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2)
    # run_resilient falls back to the state a rejected step was given
    step, shard_params, init_state = G.build_hybrid_train_step(
        cfg, mesh, opt, donate=False)
    params = shard_params(params)
    state = {"params": params, "opt": init_state(params)}
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)))
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)))

    def step_fn(st, i):
        del i
        if slow_ms > 0:
            _time.sleep(slow_ms / 1e3)  # the injected straggler stall
        p, s, loss = step(st["params"], st["opt"], tokens, labels,
                          jnp.float32(1e-2))
        return {"params": p, "opt": s}, loss

    agg = TelemetryAggregator(rank=rank, world_size=world, store=store,
                              host=rank, interval=interval, window=16,
                              straggler_factor=1.35, gather_timeout_s=60.0)
    ckpt_dir = tempfile.mkdtemp(prefix="fleet_ck_")
    try:
        _, info = run_resilient(step_fn, state, steps=steps,
                                ckpt_dir=ckpt_dir, ckpt_every=0,
                                resume=False, aggregator=agg)
    finally:
        import shutil
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    return info


def fleet_telemetry_check(n_devices: int, timeout: float = 300.0,
                          steps: int = 8, slow_ms: float = 80.0) -> dict:
    """Fleet-telemetry leg: a 2-process dp2 x mp(n/2) cluster trains with
    a TelemetryAggregator over a real cross-process TCP store; rank 1 is
    artificially slowed by `slow_ms` per step, and rank 0's aggregate
    MUST flag exactly host 1 as the straggler (skew above the 1.35
    factor) with the straggler_detected event emitted. Returns a summary
    dict for the dryrun record."""
    from .. import _native
    if _native.load() is None:
        raise ClusterUnsupported(
            "fleet telemetry leg needs the native TCPStore (cross-process "
            "KV); the pure-Python fallback store is in-process only")
    assert n_devices % 2 == 0 and n_devices >= 4, n_devices
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    store_port = s.getsockname()[1]
    s.close()
    results = spawn_cluster(
        [sys.executable, "-m", "paddle_tpu.distributed.mp_smoke"],
        nproc=2, devices_per_proc=n_devices // 2, sentinel="MPSMOKE ",
        timeout=timeout,
        extra_env={"MPSMOKE_MODE": "fleet",
                   "MPSMOKE_STORE_PORT": str(store_port),
                   "MPSMOKE_STEPS": str(steps),
                   "MPSMOKE_SLOW_MS": str(slow_ms)})
    r0 = next((r for r in results if r and r.get("rank") == 0), None)
    assert r0 is not None and r0.get("fleet"), results
    rep = r0["fleet"]
    if rep["stragglers"] != [1]:
        raise AssertionError(
            f"straggler detector flagged {rep['stragglers']}, expected "
            f"[1] (rank 1 slowed by {slow_ms} ms/step); report: {rep}")
    if not rep["skew"] or rep["skew"] <= 1.35:
        raise AssertionError(f"skew {rep['skew']} not above the 1.35 "
                             f"straggler factor; report: {rep}")
    return {"stragglers": rep["stragglers"],
            "skew": round(rep["skew"], 2),
            "fleet_median_ms": round(rep["fleet_median_ms"], 2),
            "hosts": {h: round(st["median_ms"], 2)
                      for h, st in rep["hosts"].items()}}


def elastic_restart_check(n_devices: int, ckpt_dir: str, devices=None,
                          timeout: float = 300.0, steps: int = 6,
                          kill_at: int = 3) -> dict:
    """Elastic-restart leg: a 2-process dp2 x mp(n/2) cluster trains with
    per-step commits and is HARD-KILLED (fault injection, exit 41) before
    step `kill_at`+1; the launcher then resumes the run on a 1-process
    dp1 x mp(n/2) mesh — half the chips, the preemption-shrink shape —
    where the resilient driver detects the recorded mesh mismatch and
    reshards on load. The full trajectory must match an uninterrupted
    single-process mesh-B golden (same 5e-5 budget as the other
    cross-process parity legs). Returns a summary dict for the dryrun
    record."""
    import jax
    import paddle_tpu as paddle
    from .topology import build_mesh
    from .resilience import faults
    from .resilience.commit import checkpoint_step, latest_checkpoint

    assert n_devices % 2 == 0 and n_devices >= 4, n_devices
    devices = devices if devices is not None else jax.devices()
    mesh_b = build_mesh({"dp": 1, "pp": 1, "mp": n_devices // 2},
                        devices=devices[:n_devices // 2])
    golden_dir = ckpt_dir + ".golden"
    old = paddle.get_flags(["FLAGS_ckpt_reshard"])
    paddle.set_flags({"FLAGS_ckpt_reshard": True})
    try:
        golden, _ = run_training_resilient(mesh_b, steps, golden_dir)
        # phase 1: 2-process mesh A (dp across the process boundary),
        # killed by injection entering step kill_at (commits 1..kill_at)
        spawn_cluster(
            [sys.executable, "-m", "paddle_tpu.distributed.mp_smoke"],
            nproc=2, devices_per_proc=n_devices // 2, sentinel="MPSMOKE ",
            timeout=timeout,
            ok_returncodes=(faults.FAULT_EXIT_CODE,),
            extra_env={"MPSMOKE_MODE": "elastic",
                       "MPSMOKE_CKPT": ckpt_dir,
                       "FLAGS_ckpt_reshard": "1",
                       "FLAGS_fault_inject":
                           f"loop/before_step:{kill_at + 1}:kill"})
        ck = latest_checkpoint(ckpt_dir, gc=False)
        assert ck is not None and checkpoint_step(ck) == kill_at, ck
        # phase 2: resume on the shrunken 1-process mesh B
        resumed, info = run_training_resilient(mesh_b, steps, ckpt_dir)
        assert info["resumed_from"] == ck, info
        assert info.get("resharded") is True, info
        assert sorted(resumed) == list(range(kill_at, steps)), resumed
        for i, l in resumed.items():
            if abs(l - golden[i]) > 5e-5:
                raise AssertionError(
                    f"elastic resume loss diverged at step {i}: {l} vs "
                    f"golden {golden[i]}")
        return {"killed_at_step": kill_at, "resumed_steps": len(resumed),
                "resharded": True,
                "max_loss_diff": max(abs(resumed[i] - golden[i])
                                     for i in resumed)}
    finally:
        paddle.set_flags(old)
        import shutil
        shutil.rmtree(golden_dir, ignore_errors=True)


# "dpmp" is the hybrid
# dp-across-processes layout; the pp modes put the PIPELINE axis on the
# process boundary — each stage lives on its own process and the
# 1F1B/ZBH1/interleaved ppermute hops cross it, the reference's dominant
# multi-node integration (fleet/meta_parallel/pp_utils/
# p2p_communication.py:570 cross-node p2p). "ppvpp" is the interleaved
# virtual-pipeline schedule over the same boundary (each rank's V chunk
# wrap rides the circular permute across processes). "epmoe" puts the
# EXPERT-parallel axis on the process boundary: the GPT-MoE
# dispatch/combine all-to-alls (index dispatch) cross it every layer.
# "sepring" runs ring attention with the SEP axis spanning both
# processes — the ring's neighbor hops at the process edges are
# cross-process ppermutes (2 of n hops with the contiguous hybrid
# layout; the long-context DCN path at this box's fidelity).
# mode -> dict(dims builder, microbatches, schedule, zero1, vpp, moe)
_MODES = {
    "dpmp": dict(dims=lambda n: {"dp": 2, "pp": 1, "mp": n // 2}),
    # zero1 stage-1 over the dp axis that SPANS the two processes: the
    # grad reduce-scatter and param all-gather hops cross the boundary
    "z1dpmp": dict(dims=lambda n: {"dp": 2, "pp": 1, "mp": n // 2},
                   zero1=True),
    # zero3 over the dp axis that SPANS the two processes: the per-block
    # param all-gathers (and their reduce-scatter transposes) cross the
    # boundary every layer of every step
    "z3dpmp": dict(dims=lambda n: {"dp": 2, "pp": 1, "mp": n // 2},
                   zero_stage=3),
    "pp1f1b": dict(dims=lambda n: {"pp": 2, "dp": 1, "mp": n // 2}, m=4),
    "ppzbh1": dict(dims=lambda n: {"pp": 2, "dp": 1, "mp": n // 2}, m=4,
                   schedule="ZBH1"),
    "ppvpp": dict(dims=lambda n: {"pp": 2, "dp": 1, "mp": n // 2}, m=4,
                  vpp=2),
    "epmoe": dict(dims=lambda n: {"ep": 2, "dp": 1, "pp": 1,
                                  "mp": n // 2}, moe=True),
    "sepring": dict(dims=lambda n: {"sep": n}),
}


def _mode_training_kwargs(mode_cfg):
    return dict(num_microbatches=mode_cfg.get("m", 1),
                schedule=mode_cfg.get("schedule", "1F1B"),
                zero1=mode_cfg.get("zero1", False),
                zero_stage=mode_cfg.get("zero_stage", 0),
                virtual_pp=mode_cfg.get("vpp", 1),
                moe=mode_cfg.get("moe", False))


def run_ring(mesh, steps: int = 3):
    """Seed-deterministic ring-attention fwd+grad over the mesh's 'sep'
    axis (einsum tier — portable to the gloo CPU backend); returns a
    per-step scalar series every rank can compare against the
    single-process golden."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from .fleet.meta_parallel import ring_attention
    from ..utils import shard_map

    B, S, H, D = 2, 8 * mesh.devices.size, 2, 8
    rng = np.random.RandomState(0)
    qkv = [jnp.asarray(rng.randn(B, S, H, D).astype(np.float32) * 0.3)
           for _ in range(3)]

    def loss(q, k, v):
        out = ring_attention(q, k, v, axis="sep", causal=True,
                             impl="einsum")
        return jax.lax.psum(jnp.sum(out.astype(jnp.float32) ** 2), "sep")

    spec = P(None, "sep")
    f = shard_map(loss, mesh=mesh, in_specs=(spec,) * 3, out_specs=P())
    gfn = jax.jit(jax.grad(f))  # descend on q only
    vals = []
    q, k, v = qkv
    for _ in range(steps):
        q = q - 0.05 * gfn(q, k, v)
        vals.append(float(jax.device_get(f(q, k, v))))
    return vals


def main():
    from . import env as dist_env
    from .topology import build_mesh

    dist_env.init_parallel_env()
    import jax

    mode = os.environ.get("MPSMOKE_MODE", "dpmp")
    n = len(jax.devices())
    if mode == "fleet":
        # fleet-telemetry worker: dp spans the two processes; every rank
        # publishes its step-time window + prom snapshot through the
        # launcher's TCP store, rank 0 aggregates and must flag the
        # slowed rank as a straggler
        from .store import MasterStore
        rank = jax.process_index()
        mesh = build_mesh({"dp": 2, "pp": 1, "mp": n // 2})
        store = MasterStore(
            f"127.0.0.1:{os.environ['MPSMOKE_STORE_PORT']}", 2, rank,
            timeout=60.0)  # bounded: a dead sibling must not wedge us
        #                    past the launcher's spawn timeout
        slow = (float(os.environ.get("MPSMOKE_SLOW_MS", "0"))
                if rank == 1 else 0.0)
        info = run_training_fleet(
            mesh, steps=int(os.environ.get("MPSMOKE_STEPS", "8")),
            store=store, rank=rank, world=2, slow_ms=slow)
        print("MPSMOKE " + json.dumps(
            {"rank": rank, "mode": mode, "fleet": info.get("fleet")}),
            flush=True)
        # rank 0 hosts the store server: linger briefly so a slower peer
        # can finish its last publish before the server dies with us
        if rank == 0:
            import time as _time
            _time.sleep(1.0)
        return
    if mode == "elastic":
        # elastic-restart worker: dp spans the two processes, per-step
        # crash-safe commits with layout metadata; the launcher arms
        # loop/before_step:N:kill so both ranks die mid-run and later
        # resumes the checkpoint on a 1-process mesh
        mesh = build_mesh({"dp": 2, "pp": 1, "mp": n // 2})
        losses, info = run_training_resilient(
            mesh, steps=int(os.environ.get("MPSMOKE_STEPS", "6")),
            ckpt_dir=os.environ["MPSMOKE_CKPT"])
        print("MPSMOKE " + json.dumps(
            {"rank": jax.process_index(), "mode": mode,
             "losses": {str(k): v for k, v in losses.items()},
             "resumed_from": info["resumed_from"]}), flush=True)
        return
    mode_cfg = _MODES[mode]
    mesh = build_mesh(mode_cfg["dims"](n))
    if mode == "sepring":
        # the sep ring must CROSS the process boundary somewhere: count
        # neighbor pairs (incl. the wraparound) on different processes
        procs = [d.process_index for d in mesh.devices]
        crossings = sum(procs[i] != procs[(i + 1) % len(procs)]
                        for i in range(len(procs)))
        assert crossings >= 2, procs  # contiguous layout: 2 of n hops
        vals = run_ring(mesh)
        print("MPSMOKE " + json.dumps(
            {"rank": jax.process_index(), "mode": mode, "losses": vals}),
            flush=True)
        return
    ax = dict(zip(mesh.axis_names, range(len(mesh.axis_names))))
    if mode == "epmoe":
        # ep across the PROCESS boundary (the dispatch/combine
        # all-to-alls cross it), mp intra-process
        dev = np.moveaxis(mesh.devices, (ax["ep"], ax["mp"]), (0, -1))
        dev = dev.reshape(2, -1)
        for e in range(2):
            assert len({d.process_index for d in dev[e]}) == 1, mode
        assert dev[0, 0].process_index != dev[1, 0].process_index, mode
    else:
        dev = np.moveaxis(mesh.devices,
                          (ax["dp"], ax["pp"], ax["mp"]), (0, 1, 2))
        if mode in ("dpmp", "z1dpmp"):
            # hybrid-layout invariant: mp intra-process, dp across
            # processes
            assert len({d.process_index for d in dev[0, 0, :]}) == 1
            assert dev[0, 0, 0].process_index != dev[1, 0, 0].process_index
        else:
            # pp across the PROCESS boundary: each stage entirely on one
            # process, stages on different processes
            for s in range(2):
                assert len({d.process_index for d in dev[0, s, :]}) == 1, \
                    mode
            assert dev[0, 0, 0].process_index != dev[0, 1, 0].process_index
    losses = run_training(mesh, **_mode_training_kwargs(mode_cfg))
    print("MPSMOKE " + json.dumps(
        {"rank": jax.process_index(), "mode": mode, "losses": losses}),
        flush=True)


def spawn_and_check(n_devices: int, golden, timeout: float = 300.0,
                    mode: str = "dpmp") -> None:
    """Spawn the 2-process cluster (n_devices/2 virtual CPU devices per
    process) and assert its loss curve matches `golden` (the single-process
    run of `run_training` on the same mesh shape)."""
    assert n_devices % 2 == 0 and n_devices >= 4, n_devices
    results = spawn_cluster(
        [sys.executable, "-m", "paddle_tpu.distributed.mp_smoke"],
        nproc=2, devices_per_proc=n_devices // 2, sentinel="MPSMOKE ",
        timeout=timeout, extra_env={"MPSMOKE_MODE": mode})
    for res in results:
        if not np.allclose(res["losses"], golden, rtol=0, atol=5e-5):
            raise AssertionError(
                f"2-process ({mode}) loss curve {res['losses']} != "
                f"single-process {golden}")


def golden_for(n_devices: int, mode: str = "dpmp", devices=None):
    """Single-process golden loss curve for a spawn mode (same mesh dims,
    same schedule, one process)."""
    from .topology import build_mesh
    mode_cfg = _MODES[mode]
    mesh = build_mesh(mode_cfg["dims"](n_devices), devices=devices)
    if mode == "sepring":
        return run_ring(mesh)
    return run_training(mesh, **_mode_training_kwargs(mode_cfg))


if __name__ == "__main__":
    main()
