"""Collective controller: rendezvous + pod build + watch loop (reference:
launch/controllers/collective.py:22 CollectiveController.build_pod — peer
sync via master KV :37, worker env injection :120-133;
launch/controllers/master.py:73 HTTPMaster/ETCDMaster sync_peers;
elastic restart: fleet/elastic/manager.py:125, exit codes :33-34).

TPU shape: the master KV is our native TCPStore (csrc/native_runtime.cpp);
worker processes get both the reference env names (PADDLE_TRAINER_ID, ...)
and the knobs jax.distributed.initialize reads, so user scripts can call
paddle_tpu.distributed.init_parallel_env() unchanged on a pod slice.
"""

from __future__ import annotations
from ...enforce import PreconditionNotMetError, enforce

import json
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

from ..store import TCPStore
from .context import Context

__all__ = ["CollectiveController", "ELASTIC_AUTO_PARALLEL_EXIT_CODE",
           "ELASTIC_EXIT_CODE"]

ELASTIC_EXIT_CODE = 101           # worker requests rescheduling
ELASTIC_AUTO_PARALLEL_EXIT_CODE = 102


def _tpu_host(envs) -> bool:
    """Whether workers started here would run on TPU chips — decided
    WITHOUT touching jax (the launcher must never load the TPU library:
    its workers need the chips): the platform is not pinned to the CPU
    and the host exposes accelerator device nodes."""
    import glob
    if str(envs.get("JAX_PLATFORMS", "")).startswith("cpu"):
        return False
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


class Master:
    """Rendezvous over the TCPStore: every node publishes its endpoints,
    node 0 aggregates and republishes the full list."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        args = ctx.args
        if args.master:
            host, port = args.master.rsplit(":", 1)
            self.store = TCPStore(host, int(port),
                                  world_size=args.nnodes,
                                  is_master=(args.node_rank == 0),
                                  timeout=args.rdzv_timeout)
        else:
            enforce(args.nnodes == 1, "--master required for multi-node",
                    op="launch", error=PreconditionNotMetError)
            self.store = TCPStore("127.0.0.1", 0, world_size=1,
                                  is_master=True,
                                  timeout=args.rdzv_timeout)

    def sync_peers(self, my_endpoints: List[str], generation: int = 0):
        """Returns the globally-ordered endpoint list."""
        args = self.ctx.args
        key = f"rdzv/{args.job_id}/{generation}"
        self.store.set(f"{key}/node_{args.node_rank}",
                       json.dumps(my_endpoints))
        if args.node_rank == 0:
            all_eps: List[str] = []
            for n in range(args.nnodes):
                eps = json.loads(self.store.get(
                    f"{key}/node_{n}", timeout=self.ctx.args.rdzv_timeout))
                all_eps.extend(eps)
            self.store.set(f"{key}/all", json.dumps(all_eps))
        raw = self.store.get(f"{key}/all",
                             timeout=self.ctx.args.rdzv_timeout)
        return json.loads(raw)


class Container:
    """One worker process (reference: launch/job/container.py)."""

    def __init__(self, cmd: List[str], env: dict, log_path: str):
        self.cmd = cmd
        self.env = env
        self.log_path = log_path
        self.proc: Optional[subprocess.Popen] = None

    def start(self):
        os.makedirs(os.path.dirname(self.log_path), exist_ok=True)
        self._log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(self.cmd, env=self.env,
                                     stdout=self._log,
                                     stderr=subprocess.STDOUT)

    def poll(self):
        return self.proc.poll() if self.proc else None

    def terminate(self, grace: float = 5.0):
        if self.proc and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if getattr(self, "_log", None) is not None:
            self._log.close()  # elastic restarts must not leak worker fds
            self._log = None


class CollectiveController:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.master = Master(ctx)
        self.containers: List[Container] = []
        self.restarts = 0
        self.rescales = 0
        self.generation = 0
        self._elastic = None
        if ctx.args.elastic_level >= 1:
            from .elastic import ElasticManager
            # --elastic_np shapes the FIRST pod directly (no wasted
            # build-then-rescale cycle, no restart credit burned)
            want = getattr(ctx.args, "elastic_np", 0)
            if want and want % ctx.args.nnodes == 0:
                ctx.nproc = want // ctx.args.nnodes
            world = ctx.args.nnodes * ctx.nproc
            self._elastic = ElasticManager(
                self.master.store, ctx.args.job_id, np=world)
            self._rescale_seen = self._elastic.rescale_seq()

    def _gen_key(self) -> str:
        return f"rdzv/{self.ctx.args.job_id}/generation"

    def _current_generation(self) -> int:
        # add(key, 0) = atomic non-blocking read of the counter
        return self.master.store.add(self._gen_key(), 0)

    # -- pod build -----------------------------------------------------------
    def _worker_env(self, global_rank: int, local_rank: int,
                    endpoints: List[str], coordinator: str) -> dict:
        ctx = self.ctx
        env = dict(ctx.envs)
        env.update({
            # reference names (ported scripts keep working)
            "PADDLE_TRAINER_ID": str(global_rank),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_TRAINERS_NUM": str(len(endpoints)),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "PADDLE_CURRENT_ENDPOINT": endpoints[global_rank],
            "PADDLE_MASTER": ctx.args.master or "",
            "PADDLE_JOB_ID": ctx.args.job_id,
            # elastic: scripts check this to auto-resume from checkpoints
            # (reference: PADDLE_RESTART semantics in elastic manager)
            "PADDLE_RESTART_COUNT": str(self.restarts + self.rescales),
            # workers may opt into heartbeats via launch.elastic
            "PADDLE_ELASTIC_STORE_ENDPOINT":
                f"{self.master.store.host}:{self.master.store.port}",
            # jax.distributed knobs (read by init_parallel_env)
            "JAX_COORDINATOR_ADDRESS": coordinator,
            "JAX_NUM_PROCESSES": str(len(endpoints)),
            "JAX_PROCESS_ID": str(global_rank),
        })
        if ctx.node.device_ids and len(ctx.node.device_ids) > 1:
            env["PADDLE_DEVICE_ID"] = ctx.node.device_ids[
                local_rank % len(ctx.node.device_ids)]
        return env

    def build_pod(self, generation: int = 0) -> List[str]:
        self.generation = generation
        if self._elastic is not None:
            self._elastic.invalidate_cache()
            # Stale membership from the previous generation must not trip
            # the hang detector while the new pod registers. Only node 0
            # cleans: workers start strictly after node 0 publishes its
            # endpoints (sync_peers), which happens after this block — a
            # per-node delete would race new registrations on fast nodes.
            if self.ctx.args.node_rank == 0:
                for r in range(self._elastic.np):
                    self._elastic.store.delete_key(
                        self._elastic._key("member", r))
                    self._elastic.store.delete_key(
                        self._elastic._key("hb", r))
                self._elastic.store.delete_key(
                    self._elastic._key("registered_count"))
        ctx = self.ctx
        if ctx.nproc > 1 and _tpu_host(ctx.envs):
            # nothing here confines a worker to one chip
            # (PADDLE_DEVICE_ID is a label): N workers would each open
            # every chip, and a chip belongs to one process
            raise RuntimeError(
                f"--nproc_per_node {ctx.nproc} on a TPU host: every worker "
                "would open all of the host's chips, and a chip belongs to "
                "one process. Launch ONE process per host "
                "(--nproc_per_node 1) and build the mesh over its chips in "
                "that process (paddle_tpu.distributed.build_mesh).")
        from ...flags import flag
        base_port = (int(flag("launch_base_port"))
                     + (os.getpid() + generation * 131) % 2000)
        my_eps = [f"{ctx.node.ip}:{base_port + i}" for i in range(ctx.nproc)]
        endpoints = self.master.sync_peers(my_eps, generation)
        coordinator = endpoints[0].rsplit(":", 1)[0] + ":" + str(
            int(endpoints[0].rsplit(":", 1)[1]) + 1000)

        self.containers = []
        first_global = ctx.args.node_rank * ctx.nproc
        for lr in range(ctx.nproc):
            gr = first_global + lr
            env = self._worker_env(gr, lr, endpoints, coordinator)
            cmd = [sys.executable, ctx.args.training_script,
                   *ctx.args.training_script_args]
            log = os.path.join(ctx.args.log_dir,
                               f"{ctx.args.job_id}.rank{gr}.log")
            self.containers.append(Container(cmd, env, log))
        for c in self.containers:
            c.start()
        return endpoints

    # -- watch / elastic -----------------------------------------------------
    def _restartable(self, code: int) -> bool:
        """Level 1 restarts only explicit reschedule requests (reference
        exit-code contract); level >= 2 restarts any failure."""
        if self.ctx.args.elastic_level >= 2:
            return True
        return code in (ELASTIC_EXIT_CODE, ELASTIC_AUTO_PARALLEL_EXIT_CODE)

    def _restart_pod(self):
        """Bump the shared generation counter so EVERY node (not just the
        failing one) tears down and re-rendezvouses at the new generation."""
        new_gen = self.master.store.add(self._gen_key(), 1)
        self.restarts += 1
        self.build_pod(generation=new_gen)

    def _adopt_np(self, new_np: int) -> bool:
        """Adopt a new desired world size (shared by the driving node and
        multi-node followers). Rejects non-divisible requests with a
        warning — a bad external scale_job() must not kill a healthy
        job."""
        ctx = self.ctx
        if new_np <= 0 or new_np % ctx.args.nnodes != 0:
            print(f"elastic rescale rejected: desired np {new_np} not "
                  f"divisible by nnodes {ctx.args.nnodes}", file=sys.stderr)
            return False
        ctx.nproc = new_np // ctx.args.nnodes
        self._elastic.np = new_np
        self._elastic.invalidate_cache()
        return True

    def _rescale_pod(self, new_np: int):
        """Scale in/out (reference: fleet/elastic/manager.py watching
        PADDLE_ELASTIC_NP): adopt the new world size, tear the pod down
        and re-rendezvous at a bumped generation (multi-node followers
        pick the change up through the generation counter)."""
        if not self._adopt_np(new_np):
            return
        for c in self.containers:
            c.terminate()
        # a rescale is not a failure: it doesn't consume max_restarts
        # budget, but workers still see a bumped PADDLE_RESTART_COUNT so
        # checkpoint auto-resume kicks in
        self.rescales += 1
        new_gen = self.master.store.add(self._gen_key(), 1)
        self.build_pod(generation=new_gen)

    def watch(self, poll_interval: float = 0.2) -> int:
        """Wait for the pod. On worker failure: tear down (level 0), or
        rebuild across all nodes up to max_restarts (level >= 1 for
        reschedule exit codes, level >= 2 for any failure). Hung workers
        that opted into heartbeats (launch.elastic.worker_heartbeat) are
        treated as failures. Returns the job exit code."""
        ctx = self.ctx
        while True:
            codes = [c.poll() for c in self.containers]
            if all(c == 0 for c in codes):
                return 0

            # scale in/out: someone bumped the rescale counter via
            # scale_job(); node 0 drives, other nodes follow through the
            # generation bump below. The counter poll is one cheap
            # non-blocking add(key, 0) per tick (a desired_np get would
            # block 50 ms per tick in the steady state).
            if (self._elastic is not None and ctx.args.node_rank == 0
                    and self._elastic.rescale_seq() > self._rescale_seen):
                self._rescale_seen = self._elastic.rescale_seq()
                if self._elastic.need_rescale():
                    self._rescale_pod(self._elastic.desired_np())
                    continue

            # another node already moved to a newer generation: follow it
            # (adopting any rescaled world size first)
            if ctx.args.elastic_level >= 1 and ctx.is_multi_node:
                cur = self._current_generation()
                if cur > self.generation:
                    for c in self.containers:
                        c.terminate()
                    if (self._elastic is not None
                            and self._elastic.need_rescale()):
                        self._adopt_np(self._elastic.desired_np())
                    self.restarts += 1
                    self.build_pod(generation=cur)
                    continue

            failed = [(i, c) for i, c in enumerate(codes)
                      if c is not None and c != 0]
            # hang check is scoped to LOCAL ranks whose process is still
            # alive: finished ranks are never re-judged, and heartbeat
            # timestamps are compared against the clock that wrote them
            hung = []
            if self._elastic is not None:
                first = ctx.args.node_rank * ctx.nproc
                running = [first + i for i, c in enumerate(codes)
                           if c is None]
                if running:
                    hung = self._elastic.dead_registered_members(running)
            if failed or hung:
                for c in self.containers:
                    c.terminate()
                code = failed[0][1] if failed else ELASTIC_EXIT_CODE
                if (ctx.args.elastic_level >= 1
                        and self.restarts < ctx.args.max_restarts
                        and self._restartable(code)):
                    self._restart_pod()
                    continue
                return code
            time.sleep(poll_interval)

    def stop(self):
        for c in self.containers:
            c.terminate()
        self.master.store.close()

    def run(self) -> int:
        self.build_pod()
        try:
            return self.watch()
        finally:
            self.stop()
