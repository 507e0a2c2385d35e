"""Fleet facade (reference: python/paddle/distributed/fleet/fleet.py —
init :218, distributed_model via fleet/model.py:32, distributed_optimizer
:~1100, collective_perf :632 `_collective_perf_impl` :572).

TPU design: `fleet.init` builds the hybrid mesh (CommunicateTopology →
HybridCommunicateGroup over jax devices) instead of spinning up NCCL process
groups; worker identity comes from jax.process_index/count (the TPU
coordination service replaces PaddleCloud envs + TCPStore rendezvous).
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import numpy as np
from ...enforce import (InvalidArgumentError,
                        PreconditionNotMetError, enforce,
                        enforce_eq)

from ..topology import (CommunicateTopology, HybridCommunicateGroup,
                        set_hybrid_communicate_group)
from .distributed_strategy import DistributedStrategy

__all__ = ["Fleet", "fleet", "init", "distributed_model",
           "distributed_optimizer", "get_hybrid_communicate_group",
           "collective_perf", "DistributedStrategy"]

_AXIS_TO_NAME = {"dp": "data", "pp": "pipe", "sharding": "sharding",
                 "sep": "sep", "mp": "model"}


class Fleet:
    def __init__(self):
        self._is_initialized = False
        self._hcg: Optional[HybridCommunicateGroup] = None
        self._strategy: Optional[DistributedStrategy] = None
        self._is_collective = True

    # -- lifecycle -----------------------------------------------------------
    def init(self, role_maker=None, is_collective: bool = True,
             strategy: Optional[DistributedStrategy] = None,
             log_level: str = "INFO"):
        """Build the hybrid mesh from strategy.hybrid_configs. Degrees of 1
        everywhere means pure DP over all visible devices."""
        del role_maker, log_level  # PS-style role makers are a non-goal on TPU
        strategy = strategy or DistributedStrategy()
        dims = strategy.mesh_dims()
        n_dev = len(jax.devices())
        degrees = int(np.prod(list(dims.values())))
        if degrees == 1 and n_dev > 1:
            dims = dict(dims)
            dims["dp"] = n_dev  # default: pure data parallel
        else:
            enforce_eq(degrees, n_dev,
                       f"hybrid degrees {dims} multiply to {degrees} but "
                       f"{n_dev} devices are visible", op="fleet.init")
        topo = CommunicateTopology(
            [_AXIS_TO_NAME[a] for a in dims], list(dims.values()))
        self._hcg = HybridCommunicateGroup(topo)
        set_hybrid_communicate_group(self._hcg)
        self._strategy = strategy
        self._is_collective = is_collective
        self._is_initialized = True
        return self

    def reset(self):
        """Clear all process-global fleet state (strategy, HCG, init flag).

        fleet.init is process-global by design (reference semantics: one
        fleet per trainer process, test_dist_base.py:954 spawns a fresh
        subprocess per scenario precisely so state can't leak). In-process
        test suites must call this between scenarios — a leaked strategy
        (e.g. fp16_allreduce=True) silently changes the reduction dtype of
        every later engine built with grad_reduce_dtype="auto"."""
        self._strategy = None
        self._hcg = None
        self._is_initialized = False
        self._is_collective = True
        set_hybrid_communicate_group(None)
        return self

    # -- identity ------------------------------------------------------------
    def is_first_worker(self) -> bool:
        return jax.process_index() == 0

    def worker_index(self) -> int:
        return jax.process_index()

    def worker_num(self) -> int:
        return jax.process_count()

    def is_worker(self) -> bool:
        return True

    def barrier_worker(self):
        from ..collective import barrier
        barrier()

    # -- accessors -----------------------------------------------------------
    def get_hybrid_communicate_group(self) -> HybridCommunicateGroup:
        enforce(self._hcg is not None, "call fleet.init first",
                op="fleet", error=PreconditionNotMetError)
        return self._hcg

    def is_initialized(self):
        return self._is_initialized

    @property
    def strategy(self):
        return self._strategy

    # -- wrapping ------------------------------------------------------------
    def distributed_model(self, model):
        """Wrap by parallel mode (reference: fleet/model.py:143-172 selects
        ShardingParallel/SegmentParallel/TensorParallel/PipelineParallel)."""
        enforce(self._is_initialized, "call fleet.init first",
                op="fleet.distributed_model",
                error=PreconditionNotMetError)
        hcg = self._hcg
        strat = self._strategy
        if hcg.get_sharding_parallel_world_size() > 1:
            from .meta_parallel.sharding.group_sharded_stage import (
                GroupShardedStage1, GroupShardedStage2, GroupShardedStage3)
            stage = strat.sharding_configs["stage"]
            cls = {1: GroupShardedStage1, 2: GroupShardedStage2,
                   3: GroupShardedStage3}[min(max(stage, 1), 3)]
            return cls(model, mesh=hcg.mesh, axis="sharding")
        if hcg.get_sep_parallel_world_size() > 1:
            from .meta_parallel.segment_parallel import SegmentParallel
            return SegmentParallel(model, mesh=hcg.mesh)
        if (hcg.get_model_parallel_world_size() > 1
                or hcg.get_pipe_parallel_world_size() > 1):
            # TP/PP are shardings on the params/program, not a wrapper
            # protocol: the model's layers already carry placement hints
            # (mpu layers) and the train step is built over hcg.mesh.
            return model
        from ..parallel import DataParallel
        return DataParallel(model)

    def distributed_optimizer(self, optimizer, strategy=None):
        from .meta_optimizers import HybridParallelOptimizer
        if strategy is not None:
            self._strategy = strategy
        s = self._strategy
        if s is not None and getattr(s, "gradient_merge", False):
            k = s.gradient_merge_configs["k_steps"]
            if k > 1:
                from ...optimizer import GradientMergeOptimizer
                optimizer = GradientMergeOptimizer(
                    optimizer, k_steps=k, avg=s.gradient_merge_configs["avg"])
        return HybridParallelOptimizer(optimizer, self._hcg, self._strategy)

    def grad_reduce_dtype(self):
        """Reduction dtype implied by the strategy — bf16 when
        ``strategy.fp16_allreduce`` is set (the reference fp16_allreduce
        meta-optimizer; bf16 is the TPU-native half type). Pass the result
        to build_hybrid_train_step/build_train_step(grad_reduce_dtype=)."""
        import jax.numpy as jnp
        s = self._strategy
        if s is not None and getattr(s, "fp16_allreduce", False):
            return jnp.bfloat16
        return None

    def distributed_scaler(self, scaler):
        from .meta_optimizers import HybridParallelGradScaler
        return HybridParallelGradScaler(scaler, self._hcg)

    # -- comm micro-bench ----------------------------------------------------
    def collective_perf(self, comm_type: str = "allreduce",
                        round: int = 10,  # noqa: A002 (reference arg name)
                        size_and_time: Optional[Dict[int, float]] = None):
        """Micro-benchmark a collective over the full device set; returns
        {size_MB: GB/s} of algorithmic bandwidth (reference fleet.py:572
        prints GB/s vs per-generation expectations)."""
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        sizes_mb = sorted(size_and_time) if size_and_time else [1, 16, 64]
        devs = np.array(jax.devices())
        mesh = Mesh(devs, ("x",))
        n = len(devs)
        results: Dict[int, float] = {}

        def make(op):
            # fn: per-device body; out: shard_map out_specs; vol(bytes) =
            # bytes moved per device (ring-algorithm bandwidth accounting,
            # matching the reference's GB/s tables fleet.py:572)
            if op == "allreduce":
                fn = lambda x: jax.lax.psum(x, "x")
                out = P()
                vol = lambda b: 2 * (n - 1) / n * b
            elif op == "allgather":
                fn = lambda x: jax.lax.all_gather(x, "x", tiled=True)
                out = P()
                vol = lambda b: (n - 1) / n * b
            elif op == "reduce_scatter":
                fn = lambda x: jax.lax.psum_scatter(x, "x", tiled=True)
                out = P("x")
                vol = lambda b: (n - 1) / n * b
            elif op == "broadcast":
                fn = lambda x: jax.lax.all_gather(x[0:1], "x", tiled=True)
                out = P()
                vol = lambda b: b / n
            elif op == "alltoall":
                fn = lambda x: jax.lax.all_to_all(
                    x.reshape(n, -1), "x", 0, 0, tiled=False).reshape(-1)
                out = P("x")
                vol = lambda b: (n - 1) / n * b
            else:
                raise InvalidArgumentError(f"unknown comm_type {op}",
                                           op="collective_perf")
            return fn, out, vol

        fn, out_spec, vol = make(comm_type)
        # the engine's shard_map wrapper (varying-axes check off)
        from ...utils import shard_map as _smap
        for mb in sizes_mb:
            elems = max(mb * (1 << 20) // 4 // (n * n) * (n * n), n * n)
            x = jax.device_put(
                jnp.ones((elems,), jnp.float32),
                NamedSharding(mesh, P("x")))
            smapped = _smap(fn, mesh=mesh, in_specs=P("x"),
                            out_specs=out_spec)
            run = jax.jit(smapped)
            run(x).block_until_ready()  # compile
            t0 = time.perf_counter()
            for _ in range(round):
                out = run(x)
            out.block_until_ready()
            dt = (time.perf_counter() - t0) / round
            gbs = vol(elems * 4) / dt / 1e9
            results[mb] = gbs
        return results


fleet = Fleet()

# module-level convenience API mirroring `paddle.distributed.fleet.*`
init = fleet.init
distributed_model = fleet.distributed_model
distributed_optimizer = fleet.distributed_optimizer
get_hybrid_communicate_group = fleet.get_hybrid_communicate_group
collective_perf = fleet.collective_perf
