"""Collective communication (reference: §2.4 of the survey —
ProcessGroupNCCL paddle/phi/core/distributed/collective/process_group_nccl.cc,
Python wrappers python/paddle/distributed/communication/*).

TPU design — two tiers:

1. **In-program (the hot path).** Called inside shard_map/pjit where values
   are per-device shards and mesh axes are in scope: thin wrappers over
   lax.psum / all_gather / psum_scatter / all_to_all / ppermute. XLA
   schedules them onto ICI/DCN; there are no streams, rings or communicator
   caches to manage (ProcessGroupNCCL's stream pool, event sync and
   coalescing all disappear into the compiler).

2. **Eager (compat/test surface).** Single-controller JAX has no per-rank
   eager tensors, so the reference's "every rank calls all_reduce on its
   tensor" maps to a *rank-major* global array: dim 0 is the group dimension
   (size = group.nranks). Eager collectives consume/produce rank-major
   arrays; they are implemented as one-op jitted shard_map programs over the
   group's mesh axis so the same lax collectives execute on real hardware.

The in-program tier dispatches automatically when the input is a tracer.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from .topology import Group

__all__ = ["ReduceOp", "all_reduce", "all_gather", "reduce_scatter",
           "broadcast", "reduce", "scatter", "all_to_all", "send", "recv",
           "ppermute", "barrier", "P2POp", "batch_isend_irecv",
           "new_group", "get_group", "default_axis"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


_REDUCERS = {
    ReduceOp.SUM: lax.psum,
    ReduceOp.MAX: lax.pmax,
    ReduceOp.MIN: lax.pmin,
}

_groups = {}
_default_mesh: List[Optional[Mesh]] = [None]


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def set_default_mesh(mesh: Mesh):
    _default_mesh[0] = mesh


def default_axis(group: Optional[Group]) -> str:
    if group is not None and group.axis_name is not None:
        return group.axis_name
    return "world"


def _world_mesh(n: Optional[int] = None) -> Mesh:
    if _default_mesh[0] is not None:
        return _default_mesh[0]
    devs = np.array(jax.devices() if n is None else jax.devices()[:n])
    return Mesh(devs, ("world",))


def new_group(ranks: Optional[List[int]] = None, backend=None, timeout=None) -> Group:
    """(reference: python/paddle/distributed/communication/group.py new_group).
    Creates a Group over a contiguous device subset as a 1-axis mesh."""
    del backend, timeout
    devs = jax.devices()
    if ranks is None:
        ranks = list(range(len(devs)))
    mesh = Mesh(np.array([devs[r] for r in ranks]), ("world",))
    import itertools
    g = Group(0, next(Group._group_counter), ranks, axis_name="world", mesh=mesh)
    _groups[g.id] = g
    return g


def get_group(gid: int) -> Optional[Group]:
    return _groups.get(gid)


def _reduce_traced(x, op, axis):
    if op == ReduceOp.AVG:
        return lax.pmean(x, axis)
    if op == ReduceOp.PROD:
        return jnp.exp(lax.psum(jnp.log(x.astype(jnp.float32)), axis)).astype(x.dtype)
    return _REDUCERS[op](x, axis)


def _local_axis_positions(mesh: Mesh, axis: str) -> List[int]:
    """The positions along `axis` covered by this process's devices — i.e.
    which rank-major rows of an eager collective this process feeds and
    receives (multi-process runs only own a slice of the group)."""
    ai = mesh.axis_names.index(axis)
    pid = jax.process_index()
    return sorted({idx[ai] for idx, d in np.ndenumerate(mesh.devices)
                   if d.process_index == pid})


def _eager_collective(x, group, per_shard_fn, out_rank_major=True,
                      op_name="collective", scatter_dim=None):
    """Run `per_shard_fn(local)` under shard_map over the group axis, with
    rank-major input (dim 0 = group).

    Multi-process: each process passes only the rows for the group positions
    its devices cover (`_local_axis_positions`, usually one row for a
    cross-host axis, all rows for an intra-host axis) and gets those rows
    back — the reference's per-rank eager semantics
    (python/paddle/distributed/communication/all_reduce.py:29) without any
    process owning the global array."""
    mesh = group.mesh if group is not None and group.mesh is not None else _world_mesh()
    axis = default_axis(group)
    n = mesh.shape[axis]
    from .check import nan_guard, static_check
    in_spec = P(axis)
    fn = shard_map(per_shard_fn, mesh=mesh, in_specs=(in_spec,),
                   out_specs=in_spec if out_rank_major else P(),
                   )
    if jax.process_count() > 1:
        xh = np.asarray(x)
        positions = _local_axis_positions(mesh, axis)
        assert xh.shape[0] == len(positions), (
            f"multi-process eager collective: this process covers group "
            f"positions {positions} of axis '{axis}' and must pass "
            f"{len(positions)} rank-major rows, got shape {xh.shape}")
        static_check(xh, n, op_name, scatter_dim=scatter_dim,
                     expected_dim0=len(positions))
        nan_guard(xh, op_name)
        global_shape = (n,) + tuple(xh.shape[1:])
        sharding = NamedSharding(mesh, in_spec)
        garr = jax.make_array_from_process_local_data(sharding, xh,
                                                      global_shape)
        out = jax.jit(fn)(garr)
        if not out_rank_major:
            return jnp.asarray(np.asarray(out.addressable_shards[0].data))
        rows = {}
        for s in out.addressable_shards:
            start = s.index[0].start or 0
            rows[start] = np.asarray(s.data)
        return jnp.concatenate([rows[i] for i in sorted(rows)], axis=0)
    x = jnp.asarray(x)
    static_check(x, n, op_name, scatter_dim=scatter_dim)
    x = nan_guard(x, op_name)
    assert x.shape[0] == n, (
        f"eager collective expects rank-major input with dim0 == group size "
        f"{n}, got shape {x.shape}")
    return jax.jit(fn)(x)


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------
def all_reduce(tensor, op: str = ReduceOp.SUM, group: Optional[Group] = None,
               sync_op=True, axis: Optional[str] = None):
    if _is_traced(tensor):
        return _reduce_traced(tensor, op, axis or default_axis(group))

    def f(local):
        return _reduce_traced(local, op, default_axis(group))

    return _eager_collective(tensor, group, f, op_name="all_reduce")


def all_gather(tensor_or_list, tensor=None, group: Optional[Group] = None,
               sync_op=True, axis: Optional[str] = None, gather_axis: int = 0,
               tiled: bool = False):
    """In-jit: all_gather(x, axis=...) -> stacked [n, ...] (or concat on
    gather_axis with tiled=True). Eager: rank-major in, [n, n, *S] out
    mirroring the reference's per-rank result list."""
    if tensor is None or _is_traced(tensor_or_list):
        x = tensor_or_list
        if _is_traced(x):
            return lax.all_gather(x, axis or default_axis(group),
                                  axis=gather_axis if tiled else 0,
                                  tiled=tiled)

        def f(local):
            local = local.reshape(local.shape[1:])  # drop rank dim
            g = lax.all_gather(local, default_axis(group))
            return g[None]  # rank-major

        return _eager_collective(x, group, f)
    # list-output compat form: all_gather(out_list, tensor, group)
    out = all_gather(tensor, group=group)
    # out is [k, n, *S] with every row block the identical gathered result
    # (k = group size single-process, locally-covered positions otherwise)
    tensor_or_list.extend([out[0, i] for i in range(out.shape[1])])
    return tensor_or_list


def reduce_scatter(tensor, op: str = ReduceOp.SUM, group: Optional[Group] = None,
                   sync_op=True, axis: Optional[str] = None,
                   scatter_dim: int = 0):
    if _is_traced(tensor):
        return lax.psum_scatter(tensor, axis or default_axis(group),
                                scatter_dimension=scatter_dim, tiled=True)

    def f(local):
        local = local.reshape(local.shape[1:])
        out = lax.psum_scatter(local, default_axis(group),
                               scatter_dimension=scatter_dim, tiled=True)
        return out[None]

    return _eager_collective(tensor, group, f, op_name="reduce_scatter",
                             scatter_dim=scatter_dim)


def broadcast(tensor, src: int = 0, group: Optional[Group] = None,
              sync_op=True, axis: Optional[str] = None):
    ax = axis or default_axis(group)
    src_in_group = group.get_group_rank(src) if group is not None and src in group.ranks else src
    if _is_traced(tensor):
        idx = lax.axis_index(ax)
        masked = jnp.where(idx == src_in_group, tensor,
                           jnp.zeros_like(tensor))
        return lax.psum(masked, ax)

    def f(local):
        local = local.reshape(local.shape[1:])
        idx = lax.axis_index(default_axis(group))
        masked = jnp.where(idx == src_in_group, local, jnp.zeros_like(local))
        return lax.psum(masked, default_axis(group))[None]

    return _eager_collective(tensor, group, f)


def reduce(tensor, dst: int = 0, op: str = ReduceOp.SUM,
           group: Optional[Group] = None, sync_op=True,
           axis: Optional[str] = None):
    """Reduce-to-one. On TPU there is no cheaper 'reduce' than all_reduce
    (the result is SPMD-replicated anyway); non-dst ranks simply ignore it —
    matching XLA's lowering of reduce ops."""
    return all_reduce(tensor, op=op, group=group, axis=axis)


def scatter(tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op=True,
            axis: Optional[str] = None):
    ax = axis or default_axis(group)
    if _is_traced(tensor):
        # tensor: [n, *S] replicated (or same on src); take my slice
        idx = lax.axis_index(ax)
        src_val = broadcast(tensor, src=src, group=group, axis=ax)
        return lax.dynamic_index_in_dim(src_val, idx, axis=0, keepdims=False)

    src_in_group = (group.get_group_rank(src)
                    if group is not None and src in group.ranks else src)

    def f(local):
        local = local.reshape(local.shape[1:])  # [n, *S] view on each rank
        ax2 = default_axis(group)
        idx = lax.axis_index(ax2)
        sv = jnp.where(idx == src_in_group, local, jnp.zeros_like(local))
        sv = lax.psum(sv, ax2)  # broadcast src's [n, *S]
        return lax.dynamic_index_in_dim(sv, idx, axis=0, keepdims=False)[None]

    return _eager_collective(tensor, group, f)


def all_to_all(out_tensor_list, in_tensor_list=None,
               group: Optional[Group] = None, sync_op=True,
               axis: Optional[str] = None, split_axis: int = 0,
               concat_axis: int = 0):
    """In-jit form: all_to_all(x, axis=...) with x's split_axis divided over
    the group and results concatenated on concat_axis (reference op:
    paddle/phi/kernels/gpu/all_to_all_kernel.cu; lowers to ICI all-to-all)."""
    x = out_tensor_list
    if _is_traced(x):
        ax = axis or default_axis(group)
        return lax.all_to_all(x, ax, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)

    def f(local):
        local = local.reshape(local.shape[1:])
        out = lax.all_to_all(local, default_axis(group),
                             split_axis=split_axis, concat_axis=concat_axis,
                             tiled=True)
        return out[None]

    return _eager_collective(x, group, f)


def ppermute(x, perm: Sequence, axis: Optional[str] = None,
             group: Optional[Group] = None):
    """Point-to-point permutation (the TPU-native send/recv: neighbor
    exchange over ICI; reference: isend/irecv + batch_isend_irecv)."""
    ax = axis or default_axis(group)
    if _is_traced(x):
        return lax.ppermute(x, ax, perm=list(perm))

    def f(local):
        local = local.reshape(local.shape[1:])
        return lax.ppermute(local, default_axis(group), perm=list(perm))[None]

    return _eager_collective(x, group, f)


def send(tensor, dst: int, group: Optional[Group] = None, sync_op=True,
         axis: Optional[str] = None):
    """SPMD send half: use ppermute with {me->dst}. Must be paired with recv
    in the same program — see P2POp/batch_isend_irecv for the batched form
    the pipeline engine uses."""
    raise NotImplementedError(
        "point-to-point send/recv are compiled as ppermute pairs on TPU; "
        "use batch_isend_irecv or distributed.ppermute inside the program")


recv = send


class P2POp:
    """(reference: python/paddle/distributed/communication/batch_isend_irecv.py
    P2POp)."""

    def __init__(self, op, tensor, peer: int, group: Optional[Group] = None):
        self.op = op  # "isend" | "irecv" or the send/recv callables
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list: List[P2POp], axis: Optional[str] = None):
    """Execute a batch of matched send/recv as one ppermute (in-jit only).

    Every rank passes its full op list (SPMD); sends define the permutation,
    recvs receive. Returns the received tensors in op-list order."""
    sends = [op for op in p2p_op_list
             if op.op in ("isend", "send") or getattr(op.op, "__name__", "") == "isend"]
    recvs = [op for op in p2p_op_list
             if op.op in ("irecv", "recv") or getattr(op.op, "__name__", "") == "irecv"]
    if not sends:
        return []
    ax = axis or default_axis(sends[0].group)
    results = []
    for s in sends:
        if isinstance(s.peer, (list, tuple)):
            perm = list(s.peer)  # explicit (src, dst) pairs
        else:
            # SPMD ring shift: peer is the uniform offset (+1 = next stage)
            n = s.group.nranks if s.group is not None else len(jax.devices())
            perm = [(i, (i + s.peer) % n) for i in range(n)]
        results.append(lax.ppermute(s.tensor, ax, perm=perm))
    return results


def barrier(group: Optional[Group] = None):
    """Host-level barrier: on TPU in-program ordering is total, so a barrier
    only matters across hosts (reference: barrier op + TCPStore barrier)."""
    import jax
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("paddle_tpu_barrier")
