"""Parallel topology (reference:
python/paddle/distributed/fleet/base/topology.py — CommunicateTopology :70
cartesian rank mapping, HybridCommunicateGroup :189 building dp/mp/pp/
sharding/sep groups and p2p rings).

TPU design: the topology IS a `jax.sharding.Mesh`. Where the reference builds
one NCCL communicator per axis-group (new_group per dp/mp/pp/... slice), a
TPU program needs only the mesh: collectives name a mesh axis and XLA routes
them over ICI/DCN. HybridCommunicateGroup keeps the reference's query surface
(ranks, degrees, per-axis groups) so Fleet-style code ports, and exposes
`.mesh` for pjit/shard_map.

Axis order matches the reference default ["dp", "pp", "sharding", "sep",
"mp"] (topology.py:73): outermost axes change slowest — dp maps across
hosts/DCN, mp innermost rides the fastest ICI links.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from ..enforce import InvalidArgumentError, enforce_eq
from jax.sharding import Mesh

__all__ = ["CommunicateTopology", "HybridCommunicateGroup", "Group",
           "build_mesh"]


class Group:
    """A set of ranks forming one collective scope (reference:
    python/paddle/distributed/communication/group.py:29). On TPU a Group is a
    view over a mesh axis; `axis_name` is what in-jit collectives reference."""

    _group_counter = itertools.count()

    def __init__(self, rank_in_group: int, group_id: int, ranks: List[int],
                 axis_name: Optional[str] = None, mesh: Optional[Mesh] = None):
        self.rank = rank_in_group
        self.id = group_id
        self.ranks = list(ranks)
        self.nranks = len(ranks)
        self.axis_name = axis_name
        self.mesh = mesh

    @property
    def world_size(self):
        return self.nranks

    @property
    def process_group(self):
        return self

    def get_group_rank(self, rank: int) -> int:
        return self.ranks.index(rank) if rank in self.ranks else -1

    def is_member(self) -> bool:
        return self.rank >= 0

    def __repr__(self):
        return (f"Group(id={self.id}, nranks={self.nranks}, "
                f"axis={self.axis_name}, ranks={self.ranks})")


class CommunicateTopology:
    """Cartesian rank <-> coordinate mapping (reference: topology.py:70)."""

    def __init__(self, hybrid_group_names: Sequence[str] = ("data", "pipe", "sharding", "sep", "model"),
                 dims: Sequence[int] = (1, 1, 1, 1, 1)):
        enforce_eq(len(hybrid_group_names), len(dims),
                   "group names and degrees must align",
                   op="CommunicateTopology")
        self._parallel_names = list(hybrid_group_names)
        self._dims = list(dims)
        self.coordinate = list(itertools.product(*[range(d) for d in dims]))
        self._coord2rank = {c: i for i, c in enumerate(self.coordinate)}
        self._rank2coord = {i: c for i, c in enumerate(self.coordinate)}
        self._world_size = int(np.prod(dims))

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name: str) -> int:
        return self._dims[self._parallel_names.index(axis_name)]

    get_dim_size = get_dim

    def world_size(self) -> int:
        return self._world_size

    def get_rank(self, **kwargs) -> int:
        enforce_eq(len(kwargs), len(self._parallel_names),
                   "get_rank needs one coordinate per axis",
                   op="CommunicateTopology.get_rank")
        coord = tuple(kwargs[n] for n in self._parallel_names)
        return self._coord2rank[coord]

    def get_coord(self, rank: int):
        return self._rank2coord[rank]

    def get_axis_list(self, axis_name: str, index: int) -> List[int]:
        axis = self._parallel_names.index(axis_name)
        return sorted(r for c, r in self._coord2rank.items() if c[axis] == index)

    def get_comm_list(self, axis_name: str) -> List[List[int]]:
        """All groups along `axis_name`: one list of ranks per combination of
        the other axes (reference: topology.py get_comm_list)."""
        axis = self._parallel_names.index(axis_name)
        other_dims = [d for i, d in enumerate(self._dims) if i != axis]
        comm_list = []
        for other in itertools.product(*[range(d) for d in other_dims]):
            ranks = []
            for i in range(self._dims[axis]):
                coord = list(other)
                coord.insert(axis, i)
                ranks.append(self._coord2rank[tuple(coord)])
            comm_list.append(ranks)
        return comm_list

    def get_rank_from_stage(self, global_rank: int, **kwargs) -> int:
        coord = list(self.get_coord(global_rank))
        for k, v in kwargs.items():
            coord[self._parallel_names.index(k)] = v
        return self._coord2rank[tuple(coord)]


def _local_order_key(d):
    """Stable intra-host device order: physical coords when the backend
    exposes them (TPU: (x, y, z) + core), else the global id. Every host must
    sort its local devices the same way or cross-host axes would twist."""
    coords = getattr(d, "coords", None)
    if coords is not None:
        return (0, tuple(coords), getattr(d, "core_on_chip", 0))
    return (1, d.id)


def _split_ici_dcn(shape: Sequence[int], n_local: int):
    """Factor an outer->inner axis-degree list at the per-process device
    count. Returns (dcn_shape, ici_shape) aligned per axis (degree =
    dcn*ici); axes fully across hosts get ici=1, fully intra-host dcn=1, and
    at most one axis straddles the boundary with both factors > 1.

    Raises if the boundary does not fall cleanly (e.g. an inner axis degree
    that does not divide the local device count) — such a mesh would route an
    inner (fast) axis over DCN, which is never what the caller wants."""
    dcn, ici = [], []
    rem = n_local
    for deg in reversed(list(shape)):
        if rem == 1:
            dcn.insert(0, deg)
            ici.insert(0, 1)
        elif deg <= rem:
            if rem % deg:
                raise InvalidArgumentError(
                    f"axis degree {deg} does not divide the remaining "
                    f"intra-host device block {rem} (shape={list(shape)}, "
                    f"devices/process={n_local})")
            ici.insert(0, deg)
            dcn.insert(0, 1)
            rem //= deg
        else:
            if deg % rem:
                raise InvalidArgumentError(
                    f"axis degree {deg} cannot absorb the remaining "
                    f"intra-host device block {rem} (shape={list(shape)}, "
                    f"devices/process={n_local})")
            ici.insert(0, rem)
            dcn.insert(0, deg // rem)
            rem = 1
    return dcn, ici


def _hybrid_device_array(shape: Sequence[int], devices: Sequence) -> np.ndarray:
    """Arrange devices so inner mesh axes ride ICI (intra-process) and outer
    axes cross hosts/DCN (the reference assumes a flat NCCL ring per group —
    SURVEY §5 comm-backend note; on TPU the 2-level ICI+DCN layout is what
    makes mp/sep collectives fast). Equivalent of
    jax.experimental.mesh_utils.create_hybrid_device_mesh keyed off each
    device's process_index."""
    by_proc: Dict[int, list] = {}
    for d in devices:
        by_proc.setdefault(d.process_index, []).append(d)
    procs = sorted(by_proc)
    locals_ = [sorted(by_proc[p], key=_local_order_key) for p in procs]
    n_local = len(locals_[0])
    if any(len(l) != n_local for l in locals_):
        raise InvalidArgumentError(
            "uneven device count per process: "
            + str({p: len(by_proc[p]) for p in procs}))
    dcn_shape, ici_shape = _split_ici_dcn(shape, n_local)

    if all(getattr(d, "platform", "") == "tpu" for d in devices):
        # real TPU: let mesh_utils pick the ICI-optimal intra-slice order
        # (ring/torus-aware); per-axis (ici, dcn) factors from the split.
        try:
            from jax.experimental import mesh_utils
            arr = mesh_utils.create_hybrid_device_mesh(
                tuple(ici_shape), tuple(dcn_shape), devices=devices)
            return arr.reshape(tuple(shape))
        except Exception:
            pass  # fall through to the explicit construction

    flat = np.empty(len(devices), dtype=object)
    for i, ds in enumerate(locals_):
        flat[i * n_local:(i + 1) * n_local] = ds
    # host-major flat order: outer (DCN) axes stride across processes, inner
    # (ICI) axes stay within one process; the straddling axis (if any) has
    # its dcn factor adjacent-outer to its ici factor, so the direct reshape
    # merges them in the right order.
    return flat.reshape(tuple(shape))


def build_mesh(dims: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh with named axes from {axis: degree}. Degrees must multiply
    to the device count (axes of degree 1 are kept so shardings can name
    them).

    Axis order is outer->inner: the LAST axes in `dims` (mp/sep in the
    fleet order) land on the fastest links. Multi-process runs get the
    2-level hybrid layout (inner axes intra-host on ICI, outer axes across
    hosts on DCN); single-process real-TPU runs get mesh_utils' ICI-aware
    device order; everything else is the flat reshape."""
    devices = list(devices if devices is not None else jax.devices())
    total = int(np.prod(list(dims.values())))
    from ..enforce import enforce
    enforce(total == len(devices),
            f"product of parallel degrees {dims} = {total} != device "
            f"count {len(devices)}", op="build_mesh")
    shape = tuple(dims.values())
    n_proc = len({d.process_index for d in devices})
    if n_proc > 1:
        arr = _hybrid_device_array(shape, devices)
    elif all(getattr(d, "platform", "") == "tpu" for d in devices):
        # no silent flat-reshape fallback: a layout mesh_utils cannot
        # place on the physical topology is an error the caller must see
        from jax.experimental import mesh_utils
        arr = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        arr = np.array(devices).reshape(shape)
    return Mesh(arr, tuple(dims.keys()))


class HybridCommunicateGroup:
    """(reference: topology.py:189). Builds the mesh and per-axis Group views.

    Mesh axis names: dp / pp / sharding / sep / mp (the reference's
    data/pipe/sharding/sep/model axes)."""

    AXIS_MAP = {"data": "dp", "pipe": "pp", "sharding": "sharding",
                "sep": "sep", "model": "mp"}

    def __init__(self, topology: CommunicateTopology,
                 devices: Optional[Sequence] = None,
                 global_rank: Optional[int] = None):
        self._topo = topology
        self.nranks = topology.world_size()
        from .env import get_rank
        self.global_rank = get_rank() if global_rank is None else global_rank

        names = topology.get_hybrid_group_names()
        self._dp_degree = topology.get_dim("data") if "data" in names else 1
        self._pp_degree = topology.get_dim("pipe") if "pipe" in names else 1
        self._sharding_degree = topology.get_dim("sharding") if "sharding" in names else 1
        self._sep_degree = topology.get_dim("sep") if "sep" in names else 1
        self._mp_degree = topology.get_dim("model") if "model" in names else 1

        mesh_dims = {self.AXIS_MAP[n]: topology.get_dim(n) for n in names}
        self.mesh = build_mesh(mesh_dims, devices)

        self._groups: Dict[str, Group] = {}
        for name in names:
            axis = self.AXIS_MAP[name]
            comm_list = self._topo.get_comm_list(name)
            my = next((g for g in comm_list if self.global_rank in g), comm_list[0])
            self._groups[axis] = Group(my.index(self.global_rank)
                                       if self.global_rank in my else 0,
                                       next(Group._group_counter), my,
                                       axis_name=axis, mesh=self.mesh)

    # --- degree / rank queries (reference API surface) ---
    def get_parallel_mode(self):
        if self._mp_degree == 1 and self._pp_degree == 1 and self._sharding_degree == 1:
            return "data_parallel" if self._dp_degree > 1 else "single"
        return "hybrid_parallel"

    def topology(self):
        return self._topo

    def get_global_rank(self):
        return self.global_rank

    def get_data_parallel_rank(self):
        return self._groups["dp"].rank

    def get_data_parallel_world_size(self):
        return self._dp_degree

    def get_data_parallel_group(self):
        return self._groups["dp"]

    def get_model_parallel_rank(self):
        return self._groups["mp"].rank

    def get_model_parallel_world_size(self):
        return self._mp_degree

    def get_model_parallel_group(self):
        return self._groups["mp"]

    def get_stage_id(self):
        return self._groups["pp"].rank

    def get_pipe_parallel_rank(self):
        return self._groups["pp"].rank

    def get_pipe_parallel_world_size(self):
        return self._pp_degree

    def get_pipe_parallel_group(self):
        return self._groups["pp"]

    def get_sharding_parallel_rank(self):
        return self._groups["sharding"].rank

    def get_sharding_parallel_world_size(self):
        return self._sharding_degree

    def get_sharding_parallel_group(self):
        return self._groups["sharding"]

    def get_sep_parallel_rank(self):
        return self._groups.get("sep", Group(0, -1, [0])).rank

    def get_sep_parallel_world_size(self):
        return self._sep_degree

    def get_sep_parallel_group(self):
        return self._groups.get("sep")

    def get_rank_from_stage(self, stage_id, **kwargs):
        return self._topo.get_rank_from_stage(self.global_rank, pipe=stage_id,
                                              **kwargs)

    # --- pipeline helpers ---
    def is_first_stage(self):
        return self.get_stage_id() == 0

    def is_last_stage(self):
        return self.get_stage_id() == self._pp_degree - 1


_HCG: List[Optional[HybridCommunicateGroup]] = [None]


def set_hybrid_communicate_group(hcg: HybridCommunicateGroup):
    _HCG[0] = hcg


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _HCG[0]
