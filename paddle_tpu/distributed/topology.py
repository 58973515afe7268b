"""Hybrid-parallel topology — parity with
python/paddle/distributed/fleet/base/topology.py (CommunicateTopology:52,
HybridCommunicateGroup:134) rebuilt as a `jax.sharding.Mesh` factory.

The reference builds a cartesian rank mesh over axes **[data, pipe, sharding,
model]** and creates one NCCL comm group per axis slice (topology.py:157-168).
Here the same cartesian structure IS the device mesh: axis "dp"/"pp"/
"sharding"/"mp" (+"sep" when sequence parallel is on), and "comm groups" are
the named axes themselves — XLA lowers collectives over them onto ICI.  The
HybridCommunicateGroup API surface (get_model_parallel_rank & co.) survives so
fleet user code ports unchanged.
"""
from __future__ import annotations

import itertools
from functools import reduce

import numpy as np

from . import collective as coll
from . import mesh as mesh_mod

# canonical axis order, reference topology.py:134 hybrid_group_names
_AXIS_TO_MESH_NAME = {"data": "dp", "pipe": "pp", "sharding": "sharding",
                      "model": "mp", "sep": "sep"}


class CommunicateTopology:
    """topology.py:52 parity: a named cartesian rank grid."""

    def __init__(self, hybrid_group_names=("data", "pipe", "sharding", "model"),
                 dims=(1, 1, 1, 1)):
        self._parallel_names = list(hybrid_group_names)
        self._dims = list(dims)
        self.coordinate = itertools.product(*(range(d) for d in self._dims))
        self._world = np.arange(int(np.prod(self._dims))).reshape(self._dims)
        self._coord_of = {}
        for coord in np.ndindex(*self._dims):
            self._coord_of[int(self._world[coord])] = coord

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name):
        return self._dims[self._parallel_names.index(axis_name)]

    get_dim_size = get_dim

    def world_size(self):
        return int(self._world.size)

    def get_rank(self, **kwargs) -> int:
        coord = tuple(kwargs[name] for name in self._parallel_names)
        return int(self._world[coord])

    def get_coord(self, rank: int):
        return self._coord_of[rank]

    def get_axis_list(self, axis_name: str, index: int):
        """All ranks whose coordinate on `axis_name` == index."""
        axis = self._parallel_names.index(axis_name)
        sl = [slice(None)] * len(self._dims)
        sl[axis] = index
        return sorted(int(r) for r in self._world[tuple(sl)].reshape(-1))

    def get_comm_list(self, axis_name: str):
        """List of rank-lists, one per communicator along `axis_name`
        (topology.py get_comm_list)."""
        axis = self._parallel_names.index(axis_name)
        other = [d for i, d in enumerate(self._dims) if i != axis]
        comms = []
        moved = np.moveaxis(self._world, axis, -1).reshape(-1, self._dims[axis])
        for row in moved:
            comms.append([int(r) for r in row])
        return comms

    def get_rank_from_stage(self, global_rank, **kwargs):
        coord = list(self.get_coord(global_rank))
        for k, v in kwargs.items():
            coord[self._parallel_names.index(k)] = v
        return int(self._world[tuple(coord)])


class HybridCommunicateGroup:
    """topology.py:134 parity.  Also owns the global `jax.sharding.Mesh` whose
    axis names are the GSPMD handles for every parallelism dimension."""

    def __init__(self, topology: CommunicateTopology):
        self._topo = topology
        from .parallel import get_rank
        self.global_rank = get_rank()
        self.nranks = topology.world_size()

        names = topology.get_hybrid_group_names()
        self._dp_degree = topology.get_dim("data") if "data" in names else 1
        self._pp_degree = topology.get_dim("pipe") if "pipe" in names else 1
        self._sharding_degree = (topology.get_dim("sharding")
                                 if "sharding" in names else 1)
        self._mp_degree = topology.get_dim("model") if "model" in names else 1
        self._sep_degree = topology.get_dim("sep") if "sep" in names else 1

        coord = topology.get_coord(self.global_rank % self.nranks)
        self._coord = dict(zip(names, coord))

        # The device mesh: one named axis per parallel dim, in reference order.
        import jax
        dims, axes = [], []
        for name in names:
            dims.append(topology.get_dim(name))
            axes.append(_AXIS_TO_MESH_NAME.get(name, name))
        self._axis_names = axes
        n_need = int(np.prod(dims))
        n_have = len(jax.devices())
        if n_need <= n_have:
            self.mesh = mesh_mod.build_mesh(dims, axes)
            mesh_mod.set_global_mesh(self.mesh)
        else:
            from .parallel import get_world_size
            if get_world_size() * n_have < n_need:
                raise RuntimeError(
                    f"hybrid topology {dict(zip(names, dims))} needs "
                    f"{n_need} devices, found {n_have} "
                    f"({jax.devices()[0].platform}). For a CPU simulation "
                    f"set JAX_PLATFORMS=cpu and XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={n_need}; on "
                    f"hardware launch across enough hosts.")
            # genuine multi-host launch where jax.distributed has not made
            # remote devices visible yet: keep a logical-only topology,
            # mesh construction is deferred — and say so
            import warnings
            warnings.warn(
                f"hybrid topology {dict(zip(names, dims))} needs "
                f"{n_need} devices but only {n_have} are visible on this "
                f"host; deferring mesh construction until jax.distributed "
                f"exposes the global device set", RuntimeWarning,
                stacklevel=2)
            self.mesh = None

        # per-axis groups bound to mesh axis names
        def _grp(axis, mesh_name):
            if axis not in names:
                return coll.new_group(list(range(1)), axis_name=mesh_name)
            comm = None
            for comm_ranks in topology.get_comm_list(axis):
                if self.global_rank in comm_ranks:
                    comm = comm_ranks
                    break
            comm = comm or topology.get_comm_list(axis)[0]
            return coll.new_group(comm, axis_name=mesh_name)

        self._dp_group = _grp("data", "dp")
        self._pp_group = _grp("pipe", "pp")
        self._sharding_group = _grp("sharding", "sharding")
        self._mp_group = _grp("model", "mp")
        self._sep_group = _grp("sep", "sep") if "sep" in names else None

        # "check group" = mp+pp+sharding combined, used for global-norm clip
        # (topology.py:170-171)
        self._check_group = coll.new_group(list(range(self.nranks)),
                                           axis_name=None)

    # -- parity accessors ---------------------------------------------------
    def get_parallel_mode(self):
        # topology.py get_parallel_mode: returns one of the ParallelMode enum
        from .fleet.base.strategy_group import ParallelMode
        if self._mp_degree == 1 and self._pp_degree == 1 and \
                self._sharding_degree == 1 and self._dp_degree > 1:
            return ParallelMode.DATA_PARALLEL
        if self._pp_degree > 1:
            return ParallelMode.PIPELINE_PARALLEL
        if self._sharding_degree > 1:
            return ParallelMode.SHARDING_PARALLEL
        if self._mp_degree > 1:
            return ParallelMode.TENSOR_PARALLEL
        return ParallelMode.DATA_PARALLEL

    def topology(self):
        return self._topo

    def get_global_rank(self):
        return self.global_rank

    # data parallel
    def get_data_parallel_rank(self):
        return self._coord.get("data", 0)

    def get_data_parallel_world_size(self):
        return self._dp_degree

    def get_data_parallel_group(self):
        return self._dp_group

    def get_data_parallel_group_src_rank(self):
        return self._dp_group.ranks[0]

    # model (tensor) parallel
    def get_model_parallel_rank(self):
        return self._coord.get("model", 0)

    def get_model_parallel_world_size(self):
        return self._mp_degree

    def get_model_parallel_group(self):
        return self._mp_group

    def get_model_parallel_group_src_rank(self):
        return self._mp_group.ranks[0]

    # pipeline
    def get_stage_id(self):
        return self._coord.get("pipe", 0)

    def get_pipe_parallel_world_size(self):
        return self._pp_degree

    def get_pipe_parallel_group(self):
        return self._pp_group

    def is_first_stage(self):
        return self.get_stage_id() == 0

    def is_last_stage(self):
        return self.get_stage_id() == self._pp_degree - 1

    # sharding
    def get_sharding_parallel_rank(self):
        return self._coord.get("sharding", 0)

    def get_sharding_parallel_world_size(self):
        return self._sharding_degree

    def get_sharding_parallel_group(self):
        return self._sharding_group

    def get_sharding_parallel_group_src_rank(self):
        return self._sharding_group.ranks[0]

    # sep (sequence parallel — reference lacks it; TPU extension, SURVEY §5.7)
    def get_sep_parallel_world_size(self):
        return self._sep_degree

    def get_sep_parallel_group(self):
        return self._sep_group

    def get_check_parallel_group(self, *a):
        return self._check_group

    def get_rank_from_stage(self, stage_id, **kwargs):
        return self._topo.get_rank_from_stage(self.global_rank,
                                              pipe=stage_id, **kwargs)

    # -- TPU-native accessors ------------------------------------------------
    @property
    def axis_names(self):
        return list(self._axis_names)

    def get_mesh(self):
        return self.mesh


_HCG: HybridCommunicateGroup | None = None


def set_hybrid_communicate_group(hcg):
    global _HCG
    _HCG = hcg


def get_hybrid_communicate_group() -> HybridCommunicateGroup | None:
    return _HCG
