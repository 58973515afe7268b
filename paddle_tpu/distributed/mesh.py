"""Global device-mesh state.

TPU-native replacement for the reference's ring/communicator registries
(paddle/fluid/platform/collective_helper.h:70 `NCCLCommContext` and
paddle/fluid/distributed/collective/ProcessGroup.h): instead of NCCL rings
keyed by ring_id, parallelism is expressed as named axes of one
``jax.sharding.Mesh``; XLA emits the collectives over ICI/DCN (SURVEY §5.8).
"""
from __future__ import annotations

import contextlib
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_GLOBAL_MESH: Mesh | None = None


def build_mesh(shape: Sequence[int], axis_names: Sequence[str],
               devices=None) -> Mesh:
    """Create a Mesh; `shape` may contain one -1 (inferred from device count).

    The devices are the ones given, else `jax.devices()` — the default
    backend's, whatever it is.  Too few is an error, never a reason to
    build the mesh from another platform's devices (on the CPU, tests get
    their 8 from `--xla_force_host_platform_device_count`)."""
    devices = list(devices if devices is not None else jax.devices())
    shape = list(shape)
    if -1 in shape:
        known = int(np.prod([s for s in shape if s != -1]))
        shape[shape.index(-1)] = len(devices) // known
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(
            f"mesh shape {shape} needs {n} devices, found {len(devices)} "
            f"({devices[0].platform if devices else 'none'})")
    arr = np.array(devices[:n]).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def set_global_mesh(mesh: Mesh | None):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_global_mesh() -> Mesh | None:
    return _GLOBAL_MESH


def bound_axis_size(name) -> int | None:
    """Size of SPMD axis `name` when it is bound in the current trace (i.e.
    inside shard_map over a mesh that has the axis), else None."""
    if name is None:
        return None
    try:
        return jax.lax.axis_size(name)      # a static Python int
    except NameError:           # jax's "unbound axis name"
        return None


def axis_bound(name) -> bool:
    """True when `name` is a bound SPMD axis in the current trace."""
    return bound_axis_size(name) is not None


def put_global(value, sharding):
    """device_put that also works under MULTI-PROCESS meshes: a global
    NamedSharding is not addressable from one process, so the array is
    assembled from per-shard callbacks (each process materializes only its
    addressable shards — the jax.distributed analog of the reference's
    per-trainer feed split)."""
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(value, sharding)
    arr = np.asarray(value)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def sharding_for(spec: PartitionSpec, mesh: Mesh | None = None):
    mesh = mesh or _GLOBAL_MESH
    if mesh is None:
        raise RuntimeError("no global mesh set; call init_parallel_env or "
                           "fleet.init(is_collective=True) first")
    return NamedSharding(mesh, spec)


@contextlib.contextmanager
def global_mesh(mesh: Mesh):
    prev = _GLOBAL_MESH
    set_global_mesh(mesh)
    try:
        yield mesh
    finally:
        set_global_mesh(prev)


def build_hybrid_mesh(dcn_shape: Sequence[int], ici_shape: Sequence[int],
                      axis_names: Sequence[str], devices=None) -> Mesh:
    """Multi-slice mesh: outer axes span slices over DCN, inner axes stay
    inside a slice on ICI — the reference's two-level ProcessGroupHeter
    topology (ProcessGroupHeter.h:128-134 `inner_pg_` NCCL intra-node +
    `inter_pg_` cross-node, SURVEY §5.8).

    `dcn_shape` sizes the outer (cross-slice) axes, `ici_shape` the inner
    ones; `axis_names` covers both in order.  On real multi-slice TPU
    hardware devices are grouped by `slice_index` so each DCN coordinate
    is one slice; elsewhere (single slice, CPU sim) the grouping falls
    back to contiguous blocks — same program, laxer physical locality.
    """
    devices = list(devices if devices is not None else jax.devices())
    dcn_shape, ici_shape = list(dcn_shape), list(ici_shape)
    if len(dcn_shape) + len(ici_shape) != len(axis_names):
        raise ValueError(
            f"axis_names {list(axis_names)} must cover dcn {dcn_shape} + "
            f"ici {ici_shape}")
    n_slices = int(np.prod(dcn_shape))
    per_slice = int(np.prod(ici_shape))
    if n_slices * per_slice > len(devices):
        raise ValueError(
            f"hybrid mesh needs {n_slices}x{per_slice} devices, have "
            f"{len(devices)}")
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    multi_slice = len(slice_ids - {None}) > 1
    if multi_slice:
        if n_slices * per_slice != len(devices):
            raise ValueError(
                f"multi-slice hybrid mesh must use every device: "
                f"{n_slices}x{per_slice} != {len(devices)}")
        from jax.experimental import mesh_utils

        # create_hybrid_device_mesh takes PER-AXIS (ici, dcn) factors of
        # equal rank; model "outer dcn axes + inner ici axes" as dcn
        # factors on the leading axes and ici factors on the trailing ones
        mesh_shape = [1] * len(dcn_shape) + ici_shape
        dcn_factors = dcn_shape + [1] * len(ici_shape)
        arr = mesh_utils.create_hybrid_device_mesh(
            mesh_shape, dcn_factors, devices=devices)
        arr = arr.reshape(dcn_shape + ici_shape)
    else:
        # single slice (or CPU sim): same program, laxer physical locality
        return build_mesh(dcn_shape + ici_shape, axis_names, devices)
    return Mesh(arr, axis_names=tuple(axis_names))
