"""Collective communication API — parity with
python/paddle/distributed/collective.py (all_reduce:751, all_gather:956,
alltoall:1239, reduce_scatter:1813, new_group:396, ...) rebuilt TPU-first.

Design (SURVEY §5.8): the reference routes collectives through ProcessGroup
objects onto NCCL rings.  On TPU the fast path is *in-program*: a collective is
an XLA op over a named mesh axis, compiled into the step function and scheduled
on ICI by the compiler.  Every function here therefore has two modes:

* **in-trace** — called under ``jax.shard_map`` (or any trace where the group's
  mesh axis is bound): lowers to ``lax.psum/all_gather/all_to_all/ppermute``.
  This is the hot path; it is what fleet layers and the pipeline runtime use.
* **eager** — called on concrete arrays outside any trace.  A concrete array in
  the single-controller model is the *replicated view* of "every rank holds
  this value", so reductions scale by group size, gathers tile, broadcast is
  identity.  If the value is actually sharded along the group's axis of the
  global mesh, the collective is executed for real via a one-op shard_map.

Groups map to mesh axes, not NCCL communicators; `new_group(ranks)` returns a
facade object compatible with the reference API surface.
"""
from __future__ import annotations

import pickle
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from . import mesh as mesh_mod


class ReduceOp:
    """paddle.distributed.ReduceOp parity (collective.py:57)."""
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


_LAX_REDUCE = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
}


@dataclass
class Group:
    """ProcessGroup facade (distributed/collective/ProcessGroup.h:53).

    `axis_name` ties the group to a mesh axis; groups made by
    HybridCommunicateGroup always have one.  Ad-hoc `new_group(ranks)` groups
    without a live mesh axis still work for eager (replicated-view) semantics.
    """
    ranks: list
    id: int = 0
    axis_name: str | None = None

    _next_id = 1

    @property
    def nranks(self) -> int:
        return len(self.ranks)

    @property
    def world_size(self) -> int:
        return len(self.ranks)

    @property
    def rank(self) -> int:
        r = _env_rank()
        return self.ranks.index(r) if r in self.ranks else -1

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    @property
    def process_group(self):
        return self

    def __post_init__(self):
        pass


_default_group: Group | None = None
_groups: dict[int, Group] = {}


def _env_rank() -> int:
    from .parallel import get_rank
    return get_rank()


def _ensure_default_group() -> Group:
    global _default_group
    if _default_group is None:
        from .parallel import get_world_size
        _default_group = Group(ranks=list(range(max(1, get_world_size()))), id=0)
        _groups[0] = _default_group
    return _default_group


def get_group(gid: int = 0) -> Group | None:
    return _groups.get(gid, _ensure_default_group() if gid == 0 else None)


def new_group(ranks=None, backend=None, timeout=None, axis_name=None) -> Group:
    """collective.py:396 parity.  `axis_name` is the TPU extension binding the
    group to a mesh axis for in-program lowering."""
    g = _ensure_default_group()
    ranks = sorted(ranks) if ranks is not None else list(g.ranks)
    gid = Group._next_id
    Group._next_id += 1
    grp = Group(ranks=ranks, id=gid, axis_name=axis_name)
    _groups[gid] = grp
    return grp


def _group(group) -> Group:
    if group is None:
        return _ensure_default_group()
    return group


def is_initialized() -> bool:
    return _default_group is not None


def destroy_process_group(group=None):
    global _default_group
    if group is None:
        _groups.clear()
        _default_group = None
        Group._next_id = 1
    else:
        _groups.pop(group.id, None)


def _unwrap(x):
    return x._value if isinstance(x, Tensor) else x


def _rewrap(tensor, value):
    if isinstance(tensor, Tensor):
        tensor._replace_(value)
        return tensor
    return value


def _in_trace(g: Group) -> bool:
    return g.axis_name is not None and mesh_mod.axis_bound(g.axis_name)


def _span(op_name: str, g: Group, value=None):
    """Flight-recorder span for one collective call: op, mesh axis, group
    size, payload bytes, eager-vs-in-trace mode.  Always on (collectives
    are per-step, not per-op); in-trace calls record once per compile —
    exactly the provenance a hung-allreduce crash dump needs."""
    from ..observability import trace as trace_mod
    attrs = {"axis": g.axis_name or "", "nranks": g.nranks,
             "mode": "trace" if _in_trace(g) else "eager"}
    count = 1
    if isinstance(value, (list, tuple)):
        count, value = len(value), (value[0] if value else None)
    v = value._value if isinstance(value, Tensor) else value
    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            attrs["bytes"] = (int(np.prod(shape)) *
                              np.dtype(dtype).itemsize * count)
        except Exception:  # exotic dtypes: the span still records
            pass
    return trace_mod.span(f"collective.{op_name}", attrs)


def _instrumented(value_param: str | None):
    """Wrap a collective in a flight-recorder span; `value_param` names
    the payload argument (shape/dtype → bytes attr).  Resolved by
    signature position once at decoration time so the per-call cost is a
    couple of dict lookups on top of the span itself."""
    import functools
    import inspect

    def deco(fn):
        params = list(inspect.signature(fn).parameters)
        gi = params.index("group")
        vi = params.index(value_param) if value_param else None

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            group = kwargs.get("group", args[gi] if gi < len(args) else None)
            value = None
            if vi is not None:
                value = kwargs.get(value_param,
                                   args[vi] if vi < len(args) else None)
            with _span(fn.__name__, _group(group), value):
                return fn(*args, **kwargs)
        wrapper.__wrapped__ = fn
        return wrapper
    return deco


def _sharded_axis_exec(fn, value, g: Group):
    """Run `fn` (written against a bound axis) for real via shard_map when the
    eager value is sharded along the group's mesh axis."""
    mesh = mesh_mod.get_global_mesh()
    if mesh is None or g.axis_name not in mesh.axis_names:
        return None
    try:
        sh = value.sharding
        spec = sh.spec if hasattr(sh, "spec") else None
    except Exception:
        return None
    if spec is None or g.axis_name not in [a for s in spec for a in
                                           (s if isinstance(s, tuple) else (s,))
                                           if s is not None]:
        return None
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec)(value)


# -- core collectives --------------------------------------------------------

@_instrumented("tensor")
def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True,
               use_calc_stream=None):
    """collective.py:751 parity; in-place on `tensor` like the reference."""
    g = _group(group)
    value = _unwrap(tensor)
    if _in_trace(g):
        if op == ReduceOp.AVG:
            out = jax.lax.pmean(value, g.axis_name)
        elif op == ReduceOp.PROD:
            # sign-and-zero-safe product: prod(x) = parity(sign) * exp(Σlog|x|),
            # forced to 0 when any shard holds a 0
            x = value.astype(jnp.float32)
            n_neg = jax.lax.psum((x < 0).astype(jnp.int32), g.axis_name)
            any_zero = jax.lax.psum((x == 0).astype(jnp.int32), g.axis_name) > 0
            mag = jnp.exp(jax.lax.psum(
                jnp.log(jnp.where(x == 0, 1.0, jnp.abs(x))), g.axis_name))
            signed = jnp.where(n_neg % 2 == 1, -mag, mag)
            out = jnp.where(any_zero, 0.0, signed).astype(value.dtype)
        else:
            out = _LAX_REDUCE[op](value, g.axis_name)
        return _rewrap(tensor, out)
    if g.nranks == 1:
        return tensor
    if g.axis_name is not None:
        def _f(v):
            return all_reduce(v, op=op, group=g)
        res = _sharded_axis_exec(_f, value, g)
        if res is not None:
            return _rewrap(tensor, res)
    # replicated view: every rank holds `value`
    n = g.nranks
    if op == ReduceOp.SUM:
        out = value * n
    elif op == ReduceOp.AVG or op in (ReduceOp.MAX, ReduceOp.MIN):
        out = value
    elif op == ReduceOp.PROD:
        out = value ** n
    else:
        raise ValueError(f"unknown reduce op {op}")
    return _rewrap(tensor, out)


@_instrumented("tensor")
def all_gather(tensor_list, tensor, group=None, sync_op=True,
               use_calc_stream=None):
    """collective.py:956 parity: appends nranks tensors to tensor_list.
    In-trace, prefer :func:`all_gather_concat` (functional) — this list-out
    facade exists for API compatibility."""
    g = _group(group)
    value = _unwrap(tensor)
    if _in_trace(g):
        stacked = jax.lax.all_gather(value, g.axis_name)
        if tensor_list is not None:
            for i in range(g.nranks):
                tensor_list.append(Tensor(stacked[i], _internal=True))
        return stacked
    for _ in range(g.nranks):
        tensor_list.append(Tensor(value, _internal=True)
                           if isinstance(tensor, Tensor) else value)
    return tensor_list


@_instrumented("value")
def all_gather_concat(value, group=None, axis=0):
    """Functional all-gather along `axis` (the shape used by mp layers)."""
    g = _group(group)
    v = _unwrap(value)
    if _in_trace(g):
        return jax.lax.all_gather(v, g.axis_name, axis=axis, tiled=True)
    if g.nranks == 1:
        return v
    return jnp.concatenate([v] * g.nranks, axis=axis)


@_instrumented("tensor")
def broadcast(tensor, src=0, group=None, sync_op=True, use_calc_stream=None):
    """collective.py parity.  In-trace this selects src's shard on every rank."""
    g = _group(group)
    value = _unwrap(tensor)
    if _in_trace(g):
        src_idx = g.get_group_rank(src) if src in g.ranks else src
        i = jax.lax.axis_index(g.axis_name)
        masked = jnp.where(i == src_idx, value, jnp.zeros_like(value))
        out = jax.lax.psum(masked, g.axis_name)
        return _rewrap(tensor, out)
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True,
           use_calc_stream=None):
    """Implemented as all_reduce: every rank gets the reduced value (a
    superset of the reference's dst-only semantics — in SPMD programs the
    non-dst values are dead code XLA removes)."""
    return all_reduce(tensor, op=op, group=_group(group))


@_instrumented("tensor_or_tensor_list")
def reduce_scatter(tensor, tensor_or_tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True, use_calc_stream=None):
    """collective.py:1813 parity: reduce then scatter chunks across ranks."""
    g = _group(group)
    inp = tensor_or_tensor_list
    if isinstance(inp, (list, tuple)):
        vals = [_unwrap(t) for t in inp]
        value = jnp.concatenate([v[None] for v in vals], axis=0) \
            if vals[0].ndim == 0 else jnp.concatenate(vals, axis=0)
    else:
        value = _unwrap(inp)
    if _in_trace(g):
        out = jax.lax.psum_scatter(value, g.axis_name, tiled=True)
        return _rewrap(tensor, out)
    if g.nranks == 1:
        return _rewrap(tensor, value)
    n = g.nranks
    chunk = value.shape[0] // n
    out = value[:chunk] * (n if op == ReduceOp.SUM else 1)
    return _rewrap(tensor, out)


@_instrumented("in_tensor_list")
def all_to_all(out_tensor_list, in_tensor_list, group=None, sync_op=True,
               use_calc_stream=None):
    """collective.py:1239 parity."""
    g = _group(group)
    vals = [_unwrap(t) for t in in_tensor_list]
    if _in_trace(g):
        stacked = jnp.stack(vals, axis=0)
        out = jax.lax.all_to_all(stacked, g.axis_name, split_axis=0,
                                 concat_axis=0, tiled=True)
        chunks = jnp.split(out, g.nranks, axis=0)
        res = [c.squeeze(0) if c.shape[0] == 1 and vals[0].ndim == out.ndim - 1
               else c for c in chunks]
    else:
        res = list(vals)
    if out_tensor_list is not None:
        for r in res:
            out_tensor_list.append(Tensor(r, _internal=True))
    return res


@_instrumented("in_value")
def all_to_all_single(out_value, in_value, out_split_sizes=None,
                      in_split_sizes=None, group=None, sync_op=True):
    g = _group(group)
    v = _unwrap(in_value)
    if _in_trace(g):
        out = jax.lax.all_to_all(v, g.axis_name, split_axis=0, concat_axis=0,
                                 tiled=True)
    else:
        out = v
    return _rewrap(out_value, out) if out_value is not None else out


@_instrumented("tensor")
def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    g = _group(group)
    if _in_trace(g):
        value = jnp.stack([_unwrap(t) for t in tensor_list], axis=0) \
            if tensor_list else _unwrap(tensor)
        idx = jax.lax.axis_index(g.axis_name)
        out = jax.lax.dynamic_index_in_dim(value, idx, 0, keepdims=False)
        return _rewrap(tensor, out)
    if tensor_list:
        return _rewrap(tensor, _unwrap(tensor_list[src]))
    return tensor


@_instrumented("tensor")
def send(tensor, dst=0, group=None, sync_op=True, use_calc_stream=None):
    """P2P send (collective.py send/recv).  Only meaningful in-program: the
    pipeline runtime lowers send/recv pairs to ppermute (SURVEY §7: PP via
    collective-permute).  Eager send outside a trace is a no-op placeholder."""
    g = _group(group)
    if _in_trace(g):
        src_idx = g.rank if g.rank >= 0 else 0
        return p2p_shift(tensor, g, [(src_idx, g.get_group_rank(dst))])
    return tensor


def recv(tensor, src=0, group=None, sync_op=True, use_calc_stream=None):
    return tensor


def p2p_shift(value, group, perm):
    """ppermute over the group's axis: the TPU-native send/recv primitive."""
    g = _group(group)
    return jax.lax.ppermute(_unwrap(value), g.axis_name, perm)


def barrier(group=None):
    """collective.py barrier parity: in the single-controller model dispatch is
    ordered per device; across processes sync via a tiny psum."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("paddle_tpu_barrier")
    return None


def wait(tensor, group=None, use_calc_stream=True):
    v = _unwrap(tensor)
    if isinstance(v, jax.Array):
        try:
            v.block_until_ready()
        except Exception:
            pass
    return tensor


# -- object collectives ------------------------------------------------------

def all_gather_object(object_list, obj, group=None):
    """collective.py all_gather_object parity.  Multi-process: ships pickles
    through jax's global broadcast; single-process replicated view: tiles."""
    g = _group(group)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        gathered = multihost_utils.process_allgather(
            np.frombuffer(pickle.dumps(obj), dtype=np.uint8))
        for row in gathered:
            object_list.append(pickle.loads(bytes(row)))
        return object_list
    for _ in range(g.nranks):
        object_list.append(obj)
    return object_list


def broadcast_object_list(object_list, src=0, group=None):
    return object_list


# -- rank helpers ------------------------------------------------------------

def get_rank(group=None) -> int:
    if group is not None:
        return group.rank
    from .parallel import get_rank as _gr
    return _gr()


def get_world_size(group=None) -> int:
    if group is not None:
        return group.nranks
    from .parallel import get_world_size as _gws
    return _gws()


# -- legacy/P2P aliases (reference collective.py:1239 alltoall,
# :1340 alltoall_single, :1583 isend, :1633 irecv, :1682 P2POp,
# :1740 batch_isend_irecv) --------------------------------------------------

def alltoall(in_tensor_list, out_tensor_list, group=None,
             use_calc_stream=True):
    """Legacy arg-order alias of all_to_all (inputs first)."""
    return all_to_all(out_tensor_list, in_tensor_list, group=group)


def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, use_calc_stream=True):
    return all_to_all_single(out_tensor, in_tensor,
                             out_split_sizes=out_split_sizes,
                             in_split_sizes=in_split_sizes, group=group)


class _P2PTask:
    """Completed-on-return task handle: the eager send/recv here complete
    synchronously (device-to-device copies through the host bus), so
    wait() is a no-op — the same contract a finished NCCL task exposes."""

    def __init__(self, result=None):
        self.result = result

    def wait(self):
        return self.result

    def is_completed(self):
        return True


def isend(tensor, dst, group=None):
    send(tensor, dst=dst, group=group, sync_op=False)
    return _P2PTask()


def irecv(tensor, src=None, group=None):
    out = recv(tensor, src=src or 0, group=group, sync_op=False)
    return _P2PTask(out)


class P2POp:
    """One deferred point-to-point op for batch_isend_irecv
    (collective.py:1682): op is `isend` or `irecv`."""

    def __init__(self, op, tensor, peer, group=None):
        if op not in (isend, irecv):
            raise RuntimeError(
                "Invalid ``op`` function. Expected ``op`` to be of type "
                "``paddle.distributed.isend`` or ``paddle.distributed.irecv``.")
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    """Run the deferred P2P ops; returns their task handles
    (collective.py:1740)."""
    if not p2p_op_list or not all(isinstance(p, P2POp)
                                  for p in p2p_op_list):
        raise RuntimeError("Invalid ``p2p_op_list``.")
    return [p.op(p.tensor, p.peer, p.group) for p in p2p_op_list]
