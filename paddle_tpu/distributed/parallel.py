"""Process bootstrap + DataParallel — parity with
python/paddle/distributed/parallel.py (init_parallel_env:94, TCPStore
rendezvous :248) and fluid/dygraph/parallel.py:437 (`DataParallel`).

TPU-native: rendezvous is `jax.distributed.initialize` (its coordination
service plays the TCPStore role); the per-process device set comes from the
TPU runtime; "ranks" are jax processes.  The PADDLE_* env contract set by
`paddle_tpu.distributed.launch` is honored for drop-in compatibility.
"""
from __future__ import annotations

import os

import jax

from ..core.tensor import Tensor
from ..nn.layer_base import Layer
from ..parallel.env import ParallelEnv, get_rank, get_world_size  # noqa: F401
from . import collective as coll
from . import mesh as mesh_mod

_initialized = False


def init_parallel_env(strategy=None):
    """parallel.py:94 parity.  Multi-process: initialize jax.distributed from
    the PADDLE_*/standard env contract; always: create the default group and a
    1-D "dp" world mesh so data-parallel code can run immediately."""
    global _initialized
    if _initialized:
        return ParallelEnv()

    world = get_world_size()
    if world > 1 and os.environ.get("PADDLE_MASTER") and \
            os.environ.get("PADDLE_TRAINER_ID") is not None:
        # IMPORTANT: don't touch jax.process_count()/jax.devices() before
        # initialize — backend init would make the rendezvous impossible.
        # A failed rendezvous raises: continuing single-process would run
        # every "collective" over this process's devices only, and look
        # like a working job.
        if not jax.distributed.is_initialized():
            jax.distributed.initialize(
                coordinator_address=os.environ["PADDLE_MASTER"],
                num_processes=world,
                process_id=int(os.environ["PADDLE_TRAINER_ID"]))

    coll._ensure_default_group()
    if mesh_mod.get_global_mesh() is None:
        mesh_mod.set_global_mesh(
            mesh_mod.build_mesh([len(jax.devices())], ["dp"]))
    _initialized = True
    return ParallelEnv()


def is_initialized() -> bool:
    return _initialized


class DataParallel(Layer):
    """fluid/dygraph/parallel.py:437 / paddle.DataParallel parity.

    The reference fuses bucketed grad allreduce into backward hooks
    (collective/reducer.cc `EagerReducer`).  TPU-native, DP gradient averaging
    is one `psum`/sharding annotation inside the jitted step — so this wrapper
    (a) marks the model's data axis for the step builder and (b) provides the
    eager `apply_collective_grads` fallback used by the hybrid optimizer.
    """

    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        super().__init__()
        self._layers = layers
        self.find_unused_parameters = find_unused_parameters
        self.group = group
        self.comm_buffer_size = comm_buffer_size

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    def scale_loss(self, loss):
        return loss

    def apply_collective_grads(self):
        g = self.group or coll._ensure_default_group()
        n = g.nranks
        if n <= 1:
            return
        for p in self._layers.parameters():
            if p.grad is not None:
                coll.all_reduce(p.grad, op=coll.ReduceOp.SUM, group=g)
                p.grad._replace_(p.grad._value / n)

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, state_dict, *args, **kwargs):
        return self._layers.set_state_dict(state_dict, *args, **kwargs)

    def __getattr__(self, name):
        try:
            return super().__getattr__(name)
        except AttributeError:
            return getattr(self.__dict__.get("_sub_layers", {}).get("_layers")
                           or object.__getattribute__(self, "_layers"), name)


def get_data_parallel_group():
    return coll._ensure_default_group()
