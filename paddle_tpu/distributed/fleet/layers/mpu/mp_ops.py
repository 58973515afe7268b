"""Model-parallel collective autograd ops — parity with
fleet/layers/mpu/mp_ops.py (`_c_identity`:30, `_c_concat`:69, `_c_split`:117,
`_mp_allreduce`:165, `_c_softmax_with_cross_entropy` backing
ParallelCrossEntropy, `split` API :563).

Each op is a forward/backward collective *pair* (identity↔allreduce,
concat↔split).  Two execution modes:

* **explicit SPMD** (inside shard_map, mp axis bound): `jax.custom_vjp`
  wrappers around `lax.psum/all_gather/dynamic_slice` reproduce the reference's
  autograd pairing exactly, per shard.
* **GSPMD** (jit over a mesh, axis not bound): the ops are identity —
  parallelism comes from the params' PartitionSpecs; XLA inserts the same
  collectives (and their transposes) automatically.  Eager single-process is
  the degenerate GSPMD case.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .....core.op import apply_op
from .....core.tensor import Tensor
from .... import mesh as mesh_mod


def _axis(group):
    return getattr(group, "axis_name", None) or "mp"


def _in_trace(group) -> bool:
    return mesh_mod.axis_bound(_axis(group))


# -- raw custom-vjp pairs (explicit SPMD mode) --------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _identity_raw(x, axis):
    return x


def _identity_fwd(x, axis):
    return x, None


def _identity_bwd(axis, _, g):
    return (jax.lax.psum(g, axis),)


_identity_raw.defvjp(_identity_fwd, _identity_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _allreduce_raw(x, axis):
    return jax.lax.psum(x, axis)


def _allreduce_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _allreduce_bwd(axis, _, g):
    return (g,)


_allreduce_raw.defvjp(_allreduce_fwd, _allreduce_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _concat_raw(x, axis):
    return jax.lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)


def _concat_fwd(x, axis):
    return _concat_raw(x, axis), x.shape[-1]


def _concat_bwd(axis, local_width, g):
    i = jax.lax.axis_index(axis)
    start = i * local_width
    return (jax.lax.dynamic_slice_in_dim(g, start, local_width, g.ndim - 1),)


_concat_raw.defvjp(_concat_fwd, _concat_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _split_raw(x, axis):
    n = jax.lax.axis_size(axis)
    i = jax.lax.axis_index(axis)
    w = x.shape[-1] // n
    return jax.lax.dynamic_slice_in_dim(x, i * w, w, x.ndim - 1)


def _split_fwd(x, axis):
    return _split_raw(x, axis), None


def _split_bwd(axis, _, g):
    return (jax.lax.all_gather(g, axis, axis=g.ndim - 1, tiled=True),)


_split_raw.defvjp(_split_fwd, _split_bwd)


# -- framework-level ops ------------------------------------------------------

def _c_identity(tensor, group=None):
    """mp_ops.py:30: identity forward, allreduce backward (enter a TP region)."""
    if not _in_trace(group):
        return tensor
    return apply_op(lambda x: _identity_raw(x, _axis(group)),
                    "c_identity", (tensor,), {})


def _mp_allreduce(tensor, op=None, group=None, use_calc_stream=True,
                  use_model_parallel=True):
    """mp_ops.py:165: allreduce forward, identity backward (leave a TP region)."""
    if not _in_trace(group):
        return tensor
    return apply_op(lambda x: _allreduce_raw(x, _axis(group)),
                    "mp_allreduce_sum", (tensor,), {})


def _c_concat(tensor, group=None):
    """mp_ops.py:69: all-gather last dim forward, slice backward."""
    if not _in_trace(group):
        return tensor
    return apply_op(lambda x: _concat_raw(x, _axis(group)),
                    "c_concat", (tensor,), {})


def _c_split(tensor, group=None):
    """mp_ops.py:117: slice own last-dim shard forward, all-gather backward."""
    if not _in_trace(group):
        return tensor
    return apply_op(lambda x: _split_raw(x, _axis(group)),
                    "c_split", (tensor,), {})


def _c_lookup_table(table, index, start_index=0, name=None):
    """Sharded embedding lookup: rows outside this shard contribute zeros
    (operators/collective/c_embedding_op.* semantics)."""
    def raw(tbl, idx):
        local_rows = tbl.shape[0]
        shifted = idx - start_index
        valid = (shifted >= 0) & (shifted < local_rows)
        safe = jnp.clip(shifted, 0, local_rows - 1)
        out = jnp.take(tbl, safe, axis=0)
        return jnp.where(valid[..., None], out, 0)
    return apply_op(raw, "c_embedding", (table, index), {})


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _sharded_softmax_ce_raw(logits, label, axis, ignore_index):
    loss, _ = _sharded_softmax_ce_fwd_impl(logits, label, axis, ignore_index)
    return loss


def _sharded_softmax_ce_fwd_impl(logits, label, axis, ignore_index):
    """c_softmax_with_cross_entropy (operators/collective/
    c_softmax_with_cross_entropy_op.cu): logits sharded on the class dim.
    Labels equal to ignore_index contribute zero loss and zero gradient."""
    n_local = logits.shape[-1]
    i = jax.lax.axis_index(axis)
    start = i * n_local
    m = jax.lax.pmax(jnp.max(logits, axis=-1, keepdims=True), axis)
    exp = jnp.exp(logits - m)
    denom = jax.lax.psum(jnp.sum(exp, axis=-1, keepdims=True), axis)
    # target logit: owned by exactly one shard
    shifted = label - start
    valid = (shifted >= 0) & (shifted < n_local)
    safe = jnp.clip(shifted, 0, n_local - 1)
    tgt = jnp.take_along_axis(logits - m, safe[..., None], axis=-1)[..., 0]
    tgt = jax.lax.psum(jnp.where(valid, tgt, 0.0), axis)
    ignored = label == ignore_index
    loss = jnp.where(ignored, 0.0, jnp.log(denom[..., 0]) - tgt)
    softmax = exp / denom
    return loss, (softmax, label, start, n_local, ignored)


def _sharded_softmax_ce_fwd(logits, label, axis, ignore_index):
    loss, res = _sharded_softmax_ce_fwd_impl(logits, label, axis, ignore_index)
    return loss, res


def _sharded_softmax_ce_bwd(axis, ignore_index, res, g):
    softmax, label, start, n_local, ignored = res
    shifted = label - start
    valid = (shifted >= 0) & (shifted < n_local)
    onehot = jax.nn.one_hot(jnp.where(valid, shifted, -1), n_local,
                            dtype=softmax.dtype)
    grad = (softmax - onehot) * jnp.where(ignored, 0.0, g)[..., None]
    return grad, None


_sharded_softmax_ce_raw.defvjp(_sharded_softmax_ce_fwd, _sharded_softmax_ce_bwd)


def _sharded_softmax_raw(logits, axis):
    m = jax.lax.pmax(jnp.max(logits, axis=-1, keepdims=True), axis)
    exp = jnp.exp(logits - m)
    return exp / jax.lax.psum(jnp.sum(exp, axis=-1, keepdims=True), axis)


def _c_softmax_with_cross_entropy(logits, label, group=None, ignore_index=-100,
                                  return_softmax=False):
    axis = _axis(group)
    if not _in_trace(group):
        from .....nn.functional.loss import softmax_with_cross_entropy
        lbl = label.squeeze(-1) if label.ndim == logits.ndim else label
        return softmax_with_cross_entropy(logits, lbl,
                                          ignore_index=ignore_index,
                                          return_softmax=return_softmax)
    squeeze = isinstance(label, Tensor) and label.ndim == logits.ndim
    lbl = label.squeeze(-1) if squeeze else label
    out = apply_op(lambda lg, lb: _sharded_softmax_ce_raw(lg, lb, axis,
                                                          ignore_index),
                   "c_softmax_with_cross_entropy", (logits, lbl), {})
    if return_softmax:
        # softmax returned for reuse, detached like the reference (grads flow
        # through the loss output only)
        sm = apply_op(lambda lg: _sharded_softmax_raw(lg, axis),
                      "c_softmax", (logits.detach(),), {})
        return out, sm
    return out


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """paddle.distributed.split parity (mp_ops.py:563): builds a row/column
    parallel linear or sharded embedding on the fly."""
    from .mp_layers import (ColumnParallelLinear, RowParallelLinear,
                            VocabParallelEmbedding)
    if operation == "linear":
        if axis == 0:
            layer = RowParallelLinear(size[0], size[1], weight_attr=weight_attr,
                                      has_bias=bias_attr is not False,
                                      input_is_parallel=False, name=name)
        else:
            layer = ColumnParallelLinear(size[0], size[1],
                                         weight_attr=weight_attr,
                                         has_bias=bias_attr is not False,
                                         gather_output=gather_out, name=name)
        return layer(x)
    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1],
                                       weight_attr=weight_attr, name=name)
        return layer(x)
    raise ValueError(f"unsupported split operation {operation!r}")
