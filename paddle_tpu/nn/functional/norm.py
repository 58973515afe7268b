"""Normalisation functionals (reference: python/paddle/nn/functional/norm.py →
phi batch_norm/layer_norm kernels).  XLA fuses these into surrounding matmuls;
a Pallas fused layernorm lives in paddle_tpu.kernels for the hot transformer
path."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.op import defop, apply_op
from ...core.tensor import Tensor


@defop
def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n_axes = len(tuple(normalized_shape))
    axes = tuple(range(x.ndim - n_axes, x.ndim))
    from ...kernels.layer_norm import layer_norm_fused, layer_norm_fused_ok
    if layer_norm_fused_ok(x, axes, weight, bias):
        # fused Pallas path: one pass per row block incl. the backward's
        # dgamma/dbeta accumulation (reference layer_norm_kernel.cu analog)
        return layer_norm_fused(x, weight, bias, epsilon)
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


@defop
def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """x / sqrt(mean(x^2) + epsilon) * weight over the last axis; the mean
    of squares is taken in float32 and the result is in x's type."""
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                             + epsilon)
    if weight is not None:
        out = out * weight.astype(out.dtype)
    return out.astype(x.dtype)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW",
               use_global_stats=None, name=None):
    """Returns normalized output; updates running stats in-place when training
    (matching the reference's in-place mean/variance update)."""
    channel_axis = 1 if data_format.startswith("NC") or x.ndim <= 2 else x.ndim - 1
    if x.ndim <= 2:
        channel_axis = x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != channel_axis)

    use_batch_stats = training and not use_global_stats

    def impl(xv, w, b, rm, rv):
        shape = [1] * xv.ndim
        shape[channel_axis] = xv.shape[channel_axis]
        half = jnp.issubdtype(xv.dtype, jnp.floating) and \
            jnp.finfo(xv.dtype).bits < 32
        if use_batch_stats:
            if half:
                # one-pass stats for half dtypes: E[x²]−E[x]² in f32 lets
                # XLA fuse both channel reductions into a single read of
                # the activation, where the two-pass mean→var form forces
                # a second dependent pass (measured on ResNet-50,
                # tools/profile_model.py).  The f32 accumulation is as
                # accurate as half-precision data allows: cancellation
                # only bites when |mean|/std exceeds what the input's own
                # mantissa can represent.
                xf = xv.astype(jnp.float32)
                mean = jnp.mean(xf, axis=reduce_axes)
                var = jnp.maximum(
                    jnp.mean(jnp.square(xf), axis=reduce_axes)
                    - jnp.square(mean), 0)
            else:
                # full-precision inputs keep the exact two-pass form in
                # their own dtype (E[x²]−E[x]² cancels catastrophically
                # for |mean| >> std even in f32)
                mean = jnp.mean(xv, axis=reduce_axes)
                var = jnp.var(xv, axis=reduce_axes)
        else:
            mean, var = rm, rv
        # fold the normalisation into one scale+shift over x: out =
        # x*scale + shift with per-channel scalars, a single fused pass
        stat_dtype = mean.dtype
        inv = jax.lax.rsqrt(var.astype(stat_dtype) + epsilon)
        scale = inv if w is None else inv * w.astype(stat_dtype)
        shift = -mean * scale
        if b is not None:
            shift = shift + b.astype(stat_dtype)
        out = xv * scale.reshape(shape).astype(xv.dtype) \
            + shift.reshape(shape).astype(xv.dtype)
        return out, mean, var

    out, mean, var = apply_op(impl, "batch_norm",
                              (x, weight, bias, running_mean, running_var), {})
    if use_batch_stats and running_mean is not None:
        with_no_grad_update(running_mean, running_var, mean, var, momentum)
    return out


def with_no_grad_update(running_mean, running_var, mean, var, momentum):
    from ...core.autograd import no_grad
    with no_grad():
        running_mean._replace_(
            (momentum * running_mean._value +
             (1 - momentum) * mean._value.astype(running_mean._value.dtype)), None)
        running_var._replace_(
            (momentum * running_var._value +
             (1 - momentum) * var._value.astype(running_var._value.dtype)), None)


@defop
def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    if data_format == "NCHW" or x.ndim <= 2:
        n, c = x.shape[0], x.shape[1]
        rest = x.shape[2:]
        g = x.reshape((n, num_groups, c // num_groups) + rest)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape)
        shape = (1, c) + (1,) * len(rest)
    else:
        n, c = x.shape[0], x.shape[-1]
        rest = x.shape[1:-1]
        g = x.reshape((n,) + rest + (num_groups, c // num_groups))
        axes = tuple(range(1, g.ndim - 2)) + (g.ndim - 1,)
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape)
        shape = (1,) * (1 + len(rest)) + (c,)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@defop
def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    axes = tuple(range(2, x.ndim)) if data_format.startswith("NC") \
        else tuple(range(1, x.ndim - 1))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    ch = 1 if data_format.startswith("NC") else x.ndim - 1
    shape[ch] = x.shape[ch]
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@defop
def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    ch = 1 if data_format.startswith("NC") else x.ndim - 1
    sq = jnp.square(x)
    half = size // 2
    pad_width = [(0, 0)] * x.ndim
    pad_width[ch] = (half, size - half - 1)
    padded = jnp.pad(sq, pad_width)
    window = [1] * x.ndim
    window[ch] = size
    summed = jax.lax.reduce_window(padded, jnp.zeros((), x.dtype), jax.lax.add,
                                   tuple(window), (1,) * x.ndim, "VALID")
    return x / jnp.power(k + alpha * summed, beta)


@defop
def spectral_norm(weight, u, v, dim=0, power_iters=1, eps=1e-12, name=None):
    w = jnp.moveaxis(weight, dim, 0).reshape(weight.shape[dim], -1)
    for _ in range(power_iters):
        v = w.T @ u
        v = v / (jnp.linalg.norm(v) + eps)
        u = w @ v
        u = u / (jnp.linalg.norm(u) + eps)
    sigma = u @ w @ v
    return weight / sigma
