"""Fused LayerNorm->matmul as one Pallas TPU kernel.

docs/PERF.md's round-3 conclusion after three standalone-LN attempts: any
opaque LN boundary loses because XLA's LN fusions are load-bearing hubs —
the LN math must live INSIDE the consuming custom call.  Every LN in the
GPT/BERT block feeds a projection (norm1 -> qkv_proj, norm2 -> fc0), so
the fusable form is y = LN(x; g, b) @ W + bias: the matmul has to read
the normalized rows anyway, and the row stats are VPU work that overlaps
the MXU.  Forward = this kernel; backward = plain jnp (XLA fuses the
grad reductions with its neighbors exactly as before, which the round-3
measurements showed it must).

Reference analog: fused_attention_op.cu's pre-LN + qkv fusion
(paddle/fluid/operators/fused/fused_attention_op.cu).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa  # shared interpret toggle

_ENABLED = False


def enable_ln_matmul(flag: bool):
    """Opt in to the fused kernel.  Inside a jitted train step the
    pallas_call only traces, so a Mosaic refusal surfaces at the outer
    step compile — as that compile's error, never as a quiet return to
    the jnp composition."""
    global _ENABLED
    _ENABLED = bool(flag)


def ln_matmul_enabled() -> bool:
    return _ENABLED


def _kernel(x_ref, g_ref, b_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    d = x - mu
    var = jnp.mean(d * d, axis=1, keepdims=True)
    rs = jax.lax.rsqrt(var + eps)
    xln = (d * rs * g_ref[...].astype(jnp.float32) +
           b_ref[...].astype(jnp.float32)).astype(x_ref.dtype)
    o_ref[...] = jax.lax.dot_general(
        xln, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


_BN = 256    # rows per block
_BM = 4096   # output columns per block (GPT projections fit whole in VMEM)


def _pad(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x


def _ln_matmul_fwd_impl(x2, g, b, w, eps):
    n, k = x2.shape
    m = w.shape[1]
    bn = min(_BN, max(8, n))
    bm = min(_BM, max(128, m))
    xp = _pad(x2, bn, 0)
    wp = _pad(w, bm, 1)
    ni = xp.shape[0] // bn
    nj = wp.shape[1] // bm
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((bn, k), lambda i, j: (i, j * 0)),
            pl.BlockSpec((k,), lambda i, j: (i * 0,)),
            pl.BlockSpec((k,), lambda i, j: (i * 0,)),
            pl.BlockSpec((k, bm), lambda i, j: (i * 0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1]), x2.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_fa._INTERPRET,
    )(xp, g, b, wp)
    return out[:n, :m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ln_matmul(x2, g, b, w, eps):
    return _ln_matmul_fwd_impl(x2, g, b, w, eps)


def _fwd(x2, g, b, w, eps):
    return _ln_matmul_fwd_impl(x2, g, b, w, eps), (x2, g, b, w)


def _bwd(eps, res, dy):
    # plain jnp: XLA fuses these reductions with their graph neighbors —
    # measured faster than any pallas LN-backward boundary (docs/PERF.md)
    x2, g, b, w = res
    xf = x2.astype(jnp.float32)
    mu = jnp.mean(xf, axis=1, keepdims=True)
    d = xf - mu
    var = jnp.mean(d * d, axis=1, keepdims=True)
    rs = jax.lax.rsqrt(var + eps)
    xhat = d * rs
    gf = g.astype(jnp.float32)
    xln = (xhat * gf + b.astype(jnp.float32)).astype(x2.dtype)
    dyf = dy
    dw = jax.lax.dot_general(xln, dyf, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dxln = jax.lax.dot_general(dyf, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dgamma = jnp.sum(dxln * xhat, axis=0)
    dbeta = jnp.sum(dxln, axis=0)
    gg = dxln * gf
    m1 = jnp.mean(gg, axis=1, keepdims=True)
    m2 = jnp.mean(gg * xhat, axis=1, keepdims=True)
    dx = (rs * (gg - m1 - xhat * m2)).astype(x2.dtype)
    return (dx, dgamma.astype(g.dtype), dbeta.astype(b.dtype),
            dw.astype(w.dtype))


_ln_matmul.defvjp(_fwd, _bwd)


def ln_matmul(x, ln_weight, ln_bias, w, bias=None, eps=1e-5):
    """y = LayerNorm(x over last axis; ln_weight, ln_bias) @ w (+ bias).

    x: [..., K]; w: [K, M].  The bias add stays OUTSIDE the kernel so XLA
    fuses it with whatever consumes y.
    """
    shape = x.shape
    k = shape[-1]
    y = _ln_matmul(x.reshape(-1, k), ln_weight, ln_bias, w, float(eps))
    y = y.reshape(shape[:-1] + (w.shape[1],))
    if bias is not None:
        y = y + bias
    return y


def ln_matmul_ok(x, w, mesh_free: bool) -> bool:
    """Routing predicate: opt-in, lane-aligned dims, real accelerator,
    single-device only for now (no GSPMD partitioning rule is registered
    for the custom call)."""
    if not _ENABLED or not mesh_free:
        return False
    if x.shape[-1] % 128 or w.shape[1] % 128:
        return False
    # the cpu backend has no Mosaic: there the kernel runs only under the
    # interpret pin (tests); every other backend compiles it
    return _fa._INTERPRET or jax.default_backend() != "cpu"
