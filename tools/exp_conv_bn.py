"""Experiment: fused (BN-apply -> relu -> 1x1 conv -> BN-stats) as ONE
Pallas kernel vs the XLA chain the model currently runs.

ResNet's HBM traffic per 1x1 conv today (docs/PERF.md): conv reads xn,
writes y; BN stats read y; BN apply reads y, writes z.  The fused form
reads x_raw once, writes y once, and carries the prologue (prev BN
apply + relu) and epilogue (per-channel sum/sumsq of y) in registers.

Usage: python tools/exp_conv_bn.py
"""
from __future__ import annotations

import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, s_ref, b_ref, w_ref, o_ref, st_ref, *, m_total, bm):
    i = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    xn = jnp.maximum(x * s_ref[...].astype(jnp.float32)
                     + b_ref[...].astype(jnp.float32), 0).astype(x_ref.dtype)
    y = jax.lax.dot_general(xn, w_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (y.shape[0], 1), 0)
    ym = jnp.where(rows < m_total, y, 0.0)
    ps = jnp.sum(ym, axis=0, keepdims=True)
    pq = jnp.sum(ym * ym, axis=0, keepdims=True)
    stat = jnp.concatenate([ps, pq], axis=0)

    @pl.when(i == 0)
    def _init():
        st_ref[...] = stat

    @pl.when(i > 0)
    def _acc():
        st_ref[...] += stat


def fused_conv1x1_bn(x2, s, b, w, bm=1024, bn=512):
    """x2: [M, K] raw prev-conv output (bf16); s,b: [K] f32 BN scale/shift;
    w: [K, N].  Returns y [M, N] bf16, stats [2, N] f32 (sum, sumsq)."""
    m, k = x2.shape
    n = w.shape[1]
    bn = min(bn, n)
    bm = min(bm, m)
    mp = -(-m // bm) * bm
    if mp != m:
        x2 = jnp.pad(x2, ((0, mp - m), (0, 0)))
    ni = mp // bm
    nj = n // bn
    y, st = pl.pallas_call(
        functools.partial(_kernel, m_total=m, bm=bm),
        grid=(nj, ni),
        in_specs=[
            pl.BlockSpec((bm, k), lambda j, i: (i, 0)),
            pl.BlockSpec((1, k), lambda j, i: (0, 0)),
            pl.BlockSpec((1, k), lambda j, i: (0, 0)),
            pl.BlockSpec((k, bn), lambda j, i: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            pl.BlockSpec((2, bn), lambda j, i: (0, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((mp, n), x2.dtype),
                   jax.ShapeDtypeStruct((2, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
    )(x2, s.reshape(1, -1), b.reshape(1, -1), w)
    return y[:m], st


def xla_chain(x2, s, b, w):
    xn = jnp.maximum(x2.astype(jnp.float32) * s + b, 0).astype(x2.dtype)
    y = jax.lax.dot_general(xn, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32
                            ).astype(x2.dtype)
    yf = y.astype(jnp.float32)
    mean = jnp.mean(yf, axis=0)
    var = jnp.maximum(jnp.mean(yf * yf, axis=0) - mean * mean, 0)
    return y, mean, var


def _time(fn, args, iters=400, perturb=1):
    """Scan-chained timing for sub-dispatch-cost ops: the carry perturbs one
    SMALL argument by carry*1e-45 (a denormal — numerically invisible, but
    not constant-foldable), so XLA cannot hoist the body out of the loop.
    The fixed dispatch+fetch cost is spread over `iters` (see below)."""
    def body(c, _):
        a = list(args)
        a[perturb] = a[perturb] + (c * 1e-45).astype(a[perturb].dtype)
        out = fn(*a)
        leaf = jax.tree_util.tree_leaves(out)[0]
        return c + leaf.reshape(-1)[0].astype(jnp.float32), None

    chained = jax.jit(functools.partial(
        lambda ln: jax.lax.scan(body, jnp.float32(0), None, length=ln),
        iters))
    float(chained()[0])
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        float(chained()[0])
        best = min(best, time.perf_counter() - t0)
    # ~2 ms fixed dispatch+fetch cost spread over `iters` (+5 us/iter bias
    # at iters=400 — identical for both sides of every comparison here)
    return best / iters * 1e6


def main():
    shapes = [
        # (M, K, N) — ResNet-50 batch-64 1x1 convs, NHWC-flattened
        (64 * 56 * 56, 64, 256),
        (64 * 56 * 56, 256, 64),
        (64 * 28 * 28, 512, 128),
        (64 * 28 * 28, 128, 512),
        (64 * 14 * 14, 1024, 256),
        (64 * 14 * 14, 256, 1024),
        (64 * 7 * 7, 2048, 512),
        (64 * 7 * 7, 512, 2048),
    ]
    rng = np.random.RandomState(0)
    for m, k, n in shapes:
        x2 = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32),
                         jnp.bfloat16)
        s = jnp.asarray(rng.standard_normal(k).astype(np.float32)) * 0.1 + 1
        b = jnp.asarray(rng.standard_normal(k).astype(np.float32)) * 0.1
        w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32) /
                        np.sqrt(k), jnp.bfloat16)
        # correctness
        yf, st = jax.jit(fused_conv1x1_bn)(x2, s, b, w)
        yx, mean, var = jax.jit(xla_chain)(x2, s, b, w)
        mf = st[0] / m
        vf = jnp.maximum(st[1] / m - mf * mf, 0)
        err_y = float(jnp.max(jnp.abs(yf.astype(jnp.float32)
                                      - yx.astype(jnp.float32))))
        err_m = float(jnp.max(jnp.abs(mf - mean)))
        err_v = float(jnp.max(jnp.abs(vf - var)))
        t_pal = _time(fused_conv1x1_bn, (x2, s, b, w))
        t_xla = _time(xla_chain, (x2, s, b, w))
        gb = (m * k + m * n) * 2 / 1e9  # one read + one write, bf16
        print(f"M={m:7d} K={k:4d} N={n:4d}  pallas={t_pal:8.1f}us "
              f"xla={t_xla:8.1f}us  speedup={t_xla / t_pal:5.2f}x  "
              f"bw={gb / (t_pal / 1e6):6.0f}GB/s  err y/m/v="
              f"{err_y:.3g}/{err_m:.3g}/{err_v:.3g}")


if __name__ == "__main__":
    main()
