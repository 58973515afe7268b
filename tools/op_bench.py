"""Per-op micro-benchmark harness.

Reference: paddle/fluid/operators/benchmark/op_tester.cc (runs one op
repeatedly, prints "Speed" lines) feeding the CI latency gate
tools/check_op_benchmark_result.py.

Usage:
    python tools/op_bench.py                    # all configs, JSON lines
    python tools/op_bench.py --ops matmul conv2d
    python tools/op_bench.py --output base.json
Each line: {"op": ..., "config": ..., "speed_us": ..., "device": ...}.
Compare two runs with tools/check_op_benchmark_result.py.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# runnable as `python tools/op_bench.py` from the repo root: the script dir
# is tools/, so the package root must be put on the path explicitly
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _configs():
    """Configs hold tensor SHAPES, not tensors: arguments are materialized
    lazily per selected op (float64 host randn for the big vocab shapes
    alone would be multiple GB)."""
    import jax
    import jax.numpy as jnp

    cfgs = {}

    def add(op, config, fn, *shapes):
        cfgs[f"{op}/{config}"] = (op, config, fn, shapes)

    add("matmul", "4096x4096x4096",
        lambda a, b: a @ b, (4096, 4096), (4096, 4096))
    add("matmul", "batch16_1024x768x3072",
        lambda a, b: jnp.einsum("bsh,hf->bsf", a, b),
        (16, 1024, 768), (768, 3072))
    add("softmax", "16x1024x50304",
        lambda a: jax.nn.softmax(a, axis=-1), (16, 1024, 50304))
    add("layernorm", "16x1024x2048",
        lambda a: (a - a.mean(-1, keepdims=True))
        / jnp.sqrt(a.var(-1, keepdims=True) + 1e-5), (16, 1024, 2048))
    add("gelu", "16x1024x8192", jax.nn.gelu, (16, 1024, 8192))
    add("conv2d", "32x3x224x224_k7s2",
        lambda x, w: jax.lax.conv_general_dilated(
            x, w, (2, 2), "SAME", dimension_numbers=("NCHW", "OIHW", "NCHW")),
        (32, 3, 224, 224), (64, 3, 7, 7))
    add("reduce_sum", "16x1024x50304",
        lambda a: a.sum(), (16, 1024, 50304))

    def _flash(q):
        from paddle_tpu.kernels.flash_attention import flash_attention_bhtd
        return flash_attention_bhtd(q, q, q, causal=True)
    add("flash_attention", "192x1024x64", _flash, (192, 1024, 64))
    return cfgs


def _materialize(shapes):
    import jax.numpy as jnp

    r = np.random.RandomState(0)
    # float32 host draws: float64 at vocab-sized shapes is pointless bulk
    return tuple(jnp.asarray(r.standard_normal(s).astype(np.float32),
                             jnp.bfloat16) for s in shapes)


def bench_op(fn, args, iters: int = 20, warmup: int = 2) -> float:
    """Median-of-three timing of `iters` executions, us/call; every window
    ends in `block_until_ready`, so it times finished device work."""
    import jax

    jitted = jax.jit(fn)
    for _ in range(max(1, warmup)):
        out = jitted(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jitted(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters)
    return float(np.median(times) * 1e6)


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--ops", nargs="*", default=None)
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--output", default=None)
    args = parser.parse_args(argv)

    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    device = jax.devices()[0]
    results = []
    for key, (op, config, fn, shapes) in sorted(_configs().items()):
        if args.ops and op not in args.ops:
            continue
        try:
            tensors = _materialize(shapes)
            us = bench_op(fn, tensors, iters=args.iters)
            del tensors
            row = {"op": op, "config": config, "speed_us": round(us, 2),
                   "device": str(getattr(device, "device_kind", device))}
        except Exception as e:  # report, keep going (op_tester.cc contract)
            row = {"op": op, "config": config, "error": repr(e)[:200]}
        results.append(row)
        print(json.dumps(row), flush=True)
    if args.output:
        with open(args.output, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
