"""Device-time micro harness: xplane-based per-call device compute time.

Device durations from the trace rather than host walls: a wall clock
around a microsecond-scale call is mostly dispatch, and scan-chained
bodies risk DCE/hoisting.  Here each call is dispatched normally and the
sync "XLA Ops" line of the device trace is summed.
"""
from __future__ import annotations

import collections
import glob
import os
import tempfile

import jax


def dtime(fn, args, iters=20, warmup=2):
    """Median-free total-device-time/iters in us for jitted fn(*args)."""
    jitted = jax.jit(fn)
    out = None
    for _ in range(warmup):
        out = jitted(*args)
    jax.block_until_ready(out)
    outdir = tempfile.mkdtemp(prefix="dtime_")
    with jax.profiler.trace(outdir):
        for _ in range(iters):
            out = jitted(*args)
        jax.block_until_ready(out)
    return device_total_us(outdir) / iters


def device_total_us(outdir):
    paths = glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, f"no xplane under {outdir}"
    data = jax.profiler.ProfileData.from_file(paths[-1])
    plane = None
    for p in data.planes:
        if "TPU" in p.name or "/device" in p.name.lower():
            plane = p
            break
    assert plane is not None, [p.name for p in data.planes]
    total = 0.0
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            total += ev.duration_ns / 1e3
    return total


def dtime_ops(fn, args, iters=20, warmup=2, top=15):
    """Like dtime but also returns per-op-group device us/iter."""
    import re
    jitted = jax.jit(fn)
    out = None
    for _ in range(warmup):
        out = jitted(*args)
    jax.block_until_ready(out)
    outdir = tempfile.mkdtemp(prefix="dtime_")
    with jax.profiler.trace(outdir):
        for _ in range(iters):
            out = jitted(*args)
        jax.block_until_ready(out)
    paths = glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(paths[-1])
    plane = next(p for p in data.planes
                 if "TPU" in p.name or "/device" in p.name.lower())
    groups = collections.Counter()
    total = 0.0
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            base = ev.name.split(" = ")[0].lstrip("%")
            groups[re.sub(r"[.\d]+$", "", base)] += ev.duration_ns / 1e3
            total += ev.duration_ns / 1e3
    per = {k: v / iters for k, v in groups.most_common(top)}
    return total / iters, per
