"""xplane -> numbers.  The one reduction from a profiler trace to metrics.

`reduce_trace(path)` reads the `.xplane.pb` the JAX profiler wrote (with
`jax.profiler.ProfileData`, nothing else) and returns, for the steady window
the benchmark marked with its `bench.window` host span:

  window_s, busy_s      per device: union of the device-op intervals
  ops                   per device: seconds by SHORT instruction name
  custom_calls          device 0: (short name, operand count, seconds) of
                        every Mosaic kernel call (`tpu_custom_call`)
  collective_s          device 0: union of the collective ops' intervals
  gaps                  device 0's idle gaps, each named by the benchmark's
                        own host span (`bench.*`) that overlaps it most, else
                        by JAX's own host event (`host:PjitFunction(...)`),
                        else `unattributed`

Device ops are the events of a device plane's "XLA Ops" line.  Control-flow
containers (`while`, `conditional`, `call`) span their bodies, whose ops are
events of their own, so containers are left out of busy time and op time.
"""
from __future__ import annotations

import glob
import os
import re

_CONTAINERS = ("while", "conditional", "call")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")
_OPCODE = re.compile(r"(?:^|[\s)}])([a-z][a-z0-9_\-]*)\(")
_WINDOW = "bench.window"
# spans in which the benchmark only waits; a gap under one is named by a
# more telling host event if there is one
_WAIT_SPANS = ("bench.client_wait",)


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def short_name(event_name: str) -> str:
    """`%fusion.9997 = bf16[...] fusion(...)` -> `fusion.9997`."""
    s = event_name.split(" = ", 1)[0].strip()
    return s.lstrip("%")[:64]


def opcode(event_name: str) -> str:
    if " = " in event_name:
        m = _OPCODE.search(event_name.split(" = ", 1)[1])
        if m:
            return m.group(1)
    return short_name(event_name).rsplit(".", 1)[0]


def n_operands(event_name: str) -> int:
    """Operands of the instruction: `%name`s inside its argument list."""
    if " = " not in event_name:
        return 0
    rhs = event_name.split(" = ", 1)[1]
    m = _OPCODE.search(rhs)
    if not m:
        return 0
    depth, i = 0, m.end() - 1
    for j in range(i, len(rhs)):
        if rhs[j] == "(":
            depth += 1
        elif rhs[j] == ")":
            depth -= 1
            if depth == 0:
                return rhs[i:j].count("%")
    return rhs[i:].count("%")


def union_s(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, w0: float, w1: float) -> list:
    """The (start, end) stretches of [w0, w1] that no interval covers."""
    gaps, t = [], w0
    for s, e in sorted(intervals):
        if s > t:
            gaps.append((t, min(s, w1)))
        t = max(t, e)
        if t >= w1:
            break
    if t < w1:
        gaps.append((t, w1))
    return [g for g in gaps if g[1] > g[0]]


def _clip(s, e, w0, w1):
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def _best_overlap(gap, spans):
    best, name = 0.0, None
    for s, e, n in spans:
        o = min(e, gap[1]) - max(s, gap[0])
        if o > best:
            best, name = o, n
    return name


def _device_planes(pd):
    out = []
    for p in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", p.name)
        if m:
            out.append((int(m.group(1)), p))
    return [p for _, p in sorted(out, key=lambda t: t[0])]


def reduce_trace(path: str) -> dict | None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = _device_planes(pd)
    if not devices:
        return None
    bench, host = [], []                  # (start_s, end_s, name)
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                n = ev.name
                if n.startswith("bench."):
                    bench.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9, n))
                elif n.startswith("PjitFunction(") or \
                        n == "np.asarray(jax.Array)":
                    host.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                 ("host:" + n)[:64]))
    per_device = []
    for p in devices:
        ops, coll = [], []
        for line in p.lines:
            if line.name not in ("XLA Ops", "Async XLA Ops"):
                continue
            for ev in line.events:
                oc = opcode(ev.name)
                iv = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                if oc.startswith(_COLLECTIVES):
                    coll.append(iv)
                if line.name == "XLA Ops" and oc not in _CONTAINERS:
                    ops.append((iv[0], iv[1], ev.name))
        per_device.append((ops, coll))
    wins = [(s, e) for s, e, n in bench if n == _WINDOW]
    all_ops = [iv for ops, _ in per_device for iv in ops]
    if not all_ops:
        return None
    if wins:
        w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    else:
        w0, w1 = min(o[0] for o in all_ops), max(o[1] for o in all_ops)
    out = {"window_s": w1 - w0, "devices": []}
    clipped = []                          # per device: intervals in the window
    for ops, _ in per_device:
        ivs, by_name = [], {}
        for s, e, name in ops:
            c = _clip(s, e, w0, w1)
            if c:
                ivs.append(c)
                k = short_name(name)
                by_name[k] = by_name.get(k, 0.0) + c[1] - c[0]
        clipped.append(ivs)
        out["devices"].append({"busy_s": union_s(ivs), "ops": by_name})
    ops0, coll0 = per_device[0]
    out["busy_s"] = (sum(d["busy_s"] for d in out["devices"]) /
                     len(out["devices"]))
    out["collective_s"] = union_s(
        [c for c in (_clip(s, e, w0, w1) for s, e in coll0) if c])
    out["custom_calls"] = [
        (short_name(n), n_operands(n), min(e, w1) - max(s, w0))
        for s, e, n in ops0
        if "tpu_custom_call" in n and _clip(s, e, w0, w1)]
    leaf = [b for b in bench if b[2] != _WINDOW]
    named = {}
    for g in idle_gaps(clipped[0], w0, w1):
        name = _best_overlap(g, leaf)
        if name is None or name in _WAIT_SPANS:
            name = _best_overlap(g, host) or name or "unattributed"
        named[name] = named.get(name, 0.0) + g[1] - g[0]
    out["gaps"] = named
    return out


def breakdown(red: dict, top: int = 10) -> dict:
    """The contract's `breakdown`: device 0's ten longest ops by short name
    and the longest idle gaps by what the host was doing."""
    def head(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": head(red["devices"][0]["ops"]),
            "idle_gaps": head(red["gaps"])}
