"""Per-layer metric readers of the SmallThinker cells: the roofline shares
of the grouped expert products, of the dense pool's decode read and of the
prefill flash calls, the expert load's skew and the whole step's share of
the chip's peak.

**Telling device ops apart.**  `jax.named_scope("moe.experts")`, `("attn.
window")`, `("attn.global")` and the kernels' own names (`dense_decode_read`,
`flash_fwd`; `pallas_call(name=...)` enters a scope of that name) reach the
profile as the JAX op name of each device op: the `tf_op` stat of the op's
event METADATA in the xplane.  `jax.profiler.ProfileData` hands out an
event's own stats only, so `device_ops` reads the protobuf itself (a few
fields of the wire format; no dependency beyond the standard library).

**Which steps are read.**  The engine's scheduler thread runs one program
at a time: everything a step runs on the device lies between the start of
its `serving.<kind>.dispatch` span and the start of its `serving.<kind>.
emit` span (the fetch between them waits for the tokens).  A reader counts
the device time of its ops inside those stretches and takes the work (KV
positions, assignments, experts touched) from the same steps' span stats,
so both sides of a share cover the same steps, whole.

Every reader returns None, and the harness leaves the metric out, where
there is nothing to read: no trace, no device plane (a CPU rehearsal), a
program without the spans, scopes or counters (the parent commit).
"""
from __future__ import annotations

import bisect
import functools
import re

from benchmark import (flops, flops_smallthinker as fs, span_readers,
                       trace_reduce)


# -- the xplane's wire format, as far as needed --------------------------------

def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varint and
    fixed fields, memoryviews for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield num, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield num, int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {wire} in an xplane")


def _first(buf, want):
    for num, val in _fields(buf):
        if num == want:
            return val
    return None


def _text(v) -> str:
    return "" if v is None else bytes(v).decode("utf-8", "replace")


@functools.lru_cache(maxsize=1)
def device_ops(path: str, plane: str = "/device:TPU:0"):
    """Device 0's ops as sorted (start_s, end_s, HLO text, JAX op name), or
    None without that plane.  XSpace.planes=1; XPlane: name=2, lines=3,
    event_metadata=4, stat_metadata=5; XLine: name=2, timestamp_ns=3,
    events=4; XEvent: metadata_id=1, offset_ps=2, duration_ps=3;
    XEventMetadata: name=2, stats=5; XStat: metadata_id=1, str_value=5,
    ref_value=7; XStatMetadata: name=2; a map entry is key=1, value=2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, pl in _fields(space):
        if num != 1 or _text(_first(pl, 2)) != plane:
            continue
        stat_names, metas, lines = {}, {}, []
        for n, v in _fields(pl):
            if n == 5:
                stat_names[_first(v, 1)] = _text(_first(_first(v, 2), 2))
            elif n == 4:
                metas[_first(v, 1)] = _first(v, 2)
            elif n == 3 and _text(_first(v, 2)) == "XLA Ops":
                lines.append(v)
        tf_op = next((k for k, s in stat_names.items() if s == "tf_op"), None)
        named = {}

        def meta(mid):
            if mid not in named:
                name, op = "", ""
                for n, v in _fields(metas.get(mid, b"")):
                    if n == 2:
                        name = _text(v)
                    elif n == 5 and _first(v, 1) == tf_op:
                        ref = _first(v, 7)
                        op = (stat_names.get(ref, "") if ref is not None
                              else _text(_first(v, 5)))
                named[mid] = (name, op)
            return named[mid]

        ops = []
        for line in lines:
            t0 = (_first(line, 3) or 0) * 1e-9
            for n, ev in _fields(line):
                if n != 4:
                    continue
                mid = off = dur = 0
                for k, v in _fields(ev):
                    if k == 1:
                        mid = v
                    elif k == 2:
                        off = v
                    elif k == 3:
                        dur = v
                start = t0 + off * 1e-12
                hlo, op = meta(mid)
                # a control-flow container spans its body's ops
                if trace_reduce.opcode(hlo) not in trace_reduce._CONTAINERS:
                    ops.append((start, start + dur * 1e-12, hlo, op))
        return sorted(ops)
    return None


# -- steps and the device time inside them -------------------------------------

def _steps(t: dict, kinds) -> list:
    """(start_s, end_s, stats) of every step of `kinds` ("decode",
    "prefill") whose dispatch and emit spans are both in the trace; the
    stats of the two spans merged."""
    out = []
    for kind in kinds:
        disp = sorted(t["host"].get(f"serving.{kind}.dispatch", ()),
                      key=lambda e: e[0])
        starts = [d[0] for d in disp]
        for s, _, stats in t["host"].get(f"serving.{kind}.emit", ()):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0:
                out.append((disp[i][0], s, {**disp[i][2], **stats}))
    return sorted(out, key=lambda e: e[0])


def _ops_in(ops, steps, match) -> list:
    """The ops that `match(hlo, jax_name)` keeps and that start inside one
    of `steps`."""
    starts = [s[0] for s in steps]
    keep = []
    for op in ops:
        i = bisect.bisect_right(starts, op[0]) - 1
        if i >= 0 and op[0] < steps[i][1] and match(op[2], op[3]):
            keep.append(op)
    return keep


def _read(obs, kinds):
    """(peak row, device ops, steps) or None."""
    path = obs.get("span_trace_path") or span_readers.trace_path()
    peak = flops.peaks(obs["device_kind"])
    if not path or peak is None:
        return None
    ops = device_ops(path)
    steps = _steps(span_readers.load(path), kinds)
    return (peak, ops, steps) if ops and steps else None


def _share(least_s: float, took_s: float):
    return 100.0 * least_s / took_s if took_s > 0 and least_s > 0 else None


# -- the readers ---------------------------------------------------------------

def moe_experts_roofline(obs):
    """Least time by the peak table for the grouped expert products of the
    traced steps (their FLOPs, and the bytes of the experts they touched)
    over the device time of the ops under `moe.experts`."""
    got = _read(obs, ("decode", "prefill"))
    if got is None:
        return None
    peak, ops, steps = got
    steps = [s for s in steps if "moe_assignments" in s[2]]
    least = 0.0
    for _, _, st in steps:
        # a step's counts are of its real tokens: padding and idle rows are
        # multiplied too, so the share errs low, never high
        f, b = fs.experts_cost(obs["config"], int(st["moe_assignments"]),
                               int(st["moe_experts_touched"]))
        least += flops.least_time_s(f, b, peak)[0]
    # XLA's grouped-matmul kernels keep their own name (`ragged-dot-none`)
    # and lose the scope's; everything else of the layer's second half
    # (permute back, weight, sum) carries `moe.experts`
    took = sum(e - s for s, e, *_ in _ops_in(
        ops, steps, lambda hlo, op: "moe.experts" in op or
        "ragged-dot" in op))
    return _share(least, took)


def decode_read_roofline(obs):
    """Least time for the KV bytes the decode steps' attention read streams
    (`kv_read` x the bytes of one position) over the device time of the
    `dense_decode_read` kernel calls."""
    got = _read(obs, ("decode",))
    if got is None:
        return None
    peak, ops, steps = got
    steps = [s for s in steps if "kv_read" in s[2]]
    calls = _ops_in(ops, steps, lambda hlo, op: "dense_decode_read" in op)
    least = 0.0
    for _, _, st in steps:
        f, b = fs.decode_read_cost(obs["config"], int(st["kv_read"]),
                                   rows=int(st.get("active", 0)))
        least += flops.least_time_s(f, b, peak)[0]
    return _share(least, sum(e - s for s, e, *_ in calls)) if calls else None


_OPERAND = re.compile(r"custom-call\(\s*\w+\[(\d+),(\d+),(\d+)\]")


def window_flash_roofline(obs):
    """Least time for the prefill steps' flash forward calls (the causal
    part of the score matrix on a global layer, the window's band on a
    sliding-window layer; sizes from each call's own operands) over their
    device time."""
    got = _read(obs, ("prefill",))
    if got is None:
        return None
    peak, ops, steps = got
    cfg = obs["config"]
    calls = _ops_in(ops, steps, lambda hlo, op: "flash_fwd" in op)
    least = took = 0.0
    for s, e, hlo, op in calls:
        m = _OPERAND.search(hlo)
        if not m:
            continue
        heads, t, d = (int(g) for g in m.groups())
        group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
        f, b = fs.flash_cost(
            heads, heads // group, t, d,
            cfg["sliding_window_size"] if "attn.window" in op else None)
        least += flops.least_time_s(f, b, peak)[0]
        took += e - s
    return _share(least, took)


def load_max_over_mean(obs):
    """experts x sum of each layer's largest expert load / sum of
    assignments, over the window's decode and prefill steps: 1 = even."""
    a, m = obs.get("moe_assignments"), obs.get("moe_load_max")
    if not a or m is None:
        return None
    return obs["config"]["moe_num_primary_experts"] * m / a


def serve_mfu(obs):
    """Model FLOPs of the tokens really prefilled and decoded in the window
    (`flops_smallthinker.py`) over the chip's peak x the window."""
    peak = flops.peaks(obs["device_kind"])
    f, w = obs.get("model_flops"), obs.get("window_s")
    if peak is None or not f or not w:
        return None
    return 100.0 * f / (peak["bf16_flops_per_s"] * w * obs["chips"])
