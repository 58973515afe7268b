"""Per-layer metric readers of the Trinity-Large-Preview cells: the roofline
shares of the dense pool's decode read (bytes by layer kind: the window
layers' rings and the full layers' rows) and of the prefill flash calls
(the window's band or the causal triangle), the held experts' part of what
was routed and their skew, the window layers' part of the positions a
decode step reads, and how full the pool's reservation is.

Device ops are told apart as `moe_readers.py` says (its docstring; `device_ops`,
`_read`, `_ops_in`, `_steps` and `_share` are imported from there), and so are the prefill
steps; the decode steps of the two shares that sum over steps are paired by
the `step` ordinal their spans carry, over one contiguous stretch of the
trace (`_stretch`; PERF.md section 7 says what the older pairing by time
loses once a step is queued behind the running one: a tenth of a 5.8 ms
step here).  Sizes come from `flops_trinity.py`.  The whole step's share is
`moe_readers.serve_mfu`: the cell's metric file names it.  Every reader
returns None, and the harness leaves the metric out, where there is nothing
to read: no trace, no device plane (a CPU rehearsal), a program without the
spans, scopes or counters (the parent commit).
"""
from __future__ import annotations

import re

from benchmark import flops, span_readers
from benchmark import flops_trinity as ft
from benchmark.moe_readers import (_ops_in, _read, _share, _steps,
                                   device_ops)


def _stretch(obs):
    """One contiguous stretch of the trace and the steps whose programs ran
    in it: (peak row, device ops that start inside it, the stats of its
    decode steps, the stats of its prefill steps), or None.

    A decode step's two spans carry its ordinal (`step`, since PR 34), so
    they are paired by it and not by their order in time: with a step
    queued behind the running one, `serving.decode.dispatch` of step n + 1
    precedes `serving.decode.emit` of step n.  The device runs step k's
    program between the moments the host has step k - 1's and step k's
    tokens (the start of each one's emit span), so the stretch runs from
    one emit's start to a later one's, and holds, whole, every decode step
    whose emit starts inside it and every prefill whose dispatch and emit
    both lie inside it.  Both sides of a share cover the same stretch:
    nothing rests on how long the host takes between two spans, and a
    constant offset between the trace's host and device clocks moves both
    ends alike."""
    path = obs.get("span_trace_path") or span_readers.trace_path()
    peak = flops.peaks(obs["device_kind"])
    ops = device_ops(path) if path and peak is not None else None
    if not ops:
        return None
    trace = span_readers.load(path)
    host, prefills = trace["host"], _steps(trace, ("prefill",))
    disp = {int(st["step"]): st
            for _, _, st in host.get("serving.decode.dispatch", ())
            if "step" in st}
    emits = sorted((s, int(st["step"]), st)
                   for s, _, st in host.get("serving.decode.emit", ())
                   if "step" in st)
    first = next((i for i in range(1, len(emits)) if emits[i][1] in disp),
                 None)
    if first is None:
        return None
    t_a, t_b = emits[first - 1][0], emits[-1][0]
    decode = [{**disp[k], **st} for _, k, st in emits[first:] if k in disp]
    prefill = [st for s, e, st in prefills if t_a <= s and e <= t_b]
    inside = [op for op in ops if t_a <= op[0] < t_b]
    return peak, inside, decode, prefill


def decode_read_roofline(obs):
    """Least time for the KV bytes the decode steps' attention read streams
    (`kv_read_window` + `kv_read_global` positions x the bytes of one
    position of one layer) over the device time of the `dense_decode_read`
    kernel calls of the same stretch (`_stretch`)."""
    got = _stretch(obs)
    if got is None:
        return None
    peak, ops, decode, _ = got
    least = 0.0
    for st in decode:
        if "kv_read_window" not in st:
            return None
        f, b = ft.decode_read_cost(
            obs["config"], int(st["kv_read_window"]),
            int(st["kv_read_global"]), rows=int(st.get("active", 0)))
        least += flops.least_time_s(f, b, peak)[0]
    return _share(least, sum(e - s for s, e, _, op in ops
                             if "dense_decode_read" in op))


def moe_experts_roofline(obs):
    """Least time by the peak table for the grouped expert products of a
    stretch's decode and prefill steps (the assignments that landed on held
    experts and the held experts they touched) over the device time of the
    stretch's ops under `moe.experts` (XLA's grouped-matmul kernels keep
    their own name, `ragged-dot-*`); the shared expert's ops (`moe.shared`)
    are not in it.  A prefill that the stretch's edge cuts adds its ops and
    not its work: the share errs low, never high."""
    got = _stretch(obs)
    if got is None:
        return None
    peak, ops, decode, prefill = got
    least = 0.0
    for st in decode + prefill:
        if "moe_assignments" not in st:
            return None
        f, b = ft.experts_cost(obs["config"], int(st["moe_assignments"]),
                               int(st["moe_experts_touched"]))
        least += flops.least_time_s(f, b, peak)[0]
    return _share(least, sum(e - s for s, e, _, op in ops
                             if "moe.experts" in op or "ragged-dot" in op))


_OPERAND = re.compile(r"custom-call\(\s*\w+\[(\d+),(\d+),(\d+)\]")


def window_flash_roofline(obs):
    """Least time for the prefill steps' flash forward calls (the window's
    band on a sliding layer, the causal triangle on a full one; sizes from
    each call's own operands, the kind from its scope) over their device
    time."""
    got = _read(obs, ("prefill",))
    if got is None:
        return None
    peak, ops, steps = got
    cfg = obs["config"]
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    least = took = 0.0
    for s, e, hlo, op in _ops_in(ops, steps,
                                 lambda hlo, op: "flash_fwd" in op):
        m = _OPERAND.search(hlo)
        if not m:
            continue
        heads, t, d = (int(g) for g in m.groups())
        f, b = ft.flash_cost(
            heads, heads // group, t, d,
            cfg["sliding_window"] if "attn.window" in op else None)
        least += flops.least_time_s(f, b, peak)[0]
        took += e - s
    return _share(least, took)


def _spread(obs) -> float:
    """How many shares like this one the routed experts are spread over."""
    cfg = obs["config"]
    return cfg.get("published_num_experts", cfg["num_experts"]) / \
        cfg["num_experts"]


def held_share_over_even(obs):
    """shares x sum of `moe_assignments` / sum of `moe_routed` over the
    window's decode and prefill steps: the part of all routed assignments
    that landed on the experts held here, over the even part; 1 = even."""
    a, r = obs.get("moe_assignments"), obs.get("moe_routed")
    if not r or a is None:
        return None
    return _spread(obs) * a / r


def load_max_over_mean(obs):
    """held experts x sum of each layer's largest held-expert load / sum of
    the held experts' assignments: 1 = even among the experts held."""
    a, m = obs.get("moe_assignments"), obs.get("moe_load_max")
    if not a or m is None:
        return None
    return obs["config"]["num_experts"] * m / a


def kv_window_read_share(obs):
    """100 x the positions the window's decode steps read from window
    layers / all positions they read: what the window bounds."""
    w, g = (obs.get("decode_kv_read_positions_window"),
            obs.get("decode_kv_read_positions_global"))
    if w is None or g is None or not w + g:
        return None
    return 100.0 * w / (w + g)


def kv_pool_live_share(obs):
    """100 x the bytes of the live positions of the window's decode steps
    (each step's, summed over layers) / the pool's bytes x the steps: how
    full the reservation is."""
    live, steps, pool = (obs.get("decode_kv_live_positions"),
                         obs.get("decode_steps"), obs.get("kv_pool_bytes"))
    if not live or not steps or not pool:
        return None
    return (100.0 * live * ft.kv_bytes_per_position(obs["config"]) /
            (steps * pool))
