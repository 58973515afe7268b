"""Per-layer metric readers of the latent-attention cells: the roofline
shares of the latent pool's decode read, of the prefill flash calls at two
head sizes and of the grouped expert products, the held experts' part of
what was routed, and the held experts' skew.

Device ops are told apart and steps are cut as `moe_readers.py` says (its
docstring): `device_ops`, `_steps`, `_ops_in`, `_read` and `_share` are
imported from there.  Sizes come from the module the configuration names
under `flops` (`flops_pangu.py`).  Every reader returns None, and the
harness leaves the metric out, where there is nothing to read: no trace, no
device plane (a CPU rehearsal), a program without the spans, scopes or
counters (the parent commit).
"""
from __future__ import annotations

import importlib
import re

from benchmark import flops
from benchmark.moe_readers import _ops_in, _read, _share


def _fl(obs):
    return importlib.import_module(f"benchmark.{obs['config']['flops']}")


def latent_read_roofline(obs):
    """Least time for the latent rows the decode steps' attention read
    streams (`kv_read` x the bytes of one [c | kr] row) and for the
    absorbed form's FLOPs on them (every head against the whole row), over
    the device time of the `latent_decode_read` kernel calls."""
    got = _read(obs, ("decode",))
    if got is None:
        return None
    peak, ops, steps = got
    steps = [s for s in steps if "kv_read" in s[2]]
    calls = _ops_in(ops, steps, lambda hlo, op: "latent_decode_read" in op)
    least = 0.0
    for _, _, st in steps:
        f, b = _fl(obs).latent_read_cost(obs["config"], int(st["kv_read"]),
                                         rows=int(st.get("active", 0)))
        least += flops.least_time_s(f, b, peak)[0]
    return _share(least, sum(e - s for s, e, *_ in calls)) if calls else None


_SHAPE3 = re.compile(r"\b[a-z]\w*\[(\d+),(\d+),(\d+)\]")


def mla_flash_roofline(obs):
    """Least time for the prefill steps' flash forward calls under
    `attn.latent` (the causal triangle with queries and keys of one head
    size and values of another; sizes from each call's own operands) over
    their device time."""
    got = _read(obs, ("prefill",))
    if got is None:
        return None
    peak, ops, steps = got
    calls = _ops_in(ops, steps, lambda hlo, op: "flash_fwd" in op)
    least = took = 0.0
    for s, e, hlo, _ in calls:
        # the call's operands: q [heads, t, d], k [heads, t, d], v [heads,
        # t, dv] (layouts between them carry commas of their own)
        shapes = _SHAPE3.findall(hlo.partition("custom-call(")[2])
        if len(shapes) < 3:
            continue
        (heads, t, d), dv = (int(g) for g in shapes[0]), int(shapes[2][2])
        f, b = _fl(obs).flash_cost(heads, t, d, dv)
        least += flops.least_time_s(f, b, peak)[0]
        took += e - s
    return _share(least, took)


def moe_experts_roofline(obs):
    """Least time by the peak table for the grouped expert products of the
    traced steps (the assignments that landed on held experts and the held
    experts they touched) over the device time of the ops under
    `moe.experts`; the shared expert's ops (`moe.shared`) are not in it."""
    got = _read(obs, ("decode", "prefill"))
    if got is None:
        return None
    peak, ops, steps = got
    steps = [s for s in steps if "moe_assignments" in s[2]]
    least = 0.0
    for _, _, st in steps:
        f, b = _fl(obs).experts_cost(obs["config"],
                                     int(st["moe_assignments"]),
                                     int(st["moe_experts_touched"]))
        least += flops.least_time_s(f, b, peak)[0]
    took = sum(e - s for s, e, *_ in _ops_in(
        ops, steps, lambda hlo, op: "moe.experts" in op or
        "ragged-dot" in op))
    return _share(least, took)


def _spread(obs) -> float:
    """How many shares like this one the routed experts are spread over."""
    cfg = obs["config"]
    return (cfg.get("published_n_routed_experts", cfg["n_routed_experts"]) /
            cfg["n_routed_experts"])


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
    return obs["config"]["n_routed_experts"] * m / a
