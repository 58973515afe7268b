"""Per-layer metric readers.  Each takes the run's observations (`obs`: what
the driver counted and clocked, the reduced trace under `obs["trace"]`, the
configuration, the device kind) plus the `args` of its metric file, and
returns a number, or None when there is nothing to read; the harness then
leaves the metric out.  A metric file names its reader as
`<module>.<function>` under `benchmark/`, so a later PR adds readers in a
module of its own.
"""
from __future__ import annotations

import statistics

from benchmark import flops


def observed(obs, key: str, scale: float = 1.0):
    """A number the driver observed, as it is."""
    v = obs.get(key)
    return None if v is None else v * scale


def median_of(obs, key: str, scale: float = 1.0):
    v = obs.get(key)
    return statistics.median(v) * scale if v else None


def p95_of(obs, key: str, scale: float = 1.0):
    v = sorted(obs.get(key) or ())
    if len(v) < 2:
        return None
    return statistics.quantiles(v, n=20, method="inclusive")[18] * scale


def ratio_pct(obs, num: str, den: str):
    n, d = obs.get(num), obs.get(den)
    return None if n is None or not d else 100.0 * n / d


def train_mfu(obs):
    """Model FLOP/s utilization: FLOPs the algorithm needs per token (6N +
    12Lhs, recomputation not counted) x tokens/s/chip over the chip's peak."""
    peak = flops.peaks(obs["device_kind"])
    if peak is None:
        return None
    return (100.0 * obs["train_flops_per_token"] *
            obs["tokens_per_s_per_chip"] / peak["bf16_flops_per_s"])


def device_idle_share(obs):
    red = obs.get("trace")
    if not red or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def collective_share(obs):
    red = obs.get("trace")
    if not red or not red["window_s"]:
        return None
    return 100.0 * red["collective_s"] / red["window_s"]


def flash_roofline(obs):
    """Least time the peak table allows for the traced flash calls over the
    device time they took.  Every Mosaic call of a train step is flash:
    forward calls take q, k, v (3 operands), the fused backward more.  Both
    are compute-bound at these shapes (the bound is logged)."""
    red = obs.get("trace")
    calls = (red or {}).get("custom_calls")
    peak = flops.peaks(obs["device_kind"])
    if not calls or peak is None:
        return None
    s = obs["flash_shape"]
    least = took = 0.0
    for _, n_operands, seconds in calls:
        cost = flops.flash_fwd_cost if n_operands <= 3 else flops.flash_bwd_cost
        f, b = cost(s["bh"], s["t"], s["t"], s["d"], causal=True)
        least += flops.least_time_s(f, b, peak)[0]
        took += seconds
    return 100.0 * least / took if took else None
