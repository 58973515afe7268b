"""One general traffic generator, driven by `benchmark/traffic/<mix>.json`.

Every seed gets the SAME multiset of prompt lengths, output lengths and
arrival gaps (stratified quantiles of the distributions the mix names) and
other token ids.  Without `order_seed` the seed also draws the order, so it
changes which request meets which, never how much work a window holds.  With
`order_seed` the order is the mix's own and every run replays one schedule:
a tail that rests on a few coincidences of arrivals (a p95 of 140 requests)
then moves with the system and not with the draw.

Mix parameters (all optional but `loop`):
  loop            "open" (arrivals on a schedule), "closed" (`clients` callers
                  who each send their next request when the last completes)
                  or "train_steps" (read by the train driver, not here)
  rate_rps        open loop: mean arrivals per second
  order_seed      draws the order of lengths and gaps in place of `--seed`
  clients         closed loop: callers
  max_rps         closed loop: a completion rate the system cannot reach;
                  sizes the pool of requests the callers draw from
  ramp_s          seconds of the same traffic before the window opens
                  (counted as set-up), so the window opens on a steady state
  prompt, output  {"median", "sigma", "min", "max"}: clipped lognormal lengths
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def stratified_lognormal(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    """n lengths at the mid-quantiles of a lognormal, clipped to [lo, hi]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def stratified_exp_gaps(n: int) -> np.ndarray:
    """n gaps at the mid-quantiles of Exp(1), scaled to sum to n."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (n / g.sum())


def _lengths(n: int, spec: dict, rs) -> np.ndarray:
    v = stratified_lognormal(n, spec["median"], spec["sigma"], spec["min"],
                             spec["max"])
    return v[rs.permutation(n)]


def _arrivals(n: int, t0: float, t1: float, order) -> np.ndarray:
    """Due times of n arrivals in [t0, t1): permuted stratified gaps, each
    arrival at the middle of its gap."""
    gaps = stratified_exp_gaps(n)[order.permutation(n)]
    return t0 + (np.cumsum(gaps) - 0.5 * gaps) * ((t1 - t0) / n)


def _batch(prefix: str, n: int, mix: dict, vocab: int, order, rs) -> list:
    plen = _lengths(n, mix["prompt"], order)
    olen = _lengths(n, mix["output"], order)
    return [{"id": f"{prefix}{i:05d}",
             "prompt": rs.randint(0, vocab, int(plen[i])).tolist(),
             "max_tokens": int(olen[i])} for i in range(n)]


def make_requests(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests of one run.  Open loop: each has `due` (seconds from the
    window's opening, negative in the ramp) and `counted` (due in the
    window).  Closed loop: an ordered pool the clients draw from, in blocks
    of `clients` requests that each hold the whole length distribution."""
    rs = np.random.RandomState(seed % (2 ** 32))          # token ids
    order = (np.random.RandomState(mix["order_seed"])
             if "order_seed" in mix else rs)
    ramp = float(mix.get("ramp_s", 0.0))
    if mix["loop"] == "open":
        rate = float(mix["rate_rps"])
        out = []
        n_ramp = int(round(rate * ramp))
        if n_ramp:
            due = _arrivals(n_ramp, -ramp, 0.0, order)
            for r, t in zip(_batch("r", n_ramp, mix, vocab, order, rs), due):
                out.append(dict(r, due=float(t), counted=False))
        n = int(round(rate * seconds))
        due = _arrivals(n, 0.0, seconds, order)
        for r, t in zip(_batch("w", n, mix, vocab, order, rs), due):
            out.append(dict(r, due=float(t), counted=True))
        return out
    if mix["loop"] == "closed":
        c = int(mix["clients"])
        # more than the system can complete: `max_rps` bounds its rate
        blocks = 1 + int(math.ceil(mix["max_rps"] * (ramp + seconds) / c))
        out = []
        for b in range(blocks):
            out.extend(_batch(f"c{b:03d}-", c, mix, vocab, order, rs))
        return out
    raise ValueError(f"traffic loop {mix['loop']!r} is not served by "
                     f"make_requests")


def prefill_buckets(requests, lo: int, hi: int) -> list:
    """The engine's prompt buckets (powers of two from `lo`, capped at `hi`;
    a copy of `serving.engine._bucket`) that these requests reach."""
    def bucket(n):
        b = lo
        while b < n:
            b *= 2
        return min(b, hi)
    return sorted({bucket(len(r["prompt"])) for r in requests})
