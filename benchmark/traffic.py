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
                  sizes the pool of requests the callers draw from:
                  1 + ceil(max_rps x (ramp_s + seconds) / clients) blocks of
                  `clients` requests.  A system that does reach it finds the
                  list empty, its callers stop one by one, and the run reads
                  the pool's size over the window and not what the system
                  can do (a faster system then reads LOWER: more of the pool
                  is gone before the window opens).  `closed_loop_supply`
                  says so and the run is reported as not correct: raise
                  `max_rps` in the mix file
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


def in_flight(results: list, t: float) -> int:
    """Requests of a client's report sent by `t` (seconds from the window's
    opening) and not answered in full by then."""
    return sum(1 for r in results
               if r["sent"] is not None and r["sent"] <= t and
               not (r["done"] and r["stamps"] and r["stamps"][-1] <= t))


def closed_loop_supply(mix: dict, requests: list, results: list,
                       seconds: float) -> tuple[dict, str | None]:
    """What a run left of its requests, from the client's report: notes for
    every serve driver (`pool_size`, `pool_left` = requests of the plan that
    no caller had sent when the window closed, `in_flight_end`) and, for a
    closed loop whose callers could not all have drawn once more
    (`pool_left < clients`: the pool ran dry, or was about to), the reason
    why the run is not correct.  An open loop sends its whole schedule and
    is never judged (`pool_left` None)."""
    notes = {"pool_size": len(requests), "pool_left": None,
             "in_flight_end": in_flight(results, seconds)}
    if mix["loop"] != "closed":
        return notes, None
    notes["pool_left"] = len(requests) - sum(
        1 for r in results if r["sent"] is not None)
    if notes["pool_left"] >= int(mix["clients"]):
        return notes, None
    return notes, (
        f"the closed loop's pool ran dry: pool_left {notes['pool_left']} of "
        f"pool_size {len(requests)} is under clients {mix['clients']} "
        f"(in_flight_end {notes['in_flight_end']}), so the window measured "
        f"the pool and not the system: raise `max_rps` (now "
        f"{mix['max_rps']}) in the cell's traffic mix file; not correct")


def prefill_buckets(requests, lo: int, hi: int) -> list:
    """The engine's prompt buckets (powers of two from `lo`, capped at `hi`;
    a copy of `serving.engine._bucket`) that these requests reach."""
    def bucket(n):
        b = lo
        while b < n:
            b *= 2
        return min(b, hi)
    return sorted({bucket(len(r["prompt"])) for r in requests})
