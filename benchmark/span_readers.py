"""Per-layer metric readers over the program's own spans in the profiler's
trace.

`paddle_tpu.observability.trace` enters a `jax.profiler.TraceAnnotation` for
every span, so a `--trace 1` run's xplane holds the program's spans (the
engine's `serving.*` phases, `train_step`, `compile`, `checkpoint.*`) as
host-plane events on the clock of the device ops, each with the short scalar
attributes it was opened with as the event's stats.

**How a reader finds the trace.**  The harness hands a reader `obs` and no
path.  `run.py` writes a traced run's profile under
`<checkout>/.bench_trace/<--workload>`, so this module reads `--workload`
from the process's own command line, takes the newest xplane there
(`trace_reduce.find_xplane`) and parses it once per process.  With no such
file, no device plane (a CPU rehearsal) or none of the named spans in the
trace (a program from before the spans), a reader returns None and the
harness leaves its metric out.

    python3 -m benchmark.span_readers <workload>

prints, for the trace a run of that cell left behind, how device 0's idle
time splits over the span groups of the `engine.idle_*_share` metric files
and what is left (`serving.wait`, bubbles inside a running program, the
rest), and the median duration of every `serving.*` span: the numbers
`PERF.md` section 5 records.
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import statistics
import sys

from benchmark import trace_reduce

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)


def _workload(argv) -> str | None:
    for i, a in enumerate(argv):
        if a == "--workload" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--workload="):
            return a.split("=", 1)[1]
    return None


def trace_path(workload: str | None = None) -> str | None:
    workload = workload or _workload(sys.argv)
    if not workload:
        return None
    return trace_reduce.find_xplane(
        os.path.join(ROOT, ".bench_trace", workload))


@functools.lru_cache(maxsize=1)
def load(path: str) -> dict:
    """The trace, as far as the readers need it:

      window   (start_s, end_s) of `bench.window`, else of everything
      host     name -> [(start_s, end_s, stats)] of every host-plane event
      gaps     device 0's idle (start_s, end_s) stretches inside the
               window, sorted; None without a device plane
      op_ends  the ends of device 0's ops, sorted
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: dict = {}
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                host.setdefault(ev.name, []).append(
                    (ev.start_ns * 1e-9, ev.end_ns * 1e-9, dict(ev.stats)))
    ops = []
    for p in pd.planes:
        if p.name != "/device:TPU:0":
            continue
        for line in p.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                if trace_reduce.opcode(ev.name) not in \
                        trace_reduce._CONTAINERS:
                    ops.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9))
    wins = host.get("bench.window")
    if wins:
        window = (min(w[0] for w in wins), max(w[1] for w in wins))
    elif ops:
        window = (min(o[0] for o in ops), max(o[1] for o in ops))
    else:
        every = [iv for evs in host.values() for iv in evs]
        window = ((min(e[0] for e in every), max(e[1] for e in every))
                  if every else (0.0, 0.0))
    return {"window": window, "host": host,
            "gaps": trace_reduce.idle_gaps(ops, *window) if ops else None,
            "op_ends": sorted(e for _, e in ops)}


def _trace(obs) -> dict | None:
    path = obs.get("span_trace_path") or trace_path()
    return load(path) if path else None


def _merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def overlap_s(gaps, intervals) -> float:
    """Length of (union of `intervals`) intersected with the sorted,
    disjoint `gaps`: exact, no best-overlap."""
    total, i = 0.0, 0
    for s, e in _merged(intervals):
        while i < len(gaps) and gaps[i][1] <= s:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < e:
            total += min(e, gaps[j][1]) - max(s, gaps[j][0])
            j += 1
    return total


def trailing(t: dict, events) -> list:
    """Of each event, the stretch after the last device op that ends inside
    it (the whole event when none does): while the host sits in a fetch the
    program is still running, and the bubbles between its ops are the
    device's, not the host's."""
    ends, out = t["op_ends"], []
    for s, e, *_ in events:
        i = bisect.bisect_right(ends, e) - 1
        out.append((ends[i] if i >= 0 and ends[i] >= s else s, e))
    return out


def _events(t: dict, spans) -> list:
    return [ev for n in spans for ev in t["host"].get(n, ())]


def idle_under(obs, spans, trailing_only: bool = False):
    """Device 0's idle time inside the window that lies under the host
    events named in `spans`, as % of the window."""
    t = _trace(obs)
    if t is None or t["gaps"] is None:
        return None
    evs = _events(t, spans)
    if not evs:
        return None
    ivs = trailing(t, evs) if trailing_only else [(s, e) for s, e, _ in evs]
    w0, w1 = t["window"]
    return 100.0 * overlap_s(t["gaps"], ivs) / (w1 - w0)


def stat_complement_pct(obs, span: str, num: str, den: str):
    """Over the events named `span` that start inside the window:
    100 x (1 - sum of stat `num` / sum of stat `den`)."""
    t = _trace(obs)
    if t is None:
        return None
    w0, w1 = t["window"]
    n = d = 0.0
    for s, _, stats in t["host"].get(span, ()):
        if w0 <= s < w1 and num in stats and den in stats:
            n += float(stats[num])
            d += float(stats[den])
    return 100.0 * (1.0 - n / d) if d else None


def split(path: str) -> dict:
    """Where device 0's idle time of one serve trace lies, in % of the
    window, and the median duration of each `serving.*` span in ms."""
    t = load(path)
    w0, w1 = t["window"]
    win = w1 - w0
    out = {"window_s": win, "median_ms": {}, "count": {}}
    for name, evs in sorted(t["host"].items()):
        inside = [e - s for s, e, _ in evs if w0 <= s < w1]
        if name.startswith("serving.") and inside:
            out["median_ms"][name] = statistics.median(inside) * 1e3
            out["count"][name] = len(inside)
    if t["gaps"] is None:
        return out

    def pct(intervals):
        return 100.0 * overlap_s(t["gaps"], intervals) / win

    def plain(evs):
        return [(s, e) for s, e, _ in evs]

    groups, fetch_evs, covered = {}, [], []
    for kind in ("fetch", "emit", "prepare"):
        with open(os.path.join(_HERE, "metrics",
                               f"engine.idle_{kind}_share.steady.json")) as f:
            args = json.load(f)["args"]
        evs = _events(t, args["spans"])
        covered += plain(evs)
        if args.get("trailing_only"):
            fetch_evs = evs
            groups[kind] = pct(trailing(t, evs))
        else:
            groups[kind] = pct(plain(evs))
    wait = plain(_events(t, ["serving.wait"]))
    idle = 100.0 * sum(e - s for s, e in t["gaps"]) / win
    out["idle_pct"] = dict(
        groups, total=idle, wait=pct(wait),
        in_program_bubbles=pct(plain(fetch_evs)) - groups["fetch"],
        # under no leaf: between the leaves of an iteration, and outside
        # every iteration (the loop's heartbeat, another thread's program)
        rest=idle - pct(covered + wait),
        rest_inside_iterations=(
            pct(plain(_events(t, ["serving.iteration"]))) - pct(covered)))
    return out


if __name__ == "__main__":
    found = trace_path(sys.argv[1])
    if found is None:
        sys.exit(f"span_readers: no trace under .bench_trace/{sys.argv[1]}")
    print(json.dumps(split(found)))
