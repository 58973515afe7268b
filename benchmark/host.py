"""What the host gave this process over a stretch of a run.

A serve cell's window is a third the host's (the scheduler thread's Python
between two programs), and a one-chip machine shares its host's cores: the
same program on the same seed reads 1-2% apart by the half hour.  These
readings say which it was: `snapshot()` at both edges of the window,
`delta(a, b)` between them.  Notes only (stderr, `host over the window`);
no metric reads them.  Only calls every machine answers: the chip
machine's /proc has no `schedstat` and an empty `stat` (PERF.md section 6,
PR 33), so a thread's wait for a core is not among them.

  threads        by Python thread name: seconds on a core (`cpu_s`, the
                 thread's own CPU clock, `time.pthread_getcpuclockid`)
  process_cpu_s  all threads of this process, user + system
  switched_out   times the process was taken off a core while runnable
                 (`ru_nivcsw`)
  probe_ms       a fixed stretch of pure Python (the scheduler's kind of
                 work) timed on this thread: the median of `_PROBES` turns
"""
from __future__ import annotations

import os
import resource
import statistics
import threading
import time

_PROBES = 7


def probe_ms() -> float:
    """About 4 ms of dictionary and integer work a turn; the median turn."""
    turns = []
    for _ in range(_PROBES):
        t0 = time.perf_counter()
        d, acc = {}, 0
        for i in range(20000):
            d[i & 1023] = acc
            acc = (acc + d.get((i * 7) & 1023, i)) & 0xFFFFFF
        turns.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(turns)


def _thread_cpu_s(t: threading.Thread):
    # a thread that has been started and has not yet run has no ident; one
    # that ends between `enumerate` and here has no clock
    if t.ident is None:
        return None
    try:
        return time.clock_gettime(time.pthread_getcpuclockid(t.ident))
    except (OSError, AttributeError, ValueError):
        return None


def snapshot() -> dict:
    threads = {}
    for t in threading.enumerate():
        cpu = _thread_cpu_s(t)
        if cpu is not None:
            threads.setdefault(t.name, {})[t.ident] = cpu
    times = os.times()
    return {"t": time.monotonic(), "threads": threads,
            "process_cpu_s": times.user + times.system,
            "children_cpu_s": times.children_user + times.children_system,
            "switched_out": resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw}


def delta(a: dict, b: dict, names=()) -> dict:
    """What passed between two snapshots.  `names`: the threads to list (a
    name held by several threads is summed over those alive at both ends)."""
    out = {"wall_s": b["t"] - a["t"], "cores": len(os.sched_getaffinity(0)),
           "process_cpu_s": b["process_cpu_s"] - a["process_cpu_s"],
           "switched_out": b["switched_out"] - a["switched_out"],
           "threads": {}}
    for name in names:
        old, new = a["threads"].get(name, {}), b["threads"].get(name, {})
        both = [i for i in new if i in old]
        if both:
            out["threads"][name] = {
                "n": len(both), "cpu_s": sum(new[i] - old[i] for i in both)}
    return out
