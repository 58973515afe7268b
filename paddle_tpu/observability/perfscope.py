"""Device perfscope — per-program device-time/MFU attribution + the HBM
ownership ledger (the device-side twin of the journey layer).

PR 13's request journeys partition *host* wall time exactly; nothing
attributed *device* time or HBM bytes.  This module closes that gap with
two always-available registries:

**Per-program device accounting.**  Every :class:`retrace.InstrumentedJit`
entry point (the SPMD train steps, ``jit.to_static`` caches, and the
serving engine's prefill / tail_prefill / prefix_copy / decode programs)
registers its compiled ``cost_analysis`` (flops + bytes accessed) once
per abstract signature, and a sampling timer measures device seconds:
with ``PADDLE_TPU_PERFSCOPE_SAMPLE=N`` (or :func:`set_sample_every`),
every Nth dispatch of a program is bracketed with a
``block_until_ready`` — the other ``N-1`` dispatches stay fully async,
and the decode hot path keeps its ONE compiled signature (sampling never
touches the arguments, test-asserted).  Dividing the sampled wall by the
:mod:`~paddle_tpu.distributed.auto_parallel.cluster` peak table (CPU
carries a synthetic peak so the math is tier-1-testable) yields live

* ``paddle_tpu_device_program_seconds{program}`` — sampled device
  seconds (counter),
* ``paddle_tpu_device_program_mfu{program}`` — model-flops utilization
  of the last sampled dispatch (gauge),
* ``paddle_tpu_device_program_hbm_bw_frac{program}`` — fraction of peak
  HBM bandwidth (gauge),

plus :func:`perf_report` (the ``GET /debug/perf`` JSON roofline table)
and :func:`chrome_events` (sampled program intervals as a
``"cat": "device"`` lane that merges with the PR 2 span ring and the
journey tracks on one timeline).

**HBM ownership ledger.**  Long-lived device allocations declare a named
owner (``weights`` incl. int8 + scales, ``kv_pool`` / page pool,
``adapter_bank``, ``prefix_cache`` retained rows — a *nested*
sub-account of the pool bytes — and ``prefetch`` buffers):
``ledger().register(owner, nbytes)`` returns a row with
``update``/``add``/``release``; per-owner sums export as
``paddle_tpu_hbm_bytes{owner}`` and :func:`memory_report` (the
``GET /debug/memory`` JSON) reconciles them against the backend's
``bytes_in_use`` with an explicit ``unattributed`` remainder.  The
ledger is always on (flight-recorder duty cycle: a few rows per engine
build, never per-op) so an allocation failure can name its owner:
:func:`note_exception` detects RESOURCE_EXHAUSTED, records an ``oom``
flight event with the owner table, and writes a watchdog crash bundle
whose ``hbm_ledger`` section carries the full ledger — an OOM becomes an
artifact that says *who* held the HBM, not just that it ran out.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque

from . import flight, registry

logger = logging.getLogger("paddle_tpu.observability")

# -- metric names --------------------------------------------------------------
DEVICE_PROGRAM_SECONDS = "paddle_tpu_device_program_seconds"
DEVICE_PROGRAM_MFU = "paddle_tpu_device_program_mfu"
DEVICE_PROGRAM_BW_FRAC = "paddle_tpu_device_program_hbm_bw_frac"
HBM_BYTES = "paddle_tpu_hbm_bytes"

# ledger owners whose bytes live in host DRAM, not on the device: part of
# the consolidated KV budget, excluded from the bytes_in_use reconciliation
HOST_OWNERS = frozenset({"host_prefix"})

_lock = threading.Lock()

# sample every Nth dispatch per program; 0 = sampling off (the default:
# the hot path then costs one integer compare per dispatch)
_SAMPLE = [max(0, int(os.environ.get("PADDLE_TPU_PERFSCOPE_SAMPLE",
                                     "0") or 0))]
# sampled program intervals (the cat:"device" chrome lane)
_RING: deque = deque(
    maxlen=max(16, int(os.environ.get("PADDLE_TPU_PERFSCOPE_RING", "2048"))))
# (peak_flops, peak_hbm_bw) — resolved lazily from the cluster table
_peaks: list = [None]


def sample_every() -> int:
    return _SAMPLE[0]


def set_sample_every(n: int):
    """Sample one in every ``n`` dispatches per program (0 disables)."""
    _SAMPLE[0] = max(0, int(n))


def sampling_active() -> bool:
    return _SAMPLE[0] > 0


def _telemetry_on() -> bool:
    from ..core import op as op_mod
    return bool(op_mod.TELEMETRY)


# -- peaks ---------------------------------------------------------------------

def peaks() -> tuple:
    """(peak FLOP/s, peak HBM bytes/s) of the live backend, from the
    cluster spec table.  CPU resolves to the synthetic spec-sheet entry
    so MFU math is exercised (and testable) in tier-1."""
    p = _peaks[0]
    if p is None:
        try:
            from ..distributed.auto_parallel.cluster import Cluster
            c = Cluster.auto()
            p = (float(c.peak_flops()), float(c.peak_hbm_bw()))
        except Exception:  # noqa: BLE001 — no backend: MFU just stays None
            p = (0.0, 0.0)
        _peaks[0] = p
    return p


def set_peaks(flops: float, hbm_bw: float):
    """Pin the peak table (tests / explicit hardware description)."""
    _peaks[0] = (float(flops), float(hbm_bw))


def reset_peaks():
    _peaks[0] = None


# -- per-program accounting ----------------------------------------------------

class _ProgramStats:
    __slots__ = ("name", "costs", "dispatches", "sampled",
                 "device_seconds", "last")

    def __init__(self, name: str):
        self.name = name
        self.costs: dict = {}        # signature key -> {"flops", "bytes"}
        self.dispatches = 0
        self.sampled = 0
        self.device_seconds = 0.0
        self.last: dict | None = None


_programs: dict[str, _ProgramStats] = {}


def _program(name: str) -> _ProgramStats:
    st = _programs.get(name)
    if st is None:
        st = _programs[name] = _ProgramStats(name)
    return st


def poll_sample(program: str) -> bool:
    """Count one dispatch of ``program``; True when THIS dispatch should
    be timed (every ``sample_every()``-th).  Callers only invoke this
    while :func:`sampling_active`."""
    n = _SAMPLE[0]
    with _lock:
        st = _program(program)
        st.dispatches += 1
        return n > 0 and st.dispatches % n == 0


def register_cost(program: str, key, cost: dict):
    """Book one compiled signature's ``cost_analysis`` numbers (called
    once per signature, at compile time)."""
    with _lock:
        _program(program).costs[str(key)[:256]] = {
            "flops": float(cost.get("flops", 0.0) or 0.0),
            "bytes": float(cost.get("bytes accessed", 0.0) or 0.0),
        }


def register_program(program: str, key, fn, args, kwargs):
    """Cost registration hook for :class:`retrace.InstrumentedJit`: AOT
    lower+compile the entry point at the signature just compiled and book
    its cost.  Only runs when the perfscope is live (sampling on or
    telemetry on) — the lower/compile is once per signature, the same
    order of work as the compile that just happened."""
    if not (sampling_active() or _telemetry_on()):
        return
    try:
        cost = fn.lower(*args, **kwargs).compile().cost_analysis()
    except Exception:  # noqa: BLE001 — AOT path missing on this fn: no cost
        return
    register_cost(program, key, cost)


def block_ready(out):
    """The sampling barrier (module-level so tests can count calls)."""
    import jax
    jax.block_until_ready(out)


def record_sample(program: str, key, seconds: float):
    """Book one sampled dispatch: ``seconds`` is the host-observed wall
    of a blocked call (dispatch + device; on a warm async backend the
    device term dominates).  Updates the roofline stats, the device-lane
    ring, and (telemetry on) the exported series."""
    seconds = max(float(seconds), 1e-12)
    pf, pb = peaks()
    with _lock:
        st = _program(program)
        st.sampled += 1
        st.device_seconds += seconds
        cost = st.costs.get(str(key)[:256]) or {}
        flops = cost.get("flops", 0.0)
        bts = cost.get("bytes", 0.0)
        mfu = (flops / (seconds * pf)) if flops and pf else None
        bw = (bts / (seconds * pb)) if bts and pb else None
        st.last = {"seconds": seconds, "mfu": mfu, "bw_frac": bw,
                   "flops": flops, "bytes": bts}
        _RING.append({"program": program, "ts": time.perf_counter() * 1e6,
                      "dur": seconds * 1e6, "mfu": mfu, "bw_frac": bw,
                      "flops": flops, "bytes": bts})
    if _telemetry_on():
        reg = registry()
        reg.counter(DEVICE_PROGRAM_SECONDS,
                    "sampled device seconds per compiled program").inc(
            seconds, labels={"program": program})
        if mfu is not None:
            reg.gauge(DEVICE_PROGRAM_MFU,
                      "model-flops utilization of the last sampled "
                      "dispatch").set(mfu, labels={"program": program})
        if bw is not None:
            reg.gauge(DEVICE_PROGRAM_BW_FRAC,
                      "fraction of peak HBM bandwidth of the last "
                      "sampled dispatch").set(bw, labels={"program": program})


def program_stats(program: str) -> dict | None:
    """One program's accounting as plain data (None when never seen)."""
    with _lock:
        st = _programs.get(program)
        if st is None:
            return None
        return {"program": st.name, "signatures": len(st.costs),
                "dispatches": st.dispatches, "sampled": st.sampled,
                "device_seconds": st.device_seconds,
                "costs": dict(st.costs), "last": dict(st.last or {})}


def perf_report() -> dict:
    """The ``GET /debug/perf`` roofline table: one row per program with
    dispatch/sample counts, sampled device time, the estimated total
    (mean sampled dt x dispatches), its share of the estimated step, and
    the cost-derived MFU / HBM-bandwidth fractions."""
    pf, pb = peaks()
    rows = []
    with _lock:
        stats = list(_programs.values())
        for st in stats:
            mean_dt = (st.device_seconds / st.sampled) if st.sampled else None
            # estimated total device time: mean sampled dt x dispatches
            # (every dispatch counted while sampling; direct
            # record_sample feeds fall back to the sampled count)
            est = (mean_dt * max(st.dispatches, st.sampled)
                   if mean_dt is not None else None)
            # the roofline row uses the largest-cost signature (the
            # steady-state program; tiny warmup signatures would
            # understate flops)
            cost = max(st.costs.values(), key=lambda c: c["flops"],
                       default={"flops": 0.0, "bytes": 0.0})
            mfu = (cost["flops"] / (mean_dt * pf)
                   if mean_dt and cost["flops"] and pf else None)
            bw = (cost["bytes"] / (mean_dt * pb)
                  if mean_dt and cost["bytes"] and pb else None)
            rows.append({
                "program": st.name, "signatures": len(st.costs),
                "dispatches": st.dispatches, "sampled": st.sampled,
                "device_s": round(st.device_seconds, 6),
                "est_total_s": None if est is None else round(est, 6),
                "flops": cost["flops"], "bytes": cost["bytes"],
                "mfu": None if mfu is None else round(mfu, 6),
                "hbm_bw_frac": None if bw is None else round(bw, 6),
                "last": dict(st.last) if st.last else None,
            })
    total_est = sum(r["est_total_s"] or 0.0 for r in rows)
    for r in rows:
        r["share"] = (round((r["est_total_s"] or 0.0) / total_est, 4)
                      if total_est > 0 else 0.0)
    rows.sort(key=lambda r: -(r["est_total_s"] or 0.0))
    return {"sample_every": _SAMPLE[0], "peak_flops": pf,
            "peak_hbm_bw": pb, "programs": rows}


def chrome_events() -> list[dict]:
    """Sampled program intervals as chrome-trace 'X' events on the SAME
    perf_counter*1e6 clock base as ``trace.chrome_events`` and the
    journey tracks, ``"cat": "device"`` — one lane per program."""
    pid = os.getpid()
    with _lock:
        samples = list(_RING)
    out = []
    for s in samples:
        args = {k: s[k] for k in ("mfu", "bw_frac", "flops", "bytes")
                if s[k] is not None}
        out.append({"name": s["program"], "ph": "X",
                    "ts": s["ts"] - s["dur"], "dur": s["dur"], "pid": pid,
                    "tid": f"device:{s['program']}", "cat": "device",
                    "args": args})
    return out


def reset_programs():
    """Drop program stats + the device-lane ring (bench per-leg deltas,
    tests).  The HBM ledger is NOT touched — its rows mirror live
    allocations."""
    with _lock:
        _programs.clear()
        _RING.clear()


# -- the HBM ownership ledger --------------------------------------------------

class LedgerRow:
    """One owned long-lived device allocation.  ``nested`` rows are
    informational sub-accounts of bytes already counted by a top-level
    owner (e.g. prefix-cache retained rows inside the KV pool) — they
    never contribute to the ledger total."""

    __slots__ = ("owner", "detail", "nbytes", "nested", "_ledger",
                 "_released")

    def __init__(self, ledger, owner: str, nbytes: int, detail, nested):
        self.owner = str(owner)
        self.detail = detail
        self.nbytes = max(0, int(nbytes))
        self.nested = bool(nested)
        self._ledger = ledger
        self._released = False

    @property
    def released(self) -> bool:
        return self._released

    def update(self, nbytes: int):
        """Set this row's byte count (in-place resize)."""
        self._ledger._set(self, max(0, int(nbytes)))

    def add(self, delta: int):
        """Adjust this row's byte count by ``delta`` (clamped at 0)."""
        self._ledger._add(self, int(delta))

    def release(self):
        """Drop the row (the allocation was freed).  Idempotent."""
        self._ledger._release(self)


class HbmLedger:
    """Registry of named long-lived device allocations (see module doc).
    Always on; one lock-guarded dict update per register/update/release
    — never per-op, never per-dispatch."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rows: list[LedgerRow] = []
        self.registered_total = 0       # rows ever registered (chaos lane)
        self.released_total = 0

    def register(self, owner: str, nbytes: int = 0, detail=None,
                 nested: bool = False) -> LedgerRow:
        row = LedgerRow(self, owner, nbytes, detail, nested)
        with self._lock:
            self._rows.append(row)
            self.registered_total += 1
        self._export(row.owner, row.nested)
        return row

    # -- row plumbing --------------------------------------------------------
    def _set(self, row: LedgerRow, nbytes: int):
        with self._lock:
            if row._released:
                return
            row.nbytes = nbytes
        self._export(row.owner, row.nested)

    def _add(self, row: LedgerRow, delta: int):
        with self._lock:
            if row._released:
                return
            row.nbytes = max(0, row.nbytes + delta)
        self._export(row.owner, row.nested)

    def _release(self, row: LedgerRow):
        with self._lock:
            if row._released:
                return
            row._released = True
            self._rows.remove(row)
            self.released_total += 1
        self._export(row.owner, row.nested)

    def _export(self, owner: str, nested: bool):
        """Refresh the owner's gauge after any row change (telemetry
        on); nested owners export too — their gauge is the sub-account,
        not part of the total."""
        if not _telemetry_on():
            return
        with self._lock:
            total = sum(r.nbytes for r in self._rows if r.owner == owner)
        registry().gauge(
            HBM_BYTES,
            "device bytes held per declared owner (HBM ledger)").set(
            float(total), labels={"owner": owner})

    # -- reading -------------------------------------------------------------
    def owner_bytes(self) -> dict:
        """{owner: bytes} over top-level rows (the partition that sums
        to :meth:`total`)."""
        out: dict[str, int] = {}
        with self._lock:
            for r in self._rows:
                if not r.nested:
                    out[r.owner] = out.get(r.owner, 0) + r.nbytes
        return out

    def nested_bytes(self) -> dict:
        out: dict[str, int] = {}
        with self._lock:
            for r in self._rows:
                if r.nested:
                    out[r.owner] = out.get(r.owner, 0) + r.nbytes
        return out

    def total(self) -> int:
        with self._lock:
            return sum(r.nbytes for r in self._rows if not r.nested)

    def rows(self) -> list[dict]:
        with self._lock:
            return [{"owner": r.owner, "bytes": r.nbytes,
                     "nested": r.nested, "detail": r.detail}
                    for r in self._rows]

    def snapshot(self) -> dict:
        """JSON-safe ledger state (the watchdog bundle section and the
        OOM flight payload)."""
        return {"owners": self.owner_bytes(), "nested": self.nested_bytes(),
                "total": self.total(), "rows": self.rows(),
                "registered_total": self.registered_total,
                "released_total": self.released_total}


_LEDGER = HbmLedger()


def ledger() -> HbmLedger:
    """The process-wide HBM ownership ledger (always usable)."""
    return _LEDGER


def memory_report() -> dict:
    """The ``GET /debug/memory`` JSON: per-owner bytes, the tracked
    total, the backend allocator's view, and the unattributed remainder
    (``bytes_in_use`` the ledger cannot name — jit temporaries, XLA
    scratch, untracked arrays)."""
    led = ledger()
    owners = led.owner_bytes()
    total = sum(owners.values())
    # host-plane rows (the host prefix tier) live in the same ledger for
    # one consolidated budget, but must not count against the device
    # allocator when reconciling bytes_in_use
    device_total = total - sum(owners.get(o, 0) for o in HOST_OWNERS)
    backend = {}
    try:
        from ..device.tpu import memory_stats
        backend = {k: int(v) for k, v in memory_stats(0).items()
                   if isinstance(v, (int, float))}
    except Exception:  # noqa: BLE001 — no backend stats on this platform
        backend = {}
    out = {"owners": owners, "nested": led.nested_bytes(),
           "total_tracked": total, "backend": backend,
           "rows": led.rows()}
    if "bytes_in_use" in backend:
        out["unattributed"] = int(backend["bytes_in_use"]) - device_total
    return out


# -- OOM forensics -------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")
_oom_dumped: set = set()


def looks_like_oom(exc: BaseException) -> bool:
    text = f"{type(exc).__name__}: {exc}"
    return any(m in text for m in _OOM_MARKERS)


def note_exception(exc: BaseException, program: str = "") -> bool:
    """Allocation-failure forensics: when ``exc`` is a RESOURCE_EXHAUSTED
    (device OOM), record an ``oom`` flight event carrying the owner
    table and write ONE watchdog bundle per program (the bundle's
    ``hbm_ledger`` section holds the full ledger + the flight tail shows
    what led up to it).  Returns whether the exception matched."""
    if not looks_like_oom(exc):
        return False
    snap = ledger().snapshot()
    flight.record("oom", program or "device",
                  error=f"{type(exc).__name__}: {str(exc)[:512]}",
                  total_tracked=snap["total"],
                  owners=json.dumps(snap["owners"]))
    logger.warning(
        "paddle_tpu perfscope: %s",
        json.dumps({"event": "resource_exhausted",
                    "program": program or None,
                    "owners": snap["owners"],
                    "total_tracked": snap["total"],
                    "hint": "device OOM — the hbm_ledger section of the "
                            "crash bundle names who holds the bytes; "
                            "see GET /debug/memory on a live server"}))
    if program not in _oom_dumped:
        _oom_dumped.add(program)
        from . import watchdog
        watchdog.dump(f"resource_exhausted:{program or 'device'}")
    return True


def reset_oom_dumps():
    """Re-arm the one-bundle-per-program guard (tests)."""
    _oom_dumped.clear()


# the crash bundle carries the ledger: an OOM artifact names its owners
from . import watchdog as _watchdog  # noqa: E402

_watchdog.add_section("hbm_ledger", lambda: ledger().snapshot())
