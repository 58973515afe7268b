"""Retrace sentinel — catches shape-driven recompile storms.

``jax.jit`` silently retraces (and XLA recompiles) whenever a call arrives
with a new abstract signature.  On TPU that is the classic silent perf
killer: a stray python int in the batch path or a ragged final batch turns
every step into a multi-second compile while the throughput chart quietly
collapses.  The sentinel wraps the framework's jit entry points
(`distributed/spmd.py` train steps, `jit.to_static` caches), records every
distinct abstract signature and its compile wall-time, and logs ONE
structured warning per threshold crossing when the same entry point
recompiles more than N times.

The signature key is the tree of (shape, dtype) of the flattened call args
— exactly the part of jax's cache key a user can influence from the data
path.  Compile wall-time is measured around the first call with a new
signature, so it includes trace + lower + backend compile (the end-to-end
latency a training loop actually observes).
"""
from __future__ import annotations

import json
import logging
import os
import time

from . import flight, perfscope, registry
from . import trace as trace_mod

logger = logging.getLogger("paddle_tpu.observability")

JIT_COMPILE_TOTAL = "paddle_tpu_jit_compile_total"
JIT_COMPILE_SECONDS = "paddle_tpu_jit_compile_seconds"
JIT_RETRACE_WARNINGS = "paddle_tpu_jit_retrace_warnings_total"
DYNAMIC_CACHE_WARNINGS = "paddle_tpu_dynamic_cache_warnings_total"

# warn when one entry point compiles MORE than this many times
_DEFAULT_THRESHOLD = int(os.environ.get("PADDLE_TPU_RETRACE_WARN", "5"))
_threshold = [_DEFAULT_THRESHOLD]


def set_retrace_threshold(n: int):
    _threshold[0] = int(n)


def get_retrace_threshold() -> int:
    return _threshold[0]


_dtype_names: dict = {}      # dtype -> its name; `str(dtype)` costs ~1 us


def _abstract_signature(args, kwargs=None) -> tuple:
    """Runs on every dispatch of an instrumented entry point, over every
    leaf (a serving decode carries the model's weights: hundreds), so it
    keeps the tree definition as it is (hashed and compared structurally;
    `str()` of it only where a new signature is logged) and looks each
    dtype's name up."""
    import jax.tree_util as jtu
    leaves, treedef = jtu.tree_flatten((args, kwargs or {}))
    sig = []
    for lv in leaves:
        shape = getattr(lv, "shape", None)
        dtype = getattr(lv, "dtype", None)
        if shape is None and dtype is None:
            sig.append(repr(lv))  # static python leaf
        else:
            name = _dtype_names.get(dtype)
            if name is None:
                name = _dtype_names[dtype] = str(dtype)
            sig.append((tuple(shape) if shape is not None else None, name))
    return (treedef, tuple(sig))


def record_compile(name: str, key, seconds: float, n_compiles: int):
    """Book one (re)compile of jit entry point `name`; warn on storms."""
    reg = registry()
    reg.counter(JIT_COMPILE_TOTAL,
                "jit trace+compile events per entry point").inc(
        1.0, labels={"fn": name})
    reg.histogram(JIT_COMPILE_SECONDS,
                  "end-to-end compile wall-time (trace+lower+compile)"
                  ).observe(seconds, labels={"fn": name})
    if n_compiles > _threshold[0]:
        reg.counter(JIT_RETRACE_WARNINGS,
                    "retrace-storm warnings emitted").inc(
            1.0, labels={"fn": name})
        flight.record("retrace_storm", name, compiles=n_compiles,
                      threshold=_threshold[0])
        logger.warning(
            "paddle_tpu retrace sentinel: %s",
            json.dumps({"event": "retrace_storm", "fn": name,
                        "compiles": n_compiles,
                        "threshold": _threshold[0],
                        "last_signature": str(key)[:512],
                        "hint": "same step function keeps recompiling — "
                                "check for shape-polymorphic inputs "
                                "(ragged final batch, python scalars in "
                                "the data path)"}))


_STATIC_CACHE_HINT = (
    "a growing-concat KV cache changes the key length every decode step, "
    "so a jitted decode retraces per token; use the STATIC cache path — "
    "caches of (k_buf, v_buf, length) fixed-shape buffers, as built by "
    "paddle_tpu.serving.Engine or "
    "fleet.utils.HybridParallelInferenceHelper")
_dynamic_cache_warned: set = set()


def note_dynamic_cache_growth(site: str):
    """One-shot structured warning for the growing-concat KV-cache shape
    pattern: emitted the first time `site` is seen appending to a cache,
    into the flight recorder always and the metrics registry when telemetry
    is on.  The hint names the static-cache path to switch to."""
    if site in _dynamic_cache_warned:
        return
    _dynamic_cache_warned.add(site)
    flight.record("dynamic_kv_cache", site, hint=_STATIC_CACHE_HINT)
    logger.warning(
        "paddle_tpu retrace sentinel: %s",
        json.dumps({"event": "dynamic_kv_cache_growth", "site": site,
                    "hint": _STATIC_CACHE_HINT}))
    from ..core import op as op_mod
    if op_mod.TELEMETRY:
        registry().counter(
            DYNAMIC_CACHE_WARNINGS,
            "growing-concat KV-cache warnings emitted").inc(
            1.0, labels={"site": site})


def reset_dynamic_cache_warnings():
    """Re-arm the one-shot (tests)."""
    _dynamic_cache_warned.clear()


class InstrumentedJit:
    """Pass-through wrapper over a ``jax.jit``-ed callable that books
    compiles per distinct abstract signature.  Signature tracking is
    always on (one tree-flatten per *step* call — per-step, never per-op)
    so compile begin/end lands in the flight recorder even with telemetry
    off; the metrics registry is only touched when telemetry is on.
    Attribute access (``.lower``, ``.trace``...) delegates to the wrapped
    function so AOT paths keep working.

    Device perfscope (observability/perfscope.py) rides the same wrapper:
    each new signature registers its ``cost_analysis`` flops/bytes once
    at compile, and with ``PADDLE_TPU_PERFSCOPE_SAMPLE=N`` every Nth
    dispatch is bracketed with a ``block_until_ready`` to measure device
    seconds — the other ``N-1`` dispatches stay fully async, and the
    arguments are never touched, so the signature count (ONE compiled
    decode program per serving config) is unaffected."""

    def __init__(self, fn, name: str):
        self._fn = fn
        self._name = name
        self._signatures: set = set()

    def _invoke(self, args, kwargs):
        try:
            return self._fn(*args, **kwargs)
        except Exception as e:  # noqa: BLE001 — OOM forensics, then re-raise
            perfscope.note_exception(e, program=self._name)
            raise

    def _timed(self, key, args, kwargs):
        """One sampled dispatch: block until the result is device-ready
        and book the wall as device seconds for this program."""
        t0 = time.perf_counter()
        out = self._invoke(args, kwargs)
        # audited sync: runs on 1/N dispatches only (perfscope sampling);
        # the timer must observe device completion to measure anything
        perfscope.block_ready(out)  # tpu-lint: ok(trace-hygiene)
        perfscope.record_sample(self._name, key,
                                time.perf_counter() - t0)
        return out

    def __call__(self, *args, **kwargs):
        key = _abstract_signature(args, kwargs)
        sample = (perfscope.poll_sample(self._name)
                  if perfscope.sampling_active() else False)
        if key in self._signatures:
            if sample:
                return self._timed(key, args, kwargs)
            return self._invoke(args, kwargs)
        # new abstract signature → jax will trace + compile inside this
        # call; the span books compile begin/end (with the signature key)
        # into the flight record — a hang inside XLA leaves an open
        # "compile" span for the crash dump to show.  Compile dispatches
        # are never timed (the wall is trace+compile, not device time).
        n = len(self._signatures) + 1
        t0 = time.perf_counter()
        with trace_mod.span("compile", fn=self._name, n_compiles=n,
                            signature=str(key)[:256]):
            out = self._invoke(args, kwargs)
        dt = time.perf_counter() - t0
        self._signatures.add(key)
        from ..core import op as op_mod
        if op_mod.TELEMETRY:
            record_compile(self._name, key, dt, len(self._signatures))
        perfscope.register_program(self._name, key, self._fn, args, kwargs)
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


def instrument_jit(fn, name: str) -> InstrumentedJit:
    return InstrumentedJit(fn, name)


def compile_count(name: str | None = None) -> float:
    """Total recorded compiles (optionally for one entry point)."""
    c = registry().get(JIT_COMPILE_TOTAL)
    if c is None:
        return 0.0
    if name is None:
        return c.total()
    return c.value(labels={"fn": name})


def retrace_warning_count() -> float:
    c = registry().get(JIT_RETRACE_WARNINGS)
    return c.total() if c is not None else 0.0
