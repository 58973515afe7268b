"""Op-dispatch telemetry — the counters behind ``@defop``.

Every framework op funnels through ``core.op.apply_op``; when telemetry is
on, that hub calls :func:`record` with the op name and host wall-time.  The
eager-vs-traced split rides on ``jax.core.trace_ctx.is_top_level()``:
inside any jit/vjp trace the op executes as graph construction (its host
time is trace overhead, not kernel time), outside it is a real eager
dispatch — the same distinction the reference draws between dygraph kernel
launches and static program building.
"""
from __future__ import annotations

import jax

from . import metrics as metrics_mod
from . import registry

# metric names (see docs/observability.md for the naming scheme)
OP_DISPATCH_TOTAL = "paddle_tpu_op_dispatch_total"
OP_HOST_SECONDS = "paddle_tpu_op_host_seconds_total"


def record(name: str, seconds: float):
    """One op dispatch: count it, split by mode, accumulate host time."""
    mode = "eager" if jax.core.trace_ctx.is_top_level() else "traced"
    reg = registry()
    reg.counter(OP_DISPATCH_TOTAL,
                "framework op dispatches through apply_op").inc(
        1.0, labels={"op": name, "mode": mode})
    reg.counter(OP_HOST_SECONDS,
                "cumulative host wall-time inside apply_op").inc(
        seconds, labels={"op": name})


def dispatch_counts(mode: str | None = None) -> dict[str, float]:
    """{op name: dispatch count}, optionally filtered by mode."""
    c = registry().get(OP_DISPATCH_TOTAL)
    out: dict[str, float] = {}
    if not isinstance(c, metrics_mod.Counter):
        return out
    for labels, v in c.series():
        if mode is not None and labels.get("mode") != mode:
            continue
        out[labels.get("op", "?")] = out.get(labels.get("op", "?"), 0.0) + v
    return out
