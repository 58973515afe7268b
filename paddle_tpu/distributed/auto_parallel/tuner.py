"""Profile-based parallel-strategy tuner — the TPU-native analog of
python/paddle/distributed/auto_parallel/tuner/ (OptimizationTuner,
profiler.py: launch candidate configs, measure, pick the winner).

The reference tunes by RUNNING candidate distributed programs.  Under XLA
the same information is available without occupying a cluster: lower +
compile each candidate sharding and read the compiled artifact's cost
model (FLOPs, bytes accessed, peak memory) — `measure="compile"`.  When
devices ARE available (CPU sim or a real slice), `measure="run"` times
one real execution per candidate, which also captures collective costs
the static model underweights.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass
class Candidate:
    """One parallelization candidate: a mesh plus input PartitionSpecs."""
    name: str
    mesh: Mesh
    in_specs: Sequence[P]
    metrics: dict = field(default_factory=dict)


class Tuner:
    """Pick the best candidate layout for `fn` (OptimizationTuner parity).

    fn: a jittable callable over arrays; candidates supply per-arg specs.
    measure:
      * "compile" — rank by the compiled cost model (no execution):
        peak device memory first (a config that does not fit loses), then
        estimated wall proxy = max(flops/chip_flops, bytes/chip_bw).
      * "run"     — execute each candidate once after warmup and rank by
        measured wall time.
    """

    def __init__(self, fn: Callable, example_args: Sequence[Any],
                 measure: str = "compile",
                 chip_flops: float = 197e12, chip_bw: float = 819e9):
        if measure not in ("compile", "run"):
            raise ValueError(f"measure must be compile|run, got {measure!r}")
        self.fn = fn
        self.example_args = list(example_args)
        self.measure = measure
        self.chip_flops = chip_flops
        self.chip_bw = chip_bw

    def _place(self, cand: Candidate):
        from .. import mesh as mesh_mod
        if len(cand.in_specs) != len(self.example_args):
            raise ValueError(
                f"candidate {cand.name!r} supplies {len(cand.in_specs)} "
                f"specs for {len(self.example_args)} arguments")
        out = []
        for v, spec in zip(self.example_args, cand.in_specs):
            out.append(mesh_mod.put_global(
                np.asarray(v), NamedSharding(cand.mesh, spec or P())))
        return out

    def _evaluate(self, cand: Candidate) -> dict:
        args = self._place(cand)
        jitted = jax.jit(self.fn)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        m = {}
        mem = compiled.memory_analysis()
        if mem is not None:
            m["peak_bytes"] = int(mem.temp_size_in_bytes +
                                  mem.argument_size_in_bytes +
                                  mem.output_size_in_bytes)
        cost = compiled.cost_analysis()
        if cost:
            flops = float(cost.get("flops", 0.0))
            bytes_ = float(cost.get("bytes accessed", 0.0))
            m["flops"] = flops
            m["bytes"] = bytes_
            n_dev = cand.mesh.devices.size
            m["est_seconds"] = max(flops / (self.chip_flops * n_dev),
                                   bytes_ / (self.chip_bw * n_dev))
        if self.measure == "run":
            out = compiled(*args)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            out = compiled(*args)
            jax.block_until_ready(out)
            m["wall_seconds"] = time.perf_counter() - t0
        return m

    def tune(self, candidates: Sequence[Candidate],
             memory_limit_bytes: int | None = None) -> Candidate:
        """Evaluate all candidates, attach metrics, return the winner."""
        scored = []
        for cand in candidates:
            if len(cand.in_specs) != len(self.example_args):
                # caller error, not a disqualified candidate
                raise ValueError(
                    f"candidate {cand.name!r} supplies "
                    f"{len(cand.in_specs)} specs for "
                    f"{len(self.example_args)} arguments")
            try:
                cand.metrics = self._evaluate(cand)
            except Exception as e:  # candidate doesn't compile: disqualify
                cand.metrics = {"error": f"{type(e).__name__}: {e}"}
                continue
            if memory_limit_bytes is not None and \
                    cand.metrics.get("peak_bytes", 0) > memory_limit_bytes:
                cand.metrics["over_memory"] = True
                continue
            key = cand.metrics.get(
                "wall_seconds",
                cand.metrics.get("est_seconds", float("inf")))
            scored.append((key, len(scored), cand))
        if not scored:
            raise RuntimeError(
                "no candidate compiled within limits: " +
                "; ".join(f"{c.name}: {c.metrics}" for c in candidates))
        scored.sort(key=lambda t: t[:2])
        return scored[0][2]
