"""Parallel-plan search — the Planner/tuner.

Reference: python/paddle/distributed/auto_parallel/planner.py (MCMC
search over dist-attr assignments) + tuner/ (profile-based optimization
tuner) + mapper.py (rank->device placement).

TPU-native reshape: on a TPU mesh the search space is the factorization
of the chip count into [dp, pp, sharding-stage, mp], constrained by model
divisibility — small enough to enumerate exhaustively and score with the
analytic CostModel (no MCMC needed; the reference searches per-op
dist-attrs because GPUs lack GSPMD).  `Planner.search()` returns ranked
plans; `build_mesh` realizes the winner as a jax Mesh with mp innermost
so tensor-parallel collectives ride the tightest ICI links (mapper.py's
locality goal).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from .cluster import Cluster
from .cost_model import CostModel, PlanConfig, PlanCost, WorkloadSpec

__all__ = ["Planner", "build_mesh"]


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


class Planner:
    def __init__(self, workload: WorkloadSpec,
                 cluster: Optional[Cluster] = None,
                 mfu_ceiling: float = 0.5,
                 sharding_stages: Sequence[int] = (0, 2, 3)):
        self.workload = workload
        self.cluster = cluster or Cluster.auto()
        self.cost_model = CostModel(self.cluster, mfu_ceiling)
        self.sharding_stages = tuple(sharding_stages)

    def _valid(self, c: PlanConfig) -> bool:
        w = self.workload
        if c.world != self.cluster.device_count():
            return False
        if w.hidden % c.mp != 0:          # TP shards the hidden dim
            return False
        if w.layers % c.pp != 0:          # PP segments whole layers
            return False
        if w.global_batch % (c.dp * w.micro_batches) != 0 and c.pp > 1:
            return False
        if w.global_batch % c.dp != 0:
            return False
        return True

    def candidates(self) -> List[PlanConfig]:
        n = self.cluster.device_count()
        out = []
        for mp in _divisors(n):
            for pp in _divisors(n // mp):
                dp = n // (mp * pp)
                for stage in self.sharding_stages:
                    if stage >= 2 and dp == 1:
                        continue
                    c = PlanConfig(dp=dp, mp=mp, pp=pp,
                                   sharding_stage=stage)
                    if self._valid(c):
                        out.append(c)
        return out

    def search(self, top_k: int = 5) -> List[Tuple[PlanConfig, PlanCost]]:
        """Rank all feasible plans by predicted step time (infeasible ones
        sink to the bottom, still reported with their memory estimate)."""
        scored = [(c, self.cost_model.step_time(self.workload, c))
                  for c in self.candidates()]
        scored.sort(key=lambda cc: (not cc[1].feasible, cc[1].time))
        return scored[:top_k]

    def best(self) -> PlanConfig:
        ranked = self.search(top_k=1)
        if not ranked:
            raise RuntimeError(
                f"no valid plan for {self.cluster.device_count()} devices "
                f"with hidden={self.workload.hidden}, "
                f"layers={self.workload.layers}")
        plan, cost = ranked[0]
        if not cost.feasible:
            raise RuntimeError(
                f"every plan exceeds device memory; best was {plan} at "
                f"{cost.memory / 1e9:.1f}GB — shrink the model/batch or "
                f"add chips")
        return plan


def compile_and_rank(model_factory, batch_structs, plans=None,
                     cluster: Optional[Cluster] = None,
                     workload: Optional[WorkloadSpec] = None,
                     memory_limit_bytes: Optional[int] = None,
                     chip_flops: float = 197e12, chip_bw: float = 819e9):
    """Rank whole TRAINING plans by compiling each candidate's full train
    step and reading XLA's own cost/memory analysis — the reference
    OptimizationTuner's launch-and-profile loop (tuner/profiler.py)
    without occupying a cluster, built on the abstract AOT path
    (nothing is materialized; a 6.7B plan ranks on a laptop).

    model_factory(mesh, plan) -> (model, optimizer, loss_fn, num_labels):
    called per candidate AFTER the global mesh is installed, with the
    model built under `nn.abstract_init()` (the factory may build mp
    layers — the mesh axes dp/sharding/mp are live).  GSPMD plans only
    (pp == 1); pipeline plans are scheduled explicitly
    (distributed/pipeline.py) and verified by the dryrun instead.

    Returns [(PlanConfig, metrics dict)] ranked best-first; plans that
    fail to compile or exceed `memory_limit_bytes` sink with their error
    recorded.  ZeRO plans map onto the mesh as sharding_degree = dp
    (the reference's sharding-over-the-dp-group layout).
    """
    from .. import mesh as mesh_mod
    from ...nn.meta import abstract_init
    from ..spmd import make_train_step

    if plans is None:
        if workload is None:
            raise ValueError("pass either plans or a WorkloadSpec")
        plans = [c for c, _ in
                 Planner(workload, cluster=cluster).search(top_k=16)]
    plans = [p for p in plans if p.pp == 1]
    ranked = []
    prev_mesh = mesh_mod.get_global_mesh()
    try:
        for plan in plans:
            metrics: dict = {"plan": plan}
            try:
                if plan.sharding_stage > 0:
                    dims = [1, plan.dp, plan.mp]
                else:
                    dims = [plan.dp, 1, plan.mp]
                mesh_mod.set_global_mesh(None)
                mesh = mesh_mod.build_mesh(dims, ["dp", "sharding", "mp"])
                mesh_mod.set_global_mesh(mesh)
                with abstract_init():
                    model, opt, loss_fn, num_labels = model_factory(
                        mesh, plan)
                # pass the stage straight through: ShardedTrainStep derives
                # fsdp_axis itself for stage >= 3 (and drops min_fsdp_size
                # to 0 so small params shard exactly as a real run would)
                step = make_train_step(
                    model, opt, loss_fn=loss_fn, mesh=mesh,
                    num_labels=num_labels,
                    sharding_stage=plan.sharding_stage,
                    abstract=True)
                compiled = step.aot_compile(*batch_structs)
                mem = compiled.memory_analysis()
                peak = int(mem.argument_size_in_bytes +
                           mem.temp_size_in_bytes +
                           mem.output_size_in_bytes -
                           mem.alias_size_in_bytes)
                metrics["peak_bytes_per_chip"] = peak
                cost = compiled.cost_analysis()
                flops = float(cost.get("flops", 0.0))
                bytes_ = float(cost.get("bytes accessed", 0.0))
                metrics["flops"] = flops
                metrics["bytes"] = bytes_
                metrics["est_seconds"] = max(flops / chip_flops,
                                             bytes_ / chip_bw)
                if memory_limit_bytes is not None and \
                        peak > memory_limit_bytes:
                    metrics["over_memory"] = True
            except Exception as e:
                metrics["error"] = f"{type(e).__name__}: {e}"
            ranked.append((plan, metrics))
    finally:
        mesh_mod.set_global_mesh(prev_mesh)

    def key(item):
        _, m = item
        bad = "error" in m or m.get("over_memory", False)
        return (bad, m.get("est_seconds", float("inf")))

    ranked.sort(key=key)
    return ranked


def build_mesh(plan: PlanConfig, devices=None):
    """Realize a plan as a jax Mesh with axes [data, pipe, sharding(=fsdp
    over the dp axis), model] — model INNERMOST so TP collectives ride
    adjacent ICI links (mapper.py rank placement)."""
    import numpy as np

    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    n = plan.world
    if len(devices) < n:
        raise ValueError(f"plan needs {n} devices, have {len(devices)}")
    arr = np.array(devices[:n]).reshape(plan.dp, plan.pp, plan.mp)
    return Mesh(arr, axis_names=("data", "pipe", "model"))
