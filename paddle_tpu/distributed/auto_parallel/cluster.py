"""Cluster topology description for auto-parallel planning.

Reference: python/paddle/distributed/auto_parallel/cluster.py (Device /
Machine / Cluster built from a cluster JSON: device kinds, per-device
FLOPs and memory, link bandwidths) used by the cost model and Planner.

TPU-native: the two link classes are ICI (intra-slice, ~100s of GB/s per
link) and DCN (cross-slice host network, ~10s of GB/s) — the reference's
NVLink-vs-network split (ProcessGroupHeter inner/inter, SURVEY §5.8).
`Cluster.auto()` introspects the live jax backend; `from_dict`/`from_json`
load an explicit description for offline planning.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional

__all__ = ["Device", "Machine", "Cluster", "LinkSpec"]

# public spec-sheet numbers (bf16 peak per chip, HBM bytes + GB/s,
# ICI/DCN GB/s).  The cpu row is SYNTHETIC: it exists so roofline math
# (perfscope MFU / bandwidth fractions, planner estimates) is exercised
# and testable on the CPU tier-1 harness, not to describe any real host.
_KNOWN_CHIPS = {
    "tpu v4": dict(flops=275e12, memory=32e9, hbm_gbps=1228.0,
                   ici_gbps=300.0),
    "tpu v5 lite": dict(flops=197e12, memory=16e9, hbm_gbps=819.0,
                        ici_gbps=186.0),
    "tpu v5e": dict(flops=197e12, memory=16e9, hbm_gbps=819.0,
                    ici_gbps=186.0),
    "tpu v5p": dict(flops=459e12, memory=95e9, hbm_gbps=2765.0,
                    ici_gbps=450.0),
    "tpu v6": dict(flops=918e12, memory=32e9, hbm_gbps=1640.0,
                   ici_gbps=448.0),
    "cpu": dict(flops=1e12, memory=64e9, hbm_gbps=100.0, ici_gbps=25.0),
}


@dataclass
class Device:
    global_id: int
    local_id: int
    machine_id: int
    kind: str = "tpu v5e"
    flops: float = 197e12          # peak bf16 FLOP/s
    memory: float = 16e9           # HBM bytes
    hbm_bw: float = 819e9          # HBM bytes/s


@dataclass
class LinkSpec:
    bandwidth: float               # bytes/s each direction
    latency: float                 # seconds


@dataclass
class Machine:
    machine_id: int
    devices: List[Device] = field(default_factory=list)


class Cluster:
    """Devices grouped into machines (hosts / slices) + two link classes."""

    def __init__(self, machines: Optional[List[Machine]] = None,
                 ici: Optional[LinkSpec] = None,
                 dcn: Optional[LinkSpec] = None):
        self.machines = machines or []
        self.ici = ici or LinkSpec(bandwidth=186e9, latency=1e-6)
        self.dcn = dcn or LinkSpec(bandwidth=25e9, latency=10e-6)

    # -- constructors ---------------------------------------------------------
    @classmethod
    def auto(cls) -> "Cluster":
        """Introspect the live jax backend (cluster.py builds the same
        structure from its JSON; here the runtime already knows)."""
        import jax

        machines: Dict[int, Machine] = {}
        kind = None
        for d in jax.devices():
            kind_str = getattr(d, "device_kind", "cpu").lower()
            kind = kind_str if any(k in kind_str for k in _KNOWN_CHIPS) \
                else ("cpu" if d.platform == "cpu" else kind_str)
            spec = cls._chip_spec(kind_str if d.platform != "cpu" else "cpu")
            pid = int(getattr(d, "process_index", 0))
            m = machines.setdefault(pid, Machine(machine_id=pid))
            m.devices.append(Device(
                global_id=int(d.id), local_id=len(m.devices),
                machine_id=pid, kind=kind_str,
                flops=spec["flops"], memory=spec["memory"],
                hbm_bw=spec["hbm_gbps"] * 1e9))
        spec = cls._chip_spec(kind or "cpu")
        ici = LinkSpec(bandwidth=spec["ici_gbps"] * 1e9, latency=1e-6)
        return cls(list(machines.values()), ici=ici)

    @classmethod
    def from_dict(cls, desc: dict) -> "Cluster":
        machines = []
        for mi, m in enumerate(desc.get("machines", [])):
            mach = Machine(machine_id=mi)
            for li, dev in enumerate(m.get("devices", [])):
                spec = cls._chip_spec(dev.get("type", "tpu v5e"))
                mach.devices.append(Device(
                    global_id=dev.get("global_id",
                                      len(machines) * 8 + li),
                    local_id=li, machine_id=mi,
                    kind=dev.get("type", "tpu v5e"),
                    flops=float(dev.get("flops", spec["flops"])),
                    memory=float(dev.get("memory", spec["memory"])),
                    hbm_bw=float(dev.get("hbm_bandwidth",
                                         spec["hbm_gbps"] * 1e9))))
            machines.append(mach)
        links = desc.get("links", {})
        ici = LinkSpec(float(links.get("ici_bandwidth", 186e9)),
                       float(links.get("ici_latency", 1e-6)))
        dcn = LinkSpec(float(links.get("dcn_bandwidth", 25e9)),
                       float(links.get("dcn_latency", 10e-6)))
        return cls(machines, ici=ici, dcn=dcn)

    @classmethod
    def from_json(cls, path: str) -> "Cluster":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    @staticmethod
    def _chip_spec(kind: str) -> dict:
        kind = kind.lower()
        for key, spec in _KNOWN_CHIPS.items():
            if key in kind:
                return spec
        raise ValueError(
            f"no spec-sheet row for device kind {kind!r} (known: "
            f"{sorted(_KNOWN_CHIPS)}); a roofline against another chip's "
            f"peaks would be a wrong number, so add the row")

    # -- queries --------------------------------------------------------------
    @property
    def devices(self) -> List[Device]:
        return [d for m in self.machines for d in m.devices]

    def device_count(self) -> int:
        return len(self.devices)

    def devices_per_machine(self) -> int:
        return max((len(m.devices) for m in self.machines), default=0)

    def peak_flops(self) -> float:
        devs = self.devices
        return devs[0].flops if devs else 0.0

    def device_memory(self) -> float:
        devs = self.devices
        return devs[0].memory if devs else 0.0

    def peak_hbm_bw(self) -> float:
        """Per-chip HBM bandwidth in bytes/s (the roofline denominator
        perfscope divides by)."""
        devs = self.devices
        return devs[0].hbm_bw if devs else 0.0

    def link(self, group_size: int) -> LinkSpec:
        """Link class a collective over `group_size` adjacent devices rides:
        ICI while the group fits in one machine/slice, DCN beyond."""
        if group_size <= self.devices_per_machine():
            return self.ici
        return self.dcn

    def __repr__(self):
        return (f"Cluster({len(self.machines)} machines x "
                f"{self.devices_per_machine()} devices)")
