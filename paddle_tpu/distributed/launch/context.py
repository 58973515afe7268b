"""Launch context — parity with python/paddle/distributed/launch/context/
(args + env + node detection)."""
from __future__ import annotations

import argparse
import os
import socket


def parse_args(argv=None):
    """Argument surface of launch/main.py:26-35."""
    p = argparse.ArgumentParser(prog="paddle_tpu.distributed.launch")
    base = p.add_argument_group("Base Parameters")
    base.add_argument("--master", type=str, default=None,
                      help="master endpoint ip:port")
    base.add_argument("--rank", type=int, default=-1, help="node rank")
    base.add_argument("--log_level", type=str, default="INFO")
    base.add_argument("--nnodes", type=str, default="1",
                      help="nodes, or elastic range 'min:max'")
    base.add_argument("--nproc_per_node", type=int, default=None)
    base.add_argument("--log_dir", type=str, default="log")
    base.add_argument("--run_mode", type=str, default="collective")
    base.add_argument("--job_id", type=str, default="default")
    base.add_argument("--devices", "--gpus", type=str, default=None)
    base.add_argument("--ips", type=str, default=None)
    base.add_argument("training_script", type=str)
    base.add_argument("training_script_args", nargs="...")
    elastic = p.add_argument_group("Elastic Parameters")
    elastic.add_argument("--max_restart", type=int, default=3)
    elastic.add_argument("--elastic_level", type=int, default=-1)
    elastic.add_argument("--elastic_timeout", type=int, default=30)
    return p.parse_args(argv)


class Node:
    def __init__(self):
        self.ip = self._get_host_ip()
        self.free_ports = []

    @staticmethod
    def _get_host_ip():
        try:
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.connect(("10.255.255.255", 1))
            ip = s.getsockname()[0]
            s.close()
            return ip
        except OSError:
            return "127.0.0.1"

    @staticmethod
    def get_free_port():
        with socket.socket() as s:
            s.bind(("", 0))
            return s.getsockname()[1]


class Context:
    def __init__(self, argv=None):
        self.args = parse_args(argv)
        self.envs = dict(os.environ)
        self.node = Node()
        self.status = "ready"

    def nnodes_range(self):
        n = str(self.args.nnodes)
        if ":" in n:
            lo, hi = n.split(":")
            return int(lo), int(hi)
        return int(n), int(n)

    def is_elastic(self):
        lo, hi = self.nnodes_range()
        return hi > lo or self.args.elastic_level > 0

    def nproc_per_node(self):
        if self.args.nproc_per_node is not None:
            n = self.args.nproc_per_node
        elif self.args.devices:
            n = len(self.args.devices.split(","))
        else:
            n = int(self.envs.get("PADDLE_NPROC_PER_NODE") or 1)
        check_one_process_per_tpu_host(n)
        return n


def check_one_process_per_tpu_host(nproc: int):
    """A chip belongs to one process at a time, and every JAX process opens
    all the chips of its host — so on a TPU host the launch unit is ONE
    process per host driving all local chips (scale out with --nnodes).
    N > 1 local workers is the CPU-simulation shape (JAX_PLATFORMS=cpu);
    on TPU hardware the second worker would fail or hang in backend init,
    so fail here, fast, from a parent that never imports JAX."""
    import glob
    on_cpu = os.environ.get("JAX_PLATFORMS", "").lower().startswith("cpu")
    chips = glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*")
    if nproc > 1 and chips and not on_cpu:
        raise RuntimeError(
            f"{nproc} worker processes requested on a host with "
            f"{len(chips)} TPU chip(s): a chip belongs to one process and "
            f"each JAX process opens every local chip. Run one process per "
            f"host (it drives all local chips through the mesh) and scale "
            f"with --nnodes, or set JAX_PLATFORMS=cpu for a CPU simulation.")
