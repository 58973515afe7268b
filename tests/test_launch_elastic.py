"""launch CLI / TCPStore / elastic manager tests (reference:
test_fleet_elastic_manager.py MockEtcdClient pattern, launch tests via
localhost multi-process, SURVEY §4)."""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.distributed.fleet.elastic import (ElasticLevel,
                                                  ElasticManager,
                                                  ElasticStatus)
from paddle_tpu.distributed.fleet.elastic.manager import _parse_np
from jax import shard_map


# -- TCPStore (native C++) ---------------------------------------------------

def test_tcp_store_cross_process():
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=2)
    code = f"""
import sys
sys.path.insert(0, {os.getcwd()!r})
from paddle_tpu.distributed.store import TCPStore
s = TCPStore("127.0.0.1", {master.port}, is_master=False, world_size=2)
s.set("from_child", b"hi")
assert s.get("ready") == b"go"
print("child ok")
"""
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert master.get("from_child") == b"hi"
    master.set("ready", b"go")
    out, _ = proc.communicate(timeout=60)
    assert proc.returncode == 0, out.decode()
    assert b"child ok" in out


def test_tcp_store_add_and_barrier_threads():
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=4)
    clients = [TCPStore("127.0.0.1", master.port) for _ in range(3)]
    results = []

    def work(s):
        results.append(s.add("ctr", 1))
        s.barrier("b", 4, timeout=10)

    ts = [threading.Thread(target=work, args=(c,)) for c in clients]
    for t in ts:
        t.start()
    results.append(master.add("ctr", 1))
    master.barrier("b", 4, timeout=10)
    for t in ts:
        t.join()
    assert sorted(results) == [1, 2, 3, 4]


def test_tcp_store_large_value():
    master = TCPStore("127.0.0.1", 0, is_master=True)
    blob = os.urandom(1 << 20)  # forces the grow-buffer GET path
    master.set("big", blob)
    assert master.get("big") == blob


# -- launch CLI --------------------------------------------------------------

def test_launch_env_contract(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(
        "import os, json\n"
        "rank = os.environ['PADDLE_TRAINER_ID']\n"
        "info = {k: os.environ[k] for k in ("
        "'PADDLE_TRAINER_ID', 'PADDLE_TRAINERS_NUM', 'PADDLE_LOCAL_RANK',"
        "'PADDLE_TRAINER_ENDPOINTS', 'PADDLE_CURRENT_ENDPOINT')}\n"
        "open(os.path.join(os.environ['OUT_DIR'], f'r{rank}.json'), 'w')"
        ".write(json.dumps(info))\n")
    env = dict(os.environ)
    env["OUT_DIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(tmp_path / "log"),
         str(script)],
        env=env, cwd="/root/repo", capture_output=True, timeout=120)
    assert proc.returncode == 0, proc.stderr.decode()
    import json
    infos = [json.loads((tmp_path / f"r{r}.json").read_text())
             for r in range(2)]
    assert infos[0]["PADDLE_TRAINERS_NUM"] == "2"
    eps = infos[0]["PADDLE_TRAINER_ENDPOINTS"].split(",")
    assert len(eps) == 2
    assert infos[1]["PADDLE_CURRENT_ENDPOINT"] == eps[1]
    assert {i["PADDLE_TRAINER_ID"] for i in infos} == {"0", "1"}
    # per-rank logs exist
    assert (tmp_path / "log" / "workerlog.0").exists()


def test_launch_nonzero_exit(tmp_path):
    script = tmp_path / "fail.py"
    script.write_text("import sys; sys.exit(3)\n")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--log_dir", str(tmp_path / "log"),
         str(script)],
        cwd="/root/repo", capture_output=True, timeout=120)
    assert proc.returncode == 3


# -- elastic manager (mock etcd, reference test harness pattern) -------------

class MockLease:
    def __init__(self):
        self.refreshed = 0

    def refresh(self):
        self.refreshed += 1


class MockEtcdClient:
    """Mirrors unittests/test_fleet_elastic_manager.py:76 MockEtcdClient."""

    def __init__(self):
        self.kv = {}

    def put(self, key, value, lease=None):
        self.kv[key] = value

    def get(self, key):
        return self.kv.get(key), None

    def delete(self, key):
        self.kv.pop(key, None)

    def get_prefix(self, prefix):
        return [(v, k) for k, v in self.kv.items() if k.startswith(prefix)]

    def lease(self, ttl):
        return MockLease()


def test_parse_np():
    assert _parse_np("4") == (4, 4)
    assert _parse_np("2:8") == (2, 8)
    with pytest.raises(ValueError):
        _parse_np("0")
    with pytest.raises(ValueError):
        _parse_np("5:2")


def test_elastic_registration_and_match():
    etcd = MockEtcdClient()
    m = ElasticManager(etcd_client=etcd, np="2", host="10.0.0.1",
                       job_id="job1")
    assert m.enable
    # self registered
    assert m.cur_hosts() == ["10.0.0.1"]
    assert not m._match()  # only 1 of 2
    etcd.put("/paddle/job1/nodes/10.0.0.2", b"10.0.0.2")
    assert m._match()
    m.exit()
    assert "/paddle/job1/nodes/10.0.0.1" not in etcd.kv


def test_elastic_scale_out_and_in():
    etcd = MockEtcdClient()
    m = ElasticManager(etcd_client=etcd, np="2:4", host="h1", job_id="j2")
    m.elastic_level = ElasticLevel.ELASTIC
    m.np = 2
    status, hosts = m.adjust(["h1", "h2", "h3"])  # grow
    assert status == ElasticStatus.RESTART
    assert m.np == 3 and hosts == ["h1", "h2", "h3"]

    status, hosts = m.adjust(["h1", "h2"])  # shrink within range
    assert status == ElasticStatus.RESTART
    assert m.np == 2

    status, hosts = m.adjust(["h1"])  # below min → hold
    assert status == ElasticStatus.HOLD
    assert m.np == 2

    status, hosts = m.adjust(["h1", "h2"])  # steady
    assert status == ElasticStatus.COMPLETED
    m.exit()


def test_elastic_scale_out_clamps_to_max():
    etcd = MockEtcdClient()
    m = ElasticManager(etcd_client=etcd, np="2:4", host="h1", job_id="j4")
    m.elastic_level = ElasticLevel.ELASTIC
    m.np = 3
    hosts = [f"h{i}" for i in range(6)]
    status, adopted = m.adjust(hosts)
    assert status == ElasticStatus.RESTART
    assert m.np == 4 and len(adopted) == 4  # clamped to np_max
    # steady afterwards even though 6 hosts are registered
    status, _ = m.adjust(hosts)
    assert status == ElasticStatus.COMPLETED
    m.exit()


def test_elastic_fault_tolerance_holds_on_loss():
    etcd = MockEtcdClient()
    m = ElasticManager(etcd_client=etcd, np="3", host="h1", job_id="j3")
    assert m.elastic_level == ElasticLevel.FAULT_TOLERANCE
    status, _ = m.adjust(["h1", "h2"])
    assert status == ElasticStatus.HOLD
    status, _ = m.adjust(["h1", "h2", "h3"])
    assert status == ElasticStatus.COMPLETED
    m.exit()


# -- spawn + stream collectives ---------------------------------------------

def _spawn_target(tag_dir):
    import os
    rank = os.environ["PADDLE_TRAINER_ID"]
    world = os.environ["PADDLE_TRAINERS_NUM"]
    with open(os.path.join(tag_dir, f"rank{rank}.txt"), "w") as f:
        f.write(world)


def test_spawn_runs_workers(tmp_path):
    import paddle_tpu.distributed as dist
    dist.spawn(_spawn_target, args=(str(tmp_path),), nprocs=2)
    for r in range(2):
        assert (tmp_path / f"rank{r}.txt").read_text() == "2"


def test_spawn_propagates_failure(tmp_path):
    import paddle_tpu.distributed as dist

    with pytest.raises(RuntimeError, match="failed"):
        dist.spawn(_spawn_fail, nprocs=2)


def _spawn_fail():
    raise ValueError("worker boom")


def test_stream_collectives_alias():
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.communication import stream

    mesh = dist.build_mesh([8], ["dp"])
    g = dist.new_group(list(range(8)), axis_name="dp")
    data = jnp.arange(8.0).reshape(8, 1)

    def f(x):
        return stream.all_reduce(paddle.to_tensor(x), group=g,
                                 use_calc_stream=True)._value

    out = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))(data)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 28.0))
    dist.collective.destroy_process_group()
    dist.set_global_mesh(None)
