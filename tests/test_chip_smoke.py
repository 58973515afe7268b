"""chip_smoke.py keeps its contract where there is no chip.

* the explicit CPU rehearsal runs every phase and says what it is;
* without that argument, finding no TPU is a non-zero exit that names the
  missing chip and prints no result — never a quiet CPU run;
* a mesh that wants more devices than the backend has is an error, not a
  reason to reach for another platform's devices.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_cpu_rehearsal_runs_every_phase():
    p = _run("--cpu-rehearsal")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    for phase in ("flash", "train", "mesh", "serve", "kernel"):
        assert any(f"PASS phase={phase} " in ln for ln in lines), phase
    assert all("rehearsal" in ln for ln in lines)     # every line says so
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"


def test_no_chip_is_a_named_failure_not_a_cpu_run():
    p = _run()
    assert p.returncode != 0
    assert "FAIL phase=device" in p.stdout and "no TPU" in p.stdout
    assert "PASS" not in p.stdout
    assert not p.stdout.strip().splitlines()[-1].startswith("{")


def test_build_mesh_with_too_few_devices_raises():
    import jax

    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.mesh import build_mesh

    have = len(jax.devices())
    with pytest.raises(ValueError, match=f"needs {2 * have} devices, found "
                                         f"{have}"):
        build_mesh([2, have], ["dp", "mp"])
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": have}
    with pytest.raises(RuntimeError, match=f"found {have}"):
        fleet.init(is_collective=True, strategy=strategy)


def test_launch_refuses_many_workers_on_a_tpu_host(monkeypatch):
    """One process per host drives all local chips; K > 1 local workers is
    the CPU-simulation shape and is refused where TPU device nodes exist —
    decided without importing JAX in the launcher."""
    import glob

    from paddle_tpu.distributed.launch import context

    context.check_one_process_per_tpu_host(4)     # no chips here: fine
    monkeypatch.setattr(
        glob, "glob", lambda pat: ["/dev/vfio/0"] if "vfio" in pat else [])
    context.check_one_process_per_tpu_host(1)     # one process: fine
    context.check_one_process_per_tpu_host(4)     # JAX_PLATFORMS=cpu: fine
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(RuntimeError, match="one process per host"):
        context.check_one_process_per_tpu_host(4)
