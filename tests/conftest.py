"""Test config: run on CPU-XLA with 8 virtual devices so mesh/sharding tests
work without TPU hardware (SURVEY §4: the reference's fake-device harness,
fluid/tests/custom_runtime, is mirrored by CPU-simulated meshes)."""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# The suite is a CPU suite (the 8 virtual devices above exist only there):
# pin the platform so a bare `pytest` on a TPU host cannot wander onto the
# chip.  `JAX_PLATFORMS=cpu` in the environment does the same.
jax.config.update("jax_platforms", "cpu")
# tests compare against float64 numpy.  On this jaxlib the CPU's default
# f32 matmul is already exact-f32 (re-checked, PR 21), so this pin only
# matters to code that reads the config — keep it so nothing depends on
# the backend's default
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _isolate_global_state():
    """Cross-file isolation: restore every known piece of module-global
    state after each test so the suite is order-independent (a round-2
    full-suite run once failed a gradcheck that passed alone — global
    leakage class: amp autocast, global mesh, HCG, flash interpret mode,
    channels_last, collective groups)."""
    yield
    import jax.numpy as jnp

    import paddle_tpu.distributed as dist
    from paddle_tpu.amp.auto_cast import amp_state
    from paddle_tpu.distributed import fleet
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.nn import layout

    st = amp_state()
    st.enabled, st.dtype, st.level = False, jnp.bfloat16, "O1"
    st.custom_white, st.custom_black = set(), set()
    # framework invariants a test may have toggled
    if not jax.config.read("jax_enable_x64"):
        jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_default_matmul_precision", "highest")
    if dist.get_global_mesh() is not None:
        dist.set_global_mesh(None)
    dist.set_hybrid_communicate_group(None)
    fleet._hcg = None
    fleet._is_initialized = False
    fa._INTERPRET = False
    pa.use_interpret_mode(None)
    if hasattr(layout._state, "on"):
        del layout._state.on
    layout.set_global_channels_last(False)
    from paddle_tpu.kernels import layer_norm as _ln
    from paddle_tpu.kernels import ln_matmul as _lnmm
    _ln._MODE = "off"
    _lnmm._ENABLED = False
    from paddle_tpu import observability as _obs
    if _obs.enabled():
        _obs.disable()
        _obs.registry().reset()


def pytest_collection_modifyitems(config, items):
    """Two-tier suite (round-3 verdict Weak #6: the monolithic suite had
    outgrown any review budget).  tests/slow_tests.txt lists the tests whose
    measured call time on the 8-device CPU mesh is >=2s; they get
    @pytest.mark.slow so `pytest -m "not slow"` is a fast smoke gate.
    Regenerate the list with tools/retier_tests.py."""
    import pathlib

    listing = pathlib.Path(__file__).with_name("slow_tests.txt")
    if not listing.exists():
        return
    slow_bases = {line.strip() for line in listing.read_text().splitlines()
                  if line.strip() and not line.startswith("#")}
    for item in items:
        base = item.nodeid.split("[")[0]
        if base in slow_bases:
            item.add_marker(pytest.mark.slow)
