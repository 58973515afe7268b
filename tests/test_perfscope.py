"""Device perfscope (ISSUE 14): per-program device-time/MFU attribution,
the HBM ownership ledger, and OOM forensics.

Covers: cost registration per compiled signature (vs a hand-computed
``cost_analysis`` expectation), the sampling cadence (non-sampled
dispatches stay async — no ``block_until_ready``), CPU synthetic-peak
MFU/bandwidth math, ledger register/update/release + agreement with the
pre-existing ``kv_pool_bytes`` / ``weight_bytes`` exports, the
RESOURCE_EXHAUSTED forensics hook, the ``/debug/perf`` +
``/debug/memory`` gateway endpoints end to end, and the chrome device
lane.  The decode loop must stay at ONE compiled signature with
sampling enabled."""
import http.client
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import flight, perfscope, retrace, watchdog


@pytest.fixture(autouse=True)
def _clean_perfscope(tmp_path, monkeypatch):
    """Telemetry on (gauges live), sampling off, fresh program stats and
    flight ring, crash dumps into tmp, around every test here."""
    monkeypatch.setenv("PADDLE_TPU_DUMP_DIR", str(tmp_path / "dumps"))
    obs.enable(True)
    obs.registry().reset()
    perfscope.set_sample_every(0)
    perfscope.reset_programs()
    perfscope.reset_oom_dumps()
    perfscope.set_peaks(1e12, 100e9)   # the cpu synthetic spec row
    flight.clear()
    yield
    perfscope.set_sample_every(0)
    perfscope.reset_programs()
    perfscope.reset_oom_dumps()
    perfscope.reset_peaks()
    obs.disable()
    obs.registry().reset()
    flight.clear()


def _instrumented_matmul(name="perfscope.test"):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: (x @ x).sum())
    return retrace.instrument_jit(fn, name), jnp.ones((32, 32), jnp.float32)


# -- cost registration ---------------------------------------------------------

def test_cost_registered_per_signature_matches_cost_analysis():
    import jax
    import jax.numpy as jnp

    f, x = _instrumented_matmul("perfscope.cost")
    f(x)
    st = perfscope.program_stats("perfscope.cost")
    assert st is not None and st["signatures"] == 1
    expect = jax.jit(
        lambda x: (x @ x).sum()).lower(x).compile().cost_analysis()
    (cost,) = st["costs"].values()
    assert cost["flops"] == pytest.approx(
        float(expect.get("flops", 0.0)), rel=1e-6)
    assert cost["bytes"] == pytest.approx(
        float(expect.get("bytes accessed", 0.0)), rel=1e-6)
    # a second signature registers its own cost row
    f(jnp.ones((16, 16), jnp.float32))
    st = perfscope.program_stats("perfscope.cost")
    assert st["signatures"] == 2


def test_cost_registration_skipped_when_perfscope_dark():
    obs.disable()            # telemetry off + sampling off: no AOT work
    f, x = _instrumented_matmul("perfscope.dark")
    f(x)
    st = perfscope.program_stats("perfscope.dark")
    assert st is None or st["signatures"] == 0


# -- sampling cadence ----------------------------------------------------------

def test_sampling_cadence_and_async_nonsampled(monkeypatch):
    blocks = []
    real = perfscope.block_ready
    monkeypatch.setattr(perfscope, "block_ready",
                        lambda out: (blocks.append(1), real(out)))
    perfscope.set_sample_every(3)
    f, x = _instrumented_matmul("perfscope.cadence")
    for _ in range(10):          # dispatch 1 is the compile (never timed)
        f(x)
    st = perfscope.program_stats("perfscope.cadence")
    assert st["dispatches"] == 10
    # every 3rd dispatch blocks: 3, 6, 9 -> exactly 3 samples; the other
    # 7 dispatches never touched block_until_ready
    assert st["sampled"] == 3
    assert len(blocks) == 3
    assert st["device_seconds"] > 0


def test_sampling_off_never_blocks(monkeypatch):
    called = []
    monkeypatch.setattr(perfscope, "block_ready",
                        lambda out: called.append(1))
    f, x = _instrumented_matmul("perfscope.off")
    for _ in range(5):
        f(x)
    assert not called
    st = perfscope.program_stats("perfscope.off")
    assert st["sampled"] == 0 and st["device_seconds"] == 0.0


# -- MFU / bandwidth math ------------------------------------------------------

def test_synthetic_peak_mfu_math():
    perfscope.set_peaks(2e12, 50e9)
    perfscope.register_cost("perfscope.math", "sig",
                            {"flops": 1e9, "bytes accessed": 1e6})
    perfscope.record_sample("perfscope.math", "sig", 0.001)
    st = perfscope.program_stats("perfscope.math")
    # mfu = flops / (dt * peak_flops); bw = bytes / (dt * peak_bw)
    assert st["last"]["mfu"] == pytest.approx(1e9 / (0.001 * 2e12))
    assert st["last"]["bw_frac"] == pytest.approx(1e6 / (0.001 * 50e9))
    reg = obs.registry()
    g = reg.get(perfscope.DEVICE_PROGRAM_MFU)
    assert g.value(labels={"program": "perfscope.math"}) == \
        pytest.approx(0.5)
    c = reg.get(perfscope.DEVICE_PROGRAM_SECONDS)
    assert c.value(labels={"program": "perfscope.math"}) == \
        pytest.approx(0.001)
    rep = perfscope.perf_report()
    row = next(p for p in rep["programs"]
               if p["program"] == "perfscope.math")
    assert row["mfu"] == pytest.approx(0.5, rel=1e-3)
    assert row["hbm_bw_frac"] == pytest.approx(0.02, rel=1e-3)
    assert row["share"] == 1.0


def test_cluster_peaks_cpu_synthetic():
    from paddle_tpu.distributed.auto_parallel.cluster import Cluster
    c = Cluster.auto()
    assert c.peak_flops() > 0
    assert c.peak_hbm_bw() > 0
    perfscope.reset_peaks()
    pf, pb = perfscope.peaks()
    assert pf == c.peak_flops() and pb == c.peak_hbm_bw()


# -- HBM ledger ----------------------------------------------------------------

def test_ledger_register_update_release():
    led = perfscope.ledger()
    base_total = led.total()
    row = led.register("test_owner", 1000, detail="unit test")
    nested = led.register("test_sub", 400, nested=True)
    assert led.owner_bytes()["test_owner"] == 1000
    assert "test_sub" not in led.owner_bytes()
    assert led.nested_bytes()["test_sub"] == 400
    assert led.total() == base_total + 1000    # nested never double-counts
    row.update(2000)
    assert led.owner_bytes()["test_owner"] == 2000
    row.add(-500)
    assert led.owner_bytes()["test_owner"] == 1500
    g = obs.registry().get(perfscope.HBM_BYTES)
    assert g.value(labels={"owner": "test_owner"}) == 1500.0
    row.release()
    nested.release()
    row.release()                              # idempotent
    assert "test_owner" not in led.owner_bytes()
    assert led.total() == base_total
    assert g.value(labels={"owner": "test_owner"}) == 0.0


def test_memory_report_sums_and_rows():
    led = perfscope.ledger()
    r1 = led.register("mr_a", 10)
    r2 = led.register("mr_a", 5)
    r3 = led.register("mr_b", 7)
    try:
        mem = perfscope.memory_report()
        assert mem["owners"]["mr_a"] == 15 and mem["owners"]["mr_b"] == 7
        assert mem["total_tracked"] == sum(mem["owners"].values())
        assert isinstance(mem["backend"], dict)   # {} on CPU PJRT
        json.dumps(mem)                           # JSON-safe end to end
    finally:
        for r in (r1, r2, r3):
            r.release()


# -- engine agreement ----------------------------------------------------------

def _tiny_engine(**kw):
    from paddle_tpu.models import build_gpt, gpt_config
    from paddle_tpu.serving import Engine

    cfg = gpt_config("gpt-tiny", max_position_embeddings=128,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(0)
    model = build_gpt(cfg)
    model.eval()
    return Engine(model, max_slots=2, max_len=48, **kw), cfg


def test_engine_ledger_agrees_with_byte_exports():
    eng, _ = _tiny_engine(prefix_cache=True, prefix_block=4)
    try:
        eng.submit(np.arange(1, 7), max_new_tokens=3).result(timeout=300)
        st = eng.stats()
        mem = perfscope.memory_report()
        assert mem["owners"]["kv_pool"] == st["kv_pool_bytes"] == \
            eng.pool_bytes()
        assert mem["owners"]["weights"] == st["weight_bytes"] == \
            eng.weight_bytes()
        # a completed request retained its row: the nested prefix-cache
        # sub-account holds one slot row's bytes, bounded by the pool
        assert 0 < mem["nested"]["prefix_cache"] <= st["kv_pool_bytes"]
    finally:
        eng.shutdown()
    led = perfscope.ledger().owner_bytes()
    assert led.get("kv_pool", 0) == 0 and led.get("weights", 0) == 0


def test_engine_paged_ledger_and_shutdown_release():
    eng, _ = _tiny_engine(paged_kv=True, prefix_cache=True, prefix_block=4)
    try:
        eng.submit(np.arange(1, 9), max_new_tokens=3).result(timeout=300)
        mem = perfscope.memory_report()
        assert mem["owners"]["kv_pool"] == eng.pool_bytes()
        assert eng._page_alloc.bytes_per_page > 0
        # cached pages * page bytes is the nested sub-account
        assert mem["nested"]["prefix_cache"] == \
            eng._cached_pages * eng._page_alloc.bytes_per_page
    finally:
        eng.shutdown()
    assert perfscope.ledger().owner_bytes().get("kv_pool", 0) == 0


def test_decode_single_signature_with_sampling_on():
    perfscope.set_sample_every(1)
    eng, cfg = _tiny_engine()
    try:
        rs = np.random.RandomState(0)
        for i in range(3):
            eng.submit(rs.randint(1, cfg.vocab_size, 4 + i),
                       max_new_tokens=4).result(timeout=300)
        st = eng.stats()
        assert st["decode_compiles"] == 1, st
        dec = perfscope.program_stats("serving.decode")
        assert dec["sampled"] > 0 and dec["signatures"] == 1
    finally:
        eng.shutdown()


# -- OOM forensics -------------------------------------------------------------

def test_oom_hook_dumps_ledger(tmp_path):
    import jax

    row = perfscope.ledger().register("oom_owner", 12345)
    try:
        def boom(x):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory while trying to "
                "allocate 9999999999 bytes")

        f = retrace.instrument_jit(boom, "perfscope.oom")
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            f(jax.numpy.ones(2))
        evs = flight.events("oom")
        assert len(evs) == 1
        assert evs[0]["name"] == "perfscope.oom"
        owners = json.loads(evs[0]["attrs"]["owners"])
        assert owners["oom_owner"] == 12345
        path = watchdog.last_dump_path()
        assert path is not None and os.path.exists(path)
        with open(path) as fp:
            bundle = json.load(fp)
        assert bundle["reason"] == "resource_exhausted:perfscope.oom"
        assert bundle["hbm_ledger"]["owners"]["oom_owner"] == 12345
        assert bundle["flight_events"]          # the flight tail rides along
        # one bundle per program: a second OOM only records a flight event
        with pytest.raises(RuntimeError):
            f(jax.numpy.ones(2))
        assert len(flight.events("oom")) == 2
    finally:
        row.release()


def test_non_oom_exceptions_pass_through():
    import jax

    def boom(x):
        raise ValueError("plain failure")

    f = retrace.instrument_jit(boom, "perfscope.plain")
    with pytest.raises(ValueError):
        f(jax.numpy.ones(2))
    assert not flight.events("oom")
    assert not perfscope.looks_like_oom(ValueError("nope"))
    assert perfscope.looks_like_oom(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory"))


# -- gateway endpoints e2e -----------------------------------------------------

def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read()
    conn.close()
    return r.status, body


def test_debug_perf_and_memory_endpoints():
    from paddle_tpu.serving.gateway import TenantConfig, start_gateway

    perfscope.set_sample_every(1)
    eng, cfg = _tiny_engine()
    stack = start_gateway([eng], tenants=[TenantConfig("t")])
    try:
        conn = http.client.HTTPConnection("127.0.0.1", stack.port,
                                          timeout=300)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": [3, 1, 4, 1, 5],
                                 "max_tokens": 4}).encode(),
                     {"Content-Type": "application/json", "X-Tenant": "t"})
        assert conn.getresponse().status == 200
        conn.close()

        status, body = _get(stack.port, "/debug/perf")
        assert status == 200
        perf = json.loads(body)
        assert perf["sample_every"] == 1
        assert perf["peak_flops"] > 0 and perf["peak_hbm_bw"] > 0
        progs = {p["program"]: p for p in perf["programs"]}
        assert "serving.decode" in progs and "serving.prefill" in progs
        dec = progs["serving.decode"]
        assert dec["sampled"] >= 1 and dec["mfu"] is not None
        mean_dt = dec["device_s"] / dec["sampled"]
        assert dec["mfu"] == pytest.approx(
            dec["flops"] / (mean_dt * perf["peak_flops"]), rel=0.02)

        status, body = _get(stack.port, "/debug/memory")
        assert status == 200
        mem = json.loads(body)
        assert mem["owners"]["kv_pool"] == eng.pool_bytes()
        assert mem["owners"]["weights"] == eng.weight_bytes()
        assert mem["total_tracked"] == sum(mem["owners"].values())

        # the scrape path exports the perfscope + ledger series
        status, body = _get(stack.port, "/metrics")
        text = body.decode()
        assert perfscope.DEVICE_PROGRAM_SECONDS in text
        assert perfscope.HBM_BYTES in text
        st = eng.stats()
        assert st["decode_compiles"] == 1
    finally:
        stack.close()
        eng.shutdown()


# -- chrome device lane --------------------------------------------------------

def test_chrome_events_device_lane():
    perfscope.register_cost("perfscope.lane", "s",
                            {"flops": 2e6, "bytes accessed": 1e3})
    perfscope.record_sample("perfscope.lane", "s", 0.002)
    perfscope.record_sample("perfscope.lane", "s", 0.003)
    events = perfscope.chrome_events()
    assert len(events) == 2
    blob = json.loads(json.dumps({"traceEvents": events}))
    for e in blob["traceEvents"]:
        assert e["ph"] == "X" and e["cat"] == "device"
        assert e["tid"] == "device:perfscope.lane"
        assert e["dur"] > 0 and "mfu" in e["args"]
    # merges with the span ring's format (same clock base, same keys)
    from paddle_tpu.observability import trace as obs_trace
    span_events = obs_trace.chrome_events()
    merged = events + span_events
    assert all({"name", "ph", "ts", "pid", "tid", "cat"} <= set(e)
               for e in merged)


def test_profiler_chrome_export_includes_device_lane(tmp_path):
    from paddle_tpu import profiler as prof_mod

    perfscope.register_cost("perfscope.prof", "s", {"flops": 1.0})
    perfscope.record_sample("perfscope.prof", "s", 0.001)
    p = prof_mod.Profiler()
    p.start()
    p.stop()
    out = tmp_path / "trace.json"
    p.export(str(out))
    blob = json.loads(out.read_text())
    cats = {e.get("cat") for e in blob["traceEvents"]}
    assert "device" in cats


# -- perf_report tool ----------------------------------------------------------

def test_perf_report_tool_formatting():
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.perf_report import format_memory, format_perf

    perfscope.register_cost("perfscope.tool", "s",
                            {"flops": 1e9, "bytes accessed": 1e6})
    perfscope.record_sample("perfscope.tool", "s", 0.001)
    lines = format_perf(perfscope.perf_report())
    assert any("perfscope.tool" in ln for ln in lines)
    row = perfscope.ledger().register("tool_owner", 4096)
    try:
        lines = format_memory(perfscope.memory_report())
        assert any("tool_owner" in ln for ln in lines)
        assert any("4.0 KiB" in ln for ln in lines)
    finally:
        row.release()


# -- prefetch owner ------------------------------------------------------------

def test_prefetch_ledger_owner():
    from paddle_tpu.io.prefetch import DevicePrefetcher

    batches = [np.ones((4, 8), np.float32) for _ in range(6)]
    pf = DevicePrefetcher(batches, depth=2, name="ledger-test")
    led = perfscope.ledger()
    it = iter(pf)
    seen_positive = False
    n = 0
    for _ in it:
        n += 1
        if led.owner_bytes().get("prefetch", 0) > 0:
            seen_positive = True
    assert n == 6
    assert seen_positive, "buffered batches never declared prefetch bytes"
    pf.close()
    assert led.owner_bytes().get("prefetch", 0) == 0
