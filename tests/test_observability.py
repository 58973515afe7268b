"""paddle_tpu.observability — registry semantics, op-dispatch telemetry,
the retrace sentinel, step metrics, and the export paths (prometheus/JSON
dump, chrome-trace merge); plus the always-on timeline layer: tracing
spans, the flight recorder, and crash/hang diagnostics.  The metrics
subsystem must be free when disabled: the apply_op hook is a single
boolean check and records nothing."""
import json
import logging
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import (Counter, Gauge, Histogram,
                                      MetricsRegistry, dispatch, flight,
                                      retrace, steps, trace, watchdog)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Telemetry off + empty registry/rings around every test here."""
    obs.disable()
    obs.registry().reset()
    retrace.set_retrace_threshold(retrace._DEFAULT_THRESHOLD)
    flight.clear()
    trace.clear()
    yield
    obs.disable()
    obs.registry().reset()
    retrace.set_retrace_threshold(retrace._DEFAULT_THRESHOLD)
    flight.clear()
    trace.clear()
    watchdog.disarm()


# -- registry semantics ------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    c.inc(1, labels={"op": "add"})
    c.inc(2, labels={"op": "mul"})
    assert c.value(labels={"op": "add"}) == 1
    assert c.total() == 6.5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("g")
    g.set(7, labels={"dev": "0"})
    g.inc(3, labels={"dev": "0"})
    g.dec(5, labels={"dev": "0"})
    assert g.value(labels={"dev": "0"}) == 5

    h = reg.histogram("h_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(55.55)
    # cumulative (prometheus convention): 1 obs <= 0.1, 2 <= 1.0, 3 <= 10
    assert snap["buckets"] == {"0.1": 1, "1.0": 2, "10.0": 3}

    # re-registration returns the same family; kind mismatch raises
    assert reg.counter("c_total") is c
    with pytest.raises(ValueError):
        reg.gauge("c_total")


def test_label_order_is_canonical():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc(labels={"a": 1, "b": 2})
    c.inc(labels={"b": 2, "a": 1})  # same series, different dict order
    assert c.value(labels={"a": 1, "b": 2}) == 2


def test_dump_and_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(4, labels={"code": "200"})
    reg.gauge("mem_bytes").set(1024)
    reg.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.5)

    dumped = json.loads(json.dumps(reg.dump()))  # JSON round-trip
    assert dumped["counters"]["req_total"] == [
        {"labels": {"code": "200"}, "value": 4.0}]
    assert dumped["gauges"]["mem_bytes"][0]["value"] == 1024.0
    hist = dumped["histograms"]["lat_seconds"][0]
    assert hist["count"] == 1 and hist["buckets"]["1.0"] == 1

    text = reg.to_prometheus_text()
    assert '# TYPE req_total counter' in text
    assert 'req_total{code="200"} 4.0' in text
    assert '# TYPE lat_seconds histogram' in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert 'lat_seconds_count 1' in text
    # cumulative bucket counts: le=1.0 includes the le=0.1 bucket
    assert 'lat_seconds_bucket{le="0.1"} 0' in text
    assert 'lat_seconds_bucket{le="1.0"} 1' in text


# -- op-dispatch telemetry ---------------------------------------------------

def test_op_dispatch_counters_after_eager_ops():
    obs.enable(True)
    a = paddle.to_tensor(np.ones((4, 4), np.float32))
    b = a + a
    c = paddle.matmul(a, b)
    c.sum()
    counts = dispatch.dispatch_counts(mode="eager")
    assert counts.get("add", 0) >= 1
    assert counts.get("matmul", 0) >= 1
    assert counts.get("sum", 0) >= 1
    host = obs.registry().get(dispatch.OP_HOST_SECONDS)
    assert host.value(labels={"op": "matmul"}) > 0


def test_disabled_hook_is_noop(monkeypatch):
    """With telemetry off, apply_op must not even reach the recording
    path — the fast-path boolean short-circuits before any import."""
    def boom(*a, **k):
        raise AssertionError("dispatch.record called with telemetry off")

    monkeypatch.setattr(dispatch, "record", boom)
    a = paddle.to_tensor(np.ones((2, 2), np.float32))
    (a * a).sum()  # would raise through the finally if the hook ran
    assert obs.registry().dump()["counters"] == {}


def test_enable_env_bootstrap(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", "1")
    obs._bootstrap_from_env()
    assert obs.enabled()
    from paddle_tpu.core import op as op_mod
    assert op_mod.TELEMETRY is True


def test_flags_wire_telemetry():
    paddle.set_flags({"FLAGS_telemetry": True})
    assert obs.enabled()
    paddle.set_flags({"FLAGS_telemetry": False})
    assert not obs.enabled()
    assert paddle.get_flags("FLAGS_telemetry") == {"FLAGS_telemetry": False}


# -- retrace sentinel --------------------------------------------------------

def test_retrace_sentinel_fires_on_shape_polymorphic_jit(caplog):
    import jax
    import jax.numpy as jnp

    obs.enable(True)
    retrace.set_retrace_threshold(2)
    f = obs.instrument_jit(jax.jit(lambda x: x * 2.0), name="poly_fn")
    with caplog.at_level(logging.WARNING, "paddle_tpu.observability"):
        for n in range(1, 5):  # 4 distinct shapes -> 4 compiles
            f(jnp.ones((n,), jnp.float32))
        for _ in range(3):     # stable shape -> no new compiles
            f(jnp.ones((2,), jnp.float32))
    assert retrace.compile_count("poly_fn") == 4
    assert retrace.retrace_warning_count() == 2  # compiles 3 and 4
    storm = [r for r in caplog.records if "retrace_storm" in r.getMessage()]
    assert len(storm) == 2
    payload = json.loads(storm[-1].getMessage().split("sentinel: ", 1)[1])
    assert payload["fn"] == "poly_fn" and payload["compiles"] == 4


@pytest.mark.parametrize("other, same", [
    ("numpy", True),          # a host array of the same shape and dtype
    ("shape", False), ("dtype", False), ("tree", False), ("static", False)])
def test_signature_tells_calls_apart_by_shape_dtype_and_tree(other, same):
    """The sentinel's key of a call, computed on every dispatch: equal for
    the same tree of (shape, dtype) whoever holds the arrays, another one as
    soon as a shape, a dtype, the tree or a static leaf differs; its `str()`
    (what a storm logs) names the shapes."""
    import jax.numpy as jnp
    a = {"w": jnp.ones((2, 3), jnp.bfloat16), "b": [jnp.zeros(4, jnp.int32)]}
    calls = {
        "numpy": ({"w": np.ones((2, 3), jnp.bfloat16),
                   "b": [np.zeros(4, np.int32)]}, 7),
        "shape": ({**a, "w": jnp.ones((2, 4), jnp.bfloat16)}, 7),
        "dtype": ({**a, "w": jnp.ones((2, 3), jnp.float32)}, 7),
        "tree": ({**a, "b": (a["b"][0],)}, 7),
        "static": (a, 8)}
    key = retrace._abstract_signature((a, 7))
    got = retrace._abstract_signature(calls[other])
    assert (got == key and hash(got) == hash(key)) is same
    assert "(2, 3)" in str(key) and "bfloat16" in str(key)


def test_train_step_compiles_once_and_counts_steps(caplog):
    """Acceptance: a 3-step GPT-small CPU train loop records exactly ONE
    compile for the train step (zero steady-state retraces), nonzero
    op-dispatch counters, and one step-latency sample per step."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import (GPTPretrainingCriterion, build_gpt,
                                   gpt_config)

    obs.enable(True)
    cfg = gpt_config("gpt-tiny", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0)
    paddle.seed(0)
    model = build_gpt(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = dist.make_train_step(model, opt,
                                loss_fn=GPTPretrainingCriterion())
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 33)).astype(np.int64)
    with caplog.at_level(logging.WARNING, "paddle_tpu.observability"):
        for _ in range(3):
            loss = step(ids[:, :-1], ids[:, 1:])
    assert np.isfinite(float(loss))
    assert retrace.compile_count("spmd_train_step") == 1
    assert retrace.retrace_warning_count() == 0
    assert not [r for r in caplog.records
                if "retrace_storm" in r.getMessage()]
    assert steps.step_latency_count("train_step") == 3
    # examples/s: 3 steps x batch 2
    ex = obs.registry().get(steps.EXAMPLES_TOTAL)
    assert ex.value(labels={"fn": "train_step"}) == 6
    # the traced forward/backward ops were counted under mode=traced
    assert sum(dispatch.dispatch_counts(mode="traced").values()) > 0
    # an eager op on the loss lands on the other side of the split
    (loss + 1.0).numpy()
    assert sum(dispatch.dispatch_counts(mode="eager").values()) > 0


def test_to_static_cache_miss_records_compile():
    obs.enable(True)

    @paddle.jit.to_static
    def f(x):
        return x * 2 + 1

    f(paddle.to_tensor(np.ones((3,), np.float32)))
    f(paddle.to_tensor(np.ones((3,), np.float32)))  # hit: no new compile
    f(paddle.to_tensor(np.ones((5,), np.float32)))  # miss
    assert retrace.compile_count("to_static:f") == 2


# -- step metrics ------------------------------------------------------------

def test_record_step_and_hapi_callback():
    obs.enable(True)
    steps.record_step(0.25, examples=8, fn="unit")
    assert steps.step_latency_count("unit") == 1
    g = obs.registry().get(steps.EXAMPLES_PER_SEC)
    assert g.value(labels={"fn": "unit"}) == pytest.approx(32.0)

    from paddle_tpu.hapi.callbacks import TelemetryCallback, config_callbacks
    cbks = config_callbacks(verbose=0, model=None)
    assert any(isinstance(c, TelemetryCallback) for c in cbks.callbacks)
    cb = TelemetryCallback()
    cb.set_params({"batch_size": 4})
    cb.on_train_batch_begin(0, {})
    cb.on_train_batch_end(0, {})
    assert steps.step_latency_count("hapi_train_batch") == 1

    obs.disable()
    cbks = config_callbacks(verbose=0, model=None)
    assert not any(isinstance(c, TelemetryCallback) for c in cbks.callbacks)


# -- chrome-trace merge ------------------------------------------------------

def test_chrome_trace_has_spans_and_counter_samples(tmp_path):
    import paddle_tpu.profiler as profiler

    obs.enable(True)
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    with profiler.RecordEvent("unit_span"):
        a = paddle.to_tensor(np.ones((2, 2), np.float32))
        (a + a).sum()
    prof.stop()
    path = tmp_path / "trace.json"
    prof._export_chrome(str(path))
    data = json.load(open(path))
    events = data["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    counters = [e for e in events if e["ph"] == "C"]
    assert any(e["name"] == "unit_span" for e in spans)
    assert counters, "no counter samples merged into the chrome trace"
    assert all("value" in e["args"] for e in counters)
    # labeled series fold into the track name
    assert any("op=" in e["name"] for e in counters)


# -- tracing spans -----------------------------------------------------------

def test_span_nesting_parent_child_and_decorator():
    with trace.span("outer", phase="demo") as outer:
        assert trace.current_span() is outer
        with trace.span("inner") as inner:
            assert trace.current_span() is inner
            assert inner.parent_id == outer.id
        assert trace.current_span() is outer
    assert trace.current_span() is None

    done = trace.spans()
    assert [s["name"] for s in done[-2:]] == ["inner", "outer"]
    in_rec, out_rec = done[-2], done[-1]
    assert in_rec["parent_id"] == out_rec["id"]
    assert out_rec["parent_id"] is None
    assert out_rec["attrs"]["phase"] == "demo"
    # the child is contained in the parent on the monotonic timeline
    assert in_rec["ts"] >= out_rec["ts"]
    assert in_rec["ts"] + in_rec["dur"] <= out_rec["ts"] + out_rec["dur"] + 1

    # span open/close fed the flight recorder, in order
    kinds = [(e["kind"], e["name"]) for e in flight.events()]
    assert kinds[:4] == [("span_begin", "outer"), ("span_begin", "inner"),
                        ("span_end", "inner"), ("span_end", "outer")]

    @trace.span("decorated", kind="fn")
    def f(x):
        return x + 1

    assert f(1) == 2 and f(2) == 3
    assert len(trace.spans("decorated")) == 2


def test_span_error_status_recorded():
    with pytest.raises(ValueError):
        with trace.span("failing"):
            raise ValueError("boom")
    rec = trace.spans("failing")[-1]
    assert rec["attrs"]["status"] == "error"
    assert rec["attrs"]["exception"] == "ValueError"
    end = [e for e in flight.events("span_end") if e["name"] == "failing"][-1]
    assert end["attrs"]["status"] == "error"


# -- flight recorder ---------------------------------------------------------

def test_flight_ring_bounded_and_ordered():
    old = flight.capacity()
    flight.set_capacity(16)
    try:
        flight.clear()
        for i in range(50):
            flight.record("unit", f"ev{i}", i=i)
        evs = flight.events("unit")
        assert len(evs) == 16  # bounded: oldest fell off the front
        assert [e["attrs"]["i"] for e in evs] == list(range(34, 50))
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs)
        monos = [e["mono"] for e in evs]
        assert monos == sorted(monos)
        assert flight.tail(4) == evs[-4:]
    finally:
        flight.set_capacity(old)


def test_flight_recorder_on_with_telemetry_off():
    """Collectives/compiles land in the flight record even with telemetry
    off — while the metrics registry stays empty (off means off)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.distributed as dist

    assert not obs.enabled()
    dist.all_reduce(paddle.to_tensor(np.ones((4,), np.float32)))
    f = obs.instrument_jit(jax.jit(lambda x: x * 2), name="off_fn")
    f(jnp.ones((2,), jnp.float32))
    names = [e["name"] for e in flight.events("span_end")]
    assert "collective.all_reduce" in names
    assert "compile" in names
    dumped = obs.registry().dump()
    assert dumped["counters"] == {} and dumped["histograms"] == {}


def test_collective_span_attrs():
    import paddle_tpu.distributed as dist

    dist.all_reduce(paddle.to_tensor(np.ones((8, 4), np.float32)))
    rec = trace.spans("collective.all_reduce")[-1]
    assert rec["attrs"]["bytes"] == 8 * 4 * 4
    assert rec["attrs"]["mode"] == "eager"
    assert rec["attrs"]["nranks"] >= 1


def test_checkpoint_spans(tmp_path):
    from paddle_tpu.framework.checkpoint import load_sharded, save_sharded

    state = {"w": paddle.to_tensor(np.ones((4, 4), np.float32)),
             "meta": {"step": 7}}
    d = str(tmp_path / "ckpt")
    save_sharded(state, d)
    out = load_sharded(d)
    assert np.allclose(out["w"].numpy(), 1.0)
    save_rec = trace.spans("checkpoint.save")[-1]
    assert save_rec["attrs"]["leaves"] == 2
    # the 4x4 f32 tensor plus the int64 scalar leaf
    assert save_rec["attrs"]["bytes"] == 4 * 4 * 4 + 8
    assert trace.spans("checkpoint.load")


# -- crash/hang diagnostics --------------------------------------------------

def test_excepthook_crash_dump_round_trip(tmp_path, monkeypatch):
    """A raise mid-train-step, routed through the installed excepthook,
    produces a crash-dump JSON with the step span + a collective event in
    the flight tail, the exception, and all-thread stacks."""
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn

    monkeypatch.setenv("PADDLE_TPU_DUMP_DIR", str(tmp_path))
    # a collective event lands in the flight record before the crash
    dist.all_reduce(paddle.to_tensor(np.ones((2,), np.float32)))

    paddle.seed(0)
    model = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())

    def bad_loss(out, y):
        raise RuntimeError("boom mid-step")

    step = dist.make_train_step(model, opt, loss_fn=bad_loss)
    x = np.ones((2, 4), np.float32)
    y = np.zeros((2, 2), np.float32)

    # chain onto a silent hook so the test log stays clean, then route the
    # exception through the REAL installed excepthook
    monkeypatch.setattr(sys, "excepthook", lambda *a: None)
    watchdog.install()
    try:
        with pytest.raises(RuntimeError, match="boom mid-step"):
            try:
                step(x, y)
            except RuntimeError:
                sys.excepthook(*sys.exc_info())
                raise
    finally:
        watchdog.uninstall()

    path = watchdog.last_dump_path()
    assert path and os.path.dirname(path) == str(tmp_path)
    bundle = json.load(open(path))
    assert bundle["schema"] == watchdog.SCHEMA
    assert bundle["reason"] == "uncaught_exception"
    assert bundle["exception"]["type"] == "RuntimeError"
    assert "boom mid-step" in bundle["exception"]["message"]
    events = [(e["kind"], e["name"]) for e in bundle["flight_events"]]
    assert ("span_begin", "train_step") in events
    assert any(n.startswith("collective.") for _, n in events)
    # the in-flight step span closed on the unwind with error status
    step_ends = [e for e in bundle["flight_events"]
                 if e["kind"] == "span_end" and e["name"] == "train_step"]
    assert step_ends and step_ends[-1]["attrs"]["status"] == "error"
    # all-thread stacks, including this (main) thread
    assert any(t["name"] == "MainThread" and t["stack"]
               for t in bundle["threads"])


def test_watchdog_fires_on_stalled_step(tmp_path, monkeypatch):
    """PADDLE_TPU_STEP_TIMEOUT_S + a stalled step → the SPMD-armed
    watchdog writes the diagnostic bundle (with the open step span) while
    the step is still stuck, without killing it."""
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn

    monkeypatch.setenv("PADDLE_TPU_DUMP_DIR", str(tmp_path))
    paddle.seed(0)
    model = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = dist.make_train_step(model, opt, loss_fn=nn.MSELoss())
    x = np.ones((2, 4), np.float32)
    y = np.zeros((2, 2), np.float32)
    float(step(x, y))  # compile OUTSIDE the deadline window

    monkeypatch.setenv("PADDLE_TPU_STEP_TIMEOUT_S", "0.15")
    fired_before = watchdog._watchdog.fired_count
    inner = step._jitted

    def stalled(*args, **kwargs):
        time.sleep(0.6)  # artificial stall >> deadline
        return inner(*args, **kwargs)

    step._jitted = stalled
    try:
        float(step(x, y))  # completes; the watchdog fired mid-stall
    finally:
        step._jitted = inner
    for _ in range(100):  # the dump is written from the watchdog thread
        if watchdog._watchdog.fired_count > fired_before and \
                watchdog.last_dump_path():
            break
        time.sleep(0.05)
    assert watchdog._watchdog.fired_count == fired_before + 1
    bundle = json.load(open(watchdog.last_dump_path()))
    assert bundle["reason"] == "step_timeout:spmd_train_step"
    # the stalled step's span was OPEN when the watchdog dumped
    open_names = [sp["name"] for sps in bundle["open_spans"].values()
                  for sp in sps]
    assert "train_step" in open_names
    assert any(e["kind"] == "watchdog" for e in bundle["flight_events"])
    assert bundle["threads"]
    # a healthy (disarmed) step afterwards does not re-fire
    float(step(x, y))
    time.sleep(0.3)
    assert watchdog._watchdog.fired_count == fired_before + 1


def test_watchdog_disarmed_without_env(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_STEP_TIMEOUT_S", raising=False)
    assert watchdog.step_timeout() is None
    assert watchdog.arm("unit_step") is False


# -- dataloader wait events --------------------------------------------------

class _ObsRangeDataset:
    def __init__(self, n):
        self.n = n

    def __getitem__(self, i):
        return np.float32(i)

    def __len__(self):
        return self.n


def test_multiprocess_dataloader_records_wait_events():
    """A real num_workers>0 run records parent-side get waits with queue
    depth; the worker loop body (run in-process against plain queues — the
    fork boundary keeps child rings in the child) records its own get/put
    waits."""
    import queue

    from paddle_tpu.io import DataLoader
    from paddle_tpu.io import dataloader as dl_mod

    ds = _ObsRangeDataset(16)
    loader = DataLoader(ds, batch_size=4, num_workers=2,
                        use_shared_memory=False)
    seen = sorted(float(v) for b in loader for v in b.numpy())
    assert seen == [float(i) for i in range(16)]
    gets = trace.spans("dataloader.get")
    assert len(gets) >= 4
    assert all("outstanding" in s["attrs"] for s in gets)
    assert any(s["attrs"]["outstanding"] > 0 for s in gets)

    # worker side: drive _worker_loop directly
    flight.clear()
    trace.clear()
    iq, dq = queue.Queue(), queue.Queue()
    iq.put((0, [0, 1, 2]))
    iq.put(None)
    saved_info = dl_mod._worker_info
    try:
        dl_mod._worker_loop(ds, iq, dq, dl_mod.default_collate_fn, 0, 1, 7)
    finally:
        dl_mod._worker_info = saved_info
    bid, err, batch = dq.get_nowait()
    assert bid == 0 and err is None and len(batch) == 3
    names = [e["name"] for e in flight.events("span_end")]
    assert "dataloader.worker_get" in names
    assert "dataloader.worker_put" in names
    put = trace.spans("dataloader.worker_put")[-1]
    assert put["attrs"] == {"worker": 0, "batch_id": 0}


# -- chrome-trace span merge -------------------------------------------------

def test_chrome_trace_spans_from_three_subsystems(tmp_path):
    """export_chrome_tracing output carries 'cat: span' events from the
    compile, collective and dataloader subsystems on one timeline."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.distributed as dist
    import paddle_tpu.profiler as profiler
    from paddle_tpu.io import DataLoader

    f = obs.instrument_jit(jax.jit(lambda x: x + 1), name="chrome_fn")
    f(jnp.ones((2,), jnp.float32))
    dist.all_reduce(paddle.to_tensor(np.ones((4,), np.float32)))
    loader = DataLoader(_ObsRangeDataset(8), batch_size=4, num_workers=1,
                        use_shared_memory=False)
    list(loader)

    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    prof.stop()
    path = str(tmp_path / "trace.json")
    prof._export_chrome(path)
    events = json.load(open(path))["traceEvents"]
    span_events = [e for e in events if e.get("cat") == "span"]
    names = {e["name"] for e in span_events}
    assert "compile" in names
    assert any(n.startswith("collective.") for n in names)
    assert any(n.startswith("dataloader.") for n in names)
    assert all(e["ph"] == "X" and "span_id" in e["args"]
               for e in span_events)
