"""Fleet elasticity tests (ISSUE 15): scale policy units (hysteresis,
cooldowns, flap resistance on synthetic window feeds), dynamic router
membership under concurrent dispatch, draining-is-not-dead pick
semantics, drain-before-remove with in-flight requests completing, the
crash-at-every-new-seam matrix, the sim-mode closed loop on the
flash-crowd trace, scale-aware Retry-After, and the fleet metric /
flight / ``/debug/fleet`` surfaces.

The contract under test is docs/robustness.md's "Fleet elasticity"
section: scale-up on TTFT-headroom collapse / queue-wait-p99 breach /
sustained shed, scale-down ONLY as drain → wait-empty → remove →
teardown (never a kill), and every scale-path crash absorbed (the
event retried, the fleet back inside [min, max]).
"""
import json
import sys
import threading
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu.models import build_gpt, gpt_config
from paddle_tpu.observability import flight, registry
from paddle_tpu.serving import Autoscaler, Engine, FleetSim, ScalePolicy
from paddle_tpu.serving.autoscaler import (FLEET_ALIVE, FLEET_DESIRED,
                                           FLEET_DRAINING,
                                           FLEET_SCALE_EVENTS)
from paddle_tpu.serving.gateway import Gateway, TenantConfig
from paddle_tpu.serving.gateway.protocol import parse_completion_request
from paddle_tpu.serving.gateway.router import (GATEWAY_ENGINE_SLOTS,
                                               EngineRouter,
                                               NoEngineAvailableError)
from paddle_tpu.serving.gateway.shed import LoadShedder
from paddle_tpu.testing import faults

sys.path.insert(0, ".")
from tools.load_gen import make_trace  # noqa: E402


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt_config("gpt-tiny", max_position_embeddings=128,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(21)
    model = build_gpt(cfg)
    model.eval()
    return model, cfg


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _wait(pred, timeout=90.0, period=0.01):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if pred():
            return True
        time.sleep(period)
    return False


def _creq(max_tokens=3, prompt=(1, 2, 3), **extra):
    payload = {"prompt": list(prompt), "max_tokens": max_tokens}
    payload.update(extra)
    return parse_completion_request(json.dumps(payload).encode(),
                                    has_tokenizer=False)


class StubEngine:
    """Engine-shaped fake for router/autoscaler units: O(1) load
    snapshot, instant drain, warm health — no devices, no threads."""

    def __init__(self, max_slots=2, alive=True):
        self.tokenizer = None
        self.max_len = 64
        self.max_slots = max_slots
        self.alive = alive
        self.draining = False
        self.slots = 0
        self.queue = 0
        self.shut_down = False

    def load(self):
        return {"queue_depth": self.queue, "slots_in_use": self.slots,
                "cached_slots": 0, "max_slots": self.max_slots,
                "max_queue": 16, "max_len": self.max_len,
                "alive": self.alive and not self.draining,
                "draining": self.draining}

    def drain(self, deadline_s=30.0):
        self.draining = True
        return True

    def shutdown(self):
        self.shut_down = True
        self.alive = False

    def health(self):
        return {"warm": True, "dead": not self.alive}


def _feed(est=None, qw_p99=0.0, qw_n=0, shed_rate=0.0, requests=0,
          shed=0, queue_depth=0, slots_in_use=0, total_slots=4,
          prefill=0.0):
    return {"est_ttft_s": est, "prefill_s": prefill,
            "queue_wait_s": {"p50": qw_p99 / 2, "p99": qw_p99, "n": qw_n},
            "shed_rate": shed_rate, "requests": requests, "shed": shed,
            "queue_depth": queue_depth, "slots_in_use": slots_in_use,
            "total_slots": total_slots}


def _pol(**kw):
    base = dict(slo_ttft_s=1.0, headroom_frac=0.25, queue_wait_p99_s=0.5,
                shed_rate=0.1, up_ticks=2, idle_ticks=3,
                cooldown_up_s=5.0, cooldown_down_s=10.0)
    base.update(kw)
    return ScalePolicy(**base)


# -- policy units -------------------------------------------------------------

def test_policy_up_on_headroom_collapse_needs_sustained_breach():
    """est_ttft past (1-headroom)*slo scales up — but only after
    up_ticks consecutive breach polls (hysteresis), and a recovered
    tick resets the streak."""
    pol = _pol()
    hot = _feed(est=0.9)                       # > 0.75 * 1.0
    kw = dict(replicas=1, min_replicas=1, max_replicas=4)
    assert pol.decide(hot, now=0.0, **kw) == (None, "")
    assert pol.decide(hot, now=1.0, **kw) == ("up", "ttft_headroom")
    # recovered tick resets the streak: breach must re-sustain
    pol2 = _pol()
    assert pol2.decide(hot, now=0.0, **kw) == (None, "")
    assert pol2.decide(_feed(est=0.1), now=1.0, **kw) == (None, "")
    assert pol2.decide(hot, now=2.0, **kw) == (None, "")
    assert pol2.decide(hot, now=3.0, **kw) == ("up", "ttft_headroom")


def test_policy_up_reasons_queue_wait_and_shed_rate():
    kw = dict(replicas=1, min_replicas=1, max_replicas=4)
    pol = _pol(up_ticks=1)
    assert pol.decide(_feed(qw_p99=0.8, qw_n=5), now=0.0, **kw) == \
        ("up", "queue_wait_p99")
    pol = _pol(up_ticks=1)
    assert pol.decide(_feed(shed_rate=0.5, requests=5, shed=5),
                      now=0.0, **kw) == ("up", "shed_rate")
    # at max_replicas the breach is recorded but nothing fires
    pol = _pol(up_ticks=1)
    assert pol.decide(_feed(est=0.9), now=0.0, replicas=4,
                      min_replicas=1, max_replicas=4) == (None, "")


def test_policy_down_on_sustained_idle_clamped_at_min():
    pol = _pol(idle_ticks=3)
    idle = _feed(est=0.05, queue_depth=0, slots_in_use=0)
    kw = dict(replicas=2, min_replicas=1, max_replicas=4)
    assert pol.decide(idle, now=0.0, **kw) == (None, "")
    assert pol.decide(idle, now=1.0, **kw) == (None, "")
    assert pol.decide(idle, now=2.0, **kw) == ("down", "idle")
    # at min_replicas idle never fires
    pol = _pol(idle_ticks=1)
    assert pol.decide(idle, now=0.0, replicas=1, min_replicas=1,
                      max_replicas=4) == (None, "")
    # the prefill floor does not block idleness: est == prefill EWMA
    # (cold-compile-contaminated) with zero backlog must still shrink
    pol = _pol(idle_ticks=1)
    stale = _feed(est=0.9, prefill=0.9)
    assert pol.decide(stale, now=0.0, replicas=2, min_replicas=1,
                      max_replicas=4) == ("down", "idle")


def test_policy_cooldowns_and_flap_resistance():
    """Per-direction cooldowns, and each direction refuses to fire
    inside the other's window: no up→down→up inside one cooldown."""
    pol = _pol(up_ticks=1, idle_ticks=1, cooldown_up_s=5.0,
               cooldown_down_s=10.0)
    kw = dict(replicas=2, min_replicas=1, max_replicas=4)
    assert pol.decide(_feed(est=0.9), now=0.0, **kw)[0] == "up"
    pol.note_event("up", 0.0)
    # an immediate idle swing must NOT scale down (flap): blocked until
    # cooldown_down_s past the up event
    idle = _feed(est=0.05)
    for t in (0.5, 3.0, 9.0):
        assert pol.decide(idle, now=t, **kw) == (None, "")
    assert pol.decide(idle, now=10.5, **kw)[0] == "down"
    pol.note_event("down", 10.5)
    # and an immediate re-up is blocked inside cooldown_up_s of the down
    assert pol.decide(_feed(est=0.9), now=11.0, **kw) == (None, "")
    assert pol.decide(_feed(est=0.9), now=16.0, **kw)[0] == "up"


# -- router membership --------------------------------------------------------

def test_router_add_remove_under_concurrent_dispatch():
    """pick()/loads()/total_slots() race add_replica/remove_replica from
    another thread without errors or torn membership."""
    router = EngineRouter([StubEngine(), StubEngine()],
                          names=["a", "b"])
    stop = threading.Event()
    errors = []

    def dispatch_loop():
        while not stop.is_set():
            try:
                name, eng = router.pick()
                assert eng.load()["alive"]
                router.loads()
                router.total_slots()
                router.has_headroom()
            except NoEngineAvailableError:
                pass
            except Exception as e:  # noqa: BLE001 — the test's point
                errors.append(e)
                return

    threads = [threading.Thread(target=dispatch_loop) for _ in range(4)]
    for th in threads:
        th.start()
    for i in range(50):
        name = f"dyn{i}"
        router.add_replica(name, StubEngine())
        time.sleep(0.001)
        router.remove_replica(name)
    stop.set()
    for th in threads:
        th.join(timeout=10)
    assert not errors, errors
    assert router.names == ["a", "b"]
    with pytest.raises(ValueError):
        router.add_replica("a", StubEngine())    # duplicate name
    with pytest.raises(KeyError):
        router.remove_replica("nope")


def test_router_draining_is_third_state_not_dead():
    """A draining replica is never picked (parked work can't land on a
    replica that is leaving) but counts as present: any_draining() True,
    and with every OTHER replica gone the router reports not-alive but
    draining rather than simply dead."""
    a, b = StubEngine(), StubEngine()
    router = EngineRouter([a, b], names=["a", "b"])
    b.draining = True
    for _ in range(8):
        assert router.pick()[0] == "a"
    assert router.any_alive() and router.any_draining()
    assert router.total_slots() == a.max_slots     # draining not counted
    assert router.has_headroom()
    a.alive = False
    assert not router.any_alive()
    assert router.any_draining()
    with pytest.raises(NoEngineAvailableError):
        router.pick()
    b.slots = 0
    assert not router.has_headroom()               # draining != headroom


def test_router_remove_deletes_stale_slots_gauge_series():
    """Removed replicas must have their per-engine occupancy series
    DELETED, not frozen at the last value — a dashboard showing a dead
    replica's stale slots is a mis-diagnosis trap."""
    registry().reset()
    a, b = StubEngine(), StubEngine()
    a.slots, b.slots = 1, 2
    router = EngineRouter([a, b], names=["keep", "gone"])
    router.loads()
    gauge = registry().get(GATEWAY_ENGINE_SLOTS)
    names = {dict(lbl)["engine"] for lbl, _ in gauge.series()}
    assert names == {"keep", "gone"}
    router.remove_replica("gone")
    names = {dict(lbl)["engine"] for lbl, _ in gauge.series()}
    assert names == {"keep"}, names
    # and a racing re-export is swept on the next loads() refresh
    gauge.set(2.0, labels={"engine": "gone"})
    router.loads()
    names = {dict(lbl)["engine"] for lbl, _ in gauge.series()}
    assert names == {"keep"}, names


def test_gateway_parks_work_while_draining_plus_scale_pending():
    """Admission must not 503 while the only pickable capacity is a
    draining replica with a scale-up building (capacity on the way)."""
    stub = StubEngine()
    gw = Gateway([stub], tenants=[TenantConfig("t")], start=False)
    stub.draining = True

    class _PendingScaler:
        def scale_pending(self):
            return True

        def expected_ready_s(self):
            return 0.7

        def fleet_stats(self):
            return {"stub": True}

    # with no autoscaler: draining alone already parks instead of 503
    item = gw.admit(_creq(), "t")
    assert not item.done_ev.is_set()
    gw.attach_autoscaler(_PendingScaler())
    item2 = gw.admit(_creq(), "t")
    assert not item2.done_ev.is_set()
    # truly dead fleet (no drain, no pending) still 503s at admission
    gw2 = Gateway([StubEngine(alive=False)], tenants=[TenantConfig("t")],
                  start=False)
    with pytest.raises(NoEngineAvailableError):
        gw2.admit(_creq(), "t")
    gw.shutdown()
    gw2.shutdown()


def test_shed_retry_after_capped_at_expected_warmup():
    """While a scale-up is in flight, a 429's Retry-After is the
    expected warm-up completion (cold-build EWMA), not the static
    est−deadline horizon: shed clients return when capacity arrives."""
    from paddle_tpu.serving.gateway.admission import AdmissionError
    shedder = LoadShedder()
    shedder.seed(prefill_s=5.0, token_s=1.0)   # est blows any deadline
    stub = StubEngine()
    gw = Gateway([stub], tenants=[TenantConfig("t")], shedder=shedder,
                 start=False)
    with pytest.raises(AdmissionError) as e1:
        gw.admit(_creq(deadline_ms=100), "t")
    baseline = e1.value.retry_after_s
    assert baseline > 2.0, baseline            # the static horizon

    class _BuildingScaler:
        def scale_pending(self):
            return True

        def expected_ready_s(self):
            return 1.2

        def fleet_stats(self):
            return {}

    gw.attach_autoscaler(_BuildingScaler())
    with pytest.raises(AdmissionError) as e2:
        gw.admit(_creq(deadline_ms=100), "t")
    assert e2.value.retry_after_s <= 1.2 < baseline, \
        (e2.value.retry_after_s, baseline)
    gw.shutdown()


# -- crash matrix: the new fault seams ----------------------------------------

@pytest.mark.parametrize("seam", ["scale.up_build", "scale.down_drain",
                                  "autoscaler.tick"])
def test_crash_at_scale_seam_is_absorbed_and_retried(seam):
    """A raise at any new seam never wedges the fleet: the control loop
    survives, the scale event is retried, and the fleet lands back
    inside [min, max]."""
    gw = Gateway([StubEngine()], tenants=[TenantConfig("t")], start=False)
    auto = Autoscaler(gw, StubEngine, min_replicas=1, max_replicas=3,
                      policy=_pol(), poll_interval_s=0.01,
                      drain_deadline_s=1.0, name_prefix="as")
    try:
        if seam == "scale.up_build":
            faults.arm(seam, times=1)
            auto.trigger("up")
            assert _wait(lambda: len(gw.router.names) == 2, timeout=30), \
                gw.router.names
            assert faults.hits(seam) >= 2          # failed, then retried
            names = {e["name"] for e in flight.events("autoscaler")}
            assert "scale_up_failed" in names, names
        elif seam == "scale.down_drain":
            auto.trigger("up")
            assert _wait(lambda: len(gw.router.names) == 2, timeout=30)
            faults.arm(seam, times=1)
            auto.trigger("down")
            assert _wait(lambda: len(gw.router.names) == 1, timeout=30), \
                gw.router.names
            assert faults.hits(seam) >= 2
            names = {e["name"] for e in flight.events("autoscaler")}
            assert "scale_down_failed" in names, names
        else:                                      # autoscaler.tick
            faults.arm(seam, times=3)
            time.sleep(0.2)                        # ticks crash, absorbed
            faults.disarm(seam)
            auto.trigger("up")
            assert _wait(lambda: len(gw.router.names) == 2, timeout=30)
            names = {e["name"] for e in flight.events("autoscaler")}
            assert "tick_error" in names, names
        assert 1 <= len(gw.router.names) <= 3
        assert auto.desired == len(gw.router.names)
    finally:
        faults.reset()
        auto.shutdown()
        gw.shutdown()


# -- closed loop over real engines --------------------------------------------

def test_scale_up_then_drain_down_end_to_end(tiny_gpt):
    """The full loop against real engines over HTTP: a flood breaches
    the windowed queue-wait → a replica builds and joins the router;
    idle sustains → the victim DRAINS (in-flight work completes; zero
    interruptions), leaves the router, and is shut down.  Decode stays
    at one compiled signature per engine and the fleet metrics/flight
    events record both events."""
    import http.client

    from paddle_tpu.serving.gateway import start_gateway
    model, cfg = tiny_gpt
    registry().reset()
    built = []

    def factory():
        # one model instance per replica: a scale-up build traces its
        # jit programs while the loaded replica may be compiling a new
        # prefill bucket, and concurrent tracing over one shared module
        # is not supported
        paddle.seed(21)
        m = build_gpt(cfg)
        m.eval()
        e = Engine(m, max_slots=2, max_len=48, max_queue=32)
        built.append(e)
        return e

    stack = start_gateway([factory()], own_engines=True,
                          tenants=[TenantConfig("t", max_queue=64)],
                          window_s=2.0)
    pol = ScalePolicy(slo_ttft_s=30.0, queue_wait_p99_s=0.05, up_ticks=1,
                      idle_ticks=3, cooldown_up_s=0.3, cooldown_down_s=0.8,
                      idle_util=0.99)
    auto = Autoscaler(stack, factory, min_replicas=1, max_replicas=2,
                      policy=pol, poll_interval_s=0.05,
                      drain_deadline_s=10.0, build_s_hint=2.0)
    gw = stack.gateway
    results = []
    lock = threading.Lock()

    def one(i):
        conn = http.client.HTTPConnection("127.0.0.1", stack.port,
                                          timeout=300)
        conn.request(
            "POST", "/v1/completions",
            json.dumps({"prompt": [1 + i % 7, 2, 3],
                        "max_tokens": 4}).encode(),
            {"Content-Type": "application/json", "X-Tenant": "t"})
        r = conn.getresponse()
        body = json.loads(r.read())
        conn.close()
        with lock:
            results.append((r.status,
                            len(body["choices"][0]["token_ids"])
                            if r.status == 200 else 0))

    try:
        one(0)                                   # warm the first replica
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(16)]
        # gpt-tiny decodes in a few ms on a calm CPU, and eight waves of it
        # are over before the last request has queued for 50 ms: stretch a
        # step to the time it has on a chip, so that the flood breaches the
        # queue-wait whatever the host's pace
        with faults.inject("serving.decode", mode="delay", seconds=0.02,
                           times=None):
            for th in threads:
                th.start()
            assert _wait(lambda: len(gw.router.names) == 2, timeout=120), \
                "scale-up never fired"
            for th in threads:
                th.join(timeout=300)
        assert len(results) == 17
        assert all(s == 200 and n == 4 for s, n in results), \
            results                               # zero lost requests
        # idle → drain-based scale-down back to min
        assert _wait(lambda: len(gw.router.names) == 1, timeout=120), \
            "scale-down never fired"
        assert len(built) == 2
        drained = built[0] if built[0]._stop else built[1]
        assert drained._stop                      # torn down post-drain
        assert all(e.compile_stats()["decode_compiles"] <= 1
                   for e in built)
        ev = {e["name"] for e in flight.events("autoscaler")}
        assert {"scale_up_begin", "scale_up", "scale_down_begin",
                "scale_down"} <= ev, ev
        counter = registry().get(FLEET_SCALE_EVENTS)
        # the flood breaches queue-wait OR ttft-headroom first depending
        # on scheduling — either way it's exactly one up + one down
        up = sum(counter.value({"direction": "up", "reason": r})
                 for r in ("queue_wait_p99", "ttft_headroom", "shed"))
        assert up == 1.0
        # the router shrinks a moment before the autoscaler's thread counts
        assert _wait(lambda: counter.value(
            {"direction": "down", "reason": "idle"}) == 1.0, timeout=30)
        # the router shrinks when the drain completes; the desired
        # gauge flushes on the autoscaler's next tick — wait for it
        assert _wait(lambda: registry().get(FLEET_DESIRED).value() == 1.0,
                     timeout=30)
        assert registry().get(FLEET_ALIVE).value() >= 1.0
        assert registry().get(FLEET_DRAINING) is not None
    finally:
        auto.shutdown()
        stack.close()
        for e in built:
            e.shutdown()


def test_debug_fleet_endpoint_and_metrics_export(tiny_gpt):
    """GET /debug/fleet serves the fleet state and /metrics exports the
    paddle_tpu_fleet_* gauges while an autoscaler is attached."""
    import http.client

    from paddle_tpu.serving.gateway import start_gateway
    model, cfg = tiny_gpt
    registry().reset()
    eng = Engine(model, max_slots=2, max_len=48)
    stack = start_gateway([eng], own_engines=True,
                          tenants=[TenantConfig("t")])
    auto = Autoscaler(stack, lambda: Engine(model, max_slots=2, max_len=48),
                      min_replicas=1, max_replicas=2,
                      policy=_pol(), poll_interval_s=0.05)
    try:
        assert _wait(lambda: registry().get(FLEET_DESIRED) is not None,
                     timeout=30)
        conn = http.client.HTTPConnection("127.0.0.1", stack.port,
                                          timeout=60)
        conn.request("GET", "/debug/fleet")
        r = conn.getresponse()
        fleet = json.loads(r.read())
        conn.close()
        assert r.status == 200
        assert fleet["alive"] == 1 and fleet["draining"] == 0
        assert fleet["replicas"]["engine0"]["alive"]
        a = fleet["autoscaler"]
        assert a["min_replicas"] == 1 and a["max_replicas"] == 2
        assert a["desired"] == 1 and a["op"] is None
        assert "policy" in a and a["policy"]["slo_ttft_s"] == 1.0
        conn = http.client.HTTPConnection("127.0.0.1", stack.port,
                                          timeout=60)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        for name in (FLEET_DESIRED, FLEET_ALIVE, FLEET_DRAINING):
            assert name in text, name
    finally:
        auto.shutdown()
        stack.close()


# -- simulation mode ----------------------------------------------------------

def test_sim_closed_loop_beats_static_fleets_on_flash_crowd():
    """The acceptance gate, in tier-1: on the seeded flash-crowd trace
    the autoscaled fleet matches the best static fleet's SLO attainment
    while spending fewer replica-seconds, with zero flaps."""
    trace = make_trace(60.0, 4.0, seed=0, flash_mult=8.0,
                       flash_duration_s=10.0, prompt_mean=12.0,
                       out_mean=10.0, deadline_s=3.0)
    pol = ScalePolicy(slo_ttft_s=1.0, up_ticks=2, idle_ticks=8,
                      cooldown_up_s=2.0, cooldown_down_s=6.0)
    auto = FleetSim(pol, min_replicas=1, max_replicas=5,
                    slots_per_replica=4, prefill_s=0.05, token_s=0.01,
                    build_s=1.5).run(trace)
    statics = {
        n: FleetSim(None, min_replicas=n, max_replicas=n,
                    start_replicas=n, slots_per_replica=4,
                    prefill_s=0.05, token_s=0.01).run(trace)
        for n in range(1, 6)}
    best = max(statics.values(), key=lambda s: s["slo_attainment"])
    cheapest_best = min(
        (s for s in statics.values()
         if s["slo_attainment"] >= best["slo_attainment"]),
        key=lambda s: s["replica_seconds"])
    assert auto["slo_attainment"] >= best["slo_attainment"] - 1e-9, \
        (auto["slo_attainment"], best["slo_attainment"])
    assert auto["replica_seconds"] < cheapest_best["replica_seconds"], \
        (auto["replica_seconds"], cheapest_best["replica_seconds"])
    assert auto["flaps"] == 0, auto["events"]
    assert any(e["direction"] == "up" for e in auto["events"])
    assert auto["completed"] + auto["shed"] == auto["arrivals"]


def test_sim_scale_down_drains_and_loses_nothing():
    """In sim as live: a draining replica finishes its in-flight work
    and only an EMPTY replica leaves the fleet — arrivals are conserved
    across scale-downs and the fleet returns to min after the burst."""
    trace = make_trace(40.0, 3.0, seed=1, flash_mult=10.0, flash_at=0.2,
                       flash_duration_s=6.0, out_mean=20.0)
    # sparse tail traffic: the sim stops when work runs dry, so give the
    # idle detector ticks to walk the fleet back down after the burst
    trace += [{"t": 40.0 + i, "prompt_len": 1, "max_tokens": 1}
              for i in range(25)]
    pol = ScalePolicy(slo_ttft_s=1.0, up_ticks=1, idle_ticks=4,
                      cooldown_up_s=1.0, cooldown_down_s=3.0)
    r = FleetSim(pol, min_replicas=1, max_replicas=4,
                 slots_per_replica=2, prefill_s=0.05, token_s=0.02,
                 build_s=1.0).run(trace)
    assert r["completed"] == r["arrivals"]      # no deadlines: zero shed
    assert r["shed"] == 0
    downs = [e for e in r["events"] if e["direction"] == "down"]
    assert downs, r["events"]                   # the burst fleet shrank
    assert r["final_replicas"] <= 2, r
    assert r["final_replicas"] < r["peak_replicas"], r
    assert r["flaps"] == 0


def test_sim_flap_resistance_under_oscillating_load():
    """A load square-wave faster than the cooldowns must not produce
    up→down→up churn: per-direction cooldowns bound event frequency."""
    trace = []
    for burst in range(6):                      # 5 s on, 5 s off
        t0 = burst * 10.0
        trace += [{"t": t0 + i * 0.05, "prompt_len": 8, "max_tokens": 8}
                  for i in range(100)]
    pol = ScalePolicy(slo_ttft_s=0.5, up_ticks=2, idle_ticks=4,
                      cooldown_up_s=8.0, cooldown_down_s=20.0)
    r = FleetSim(pol, min_replicas=1, max_replicas=4,
                 slots_per_replica=4, prefill_s=0.05, token_s=0.01,
                 build_s=1.0).run(trace)
    assert r["flaps"] == 0, r["events"]
    for a, b in zip(r["events"], r["events"][1:]):
        if a["direction"] != b["direction"]:
            assert b["t"] - a["t"] >= min(pol.cooldown_up_s,
                                          pol.cooldown_down_s), \
                (a, b)


# -- the trace generator ------------------------------------------------------

def test_load_gen_trace_seeded_diurnal_flash_heavy_tail():
    kw = dict(flash_mult=6.0, flash_at=0.5, flash_duration_s=8.0,
              deadline_s=2.0)
    tr = make_trace(60.0, 4.0, seed=0, **kw)
    assert tr == make_trace(60.0, 4.0, seed=0, **kw)       # deterministic
    assert tr != make_trace(60.0, 4.0, seed=1, **kw)
    ts = [e["t"] for e in tr]
    assert ts == sorted(ts) and ts[-1] < 60.0
    flash_rate = sum(1 for t in ts if 30.0 <= t < 38.0) / 8.0
    base_rate = sum(1 for t in ts if t < 30.0) / 30.0
    assert flash_rate > 2.5 * base_rate, (flash_rate, base_rate)
    lens = sorted(e["prompt_len"] for e in tr)
    p50 = lens[len(lens) // 2]
    p99 = lens[int(len(lens) * 0.99)]
    assert p99 >= 3 * p50, (p50, p99)                      # heavy tail
    assert all(e["deadline_s"] == 2.0 for e in tr)
    assert all(e["max_tokens"] >= 1 and e["prompt_len"] >= 1 for e in tr)
    no_dl = make_trace(10.0, 2.0, seed=0)
    assert all("deadline_s" not in e for e in no_dl)
    with pytest.raises(ValueError):
        make_trace(0.0, 1.0)
