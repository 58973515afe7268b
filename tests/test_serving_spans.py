"""The engine's scheduler phases on the profiler's clock (ISSUE 25).

`observability.trace.span` enters a `jax.profiler.TraceAnnotation` of the
same name, so under a profiler session every span is a host-plane event on
the clock of the device ops; `trace.phase` is its lighter sibling (no flight
events, a root may drop itself and what closed under it).  The engine cuts
each scheduler iteration into `serving.*` phases so that every instant of it
lies in exactly one leaf, and counts prefill padding where it is made.
"""
import glob
import os
import sys
import time
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import build_gpt, gpt_config
from paddle_tpu.observability import flight, trace
from paddle_tpu.serving import Engine
from paddle_tpu.testing import faults

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats:DeprecationWarning")

COLD_LEAVES = ["serving.sweep", "serving.admit", "serving.admit.wave",
               "serving.prefill.dispatch", "serving.prefill.fetch",
               "serving.prefill.emit", "serving.decode.build",
               "serving.decode.dispatch", "serving.decode.fetch",
               "serving.decode.emit"]
TAIL_LEAVES = ["serving.tail_prefill.dispatch", "serving.tail_prefill.fetch",
               "serving.tail_prefill.emit"]
COUNTS = ("rows", "batch_rows", "bucket", "prompt_tokens", "padded_tokens")


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt_config("gpt-tiny", max_position_embeddings=128,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(7)
    model = build_gpt(cfg)
    model.eval()
    return model, cfg


def _host_events(trace_dir):
    """[(name, start_ns, end_ns, stats, line)] of the newest xplane."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for p in ProfileData.from_file(path).planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats),
                            line.name))
    return path, out


def _traced(trace_dir, body):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2          # what benchmark/run.py sets
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_events(str(trace_dir))


def _run(model, cfg, tmp, prompts, params=None, **engine_kw):
    """One traced stretch of an engine: what the profiler, the span ring,
    the flight ring and `Engine.stats()` each saw of it (`params`: each
    prompt's sampling arguments; greedy without)."""
    params = params or [{}] * len(prompts)
    eng = Engine(model, max_slots=4, max_len=64, max_queue=32, **engine_kw)
    try:
        # build the pool and compile outside the stretch under test
        eng.submit(prompts[0], max_new_tokens=3).result(timeout=300)
        if engine_kw.get("prefix_cache"):
            eng.submit(prompts[0], max_new_tokens=3).result(timeout=300)
        time.sleep(0.1)
        trace.clear()
        r = types.SimpleNamespace()
        flight0 = _flight_mark()
        stats0 = eng.stats()

        def body():
            # gpt-tiny decodes in ~5 ms on the CPU, where the turns between
            # two phases (a few us each, more on a busy host) would be a
            # twentieth of it: stretch a step to the ~30 ms it has on a chip
            with faults.inject("serving.decode", mode="delay", seconds=0.02,
                               times=None):
                hs = [eng.submit(p, max_new_tokens=5, **kw)
                      for p, kw in zip(prompts, params)]
                for h in hs:
                    h.result(timeout=300)
            # the last result returns from inside the last iteration: let the
            # scheduler close it before the profiler stops, or a busy host
            # leaves the ring one iteration the trace lacks
            time.sleep(0.1)
        r.path, r.events = _traced(tmp, body)
        stats1 = eng.stats()
        r.delta = {k: stats1[k] - stats0[k]
                   for k in ("prefill_batches", "prefill_waves",
                             "prefill_tokens",
                             "prefill_padded_tokens", "decode_steps",
                             "decode_kv_live_positions",
                             "decode_kv_read_positions",
                             "decode_sampled_steps", "decode_topk_steps")}
        r.flight_spans = _span_begins_since(flight0)
        r.ring = trace.spans()
        r.prefill_batch = eng.prefill_batch
        return r
    finally:
        eng.shutdown()


@pytest.fixture(scope="module")
def cold(tiny_gpt, tmp_path_factory):
    model, cfg = tiny_gpt
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, rs.randint(4, 20))
               for _ in range(7)]
    return _run(model, cfg, tmp_path_factory.mktemp("cold"), prompts)


@pytest.fixture(scope="module")
def hits(tiny_gpt, tmp_path_factory):
    """The prefix-hit path: every prompt starts with a cached row."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(1)
    head = rs.randint(0, cfg.vocab_size, 16)
    prompts = [head] + [np.concatenate(
        [head, rs.randint(0, cfg.vocab_size, rs.randint(2, 9))])
        for _ in range(4)]
    return _run(model, cfg, tmp_path_factory.mktemp("hits"), prompts,
                prefix_cache=True, prefix_block=8)


SAMPLED_PARAMS = [{}, {"temperature": 0.8, "seed": 1},
                  {"temperature": 0.9, "top_k": 8, "seed": 2}, {},
                  {"temperature": 0.7, "top_k": 4, "seed": 3}]


@pytest.fixture(scope="module")
def sampled(tiny_gpt, tmp_path_factory):
    """Greedy, temperature-only and top-k requests side by side, cold and
    through the prefix-hit path."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(2)
    head = rs.randint(0, cfg.vocab_size, 16)
    prompts = [np.concatenate(
        [head, rs.randint(0, cfg.vocab_size, rs.randint(2, 9))])
        for _ in SAMPLED_PARAMS]
    return _run(model, cfg, tmp_path_factory.mktemp("sampled"), prompts,
                params=SAMPLED_PARAMS, prefix_cache=True, prefix_block=8)


def _flight_mark() -> int:
    """The newest flight event's seq: `_span_begins_since` reads on from
    here (an index into the ring would stand still once the ring is full)."""
    return max((e["seq"] for e in flight.events()), default=0)


def _span_begins_since(mark: int) -> list:
    return [e for e in flight.events("span_begin") if e["seq"] > mark]


def _named(events, name):
    return [e for e in events if e[0] == name]


@pytest.mark.parametrize("name", ["serving.iteration", "serving.prefill",
                                  "serving.decode"] + COLD_LEAVES)
def test_every_phase_is_a_host_plane_event(cold, name):
    assert _named(cold.events, name), f"{name} is not in the profiler's trace"


@pytest.mark.parametrize("name", ["serving.tail_prefill",
                                  "serving.prefix_copy"] + TAIL_LEAVES)
def test_tail_path_phases_are_host_plane_events(hits, name):
    assert _named(hits.events, name), f"{name} is not in the profiler's trace"


def _leaves_of(events, root, leaf_names):
    line = root[4]
    return sorted((e for e in events
                   if e[4] == line and e[0] in leaf_names and
                   root[1] <= e[1] and e[2] <= root[2]), key=lambda e: e[1])


PARENTS = {"serving.iteration", "serving.prefill", "serving.tail_prefill",
           "serving.decode"}


def _is_work(name: str) -> bool:
    """A host-plane event that is the scheduler's own doing: a jit call, a
    transfer to or from the device, a compile, a span of the program."""
    return name not in PARENTS and (
        name.startswith(("PjitFunction", "DevicePut", "serving.", "compile",
                         "np.asarray")) or "Await" in name)


@pytest.mark.parametrize("run,leaf_names", [
    ("cold", COLD_LEAVES), ("hits", COLD_LEAVES + TAIL_LEAVES)])
def test_leaves_tile_each_iteration(run, leaf_names, request):
    """Every instant of an iteration lies in one leaf.  Asserted on the
    leaves' bounds: they follow one another inside their root without an
    overlap, and every jit call, transfer and named step the scheduler
    thread made in the iteration (`_is_work`) lies inside one of them — no
    share of wall time, which a busy host's turns between two phases can
    move at will (the runtime's own bookkeeping between two leaves, a
    buffer freed as a frame is left, is nobody's phase)."""
    r = request.getfixturevalue(run)
    roots = _named(r.events, "serving.iteration")
    busy = [it for it in roots
            if _leaves_of(r.events, it, {"serving.decode.dispatch"})]
    assert len(busy) >= 3
    for it in busy:
        leaves = _leaves_of(r.events, it, set(leaf_names))
        assert it[1] <= leaves[0][1] and leaves[-1][2] <= it[2]
        for a, b in zip(leaves, leaves[1:]):
            assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"
        for e in r.events:
            if (e[4] == it[4] and it[1] <= e[1] and e[2] <= it[2] and
                    _is_work(e[0]) and e[0] not in leaf_names):
                assert any(lf[1] <= e[1] and e[2] <= lf[2] for lf in leaves), \
                    f"{e[0]} runs outside every leaf of its iteration"
    # a wave with a prefill has all of prepare, fetch and emit, in order
    with_prefill = [it for it in busy if _leaves_of(
        r.events, it, {"serving.prefill.fetch", "serving.tail_prefill.fetch"})]
    assert with_prefill
    order = [e[0] for e in _leaves_of(r.events, with_prefill[0],
                                      set(leaf_names))]
    assert order[:3] == ["serving.sweep", "serving.admit",
                         "serving.admit.wave"]
    # the look-ahead's order: an iteration dispatches the next decode step
    # and then fetches and emits the one before it — where one is in flight
    # (the first step behind an idle engine has none before it)
    tails = [[e[0] for e in _leaves_of(r.events, it, set(leaf_names))][-4:]
             for it in busy]
    assert all(t[-2:] == ["serving.decode.build", "serving.decode.dispatch"]
               or t == ["serving.decode.build", "serving.decode.dispatch",
                        "serving.decode.fetch", "serving.decode.emit"]
               for t in tails), tails
    assert sum(len(t) == 4 and t[-1] == "serving.decode.emit"
               for t in tails) >= 2
    # the step a dispatch sends out is emitted one dispatch later: the emit
    # of an iteration is of the step before the one it dispatched
    for it in busy:
        d, = _leaves_of(r.events, it, {"serving.decode.dispatch"})
        for e in _leaves_of(r.events, it, {"serving.decode.emit"}):
            assert e[3]["step"] == d[3]["step"] - 1
    steps = [e[3]["step"] for e in sorted(
        _named(r.events, "serving.decode.emit"), key=lambda e: e[1])]
    assert steps == list(range(steps[0], steps[0] + len(steps)))


def test_prefill_dispatch_counts_agree_with_stats(cold):
    """ISSUE 30: the prefill program has one row, so every dispatch is one
    request at the bucket of its own prompt (`rows == batch_rows == 1`
    whatever `prefill_batch` is: it bounds a wave, not a shape), and a
    wave of n requests is n dispatches: `prefill_batches` counts the
    dispatches, `prefill_waves` the waves."""
    from paddle_tpu.serving.engine import _bucket
    evs = _named(cold.events, "serving.prefill.dispatch")
    assert len(evs) == cold.delta["prefill_batches"] == 7   # one a request
    assert cold.prefill_batch == 4
    waves = _named(cold.events, "serving.prefill")
    assert len(waves) == cold.delta["prefill_waves"]
    assert 2 <= len(waves) <= len(evs)
    assert sum(w[3]["n"] for w in waves) == len(evs)
    assert all(1 <= w[3]["n"] <= cold.prefill_batch for w in waves)
    for e in evs:
        assert set(COUNTS) <= set(e[3]), e[3]
        assert e[3]["rows"] == e[3]["batch_rows"] == 1
        assert e[3]["padded_tokens"] == e[3]["bucket"] == _bucket(
            e[3]["prompt_tokens"], 8, 64)       # lowest bucket, max_len
    assert (sum(e[3]["prompt_tokens"] for e in evs) ==
            cold.delta["prefill_tokens"] > 0)
    assert (sum(e[3]["padded_tokens"] for e in evs) ==
            cold.delta["prefill_padded_tokens"] >
            cold.delta["prefill_tokens"])
    dec = _named(cold.events, "serving.decode.dispatch")
    assert len(dec) == cold.delta["decode_steps"]
    assert all(1 <= e[3]["active"] <= 4 for e in dec)
    roots = _named(cold.events, "serving.iteration")
    assert all({"active", "queued"} <= set(e[3]) for e in roots)


@pytest.mark.parametrize("run", ["cold", "hits"])
def test_decode_dispatch_kv_counts_agree_with_stats(run, request):
    """ISSUE 26: `kv_live` / `kv_read` on every decode dispatch, the same
    sums in `Engine.stats()`; on the CPU the XLA read streams every row of
    the pool whole (4 slots + scratch, 64 positions each).  ISSUE 27: both
    are sums over the layers (gpt-tiny has 2)."""
    r = request.getfixturevalue(run)
    dec = _named(r.events, "serving.decode.dispatch")
    assert len(dec) == r.delta["decode_steps"] > 0
    for e in dec:
        assert e[3]["kv_read"] == 2 * 5 * 64
        assert (2 * e[3]["active"] <= e[3]["kv_live"]
                <= 2 * e[3]["active"] * 64)
    assert (sum(e[3]["kv_live"] for e in dec) ==
            r.delta["decode_kv_live_positions"])
    assert (sum(e[3]["kv_read"] for e in dec) ==
            r.delta["decode_kv_read_positions"])


@pytest.mark.parametrize("run", ["cold", "hits", "sampled"])
def test_dispatch_sampling_counts_agree_with_stats(run, request):
    """ISSUE 28: `sampled` / `topk` on every dispatch phase are the live
    rows that draw and those of them that mask; the decode steps in which
    either is above 0 are `Engine.stats()` `decode_sampled_steps` /
    `decode_topk_steps` — the steps in which the device sampler leaves its
    greedy branch."""
    r = request.getfixturevalue(run)
    dec = _named(r.events, "serving.decode.dispatch")
    assert len(dec) == r.delta["decode_steps"] > 0
    for e in dec:
        assert 0 <= e[3]["topk"] <= e[3]["sampled"] <= e[3]["active"]
    assert (sum(e[3]["sampled"] > 0 for e in dec) ==
            r.delta["decode_sampled_steps"])
    assert (sum(e[3]["topk"] > 0 for e in dec) ==
            r.delta["decode_topk_steps"])
    pre = (_named(r.events, "serving.prefill.dispatch") +
           _named(r.events, "serving.tail_prefill.dispatch"))
    want = SAMPLED_PARAMS if run == "sampled" else []
    assert (sum(e[3]["sampled"] for e in pre) ==
            sum("temperature" in kw for kw in want))
    assert sum(e[3]["topk"] for e in pre) == sum("top_k" in kw for kw in want)
    if run == "sampled":
        assert _named(r.events, "serving.tail_prefill.dispatch")
        assert 0 < r.delta["decode_topk_steps"] <= \
            r.delta["decode_sampled_steps"] <= r.delta["decode_steps"]
    else:
        assert r.delta["decode_sampled_steps"] == 0


def test_tail_dispatch_counts_agree_with_stats(hits):
    evs = (_named(hits.events, "serving.tail_prefill.dispatch") +
           _named(hits.events, "serving.prefill.dispatch"))
    assert len(evs) == hits.delta["prefill_batches"]
    assert (sum(e[3]["prompt_tokens"] for e in evs) ==
            hits.delta["prefill_tokens"])
    assert (sum(e[3]["padded_tokens"] for e in evs) ==
            hits.delta["prefill_padded_tokens"])
    # the copy program's dispatch is a step inside the tail dispatch
    copy = _named(hits.events, "serving.prefix_copy")[0]
    assert any(d[1] <= copy[1] and copy[2] <= d[2]
               for d in _named(hits.events, "serving.tail_prefill.dispatch"))


def test_span_ring_holds_the_same_spans_with_parents(cold):
    ring = {r["id"]: r for r in cold.ring}
    by_name = {}
    for r in cold.ring:
        by_name.setdefault(r["name"], []).append(r)
    for name in ["serving.iteration", "serving.prefill", "serving.decode"] \
            + COLD_LEAVES:
        in_trace = len(_named(cold.events, name))
        if name in ("serving.iteration", "serving.sweep", "serving.admit"):
            # the profiler sees the empty turns too, the ring drops them
            assert (cold.delta["decode_steps"] <= len(by_name[name])
                    <= in_trace), name
        else:
            assert len(by_name[name]) == in_trace, name
    parents = {"serving.sweep": "serving.iteration",
               "serving.admit": "serving.iteration",
               "serving.admit.wave": "serving.iteration",
               "serving.prefill": "serving.iteration",
               "serving.decode": "serving.iteration",
               "serving.prefill.dispatch": "serving.prefill",
               "serving.prefill.fetch": "serving.prefill",
               "serving.prefill.emit": "serving.prefill",
               "serving.decode.build": "serving.decode",
               "serving.decode.dispatch": "serving.decode",
               "serving.decode.fetch": "serving.decode",
               "serving.decode.emit": "serving.decode"}
    for child, parent in parents.items():
        for r in by_name[child]:
            assert ring[r["parent_id"]]["name"] == parent, (child, r)
    assert all(r["parent_id"] is None for r in by_name["serving.iteration"])
    d = by_name["serving.prefill.dispatch"][0]["attrs"]
    assert set(COUNTS) <= set(d)
    # chrome export still carries them
    names = {e["name"] for e in trace.chrome_events()}
    assert "serving.decode.fetch" in names or not trace.spans()


def test_flight_ring_gets_no_more_span_events(cold):
    """Per iteration the flight ring receives what it received before the
    phases: one begin (and one end) per `serving.prefill` and
    `serving.decode`, none for a phase."""
    names = [e["name"] for e in cold.flight_spans
             if str(e["name"]).startswith("serving.")]
    assert set(names) <= {"serving.prefill", "serving.decode"}, set(names)
    assert len(names) <= (cold.delta["prefill_batches"] +
                          cold.delta["decode_steps"] + 1)
    assert names.count("serving.decode") >= cold.delta["decode_steps"] - 1


def test_idle_engine_leaves_the_span_ring_alone(tiny_gpt):
    """No profiler session: nothing raises; an engine with nothing to do
    turns its loop every 20 ms and records none of those turns."""
    model, cfg = tiny_gpt
    eng = Engine(model, max_slots=2, max_len=32)
    try:
        eng.submit(np.arange(5), max_new_tokens=3).result(timeout=300)
        time.sleep(0.1)
        trace.clear()
        time.sleep(0.5)                       # ~25 empty turns
        idle = trace.spans()
        assert not idle, [r["name"] for r in idle]
        eng.submit(np.arange(6), max_new_tokens=2).result(timeout=300)
        time.sleep(0.1)
        names = [r["name"] for r in trace.spans()]
        assert "serving.iteration" in names and "serving.decode.emit" in names
        # the one wait a submit cut short is kept
        assert names.count("serving.wait") <= 2
    finally:
        eng.shutdown()


@pytest.mark.parametrize("n_new", [1, 2, 6])
def test_a_token_reaches_its_stream_once_the_next_program_is_dispatched(
        tiny_gpt, n_new):
    """The scheduler holds a step's stream callbacks back until the next
    program is on the device (their consumers then wake beside the device's
    work, not beside the dispatch) — also with a decode step queued behind
    the emitting one (ISSUE 34): a token arrives at the tail of a dispatch
    phase, and a request's last ones (two, where the step behind its last
    was parked ahead and nothing was dispatched) before it finishes, all in
    order and all before `result()` returns."""
    model, cfg = tiny_gpt
    eng = Engine(model, max_slots=2, max_len=32)
    seen = []
    try:
        h = eng.submit(np.arange(5), max_new_tokens=n_new, stream=lambda t:
                       seen.append((t, trace.current_span().name)))
        toks = h.result(timeout=300)
        assert [t for t, _ in seen] == list(toks) and len(toks) == n_new
        under = [name for _, name in seen]
        assert all(n.endswith(".dispatch") for n in under[:-2]), under
        assert under[-1] == ("serving.prefill.emit" if n_new == 1
                             else "serving.decode.emit"), under
        assert under[-2:-1] in ([], ["serving.decode.dispatch"],
                                ["serving.decode.emit"]), under
        assert not eng._held_streams
    finally:
        eng.shutdown()


def test_span_is_a_profiler_annotation(tmp_path):
    def body():
        with trace.span("t25.outer", step=3, dir="/tmp/x", ok=True,
                        signature="f32[8,128]" * 20, shape=(1, 2)) as sp:
            sp.attrs["late"] = 1
            with trace.phase("t25.inner", rows=2):
                time.sleep(0.002)
    _, events = _traced(tmp_path, body)
    (outer,), (inner,) = _named(events, "t25.outer"), _named(events,
                                                            "t25.inner")
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert inner[2] - inner[1] >= 2_000_000
    assert outer[3] == {"step": 3, "dir": "/tmp/x", "ok": 1} or \
        outer[3] == {"step": 3, "dir": "/tmp/x", "ok": True}, outer[3]
    assert inner[3] == {"rows": 2}
    rec = trace.spans("t25.outer")[-1]
    assert rec["attrs"]["late"] == 1 and "signature" in rec["attrs"]


def test_phase_keeps_out_of_the_flight_ring_and_can_be_dropped():
    trace.clear()
    n0 = _flight_mark()
    with trace.phase("t25.root") as root:
        with trace.phase("t25.leaf"):
            pass
        with trace.span("t25.loud"):         # a span under a phase root
            pass
        assert not trace.spans("t25.leaf"), "held until the root closes"
        assert [s["name"] for s in trace.open_spans()[root.tid]] == [
            "t25.root"]
    assert [r["name"] for r in trace.spans()] == ["t25.leaf", "t25.loud",
                                                  "t25.root"]
    assert trace.spans("t25.leaf")[0]["parent_id"] == root.id
    begun = [e["name"] for e in _span_begins_since(n0)]
    assert begun == ["t25.loud"]
    trace.clear()
    with trace.phase("t25.root") as root:
        with trace.phase("t25.leaf"):
            pass
        root.drop()
    with trace.phase("t25.alone") as alone:
        alone.drop()
    assert trace.spans() == []
    with pytest.raises(ValueError):
        with trace.phase("t25.root"):
            with trace.phase("t25.leaf"):
                raise ValueError("boom")
    recs = trace.spans()
    assert [r["name"] for r in recs] == ["t25.leaf", "t25.root"]
    assert all(r["attrs"]["status"] == "error" for r in recs)
    assert trace.current_span() is None

    @trace.phase("t25.decorated")
    def f():
        return 1
    assert f() == 1 and trace.spans("t25.decorated")
    assert not [e for e in _span_begins_since(n0)
                if e["name"] == "t25.decorated"]


def test_readers_count_padding_from_the_trace(cold):
    from benchmark import span_readers
    obs = {"span_trace_path": cold.path}
    got = span_readers.stat_complement_pct(
        obs, "serving.prefill.dispatch", "prompt_tokens", "padded_tokens")
    want = 100.0 * (1 - cold.delta["prefill_tokens"] /
                    cold.delta["prefill_padded_tokens"])
    assert got == pytest.approx(want)
    # no device plane on the CPU: the idle readers have nothing to read
    assert span_readers.idle_under(obs, ["serving.decode.emit"]) is None
    s = span_readers.split(cold.path)
    assert s["count"]["serving.decode.fetch"] == cold.delta["decode_steps"]


def test_readers_count_dead_kv_from_the_trace(cold):
    """`engine.decode_kv_dead_share.*`: the metric files' reader and args."""
    import json
    from benchmark import span_readers
    for cell in ("steady", "saturated"):
        with open(os.path.join(
                os.path.dirname(span_readers.__file__), "metrics",
                f"engine.decode_kv_dead_share.{cell}.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "span_readers.stat_complement_pct"
        got = span_readers.stat_complement_pct(
            {"span_trace_path": cold.path}, **spec["args"])
        assert got == pytest.approx(
            100.0 * (1 - cold.delta["decode_kv_live_positions"] /
                     cold.delta["decode_kv_read_positions"]))
        assert 50.0 < got < 100.0
