"""The scheduler's look-ahead of one decode step (ISSUE 34).

With the device sampler and one token a step, the engine dispatches decode
step n+1 from step n's tokens on the device and only then fetches and emits
step n.  Nothing of a result may change by it: a depth-1 engine gives the
tokens and log-probabilities of a depth-0 engine, an EOS the host could not
know of discards exactly one row, and a slot handed on between two steps is
written by its new owner after the stale step, never before.

The depth is what the engine sees of its own build and no argument: a test
that wants the depth-0 side of a comparison sets `_lookahead` on an engine
it has not started.
"""
import time
from concurrent.futures import CancelledError

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import build_decoder, build_gpt, gpt_config
from paddle_tpu.observability import registry, trace
from paddle_tpu.serving import (AdapterRegistry, DeadlineExceededError, Engine,
                                EngineClosedError, make_lora)
from paddle_tpu.serving.engine import (SERVING_DECODE_LOOKAHEAD_STEPS,
                                       SERVING_DECODE_OVERSHOOT_ROWS)
from paddle_tpu.testing import faults


@pytest.fixture(scope="module")
def gpt():
    cfg = gpt_config("gpt-tiny", max_position_embeddings=128,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(7)
    model = build_gpt(cfg)
    model.eval()
    return model, cfg.vocab_size


@pytest.fixture(scope="module")
def smallthinker():
    paddle.seed(3)
    model = build_decoder("smallthinker-tiny")
    model.eval()
    return model, model.decoder.config.vocab_size


@pytest.fixture
def model(request, gpt, smallthinker):
    return {"gpt": gpt, "smallthinker": smallthinker}[request.param]


def _engine(model, depth, **kw):
    """An unstarted engine held to `depth` steps of look-ahead (1 is what
    the engine chooses for itself here; 0 is the comparison's other side)."""
    kw = {"max_slots": 3, "max_len": 64, "max_queue": 64, **kw}
    eng = Engine(model, auto_start=False, **kw)
    assert eng._lookahead == 1
    eng._lookahead = depth
    return eng


def _settled(eng, timeout=60.0):
    """Wait until the scheduler has fetched its last step and stands idle."""
    t_end = time.perf_counter() + timeout
    while time.perf_counter() < t_end:
        if (eng._flying is None and not eng.slots_in_use() and
                not eng.queue_depth()):
            return eng.stats()
        time.sleep(0.005)
    raise AssertionError("the engine never came to rest")


def _mixed(vocab, n=9, seed=0):
    """Prompts of several buckets and budgets, more requests than slots."""
    rs = np.random.RandomState(seed)
    return [(rs.randint(0, vocab, rs.randint(3, 30)).astype(np.int64),
             int(rs.randint(1, 12))) for _ in range(n)]


def _serve(eng, work, params=None, eos=None):
    """Stage `work` on the unstarted engine, run it, return what each
    request gave: (tokens, logprobs, streamed)."""
    params = params or [{}] * len(work)
    streams = [[] for _ in work]
    hs = [eng.submit(p, max_new_tokens=n, eos_token_id=eos,
                     stream=s.append, **kw)
          for (p, n), kw, s in zip(work, params, streams)]
    eng.start()
    out = []
    for h, s in zip(hs, streams):
        toks = h.result(timeout=300)
        out.append(([int(t) for t in toks], h.logprobs, list(s)))
    return out


DRAWN = [{"temperature": 0.8, "seed": 1}, {},
         {"temperature": 0.9, "top_k": 8, "seed": 2},
         {"temperature": 0.7, "top_k": 4, "seed": 3}, {},
         {"temperature": 1.1, "seed": 4}, {"temperature": 0.6, "top_k": 2,
                                           "seed": 5},
         {}, {"temperature": 0.9, "seed": 6}]


@pytest.mark.parametrize("model", ["gpt", "smallthinker"], indirect=True)
@pytest.mark.parametrize("params", [None, DRAWN], ids=["greedy", "drawn"])
def test_depth_one_gives_depth_zeros_tokens_and_logprobs(model, params):
    """The same requests through both depths: every token, every
    log-probability, every stream; one decode signature on either side."""
    net, vocab = model
    work = _mixed(vocab)
    got = {}
    for depth in (0, 1):
        eng = _engine(net, depth)
        try:
            got[depth] = _serve(eng, work, params)
            st = _settled(eng)
        finally:
            eng.shutdown()
        assert st["decode_compiles"] == 1
        assert st["completed"] == len(work)
        assert st["tokens"] == sum(len(t) for t, _, _ in got[depth])
        assert (st["decode_lookahead_steps"] > 0) == (depth == 1)
        if depth == 0:
            assert st["decode_overshoot_rows"] == 0
    for (t0, l0, s0), (t1, l1, s1), (_, n) in zip(got[0], got[1], work):
        assert t0 == t1 and len(t1) == n
        assert l0 == l1                         # bitwise: the same program
        assert s0 == t0 and s1 == t1


POOLS = {"paged": {"paged_kv": True, "page_size": 4},
         "paged-prefix": {"paged_kv": True, "page_size": 4,
                          "prefix_cache": True, "prefix_block": 4},
         "dense-prefix": {"prefix_cache": True, "prefix_block": 4},
         "int8": {"kv_dtype": "int8"},
         "paged-kernel": {"paged_kv": True, "page_size": 8,
                          "decode_kernel": "pallas"}}


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_every_pool_keeps_the_look_ahead_and_its_results(gpt, pool):
    """Paged pools, the prefix cache on either layout and int8 KV: their
    per-step operands are host-known a step ahead, so they run at depth 1
    and give depth 0's tokens, EOS finishes (overshoots) among them."""
    net, vocab = gpt
    rs = np.random.RandomState(5)
    head = rs.randint(0, vocab, 12).astype(np.int64)
    work = [(np.concatenate([head, rs.randint(0, vocab, rs.randint(1, 9))]),
             int(rs.randint(2, 10))) for _ in range(8)]
    # gpt-tiny's greedy tokens repeat, so the requests draw; an EOS that
    # ends one of them early, by a decode step and with budget left
    hot = [{"temperature": 1.0, "seed": 20 + i} for i in range(len(work))]
    probe = _engine(net, 0, **POOLS[pool])
    try:
        free = _serve(probe, work, hot)
    finally:
        probe.shutdown()
    eos = _early_eos([t for t, _, _ in free])
    got = {}
    for depth in (0, 1):
        eng = _engine(net, depth, **POOLS[pool])
        try:
            got[depth] = _serve(eng, work, hot, eos=eos)
            st = _settled(eng)
        finally:
            eng.shutdown()              # page and pin leak checks run here
        assert st["decode_compiles"] == 1
        if depth == 1:
            assert st["decode_lookahead_steps"] > 0
            assert st["decode_overshoot_rows"] > 0
            if "prefix_cache" in POOLS[pool]:
                assert st["prefix_hits"] > 0
    assert [t for t, _, _ in got[0]] == [t for t, _, _ in got[1]]
    for (_, l0, _), (_, l1, _) in zip(got[0], got[1]):
        if "prefix_cache" in POOLS[pool]:
            # which requests find a retained row hangs on who had ended at
            # their admission, and that differs by a turn between the
            # depths; a hit's first token comes from the tail prefill, a
            # miss's from the flash prefill: the same value, another
            # rounding (the retained row itself is compared bitwise below)
            np.testing.assert_allclose(l0, l1, rtol=0, atol=1e-5)
        else:
            assert l0 == l1
    assert any(t[-1] == eos and len(t) < n
               for (t, _, _), (_, n) in zip(got[1], work))


def test_adapters_keep_the_look_ahead(gpt):
    """A LoRA bank row is a per-slot operand like any other: depth 1, same
    tokens as depth 0, adapter and base rows side by side."""
    net, vocab = gpt
    cfg = net.gpt.config
    work = _mixed(vocab, n=6, seed=3)
    names = ["a", None, "b", "a", None, "b"]
    got = {}
    for depth in (0, 1):
        reg = AdapterRegistry(net, max_resident=2, max_rank=8)
        for i, name in enumerate(["a", "b"]):
            reg.register(make_lora(cfg, rank=2 + 2 * i, seed=10 + i,
                                   name=name, std=0.2))
        eng = _engine(net, depth, adapters=reg)
        try:
            got[depth] = _serve(eng, work,
                                [{"adapter": n} if n else {} for n in names])
            st = _settled(eng)
        finally:
            eng.shutdown()
        assert st["decode_compiles"] == 1
        assert (st["decode_lookahead_steps"] > 0) == (depth == 1)
    assert got[0] == got[1]


HOT = {"temperature": 1.0, "seed": 9}   # gpt-tiny's greedy tokens repeat


def _first_fresh(tokens, start=2):
    """Index >= start, and not the last, of the first token that occurs
    nowhere before it: as the EOS it ends the request there, by a decode
    step and with budget left."""
    for i in range(start, len(tokens) - 1):
        if tokens[i] not in tokens[:i]:
            return i
    raise AssertionError(f"no fresh token from {start} on in {tokens}")


def _early_eos(runs):
    """A token that, as the EOS, ends one of `runs` early (`_first_fresh`)."""
    for tokens in runs:
        try:
            return tokens[_first_fresh(tokens, start=1)]
        except AssertionError:
            continue
    raise AssertionError(f"no run ends early in {runs}")


def test_an_eos_discards_the_step_behind_it(gpt):
    """An EOS in step n is known at emit n, after step n+1 went out with the
    row live: that one row is computed and thrown away — not streamed, not
    in `tokens` or `logprobs`, not in `stats()["tokens"]`."""
    net, vocab = gpt
    prompt = np.arange(4, 11).astype(np.int64)
    free = _tokens_of(net, prompt, 10, **HOT)
    k = _first_fresh(free)              # token k ends the request
    eng = _engine(net, 1, max_slots=1)
    try:
        (toks, lps, seen), = _serve(eng, [(prompt, 10)], [HOT], eos=free[k])
        st = _settled(eng)
        time.sleep(0.05)
        assert seen == toks == free[:k + 1] and len(lps) == k + 1
    finally:
        eng.shutdown()
    # the prefill gave token 0, decode step j token j: step k+1 overshot
    assert st["decode_steps"] == k + 1
    assert st["decode_overshoot_rows"] == 1
    assert st["decode_lookahead_steps"] == k
    assert st["tokens"] == k + 1 and st["completed"] == 1
    assert st["decode_compiles"] == 1


def test_a_budget_finish_parks_the_row_a_step_ahead(gpt):
    """`max_new_tokens` is known ahead: the step behind a request's last
    does not compute its row, and no step is dispatched for no row."""
    net, vocab = gpt
    trace.clear()
    eng = _engine(net, 1, max_slots=2)
    try:
        a = np.arange(3, 9).astype(np.int64)
        b = np.arange(20, 31).astype(np.int64)
        (ta, _, _), (tb, _, _) = _serve(eng, [(a, 3), (b, 6)])
        st = _settled(eng)
    finally:
        eng.shutdown()
    assert (len(ta), len(tb)) == (3, 6)
    # a rides steps 1-2, b steps 1-5; nothing goes out behind step 5
    dispatched = trace.spans("serving.decode.dispatch")
    assert [d["attrs"]["active"] for d in dispatched] == [2, 2, 1, 1, 1]
    assert [d["attrs"]["step"] for d in dispatched] == [1, 2, 3, 4, 5]
    assert st["decode_steps"] == 5 and st["decode_overshoot_rows"] == 0
    assert st["decode_lookahead_steps"] == 4
    assert st["tokens"] == 9
    # every emit names the step it emits, one dispatch behind
    emits = trace.spans("serving.decode.emit")
    assert [e["attrs"]["step"] for e in emits] == [1, 2, 3, 4, 5]
    by_step = {d["attrs"]["step"]: d for d in dispatched}
    for e in emits[:-1]:
        nxt = by_step[e["attrs"]["step"] + 1]
        assert nxt["ts"] + nxt["dur"] <= e["ts"], "emit n before dispatch n+1"


def _tokens_of(net, prompt, n, **kw):
    ref = _engine(net, 0, max_slots=1)
    try:
        (toks, _, _), = _serve(ref, [(prompt, n)], [kw] if kw else None)
    finally:
        ref.shutdown()
    return toks


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_request_ended_from_outside_with_a_step_unfetched(gpt, how):
    """`cancel` and a deadline end a request in the sweep while a step that
    computes its row is on the device: the row is discarded, the request's
    tokens stand still, and the slot's next owner decodes as if alone."""
    net, vocab = gpt
    prompt = np.arange(5, 14).astype(np.int64)
    nxt = np.arange(30, 36).astype(np.int64)
    want = _tokens_of(net, nxt, 5)
    eng = _engine(net, 1, max_slots=1)
    seen = []
    try:
        _serve(eng, [(nxt, 2)])                 # compile outside the deadline
        st0 = _settled(eng)
        # a step long enough that the request is mid-flight when it ends
        with faults.inject("serving.decode", mode="delay", seconds=0.03,
                           times=None):
            h = eng.submit(prompt, max_new_tokens=50, stream=seen.append,
                           deadline_s=0.4 if how == "deadline" else None)
            h2 = eng.submit(nxt, max_new_tokens=5)
            eng.start()
            if how == "cancel":
                while len(h.tokens) < 3:
                    time.sleep(0.002)
                assert eng._flying is not None
                assert h.cancel()
            err = h.exception(timeout=60)
            assert isinstance(err, CancelledError if how == "cancel"
                              else DeadlineExceededError)
            ended_with = h.tokens
            assert 1 <= len(ended_with) < 50
            got = list(h2.result(timeout=60))
        st = _settled(eng)
        assert h.tokens == ended_with == seen   # nothing after the end
        assert got == want
        assert st["decode_overshoot_rows"] - st0["decode_overshoot_rows"] >= 1
        assert st["tokens"] - st0["tokens"] == len(ended_with) + len(got)
        assert st["slot_reuses"] >= 2 and st["decode_compiles"] == 1
    finally:
        eng.shutdown()


def test_shutdown_with_a_step_unfetched(gpt):
    """`shutdown` stops the loop with a step in flight: it is dropped, its
    requests fail closed and receive nothing more."""
    net, vocab = gpt
    eng = _engine(net, 1, max_slots=2)
    seen = []
    with faults.inject("serving.decode", mode="delay", seconds=0.02,
                       times=None):
        h = eng.submit(np.arange(6), max_new_tokens=50, stream=seen.append)
        eng.start()
        while len(h.tokens) < 3:
            time.sleep(0.002)
        eng.shutdown()
    assert isinstance(h.exception(timeout=10), EngineClosedError)
    assert eng._flying is None
    n = len(h.tokens)
    time.sleep(0.1)
    assert len(h.tokens) == n == len(seen) and 3 <= n < 50
    assert not eng.health()["scheduler_running"]


def test_drain_fetches_the_last_step(gpt):
    """`drain` returns on an engine at rest: every request whole, the last
    (overshooting) step fetched before the scheduler waits."""
    net, vocab = gpt
    eng = _engine(net, 1, max_slots=2)
    try:
        hs = [eng.submit(np.arange(3 + i, 9 + i), max_new_tokens=4 + i)
              for i in range(4)]
        eng.start()
        assert eng.drain(deadline_s=120)
        assert [len(h.result(timeout=1)) for h in hs] == [4, 5, 6, 7]
        st = _settled(eng, timeout=5)
        assert st["completed"] == 4 and st["tokens"] == 22
    finally:
        eng.shutdown()


def test_an_admission_between_two_steps_lands_in_a_just_freed_slot(gpt):
    """One slot, three requests, the first two ended by an EOS: each
    successor is admitted into the row its predecessor's overshooting step
    still writes, and prefills over it in the device's order."""
    net, vocab = gpt
    rs = np.random.RandomState(11)
    work = [(rs.randint(0, vocab, n).astype(np.int64), 9) for n in (7, 12, 5)]
    hot = [dict(HOT, seed=40 + i) for i in range(3)]
    probe = _engine(net, 0, max_slots=1)
    try:
        free = [t for t, _, _ in _serve(probe, work, hot)]
    finally:
        probe.shutdown()
    eos = free[0][_first_fresh(free[0])]
    got = {}
    for depth in (0, 1):
        eng = _engine(net, depth, max_slots=1)
        try:
            got[depth] = _serve(eng, work, hot, eos=eos)
            st = _settled(eng)
        finally:
            eng.shutdown()
        assert st["slot_reuses"] == 2 and st["decode_compiles"] == 1
    assert got[0] == got[1]
    assert got[1][0][0][-1] == eos and len(got[1][0][0]) < 9
    assert st["decode_overshoot_rows"] >= 1


def test_a_row_retained_after_an_overshoot_is_read_as_at_depth_zero(gpt):
    """The prefix cache retains an EOS-ended request's row; the overshooting
    step's one write lands behind the retained positions.  The retained
    positions are bitwise depth 0's, and so is what a hit reads of them."""
    net, vocab = gpt
    prompt = np.arange(8, 24).astype(np.int64)
    free = _tokens_of(net, prompt, 12, **HOT)
    k = _first_fresh(free, start=4)
    rows, hits = {}, {}
    for depth in (0, 1):
        eng = _engine(net, depth, max_slots=2, prefix_cache=True,
                      prefix_block=4)
        try:
            (toks, _, _), = _serve(eng, [(prompt, 12)], [HOT], eos=free[k])
            st = _settled(eng)
            assert toks == free[:k + 1]
            assert st["prefix_inserts"] == 1
            assert st["decode_overshoot_rows"] == depth
            (slot, entry), = eng._pool.cached().items()
            n = entry.n
            assert n == len(prompt) + k       # prompt + all but the last
            rows[depth] = [np.asarray(a[slot, :n]).copy()
                           for a in eng._kv_pool.k + eng._kv_pool.v]
            # a second turn over the retained row and four tokens more
            turn = np.concatenate([prompt, toks[:-1], [3, 1, 4, 1]])
            h = eng.submit(turn, max_new_tokens=6)
            hits[depth] = (list(h.result(timeout=300)), h.logprobs)
            assert h.prefix_hit and eng.stats()["decode_compiles"] == 1
        finally:
            eng.shutdown()
    for a0, a1 in zip(rows[0], rows[1]):
        np.testing.assert_array_equal(a0, a1)
    assert hits[0] == hits[1]


@pytest.mark.parametrize("kw", [{"sample_on_device": False},
                                {"speculative_k": 3}],
                         ids=["host-sampler", "speculative"])
def test_where_the_next_token_is_the_hosts_the_depth_is_zero(gpt, kw):
    """The host sampler's next token exists only on the host; speculative
    decoding's next lengths hang on the acceptance: the same loop, nothing
    queued, by what the engine sees of its own build."""
    net, vocab = gpt
    eng = Engine(net, max_slots=2, max_len=64, **kw)
    try:
        assert eng._lookahead == 0
        hs = [eng.submit(p, max_new_tokens=n) for p, n in _mixed(vocab, 5)]
        for h in hs:
            h.result(timeout=300)
            assert eng._flying is None
        st = _settled(eng)
    finally:
        eng.shutdown()
    assert st["decode_steps"] > 0
    assert st["decode_lookahead_steps"] == 0
    assert st["decode_overshoot_rows"] == 0
    assert st["decode_compiles"] == 1


def test_the_two_counters_reach_the_registry_and_the_gateway(gpt):
    """`decode_lookahead_steps` / `decode_overshoot_rows` in `stats()`, in
    the registry and in `GET /metrics`."""
    import http.client

    from paddle_tpu.serving.gateway import start_gateway
    net, vocab = gpt
    reg = registry()
    before = {n: (reg.get(n).value() if reg.get(n) is not None else 0.0)
              for n in (SERVING_DECODE_LOOKAHEAD_STEPS,
                        SERVING_DECODE_OVERSHOOT_ROWS)}
    prompt = np.arange(4, 11).astype(np.int64)
    free = _tokens_of(net, prompt, 10, **HOT)
    eng = Engine(net, max_slots=2, max_len=64)
    with start_gateway([eng], own_engines=True) as stack:
        eng.submit(prompt, max_new_tokens=10, **HOT,
                   eos_token_id=free[_first_fresh(free)]).result(timeout=300)
        st = _settled(eng)
        conn = http.client.HTTPConnection("127.0.0.1", stack.port,
                                          timeout=60)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
    assert st["decode_lookahead_steps"] > 0 and st["decode_overshoot_rows"] == 1
    for name, key in ((SERVING_DECODE_LOOKAHEAD_STEPS,
                       "decode_lookahead_steps"),
                      (SERVING_DECODE_OVERSHOOT_ROWS,
                       "decode_overshoot_rows")):
        assert reg.get(name).value() - before[name] == st[key]
        assert name in text


@pytest.mark.parametrize("n_new", [2, 3, 7])
def test_a_token_waits_for_the_next_dispatch_also_with_a_step_queued(gpt,
                                                                     n_new):
    """A step's tokens go to their streams at the tail of the next dispatch,
    as at depth 0, although a program is queued while they are emitted: the
    consumers they wake would otherwise run beside the scheduler's sweep,
    build and dispatch.  Step j's token rides the dispatch of step j+2 (step
    j+1 went out before step j was fetched); a request's last two leave
    inside its last emit, before it finishes."""
    net, vocab = gpt
    eng = Engine(net, max_slots=2, max_len=32)
    seen = []

    def on_token(t):
        sp = trace.current_span()
        seen.append((t, sp.name, sp.attrs.get("step")))
    try:
        h = eng.submit(np.arange(5), max_new_tokens=n_new, stream=on_token)
        toks = list(h.result(timeout=300))
        assert [t for t, _, _ in seen] == toks and len(toks) == n_new
        want = [("serving.decode.dispatch", 1)]         # the prefill's token
        want += [("serving.decode.dispatch", j + 2)
                 for j in range(1, n_new - 2)]
        want += [("serving.decode.emit", n_new - 1)] * min(2, n_new - 1)
        assert [(name, step) for _, name, step in seen] == want
        assert not eng._held_streams
    finally:
        eng.shutdown()
