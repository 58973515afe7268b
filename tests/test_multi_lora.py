"""Multi-LoRA adapter serving tests (ISSUE 12): batched per-slot
adapters, the HBM-resident adapter registry, and int8 base weights.

The contract under test (docs/serving.md "Multi-LoRA serving"):

* adapter id 0 (no adapter) is EXACT — greedy decode on an
  adapter-enabled engine is token-identical to the adapter-free engine;
* each adapter's batched output matches an offline merged-weights
  forward (``W + scale * A @ B`` folded into the QKV projections);
* residency mirrors the prefix cache: pin-while-in-flight refcounts,
  LRU eviction of refs-0 entries, admission-time cold loads, and a
  fully-pinned bank is head-of-line backpressure (queued, not failed);
* typed errors at submit: unknown adapter, rank that can never fit;
* prefix-cache entries are keyed by (adapter, tokens) — tenants never
  share KV across adapters;
* int8 base weights are parity-gated against f32 and halve-or-better
  the stored weight bytes;
* the all-flags-composed config (prefix + speculative + int8/paged KV +
  device sampling + adapters + int8 weights) compiles exactly ONE
  decode signature.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import build_gpt, gpt_config
from paddle_tpu.serving import (AdapterRankError, AdapterRegistry,
                                AdapterShapeError, Engine, LoraAdapter,
                                UnknownAdapterError, make_lora)
from paddle_tpu.serving.adapters import merge_into_qkv
from paddle_tpu.serving.adapters.registry import AdapterResidency


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt_config("gpt-tiny", max_position_embeddings=128,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(7)
    model = build_gpt(cfg)
    model.eval()
    return model, cfg


@pytest.fixture(scope="module")
def adapters(tiny_gpt):
    _, cfg = tiny_gpt
    return {name: make_lora(cfg, rank=2 + 2 * i, seed=10 + i, name=name,
                            std=0.2)
            for i, name in enumerate(["tenant-a", "tenant-b", "tenant-c"])}


def _merged_model(cfg, adapter):
    paddle.seed(7)                      # same init as the tiny_gpt fixture
    m = build_gpt(cfg)
    m.eval()
    merge_into_qkv(m, adapter)
    return m


def _run(engine, prompts, new=6, **kw):
    handles = [engine.submit(p, max_new_tokens=new, **kw) for p in prompts]
    return [h.result(timeout=300) for h in handles]


def _prompts(cfg, n, length=8, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab_size, length).astype(np.int64)
            for _ in range(n)]


# -- units: registry + residency ---------------------------------------------

def test_registry_validation_and_double_register(tiny_gpt):
    model, cfg = tiny_gpt
    reg = AdapterRegistry(model, max_resident=2, max_rank=8)
    ad = make_lora(cfg, rank=4, seed=0, name="x")
    reg.register(ad)
    assert "x" in reg and len(reg) == 1
    # double-register of the same name validates shape: same rank is a
    # weight update, a different rank is a config error
    reg.register(make_lora(cfg, rank=4, seed=9, name="x"))
    with pytest.raises(AdapterShapeError, match="rank"):
        reg.register(make_lora(cfg, rank=2, seed=0, name="x"))
    # wrong layer count / wrong hidden dim
    with pytest.raises(AdapterShapeError, match="layers"):
        reg.register(LoraAdapter("bad", [ad.a[0]], [ad.b[0]]))
    wrong = make_lora(gpt_config("gpt-tiny", hidden_size=64), rank=4,
                      seed=0, name="bad")
    with pytest.raises(AdapterShapeError):
        reg.register(wrong)
    # malformed factor lists never construct
    with pytest.raises(ValueError, match="rank"):
        LoraAdapter("bad", [np.zeros((8, 4))], [np.zeros((2, 24))])
    with pytest.raises(ValueError, match="compose"):
        LoraAdapter("bad", [np.zeros((8, 4))], [np.zeros(4)])
    with pytest.raises(ValueError):
        AdapterRegistry(object())


def test_residency_refcount_lru_units():
    res = AdapterResidency(2)
    s1, cold = res.acquire("a")
    assert cold and s1 in (1, 2) and res.n_resident == 1
    res.mark_loaded("a")
    s2, cold2 = res.acquire("b")
    assert cold2 and s2 != s1
    # bank full, both pinned: a third adapter must wait
    assert res.acquire("c") is None
    res.release("a")
    # refs-0 LRU entry ("a") is evicted for "c"; "b" (pinned) survives
    s3, cold3 = res.acquire("c")
    assert cold3 and s3 == s1 and res.evictions == 1
    assert res.slot_of("a") is None and res.slot_of("b") == s2
    # re-acquire of a resident entry is a warm hit, no reload
    res.mark_loaded("c")
    s4, cold4 = res.acquire("c")
    assert s4 == s3 and not cold4 and res.hits == 1
    with pytest.raises(AssertionError, match="leaked"):
        res.check()
    res.release("b")
    res.release("c")
    res.release("c")
    res.check()                         # zero pins: clean


# -- acceptance: parity ------------------------------------------------------

def test_adapter_id0_token_identical_to_adapter_free_engine(tiny_gpt,
                                                            adapters):
    model, cfg = tiny_gpt
    prompts = _prompts(cfg, 4)
    plain = Engine(model, max_slots=2, max_len=64)
    base = _run(plain, prompts)
    plain.shutdown()
    reg = AdapterRegistry(model, max_resident=2, max_rank=8)
    reg.register(adapters["tenant-a"])
    eng = Engine(model, max_slots=2, max_len=64, adapters=reg)
    outs = _run(eng, prompts)           # no adapter= -> id 0 rows
    st = eng.stats()
    eng.shutdown()
    for i, (b, o) in enumerate(zip(base, outs)):
        np.testing.assert_array_equal(b, o, err_msg=f"request {i}")
    assert st["decode_compiles"] == 1
    assert st["adapter_loads"] == 0     # nobody touched the bank


def test_adapter_outputs_match_offline_merged_weights(tiny_gpt, adapters):
    """Batched per-slot application == the merged-weights forward, per
    adapter, with base and adapter rows mixed in the SAME batch."""
    model, cfg = tiny_gpt
    prompts = _prompts(cfg, 3, seed=1)
    reg = AdapterRegistry(model, max_resident=3, max_rank=8)
    for ad in adapters.values():
        reg.register(ad)
    eng = Engine(model, max_slots=4, max_len=64, adapters=reg)
    # interleave adapters (and base) so every decode batch mixes rows
    names = ["tenant-a", "tenant-b", None]
    handles = [eng.submit(p, max_new_tokens=6, adapter=nm)
               for p in prompts for nm in names]
    outs = [h.result(timeout=300) for h in handles]
    st = eng.stats()
    eng.shutdown()
    assert st["decode_compiles"] == 1, st
    by_name = {}
    for (p_i, nm), o in zip(((i, nm) for i in range(len(prompts))
                            for nm in names), outs):
        by_name.setdefault(nm, []).append(o)
    for nm in ["tenant-a", "tenant-b"]:
        merged = _merged_model(cfg, adapters[nm])
        ref_eng = Engine(merged, max_slots=2, max_len=64)
        want = _run(ref_eng, prompts)
        ref_eng.shutdown()
        for i, (w, o) in enumerate(zip(want, by_name[nm])):
            np.testing.assert_array_equal(
                w, o, err_msg=f"{nm} request {i}")
        # the adapter genuinely changes the decode somewhere
        assert any(not np.array_equal(w, b)
                   for w, b in zip(want, by_name[None]))


# -- typed errors at submit --------------------------------------------------

def test_unknown_and_never_fits_typed_errors_at_submit(tiny_gpt, adapters):
    model, cfg = tiny_gpt
    reg = AdapterRegistry(model, max_resident=2, max_rank=4)
    reg.register(adapters["tenant-a"])              # rank 2: fits
    big = make_lora(cfg, rank=6, seed=5, name="too-big")
    reg.register(big)                               # registers fine...
    eng = Engine(model, max_slots=2, max_len=64, adapters=reg,
                 auto_start=False)
    p = np.arange(1, 9).astype(np.int64)
    with pytest.raises(UnknownAdapterError, match="nope"):
        eng.submit(p, adapter="nope")
    with pytest.raises(AdapterRankError, match="never"):
        eng.submit(p, adapter="too-big")            # ...but can never run
    eng.shutdown()
    plain = Engine(model, max_slots=2, max_len=64, auto_start=False)
    with pytest.raises(ValueError, match="no adapter registry"):
        plain.submit(p, adapter="tenant-a")
    plain.shutdown()
    with pytest.raises(ValueError, match="weight_dtype"):
        Engine(model, max_slots=2, max_len=32, weight_dtype="fp4")


# -- residency lifecycle on the engine ---------------------------------------

def test_pinned_adapter_survives_lru_sweep_mid_flight(tiny_gpt, adapters):
    """With a ONE-row bank, a second adapter's request must WAIT (queued
    backpressure) while the first adapter is pinned by in-flight work —
    and the pinned adapter's output is untouched by the pressure."""
    model, cfg = tiny_gpt
    reg = AdapterRegistry(model, max_resident=1, max_rank=8)
    reg.register(adapters["tenant-a"])
    reg.register(adapters["tenant-b"])
    eng = Engine(model, max_slots=2, max_len=64, adapters=reg,
                 prefill_batch=1)
    p = np.arange(3, 11).astype(np.int64)
    long_req = eng.submit(p, max_new_tokens=24, adapter="tenant-a")
    blocked = eng.submit(p, max_new_tokens=4, adapter="tenant-b")
    # while the long request runs, tenant-b must not displace the pinned
    # bank row
    stalls_seen = []
    while not long_req.done():
        st = eng.stats()
        stalls_seen.append(st["adapter_evictions"])
        time.sleep(0.002)
    long_out = long_req.result(timeout=300)
    blocked_out = blocked.result(timeout=300)
    st = eng.stats()
    eng.shutdown()
    assert all(v == 0 for v in stalls_seen[:-1] or stalls_seen), \
        "the pinned adapter was evicted mid-flight"
    assert st["adapter_load_stalls"] >= 1, st      # b actually waited
    assert st["adapter_evictions"] == 1            # then displaced a
    merged_a = _merged_model(cfg, adapters["tenant-a"])
    ref = Engine(merged_a, max_slots=2, max_len=64)
    np.testing.assert_array_equal(
        long_out, ref.submit(p, max_new_tokens=24).result(timeout=300))
    ref.shutdown()
    merged_b = _merged_model(cfg, adapters["tenant-b"])
    ref = Engine(merged_b, max_slots=2, max_len=64)
    np.testing.assert_array_equal(
        blocked_out, ref.submit(p, max_new_tokens=4).result(timeout=300))
    ref.shutdown()


def test_eviction_then_rehit_reloads_correctly(tiny_gpt, adapters):
    """a -> b (evicts a) -> a again: the re-loaded bank row serves the
    same tokens as the first residency (no stale weights)."""
    model, cfg = tiny_gpt
    reg = AdapterRegistry(model, max_resident=1, max_rank=8)
    # strong local adapters so the two variants' greedy decodes visibly
    # diverge on one prompt (the module fixtures are gentler)
    reg.register(make_lora(cfg, rank=4, seed=20, name="tenant-a", std=0.5))
    reg.register(make_lora(cfg, rank=4, seed=21, name="tenant-b", std=0.5))
    eng = Engine(model, max_slots=1, max_len=64, adapters=reg)
    p = np.arange(2, 10).astype(np.int64)
    a1 = eng.submit(p, max_new_tokens=6, adapter="tenant-a").result(
        timeout=300)
    b1 = eng.submit(p, max_new_tokens=6, adapter="tenant-b").result(
        timeout=300)
    a2 = eng.submit(p, max_new_tokens=6, adapter="tenant-a").result(
        timeout=300)
    st = eng.stats()
    eng.shutdown()
    np.testing.assert_array_equal(a1, a2)
    assert not np.array_equal(a1, b1)
    assert st["adapter_loads"] == 3, st            # a, b, a-again
    assert st["adapter_evictions"] == 2, st
    assert st["adapters_resident"] == 1 and st["adapters_pinned"] == 0


def test_prefix_cache_keyed_by_adapter(tiny_gpt, adapters):
    """The same prompt under two adapters never shares KV: each
    (adapter, tokens) pair is its own cache entry; a same-adapter rerun
    hits."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, cfg.vocab_size, 14).astype(np.int64)
    reg = AdapterRegistry(model, max_resident=2, max_rank=8)
    reg.register(adapters["tenant-a"])
    eng = Engine(model, max_slots=3, max_len=64, adapters=reg,
                 prefix_cache=True, prefix_block=4, prefill_batch=1)
    base1 = eng.submit(prompt, max_new_tokens=6).result(timeout=300)
    st0 = eng.stats()
    # adapter request with the SAME prompt: must MISS the base entry
    # (different ns) and produce the merged-weights answer
    ha = eng.submit(prompt, max_new_tokens=6, adapter="tenant-a")
    a1 = ha.result(timeout=300)
    st1 = eng.stats()
    assert not ha.prefix_hit
    assert st1["prefix_hits"] == st0["prefix_hits"]
    # reruns hit their OWN namespace, outputs unchanged
    hb = eng.submit(prompt, max_new_tokens=6)
    ha2 = eng.submit(prompt, max_new_tokens=6, adapter="tenant-a")
    base2, a2 = hb.result(timeout=300), ha2.result(timeout=300)
    st2 = eng.stats()
    eng.shutdown()
    assert hb.prefix_hit and ha2.prefix_hit
    assert st2["prefix_hits"] >= st1["prefix_hits"] + 2
    np.testing.assert_array_equal(base1, base2)
    np.testing.assert_array_equal(a1, a2)
    assert not np.array_equal(base1, a1)
    merged = _merged_model(cfg, adapters["tenant-a"])
    ref = Engine(merged, max_slots=2, max_len=64)
    np.testing.assert_array_equal(
        a1, ref.submit(prompt, max_new_tokens=6).result(timeout=300))
    ref.shutdown()


# -- int8 base weights -------------------------------------------------------

def test_weight_int8_parity_and_bytes(tiny_gpt):
    model, cfg = tiny_gpt
    prompts = _prompts(cfg, 4, seed=4)
    f32 = Engine(model, max_slots=2, max_len=64)
    base = _run(f32, prompts, new=8)
    fb = f32.weight_bytes()
    f32.shutdown()
    q = Engine(model, max_slots=2, max_len=64, weight_dtype="int8")
    got = _run(q, prompts, new=8)
    qb = q.weight_bytes()
    st = q.stats()
    q.shutdown()
    assert 0 < qb < 0.5 * fb, (qb, fb)      # 2-D leaves dominate: < 0.5x
    assert st["decode_compiles"] == 1
    match = float(np.mean([np.mean(b == g) for b, g in zip(base, got)]))
    assert match >= 0.9, f"int8 weights diverged: {match:.2f} token match"


# -- composition -------------------------------------------------------------

def test_all_flags_composed_one_decode_signature(tiny_gpt, adapters):
    """prefix + speculation + int8 KV + paged KV + device sampling +
    adapters + int8 weights: ONE decode signature, and base rows still
    match the same engine without the adapter path."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(9)
    shared = rs.randint(0, cfg.vocab_size, 12).astype(np.int64)
    prompts = [np.concatenate(
        [shared, rs.randint(0, cfg.vocab_size, 3).astype(np.int64)])
        for _ in range(6)]
    kw = dict(max_slots=3, max_len=64, prefix_cache=True, prefix_block=4,
              speculative_k=3, kv_dtype="int8", paged_kv=True,
              weight_dtype="int8")
    ref = Engine(model, **kw)
    base = _run(ref, prompts)
    ref.shutdown()
    reg = AdapterRegistry(model, max_resident=2, max_rank=8)
    reg.register(adapters["tenant-a"])
    reg.register(adapters["tenant-b"])
    eng = Engine(model, adapters=reg, **kw)
    names = [None, "tenant-a", None, "tenant-b", None, "tenant-a"]
    handles = [eng.submit(p, max_new_tokens=6, adapter=nm)
               for p, nm in zip(prompts, names)]
    outs = [h.result(timeout=300) for h in handles]
    st = eng.stats()
    eng.shutdown()
    assert st["decode_compiles"] == 1, st
    for p_i, (o, nm) in enumerate(zip(outs, names)):
        if nm is None:     # base rows: exact vs the adapter-free engine
            np.testing.assert_array_equal(base[p_i], o,
                                          err_msg=f"request {p_i}")
    assert st["adapter_loads"] == 2 and st["adapters_resident"] == 2
    assert st["prefix_hits"] + st["prefix_misses"] == len(prompts)
    assert st["weight_bytes"] > 0


# -- supervisor rebuild ------------------------------------------------------

def test_supervisor_rebuild_fresh_banks_zero_pins(tiny_gpt, adapters):
    """Kill/rebuild with adapters live: the registry persists across
    builds but residency is FRESH (cold reload on the rebuilt engine),
    no pins leak from the dead build, and per-adapter outputs match
    across the restart."""
    from paddle_tpu.serving import EngineSupervisor
    from paddle_tpu.testing import faults

    model, cfg = tiny_gpt
    reg = AdapterRegistry(model, max_resident=2, max_rank=8)
    reg.register(adapters["tenant-a"])
    engines_built = []

    def factory():
        e = Engine(model, max_slots=2, max_len=64, adapters=reg)
        engines_built.append(e)
        return e

    sup = EngineSupervisor(factory, name="lora", poll_interval_s=0.02,
                           max_restarts=4)
    p = np.arange(4, 12).astype(np.int64)
    try:
        before = sup.submit(p, max_new_tokens=6,
                            adapter="tenant-a").result(timeout=300)
        assert sup.stats()["adapter_loads"] == 1
        faults.arm("serving.scheduler", times=1)
        deadline = time.time() + 120
        while sup.restarts < 1:
            assert time.time() < deadline, "kill never absorbed"
            time.sleep(0.01)
        after = sup.submit(p, max_new_tokens=6,
                           adapter="tenant-a").result(timeout=300)
        np.testing.assert_array_equal(before, after)
        st = sup.stats()
        assert st["adapter_loads"] == 1      # the REBUILT bank reloaded
        for b in sup.builds():
            assert b["decode_compiles"] <= 1
        assert sup.failed is None
    finally:
        faults.reset()
        sup.shutdown()
    for e in engines_built:
        e.shutdown()
        e._adapters.check()                  # zero leaked pins, every build
    assert len(engines_built) >= 2


# -- gateway model= routing --------------------------------------------------

def test_gateway_model_routing(tiny_gpt, adapters):
    from paddle_tpu.serving.gateway import Gateway
    from paddle_tpu.serving.gateway.protocol import (ProtocolError,
                                                     parse_completion_request)
    import json

    model, cfg = tiny_gpt
    reg = AdapterRegistry(model, max_resident=2, max_rank=4)
    reg.register(adapters["tenant-a"])
    reg.register(make_lora(cfg, rank=6, seed=5, name="too-big"))
    eng = Engine(model, max_slots=2, max_len=64, adapters=reg)
    gw = Gateway(eng, model_name="base")
    try:
        p = [int(t) for t in np.arange(5, 13)]

        def creq(**extra):
            return parse_completion_request(
                json.dumps(dict({"prompt": p, "max_tokens": 6}, **extra)
                           ).encode(), has_tokenizer=False)

        item = gw.admit(creq(model="tenant-a"), "t1")
        toks, _ = gw.result(item, timeout=300)
        lps = np.asarray(item.handle.logprobs)
        merged = _merged_model(cfg, adapters["tenant-a"])
        ref = Engine(merged, max_slots=2, max_len=64)
        h = ref.submit(np.asarray(p), max_new_tokens=6)
        want, want_lps = h.result(timeout=300), np.asarray(h.logprobs)
        ref.shutdown()
        np.testing.assert_array_equal(toks, want)
        np.testing.assert_allclose(lps, want_lps, atol=1e-4, rtol=0)
        # base-model requests: absent model= or the base name -> id 0.
        # This tiny model's greedy tokens are the same with and without
        # the adapter ([12] * 6), so the tokens cannot tell the two routes
        # apart; each token's log-probability can: the base request's lie
        # far from the adapter's, which lie on the merged reference's
        # (5e-7 apart)
        item = gw.admit(creq(model="base"), "t1")
        toks_base, _ = gw.result(item, timeout=300)
        lps_base = np.asarray(item.handle.logprobs)
        assert len(toks_base) == len(toks)
        assert np.abs(lps - lps_base).min() > 0.05      # 0.12-0.48 here
        with pytest.raises(ProtocolError) as ei:
            gw.admit(creq(model="nope"), "t1")
        assert ei.value.status == 404 and ei.value.code == "model_not_found"
        with pytest.raises(ProtocolError) as ei:
            gw.admit(creq(model="too-big"), "t1")
        assert ei.value.status == 400 and ei.value.code == "adapter_rank"
    finally:
        gw.shutdown()
        eng.shutdown()


# -- telemetry ---------------------------------------------------------------

def test_adapter_metrics_and_flight_events(tiny_gpt, adapters):
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import flight
    from paddle_tpu.serving.engine import (
        SERVING_ADAPTER_LOADS, SERVING_ADAPTER_TOKENS,
        SERVING_ADAPTER_TTFT, SERVING_ADAPTERS_RESIDENT,
        SERVING_WEIGHT_BYTES)

    model, cfg = tiny_gpt
    reg = AdapterRegistry(model, max_resident=1, max_rank=8)
    reg.register(adapters["tenant-a"])
    reg.register(adapters["tenant-b"])
    eng = Engine(model, max_slots=2, max_len=64, adapters=reg)
    p = np.arange(6, 14).astype(np.int64)
    for nm in ("tenant-a", "tenant-b"):    # b displaces a: load + evict
        eng.submit(p, max_new_tokens=4, adapter=nm).result(timeout=300)
    st = eng.stats()
    eng.shutdown()
    assert st["adapter_loads"] == 2 and st["adapter_evictions"] == 1
    d = obs.dump()
    assert SERVING_ADAPTER_LOADS in d["counters"], sorted(d["counters"])
    assert SERVING_ADAPTER_TOKENS in d["counters"]
    assert SERVING_ADAPTERS_RESIDENT in d["gauges"]
    assert SERVING_WEIGHT_BYTES in d["gauges"]
    assert SERVING_ADAPTER_TTFT in d["histograms"]
    names = {e["name"] for e in flight.events("serving")}
    assert {"adapter_load", "adapter_evict"} <= names, names
