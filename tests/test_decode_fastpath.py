"""Decode fast path tests (ISSUE 10): prefix caching, self-speculative
decoding, int8 KV pools, and device-fused sampling in the serving engine.

The contract under test (docs/serving.md "Decode fast path"):

* prefix cache — a hit copies KV rows BITWISE identical to a cold
  re-prefill and produces identical outputs; refcounted rows survive the
  eviction sweep while a dependent request is in flight; a supervisor
  rebuild drops the cache cleanly (no stale-row reuse).
* speculative decoding — greedy output token-identical to the
  non-speculative path (an accepted draft IS the token the model would
  have emitted), with > 1 token per pool read on self-similar decodes.
* int8 KV — generate() parity within tolerance on the tiny model; 2x
  max_slots in no more pool bytes than the float pool at 1x.
* device sampling — greedy identical to the host sampler; sampled runs
  deterministic per seed and equal to an eager replay of the same
  per-slot PRNG keys.
* every flag combination keeps decode at ONE compiled signature.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import build_gpt, gpt_config
from paddle_tpu.serving import Engine, NgramDrafter, PrefixIndex


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt_config("gpt-tiny", max_position_embeddings=128,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(7)
    model = build_gpt(cfg)
    model.eval()
    return model, cfg


def _shared_prefix_prompts(cfg, n, shared_len=12, tail_len=3, seed=0):
    rs = np.random.RandomState(seed)
    shared = rs.randint(0, cfg.vocab_size, shared_len).astype(np.int64)
    return [np.concatenate([shared,
                            rs.randint(0, cfg.vocab_size,
                                       tail_len).astype(np.int64)])
            for _ in range(n)]


def _run(engine, prompts, new=6, **submit_kw):
    outs = [engine.submit(p, max_new_tokens=new, **submit_kw)
                  .result(timeout=300) for p in prompts]
    return outs


# -- unit: index + drafter ---------------------------------------------------

def test_prefix_index_block_addressing_refs_lru():
    idx = PrefixIndex(block=4)
    e1 = idx.insert(0, list(range(10)))          # boundaries 4, 8
    assert e1 is not None and e1.n == 10
    assert idx.insert(1, list(range(10))) is None      # duplicate content
    assert idx.insert(1, [1, 2, 3]) is None            # shorter than block
    # longest block-aligned match, capped at len(prompt)-1
    hit = idx.lookup(list(range(9)))             # cap 8 -> match 8
    assert hit is not None and hit[0] is e1 and hit[1] == 8
    hit = idx.lookup(list(range(6)))             # cap 5 -> match 4
    assert hit == (e1, 4)
    assert idx.lookup([9, 9, 9, 9, 9]) is None   # content mismatch
    assert idx.hits == 2 and idx.misses == 1
    # refcounts pin entries across the LRU sweep
    idx.acquire(e1)
    e2 = idx.insert(2, [5] * 8)
    assert idx.evict_lru(2) == [e2]              # e1 referenced: survives
    assert idx.entry_for_slot(0) is e1 and idx.entry_for_slot(2) is None
    idx.release(e1)
    assert idx.evict_lru(1) == [e1]
    assert len(idx) == 0 and idx.evictions == 2
    # newest entry wins a shared prefix key
    a = idx.insert(3, list(range(8)))
    b = idx.insert(4, list(range(12)))
    assert idx.lookup(list(range(5)))[0] is b
    idx.drop_all()
    assert len(idx) == 0 and idx.lookup(list(range(5))) is None
    assert a is not None and b is not None
    with pytest.raises(ValueError):
        PrefixIndex(block=0)


def test_ngram_drafter_prompt_lookup():
    d = NgramDrafter(max_ngram=3, min_ngram=1)
    # trailing bigram (7, 8) occurred earlier: propose its continuation
    ctx = [1, 2, 7, 8, 9, 4, 7, 8]
    np.testing.assert_array_equal(d(ctx, 2), [9, 4])
    # continuation shorter than n: padded with its last token
    np.testing.assert_array_equal(d([5, 6, 5, 6], 3), [5, 6, 6])
    # no match anywhere: repeat the last token
    np.testing.assert_array_equal(d([1, 2, 3, 4], 2), [4, 4])
    # degenerate contexts never crash
    np.testing.assert_array_equal(d([3], 2), [3, 3])
    np.testing.assert_array_equal(d([], 2), [0, 0])
    with pytest.raises(ValueError):
        NgramDrafter(max_ngram=0)


# -- prefix cache ------------------------------------------------------------

def test_prefix_cache_hit_bitwise_kv_and_outputs(tiny_gpt):
    """A hit must (a) produce outputs identical to a cold engine, (b) copy
    prefix KV rows BITWISE identical to a cold re-prefill of the same
    tokens, and (c) actually skip work (tail prefill, not full prefill)."""
    model, cfg = tiny_gpt
    prompts = _shared_prefix_prompts(cfg, 5)
    cold = Engine(model, max_slots=4, max_len=64)
    base = _run(cold, prompts)
    eng = Engine(model, max_slots=4, max_len=64, prefix_cache=True,
                 prefix_block=4)
    outs = _run(eng, prompts)
    for i, (b, o) in enumerate(zip(base, outs)):
        np.testing.assert_array_equal(b, o, err_msg=f"request {i}")
    st = eng.stats()
    assert st["prefix_hits"] >= 3, st       # shared 12-token system prompt
    assert st["prefix_inserts"] >= 1 and st["cached_slots"] >= 1
    assert st["decode_compiles"] == 1
    assert st["tail_prefill_compiles"] >= 1      # the hit path really ran
    assert st["prefix_copy_compiles"] == 1

    # re-submit the first prompt: full-row hit; its copied prefix rows
    # must equal the cold engine's rows for the same tokens, bit for bit
    h = eng.submit(prompts[0], max_new_tokens=6)
    np.testing.assert_array_equal(h.result(timeout=300), base[0])
    assert h.prefix_hit and h._prefix_match >= 12
    h2 = cold.submit(prompts[0], max_new_tokens=6)
    h2.result(timeout=300)
    m = h._prefix_match
    kpools, vpools = eng._kv_pool.k, eng._kv_pool.v
    ck, cv = cold._kv_pool.k, cold._kv_pool.v
    for li in range(len(kpools)):
        np.testing.assert_array_equal(
            np.asarray(kpools[li][h.slot, :m]),
            np.asarray(ck[li][h2.slot, :m]), err_msg=f"k layer {li}")
        np.testing.assert_array_equal(
            np.asarray(vpools[li][h.slot, :m]),
            np.asarray(cv[li][h2.slot, :m]), err_msg=f"v layer {li}")
    cold.shutdown()
    eng.shutdown()


def test_prefix_refcounted_row_survives_eviction_sweep(tiny_gpt):
    """While a hit request is in flight, its copy-source entry is
    refcounted: admission pressure evicts OTHER (unreferenced) entries
    but never the pinned row, and the queued request waits instead of
    corrupting it."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(3)
    shared = rs.randint(0, cfg.vocab_size, 12).astype(np.int64)
    eng = Engine(model, max_slots=2, max_len=64, prefix_cache=True,
                 prefix_block=4, prefill_batch=1)
    # seed the cache: one entry, then keep it referenced by a LONG
    # generation that hit on it
    eng.submit(shared, max_new_tokens=2).result(timeout=300)
    assert eng.stats()["cached_slots"] == 1
    long_req = eng.submit(
        np.concatenate([shared, [5, 9]]), max_new_tokens=24)
    # admission pressure from a non-matching prompt: with both slots
    # taken (1 cached+referenced soon, 1 active) the sweep may only
    # reclaim unreferenced entries — there are none while long_req runs
    other = eng.submit(rs.randint(0, cfg.vocab_size, 6).astype(np.int64),
                       max_new_tokens=2)
    evictions_seen = []
    while not long_req.done():
        evictions_seen.append(eng.stats()["prefix_evictions"])
        time.sleep(0.002)
    long_out = long_req.result(timeout=300)
    other.result(timeout=300)
    st = eng.stats()
    eng.shutdown()
    assert long_req.prefix_hit
    assert all(v == 0 for v in evictions_seen), \
        "a refcounted prefix row was evicted mid-flight"
    # the pinned copy source stayed intact: the long generation equals a
    # cold engine's output for the same prompt
    cold = Engine(model, max_slots=2, max_len=64)
    ref = cold.submit(np.concatenate([shared, [5, 9]]),
                      max_new_tokens=24).result(timeout=300)
    cold.shutdown()
    np.testing.assert_array_equal(long_out, ref)
    assert st["completed"] == 3


def test_supervisor_rebuild_drops_prefix_cache(tiny_gpt):
    """Engine kill/rebuild with the prefix cache on: the rebuilt engine
    starts with an EMPTY index (no stale-row reuse across pools) and
    still answers correctly."""
    from paddle_tpu.serving import EngineSupervisor
    from paddle_tpu.testing import faults

    model, cfg = tiny_gpt
    prompts = _shared_prefix_prompts(cfg, 2, seed=5)
    cold = Engine(model, max_slots=2, max_len=64)
    base = _run(cold, prompts)
    cold.shutdown()

    sup = EngineSupervisor(
        lambda: Engine(model, max_slots=2, max_len=64, prefix_cache=True,
                       prefix_block=4, speculative_k=3),
        name="fastpath", poll_interval_s=0.02, max_restarts=4)
    try:
        np.testing.assert_array_equal(
            sup.submit(prompts[0], max_new_tokens=6).result(timeout=300),
            base[0])
        assert sup.stats()["cached_slots"] >= 1
        faults.arm("serving.scheduler", times=1)
        deadline = time.time() + 120
        while sup.restarts < 1:
            assert time.time() < deadline, "kill never absorbed"
            time.sleep(0.01)
        # the rebuilt engine must MISS (fresh index), then serve the
        # same answer from a cold prefill of the new pool
        h = sup.submit(prompts[1], max_new_tokens=6)
        np.testing.assert_array_equal(h.result(timeout=300), base[1])
        st = sup.stats()
        assert st["prefix_hits"] == 0 and st["prefix_misses"] == 1, st
        assert not h.prefix_hit
        for b in sup.builds():
            assert b["decode_compiles"] <= 1, sup.builds()
        assert sup.failed is None
    finally:
        faults.reset()
        sup.shutdown()


# -- speculative decoding ----------------------------------------------------

def test_speculative_greedy_token_identical(tiny_gpt):
    """Speculative greedy output == plain greedy output, token for token,
    while emitting > 1 token per decode dispatch on self-similar
    continuations (the acceptance-rate criterion)."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, cfg.vocab_size,
                          rs.randint(4, 10)).astype(np.int64)
               for _ in range(6)]
    plain = Engine(model, max_slots=3, max_len=64)
    base = _run(plain, prompts, new=10)
    plain_steps = plain.stats()["decode_steps"]
    plain.shutdown()

    spec = Engine(model, max_slots=3, max_len=64, speculative_k=4)
    outs = _run(spec, prompts, new=10)
    st = spec.stats()
    spec.shutdown()
    for i, (b, o) in enumerate(zip(base, outs)):
        np.testing.assert_array_equal(b, o, err_msg=f"request {i}")
    assert st["decode_compiles"] == 1
    assert st["spec_drafted"] > 0 and st["spec_accepted"] > 0, st
    # >1 effective token per pool read: fewer verify dispatches than the
    # plain engine needed decode steps (tiny models loop fast, so the
    # n-gram drafter accepts heavily)
    assert st["decode_steps"] < plain_steps, (st["decode_steps"],
                                              plain_steps)
    tokens_per_verify = st["tokens"] / max(st["decode_steps"], 1)
    assert tokens_per_verify > 1.0, st


def test_speculative_eos_and_budget_mid_acceptance(tiny_gpt):
    """EOS or token budget landing INSIDE an accepted draft run stops the
    emission exactly where the plain path would."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, cfg.vocab_size, 6).astype(np.int64)
               for _ in range(3)]
    plain = Engine(model, max_slots=3, max_len=64)
    base = _run(plain, prompts, new=9)
    plain.shutdown()
    # eos = a token the first request actually emits mid-run
    eos = int(base[0][len(base[0]) // 2])

    for kw in (dict(speculative_k=4),
               dict(speculative_k=4, sample_on_device=False)):
        spec = Engine(model, max_slots=3, max_len=64, **kw)
        outs = [spec.submit(p, max_new_tokens=9, eos_token_id=eos)
                    .result(timeout=300) for p in prompts]
        spec.shutdown()
        for b, o in zip(base, outs):
            want = list(b)
            if eos in want:
                want = want[:want.index(eos) + 1]
            np.testing.assert_array_equal(o, want)


def test_speculative_sampled_rows_fall_back_correctly(tiny_gpt):
    """temperature > 0 rows in a speculative engine accept no drafts but
    still sample correctly — identical to the same seed on a plain
    engine (same per-slot PRNG key schedule)."""
    model, cfg = tiny_gpt
    p = np.arange(3, 11).astype(np.int64)
    plain = Engine(model, max_slots=2, max_len=64)
    want = plain.submit(p, max_new_tokens=8, temperature=0.9, top_k=8,
                        seed=11).result(timeout=300)
    plain.shutdown()
    spec = Engine(model, max_slots=2, max_len=64, speculative_k=4)
    got = spec.submit(p, max_new_tokens=8, temperature=0.9, top_k=8,
                      seed=11).result(timeout=300)
    st = spec.stats()
    spec.shutdown()
    np.testing.assert_array_equal(got, want)
    assert st["spec_drafted"] == 0      # sampled rows draft nothing


# -- int8 KV -----------------------------------------------------------------

def test_int8_kv_generate_parity_and_pool_bytes(tiny_gpt):
    """generate(kv_dtype='int8') stays within tolerance of the float
    path on the tiny model, and 2x max_slots fit in no more pool bytes
    than the float pool at 1x (the HBM-doubling criterion)."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(4)
    prompt = rs.randint(0, cfg.vocab_size, (3, 8)).astype(np.int64)
    want = model.generate(prompt, max_new_tokens=8)
    got = model.generate(prompt, max_new_tokens=8, kv_dtype="int8")
    assert want.shape == got.shape
    match = float(np.mean(want == got))
    assert match >= 0.75, f"int8 KV diverged: {match:.2f} token match"

    f32 = Engine(model, max_slots=4, max_len=64)
    f32.submit(prompt[0], max_new_tokens=2).result(timeout=300)
    int8 = Engine(model, max_slots=8, max_len=64, kv_dtype="int8")
    int8.submit(prompt[0], max_new_tokens=2).result(timeout=300)
    try:
        assert int8.pool_bytes() > 0 and f32.pool_bytes() > 0
        assert int8.pool_bytes() <= f32.pool_bytes(), \
            (int8.pool_bytes(), f32.pool_bytes())
        assert int8.stats()["decode_compiles"] == 1
    finally:
        f32.shutdown()
        int8.shutdown()

    with pytest.raises(ValueError, match="kv_dtype"):
        Engine(model, max_slots=2, max_len=32, kv_dtype="fp4")


# -- device-fused sampling ---------------------------------------------------

def test_device_sampling_greedy_matches_host_sampler(tiny_gpt):
    """Greedy decode is identical with sampling fused on device and with
    the host `_sample_row` escape hatch (same logits, same argmax)."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(6)
    prompts = [rs.randint(0, cfg.vocab_size, 7).astype(np.int64)
               for _ in range(4)]
    dev = Engine(model, max_slots=2, max_len=64, sample_on_device=True)
    host = Engine(model, max_slots=2, max_len=64, sample_on_device=False)
    a = _run(dev, prompts, new=6)
    b = _run(host, prompts, new=6)
    assert dev.stats()["decode_compiles"] == 1
    dev.shutdown()
    host.shutdown()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_device_sampling_parity_vs_eager_reference(tiny_gpt):
    """Sampled (temperature/top-k) decode at a fixed seed equals an
    EAGER replay of the device sampler — full forwards, same per-slot
    fold_in(PRNGKey(seed), position) key schedule, same Gumbel-max —
    and is deterministic across runs."""
    import jax
    import jax.numpy as jnp

    model, cfg = tiny_gpt
    p = np.arange(5, 13).astype(np.int64)
    eng = Engine(model, max_slots=2, max_len=64)
    a = eng.submit(p, max_new_tokens=8, temperature=0.9, top_k=8,
                   seed=3).result(timeout=300)
    b = eng.submit(p, max_new_tokens=8, temperature=0.9, top_k=8,
                   seed=3).result(timeout=300)
    eng.shutdown()
    np.testing.assert_array_equal(a, b)     # deterministic per seed

    def eager_sample(logits, temp, k, key):
        l32 = np.asarray(logits, np.float32) / max(temp, 1e-6)
        v = l32.shape[-1]
        kth = np.sort(l32)[int(np.clip(v - k, 0, v - 1))]
        masked = np.where((k <= 0) | (l32 >= kth), l32, -1e30)
        g = np.asarray(jax.random.gumbel(key, masked.shape, jnp.float32))
        return int(np.argmax(masked + g))

    base_key = jax.random.PRNGKey(3)
    ids = p[None]
    ref = []
    for _ in range(8):
        logits = model(paddle.to_tensor(ids)).numpy()[0, -1]
        key = jax.random.fold_in(base_key, ids.shape[1] - 1)
        tok = eager_sample(logits, 0.9, 8, key)
        ref.append(tok)
        ids = np.concatenate([ids, [[tok]]], axis=1).astype(np.int64)
    np.testing.assert_array_equal(a, ref)


def _prims(jaxpr, in_cond=False):
    """(primitive name, under a conditional?) of every equation, nested
    programs included."""
    import jax
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        yield name, in_cond
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _prims(sub, in_cond or name == "cond")


def _decode_calls(eng, submit):
    """The argument tuples of every decode dispatch `submit()` causes, and
    the decode program (the engine must have built it already).  Whatever
    the pool, the one signature: `(values, ids, pool, lengths, tables,
    temps, topks, keys, carry)`, `tables` None on the dense layout and
    `carry` None where the engine keeps no step queued (depth 0)."""
    fn = eng._decode_fn
    program, calls = fn._fn, []
    fn._fn = lambda *a: (calls.append(a), program(*a))[1]
    try:
        out = submit()
    finally:
        fn._fn = program
    for a in calls:
        assert len(a) == 9 and a[2] is not eng._kv_pool
        assert (a[8] is None) == (eng._lookahead == 0)
        assert a[2].layout == ("paged" if eng.paged_kv else "dense")
        assert (a[4] is None) == (a[2].layout == "dense")
        assert a[2].quantized == (eng.kv_dtype == "int8")
    return out, calls, program


def _sampler_branch(program, args):
    """The branch index the decode program's sampler computes for `args`:
    the traced program up to its conditional, evaluated."""
    import jax
    closed = program.trace(*args).jaxpr
    eqns = closed.jaxpr.eqns
    at, = [i for i, e in enumerate(eqns) if e.primitive.name == "cond" and
           any(n == "sort" for n, _ in _prims(e.params["branches"][-1]))]
    head = closed.jaxpr.replace(eqns=eqns[:at],
                                outvars=[eqns[at].invars[0]])
    flat = jax.tree_util.tree_leaves(args)
    return int(jax.core.eval_jaxpr(head, closed.consts, *flat)[0])


@pytest.mark.parametrize("kw", [{}, {"paged_kv": True, "page_size": 8},
                                {"speculative_k": 3}, {"kv_dtype": "int8"}],
                         ids=["dense", "paged", "speculative", "int8"])
def test_decode_program_sorts_and_draws_only_under_a_conditional(tiny_gpt,
                                                                 kw):
    """The one decode program of an engine holds the sampler's sort and
    its random bits inside conditional branches only: a step of greedy
    rows runs neither (the predicate is a scalar, outside the vmap)."""
    model, cfg = tiny_gpt
    p = np.arange(5, 13).astype(np.int64)
    eng = Engine(model, max_slots=2, max_len=64, **kw)
    eng.submit(p, max_new_tokens=2).result(timeout=300)     # builds
    _, calls, program = _decode_calls(
        eng, lambda: eng.submit(p, max_new_tokens=3).result(timeout=300))
    st = eng.stats()
    eng.shutdown()
    seen = list(_prims(program.trace(*calls[0]).jaxpr.jaxpr))
    for prim in ("sort", "random_bits"):
        where = [in_cond for name, in_cond in seen if name == prim]
        assert where and all(where), (prim, where)
    assert {_sampler_branch(program, a) for a in calls} == {0}
    assert st["decode_compiles"] == 1
    assert st["decode_sampled_steps"] == st["decode_topk_steps"] == 0


@pytest.mark.parametrize("kw", [{}, {"paged_kv": True, "page_size": 8}],
                         ids=["dense", "paged"])
def test_mixed_sampling_batch_gives_each_row_its_own_tokens(tiny_gpt, kw):
    """A greedy row, a temperature-only row and a temperature + top-k row
    decoding together get the tokens each gets alone at the same seed; the
    step counters say which branch the shared program took."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(12)
    prompts = [rs.randint(0, cfg.vocab_size, 7).astype(np.int64)
               for _ in range(3)]
    params = [dict(), dict(temperature=0.8, seed=5),
              dict(temperature=0.9, top_k=8, seed=11)]
    eng = Engine(model, max_slots=4, max_len=64, **kw)
    alone, steps = [], []
    for p, kw_ in zip(prompts, params):
        before = eng.stats()
        alone.append(eng.submit(p, max_new_tokens=8, **kw_)
                        .result(timeout=300))
        after = eng.stats()
        steps.append([after[k] - before[k] for k in
                      ("decode_steps", "decode_sampled_steps",
                       "decode_topk_steps")])
    # alone: greedy draws in no step, temperature-only sorts in none
    n, = {s[0] for s in steps}
    assert steps == [[n, 0, 0], [n, n, 0], [n, n, n]]
    before = eng.stats()
    handles = [eng.submit(p, max_new_tokens=8, **kw_)
               for p, kw_ in zip(prompts, params)]
    together = [h.result(timeout=300) for h in handles]
    st = eng.stats()
    eng.shutdown()
    for a, t in zip(alone, together):
        np.testing.assert_array_equal(a, t)
    assert len({tuple(o) for o in together}) == 3
    grew = st["decode_steps"] - before["decode_steps"]
    assert 0 < st["decode_topk_steps"] - before["decode_topk_steps"] <= grew
    assert st["decode_compiles"] == 1


def test_stale_slot_parameters_do_not_switch_the_sort_on(tiny_gpt):
    """A freed slot keeps its last request's temperature and top-k.  A
    greedy request decoding beside such slots (and in one of them) takes
    the cheap branch on the device and on the counters, and matches the
    host sampler."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(8)
    a, b, c = (rs.randint(0, cfg.vocab_size, 7).astype(np.int64)
               for _ in range(3))
    eng = Engine(model, max_slots=2, max_len=64)
    hs = [eng.submit(p, max_new_tokens=4, temperature=0.9, top_k=8, seed=2)
          for p in (a, b)]
    for h in hs:
        h.result(timeout=300)
    before = eng.stats()
    assert before["decode_topk_steps"] > 0
    assert (eng._temps[:2] > 0).all() and (eng._topks[:2] > 0).all()
    got, calls, program = _decode_calls(
        eng, lambda: eng.submit(c, max_new_tokens=6).result(timeout=300))
    st = eng.stats()
    assert (eng._temps[:2] > 0).any()           # one slot is still stale
    eng.shutdown()
    assert calls and {_sampler_branch(program, x) for x in calls} == {0}
    for k in ("decode_sampled_steps", "decode_topk_steps"):
        assert st[k] == before[k], k
    assert st["decode_compiles"] == 1
    host = Engine(model, max_slots=2, max_len=64, sample_on_device=False)
    want = host.submit(c, max_new_tokens=6).result(timeout=300)
    host.shutdown()
    np.testing.assert_array_equal(got, want)


# -- composition + telemetry -------------------------------------------------

def test_all_flags_compose_one_decode_signature(tiny_gpt):
    """prefix cache + speculation + int8 + device sampling together:
    outputs still match the int8-only engine (same quantized pool math)
    and decode stays ONE compiled signature."""
    model, cfg = tiny_gpt
    prompts = _shared_prefix_prompts(cfg, 4, seed=9)
    ref = Engine(model, max_slots=4, max_len=64, kv_dtype="int8")
    base = _run(ref, prompts)
    ref.shutdown()
    eng = Engine(model, max_slots=4, max_len=64, prefix_cache=True,
                 prefix_block=4, speculative_k=3, kv_dtype="int8")
    outs = _run(eng, prompts)
    st = eng.stats()
    eng.shutdown()
    for b, o in zip(base, outs):
        np.testing.assert_array_equal(b, o)
    assert st["decode_compiles"] == 1
    assert st["prefix_hits"] + st["prefix_misses"] == len(prompts)
    assert st["kv_pool_bytes"] > 0


def test_fastpath_metrics_and_flight_events(tiny_gpt):
    """The new counters/gauges reach the registry and the flight ring
    records prefix admit/insert/evict + speculative verify events."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import flight
    from paddle_tpu.serving.engine import (
        SERVING_KV_POOL_BYTES, SERVING_PREFIX_EVICTIONS,
        SERVING_PREFIX_HITS, SERVING_PREFIX_MISSES, SERVING_SPEC_ACCEPTED,
        SERVING_SPEC_DRAFTED)

    model, cfg = tiny_gpt
    prompts = _shared_prefix_prompts(cfg, 4, seed=13)
    eng = Engine(model, max_slots=2, max_len=64, prefix_cache=True,
                 prefix_block=4, speculative_k=3, prefill_batch=1)
    _run(eng, prompts, new=8)
    st = eng.stats()
    eng.shutdown()
    d = obs.dump()
    for name in (SERVING_PREFIX_HITS, SERVING_PREFIX_MISSES,
                 SERVING_SPEC_DRAFTED, SERVING_SPEC_ACCEPTED):
        assert name in d["counters"], (name, sorted(d["counters"]))
    assert SERVING_KV_POOL_BYTES in d["gauges"]
    if st["prefix_evictions"]:
        assert SERVING_PREFIX_EVICTIONS in d["counters"]
    names = {e["name"] for e in flight.events("serving")}
    assert {"prefix_admit", "prefix_insert", "spec_verify"} <= names, names
    if st["prefix_evictions"]:
        assert "prefix_evict" in names


def test_engine_flag_validation(tiny_gpt):
    model, _ = tiny_gpt
    with pytest.raises(ValueError, match="speculative_k"):
        Engine(model, max_slots=2, max_len=32, speculative_k=-1)
