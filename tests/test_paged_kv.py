"""Paged KV cache tests (ISSUE 11): block-granular page pool, page-table
decode, copy-on-write prefix sharing.

The contract under test (docs/serving.md "Paged KV"):

* PageAllocator — all-or-nothing alloc, refcounted share/deref,
  double-free guard, exhaustion returns None (backpressure, never a
  partial grant).
* ``Engine(paged_kv=True)`` greedy decode is token-identical to the
  dense pool — alone and with every PR 10 flag composed (prefix cache +
  speculative + int8 + device sampling) — at ONE compiled decode
  signature per config (the page table is just another operand).
* prefix-cache hits share pages BY REFERENCE (zero-copy); a hit whose
  match boundary lands inside a shared page clones exactly that page
  (COW) — the writer diverges on a private copy while the cached
  entry's bytes stay bitwise untouched.
* page exhaustion is admission backpressure: the request stays queued
  (no deadlock — admitted requests reserve every page they can write,
  so they always retire and free pages).
* prefix eviction returns pages to the free list only at refcount 0.
* sequences complete past the dense pool's compiled ``max_len`` by
  holding more table entries.
* a supervisor rebuild drops page tables with the pool: fresh allocator,
  zero leaked pages.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import build_gpt, gpt_config
from paddle_tpu.serving import Engine, PageAllocator


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt_config("gpt-tiny", max_position_embeddings=128,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(7)
    model = build_gpt(cfg)
    model.eval()
    return model, cfg


def _prompts(cfg, n, shared_len=12, tail_len=3, seed=0):
    rs = np.random.RandomState(seed)
    shared = rs.randint(0, cfg.vocab_size, shared_len).astype(np.int64)
    return [np.concatenate([shared,
                            rs.randint(0, cfg.vocab_size,
                                       tail_len).astype(np.int64)])
            for _ in range(n)]


def _run(engine, prompts, new=6, **kw):
    return [engine.submit(p, max_new_tokens=new, **kw).result(timeout=300)
            for p in prompts]


# -- unit: allocator ---------------------------------------------------------

def test_page_allocator_alloc_free_refcount_guards():
    a = PageAllocator(num_pages=4, page_size=16)
    assert a.n_free == 4 and a.n_used == 0
    pages = a.alloc(3)
    assert pages is not None and len(pages) == 3
    assert a.n_free == 1 and all(a.refs(p) == 1 for p in pages)
    # all-or-nothing: 2 > 1 free -> None, nothing consumed
    assert a.alloc(2) is None
    assert a.n_free == 1
    # refcounted sharing: the page frees only at refcount 0
    assert a.share(pages[0]) == 2
    assert a.deref(pages[0]) is False       # one reader left
    assert a.refs(pages[0]) == 1
    assert a.deref(pages[0]) is True        # last ref: back on free list
    assert a.refs(pages[0]) == 0 and a.n_free == 2
    # double-free guard
    with pytest.raises(KeyError):
        a.deref(pages[0])
    with pytest.raises(KeyError):
        a.share(pages[0])                   # can't share a free page
    # zero-page grant is legal (fully-shared hit) and empty
    assert a.alloc(0) == []
    a.check()
    with pytest.raises(ValueError):
        PageAllocator(num_pages=0, page_size=16)
    with pytest.raises(ValueError):
        PageAllocator(num_pages=4, page_size=0)
    with pytest.raises(ValueError):
        a.alloc(-1)


# -- parity + single signature -----------------------------------------------

def test_paged_greedy_token_identical_to_dense(tiny_gpt):
    model, cfg = tiny_gpt
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, cfg.vocab_size,
                          rs.randint(4, 10)).astype(np.int64)
               for _ in range(6)]
    dense = Engine(model, max_slots=3, max_len=64)
    base = _run(dense, prompts, new=8)
    dense.shutdown()
    paged = Engine(model, max_slots=3, max_len=64, paged_kv=True,
                   page_size=16)
    outs = _run(paged, prompts, new=8)
    st = paged.stats()
    paged.shutdown()
    for i, (b, o) in enumerate(zip(base, outs)):
        np.testing.assert_array_equal(b, o, err_msg=f"request {i}")
    assert st["decode_compiles"] == 1
    assert st["slot_reuses"] > 0            # lanes still recycle
    assert st["kv_pages_free"] == st["kv_num_pages"]   # all pages returned


def test_paged_all_flags_compose_one_signature(tiny_gpt):
    """paged + prefix cache + speculation + int8 + device sampling: the
    acceptance criterion — outputs match the dense engine with the same
    flags, decode stays ONE compiled signature, hits are zero-copy."""
    model, cfg = tiny_gpt
    prompts = _prompts(cfg, 5, seed=9)
    ref = Engine(model, max_slots=4, max_len=64, kv_dtype="int8")
    base = _run(ref, prompts)
    ref.shutdown()
    eng = Engine(model, max_slots=4, max_len=64, prefix_cache=True,
                 prefix_block=4, speculative_k=3, kv_dtype="int8",
                 paged_kv=True)
    outs = _run(eng, prompts)
    st = eng.stats()
    eng.shutdown()
    for i, (b, o) in enumerate(zip(base, outs)):
        np.testing.assert_array_equal(b, o, err_msg=f"request {i}")
    assert st["decode_compiles"] == 1
    assert st["prefix_hits"] >= 3 and st["prefix_inserts"] >= 1
    # block == page size: every shared page is a full page — zero COW,
    # zero device copies; sharing is host-side table writes only
    assert st["page_cow_copies"] == 0
    assert st["kv_pages_cached"] > 0
    assert st["spec_drafted"] > 0


def test_paged_sampled_parity_per_seed(tiny_gpt):
    """temperature/top-k sampling draws the same per-slot key schedule
    whichever pool layout holds the KV."""
    model, cfg = tiny_gpt
    p = np.arange(3, 11).astype(np.int64)
    dense = Engine(model, max_slots=2, max_len=64)
    want = dense.submit(p, max_new_tokens=8, temperature=0.9, top_k=8,
                        seed=11).result(timeout=300)
    dense.shutdown()
    paged = Engine(model, max_slots=2, max_len=64, paged_kv=True)
    got = paged.submit(p, max_new_tokens=8, temperature=0.9, top_k=8,
                       seed=11).result(timeout=300)
    paged.shutdown()
    np.testing.assert_array_equal(got, want)


# -- COW prefix sharing ------------------------------------------------------

def test_cow_share_then_diverge_reader_bytes_unchanged(tiny_gpt):
    """block=4, page=8: a hit at boundary 12 shares page 0 fully and
    page 1 partially — the writer clones exactly ONE page and diverges
    on the clone; the cached entry's pages stay bitwise identical."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(3)
    shared = rs.randint(0, cfg.vocab_size, 13).astype(np.int64)
    eng = Engine(model, max_slots=3, max_len=64, prefix_cache=True,
                 prefix_block=4, paged_kv=True, page_size=8)
    eng.submit(shared, max_new_tokens=4).result(timeout=300)
    entry = next(iter(eng._prefix._entries.values()))
    idx = np.asarray(entry.pages)
    kpools, vpools = eng._kv_pool.k, eng._kv_pool.v
    k_before = [np.asarray(p)[idx] for p in kpools]
    v_before = [np.asarray(p)[idx] for p in vpools]

    p2 = np.concatenate([shared[:12],
                         rs.randint(0, cfg.vocab_size, 4).astype(np.int64)])
    h = eng.submit(p2, max_new_tokens=4)
    out = h.result(timeout=300)
    st = eng.stats()
    kpools, vpools = eng._kv_pool.k, eng._kv_pool.v
    for li in range(len(kpools)):
        np.testing.assert_array_equal(
            np.asarray(kpools[li])[idx], k_before[li],
            err_msg=f"reader k pages mutated, layer {li}")
        np.testing.assert_array_equal(
            np.asarray(vpools[li])[idx], v_before[li],
            err_msg=f"reader v pages mutated, layer {li}")
    eng.shutdown()
    assert h.prefix_hit and h._prefix_match == 12
    assert st["page_cow_copies"] == 1       # exactly the boundary page

    cold = Engine(model, max_slots=2, max_len=64)
    want = cold.submit(p2, max_new_tokens=4).result(timeout=300)
    cold.shutdown()
    np.testing.assert_array_equal(out, want)


def test_prefix_hit_zero_copy_and_outputs(tiny_gpt):
    """With page == block every shared page is full: a warm hit runs NO
    device copy at all (prefix_copy never compiles) and still matches a
    cold engine's outputs."""
    model, cfg = tiny_gpt
    prompts = _prompts(cfg, 4, shared_len=8, seed=5)
    cold = Engine(model, max_slots=4, max_len=64)
    base = _run(cold, prompts)
    cold.shutdown()
    eng = Engine(model, max_slots=4, max_len=64, prefix_cache=True,
                 prefix_block=4, paged_kv=True)   # page_size = block = 4
    outs = _run(eng, prompts)
    st = eng.stats()
    eng.shutdown()
    for b, o in zip(base, outs):
        np.testing.assert_array_equal(b, o)
    assert st["prefix_hits"] >= 2
    assert st["page_cow_copies"] == 0
    assert st["prefix_copy_compiles"] == 0      # zero-copy: no jit ever ran
    assert st["tail_prefill_compiles"] >= 1


# -- page exhaustion + eviction ----------------------------------------------

def test_page_exhaustion_backpressure_no_deadlock(tiny_gpt):
    """A request whose reservation exceeds the free pages stays QUEUED
    (alloc -> None) while earlier work runs; it admits and completes
    once pages free up — backpressure, not deadlock."""
    model, cfg = tiny_gpt
    eng = Engine(model, max_slots=2, max_len=32, paged_kv=True,
                 page_size=16, num_pages=2)
    a = eng.submit(np.arange(1, 9, dtype=np.int64), max_new_tokens=8)
    b = eng.submit(np.arange(2, 26, dtype=np.int64), max_new_tokens=8)
    assert a.result(timeout=300).size == 8
    assert b.result(timeout=300).size == 8
    st = eng.stats()
    eng.shutdown()
    assert st["completed"] == 2
    assert st["page_alloc_stalls"] >= 1
    assert st["kv_pages_free"] == 2
    # a request that could NEVER fit is rejected at submit, not queued
    eng = Engine(model, max_slots=2, max_len=64, paged_kv=True,
                 page_size=16, num_pages=2)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(np.arange(1, 41, dtype=np.int64), max_new_tokens=16)
    eng.shutdown()


def test_prefix_evict_returns_pages_only_at_refcount_zero(tiny_gpt):
    """An entry whose pages are shared with an in-flight request can be
    evicted from the INDEX, but the shared pages go back to the free
    list only when the last reference (the running request) drops."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(6)
    shared = rs.randint(0, cfg.vocab_size, 8).astype(np.int64)
    # pages for: entry (1 pg of 8 toks @ page 8... ) sized to force
    # eviction pressure: page=4, entry of 8+2 tokens ~ 3 pages
    eng = Engine(model, max_slots=2, max_len=32, paged_kv=True,
                 prefix_cache=True, prefix_block=4, page_size=4,
                 num_pages=8, prefill_batch=1)
    eng.submit(shared, max_new_tokens=2).result(timeout=300)
    assert eng.stats()["kv_pages_cached"] > 0
    # long generation that hit on the cached entry: pins its pages
    long_req = eng.submit(np.concatenate([shared, [5, 9]]),
                          max_new_tokens=18)
    # pressure from non-matching prompts forces index eviction
    other = eng.submit(rs.randint(0, cfg.vocab_size, 9).astype(np.int64),
                       max_new_tokens=4)
    long_out = long_req.result(timeout=300)
    other.result(timeout=300)
    st = eng.stats()
    alloc = eng._page_alloc
    alloc.check()        # no page both free and referenced, ever
    eng.shutdown()
    assert long_req.prefix_hit
    # the long request equals a cold engine's output: its shared pages
    # were never reclaimed from under it
    cold = Engine(model, max_slots=2, max_len=32)
    ref = cold.submit(np.concatenate([shared, [5, 9]]),
                      max_new_tokens=18).result(timeout=300)
    cold.shutdown()
    np.testing.assert_array_equal(long_out, ref)
    assert st["completed"] == 3


# -- long context ------------------------------------------------------------

def test_completion_past_dense_compiled_max_len(tiny_gpt):
    """max_len=32 but 6 table entries of 16 positions: a 40-token prompt
    + 8 new tokens completes (dense rejects it at submit) and matches a
    dense engine compiled at the larger length."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(8)
    long_prompt = rs.randint(0, cfg.vocab_size, 40).astype(np.int64)
    dense = Engine(model, max_slots=2, max_len=32)
    with pytest.raises(ValueError, match="max_len"):
        dense.submit(long_prompt, max_new_tokens=8)
    dense.shutdown()
    paged = Engine(model, max_slots=2, max_len=32, paged_kv=True,
                   page_size=16, max_pages_per_slot=6)     # virt 96
    out = paged.submit(long_prompt, max_new_tokens=8).result(timeout=300)
    st = paged.stats()
    paged.shutdown()
    big = Engine(model, max_slots=2, max_len=96)
    want = big.submit(long_prompt, max_new_tokens=8).result(timeout=300)
    big.shutdown()
    np.testing.assert_array_equal(out, want)
    assert st["decode_compiles"] == 1


# -- chaos: supervisor rebuild -----------------------------------------------

def test_supervisor_rebuild_fresh_allocator_zero_leaks(tiny_gpt):
    """Kill/rebuild with paged_kv + the PR 10 flags composed: the
    rebuilt engine starts with a FRESH allocator (all pages free, empty
    index) and the dead build leaks nothing."""
    from paddle_tpu.serving import EngineSupervisor
    from paddle_tpu.testing import faults

    model, cfg = tiny_gpt
    prompts = _prompts(cfg, 2, seed=15)
    cold = Engine(model, max_slots=2, max_len=64)
    base = _run(cold, prompts)
    cold.shutdown()

    engines = []

    def factory():
        e = Engine(model, max_slots=2, max_len=64, paged_kv=True,
                   prefix_cache=True, prefix_block=4, speculative_k=3)
        engines.append(e)
        return e

    sup = EngineSupervisor(factory, name="paged", poll_interval_s=0.02,
                           max_restarts=4)
    try:
        np.testing.assert_array_equal(
            sup.submit(prompts[0], max_new_tokens=6).result(timeout=300),
            base[0])
        assert sup.stats()["kv_pages_cached"] > 0
        faults.arm("serving.scheduler", times=1)
        deadline = time.time() + 120
        while sup.restarts < 1:
            assert time.time() < deadline, "kill never absorbed"
            time.sleep(0.01)
        # dead build: host bookkeeping fully unwound (zero leaked pages)
        dead = engines[0]
        dead._page_alloc.check()
        assert dead._page_alloc.n_used == 0
        # rebuilt engine: fresh allocator, empty index — and correct
        h = sup.submit(prompts[1], max_new_tokens=6)
        np.testing.assert_array_equal(h.result(timeout=300), base[1])
        st = sup.stats()
        assert st["prefix_hits"] == 0 and st["prefix_misses"] == 1, st
        assert engines[-1] is not engines[0]
        assert engines[-1]._page_alloc is not dead._page_alloc
        for b in sup.builds():
            assert b["decode_compiles"] <= 1, sup.builds()
        assert sup.failed is None
    finally:
        faults.reset()
        sup.shutdown()
    for e in engines:
        e._page_alloc.check()
        assert e._page_alloc.n_used == 0, "leaked pages at teardown"


# -- telemetry ---------------------------------------------------------------

def test_paged_metrics_and_flight_events(tiny_gpt):
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import flight
    from paddle_tpu.serving.engine import (
        SERVING_KV_COW_COPIES, SERVING_KV_PAGES_ACTIVE,
        SERVING_KV_PAGES_CACHED, SERVING_KV_PAGES_FREE)

    model, cfg = tiny_gpt
    rs = np.random.RandomState(21)
    shared = rs.randint(0, cfg.vocab_size, 13).astype(np.int64)
    eng = Engine(model, max_slots=2, max_len=32, paged_kv=True,
                 prefix_cache=True, prefix_block=4, page_size=8,
                 num_pages=4, prefill_batch=1)
    eng.submit(shared, max_new_tokens=2).result(timeout=300)
    # COW hit (boundary 12 inside page 1) + page pressure for a stall
    h = eng.submit(np.concatenate(
        [shared[:12], rs.randint(0, cfg.vocab_size, 3).astype(np.int64)]),
        max_new_tokens=4)
    stall = eng.submit(rs.randint(0, cfg.vocab_size, 20).astype(np.int64),
                       max_new_tokens=8)
    h.result(timeout=300)
    stall.result(timeout=300)
    st = eng.stats()
    eng.shutdown()
    assert st["page_cow_copies"] >= 1 and st["page_alloc_stalls"] >= 1, st
    d = obs.dump()
    for name in (SERVING_KV_PAGES_FREE, SERVING_KV_PAGES_ACTIVE,
                 SERVING_KV_PAGES_CACHED):
        assert name in d["gauges"], (name, sorted(d["gauges"]))
    assert SERVING_KV_COW_COPIES in d["counters"]
    names = {e["name"] for e in flight.events("serving")}
    assert {"page_alloc_stall", "page_cow", "prefix_admit"} <= names, names


def test_paged_flag_validation(tiny_gpt):
    model, _ = tiny_gpt
    with pytest.raises(ValueError, match="paged_kv"):
        Engine(model, max_slots=2, max_len=32, page_size=8)
    with pytest.raises(ValueError, match="page_size"):
        Engine(model, max_slots=2, max_len=32, paged_kv=True, page_size=0)
    with pytest.raises(ValueError, match="max_pages_per_slot"):
        Engine(model, max_slots=2, max_len=32, paged_kv=True,
               max_pages_per_slot=0)
