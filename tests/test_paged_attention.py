"""Pallas paged decode-attention kernel (kernels/paged_attention.py).

Covers, all in interpret mode (tier-1 runs on CPU):

* kernel-vs-reference parity matrix: pool dtype {f32, int8} x verify
  width {1, k} x ragged per-row lengths that sit at page starts, exact
  page boundaries and mid-page, with sentinel page-table entries.
* operand validation (int8 pools require scale sidecars, f32 forbid).
* Engine flag validation (``decode_kernel`` value set, pallas requires
  ``paged_kv=True``).
* engine-level greedy token parity vs the XLA paged path at ONE
  compiled decode signature per config, including the full PR 10/11/12
  flag composition (prefix_cache + speculative_k + int8 KV).
* supervisor kill/rebuild: parity across the rebuild, zero leaked
  pages, one decode signature per build.
* ``generate(decode_kernel=...)`` passthrough parity.
* perfscope: the kernel books analytic flops/bytes under its own
  program (XLA's cost_analysis zeroes custom calls).
* the dense pool's decode read (ISSUE 26): the kernel against the XLA
  masked read for ragged lengths, a parked row and a row at the buffer's
  end; the blocks its index map visits against `live_blocks`; routing by
  backend and pool dtype; the engine's tokens, signature count and
  `decode_kv_*_positions` through it.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models import build_gpt, gpt_config
from paddle_tpu.serving import Engine


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt_config("gpt-tiny", max_position_embeddings=128,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(7)
    model = build_gpt(cfg)
    model.eval()
    return model, cfg


def _prompts(cfg, n, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab_size, ln).astype(np.int64)
            for ln, _ in zip((3, 7, 17, 2, 11), range(n))]


def _run(engine, prompts, new=6, **kw):
    return [engine.submit(p, max_new_tokens=new, **kw).result(timeout=300)
            for p in prompts]


# -- unit: kernel vs the XLA paged-read math ---------------------------------

def _ref(q, k_pages, v_pages, pt, lengths, k_scale=None, v_scale=None):
    """The XLA paged branch, verbatim: clip sentinels, gather to
    [B, virt, H, D], dequantize, mask cols <= start + row, _sdpa_ref."""
    NP, P = k_pages.shape[:2]
    B, W, H, D = q.shape
    virt = pt.shape[1] * P
    pt_safe = jnp.clip(pt, 0, NP - 1)
    if k_scale is not None:
        k = k_pages.astype(jnp.float32) * k_scale[..., None, None]
        v = v_pages.astype(jnp.float32) * v_scale[..., None, None]
    else:
        k, v = k_pages, v_pages
    k_att = k[pt_safe].reshape((B, virt, H, D))
    v_att = v[pt_safe].reshape((B, virt, H, D))
    cols = lengths[:, None] + jnp.arange(W)[None, :]
    mask = jnp.arange(virt)[None, None, :] <= cols[:, :, None]
    qt = jnp.swapaxes(q, 1, 2)                       # [B, H, W, D]
    kt = jnp.swapaxes(k_att, 1, 2)
    vt = jnp.swapaxes(v_att, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(D)
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return np.asarray(jnp.swapaxes(out, 1, 2))


def _case(W, quant, seed=0):
    """5 rows over P=8, n_pt=4 pools: lengths at a page start (0), a
    page boundary (8), mid-page (5, 13) and one parked row (virt)."""
    rs = np.random.RandomState(seed)
    P, n_pt, H, D = 8, 4, 2, 16
    lengths = np.array([0, 5, 8, 13, n_pt * P], np.int32)
    B = len(lengths)
    NP = B * n_pt + 3
    perm = rs.permutation(NP - 1)            # keep one id purely sentinel
    pt = np.full((B, n_pt), NP, np.int32)    # sentinel = NP
    for b, ln in enumerate(lengths[:-1]):    # parked row: all sentinels
        need = -(-int(ln + W) // P)
        pt[b, :need] = perm[b * n_pt:b * n_pt + need]
    q = rs.randn(B, W, H, D).astype(np.float32)
    if quant:
        k_pages = rs.randint(-127, 128, (NP, P, H, D)).astype(np.int8)
        v_pages = rs.randint(-127, 128, (NP, P, H, D)).astype(np.int8)
        ks = (rs.rand(NP, P).astype(np.float32) + 0.1) / 127.0
        vs = (rs.rand(NP, P).astype(np.float32) + 0.1) / 127.0
        return q, k_pages, v_pages, pt, lengths, ks, vs
    k_pages = rs.randn(NP, P, H, D).astype(np.float32)
    v_pages = rs.randn(NP, P, H, D).astype(np.float32)
    return q, k_pages, v_pages, pt, lengths, None, None


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("W", [1, 4])
def test_kernel_parity_matrix(W, quant):
    q, kp, vp, pt, lengths, ks, vs = _case(W, quant)
    got = np.asarray(pa.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(pt), jnp.asarray(lengths),
        k_scale=None if ks is None else jnp.asarray(ks),
        v_scale=None if vs is None else jnp.asarray(vs)))
    want = _ref(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(pt), jnp.asarray(lengths),
                None if ks is None else jnp.asarray(ks),
                None if vs is None else jnp.asarray(vs))
    assert got.shape == q.shape and np.all(np.isfinite(got))
    live = lengths < pt.shape[1] * kp.shape[1]
    np.testing.assert_allclose(got[live], want[live],
                               rtol=2e-5, atol=2e-5)


def test_kernel_scale_validation():
    q, kp, vp, pt, lengths, ks, vs = _case(1, True)
    with pytest.raises(ValueError, match="k_scale"):
        pa.paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                  jnp.asarray(vp), jnp.asarray(pt),
                                  jnp.asarray(lengths))
    q, kp, vp, pt, lengths, _, _ = _case(1, False)
    with pytest.raises(ValueError, match="k_scale"):
        pa.paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                  jnp.asarray(vp), jnp.asarray(pt),
                                  jnp.asarray(lengths),
                                  k_scale=jnp.asarray(ks),
                                  v_scale=jnp.asarray(vs))


def test_kernel_books_perfscope_cost():
    from paddle_tpu.observability import perfscope
    q, kp, vp, pt, lengths, _, _ = _case(1, False, seed=3)
    q = q[:, :, :, :8]                       # unique shape => unique key
    kp, vp = kp[:, :, :, :8], vp[:, :, :, :8]
    pa.paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                              jnp.asarray(vp), jnp.asarray(pt),
                              jnp.asarray(lengths))
    costs = perfscope._programs[pa.PERFSCOPE_PROGRAM].costs
    key, = [k for k in costs if "D8" in k]
    assert costs[key]["flops"] > 0 and costs[key]["bytes"] > 0


# -- Engine: flag validation + parity at one signature -----------------------

def test_engine_flag_validation(tiny_gpt):
    model, _ = tiny_gpt
    with pytest.raises(ValueError, match="decode_kernel"):
        Engine(model, max_slots=2, max_len=32, paged_kv=True,
               decode_kernel="mosaic")
    with pytest.raises(ValueError, match="paged_kv"):
        Engine(model, max_slots=2, max_len=32, decode_kernel="pallas")


@pytest.mark.parametrize("kv_dtype,spec_k", [
    (None, 0), (None, 3), ("int8", 0), ("int8", 3),
], ids=["f32-w1", "f32-wk", "int8-w1", "int8-wk"])
def test_engine_token_parity(tiny_gpt, kv_dtype, spec_k):
    """Greedy decode through the fused kernel is token-identical to the
    XLA paged path, at ONE compiled decode signature."""
    model, cfg = tiny_gpt
    prompts = _prompts(cfg, 3, seed=11)
    kw = dict(max_slots=4, max_len=64, paged_kv=True, page_size=8,
              kv_dtype=kv_dtype)
    if spec_k:
        kw["speculative_k"] = spec_k
    base_eng = Engine(model, decode_kernel="xla", **kw)
    base = _run(base_eng, prompts)
    base_eng.shutdown()
    eng = Engine(model, decode_kernel="pallas", **kw)
    try:
        got = _run(eng, prompts)
        assert eng.stats()["decode_compiles"] == 1
    finally:
        eng.shutdown()
    for b, g in zip(base, got):
        np.testing.assert_array_equal(g, b)


def test_all_flags_one_signature(tiny_gpt):
    """The full flag composition (prefix_cache + speculative_k + int8 KV
    + paged_kv) stays token-identical and one-signature under the
    kernel."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(21)
    shared = rs.randint(0, cfg.vocab_size, 12).astype(np.int64)
    prompts = [np.concatenate([shared,
                               rs.randint(0, cfg.vocab_size, 3)
                               .astype(np.int64)]) for _ in range(3)]
    kw = dict(max_slots=3, max_len=64, paged_kv=True, page_size=8,
              prefix_cache=True, prefix_block=4, speculative_k=3,
              kv_dtype="int8")
    base_eng = Engine(model, decode_kernel="xla", **kw)
    base = _run(base_eng, prompts)
    base_eng.shutdown()
    eng = Engine(model, decode_kernel="pallas", **kw)
    try:
        got = _run(eng, prompts)
        st = eng.stats()
        assert st["decode_compiles"] == 1, st
        assert st["prefix_hits"] > 0
    finally:
        eng.shutdown()
    for b, g in zip(base, got):
        np.testing.assert_array_equal(g, b)


def test_supervisor_rebuild_pallas(tiny_gpt):
    """Kill/rebuild with the kernel on: parity across the rebuild, the
    dead build leaks zero pages, every build has one decode
    signature."""
    from paddle_tpu.serving import EngineSupervisor
    from paddle_tpu.testing import faults

    model, cfg = tiny_gpt
    prompts = _prompts(cfg, 2, seed=15)
    cold = Engine(model, max_slots=2, max_len=64, paged_kv=True,
                  page_size=8)
    base = _run(cold, prompts)
    cold.shutdown()

    engines = []

    def factory():
        e = Engine(model, max_slots=2, max_len=64, paged_kv=True,
                   page_size=8, decode_kernel="pallas")
        engines.append(e)
        return e

    sup = EngineSupervisor(factory, name="pallas", poll_interval_s=0.02,
                           max_restarts=4)
    try:
        np.testing.assert_array_equal(
            sup.submit(prompts[0], max_new_tokens=6).result(timeout=300),
            base[0])
        faults.arm("serving.scheduler", times=1)
        deadline = time.time() + 120
        while sup.restarts < 1:
            assert time.time() < deadline, "kill never absorbed"
            time.sleep(0.01)
        dead = engines[0]
        dead._page_alloc.check()
        assert dead._page_alloc.n_used == 0
        np.testing.assert_array_equal(
            sup.submit(prompts[1], max_new_tokens=6).result(timeout=300),
            base[1])
        assert engines[-1] is not engines[0]
        for b in sup.builds():
            assert b["decode_compiles"] <= 1, sup.builds()
    finally:
        sup.shutdown()


def test_generate_passthrough(tiny_gpt):
    """generate(decode_kernel=...) reaches the Engine (mirror of the
    kv_dtype passthrough) and preserves greedy outputs."""
    model, cfg = tiny_gpt
    rs = np.random.RandomState(33)
    ids = rs.randint(0, cfg.vocab_size, (2, 6)).astype(np.int64)
    base = model.generate(ids, max_new_tokens=6, paged_kv=True,
                          page_size=8)
    got = model.generate(ids, max_new_tokens=6, paged_kv=True,
                         page_size=8, decode_kernel="pallas")
    np.testing.assert_array_equal(got, base)


# -- the dense pool's decode read (ISSUE 26) ----------------------------------

_L, _P = 32, 8      # max_len, block: four blocks a row

_DENSE_LENGTHS = {
    # a row at 0, mid-block, at a block boundary, at max_len - 1, parked,
    # and one more live row after the parked one
    "ragged": [0, 5, 8, _L - 1, _L, 13],
    "parked-first": [_L, _L, 3, _L, 20, _L],
    "all-parked": [_L] * 6,
    "all-full": [_L - 1] * 6,
}


def _dense_ref(q, k, v, lengths):
    """models/gpt.py's dense per-slot read, verbatim: the whole buffer
    under `p <= start + j`, `_sdpa_ref`'s f32 softmax."""
    B, W, H, D = q.shape
    cols = lengths[:, None] + jnp.arange(W)[None, :]
    mask = jnp.arange(k.shape[1])[None, None, :] <= cols[:, :, None]
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(D)
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return np.asarray(jnp.swapaxes(
        jnp.einsum("bhqk,bhkd->bhqd", probs, vt), 1, 2))


@pytest.mark.parametrize("lengths", list(_DENSE_LENGTHS.values()),
                         ids=list(_DENSE_LENGTHS))
@pytest.mark.parametrize("W", [1, 4])
def test_dense_kernel_parity(W, lengths):
    rs = np.random.RandomState(len(lengths) + W)
    lengths = np.asarray(lengths, np.int32)
    B, H, D = len(lengths), 2, 16
    q = jnp.asarray(rs.randn(B, W, H, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, _L, H, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, _L, H, D).astype(np.float32))
    pa.use_interpret_mode(True)
    got = np.asarray(jax.jit(
        lambda *a: pa.dense_decode_attention(*a, block=_P))(
            q, k, v, jnp.asarray(lengths)))
    want = _dense_ref(q, k, v, jnp.asarray(lengths))
    live = lengths < _L
    assert got.shape == q.shape
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    assert not got[~live].any()          # a parked row read nothing: zeros


@pytest.mark.parametrize("lengths", list(_DENSE_LENGTHS.values()),
                         ids=list(_DENSE_LENGTHS))
@pytest.mark.parametrize("W", [1, 4])
def test_dense_blocks_visited_are_the_blocks_counted(W, lengths):
    """`live_blocks` (what the engine's `kv_read` sums) against the pool
    blocks the kernel's grid holds, step by step: every live block once, a
    parked row none, and no block fetched twice."""
    lengths = np.asarray(lengths, np.int32)
    nb = pa.live_blocks(lengths, W, _L, _P)
    want = [(r, i) for r, ln in enumerate(lengths) if ln < _L
            for i in range(min(-(-(int(ln) + W) // _P), _L // _P))]
    assert int(nb.sum()) == len(want)
    assert not nb[lengths >= _L].any()
    held = pa.dense_blocks_held(lengths, W, _L, _P)
    fetched = [b for j, b in enumerate(held) if j == 0 or b != held[j - 1]]
    # before the first live block the map stands on block (0, 0)
    assert [b for b in fetched if b in want] == want
    assert set(fetched) - set(want) <= {(0, 0)}
    assert len(fetched) == len(set(fetched))


def test_dense_read_routing(monkeypatch):
    kw = dict(heads=16, head_dim=128, dtype=jnp.bfloat16, width=1,
              max_len=2048)
    assert pa.dense_read_block(**kw) is None        # the cpu backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.dense_read_block(**kw) == pa.DENSE_BLOCK
    assert pa.dense_read_block(**{**kw, "width": 4}) == pa.DENSE_BLOCK
    assert pa.dense_read_block(**{**kw, "dtype": jnp.int8}) is None
    assert pa.dense_read_block(**{**kw, "max_len": 2000}) is None
    assert pa.dense_read_block(**{**kw, "max_len": 64}) == 64
    assert pa.dense_read_block(**{**kw, "width": 512}) is None   # VMEM
    monkeypatch.undo()
    pa.use_interpret_mode(True)                      # a test's pin routes
    assert pa.dense_read_block(**kw) == pa.DENSE_BLOCK


@pytest.mark.parametrize("kv_dtype,spec_k", [(None, 0), (None, 3),
                                             ("int8", 0)],
                         ids=["f32-w1", "f32-wk", "int8-keeps-xla"])
def test_dense_engine_through_the_kernel(tiny_gpt, monkeypatch, kv_dtype,
                                         spec_k):
    """The default engine's decode step with the kernel engaged: the same
    greedy tokens as the XLA read, ONE decode signature, and
    `decode_kv_read_positions` counts live blocks instead of whole rows."""
    model, cfg = tiny_gpt
    prompts = _prompts(cfg, 5, seed=3)
    kw = dict(max_slots=3, max_len=64, kv_dtype=kv_dtype)
    if spec_k:
        kw["speculative_k"] = spec_k

    def run():
        eng = Engine(model, **kw)
        try:
            return _run(eng, prompts), eng.stats(), eng._decode_read_block
        finally:
            eng.shutdown()

    base, st0, blk0 = run()
    assert blk0 is None
    assert (st0["decode_kv_read_positions"] ==        # summed over layers
            st0["decode_steps"] * cfg.num_layers * 4 * 64)  # 3 slots + scratch
    monkeypatch.setattr(pa, "DENSE_BLOCK", 16)
    pa.use_interpret_mode(True)
    got, st1, blk1 = run()
    for b, g in zip(base, got):
        np.testing.assert_array_equal(g, b)
    assert st1["decode_compiles"] == 1
    assert st1["decode_kv_live_positions"] == st0["decode_kv_live_positions"]
    if kv_dtype == "int8":
        assert blk1 is None
        assert (st1["decode_kv_read_positions"] ==
                st0["decode_kv_read_positions"])
    else:
        assert blk1 == 16
        assert (st1["decode_kv_live_positions"]
                <= st1["decode_kv_read_positions"]
                < st0["decode_kv_read_positions"] // 2)
