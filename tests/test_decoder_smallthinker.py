"""ISSUE 27: the configuration-driven decoder (SmallThinker's block at test
size: one period of [global, window x 3], 4 query / 2 KV heads of 16, window
8, 8 experts top-2 of width 32) against the plain reference of the benchmark
(`benchmark/reference_smallthinker.py`), on the normal path and through
`serving.Engine`; the dropless expert layer; the grouped-query / window
kernels in interpret mode; every Engine option with this model."""
import argparse
import dataclasses
import glob
import json
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.distributed.models.moe.dropless import (
    DroplessMoE, collect_load)
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models import build_decoder, build_gpt, gpt_config
from paddle_tpu.models.kv_cache import KernelRead, SlotCache
from paddle_tpu.nn.functional.attention import _sdpa_ref
from paddle_tpu.observability import trace
from paddle_tpu.serving import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import reference_smallthinker as ref  # noqa: E402

TOL = 1e-4      # float32 against the float32 reference


def _model(seed=3, **overrides):
    paddle.seed(seed)
    m = build_decoder("smallthinker-tiny", **overrides)
    m.eval()
    return m, dataclasses.asdict(m.decoder.config)


@pytest.fixture(scope="module")
def tiny():
    return _model()


def _ref_logits(model, cfg, ids, rows):
    ids = np.asarray(ids)
    pad = (-len(ids)) % 8                       # right padding is causal
    return np.asarray(ref.logits_at(model.state_dict(), np.pad(ids, (0, pad)),
                                    np.asarray(rows), cfg, block=8))


def _prompts(lengths, seed=0, vocab=256):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, n).astype(np.int64) for n in lengths]


# -- (a) the model's full forward ----------------------------------------------

@pytest.mark.parametrize("seed", [3, 11])
def test_forward_matches_reference(seed):
    model, cfg = _model(seed)
    ids = np.stack(_prompts((40, 40), seed))
    got = np.asarray(model(paddle.to_tensor(ids))._value)
    for b in range(2):
        want = _ref_logits(model, cfg, ids[b], np.arange(40))
        np.testing.assert_allclose(got[b], want, atol=TOL, rtol=0)


def test_long_prefill_runs_the_experts_in_token_chunks(monkeypatch):
    """Above `_TOKEN_CHUNK` tokens the assignments are permuted and
    multiplied a chunk at a time (a 16,384-token prefill would otherwise
    hold three [98304, 2560] arrays): same logits, same load counts."""
    from paddle_tpu.incubate.distributed.models.moe import dropless
    model, cfg = _model(3)
    ids = np.stack(_prompts((48,), 8))
    with collect_load() as whole:
        want = np.asarray(model(paddle.to_tensor(ids))._value)
    monkeypatch.setattr(dropless, "_TOKEN_CHUNK", 16)
    with collect_load() as chunked:
        got = np.asarray(model(paddle.to_tensor(ids))._value)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    assert chunked.total().tolist() == whole.total().tolist()
    np.testing.assert_allclose(
        got[0], _ref_logits(model, cfg, ids[0], np.arange(48)), atol=TOL,
        rtol=0)


def test_published_preset_has_the_published_sizes():
    from benchmark import flops_smallthinker as fs
    from paddle_tpu.models.decoder import decoder_config
    cfg = dataclasses.asdict(decoder_config("smallthinker-21b-a3b"))
    assert cfg["rope_layout"] == (0, 1, 1, 1) * 13 == \
        cfg["sliding_window_layout"]
    assert fs.num_params(cfg) == pytest.approx(21.5e9, rel=0.01)
    # a layer: 21.14 M of attention, router and norms; 377.49 M of experts
    p = fs.layer_params(cfg)
    assert p["attention"] + p["router"] + p["norms"] == 21_140_480
    assert p["experts"] == 377_487_360


# -- (b) prefill, then decode on the per-slot dense pool ------------------------

@pytest.mark.parametrize("read", ["xla", "kernel"])
def test_per_slot_cache_logits_match_reference(tiny, read):
    """The engine's cache protocol by hand: three slots at different
    lengths — one under the window (8) that grows past it, one prompt longer
    than it, one parked — prefilled, then decoded 10 steps on
    `[rows, 32, 2, 16]` pools; the logits at every generated position
    against the reference's full forward."""
    model, cfg = tiny
    if read == "kernel":
        pa.use_interpret_mode(True)
    L, n_layers = 32, cfg["num_hidden_layers"]
    prompts = _prompts((3, 13), seed=5)
    pools = [(jnp.zeros((3, L, 2, 16)), jnp.zeros((3, L, 2, 16)))
             for _ in range(n_layers)]
    lengths = np.array([0, 0, L], np.int32)            # row 2 is parked
    ids = np.zeros((3, 16), np.int64)
    for r, p in enumerate(prompts):
        ids[r, :len(p)] = p

    def step(ids_, lens):
        if read == "xla":
            # the Paddle-shaped spelling: a static triple whose length is a
            # [B] vector is the dense, unquantised per-slot cache
            caches = [(Tensor(k, _internal=True), Tensor(v, _internal=True),
                       jnp.asarray(lens)) for k, v in pools]
            lg, new = model(paddle.to_tensor(ids_), caches=caches)
            return np.asarray(lg._value), [(c[0]._value, c[1]._value)
                                           for c in new]
        # the kernel is asked for as the engine asks: the cache's static
        # `read` field, the block from `dense_read_block`
        blk = pa.dense_read_block(heads=4, kv_heads=2, head_dim=16,
                                  dtype=jnp.float32, width=ids_.shape[1],
                                  max_len=L)
        assert blk == L
        caches = [SlotCache(k, v, jnp.asarray(lens),
                            read=KernelRead("dense", blk)) for k, v in pools]
        lg, new = model(paddle.to_tensor(ids_), caches=caches)
        assert all(c.read == caches[0].read and c.layout == "dense"
                   for c in new)
        return np.asarray(lg._value), [(c.k, c.v) for c in new]

    # prefill through the per-slot branch (a tail prefill from position 0)
    lg, pools = step(ids, lengths)
    seqs = [list(p) for p in prompts]
    last = [lg[r, len(p) - 1] for r, p in enumerate(prompts)]
    # the padded positions past each prompt were written too: decode
    # overwrites them one by one, and the mask never admits the rest
    lengths[:2] = [len(p) for p in prompts]
    for _ in range(10):
        for r in range(2):
            want = _ref_logits(model, cfg, seqs[r], [len(seqs[r]) - 1])[0]
            np.testing.assert_allclose(last[r], want, atol=TOL, rtol=0)
            seqs[r].append(int(np.argmax(want)))
        nxt = np.array([[seqs[0][-1]], [seqs[1][-1]], [0]], np.int64)
        lg, pools = step(nxt, lengths)
        last = [lg[0, 0], lg[1, 0]]
        lengths[:2] += 1
    assert lengths[0] > 8 and len(prompts[1]) > 8      # both past the window


def _serve(model, prompts, new=24, **kw):
    eng = Engine(model, **{"max_slots": 3, "max_len": 64,
                           "prefill_batch": 2, **kw})
    try:
        hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
        return [h.result(timeout=600) for h in hs], eng.stats(), eng
    finally:
        eng.shutdown()


def _deficit(model, cfg, prompts, outs):
    """(greedy tokens identical to the reference's, largest deficit of the
    engine's token under the reference's best logit)."""
    same, worst = True, 0.0
    for p, toks in zip(prompts, outs):
        ids = np.concatenate([p, toks[:-1]])
        lg = _ref_logits(model, cfg, ids, np.arange(len(p) - 1, len(ids)))
        same &= bool((lg.argmax(-1) == np.asarray(toks)).all())
        worst = max(worst, float(np.max(
            lg.max(-1) - lg[np.arange(len(toks)), toks])))
    return same, worst


_MIXED = (3, 5, 12, 20, 7, 30)      # under the window, over it, 6 > 3 slots


@pytest.mark.parametrize("read", ["xla", "kernel"])
def test_engine_greedy_tokens_match_reference(tiny, read):
    """Batched prefill, then decode on the dense pool: six requests over
    three slots, contexts that start under the window and grow past it and
    prompts longer than it; the tokens are the reference's greedy tokens."""
    model, cfg = tiny
    if read == "kernel":
        pa.use_interpret_mode(True)
    prompts = _prompts(_MIXED)
    outs, st, eng = _serve(model, prompts)
    assert _deficit(model, cfg, prompts, outs) == (True, 0.0)
    assert st["decode_compiles"] == 1
    assert eng._decode_read_block == (None if read == "xla" else 64)


def test_engine_sizes_come_from_the_cache_shapes(tiny):
    """Pools hold KV heads (2), not query heads (4); the layers' windows
    bound `kv_live`, and on the kernel `kv_read`."""
    model, cfg = tiny
    pa.use_interpret_mode(True)
    outs, st, eng = _serve(model, _prompts((30,)), new=20, max_slots=1,
                           max_len=64, prefill_batch=1)
    assert eng._kv_pool.k[0].shape == (2, 64, 2, 16)
    assert (eng._kv_pool.layout, eng._kv_pool.quantized) == ("dense", False)
    assert eng._kv_windows == [None, 8, 8, 8]
    # 19 decode steps at lengths 30..48: a global layer admits length + 1,
    # each of the three window layers 8
    steps = st["decode_steps"]
    assert st["decode_kv_live_positions"] == sum(
        (30 + i + 1) + 3 * 8 for i in range(steps))
    # one 64-position block per row and layer (max_len is one block)
    assert st["decode_kv_read_positions"] == steps * 4 * 64


# -- every other Engine option ---------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(paged_kv=True),
    dict(speculative_k=3),
    dict(sample_on_device=False),
    dict(paged_kv=True, prefix_cache=True, prefix_block=4, speculative_k=2),
], ids=["paged", "speculative", "host-sampler", "paged-prefix-spec"])
def test_engine_options_keep_the_greedy_tokens(tiny, kw):
    model, cfg = tiny
    pa.use_interpret_mode(True)
    prompts = _prompts(_MIXED)
    outs, st, _ = _serve(model, prompts, **kw)
    assert _deficit(model, cfg, prompts, outs) == (True, 0.0)
    assert st["decode_compiles"] == 1
    assert st["moe_assignments"] > 0


def test_engine_mixed_sampling_rows_share_one_program(tiny):
    """ISSUE 28: a greedy, a temperature-only and a top-k request decoding
    together on this model get the tokens each gets alone at its seed, the
    greedy row's are the reference's, and the step counters name the branch
    the one decode program took (greedy steps draw and sort nothing)."""
    model, cfg = tiny
    prompts = _prompts((5, 12, 20), seed=4)
    params = [{}, {"temperature": 0.8, "seed": 7},
              {"temperature": 0.9, "top_k": 6, "seed": 9}]
    keys = ("decode_steps", "decode_sampled_steps", "decode_topk_steps")
    eng = Engine(model, max_slots=3, max_len=64, prefill_batch=2)
    try:
        alone, steps = [], []
        for p, kw in zip(prompts, params):
            st0 = eng.stats()
            alone.append(eng.submit(p, max_new_tokens=12, **kw)
                            .result(timeout=600))
            steps.append([eng.stats()[k] - st0[k] for k in keys])
        hs = [eng.submit(p, max_new_tokens=12, **kw)
              for p, kw in zip(prompts, params)]
        together = [h.result(timeout=600) for h in hs]
        st = eng.stats()
    finally:
        eng.shutdown()
    assert steps == [[11, 0, 0], [11, 11, 0], [11, 11, 11]]
    for a, t in zip(alone, together):
        np.testing.assert_array_equal(a, t)
    assert _deficit(model, cfg, prompts[:1], together[:1]) == (True, 0.0)
    assert st["decode_compiles"] == 1


def test_engine_prefix_cache_prefills_the_tail_only(tiny):
    """A shared 16-token head: the second wave copies the cached rows and
    prefills tails through the per-slot branch (window mask, RoPE at the
    slots' own positions)."""
    model, cfg = tiny
    head = _prompts((16,), seed=9)[0]
    prompts = [np.concatenate([head, t]) for t in _prompts((3, 9, 5, 12), 2)]
    eng = Engine(model, max_slots=2, max_len=64, prefix_cache=True,
                 prefix_block=4)
    try:
        outs = [eng.submit(p, max_new_tokens=12).result(timeout=600)
                for p in prompts]
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["prefix_hits"] >= 2 and st["tail_prefill_compiles"] >= 1
    assert _deficit(model, cfg, prompts, outs) == (True, 0.0)


def test_engine_int8_pool_stays_close(tiny):
    """int8 KV with one scale per cached position: tokens may differ where
    two logits are close, so the limit is on the deficit — 0.01, twenty
    times the 5e-4 this reads, a hundredth of what a wrong mask reads."""
    model, cfg = tiny
    prompts = _prompts(_MIXED)
    outs, st, _ = _serve(model, prompts, kv_dtype="int8")
    assert _deficit(model, cfg, prompts, outs)[1] < 0.01


@pytest.mark.parametrize("kw,word", [
    (dict(adapters=object()), "adapters"),
    (dict(paged_kv=True, decode_kernel="pallas"), "decode_kernel='pallas'"),
], ids=["adapters", "paged-kernel"])
def test_engine_refuses_what_the_model_cannot_serve(tiny, kw, word):
    with pytest.raises(ValueError, match="cannot be served with " + word):
        Engine(tiny[0], max_slots=2, max_len=32, **kw)


# -- spans and counters -----------------------------------------------------------

def test_moe_load_rides_the_emit_spans_and_sums_in_stats(tiny):
    model, cfg = tiny
    prompts = _prompts((5, 11))
    trace.clear()
    outs, st, _ = _serve(model, prompts, new=6, max_slots=2, prefill_batch=1)
    emits = [types.SimpleNamespace(name=s["name"], attrs=s["attrs"])
             for s in trace.spans()
             if s["name"] in ("serving.decode.emit", "serving.prefill.emit")]
    keys = ("moe_assignments", "moe_experts_touched", "moe_load_max")
    assert emits and all(set(keys) <= set(s.attrs) for s in emits)
    for k in keys:
        assert sum(s.attrs[k] for s in emits) == st[k]
    # real tokens only: 16 prompt tokens and 10 decoded ones, top-2, 4 layers
    assert st["moe_assignments"] == (16 + 10) * 2 * 4
    pre = [s for s in emits if s.name == "serving.prefill.emit"]
    assert sorted(s.attrs["moe_assignments"] for s in pre) == [40, 88]
    for s in emits:
        n = s.attrs["moe_assignments"] // 8           # tokens of the step
        assert 4 <= s.attrs["moe_experts_touched"] <= 4 * min(8, 2 * n)
        assert 4 * -(-2 * n // 8) <= s.attrs["moe_load_max"] <= 4 * n


# -- (c) (d) the dropless expert layer ---------------------------------------------

def _dense_moe(layer, x, router_input):
    """Every expert on every token, weighted by the routing weight (zero
    outside the top k), in numpy float64."""
    g = lambda t: np.asarray(t._value, np.float64)        # noqa: E731
    x, r = np.asarray(x, np.float64), np.asarray(router_input, np.float64)
    logits = r @ g(layer.w_router)
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-logits[t], kind="stable")[:layer.top_k]
        w = np.exp(logits[t, top] - logits[t, top].max())
        for e, p in zip(top, w / w.sum()):
            act = np.maximum(x[t] @ g(layer.w_gate)[e], 0) * \
                (x[t] @ g(layer.w_up)[e])
            y[t] += p * (act @ g(layer.w_down)[e])
    return y


def test_forced_routing_drops_nothing():
    """A zero router ties every logit: every token of every layer goes to
    experts 0 and 1.  Nothing is dropped — the forward still equals the
    reference, and the load counters say where the tokens went."""
    model, cfg = _model(5)
    for layer in model.decoder.layers:
        layer.moe.w_router._replace_(
            jnp.zeros_like(layer.moe.w_router._value), None)
    ids = np.stack(_prompts((24,), 4))
    with collect_load() as load:
        got = np.asarray(model(paddle.to_tensor(ids))._value)
    np.testing.assert_allclose(
        got[0], _ref_logits(model, cfg, ids[0], np.arange(24)), atol=TOL,
        rtol=0)
    # 4 layers: 24 tokens x 2, on 2 experts, 24 on the fullest; every
    # expert is held, so all that was routed was assigned here
    assert load.total().tolist() == [4 * 48, 4 * 2, 4 * 24, 4 * 48]


def test_experts_held_shares_add_up_to_the_layer():
    paddle.seed(1)
    whole = DroplessMoE(64, 32, 8, 2)
    rs = np.random.RandomState(0)
    x, r = rs.randn(2, 9, 64).astype(np.float32), \
        rs.randn(2, 9, 64).astype(np.float32)
    total = np.zeros((2, 9, 64), np.float32)
    for first in range(0, 8, 2):
        share = DroplessMoE(64, 32, 8, 2, experts_held=(first, 2))
        share.w_router._replace_(whole.w_router._value, None)
        for n in ("w_gate", "w_up", "w_down"):
            getattr(share, n)._replace_(
                getattr(whole, n)._value[first:first + 2], None)
        total += np.asarray(share(paddle.to_tensor(x),
                                  router_input=paddle.to_tensor(r))._value)
    want = _dense_moe(whole, x.reshape(-1, 64), r.reshape(-1, 64))
    got = np.asarray(whole(paddle.to_tensor(x),
                           router_input=paddle.to_tensor(r))._value)
    np.testing.assert_allclose(got.reshape(-1, 64), want, atol=TOL, rtol=0)
    np.testing.assert_allclose(total.reshape(-1, 64), want, atol=TOL, rtol=0)


def test_experts_held_outside_the_experts_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        DroplessMoE(64, 32, 8, 2, experts_held=(6, 4))


# -- (e) the kernels, interpreted ----------------------------------------------------

def _masked_read(q, k, v, lengths, window):
    B, W, H, D = q.shape
    L, g = k.shape[1], H // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bwhd,blhd->bhwl", q, k) / np.sqrt(D)
    pos = lengths[:, None] + jnp.arange(W)[None]
    key = jnp.arange(L)[None, None, :]
    keep = key <= pos[:, :, None]
    if window is not None:
        keep &= key > pos[:, :, None] - window
    s = jnp.where(keep[:, None], s, -1e30)
    return jnp.einsum("bhwl,blhd->bwhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("window", [None, 12, 3])
@pytest.mark.parametrize("W", [1, 3])
def test_dense_decode_read_grouped_window(window, W):
    """2 KV heads for 4 query heads, a window, a parked row: the kernel
    against the masked XLA read, and its work list against the window."""
    pa.use_interpret_mode(True)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (5, W, 4, 16))
    k = jax.random.normal(ks[1], (5, 64, 2, 16))
    v = jax.random.normal(ks[2], (5, 64, 2, 16))
    lengths = jnp.asarray([0, 5, 30, 60, 64], jnp.int32)
    got = pa.dense_decode_attention(q, k, v, lengths, block=8, window=window)
    want = _masked_read(q, k, v, lengths, window)
    np.testing.assert_allclose(got[:4], want[:4], atol=1e-5, rtol=0)
    assert not np.asarray(got[4]).any()                 # parked: zeros
    nb = pa.live_blocks(np.asarray(lengths), W, 64, 8, window)
    if window is not None:
        assert nb.max() <= -(-(window + W - 1) // 8) + 1
    held = pa.dense_blocks_held(np.asarray(lengths), W, 64, 8, window)
    assert len(set(held)) == int(nb.sum())              # each block once


@pytest.mark.parametrize("b,t,h,hk,window,tiles", [
    (2, 64, 4, 2, 8, None), (1, 200, 6, 2, 24, None),
    (2, 256, 4, 2, 40, (32, 64)), (1, 250, 8, 2, None, (32, 64)),
    (1, 256, 4, 4, 100, (32, 64)),
], ids=["one-tile", "padded", "tiled", "tiled-global-padded", "equal-heads"])
def test_flash_grouped_window_matches_sdpa_ref(monkeypatch, b, t, h, hk,
                                               window, tiles):
    """Flash with grouped-query heads and a window, forward and backward,
    one tile and many (a tile wholly outside the window is skipped)."""
    fa.use_interpret_mode(True)
    if tiles:
        monkeypatch.setattr(fa, "_block_sizes",
                            lambda tq, tk: (min(tiles[0], tq),
                                            min(tiles[1], tk)))
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, t, h, 16))
    k = jax.random.normal(ks[1], (b, t, hk, 16))
    v = jax.random.normal(ks[2], (b, t, hk, 16))
    do = jax.random.normal(ks[3], (b, t, h, 16))
    o, vjp = jax.vjp(lambda *a: fa.flash_attention_bthd(
        *a, causal=True, window=window), q, k, v)
    o2, vjp2 = jax.vjp(lambda *a: _sdpa_ref(
        *a, None, 0.0, True, 0.25, False, window), q, k, v)
    np.testing.assert_allclose(o, o2, atol=1e-5, rtol=0)
    for g, g2 in zip(vjp(do), vjp2(do)):
        np.testing.assert_allclose(g, g2, atol=1e-5, rtol=0)


# -- (f) GPT through the same cache code and kernel -----------------------------------

def test_gpt_decode_through_the_shared_kernel_equals_the_xla_read(
        monkeypatch):
    """`models/gpt.py` and `models/decoder.py` share `kv_cache.
    cached_attention`: GPT's decode through the dense kernel (equal head
    counts, no window) gives the XLA read's greedy tokens, as PR 26's tests
    hold it to."""
    paddle.seed(7)
    model = build_gpt(gpt_config("gpt-tiny", max_position_embeddings=128,
                                 hidden_dropout_prob=0.0,
                                 attention_dropout_prob=0.0))
    model.eval()
    prompts = _prompts((3, 7, 17, 11), 3, vocab=1024)
    base, st0, eng0 = _serve(model, prompts, new=8)
    monkeypatch.setattr(pa, "DENSE_BLOCK", 16)
    pa.use_interpret_mode(True)
    got, st1, eng1 = _serve(model, prompts, new=8)
    for a, b in zip(base, got):
        np.testing.assert_array_equal(a, b)
    assert (eng0._decode_read_block, eng1._decode_read_block) == (None, 16)
    assert st1["decode_kv_read_positions"] < st0["decode_kv_read_positions"]
    assert st1["moe_assignments"] == 0 and not eng1._moe_load


# -- the benchmark's new driver, rehearsed on the CPU ------------------------------------

def _tiny_cell():
    from paddle_tpu.models.decoder import decoder_config
    config = dict(
        dataclasses.asdict(decoder_config("smallthinker-tiny")),
        name="smallthinker-tiny-serve", kind="serve_decoder",
        model="smallthinker-tiny", param_dtype="float32",
        engine={"max_slots": 4, "max_len": 128, "prefill_batch": 1},
        gateway_tenant={"max_queue": 512}, check_requests=3,
        check_controls={"int8_weights": {"reference_weights": "int8"}},
        # float32 on the CPU: the engine reads ~1e-7, the int8 control 2e-3,
        # the weakest structural fault (no RoPE at these sizes) 6e-4: the
        # limit is a hundredth of the int8 control's reading
        logit_tolerance=0.01, logit_tolerance_over="int8_weights")
    config.pop("experts_held")
    mix = {"loop": "closed", "clients": 8, "max_rps": 60, "ramp_s": 1,
           "order_seed": 27,
           "prompt": {"median": 24, "sigma": 0.8, "min": 4, "max": 80},
           "output": {"median": 10, "sigma": 0.7, "min": 2, "max": 30},
           "trace_at_frac": 0.3, "trace_s": 1}
    cell = {"name": "rehearse-smallthinker-tiny", "chips": 1,
            "platform": "cpu",
            "metrics_as": "serve-smallthinker-mixed-saturated"}
    return cell, config, mix


def test_serve_decoder_driver_rehearsal(tmp_path):
    """`serve_decoder_driver.run` through a `Ctx` built as `run.py` builds
    it: the tiny preset, a 3 s window on the CPU.  `correct` (the engine's
    tokens against the reference's full forward, a context past the window
    among those checked), the end-to-end keys, and every new reader returns
    a number or None without raising."""
    import importlib
    from benchmark import run as bench_run
    from benchmark import serve_decoder_driver
    cell, config, mix = _tiny_cell()
    args = argparse.Namespace(seed=2 ** 31 + 5, seconds=3.0, trace=0)
    ctx = bench_run.Ctx(cell, config, mix, args, jax.devices()[:1],
                        bench_run.CompileLog())
    res = serve_decoder_driver.run(ctx)
    assert res["correct"], res["notes"]
    control = res["notes"]["controls"]["int8_weights"]
    assert not control["correct"], control
    assert control["logprob_error_mean"] > 1e-3 > \
        res["notes"]["logprob_error_mean"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["notes"]["longest_context_checked"] > 8
    assert res["end_to_end"]["serve_tokens_per_s"] > 0
    assert res["setup_s"] > 0
    obs = dict(res["observations"], memory_peak_bytes=0, device_kind="cpu",
               chips=1, config=config, trace=None)
    assert obs["moe_assignments"] > 0 and obs["model_flops"] > 0
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = [m for m in manifest["per_layer"]
            if cell["metrics_as"] in m.get("workloads", ())]
    assert len(mine) == 13              # 12 new ones and decode_occupancy
    values = {}
    for m in mine:
        spec = json.load(open(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".json")))
        mod, fn = spec["reader"].rsplit(".", 1)
        reader = getattr(importlib.import_module(f"benchmark.{mod}"), fn)
        values[m["name"]] = reader(obs, **spec.get("args", {}))
        assert values[m["name"]] is None or \
            np.isfinite(float(values[m["name"]]))
    # what needs no chip is a number here; 8 experts, so 1 <= skew <= 8
    assert 1.0 <= values["moe.load_max_over_mean.smallthinker"] <= 8.0
    assert 0 < values["engine.decode_occupancy"] <= 100
    assert values["engine.itl_p50_ms.smallthinker"] > 0


_CONTROLS = {
    "int8_weights": {"reference_weights": "int8"},
    "no_rope": {"rope_layout": [0] * 4},
    "no_window": {"sliding_window_layout": [0] * 4},
    "window_one_short": {"sliding_window_size": 7},
    "one_expert_of_two": {"moe_num_active_primary_experts": 1},
}


@pytest.fixture(scope="module")
def checked(tiny):
    """`serve_decoder_driver._check` of an engine's tokens and
    log-probabilities, every control of `_CONTROLS` run beside them."""
    from benchmark import serve_decoder_driver as sdd
    model, _ = tiny
    _, config, _ = _tiny_cell()
    config["check_controls"] = _CONTROLS
    prompts = _prompts(_MIXED)
    eng = Engine(model, max_slots=3, max_len=64, prefill_batch=2)
    try:
        hs = [eng.submit(p, max_new_tokens=24) for p in prompts]
        sample = [(p, h.result(timeout=600), h.logprobs)
                  for p, h in zip(prompts, hs)]
    finally:
        eng.shutdown()
    return config, sdd._check(model, config, sample)


def test_check_passes_the_engine(checked):
    config, check = checked
    assert check["within_tolerance"], check
    assert check["tokens_checked"] == 24 * len(_MIXED) == \
        check["argmax_matches"]
    assert check["logprob_error_mean"] < 1e-6      # float32 both sides


@pytest.mark.parametrize("control", sorted(_CONTROLS))
def test_check_refuses_every_control(checked, control):
    """Lower precision and each structural fault (a position encoding, a
    mask, the window's edge — one key of 8 here —, an assignment), each as
    a setting of the reference run as a system of its own on the same rows,
    comes out NOT correct by the same limit."""
    config, check = checked
    got = check["controls"][control]
    assert not got["correct"], got
    assert got["logprob_error_mean"] > 10 * config["logit_tolerance"] * \
        check["controls"]["int8_weights"]["logprob_error_mean"]


def test_both_serve_drivers_report_the_same_things(monkeypatch):
    """`serve_decoder_driver.run` is a copy of `serve_driver.run` with the
    builder, the checker and some observations replaced (PERF.md section 7
    asks the next `benchmark` PR to fold them).  Until then: on one fake
    engine, gateway and client report, both return the same keys, count
    the same requests and compute the same end-to-end numbers."""
    from benchmark import serve_decoder_driver as sdd
    from benchmark import serve_driver as sd
    T = 10.0
    requests = [{"id": f"r{i}", "prompt": [1, 2, 3], "max_tokens": 3,
                 "counted": True} for i in range(5)]

    def result(i, sent, stamps, done=True, status=200, error=None):
        return {"id": f"r{i}", "sent": sent, "due": sent, "stamps": stamps,
                "done": done, "status": status, "error": error}

    results = [result(0, 0.5, [1.0, 1.1, 1.3]),
               result(1, 2.0, [2.5, 2.6, 2.9]),
               result(2, 9.0, [9.5, 10.2], done=False),   # cut by the end
               result(3, 4.0, [4.1], status=200),         # short: a failure
               result(4, -1.0, [0.2, 0.3, 0.4])]          # sent in the ramp

    class Handle:
        def __init__(self, i):
            self.journey = types.SimpleNamespace(id=f"r{i}")
            self.tokens, self.logprobs = [7, 8, 9], [-1.0, -1.0, -1.0]
            self.token_latencies_s, self.ttft_s = [0.1, 0.2], 0.3
            self.t_submit, self.t_admit = 0.0, 0.1
            self.prompt = np.zeros(3, np.int64)

    class FakeEngine:
        max_slots, max_len = 4, 64

        def stats(self):
            return {k: 0 for k in sdd._COUNTERS}

    stack = types.SimpleNamespace(close=lambda: None, port=0)
    setup = {"setup_s": 1.0, "setup_compile_s": 0.5, "setup_hits": 1,
             "setup_requests": 2}
    delta = {"tokens": 11, "decode_steps": 4, "slot_allocs": 5,
             "completed": 3}
    size = types.SimpleNamespace(vocab_size=256, num_layers=1,
                                 num_attention_heads=1,
                                 layer_norm_epsilon=1e-5)
    out, box = {}, {}
    for mod, check in ((sd, "_check_logits"), (sdd, "_check")):
        def build(ctx, handles):
            box["handles"] = handles
            return size, None, FakeEngine(), stack

        def drive(ctx, engine, stack, requests):
            # the admission hook's side of the window, and its two edges
            box["handles"].extend(Handle(i) for i in range(5))
            engine.stats(), engine.stats()
            return results, dict(setup), dict(delta), 0

        monkeypatch.setattr(mod, "_build", build)
        monkeypatch.setattr(mod, "_warm", lambda *a: None)
        monkeypatch.setattr(mod, "_drive", drive)
        monkeypatch.setattr(mod.traffic, "make_requests",
                            lambda *a: requests)
        monkeypatch.setattr(mod, check, lambda *a: {
            "tokens_checked": 6, "logit_deficit_max": 0.0,
            "within_tolerance": True, "longest_context_checked": 6,
            "argmax_matches": 6})
        cell, config, mix = _tiny_cell()
        ctx = types.SimpleNamespace(
            config=dict(config, logit_tolerance=1.0), mix=mix, seconds=T,
            seed=5, cell=cell)
        out[mod.__name__] = mod.run(ctx)
    a, b = out.values()
    assert set(a) == set(b)
    assert set(a["end_to_end"]) == set(b["end_to_end"])
    for k in ("correct", "attempted", "failed", "end_to_end", "setup_s",
              "setup_compile_s", "setup_hits", "setup_requests"):
        assert a[k] == b[k], k
    assert (a["attempted"], a["failed"], a["correct"]) == (4, 1, False)
    assert a["end_to_end"]["serve_tokens_per_s"] == pytest.approx(1.1)
    shared = ("decode_tokens", "decode_capacity", "engine_token_latency_s")
    assert {k: a["observations"][k] for k in shared} == \
        {k: b["observations"][k] for k in shared}
    for k in ("completed", "completed_rps", "compiles_in_window",
              "ttft_p50_ms", "itl_p50_ms", "fail_sample"):
        assert a["notes"][k] == b["notes"][k], k


def test_device_ops_reads_names_from_a_recorded_trace():
    """`moe_readers.device_ops` on the 12 ms of a v5e trace the benchmark
    keeps: HLO text and JAX op name of every device op, containers left
    out."""
    from benchmark import moe_readers
    path = glob.glob(os.path.join(ROOT, "benchmark", "testdata",
                                  "*.xplane.pb"))[0]
    ops = moe_readers.device_ops(path)
    assert len(ops) > 100 and ops == sorted(ops)
    assert all(e >= s for s, e, *_ in ops)
    assert any(" fusion(" in hlo for _, _, hlo, _ in ops)
    assert not any(hlo.split(" = ")[-1].startswith("while(")
                   for _, _, hlo, _ in ops)
    assert moe_readers.device_ops(path, plane="/device:TPU:9") is None
