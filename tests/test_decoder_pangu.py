"""ISSUE 31: the latent-attention, shared-expert block of `models/decoder.py`
(openPangu-Ultra-MoE's layer at test size: hidden 64, 1 dense + 4 expert
layers, 4 heads with nope 16 / rope 8 / v 16, q rank 24, latent rank 16, 16
experts top-4 of width 32 beside 1 shared, dense width 128, vocabulary 256)
against the plain reference of the benchmark (`benchmark/reference_pangu.py`),
on the normal path and through `serving.Engine` on the latent pool; a share
of the experts; the latent decode kernel and flash at two head sizes in
interpret mode; every Engine option with this model; the benchmark's new
driver rehearsed on the CPU."""
import argparse
import importlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.incubate.distributed.models.moe.dropless import (
    DroplessMoE, collect_load, route_top_k)
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models import build_decoder
from paddle_tpu.models.kv_cache import (KernelRead, KVPool, SlotCache,
                                        _latent_read, cached_latent_attention)
from paddle_tpu.nn.functional.attention import _sdpa_ref
from paddle_tpu.observability import trace
from paddle_tpu.serving import Engine
from paddle_tpu.testing import faults

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import reference_pangu as ref  # noqa: E402
from benchmark import serve_latent_driver as driver  # noqa: E402

TOL = 1e-4      # float32 against the float32 reference


def _config(**overrides) -> dict:
    """The tiny configuration file, in the published config.json's keys."""
    with open(os.path.join(ROOT, "benchmark", "testdata",
                           "openpangu-tiny-serve.json")) as f:
        return dict(json.load(f), **overrides)


def _model(seed=3, **overrides):
    cfg = _config(**overrides)
    return driver.build_model(cfg, seed)[1], cfg


def _ref_logits(model, cfg, ids, rows):
    ids = np.asarray(ids)
    pad = (-len(ids)) % 8                       # right padding is causal
    return np.asarray(ref.logits_at(model.state_dict(), np.pad(ids, (0, pad)),
                                    np.asarray(rows), cfg, block=8))


def _prompts(lengths, seed=0, vocab=256):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, n).astype(np.int64) for n in lengths]


# -- (a) the model's full forward ----------------------------------------------

@pytest.mark.parametrize("held", [4, 16])
def test_forward_matches_reference(held):
    """A share of 4 of 16 experts, and the uncut layer."""
    model, cfg = _model(5, n_routed_experts=held)
    ids = np.stack(_prompts((40, 40), 5))
    got = np.asarray(model(paddle.to_tensor(ids))._value)
    for b in range(2):
        want = _ref_logits(model, cfg, ids[b], np.arange(40))
        np.testing.assert_allclose(got[b], want, atol=TOL, rtol=0)


def test_published_preset_states_the_published_sizes():
    from paddle_tpu.models.decoder import decoder_config
    c = decoder_config("openpangu-ultra-moe-718b")
    assert (c.hidden_size, c.num_hidden_layers, c.num_attention_heads) == \
        (7680, 61, 128)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (c.moe_num_primary_experts, c.moe_num_active_primary_experts,
            c.moe_ffn_hidden_size, c.n_shared_experts) == (256, 8, 2048, 1)
    assert (c.first_k_dense_replace, c.intermediate_size, c.vocab_size) == \
        (3, 18432, 153600)
    assert c.scoring_func == "sigmoid" and c.routed_scaling_factor == 2.5
    assert c.sandwich_norm and c.hidden_act == "silu"


@pytest.mark.parametrize("key, value, weight", [
    ("embedding_std", 1.0, None),
    ("sandwich_norm_gain", 0.09, "layers.2.mlp_out_norm.weight"),
    ("q_norm_gain", 3.0, "layers.2.self_attn.q_a_norm.weight"),
])
def test_seeded_initialisation_is_the_configuration_files(key, value, weight):
    """`assumed.weights`: each key of the configuration file that states
    the seeded initialisation reaches the weights, and the reference, which
    takes the model's own `state_dict()`, follows it (the q norm's gain
    scales every attention score: a model that attends three times as
    peaked still equals its reference)."""
    model, cfg = _model(4, **{key: value})
    state = model.state_dict()
    if weight is None:
        std = float(np.std(np.asarray(state["decoder.embed_tokens.weight"]
                                      ._value)))
        assert abs(std - value) < 0.05 * value
    else:
        np.testing.assert_array_equal(
            np.asarray(state["decoder." + weight]._value),
            np.float32(value))
    ids = _prompts((40,), 4)[0]
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    np.testing.assert_allclose(got, _ref_logits(model, cfg, ids,
                                                np.arange(40)),
                               atol=TOL, rtol=0)
    plain = np.asarray(_model(4)[0](paddle.to_tensor(ids[None]))._value)[0]
    assert np.max(np.abs(got - plain)) > 100 * TOL


# -- (b) through the Engine, on the latent pool --------------------------------

def _engine_matches_reference(model, cfg, **engine_kw):
    """Several slots at different lengths: greedy tokens and the logits'
    log-probabilities at every generated position against the reference's
    full forward."""
    prompts = _prompts((10, 23, 5, 17), 7)
    new = (9, 6, 12, 7)
    eng = Engine(model, max_slots=3, max_len=64, **engine_kw)
    try:
        hs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
        outs = [h.result(timeout=300) for h in hs]
        stats = eng.stats()
    finally:
        eng.close()
    for p, h, toks in zip(prompts, hs, outs):
        ids = np.concatenate([p, toks[:-1]])
        lg = _ref_logits(model, cfg, ids, np.arange(len(p) - 1, len(ids)))
        assert lg.argmax(-1).tolist() == list(toks)
        want = lg[np.arange(len(toks)), toks] - (
            lg.max(-1) + np.log(np.exp(lg - lg.max(-1, keepdims=True)
                                       ).sum(-1)))
        np.testing.assert_allclose(np.asarray(h.logprobs), want, atol=TOL,
                                   rtol=0)
    return stats


def test_engine_prefill_then_decode_matches_reference():
    model, cfg = _model(3)
    st = _engine_matches_reference(model, cfg)
    # the pool's real bytes: rows x positions x (rank + rope) x 4 B x layers
    assert st["kv_pool_bytes"] == 4 * 64 * 24 * 4 * 5
    assert st["moe_routed"] > st["moe_assignments"] > 0
    # 4 expert layers x 4 per token, over the real tokens of every step
    assert st["moe_routed"] % 16 == 0


def test_engine_decode_through_the_kernel_matches_reference():
    """The decode program's read through `latent_decode_read`, interpreted
    (the engine chooses it at build wherever the backend is not the cpu)."""
    model, cfg = _model(3)
    pa.use_interpret_mode(True)
    try:
        st = _engine_matches_reference(model, cfg)
    finally:
        pa.use_interpret_mode(None)
    # live blocks of min(LATENT_BLOCK, 64) positions a layer, not the XLA
    # read's rows x max_len
    assert 0 < st["decode_kv_read_positions"] <= \
        st["decode_steps"] * 3 * 64 * 5
    assert st["decode_kv_read_positions"] % 64 == 0


def test_engine_prefix_cache_and_speculation_match_reference():
    """A prefix hit copies a latent row and prefills the tail through the
    absorbed read; a speculative step verifies k positions through it."""
    model, cfg = _model(3)
    head = _prompts((32,), 9)[0]
    a = np.concatenate([head, _prompts((5,), 1)[0]])
    b = np.concatenate([head, _prompts((7,), 2)[0]])
    for kw in ({"prefix_cache": True}, {"speculative_k": 3}):
        eng = Engine(model, max_slots=2, max_len=64, **kw)
        try:
            outs = [eng.submit(p, max_new_tokens=6).result(timeout=300)
                    for p in (a, b)]
            st = eng.stats()
        finally:
            eng.close()
        if "prefix_cache" in kw:
            assert st["prefix_hits"] >= 1
        for p, toks in zip((a, b), outs):
            ids = np.concatenate([p, toks[:-1]])
            lg = _ref_logits(model, cfg, ids,
                             np.arange(len(p) - 1, len(ids)))
            assert lg.argmax(-1).tolist() == list(toks), kw


# -- (c) absorbed = expanded; the kernels in interpret mode --------------------

def _latent_case(seed=0, rows=3, L=64, heads=4, nope=16, rope=8, v=16,
                 rank=16, t=1):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.standard_normal(s), jnp.float32)  # noqa
    return dict(qn=f(rows, t, heads, nope), qr=f(rows, t, heads, rope),
                pool=f(rows, L, rank + rope), new=f(rows, t, rank + rope),
                w=f(rank, heads * (nope + v)) * 0.2,
                lengths=jnp.asarray([5, 40, 63 - t + 1][:rows], jnp.int32))


def _expanded(c, nope, v):
    """softmax((qn kn + qr kr) / sqrt(nope + rope)) v from the written
    latent rows, per row at its own length: the expanded form."""
    rows, t, heads, _ = c["qn"].shape
    rank = c["w"].shape[0]
    w = np.asarray(c["w"]).reshape(rank, heads, nope + v)
    out = np.zeros((rows, t, heads, v))
    for r in range(rows):
        n = int(c["lengths"][r])
        lat = np.array(c["pool"][r])
        lat[n:n + t] = np.asarray(c["new"][r])
        for j in range(t):
            ctx = lat[:n + j + 1]
            kv = np.einsum("lc,chd->lhd", ctx[:, :rank], w)
            s = (np.einsum("hd,lhd->hl", np.asarray(c["qn"][r, j]),
                           kv[..., :nope]) +
                 np.asarray(c["qr"][r, j]) @ ctx[:, rank:].T) / np.sqrt(
                     nope + c["qr"].shape[-1])
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[r, j] = np.einsum("hl,lhd->hd", p, kv[..., nope:])
    return out


@pytest.mark.parametrize("t", [1, 3])
def test_absorbed_read_equals_expanded_form(t):
    c = _latent_case(1, t=t)
    want = _expanded(c, 16, 16)
    for read in (None, KernelRead("dense", 16)):
        pa.use_interpret_mode(True)
        try:
            out, new = cached_latent_attention(
                c["qn"], c["qr"], c["new"], c["w"],
                SlotCache(c["pool"], None, c["lengths"], read=read))
        finally:
            pa.use_interpret_mode(None)
        np.testing.assert_allclose(np.asarray(out._value), want, atol=1e-5,
                                   rtol=0)
        assert new.v is None and new.lengths.tolist() == \
            (c["lengths"] + t).tolist()


def test_long_prompt_expands_a_group_of_heads_at_a_time(monkeypatch):
    """Past `_EXPANDED_PAIRS` (position, head) pairs the expanded form runs
    over groups of heads; the result is the ungrouped one."""
    from paddle_tpu.models import kv_cache
    c = _latent_case(4, rows=2, t=24)
    args = (c["qn"], c["qr"], c["new"], c["w"])
    whole, cache = cached_latent_attention(*args)
    monkeypatch.setattr(kv_cache, "_EXPANDED_PAIRS", 24 * 4 // 4)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: cached_latent_attention(*a)[0]._value)(*args))
    assert "scan" in jaxpr or "while" in jaxpr          # four groups of one
    grouped, _ = cached_latent_attention(*args)
    np.testing.assert_allclose(np.asarray(grouped._value),
                               np.asarray(whole._value), atol=1e-5, rtol=0)
    assert cache[1] is None and cache[0].shape == [2, 24, 24]


def test_latent_kernel_equals_masked_xla_read_and_skips_dead_blocks():
    c = _latent_case(2, rows=3, L=64)
    q = jnp.concatenate([jnp.einsum("bthn,chn->bthc", c["qn"],
                                    c["w"].reshape(16, 4, 32)[..., :16]),
                         c["qr"]], -1)
    lengths = jnp.asarray([5, 40, 64], jnp.int32)      # the last row parked
    cols = lengths[:, None] + jnp.arange(1)[None, :]
    want = _latent_read(q, c["pool"], cols, 24 ** -0.5, 16)
    pa.use_interpret_mode(True)
    try:
        got = pa.latent_decode_attention(q, c["pool"], lengths, block=16,
                                         scale=24 ** -0.5, values=16)
    finally:
        pa.use_interpret_mode(None)
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]),
                               atol=1e-5, rtol=0)
    assert not np.asarray(got[2]).any()                # parked: zeros
    # the work list is the dense kernel's: live blocks only
    assert pa.live_blocks(np.asarray(lengths), 1, 64, 16).tolist() == [1, 3, 0]


@pytest.mark.parametrize("t", [128, 200])
def test_flash_at_two_head_sizes_equals_reference(t):
    """q, k of 24 and v of 16 (192 and 128 at the published sizes), forward."""
    rs = np.random.RandomState(t)
    q, k = (jnp.asarray(rs.standard_normal((2, t, 4, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rs.standard_normal((2, t, 4, 16)), jnp.float32)
    fa.use_interpret_mode(True)
    try:
        got = fa.flash_attention_bthd(q, k, v, causal=True)
        with pytest.raises(NotImplementedError, match="forward only"):
            jax.grad(lambda a: fa.flash_attention_bthd(a, k, v).sum())(q)
    finally:
        fa.use_interpret_mode(False)
    want = _sdpa_ref(q, k, v, None, 0.0, True, 24 ** -0.5, False)
    assert got.shape == (2, t, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)


# -- (d) (e) a share of the experts ----------------------------------------------

def _moe(held=None, seed=1):
    paddle.seed(seed)
    return DroplessMoE(64, 32, 16, 4, experts_held=held, scoring="sigmoid",
                       routed_scale=2.5, activation="silu", shared_width=32)


def _copy_share(whole, part):
    first, count = part.experts_held
    part.w_router._replace_(whole.w_router._value, None)
    for n in ("w_gate", "w_up", "w_down"):
        getattr(part, n)._replace_(
            getattr(whole, n)._value[first:first + count], None)
    for n in ("shared_gate", "shared_up", "shared_down"):
        getattr(part, n)._replace_(getattr(whole, n)._value, None)


def _layer_reference(layer, x):
    """The uncut layer by hand in float64: every expert on every token."""
    g = lambda t: np.asarray(t._value, np.float64)        # noqa: E731
    silu = lambda a: a / (1 + np.exp(-a))                 # noqa: E731
    x = np.asarray(x, np.float64)
    score = 1 / (1 + np.exp(-(x @ g(layer.w_router))))
    y = (silu(x @ g(layer.shared_gate)) * (x @ g(layer.shared_up))) @ \
        g(layer.shared_down)
    for t in range(x.shape[0]):
        top = np.argsort(-score[t], kind="stable")[:layer.top_k]
        for e in top:
            p = 2.5 * score[t, e] / (score[t, top].sum() + 1e-20)
            y[t] += p * ((silu(x[t] @ g(layer.w_gate)[e]) *
                          (x[t] @ g(layer.w_up)[e])) @ g(layer.w_down)[e])
    return y, score


def test_shares_add_up_to_the_uncut_layer():
    """4 shares of 4 experts, the shared expert counted once."""
    whole = _moe()
    x = np.random.RandomState(0).standard_normal((50, 64)).astype(np.float32)
    want, _ = _layer_reference(whole, x)
    np.testing.assert_allclose(
        np.asarray(whole(paddle.to_tensor(x))._value), want, atol=TOL, rtol=0)
    shared = np.asarray(dropless.dropless_moe.raw(
        jnp.asarray(x), jnp.asarray(x), whole.w_router._value,
        whole.w_gate._value[:1] * 0, whole.w_up._value[:1] * 0,
        whole.w_down._value[:1] * 0, 4, first=0, scoring="sigmoid",
        activation="silu", shared=(whole.shared_gate._value,
                                   whole.shared_up._value,
                                   whole.shared_down._value)))
    total = np.zeros_like(want)
    for first in (0, 4, 8, 12):
        part = _moe((first, 4))
        _copy_share(whole, part)
        total += np.asarray(part(paddle.to_tensor(x))._value) - shared
    np.testing.assert_allclose(total + shared, want, atol=TOL, rtol=0)


def test_a_share_gathers_a_bounded_buffer_of_its_own_rows():
    """No intermediate of the share's program has tokens x top-k rows of
    the hidden size: the gathered buffer is `share_rows` long."""
    part = _moe((0, 4))
    n, k, h = 512, 4, 64
    r = dropless.share_rows(n * k, 4, 16)
    assert r == 1024 and r < n * k                  # twice the even quarter
    assert dropless.share_rows(33 * 8, 16, 256) == 128
    assert dropless.share_rows(8192 * 8, 16, 256) == dropless._SHARE_ROWS
    x = jnp.zeros((n, h), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a: part(paddle.to_tensor(a))._value)(x)

    def shapes(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield getattr(v.aval, "shape", ())
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    rows = {s[0] for s in shapes(jaxpr.jaxpr) if len(s) == 2 and s[1] in
            (h, 32)}
    assert r in rows and n * k not in rows and max(rows) <= max(r, n)


@pytest.mark.parametrize("forced", [0, 8])
def test_forced_routing_drops_nothing_and_none_held_is_the_shared_expert(
        forced):
    """A direction every token shares lifts four experts' scores to 1, so
    every token's 4 choices are those four.  Held here (forced = 0): 4x
    the even load, more than one turn of the bounded buffer, nothing
    dropped.  Held elsewhere: the layer is the shared expert alone."""
    whole, part = _moe(), _moe((0, 4))
    router = np.asarray(whole.w_router._value) * 0.01
    router[0, forced:forced + 4] = 1.0
    whole.w_router._replace_(jnp.asarray(router), None)
    _copy_share(whole, part)
    xs = np.random.RandomState(3).standard_normal((300, 64)).astype(
        np.float32)
    xs[:, 0] += 100.0
    want, score = _layer_reference(whole, xs)
    top = np.argsort(-score, axis=1, kind="stable")[:, :4]
    assert set(top.ravel()) == set(range(forced, forced + 4))
    with collect_load() as load:
        got = np.asarray(part(paddle.to_tensor(xs))._value)
    assert load.total()[3] == 300 * 4
    # float32 sums of products of inputs near 100: 2e-3 is 1e-5 of them
    if forced == 0:
        # 1,200 assignments through a buffer of 640 rows: two turns
        assert dropless.share_rows(1200, 4, 16) == 640
        assert load.total()[:3].tolist() == [1200, 4, 300]
        np.testing.assert_allclose(got, want, atol=2e-3, rtol=0)
    else:
        assert load.total()[:3].tolist() == [0, 0, 0]
        silu = lambda v: v / (1 + np.exp(-v))             # noqa: E731
        g = lambda t: np.asarray(t._value, np.float64)    # noqa: E731
        x64 = xs.astype(np.float64)
        alone = (silu(x64 @ g(part.shared_gate)) *
                 (x64 @ g(part.shared_up))) @ g(part.shared_down)
        np.testing.assert_allclose(got, alone, atol=2e-3, rtol=0)


def test_a_share_computes_nothing_for_padding():
    """Inside `collect_load(valid)` a share leaves the tokens marked as
    padding to the shared expert alone; the real tokens' outputs and the
    counts are what they are without the padding."""
    part = _moe((0, 4))
    x = np.random.RandomState(5).standard_normal((2, 20, 64)).astype(
        np.float32)
    valid = jnp.arange(20)[None, :] < jnp.asarray([[13], [20]])
    plain = np.asarray(part(paddle.to_tensor(x))._value)
    with collect_load(valid) as load:
        masked = np.asarray(part(paddle.to_tensor(x))._value)
    np.testing.assert_array_equal(masked[0, :13], plain[0, :13])
    np.testing.assert_array_equal(masked[1], plain[1])
    xs = x[0, 13:].astype(np.float64)
    g = lambda t: np.asarray(t._value, np.float64)        # noqa: E731
    silu = lambda a: a / (1 + np.exp(-a))                 # noqa: E731
    alone = (silu(xs @ g(part.shared_gate)) * (xs @ g(part.shared_up))) @ \
        g(part.shared_down)
    np.testing.assert_allclose(masked[0, 13:], alone, atol=TOL, rtol=0)
    assert int(load.total()[3]) == 33 * 4


# -- (f) the router by hand; the four norms are live ------------------------------

def test_sigmoid_routing_against_a_hand_computation():
    logits = jnp.asarray([[2.0, -1.0, 0.5, 3.0, 0.0, -2.0]])
    w, idx = route_top_k(logits, 3, True, "sigmoid", 2.5)
    s = 1 / (1 + np.exp(-np.asarray([3.0, 2.0, 0.5])))
    assert idx.tolist() == [[3, 0, 2]]
    np.testing.assert_allclose(np.asarray(w[0]), 2.5 * s / s.sum(), rtol=1e-6)
    np.testing.assert_allclose(float(w.sum()), 2.5, rtol=1e-6)
    w2, _ = route_top_k(logits, 3, False, "sigmoid", 1.0)
    np.testing.assert_allclose(np.asarray(w2[0]), s, rtol=1e-6)
    # softmax over the chosen logits is untouched by the new arguments
    w3, i3 = route_top_k(logits, 2)
    e = np.exp(np.asarray([3.0, 2.0]) - 3.0)
    assert i3.tolist() == [[3, 0]]
    np.testing.assert_allclose(np.asarray(w3[0]), e / e.sum(), rtol=1e-6)


@pytest.mark.parametrize("norm", ["input_norm", "post_attn_norm",
                                  "attn_out_norm", "mlp_out_norm"])
@pytest.mark.parametrize("layer", [0, 2])
def test_each_of_the_four_norms_is_live(norm, layer):
    """Another gain on one norm changes the logits, and the reference
    follows: pre-norms and sandwich norms, on a dense and an expert layer.
    (Per channel: the norms inside attention undo a uniform factor on its
    input.)"""
    model, cfg = _model(4)
    ids = _prompts((16,), 4)[0]
    before = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    w = getattr(model.decoder.layers[layer], norm).weight
    gain = np.random.RandomState(0).uniform(0.25, 4.0, 64).astype(np.float32)
    w._replace_(w._value * jnp.asarray(gain), None)
    after = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    assert np.abs(after - before).max() > 1e-3
    np.testing.assert_allclose(
        after, _ref_logits(model, cfg, ids, np.arange(16)), atol=TOL, rtol=0)


@pytest.mark.parametrize("fault", [
    {"rope_theta": None}, {"attention_scale": 1.0},
    {"num_experts_per_tok": 3}, {"n_shared_experts": 0},
    {"routed_scaling_factor": 1.0}, {"scoring_func": "softmax"},
    {"sandwich_norm_skip": "attn"}, {"sandwich_norm_skip": "mlp"},
    {"reference_weights": "int8"}])
def test_the_reference_with_a_fault_differs(fault):
    """Every control the configuration may name moves the reference's
    logits by far more than the tolerance of (a)."""
    model, cfg = _model(5)
    ids = _prompts((24,), 5)[0]
    good = _ref_logits(model, cfg, ids, np.arange(24))
    bad = _ref_logits(model, dict(cfg, **fault), ids, np.arange(24))
    assert np.abs(bad - good).max() > 50 * TOL


# -- (g) a slice of the vocabulary ------------------------------------------------

def test_vocabulary_slice_bounds_logits_and_sampled_ids():
    model, cfg = _model(6, vocab_size=96)
    assert tuple(model.head.shape) == (64, 96)
    eng = Engine(model, max_slots=2, max_len=64)
    try:
        hs = [eng.submit(_prompts((9,), s, vocab=96)[0], max_new_tokens=8,
                         temperature=t, top_k=k, seed=s)
              for s, (t, k) in enumerate(((0.0, 0), (1.0, 0), (0.8, 5)))]
        outs = [h.result(timeout=300) for h in hs]
    finally:
        eng.close()
    for h, toks in zip(hs, outs):
        assert 0 <= min(toks) and max(toks) < 96
        assert np.all(np.asarray(h.logprobs) <= 0.0)
    ids = _prompts((12,), 0, vocab=96)[0]
    lg = np.asarray(model(paddle.to_tensor(ids[None]))._value)
    assert lg.shape == (1, 12, 96)
    np.testing.assert_allclose(lg[0], _ref_logits(model, cfg, ids,
                                                  np.arange(12)),
                               atol=TOL, rtol=0)


# -- (h) the pool ------------------------------------------------------------------

def test_latent_pool_is_one_buffer_a_layer():
    kv = [(jax.ShapeDtypeStruct((1, 1, 24), jnp.bfloat16), None)] * 5
    pool = KVPool.zeros(kv, layout="dense", rows=4, row_len=64,
                        quantized=False)
    assert pool.latent and pool.v is None and not pool.quantized
    assert pool.nbytes == 4 * 64 * 24 * 2 * 5
    assert len(jax.tree_util.tree_leaves(pool)) == 5
    caches = pool.caches(jnp.zeros((4,), jnp.int32))
    assert all(c.latent and c.v is None for c in caches)
    assert pool.updated(caches).v is None
    assert [c[1] for c in pool.prompt_caches(1, 16)] == [None] * 5
    copied = pool.copied(jnp.asarray([0]), jnp.asarray([2]))
    assert copied.v is None and copied.nbytes == pool.nbytes
    for bad in (dict(layout="paged", quantized=False),
                dict(layout="dense", quantized=True)):
        with pytest.raises(ValueError, match="dense, unquantised"):
            KVPool.zeros(kv, rows=4, row_len=64, **bad)


# -- (i) every Engine option with this model ---------------------------------------

@pytest.mark.parametrize("kw, why", [
    ({"paged_kv": True}, "paged_kv"),
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"decode_kernel": "pallas", "paged_kv": True}, "cannot be served"),
    ({"adapters": object()}, "adapters"),
    ({"host_prefix_mb": 1.0, "prefix_cache": True}, "requires paged_kv"),
])
def test_refused_engine_options_raise_at_build(kw, why):
    model, _ = _model(3)
    with pytest.raises(ValueError, match=why):
        Engine(model, max_slots=2, max_len=64, auto_start=False, **kw)


def test_span_stats_carry_moe_routed():
    model, _ = _model(3)
    trace.clear()
    eng = Engine(model, max_slots=2, max_len=64)
    try:
        eng.submit(_prompts((11,), 1)[0], max_new_tokens=4).result(
            timeout=300)
        st = eng.stats()
    finally:
        eng.close()
    emits = [s for s in trace.spans() if s["name"].endswith(".emit") and
             "moe_routed" in s["attrs"]]
    assert emits and all(s["attrs"]["moe_routed"] >=
                         s["attrs"]["moe_assignments"] for s in emits)
    assert sum(s["attrs"]["moe_routed"] for s in emits) == st["moe_routed"]
    # 11 prompt tokens x 4 per token x 4 expert layers
    assert [s["attrs"]["moe_routed"] for s in emits
            if s["name"] == "serving.prefill.emit"] == [11 * 4 * 4]


# -- the benchmark's new driver, rehearsed -----------------------------------------

def test_serve_latent_driver_rehearsal():
    """`serve_latent_driver.run` through a `Ctx` built as `run.py` builds
    it: the tiny configuration, a 3 s window on the CPU.  `correct`, the
    controls false, the end-to-end keys, the counters, and every reader of
    the new cell returns a number or None without raising."""
    from benchmark import run as bench_run
    config = _config()
    with open(os.path.join(ROOT, "benchmark", "testdata",
                           "reasoning-saturated-tiny.json")) as f:
        mix = json.load(f)
    cell = {"name": "rehearse-pangu-tiny", "chips": 1, "platform": "cpu",
            "metrics_as": "serve-pangu-reasoning-saturated"}
    args = argparse.Namespace(seed=2 ** 31 + 7, seconds=3.0, trace=0)
    ctx = bench_run.Ctx(cell, config, mix, args, jax.devices()[:1],
                        bench_run.CompileLog())
    # the tiny model decodes in ~2 ms on a calm CPU and would drain the
    # mix's pool (408 requests: `max_rps` 100 over 4 s) inside the window:
    # stretch a step, so that the loop stays closed whatever the host's pace
    with faults.inject("serving.decode", mode="delay", seconds=0.01,
                       times=None):
        res = driver.run(ctx)
    assert res["correct"], res["notes"]
    for name, control in res["notes"]["controls"].items():
        assert not control["correct"], (name, control)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["end_to_end"]["serve_tokens_per_s"] > 0 and res["setup_s"] > 0
    # the pool did not run dry (a caller may be between two requests)
    assert res["notes"]["in_flight_end"] >= mix["clients"] - 2
    obs = dict(res["observations"], memory_peak_bytes=0, device_kind="cpu",
               chips=1, config=config, trace=None)
    assert obs["moe_routed"] >= obs["moe_assignments"] > 0
    assert obs["model_flops"] > 0
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = [m for m in manifest["per_layer"]
            if cell["metrics_as"] in m.get("workloads", ())]
    assert len(mine) == 14              # 8 list memberships and 6 new ones
    values = {}
    for m in mine:
        spec = json.load(open(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".json")))
        mod, fn = spec["reader"].rsplit(".", 1)
        reader = getattr(importlib.import_module(f"benchmark.{mod}"), fn)
        values[m["name"]] = reader(obs, **spec.get("args", {}))
    assert all(v is None or np.isfinite(v) for v in values.values())
    # 4 of 16 experts held: the even part is a quarter
    assert 0.3 < values["moe.held_share_over_even.pangu"] < 3.0
    assert values["moe.load_max_over_mean.pangu"] >= 1.0
    assert values["engine.decode_occupancy"] > 0
    # the device's shares come from a device trace only
    for k in ("kernels.latent_read_roofline.pangu",
              "kernels.mla_flash_roofline.pangu",
              "kernels.moe_experts_roofline.pangu", "serve.mfu.pangu"):
        assert values[k] is None, k
