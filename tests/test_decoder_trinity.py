"""ISSUE 35: the afmoe block of `models/decoder.py` (Trinity-Large-Preview's
layer at test size: hidden 64, 1 dense + 4 expert layers [window, window,
window, window, global], 4 query / 2 KV heads of 16 with per-head q/k norms
and a sigmoid output gate, window 8, 16 experts top-4 of width 32 beside 1
shared, 4 held, a selection bias, a muP embedding scale) against the plain
reference of the benchmark (`benchmark/reference_trinity.py`), on the normal
path and through `serving.Engine` on a dense pool whose window layers are
RINGS (max_len 512: the read block of a 4 / 2 grouped pool is 256, so a ring
holds 256 positions); the ring pool against a full-row pool; the shares of a
layer; every Engine option; the benchmark's new driver rehearsed on the CPU."""
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
from paddle_tpu.incubate.distributed.models.moe.dropless import (
    DroplessMoE, route_top_k)
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models import build_decoder
from paddle_tpu.models.decoder import decoder_config
from paddle_tpu.observability import trace
from paddle_tpu.serving import Engine
from paddle_tpu.testing import faults

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import reference_trinity as ref  # noqa: E402
from benchmark import serve_afmoe_driver as driver  # noqa: E402

TOL = 1e-4      # float32 against the float32 reference
RING = 256      # window 8 + span 1 - 1, rounded up to the 256-position block


def _config(**overrides) -> dict:
    """The tiny configuration file, in the published config.json's keys."""
    with open(os.path.join(ROOT, "benchmark", "testdata",
                           "trinity-tiny-serve.json")) as f:
        return dict(json.load(f), **overrides)


def _model(seed=3, bias=None, **overrides):
    cfg = _config(**overrides)
    model = driver.build_model(cfg, seed)[1]
    if bias is not None:
        rs = np.random.RandomState(seed)
        for layer in model.decoder.layers[cfg["num_dense_layers"]:]:
            layer.moe.expert_bias._value = jnp.asarray(
                bias * rs.standard_normal(16), jnp.float32)
    return model, cfg


def _ref_logits(model, cfg, ids, rows):
    ids = np.asarray(ids)
    pad = (-len(ids)) % 8                       # right padding is causal
    return np.asarray(ref.logits_at(model.state_dict(), np.pad(ids, (0, pad)),
                                    np.asarray(rows), cfg, block=8))


def _prompts(lengths, seed=0, vocab=256):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, n).astype(np.int64) for n in lengths]


# -- (a) the model's full forward ----------------------------------------------

@pytest.mark.parametrize("held, bias", [(4, None), (16, None), (4, 0.3)],
                         ids=["share", "uncut", "share-biased"])
def test_forward_matches_reference(held, bias):
    """A share of 4 of 16 experts, the uncut layer, and a share whose router
    carries a non-zero selection bias (the reference reads it from the
    state)."""
    model, cfg = _model(5, bias=bias, num_experts=held)
    ids = np.stack(_prompts((40, 40), 5))
    got = np.asarray(model(paddle.to_tensor(ids))._value)
    for b in range(2):
        want = _ref_logits(model, cfg, ids[b], np.arange(40))
        np.testing.assert_allclose(got[b], want, atol=TOL, rtol=0)


def test_published_preset_states_the_published_sizes():
    c = decoder_config("trinity-large-preview")
    assert (c.hidden_size, c.num_hidden_layers, c.num_attention_heads,
            c.num_key_value_heads, c.head_dim) == (3072, 60, 48, 8, 128)
    assert (c.moe_num_primary_experts, c.moe_num_active_primary_experts,
            c.moe_ffn_hidden_size, c.n_shared_experts) == (256, 4, 3072, 1)
    assert (c.first_k_dense_replace, c.intermediate_size, c.vocab_size,
            c.max_position_embeddings) == (6, 12288, 200192, 262144)
    assert c.scoring_func == "sigmoid" and c.routed_scaling_factor == 2.448
    assert c.sliding_window_size == 4096 and c.rope_theta == 1e4
    # [sliding, sliding, sliding, full] x 15; RoPE on the sliding layers only
    assert c.sliding_window_layout == (1, 1, 1, 0) * 15 == c.rope_layout
    assert (c.attention_gate and c.qk_norm and c.expert_bias and
            c.sandwich_norm and c.embedding_scale == 3072 ** 0.5)


def test_driver_maps_the_published_keys():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-large-preview-serve.json")) as f:
        cfg = json.load(f)
    c = driver.decoder_config_of(cfg)
    assert (c.num_hidden_layers, c.first_k_dense_replace, c.vocab_size) == \
        (5, 1, 25024)
    assert c.experts_held == (0, 32) and c.moe_num_primary_experts == 256
    assert c.sliding_window_layout == (1, 1, 1, 1, 0) == c.rope_layout
    assert [c.window(i) for i in range(5)] == [4096] * 4 + [None]
    assert (c.q_norm_gain, c.sandwich_norm_gain) == (2.5, 0.09)
    assert c.embedding_scale == 3072 ** 0.5 and c.routed_scaling_factor == 2.448
    # every width is the published one
    published = decoder_config("trinity-large-preview")
    for k in ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "intermediate_size", "moe_ffn_hidden_size",
              "moe_num_active_primary_experts", "sliding_window_size"):
        assert getattr(c, k) == getattr(published, k), k


@pytest.mark.parametrize("preset", ["smallthinker-tiny", "openpangu-tiny"])
def test_other_presets_build_what_they_built(preset):
    """The new fields default to off: the two earlier models carry no gate,
    no q/k norm, no selection bias, and an unscaled embedding."""
    paddle.seed(1)
    model = build_decoder(preset)
    names = set(model.state_dict())
    assert not [n for n in names if "gate_proj" in n and "self_attn" in n]
    assert not [n for n in names if "q_norm" in n or "expert_bias" in n]
    assert model.decoder.config.embedding_scale == 1.0


# -- (b) through the Engine: prefill, then decode through rings ----------------

def _logprobs(lg, toks):
    return lg[np.arange(len(toks)), toks] - (
        lg.max(-1) + np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)))


def _engine_matches_reference(model, cfg, prompts, new, **engine_kw):
    eng = Engine(model, **{"max_slots": 3, "max_len": 512,
                           "prefill_batch": 2, **engine_kw})
    try:
        hs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
        outs = [h.result(timeout=600) for h in hs]
        stats = eng.stats()
        rings = eng._kv_pool.ring_lens
    finally:
        eng.close()
    for p, h, toks in zip(prompts, hs, outs):
        ids = np.concatenate([p, toks[:-1]])
        lg = _ref_logits(model, cfg, ids, np.arange(len(p) - 1, len(ids)))
        assert lg.argmax(-1).tolist() == list(toks)
        np.testing.assert_allclose(np.asarray(h.logprobs),
                                   _logprobs(lg, toks), atol=TOL, rtol=0)
    return stats, rings


# a prompt that has wrapped the ring before decode starts, a short one, one
# that wraps while it decodes (250 -> 280), one past window + ring at once
_LONG = ((300, 12), (20, 16), (250, 30), (270, 10))


def test_engine_prefill_then_decode_through_rings_matches_reference():
    model, cfg = _model(3)
    prompts = _prompts([n for n, _ in _LONG], 7)
    st, rings = _engine_matches_reference(model, cfg, prompts,
                                          [n for _, n in _LONG])
    assert rings == [RING] * 4 + [None]
    assert max(len(p) + n for p, (_, n) in zip(prompts, _LONG)) > 8 + RING
    assert st["kv_ring_len"] == RING
    # K + V of 2 heads x 16 float32: 256 B a position; 4 rows; four rings
    # of 256 positions and one full layer of 512
    assert st["kv_pool_bytes_window"] == 4 * 4 * RING * 256
    assert st["kv_pool_bytes_global"] == 4 * 512 * 256
    assert st["kv_pool_bytes"] == (st["kv_pool_bytes_window"] +
                                   st["kv_pool_bytes_global"])
    for k in ("live", "read"):
        assert st[f"decode_kv_{k}_positions"] == (
            st[f"decode_kv_{k}_positions_window"] +
            st[f"decode_kv_{k}_positions_global"]) > 0
    # the XLA read streams every row whole: a ring's row is the ring
    assert st["decode_kv_read_positions_window"] == \
        st["decode_steps"] * 4 * 4 * RING
    assert st["decode_kv_read_positions_global"] == st["decode_steps"] * 4 * 512


def test_engine_decode_through_the_kernel_on_rings_matches_reference():
    """The decode program's read through `dense_decode_read`, interpreted:
    on a ring the work list is the row's live ring blocks."""
    model, cfg = _model(3)
    pa.use_interpret_mode(True)
    prompts = _prompts((300, 20, 250), 8)
    st, rings = _engine_matches_reference(model, cfg, prompts, (6, 6, 10))
    assert rings == [RING] * 4 + [None]
    # one 256-position block a live row on a ring, one or two on the full
    # layer: never a parked row's, never more than the ring
    assert 0 < st["decode_kv_read_positions_window"] <= \
        st["decode_steps"] * 3 * 4 * RING
    assert st["decode_kv_read_positions_window"] % RING == 0


@pytest.mark.parametrize("kw", [
    dict(speculative_k=3), dict(sample_on_device=False),
    dict(kv_dtype="int8"),
], ids=["speculative", "host-sampler", "int8"])
def test_engine_options_on_rings(kw):
    """Speculative verification writes k positions a step: the ring covers
    the span (window + k - 1 <= 256) and a rolled-back draft is overwritten
    before anything reads it.  The int8 pool's scales ride the ring."""
    model, cfg = _model(4)
    prompts = _prompts((300, 20, 250), 9)
    if "kv_dtype" not in kw:
        st, rings = _engine_matches_reference(model, cfg, prompts,
                                              (8, 8, 12), **kw)
        assert rings == [RING] * 4 + [None]
        return
    def serve(rings: bool):
        eng = Engine(model, max_slots=3, max_len=512, auto_start=False, **kw)
        if not rings:
            eng._ring_block = None
        eng.start()
        try:
            hs = [eng.submit(p, max_new_tokens=8) for p in prompts]
            outs = [h.result(timeout=600) for h in hs]
            return (outs, [np.asarray(h.logprobs) for h in hs],
                    eng._kv_pool.k_scale[0].shape)
        finally:
            eng.close()

    # what int8 storage costs is the full-row int8 pool's; the ring adds
    # nothing to it
    outs, lps, scales = serve(True)
    outs_full, lps_full, scales_full = serve(False)
    assert scales == (4, RING) and scales_full == (4, 512)
    for a, b in zip(outs + lps, outs_full + lps_full):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=0)
    for p, toks in zip(prompts, outs):
        ids = np.concatenate([p, toks[:-1]])
        lg = _ref_logits(model, cfg, ids, np.arange(len(p) - 1, len(ids)))
        assert float(np.max(lg.max(-1) - lg[np.arange(len(toks)), toks])) < 0.05


@pytest.mark.parametrize("preset", ["smallthinker-tiny", "trinity-tiny"])
def test_ring_pool_gives_the_full_row_pools_logits(preset):
    """The same requests on a pool whose window layers are rings and on one
    whose rows are all `max_len` long (the engine built with its ring plan
    taken away): the tokens are equal and the log-probabilities agree to
    float32 rounding -- NOT bitwise, because the masked XLA read sums a
    softmax row of 256 terms where the full row's has 512 (the masked terms
    are exact zeros, the order of the sum is another)."""
    paddle.seed(2)
    model = build_decoder(preset, max_position_embeddings=1024)
    model.eval()
    prompts = _prompts((300, 20, 250), 11)

    def serve(rings: bool):
        eng = Engine(model, max_slots=3, max_len=512, auto_start=False)
        if not rings:
            eng._ring_block = None
        eng.start()
        try:
            hs = [eng.submit(p, max_new_tokens=12) for p in prompts]
            outs = [h.result(timeout=600) for h in hs]
            return (outs, [np.asarray(h.logprobs) for h in hs],
                    eng._kv_pool.ring_lens, eng.stats()["kv_pool_bytes"])
        finally:
            eng.close()

    toks_r, lps_r, rings, bytes_r = serve(True)
    toks_f, lps_f, full, bytes_f = serve(False)
    assert any(rings) and not any(full) and bytes_r < bytes_f
    for a, b in zip(toks_r, toks_f):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(lps_r, lps_f):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=0)


# -- (c) the router's selection bias, the shares of a layer --------------------

def test_selection_bias_changes_who_is_chosen_and_not_the_weights():
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.standard_normal((64, 16)), jnp.float32)
    bias = jnp.asarray(rs.standard_normal(16), jnp.float32)
    score = np.asarray(jax.nn.sigmoid(logits))
    w0, i0 = route_top_k(logits, 4, True, "sigmoid", 2.448)
    w, idx = route_top_k(logits, 4, True, "sigmoid", 2.448, bias)
    w, idx = np.asarray(w), np.asarray(idx)
    assert (np.sort(idx, -1) != np.sort(np.asarray(i0), -1)).any()
    # chosen by score + bias ...
    want = np.argsort(-(score + np.asarray(bias)), -1)[:, :4]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want, -1))
    # ... weighed by the score alone, renormalised over the chosen, scaled
    s = np.take_along_axis(score, idx, -1)
    np.testing.assert_allclose(w, 2.448 * s / s.sum(-1, keepdims=True),
                               atol=1e-6, rtol=0)
    # a zero bias is no bias
    wz, iz = route_top_k(logits, 4, True, "sigmoid", 2.448, jnp.zeros(16))
    np.testing.assert_array_equal(np.asarray(iz), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(wz), np.asarray(w0))
    with pytest.raises(ValueError, match="sigmoid"):
        route_top_k(logits, 4, True, "softmax", 1.0, bias)
    with pytest.raises(ValueError, match="sigmoid"):
        DroplessMoE(8, 8, 4, 2, expert_bias=True)


def test_bias_in_the_weights_is_a_fault_the_reference_can_state():
    """With a non-zero bias, weighing by score + bias moves the logits; the
    program weighs by the score (test_forward_matches_reference
    [share-biased] holds it to the reference)."""
    model, cfg = _model(5, bias=0.3)
    ids = _prompts((24,), 5)[0]
    good = _ref_logits(model, cfg, ids, np.arange(24))
    bad = _ref_logits(model, dict(cfg, bias_in_weights=True), ids,
                      np.arange(24))
    assert np.abs(bad - good).max() > 50 * TOL
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    np.testing.assert_allclose(got, good, atol=TOL, rtol=0)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """4 shares of 4 of the 16 experts, a biased router, the shared expert
    in every share: the shares' outputs less three copies of the shared
    expert's are the uncut layer's."""
    rs = np.random.RandomState(1)
    x = paddle.to_tensor(rs.standard_normal((2, 24, 64)).astype(np.float32))
    kw = dict(scoring="sigmoid", routed_scale=2.448, activation="silu",
              shared_width=32, expert_bias=True)
    paddle.seed(6)
    whole = DroplessMoE(64, 32, 16, 4, **kw)
    bias = jnp.asarray(0.3 * rs.standard_normal(16), jnp.float32)
    whole.expert_bias._value = bias
    state = {k: v._value for k, v in whole.state_dict().items()}
    total = 0.0
    for first in range(0, 16, 4):
        share = DroplessMoE(64, 32, 16, 4, experts_held=(first, 4), **kw)
        for k, v in share.state_dict().items():
            v._value = (state[k][first:first + 4]
                        if k in ("w_gate", "w_up", "w_down") else state[k])
        total = total + np.asarray(share(x)._value, np.float64)
    only = DroplessMoE(64, 32, 16, 4, experts_held=(0, 4), **kw)
    for k, v in only.state_dict().items():
        v._value = (jnp.zeros_like(state[k][:4])
                    if k in ("w_gate", "w_up", "w_down") else state[k])
    shared = np.asarray(only(x)._value, np.float64)     # the shared part
    np.testing.assert_allclose(total - 3 * shared,
                               np.asarray(whole(x)._value), atol=TOL, rtol=0)


def test_eight_shares_of_the_model_add_up_in_the_reference():
    """The reference's own share arithmetic: a layer's held part, summed
    over the shares, is the uncut layer's routed part (one expert layer,
    the rest of the block identical)."""
    model, cfg = _model(5, bias=0.2, num_experts=16, num_hidden_layers=2)
    state = {k: v._value for k, v in model.state_dict().items()}
    pre = "decoder.layers.1."
    p = {k[len(pre):]: v for k, v in state.items() if k.startswith(pre)}
    h = jnp.asarray(np.random.RandomState(2).standard_normal((16, 64)),
                    jnp.float32)
    kw = dict(top_k=4, eps=1e-5, norm_topk=True, routed_scale=2.448,
              bias_in_weights=False, skip="mlp", weights=None)
    whole = ref._expert_mlp(h, p, first=0, shared=True, **kw) - h
    parts = 0.0
    for first in range(0, 16, 2):
        q = dict(p, **{f"moe.{w}": p[f"moe.{w}"][first:first + 2]
                       for w in ("w_gate", "w_up", "w_down")})
        parts = parts + (ref._expert_mlp(h, q, first=first, shared=False,
                                         **kw) - h)
    none = dict(p, **{f"moe.{w}": jnp.zeros_like(p[f"moe.{w}"][:1])
                      for w in ("w_gate", "w_up", "w_down")})
    shared = ref._expert_mlp(h, none, first=0, shared=True, **kw) - h
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole),
                               atol=TOL, rtol=0)


# -- (d) every fault the configuration may name as a control -------------------

@pytest.mark.parametrize("fault", [
    {"attention_gate": False}, {"qk_norm": False}, {"rope_on_full": True},
    {"sliding_window": 7}, {"route_scale": 1.0}, {"num_shared_experts": 0},
    {"mup_enabled": False}, {"sandwich_norm_skip": "attn"},
    {"sandwich_norm_skip": "mlp"}, {"reference_weights": "int8"},
], ids=lambda f: "-".join(f"{k}={v}" for k, v in f.items()))
def test_the_reference_with_a_fault_differs(fault):
    """Every control the configuration may name moves the reference's
    logits by far more than the tolerance of (a): no gate, no q/k norm,
    RoPE on the full layer, a window one position short, route_scale 1, no
    shared expert, no embedding scale, a sandwich norm missing."""
    model, cfg = _model(5)
    ids = _prompts((24,), 5)[0]
    good = _ref_logits(model, cfg, ids, np.arange(24))
    bad = _ref_logits(model, dict(cfg, **fault), ids, np.arange(24))
    assert np.abs(bad - good).max() > 50 * TOL


def test_a_ring_one_block_short_differs_from_the_reference():
    """A ring that holds one block less than window + span - 1 has lost
    keys the window still admits: the kernel refuses it by name, and the
    masked read of such a row differs from the full row's."""
    from paddle_tpu.models.kv_cache import SlotCache, cached_attention
    rs = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(rs.standard_normal(s), jnp.float32)  # noqa
    full_k, full_v = f(1, 64, 2, 8), f(1, 64, 2, 8)
    q, k, v = f(1, 1, 4, 8), f(1, 1, 2, 8), f(1, 1, 2, 8)
    lengths = jnp.asarray([40], jnp.int32)

    def ring_of(n):
        at = np.arange(40 - n, 40)
        rk = np.zeros((1, n, 2, 8), np.float32)
        rv = np.zeros((1, n, 2, 8), np.float32)
        rk[0, at % n], rv[0, at % n] = full_k[0, at], full_v[0, at]
        return SlotCache(jnp.asarray(rk), jnp.asarray(rv), lengths, limit=64)

    want, _ = cached_attention(q, k, v, SlotCache(full_k, full_v, lengths),
                               window=16)
    good, _ = cached_attention(q, k, v, ring_of(16), window=16)
    short, _ = cached_attention(q, k, v, ring_of(12), window=16)
    np.testing.assert_allclose(np.asarray(good._value),
                               np.asarray(want._value), atol=1e-6, rtol=0)
    assert np.abs(np.asarray(short._value) -
                  np.asarray(want._value)).max() > 1e-3
    pa.use_interpret_mode(True)
    with pytest.raises(ValueError, match="does not hold a window"):
        pa.dense_decode_attention(q, ring_of(12).k, ring_of(12).v, lengths,
                                  block=4, window=16, limit=64)


# -- (e) what a ring cannot honour is refused at build -------------------------

@pytest.mark.parametrize("kw, why", [
    ({"prefix_cache": True}, "ring of its last 256 positions"),
    ({"adapters": object()}, "adapters"),
    ({"decode_kernel": "pallas", "paged_kv": True}, "cannot be served"),
    ({"host_prefix_mb": 1.0, "prefix_cache": True}, "requires paged_kv"),
])
def test_refused_engine_options_raise_at_build(kw, why):
    model, _ = _model(3)
    with pytest.raises(ValueError, match=why):
        Engine(model, max_slots=2, max_len=512, auto_start=False, **kw)


def test_options_a_ring_does_not_meet_are_not_refused():
    """The paged pool has no ring (pages keep every position), so its
    prefix cache stays; a dense pool whose max_len a ring would not shorten
    (64 < the 256-position block) has none either."""
    model, _ = _model(3)
    for kw in (dict(max_len=512, paged_kv=True, prefix_cache=True,
                    prefix_block=4),
               dict(max_len=64, prefix_cache=True, prefix_block=4)):
        eng = Engine(model, max_slots=2, auto_start=False, **kw)
        assert eng._ring_block is None
        eng.close()


# -- (f) spans, scopes and counters ----------------------------------------------

def test_dispatch_spans_carry_the_positions_by_layer_kind():
    model, _ = _model(3)
    trace.clear()
    eng = Engine(model, max_slots=2, max_len=512)
    try:
        eng.submit(_prompts((30,), 1)[0], max_new_tokens=5).result(
            timeout=300)
        while eng._flying is not None:
            pass
        st = eng.stats()
    finally:
        eng.close()
    disp = [s["attrs"] for s in trace.spans()
            if s["name"] == "serving.decode.dispatch"]
    assert disp
    for a in disp:
        assert a["kv_read"] == a["kv_read_window"] + a["kv_read_global"]
        assert a["kv_live"] == a["kv_live_window"] + a["kv_live_global"]
        # four window layers admit 8 positions each
        assert a["kv_live_window"] == 4 * 8
    for k in ("kv_read_window", "kv_read_global", "kv_live_window",
              "kv_live_global"):
        name = "decode_" + k.replace("_w", "_positions_w").replace(
            "_g", "_positions_g")
        assert sum(a[k] for a in disp) == st[name], k
    emits = [s["attrs"] for s in trace.spans()
             if s["name"].endswith(".emit") and "moe_routed" in s["attrs"]]
    assert emits and all(e["moe_routed"] >= e["moe_assignments"]
                         for e in emits)


def test_named_scopes_reach_the_program():
    model, _ = _model(3)
    ids = jnp.asarray(_prompts((16,), 0)[0][None])
    from paddle_tpu.nn.functional_call import _swapped_state, state_values
    vals = state_values(model)

    def f(vals, ids):
        with _swapped_state(model, vals):
            return model(paddle.to_tensor(ids))._value

    text = jax.jit(f).lower(vals, ids).as_text(debug_info=True)
    for scope in ("attn.gate", "moe.router", "attn.window", "attn.global",
                  "moe.shared", "mlp.dense"):
        assert scope in text, scope


# -- (g) the ring's kernel compiles for the chip ---------------------------------

def test_ring_decode_read_compiles_for_the_chip():
    """`dense_decode_attention` on a ring at the cell's shape: 12 slots +
    scratch, rings of 4,096 positions of 8 KV heads of 128 under 32,768
    addressable, 48 query heads, 512-position blocks (AOT for a described
    v5e, no chip)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no libtpu in this install
        pytest.skip(f"no TPU compiler available: {e}")
    s = SingleDeviceSharding(topo.devices[0])
    pa.use_interpret_mode(False)
    B, R, H, Hkv, D = 13, 4096, 48, 8, 128
    blk = pa.dense_read_block(heads=H, kv_heads=Hkv, head_dim=D,
                              dtype=jnp.bfloat16, width=1, max_len=32768)
    assert blk == 512

    def read(q, k, v, ln):
        return pa.dense_decode_attention(q, k, v, ln, block=blk, window=4096,
                                         limit=32768)

    pool = jax.ShapeDtypeStruct((B, R, Hkv, D), jnp.bfloat16)
    with jax.default_matmul_precision("default"):
        c = (jax.jit(read, in_shardings=s, out_shardings=s)
             .trace(jax.ShapeDtypeStruct((B, 1, H, D), jnp.bfloat16), pool,
                    pool, jax.ShapeDtypeStruct((B,), jnp.int32))
             .lower(lowering_platforms=("tpu",)).compile())
    assert c.as_text().count("tpu_custom_call") == 1


# -- the benchmark's new driver, rehearsed ---------------------------------------

def test_serve_afmoe_driver_rehearsal():
    """`serve_afmoe_driver.run` through a `Ctx` built as `run.py` builds it:
    the tiny configuration, a 3 s window on the CPU.  `correct`, the
    controls false, a context that has gone round the ring among those
    checked, the counters by kind, and every reader of the new cell returns
    a number or None without raising."""
    from benchmark import run as bench_run
    config = _config()
    with open(os.path.join(ROOT, "benchmark", "testdata",
                           "longctx-mixed-saturated-tiny.json")) as f:
        mix = json.load(f)
    cell = {"name": "rehearse-trinity-tiny", "chips": 1, "platform": "cpu",
            "metrics_as": "serve-trinity-longctx-saturated"}
    args = argparse.Namespace(seed=2 ** 31 + 11, seconds=3.0, trace=0)
    ctx = bench_run.Ctx(cell, config, mix, args, jax.devices()[:1],
                        bench_run.CompileLog())
    # stretch a step, so that the tiny engine stays under the pool's
    # `max_rps` whatever the host's pace (as the pangu rehearsal does)
    with faults.inject("serving.decode", mode="delay", seconds=0.01,
                       times=None):
        res = driver.run(ctx)
    assert res["correct"], res["notes"]
    for name, control in res["notes"]["controls"].items():
        assert not control["correct"], (name, control)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["end_to_end"]["serve_tokens_per_s"] > 0 and res["setup_s"] > 0
    assert res["notes"]["in_flight_end"] >= mix["clients"] - 2
    assert res["notes"]["kv_ring_len"] == RING
    assert res["checks"]["longest_context_checked"]["value"] > 8 + RING
    obs = dict(res["observations"], memory_peak_bytes=0, device_kind="cpu",
               chips=1, config=config, trace=None)
    assert obs["moe_routed"] >= obs["moe_assignments"] > 0
    assert obs["model_flops"] > 0 and obs["decode_steps"] > 0
    assert obs["decode_kv_read_positions"] == (
        obs["decode_kv_read_positions_window"] +
        obs["decode_kv_read_positions_global"])
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = [m for m in manifest["per_layer"]
            if cell["metrics_as"] in m.get("workloads", ())]
    assert len(mine) == 16              # 8 list memberships and 8 new ones
    values = {}
    for m in mine:
        spec = json.load(open(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".json")))
        mod, fn = spec["reader"].rsplit(".", 1)
        reader = getattr(importlib.import_module(f"benchmark.{mod}"), fn)
        values[m["name"]] = reader(obs, **spec.get("args", {}))
    assert all(v is None or np.isfinite(v) for v in values.values())
    # 4 of 16 experts held: the even part is a quarter
    assert 0.3 < values["moe.held_share_over_even.trinity"] < 3.0
    assert values["moe.load_max_over_mean.trinity"] >= 1.0
    assert values["engine.decode_occupancy"] > 0
    # four rings of 256 against one full row of 512 on the XLA read
    assert values["engine.kv_window_read_share.trinity"] == pytest.approx(
        100 * 4 * RING / (4 * RING + 512))
    assert 0 < values["engine.kv_pool_live_share.trinity"] < 100
    # the device's shares come from a device trace only
    for k in ("kernels.decode_read_roofline.trinity",
              "kernels.window_flash_roofline.trinity",
              "kernels.moe_experts_roofline.trinity", "serve.mfu.trinity"):
        assert values[k] is None, k


def test_roofline_readers_pair_a_steps_spans_by_its_ordinal(monkeypatch):
    """With a step queued behind the running one, dispatch n + 1 precedes
    emit n.  The two summing readers take one contiguous stretch of the
    trace (emit to emit), the decode steps in it by their `step` stat and
    the prefills that lie whole inside it: every op of the stretch counts
    on one side and every step's work on the other, whatever the host's
    time between two spans."""
    from benchmark import flops, trinity_readers as tr
    cfg = _config()
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    step_s, n = 6e-3, 10
    host = {"serving.decode.dispatch": [], "serving.decode.emit": [],
            "serving.prefill.dispatch": [], "serving.prefill.emit": []}
    ops = []
    for k in range(n):
        t0 = k * step_s                   # the device runs step k from here
        # dispatched one step ahead; emitted once the program has ended
        host["serving.decode.dispatch"].append(
            (t0 - 0.8 * step_s, t0 - 0.6 * step_s,
             {"step": k, "active": 3, "kv_read_window": 4096 * (k + 1),
              "kv_read_global": 512 * (k + 1), "kv_read": 0}))
        host["serving.decode.emit"].append(
            (t0 + step_s, t0 + step_s + 1e-4,
             {"step": k, "moe_assignments": 6, "moe_experts_touched": 5}))
        ops.append((t0 + 1e-3, t0 + 1e-3 + 2e-3, "custom-call",
                    "jit(decode)/dense_decode_read"))
        ops.append((t0 + 4e-3, t0 + 4e-3 + 1e-3, "ragged-dot-none.1",
                    "jit(decode)/moe.experts/ragged_dot"))
    monkeypatch.setattr(tr, "device_ops", lambda path: sorted(ops))
    monkeypatch.setattr(tr.span_readers, "load",
                        lambda path: {"host": host})
    monkeypatch.setattr(flops, "peaks", lambda kind: peak)
    obs = {"span_trace_path": "x", "device_kind": "any", "config": cfg}
    # the stretch runs from emit 0 to emit 9: steps 1..9, nine ops each
    want = sum(flops.least_time_s(*tr.ft.decode_read_cost(
        cfg, 4096 * (k + 1), 512 * (k + 1), rows=3), peak)[0]
        for k in range(1, n))
    assert tr.decode_read_roofline(obs) == pytest.approx(
        100 * want / (9 * 2e-3))
    want = 9 * flops.least_time_s(*tr.ft.experts_cost(cfg, 6, 5), peak)[0]
    assert tr.moe_experts_roofline(obs) == pytest.approx(
        100 * want / (9 * 1e-3))
    # a program from before the counters by layer kind: nothing to read
    for d in host["serving.decode.dispatch"]:
        del d[2]["kv_read_window"]
    assert tr.decode_read_roofline(obs) is None
    # no trace at all
    monkeypatch.setattr(tr, "device_ops", lambda path: None)
    assert tr.moe_experts_roofline(obs) is None
