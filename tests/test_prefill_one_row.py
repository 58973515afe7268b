"""The prefill program has one row (ISSUE 30).

`Engine.prefill_batch` bounds the requests admitted between two decode
steps; it is not a shape.  A wave of n admitted requests is n dispatches of
the one-row prefill program, each at the power-of-two bucket of its own
prompt, all of them before the first fetch; then one fetch and one emit per
dispatch, in admission order.  So there is one prefill program per bucket
whatever the wave sizes were, no padding rows, and a request's tokens and
log-probabilities do not depend on who was admitted beside it.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import build_gpt, gpt_config
from paddle_tpu.observability import to_prometheus_text, trace
from paddle_tpu.serving import AdapterRegistry, Engine, make_lora
from paddle_tpu.serving.engine import SERVING_PREFILL_WAVES
from paddle_tpu.testing import faults

# prompt lengths of one wave and the buckets they fall in (lowest bucket 8)
LENGTHS, BUCKETS = (5, 12, 40), (8, 16, 64)


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = gpt_config("gpt-tiny", max_position_embeddings=128,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(7)
    model = build_gpt(cfg)
    model.eval()
    return model, cfg


def _prompts(cfg, lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab_size, n).astype(np.int64)
            for n in lengths]


def _staged(model, prompts, new=5, submit_kw=None, **engine_kw):
    """Every prompt queued before the scheduler starts, so that one
    admission takes as many as `prefill_batch` allows; (handles, stats,
    the span ring of the run)."""
    eng = Engine(model, max_slots=4, max_len=128, auto_start=False,
                 **engine_kw)
    try:
        trace.clear()
        hs = [eng.submit(p, max_new_tokens=new, **kw)
              for p, kw in zip(prompts, submit_kw or [{}] * len(prompts))]
        eng.start()
        for h in hs:
            h.result(timeout=300)
        return hs, eng.stats(), trace.spans()
    finally:
        eng.shutdown()


def test_a_wave_is_one_dispatch_per_request_at_its_own_bucket(tiny_gpt):
    model, cfg = tiny_gpt
    _, st, ring = _staged(model, _prompts(cfg, LENGTHS))
    disp = [r for r in ring if r["name"] == "serving.prefill.dispatch"]
    assert [d["attrs"]["bucket"] for d in disp] == list(BUCKETS)
    for d, n in zip(disp, LENGTHS):
        a = d["attrs"]
        assert a["rows"] == a["batch_rows"] == 1
        assert a["prompt_tokens"] == n
        assert a["padded_tokens"] == a["bucket"]
    assert (st["prefill_batches"], st["prefill_waves"]) == (3, 1)
    assert st["prefill_tokens"] == sum(LENGTHS)
    assert st["prefill_padded_tokens"] == sum(BUCKETS)
    assert st["prefill_compiles"] == 3
    wave, = [r for r in ring if r["name"] == "serving.prefill"]
    assert wave["attrs"] == {"n": 3, "bucket": max(BUCKETS)}
    # every dispatch of the wave before its first fetch; then fetch and
    # emit a request, in admission order
    leaves = sorted((r for r in ring if r["parent_id"] == wave["id"]),
                    key=lambda r: r["ts"])
    assert [r["name"].rsplit(".", 1)[1] for r in leaves] == (
        ["dispatch"] * 3 + ["fetch", "emit"] * 3)
    assert f"{SERVING_PREFILL_WAVES} " in to_prometheus_text()


def _lora_registry(model, cfg):
    reg = AdapterRegistry(model, max_resident=2, max_rank=8)
    for i, name in enumerate(["tenant-a", "tenant-b"]):
        reg.register(make_lora(cfg, rank=2 + 2 * i, seed=10 + i, name=name,
                               std=0.2))
    return reg


@pytest.mark.parametrize("form", ["dense", "paged", "int8", "lora"])
def test_a_mixed_wave_equals_one_request_at_a_time(tiny_gpt, form):
    """Greedy tokens and log-probabilities, bit for bit in float32: the
    wave (`prefill_batch` 4) against the same requests admitted one by one
    (`prefill_batch` 1)."""
    model, cfg = tiny_gpt
    prompts = _prompts(cfg, LENGTHS + (9,), seed=3)
    submit_kw = None
    if form == "lora":
        submit_kw = [{"adapter": "tenant-a"}, {}, {"adapter": "tenant-b"},
                     {"adapter": "tenant-a"}]

    def run(prefill_batch):
        kw = {"dense": {}, "paged": {"paged_kv": True, "page_size": 8},
              "int8": {"kv_dtype": "int8"},
              "lora": {"adapters": _lora_registry(model, cfg)}}[form]
        return _staged(model, prompts, submit_kw=submit_kw,
                       prefill_batch=prefill_batch, **kw)

    wave, st_w, _ = run(4)
    single, st_s, _ = run(1)
    assert (st_w["prefill_batches"], st_w["prefill_waves"]) == (4, 1)
    assert (st_s["prefill_batches"], st_s["prefill_waves"]) == (4, 4)
    assert st_w["prefill_compiles"] == st_s["prefill_compiles"] == 3
    for a, b in zip(wave, single):
        assert a.tokens == b.tokens
        la, lb = (np.asarray(h.logprobs, np.float32) for h in (a, b))
        assert la.size == 5
        np.testing.assert_array_equal(la.view(np.int32), lb.view(np.int32))
    if form == "lora":      # the lanes did differ: the adapters were applied
        assert wave[0].tokens != _staged(model, prompts[:1])[0][0].tokens


def test_one_prefill_program_per_bucket_whatever_the_wave_sizes(tiny_gpt):
    """Waves of 1, 2 and 4 requests at one bucket: one compile."""
    model, cfg = tiny_gpt
    eng = Engine(model, max_slots=4, max_len=128, max_queue=16)
    try:
        n_waves = 0
        for size in (1, 2, 4):
            # every turn of the scheduler starts 0.1 s late: the submits of
            # one size are all queued before the turn that admits them
            with faults.inject("serving.scheduler", mode="delay",
                               seconds=0.1, times=None):
                hs = [eng.submit(p, max_new_tokens=3)
                      for p in _prompts(cfg, [10 + i for i in range(size)],
                                        seed=size)]
                for h in hs:
                    h.result(timeout=300)
            st = eng.stats()
            assert st["prefill_compiles"] == 1, (size, st)
            assert st["prefill_waves"] == n_waves + 1, (size, st)
            n_waves = st["prefill_waves"]
        assert st["prefill_batches"] == 7
        assert st["prefill_padded_tokens"] == 7 * 16
    finally:
        eng.shutdown()
