"""`RequestHandle.logprobs`: the log-probability of every generated token,
from the step that chose it, on each sampler path of the engine and for both
decoder families."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import build_decoder, build_gpt, gpt_config
from paddle_tpu.serving import Engine

TOL = 1e-4      # float32 against the model's own float32 full forward


def _model(family):
    paddle.seed(3)
    if family == "decoder":
        model, vocab = build_decoder("smallthinker-tiny"), 256
    else:
        model, vocab = build_gpt(gpt_config(
            "gpt-tiny", hidden_dropout_prob=0.0,
            attention_dropout_prob=0.0)), 1000
    model.eval()
    return model, vocab


@pytest.mark.parametrize("kw", [{}, {"sample_on_device": False},
                                {"speculative_k": 3}, {"paged_kv": True}],
                         ids=["device", "host", "speculative", "paged"])
@pytest.mark.parametrize("family", ["decoder", "gpt"])
def test_engine_logprobs_are_the_models_own(family, kw):
    """`RequestHandle.logprobs`: one per token, the log-softmax of the
    model's full-forward logits at the token, on every sampler path, for a
    greedy and a sampled request."""
    model, vocab = _model(family)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, vocab, n).astype(np.int64)
               for n in (5, 12, 20, 7)]
    eng = Engine(model, max_slots=3, max_len=64, **kw)
    try:
        hs = [eng.submit(p, max_new_tokens=9, seed=5,
                         temperature=0.8 if i == 3 else 0.0)
              for i, p in enumerate(prompts)]
        outs = [(h.result(timeout=600), np.asarray(h.logprobs)) for h in hs]
    finally:
        eng.shutdown()
    for p, (toks, lps) in zip(prompts, outs):
        assert len(lps) == len(toks) == 9
        ids = np.concatenate([p, toks[:-1]])
        lg = np.asarray(model(paddle.to_tensor(ids[None]))._value,
                        np.float64)[0, len(p) - 1:]
        want = (lg[np.arange(9), toks] -
                np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) -
                lg.max(-1))
        np.testing.assert_allclose(lps, want, atol=TOL, rtol=0)
