"""Serve cells of a configuration-driven decoder (`kind: "serve_decoder"`):
`paddle_tpu.models.build_decoder` behind the same `Engine` and
`start_gateway` as the GPT serve cells, loaded by the same `client.py`.

The configuration file states the model in the published config.json's own
keys (`model` names the preset of `models/decoder.py`; every key that is a
field of `DecoderConfig` overrides it), plus `param_dtype`, `engine`,
`gateway_tenant`, `check_requests`, `check_controls` and the check's limit
`logit_tolerance` with `logit_tolerance_over`.

`serve_driver._warm` and `serve_driver._drive` are imported unchanged: this
module differs in what it builds, in the reference it checks against
(`reference_smallthinker.py`) and in what it observes.  `run` below is a
copy of `serve_driver.run` with those three parts replaced (PERF.md section
7 asks the next `benchmark` PR to let `serve_driver.run` take its builder
and checker from the configuration, so that the copy can go).

What `correct` checks, beyond `serve_driver`'s own conditions: for
`check_requests` completed requests, at least one of them with a context
past the sliding window where the window's traffic holds one, the
reference's logits (full forward, no cache) over the whole vocabulary at
every generated position, against what the engine gave for that position:
its token and the log-probability it computed for it
(`RequestHandle.logprobs`, from the step that chose the token).  The reading
is the mean distance between the engine's log-probability (its logit less
the log-sum-exp of all of them) of its token and the reference's of the same
token: every row counts, so it tells precisions apart, which a greedy token
alone cannot (it says only which logit was largest).  `check_controls` names
settings of the reference (`reference_weights: "int8"`, or any key of the
configuration) that are run on the same rows as systems of their own: a
control's greedy token and its log-probability give the same reading.  The
one limit: the engine's reading is at most `logit_tolerance` times the
reading of the control `logit_tolerance_over` (the reference in the nearest
precision below the served one) IN THE SAME RUN — both readings grow with
the contexts a run happens to check, their ratio does not.  Every control
goes through the same comparison, and the notes carry its readings and its
verdict, which has to be false.  The largest deficit of the engine's token
under the reference's best logit is printed and not judged: it is the
largest of a thousand heavy-tailed readings.

Observations beyond `serve_driver`'s: the engine's counters over the window
(`prefill_tokens`, `moe_assignments`, `moe_experts_touched`,
`moe_load_max`, KV positions) and `model_flops`, the model FLOPs of the
tokens prefilled and decoded inside the window (`flops_smallthinker.py`).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops_smallthinker as fs
from benchmark import reference_smallthinker as reference
from benchmark import traffic
from benchmark.checks import held
from benchmark.serve_driver import (_drive, _warm, serve_checks,
                                    supply_check)

_PAD = 2048            # reference sequences are padded to a multiple of this
_ROWS = 256            # head rows computed at a time (two blocks are held)
_COUNTERS = ("prefill_tokens", "prefill_padded_tokens", "moe_assignments",
             "moe_experts_touched", "moe_load_max",
             "decode_kv_live_positions", "decode_kv_read_positions")


class _StatsTap:
    """Stands for the engine in `_drive`, which reads `stats()` at the
    window's two edges: keeps both snapshots and when they were taken."""

    def __init__(self, engine):
        self.engine, self.snaps = engine, []

    def stats(self):
        s = self.engine.stats()
        self.snaps.append((time.perf_counter(), s))
        return s


def _build(ctx, handles: list):
    import paddle_tpu as paddle
    from paddle_tpu.models.decoder import (DecoderConfig, build_decoder,
                                           decoder_config)
    from paddle_tpu.serving import Engine
    from paddle_tpu.serving.gateway import TenantConfig, start_gateway

    cfg = ctx.config
    names = {f.name for f in dataclasses.fields(DecoderConfig)}
    dcfg = decoder_config(cfg["model"],
                          **{k: v for k, v in cfg.items() if k in names})
    paddle.seed(ctx.seed)
    paddle.set_default_dtype(cfg["param_dtype"])
    try:
        model = build_decoder(dcfg)
    finally:
        paddle.set_default_dtype("float32")
    model.eval()
    engine = Engine(model, admission_hook=lambda req, load: handles.append(req),
                    **cfg["engine"])
    stack = start_gateway(
        [engine], own_engines=True,
        default_tenant=TenantConfig("default", **cfg["gateway_tenant"]))
    return dcfg, model, engine, stack


@jax.jit
def _readings(lg, toks, lps):
    """A system's tokens and their log-probabilities against the
    reference's logits `lg` [n, V]: each token's deficit under the
    reference's best logit, and how far its log-probability lies from the
    reference's log-probability of the same token."""
    at = lg[jnp.arange(toks.shape[0]), toks]
    return (lg.max(-1) - at,
            jnp.abs(lps - (at - jax.nn.logsumexp(lg, axis=-1))))


@jax.jit
def _greedy(lg):
    """The greedy token of each row of `lg` and its log-probability."""
    return lg.argmax(-1), lg.max(-1) - jax.nn.logsumexp(lg, axis=-1)


def within(readings: dict, yardstick: dict, cfg) -> bool:
    """A system's reading against the cell's limit: at most `logit_tolerance`
    times the yardstick control's reading of the same rows."""
    return (readings["logprob_error_mean"] <=
            cfg["logit_tolerance"] * yardstick["logprob_error_mean"])


def _check(model, cfg, sample) -> dict:
    """For each sampled request (prompt, tokens, log-probabilities): the
    reference's logits at every position the engine generated from, and the
    engine's readings against them; then each control's, on the same rows."""
    state = model.state_dict()
    controls = {k: dict(cfg, **v)
                for k, v in cfg.get("check_controls", {}).items()}
    rows_of = {k: ([], []) for k in ("engine", *controls)}
    agree, longest = 0, 0
    for prompt, toks, lps in sample:
        ids = np.asarray(list(prompt) + list(toks[:-1]), np.int64)
        rows = np.arange(len(prompt) - 1, len(ids))
        padded = np.zeros(-(-len(ids) // _PAD) * _PAD, np.int64)
        padded[:len(ids)] = ids           # right padding is causal
        x = reference.hidden(state, padded, cfg)
        xs = {k: reference.hidden(state, padded, c)
              for k, c in controls.items()}
        toks, lps = np.asarray(toks), np.asarray(lps, np.float32)
        for i in range(0, len(rows), _ROWS):
            blk = rows[i:i + _ROWS]
            lg = reference.head_logits(state, x, blk, cfg)
            t = jnp.asarray(toks[i:i + _ROWS])
            systems = {"engine": (t, jnp.asarray(lps[i:i + _ROWS]))}
            for k, c in controls.items():
                systems[k] = _greedy(reference.head_logits(state, xs[k], blk,
                                                          c))
            for k, (tk, lp) in systems.items():
                for acc, r in zip(rows_of[k], _readings(lg, tk, lp)):
                    acc.append(np.asarray(r, np.float64))
            agree += int(jnp.sum(lg.argmax(-1) == t))
        longest = max(longest, len(ids) + 1)

    def summary(deficit, error):
        deficit, error = np.concatenate(deficit), np.concatenate(error)
        return {"logprob_error_mean": float(error.mean()),
                "logprob_error_max": float(error.max()),
                "logit_deficit_max": float(deficit.max())}

    if not rows_of["engine"][0]:
        return {"tokens_checked": 0}
    of = {k: summary(*rows_of[k]) for k in rows_of}
    engine, yardstick = of.pop("engine"), of[cfg["logit_tolerance_over"]]
    return dict(engine, argmax_matches=agree,
                within_tolerance=within(engine, yardstick, cfg),
                logprob_error_limit=(cfg["logit_tolerance"] *
                                     yardstick["logprob_error_mean"]),
                tokens_checked=sum(len(t) for _, t, _ in sample),
                longest_context_checked=longest,
                controls={k: dict(r, correct=within(r, yardstick, cfg))
                          for k, r in of.items()})


def _pick(pairs, by_id, n: int, window: int, rs) -> list:
    """`n` of the completed requests at random, the first of them one whose
    context passes the window where there is such a request."""
    order = [int(i) for i in rs.permutation(len(pairs))]

    def context(i):
        r = by_id[pairs[i][0]["id"]]
        return len(r["prompt"]) + r["max_tokens"]

    past = [i for i in order if context(i) > window]
    return (past[:1] + [i for i in order if i not in past[:1]])[:n]


def run(ctx) -> dict:
    cfg, mix, T = ctx.config, ctx.mix, ctx.seconds
    handles: list = []
    dcfg, model, engine, stack = _build(ctx, handles)
    try:
        requests = traffic.make_requests(mix, ctx.seed, T, dcfg.vocab_size)
        _warm(ctx, engine, dcfg, requests)
        handles.clear()
        tap = _StatsTap(engine)
        results, setup, d, compiles = _drive(ctx, tap, stack, requests)
        (t_open, s0), (t_close, s1) = tap.snaps
        by_id = {r["id"]: r for r in requests}
        if mix["loop"] == "open":
            counted = [r for r in results if by_id[r["id"]]["counted"]]
            start = "due"
        else:
            counted = [r for r in results
                       if r["sent"] is not None and 0.0 <= r["sent"] < T]
            start = "sent"

        def bad(r):
            """Error, refusal, or short.  A closed loop's callers abandon
            what is in flight when the window ends (answered or not yet):
            that is no failure; in an open loop every request must finish."""
            if r["error"]:
                return True
            if r["done"]:
                return (r["status"] != 200 or
                        len(r["stamps"]) != by_id[r["id"]]["max_tokens"])
            return mix["loop"] == "open" or r["status"] not in (0, 200)

        failed = [r for r in counted if bad(r)]
        good = [r for r in counted if r["done"] and not bad(r)]
        hid = {h.journey.id: h for h in handles if h.journey is not None}
        pairs = [(r, hid[r["id"]]) for r in good if r["id"] in hid]
        window = min([w for w in fs.windows(cfg) if w] or [0])
        pick = _pick(pairs, by_id, cfg["check_requests"], window,
                     np.random.RandomState(ctx.seed % 2 ** 32))
        check = _check(model, cfg, [
            (by_id[pairs[i][0]["id"]]["prompt"], pairs[i][1].tokens,
             pairs[i][1].logprobs) for i in pick])
    finally:
        stack.close()

    ttft = [r["stamps"][0] - r[start] for r in good]
    gaps = [g for r in good for g in np.diff(r["stamps"])]
    in_window = sum(1 for r in results for s in r["stamps"] if 0.0 <= s < T)
    supply, dry = traffic.closed_loop_supply(mix, requests, results, T)
    delta = {k: s1[k] - s0[k] for k in _COUNTERS}
    # model FLOPs of the window: the prompts whose prefill was dispatched
    # inside it (the engine's clock is the tap's), and the decoded tokens
    # with the KV positions their attention really read
    admitted = [h for h in handles
                if h.t_admit is not None and t_open <= h.t_admit < t_close]
    decode_tokens = d["tokens"] - d["slot_allocs"]
    model_flops = (
        sum(fs.prefill_flops(cfg, int(h.prompt.size)) for h in admitted) +
        fs.decode_flops(cfg, decode_tokens,
                        delta["decode_kv_live_positions"]))

    def p95_ms(v):
        return float(np.percentile(v, 95)) * 1e3 if len(v) else None

    # the last entry: a context past the window was among those checked,
    # where the traffic holds one at all
    past = any(len(r["prompt"]) + r["max_tokens"] > window for r in requests)
    checks = serve_checks(
        len(good), check["tokens_checked"], len(failed), compiles,
        {"logprob_error_mean": (check.get("logprob_error_mean"),
                                check.get("logprob_error_limit"))},
        {"longest_context_checked": (check["longest_context_checked"],
                                     window + 1)} if past else None)
    return dict(
        setup, correct=held(checks), checks=checks,
        supply=supply_check(mix, supply, dry),
        attempted=len(counted), failed=len(failed),
        end_to_end={"ttft_p95_ms": p95_ms(ttft), "itl_p95_ms": p95_ms(gaps),
                    "serve_tokens_per_s": in_window / T / ctx.cell["chips"]},
        observations=dict(
            delta,
            engine_token_latency_s=[g for _, h in pairs
                                    for g in h.token_latencies_s],
            # the first token of each admission comes from its prefill
            decode_tokens=decode_tokens,
            decode_capacity=d["decode_steps"] * engine.max_slots,
            model_flops=model_flops, window_s=T),
        notes=dict(
            check, compiles_in_window=compiles, completed=len(good),
            completed_rps=len(good) / T, prefills_in_window=len(admitted),
            **supply,
            ttft_p50_ms=float(np.median(ttft)) * 1e3 if ttft else None,
            ttft_p95_ms=p95_ms(ttft),
            itl_p50_ms=float(np.median(gaps)) * 1e3 if len(gaps) else None,
            engine=dict(d, **delta),
            fail_sample=[(r["id"], r["status"], r["error"])
                         for r in failed[:3]]))
