"""Serve cells of a latent-attention decoder that is one chip's share of its
deployment (`kind: "serve_latent"`): `paddle_tpu.models.build_decoder` behind
the same `Engine` and `start_gateway` as every other serve cell, loaded by
the same `client.py`.

The configuration file states the model in the published config.json's own
keys.  `model` names the preset of `models/decoder.py`; every key that is a
field of `DecoderConfig` overrides it; the expert keys are mapped
(`decoder_config_of`): `n_routed_experts` is the number HELD here,
`published_n_routed_experts` the router's width, `deployment.experts_first`
the first held expert, `num_experts_per_tok` and `moe_intermediate_size` as
published.  Beside them `param_dtype`, `engine`, `gateway_tenant`,
`check_requests`, `check_controls`, `logit_tolerance` with
`logit_tolerance_over`, and **the two modules this driver takes by name**:
`reference` (`benchmark/<name>.py` with `hidden(state, ids, cfg)` and
`head_logits(state, x, rows, cfg)`) and `flops` (`benchmark/<name>.py` with
`model_flops(cfg, prompt_lens, decode_tokens, kv_live_positions,
assignments)`), so that the next configuration of this kind brings its two
modules and no third copy of `run`.

`serve_driver._warm` and `_drive` and `serve_decoder_driver`'s `_StatsTap`,
`_readings`, `_greedy`, `within` and `_pick` are imported unchanged.  What
`correct` checks is what `serve_decoder_driver` checks (its docstring): for
`check_requests` completed requests the reference's full forward at every
generated position against the engine's token and the log-probability it
computed for it — prefill, then decode through the latent cache —, the
reading at most `logit_tolerance` times the same reading of the control
`logit_tolerance_over` in the same run; every control goes through the same
comparison and has to come out false.  No request failed or was refused,
each has exactly the tokens asked for, no program was built in the window.

Observations: as `serve_decoder_driver` plus `moe_routed` (real tokens x
top-k whoever holds the expert; `moe_assignments` are those that landed on
held experts).  Notes: also `in_flight_end` (a closed loop whose pool ran
dry reads under its callers).
"""
from __future__ import annotations

import dataclasses
import importlib

import jax.numpy as jnp
import numpy as np

from benchmark import traffic
from benchmark.serve_decoder_driver import (_greedy, _pick, _readings,
                                            _StatsTap, within)
from benchmark.checks import held
from benchmark.serve_driver import (_drive, _warm, serve_checks,
                                    supply_check)

_PAD = 2048            # reference sequences are padded to a multiple of this
_ROWS = 256            # head rows computed at a time
_COUNTERS = ("prefill_tokens", "prefill_padded_tokens", "moe_assignments",
             "moe_experts_touched", "moe_load_max", "moe_routed",
             "decode_kv_live_positions", "decode_kv_read_positions")


def decoder_config_of(cfg: dict):
    """The `DecoderConfig` a configuration file states."""
    from paddle_tpu.models.decoder import DecoderConfig, decoder_config
    names = {f.name for f in dataclasses.fields(DecoderConfig)}
    held = cfg["n_routed_experts"]
    n_all = cfg.get("published_n_routed_experts", held)
    layers = cfg["num_hidden_layers"]
    return decoder_config(
        cfg["model"], **{k: v for k, v in cfg.items() if k in names},
        moe_num_primary_experts=n_all,
        moe_num_active_primary_experts=cfg["num_experts_per_tok"],
        moe_ffn_hidden_size=cfg["moe_intermediate_size"],
        experts_held=(None if held == n_all else
                      (cfg.get("deployment", {}).get("experts_first", 0),
                       held)),
        rope_layout=(1,) * layers, sliding_window_layout=(0,) * layers)


def build_model(cfg: dict, seed: int):
    """The seeded model of a configuration file, in eval mode."""
    import paddle_tpu as paddle
    from paddle_tpu.models.decoder import build_decoder
    dcfg = decoder_config_of(cfg)
    paddle.seed(seed)
    paddle.set_default_dtype(cfg["param_dtype"])
    try:
        model = build_decoder(dcfg)
    finally:
        paddle.set_default_dtype("float32")
    model.eval()
    return dcfg, model


def _build(ctx, handles: list):
    from paddle_tpu.serving import Engine
    from paddle_tpu.serving.gateway import TenantConfig, start_gateway
    cfg = ctx.config
    dcfg, model = build_model(cfg, ctx.seed)
    engine = Engine(model, admission_hook=lambda req, load: handles.append(req),
                    **cfg["engine"])
    stack = start_gateway(
        [engine], own_engines=True,
        default_tenant=TenantConfig("default", **cfg["gateway_tenant"]))
    return dcfg, model, engine, stack


def check(reference, model, cfg, sample) -> dict:
    """For each sampled request (prompt, tokens, log-probabilities): the
    reference's logits at every position the engine generated from, and the
    engine's readings against them; then each control's, on the same rows.
    One system's hidden states are held at a time ([T, hidden] float32
    each); the reference's logits of the checked rows are kept for the
    controls to be read against."""
    state = model.state_dict()
    controls = {k: dict(cfg, **v)
                for k, v in cfg.get("check_controls", {}).items()}
    rows_of = {k: ([], []) for k in ("engine", *controls)}
    agree, longest = 0, 0

    def add(name, readings):
        for acc, r in zip(rows_of[name], readings):
            acc.append(np.asarray(r, np.float64))

    for prompt, toks, lps in sample:
        ids = np.asarray(list(prompt) + list(toks[:-1]), np.int64)
        rows = np.arange(len(prompt) - 1, len(ids))
        padded = np.zeros(-(-len(ids) // _PAD) * _PAD, np.int64)
        padded[:len(ids)] = ids           # right padding is causal
        toks, lps = np.asarray(toks), np.asarray(lps, np.float32)
        blocks = [rows[i:i + _ROWS] for i in range(0, len(rows), _ROWS)]
        x = reference.hidden(state, padded, cfg)
        true = [reference.head_logits(state, x, b, cfg) for b in blocks]
        del x
        for i, lg in enumerate(true):
            t = jnp.asarray(toks[i * _ROWS:(i + 1) * _ROWS])
            add("engine", _readings(
                lg, t, jnp.asarray(lps[i * _ROWS:(i + 1) * _ROWS])))
            agree += int(jnp.sum(lg.argmax(-1) == t))
        for k, c in controls.items():
            x = reference.hidden(state, padded, c)
            for b, lg in zip(blocks, true):
                add(k, _readings(lg, *_greedy(
                    reference.head_logits(state, x, b, c))))
            del x
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


def run(ctx) -> dict:
    cfg, mix, T = ctx.config, ctx.mix, ctx.seconds
    reference = importlib.import_module(f"benchmark.{cfg['reference']}")
    fl = importlib.import_module(f"benchmark.{cfg['flops']}")
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
        pick = _pick(pairs, by_id, cfg["check_requests"], 0,
                     np.random.RandomState(ctx.seed % 2 ** 32))
        checked = check(reference, model, cfg, [
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
    # inside it (the engine's clock is the tap's), the decoded tokens with
    # the positions their attention really read, and the routed experts by
    # the assignments that landed here
    admitted = [h for h in handles
                if h.t_admit is not None and t_open <= h.t_admit < t_close]
    decode_tokens = d["tokens"] - d["slot_allocs"]
    model_flops = fl.model_flops(
        cfg, [int(h.prompt.size) for h in admitted], decode_tokens,
        delta["decode_kv_live_positions"], delta["moe_assignments"])

    def p95_ms(v):
        return float(np.percentile(v, 95)) * 1e3 if len(v) else None

    checks = serve_checks(
        len(good), checked["tokens_checked"], len(failed), compiles,
        {"logprob_error_mean": (checked.get("logprob_error_mean"),
                                checked.get("logprob_error_limit"))})
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
            checked, compiles_in_window=compiles, completed=len(good),
            completed_rps=len(good) / T, prefills_in_window=len(admitted),
            **supply,
            ttft_p50_ms=float(np.median(ttft)) * 1e3 if ttft else None,
            ttft_p95_ms=p95_ms(ttft),
            itl_p50_ms=float(np.median(gaps)) * 1e3 if len(gaps) else None,
            engine=dict(d, **delta),
            fail_sample=[(r["id"], r["status"], r["error"])
                         for r in failed[:3]]))
