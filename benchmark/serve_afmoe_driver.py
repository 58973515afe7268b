"""Serve cells of an afmoe decoder (Trinity-Large-Preview) that is one chip's
share of its deployment (`kind: "serve_afmoe"`): `paddle_tpu.models.
build_decoder` behind the same `Engine` and `start_gateway` as every other
serve cell, loaded by the same `client.py`.

The configuration file states the model in the published config.json's own
keys.  `model` names the preset of `models/decoder.py`; every key that is a
field of `DecoderConfig` overrides it; the family's own keys are mapped
(`decoder_config_of`): `num_experts` is the number HELD here,
`published_num_experts` the router's width, `deployment.experts_first` the
first held expert; `layer_types` gives both layouts (a sliding layer has a
window and RoPE, a full layer neither), `sliding_window`,
`num_dense_layers`, `num_shared_experts`, `route_scale`, `route_norm`,
`score_func`, `num_experts_per_tok`, `moe_intermediate_size` as published,
`mup_enabled` the embedding's scale sqrt(hidden_size).  Beside them
`param_dtype`, `engine`, `gateway_tenant`, `check_requests`,
`check_controls`, `logit_tolerance` with `logit_tolerance_over`, and the two
modules taken by name, as `serve_latent_driver` takes them: `reference`
(`reference_trinity`) and `flops` (`flops_trinity`).

`serve_driver._warm`, `_drive`, `serve_checks`, `supply_check`,
`serve_decoder_driver._StatsTap` and `_pick` and `serve_latent_driver.check`
(with `_readings`, `_greedy`, `within` under it) are imported unchanged.
What `correct` checks is what `serve_latent_driver` checks (its docstring),
and one thing more: where the traffic holds a request whose context passes
the window by more than a ring (`sliding_window` + `Engine.stats()`
`kv_ring_len`), the first checked request is such a one, so the check reads
log-probabilities that were decoded through a ring that has wrapped, beside
a full row that has not.

Observations: as `serve_latent_driver` plus the KV counters by layer kind
(`decode_kv_read_positions_window` / `_global`, `decode_kv_live_positions_
window` / `_global`), the window's `decode_steps`, and the pool's bytes and
ring length from `Engine.stats()` (`kv_pool_bytes`, `kv_pool_bytes_window`,
`kv_pool_bytes_global`, `kv_ring_len`; printed in the notes too).
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np

from benchmark import traffic
from benchmark.checks import held
from benchmark.serve_decoder_driver import _pick, _StatsTap
from benchmark.serve_driver import (_drive, _warm, serve_checks,
                                    supply_check)
from benchmark.serve_latent_driver import check

_COUNTERS = ("prefill_tokens", "prefill_padded_tokens", "moe_assignments",
             "moe_experts_touched", "moe_load_max", "moe_routed",
             "decode_kv_live_positions", "decode_kv_read_positions",
             "decode_kv_live_positions_window",
             "decode_kv_live_positions_global",
             "decode_kv_read_positions_window",
             "decode_kv_read_positions_global")
_POOL = ("kv_pool_bytes", "kv_pool_bytes_window", "kv_pool_bytes_global",
         "kv_ring_len")


def decoder_config_of(cfg: dict):
    """The `DecoderConfig` a configuration file states."""
    from paddle_tpu.models.decoder import DecoderConfig, decoder_config
    names = {f.name for f in dataclasses.fields(DecoderConfig)}
    n_held = cfg["num_experts"]
    n_all = cfg.get("published_num_experts", n_held)
    sliding = tuple(int(t == "sliding_attention")
                    for t in cfg["layer_types"][:cfg["num_hidden_layers"]])
    return decoder_config(
        cfg["model"], **{k: v for k, v in cfg.items() if k in names},
        moe_num_primary_experts=n_all,
        moe_num_active_primary_experts=cfg["num_experts_per_tok"],
        moe_ffn_hidden_size=cfg["moe_intermediate_size"],
        experts_held=(None if n_held == n_all else
                      (cfg.get("deployment", {}).get("experts_first", 0),
                       n_held)),
        rope_layout=sliding, sliding_window_layout=sliding,
        sliding_window_size=cfg["sliding_window"],
        first_k_dense_replace=cfg["num_dense_layers"],
        n_shared_experts=cfg["num_shared_experts"],
        routed_scaling_factor=cfg["route_scale"],
        norm_topk_prob=cfg["route_norm"], scoring_func=cfg["score_func"],
        embedding_scale=(float(cfg["hidden_size"]) ** 0.5
                         if cfg["mup_enabled"] else 1.0))


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
        pool = {k: s1[k] for k in _POOL}
        # a context this long has gone once round the ring
        wrapped = cfg["sliding_window"] + pool["kv_ring_len"]
        pick = _pick(pairs, by_id, cfg["check_requests"], wrapped,
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

    # the last entry: a context that has gone round the ring was among those
    # checked, where the traffic holds one at all
    past = any(len(r["prompt"]) + r["max_tokens"] > wrapped for r in requests)
    checks = serve_checks(
        len(good), checked["tokens_checked"], len(failed), compiles,
        {"logprob_error_mean": (checked.get("logprob_error_mean"),
                                checked.get("logprob_error_limit"))},
        {"longest_context_checked": (checked.get("longest_context_checked",
                                                 0), wrapped + 1)}
        if past else None)
    return dict(
        setup, correct=held(checks), checks=checks,
        supply=supply_check(mix, supply, dry),
        attempted=len(counted), failed=len(failed),
        end_to_end={"ttft_p95_ms": p95_ms(ttft), "itl_p95_ms": p95_ms(gaps),
                    "serve_tokens_per_s": in_window / T / ctx.cell["chips"]},
        observations=dict(
            delta, **pool,
            engine_token_latency_s=[g for _, h in pairs
                                    for g in h.token_latencies_s],
            # the first token of each admission comes from its prefill
            decode_tokens=decode_tokens, decode_steps=d["decode_steps"],
            decode_capacity=d["decode_steps"] * engine.max_slots,
            model_flops=model_flops, window_s=T),
        notes=dict(
            checked, compiles_in_window=compiles, completed=len(good),
            completed_rps=len(good) / T, prefills_in_window=len(admitted),
            **supply, **pool,
            ttft_p50_ms=float(np.median(ttft)) * 1e3 if ttft else None,
            ttft_p95_ms=p95_ms(ttft),
            itl_p50_ms=float(np.median(gaps)) * 1e3 if len(gaps) else None,
            engine=dict(d, **delta),
            fail_sample=[(r["id"], r["status"], r["error"])
                         for r in failed[:3]]))
