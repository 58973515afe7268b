"""Serve cells: the configuration's `Engine` behind `start_gateway`, loaded
over real HTTP (SSE) by `client.py` in a child process that never touches
JAX.  This process holds the chip and only waits, reads the engine's counters
at the window's edges and, in a traced run, profiles a stretch of the window.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmark import flops, host, reference, traffic
from benchmark.checks import check, held

_HERE = os.path.dirname(os.path.abspath(__file__))
_LEAD_S = 2.0          # child start-up before its first request is due
# the threads whose share of the host `host.delta` lists: the engine's
# scheduler, the gateway's dispatcher and acceptor, and this one (asleep)
_THREADS = ("paddle-tpu-serving", "paddle-tpu-gateway",
            "paddle-tpu-gateway-http", "MainThread")


def _build(ctx, handles: list):
    import paddle_tpu as paddle
    from paddle_tpu.models import build_gpt, gpt_config
    from paddle_tpu.serving import Engine
    from paddle_tpu.serving.gateway import TenantConfig, start_gateway

    cfg = ctx.config
    sizes = {k: cfg[k] for k in flops.GPT_SIZE_KEYS}
    gcfg = gpt_config(cfg["model"], hidden_dropout_prob=0.0,
                      attention_dropout_prob=0.0, **sizes)
    paddle.seed(ctx.seed)
    paddle.set_default_dtype(cfg["param_dtype"])
    try:
        model = build_gpt(gcfg)
    finally:
        paddle.set_default_dtype("float32")
    model.eval()
    # the engine hands every request it is given to this hook: the handles
    # carry the engine's own clocks (t_submit, t_admit, ttft_s, gaps)
    engine = Engine(model, admission_hook=lambda req, load: handles.append(req),
                    **cfg["engine"])
    stack = start_gateway(
        [engine], own_engines=True,
        default_tenant=TenantConfig("default", **cfg["gateway_tenant"]))
    return gcfg, model, engine, stack


def _warm(ctx, engine, gcfg, requests):
    """Each prefill bucket the traffic reaches, once, and the decode program
    (two new tokens: the second comes from a decode step)."""
    import numpy as np
    rs = np.random.RandomState(ctx.seed % 2 ** 32)
    longest = max(len(r["prompt"]) for r in requests)
    for b in traffic.prefill_buckets(requests, min(8, engine.max_len),
                                     engine.max_len):
        t0 = time.monotonic()
        n = min(b, longest, engine.max_len - 2)
        engine.submit(rs.randint(0, gcfg.vocab_size, n),
                      max_new_tokens=2).result(timeout=1100)
        ctx.say(f"warm-up bucket {b} (prompt {n}): "
                f"{time.monotonic() - t0:.2f}s")


def _check_logits(model, gcfg, sample) -> dict:
    """For each sampled request: the reference's logits (full forward, no
    cache) at every position the engine generated from; how far the logit
    of the engine's token lies under the reference's largest."""
    import numpy as np
    worst, checked, agree = 0.0, 0, 0
    state = model.state_dict()
    for prompt, toks in sample:
        ids = np.asarray(list(prompt) + list(toks[:-1]), np.int64)
        rows = np.arange(len(prompt) - 1, len(ids))
        size = 128
        while size < len(ids):
            size *= 2                     # few shapes; right padding is causal
        padded = np.zeros(size, np.int64)
        padded[:len(ids)] = ids
        lg = np.asarray(reference.logits_at(
            state, padded, rows, gcfg.num_layers, gcfg.num_attention_heads,
            gcfg.layer_norm_epsilon))
        chosen = lg[np.arange(len(toks)), np.asarray(toks)]
        worst = max(worst, float(np.max(lg.max(-1) - chosen)))
        agree += int(np.sum(lg.argmax(-1) == np.asarray(toks)))
        checked += len(toks)
    return {"logit_deficit_max": worst, "tokens_checked": checked,
            "argmax_matches": agree}


def _drive(ctx, engine, stack, requests):
    """Start the client, wait out ramp and window, read the engine's
    counters at the window's edges; in a traced run profile a stretch of it.
    Returns the client's report and what was read; what the host gave the
    process over the window goes to `ctx.host` (`host.py`; notes only)."""
    import jax
    mix = ctx.mix
    ann = jax.profiler.TraceAnnotation

    def sleep_until(t):
        with ann("bench.client_wait"):
            time.sleep(max(0.0, t - time.monotonic()))

    probe = host.probe_ms()            # the host's pace, the engine idle
    child = subprocess.Popen(
        [sys.executable, os.path.join(_HERE, "client.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    try:
        t0 = time.monotonic() + _LEAD_S + float(mix.get("ramp_s", 0.0))
        plan = {"host": "127.0.0.1", "port": stack.port, "t0": t0,
                "mode": mix["loop"], "end_s": ctx.seconds,
                "clients": mix.get("clients", 0), "requests": requests}
        child.stdin.write((json.dumps(plan) + "\n").encode())
        child.stdin.close()
        sleep_until(t0)
        setup = {"setup_s": time.monotonic() - ctx.t_start,
                 "setup_compile_s": ctx.log.compile_s,
                 "setup_hits": ctx.log.hits,
                 "setup_requests": ctx.log.requests}
        stats0, host0 = engine.stats(), host.snapshot()
        if ctx.trace:
            sleep_until(t0 + mix["trace_at_frac"] * ctx.seconds)
            ctx.start_trace()
            with ann("bench.window"):
                sleep_until(time.monotonic() + mix["trace_s"])
            ctx.stop_trace()
        sleep_until(t0 + ctx.seconds)
        stats1, host1 = engine.stats(), host.snapshot()
        compiles = ctx.log.requests - setup["setup_requests"]
        report = json.loads(child.stdout.read())
        child.wait(timeout=60)
        client_cpu = (host.snapshot()["children_cpu_s"] -
                      host0["children_cpu_s"])
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    delta = {k: stats1[k] - stats0[k]
             for k in ("tokens", "decode_steps", "slot_allocs", "completed")}
    # the client's CPU is its whole life's (ramp and the report's dump too)
    ctx.host = dict(host.delta(host0, host1, _THREADS), probe_ms=probe,
                    client_cpu_s=client_cpu)
    return report["results"], setup, delta, compiles


def serve_checks(completed: int, tokens_checked: int, failed: int,
                 compiles: int, at_most: dict, at_least=None) -> dict:
    """Each number a serve driver's `correct` compares, beside its limit;
    `correct` is `held` of them and nothing else.  `at_most` / `at_least`
    are the driver's own readings against the reference, `name: (value,
    limit)`.  The closed loop's pool is not among them (`supply_check`)."""
    out = {k: check(v, lim) for k, (v, lim) in at_most.items()}
    out.update((k, check(v, lim, ">="))
               for k, (v, lim) in (at_least or {}).items())
    out.update(completed=check(completed, 1, ">="),
               tokens_checked=check(tokens_checked, 1, ">="),
               failed=check(failed, 0), compiles_in_window=check(compiles, 0))
    return out


def supply_check(mix, supply, dry) -> dict:
    """The closed loop's pool, held from below (`supply`, `dry`: what
    `traffic.closed_loop_supply` returned); nothing for an open loop.  It
    is the run's and not the system's, so a driver returns it beside its
    `checks` (key `supply`) and `run.py` holds the result's `correct` to it:
    a driver's own `correct` says what it compared of the system, and tests
    that drive a `run` on a pool of their own keep reading that."""
    if supply["pool_left"] is None:
        return {}
    return {"pool_left": check(supply["pool_left"], int(mix["clients"]),
                               ">=", dry)}


def run(ctx) -> dict:
    import numpy as np

    cfg, mix, T = ctx.config, ctx.mix, ctx.seconds
    handles: list = []
    gcfg, model, engine, stack = _build(ctx, handles)
    try:
        requests = traffic.make_requests(mix, ctx.seed, T, gcfg.vocab_size)
        _warm(ctx, engine, gcfg, requests)
        handles.clear()
        results, setup, d, compiles = _drive(ctx, engine, stack, requests)
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
        # the engine's side of the same requests, by journey id
        hid = {h.journey.id: h for h in handles if h.journey is not None}
        pairs = [(r, hid[r["id"]]) for r in good if r["id"] in hid]
        rs = np.random.RandomState(ctx.seed % 2 ** 32)
        pick = rs.permutation(len(pairs))[:cfg["check_requests"]]
        logits = _check_logits(model, gcfg, [
            (by_id[pairs[i][0]["id"]]["prompt"], pairs[i][1].tokens)
            for i in pick])
    finally:
        stack.close()

    ttft = [r["stamps"][0] - r[start] for r in good]
    gaps = [g for r in good for g in np.diff(r["stamps"])]
    in_window = sum(1 for r in results for s in r["stamps"] if 0.0 <= s < T)

    supply, dry = traffic.closed_loop_supply(mix, requests, results, T)

    def p95_ms(v):
        return float(np.percentile(v, 95)) * 1e3 if len(v) else None

    checks = serve_checks(
        len(good), logits["tokens_checked"], len(failed), compiles,
        {"logit_deficit_max": (logits["logit_deficit_max"],
                               cfg["logit_tolerance"])})
    late = [r["sent"] - r["due"] for r in counted
            if r["sent"] is not None and r["due"] is not None]
    return dict(
        setup, correct=held(checks), checks=checks,
        supply=supply_check(mix, supply, dry),
        attempted=len(counted), failed=len(failed),
        # every serve cell computes all three; the manifest says which a
        # cell is judged by (none by ttft_p95_ms or itl_p95_ms today: PERF.md
        # section 2; the steady cell's stutter is per layer, `client_gap_s`)
        end_to_end={"ttft_p95_ms": p95_ms(ttft), "itl_p95_ms": p95_ms(gaps),
                    "serve_tokens_per_s": in_window / T / ctx.cell["chips"]},
        observations={
            "gateway_overhead_s": [r["stamps"][0] - r[start] - h.ttft_s
                                   for r, h in pairs if h.ttft_s is not None],
            "engine_queue_s": [h.t_admit - h.t_submit for _, h in pairs
                               if h.t_admit is not None],
            "engine_token_latency_s": [g for _, h in pairs
                                       for g in h.token_latencies_s],
            # every gap between two streamed tokens, clocked at the client
            "client_gap_s": [float(g) for g in gaps],
            # the first token of each admission comes from its prefill
            "decode_tokens": d["tokens"] - d["slot_allocs"],
            "decode_capacity": d["decode_steps"] * engine.max_slots,
        },
        notes=dict(
            logits, compiles_in_window=compiles, completed=len(good),
            completed_rps=len(good) / T,
            ttft_p50_ms=float(np.median(ttft)) * 1e3 if ttft else None,
            ttft_p95_ms=p95_ms(ttft),
            itl_p50_ms=float(np.median(gaps)) * 1e3 if len(gaps) else None,
            itl_p95_ms=p95_ms(gaps),
            itl_mean_ms=float(np.mean(gaps)) * 1e3 if len(gaps) else None,
            itl_p99_ms=(float(np.percentile(gaps, 99)) * 1e3 if len(gaps)
                        else None),
            in_flight_mid=traffic.in_flight(results, T / 2), **supply,
            late_p95_ms=p95_ms(late), engine=d,
            fail_sample=[(r["id"], r["status"], r["error"])
                         for r in failed[:3]]))
