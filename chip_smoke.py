#!/usr/bin/env python3
"""chip_smoke.py — the system's main path, once, on the chip.

    python3 chip_smoke.py                  # needs a TPU; fails without one
    python3 chip_smoke.py --cpu-rehearsal  # gpt-tiny on the CPU (see below)

One process (a chip belongs to one process at a time) drives GPT-3 1.3B
(`gpt3-1.3B-en`: h=2048, 24 layers, 16 heads) at full width and depth
through the entry points a user calls, weights and batches from a seed:

  flash   Pallas flash attention, forward + gradients, against the dense
          f32 reference `_sdpa_ref` at one real shape.
  train   `build_gpt` -> `AdamW` -> `dist.make_train_step(compute_dtype=
          "bfloat16")` exactly as bench.py's `bench_gpt_1p3b` builds it,
          8x1024 tokens, 5 steps on one fixed batch: loss finite at every
          step and lower at the last than at the first; the lowered step
          holds the flash kernels (Mosaic custom calls).
  mesh    (>= 4 TPU devices only) the same step over `fleet.init` ->
          `hcg.get_mesh()` with sharding=2 x mp=2, `fsdp_axis="sharding"`:
          four distinct tpu devices, state split over all four, flash
          routed under the mesh, first loss equal to the one-chip loss.
  serve   the same model, eval/bf16, behind `start_gateway([Engine(model,
          max_slots=8, max_len=2048)])`: 18 `POST /v1/completions` over
          real HTTP on an ephemeral port, more requests than slots, prompts
          whose prefill takes the dense path (bucket < 128) and the flash
          path (bucket >= 128); every response 200 with the tokens asked
          for, one decode signature, slots reused.
  kernel  the same greedy prompts through `Engine(paged_kv=True,
          decode_kernel="pallas")` and `decode_kernel="xla"`, compiled,
          token for token equal — for a float pool and an int8 pool.

Each phase reports its wall seconds, the seconds XLA spent compiling, how
many compiles the persistent cache served, and the device's peak bytes so
far (the allocator's high-water mark does not reset between phases).
Those are set-up facts of one run, not the benchmark: no rate is printed.
Any phase failing fails the script (non-zero exit, the phase named), and
so does finding no TPU — this script never falls back to the CPU.

`--cpu-rehearsal` is the sandbox's dress rehearsal of the same code at
`gpt-tiny` with interpret-mode kernels; every line it prints says
"rehearsal" and its result line carries `"rehearsal": true`.  It proves
the script, not the chip.

The last line of stdout is one JSON object:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
"""
from __future__ import annotations

import argparse
import gc
import http.client
import json
import os
import sys
import threading
import time
import traceback


def _say(msg: str):
    print(f"chip_smoke{_say.tag}: {msg}", flush=True)


_say.tag = ""


class _CompileLog:
    """Per-phase compile accounting from `jax.monitoring`: seconds inside
    the backend compiler (cache reads included) and what the persistent
    cache did."""

    def __init__(self):
        import jax.monitoring as mon
        self.reset()
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def reset(self):
        self.compile_s = 0.0
        self.requests = self.hits = self.writes = 0

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration


def _peak_bytes(devices):
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append(st.get("peak_bytes_in_use"))
    return out


def _free():
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


# -- sizes --------------------------------------------------------------------

def _sizes(rehearsal: bool) -> dict:
    if rehearsal:
        return dict(
            name="gpt-tiny", train_batch=4, train_seq=128, train_steps=5,
            flash_shape=(1, 128, 2, 16),
            serve_slots=4, serve_len=256, serve_new=4,
            short=(36, 33, 48, 60, 64, 41), long=(130, 140, 200, 250),
            lane_layers=2, lane_slots=2, lane_len=128, lane_new=5,
            lane_prompts=(5, 17, 40, 70))
    return dict(
        name="gpt3-1.3B-en", train_batch=8, train_seq=1024, train_steps=5,
        flash_shape=(2, 1024, 16, 128),
        serve_slots=8, serve_len=2048, serve_new=8,
        short=(36, 33, 48, 60, 64, 41),
        long=(130, 140, 160, 180, 200, 210, 220, 230, 240, 250, 255, 256),
        lane_layers=24, lane_slots=4, lane_len=512, lane_new=8,
        lane_prompts=(5, 17, 40, 100, 130, 200, 31, 64))


# -- phase: flash -------------------------------------------------------------

def phase_flash(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.nn.functional.attention import _sdpa_ref

    fa.use_interpret_mode(ctx.rehearsal)
    b, t, h, d = ctx.sz["flash_shape"]
    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.randn(b, t, h, d).astype(np.float32) * 0.5,
                           jnp.bfloat16) for _ in range(3))
    scale = 1.0 / np.sqrt(d)

    def flash(q, k, v):
        return jnp.sum(fa.flash_attention_bthd(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    def dense(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        return jnp.sum(_sdpa_ref(q, k, v, None, 0.0, True, scale, False) ** 2)

    got = jax.jit(jax.value_and_grad(flash, argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(dense, argnums=(0, 1, 2)))(q, k, v)
    (lf, gf), (lr, gr) = jax.device_get((got, want))
    rel = {"loss": abs(float(lf) - float(lr)) / abs(float(lr))}
    for name, a, r in zip("qkv", gf, gr):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        if not np.isfinite(a).all():
            raise AssertionError(f"flash d{name} is not finite")
        rel["d" + name] = float(np.linalg.norm(a - r) / np.linalg.norm(r))
    # bf16 inputs and a bf16 output against an f32 reference: 2^-8 per
    # rounding, a few roundings deep
    bad = {k: v for k, v in rel.items() if not v < 2e-2}
    if bad:
        raise AssertionError(f"flash disagrees with _sdpa_ref: {bad}")
    return {"shape": [b, t, h, d], "rel_err_vs_sdpa_ref":
            {k: round(v, 5) for k, v in rel.items()}}


# -- phases: train (one chip) and mesh (four) ---------------------------------

def _train_step(ctx, mesh=None):
    """`bench_gpt_1p3b`'s recipe: bf16 state on device, scan_layers,
    per-layer recompute; the eager weight copies are dropped once the
    train state owns the live ones."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import (GPTPretrainingCriterion, build_gpt,
                                   gpt_config)

    seq = ctx.sz["train_seq"]
    cfg = gpt_config(ctx.sz["name"], max_position_embeddings=max(seq, 1024),
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                     scan_layers=True, use_recompute=True)
    paddle.seed(0)
    if not ctx.rehearsal:
        paddle.set_default_dtype("bfloat16")
    try:
        model = build_gpt(cfg)
    finally:
        paddle.set_default_dtype("float32")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    kw = {} if mesh is None else dict(mesh=mesh, fsdp_axis="sharding")
    step = dist.make_train_step(
        model, opt, loss_fn=GPTPretrainingCriterion(),
        compute_dtype=None if ctx.rehearsal else "bfloat16", **kw)
    for p in model.parameters():
        p._replace_(jnp.zeros((), p._value.dtype), None)
    gc.collect()
    return cfg, step


def _batch(ctx, cfg):
    import numpy as np
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size,
        size=(ctx.sz["train_batch"], ctx.sz["train_seq"] + 1)).astype(
            np.int64)
    return ids[:, :-1], ids[:, 1:]


def _flash_in_step(ctx, step, x, y, where: str) -> dict:
    """Mosaic custom calls in the LOWERED step — evidence, not an
    assumption, that flash attention (forward + backward) is what the step
    will run.  The cpu backend never routes flash, so a rehearsal skips."""
    if ctx.rehearsal:
        return {}
    n = step.lower(x, y).as_text().count("tpu_custom_call")
    if n < 2:
        raise AssertionError(f"{n} Mosaic custom calls in the lowered train "
                             f"step {where}: flash attention is not in it")
    return {"mosaic_custom_calls": n}


def _run_steps(ctx, step, x, y) -> dict:
    import math
    losses, walls = [], []
    for _ in range(ctx.sz["train_steps"]):
        t0 = time.perf_counter()
        losses.append(float(step(x, y)))     # host fetch ends the step
        walls.append(round(time.perf_counter() - t0, 3))
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    return {"losses": [round(v, 4) for v in losses],
            "first_step_wall_s": walls[0], "step_wall_s": walls[1:]}


def phase_train(ctx) -> dict:
    cfg, step = _train_step(ctx)
    x, y = _batch(ctx, cfg)
    out = {"model": ctx.sz["name"], "tokens_per_step": int(x.size)}
    out.update(_flash_in_step(ctx, step, x, y, "on one chip"))
    out.update(_run_steps(ctx, step, x, y))
    ctx.one_chip_first_loss = out["losses"][0]
    return out


def phase_mesh(ctx) -> dict:
    import jax

    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "sharding_degree": 2,
                               "mp_degree": 2, "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    try:
        mesh = fleet.get_hybrid_communicate_group().get_mesh()
        devs = list(mesh.devices.flat)
        want = "cpu" if ctx.rehearsal else "tpu"
        if len({d.id for d in devs}) != 4 or \
                any(d.platform != want for d in devs):
            raise AssertionError(f"mesh devices are not 4 distinct {want} "
                                 f"devices: {devs}")
        cfg, step = _train_step(ctx, mesh=mesh)
        x, y = _batch(ctx, cfg)
        out = {"mesh": {k: int(v) for k, v in mesh.shape.items()},
               "devices": [f"{d.platform}:{d.id}" for d in devs]}
        out.update(_flash_in_step(ctx, step, x, y, "under the live mesh"))
        # parameters and optimizer slots are actually split: every device
        # holds shards, no device holds more than half of either (a quarter
        # is the ideal; embeddings split over mp only), evenly
        tree = step.state.tree()
        for part in ("params", "slots"):
            total, per = 0, {d.id: 0 for d in devs}
            for leaf in jax.tree_util.tree_leaves(tree[part]):
                total += leaf.nbytes
                for sh in leaf.addressable_shards:
                    per[sh.device.id] += sh.data.nbytes
            share = {k: round(v / total, 3) for k, v in per.items()}
            out[part + "_share_per_device"] = share
            if max(share.values()) > 0.5 or \
                    max(share.values()) > 1.2 * min(share.values()):
                raise AssertionError(
                    f"{part} ({total} bytes) are not split over the four "
                    f"devices: shares {share}")
        out.update(_run_steps(ctx, step, x, y))
        used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
        out["bytes_in_use"] = used
        if None not in used and max(used) > 2 * min(used):
            raise AssertionError(f"state is not spread evenly: {used}")
        one = ctx.one_chip_first_loss
        if one is not None:
            out["one_chip_first_loss"] = one
            # same seed, same batch, bf16 compute: equal to about one bf16
            # ulp (2^-8 relative; measured 5e-5 on the chip, PR 21)
            if abs(out["losses"][0] - one) > 0.005 * abs(one):
                raise AssertionError(
                    f"first loss {out['losses'][0]} on the mesh vs {one} "
                    f"on one chip")
        return out
    finally:
        dist.set_global_mesh(None)
        dist.set_hybrid_communicate_group(None)
        fleet._hcg = None
        fleet._is_initialized = False


# -- phase: serve -------------------------------------------------------------

def _eval_model(ctx, layers=None, dtype="bfloat16"):
    import paddle_tpu as paddle
    from paddle_tpu.models import build_gpt, gpt_config

    over = {} if layers is None else {"num_layers": layers}
    cfg = gpt_config(ctx.sz["name"], hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0, **over)
    paddle.seed(0)
    if not ctx.rehearsal:
        paddle.set_default_dtype(dtype)
    try:
        model = build_gpt(cfg)
    finally:
        paddle.set_default_dtype("float32")
    model.eval()
    return cfg, model


def _post(port, prompt, max_tokens, out, i):
    t0 = time.perf_counter()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                                 "temperature": 0.0}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out[i] = (resp.status, json.loads(resp.read() or b"{}"),
                  time.perf_counter() - t0)
    except Exception as e:  # noqa: BLE001 — reported as this request's failure
        out[i] = (0, {"error": f"{type(e).__name__}: {e}"},
                  time.perf_counter() - t0)
    finally:
        conn.close()


def phase_serve(ctx) -> dict:
    import numpy as np

    from paddle_tpu.serving import Engine
    from paddle_tpu.serving.gateway import start_gateway

    cfg, model = _eval_model(ctx)
    sz = ctx.sz
    engine = Engine(model, max_slots=sz["serve_slots"],
                    max_len=sz["serve_len"])
    stack = start_gateway([engine], own_engines=True)
    rs = np.random.RandomState(3)
    new = sz["serve_new"]

    def wave(lengths):
        prompts = [rs.randint(0, cfg.vocab_size, n).tolist() for n in lengths]
        res = [None] * len(prompts)
        threads = [threading.Thread(target=_post,
                                    args=(stack.port, p, new, res, i))
                   for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for n, (status, body, _) in zip(lengths, res):
            toks = (body.get("choices") or [{}])[0].get("token_ids")
            if status != 200 or toks is None or len(toks) != new or \
                    body["usage"]["prompt_tokens"] != n:
                raise AssertionError(
                    f"prompt of {n} tokens: HTTP {status} {body}")
        return {"requests": len(prompts),
                "wall_s": round(time.perf_counter() - t0, 3),
                "slowest_request_s": round(max(r[2] for r in res), 3)}

    try:
        # prompts padded to a bucket under 128 prefill on the dense
        # reference; at 128 and above flash takes the prompt
        out = {"model": sz["name"], "slots": sz["serve_slots"],
               "max_len": sz["serve_len"], "new_tokens": new,
               "dense_prefill_wave": wave(sz["short"]),
               "flash_prefill_wave": wave(sz["long"])}
        out["compile_stats"] = cs = engine.compile_stats()
        st = engine.stats()
        out["slot_reuses"] = int(st["slot_reuses"])
        out["completed"] = int(st["completed"])
        n_req = len(sz["short"]) + len(sz["long"])
        if cs["decode_compiles"] != 1 or cs["prefill_compiles"] < 2 or \
                out["slot_reuses"] < 1 or out["completed"] != n_req or \
                n_req <= sz["serve_slots"]:
            raise AssertionError(f"serving invariants: {out}")
        return out
    finally:
        stack.close()


# -- phase: kernel lane -------------------------------------------------------

def phase_kernel(ctx) -> dict:
    import jax
    import numpy as np

    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.serving import Engine

    sz = ctx.sz
    if pa._interpret_now() != ctx.rehearsal:
        raise AssertionError(
            f"paged kernel interpret mode is {pa._interpret_now()} on "
            f"{jax.default_backend()}")
    # The two reads are equal as real-number programs; they are equal token
    # for token where the arithmetic is: f32 weights with full-precision
    # matmuls.  (Under the default precision XLA rounds the f32 attention
    # operands to bf16 and Mosaic does not, and the two drift apart.)
    cfg, model = _eval_model(ctx, layers=sz["lane_layers"], dtype="float32")
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, cfg.vocab_size, n).astype(np.int64)
               for n in sz["lane_prompts"]]
    out = {"model": sz["name"], "layers": sz["lane_layers"],
           "weights": "float32", "matmul_precision": "highest",
           "prompts": list(sz["lane_prompts"]), "new_tokens": sz["lane_new"]}
    # process-wide, not the thread-local context manager: the engine's
    # programs trace on its scheduler thread
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        for pool in (None, "int8"):
            toks = {}
            for kernel in ("xla", "pallas"):
                eng = Engine(model, max_slots=sz["lane_slots"],
                             max_len=sz["lane_len"],
                             max_queue=2 * len(prompts), paged_kv=True,
                             kv_dtype=pool, decode_kernel=kernel)
                try:
                    hs = [eng.submit(p, max_new_tokens=sz["lane_new"])
                          for p in prompts]
                    toks[kernel] = [np.asarray(h.result(timeout=900))
                                    for h in hs]
                    cs = eng.compile_stats()
                finally:
                    eng.shutdown()
                if cs["decode_compiles"] != 1:
                    raise AssertionError(f"{kernel}/{pool}: {cs}")
            diff = [i for i, (a, b) in enumerate(zip(toks["xla"],
                                                     toks["pallas"]))
                    if not np.array_equal(a, b)]
            if diff:
                raise AssertionError(
                    f"pool {pool or 'float'}: pallas != xla for prompts "
                    f"{diff}: {[(toks['xla'][i], toks['pallas'][i]) for i in diff]}")
            out[f"{pool or 'float'}_pool"] = (
                f"{len(prompts)} prompts x {sz['lane_new']} tokens equal")
    finally:
        jax.config.update("jax_default_matmul_precision", before)
    return out


# -- driver -------------------------------------------------------------------

class _Ctx:
    def __init__(self, rehearsal, devices):
        self.rehearsal = rehearsal
        self.devices = devices
        self.sz = _sizes(rehearsal)
        self.one_chip_first_loss = None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the same phases at gpt-tiny on the CPU with "
                         "interpret-mode kernels; proves the script, not "
                         "the chip")
    args = ap.parse_args(argv)
    rehearsal = args.cpu_rehearsal
    if rehearsal:
        _say.tag = " [CPU rehearsal]"
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count=4").strip()

    t_start = time.perf_counter()
    try:
        from paddle_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        _say(f"FAIL phase=import: {e} — run from the root of a checkout")
        return 1
    try:
        import jax
        cache_dir = enable_compile_cache()
        devices = jax.devices()
        dev = {"platform": devices[0].platform,
               "kind": devices[0].device_kind, "count": len(devices)}
    except Exception as e:  # noqa: BLE001 — no backend is this phase failing
        traceback.print_exc()
        _say(f"FAIL phase=device: {type(e).__name__}: {e}")
        return 1
    _say(f"device platform={dev['platform']} kind={dev['kind']!r} "
         f"count={dev['count']} compile_cache={cache_dir}")
    if dev["platform"] != ("cpu" if rehearsal else "tpu"):
        _say(f"FAIL phase=device: no TPU — JAX reports platform "
             f"{dev['platform']!r} ({dev['kind']}); this script measures "
             f"nothing on it and does not fall back. On the chip: "
             f"`python3 chip_smoke.py`; in a sandbox, `--cpu-rehearsal` "
             f"rehearses the script itself.")
        return 1

    ctx = _Ctx(rehearsal, devices)
    log = _CompileLog()
    phases = [("flash", phase_flash), ("train", phase_train)]
    if len(devices) >= 4:
        phases.append(("mesh", phase_mesh))
    else:
        _say(f"phase=mesh NOT RUN: needs >= 4 devices, found {len(devices)}")
    phases += [("serve", phase_serve), ("kernel", phase_kernel)]

    failed = []
    for name, fn in phases:
        log.reset()
        t0 = time.perf_counter()
        try:
            report = fn(ctx)
        except Exception as e:  # noqa: BLE001 — a phase failing is the result
            traceback.print_exc()
            _say(f"FAIL phase={name} after "
                 f"{time.perf_counter() - t0:.1f}s: {type(e).__name__}: "
                 f"{str(e)[:2000]}")
            failed.append(name)
            report = None
        _free()
        if report is not None:
            report.update(
                wall_s=round(time.perf_counter() - t0, 1),
                compile_s=round(log.compile_s, 1),
                cache={"requests": log.requests, "hits": log.hits,
                       "writes": log.writes},
                peak_bytes_in_use=_peak_bytes(devices))
            _say(f"PASS phase={name} {json.dumps(report)}")

    _say(f"total wall {time.perf_counter() - t_start:.1f}s")
    result = {"ok": not failed, "device": dev}
    if rehearsal:
        result["rehearsal"] = True
    if failed:
        result["failed"] = failed
        _say(f"FAIL phases={','.join(failed)}")
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
