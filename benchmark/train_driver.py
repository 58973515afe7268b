"""Train cells: `build_gpt` -> `AdamW` -> `dist.make_train_step`, as the
configuration file's recipe says, on one chip or on the `fleet.init` mesh.

The measured loop keeps one step queued behind the running one (the host
dispatches step k+1, then fetches the loss of step k), as a training loop
that logs its loss does; the window ends with the last loss fetched, so
tokens per second are tokens of completed steps over all of the window.
"""
from __future__ import annotations

import gc
import math
import time

from benchmark import flops, reference
from benchmark.checks import check, held


def _build(ctx):
    """The model as the configuration file sizes it, on the file's mesh."""
    import paddle_tpu as paddle
    from paddle_tpu.models import build_gpt, gpt_config

    cfg, rec = ctx.config, ctx.config["recipe"]
    mesh, groups = None, 1
    if cfg.get("mesh"):
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = dict(cfg["mesh"])
        fleet.init(is_collective=True, strategy=strategy)
        mesh = fleet.get_hybrid_communicate_group().get_mesh()
        groups = (cfg["mesh"].get("dp_degree", 1) *
                  cfg["mesh"].get("sharding_degree", 1))
    sizes = {k: cfg[k] for k in flops.GPT_SIZE_KEYS}
    gcfg = gpt_config(cfg["model"], hidden_dropout_prob=0.0,
                      attention_dropout_prob=0.0,
                      scan_layers=rec["scan_layers"],
                      use_recompute=rec["use_recompute"], **sizes)
    paddle.seed(ctx.seed)
    paddle.set_default_dtype(rec["param_dtype"])
    try:
        model = build_gpt(gcfg)
    finally:
        paddle.set_default_dtype("float32")
    return gcfg, model, mesh, cfg["batch_per_data_group"] * groups


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import GPTPretrainingCriterion

    cfg, rec, mix = ctx.config, ctx.config["recipe"], ctx.mix
    gcfg, model, mesh, batch = _build(ctx)
    seq = cfg["seq_len"]
    tokens_per_step = batch * seq

    def make_batch(i: int):
        # a fresh seeded batch each step, made on the host: the repo's `io`
        # pipeline is bypassed
        rs = np.random.RandomState((ctx.seed + 7919 * (i + 1)) % 2 ** 32)
        ids = rs.randint(0, gcfg.vocab_size, (batch, seq + 1)).astype(np.int64)
        return ids[:, :-1], ids[:, 1:]

    # the reference runs first, on the model's own arrays, before the train
    # state takes its memory
    x0, y0 = make_batch(0)
    t_ref = time.monotonic()
    ref_loss = reference.loss(model.state_dict(), x0, y0, gcfg.num_layers,
                              gcfg.num_attention_heads,
                              gcfg.layer_norm_epsilon)
    ref_s = time.monotonic() - t_ref

    opt = paddle.optimizer.AdamW(learning_rate=rec["learning_rate"],
                                 parameters=model.parameters(),
                                 weight_decay=rec["weight_decay"])
    kw = {} if mesh is None else dict(mesh=mesh, fsdp_axis=rec["fsdp_axis"])
    step = dist.make_train_step(model, opt, loss_fn=GPTPretrainingCriterion(),
                                compute_dtype=rec["compute_dtype"], **kw)
    # the train state owns copies now; drop the eager weights
    for p in model.parameters():
        p._replace_(jnp.zeros((), p._value.dtype), None)
    gc.collect()

    losses = []
    for i in range(mix["warmup_steps"]):
        t0 = time.monotonic()
        losses.append(float(step(*make_batch(i))))
        ctx.say(f"warm-up step {i}: loss {losses[-1]:.4f} in "
                f"{time.monotonic() - t0:.2f}s")
    k = mix["warmup_steps"]
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    ctx.say(f"first loss {losses[0]:.5f} vs reference {ref_loss:.5f} (rel "
            f"{rel:.2e}, tolerance {cfg['loss_rel_tolerance']}, reference "
            f"took {ref_s:.1f}s)")

    ann = jax.profiler.TraceAnnotation

    def loop(stop) -> tuple[list, list]:
        """Steps until `stop(n_dispatched, elapsed)`; one step stays queued
        behind the running one.  Returns losses and fetch instants."""
        nonlocal k
        out, stamps, pending, n = [], [], None, 0
        t0 = time.monotonic()
        while True:
            with ann("bench.batch"):
                x, y = make_batch(k)
            with ann("bench.step_call"):
                nxt = step(x, y)
            k, n = k + 1, n + 1
            if pending is not None:
                with ann("bench.fetch"):
                    out.append(float(pending))
                stamps.append(time.monotonic())
            pending = nxt
            if stop(n, time.monotonic() - t0):
                break
        with ann("bench.fetch"):
            out.append(float(pending))
        stamps.append(time.monotonic())
        return out, [t0] + stamps

    setup_req, setup_hits, setup_cs = (ctx.log.requests, ctx.log.hits,
                                       ctx.log.compile_s)
    setup_s = time.monotonic() - ctx.t_start
    win_losses, stamps = loop(lambda n, dt: dt >= ctx.seconds)
    window_s = stamps[-1] - stamps[0]
    steps = len(win_losses)
    compiles = ctx.log.requests - setup_req
    losses += win_losses
    if ctx.trace:
        ctx.start_trace()
        with ann("bench.window"):
            tl, _ = loop(lambda n, dt: n >= mix["trace_steps"])
        ctx.stop_trace()
        losses += tl

    checks = {
        "loss_rel_err": check(rel, cfg["loss_rel_tolerance"]),
        "nonfinite_losses": check(
            sum(not math.isfinite(v) for v in losses), 0),
        "compiles_in_window": check(compiles, 0)}
    rate = steps * tokens_per_step / window_s / ctx.cell["chips"]
    gaps = np.diff(stamps[1:]) if steps > 2 else np.array([window_s / steps])
    return {
        "correct": held(checks), "checks": checks, "attempted": steps,
        "failed": sum(not math.isfinite(v) for v in win_losses),
        "setup_s": setup_s, "setup_compile_s": setup_cs,
        "setup_hits": setup_hits, "setup_requests": setup_req,
        "end_to_end": {"train_tokens_per_s": rate},
        "observations": {
            "step_ms": float(np.median(gaps) * 1e3),
            "tokens_per_s_per_chip": rate,
            "train_flops_per_token": flops.gpt_train_flops_per_token(
                cfg, seq),
            "flash_shape": {
                "bh": (cfg["batch_per_data_group"] *
                       cfg["num_attention_heads"] //
                       (cfg.get("mesh") or {}).get("mp_degree", 1)),
                "t": seq,
                "d": cfg["hidden_size"] // cfg["num_attention_heads"]},
        },
        "notes": {"steps": steps, "window_s": window_s,
                  "first_loss": losses[0], "reference_loss": ref_loss,
                  "loss_rel_err": rel, "last_loss": losses[-1],
                  "compiles_in_window": compiles, "reference_s": ref_s},
    }
