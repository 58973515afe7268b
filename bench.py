"""Benchmark: BASELINE.md configs on one TPU chip.

Prints ONE JSON line with the flagship GPT metric at the top level plus a
"legs" object carrying EVERY leg's result — GPT-2-small, PP-YOLOE,
GPT-3-1.3B (north-star scale: on-device bf16 state + scan_layers +
remat), ResNet-50, BERT-base (batch 64 + bf16 state), and a GPT KV-cache
decode serving leg.  Every leg reports a `noise_pct` band from repeat
windows, and the persistent XLA compile cache
(paddle_tpu/utils/compile_cache.py) keeps repeat runs inside the time
budget.  The exit code is non-zero when the backend is missing or any leg
raised.

Nothing here has run on the stock `tpu` backend yet (PR 21 brought the
main path up through chip_smoke.py, not this file).  Known debt for
ROADMAP S1, deliberately not fixed here: with no chip several legs swap
in `gpt-tiny` on the CPU and still print under device metric names
(`*_tokens_per_sec_per_chip`); those values are CPU walls, not
measurements of the device.

`python bench.py --flagship-only` restores the old single-leg behavior.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

# CPU smoke runs get 2 simulated host devices so the cross-dp elastic
# resume gate can build a real dp=2 mesh (must land before the backend
# initializes; hardware runs don't set JAX_PLATFORMS=cpu and are
# untouched)
if os.environ.get("JAX_PLATFORMS", "").lower().startswith("cpu") and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=2").strip()

# bf16 peak FLOPs/s per chip by TPU generation (public spec sheets)
_PEAK = {"v5 lite": 197e12, "v5e": 197e12, "v4": 275e12, "v5p": 459e12,
         "v6": 918e12}


def _peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for key, val in _PEAK.items():
        if key in kind:
            return val
    raise ValueError(
        f"no peak FLOP/s entry for device_kind {kind!r} (known: "
        f"{sorted(_PEAK)}); an MFU against another chip's peak would be "
        f"a wrong number")


def _reset_parallel_state():
    import paddle_tpu.distributed as dist
    dist.set_global_mesh(None)




def _timed_rate(step_once, units_per_step, steps, reps=3):
    """Headline rate from ONE long window of `steps` steps, plus a noise
    band (max-min)/median measured over `reps` short windows of
    steps//reps steps each.  Every window ends in a host fetch of the
    loss, so it times finished device work; the band comes from
    equal-sized windows and only the long window sets the reported
    value."""
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step_once()
    float(loss)
    value = units_per_step * steps / (time.perf_counter() - t0)
    sub = max(1, steps // reps)
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(sub):
            loss = step_once()
        float(loss)
        rates.append(units_per_step * sub / (time.perf_counter() - t0))
    med = float(np.median(rates))
    noise = (max(rates) - min(rates)) / med if med else 0.0
    return value, round(100 * noise, 2), loss


def _loss_series(losses):
    """One host sync for a list of step losses (scalars or [K] stacks)."""
    out = []
    for l in losses:
        a = np.asarray(l.numpy() if hasattr(l, "numpy") else l)
        out.extend(np.ravel(a).astype(np.float64).tolist())
    return out


def _input_overlap_block(step, batches, stacked=False, parity_make=None):
    """Input-overlap probe (ISSUE 4): drive a train step over host-side
    numpy batches twice — synchronous inline transfers vs DevicePrefetcher
    — and report each path's host-wait fraction (time the loop spent
    obtaining a device-ready batch / loop wall time).  On an accelerator
    the prefetched path must wait less (the transfer overlaps compute);
    on CPU timings are noise, so the fallback assertion is bit-identical
    loss parity between the two paths on fresh models (`parity_make`)."""
    import jax

    from paddle_tpu.io.prefetch import DevicePrefetcher

    on_tpu = jax.devices()[0].platform != "cpu"
    call = (lambda s, xs: s.run_steps(*xs)) if stacked \
        else (lambda s, xs: s(*xs))

    def run(s, prefetch):
        # warmup outside the timed window (compile / allocator settle);
        # both paths run it, so parity series stay aligned
        warm = tuple(jax.device_put(np.asarray(a)) for a in batches[0])
        _loss_series([call(s, warm)])
        wait, losses, stalls = 0.0, [], 0
        t_loop = time.perf_counter()
        if prefetch:
            pf = DevicePrefetcher(batches, depth=2, mesh=s.mesh,
                                  stacked=stacked, name="bench")
            for xs in pf:
                losses.append(call(s, xs))
            wait = pf.stats()["wait_seconds"]
            stalls = pf.stats()["stalls"]
        else:
            for b in batches:
                t0 = time.perf_counter()
                xs = tuple(jax.device_put(np.asarray(a)) for a in b)
                wait += time.perf_counter() - t0
                losses.append(call(s, xs))
        series = _loss_series(losses)  # the sync point closing the window
        wall = time.perf_counter() - t_loop
        return (wait / wall if wall > 0 else 0.0), series, stalls

    sync_frac, _, _ = run(step, prefetch=False)
    pf_frac, _, stalls = run(step, prefetch=True)
    block = {"steps": len(batches),
             "host_wait_frac_sync": round(sync_frac, 4),
             "host_wait_frac_prefetch": round(pf_frac, 4),
             "prefetch_stalls": int(stalls)}
    if on_tpu and pf_frac >= sync_frac:
        raise RuntimeError(
            f"input overlap regressed: prefetch host-wait frac {pf_frac:.4f}"
            f" >= sync {sync_frac:.4f}")
    if parity_make is not None and not on_tpu:
        _, s_sync, _ = run(parity_make(), prefetch=False)
        _, s_pf, _ = run(parity_make(), prefetch=True)
        if s_sync != s_pf:
            raise RuntimeError(
                f"prefetch loss parity broke: {s_sync} vs {s_pf}")
        block["loss_parity"] = True
    return block


def _checkpoint_block(step, batch, on_tpu, make_step=None):
    """Checkpoint-overhead probe (ISSUE 5): host snapshot, async sharded
    write (CRC + COMMITTED marker), validated restore — the costs the
    preemption-safe training path adds per checkpoint — plus the CPU
    resume-parity gate: load_state_dict must reproduce the next steps'
    losses bit-identically without adding a jit signature.

    Elastic additions (ISSUE 6): `restore_reshard_ms` times the
    load-with-relayout path (read + CRC on stored bytes + per-leaf
    placement onto a target mesh), and — CPU with >=2 devices and a
    `make_step(mesh=...)` factory — a cross-dp resume-parity gate: the
    same checkpoint restored onto a dp=2 mesh must reproduce the next
    steps' losses to tolerance with ZERO new jit signatures."""
    import tempfile

    import numpy as _np

    from paddle_tpu.framework.checkpoint import AsyncCheckpointSaver

    block = {}
    with tempfile.TemporaryDirectory() as d:
        saver = AsyncCheckpointSaver(d, keep_last=2)
        t0 = time.perf_counter()
        state = step.state_dict()
        block["snapshot_ms"] = round(1e3 * (time.perf_counter() - t0), 2)
        t0 = time.perf_counter()
        saver.save(state, step=int(step.optimizer._step_count))
        saver.wait()
        block["async_write_ms"] = round(1e3 * (time.perf_counter() - t0), 2)
        t0 = time.perf_counter()
        _, restored = saver.restore_latest_valid()
        block["restore_ms"] = round(1e3 * (time.perf_counter() - t0), 2)
        # elastic restore timing: relayout every leaf onto a mesh (the
        # step's own, or a 1-device mesh when the step runs mesh-free)
        import jax as _jax

        import paddle_tpu.distributed as _dist
        resh_mesh = step.mesh if step.mesh is not None else \
            _dist.build_mesh([1], ["dp"], devices=_jax.devices()[:1])
        t0 = time.perf_counter()
        saver.restore(target_mesh=resh_mesh,
                      target_specs=step.elastic_specs())
        block["restore_reshard_ms"] = round(
            1e3 * (time.perf_counter() - t0), 2)
        parity = None
        tail_b = None
        if not on_tpu:
            sigs_before = len(step._jitted._signatures)
            tail_a = _loss_series([step(*batch) for _ in range(2)])
            step.load_state_dict(restored)
            tail_b = _loss_series([step(*batch) for _ in range(2)])
            parity = (tail_a == tail_b and
                      len(step._jitted._signatures) == sigs_before)
            if not parity:
                raise RuntimeError(
                    f"checkpoint resume parity broke: {tail_a} vs {tail_b} "
                    f"(signatures {sigs_before} -> "
                    f"{len(step._jitted._signatures)})")
        block["resume_parity"] = parity
        # cross-dp elastic resume gate: restore the SAME checkpoint onto
        # a dp=2 mesh and require the loss tail to match (cross-dp
        # reduction order differs by ~1 ulp on CPU, hence tolerance — the
        # relayout itself is byte-lossless, asserted in tests)
        cross = None
        cpu_devs = len([dev for dev in _jax.devices()
                        if dev.platform == "cpu"])
        if not on_tpu and make_step is not None and cpu_devs >= 2:
            mesh2 = _dist.build_mesh([2], ["dp"])
            step2 = make_step(mesh=mesh2)
            _loss_series([step2(*batch)])  # compile BEFORE the restore
            sigs = len(step2._jitted._signatures)
            step2.load_state_dict(restored)
            tail_c = _loss_series([step2(*batch) for _ in range(2)])
            cross = (len(step2._jitted._signatures) == sigs and
                     bool(_np.allclose(tail_c, tail_b,
                                       rtol=1e-4, atol=1e-6)))
            if not cross:
                raise RuntimeError(
                    f"cross-dp elastic resume parity broke: {tail_b} vs "
                    f"{tail_c} (signatures {sigs} -> "
                    f"{len(step2._jitted._signatures)})")
        block["cross_dp_resume_parity"] = cross
    return block


def bench_gpt_small():
    """Flagship: GPT-2-small pretraining step (125M; comparable to the
    round-1..3 flagship numbers)."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import (GPTPretrainingCriterion, build_gpt,
                                   gpt_config, gpt_train_flops_per_token)

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"

    if on_tpu:
        name, batch, seq, steps = "gpt2-small-en", 16, 1024, 20
    else:  # CI/CPU smoke: tiny shapes, same code path
        name, batch, seq, steps = "gpt-tiny", 2, 128, 3

    cfg = gpt_config(name, max_position_embeddings=max(seq, 1024),
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)

    def make_step(mesh=None):
        paddle.seed(0)
        m = build_gpt(cfg)
        o = paddle.optimizer.AdamW(learning_rate=1e-4,
                                   parameters=m.parameters(),
                                   weight_decay=0.01)
        return dist.make_train_step(
            m, o, loss_fn=GPTPretrainingCriterion(), mesh=mesh,
            compute_dtype="bfloat16" if on_tpu else None)

    step = make_step()
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(batch, seq + 1)).astype(np.int64)
    x, y = ids[:, :-1], ids[:, 1:]

    loss = step(x, y)  # compile + warmup
    float(loss)
    tokens_per_sec, noise, loss = _timed_rate(
        lambda: step(x, y), batch * seq, steps)
    flops_tok = gpt_train_flops_per_token(cfg, seq)
    mfu = tokens_per_sec * flops_tok / _peak_flops(dev) if on_tpu else 0.0
    print(f"# device={dev.device_kind} loss={float(loss):.4f} "
          f"mfu={mfu:.3f} steps={steps} noise={noise}%", file=sys.stderr)
    overlap = _input_overlap_block(
        step, [(x, y)] * (8 if on_tpu else 3),
        parity_make=None if on_tpu else make_step)
    ckpt = _checkpoint_block(step, (x, y), on_tpu,
                             make_step=None if on_tpu else make_step)
    return {
        "metric": f"gpt_{name}_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "noise_pct": noise,
        "input_overlap": overlap,
        "checkpoint": ckpt,
        "vs_baseline": round(mfu / 0.35, 4) if on_tpu else 0.0,
    }


def bench_gpt_1p3b():
    """North-star-scale leg (round-3 verdict #1): GPT-3 1.3B — the
    BASELINE.md gate model (>=0.35 MFU, FleetX recipe) — on ONE chip.
    Measured recipe (round 4): bf16 params + slots on device, scan_layers +
    per-layer remat, eager weight copies freed after the train state is
    built (the state owns the live weights; sync_to_model is never called
    here).  Host-offloaded slots were measured 8.8x slower (0.057 MFU, the
    PCIe staging dominates) and batch 16 regresses to 0.450 — batch 8 +
    remat gives 0.506 MFU, 1.45x the 0.35 gate.  MFU is per-step, so
    single-chip throughput is the honest scale measurement the 125M proxy
    could not provide."""
    import gc

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import (GPTPretrainingCriterion, build_gpt,
                                   gpt_config, gpt_train_flops_per_token)

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    if on_tpu:
        name, batch, seq, steps = "gpt3-1.3B-en", 8, 1024, 5
    else:
        name, batch, seq, steps = "gpt-tiny", 2, 128, 2

    cfg = gpt_config(name, max_position_embeddings=max(seq, 1024),
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                     scan_layers=True, use_recompute=True)

    def make_step():
        paddle.seed(0)
        if on_tpu:
            paddle.set_default_dtype("bfloat16")
        try:
            m = build_gpt(cfg)
        finally:
            paddle.set_default_dtype("float32")
        o = paddle.optimizer.AdamW(learning_rate=1e-4,
                                   parameters=m.parameters(),
                                   weight_decay=0.01)
        return m, dist.make_train_step(
            m, o, loss_fn=GPTPretrainingCriterion(),
            compute_dtype="bfloat16" if on_tpu else None)

    model, step = make_step()
    if on_tpu:
        # free the eager weight copies: 2.6 GiB of headroom the 1.3B
        # single-chip budget needs (params 2.6 + slots 5.2 + grads 2.6)
        for p in model.parameters():
            p._replace_(jnp.zeros((), p._value.dtype), None)
        gc.collect()
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(batch, seq + 1)).astype(np.int64)
    x, y = ids[:, :-1], ids[:, 1:]
    loss = step(x, y)
    float(loss)
    tps, noise, loss = _timed_rate(lambda: step(x, y), batch * seq, steps)
    flops_tok = gpt_train_flops_per_token(cfg, seq)
    mfu = tps * flops_tok / _peak_flops(dev) if on_tpu else 0.0
    print(f"# gpt-1.3B device={dev.device_kind} loss={float(loss):.4f} "
          f"mfu={mfu:.3f} noise={noise}%", file=sys.stderr)
    # overlap probe reuses the live step (no second 1.3B model on TPU);
    # parity on the CPU fallback only, where the model is gpt-tiny
    overlap = _input_overlap_block(
        step, [(x, y)] * (4 if on_tpu else 3),
        parity_make=None if on_tpu else (lambda: make_step()[1]))
    return {
        "noise_pct": noise,
        "metric": f"gpt_{name}_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "input_overlap": overlap,
        "vs_baseline": round(mfu / 0.35, 4) if on_tpu else 0.0,
    }


def bench_resnet50():
    """ResNet-50 ImageNet-shape training step, images/s/chip (BASELINE.md
    row 1; reference model zoo resnet50)."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import resnet50

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    # batch 128 amortizes the fixed per-op costs best on one v5e chip
    # (measured: 64 -> 0.130 MFU, 128 -> 0.146, 256 -> 0.143)
    batch, steps = (128, 10) if on_tpu else (2, 2)
    size = 224 if on_tpu else 32

    def make_step():
        paddle.seed(0)
        # stem_s2d: space-to-depth stem, +1.4% end-to-end measured (2541 ->
        # 2577 img/s; exact-equivalent math, docs/PERF.md round-4 A/B)
        m = resnet50(num_classes=1000, stem_s2d=on_tpu)
        o = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                      parameters=m.parameters(),
                                      weight_decay=1e-4)
        return dist.make_train_step(
            m, o, loss_fn=nn.CrossEntropyLoss(),
            compute_dtype="bfloat16" if on_tpu else None)

    step = make_step()
    rng = np.random.RandomState(0)
    # device-resident batch: a real input pipeline overlaps H2D with
    # compute, so the un-overlapped 38 MB image batch stays out of the
    # window.  The K-step stack is materialized ON DEVICE (broadcast of
    # one batch) and stepped through run_steps — one dispatch for all K
    # steps, the same amortization the reference gets from its C++ trainer
    # run loop (trainer.cc).
    import jax.numpy as jnp
    x1 = jnp.asarray(
        rng.standard_normal((batch, 3, size, size)).astype(np.float32))
    y1 = jnp.asarray(rng.randint(0, 1000, (batch,)).astype(np.int64))
    rep = jax.jit(lambda a, k: jnp.broadcast_to(a[None], (k,) + a.shape) + 0,
                  static_argnums=1)
    x, y = rep(x1, steps), rep(y1, steps)
    jax.block_until_ready(x)
    loss = step.run_steps(x, y)  # compile + warmup
    np.asarray(loss.numpy() if hasattr(loss, "numpy") else loss)
    # value: 3 back-to-back run_steps stacks, ONE sync
    t0 = time.perf_counter()
    for _ in range(3):
        loss = step.run_steps(x, y)
    losses = np.asarray(loss.numpy() if hasattr(loss, "numpy") else loss)
    ips = batch * steps * 3 / (time.perf_counter() - t0)
    # noise band: equal-sized singly-synced stacks (sync bias cancels)
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        loss = step.run_steps(x, y)
        losses = np.asarray(loss.numpy() if hasattr(loss, "numpy")
                            else loss)
        rates.append(batch * steps / (time.perf_counter() - t0))
    loss = float(losses[-1])
    noise = round(100 * (max(rates) - min(rates)) / float(np.median(rates)),
                  2)
    # ~3.8 GFLOP/image fwd at 224², x3 for fwd+bwd
    mfu = ips * 3 * 3.8e9 / _peak_flops(dev) if on_tpu else 0.0
    print(f"# resnet50 device={dev.device_kind} loss={float(loss):.4f} "
          f"mfu={mfu:.3f} batch={batch} noise={noise}%", file=sys.stderr)
    # overlap probe: host-side [K,B,...] stacks (38 MB/batch images are
    # exactly the payload the prefetcher exists for) through the SAME
    # compiled run_steps signature — sync inline puts vs prefetched
    x_np = rng.standard_normal((batch, 3, size, size)).astype(np.float32)
    y_np = rng.randint(0, 1000, (batch,)).astype(np.int64)
    stack = (np.broadcast_to(x_np[None], (steps,) + x_np.shape),
             np.broadcast_to(y_np[None], (steps,) + y_np.shape))
    overlap = _input_overlap_block(
        step, [stack] * (3 if on_tpu else 2), stacked=True,
        parity_make=None if on_tpu else make_step)
    return {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(ips, 1),
        "noise_pct": noise,
        "unit": "images/s/chip",
        "input_overlap": overlap,
        "vs_baseline": round(mfu / 0.35, 4) if on_tpu else 0.0,
    }


def bench_ppyoloe():
    """PP-YOLOE-s-class detector train step at 640x640 (BASELINE.md row 6;
    conv-heavy detection workload on top of the same conv/BN path as
    ResNet).  No reference number exists in-tree, so vs_baseline reports
    MFU/0.35 like the other rows (FLOPs ~17.4 GFLOP/image fwd at 6402 for
    the s scale)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.vision.models import PPYOLOE, PPYOLOELoss

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    batch, size, steps = (8, 640, 10) if on_tpu else (2, 64, 2)

    paddle.seed(0)
    model = PPYOLOE(num_classes=80)
    loss_fn = PPYOLOELoss(model)
    opt = paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                    parameters=model.parameters(),
                                    weight_decay=5e-4)
    step = dist.make_train_step(
        model, opt, loss_fn=loss_fn, num_labels=2,
        compute_dtype="bfloat16" if on_tpu else None)
    rng = np.random.RandomState(0)
    x = jnp.asarray(
        rng.standard_normal((batch, 3, size, size)).astype(np.float32))
    gtb = jnp.asarray(np.stack([np.array([[4, 4, 300, 300], [64, 32, 400,
                                          500]], "float32")] * batch))
    gtl = jnp.asarray(np.stack([np.array([1, 3], "int64")] * batch))
    loss = step(x, gtb, gtl)
    float(loss)
    ips, noise, loss = _timed_rate(lambda: step(x, gtb, gtl), batch, steps)
    mfu = ips * 3 * 17.4e9 / _peak_flops(dev) if on_tpu else 0.0
    print(f"# ppyoloe device={dev.device_kind} loss={float(loss):.4f} "
          f"mfu={mfu:.3f} noise={noise}%", file=sys.stderr)
    return {
        "metric": "ppyoloe_s_images_per_sec_per_chip",
        "value": round(ips, 1),
        "noise_pct": noise,
        "unit": "images/s/chip",
        "vs_baseline": round(mfu / 0.35, 4) if on_tpu else 0.0,
    }


def bench_bert():
    """BERT-base MLM-shape step, tokens/s/chip (BASELINE.md row 2; the DP
    scaling leg runs on the CPU-sim mesh in tests/test_bert.py)."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import (BertPretrainingCriterion, bert_config,
                                   build_bert)

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    # batch 64 measured +17% tokens/s over the round-4 batch 16 (0.355 ->
    # 0.415 MFU: BERT at 16x512 has half GPT's tokens/step, so the
    # param-proportional costs — AdamW f32 state traffic, vocab-head
    # wgrad — weighed double; docs/PERF.md round-5 BERT section)
    batch, seq, steps = (64, 512, 9) if on_tpu else (2, 64, 2)
    name = "bert-base-uncased" if on_tpu else "bert-tiny"

    paddle.seed(0)
    cfg = bert_config(name, hidden_dropout_prob=0.0,
                      attention_dropout_prob=0.0)
    model = build_bert(cfg)
    if on_tpu:
        # bf16 params + AdamW state — the same measured recipe the 1.3B
        # leg ships (docs/PERF.md): +5% over f32 masters at batch 64
        # (0.415 -> 0.435 MFU), loss parity to 3e-4 at step 10
        model.to(dtype="bfloat16")
    crit = BertPretrainingCriterion()

    def loss_fn(out, labels, nsp_labels):
        mlm, nsp = out
        return crit(mlm, nsp, labels, nsp_labels)

    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = dist.make_train_step(
        model, opt, loss_fn=loss_fn, num_labels=2)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    labels = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    nsp = rng.randint(0, 2, (batch,)).astype(np.int64)
    loss = step(ids, labels, nsp)
    float(loss)
    tps, noise, loss = _timed_rate(
        lambda: step(ids, labels, nsp), batch * seq, steps)
    # 6 * params flops/token (110M)
    mfu = tps * 6 * 110e6 / _peak_flops(dev) if on_tpu else 0.0
    print(f"# bert device={dev.device_kind} loss={float(loss):.4f} "
          f"mfu={mfu:.3f} noise={noise}%", file=sys.stderr)
    return {
        "metric": "bert_base_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "noise_pct": noise,
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.35, 4) if on_tpu else 0.0,
    }


def bench_gpt_decode():
    """Serving leg (round-5 verdict ask #5): GPT-2-small KV-cache decode
    through HybridParallelInferenceHelper — prefill once, then
    autoregressive per-token steps with donated cache buffers (the
    AnalysisPredictor zero-copy analog, analysis_predictor.cc:1618).
    Reports decode tokens/s and ms/token; vs_baseline is decode HBM
    utilization: roofline ms/token (params read once per token at spec
    bandwidth) over measured ms/token."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.utils import (
        HybridParallelInferenceHelper)
    from paddle_tpu.models import build_gpt, gpt_config

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    if on_tpu:
        name, batch, prompt, new = "gpt2-small-en", 8, 512, 64
    else:
        name, batch, prompt, new = "gpt-tiny", 2, 16, 4

    cfg = gpt_config(name, max_position_embeddings=max(prompt + new, 128),
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(0)
    if on_tpu:
        paddle.set_default_dtype("bfloat16")
    try:
        model = build_gpt(cfg)
    finally:
        paddle.set_default_dtype("float32")
    model.eval()
    helper = HybridParallelInferenceHelper(model,
                                           max_length=prompt + new)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, prompt)).astype(np.int64)
    # decode-only differential: generations at n=new and n=1 share the
    # (compute-bound) prefill cost, so their time difference isolates the
    # per-token decode loop.  Warm each shape twice (compile + allocator
    # settle), then 3 timed reps each.
    def timed(n, reps=3):
        helper.generate(ids, max_new_tokens=n)
        helper.generate(ids, max_new_tokens=n)
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = helper.generate(ids, max_new_tokens=n)
            ts.append(time.perf_counter() - t0)
        assert out.shape == (batch, prompt + n)
        return ts

    t_full = timed(new)
    t_one = timed(1)
    dts = [a - b for a, b in zip(sorted(t_full), sorted(t_one))]
    dt = float(np.median(dts))
    # prefill noise can swamp the decode delta on fast/tiny runs and push
    # the median to <= 0; clamp so the reported JSON can't carry a
    # divide-by-zero or negative tokens/s
    eps = 1e-9
    if dt < eps:
        print(f"# gpt-decode: decode delta {dt:.3e}s <= 0 (prefill noise "
              f"dominates); clamping to {eps}", file=sys.stderr)
        dt = eps
    noise = round(100 * (max(dts) - min(dts)) / dt, 2)
    tps = batch * (new - 1) / dt
    ms_tok = dt / (new - 1) * 1000
    prefill_ms = float(np.median(t_one)) * 1000
    # decode roofline: every param read once per token (bf16) at HBM BW
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    if on_tpu:
        from paddle_tpu.distributed.auto_parallel.cluster import Cluster
        hbm_bps = Cluster._chip_spec(dev.device_kind)["hbm_gbps"] * 1e9
        util = n_params * 2 / hbm_bps * 1000 / ms_tok
    else:
        util = 0.0
    print(f"# gpt-decode device={dev.device_kind} batch={batch} "
          f"prompt={prompt} new={new} {tps:,.0f} tok/s "
          f"{ms_tok:.2f} ms/token (prefill+1 {prefill_ms:.0f} ms) "
          f"noise={noise}%", file=sys.stderr)
    return {
        "metric": "gpt_decode_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "ms_per_token": round(ms_tok, 3),
        "prefill_ms": round(prefill_ms, 1),
        "batch": batch,
        "noise_pct": noise,
        "vs_baseline": round(util, 4),
    }


def bench_serving():
    """Continuous-batching serving leg (ISSUE 3): synthetic Poisson
    arrivals through serving.Engine — many concurrent requests share one
    compiled prefill and ONE compiled decode step over a fixed slot pool.
    Reports request throughput, token throughput, p50/p99 time-to-first-
    token and per-token latency; asserts the continuous-batching
    invariants (all requests complete, slots recycled, decode never
    retraces after warmup).  Then sweeps offered QPS through the HTTP
    gateway (ISSUE 8) for the closed-loop latency-under-load curve —
    client-measured TTFT percentiles, tokens/s and shed rate per level
    (`gateway` block)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import build_gpt, gpt_config
    from paddle_tpu.serving import Engine

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    if on_tpu:
        name, slots, max_len, n_req, new = "gpt2-small-en", 8, 640, 24, 32
        p_lo, p_hi, rate = 32, 128, 50.0
    else:  # CI/CPU: tiny shapes, same code path (>=16 concurrent requests)
        name, slots, max_len, n_req, new = "gpt-tiny", 4, 64, 16, 8
        p_lo, p_hi, rate = 4, 12, 50.0

    cfg = gpt_config(name, max_position_embeddings=max(max_len, 128),
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(0)
    model = build_gpt(cfg)
    model.eval()
    # device perfscope ON for the leg: every Nth decode dispatch is
    # timed, so the leg reports MFU/BW-per-program for free (the next
    # hardware round's roofline comes straight from this block)
    from paddle_tpu.observability import perfscope
    prev_sample = perfscope.sample_every()
    perfscope.set_sample_every(
        int(os.environ.get("PADDLE_TPU_PERFSCOPE_SAMPLE", "4") or 0))
    perfscope.reset_programs()
    engine = Engine(model, max_slots=slots, max_len=max_len,
                    max_queue=2 * n_req)
    try:
        rs = np.random.RandomState(0)
        prompts = [rs.randint(0, cfg.vocab_size,
                              rs.randint(p_lo, p_hi + 1)).astype(np.int64)
                   for _ in range(n_req)]
        # warmup: compile the decode step and both pow2 prompt buckets
        for plen in {len(min(prompts, key=len)), len(max(prompts, key=len))}:
            engine.submit(prompts[0][:plen] if plen <= len(prompts[0])
                          else rs.randint(0, cfg.vocab_size,
                                          plen).astype(np.int64),
                          max_new_tokens=2).result(timeout=600)
        warm_decode = engine.compile_stats()["decode_compiles"]

        t0 = time.perf_counter()
        handles = []
        for p in prompts:  # Poisson arrivals: exp-distributed gaps
            handles.append(engine.submit(p, max_new_tokens=new))
            time.sleep(min(rs.exponential(1.0 / rate), 0.25))
        for h in handles:
            h.result(timeout=600)
        wall = time.perf_counter() - t0
        st = engine.stats()
        decode_compiles = engine.compile_stats()["decode_compiles"]
        perf_rep = perfscope.perf_report()
    finally:
        engine.shutdown()
        perfscope.set_sample_every(prev_sample)

    if st["completed"] < n_req:
        raise RuntimeError(f"serving leg: only {st['completed']}/{n_req} "
                           f"requests completed: {st}")
    if st["slot_reuses"] <= 0:
        raise RuntimeError(f"serving leg: no slot reuse across {n_req} "
                           f"requests over {slots} slots: {st}")
    if decode_compiles != warm_decode:
        raise RuntimeError(
            f"serving leg: decode retraced after warmup "
            f"({warm_decode} -> {decode_compiles} signatures)")
    # perfscope roofline gate: the decode program must have sampled at
    # ONE compiled signature, and its reported MFU/BW fraction must match
    # the cost_analysis-derived expectation (flops / (mean sampled dt x
    # peak)) — validating the whole attribution chain on every CPU run
    dec = next((p for p in perf_rep["programs"]
                if p["program"] == "serving.decode"), None)
    if dec is None or not dec["sampled"]:
        raise RuntimeError(
            f"serving leg: perfscope sampled no decode dispatches: "
            f"{perf_rep['programs']}")
    if dec["signatures"] != 1:
        raise RuntimeError(
            f"serving leg: decode registered {dec['signatures']} "
            f"signatures with perfscope sampling on (must stay at 1)")
    mean_dt = dec["device_s"] / dec["sampled"]
    for got, flop_or_bytes, peak in (
            (dec["mfu"], dec["flops"], perf_rep["peak_flops"]),
            (dec["hbm_bw_frac"], dec["bytes"], perf_rep["peak_hbm_bw"])):
        if not (flop_or_bytes and peak):
            continue
        expect = flop_or_bytes / (mean_dt * peak)
        if got is None or abs(got - expect) > 0.02 * expect + 1e-9:
            raise RuntimeError(
                f"serving leg: perfscope roofline mismatch: got {got}, "
                f"cost_analysis expectation {expect:.6g}")
    perfscope_block = {
        "sample_every": perf_rep["sample_every"],
        "peak_flops": perf_rep["peak_flops"],
        "peak_hbm_bw": perf_rep["peak_hbm_bw"],
        "programs": {p["program"]: {
            k: p[k] for k in ("dispatches", "sampled", "device_s",
                              "est_total_s", "share", "mfu",
                              "hbm_bw_frac")}
            for p in perf_rep["programs"]},
    }
    total_tokens = sum(len(h.generated) for h in handles)
    ttfts = np.array([h.ttft_s for h in handles])
    toks = np.array([t for h in handles for t in h.token_latencies_s])
    # seed the gateway sweep's shed model with the measured engine
    # latencies so the first load level already sheds meaningfully
    measured = {"prefill_s": float(np.percentile(ttfts, 50)),
                "token_s": float(np.percentile(toks, 50))}
    fast_path_block = _bench_fast_path(model, cfg, on_tpu)
    paged_block = _bench_paged_kv(model, cfg, on_tpu)
    decode_kernel_block = _bench_decode_kernel(model, cfg, on_tpu)
    kv_tier_block = _bench_kv_tier(model, cfg, on_tpu)
    multi_lora_block = _bench_multi_lora(model, cfg, on_tpu)
    gateway_block = _bench_gateway_curve(cfg, on_tpu, measured)
    autoscale_block = _bench_autoscale_curve(measured)
    slo_block = _bench_slo_alerting(measured)
    capture_block = _bench_capture_fit(measured)
    tok_p50 = float(np.percentile(toks, 50))
    noise = round(100 * (float(np.percentile(toks, 90)) -
                         float(np.percentile(toks, 10))) / tok_p50, 2) \
        if tok_p50 else 0.0
    tps = total_tokens / wall
    print(f"# serving device={dev.device_kind} slots={slots} "
          f"requests={n_req} {tps:,.0f} tok/s "
          f"ttft p50={np.percentile(ttfts, 50) * 1e3:.1f}ms "
          f"p99={np.percentile(ttfts, 99) * 1e3:.1f}ms "
          f"token p50={tok_p50 * 1e3:.2f}ms "
          f"reuses={st['slot_reuses']}", file=sys.stderr)
    return {
        "metric": "serving_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "noise_pct": noise,
        "vs_baseline": 0.0,
        "requests": n_req,
        "requests_per_sec": round(n_req / wall, 2),
        "max_slots": slots,
        "slot_reuses": int(st["slot_reuses"]),
        "decode_steps": int(st["decode_steps"]),
        "decode_compiles": int(decode_compiles),
        "ttft_ms": {"p50": round(float(np.percentile(ttfts, 50)) * 1e3, 2),
                    "p99": round(float(np.percentile(ttfts, 99)) * 1e3, 2)},
        "token_ms": {"p50": round(tok_p50 * 1e3, 3),
                     "p99": round(float(np.percentile(toks, 99)) * 1e3, 3)},
        "fast_path": fast_path_block,
        "paged_kv": paged_block,
        "decode_kernel": decode_kernel_block,
        "kv_tier": kv_tier_block,
        "multi_lora": multi_lora_block,
        "gateway": gateway_block,
        "autoscale": autoscale_block,
        "slo": slo_block,
        "capture": capture_block,
        "perfscope": perfscope_block,
    }


def _bench_fast_path(model, cfg, on_tpu):
    """Decode fast-path blocks (ISSUE 10): prefix caching, speculative
    decoding and int8 KV, each measured on the serving engine with its
    flag on and parity-gated against the plain engine (CPU-runnable,
    like the input_overlap blocks).  Reports prefix hit rate + TTFT
    delta, draft acceptance rate + effective tokens per verify dispatch,
    and pool bytes + token-level quality delta for int8."""
    from paddle_tpu.serving import Engine

    if on_tpu:
        slots, max_len, new = 8, 640, 32
        shared_len, tail_len, n_req, block = 384, 16, 16, 16
    else:
        slots, max_len, new = 4, 64, 8
        shared_len, tail_len, n_req, block = 24, 4, 8, 4

    rs = np.random.RandomState(11)
    shared = rs.randint(0, cfg.vocab_size, shared_len).astype(np.int64)

    def make_prompts():
        return [np.concatenate(
            [shared,
             rs.randint(0, cfg.vocab_size, tail_len).astype(np.int64)])
            for _ in range(n_req)]

    prompts_w, prompts_m = make_prompts(), make_prompts()

    def run(engine, prompts):
        handles = [engine.submit(p, max_new_tokens=new) for p in prompts]
        outs = [h.result(timeout=600) for h in handles]
        return handles, outs

    def admit_to_first(handles):
        return [h.ttft_s - (h.t_admit - h.t_submit) for h in handles]

    # -- baseline: plain engine.  Wave 1 warms the compiles; wave 2 is
    # the measured cold-prefill reference (admit->first-token, so queue
    # wait behind earlier waves doesn't pollute the comparison) --------
    plain = Engine(model, max_slots=slots, max_len=max_len,
                   max_queue=2 * n_req)
    _, base_w = run(plain, prompts_w)
    h_plain, base_m = run(plain, prompts_m)
    plain_st = plain.stats()
    plain_bytes = plain.pool_bytes()
    plain.shutdown()
    cold_adm = admit_to_first(h_plain)

    # -- prefix cache: wave 1 seeds the index (and compiles the tail
    # program via its own later admissions); wave 2 hits a warm cache
    # with warm programs — the measured TTFT win -------------------------
    eng = Engine(model, max_slots=slots, max_len=max_len,
                 max_queue=2 * n_req, prefix_cache=True,
                 prefix_block=block)
    _, outs_w = run(eng, prompts_w)
    st1 = eng.stats()
    h_hit, outs_m = run(eng, prompts_m)
    st = eng.stats()
    eng.shutdown()
    for b, o in zip(base_w + base_m, outs_w + outs_m):
        np.testing.assert_array_equal(b, o)   # hits change nothing
    hits_m = st["prefix_hits"] - st1["prefix_hits"]
    misses_m = st["prefix_misses"] - st1["prefix_misses"]
    if hits_m <= 0:
        raise RuntimeError(f"fast path: no prefix hits on a shared-prefix "
                           f"workload: {st}")
    if st["decode_compiles"] != 1:
        raise RuntimeError(f"fast path: prefix cache retraced decode: {st}")
    hit_adm = admit_to_first([h for h in h_hit if h.prefix_hit])
    prefix_block_out = {
        "requests": n_req,
        "hit_rate": round(hits_m / max(hits_m + misses_m, 1), 3),
        "shared_prefix_tokens": shared_len,
        "admit_to_first_ms_hit_p50": round(
            float(np.percentile(hit_adm, 50)) * 1e3, 2),
        "admit_to_first_ms_cold_p50": round(
            float(np.percentile(cold_adm, 50)) * 1e3, 2),
        "ttft_delta_ms": round(
            (float(np.percentile(cold_adm, 50)) -
             float(np.percentile(hit_adm, 50))) * 1e3, 2),
        "tail_prefill_compiles": st["tail_prefill_compiles"],
        "decode_compiles": st["decode_compiles"],
        "parity": "exact",
    }

    # -- speculative: accepted drafts > 1 token per pool read ------------
    eng = Engine(model, max_slots=slots, max_len=max_len,
                 max_queue=2 * n_req, speculative_k=4)
    _, outs = run(eng, prompts_w)
    st = eng.stats()
    eng.shutdown()
    for b, o in zip(base_w, outs):      # greedy token-identical gate
        np.testing.assert_array_equal(b, o)
    # decode tokens only: the first token of each request comes from its
    # prefill, not from a verify dispatch
    tokens_per_verify = (st["tokens"] - n_req) / max(st["decode_steps"], 1)
    if tokens_per_verify <= 1.0:
        raise RuntimeError(
            f"fast path: speculative decode gained nothing "
            f"({tokens_per_verify:.2f} tokens/verify): {st}")
    if st["decode_compiles"] != 1:
        raise RuntimeError(f"fast path: speculation retraced decode: {st}")
    spec_block = {
        "k": 4,
        "drafted": int(st["spec_drafted"]),
        "accepted": int(st["spec_accepted"]),
        "acceptance_rate": round(
            st["spec_accepted"] / max(st["spec_drafted"], 1), 3),
        "tokens_per_verify": round(tokens_per_verify, 3),
        "verify_steps": int(st["decode_steps"]),
        "plain_decode_steps": int(plain_st["decode_steps"]),
        "decode_compiles": st["decode_compiles"],
        "parity": "exact",
    }

    # -- int8 KV: 2x slots in the same pool bytes ------------------------
    eng = Engine(model, max_slots=2 * slots, max_len=max_len,
                 max_queue=2 * n_req, kv_dtype="int8")
    _, outs = run(eng, prompts_w)
    st = eng.stats()
    int8_bytes = eng.pool_bytes()
    eng.shutdown()
    if int8_bytes > plain_bytes:
        raise RuntimeError(
            f"fast path: int8 pool at 2x slots ({int8_bytes}B) exceeds "
            f"the float pool at 1x ({plain_bytes}B)")
    if st["decode_compiles"] != 1:
        raise RuntimeError(f"fast path: int8 KV retraced decode: {st}")
    match = float(np.mean([np.mean(
        np.pad(b, (0, max(0, len(o) - len(b))))[:min(len(b), len(o))] ==
        np.pad(o, (0, max(0, len(b) - len(o))))[:min(len(b), len(o))])
        for b, o in zip(base_w, outs)]))
    int8_block = {
        "max_slots": 2 * slots,
        "kv_pool_bytes": int(int8_bytes),
        "baseline_pool_bytes_1x": int(plain_bytes),
        "bytes_ratio_vs_1x_float": round(int8_bytes / plain_bytes, 3),
        "token_match_vs_float": round(match, 3),
        "decode_compiles": st["decode_compiles"],
    }
    print(f"# fast-path prefix hit_rate="
          f"{prefix_block_out['hit_rate']} spec tokens/verify="
          f"{spec_block['tokens_per_verify']} int8 2x-slots bytes ratio="
          f"{int8_block['bytes_ratio_vs_1x_float']} "
          f"match={int8_block['token_match_vs_float']}", file=sys.stderr)
    return {"prefix_cache": prefix_block_out, "speculative": spec_block,
            "kv_int8": int8_block}


def _bench_kv_tier(model, cfg, on_tpu):
    """KV tiering block (ISSUE 18): multi-turn conversations whose
    turn-1 KV pages are EVICTED from the device pool before the warm
    turn arrives.  The tiered engine (``host_prefix_mb=``) demotes the
    victims to host DRAM and serves the warm turn via promote —
    tail-prefill only; the untiered engine pays full re-prefill.
    Reports warm-vs-cold admit->first-token, the host-tier hit rate and
    promote p50, and GATES warm < cold (the whole point of the tier).
    In ROADMAP's standing next-hardware-round list."""
    import paddle_tpu as paddle
    from paddle_tpu.models import build_gpt, gpt_config
    from paddle_tpu.serving import Engine

    if on_tpu:
        slots, max_len, turn, new, n_conv, num_pages, block = \
            8, 640, 256, 32, 8, 192, 16
    else:
        # gpt-tiny prefill is dispatch-dominated on CPU (a 4-token tail
        # costs the same as a 64-token prompt), which would make the
        # warm-vs-cold gate meaningless — this block sizes the model up
        # until COMPUTE dominates, the regime the tier exists for
        cfg = gpt_config("gpt-tiny", hidden_size=512, num_layers=6,
                         num_attention_heads=8,
                         hidden_dropout_prob=0.0,
                         attention_dropout_prob=0.0)
        paddle.seed(0)
        model = build_gpt(cfg)
        model.eval()
        slots, max_len, turn, new, n_conv, num_pages, block = \
            2, 96, 56, 4, 4, 48, 4

    rs = np.random.RandomState(19)
    firsts = [rs.randint(0, cfg.vocab_size, turn).astype(np.int64)
              for _ in range(n_conv)]
    extras = [rs.randint(0, cfg.vocab_size, block).astype(np.int64)
              for _ in range(n_conv)]

    def run(tiered):
        kw = {"host_prefix_mb": 64} if tiered else {}
        eng = Engine(model, max_slots=slots, max_len=max_len,
                     max_queue=4 * n_conv, prefix_cache=True,
                     prefix_block=block, paged_kv=True,
                     num_pages=num_pages, **kw)
        try:
            # warm the prefill buckets + decode compile out of the
            # measured window
            eng.submit(firsts[0][:turn // 2],
                       max_new_tokens=2).result(timeout=600)
            # turn 1 of every conversation, sequentially: each insert
            # pressures the fixed pool, so early entries are evicted
            # (tiered: demoted to host) before their warm turn returns
            replies = [np.asarray(eng.submit(
                p, max_new_tokens=new,
                conversation=f"conv{i}").result(timeout=600))
                for i, p in enumerate(firsts)]
            if eng._host_tier is not None:
                eng._host_tier.flush()
            handles, outs = [], []
            for i, (p, r, x) in enumerate(zip(firsts, replies, extras)):
                warm = np.concatenate([p, r, x]).astype(np.int64)
                h = eng.submit(warm, max_new_tokens=new,
                               conversation=f"conv{i}")
                outs.append(np.asarray(h.result(timeout=600)))
                handles.append(h)
            st = eng.stats()
        finally:
            eng.shutdown()
        return handles, outs, st

    h_cold, outs_cold, st_cold = run(tiered=False)
    h_warm, outs_warm, st_warm = run(tiered=True)
    for b, o in zip(outs_cold, outs_warm):   # the tier changes nothing
        np.testing.assert_array_equal(b, o)
    if st_warm["decode_compiles"] != 1:
        raise RuntimeError(f"kv tier: promote retraced decode: {st_warm}")
    promoted = [h for h in h_warm if h.promote_s is not None]
    if not promoted:
        raise RuntimeError(
            f"kv tier: no warm turn was served via a host-tier promote "
            f"(nothing evicted?): {st_warm}")
    # cold reference: only TRUE re-prefills (a late conversation whose
    # entry survived in the device index would pollute the baseline)
    cold = [h for h in h_cold if not h.prefix_hit]
    if not cold:
        raise RuntimeError(
            "kv tier: the untiered run never re-prefilled — the pool "
            "never evicted, the comparison is void")

    def admit_to_first(handles):
        return [h.ttft_s - (h.t_admit - h.t_submit) for h in handles]

    warm_p50 = float(np.percentile(admit_to_first(promoted), 50))
    cold_p50 = float(np.percentile(admit_to_first(cold), 50))
    if warm_p50 >= cold_p50:
        raise RuntimeError(
            f"kv tier: warm TTFT p50 ({warm_p50 * 1e3:.2f}ms) is not "
            f"below cold re-prefill p50 ({cold_p50 * 1e3:.2f}ms)")
    tier_st = st_warm["host_prefix"]
    hit_rate = tier_st["hits"] / max(tier_st["hits"] +
                                     tier_st["misses"], 1)
    promote_p50 = float(np.percentile(
        [h.promote_s for h in promoted], 50))
    block_out = {
        "conversations": n_conv,
        "turn_tokens": turn,
        "host_capacity_mb": 64,
        "demotes": int(tier_st["demotes"]),
        "host_hit_rate": round(hit_rate, 3),
        "promotes": int(st_warm["host_prefix_promotes"]),
        "promote_ms_p50": round(promote_p50 * 1e3, 3),
        "warm_ttft_ms_p50": round(warm_p50 * 1e3, 2),
        "cold_ttft_ms_p50": round(cold_p50 * 1e3, 2),
        "ttft_delta_ms": round((cold_p50 - warm_p50) * 1e3, 2),
        "decode_compiles": int(st_warm["decode_compiles"]),
        "parity": "exact",
    }
    print(f"# kv-tier warm p50={block_out['warm_ttft_ms_p50']}ms "
          f"cold p50={block_out['cold_ttft_ms_p50']}ms "
          f"host hit_rate={block_out['host_hit_rate']} "
          f"promote p50={block_out['promote_ms_p50']}ms", file=sys.stderr)
    return block_out


def _bench_multi_lora(model, cfg, on_tpu):
    """Multi-LoRA block (ISSUE 12): many-adapter mixed traffic with a
    hot/cold skew through one engine, all CPU-gateable.

    * a registry holding MORE adapters than the resident bank, with 70%
      of traffic on two hot adapters — cold adapters churn through
      admission-time loads + LRU eviction while the hot ones stay
      resident; reports tokens/s, the resident-bank hit rate, and the
      p50 cold-adapter admit stall (bank upload wall time);
    * ``weight_int8`` — the SAME mixed traffic on
      ``Engine(weight_dtype="int8")``: stored weight bytes ratio vs f32
      and a token-match gate (>= 0.9) against the f32 outputs;
    * decode stays at ONE compiled signature in both configs.
    """
    from paddle_tpu.serving import AdapterRegistry, Engine, make_lora

    if on_tpu:
        slots, max_len, new, n_req = 8, 640, 32, 24
        n_adapters, resident, rank = 8, 4, 8
    else:
        slots, max_len, new, n_req = 4, 64, 8, 16
        n_adapters, resident, rank = 6, 3, 4

    reg = AdapterRegistry(model, max_resident=resident, max_rank=rank)
    names = [f"lora{i}" for i in range(n_adapters)]
    for i, nm in enumerate(names):
        reg.register(make_lora(cfg, rank=rank, seed=100 + i, name=nm,
                               std=0.1))
    rs = np.random.RandomState(21)
    prompts = [rs.randint(0, cfg.vocab_size, 8).astype(np.int64)
               for _ in range(n_req)]
    # hot/cold skew: most traffic on two hot adapters, the rest rotates
    # through a cold tail wider than the bank (forces load + eviction)
    picks = [names[i % 2] if rs.rand() < 0.7
             else names[2 + i % (n_adapters - 2)] for i in range(n_req)]

    def run(engine):
        engine.submit(prompts[0], max_new_tokens=2).result(
            timeout=600)                       # warm the compiles
        t0 = time.perf_counter()
        handles = [engine.submit(p, max_new_tokens=new, adapter=nm)
                   for p, nm in zip(prompts, picks)]
        outs = [h.result(timeout=600) for h in handles]
        return outs, time.perf_counter() - t0

    eng = Engine(model, max_slots=slots, max_len=max_len,
                 max_queue=2 * n_req, adapters=reg)
    outs, wall = run(eng)
    st = eng.stats()
    load_ms = [t * 1e3 for t in eng._adapter_load_times]
    f32_bytes = eng.weight_bytes()
    eng.shutdown()
    if st["decode_compiles"] != 1:
        raise RuntimeError(f"multi_lora: adapters retraced decode: {st}")
    if st["adapter_evictions"] <= 0 or st["adapter_loads"] <= resident:
        raise RuntimeError(
            f"multi_lora: no cold-adapter churn on a {n_adapters}-adapter "
            f"mix over a {resident}-row bank: {st}")
    hits, loads = st["adapter_hits"], st["adapter_loads"]
    tokens = sum(len(o) for o in outs)

    # -- int8 base weights on the same mixed traffic ---------------------
    q = Engine(model, max_slots=slots, max_len=max_len,
               max_queue=2 * n_req, adapters=reg, weight_dtype="int8")
    qouts, _ = run(q)
    q_st = q.stats()
    q_bytes = q.weight_bytes()
    q.shutdown()
    if q_st["decode_compiles"] != 1:
        raise RuntimeError(
            f"multi_lora: int8 weights retraced decode: {q_st}")
    ratio = q_bytes / max(f32_bytes, 1)
    if ratio >= 0.5:
        raise RuntimeError(
            f"multi_lora: int8 weights did not halve the stored bytes "
            f"({q_bytes}B vs {f32_bytes}B)")
    match = float(np.mean([np.mean(b == g) for b, g in zip(outs, qouts)]))
    if match < 0.9:
        raise RuntimeError(
            f"multi_lora: int8 weights token match {match:.3f} < 0.9")

    block = {
        "requests": n_req,
        "adapters": n_adapters,
        "resident_bank": resident,
        "rank": rank,
        "tokens_per_sec": round(tokens / wall, 1),
        "resident_hit_rate": round(hits / max(hits + loads, 1), 3),
        "cold_loads": int(loads),
        "evictions": int(st["adapter_evictions"]),
        "load_stalls": int(st["adapter_load_stalls"]),
        "cold_admit_stall_ms_p50": round(
            float(np.percentile(load_ms, 50)), 2) if load_ms else 0.0,
        "decode_compiles": int(st["decode_compiles"]),
        "weight_int8": {
            "weight_bytes": int(q_bytes),
            "baseline_weight_bytes_f32": int(f32_bytes),
            "bytes_ratio": round(ratio, 3),
            "token_match_vs_f32": round(match, 3),
            "decode_compiles": int(q_st["decode_compiles"]),
        },
    }
    print(f"# multi_lora adapters={n_adapters}/bank={resident} "
          f"hit_rate={block['resident_hit_rate']} "
          f"cold stall p50={block['cold_admit_stall_ms_p50']}ms "
          f"int8 weights ratio={block['weight_int8']['bytes_ratio']} "
          f"match={block['weight_int8']['token_match_vs_f32']}",
          file=sys.stderr)
    return block


def _bench_paged_kv(model, cfg, on_tpu):
    """Paged KV block (ISSUE 11): the block-granular pool against the
    dense slot pool, all CPU-gateable.

    * ``effective_slots_per_hbm_byte`` — a heavy-tail length mix (many
      short requests, a few long) runs through a dense pool and a paged
      pool holding NO MORE bytes; the paged pool must sustain strictly
      more concurrent resident sequences per byte (its HBM scales with
      actual tokens, the dense pool's with max_len * slots).
    * ``long_context`` — a completion past the dense pool's compiled
      ``max_len`` (more page-table entries, same decode program).
    * ``prefix_hit`` — admit→first-token for warm prefix hits: the paged
      hit shares pages by reference (zero-copy page-table writes) where
      the dense hit device-copies the whole row bitwise.
    """
    from paddle_tpu.serving import Engine

    if on_tpu:
        slots, max_len, page = 8, 640, 16
        short_lo, short_new, long_len, long_new, n_req = 24, 16, 500, 32, 24
        shared_len, tail_len, n_hit = 384, 16, 8
    else:
        slots, max_len, page = 3, 64, 8
        short_lo, short_new, long_len, long_new, n_req = 6, 4, 48, 8, 12
        shared_len, tail_len, n_hit = 24, 4, 6

    rs = np.random.RandomState(17)

    def heavy_tail_prompts():
        # ~5/6 short, ~1/6 near-max_len long — the traffic shape the
        # dense pool provisions every slot for
        out = []
        for i in range(n_req):
            if i % 6 == 5:
                out.append((rs.randint(0, cfg.vocab_size,
                                       long_len).astype(np.int64), long_new))
            else:
                plen = rs.randint(short_lo, short_lo + 8)
                out.append((rs.randint(0, cfg.vocab_size,
                                       plen).astype(np.int64), short_new))
        return out

    def run_mix(engine, mix):
        handles = [engine.submit(p, max_new_tokens=new) for p, new in mix]
        peak = 0
        while not all(h.done() for h in handles):
            peak = max(peak, engine.slots_in_use())
            time.sleep(0.001)
        for h in handles:
            h.result(timeout=600)
        return handles, peak

    mix = heavy_tail_prompts()
    dense = Engine(model, max_slots=slots, max_len=max_len,
                   max_queue=2 * n_req)
    d_handles, d_peak = run_mix(dense, mix)
    dense_bytes = dense.pool_bytes()
    dense.shutdown()
    d_peak = max(d_peak, 1)

    # paged pool: MORE lanes, NO MORE bytes — pages sized to the dense
    # budget, so the byte denominator is apples-to-apples
    pages_budget = (slots * -(-max_len // page))
    paged = Engine(model, max_slots=3 * slots, max_len=max_len,
                   max_queue=2 * n_req, paged_kv=True, page_size=page,
                   num_pages=pages_budget)
    p_handles, p_peak = run_mix(paged, mix)
    paged_bytes = paged.pool_bytes()
    p_stats = paged.stats()
    paged.shutdown()
    for (dh, ph) in zip(d_handles, p_handles):   # greedy parity gate
        np.testing.assert_array_equal(dh.result(), ph.result())
    if paged_bytes > dense_bytes:
        raise RuntimeError(
            f"paged pool ({paged_bytes}B) exceeds the dense budget "
            f"({dense_bytes}B)")
    d_eff = d_peak / dense_bytes
    p_eff = p_peak / paged_bytes
    if p_eff <= d_eff:
        raise RuntimeError(
            f"paged_kv: effective slots per HBM byte did not improve "
            f"(paged {p_peak}/{paged_bytes}B vs dense "
            f"{d_peak}/{dense_bytes}B)")
    if p_stats["decode_compiles"] != 1:
        raise RuntimeError(f"paged_kv: decode retraced: {p_stats}")

    # long context: complete past a dense pool's compiled max_len (the
    # probe pool compiles at max_len // 2 so the demo stays inside the
    # model's position-embedding table on every platform; the paged
    # engine's table simply holds twice the entries)
    lc_max = max_len // 2
    lc = Engine(model, max_slots=2, max_len=lc_max, paged_kv=True,
                page_size=page, max_pages_per_slot=2 * (-(-lc_max // page)))
    lc_prompt = rs.randint(0, cfg.vocab_size, lc_max - 2).astype(np.int64)
    lc_new = min(2 * page, lc_max)       # finishes past lc_max
    lc_out = lc.submit(lc_prompt, max_new_tokens=lc_new).result(timeout=600)
    lc_len = int(lc_prompt.size + lc_out.size)
    lc.shutdown()
    if lc_len <= lc_max:
        raise RuntimeError(
            f"paged_kv: long-context completion did not pass the "
            f"compiled max_len ({lc_len} <= {lc_max})")

    # prefix-hit TTFT: zero-copy page sharing vs the dense row copy
    shared = rs.randint(0, cfg.vocab_size, shared_len).astype(np.int64)

    def hit_wave():
        return [np.concatenate(
            [shared, rs.randint(0, cfg.vocab_size,
                                tail_len).astype(np.int64)])
            for _ in range(n_hit)]

    def admit_to_first(handles):
        return [h.ttft_s - (h.t_admit - h.t_submit) for h in handles]

    def measure_hits(**kw):
        eng = Engine(model, max_slots=slots, max_len=max_len,
                     max_queue=2 * n_hit, prefix_cache=True,
                     prefix_block=page, **kw)
        for p in hit_wave():                       # warm: seed + compile
            eng.submit(p, max_new_tokens=short_new).result(timeout=600)
        hs = [eng.submit(p, max_new_tokens=short_new)
              for p in hit_wave()]                 # measured: warm hits
        for h in hs:
            h.result(timeout=600)
        st = eng.stats()
        eng.shutdown()
        hits = [h for h in hs if h.prefix_hit]
        return admit_to_first(hits), st

    dense_adm, dense_st = measure_hits()
    paged_adm, paged_st = measure_hits(paged_kv=True)
    if not paged_adm or not dense_adm:
        raise RuntimeError(
            f"paged_kv: no warm prefix hits to measure "
            f"(dense {dense_st}, paged {paged_st})")
    dense_p50 = float(np.percentile(dense_adm, 50))
    paged_p50 = float(np.percentile(paged_adm, 50))

    block = {
        "pool_bytes": {"dense": int(dense_bytes), "paged": int(paged_bytes)},
        "heavy_tail": {
            "requests": n_req,
            "peak_concurrent": {"dense": int(d_peak), "paged": int(p_peak)},
            "effective_slots_per_mib": {
                "dense": round(d_peak / (dense_bytes / 2**20), 3),
                "paged": round(p_peak / (paged_bytes / 2**20), 3)},
            "parity": "exact",
        },
        "long_context": {
            "compiled_max_len": lc_max,
            "completed_len": lc_len,
            "page_size": page,
        },
        "prefix_hit": {
            "admit_to_first_ms_dense_copy_p50": round(dense_p50 * 1e3, 2),
            "admit_to_first_ms_paged_zero_copy_p50": round(
                paged_p50 * 1e3, 2),
            "ttft_delta_ms": round((dense_p50 - paged_p50) * 1e3, 2),
            "cow_copies": int(paged_st["page_cow_copies"]),
        },
        "decode_compiles": int(p_stats["decode_compiles"]),
    }
    print(f"# paged_kv eff-slots/MiB dense="
          f"{block['heavy_tail']['effective_slots_per_mib']['dense']} "
          f"paged={block['heavy_tail']['effective_slots_per_mib']['paged']} "
          f"long_context={lc_len}>{lc_max} "
          f"hit ttft delta={block['prefix_hit']['ttft_delta_ms']}ms",
          file=sys.stderr)
    return block


def _bench_decode_kernel(model, cfg, on_tpu):
    """Decode-kernel block (ISSUE 19): the fused Pallas paged-attention
    read (``Engine(decode_kernel="pallas")``) against the XLA
    gather-then-attend paged path, composed with the int8 pool and
    speculative verify it exists to accelerate.

    CPU (interpret mode) gates correctness: greedy token parity, ONE
    compiled decode signature, and identical per-step dispatch counts
    (exact parity forces the same speculative accept trace, so a step
    drift means the kernel changed math).  tokens/s and measured
    HBM-bytes/token vs the XLA read are hardware numbers — interpret
    walls are not kernel timings — and stay reserved for the TPU round;
    the analytic streamed-bytes ratio is reported from the kernel's own
    perfscope cost booking.
    """
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.observability import perfscope
    from paddle_tpu.serving import Engine

    if on_tpu:
        slots, max_len, page, n_req, new = 8, 640, 16, 16, 32
    else:
        slots, max_len, page, n_req, new = 3, 64, 8, 8, 6

    rs = np.random.RandomState(23)
    prompts = [rs.randint(0, cfg.vocab_size,
                          rs.randint(6, 20)).astype(np.int64)
               for _ in range(n_req)]

    def run(kernel):
        eng = Engine(model, max_slots=slots, max_len=max_len,
                     max_queue=2 * n_req, paged_kv=True, page_size=page,
                     kv_dtype="int8", speculative_k=3,
                     decode_kernel=kernel)
        t0 = time.perf_counter()
        handles = [eng.submit(p, max_new_tokens=new) for p in prompts]
        outs = [h.result(timeout=600) for h in handles]
        wall = time.perf_counter() - t0
        st = eng.stats()
        eng.shutdown()
        return outs, st, wall

    x_out, x_st, x_wall = run("xla")
    p_out, p_st, p_wall = run("pallas")
    for a, b in zip(x_out, p_out):            # greedy parity gate
        np.testing.assert_array_equal(a, b)
    if p_st["decode_compiles"] != 1:
        raise RuntimeError(
            f"decode_kernel: pallas decode retraced: {p_st}")
    if p_st["decode_steps"] != x_st["decode_steps"]:
        raise RuntimeError(
            f"decode_kernel: per-step dispatch counts diverged "
            f"(xla {x_st['decode_steps']} vs pallas "
            f"{p_st['decode_steps']})")
    prog = perfscope._programs.get(pa.PERFSCOPE_PROGRAM)
    if prog is None or not prog.costs:
        raise RuntimeError(
            "decode_kernel: kernel never booked its perfscope cost")
    # analytic streamed-bytes ratio: what HBM moves per attended
    # position under the fused int8 read (1B/elem + one f32 absmax per
    # position per pool) vs the XLA f32 gather it replaces (4B/elem)
    hd = cfg.hidden_size // cfg.num_attention_heads * \
        cfg.num_attention_heads
    streamed_ratio = (hd + 4.0) / (4.0 * hd)
    total_tokens = sum(len(o) for o in p_out)
    block = {
        "parity": "exact",
        "requests": n_req,
        "tokens": int(total_tokens),
        "decode_steps": int(p_st["decode_steps"]),
        "decode_compiles": int(p_st["decode_compiles"]),
        "kernel_cost_signatures": sorted(prog.costs),
        "analytic_streamed_bytes_ratio_int8_vs_f32_gather": round(
            streamed_ratio, 3),
    }
    if on_tpu:
        block["tokens_per_sec"] = {
            "xla": round(total_tokens / x_wall, 1),
            "pallas": round(total_tokens / p_wall, 1)}
    else:
        block["tokens_per_sec"] = \
            "reserved for hardware round (interpret mode)"
        block["hbm_bytes_per_token"] = \
            "reserved for hardware round (interpret mode)"
    print(f"# decode_kernel parity=exact steps={p_st['decode_steps']} "
          f"compiles={p_st['decode_compiles']} "
          f"streamed-bytes ratio={streamed_ratio:.3f}",
          file=sys.stderr)
    return block


def _bench_autoscale_curve(measured):
    """Closed-loop fleet elasticity block (ISSUE 15): SLO-attainment vs
    replica-seconds curves instead of fixed-QPS points.  The seeded
    flash-crowd trace (tools/load_gen.py: diurnal + 8x flash +
    heavy-tail lengths) runs through FleetSim — virtual time, the
    shedder's latency model parameterized by THIS leg's measured
    prefill/per-token latencies — once per static fleet size and once
    autoscaled by the default ScalePolicy.  Gates: the autoscaled fleet
    matches the best static fleet's SLO attainment while spending fewer
    replica-seconds than the cheapest static fleet achieving it, with
    zero scale-flaps."""
    from paddle_tpu.serving import FleetSim, ScalePolicy
    from tools.load_gen import make_trace

    prefill_s = measured["prefill_s"]
    token_s = max(measured["token_s"], 1e-4)
    slots, out_mean, max_n = 4, 10.0, 5
    # the policy dynamics are scale-free: normalize the measured
    # latencies so the mean request's SERVICE time is 0.15 virtual
    # seconds (the regime the tier-1 sim tests pin down) — the measured
    # values contribute their prefill:token RATIO, the trace overloads
    # one replica by a fixed 25% at the flash peak, and absolute
    # magnitudes (which would otherwise quantize against the sim tick
    # for very fast engines) scale out.  Reported numbers carry the
    # virtual→measured conversion factor.
    service_meas = prefill_s + out_mean * token_s
    k = 0.15 / service_meas
    prefill_v, token_v = prefill_s * k, token_s * k
    capacity_qps = slots / 0.15
    base_qps = 0.15 * capacity_qps
    flash_mult = 1.25 * capacity_qps / base_qps
    slo_ttft_s = prefill_v + 1.5
    trace = make_trace(60.0, base_qps, seed=0, flash_mult=flash_mult,
                       flash_at=0.25, flash_duration_s=10.0,
                       prompt_mean=12.0, out_mean=out_mean, out_max=48,
                       deadline_s=prefill_v + 3.0)
    # headroom_frac 0.4 + up_ticks 1: trigger while the projected wait
    # is still well inside the SLO slack — the build takes 1.5 virtual
    # seconds and the backlog keeps growing until the replica lands.
    # cooldown_up 4.0 gives each new replica time to absorb the drained
    # backlog before the (still-elevated) estimate buys another chip;
    # cooldown_down 3.0 walks the flash fleet back down briskly — both
    # matter for the fewer-replica-seconds gate, and the heavy tail is
    # bounded at out_max=48 (4.8x the mean; an unbounded p99.9 request
    # holds a slot for ~25x mean service and makes ramp waits a lottery)
    policy = ScalePolicy(slo_ttft_s=slo_ttft_s, headroom_frac=0.4,
                         up_ticks=1, idle_ticks=8,
                         cooldown_up_s=4.0, cooldown_down_s=3.0)
    sim_kw = dict(slots_per_replica=slots, prefill_s=prefill_v,
                  token_s=token_v, slo_ttft_s=slo_ttft_s)
    auto = FleetSim(policy, min_replicas=1, max_replicas=max_n,
                    build_s=1.5, **sim_kw).run(trace)
    statics = {n: FleetSim(None, min_replicas=n, max_replicas=n,
                           start_replicas=n, **sim_kw).run(trace)
               for n in range(1, max_n + 1)}
    best_att = max(s["slo_attainment"] for s in statics.values())
    cheapest_best = min(
        (s["replica_seconds"] for s in statics.values()
         if s["slo_attainment"] >= best_att))
    if auto["slo_attainment"] < best_att - 1e-9:
        raise RuntimeError(
            f"autoscale gate: attainment {auto['slo_attainment']} < best "
            f"static {best_att}")
    if auto["replica_seconds"] >= cheapest_best:
        raise RuntimeError(
            f"autoscale gate: {auto['replica_seconds']} replica-seconds "
            f">= cheapest SLO-attaining static fleet ({cheapest_best})")
    if auto["flaps"] != 0:
        raise RuntimeError(f"autoscale gate: {auto['flaps']} scale-flaps "
                           f"(events: {auto['events']})")
    # warm-pool gate (ISSUE 20): the same policy with one parked spare
    # must answer the flash with a route-in, not a cold build — the
    # reaction time of every warm scale-up stays under the build time
    warm_policy = ScalePolicy(slo_ttft_s=slo_ttft_s, headroom_frac=0.4,
                              up_ticks=1, idle_ticks=8,
                              cooldown_up_s=4.0, cooldown_down_s=3.0)
    warm = FleetSim(warm_policy, min_replicas=1, max_replicas=max_n,
                    build_s=1.5, warm_pool=1, route_in_s=0.05,
                    **sim_kw).run(trace)
    wblock = warm["warm"] or {}
    if not wblock.get("warm_route_ins"):
        raise RuntimeError(
            f"warm-pool gate: no warm route-in fired "
            f"(events: {warm['events']})")
    if not wblock.get("max_warm_reaction_s", 1.5) < 1.5:
        raise RuntimeError(
            f"warm-pool gate: warm reaction "
            f"{wblock.get('max_warm_reaction_s')}s not under the 1.5s "
            f"cold build")
    print(f"# autoscale attainment={auto['slo_attainment']} "
          f"replica_s={auto['replica_seconds']} "
          f"(best static {best_att} @ {cheapest_best}) "
          f"peak={auto['peak_replicas']} events={len(auto['events'])} "
          f"warm_route_ins={wblock['warm_route_ins']} "
          f"warm_reaction_s={wblock['max_warm_reaction_s']}",
          file=sys.stderr)
    return {
        "trace": {"arrivals": len(trace), "duration_s": 60.0,
                  "base_qps": round(base_qps, 2),
                  "flash_mult": round(flash_mult, 2), "seed": 0},
        "model": {"prefill_s": round(prefill_s, 4),
                  "token_s": round(token_s, 5),
                  "virtual_per_measured_s": round(k, 4),
                  "slots_per_replica": slots,
                  "slo_ttft_virtual_s": round(slo_ttft_s, 3),
                  "slo_ttft_measured_s": round(slo_ttft_s / k, 3)},
        "autoscaled": {k: auto[k] for k in (
            "slo_attainment", "replica_seconds", "peak_replicas", "shed",
            "flaps", "ttft_p50_s", "ttft_p99_s")},
        "warm_pool": dict(
            wblock,
            slo_attainment=warm["slo_attainment"],
            replica_seconds=warm["replica_seconds"]),
        "scale_events": auto["events"],
        "curve": [{"replicas": n,
                   "slo_attainment": s["slo_attainment"],
                   "replica_seconds": s["replica_seconds"],
                   "shed": s["shed"]}
                  for n, s in sorted(statics.items())],
        "gates": {"attainment_vs_best_static": True,
                  "fewer_replica_seconds": True, "zero_flaps": True,
                  "warm_pool_reaction": True},
    }


def _bench_capture_fit(measured):
    """Capture→fit round-trip block (ISSUE 17): the seeded diurnal+flash
    trace is recorded through a shape-mode TrafficCapture (virtual
    arrival times, no HTTP — CPU-runnable like the autoscale curve),
    fitted back into a synthetic trace by ``capture.fit_trace``, and
    both traces run the SAME autoscaled FleetSim (measured latencies
    normalized to the 0.15 s mean service time).  Gates: the fit
    recovers the flash window (overlap with truth) and the heavy-tail
    output-length shape, and the fitted trace reproduces the source
    trace's scale-up decision sequence — same number of scale-ups, same
    peak fleet, first scale-up within a policy-poll-scaled tolerance."""
    from paddle_tpu.observability.capture import (TrafficCapture,
                                                  fit_params, fit_trace)
    from paddle_tpu.serving import FleetSim, ScalePolicy
    from tools.load_gen import make_trace

    prefill_s = measured["prefill_s"]
    token_s = max(measured["token_s"], 1e-4)
    slots, out_mean, out_sigma = 4, 10.0, 0.7
    service_meas = prefill_s + out_mean * token_s
    k = 0.15 / service_meas
    prefill_v, token_v = prefill_s * k, token_s * k
    capacity_qps = slots / 0.15
    base_qps = 0.15 * capacity_qps
    flash_mult = 1.25 * capacity_qps / base_qps
    slo_ttft_s = prefill_v + 1.5
    flash_t0, flash_t1 = 0.25 * 60.0, 0.25 * 60.0 + 10.0
    src = make_trace(60.0, base_qps, seed=0, flash_mult=flash_mult,
                     flash_at=0.25, flash_duration_s=10.0,
                     prompt_mean=12.0, out_mean=out_mean,
                     out_sigma=out_sigma, out_max=48,
                     deadline_s=prefill_v + 3.0)
    cap = TrafficCapture(max_entries=len(src) + 16, mode="shape")
    for e in src:
        cap.record(tenant="bench", priority="standard",
                   outcome="admitted", prompt_len=e["prompt_len"],
                   max_tokens=e["max_tokens"],
                   deadline_s=e["deadline_s"], t=e["t"])
    assert cap.stats()["dropped"] == 0
    # 1.0s bins: the auto heuristic picks ~2.5s bins for a 60s window,
    # which smears the 10s flash edges enough to drop a scale-up from
    # the fitted replay — fine bins keep the overload depth faithful
    p = fit_params(cap.entries(), bin_s=1.0)
    if p["flash"] is None or not (p["flash"]["t0"] < flash_t1
                                  and p["flash"]["t1"] > flash_t0):
        raise RuntimeError(
            f"capture gate: fitted flash window {p['flash']} misses the "
            f"true [{flash_t0}, {flash_t1})")
    if not (0.5 * flash_mult <= p["flash"]["mult"] <= 2.0 * flash_mult):
        raise RuntimeError(
            f"capture gate: fitted flash mult {p['flash']['mult']} "
            f"outside [{0.5 * flash_mult}, {2.0 * flash_mult}]")
    if abs(p["out"]["sigma"] - out_sigma) > 0.15:
        raise RuntimeError(
            f"capture gate: fitted out sigma {p['out']['sigma']} "
            f"not within 0.15 of the seeded {out_sigma} (heavy tail "
            f"lost in the fit)")
    fitted = fit_trace(cap.entries(), seed=1, params=p, out_max=48)

    def run(trace):
        pol = ScalePolicy(slo_ttft_s=slo_ttft_s, headroom_frac=0.4,
                          up_ticks=1, idle_ticks=8, cooldown_up_s=4.0,
                          cooldown_down_s=3.0)
        return FleetSim(pol, min_replicas=1, max_replicas=5, build_s=1.5,
                        slots_per_replica=slots, prefill_s=prefill_v,
                        token_s=token_v, slo_ttft_s=slo_ttft_s).run(trace)

    src_res, fit_res = run(src), run(fitted)
    src_ups = [e for e in src_res["events"] if e["direction"] == "up"]
    fit_ups = [e for e in fit_res["events"] if e["direction"] == "up"]
    if len(src_ups) != len(fit_ups):
        raise RuntimeError(
            f"capture gate: fitted trace drove {len(fit_ups)} scale-ups "
            f"vs the source's {len(src_ups)} "
            f"(src={src_res['events']}, fit={fit_res['events']})")
    if fit_res["peak_replicas"] != src_res["peak_replicas"]:
        raise RuntimeError(
            f"capture gate: fitted peak {fit_res['peak_replicas']} != "
            f"source peak {src_res['peak_replicas']}")
    # the first scale-up is the flash response; the fitted trace must
    # place it in the same regime (within the rate-curve bin width plus
    # policy hysteresis, not e.g. pre-scaled by a smeared-out flash)
    first_up_tol = 2.0 * p["bin_s"] + 2.0
    if src_ups and abs(fit_ups[0]["t"] - src_ups[0]["t"]) > first_up_tol:
        raise RuntimeError(
            f"capture gate: first scale-up at t={fit_ups[0]['t']} under "
            f"the fitted trace vs t={src_ups[0]['t']} under the source "
            f"(tolerance {first_up_tol})")
    print(f"# capture fit arrivals={len(src)}->{len(fitted)} "
          f"flash=[{p['flash']['t0']},{p['flash']['t1']}]x"
          f"{p['flash']['mult']} ups={len(src_ups)}=={len(fit_ups)} "
          f"first_up {src_ups[0]['t'] if src_ups else None}->"
          f"{fit_ups[0]['t'] if fit_ups else None} "
          f"peak={fit_res['peak_replicas']}", file=sys.stderr)
    return {
        "source": {"arrivals": len(src), "duration_s": 60.0,
                   "base_qps": round(base_qps, 2),
                   "flash_mult": round(flash_mult, 2), "seed": 0},
        "fit": {"arrivals": len(fitted), "bin_s": p["bin_s"],
                "flash": p["flash"], "base_qps": p["base_qps"],
                "prompt": p["prompt"], "out": p["out"]},
        "sim": {
            "source": {k2: src_res[k2] for k2 in (
                "slo_attainment", "replica_seconds", "peak_replicas",
                "shed")},
            "fitted": {k2: fit_res[k2] for k2 in (
                "slo_attainment", "replica_seconds", "peak_replicas",
                "shed")},
            "source_scale_ups": len(src_ups),
            "fitted_scale_ups": len(fit_ups),
            "first_up_delta_s": (round(abs(
                fit_ups[0]["t"] - src_ups[0]["t"]), 3)
                if src_ups and fit_ups else None),
        },
        "gates": {"flash_window_recovered": True,
                  "length_tail_recovered": True,
                  "scale_up_sequence_reproduced": True},
    }


def _bench_slo_alerting(measured):
    """Burn-rate alerting block (ISSUE 16): the multi-window SLO
    evaluator rides the same virtual-time FleetSim as the autoscale
    curve (measured prefill/token latencies normalized to a 0.15 s mean
    service time).  Gates: on the flash-crowd trace the fast-burn rule
    fires BEFORE the slow-window attainment itself crosses below the
    target (early warning, not postmortem), the alert resolves only
    after the autoscaler's first scale-up lands (absorption, not
    flapping), and the steady diurnal trace fires zero alerts (no false
    positives)."""
    from paddle_tpu.observability.slo import SloEvaluator, SloObjective
    from paddle_tpu.serving import FleetSim, ScalePolicy
    from tools.load_gen import make_trace

    prefill_s = measured["prefill_s"]
    token_s = max(measured["token_s"], 1e-4)
    slots, out_mean = 4, 10.0
    service_meas = prefill_s + out_mean * token_s
    k = 0.15 / service_meas
    prefill_v, token_v = prefill_s * k, token_s * k
    capacity_qps = slots / 0.15
    # base load leaves the 1-replica fleet comfortable (Poisson bursts
    # at high utilization would pre-scale the fleet and absorb the
    # flash before it ever burns); the 5x flash then hits cold
    base_qps = 0.375 * capacity_qps
    slo_ttft_s = prefill_v + 1.5
    target = 0.9

    def objective():
        # slow window 30 s: long enough that the flash's first seconds
        # barely move it — the 3 s fast window is what catches the
        # crowd, which is the whole point of the multi-window split
        return SloObjective("bench-ttft", "ttft_p99", target,
                            threshold_s=slo_ttft_s, fast_window_s=3.0,
                            fast_burn=6.0, slow_window_s=30.0,
                            slow_burn=2.0, fire_ticks=2, resolve_ticks=6,
                            min_events=4)

    def run(trace, start_replicas):
        pol = ScalePolicy(slo_ttft_s=slo_ttft_s, headroom_frac=0.4,
                          up_ticks=1, idle_ticks=8, cooldown_up_s=4.0,
                          cooldown_down_s=3.0)
        return FleetSim(pol, min_replicas=1, max_replicas=6,
                        start_replicas=start_replicas,
                        slots_per_replica=slots, prefill_s=prefill_v,
                        token_s=token_v, build_s=2.0, policy_poll_s=0.25,
                        window_s=5.0, slo_ttft_s=slo_ttft_s,
                        slo_evaluator=SloEvaluator([objective()])
                        ).run(trace)

    # a long pre-flash history makes the period attainment (the curve
    # the error budget is spent against) move slowly, which is exactly
    # why burn-rate alerts exist: the fast window reacts in seconds
    # while the compliance curve takes its time crossing the target
    flash = run(make_trace(120.0, base_qps, seed=0, flash_mult=5.0,
                           flash_at=0.75, flash_duration_s=10.0,
                           prompt_mean=12.0, out_mean=out_mean,
                           out_max=48), 1)
    slo = flash["slo"]
    firings = [t for t in slo["transitions"] if t["to"] == "firing"]
    resolves = [t for t in slo["transitions"] if t["to"] == "resolved"]
    if not firings:
        raise RuntimeError(f"slo gate: flash crowd never fired "
                           f"(transitions: {slo['transitions']})")
    breaches = [r["t"] for r in slo["attainment_series"]
                if r["attainment"] is not None
                and r["attainment"] < target]
    first_breach = breaches[0] if breaches else None
    if first_breach is not None and firings[0]["t"] >= first_breach:
        raise RuntimeError(
            f"slo gate: fast-burn fired at {firings[0]['t']} but the "
            f"period attainment crossed {target} at {first_breach} — "
            f"the alert must lead the breach")
    ups = [e for e in flash["events"] if e["direction"] == "up"]
    if not ups or not resolves or resolves[0]["t"] <= ups[0]["t"]:
        raise RuntimeError(
            f"slo gate: no resolve after absorption (ups={ups[:1]}, "
            f"resolves={resolves[:1]})")
    steady = run(make_trace(60.0, 0.3 * capacity_qps, seed=1,
                            flash_mult=1.0, prompt_mean=12.0,
                            out_mean=out_mean, out_max=48), 2)
    if steady["slo"]["fired"] != 0:
        raise RuntimeError(f"slo gate: steady diurnal fired "
                           f"{steady['slo']['fired']} false positives: "
                           f"{steady['slo']['transitions']}")
    lead_s = round(first_breach - firings[0]["t"], 3) \
        if first_breach is not None else None
    print(f"# slo fast-burn fired t={firings[0]['t']} "
          f"(lead {lead_s}s before period-attainment breach at "
          f"{first_breach}) resolved t={resolves[0]['t']} after up "
          f"t={ups[0]['t']} steady_false_positives=0", file=sys.stderr)
    return {
        "objective": objective().snapshot(),
        "flash": {"fired": slo["fired"], "resolved": slo["resolved"],
                  "first_fire_t": round(firings[0]["t"], 3),
                  "first_attainment_breach_t": first_breach,
                  "alert_lead_s": lead_s,
                  "first_up_t": round(ups[0]["t"], 3),
                  "first_resolve_t": round(resolves[0]["t"], 3),
                  "rules": sorted({t["rule"] for t in firings})},
        "steady": {"fired": 0,
                   "attainment": steady["slo_attainment"]},
        "gates": {"fires_before_attainment_breach": True,
                  "resolves_after_absorption": True,
                  "zero_false_positives": True},
    }


def _bench_gateway_curve(cfg, on_tpu, measured):
    """Latency-under-load curve through the HTTP gateway (ISSUE 8): an
    offered-QPS sweep of Poisson arrivals against a fresh engine behind
    the full front door.  Each level reports client-measured p50/p99 TTFT
    (time to the first streamed SSE chunk), token throughput, and the
    shed rate (429s from queue caps + the deadline shed model); asserts
    the decode program never retraces across the sweep."""
    import http.client
    import json as json_mod
    import threading

    import paddle_tpu as paddle
    from paddle_tpu.models import build_gpt
    from paddle_tpu.serving import Engine, EngineSupervisor
    from paddle_tpu.serving.gateway import (LoadShedder, TenantConfig,
                                            start_gateway)

    if on_tpu:
        slots, max_len, new, n_req = 8, 640, 32, 30
        qps_levels, p_len, deadline_ms = (10.0, 40.0, 160.0), 64, 2000
    else:
        slots, max_len, new, n_req = 4, 64, 6, 10
        qps_levels, p_len, deadline_ms = (2.0, 8.0, 32.0), 6, 1500

    paddle.seed(0)
    model = build_gpt(cfg)
    model.eval()
    # supervised replica (ISSUE 9): the sweep runs through the same
    # self-healing layer production would, and the kill/restart probe at
    # the end measures recovery TTFT through a supervisor rebuild
    engine = EngineSupervisor(
        lambda: Engine(model, max_slots=slots, max_len=max_len,
                       max_queue=slots),
        name="bench0", poll_interval_s=0.02)
    shedder = LoadShedder()
    shedder.seed(measured["prefill_s"], measured["token_s"])
    stack = start_gateway(
        [engine], own_engines=True, shedder=shedder,
        tenants=[TenantConfig("bench", max_queue=2 * slots)])
    curve = []
    rs = np.random.RandomState(7)
    try:
        port = stack.port
        # warm the wire path once (compiles already warm via seed model?
        # no — this is a fresh engine: the first request pays prefill +
        # decode compile; keep it out of the measured levels)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        conn.request("POST", "/v1/completions", json_mod.dumps(
            {"prompt": [3] * p_len, "max_tokens": 2}).encode(),
            {"Content-Type": "application/json", "X-Tenant": "bench"})
        assert conn.getresponse().status == 200
        conn.close()

        def one_request(prompt, out, lock):
            """Streamed request; records (ttft_s, n_tokens, status)."""
            body = json_mod.dumps({
                "prompt": prompt, "max_tokens": new, "stream": True,
                "deadline_ms": deadline_ms}).encode()
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
            t0 = time.perf_counter()
            try:
                c.request("POST", "/v1/completions", body,
                          {"Content-Type": "application/json",
                           "X-Tenant": "bench"})
                r = c.getresponse()
                if r.status != 200:
                    r.read()
                    with lock:
                        out.append((None, 0, r.status))
                    return
                ttft, n_tok = None, 0
                for line in r:
                    if not line.startswith(b"data: "):
                        continue
                    if ttft is None:
                        ttft = time.perf_counter() - t0
                    data = line[6:].strip()
                    if data == b"[DONE]":
                        break
                    n_tok += len(json_mod.loads(data)
                                 ["choices"][0]["token_ids"])
                with lock:
                    out.append((ttft, n_tok, 200))
            except Exception:  # noqa: BLE001 — count as a failed sample
                with lock:
                    out.append((None, 0, -1))
            finally:
                c.close()

        for qps in qps_levels:
            out, lock = [], threading.Lock()
            threads = []
            t_level = time.perf_counter()
            for i in range(n_req):
                prompt = [int(t) for t in
                          rs.randint(1, cfg.vocab_size, p_len)]
                th = threading.Thread(target=one_request,
                                      args=(prompt, out, lock))
                th.start()
                threads.append(th)
                time.sleep(min(rs.exponential(1.0 / qps), 0.5))
            for th in threads:
                th.join(timeout=600)
            wall = time.perf_counter() - t_level
            ttfts_ms = sorted(t * 1e3 for t, _, s in out
                              if s == 200 and t is not None)
            tokens = sum(n for _, n, _ in out)
            shed = sum(1 for _, _, s in out if s == 429)
            completed = sum(1 for _, _, s in out if s == 200)
            level = {
                "offered_qps": qps,
                "achieved_qps": round(completed / wall, 2),
                "requests": n_req, "completed": completed, "shed": shed,
                "shed_rate": round(shed / n_req, 3),
                "tokens_per_sec": round(tokens / wall, 1),
                "ttft_ms": {
                    "p50": round(float(np.percentile(ttfts_ms, 50)), 1)
                    if ttfts_ms else None,
                    "p99": round(float(np.percentile(ttfts_ms, 99)), 1)
                    if ttfts_ms else None,
                },
            }
            curve.append(level)
            print(f"# gateway qps={qps} completed={completed}/{n_req} "
                  f"shed={shed} ttft_p50="
                  f"{level['ttft_ms']['p50']}ms", file=sys.stderr)
        decode_compiles = engine.compile_stats()["decode_compiles"]
        if decode_compiles != 1:
            raise RuntimeError(
                f"gateway sweep: decode retraced "
                f"({decode_compiles} signatures)")
        shed_total = stack.gateway.stats()["tenants"].get(
            "bench", {}).get("rejected", 0)

        # -- kill/restart recovery probe (ISSUE 9): SIGKILL-equivalent
        # scheduler fault mid-load, then TTFT of the first request that
        # COMPLETES after the supervisor rebuilt the engine
        from paddle_tpu.testing import faults as _faults
        kill_restart_ttft_ms = None
        try:
            bg = [threading.Thread(
                target=one_request,
                args=([int(t) for t in rs.randint(1, cfg.vocab_size,
                                                  p_len)], [],
                      threading.Lock()))
                for _ in range(max(2, slots // 2))]
            for th in bg:
                th.start()
            _faults.arm("serving.scheduler", times=1)
            t_kill = time.perf_counter()
            deadline = t_kill + 300
            while engine.restarts < 1:
                if time.perf_counter() > deadline:
                    raise RuntimeError("kill never absorbed by a restart")
                time.sleep(0.01)
            # first completion AFTER the rebuild (429/503 are retried:
            # recovery time includes the backpressure window)
            while time.perf_counter() < deadline:
                probe, plock = [], threading.Lock()
                one_request([int(t) for t in
                             rs.randint(1, cfg.vocab_size, p_len)],
                            probe, plock)
                if probe and probe[0][2] == 200:
                    kill_restart_ttft_ms = round(
                        (time.perf_counter() - t_kill) * 1e3, 1)
                    break
                time.sleep(0.05)
            for th in bg:
                th.join(timeout=300)
            if kill_restart_ttft_ms is None:
                raise RuntimeError("no request completed after the "
                                   "mid-load engine restart")
            print(f"# gateway kill_restart_ttft={kill_restart_ttft_ms}ms "
                  f"(supervisor restarts={engine.restarts})",
                  file=sys.stderr)
        finally:
            _faults.reset()
    finally:
        stack.close()
    return {"deadline_ms": deadline_ms, "curve": curve,
            "decode_compiles": decode_compiles,
            "queue_rejected": int(shed_total),
            "kill_restart_ttft_ms": kill_restart_ttft_ms,
            "supervisor_restarts": int(engine.restarts)}


# Flagship first (its number is the driver-parsed top level); then
# PP-YOLOE (the leg the round-4 budget dropped — it must land before the
# expensive 1.3B compile); then the north-star 1.3B leg; then the smaller
# legs.  Estimated seconds per leg (compile + steps, measured on the real
# chip) gate a global budget so the bench SKIPS trailing legs instead of
# being killed mid-run with no output at all.
# estimates are COLD-cache costs (compile + steps, measured); with the
# persistent compile cache warm they overestimate ~2-4x, so the budget
# gate only sheds trailing legs on a genuinely cold host
_LEGS = [
    ("gpt2_small", bench_gpt_small, 85),
    ("ppyoloe_s", bench_ppyoloe, 130),
    ("gpt3_1p3b", bench_gpt_1p3b, 200),
    ("resnet50", bench_resnet50, 115),
    ("bert_base", bench_bert, 85),
    ("gpt_decode", bench_gpt_decode, 110),
    ("serving", bench_serving, 150),
]


def _flight_tail(n=50):
    """Last flight-recorder events for a failed/skipped leg's artifact —
    the timeline that explains WHY (round-5 weak #1: 1,501 s inside
    jax.devices() with no artifact)."""
    try:
        from paddle_tpu.observability import flight
        return flight.tail(n)
    except Exception:
        return []


def _probe_backend(timeout_s=None, retries=3):
    """Fail-fast backend probe, run BEFORE the budget clock starts: a
    bounded-timeout jax.devices() with retries.  jax.devices() is not
    interruptible, so the probe runs it on a daemon thread and gives up
    waiting after timeout_s — on persistent failure the bench emits a
    distinct backend_unavailable artifact immediately instead of burning
    the whole budget inside leg 1.  Returns (devices | None, error)."""
    import threading

    if timeout_s is None:
        timeout_s = float(os.environ.get("BENCH_PROBE_TIMEOUT_S", "120"))
    err = "unknown"
    for attempt in range(1, retries + 1):
        result = {}

        def probe():
            try:
                import jax
                result["devices"] = jax.devices()
            except Exception as e:  # noqa: BLE001
                result["error"] = f"{type(e).__name__}: {e}"

        t = threading.Thread(target=probe, daemon=True,
                             name=f"bench-backend-probe-{attempt}")
        t0 = time.perf_counter()
        t.start()
        t.join(timeout_s)
        dt = time.perf_counter() - t0
        if "devices" in result:
            if attempt > 1:
                print(f"# backend probe recovered on attempt {attempt} "
                      f"({dt:.1f}s)", file=sys.stderr)
            return result["devices"], None
        err = result.get("error",
                         f"jax.devices() still blocked after {timeout_s:.0f}s")
        print(f"# backend probe attempt {attempt}/{retries} failed after "
              f"{dt:.1f}s: {err}", file=sys.stderr)
    return None, err


def _telemetry_block():
    """Per-leg telemetry summary from the observability registry (the
    registry is reset before each leg, so these are per-leg deltas):
    compile counts + retrace warnings from the sentinel, op-dispatch
    totals, step-latency stats, peak device memory.  Appended under a new
    'telemetry' key — the existing metric schema fields are untouched."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import dispatch, retrace, steps
    reg = obs.registry()
    block = {
        "compiles": {}, "retraces": int(retrace.retrace_warning_count()),
        "op_dispatch_total": 0, "op_dispatch_eager": 0,
        "op_dispatch_traced": 0,
    }
    c = reg.get(retrace.JIT_COMPILE_TOTAL)
    if c is not None:
        for labels, v in c.series():
            block["compiles"][labels.get("fn", "?")] = int(v)
    d = reg.get(dispatch.OP_DISPATCH_TOTAL)
    if d is not None:
        for labels, v in d.series():
            block["op_dispatch_total"] += int(v)
            mode = labels.get("mode")
            if mode in ("eager", "traced"):
                block[f"op_dispatch_{mode}"] += int(v)
    h = reg.get(steps.STEP_LATENCY)
    if h is not None:
        for labels, _ in h.series():
            snap = h.snapshot(labels)
            if snap["count"]:
                block.setdefault("step_latency", {})[
                    labels.get("fn", "?")] = {
                    "count": snap["count"],
                    "mean_ms": round(1e3 * snap["sum"] / snap["count"], 3)}
    c = reg.get(steps.HOST_INPUT_WAIT)
    if c is not None:
        block["host_input_wait_s"] = round(c.total(), 4)
    c = reg.get(steps.PIPELINE_STALLS)
    if c is not None:
        block["pipeline_stalls"] = int(c.total())
    # per-leg perfscope roofline (programs that registered cost and/or
    # sampled device time this leg; empty when sampling was off)
    from paddle_tpu.observability import perfscope
    rep = perfscope.perf_report()
    if rep["programs"]:
        block["perfscope"] = {
            "sample_every": rep["sample_every"],
            "programs": {p["program"]: {
                "dispatches": p["dispatches"], "sampled": p["sampled"],
                "device_s": p["device_s"], "share": p["share"],
                "mfu": p["mfu"], "hbm_bw_frac": p["hbm_bw_frac"]}
                for p in rep["programs"]}}
    steps.record_memory_stats()  # refresh the gauges at leg end
    g = reg.get(steps.MEMORY_GAUGE)
    if g is not None:
        peak = g.value(labels={"stat": "peak_bytes_in_use"})
        if peak:
            block["peak_memory_bytes"] = int(peak)
    return block


def main() -> int:
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    flagship_only = "--flagship-only" in sys.argv
    telemetry = "--telemetry" in sys.argv
    if telemetry:
        from paddle_tpu import observability as obs
        obs.enable(True)
    # fail-fast probe BEFORE the budget clock: a wedged backend becomes a
    # distinct artifact in ~3*timeout seconds, not a silently burned budget
    devices, probe_err = _probe_backend()
    if devices is None:
        print(json.dumps({
            "metric": "gpt_flagship_failed", "value": 0.0,
            "unit": "tokens/s/chip", "vs_baseline": 0.0,
            "error": "backend_unavailable", "detail": probe_err,
            "flight_tail": _flight_tail()}))
        return 1
    # default covers the measured sum of all seven legs + headroom;
    # a tighter driver can export BENCH_BUDGET_S to shed trailing legs
    budget = float(os.environ.get("BENCH_BUDGET_S", "810"))
    start = time.perf_counter()
    legs = {}
    for key, fn, est in _LEGS:
        if flagship_only and key != "gpt2_small":
            continue
        elapsed = time.perf_counter() - start
        if elapsed + est > budget and legs:
            legs[key] = {"skipped": f"time budget ({elapsed:.0f}s elapsed "
                                    f"+ ~{est}s > {budget:.0f}s)",
                         "flight_tail": _flight_tail()}
            continue
        try:
            _reset_parallel_state()
            if telemetry:
                from paddle_tpu import observability as obs
                from paddle_tpu.observability import perfscope
                obs.registry().reset()  # per-leg deltas
                perfscope.reset_programs()
            legs[key] = fn()
        except Exception as e:  # later legs still run; the exit code says
            traceback.print_exc(file=sys.stderr)   # that this one failed
            legs[key] = {"error": f"{type(e).__name__}: {e}",
                         "flight_tail": _flight_tail()}
        finally:
            if telemetry:
                try:
                    legs[key]["telemetry"] = _telemetry_block()
                except Exception:
                    traceback.print_exc(file=sys.stderr)
            _reset_parallel_state()
            import gc
            import jax
            gc.collect()           # drop the leg's device buffers
            jax.clear_caches()     # and its compiled executables
    flagship = legs.get("gpt2_small") or {}
    line = dict(flagship) if "error" not in flagship else {
        "metric": "gpt_flagship_failed", "value": 0.0,
        "unit": "tokens/s/chip", "vs_baseline": 0.0}
    if not flagship_only:
        line["legs"] = legs
    print(json.dumps(line))
    return 1 if any("error" in leg for leg in legs.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
