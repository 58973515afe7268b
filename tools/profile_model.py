"""Profile a BASELINE.md model's train step on the real chip and print a
per-op time breakdown from the xplane trace (device durations, not host
walls — see docs/PERF.md).

Usage: python tools/profile_model.py [resnet|gpt|bert] [--steps N]
"""
from __future__ import annotations

import collections
import glob
import os
import re
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_resnet(batch=64, size=224, data_format="NCHW"):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    with nn.channels_last(data_format == "NHWC"):
        model = resnet50(num_classes=1000)
    crit = nn.CrossEntropyLoss()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters(),
                                    weight_decay=1e-4)
    step = dist.make_train_step(model, opt, loss_fn=crit,
                                compute_dtype="bfloat16")
    rng = np.random.RandomState(0)
    shape = (batch, 3, size, size) if data_format == "NCHW" \
        else (batch, size, size, 3)
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, (batch,)).astype(np.int64))
    return step, (x, y)


def _build_gpt(batch=16, seq=1024):
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import (GPTPretrainingCriterion, build_gpt,
                                   gpt_config)

    cfg = gpt_config("gpt2-small-en", max_position_embeddings=1024,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(0)
    model = build_gpt(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    step = dist.make_train_step(model, opt,
                                loss_fn=GPTPretrainingCriterion(),
                                compute_dtype="bfloat16")
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(batch, seq + 1)).astype(np.int64)
    return step, (ids[:, :-1], ids[:, 1:])


def _build_bert(batch=16, seq=512):
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import (BertPretrainingCriterion, bert_config,
                                   build_bert)

    cfg = bert_config("bert-base-uncased", hidden_dropout_prob=0.0,
                      attention_dropout_prob=0.0)
    paddle.seed(0)
    model = build_bert(cfg)
    crit = BertPretrainingCriterion()

    def loss_fn(out, labels, nsp_labels):
        mlm, nsp = out
        return crit(mlm, nsp, labels, nsp_labels)

    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = dist.make_train_step(model, opt, loss_fn=loss_fn, num_labels=2,
                                compute_dtype="bfloat16")
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    labels = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    nsp = rng.randint(0, 2, (batch,)).astype(np.int64)
    return step, (ids, labels, nsp)


def _build_ppyoloe(batch=8, size=640):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.vision.models import PPYOLOE, PPYOLOELoss

    paddle.seed(0)
    model = PPYOLOE(num_classes=80)
    loss_fn = PPYOLOELoss(model)
    opt = paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                    parameters=model.parameters(),
                                    weight_decay=5e-4)
    step = dist.make_train_step(model, opt, loss_fn=loss_fn, num_labels=2,
                                compute_dtype="bfloat16")
    rng = np.random.RandomState(0)
    x = jnp.asarray(
        rng.standard_normal((batch, 3, size, size)).astype(np.float32))
    gtb = jnp.asarray(np.stack([np.array([[4, 4, 300, 300],
                                          [64, 32, 400, 500]],
                                         "float32")] * batch))
    gtl = jnp.asarray(np.stack([np.array([1, 3], "int64")] * batch))
    return step, (x, gtb, gtl)


def profile(step, args, steps=5, outdir=None):
    import jax

    loss = step(*args)
    float(loss)  # compile + settle
    outdir = outdir or tempfile.mkdtemp(prefix="xprof_")
    with jax.profiler.trace(outdir):
        for _ in range(steps):
            loss = step(*args)
        float(loss)
    return outdir


def report(outdir, steps, top=40):
    import jax

    paths = glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, f"no xplane under {outdir}"
    data = jax.profiler.ProfileData.from_file(paths[-1])
    plane = None
    for p in data.planes:
        if "TPU" in p.name or "/device" in p.name.lower():
            plane = p
            break
    assert plane is not None, [p.name for p in data.planes]
    # ONLY the sync "XLA Ops" line is the device critical path; the
    # "Async XLA Ops" line overlaps compute (copy-start DMA engines)
    op_total = collections.Counter()
    op_count = collections.Counter()
    total = async_total = 0.0
    for line in plane.lines:
        if line.name == "Async XLA Ops":
            async_total = sum(e.duration_ns for e in line.events) / 1e6
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            dur = ev.duration_ns / 1e6
            op_total[ev.name] += dur
            op_count[ev.name] += 1
            total += dur
    print(f"device compute {total:.1f} ms over {steps} steps "
          f"-> {total / steps:.2f} ms/step "
          f"(async DMA engine-time {async_total / steps:.1f} ms/step)")
    groups = collections.Counter()
    for name, t in op_total.items():
        base = name.split(" = ")[0].lstrip("%")
        groups[re.sub(r"[.\d]+$", "", base)] += t
    print("\n-- grouped by op kind (ms/step) --")
    for name, t in groups.most_common(20):
        print(f"{t / steps:8.3f}  {name}")
    print("\n-- top single ops (ms/step) --")
    for name, t in op_total.most_common(12):
        print(f"{t / steps:8.3f}  {name[:140]}")
    return op_total


if __name__ == "__main__":
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    which = sys.argv[1] if len(sys.argv) > 1 else "resnet"
    steps = 5
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])
    fmt = "NHWC" if "--nhwc" in sys.argv else "NCHW"
    if which == "resnet":
        step, args = _build_resnet(data_format=fmt)
    elif which == "gpt":
        step, args = _build_gpt()
    elif which == "bert":
        step, args = _build_bert()
    elif which == "ppyoloe":
        step, args = _build_ppyoloe()
    else:
        raise SystemExit(f"unknown model {which}")
    t0 = time.perf_counter()
    outdir = profile(step, args, steps=steps)
    print(f"trace in {outdir} ({time.perf_counter() - t0:.1f}s wall)")
    report(outdir, steps)
