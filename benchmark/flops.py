"""The yardstick's arithmetic: peaks, model FLOPs, kernel FLOPs and bytes.

Kept with the benchmark so that no later PR can move a utilization or a
roofline share by changing how work is counted.  `gpt_num_params` and
`gpt_train_flops_per_token` are copies of the functions of the same name in
`paddle_tpu/models/gpt.py` (PERF.md lists the originals for deletion).
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

# the sizes a configuration file states and the drivers hand to `gpt_config`
GPT_SIZE_KEYS = ("vocab_size", "hidden_size", "num_layers",
                 "num_attention_heads", "intermediate_size",
                 "max_position_embeddings")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, keyed by `device_kind`.  A device that
    is not in `peaks.json` is an error, never a default.  The CPU of the
    rehearsal cells has no row and no number: None, and every reader that
    needs a peak then leaves its metric out."""
    if device_kind == "cpu":
        return None
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peak row for device kind {device_kind!r} in "
                       f"benchmark/peaks.json (has {sorted(table)})")
    return table[device_kind]


def gpt_num_params(m: dict) -> int:
    """Parameters of a GPT-3-style decoder; `m` holds hidden_size,
    num_layers, vocab_size, max_position_embeddings, intermediate_size."""
    h, L, V, T = (m["hidden_size"], m["num_layers"], m["vocab_size"],
                  m["max_position_embeddings"])
    ffn = m.get("intermediate_size") or 4 * h
    per_layer = 4 * h * h + 4 * h + 2 * h * ffn + ffn + h + 4 * h
    return V * h + T * h + L * per_layer + 2 * h


def gpt_train_flops_per_token(m: dict, seq_len: int) -> float:
    """6N + 12*L*h*s: forward and backward as the algorithm requires them.
    Recomputed forward work (use_recompute) is NOT counted."""
    return (6.0 * gpt_num_params(m) +
            12.0 * m["num_layers"] * m["hidden_size"] * seq_len)


def flash_fwd_cost(bh: int, tq: int, tk: int, d: int, causal: bool = True,
                   itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one flash-attention forward call needs: QK^T and
    PV are 2*tq*tk*d FLOPs each per (batch, head); a causal call needs the
    lower triangle only, taken as half.  Bytes: q, k, v read once, the
    output written once, plus the f32 logsumexp row."""
    flops = 4.0 * bh * tq * tk * d * (0.5 if causal else 1.0)
    nbytes = bh * (2 * tq * d + 2 * tk * d) * itemsize + bh * tq * 4
    return flops, float(nbytes)


def flash_bwd_cost(bh: int, tq: int, tk: int, d: int, causal: bool = True,
                   itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one flash backward call: five tq*tk*d
    contractions (scores again, dP, dV, dQ, dK), so 2.5x the forward.
    Bytes: q, k, v, o, do read, dq, dk, dv written, plus lse and delta."""
    flops = 10.0 * bh * tq * tk * d * (0.5 if causal else 1.0)
    nbytes = bh * (4 * tq * d + 4 * tk * d) * itemsize + 2 * bh * tq * 4
    return flops, float(nbytes)


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Roofline: the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s, and which of the two it is."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
