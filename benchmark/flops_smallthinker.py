"""The arithmetic of the SmallThinker cells: parameters, model FLOPs per
token, the bytes of a decode step given the experts it touched, and the
FLOPs and bytes of the three kernels a step spends its time in (the grouped
expert products, the dense pool's decode read, the prefill flash call).

`cfg` is the configuration file's mapping (the published config.json's
keys).  Peaks and the roofline itself come from `flops.py`.
"""
from __future__ import annotations


def _sizes(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_ffn_hidden_size"], cfg["moe_num_primary_experts"],
            cfg["moe_num_active_primary_experts"])


def layer_params(cfg: dict) -> dict:
    """Parameters of one layer by part: attention projections, router,
    norms, one expert, all experts."""
    h, nq, nkv, d, f, e, _ = _sizes(cfg)
    return {"attention": 2 * h * nq * d + 2 * h * nkv * d, "router": h * e,
            "norms": 2 * h, "expert": 3 * h * f, "experts": 3 * h * f * e}


def num_params(cfg: dict) -> int:
    """All parameters held: the layers that are built, the embedding, the
    untied head and the final norm."""
    p = layer_params(cfg)
    per_layer = p["attention"] + p["router"] + p["norms"] + p["experts"]
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * per_layer + 2 * v * h + h


def matmul_flops_per_token(cfg: dict, head: bool) -> float:
    """Forward matmul FLOPs one token needs in the layers: the attention
    projections, the router and its top-k experts (2 per parameter it
    meets); with `head`, the LM head too (decoded tokens, and the last
    position of a prompt)."""
    p = layer_params(cfg)
    k = cfg["moe_num_active_primary_experts"]
    per_layer = p["attention"] + p["router"] + k * p["expert"]
    return 2.0 * (cfg["num_hidden_layers"] * per_layer +
                  (cfg["hidden_size"] * cfg["vocab_size"] if head else 0))


def admitted_pairs(t: int, window=None) -> float:
    """(query, key) pairs of a causal score matrix over t positions: the
    lower triangle, or with a window the band of `window` keys per query."""
    if window is None or t <= window:
        return t * (t + 1) / 2.0
    return window * (window + 1) / 2.0 + (t - window) * float(window)


def windows(cfg: dict) -> list:
    """Per built layer: its sliding window, None for a global layer."""
    return [cfg["sliding_window_size"] if on else None
            for on in cfg["sliding_window_layout"][:cfg["num_hidden_layers"]]]


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Model FLOPs of prefilling one prompt: every position's matmuls, the
    head once, and QK^T + PV over the pairs each layer's mask admits."""
    _, nq, _, d, *_ = _sizes(cfg)
    attn = sum(4.0 * nq * d * admitted_pairs(prompt_len, w)
               for w in windows(cfg))
    return (prompt_len * matmul_flops_per_token(cfg, head=False) +
            2.0 * cfg["hidden_size"] * cfg["vocab_size"] + attn)


def decode_flops(cfg: dict, tokens: int, kv_live_positions: int) -> float:
    """Model FLOPs of `tokens` decoded tokens whose attention read
    `kv_live_positions` live KV positions, summed over tokens and layers
    (the engine's `decode_kv_live_positions`)."""
    _, nq, _, d, *_ = _sizes(cfg)
    return (tokens * matmul_flops_per_token(cfg, head=True) +
            4.0 * nq * d * kv_live_positions)


def kv_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one position of one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def experts_cost(cfg: dict, assignments: int, experts_touched: int,
                 itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of the grouped expert products of
    `assignments` token-expert pairs that touch `experts_touched` experts
    (both summed over layers): three products per pair; each touched
    expert's three matrices read once, the permuted rows read and the
    outputs written once."""
    h, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    flops = 6.0 * assignments * h * f
    nbytes = (experts_touched * 3 * h * f + assignments * (2 * h + 3 * f)
              ) * itemsize
    return flops, float(nbytes)


def decode_step_bytes(cfg: dict, experts_touched: int, kv_read: int,
                      itemsize: int = 2) -> float:
    """HBM bytes one decode step must read: the touched experts, every
    layer's attention weights, router and norms, the head, and `kv_read`
    KV positions (summed over layers)."""
    p = layer_params(cfg)
    fixed = (cfg["num_hidden_layers"] * (p["attention"] + p["router"] +
                                         p["norms"]) +
             cfg["hidden_size"] * cfg["vocab_size"])
    return float((fixed + experts_touched * p["expert"]) * itemsize +
                 kv_read * kv_bytes_per_position(cfg, itemsize))


def decode_read_cost(cfg: dict, kv_read: int, rows: int,
                     itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of the dense pool's decode read over `kv_read`
    streamed positions (summed over layers): QK^T and PV for every query
    head, K and V of each position read once for its whole group; q in, o
    out for `rows` query rows."""
    _, nq, _, d, *_ = _sizes(cfg)
    flops = 4.0 * nq * d * kv_read
    nbytes = (kv_read * kv_bytes_per_position(cfg, itemsize) +
              2 * rows * nq * d * itemsize)
    return flops, float(nbytes)


def flash_cost(heads: int, kv_heads: int, t: int, d: int, window=None,
               itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one causal flash forward call over t positions
    needs: QK^T and PV over the admitted pairs only (the lower triangle, or
    the window's band); q read and o written per query head, k and v read
    per KV head, plus the f32 logsumexp row."""
    flops = 4.0 * heads * d * admitted_pairs(t, window)
    nbytes = (2 * heads + 2 * kv_heads) * t * d * itemsize + heads * t * 4
    return flops, float(nbytes)
