"""The arithmetic of the Trinity-Large-Preview cells: parameters held and
published, model FLOPs of a prefilled and of a decoded token, the bytes of a
cached position, and the FLOPs and bytes of the three kernels a step spends
its time in (the dense pool's decode read over rings and full rows, the
prefill flash call over the window's band or the causal triangle, the
grouped expert products).

`cfg` is the configuration file's mapping (the published config.json's
keys; `num_experts` the experts HELD here, `published_num_experts` the
router's width).  Peaks and the roofline itself come from `flops.py`.
"""
from __future__ import annotations

from benchmark.flops_smallthinker import admitted_pairs


def _attn(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def layer_params(cfg: dict) -> dict:
    """Parameters of one layer by part: the attention's five projections
    (q, k, v, the output gate, o), the dense MLP, the router (its published
    width), one routed expert, the shared expert, the norms (four of the
    layer, two over head_dim inside attention)."""
    h, nq, nkv, d = _attn(cfg)
    f = cfg["moe_intermediate_size"]
    return {
        "attention": 3 * h * nq * d + 2 * h * nkv * d,
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "router": h * cfg.get("published_num_experts", cfg["num_experts"]),
        "expert": 3 * h * f,
        "shared": 3 * h * f * cfg["num_shared_experts"],
        "norms": 4 * h + 2 * d}


def _layers(cfg):
    dense = min(cfg["num_dense_layers"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def windows(cfg: dict) -> list:
    """Per built layer: its sliding window, None for a full layer."""
    return [cfg["sliding_window"] if t == "sliding_attention" else None
            for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def num_params(cfg: dict, published: bool = False) -> int:
    """All parameters held here (the layers built, the experts held, the
    rows of the vocabulary held, the untied head, the final norm), or with
    `published` the whole model's: every `published_*` key in place of its
    cut."""
    if published:
        cfg = dict(cfg, **{k[len("published_"):]: v for k, v in cfg.items()
                           if k.startswith("published_")})
    p = layer_params(cfg)
    dense, sparse = _layers(cfg)
    common = p["attention"] + p["norms"]
    return (dense * (common + p["dense_mlp"]) +
            sparse * (common + p["router"] + p["shared"] +
                      cfg["num_experts"] * p["expert"]) +
            2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def matmul_flops_per_token(cfg: dict, head: bool) -> float:
    """Forward matmul FLOPs one token needs outside its routed experts and
    its attention scores: 2 per parameter it meets here (projections and
    gate, dense MLP or router + shared expert); with `head`, the held rows
    of the LM head too (decoded tokens, and the last position of a
    prompt)."""
    p = layer_params(cfg)
    dense, sparse = _layers(cfg)
    return 2.0 * (dense * (p["attention"] + p["dense_mlp"]) +
                  sparse * (p["attention"] + p["router"] + p["shared"]) +
                  (cfg["hidden_size"] * cfg["vocab_size"] if head else 0))


def experts_flops(cfg: dict, assignments: int) -> float:
    """The three products of `assignments` token-expert pairs that landed
    on experts held here (summed over layers)."""
    return 6.0 * assignments * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _score_flops(cfg) -> float:
    """QK^T and PV per (query, key) pair, all query heads."""
    _, nq, _, d = _attn(cfg)
    return 4.0 * nq * d


def model_flops(cfg: dict, prompt_lens, decode_tokens: int,
                kv_live_positions: int, assignments: int) -> float:
    """Model FLOPs of a window: the prompts prefilled in it (every
    position's matmuls, the head once, the scores over the pairs each
    layer's mask admits: the window's band on a sliding layer, the causal
    triangle on a full one), `decode_tokens` decoded tokens whose attention
    read `kv_live_positions` live positions (summed over tokens and layers:
    a sliding layer's are bounded by its window), and the routed experts by
    the `assignments` that really landed here."""
    pre = sum(n * matmul_flops_per_token(cfg, head=False) +
              2.0 * cfg["hidden_size"] * cfg["vocab_size"] +
              _score_flops(cfg) * sum(admitted_pairs(n, w)
                                      for w in windows(cfg))
              for n in prompt_lens)
    dec = (decode_tokens * matmul_flops_per_token(cfg, head=True) +
           _score_flops(cfg) * kv_live_positions)
    return pre + dec + experts_flops(cfg, assignments)


def kv_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one position of one layer, either kind (a ring holds
    fewer positions, not smaller ones)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def decode_read_cost(cfg: dict, kv_read_window: int, kv_read_global: int,
                     rows: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of the dense pool's decode read over the
    positions it streams from the window layers' rings and from the full
    layers' rows (each summed over its layers): QK^T and PV for every query
    head, K and V of a position read once for its whole group; q in, o out
    for `rows` query rows a layer."""
    _, nq, _, d = _attn(cfg)
    kv_read = kv_read_window + kv_read_global
    nbytes = (kv_read * kv_bytes_per_position(cfg, itemsize) +
              cfg["num_hidden_layers"] * 2 * rows * nq * d * itemsize)
    return _score_flops(cfg) * kv_read, float(nbytes)


def flash_cost(heads: int, kv_heads: int, t: int, d: int, window=None,
               itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one causal flash forward call over t positions
    needs: QK^T and PV over the admitted pairs only (the window's band, or
    the lower triangle); q read and o written per query head, k and v read
    per KV head, plus the f32 logsumexp row."""
    flops = 4.0 * heads * d * admitted_pairs(t, window)
    nbytes = (2 * heads + 2 * kv_heads) * t * d * itemsize + heads * t * 4
    return flops, float(nbytes)


def experts_cost(cfg: dict, assignments: int, experts_touched: int,
                 itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of the grouped expert products of `assignments`
    token-expert pairs on held experts that touch `experts_touched` of them
    (both summed over layers): each touched expert's three matrices read
    once, the gathered rows read and the outputs written once.  The shared
    expert's products are not in it."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    nbytes = (experts_touched * 3 * h * f + assignments * (2 * h + 3 * f)
              ) * itemsize
    return experts_flops(cfg, assignments), float(nbytes)
