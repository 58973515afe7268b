"""The arithmetic of the openPangu-Ultra-MoE cells: parameters held and
published, model FLOPs of a prefilled and of a decoded token, the bytes of a
cached position, and the FLOPs and bytes of the three kernels a step spends
its time in (the latent pool's decode read, the prefill flash call at two
head sizes, the grouped expert products).

`cfg` is the configuration file's mapping (the published config.json's
keys; `n_routed_experts` the experts HELD here, `published_n_routed_experts`
the router's width).  Peaks and the roofline itself come from `flops.py`.
"""
from __future__ import annotations


def _attn(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def layer_params(cfg: dict) -> dict:
    """Parameters of one layer by part: the six attention projections, the
    dense MLP, the router (its published width), one routed expert, the
    shared expert, the norms (four of the layer, two inside attention)."""
    h, nq, rq, rkv, nope, rope, vd = _attn(cfg)
    f = cfg["moe_intermediate_size"]
    return {
        "attention": (h * rq + rq * nq * (nope + rope) + h * (rkv + rope) +
                      rkv * nq * (nope + vd) + nq * vd * h),
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "router": h * cfg.get("published_n_routed_experts",
                              cfg["n_routed_experts"]),
        "expert": 3 * h * f,
        "shared": 3 * h * f * cfg["n_shared_experts"],
        "norms": (4 if cfg.get("sandwich_norm") else 2) * h + rq + rkv}


def _layers(cfg):
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def num_params(cfg: dict, published: bool = False) -> int:
    """All parameters held here (the layers built, the experts held, the
    rows of the vocabulary held, the untied head, the final norm), or with
    `published` the whole model's: every `published_*` key in place of its
    cut (the multi-token-prediction module not counted)."""
    if published:
        cfg = dict(cfg, **{k[len("published_"):]: v for k, v in cfg.items()
                           if k.startswith("published_")})
    p = layer_params(cfg)
    dense, sparse = _layers(cfg)
    common = p["attention"] + p["norms"]
    return (dense * (common + p["dense_mlp"]) +
            sparse * (common + p["router"] + p["shared"] +
                      cfg["n_routed_experts"] * p["expert"]) +
            2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def matmul_flops_per_token(cfg: dict, head: bool) -> float:
    """Forward matmul FLOPs one token needs outside its routed experts and
    its attention scores: 2 per parameter it meets here (projections, dense
    MLP or router + shared expert); with `head`, the held rows of the LM
    head too (decoded tokens, and the last position of a prompt)."""
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
    """QK^T at nope + rope and PV at v, per (query, key) pair, all heads."""
    _, nq, _, _, nope, rope, vd = _attn(cfg)
    return 2.0 * nq * (nope + rope + vd)


def model_flops(cfg: dict, prompt_lens, decode_tokens: int,
                kv_live_positions: int, assignments: int) -> float:
    """Model FLOPs of a window: the prompts prefilled in it (every
    position's matmuls, the head once, the scores over the causal pairs in
    every layer), `decode_tokens` decoded tokens whose attention read
    `kv_live_positions` live positions (summed over tokens and layers), and
    the routed experts by the `assignments` that really landed here.  The
    expanded form's sizes count for decoded tokens too: the work the model
    needs, not what the absorbed read executes."""
    layers = cfg["num_hidden_layers"]
    pre = sum(n * matmul_flops_per_token(cfg, head=False) +
              2.0 * cfg["hidden_size"] * cfg["vocab_size"] +
              layers * _score_flops(cfg) * n * (n + 1) / 2.0
              for n in prompt_lens)
    dec = (decode_tokens * matmul_flops_per_token(cfg, head=True) +
           _score_flops(cfg) * kv_live_positions)
    return pre + dec + experts_flops(cfg, assignments)


def latent_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    """What one position of one layer stores: [c | kr]."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def latent_read_cost(cfg: dict, kv_read: int, rows: int,
                     itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of the latent pool's decode read over `kv_read`
    streamed positions (summed over layers) in the absorbed form it runs:
    every head scores against the whole latent row (rank + rope) and sums
    its first rank columns; a row is read once for all heads; q in and o
    out for `rows` query rows a layer."""
    _, nq, _, rkv, _, rope, _ = _attn(cfg)
    flops = 2.0 * nq * (2 * rkv + rope) * kv_read
    nbytes = (kv_read * latent_bytes_per_position(cfg, itemsize) +
              cfg["num_hidden_layers"] * rows * nq * (2 * rkv + rope)
              * itemsize)
    return flops, float(nbytes)


def flash_cost(heads: int, t: int, d: int, dv: int,
               itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one causal flash forward call over t positions
    needs with queries and keys of `d` and values of `dv`: QK^T and PV over
    the lower triangle; q, k, v read and o written per head, plus the f32
    logsumexp row."""
    flops = 2.0 * heads * (d + dv) * t * (t + 1) / 2.0
    nbytes = heads * t * (2 * d + 2 * dv) * itemsize + heads * t * 4
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
