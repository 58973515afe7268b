"""The plain reference for Trinity-Large-Preview (Arcee, 2026; `model_type:
afmoe`; https://huggingface.co/arcee-ai/Trinity-Large-Preview): float32
`jax.numpy`, matmuls at "highest" precision, no kernels, no cache, no ring,
full-length masks, no batching, no sort and no gather in the expert layer.

One layer, x the residual stream [T, hidden], no bias on any product,
N(.; w) = RMSNorm with eps 1e-5:

    x0   = E[ids] * sqrt(hidden)                  mup_enabled
    a    = N(x; w_in);  q, k, v = a W_q, a W_k, a W_v        48 / 8 / 8 heads
    q, k = N(q; w_qn), N(k; w_kn)                 over head_dim, per head
    q, k = RoPE(q, k)                             half-split pairs, theta 1e4,
                                                  on sliding_attention layers
                                                  ONLY; a full_attention
                                                  layer has no positions
    mask = causal, and t_q - t_k < sliding_window on sliding layers
    o    = softmax(q k^T / sqrt(head_dim) + mask) v          query head i
                                                  reads KV head i // group
    h    = x + N((o * sigmoid(a W_gate)) W_o; w_attn_out)    sandwich norm
    m    = N(h; w_pre_mlp)
    l <  num_dense_layers:  f = (silu(m W_g) * (m W_u)) W_d
    l >= num_dense_layers:  s = sigmoid(m W_r);  S = top 4 of (s + b)
                            p_e = route_scale s_e / (sum_S s + 1e-20)
                            f = sum_{e in S, e held} p_e E_e(m) + E_shared(m)
    x'   = h + N(f; w_mlp_out)                               sandwich norm

and logits = N(x_L; w_f) W_head over the rows of the vocabulary that are
held.  EVERY HELD expert is applied to EVERY token and weighted by p (zero
outside the top 4): one shape and no routing machinery to share a fault with
the system under test.  Experts that are not held add nothing, in the
program and here alike (`model-configs` section 4: the chip's share; what
the absent experts would have added is left out of the partial result that
goes on).

Departures from the published description, each an assumption the
configuration file lists under `assumed` (config.json has no key for them
beyond `described_as`):
  * the output gate, the per-head q/k norms, "no positions on full layers",
    the four-norm order and the `+ 1e-20` follow the family's public
    modelling code;
  * the selection bias b (`expert_bias`) is read from the state and is
    zeros as built (the trained one is not in config.json): chosen by
    s + b, weighed by s;
  * RoPE pairs dimension i with i + 64 of the 128;
  * the shared expert's width is num_shared_experts x moe_intermediate_size.

`cfg` is the configuration file's mapping (the published config.json's keys;
`num_experts` the experts HELD, `published_num_experts` the router's width,
`deployment.experts_first` the first held).  Keys a control may set
(`serve_afmoe_driver.py` runs each as a system of its own on the rows it
checks): `reference_weights: "int8"` rounds every weight matrix to 8-bit
integers as it is cast (the nearest precision below the served bfloat16);
`attention_gate: false`, `qk_norm: false`, `rope_on_full: true`,
`sliding_window` (another number), `bias_in_weights: true` (weigh by s + b),
`route_scale`, `num_shared_experts: 0`, `mup_enabled: false`,
`sandwich_norm_skip: "attn" | "mlp"` are faults that the check has to read
as NOT correct.

Memory.  Weights arrive as the model's own `state_dict()` arrays in the
type they are served in and are cast a matrix (an expert, a KV head's
group) at a time.  A 30k-token request must fit beside 11 GB of weights and
pool, so nothing of size T x heads x head_dim exists: attention runs over
one KV head's group of query heads at a time (its q, k, v and gate made
inside the loop) and blocks of queries, both MLPs over blocks of tokens,
the head over blocks of the vocabulary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference_smallthinker import _rms, _rope, _w

_F32 = jnp.float32
_HEAD_COLS = 4096           # head columns cast to float32 at a time
_TOKENS = 2048              # tokens an MLP computes at a time


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "theta", "window", "block", "gate",
    "qk_norm", "skip", "weights"))
def _attention(x, p, *, heads, kv_heads, eps, theta, window, block, gate,
               qk_norm, skip, weights):
    """h = x + N(gated attention(N(x))): the first half of a layer on x
    [T, H].  `theta` None: no positions; `window` None: global."""
    t = x.shape[0]
    g = heads // kv_heads
    a = _rms(x, p["input_norm.weight"].astype(_F32), eps)
    d = p["self_attn.q_proj"].shape[1] // heads
    tk = jnp.arange(t)

    def cols(w, n):             # [H, kv x n x d] -> [kv, H, n x d]
        return w.reshape(w.shape[0], kv_heads, n * d).transpose(1, 0, 2)

    def group(y, ws):
        wq, wk, wv, wg, wo = ws
        q = (a @ _w(wq, weights)).reshape(t, g, d)
        k = (a @ _w(wk, weights)).reshape(t, 1, d)
        v = a @ _w(wv, weights)                                  # [T, d]
        if qk_norm:
            q = _rms(q, p["self_attn.q_norm.weight"].astype(_F32), eps)
            k = _rms(k, p["self_attn.k_norm.weight"].astype(_F32), eps)
        if theta is not None:
            q, k = _rope(q, theta), _rope(k, theta)
        k = k[:, 0]

        def attend(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block)  # [b, g, d]
            s = jnp.einsum("qgd,td->gqt", qb, k) / jnp.sqrt(_F32(d))
            tq = start + jnp.arange(block)
            keep = tk[None, :] <= tq[:, None]
            if window is not None:
                keep &= tq[:, None] - tk[None, :] < window
            s = jnp.where(keep, s, -jnp.inf)
            return jnp.einsum("gqt,td->qgd", jax.nn.softmax(s, -1), v)

        o = jax.lax.map(attend, jnp.arange(0, t, block)).reshape(t, g * d)
        if gate:
            o = o * jax.nn.sigmoid(a @ _w(wg, weights))
        return y + o @ _w(wo, weights), None

    wq = cols(p["self_attn.q_proj"], g)
    wg = cols(p["self_attn.gate_proj"], g) if gate else jnp.zeros_like(wq)
    y, _ = jax.lax.scan(group, jnp.zeros_like(x), (
        wq, cols(p["self_attn.k_proj"], 1), cols(p["self_attn.v_proj"], 1),
        wg, p["self_attn.o_proj"].reshape(kv_heads, g * d, -1)))
    if skip != "attn":
        y = _rms(y, p["attn_out_norm.weight"].astype(_F32), eps)
    return x + y


def _glu(m, wg, wu, wd, weights):
    return (jax.nn.silu(m @ _w(wg, weights)) * (m @ _w(wu, weights))) @ \
        _w(wd, weights)


def _by_tokens(f, m):
    """f over blocks of `_TOKENS` rows of m [T, H] (T a multiple of them,
    or one block)."""
    n = _TOKENS if m.shape[0] % _TOKENS == 0 else m.shape[0]
    return jax.lax.map(f, m.reshape(-1, n, m.shape[1])).reshape(m.shape)


@functools.partial(jax.jit, static_argnames=("eps", "skip", "weights"))
def _dense_mlp(h, p, *, eps, skip, weights):
    """x' = h + N(MLP(N(h))) on a leading layer."""
    m = _rms(h, p["post_attn_norm.weight"].astype(_F32), eps)
    y = _by_tokens(lambda mb: _glu(mb, p["mlp.gate_proj"], p["mlp.up_proj"],
                                   p["mlp.down_proj"], weights), m)
    if skip != "mlp":
        y = _rms(y, p["mlp_out_norm.weight"].astype(_F32), eps)
    return h + y


@functools.partial(jax.jit, static_argnames=(
    "top_k", "eps", "first", "norm_topk", "routed_scale", "shared",
    "bias_in_weights", "skip", "weights"))
def _expert_mlp(h, p, *, top_k, eps, first, norm_topk, routed_scale, shared,
                bias_in_weights, skip, weights):
    """x' = h + N(held experts + shared expert) on an expert layer."""
    m = _rms(h, p["post_attn_norm.weight"].astype(_F32), eps)
    held = p["moe.w_gate"].shape[0]

    def block(mb):
        n = mb.shape[0]
        s = jax.nn.sigmoid(mb @ _w(p["moe.w_router"], weights))   # [n, E]
        biased = s + p["moe.expert_bias"].astype(_F32)
        _, idx = jax.lax.top_k(biased, top_k)
        w = jnp.take_along_axis(biased if bias_in_weights else s, idx, -1)
        if norm_topk:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        # [n, E]: the routing weight of every expert, zero outside the top k
        dense = jnp.zeros_like(s).at[jnp.arange(n)[:, None], idx].set(
            w * routed_scale)

        def expert(y, e):
            wg, wu, wd, pe = e
            return y + pe[:, None] * _glu(mb, wg, wu, wd, weights), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(mb), (
            p["moe.w_gate"], p["moe.w_up"], p["moe.w_down"],
            dense.T[first:first + held]))
        if shared:
            y = y + _glu(mb, p["moe.shared_gate"], p["moe.shared_up"],
                         p["moe.shared_down"], weights)
        return y

    y = _by_tokens(block, m)
    if skip != "mlp":
        y = _rms(y, p["mlp_out_norm.weight"].astype(_F32), eps)
    return h + y


@functools.partial(jax.jit, static_argnames=("weights", "scale"))
def _embed(ids, wte, weights, scale):
    return _w(wte[ids], weights, axis=-1) * scale


@functools.partial(jax.jit, static_argnames=("eps", "weights"))
def _head_block(x, rows, fw, head, eps, weights):
    return _rms(x[rows], fw.astype(_F32), eps) @ _w(head, weights)


def _arrays(state: dict) -> dict:
    return {k: getattr(v, "_value", v) for k, v in state.items()}


def hidden(state: dict, ids, cfg, block: int = 256):
    """Final hidden states [T, H] (before the last norm) of one sequence of
    token ids [T]; T must be a multiple of `block` (right padding is
    causal: pad, then read the rows you need)."""
    st = _arrays(state)
    ids = jnp.asarray(ids)
    if ids.shape[0] % block:
        raise ValueError(f"{ids.shape[0]} tokens are no multiple of the "
                         f"query block {block}")
    weights, eps = cfg.get("reference_weights"), float(cfg["rms_norm_eps"])
    skip = cfg.get("sandwich_norm_skip")
    scale = (float(cfg["hidden_size"]) ** 0.5
             if cfg.get("mup_enabled", True) else 1.0)
    with jax.default_matmul_precision("highest"):
        x = _embed(ids, st["decoder.embed_tokens.weight"], weights, scale)
        for i in range(cfg["num_hidden_layers"]):
            pre = f"decoder.layers.{i}."
            p = {k[len(pre):]: v for k, v in st.items() if k.startswith(pre)}
            sliding = cfg["layer_types"][i] == "sliding_attention"
            h = _attention(
                x, p, heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], eps=eps,
                theta=(float(cfg["rope_theta"])
                       if sliding or cfg.get("rope_on_full") else None),
                window=int(cfg["sliding_window"]) if sliding else None,
                block=block, gate=bool(cfg.get("attention_gate", True)),
                qk_norm=bool(cfg.get("qk_norm", True)), skip=skip,
                weights=weights)
            if i < cfg["num_dense_layers"]:
                x = _dense_mlp(h, p, eps=eps, skip=skip, weights=weights)
            else:
                x = _expert_mlp(
                    h, p, top_k=cfg["num_experts_per_tok"], eps=eps,
                    first=int(cfg.get("deployment", {}).get(
                        "experts_first", 0)),
                    norm_topk=bool(cfg["route_norm"]),
                    routed_scale=float(cfg["route_scale"]),
                    shared=bool(cfg["num_shared_experts"]),
                    bias_in_weights=bool(cfg.get("bias_in_weights")),
                    skip=skip, weights=weights)
    return x


def head_logits(state: dict, x, rows, cfg):
    """Logits [len(rows), V] of the rows `rows` of the hidden states x, over
    the rows of the vocabulary that are held (the head's own width)."""
    st = _arrays(state)
    head, rows = st["head"], jnp.asarray(rows)
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate([
            _head_block(x, rows, st["decoder.final_norm.weight"],
                        head[:, c:c + _HEAD_COLS],
                        eps=float(cfg["rms_norm_eps"]),
                        weights=cfg.get("reference_weights"))
            for c in range(0, head.shape[1], _HEAD_COLS)], axis=1)


def logits_at(state: dict, ids, rows, cfg, block: int = 256):
    """Reference logits [len(rows), V] at positions `rows` of the sequence
    `ids`: a full forward pass with no cache."""
    return head_logits(state, hidden(state, ids, cfg, block), rows, cfg)
