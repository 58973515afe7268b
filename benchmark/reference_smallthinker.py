"""The plain reference for SmallThinker-21B-A3B (PowerInfer, 2025;
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct): float32
`jax.numpy`, matmuls at "highest" precision, no kernels, no cache, no
batching, no sort and no gather in the expert layer.

One layer, x the residual stream [T, hidden], every projection without bias:

    g = x W_r                                router logits from the layer's
                                             INPUT, before the norm
    a = RMSNorm(x);  q, k, v = a W_q, a W_k, a W_v
    q, k = RoPE(q, k) on rope_layout[l] = 1  half-split pairs, theta 1.5e6
    mask = causal, and t_q - t_k < window on sliding_window_layout[l] = 1
    h = x + softmax(q k^T / sqrt(head_dim) + mask) v W_o
                                             query head i reads KV head i // group
    p = softmax over the top-k of g, 0 elsewhere
    x' = h + sum_e p_e (relu(m W_gate^e) * (m W_up^e)) W_down^e,  m = RMSNorm(h)

and logits = RMSNorm(x_L) W_head with an untied head.  EVERY expert is
applied to EVERY token and weighted by p (zero outside the top k), in a loop
over the stacked experts: one shape, ~E/k times the routed FLOPs, and no
routing machinery to share a fault with the system under test.

Departures from the published description, each an assumption the
configuration file lists under `assumed` (the published config.json has no
key for them):
  * the gate's activation is ReLU ("sparse ReGLU");
  * no biases anywhere, no query/key norm, attention scale 1/sqrt(head_dim);
  * the router reads the un-normed layer input ("router placed before
    attention"; the model's public inference code does the same);
  * RoPE pairs dimension i with i + head_dim/2;
  * the card's "secondary experts" have no key in config.json and are left
    out: the config is trusted.

Weights arrive as the model's own `state_dict()` arrays in whatever type
they are served in; a layer's attention weights are cast to float32 when the
layer runs, an expert's when the loop reaches it, the head's a block of
columns at a time, so the reference never holds a second copy of the model.
Attention runs over blocks of queries so that a 14k-token request fits
beside the serving pool (`[heads, block, T]` scores at a time).

`cfg` is a mapping with the published config.json's keys (the benchmark's
configuration file, or `dataclasses.asdict(DecoderConfig)`).

The control.  With `cfg["reference_weights"] == "int8"` every weight matrix
is rounded to 8-bit integers as it is cast (one scale per output channel,
per row of the embedding; norms stay as they are): the same forward pass in
the nearest precision below the served bfloat16.  A check of the served
model has to read this control as NOT correct (`serve_decoder_driver.py`
computes it on the rows it checks, in every run).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HEAD_COLS = 16384          # head columns cast to float32 at a time


def _w(v, weights, axis=-2):
    """The weight array `v` in float32; with `weights == "int8"` rounded to
    255 levels, one scale for each index of every axis but `axis` (the
    input axis of a matrix product)."""
    a = v.astype(_F32)
    if weights is None:
        return a
    if weights != "int8":
        raise ValueError(f"reference_weights={weights!r}: None or 'int8'")
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30) / 127
    return jnp.round(a / s) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """x [T, heads, d] at positions 0..T-1; dimension i pairs with i + d/2."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=_F32) / half))
    ang = jnp.arange(x.shape[0], dtype=_F32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "top_k", "eps", "theta", "window", "norm_topk",
    "block", "weights"))
def _layer(x, p, *, heads, kv_heads, top_k, eps, theta, window, norm_topk,
           block, weights):
    """One decoder block on x [T, H]; p holds this layer's arrays.  `theta`
    None: no positions; `window` None: global attention."""
    t = x.shape[0]
    g = x @ _w(p["moe.w_router"], weights)                       # [T, E]
    a = _rms(x, p["input_norm.weight"].astype(_F32), eps)
    q = (a @ _w(p["self_attn.q_proj"], weights)).reshape(t, heads, -1)
    k = (a @ _w(p["self_attn.k_proj"], weights)).reshape(t, kv_heads, -1)
    v = (a @ _w(p["self_attn.v_proj"], weights)).reshape(t, kv_heads, -1)
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    d = q.shape[-1]
    q = q.reshape(t, kv_heads, heads // kv_heads, d)
    tk = jnp.arange(t)

    def attend(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)      # [b,kv,g,d]
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k) / jnp.sqrt(_F32(d))
        tq = start + jnp.arange(block)
        keep = tk[None, :] <= tq[:, None]
        if window is not None:
            keep &= tq[:, None] - tk[None, :] < window
        s = jnp.where(keep, s, -jnp.inf)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(attend, jnp.arange(0, t, block)).reshape(t, heads * d)
    h = x + o @ _w(p["self_attn.o_proj"], weights)
    m = _rms(h, p["post_attn_norm.weight"].astype(_F32), eps)
    top, idx = jax.lax.top_k(g, top_k)
    w = (jax.nn.softmax(top, -1) if norm_topk else
         jnp.take_along_axis(jax.nn.softmax(g, -1), idx, -1))
    # [T, E]: the routing weight of every expert, zero outside the top k
    dense = jnp.zeros_like(g).at[jnp.arange(t)[:, None], idx].set(w)

    def expert(y, e):
        wg, wu, wd, pe = e
        act = (jax.nn.relu(m @ _w(wg, weights)) * (m @ _w(wu, weights)))
        return y + pe[:, None] * (act @ _w(wd, weights)), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["moe.w_gate"], p["moe.w_up"], p["moe.w_down"],
                         dense.T))
    return h + y


@functools.partial(jax.jit, static_argnames=("weights",))
def _embed(ids, wte, weights):
    return _w(wte[ids], weights, axis=-1)


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
    with jax.default_matmul_precision("highest"):
        x = _embed(ids, st["decoder.embed_tokens.weight"],
                   cfg.get("reference_weights"))
        for i in range(cfg["num_hidden_layers"]):
            pre = f"decoder.layers.{i}."
            p = {k[len(pre):]: v for k, v in st.items() if k.startswith(pre)}
            x = _layer(
                x, p, heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                top_k=cfg["moe_num_active_primary_experts"],
                eps=float(cfg["rms_norm_eps"]),
                theta=(float(cfg["rope_theta"]) if cfg["rope_layout"][i]
                       else None),
                window=(int(cfg["sliding_window_size"])
                        if cfg["sliding_window_layout"][i] else None),
                norm_topk=bool(cfg["norm_topk_prob"]), block=block,
                weights=cfg.get("reference_weights"))
    return x


def head_logits(state: dict, x, rows, cfg):
    """Logits [len(rows), V] of the rows `rows` of the hidden states x."""
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
