"""The plain reference for the GPT-3 ladder: float32 `jax.numpy`, matmuls at
"highest" precision, no kernels, no cache, no scan, no rematerialization.

It follows Brown et al. 2020 / the GPT-2 block as `paddle_tpu/models/gpt.py`
lays its weights out: pre-LN, fused QKV whose columns are head-major
`[heads, 3, head_dim]`, exact (erf) GELU, learned positions, LM head tied to
the word embedding, loss = mean next-token NLL.  Weights arrive as the
model's own `state_dict()` arrays in whatever type they are served in and are
cast to float32 one layer at a time, so the reference never holds a second
full copy of the model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _layer(x, p, heads: int, eps: float):
    """One decoder block on x [T, H]; p holds this layer's arrays."""
    p = {k: v.astype(_F32) for k, v in p.items()}
    t, h = x.shape
    hd = h // heads
    y = _ln(x, p["norm1.weight"], p["norm1.bias"], eps)
    qkv = (y @ p["self_attn.qkv_proj.weight"] +
           p["self_attn.qkv_proj.bias"]).reshape(t, heads, 3, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(_F32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v).reshape(t, h)
    x = x + a @ p["self_attn.out_proj.weight"] + p["self_attn.out_proj.bias"]
    y = _ln(x, p["norm2.weight"], p["norm2.bias"], eps)
    y = jax.nn.gelu(y @ p["mlp.fc0.weight"] + p["mlp.fc0.bias"],
                    approximate=False)
    return x + y @ p["mlp.fc1.weight"] + p["mlp.fc1.bias"]


@jax.jit
def _embed(ids, wte, wpe):
    return (wte[ids].astype(_F32) +
            wpe[jnp.arange(ids.shape[0])].astype(_F32))


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, rows, fw, fb, wte, eps: float):
    """Logits [len(rows), V] of the hidden rows `rows` of x [T, H]."""
    y = _ln(x[rows], fw.astype(_F32), fb.astype(_F32), eps)
    return y @ wte.astype(_F32).T


def _arrays(state: dict) -> dict:
    return {k: getattr(v, "_value", v) for k, v in state.items()}


def hidden(state: dict, ids, layers: int, heads: int, eps: float):
    """Final hidden states [T, H] (before the last LayerNorm) of one
    sequence of token ids [T]."""
    st = _arrays(state)
    x = _embed(jnp.asarray(ids), st["gpt.embeddings.word_embeddings.weight"],
               st["gpt.embeddings.position_embeddings.weight"])
    for i in range(layers):
        pre = f"gpt.layers.{i}."
        p = {k[len(pre):]: v for k, v in st.items()
             if k.startswith(pre) and not k.endswith("qkv_layout")}
        x = _layer(x, p, heads=heads, eps=eps)
    return x


def logits_at(state: dict, ids, rows, layers: int, heads: int, eps: float):
    """Reference logits [len(rows), V] at positions `rows` of the sequence
    `ids`: a full forward pass with no cache."""
    st = _arrays(state)
    with jax.default_matmul_precision("highest"):
        x = hidden(state, ids, layers, heads, eps)
        return _logits(x, jnp.asarray(rows), st["gpt.final_norm.weight"],
                       st["gpt.final_norm.bias"],
                       st["gpt.embeddings.word_embeddings.weight"], eps=eps)


@jax.jit
def _nll_sum(lg, labels):
    lse = jax.nn.logsumexp(lg, -1)
    return jnp.sum(lse - jnp.take_along_axis(lg, labels[:, None], 1)[:, 0])


def loss(state: dict, x, y, layers: int, heads: int, eps: float) -> float:
    """Mean next-token NLL of the batch x, y [B, T], one sequence at a time."""
    import numpy as np
    total = 0.0
    rows = np.arange(x.shape[1])
    for ids, labels in zip(np.asarray(x), np.asarray(y)):
        lg = logits_at(state, ids, rows, layers, heads, eps)
        total += float(_nll_sum(lg, jnp.asarray(labels)))
    return total / x.size
