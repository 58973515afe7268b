"""The plain reference for openPangu-Ultra-MoE-718B (FreedomIntelligence,
2025; https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B):
float32 `jax.numpy`, matmuls at "highest" precision, the EXPANDED form of
latent attention throughout, no kernels, no cache, no absorption, no
batching, no sort and no gather in the expert layer.

One layer, x the residual stream [T, hidden], no bias anywhere, N(.; w) =
RMSNorm with eps 1e-5:

    a          = N(x; w_in)
    c_q        = N(a W_qa; w_q)
    [qn | qr]  = c_q W_qb              per head: nope 128 | rope 64
    [c | kr]   = a W_kva               512 | 64; kr is ONE key part for all heads
    c          = N(c; w_kv)
    qr, kr     = RoPE(qr, kr)          half-split pairs, theta 25.6e6
    [kn | v]   = c W_kvb               per head: 128 | 128
    s_h        = (qn_h kn_h + qr_h kr) / sqrt(192) + causal mask
    h          = x + N(softmax(s) v W_o; w_attn_out)          sandwich norm
    m          = N(h; w_pre_mlp)
    l <  first_k_dense_replace:  f = (silu(m W_g) * (m W_u)) W_d
    l >= first_k_dense_replace:  g = sigmoid(m W_r)
                                 S = top 8 of g; p_e = 2.5 g_e / (sum_S g + 1e-20)
                                 f = sum_{e in S, e held} p_e E_e(m) + E_shared(m)
    x'         = h + N(f; w_mlp_out)                           sandwich norm

and logits = N(x_L; w_f) W_head over the rows of the vocabulary that are held.
EVERY HELD expert is applied to EVERY token and weighted by p (zero outside
the top 8): one shape and no routing machinery to share a fault with the
system under test.  Experts that are not held add nothing, in the program
and here alike (`model-configs` section 4: the chip's share; what the absent
experts would have added is left out of the partial result that goes on).

Departures from the published description, each an assumption the
configuration file lists under `assumed` (config.json has no key for them):
  * scores are a sigmoid of the router logits, no groups, no selection bias
    (`scoring_func`; the family's convention);
  * `sandwich_norm` = one RMSNorm on the attention's output and one on the
    MLP's, each before its residual add, beside the two pre-norms;
  * RoPE pairs dimension i with i + 32 of the 64;
  * the attention scale is 1/sqrt(192), no mscale (no `rope_scaling`);
  * the shared expert's width is n_shared_experts x moe_intermediate_size;
  * the multi-token-prediction module (layer 62) is not part of a plain
    forward pass and is left out.

`cfg` is the configuration file's mapping (the published config.json's keys;
`n_routed_experts` the experts HELD, `published_n_routed_experts` the
router's width, `deployment.experts_first` the first held).  Keys a control
may set (`serve_latent_driver.py` runs each as a system of its own on the
rows it checks): `reference_weights: "int8"` rounds every weight matrix to
8-bit integers as it is cast (the nearest precision below the served
bfloat16); `rope_theta: null` (no RoPE), `attention_scale` (a number in
place of 1/sqrt(192)), `num_experts_per_tok`, `n_shared_experts: 0`,
`routed_scaling_factor`, `scoring_func: "softmax"`, `sandwich_norm_skip:
"attn" | "mlp"` are faults that the check has to read as NOT correct.

Memory.  Weights arrive as the model's own `state_dict()` arrays in the
type they are served in and are cast a matrix (an expert, a block of
columns) at a time.  An 11k-token request must fit beside 13 GB of weights
and pool, so nothing of size T x heads x head_dim exists: attention runs
over groups of heads (their q, k, v made inside the loop) and blocks of
queries, the dense MLP over blocks of its columns, the head over blocks of
the vocabulary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference_smallthinker import _rms, _rope, _w

_F32 = jnp.float32
_HEAD_COLS = 4096           # head columns cast to float32 at a time
_MLP_COLS = 2048            # dense MLP columns computed at a time
_HEAD_GROUP = 8             # attention heads computed at a time


def _rope_or_not(x, theta):
    return x if theta is None else _rope(x, theta)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "vdim", "eps", "theta", "scale", "block",
    "skip", "weights"))
def _attention(x, p, *, heads, nope, rope, vdim, eps, theta, scale, block,
               skip, weights):
    """h = x + N(attention(N(x))): the first half of a layer on x [T, H]."""
    t = x.shape[0]
    rank = p["self_attn.kv_a_norm.weight"].shape[0]
    a = _rms(x, p["input_norm.weight"].astype(_F32), eps)
    cq = _rms(a @ _w(p["self_attn.q_a_proj"], weights),
              p["self_attn.q_a_norm.weight"].astype(_F32), eps)
    ckr = a @ _w(p["self_attn.kv_a_proj"], weights)
    c = _rms(ckr[:, :rank], p["self_attn.kv_a_norm.weight"].astype(_F32), eps)
    kr = _rope_or_not(ckr[:, None, rank:], theta)[:, 0]           # [T, rope]
    g = min(_HEAD_GROUP, heads)
    wq = p["self_attn.q_b_proj"].reshape(-1, heads // g, g, nope + rope)
    wkv = p["self_attn.kv_b_proj"].reshape(rank, heads // g, g, nope + vdim)
    wo = p["self_attn.o_proj"].reshape(heads // g, g * vdim, -1)
    tk = jnp.arange(t)

    def group(y, ws):
        wq_g, wkv_g, wo_g = ws
        q = jnp.einsum("tr,rgd->tgd", cq, _w(wq_g, weights, axis=0))
        kv = jnp.einsum("tc,cgd->tgd", c, _w(wkv_g, weights, axis=0))
        qn, qr = q[..., :nope], _rope_or_not(q[..., nope:], theta)
        kn, v = kv[..., :nope], kv[..., nope:]

        def attend(start):
            sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                                   start_index=start, slice_size=block)
            s = (jnp.einsum("qgd,tgd->gqt", sl(qn), kn) +
                 jnp.einsum("qgd,td->gqt", sl(qr), kr)) * scale
            keep = tk[None, :] <= (start + jnp.arange(block))[:, None]
            s = jnp.where(keep, s, -jnp.inf)
            return jnp.einsum("gqt,tgd->qgd", jax.nn.softmax(s, -1), v)

        o = jax.lax.map(attend, jnp.arange(0, t, block)).reshape(t, -1)
        return y + o @ _w(wo_g, weights), None

    y, _ = jax.lax.scan(group, jnp.zeros_like(x),
                        (wq.transpose(1, 0, 2, 3), wkv.transpose(1, 0, 2, 3),
                         wo))
    if skip != "attn":
        y = _rms(y, p["attn_out_norm.weight"].astype(_F32), eps)
    return x + y


def _glu(m, wg, wu, wd, weights):
    return (jax.nn.silu(m @ _w(wg, weights)) * (m @ _w(wu, weights))) @ \
        _w(wd, weights)


@functools.partial(jax.jit, static_argnames=("eps", "skip", "weights"))
def _dense_mlp(h, p, *, eps, skip, weights):
    """x' = h + N(MLP(N(h))) on a leading layer, a block of the MLP's
    columns at a time."""
    m = _rms(h, p["post_attn_norm.weight"].astype(_F32), eps)
    f = p["mlp.gate_proj"].shape[1]
    cols = _MLP_COLS if f % _MLP_COLS == 0 else f

    def chunk(i, y):
        cut = functools.partial(jax.lax.dynamic_slice_in_dim,
                                start_index=i * cols, slice_size=cols)
        return y + _glu(m, cut(p["mlp.gate_proj"], axis=1),
                        cut(p["mlp.up_proj"], axis=1),
                        cut(p["mlp.down_proj"], axis=0), weights)

    y = jax.lax.fori_loop(0, f // cols, chunk, jnp.zeros_like(h))
    if skip != "mlp":
        y = _rms(y, p["mlp_out_norm.weight"].astype(_F32), eps)
    return h + y


@functools.partial(jax.jit, static_argnames=(
    "top_k", "eps", "first", "norm_topk", "scoring", "routed_scale",
    "shared", "skip", "weights"))
def _expert_mlp(h, p, *, top_k, eps, first, norm_topk, scoring, routed_scale,
                shared, skip, weights):
    """x' = h + N(held experts + shared expert) on an expert layer."""
    t = h.shape[0]
    m = _rms(h, p["post_attn_norm.weight"].astype(_F32), eps)
    logits = m @ _w(p["moe.w_router"], weights)                  # [T, E]
    if scoring == "sigmoid":
        top, idx = jax.lax.top_k(jax.nn.sigmoid(logits), top_k)
        w = top / (top.sum(-1, keepdims=True) + 1e-20) if norm_topk else top
    else:
        top, idx = jax.lax.top_k(logits, top_k)
        w = (jax.nn.softmax(top, -1) if norm_topk else
             jnp.take_along_axis(jax.nn.softmax(logits, -1), idx, -1))
    # [T, E]: the routing weight of every expert, zero outside the top k
    dense = jnp.zeros_like(logits).at[jnp.arange(t)[:, None], idx].set(
        w * routed_scale)
    held = p["moe.w_gate"].shape[0]

    def expert(y, e):
        wg, wu, wd, pe = e
        return y + pe[:, None] * _glu(m, wg, wu, wd, weights), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["moe.w_gate"], p["moe.w_up"], p["moe.w_down"],
                         dense.T[first:first + held]))
    if shared:
        y = y + _glu(m, p["moe.shared_gate"], p["moe.shared_up"],
                     p["moe.shared_down"], weights)
    if skip != "mlp":
        y = _rms(y, p["mlp_out_norm.weight"].astype(_F32), eps)
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
    weights, eps = cfg.get("reference_weights"), float(cfg["rms_norm_eps"])
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    theta = cfg.get("rope_theta")
    skip = cfg.get("sandwich_norm_skip")
    with jax.default_matmul_precision("highest"):
        x = _embed(ids, st["decoder.embed_tokens.weight"], weights)
        for i in range(cfg["num_hidden_layers"]):
            pre = f"decoder.layers.{i}."
            p = {k[len(pre):]: v for k, v in st.items() if k.startswith(pre)}
            h = _attention(
                x, p, heads=cfg["num_attention_heads"], nope=nope, rope=rope,
                vdim=cfg["v_head_dim"], eps=eps,
                theta=None if theta is None else float(theta),
                scale=float(cfg.get("attention_scale") or
                            (nope + rope) ** -0.5),
                block=block, skip=skip, weights=weights)
            if i < cfg["first_k_dense_replace"]:
                x = _dense_mlp(h, p, eps=eps, skip=skip, weights=weights)
            else:
                x = _expert_mlp(
                    h, p, top_k=cfg["num_experts_per_tok"], eps=eps,
                    first=int(cfg.get("deployment", {}).get(
                        "experts_first", 0)),
                    norm_topk=bool(cfg["norm_topk_prob"]),
                    scoring=cfg.get("scoring_func", "sigmoid"),
                    routed_scale=float(cfg["routed_scaling_factor"]),
                    shared=bool(cfg["n_shared_experts"]), skip=skip,
                    weights=weights)
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
