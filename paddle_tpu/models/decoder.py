"""A decoder-only LM whose block is driven by its configuration: RMSNorm,
grouped-query attention, per-layer position kind (RoPE or none) and window
(sliding or global), a dropless mixture of experts whose router reads the
layer's input, an untied LM head.

The first model built from it is SmallThinker-21B-A3B (PowerInfer, 2025): 52
layers in periods of [global attention without positions, 3 x 4096-token
sliding-window attention with RoPE], every layer 64 ReGLU experts of width
768, 6 per token.  The configuration uses the published `config.json`'s key
names.  One layer, x the residual stream [T, hidden], no bias anywhere:

    g = x W_r                                  router logits, from the INPUT
    a = RMSNorm(x);  q, k, v = a W_q, a W_k, a W_v
    q, k = RoPE(q, k) where rope_layout[l]     half-split pairs
    h = x + attention(q, k, v) W_o             causal; window where
                                               sliding_window_layout[l]
    x' = h + MoE(RMSNorm(h); routed by g)      softmax over the top-k logits

The second is openPangu-Ultra-MoE-718B (FreedomIntelligence, 2025): 61 layers
of multi-head latent attention (`kv_lora_rank` > 0: the queries through a
normed rank-1,536 bottleneck, keys and values through a normed rank-512
latent beside a 64-wide rotated key part every head shares; the CACHE holds
that latent, 576 numbers a position), sandwich norms (a second RMSNorm on
each sub-block's OUTPUT before the residual add), a dense SwiGLU MLP on the
first `first_k_dense_replace` layers and then 256 SwiGLU experts, 8 per
token by sigmoid scores renormalised over the chosen and scaled by 2.5,
beside one shared expert; the router reads the normed MLP input:

    a = N(x);  c_q = N(a W_qa);  [qn | qr] = c_q W_qb
    [c | kr] = a W_kva;  c = N(c);  qr, kr = RoPE(qr, kr)     cache: [c | kr]
    [kn | v] = c W_kvb;  s_h = (qn_h kn_h + qr_h kr) / sqrt(nope + rope)
    h = x + N(softmax(s) v W_o);  m = N(h)
    f = dense MLP(m)  or  sum_{e in top 8, held} p_e E_e(m) + E_shared(m)
    x' = h + N(f)

The third is Trinity-Large-Preview (Arcee, 2026; `model_type: afmoe`): 60
layers in periods of [3 x 4096-token sliding-window attention with RoPE,
global attention without positions], grouped-query heads (48 / 8 of 128)
with an RMSNorm over every query and key head (`qk_norm`) and a sigmoid
gate on the attention's output computed from the layer's normed input
(`attention_gate`), sandwich norms, a dense SwiGLU MLP on the first
`first_k_dense_replace` layers and then 256 SwiGLU experts, 4 per token,
beside one shared expert; the router scores by sigmoid, CHOOSES by score +
a selection bias (`expert_bias`: a buffer, not trained) and WEIGHS by the
score alone, renormalised over the chosen and scaled by 2.448; the
embedding is scaled by sqrt(hidden) (`embedding_scale`, muP):

    x0 = E[ids] sqrt(hidden)
    a = N(x);  q, k, v = a W_q, a W_k, a W_v;  q, k = N_q(q), N_k(k)  per head
    q, k = RoPE(q, k) on sliding layers only
    h = x + N((attention(q, k, v) * sigmoid(a W_gate)) W_o);  m = N(h)
    s = sigmoid(m W_r);  S = top 4 of (s + b);  p_e = 2.448 s_e / sum_S s
    f = dense MLP(m)  or  sum_{e in S, held} p_e E_e(m) + E_shared(m)
    x' = h + N(f)

Serving rides the same KV-cache protocol as GPT (`models/kv_cache.py`):
`DecoderForCausalLM(ids, caches=..., use_cache=True)`; the engine finds the
trunk as `.decoder` and the head as `.lm_head`.  Serving only: no dropout,
no recompute, no tensor-parallel layers; `experts_held` builds one share of
every expert layer.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..incubate.distributed.models.moe.dropless import DroplessMoE
from ..nn import functional as F
from ..nn.initializer import Constant, Normal
from ..nn.layer.common import Embedding
from ..nn.layer.container import LayerList
from ..nn.layer.norm import RMSNorm
from ..nn.layer_base import Layer, ParamAttr
from ..ops.linalg import matmul
from .kv_cache import (cache_positions, cached_attention,
                       cached_latent_attention)

_PERIOD = (0, 1, 1, 1)      # global without positions, then window + RoPE
_AFMOE_PERIOD = (1, 1, 1, 0)    # three window + RoPE, then global without


@dataclass
class DecoderConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 16384
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    # per layer: 1 = RoPE / sliding window, 0 = no positions / global; a
    # layout longer than the stack is read for the layers that are built
    rope_layout: tuple = _PERIOD * 13
    sliding_window_layout: tuple = _PERIOD * 13
    sliding_window_size: int = 4096
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    norm_topk_prob: bool = True
    initializer_range: float = 0.02
    # (first, count): build this share of every expert layer; None = all
    experts_held: tuple | None = None
    # the router: "softmax" over the top-k logits, or "sigmoid" scores
    # renormalised over the chosen (norm_topk_prob), times the scale; fed
    # from the layer's input (before attention) or from the normed MLP input
    scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0
    router_before_attention: bool = True
    hidden_act: str = "relu"            # the gate's activation: relu | silu
    n_shared_experts: int = 0           # shared experts of the experts' width
    # the first layers carry a dense gated MLP of this width, no experts
    first_k_dense_replace: int = 0
    intermediate_size: int = 0
    # one more RMSNorm on the attention's and on the MLP's output, each
    # before its residual add
    sandwich_norm: bool = False
    # latent attention where kv_lora_rank > 0 (head_dim and
    # num_key_value_heads are then not read)
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # seeded initialisation where it is not Normal(0, initializer_range) and
    # gains of 1: the embedding's standard deviation, the sandwich norms'
    # gain, the gain of the latent attention's inner norm on the queries
    # (it scales every attention score: how peaked a random model attends;
    # with qk_norm, the gain of the per-head norm on the queries)
    embedding_std: float | None = None
    sandwich_norm_gain: float = 1.0
    q_norm_gain: float = 1.0
    # afmoe: a sigmoid gate on the attention's output, from the layer's
    # normed input (W_gate: hidden -> heads x head_dim); an RMSNorm over
    # head_dim on every query and key head, before RoPE; a selection bias on
    # the sigmoid router's scores (chosen by score + bias, weighed by the
    # score; a buffer of zeros, not trained); the embedding times a scale
    attention_gate: bool = False
    qk_norm: bool = False
    expert_bias: bool = False
    embedding_scale: float = 1.0

    def __post_init__(self):
        self.rope_layout = tuple(self.rope_layout)
        self.sliding_window_layout = tuple(self.sliding_window_layout)
        n = self.num_hidden_layers
        if len(self.rope_layout) < n or len(self.sliding_window_layout) < n:
            raise ValueError(f"rope_layout / sliding_window_layout must "
                             f"cover the {n} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads are no multiple of "
                f"{self.num_key_value_heads} KV heads")
        if self.head_dim % 2 or self.qk_rope_head_dim % 2:
            raise ValueError("head_dim must be even for half-split RoPE")
        if self.kv_lora_rank and not (self.q_lora_rank and self.v_head_dim
                                      and self.qk_nope_head_dim
                                      and self.qk_rope_head_dim):
            raise ValueError("latent attention needs q_lora_rank, "
                             "qk_nope_head_dim, qk_rope_head_dim, v_head_dim")
        if self.first_k_dense_replace and not self.intermediate_size:
            raise ValueError("first_k_dense_replace needs intermediate_size")

    def window(self, layer: int):
        return (self.sliding_window_size
                if self.sliding_window_layout[layer] else None)


DECODER_CONFIGS = {
    # the published sizes (config.json of the source; 21.5 B parameters)
    "smallthinker-21b-a3b": dict(),
    # the same block at test size: one period, window 8, 8 experts top-2
    "smallthinker-tiny": dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        max_position_embeddings=128, rope_layout=_PERIOD,
        sliding_window_layout=_PERIOD, sliding_window_size=8,
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        moe_ffn_hidden_size=32),
    # openPangu-Ultra-MoE-718B as published (config.json of the source;
    # the multi-token-prediction module is not built)
    "openpangu-ultra-moe-718b": dict(
        vocab_size=153600, hidden_size=7680, num_hidden_layers=61,
        num_attention_heads=128, num_key_value_heads=128,
        max_position_embeddings=131072, rms_norm_eps=1e-5,
        rope_theta=25.6e6, rope_layout=(1,) * 61,
        sliding_window_layout=(0,) * 61, moe_num_primary_experts=256,
        moe_num_active_primary_experts=8, moe_ffn_hidden_size=2048,
        scoring_func="sigmoid", routed_scaling_factor=2.5,
        router_before_attention=False, hidden_act="silu",
        n_shared_experts=1, first_k_dense_replace=3,
        intermediate_size=18432, sandwich_norm=True, kv_lora_rank=512,
        q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128),
    # the same block at test size: 1 dense + 4 expert layers
    "openpangu-tiny": dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=5,
        num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=25.6e6,
        rope_layout=(1,) * 5, sliding_window_layout=(0,) * 5,
        moe_num_primary_experts=16, moe_num_active_primary_experts=4,
        moe_ffn_hidden_size=32, scoring_func="sigmoid",
        routed_scaling_factor=2.5, router_before_attention=False,
        hidden_act="silu", n_shared_experts=1, first_k_dense_replace=1,
        intermediate_size=128, sandwich_norm=True, kv_lora_rank=16,
        q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16),
    # Trinity-Large-Preview as published (config.json of the source; 400 B
    # parameters, 13 B active)
    "trinity-large-preview": dict(
        vocab_size=200192, hidden_size=3072, num_hidden_layers=60,
        num_attention_heads=48, num_key_value_heads=8, head_dim=128,
        max_position_embeddings=262144, rms_norm_eps=1e-5, rope_theta=1e4,
        rope_layout=_AFMOE_PERIOD * 15,
        sliding_window_layout=_AFMOE_PERIOD * 15, sliding_window_size=4096,
        moe_num_primary_experts=256, moe_num_active_primary_experts=4,
        moe_ffn_hidden_size=3072, scoring_func="sigmoid",
        routed_scaling_factor=2.448, router_before_attention=False,
        hidden_act="silu", n_shared_experts=1, first_k_dense_replace=6,
        intermediate_size=12288, sandwich_norm=True, attention_gate=True,
        qk_norm=True, expert_bias=True, embedding_scale=3072 ** 0.5),
    # the same block at test size: 1 dense + 4 expert layers, the dense
    # layer and one period [window, window, window, global]; 4 of the 16
    # experts held
    "trinity-tiny": dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=5,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        max_position_embeddings=1024, rms_norm_eps=1e-5, rope_theta=1e4,
        rope_layout=(1, 1, 1, 1, 0), sliding_window_layout=(1, 1, 1, 1, 0),
        sliding_window_size=8, moe_num_primary_experts=16,
        moe_num_active_primary_experts=4, moe_ffn_hidden_size=32,
        experts_held=(0, 4), scoring_func="sigmoid",
        routed_scaling_factor=2.448, router_before_attention=False,
        hidden_act="silu", n_shared_experts=1, first_k_dense_replace=1,
        intermediate_size=128, sandwich_norm=True, attention_gate=True,
        qk_norm=True, expert_bias=True, embedding_scale=8.0),
}


def decoder_config(name: str, **overrides) -> DecoderConfig:
    return DecoderConfig(**{**DECODER_CONFIGS[name], **overrides})


def _matrix(layer: Layer, config: DecoderConfig, shape):
    """A weight matrix of `layer`, Normal(0, initializer_range)."""
    return layer.create_parameter(shape, attr=ParamAttr(
        initializer=Normal(0.0, config.initializer_range)))


class DecoderAttention(Layer):
    """Grouped-query causal attention, with RoPE and a sliding window where
    the layer's entries of the layouts say so; with `qk_norm` an RMSNorm
    over head_dim on every query and key head, with `attention_gate` a
    sigmoid gate on the output (both from the configuration)."""

    def __init__(self, config: DecoderConfig, layer: int):
        super().__init__()
        h, d = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = d
        self.rope_theta = (float(config.rope_theta)
                           if config.rope_layout[layer] else None)
        self.window = config.window(layer)

        param = functools.partial(_matrix, self, config)
        self.q_proj = param((h, self.num_heads * d))
        self.k_proj = param((h, self.num_kv_heads * d))
        self.v_proj = param((h, self.num_kv_heads * d))
        self.o_proj = param((self.num_heads * d, h))
        self.gate_proj = (param((h, self.num_heads * d))
                          if config.attention_gate else None)
        self.qk_norm = config.qk_norm
        if self.qk_norm:
            self.q_norm = RMSNorm(
                d, epsilon=config.rms_norm_eps, weight_attr=ParamAttr(
                    initializer=Constant(config.q_norm_gain)))
            self.k_norm = RMSNorm(d, epsilon=config.rms_norm_eps)

    def forward(self, x, positions, cache=None):
        b, t = x.shape[0], x.shape[1]
        q = matmul(x, self.q_proj).reshape([b, t, self.num_heads,
                                            self.head_dim])
        k = matmul(x, self.k_proj).reshape([b, t, self.num_kv_heads,
                                            self.head_dim])
        v = matmul(x, self.v_proj).reshape([b, t, self.num_kv_heads,
                                            self.head_dim])
        if self.qk_norm:
            q, k = self.q_norm(q), self.k_norm(k)
        # the trace tells the two kinds of layer apart by these names
        with jax.named_scope("attn.window" if self.window else
                             "attn.global"):
            if self.rope_theta is not None:
                q = F.rotary_embedding(q, positions, theta=self.rope_theta)
                k = F.rotary_embedding(k, positions, theta=self.rope_theta)
            out, new_cache = cached_attention(
                q, k, v, cache, window=self.window,
                owner="models.decoder.DecoderAttention")
        out = out.reshape([b, t, self.num_heads * self.head_dim])
        if self.gate_proj is not None:
            with jax.named_scope("attn.gate"):
                out = out * F.sigmoid(matmul(x, self.gate_proj))
        return matmul(out, self.o_proj), new_cache


class LatentAttention(Layer):
    """Multi-head latent attention (module docstring): six projections and
    two inner norms; the layer's cache is the latent ``[c | kr]``, and
    `cached_latent_attention` reads it expanded (a prompt with no past) or
    absorbed (the pool)."""

    def __init__(self, config: DecoderConfig, layer: int):
        super().__init__()
        h, heads = config.hidden_size, config.num_attention_heads
        self.num_heads = heads
        self.nope, self.rope = config.qk_nope_head_dim, config.qk_rope_head_dim
        self.rank = config.kv_lora_rank
        self.rope_theta = float(config.rope_theta)
        eps = config.rms_norm_eps

        param = functools.partial(_matrix, self, config)
        self.q_a_proj = param((h, config.q_lora_rank))
        self.q_a_norm = RMSNorm(
            config.q_lora_rank, epsilon=eps, weight_attr=ParamAttr(
                initializer=Constant(config.q_norm_gain)))
        self.q_b_proj = param((config.q_lora_rank,
                               heads * (self.nope + self.rope)))
        self.kv_a_proj = param((h, self.rank + self.rope))
        self.kv_a_norm = RMSNorm(self.rank, epsilon=eps)
        self.kv_b_proj = param((self.rank,
                                heads * (self.nope + config.v_head_dim)))
        self.o_proj = param((heads * config.v_head_dim, h))

    def forward(self, x, positions, cache=None):
        b, t = x.shape[0], x.shape[1]
        q = matmul(self.q_a_norm(matmul(x, self.q_a_proj)),
                   self.q_b_proj).reshape([b, t, self.num_heads,
                                           self.nope + self.rope])
        ckr = matmul(x, self.kv_a_proj)
        c = self.kv_a_norm(ckr[:, :, :self.rank])
        kr = ckr[:, :, self.rank:].reshape([b, t, 1, self.rope])
        with jax.named_scope("attn.latent"):
            qr = F.rotary_embedding(q[:, :, :, self.nope:], positions,
                                    theta=self.rope_theta)
            kr = F.rotary_embedding(kr, positions, theta=self.rope_theta)
            latent = jnp.concatenate(
                [c._value, kr._value[:, :, 0].astype(c._value.dtype)], -1)
            out, new_cache = cached_latent_attention(
                q[:, :, :, :self.nope], qr, latent, self.kv_b_proj, cache,
                owner="models.decoder.LatentAttention")
        out = out.reshape([b, t, -1])
        return matmul(out, self.o_proj), new_cache


class GatedMLP(Layer):
    """The dense gated MLP of the leading layers: (act(m W_g) * (m W_u))
    W_d, no biases."""

    def __init__(self, config: DecoderConfig):
        super().__init__()
        h, f = config.hidden_size, config.intermediate_size
        self.act = getattr(F, config.hidden_act)

        param = functools.partial(_matrix, self, config)
        self.gate_proj, self.up_proj = param((h, f)), param((h, f))
        self.down_proj = param((f, h))

    def forward(self, m):
        with jax.named_scope("mlp.dense"):
            return matmul(self.act(matmul(m, self.gate_proj)) *
                          matmul(m, self.up_proj), self.down_proj)


class DecoderLayer(Layer):
    def __init__(self, config: DecoderConfig, layer: int):
        super().__init__()
        eps = config.rms_norm_eps
        self.input_norm = RMSNorm(config.hidden_size, epsilon=eps)
        self.self_attn = (LatentAttention if config.kv_lora_rank else
                          DecoderAttention)(config, layer)
        self.post_attn_norm = RMSNorm(config.hidden_size, epsilon=eps)
        self.sandwich = config.sandwich_norm
        if self.sandwich:
            gain = ParamAttr(initializer=Constant(config.sandwich_norm_gain))
            self.attn_out_norm = RMSNorm(config.hidden_size, epsilon=eps,
                                         weight_attr=gain)
            self.mlp_out_norm = RMSNorm(config.hidden_size, epsilon=eps,
                                        weight_attr=gain)
        self.router_before_attention = config.router_before_attention
        if layer < config.first_k_dense_replace:
            self.moe, self.mlp = None, GatedMLP(config)
            return
        self.moe = DroplessMoE(
            config.hidden_size, config.moe_ffn_hidden_size,
            config.moe_num_primary_experts,
            config.moe_num_active_primary_experts,
            norm_topk_prob=config.norm_topk_prob,
            experts_held=config.experts_held,
            scoring=config.scoring_func,
            routed_scale=config.routed_scaling_factor,
            activation=config.hidden_act,
            shared_width=config.n_shared_experts * config.moe_ffn_hidden_size,
            expert_bias=config.expert_bias,
            weight_attr=ParamAttr(
                initializer=Normal(0.0, config.initializer_range)))

    def forward(self, x, positions, cache=None):
        y, new_cache = self.self_attn(self.input_norm(x), positions, cache)
        h = x + (self.attn_out_norm(y) if self.sandwich else y)
        m = self.post_attn_norm(h)
        if self.moe is None:
            f = self.mlp(m)
        else:
            # SmallThinker's router reads the layer's input, before the
            # attention norm; the latent model's the normed MLP input
            f = self.moe(m, router_input=x if self.router_before_attention
                         else m)
        return h + (self.mlp_out_norm(f) if self.sandwich else f), new_cache


class DecoderModel(Layer):
    """Embedding, the stack, the final norm.  Output: hidden [B, T, H]."""

    def __init__(self, config: DecoderConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size, weight_attr=ParamAttr(
                initializer=Normal(0.0, config.embedding_std or
                                   config.initializer_range)))
        self.layers = LayerList([DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.final_norm = RMSNorm(config.hidden_size,
                                  epsilon=config.rms_norm_eps)

    def attention_windows(self) -> list:
        """Per layer: the sliding window its attention reads, None for a
        global layer (the serving engine bounds each layer's KV read)."""
        return [self.config.window(i) for i in range(len(self.layers))]

    def forward(self, input_ids, caches=None, use_cache=False):
        use_cache = use_cache or caches is not None
        if caches is None:
            caches = [None] * len(self.layers)
        positions = Tensor(cache_positions(caches[0], input_ids.shape[1]),
                           _internal=True)
        x = self.embed_tokens(input_ids)
        if self.config.embedding_scale != 1.0:
            x = x * self.config.embedding_scale
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, c = layer(x, positions, cache)
            new_caches.append(c)
        x = self.final_norm(x)
        return (x, new_caches) if use_cache else x


class DecoderForCausalLM(Layer):
    """The trunk and its own (untied) LM head."""

    # Engine options these layers cannot serve: refused when the engine is
    # built (serving.Engine reads this), never answered wrongly
    _UNSUPPORTED = {
        "adapters": "the LoRA banks add their delta to a fused qkv "
                    "projection, which this block does not have",
        "decode_kernel='pallas'": "the paged decode kernel reads neither "
                                  "grouped-query heads nor a sliding "
                                  "window; the dense pool's kernel does",
    }
    # ... and those a latent cache cannot: it lives on the dense,
    # unquantised pool, one row per position for all heads
    _LATENT_UNSUPPORTED = {
        "paged_kv": "a page of the paged pool holds a K and a V per head; "
                    "the latent row has no page yet (nor the host tier "
                    "that stores pages)",
        "kv_dtype='int8'": "the int8 pool quantises K and V per position "
                           "over [kv_heads, head_dim]; the latent row has "
                           "no such pair",
        "decode_kernel='pallas'": "the paged decode kernel reads K and V "
                                  "pages; the latent dense pool has its own",
    }

    @property
    def serving_unsupported(self) -> dict:
        if self.decoder.config.kv_lora_rank:
            return {**self._UNSUPPORTED, **self._LATENT_UNSUPPORTED}
        return self._UNSUPPORTED

    def __init__(self, decoder: DecoderModel):
        super().__init__()
        self.decoder = decoder
        cfg = decoder.config
        self.head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_size), attr=ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range)))

    def lm_head(self, hidden_states):
        """float32 logits, whatever the weights' type: in bfloat16 the top
        logits of a 151,936-word vocabulary (4 to 8) lie 0.03 apart, so
        near-ties collapse and the argmax picks by index."""
        return Tensor(jnp.dot(hidden_states._value, self.head._value,
                              preferred_element_type=jnp.float32),
                      _internal=True)

    def forward(self, input_ids, caches=None, use_cache=False):
        if use_cache or caches is not None:
            x, new_caches = self.decoder(input_ids, caches=caches,
                                         use_cache=True)
            return self.lm_head(x), new_caches
        return self.lm_head(self.decoder(input_ids))


def build_decoder(name_or_config="smallthinker-tiny", **overrides):
    """`build_decoder("smallthinker-21b-a3b", num_hidden_layers=8)`: a named
    preset (or a `DecoderConfig`) with overrides, as a causal LM."""
    if isinstance(name_or_config, DecoderConfig):
        cfg = (dataclasses.replace(name_or_config, **overrides)
               if overrides else name_or_config)
    else:
        cfg = decoder_config(name_or_config, **overrides)
    return DecoderForCausalLM(DecoderModel(cfg))
