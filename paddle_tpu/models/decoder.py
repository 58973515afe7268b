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

Serving rides the same KV-cache protocol as GPT (`models/kv_cache.py`):
`DecoderForCausalLM(ids, caches=..., use_cache=True)`; the engine finds the
trunk as `.decoder` and the head as `.lm_head`.  Serving only: no dropout,
no recompute, no tensor-parallel layers; `experts_held` builds one share of
every expert layer.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..incubate.distributed.models.moe.dropless import DroplessMoE
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Embedding
from ..nn.layer.container import LayerList
from ..nn.layer.norm import RMSNorm
from ..nn.layer_base import Layer, ParamAttr
from ..ops.linalg import matmul
from .kv_cache import cache_positions, cached_attention

_PERIOD = (0, 1, 1, 1)      # global without positions, then window + RoPE


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
        if self.head_dim % 2:
            raise ValueError("head_dim must be even for half-split RoPE")

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
}


def decoder_config(name: str, **overrides) -> DecoderConfig:
    return DecoderConfig(**{**DECODER_CONFIGS[name], **overrides})


class DecoderAttention(Layer):
    """Grouped-query causal attention, with RoPE and a sliding window where
    the layer's entries of the layouts say so."""

    def __init__(self, config: DecoderConfig, layer: int):
        super().__init__()
        h, d = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = d
        self.rope_theta = (float(config.rope_theta)
                           if config.rope_layout[layer] else None)
        self.window = config.window(layer)

        def param(shape):
            return self.create_parameter(shape, attr=ParamAttr(
                initializer=Normal(0.0, config.initializer_range)))

        self.q_proj = param((h, self.num_heads * d))
        self.k_proj = param((h, self.num_kv_heads * d))
        self.v_proj = param((h, self.num_kv_heads * d))
        self.o_proj = param((self.num_heads * d, h))

    def forward(self, x, positions, cache=None):
        b, t = x.shape[0], x.shape[1]
        q = matmul(x, self.q_proj).reshape([b, t, self.num_heads,
                                            self.head_dim])
        k = matmul(x, self.k_proj).reshape([b, t, self.num_kv_heads,
                                            self.head_dim])
        v = matmul(x, self.v_proj).reshape([b, t, self.num_kv_heads,
                                            self.head_dim])
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
        return matmul(out, self.o_proj), new_cache


class DecoderLayer(Layer):
    def __init__(self, config: DecoderConfig, layer: int):
        super().__init__()
        eps = config.rms_norm_eps
        self.input_norm = RMSNorm(config.hidden_size, epsilon=eps)
        self.self_attn = DecoderAttention(config, layer)
        self.post_attn_norm = RMSNorm(config.hidden_size, epsilon=eps)
        self.moe = DroplessMoE(
            config.hidden_size, config.moe_ffn_hidden_size,
            config.moe_num_primary_experts,
            config.moe_num_active_primary_experts,
            norm_topk_prob=config.norm_topk_prob,
            experts_held=config.experts_held,
            weight_attr=ParamAttr(
                initializer=Normal(0.0, config.initializer_range)))

    def forward(self, x, positions, cache=None):
        y, new_cache = self.self_attn(self.input_norm(x), positions, cache)
        h = x + y
        # the router reads the layer's input, before the attention norm
        return h + self.moe(self.post_attn_norm(h), router_input=x), new_cache


class DecoderModel(Layer):
    """Embedding, the stack, the final norm.  Output: hidden [B, T, H]."""

    def __init__(self, config: DecoderConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size, weight_attr=ParamAttr(
                initializer=Normal(0.0, config.initializer_range)))
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
    serving_unsupported = {
        "adapters": "the LoRA banks add their delta to a fused qkv "
                    "projection, which this block does not have",
        "decode_kernel='pallas'": "the paged decode kernel reads neither "
                                  "grouped-query heads nor a sliding "
                                  "window; the dense pool's kernel does",
    }

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
