"""GPT model family — the flagship decoder-only LM, TPU-first.

Parity target: the FleetX GPT-3 pretraining recipe the reference's hybrid
parallel stack exists to serve (SURVEY.md §6 north star: GPT-3 1.3B at
>=35% MFU).  The reference implements this model with fused CUDA ops
(paddle/fluid/operators/fused/fused_multi_transformer_op.cu,
fused_attention_op.cu) driven by fleet's mpu layers
(fleet/layers/mpu/mp_layers.py:39,155,293,438).  Here the same architecture is
written once in terms of:

* mpu TP layers (VocabParallelEmbedding / ColumnParallelLinear /
  RowParallelLinear) whose parameters carry PartitionSpecs — GSPMD partitions
  the matmuls over the 'mp' mesh axis;
* `scaled_dot_product_attention`, which routes to the Pallas flash-attention
  kernel on TPU (paddle_tpu/kernels/flash_attention.py) — the analog of the
  reference's fmha_ref.h, minus the S×S materialisation;
* `jax.checkpoint`-backed `recompute` for activation checkpointing
  (fleet/utils/recompute.py:350 parity);
* sequence-axis sharding constraints so long sequences can shard over a
  'sep' mesh axis (context parallelism — a TPU extension; the reference has
  none, SURVEY.md §5.7).

Everything is global-shape SPMD: no per-rank branches, no explicit p2p.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from ..distributed import mesh as mesh_mod

from ..distributed.fleet.layers.mpu.mp_layers import (
    _U,
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
    _constrain,
    _mp_info,
)
from ..distributed.fleet.utils.recompute import recompute
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Dropout, Embedding
from ..nn.layer.container import LayerList
from ..nn.layer.norm import LayerNorm
from ..nn.layer_base import Layer, ParamAttr
from ..ops.linalg import matmul


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 0  # 0 -> 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    use_recompute: bool = False
    # scan-over-layers: run the (uniform) decoder stack as ONE lax.scan
    # over stacked per-layer params.  TPU-native big-model form: compile
    # time stops scaling with depth (the body compiles once) and, with
    # use_recompute, the scan's sequential backward ENFORCES one-layer-at-
    # a-time rematerialization — the unrolled form leaves the scheduler
    # free to float recomputed forwards early (measured ~1.9 GiB/layer
    # retained on the 6.7B AOT plan, docs/PERF.md).  No reference analog
    # (its static graphs unroll).
    scan_layers: bool = False
    fuse_qkv: bool = True
    activation: str = "gelu"
    # MoE (GPT-MoE / GShard-style FFN replacement): 0 = dense FFN
    moe_num_experts: int = 0
    moe_top_k: int = 0  # 0 = the gate's own default (gshard 2, switch 1)
    moe_every_n_layers: int = 2  # every n-th block becomes MoE
    moe_capacity_factor: float = 1.2
    moe_aux_loss_weight: float = 0.01
    moe_gate: str = "gshard"
    # fused LM-head + cross-entropy: the [B,T,V] logits never materialize
    # (chunked online-logsumexp, F.fused_linear_nll_loss).  Applies ONLY
    # to the TRAINING forward (model.training and single mp) — there
    # forward returns FusedHeadOutput(hidden, tied_weight) for the
    # criterion; eval/decode forwards always return logits.  Measured
    # −10% on gpt2-small/v5e (docs/PERF.md round-5 dead ends): opt-in for
    # large-vocab / HBM-constrained regimes, default off.
    fuse_head_loss: bool = False

    def __post_init__(self):
        if not self.intermediate_size:
            self.intermediate_size = 4 * self.hidden_size


# FleetX / GPT-3 paper ladder (vocab padded to a 128 multiple for MXU tiling)
GPT_CONFIGS = {
    "gpt-tiny": dict(vocab_size=1024, hidden_size=128, num_layers=2,
                     num_attention_heads=4, max_position_embeddings=256),
    "gpt2-small-en": dict(hidden_size=768, num_layers=12,
                          num_attention_heads=12),      # 125M
    "gpt2-medium-en": dict(hidden_size=1024, num_layers=24,
                           num_attention_heads=16),     # 345M
    "gpt2-large-en": dict(hidden_size=1536, num_layers=24,
                          num_attention_heads=16),      # 760M
    "gpt3-1.3B-en": dict(hidden_size=2048, num_layers=24,
                         num_attention_heads=16,
                         max_position_embeddings=2048),
    "gpt3-2.7B-en": dict(hidden_size=2560, num_layers=32,
                         num_attention_heads=32,
                         max_position_embeddings=2048),
    "gpt3-6.7B-en": dict(hidden_size=4096, num_layers=32,
                         num_attention_heads=32,
                         max_position_embeddings=2048),
    "gpt3-13B-en": dict(hidden_size=5120, num_layers=40,
                        num_attention_heads=40,
                        max_position_embeddings=2048),
}


def gpt_config(name: str, **overrides) -> GPTConfig:
    base = dict(GPT_CONFIGS[name])
    base.update(overrides)
    return GPTConfig(**base)


def _init_attr(std: float) -> ParamAttr:
    return ParamAttr(initializer=Normal(mean=0.0, std=std))


def _activation_spec() -> P:
    """Batch over the data axes, sequence over 'sep' (context parallelism —
    _constrain drops whichever axes the live mesh lacks)."""
    return P(("dcn", "dp", "sharding"), "sep", None)


# fused-qkv column layout versions: 1 = role-major [3, nh, hd] (round-1 /
# reference fused_attention_op.cu layout), 2 = head-major [nh, 3, hd]
QKV_LAYOUT_HEAD_MAJOR = 2


class GPTSelfAttention(Layer):
    """Causal self-attention: fused QKV column-parallel projection, flash
    attention core, row-parallel output projection — the TP structure of the
    reference's fused_attention_op.cu + mp_layers.py column/row pair."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        h, nh = config.hidden_size, config.num_attention_heads
        assert h % nh == 0
        self.num_heads = nh
        self.head_dim = h // nh
        self.mp_degree = max(_mp_info()[0], 1)
        assert nh % self.mp_degree == 0, (
            f"num heads {nh} not divisible by mp degree {self.mp_degree}")
        wa = _init_attr(config.initializer_range)
        self.qkv_proj = ColumnParallelLinear(
            h, 3 * h, weight_attr=wa, has_bias=True, gather_output=False)
        # reference scales the residual-path init by 1/sqrt(2*L)
        out_std = config.initializer_range / math.sqrt(
            2.0 * config.num_layers)
        self.out_proj = RowParallelLinear(
            h, h, weight_attr=_init_attr(out_std), has_bias=True,
            input_is_parallel=True)
        self.attn_dropout_prob = config.attention_dropout_prob
        # QKV interleaving must keep each head's q,k,v on the same mp shard.
        # The fused columns are grouped HEAD-major [nh, 3, hd] (vs the
        # reference's [3, nh, hd], fused_attention_op.cu): a contiguous
        # column shard is then a set of complete (q,k,v) head triples, so
        # the same weight layout serves both the GSPMD path (constraint on
        # the nh dim) and the explicit shard_map pipeline path where the
        # local shard is reshaped directly.
        # The fused-column layout is versioned: qkv_layout==2 means
        # head-major [nh, 3, hd]. Checkpoints without the marker (round-1
        # saves, reference exports) are role-major [3, nh, hd] and are
        # permuted on load by _state_dict_compat_ below.
        self.register_buffer(
            "qkv_layout",
            Tensor(jnp.asarray(QKV_LAYOUT_HEAD_MAJOR, jnp.int32),
                   _internal=True))

    # What a checkpoint WITHOUT a qkv_layout marker means.  Markerless
    # checkpoints are ambiguous: saves made after the head-major layout
    # landed but before the marker existed are head-major, while reference
    # exports (fused_attention_op.cu) are role-major.  Head-major is the
    # default because that is what every save from this codebase since the
    # layout change contains; set to "role_major" (class-wide) before
    # set_state_dict to import reference-layout fused qkv weights.
    markerless_qkv_layout = "head_major"

    def _state_dict_compat_(self, state, prefix):
        """Migrate role-major fused-qkv checkpoints (qkv_layout marker < 2,
        or markerless with markerless_qkv_layout == "role_major") to the
        head-major column layout in place."""
        wkey = prefix + "qkv_proj.weight"
        mkey = prefix + "qkv_layout"
        if wkey not in state:
            return
        marker = state.get(mkey)
        if marker is None:
            if self.markerless_qkv_layout != "role_major":
                # assume head-major (every post-layout-change save); stamp
                # the marker so the re-saved checkpoint is unambiguous
                state[mkey] = jnp.asarray(QKV_LAYOUT_HEAD_MAJOR, jnp.int32)
                return
        elif int(np.asarray(
                marker._value if hasattr(marker, "_value") else marker)) \
                >= QKV_LAYOUT_HEAD_MAJOR:
            return
        nh_hd = self.num_heads * self.head_dim

        def _permute(arr, is_bias):
            a = np.asarray(arr._value if hasattr(arr, "_value") else arr)
            if is_bias:
                return a.reshape(3, self.num_heads, self.head_dim) \
                        .transpose(1, 0, 2).reshape(3 * nh_hd)
            h = a.shape[0]
            return a.reshape(h, 3, self.num_heads, self.head_dim) \
                    .transpose(0, 2, 1, 3).reshape(h, 3 * nh_hd)

        state[wkey] = jnp.asarray(_permute(state[wkey], False))
        bkey = prefix + "qkv_proj.bias"
        if bkey in state:
            state[bkey] = jnp.asarray(_permute(state[bkey], True))
        state[mkey] = jnp.asarray(QKV_LAYOUT_HEAD_MAJOR, jnp.int32)

    def forward(self, x, cache=None, use_cache=False, pre_norm=None):
        b, t = x.shape[0], x.shape[1]
        if pre_norm is not None:
            # fused pre-LN -> qkv projection (kernels/ln_matmul.py); bias
            # stays outside the kernel so XLA fuses it downstream
            qkv = F.fused_ln_linear(
                x, pre_norm.weight, pre_norm.bias, self.qkv_proj.weight,
                self.qkv_proj.bias, eps=pre_norm._epsilon)
        else:
            qkv = self.qkv_proj(x)  # [B, T, 3H/mp-sharded]
        if use_cache or cache is not None:
            # batched multi-LoRA serving path (serving/adapters): when the
            # engine's jit entered an adapter scope, add the per-row
            # low-rank delta gathered by each row's adapter_id from the
            # stacked banks — fixed-shape operands, so the decode program
            # keeps its ONE compiled signature; rows at id 0 gather the
            # zero adapter (delta exactly 0.0: base rows stay exact)
            from ..serving.adapters.lora import active as _lora_active
            _scope = _lora_active()
            if _scope is not None:
                from ..core.tensor import Tensor as _T
                xv = x._value if isinstance(x, Tensor) else x
                qkv = _T(qkv._value + _scope.delta_qkv(xv), _internal=True)
        # under explicit shard_map (pipeline stage bodies) the mp axis is
        # bound and qkv is the LOCAL column shard: reshape over local heads
        nh = self.num_heads
        axis = getattr(self.qkv_proj.mp_group, "axis_name", None) or "mp"
        if self.mp_degree > 1 and mesh_mod.axis_bound(axis):
            nh //= mesh_mod.bound_axis_size(axis)
        qkv = qkv.reshape([b, t, nh, 3, self.head_dim])
        qkv = _constrain(qkv, P(_U, _U, "mp", _U, _U))
        if cache is None and not use_cache:
            # fused path: ONE whole-qkv transpose (fuses into the projection
            # matmul) instead of three per-operand layout copies at the
            # flash custom-call boundary (docs/PERF.md)
            out = F.fused_qkv_attention(
                qkv, dropout_p=self.attn_dropout_prob, is_causal=True,
                training=self.training)
        else:
            # every cache form (none, growing concat, static, per-slot,
            # int8, paged) lives in models/kv_cache.py, shared with
            # models/decoder.py
            from .kv_cache import cached_attention
            q, k, v = (qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2])
            out, new_cache = cached_attention(
                q, k, v, cache, dropout_p=self.attn_dropout_prob,
                training=self.training, owner="models.gpt.GPTSelfAttention")
            out = out.reshape([b, t, nh * self.head_dim])
        out = _constrain(out, P(_U, _U, "mp"))
        out = self.out_proj(out)
        if use_cache:
            return out, new_cache
        return out


class GPTMLP(Layer):
    """Column→Row parallel FFN (reference fused_feedforward_op.cu shape)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        h, ffn = config.hidden_size, config.intermediate_size
        out_std = config.initializer_range / math.sqrt(2.0 * config.num_layers)
        self.fc0 = ColumnParallelLinear(
            h, ffn, weight_attr=_init_attr(config.initializer_range),
            has_bias=True, gather_output=False)
        self.fc1 = RowParallelLinear(
            ffn, h, weight_attr=_init_attr(out_std), has_bias=True,
            input_is_parallel=True)
        self.act = getattr(F, config.activation)

    def forward(self, x, pre_norm=None):
        if pre_norm is not None:
            h = F.fused_ln_linear(x, pre_norm.weight, pre_norm.bias,
                                  self.fc0.weight, self.fc0.bias,
                                  eps=pre_norm._epsilon)
        else:
            h = self.fc0(x)
        return self.fc1(self.act(h))


class GPTMoEMLP(Layer):
    """GShard-style FFN: the dense MLP becomes a mixture of expert MLPs with
    capacity-based token dispatch (GPT-MoE / FleetX moe recipe; backed by
    incubate MoELayer → all_to_all over the expert axis when bound).  The
    gate's balance loss is surfaced via `last_aux_loss` and folded into the
    LM loss by GPTForPretraining."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        from ..incubate.distributed.models.moe import MoELayer

        cf = config.moe_capacity_factor
        gate = {"type": config.moe_gate}
        fixed_k = {"gshard": 2, "switch": 1}.get(config.moe_gate)
        if fixed_k is None:
            gate["top_k"] = config.moe_top_k or 2
        elif config.moe_top_k not in (0, fixed_k):
            raise ValueError(
                f"moe_gate={config.moe_gate!r} requires moe_top_k={fixed_k} "
                f"(got {config.moe_top_k}); use moe_gate='naive' for other k")
        if config.moe_gate in ("gshard", "switch"):
            gate["capacity"] = (cf, 2 * cf)  # train/eval caps the gate uses
        self.moe = MoELayer(
            config.hidden_size,
            [GPTMLP(config) for _ in range(config.moe_num_experts)],
            gate=gate, capacity_factor=cf)
        self.last_aux_loss = None

    def forward(self, x):
        out = self.moe(x)
        self.last_aux_loss = self.moe.gate.get_loss()
        return out


class GPTDecoderLayer(Layer):
    """Pre-LN transformer block (the GPT-2/3 arrangement the reference's
    FusedMultiTransformer implements with normalize_before=True)."""

    def __init__(self, config: GPTConfig, use_moe: bool = False):
        super().__init__()
        eps = config.layer_norm_epsilon
        self.norm1 = LayerNorm(config.hidden_size, epsilon=eps)
        self.self_attn = GPTSelfAttention(config)
        self.norm2 = LayerNorm(config.hidden_size, epsilon=eps)
        self.mlp = GPTMoEMLP(config) if use_moe else GPTMLP(config)
        self.dropout1 = Dropout(config.hidden_dropout_prob)
        self.dropout2 = Dropout(config.hidden_dropout_prob)

    def _fuse_ln_proj(self):
        """Route the pre-LNs INTO their consuming projections (one pallas
        ln->matmul custom call per projection) when the opt-in kernel
        applies — single device, dense MLP, no KV cache."""
        from ..kernels.ln_matmul import ln_matmul_enabled
        return (ln_matmul_enabled() and self.self_attn.mp_degree <= 1
                and mesh_mod.get_global_mesh() is None
                and not isinstance(self.mlp, GPTMoEMLP))

    def forward(self, x, cache=None, use_cache=False):
        residual = x
        if not use_cache and self._fuse_ln_proj():
            y = self.self_attn(x, pre_norm=self.norm1)
            x = residual + self.dropout1(y)
            residual = x
            y = self.mlp(x, pre_norm=self.norm2)
            return residual + self.dropout2(y)
        y = self.norm1(x)
        if use_cache:
            y, new_cache = self.self_attn(y, cache=cache, use_cache=True)
        else:
            y = self.self_attn(y)
            new_cache = None
        x = residual + self.dropout1(y)
        residual = x
        y = self.mlp(self.norm2(x))
        x = residual + self.dropout2(y)
        if use_cache:
            return x, new_cache
        return x


class GPTEmbeddings(Layer):
    """Word (vocab-parallel) + learned position embeddings."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        wa = _init_attr(config.initializer_range)
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, weight_attr=wa)
        self.position_embeddings = Embedding(
            config.max_position_embeddings, config.hidden_size, weight_attr=wa)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            from ..ops.creation import arange
            t = input_ids.shape[1]
            position_ids = arange(0, t, dtype="int64").reshape([1, t])
        w = self.word_embeddings(input_ids)
        p = self.position_embeddings(position_ids)
        return self.dropout(w + p)


class GPTModel(Layer):
    """The transformer stack.  Output: hidden states [B, T, H]."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = LayerList(
            [GPTDecoderLayer(
                config,
                use_moe=(config.moe_num_experts > 0 and
                         (i + 1) % max(config.moe_every_n_layers, 1) == 0))
             for i in range(config.num_layers)])
        self.final_norm = LayerNorm(config.hidden_size,
                                    epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, caches=None,
                use_cache=False):
        use_cache = use_cache or caches is not None
        if caches is None and use_cache:
            caches = [None] * len(self.layers)
        if position_ids is None and use_cache and caches[0] is not None:
            # incremental decode: offset positions by the cached key length
            from .kv_cache import cache_positions
            position_ids = Tensor(
                cache_positions(caches[0], input_ids.shape[1]),
                _internal=True)
        x = self.embeddings(input_ids, position_ids)
        x = _constrain(x, _activation_spec())
        new_caches = [] if use_cache else None
        if self.config.scan_layers and not use_cache and \
                self.config.moe_num_experts == 0:
            x = self._scan_layers(x)
        else:
            _scope = None
            if use_cache:
                # advance the batched-adapter scope's layer cursor as the
                # stack walks (each layer gathers ITS bank slice)
                from ..serving.adapters.lora import active as _lora_active
                _scope = _lora_active()
            for i, layer in enumerate(self.layers):
                if use_cache:
                    if _scope is not None:
                        _scope.layer = i
                    x, c = layer(x, cache=caches[i], use_cache=True)
                    new_caches.append(c)
                elif self.config.use_recompute and self.training and \
                        not isinstance(layer.mlp, GPTMoEMLP):
                    # MoE layers run outside remat: the recorded gate aux
                    # loss would otherwise leak a jax.checkpoint tracer
                    x = recompute(layer, x)
                else:
                    x = layer(x)
        x = self.final_norm(x)
        if use_cache:
            return x, new_caches
        return x

    def _scan_layers(self, x):
        """Uniform decoder stack as ONE lax.scan over stacked per-layer
        params; body optionally under jax.checkpoint (see
        GPTConfig.scan_layers).  Parameters stay per-layer objects (state
        dict / checkpoint layout unchanged); the stack happens at trace
        time and autodiff routes layer grads back through it."""
        from ..core import random as random_mod
        from ..nn.functional_call import functional_call

        template = self.layers[0]
        sds = [layer.state_dict() for layer in self.layers]
        param_names = {k for k, _ in template.named_parameters()}
        stacked, static_vals = {}, {}
        for k in sds[0]:
            if k in param_names:
                stacked[k] = jnp.stack([sd[k]._value for sd in sds])
            else:
                # non-param buffers (layout markers) are identical across
                # layers; bind layer 0's
                static_vals[k] = sds[0][k]._value
        base_key = random_mod.next_key()
        xs = (jnp.arange(len(self.layers)), stacked)

        def body(h, sl):
            idx, vals = sl
            values = dict(static_vals)
            values.update(vals)
            # per-layer RNG stream (dropout masks must differ by depth)
            with random_mod.push_key(jax.random.fold_in(base_key, idx)):
                out, _ = functional_call(template, values,
                                         (Tensor(h, _internal=True),))
            return (out._value if isinstance(out, Tensor) else out), None

        if self.config.use_recompute and self.training:
            body = jax.checkpoint(body)
        h0 = x._value if isinstance(x, Tensor) else x
        h, _ = jax.lax.scan(body, h0, xs)
        return Tensor(h, _internal=True)

    def moe_aux_loss(self):
        """Sum of gate balance losses from the last forward (None when the
        model has no MoE layers, or when the last forward ran inside a
        now-finished trace — the compiled step consumes the aux loss inside
        its own program, so a stale tracer outside it is meaningless)."""
        import jax

        total = None
        try:
            for layer in self.layers:
                aux = getattr(layer.mlp, "last_aux_loss", None)
                if aux is not None:
                    total = aux if total is None else total + aux
            if total is not None:
                total._value + 0  # probe: stale tracers raise here
        except jax.errors.UnexpectedTracerError:
            return None
        return total


class FusedHeadOutput(tuple):
    """(hidden, head_weight) marker the pretraining criterion consumes via
    F.fused_linear_nll_loss — produced when config.fuse_head_loss."""

    def __new__(cls, hidden, weight):
        return super().__new__(cls, (hidden, weight))


class GPTForPretraining(Layer):
    """LM head tied to the (vocab-parallel) word embedding — logits are
    vocab-sharded over 'mp', consumed by ParallelCrossEntropy without ever
    gathering the [B,T,V] tensor (the reference's
    c_softmax_with_cross_entropy_op.cu pattern)."""

    def __init__(self, gpt: GPTModel):
        super().__init__()
        self.gpt = gpt

    def forward(self, input_ids, position_ids=None, caches=None,
                use_cache=False):
        if use_cache or caches is not None:
            x, new_caches = self.gpt(input_ids, position_ids, caches=caches,
                                     use_cache=True)
            return self.lm_head(x), new_caches
        x = self.gpt(input_ids, position_ids)
        if self.gpt.config.fuse_head_loss and self.training \
                and max(_mp_info()[0], 1) == 1:
            # hand the criterion (hidden, tied weight) instead of logits so
            # the head matmul fuses into the chunked CE (the [B,T,V]
            # tensor never exists); under mp the vocab-parallel
            # ParallelCrossEntropy path already avoids the gather.
            # Traced (functional_call) path: the weight's traced VALUE is
            # captured into a fresh Tensor — the state swap restores the
            # parameter object in place on exit, so returning the param
            # itself would hand the criterion the CONCRETE weights
            # (constant under jax.grad — the tied head grad would
            # silently vanish).  Eager path: the detached copy is the bug
            # — loss.backward() would never reach the tied table — so the
            # parameter itself rides on the tape.
            w = self.gpt.embeddings.word_embeddings.weight
            if isinstance(w._value, jax.core.Tracer):
                return FusedHeadOutput(x, Tensor(w._value, _internal=True))
            return FusedHeadOutput(x, w)
        return self.lm_head(x)

    def lm_head(self, hidden_states):
        w = self.gpt.embeddings.word_embeddings.weight
        logits = matmul(hidden_states, w, transpose_y=True)
        return _constrain(logits, P(("dcn", "dp", "sharding"), None, "mp"))

    def generate(self, input_ids, max_new_tokens: int = 32,
                 eos_token_id=None, temperature: float = 0.0, top_k: int = 0,
                 seed: int = 0, max_slots: int = 8,
                 timeout_s: float = 600.0, **engine_kwargs) -> np.ndarray:
        """Batch generation built on the continuous-batching serving engine
        (paddle_tpu.serving.Engine): each row becomes one request over a
        shared slot pool, so generation and the serving path are the SAME
        code.  Returns [batch, prompt + longest] ids; rows that stopped at
        `eos_token_id` are right-padded with it (0 when no eos is set).
        Extra keyword args reach the Engine — the decode fast-path knobs
        (``kv_dtype="int8"``, ``speculative_k=``, ``prefix_cache=``,
        ``sample_on_device=``, ``decode_kernel="pallas"`` with
        ``paged_kv=True``) apply to offline generation too."""
        from ..serving import Engine

        ids = np.asarray(input_ids._value if isinstance(input_ids, Tensor)
                         else input_ids).astype(np.int64)
        if ids.ndim == 1:
            ids = ids[None]
        b, t = ids.shape
        engine = Engine(self, max_slots=min(int(max_slots), b),
                        max_len=t + int(max_new_tokens), **engine_kwargs)
        try:
            handles = [engine.submit(row, max_new_tokens=max_new_tokens,
                                     eos_token_id=eos_token_id,
                                     temperature=temperature, top_k=top_k,
                                     seed=seed + i)
                       for i, row in enumerate(ids)]
            gen = [h.result(timeout=timeout_s) for h in handles]
        finally:
            engine.shutdown()
        width = max(len(g) for g in gen)
        pad = 0 if eos_token_id is None else int(eos_token_id)
        out = np.full((b, t + width), pad, np.int64)
        out[:, :t] = ids
        for i, g in enumerate(gen):
            out[i, t:t + len(g)] = g
        return out


class GPTPretrainingCriterion(Layer):
    """Masked next-token cross entropy (FleetX pretraining loss)."""

    def __init__(self, topo=None, ignore_index=-100):
        super().__init__()
        mp_degree = max(_mp_info()[0], 1)
        self.mp = mp_degree > 1
        self.ignore_index = ignore_index
        self.parallel_loss = (ParallelCrossEntropy(ignore_index=ignore_index)
                              if self.mp else None)

    def forward(self, prediction_scores, masked_lm_labels, loss_mask=None):
        if isinstance(prediction_scores, FusedHeadOutput):
            hidden, w = prediction_scores
            loss = F.fused_linear_nll_loss(hidden, w, masked_lm_labels,
                                           ignore_index=self.ignore_index)
        elif self.parallel_loss is not None:
            loss = self.parallel_loss(prediction_scores, masked_lm_labels)
        else:
            loss = F.fused_nll_loss(prediction_scores, masked_lm_labels,
                                    ignore_index=self.ignore_index)
        loss = loss.reshape([-1]).astype("float32")
        if loss_mask is not None:
            m = loss_mask.reshape([-1]).astype("float32")
            return (loss * m).sum() / m.sum().clip(min=1.0)
        return loss.mean()


class GPTHeadPipe(Layer):
    """Last pipeline stage: final norm + (untied) vocab-parallel LM head.
    The tied-weight head needs the embedding table on the same stage, which
    the explicit pipeline schedule can't provide — FleetX's PP GPT recipe
    likewise unties or all-reduces the shared grads (SharedLayerDesc); here
    untied.  Under mp the head column-shards the vocab dim so the [B,T,V]
    logits stay mp-sharded for ParallelCrossEntropy."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.final_norm = LayerNorm(config.hidden_size,
                                    epsilon=config.layer_norm_epsilon)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size,
            weight_attr=_init_attr(config.initializer_range),
            has_bias=False, gather_output=False)

    def forward(self, x):
        logits = self.lm_head(self.final_norm(x))
        return _constrain(logits, P(("dcn", "dp", "sharding"), None, "mp"))


def gpt_pipeline_descs(config: GPTConfig):
    """LayerDesc list for fleet.PipelineLayer — the FleetX GPT PP recipe
    shape (embeddings | N decoder layers | norm+head); a uniform decoder run
    is what the explicit GPipe schedule stacks over the pipe axis.  MoE
    configs produce their MoE layers here too (structurally non-uniform
    stages then take the one-program GSPMD pipeline path).  Recompute is a
    PipelineLayer concern: pass recompute_interval=1 to PipelineLayer when
    config.use_recompute is set."""
    from ..distributed.fleet.meta_parallel.parallel_layers.pp_layers import (
        LayerDesc)

    return ([LayerDesc(GPTEmbeddings, config)] +
            [LayerDesc(
                GPTDecoderLayer, config,
                use_moe=(config.moe_num_experts > 0 and
                         (i + 1) % max(config.moe_every_n_layers, 1) == 0))
             for i in range(config.num_layers)] +
            [LayerDesc(GPTHeadPipe, config)])


class GPTMoEPretrainingCriterion(Layer):
    """LM loss + weighted MoE gate balance loss (the GShard/GPT-MoE training
    objective).  Reads the aux loss the model recorded during ITS forward in
    the same trace, so it works eagerly and inside the compiled step."""

    def __init__(self, model, aux_loss_weight=None, ignore_index=-100):
        super().__init__()
        # read-only reference: bypass Layer registration so the criterion
        # never claims the model's parameters/state as its own
        gpt = getattr(model, "gpt", model)
        object.__setattr__(self, "_gpt", gpt)
        w = aux_loss_weight
        if w is None:
            w = getattr(gpt, "config", None)
            w = w.moe_aux_loss_weight if w is not None else 0.01
        self.aux_weight = w
        self.lm = GPTPretrainingCriterion(ignore_index=ignore_index)

    def forward(self, prediction_scores, masked_lm_labels, loss_mask=None):
        loss = self.lm(prediction_scores, masked_lm_labels, loss_mask)
        aux = self._gpt.moe_aux_loss()
        if aux is not None:
            loss = loss + self.aux_weight * aux
        return loss


def build_gpt(name_or_config="gpt-tiny", for_pretraining=True, **overrides):
    if isinstance(name_or_config, GPTConfig):
        import dataclasses
        if "hidden_size" in overrides and "intermediate_size" not in overrides:
            # let __post_init__ recompute 4*hidden instead of copying the
            # stale materialized width
            overrides["intermediate_size"] = 0
        cfg = (dataclasses.replace(name_or_config, **overrides)
               if overrides else name_or_config)
    else:
        cfg = gpt_config(name_or_config, **overrides)
    model = GPTModel(cfg)
    if for_pretraining:
        return GPTForPretraining(model)
    return model


def gpt_num_params(cfg: GPTConfig) -> int:
    h, L, V, T = (cfg.hidden_size, cfg.num_layers, cfg.vocab_size,
                  cfg.max_position_embeddings)
    per_layer = 4 * h * h + 4 * h + 2 * h * cfg.intermediate_size \
        + cfg.intermediate_size + h + 4 * h  # attn + mlp + 2 LN
    return V * h + T * h + L * per_layer + 2 * h


def gpt_train_flops_per_token(cfg: GPTConfig, seq_len: int) -> float:
    """6*N + 12*L*h*s — the standard train-MFU accounting (fwd+bwd = 3x fwd;
    fwd matmuls = 2*N per token; the 12*L*h*s attention term already carries
    the 3x and the QK^T+AV pair)."""
    return (6.0 * gpt_num_params(cfg) +
            12.0 * cfg.num_layers * cfg.hidden_size * seq_len)
