"""Dropless mixture of experts: every assignment is computed, whatever the
load.

`MoELayer` (moe_layer.py) is the GShard shape: a fixed capacity per expert,
tokens over it dropped, experts a Python list of layers.  A served model
whose logits must match its reference cannot drop, so this layer keeps the
experts as stacked arrays ``[experts, hidden, width]``, sorts the
``tokens x top_k`` assignments by expert and multiplies each expert's run of
rows with `jax.lax.ragged_dot` (grouped matrix products; XLA:TPU lowers them
to a grouped-matmul kernel from 128 rows on, so the rows are padded to a
multiple of 128).  Routing weights are float32.

``experts_held=(first, count)``: the layer routes over ALL experts and
computes the part of the output that its own experts ``first .. first +
count - 1`` contribute; the parts of the shares add up to the whole layer.
Nothing here stands in for absent shares.

Load counters.  Inside `collect_load()` every layer call appends
``[assignments, experts touched, largest expert load]`` (int32) for its own
experts; `LoadCollector.total()` sums them over the layers of one traced
program, so the serving engine returns them with the tokens of a step.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp

from .....core.op import defop
from .....nn import initializer as I
from .....nn.layer_base import Layer

_ROW_MULTIPLE = 128     # XLA:TPU's grouped-matmul kernel needs >= 128 rows
_TOKEN_CHUNK = 8192     # tokens whose assignments are permuted at a time
_TLS = threading.local()


class LoadCollector:
    """What the MoE layers of one traced program counted.  `valid` ([B, T]
    or [B] bool, or None for all) says which tokens are real: padding and
    idle rows route like any token but are left out of the counts."""

    def __init__(self, valid=None):
        self.valid = valid
        self.parts: list = []

    def total(self):
        """int32 [3]: assignments, experts touched and largest expert load,
        each summed over the layers; None when no MoE layer ran."""
        return jnp.sum(jnp.stack(self.parts), axis=0) if self.parts else None


@contextlib.contextmanager
def collect_load(valid=None):
    prev = getattr(_TLS, "collector", None)
    _TLS.collector = LoadCollector(valid)
    try:
        yield _TLS.collector
    finally:
        _TLS.collector = prev


def route_top_k(logits, top_k: int, norm_topk_prob: bool = True):
    """float32 routing: (weights [T, k], experts [T, k]).  With
    `norm_topk_prob` the weights are the softmax over the k chosen logits
    (= softmax over all experts, top k, renormalised); without it the
    softmax over all experts at the chosen ones."""
    logits = logits.astype(jnp.float32)
    top, idx = jax.lax.top_k(logits, top_k)
    if norm_topk_prob:
        return jax.nn.softmax(top, axis=-1), idx
    return jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, -1), idx


@defop
def dropless_moe(x, router_input, w_router, w_gate, w_up, w_down, top_k,
                 first=0, norm_topk_prob=True, name=None):
    """y = sum over the top-k experts e of p_e * (relu(x W_gate^e) *
    (x W_up^e)) W_down^e for every token of x [..., hidden]; the router
    reads `router_input` [..., hidden].  `w_gate`/`w_up` [E_held, hidden,
    width] and `w_down` [E_held, width, hidden] are the experts `first ..
    first + E_held - 1` of the `w_router.shape[1]` routed over."""
    lead, h = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, h)
    n_tok, held, n_all = x2.shape[0], w_gate.shape[0], w_router.shape[1]
    with jax.named_scope("moe.route"):
        logits = jnp.dot(router_input.reshape(-1, h), w_router,
                         preferred_element_type=jnp.float32)
        p, idx = route_top_k(logits, top_k, norm_topk_prob)
        _count_load(idx, lead, first, held)

    def experts(args):
        xc, pc, ic = args                       # [n, h], [n, k], [n, k]
        n = xc.shape[0] * top_k
        with jax.named_scope("moe.route"):
            # assignments sorted by expert; those of experts held elsewhere
            # sort behind the last group and are multiplied by nothing
            local = ic.reshape(-1) - first
            mine = (local >= 0) & (local < held)
            key = jnp.where(mine, local, held)
            order = jnp.argsort(key, stable=True)
            sizes = _counts(key, held)
            rows = jnp.pad(xc[order // top_k],
                           ((0, (-n) % _ROW_MULTIPLE), (0, 0)))
        with jax.named_scope("moe.experts"):
            act = (jax.nn.relu(jax.lax.ragged_dot(rows, w_gate, sizes)) *
                   jax.lax.ragged_dot(rows, w_up, sizes))
            out = jax.lax.ragged_dot(act, w_down, sizes)[:n]
            if held != n_all:
                out = jnp.where(mine[order][:, None], out, 0)
            # back to token order, weighted and summed in float32
            out = out[jnp.argsort(order)].reshape(-1, top_k, h)
            return jnp.sum(out.astype(jnp.float32) * pc[:, :, None],
                           axis=1).astype(x.dtype)

    if n_tok > _TOKEN_CHUNK and n_tok % _TOKEN_CHUNK == 0:
        # a long prefill: the permuted rows and their products of one chunk
        # at a time (each is tokens x top_k x hidden)
        y = jax.lax.map(experts, tuple(
            a.reshape((-1, _TOKEN_CHUNK) + a.shape[1:])
            for a in (x2, p, idx)))
    else:
        y = experts((x2, p, idx))
    return y.reshape(lead + (h,))


def _counts(keys, n: int):
    """int32 [n]: how many of `keys` equal 0 .. n - 1 (others are counted
    nowhere).  A compare-and-sum: `jnp.bincount` is a scatter-add, which the
    TPU runs an element at a time (0.090 of the 0.415 s that three prefills
    of 14,336 tokens took on a v5e)."""
    return jnp.sum(keys[:, None] == jnp.arange(n, dtype=keys.dtype)[None, :],
                   axis=0, dtype=jnp.int32)


def _count_load(idx, lead, first, held):
    """Append this layer's counts to the active collector: `idx` [T, k] the
    experts chosen, `lead` the tokens' leading shape."""
    col = getattr(_TLS, "collector", None)
    if col is None:
        return
    mine = (idx >= first) & (idx < first + held)
    if col.valid is not None:
        valid = col.valid.reshape(col.valid.shape +
                                  (1,) * (len(lead) - col.valid.ndim))
        mine &= jnp.broadcast_to(valid, lead).reshape(-1, 1)
    load = _counts(jnp.where(mine, idx - first, held).reshape(-1), held)
    col.parts.append(jnp.stack([jnp.sum(load), jnp.sum(load > 0),
                                jnp.max(load)]).astype(jnp.int32))


class DroplessMoE(Layer):
    """Gated (ReGLU) expert FFN with a softmax top-k router and no capacity:
    `forward(x, router_input=None)`; the router reads `router_input`
    (default x).  Parameters: `w_router` [hidden, experts] and the
    stacked `w_gate`, `w_up` [held, hidden, width], `w_down` [held, width,
    hidden].  No biases."""

    def __init__(self, hidden_size: int, expert_width: int, num_experts: int,
                 top_k: int, norm_topk_prob: bool = True, experts_held=None,
                 weight_attr=None):
        super().__init__()
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and count >= 1 and first + count <= num_experts):
            raise ValueError(f"experts_held={experts_held} lies outside the "
                             f"{num_experts} experts")
        if top_k > num_experts:
            raise ValueError(f"top_k={top_k} exceeds {num_experts} experts")
        self.top_k, self.norm_topk_prob = int(top_k), bool(norm_topk_prob)
        self.experts_held = (int(first), int(count))
        h, f = int(hidden_size), int(expert_width)

        def param(shape):
            return self.create_parameter(
                shape, attr=weight_attr,
                default_initializer=I.Normal(mean=0.0, std=0.02))

        self.w_router = param((h, int(num_experts)))
        self.w_gate = param((count, h, f))
        self.w_up = param((count, h, f))
        self.w_down = param((count, f, h))

    def forward(self, x, router_input=None):
        return dropless_moe(
            x, x if router_input is None else router_input, self.w_router,
            self.w_gate, self.w_up, self.w_down, top_k=self.top_k,
            first=self.experts_held[0], norm_topk_prob=self.norm_topk_prob)
