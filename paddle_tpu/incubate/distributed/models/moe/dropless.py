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
count - 1`` contribute; the parts of the shares add up to the whole layer
(a shared expert, which every share computes alike, counted once).  A share
pays for its own assignments only: they sort to the front, and a loop takes
them `share_rows` at a time -- a bounded buffer of gathered rows, as many
turns as the load needs, so nothing is dropped.  Nothing here stands in for
absent shares.

Routing: softmax over the top-k logits (`scoring="softmax"`), or sigmoid
scores, top-k over the scores, renormalised over the chosen and scaled
(`scoring="sigmoid"`, `routed_scale`); with `expert_bias` the sigmoid router
CHOOSES by score + a per-expert selection bias (a buffer, not trained; the
load balancer of the afmoe family moves it between steps of training) and
WEIGHS by the score alone.  The gate's activation is ReLU or
SiLU.  `shared_width` adds one gated expert that every token meets.

Load counters.  Inside `collect_load()` every layer call appends
``[assignments, experts touched, largest expert load, routed]`` (int32):
the first three for its own experts, `routed` the real tokens x top-k
whoever holds the expert (= assignments where every expert is held);
`LoadCollector.total()` sums them over the layers of one traced program, so
the serving engine returns them with the tokens of a step.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp

from .....core.op import defop
from .....core.tensor import Tensor
from .....nn import initializer as I
from .....nn.layer_base import Layer

_ROW_MULTIPLE = 128     # XLA:TPU's grouped-matmul kernel needs >= 128 rows
_TOKEN_CHUNK = 8192     # tokens whose assignments are permuted at a time
# most rows a share gathers and multiplies at a time: 1.25 x 4096, the even
# part of a `_TOKEN_CHUNK`-token prompt, so that a prompt that fills the
# longest bucket takes one turn whichever side of even its load falls (a
# bound at the even part itself makes a second turn in every layer hang on
# whether the seed's share lies a little over or under even)
_SHARE_ROWS = 5120
_ACT = {"relu": jax.nn.relu, "silu": jax.nn.silu}
_TLS = threading.local()


class LoadCollector:
    """What the MoE layers of one traced program counted.  `valid` ([B, T]
    or [B] bool, or None for all) says which tokens are real: padding and
    idle rows route like any token but are left out of the counts, and a
    share of the experts (`experts_held`) computes nothing for them."""

    def __init__(self, valid=None):
        self.valid = valid
        self.parts: list = []

    def total(self):
        """int32 [4]: assignments, experts touched, largest expert load and
        routed assignments, each summed over the layers; None when no MoE
        layer ran."""
        return jnp.sum(jnp.stack(self.parts), axis=0) if self.parts else None


@contextlib.contextmanager
def collect_load(valid=None):
    prev = getattr(_TLS, "collector", None)
    _TLS.collector = LoadCollector(valid)
    try:
        yield _TLS.collector
    finally:
        _TLS.collector = prev


def route_top_k(logits, top_k: int, norm_topk_prob: bool = True,
                scoring: str = "softmax", routed_scale: float = 1.0,
                bias=None):
    """float32 routing: (weights [T, k], experts [T, k]).  `softmax`: with
    `norm_topk_prob` the weights are the softmax over the k chosen logits
    (= softmax over all experts, top k, renormalised); without it the
    softmax over all experts at the chosen ones.  `sigmoid`: the scores are
    sigmoids of the logits, the k largest are chosen, and with
    `norm_topk_prob` each weight is its score over the chosen scores' sum
    (+ 1e-20); with `bias` [experts] the k largest of score + bias are
    chosen and the weights are still the scores' own.  Either way times
    `routed_scale`."""
    logits = logits.astype(jnp.float32)
    if bias is not None and scoring != "sigmoid":
        raise ValueError("a selection bias goes with sigmoid scoring")
    if scoring == "sigmoid":
        score = jax.nn.sigmoid(logits)
        if bias is None:
            w, idx = jax.lax.top_k(score, top_k)
        else:
            _, idx = jax.lax.top_k(score + bias.astype(jnp.float32), top_k)
            w = jnp.take_along_axis(score, idx, -1)
        if norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    elif scoring == "softmax":
        top, idx = jax.lax.top_k(logits, top_k)
        w = (jax.nn.softmax(top, axis=-1) if norm_topk_prob else
             jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, -1))
    else:
        raise ValueError(f"scoring={scoring!r}: 'softmax' or 'sigmoid'")
    return (w if routed_scale == 1.0 else w * jnp.float32(routed_scale)), idx


def share_rows(assignments: int, held: int, n_all: int) -> int:
    """Rows a share gathers and multiplies at a time, of `assignments` =
    tokens x top-k routed over `n_all` experts of which it holds `held`:
    twice its even part (so one turn is the rule), a multiple of the
    grouped-matmul kernel's 128, at most `_SHARE_ROWS` (1.25 x the even
    part of the longest prompt bucket: still one turn there)."""
    even = -(-assignments * held // n_all)
    return min(_SHARE_ROWS, -(-2 * even // _ROW_MULTIPLE) * _ROW_MULTIPLE)


@defop
def dropless_moe(x, router_input, w_router, w_gate, w_up, w_down, top_k,
                 first=0, norm_topk_prob=True, scoring="softmax",
                 routed_scale=1.0, activation="relu", shared=None, bias=None,
                 name=None):
    """y = sum over the top-k experts e of p_e * (act(x W_gate^e) *
    (x W_up^e)) W_down^e for every token of x [..., hidden]; the router
    reads `router_input` [..., hidden].  `w_gate`/`w_up` [E_held, hidden,
    width] and `w_down` [E_held, width, hidden] are the experts `first ..
    first + E_held - 1` of the `w_router.shape[1]` routed over.  `shared`
    (gate, up, down), each one matrix: an expert every token meets with
    weight 1, added under the scope `moe.shared`.  `bias` [experts]: the
    router's selection bias (`route_top_k`)."""
    lead, h = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, h)
    n_tok, held, n_all = x2.shape[0], w_gate.shape[0], w_router.shape[1]
    act = _ACT[activation]
    with jax.named_scope("moe.route"):
        with jax.named_scope("moe.router"):
            logits = jnp.dot(router_input.reshape(-1, h), w_router,
                             preferred_element_type=jnp.float32)
            p, idx = route_top_k(logits, top_k, norm_topk_prob, scoring,
                                 routed_scale, bias)
        _count_load(idx, lead, first, held)

    def ffn(rows, sizes):
        return jax.lax.ragged_dot(
            act(jax.lax.ragged_dot(rows, w_gate, sizes)) *
            jax.lax.ragged_dot(rows, w_up, sizes), w_down, sizes)

    def experts(args):
        xc, pc, ic = args                       # [n, h], [n, k], [n, k]
        n = xc.shape[0] * top_k
        with jax.named_scope("moe.route"):
            # assignments sorted by expert
            order = jnp.argsort(ic.reshape(-1), stable=True)
            sizes = _counts(ic.reshape(-1), held)
            rows = jnp.pad(xc[order // top_k],
                           ((0, (-n) % _ROW_MULTIPLE), (0, 0)))
        with jax.named_scope("moe.experts"):
            out = ffn(rows, sizes)[:n]
            # back to token order, weighted and summed in float32
            out = out[jnp.argsort(order)].reshape(-1, top_k, h)
            return jnp.sum(out.astype(jnp.float32) * pc[:, :, None],
                           axis=1).astype(x.dtype)

    if held != n_all:
        y = _share(x2, p, idx, ffn, first, held, n_all, _real_tokens(lead))
    elif n_tok > _TOKEN_CHUNK and n_tok % _TOKEN_CHUNK == 0:
        # a long prefill: the permuted rows and their products of one chunk
        # at a time (each is tokens x top_k x hidden)
        y = jax.lax.map(experts, tuple(
            a.reshape((-1, _TOKEN_CHUNK) + a.shape[1:])
            for a in (x2, p, idx)))
    else:
        y = experts((x2, p, idx))
    y = y.reshape(lead + (h,))
    if shared is not None:
        with jax.named_scope("moe.shared"):
            s_gate, s_up, s_down = shared
            y = y + jnp.dot(act(jnp.dot(x, s_gate)) * jnp.dot(x, s_up),
                            s_down)
    return y


def _share(x2, p, idx, ffn, first, held, n_all, real=None):
    """The held experts' part of the layer for the tokens x2 [n, h]: their
    assignments sort to the front (by expert), and each turn of the loop
    gathers, multiplies and adds back `share_rows` of them; the turns are
    as many as this step's load on the held experts needs.  Tokens that
    `real` [n, 1] marks as padding or idle rows (the serving engine's
    collector says which) are assigned nowhere: a prompt's padding is one
    token id a thousand times over, so it routes in lockstep, and whether
    its eight choices are held here would decide the turns of a prefill."""
    n, top_k = idx.shape
    r = share_rows(n * top_k, held, n_all)
    with jax.named_scope("moe.route"):
        local = idx.reshape(-1) - first
        mine = (local >= 0) & (local < held)
        if real is not None:
            mine &= jnp.broadcast_to(real, idx.shape).reshape(-1)
        key = jnp.where(mine, local, held)
        order = jnp.argsort(key, stable=True)
        pad = (0, (-order.shape[0]) % r)
        weight = jnp.pad(jnp.where(mine, p.reshape(-1), 0.0)[order], pad)
        live = jnp.pad(mine[order], pad)
        token = jnp.pad(order // top_k, pad)
        ends = jnp.cumsum(_counts(key, held), dtype=jnp.int32)
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])

    def turn(b, y):
        lo = b * r
        with jax.named_scope("moe.route"):
            tok = jax.lax.dynamic_slice_in_dim(token, lo, r)
            rows = x2[tok]                      # [r, h]: the bounded buffer
            sizes = jnp.clip(jnp.minimum(ends, lo + r) -
                             jnp.maximum(starts, lo), 0, r)
        with jax.named_scope("moe.experts"):
            out = jnp.where(
                jax.lax.dynamic_slice_in_dim(live, lo, r)[:, None],
                ffn(rows, sizes).astype(jnp.float32), 0.0)
            w = jax.lax.dynamic_slice_in_dim(weight, lo, r)
            return y.at[tok].add(out * w[:, None])

    y = jax.lax.fori_loop(0, (ends[-1] + (r - 1)) // r, turn,
                          jnp.zeros(x2.shape, jnp.float32))
    return y.astype(x2.dtype)


def _counts(keys, n: int):
    """int32 [n]: how many of `keys` equal 0 .. n - 1 (others are counted
    nowhere).  A compare-and-sum: `jnp.bincount` is a scatter-add, which the
    TPU runs an element at a time (0.090 of the 0.415 s that three prefills
    of 14,336 tokens took on a v5e)."""
    return jnp.sum(keys[:, None] == jnp.arange(n, dtype=keys.dtype)[None, :],
                   axis=0, dtype=jnp.int32)


def _real_tokens(lead):
    """[T, 1] bool: which of the tokens of leading shape `lead` the active
    collector marks as real; None without a collector or a mask."""
    col = getattr(_TLS, "collector", None)
    if col is None or col.valid is None:
        return None
    valid = col.valid.reshape(col.valid.shape +
                              (1,) * (len(lead) - col.valid.ndim))
    return jnp.broadcast_to(valid, lead).reshape(-1, 1)


def _count_load(idx, lead, first, held):
    """Append this layer's counts to the active collector: `idx` [T, k] the
    experts chosen, `lead` the tokens' leading shape."""
    col = getattr(_TLS, "collector", None)
    if col is None:
        return
    mine = (idx >= first) & (idx < first + held)
    real = _real_tokens(lead)
    if real is not None:
        mine &= real
    load = _counts(jnp.where(mine, idx - first, held).reshape(-1), held)
    routed = (idx.size if real is None else jnp.sum(real) * idx.shape[-1])
    col.parts.append(jnp.stack([jnp.sum(load), jnp.sum(load > 0),
                                jnp.max(load), routed]).astype(jnp.int32))


class DroplessMoE(Layer):
    """Gated expert FFN (ReGLU, or SwiGLU with `activation="silu"`) with a
    top-k router and no capacity: `forward(x, router_input=None)`; the
    router reads `router_input` (default x) and scores by `scoring`
    (`route_top_k`).  Parameters: `w_router` [hidden, experts], the stacked
    `w_gate`, `w_up` [held, hidden, width], `w_down` [held, width, hidden]
    and, with `shared_width`, one shared expert's `shared_gate`,
    `shared_up` [hidden, shared_width], `shared_down`.  No biases on any
    product; with `expert_bias` the buffer `expert_bias` [experts] (float32
    zeros until something loads or sets it) is the router's selection
    bias."""

    def __init__(self, hidden_size: int, expert_width: int, num_experts: int,
                 top_k: int, norm_topk_prob: bool = True, experts_held=None,
                 weight_attr=None, scoring: str = "softmax",
                 routed_scale: float = 1.0, activation: str = "relu",
                 shared_width: int = 0, expert_bias: bool = False):
        super().__init__()
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and count >= 1 and first + count <= num_experts):
            raise ValueError(f"experts_held={experts_held} lies outside the "
                             f"{num_experts} experts")
        if top_k > num_experts:
            raise ValueError(f"top_k={top_k} exceeds {num_experts} experts")
        if scoring not in ("softmax", "sigmoid") or activation not in _ACT:
            raise ValueError(f"scoring={scoring!r} / activation="
                             f"{activation!r} is not known")
        self.top_k, self.norm_topk_prob = int(top_k), bool(norm_topk_prob)
        self.experts_held = (int(first), int(count))
        self.scoring, self.routed_scale = scoring, float(routed_scale)
        self.activation = activation
        h, f = int(hidden_size), int(expert_width)

        def param(shape):
            return self.create_parameter(
                shape, attr=weight_attr,
                default_initializer=I.Normal(mean=0.0, std=0.02))

        self.w_router = param((h, int(num_experts)))
        self.w_gate = param((count, h, f))
        self.w_up = param((count, h, f))
        self.w_down = param((count, f, h))
        self.has_shared = shared_width > 0
        if self.has_shared:
            self.shared_gate = param((h, int(shared_width)))
            self.shared_up = param((h, int(shared_width)))
            self.shared_down = param((int(shared_width), h))
        self.has_bias = bool(expert_bias)
        if self.has_bias:
            if scoring != "sigmoid":
                raise ValueError("expert_bias goes with sigmoid scoring")
            self.register_buffer("expert_bias", Tensor(
                jnp.zeros((int(num_experts),), jnp.float32), _internal=True))

    def forward(self, x, router_input=None):
        shared = ((self.shared_gate, self.shared_up, self.shared_down)
                  if self.has_shared else None)
        return dropless_moe(
            x, x if router_input is None else router_input, self.w_router,
            self.w_gate, self.w_up, self.w_down, top_k=self.top_k,
            first=self.experts_held[0], norm_topk_prob=self.norm_topk_prob,
            scoring=self.scoring, routed_scale=self.routed_scale,
            activation=self.activation, shared=shared,
            bias=self.expert_bias if self.has_bias else None)
