"""The decoder models' KV-cache attention: ONE copy of the cache protocol the
serving engine drives, shared by every decoder block (`models/gpt.py`,
`models/decoder.py`).

`cached_attention(q, k, v, cache, ...)` takes the new positions' projected
heads (q ``[B, t, heads, head_dim]``; k, v ``[B, t, kv_heads, head_dim]``,
``kv_heads`` a divisor of ``heads``) and the layer's cache, writes K/V, reads
the context under the causal (and, with ``window``, sliding-window) mask and
returns ``(out [B, t, heads, head_dim], new_cache)``.  The cache forms:

* ``None`` — no past: plain causal attention (flash where it applies); the
  new cache is the ``(k, v)`` pair.
* ``(k, v)`` — growing concat: every step has a new key length, so a jitted
  caller retraces per token (the retrace sentinel is told).
* STATIC ``(k_buf [B,L,kv_heads,hd], v_buf, length)`` — write the new
  keys/values in place at ``length`` and attend over the fixed-shape buffer
  under an explicit validity mask: every decode step is ONE compiled program
  with donated buffers (the AnalysisPredictor zero-copy run analog,
  analysis_predictor.cc:1618).  A python-int ``length == 0`` is the static
  prefill: no past, the prompt keeps the causal flash path.  A ``length``
  that is a ``[B]`` vector is the dense, unquantised :class:`SlotCache`
  spelled as a triple (triple in, triple out).
* :class:`SlotCache` — PER-SLOT (continuous batching, serving.Engine): every
  row owns a slot of a shared :class:`KVPool` and sits at its own position.
  The type names what the engine decided for the pool: the layout (``dense``
  rows, or ``paged`` through an int32 page table), the precision (int8
  storage when it carries scale sidecars) and the decode read (the masked
  XLA read, or a Pallas kernel).

`cache_positions(cache, t)` gives the positions of the ``t`` new tokens of a
layer's cache in any of these forms (learned position ids, RoPE angles).

**The latent kind** (`cached_latent_attention`, multi-head latent attention).
A layer stores ONE array per position, ``[c | kr]``: the normed compressed
KV (``kv_lora_rank`` numbers) beside the rotated key part every head shares,
not a K and a V per head.  Every cache form above carries it in the ``k``
place with ``None`` in the ``v`` place (`KVPool.latent`, `SlotCache.latent`):
no second buffer, no new arity.  A prompt with no past attends in the
EXPANDED form (keys and values made from the latent by ``W_kvb``, through
flash with q/k wider than v); a read of the pool attends in the ABSORBED
form (the query taken through ``W_UK``, scores against the latent itself,
values = its first ``kv_lora_rank`` columns, the output taken through
``W_UV``): one shared row per position for all heads.  Dense pool only.

**Rings.**  A sliding-window layer never reads a key more than ``window``
positions back, so its rows of the dense pool are RINGS of
``R = window + span - 1`` positions rounded up to the read block
(:func:`ring_len`; ``span`` the widest span a step writes), not ``max_len``
long: position p lives at ``p mod R``, a slot holds the newest position
congruent to it, and the mask goes by the position a slot holds (keys carry
their RoPE already, so their order in the ring does not matter).  A prompt
leaves its last ``R`` positions behind.  Nothing asks for it: every window
layer of a dense pool whose ``max_len`` is longer than ``R`` is a ring
(`KVPool.zeros` ``windows``; a layer's `SlotCache.limit` says so).

int8 storage (``Engine(kv_dtype="int8")``): one float32 scale per *cached
position* (the absmax over that position's ``[kv_heads, head_dim]`` vector),
so a new token's K/V is quantised against its OWN absmax at write time,
nothing resident ever rescales and the pool update stays a pure scatter.
The paged pool keeps the same granularity as a ``[num_pages, page_size]``
sidecar written by the scatter that writes the int8 page, so a page shared by
reference (prefix COW) shares its scales, and the quantised paged pool's
values are bitwise the quantised dense pool's.  Symmetric absmax int8 keeps
the worst per-element error at ``absmax / 254``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import functional as F

INT8_MAX = 127.0
# (position, head) pairs whose expanded keys and values a latent prefill
# holds at a time (8,192 positions x 16 heads)
_EXPANDED_PAIRS = 1 << 17
# floor for the per-row scale: an all-zero row (unwritten pool padding)
# quantizes to zeros with a tiny finite scale instead of dividing by 0
_SCALE_EPS = 1e-8


def quantize_rows(x, eps: float = _SCALE_EPS):
    """``x [..., heads, head_dim]`` float → ``(q int8 same shape,
    scales [...] float32)``: symmetric absmax over the trailing two dims,
    one scale per leading index (= per cached row position)."""
    amax = jnp.max(jnp.abs(x), axis=(-2, -1))
    scale = jnp.maximum(amax.astype(jnp.float32) / INT8_MAX, eps)
    q = jnp.clip(jnp.round(x / scale[..., None, None].astype(x.dtype)),
                 -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


def dequantize_pool(q, scale, dtype):
    """Inverse of :func:`quantize_rows`: ``q [..., heads, head_dim]`` int8
    + ``scale [...]`` → float ``dtype``.  Runs inside the attention read,
    so XLA fuses it with the QK^T consumer — HBM sees int8 bytes."""
    return q.astype(dtype) * scale[..., None, None].astype(dtype)


def _raw(x):
    return x._value if isinstance(x, Tensor) else x


def _wrap(x):
    return Tensor(x, _internal=True)


def _span_mask(cols, att_len: int, window):
    """[B, 1, t, L] validity of key l for the query at position cols[b, j]:
    causal, and inside the window where the layer has one."""
    key = jnp.arange(att_len)[None, None, :]
    mask = key <= cols[:, :, None]
    if window is not None:
        mask &= key > cols[:, :, None] - window
    return mask[:, None]


def ring_len(window, span: int, block: int, row_len: int) -> int:
    """Positions a layer's row of the dense pool holds: ``row_len``, or on a
    sliding-window layer the ring of ``window + span - 1`` positions (what
    the first query of a ``span``-wide step still reads once the whole span
    is written), rounded up to the read ``block``, where that is shorter."""
    if window is None:
        return row_len
    return min(-(-(window + span - 1) // block) * block, row_len)


def _ring_positions(last, ring: int):
    """``[B, ring]``: the position each slot of a ring holds once every
    position up to ``last [B, 1]`` is written (the newest one congruent to
    the slot; negative: never written)."""
    return last - (last - jnp.arange(ring)[None, :]) % ring


def _ring_mask(cols, ring: int, window: int):
    """:func:`_span_mask` on a ring whose span ``cols`` is written."""
    pos = _ring_positions(cols[:, -1:], ring)[:, None, :]
    col = cols[:, :, None]
    return ((pos <= col) & (pos > col - window) & (pos >= 0))[:, None]


def _page_address(table, cols, num_pages: int, page_size: int):
    """``(page id, offset)`` of position ``cols[r, j]`` of row ``r``:
    ``pages[table[r, p // P], p % P]``.  A sentinel table entry
    (``>= num_pages``) or a position past the table's reach gives page id
    ``num_pages``, which a ``mode="drop"`` scatter discards: an unallocated
    or parked position is unwritable."""
    n_pt = table.shape[1]
    rows = jnp.arange(table.shape[0])[:, None]
    pslot = jnp.clip(cols // page_size, 0, n_pt - 1)
    pid = jnp.where(cols < n_pt * page_size, table[rows, pslot], num_pages)
    return pid, cols % page_size


def _latent_read(q, latent, cols, scale: float, values: int):
    """The masked XLA read of the latent kind, absorbed form: queries
    ``q [rows, t, heads, width]`` at positions ``cols`` against every row's
    whole latent ``[rows, L, width]``, all heads on the one shared row;
    the values are a row's first ``values`` columns.  ``[rows, t, heads,
    values]``; softmax in float32."""
    s = jnp.einsum("bthd,bld->bhtl", q, latent.astype(q.dtype),
                   preferred_element_type=jnp.float32) * jnp.float32(scale)
    s = jnp.where(_span_mask(cols, latent.shape[1], None), s,
                  jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhtl,blc->bthc", p,
                      latent[..., :values].astype(q.dtype))


class KernelRead(NamedTuple):
    """The Pallas kernel one program's attention read goes through
    (`kernels/paged_attention.py`), chosen once by the engine when it is
    built: ``dense`` streams each row's live ``block``-position blocks of
    the dense pool, ``paged`` walks the page table (``block`` = the page
    size)."""
    kernel: str
    block: int


@dataclasses.dataclass(frozen=True)
class SlotCache:
    """One layer's per-slot cache.  ``k`` / ``v``: the pool's storage,
    ``[rows, L, kv_heads, hd]`` (``dense``) or ``[num_pages, page_size,
    kv_heads, hd]`` (``paged``, with ``table [rows, n_pt]`` int32: position
    p of row r lives at ``pages[table[r, p // P], p % P]``); ``lengths
    [rows]``: each row's cached positions (a row parked at the addressable
    end writes nothing); ``k_scale`` / ``v_scale``: the int8 storage's
    per-position float32 scales, shaped like the storage's two leading
    dims.  Static: ``layout``, ``read`` (None = the masked XLA read over
    every row whole; a :class:`KernelRead` in the engine's decode
    program) and ``limit`` (a window layer's RING, module docstring: the
    positions a row addresses, more than the ``k.shape[1]`` it holds; None
    = the row holds what it addresses).  The new span may be wider than one
    position (speculative
    verification, prefix-tail prefill): position j of a row writes at its
    own offset + j and attends causally within the span.  The latent kind
    (module docstring) holds ``[rows, L, width]`` in ``k`` and None in
    ``v``."""
    k: Any
    v: Any
    lengths: Any
    table: Any = None
    k_scale: Any = None
    v_scale: Any = None
    layout: str = "dense"
    read: Optional[KernelRead] = None
    limit: Optional[int] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def latent(self) -> bool:
        return self.v is None

    @property
    def span(self) -> int:
        """Positions a row can address."""
        if self.limit is not None:
            return self.limit
        return (self.table.shape[1] * self.k.shape[1]
                if self.layout == "paged" else self.k.shape[1])

    def cols(self, t: int):
        """``[rows, t]``: the positions a span of ``t`` new tokens takes."""
        return self.lengths[:, None] + jnp.arange(t)[None, :]

    def put(self, at, k, v):
        """The cache with ``k`` / ``v`` stored at storage index ``at``
        (quantised where the storage has scales); an index out of range
        DROPS its write."""
        if self.latent:
            return dataclasses.replace(
                self, k=self.k.at[at].set(k.astype(self.k.dtype),
                                          mode="drop"))
        if not self.quantized:
            return dataclasses.replace(
                self,
                k=self.k.at[at].set(k.astype(self.k.dtype), mode="drop"),
                v=self.v.at[at].set(v.astype(self.v.dtype), mode="drop"))
        (kq, ksc), (vq, vsc) = quantize_rows(k), quantize_rows(v)
        return dataclasses.replace(
            self,
            k=self.k.at[at].set(kq, mode="drop"),
            v=self.v.at[at].set(vq, mode="drop"),
            k_scale=self.k_scale.at[at].set(ksc, mode="drop"),
            v_scale=self.v_scale.at[at].set(vsc, mode="drop"))

    def written(self, k, v, cols):
        """The cache with the new positions' K/V scattered in at ``cols``
        (``lengths`` as they were).  A write past a dense row's end, or
        through a sentinel page, drops: never clipped onto a live row (on a
        ring, past the positions the row addresses).  The latent kind in a
        program that reads through the kernel writes
        through the kernel's own write (a span from ``cols[:, 0]`` on)."""
        if self.latent and self.read is not None:
            from ..kernels import paged_attention as pk
            return dataclasses.replace(self, k=pk.latent_pool_write(
                self.k, k, cols[:, 0]))
        if self.layout == "paged":
            return self.put(_page_address(
                jnp.asarray(self.table, jnp.int32), cols, self.k.shape[0],
                self.k.shape[1]), k, v)
        if self.limit is not None:
            ring = self.k.shape[1]
            cols = jnp.where(cols < self.limit, cols % ring, ring)
        return self.put((jnp.arange(self.k.shape[0])[:, None], cols), k, v)

    def _rows(self, dtype):
        """K and V as ``[rows, span, kv_heads, hd]`` of ``dtype``: the pages
        gathered through the table (a sentinel entry gathers a clamped
        garbage page that the validity mask excludes), int8 dequantised."""
        k, v, ks, vs = self.k, self.v, self.k_scale, self.v_scale
        if self.layout == "paged":
            pt = jnp.clip(jnp.asarray(self.table, jnp.int32), 0,
                          k.shape[0] - 1)
            n = pt.shape[0]
            k = k[pt].reshape((n, self.span) + k.shape[2:])
            v = v[pt].reshape((n, self.span) + v.shape[2:])
            if self.quantized:
                ks = ks[pt].reshape(n, self.span)
                vs = vs[pt].reshape(n, self.span)
        if self.quantized:
            k, v = dequantize_pool(k, ks, dtype), dequantize_pool(v, vs, dtype)
        return k, v

    def attend(self, q, cols, window=None, scale=None, values=None):
        """Attention of the span's queries ``q [rows, t, heads, hd]`` at
        ``cols`` over the (already written) cache, through the read this
        program was given.  The latent kind: ``q [rows, t, heads, width]``
        absorbed queries, ``scale`` the model's, ``values`` the leading
        columns of a latent row that are its values."""
        if self.latent:
            if self.read is None:
                return _wrap(_latent_read(_raw(q), self.k, cols, scale,
                                          values))
            from ..kernels import paged_attention as pk
            return _wrap(pk.latent_decode_attention(
                _raw(q), self.k, self.lengths, block=self.read.block,
                scale=scale, values=values))
        if self.read is None:
            k, v = self._rows(_raw(q).dtype)
            mask = (_span_mask(cols, self.span, window) if self.limit is None
                    else _ring_mask(cols, self.k.shape[1], window))
            return F.scaled_dot_product_attention(
                q, _wrap(k), _wrap(v), attn_mask=_wrap(mask),
                dropout_p=0.0, is_causal=False, training=False)
        from ..kernels import paged_attention as pk
        if self.read.kernel == "dense":
            return _wrap(pk.dense_decode_attention(
                _raw(q), self.k, self.v, self.lengths, block=self.read.block,
                window=window, limit=self.limit))
        if window is not None or self.k.shape[2] != q.shape[2]:
            raise ValueError(
                "the paged decode kernel reads neither a sliding window "
                "nor grouped-query heads; serve this model with "
                "decode_kernel='xla'")
        return _wrap(pk.paged_decode_attention(
            _raw(q), self.k, self.v, jnp.asarray(self.table, jnp.int32),
            self.lengths, k_scale=self.k_scale, v_scale=self.v_scale))


jax.tree_util.register_dataclass(
    SlotCache,
    data_fields=["k", "v", "lengths", "table", "k_scale", "v_scale"],
    meta_fields=["layout", "read", "limit"])


@dataclasses.dataclass(frozen=True)
class KVPool:
    """The serving engine's KV storage, every layer's: the donated operand
    of its programs.  ``dense``: ``rows`` slot rows of ``row_len``
    positions, the LAST row the scratch row that a row copy's padding
    lanes name;
    ``paged``: ``rows`` pages of ``row_len`` positions, addressed through
    per-slot page tables whose sentinel entry is ``rows``.  ``dtype`` is
    the precision K/V are computed in; storage is int8 with float32 scale
    sidecars when ``k_scale`` is there.  What a layer stores is the model's
    to say (the cache shapes it reports): a K and a V per KV head, or, the
    latent kind, one ``[rows, row_len, width]`` array in ``k`` and no ``v``
    at all (``v is None``; dense, unquantised).  A dense layer whose rows
    are shorter than ``row_len`` is a window layer's ring (module
    docstring)."""
    k: list
    v: Optional[list]
    k_scale: Optional[list] = None
    v_scale: Optional[list] = None
    layout: str = "dense"
    dtype: Any = None
    row_len: Optional[int] = None

    @classmethod
    def zeros(cls, kv, *, layout: str, rows: int, row_len: int,
              quantized: bool, windows=None, ring_span: int = 1,
              ring_block: Optional[int] = None):
        """Sized from the per-layer ``(k, v)`` cache shapes the model
        reports (``[.., .., kv_heads, hd]``: KV heads, not query heads; or
        ``([.., .., width], None)`` from a layer that stores a latent).
        ``windows`` (per layer, None = global) with ``ring_block`` makes the
        dense pool's window layers rings of :func:`ring_len` positions for
        steps that write at most ``ring_span`` positions."""
        latent = kv[0][1] is None
        if latent and (quantized or layout != "dense"):
            raise ValueError("a latent cache lives on the dense, "
                             "unquantised pool only")
        if windows is None or ring_block is None or layout != "dense":
            windows = [None] * len(kv)
        lens = [ring_len(w, ring_span, ring_block, row_len) for w in windows]

        def buf(s, n):
            return jnp.zeros((rows, n) + tuple(s.shape[2:]),
                             jnp.int8 if quantized else s.dtype)

        def scales():
            return ([jnp.zeros((rows, n), jnp.float32) for n in lens]
                    if quantized else None)
        return cls([buf(k, n) for (k, _), n in zip(kv, lens)],
                   None if latent else [buf(v, n)
                                        for (_, v), n in zip(kv, lens)],
                   scales(), scales(), layout, jnp.dtype(kv[0][0].dtype),
                   row_len)

    @property
    def latent(self) -> bool:
        return self.v is None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def rows(self) -> int:
        return self.k[0].shape[0]

    @property
    def nbytes(self) -> int:
        return sum(self.layer_nbytes)

    @property
    def layer_nbytes(self) -> list:
        """Bytes each layer holds (K, V and their scales)."""
        return [sum(int(g[i].size) * g[i].dtype.itemsize
                    for g in self.groups()) for i in range(len(self.k))]

    @property
    def ring_lens(self) -> list:
        """Per layer: the positions its ring holds, None where a row holds
        all it addresses."""
        return [k.shape[1] if self.layout == "dense" and self.row_len and
                k.shape[1] < self.row_len else None for k in self.k]

    def groups(self) -> tuple:
        """The per-layer buffer lists: K, V and, for int8 storage, their
        scale sidecars (the order of a host-tier payload)."""
        return tuple(g for g in (self.k, self.v, self.k_scale, self.v_scale)
                     if g is not None)

    def with_groups(self, groups):
        return dataclasses.replace(self, **dict(zip(
            ("k", "v", "k_scale", "v_scale"), groups)))

    def caches(self, lengths, tables=None, read=None) -> list:
        """The per-layer caches of one step (``tables`` with the paged
        layout)."""
        none = [None] * len(self.k)
        return [SlotCache(k, v, lengths, tables, ks, vs, self.layout, read,
                          ring and self.row_len)
                for k, v, ks, vs, ring in zip(
                    self.k, self.v or none, self.k_scale or none,
                    self.v_scale or none, self.ring_lens)]

    def updated(self, caches):
        """The pool holding the buffers of the caches a model returned."""
        return dataclasses.replace(
            self, k=[c.k for c in caches],
            v=None if self.latent else [c.v for c in caches],
            k_scale=[c.k_scale for c in caches] if self.quantized else None,
            v_scale=[c.v_scale for c in caches] if self.quantized else None)

    def prompt_caches(self, n: int, bucket: int) -> list:
        """Static caches (python-int length 0, so the prompt keeps the
        causal flash path) for ``n`` fresh prompts of up to ``bucket``
        positions, in the compute precision: a dense pool takes whole rows
        back, a paged pool and a ring the bucket's positions."""
        def zeros(p, ring):
            length = (bucket if self.layout == "paged" or ring
                      else p.shape[1])
            return _wrap(jnp.zeros((n, length) + p.shape[2:], self.dtype))
        return [(zeros(k, ring), None if v is None else zeros(v, ring), 0)
                for k, v, ring in zip(self.k, self.v or [None] * len(self.k),
                                      self.ring_lens)]

    def with_prompts(self, caches, addr, prompt_lens):
        """The pool with freshly prefilled :meth:`prompt_caches` written
        where ``addr`` says: whole rows at slot indices (``dense``; a ring
        takes the prompt's last positions, each at its slot), or
        every real position through its lane's page-table row (``paged``;
        padding positions drop).  Every lane names a request.  int8 storage
        quantises here: the prompt math itself stays full precision."""
        at = addr

        def row(x, ring):
            """The ring's row of each prompt, from its bucket ``x``."""
            x = _raw(x)
            if not ring or x is None:
                return x
            pos = jnp.clip(_ring_positions(prompt_lens[:, None] - 1, ring),
                           0, x.shape[1] - 1)
            return jnp.take_along_axis(
                x, pos.reshape(pos.shape + (1,) * (x.ndim - 2)), axis=1)

        if self.layout == "paged":
            pos = jnp.arange(caches[0][0].shape[1])
            pid, off = _page_address(addr, jnp.broadcast_to(
                pos[None, :], (addr.shape[0], pos.shape[0])),
                self.rows, self.k[0].shape[1])
            live = pos[None, :] < prompt_lens[:, None]
            at = (jnp.where(live, pid, self.rows), off)
        return self.updated([
            view.put(at, row(c[0], ring), row(c[1], ring))
            for view, c, ring in zip(self.caches(None), caches,
                                     self.ring_lens)])

    def copied(self, src, dst):
        """Rows (a prefix hit's cached row into the hitting request's
        slot) or pages (copy-on-write of a shared boundary page) cloned
        ``src -> dst`` with their scales, bitwise.  A sentinel page lane
        gathers a clamped page and drops its scatter; a dense padding lane
        copies the scratch row onto itself."""
        return jax.tree_util.tree_map(
            lambda p: p.at[dst].set(p[jnp.clip(src, 0, p.shape[0] - 1)],
                                    mode="drop"), self)


jax.tree_util.register_dataclass(
    KVPool, data_fields=["k", "v", "k_scale", "v_scale"],
    meta_fields=["layout", "dtype", "row_len"])


def cache_positions(cache, t: int):
    """int64 positions ``[B or 1, t]`` of the ``t`` new tokens: from 0 with
    no cache, offset by the cached key length otherwise (a python int, a
    traced scalar or the per-slot ``[B]`` vector; the buffer length of a
    growing-concat pair)."""
    if cache is None:
        past = 0
    elif isinstance(cache, SlotCache) or len(cache) == 3:
        past = jnp.asarray(
            cache.lengths if isinstance(cache, SlotCache) else cache[2],
            jnp.int64)
        if past.ndim == 1:        # per-slot: each row at its own position
            return past[:, None] + jnp.arange(t, dtype=jnp.int64)
    else:
        past = cache[0].shape[1]
    return (past + jnp.arange(t, dtype=jnp.int64)).reshape(1, t)


def cached_attention(q, k, v, cache=None, *, window=None, dropout_p=0.0,
                     training=False, owner="models.kv_cache"):
    """See the module docstring.  Returns ``(out, new_cache)``; ``out`` is a
    Tensor ``[B, t, heads, head_dim]``."""
    t = q.shape[1]
    if isinstance(cache, SlotCache):
        cols = cache.cols(t)
        cache = cache.written(_raw(k), _raw(v), cols)
        out = cache.attend(q, cols, window)
        return out, dataclasses.replace(cache, lengths=cache.lengths + t)

    if cache is None or len(cache) != 3:
        if cache is not None:
            from ..observability.retrace import note_dynamic_cache_growth
            from ..ops.manipulation import concat
            note_dynamic_cache_growth(owner)
            k = concat([cache[0], k], axis=1)
            v = concat([cache[1], v], axis=1)
        if window is not None and k.shape[1] != t:
            # a past shifts the diagonal: state the window in the mask
            cols = (k.shape[1] - t) + jnp.arange(t)[None, :]
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=_wrap(_span_mask(cols, k.shape[1],
                                                    window)),
                dropout_p=dropout_p, is_causal=False, training=training)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, dropout_p=dropout_p, is_causal=True,
                training=training, window=window)
        return out, (k, v)

    k_buf, v_buf, pos0 = cache
    start = jnp.asarray(pos0, jnp.int32)
    if start.ndim == 1:
        out, new = cached_attention(
            q, k, v, SlotCache(_raw(k_buf), _raw(v_buf), start),
            window=window)
        return out, (_wrap(new.k), _wrap(new.v), new.lengths)
    k_raw, v_raw = _raw(k_buf), _raw(v_buf)
    z = jnp.zeros((), jnp.int32)
    k_raw = jax.lax.dynamic_update_slice(
        k_raw, _raw(k).astype(k_raw.dtype), (z, start, z, z))
    v_raw = jax.lax.dynamic_update_slice(
        v_raw, _raw(v).astype(v_raw.dtype), (z, start, z, z))
    if isinstance(pos0, int) and pos0 == 0:
        # static prefill (the engine builds the cache inside the prefill
        # jit with a PYTHON-int length 0): no past to attend over, so
        # the prompt keeps the causal flash-attention path instead of
        # dense masked attention over the zero-padded buffer
        out = F.scaled_dot_product_attention(
            q, k, v, dropout_p=0.0, is_causal=True, training=False,
            window=window)
    else:
        cols = (start + jnp.arange(t))[None, :]
        out = F.scaled_dot_product_attention(
            q, _wrap(k_raw), _wrap(v_raw),
            attn_mask=_wrap(_span_mask(cols, k_raw.shape[1], window)),
            dropout_p=0.0, is_causal=False, training=False)
    return out, (_wrap(k_raw), _wrap(v_raw), start + t)


def cached_latent_attention(q_nope, q_rope, latent, w_kvb, cache=None, *,
                            owner="models.kv_cache"):
    """Multi-head latent attention over the cache forms of the module
    docstring.  ``q_nope [B, t, heads, nope]`` and ``q_rope [B, t, heads,
    rope]`` (rotated) are the new positions' queries, ``latent [B, t,
    kv_lora_rank + rope]`` their ``[c | kr]`` rows (normed, rotated: what
    the cache stores), ``w_kvb [kv_lora_rank, heads * (nope + v)]`` the
    up-projection whose head slices are ``[W_UK | W_UV]``.  The scale is
    ``1 / sqrt(nope + rope)`` in both forms.  Returns ``(out [B, t, heads,
    v], new_cache)``."""
    b, t, heads, nope = q_nope.shape
    q_nope, q_rope, latent, w_kvb = (_raw(a) for a in (q_nope, q_rope,
                                                       latent, w_kvb))
    rank = w_kvb.shape[0]
    w = w_kvb.reshape(rank, heads, -1)
    scale = 1.0 / float(nope + q_rope.shape[-1]) ** 0.5

    def expanded(lat):
        """Keys and values of every head from the latent rows ``lat``;
        the new queries attend causally (flash where it applies).  A long
        prompt goes a group of heads at a time: the expanded keys and
        values of all 128 heads of 8,192 positions, with the kernel's
        layout copies, are 3.4 GB."""
        c, kr = lat[..., :rank], lat[..., rank:]
        n = max(1, min(heads, lat.shape[1] * heads // _EXPANDED_PAIRS))
        while heads % n:
            n -= 1

        def group(g):
            wg, qn, qr = g                  # [rank, hg, nope+v], [b,t,hg,.]
            kv = jnp.einsum("blc,chd->blhd", c, wg.astype(c.dtype))
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                kr[:, :, None, :], kv.shape[:3] + (kr.shape[-1],))], -1)
            return _raw(F.scaled_dot_product_attention(
                _wrap(jnp.concatenate([qn, qr], -1)), _wrap(k),
                _wrap(kv[..., nope:]), dropout_p=0.0, is_causal=True,
                training=False))

        if n == 1:
            return _wrap(group((w, q_nope, q_rope)))

        def split(a, axis):                 # heads -> [n, heads / n] first
            a = a.reshape(a.shape[:axis] + (n, heads // n) +
                          a.shape[axis + 1:])
            return jnp.moveaxis(a, axis, 0)
        out = jax.lax.map(group, (split(w, 1), split(q_nope, 2),
                                  split(q_rope, 2)))     # [n, b, t, hg, v]
        return _wrap(jnp.moveaxis(out, 0, 2).reshape(
            out.shape[1:3] + (heads, out.shape[-1])))

    def absorbed(slots: SlotCache):
        cols = slots.cols(t)
        slots = slots.written(latent, None, cols)
        q = jnp.concatenate([
            jnp.einsum("bthn,chn->bthc", q_nope,
                       w[..., :nope].astype(q_nope.dtype)), q_rope], -1)
        o = _raw(slots.attend(_wrap(q), cols, scale=scale, values=rank))
        out = jnp.einsum("bthc,chv->bthv", o, w[..., nope:].astype(o.dtype))
        return _wrap(out), dataclasses.replace(slots,
                                               lengths=slots.lengths + t)

    if isinstance(cache, SlotCache):
        return absorbed(cache)
    if cache is None or len(cache) != 3:
        if cache is not None:
            from ..observability.retrace import note_dynamic_cache_growth
            note_dynamic_cache_growth(owner)
            latent = jnp.concatenate([_raw(cache[0]), latent], axis=1)
        return expanded(latent), (_wrap(latent), None)
    buf, _, pos0 = cache
    if isinstance(pos0, int) and pos0 == 0:
        # static prefill: no past, so the prompt attends in the expanded
        # form and keeps the causal flash path
        new = jax.lax.dynamic_update_slice(
            _raw(buf), latent.astype(_raw(buf).dtype), (0, 0, 0))
        return expanded(latent), (_wrap(new), None, t)
    start = jnp.asarray(pos0, jnp.int32)
    out, new = absorbed(SlotCache(
        _raw(buf), None, jnp.broadcast_to(start, (b,))))
    return out, (_wrap(new.k), None, start + t)
