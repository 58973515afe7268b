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
  prefill: no past, the prompt keeps the causal flash path.
* PER-SLOT (continuous batching, serving.Engine): ``length`` is a ``[B]``
  vector — every row owns a slot in a shared pool and sits at its own
  position, so the new keys/values scatter to per-row offsets and attention
  runs under a per-row validity mask.  Rows whose write would fall off the
  buffer end (an inactive slot parked at max_len) are dropped by the
  scatter, never clipped onto a live row.  t may be > 1 (speculative
  verification / prefix-tail prefill): position j of a row writes at its own
  offset + j and attends causally within the new span.
* The 5-tuple ``(k_buf, v_buf, lengths, k_scale, v_scale)`` is the
  int8-quantized pool (serving kv_dtype="int8"): buffers store int8, scales
  ``[B, L]`` carry one absmax scale per cached row; writes quantize, the
  attention read dequantizes inline (kv_quant helpers).
* The PAGED forms (serving paged_kv=True) add an int32 page table at index
  3: 4-tuple ``(k_pages, v_pages, lengths, page_table)`` and 6-tuple
  ``(..., k_scale, v_scale)``.  K/V live as ``[num_pages, page_size,
  kv_heads, head_dim]`` pages; position p of row b maps to
  ``pages[page_table[b, p // P], p % P]``.  Writes scatter through the table
  (sentinel/out-of-range entries DROP — unallocated virtual positions are
  unwritable), reads gather the row's pages back into a ``[B, L_virt, ...]``
  view under the same validity mask as the dense pool — the page table is
  just one more fixed-shape operand, so decode keeps its ONE compiled
  signature.

`cache_positions(cache, t)` gives the positions of the ``t`` new tokens of a
layer's cache in any of these forms (learned position ids, RoPE angles).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import functional as F


def _raw(x):
    return x._value if isinstance(x, Tensor) else x


def _wrap(x):
    return Tensor(x, _internal=True)


def cache_positions(cache, t: int):
    """int64 positions ``[B or 1, t]`` of the ``t`` new tokens: from 0 with
    no cache, offset by the cached key length otherwise (a python int, a
    traced scalar or the per-slot ``[B]`` vector of the static forms; the
    buffer length of a growing-concat pair)."""
    if cache is None:
        past = 0
    elif len(cache) in (3, 4, 5, 6):
        past = jnp.asarray(cache[2], jnp.int64)
        if past.ndim == 1:        # per-slot: each row at its own position
            return past[:, None] + jnp.arange(t, dtype=jnp.int64)
    else:
        past = cache[0].shape[1]
    return (past + jnp.arange(t, dtype=jnp.int64)).reshape(1, t)


def _span_mask(cols, att_len: int, window):
    """[B, 1, t, L] validity of key l for the query at position cols[b, j]:
    causal, and inside the window where the layer has one."""
    key = jnp.arange(att_len)[None, None, :]
    mask = key <= cols[:, :, None]
    if window is not None:
        mask &= key > cols[:, :, None] - window
    return mask[:, None]


def cached_attention(q, k, v, cache=None, *, window=None, dropout_p=0.0,
                     training=False, owner="models.kv_cache"):
    """See the module docstring.  Returns ``(out, new_cache)``; ``out`` is a
    Tensor ``[B, t, heads, head_dim]``."""
    t = q.shape[1]
    if cache is None or len(cache) not in (3, 4, 5, 6):
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

    k_buf, v_buf, pos0 = cache[0], cache[1], cache[2]
    quantized = len(cache) in (5, 6)
    paged = len(cache) in (4, 6)
    k_raw, v_raw = _raw(k_buf), _raw(v_buf)
    start = jnp.asarray(pos0, jnp.int32)
    if (quantized or paged) and start.ndim != 1:
        raise ValueError(
            "int8 (5/6-tuple) and paged (4/6-tuple) KV caches are supported "
            "only in the per-slot vector-length form the serving engine "
            "uses")
    if start.ndim != 1:
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

    # -- per-slot lengths ----------------------------------------------------
    scale_i = 4 if paged else 3
    att_out = None
    cols = start[:, None] + jnp.arange(t)[None, :]
    if quantized:
        from ..serving.kv_quant import dequantize_pool, quantize_rows
        ks_raw, vs_raw = _raw(cache[scale_i]), _raw(cache[scale_i + 1])
        kq, ksc = quantize_rows(_raw(k))
        vq, vsc = quantize_rows(_raw(v))
    if paged:
        # gather/scatter through the page table: position p of row r lives
        # at pages[table[r, p // P], p % P].  Sentinel table entries
        # (>= num_pages) make the scatter DROP (an unallocated or parked
        # position is unwritable) and gather a clamped garbage page that the
        # validity mask excludes from attention.
        pt = jnp.asarray(_raw(cache[3]), jnp.int32)
        n_pages, psz = k_raw.shape[0], k_raw.shape[1]
        n_pt = pt.shape[1]
        virt = n_pt * psz
        rows = jnp.arange(pt.shape[0])[:, None]
        pslot = jnp.clip(cols // psz, 0, n_pt - 1)
        pid = jnp.where(cols < virt, pt[rows, pslot], n_pages)
        off = cols % psz
        if quantized:
            k_raw = k_raw.at[pid, off].set(kq, mode="drop")
            v_raw = v_raw.at[pid, off].set(vq, mode="drop")
            ks_raw = ks_raw.at[pid, off].set(ksc, mode="drop")
            vs_raw = vs_raw.at[pid, off].set(vsc, mode="drop")
        else:
            k_raw = k_raw.at[pid, off].set(
                _raw(k).astype(k_raw.dtype), mode="drop")
            v_raw = v_raw.at[pid, off].set(
                _raw(v).astype(v_raw.dtype), mode="drop")
        # serving decode with Engine(decode_kernel="pallas"): the attention
        # READ runs as the fused Pallas kernel — page-table walk + (int8)
        # dequant + masked softmax in one custom call, no [B, virt, ...]
        # gather temp.  The write scatter above is unchanged, so the kernel
        # attends over the post-write pool exactly like the XLA read.
        from ..kernels.paged_attention import (active as _paged_kernel_active,
                                               paged_decode_attention)
        if _paged_kernel_active():
            if window is not None or k_raw.shape[2] != q.shape[2]:
                raise ValueError(
                    "the paged decode kernel reads neither a sliding window "
                    "nor grouped-query heads; serve this model with "
                    "decode_kernel='xla'")
            att_out = paged_decode_attention(
                _raw(q), k_raw, v_raw, pt, start,
                k_scale=ks_raw if quantized else None,
                v_scale=vs_raw if quantized else None)
        else:
            pt_safe = jnp.clip(pt, 0, n_pages - 1)
            k_att = k_raw[pt_safe].reshape(
                (pt.shape[0], virt) + k_raw.shape[2:])
            v_att = v_raw[pt_safe].reshape(
                (pt.shape[0], virt) + v_raw.shape[2:])
            if quantized:
                k_att = dequantize_pool(
                    k_att, ks_raw[pt_safe].reshape(pt.shape[0], virt),
                    _raw(k).dtype)
                v_att = dequantize_pool(
                    v_att, vs_raw[pt_safe].reshape(pt.shape[0], virt),
                    _raw(v).dtype)
        att_len = virt
    else:
        rows = jnp.arange(k_raw.shape[0])[:, None]
        if quantized:
            k_raw = k_raw.at[rows, cols].set(kq, mode="drop")
            v_raw = v_raw.at[rows, cols].set(vq, mode="drop")
            ks_raw = ks_raw.at[rows, cols].set(ksc, mode="drop")
            vs_raw = vs_raw.at[rows, cols].set(vsc, mode="drop")
            k_att = dequantize_pool(k_raw, ks_raw, _raw(k).dtype)
            v_att = dequantize_pool(v_raw, vs_raw, _raw(v).dtype)
        else:
            k_raw = k_raw.at[rows, cols].set(
                _raw(k).astype(k_raw.dtype), mode="drop")
            v_raw = v_raw.at[rows, cols].set(
                _raw(v).astype(v_raw.dtype), mode="drop")
            k_att, v_att = k_raw, v_raw
            # the engine's decode step on the TPU: the read streams each
            # row's live blocks only (kernels/paged_attention.py "the dense
            # pool's decode read"; on a window layer from the window's
            # first block on); everywhere else, and for tail_prefill's long
            # spans, the masked XLA read below
            from ..kernels import paged_attention as _pk
            blk = (_pk.dense_read_block(
                heads=q.shape[2], kv_heads=k_raw.shape[2],
                head_dim=q.shape[3], dtype=k_raw.dtype, width=t,
                max_len=k_raw.shape[1]) if _pk.active() else None)
            if blk is not None:
                att_out = _pk.dense_decode_attention(
                    _raw(q), k_raw, v_raw, start, block=blk, window=window)
        att_len = k_raw.shape[1]
    if att_out is not None:
        out = _wrap(att_out)
    else:
        out = F.scaled_dot_product_attention(
            q, _wrap(k_att), _wrap(v_att),
            attn_mask=_wrap(_span_mask(cols, att_len, window)),
            dropout_p=0.0, is_causal=False, training=False)
    new_cache = (_wrap(k_raw), _wrap(v_raw), start + t)
    if paged:
        new_cache = new_cache + (cache[3],)
    if quantized:
        new_cache = new_cache + (_wrap(ks_raw), _wrap(vs_raw))
    return out, new_cache
