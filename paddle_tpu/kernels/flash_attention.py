"""Flash attention as Pallas TPU kernels — the framework's analog of the
reference's fused CUDA attention family (paddle/fluid/operators/fused/
fused_attention_op.cu, fmha_ref.h), which materialises the S×S score matrix.
Here the online-softmax tiling keeps scores in VMEM tiles only:

* forward: grid (B*H/nb, Tq/bq, Tk/bk) with VMEM accumulators carried across
  the kv-block grid dimension (TPU grids execute sequentially, so scratch
  persists across the innermost dimension).  `nb` heads are processed per
  grid invocation as a batched MXU contraction — per-invocation launch
  overhead dominates wall time at GPT head sizes (d=64 means each single-head
  tile is only ~17M MACs), so amortizing it 8-way is worth ~5x end-to-end;
* backward: two kernels (dq; dk/dv) recomputing the tile probabilities from
  the saved logsumexp — the standard flash-attention-2 decomposition;
* `jax.custom_vjp` ties them together so `jax.grad` through the train step
  uses the fused backward.

Layout [B, T, H, D] at the API (the reference fused-op convention), internally
[(B*H), T, D].  MXU work is f32-accumulated (`preferred_element_type`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# interpret mode runs the kernels on CPU (tests / debugging); set via
# use_interpret_mode() before first call
_INTERPRET = False


def use_interpret_mode(flag: bool):
    global _INTERPRET
    _INTERPRET = bool(flag)


def _block_sizes(tq, tk):
    # measured on v5e: attention at GPT head sizes is VPU-bound (softmax
    # ops on the score tile), so bigger tiles win — a full 1024-row kv tile
    # enables the one-pass (no online-softmax carry) kernel path below
    bq = min(1024, tq)
    bk = min(1024, tk)
    return bq, bk


def _head_block(bh: int, bq: int, bk: int) -> int:
    """Heads per grid invocation: the largest divisor of bh with the f32
    score tile (nb, bq, bk) comfortably inside VMEM.

    The 16 MB figure budgets the score tile only; the exp/p temporary,
    q/k/v/o tiles and double buffering ride in the remaining headroom of
    the 100 MB vmem_limit_bytes.  The resulting hot config — nb=4 at
    bq=bk=1024 one-pass forward, nbf=2 fused backward — is validated on
    real v5e hardware by every `python bench.py` run (docs/PERF.md);
    Mosaic rejects at compile time (scoped-vmem OOM), not silently, if a
    future shape breaks the envelope."""
    budget = 16 * 1024 * 1024   # bytes for the f32 score tile
    for nb in (8, 4, 2, 1):
        if bh % nb == 0 and nb * bq * bk * 4 <= budget:
            return nb
    return 1


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _mxu(a, b, dims):
    """f32-accumulated MXU contraction.  A bf16 operand has no
    higher-precision mode, and Mosaic refuses the request ("Bad lhs type")
    rather than ignoring it — so `jax_default_matmul_precision="highest"`
    (tests/conftest.py pins it; a user may) must not reach a bf16 dot.
    f32 operands keep whatever the config asks for."""
    low = a.dtype == jnp.bfloat16 or b.dtype == jnp.bfloat16
    return jax.lax.dot_general(
        a, b, (dims, ((0,), (0,))),
        precision=jax.lax.Precision.DEFAULT if low else None,
        preferred_element_type=jnp.float32)


def _qk(q, k):
    """(nb,bq,d) x (nb,bk,d) -> scores (nb,bq,bk), f32."""
    return _mxu(q, k, ((2,), (2,)))


def _pv(p, v):
    """(nb,bq,bk) x (nb,bk,d) -> (nb,bq,d), f32."""
    return _mxu(p, v, ((2,), (1,)))


def _tq_contract(a, b):
    """(nb,bq,bk) x (nb,bq,d) contracted over bq -> (nb,bk,d), f32."""
    return _mxu(a, b, ((1,), (1,)))


def _tile_mask(i, j, bq, bk, causal, offset, t_real, pad_cols, window=None):
    """None when no masking is needed (interior tile, no kv padding)."""
    mask = None
    if pad_cols:                # kv padding exists: mask the dead columns
        col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = col < t_real
    if causal:
        col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cm = col <= row + offset
        if window is not None:  # query t reads keys t - window + 1 .. t
            cm &= col > row + (offset - window)
        mask = cm if mask is None else (mask & cm)
    return None if mask is None else mask[None]  # broadcast over head dim


def _tile_live(i, j, bq, bk, causal, offset, window=None):
    """Whether tile (i, j) holds any unmasked pair: not strictly above the
    causal diagonal band and, with a window, not wholly before it."""
    if not causal:
        return True
    live = j * bk <= i * bq + (bq - 1) + offset
    if window is not None:
        live &= j * bk + (bk - 1) > i * bq + (offset - window)
    return live


def _same_heads(q, k):
    """K or V tile of a grouped-query call: the group's one KV head,
    broadcast to the query heads of the block."""
    return k if k.shape[0] == q.shape[0] else jnp.broadcast_to(
        k, (q.shape[0],) + k.shape[1:])


# -- forward ------------------------------------------------------------------

def _rld(ref):
    """Load a q/k/v tile.  3D blocks load as-is; 4D (nb, 1, b*, d) blocks —
    the role-sliced views of a fused [BH, 3, T, D] qkv operand — squeeze
    the singleton role dim."""
    x = ref[...]
    return x[:, 0] if x.ndim == 4 else x


def _scaled_scores(q, k, i, j, *, scale, causal, offset, bq, bk,
                   pad_cols, t_real, window=None):
    """Masked scaled scores for one tile.  The scale folds into the small
    (nb,bq,d) q operand instead of the (nb,bq,bk) score tile — 16x fewer
    VPU multiplies at d=64."""
    q = (q.astype(jnp.float32) * jnp.float32(scale)).astype(q.dtype)
    s = _qk(q, k)
    mask = _tile_mask(i, j, bq, bk, causal, offset, t_real, pad_cols, window)
    if mask is not None:
        s = jnp.where(mask, s, jnp.float32(_NEG_INF))
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                scale, causal, offset, bq, bk, nk, t_real, pad_cols,
                window=None):
    i, j = pl.program_id(1), pl.program_id(2)
    qv = _rld(q_ref)
    kv, vv = _same_heads(qv, _rld(k_ref)), _same_heads(qv, _rld(v_ref))

    if nk == 1:
        # no scratch is declared for the one-pass path (scratch == ())
        # one-pass softmax: the whole kv row is in this tile, so the online
        # rescaling carry (alpha, running m/l broadcasts) is dead weight
        s = _scaled_scores(qv, kv, i, j, scale=scale, causal=causal,
                           offset=offset, bq=bq, bk=bk, pad_cols=pad_cols,
                           t_real=t_real, window=window)
        m = jnp.max(s, axis=2, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.maximum(jnp.sum(p, axis=2, keepdims=True),
                        jnp.float32(1e-30))
        o_ref[...] = (_pv(p.astype(vv.dtype), vv) / l).astype(
            o_ref.dtype)
        lse_ref[...] = m + jnp.log(l)
        return

    acc, m_i, l_i = scratch

    @pl.when(j == 0)
    def _init():
        m_i[:] = jnp.full_like(m_i, _NEG_INF)
        l_i[:] = jnp.zeros_like(l_i)
        acc[:] = jnp.zeros_like(acc)

    # a kv block strictly above the diagonal band, or wholly before the
    # window, has nothing to do
    @pl.when(_tile_live(i, j, bq, bk, causal, offset, window))
    def _compute():
        s = _scaled_scores(qv, kv, i, j, scale=scale, causal=causal,
                           offset=offset, bq=bq, bk=bk, pad_cols=pad_cols,
                           t_real=t_real, window=window)
        m_prev = m_i[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_i[:, :, :1] + jnp.sum(p, axis=2, keepdims=True)
        acc[:] = acc[:] * alpha + _pv(p.astype(vv.dtype), vv)
        m_i[:] = jnp.broadcast_to(m_new, m_i.shape)
        l_i[:] = jnp.broadcast_to(l_new, l_i.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_i[:, :, :1], jnp.float32(1e-30))
        o_ref[...] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[...] = m_i[:, :, :1] + jnp.log(l)


def _flash_fwd(q, k, v, scale, causal, window=None):
    """q: [BH, Tq, D], k: [BH / group, Tk, D], v: [BH / group, Tk, Dv] →
    (out [BH,Tq,Dv], lse [BH,Tq,1]).  With group > 1 (grouped-query
    attention) a grid step holds one KV head and the `group` query heads
    that read it: the K and V tiles are fetched once for all of them.  The
    values' head size may differ from the queries' and keys' (latent
    attention's expanded form: 192 and 128); forward only."""
    bh, tq, d = q.shape
    tk, dv = k.shape[1], v.shape[2]
    group = bh // k.shape[0]
    bq, bk = _block_sizes(tq, tk)
    if group > 1:
        nb = group
        while nb * bq * bk * 4 > 16 * 1024 * 1024 and bq > 128:
            bq //= 2        # the f32 score tile of the whole group in VMEM
    else:
        nb = _head_block(bh, bq, bk)
    nkv = nb // group       # KV heads per grid step
    qp = _pad_to(q, 1, bq)
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    tqp, tkp = qp.shape[1], kp.shape[1]
    nq, nk = tqp // bq, tkp // bk
    offset = tk - tq  # causal diagonal shift for cached decode

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, offset=offset,
        bq=bq, bk=bk, nk=nk, t_real=tk, pad_cols=(tkp != tk), window=window)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh // nb, nq, nk),
        in_specs=[
            pl.BlockSpec((nb, bq, d), lambda b, i, j: (b, i, j * 0)),
            pl.BlockSpec((nkv, bk, d), lambda b, i, j: (b, j, i * 0)),
            pl.BlockSpec((nkv, bk, dv), lambda b, i, j: (b, j, i * 0)),
        ],
        out_specs=[
            pl.BlockSpec((nb, bq, dv), lambda b, i, j: (b, i, j * 0)),
            pl.BlockSpec((nb, bq, 1), lambda b, i, j: (b, i, j * 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tqp, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, tqp, 1), jnp.float32),
        ],
        scratch_shapes=[] if nk == 1 else [
            pltpu.VMEM((nb, bq, dv), jnp.float32),
            pltpu.VMEM((nb, bq, 128), jnp.float32),
            pltpu.VMEM((nb, bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_INTERPRET,
    )(qp, kp, vp)
    return out[:, :tq], lse[:, :tq]  # lse: [BH, Tq, 1]


# -- backward -----------------------------------------------------------------

def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *out_refs, scale, causal, offset,
                      bq, bk, t_real, pad_cols, fused_out=False,
                      window=None):
    """Single-tile backward (nq == nk == 1): dq, dk, dv in one pass sharing
    one recomputation of s/p — the two-kernel split exists only to give
    each output a sequential accumulation dimension, which a single tile
    does not need.  With ``fused_out`` the three grads go into role slices
    of ONE (nbf, 3, bq, d) output block, so XLA materializes a single
    layout copy for d_qkv instead of three."""
    q, k, v = _rld(q_ref), _rld(k_ref), _rld(v_ref)
    do = do_ref[...]
    qs = (q.astype(jnp.float32) * jnp.float32(scale)).astype(q.dtype)
    s = _qk(qs, k)
    mask = _tile_mask(0, 0, bq, bk, causal, offset, t_real, pad_cols, window)
    if mask is not None:
        s = jnp.where(mask, s, jnp.float32(_NEG_INF))
    p = jnp.exp(s - lse_ref[...])
    pt = p.astype(do.dtype)
    dv = _tq_contract(pt, do)
    dp = _qk(do, v)
    ds = (p * (dp - delta_ref[...])).astype(q.dtype)  # scale folded below
    ks = (k.astype(jnp.float32) * jnp.float32(scale)).astype(q.dtype)
    dq = _pv(ds, ks)
    dk = _tq_contract(ds, qs)
    if fused_out:
        (dqkv_ref,) = out_refs
        dqkv_ref[:, 0] = dq.astype(dqkv_ref.dtype)
        dqkv_ref[:, 1] = dk.astype(dqkv_ref.dtype)
        dqkv_ref[:, 2] = dv.astype(dqkv_ref.dtype)
    else:
        dq_ref, dk_ref, dv_ref = out_refs
        dq_ref[...] = dq.astype(dq_ref.dtype)
        dk_ref[...] = dk.astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, offset, bq, bk, nk, t_real,
                   pad_cols, window=None):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_tile_live(i, j, bq, bk, causal, offset, window))
    def _compute():
        q, k, v = _rld(q_ref), _rld(k_ref), _rld(v_ref)
        do = do_ref[...]
        s = _scaled_scores(q, k, i, j, scale=scale, causal=causal,
                           offset=offset, bq=bq, bk=bk, pad_cols=pad_cols,
                           t_real=t_real, window=window)
        p = jnp.exp(s - lse_ref[...])
        dp = _qk(do, v)                    # (nb, bq, bk)
        ds = p * (dp - delta_ref[...])     # scale folds into k below
        ks = (k.astype(jnp.float32) * jnp.float32(scale)).astype(k.dtype)
        dq_acc[:] += _pv(ds.astype(k.dtype), ks)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[...] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, causal, offset, bq, bk, nq, t_real, pad_cols,
                    window=None):
    j, i = pl.program_id(1), pl.program_id(2)  # j: kv block, i: q block

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_tile_live(i, j, bq, bk, causal, offset, window))
    def _compute():
        q, k, v = _rld(q_ref), _rld(k_ref), _rld(v_ref)
        do = do_ref[...]
        qs = (q.astype(jnp.float32) * jnp.float32(scale)).astype(q.dtype)
        s = _qk(qs, k)
        mask = _tile_mask(i, j, bq, bk, causal, offset, t_real, pad_cols,
                          window)
        if mask is not None:
            s = jnp.where(mask, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse_ref[...])
        dv_acc[:] += _tq_contract(p.astype(do.dtype), do)
        dp = _qk(do, v)
        ds = p * (dp - delta_ref[...])     # scale folds into qs below
        dk_acc[:] += _tq_contract(ds.astype(q.dtype), qs)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[...] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, scale, causal, window=None):
    bh, tq, d = q.shape
    group = bh // k.shape[0]
    if group > 1:
        # grouped-query heads: the kernels below hold one KV head per query
        # head, so K and V are repeated going in and their gradients summed
        # over each group coming out (serving never differentiates; a
        # training path for grouped heads would fold this into the kernels)
        dq, dk, dv = _flash_bwd(q, jnp.repeat(k, group, axis=0),
                                jnp.repeat(v, group, axis=0), o, lse, do,
                                scale, causal, window)
        fold = lambda g: g.reshape(  # noqa: E731
            (bh // group, group) + g.shape[1:]).sum(axis=1).astype(k.dtype)
        return dq, fold(dk), fold(dv)
    tk = k.shape[1]
    bq, bk = _block_sizes(tq, tk)
    nb = _head_block(bh, bq, bk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [BH, Tq, 1]
    qp, dop = _pad_to(q, 1, bq), _pad_to(do, 1, bq)
    kp, vp = _pad_to(k, 1, bk), _pad_to(v, 1, bk)
    # pad lse with a huge value (and delta with zeros): padded q rows then
    # produce p=exp(-1e30-big)=0 contributions in the dkv kernel
    lsep = _pad_to(lse, 1, bq)
    lsep = lsep.at[:, tq:].set(1e30) if lsep.shape[1] > tq else lsep
    deltap = _pad_to(delta, 1, bq)
    tqp, tkp = qp.shape[1], kp.shape[1]
    nq, nk = tqp // bq, tkp // bk
    offset = tk - tq

    if nq == 1 and nk == 1:
        fused = functools.partial(
            _bwd_fused_kernel, scale=scale, causal=causal, offset=offset,
            bq=bq, bk=bk, t_real=tk, pad_cols=(tkp != tk), window=window)
        # one score tile per invocation: halve the head block vs the
        # split kernels' budget since dq/dk/dv tiles coexist in VMEM
        nbf = max(1, _head_block(bh, bq, bk) // 2)
        assert bh % nbf == 0  # nbf divides _head_block's pick, which divides bh
        # NOTE: index maps must reference the grid vars (b, i, j*0) — this
        # backend's Mosaic fails to legalize constant-only maps
        qmap = lambda b, i, j: (b, i, j * 0)       # noqa: E731
        kmap = lambda b, i, j: (b, j, i * 0)       # noqa: E731
        dq, dk, dv = pl.pallas_call(
            fused,
            name="flash_bwd",
            grid=(bh // nbf, 1, 1),
            in_specs=[
                pl.BlockSpec((nbf, bq, d), qmap),
                pl.BlockSpec((nbf, bk, d), kmap),
                pl.BlockSpec((nbf, bk, d), kmap),
                pl.BlockSpec((nbf, bq, d), qmap),
                pl.BlockSpec((nbf, bq, 1), qmap),
                pl.BlockSpec((nbf, bq, 1), qmap),
            ],
            out_specs=[
                pl.BlockSpec((nbf, bq, d), qmap),
                pl.BlockSpec((nbf, bk, d), kmap),
                pl.BlockSpec((nbf, bk, d), kmap),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, tqp, d), q.dtype),
                jax.ShapeDtypeStruct((bh, tkp, d), k.dtype),
                jax.ShapeDtypeStruct((bh, tkp, d), v.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=_INTERPRET,
        )(qp, kp, vp, dop, lsep, deltap)
        return dq[:, :tq], dk[:, :tk], dv[:, :tk]

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, offset=offset,
        bq=bq, bk=bk, nk=nk, t_real=tk, pad_cols=(tkp != tk), window=window)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd",
        grid=(bh // nb, nq, nk),
        in_specs=[
            pl.BlockSpec((nb, bq, d), lambda b, i, j: (b, i, j * 0)),
            pl.BlockSpec((nb, bk, d), lambda b, i, j: (b, j, i * 0)),
            pl.BlockSpec((nb, bk, d), lambda b, i, j: (b, j, i * 0)),
            pl.BlockSpec((nb, bq, d), lambda b, i, j: (b, i, j * 0)),
            pl.BlockSpec((nb, bq, 1), lambda b, i, j: (b, i, j * 0)),
            pl.BlockSpec((nb, bq, 1), lambda b, i, j: (b, i, j * 0)),
        ],
        out_specs=pl.BlockSpec((nb, bq, d), lambda b, i, j: (b, i, j * 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tqp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((nb, bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_INTERPRET,
    )(qp, kp, vp, dop, lsep, deltap)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, offset=offset,
        bq=bq, bk=bk, nq=nq, t_real=tk, pad_cols=(tkp != tk), window=window)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd",
        grid=(bh // nb, nk, nq),
        in_specs=[
            pl.BlockSpec((nb, bq, d), lambda b, j, i: (b, i, j * 0)),
            pl.BlockSpec((nb, bk, d), lambda b, j, i: (b, j, i * 0)),
            pl.BlockSpec((nb, bk, d), lambda b, j, i: (b, j, i * 0)),
            pl.BlockSpec((nb, bq, d), lambda b, j, i: (b, i, j * 0)),
            pl.BlockSpec((nb, bq, 1), lambda b, j, i: (b, i, j * 0)),
            pl.BlockSpec((nb, bq, 1), lambda b, j, i: (b, i, j * 0)),
        ],
        out_specs=[
            pl.BlockSpec((nb, bk, d), lambda b, j, i: (b, j, i * 0)),
            pl.BlockSpec((nb, bk, d), lambda b, j, i: (b, j, i * 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tkp, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tkp, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((nb, bk, d), jnp.float32),
            pltpu.VMEM((nb, bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_INTERPRET,
    )(qp, kp, vp, dop, lsep, deltap)
    return dq[:, :tq], dk[:, :tk], dv[:, :tk]


# -- fused-qkv drivers --------------------------------------------------------
#
# Layout [BH, 3, T, D]: ONE custom-call operand carries q, k and v.  The
# same array is passed three times with role-selecting index maps, so XLA
# materializes a single layout copy at the call boundary instead of three
# (docs/PERF.md layout-copy tax); the single-tile backward writes the three
# grads into role slices of one output for the same reason.

def _role_specs(nb, bq, bk, d):
    # NOTE: every index-map coordinate must involve a grid variable — this
    # backend's Mosaic fails to legalize constant-only coordinates
    # ("failed to legalize func.return", docs/PERF.md), so the role constants
    # are written j*0 + r
    qmap = lambda b, i, j: (b, j * 0, i, j * 0)            # noqa: E731
    kmap = lambda b, i, j: (b, i * 0 + 1, j, i * 0)        # noqa: E731
    vmap = lambda b, i, j: (b, i * 0 + 2, j, i * 0)        # noqa: E731
    return [pl.BlockSpec((nb, 1, bq, d), qmap),
            pl.BlockSpec((nb, 1, bk, d), kmap),
            pl.BlockSpec((nb, 1, bk, d), vmap)]


def _flash_fused_fwd_impl(qkv, scale, causal):
    """qkv: [BH, 3, T, D] → (out [BH, T, D], lse [BH, T, 1])."""
    bh, three, t, d = qkv.shape
    assert three == 3
    bq, bk = _block_sizes(t, t)
    nb = _head_block(bh, bq, bk)
    qkvp = _pad_to(qkv, 2, max(bq, bk))
    tp = qkvp.shape[2]
    nq, nk = tp // bq, tp // bk

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, offset=0,
        bq=bq, bk=bk, nk=nk, t_real=t, pad_cols=(tp != t))
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh // nb, nq, nk),
        in_specs=_role_specs(nb, bq, bk, d),
        out_specs=[
            pl.BlockSpec((nb, bq, d), lambda b, i, j: (b, i, j * 0)),
            pl.BlockSpec((nb, bq, 1), lambda b, i, j: (b, i, j * 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tp, d), qkv.dtype),
            jax.ShapeDtypeStruct((bh, tp, 1), jnp.float32),
        ],
        scratch_shapes=[] if nk == 1 else [
            pltpu.VMEM((nb, bq, d), jnp.float32),
            pltpu.VMEM((nb, bq, 128), jnp.float32),
            pltpu.VMEM((nb, bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_INTERPRET,
    )(qkvp, qkvp, qkvp)
    return out[:, :t], lse[:, :t]


def _flash_fused_bwd_impl(qkv, o, lse, do, scale, causal):
    bh, _, t, d = qkv.shape
    bq, bk = _block_sizes(t, t)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    qkvp = _pad_to(qkv, 2, max(bq, bk))
    dop = _pad_to(do, 1, bq)
    lsep = _pad_to(lse, 1, bq)
    lsep = lsep.at[:, t:].set(1e30) if lsep.shape[1] > t else lsep
    deltap = _pad_to(delta, 1, bq)
    tp = qkvp.shape[2]
    nq, nk = tp // bq, tp // bk

    if nq == 1 and nk == 1:
        fused = functools.partial(
            _bwd_fused_kernel, scale=scale, causal=causal, offset=0,
            bq=bq, bk=bk, t_real=t, pad_cols=(tp != t), fused_out=True)
        nbf = max(1, _head_block(bh, bq, bk) // 2)
        qmap3 = lambda b, i, j: (b, i, j * 0)      # noqa: E731
        dqkv = pl.pallas_call(
            fused,
            name="flash_bwd",
            grid=(bh // nbf, 1, 1),
            in_specs=_role_specs(nbf, bq, bk, d) + [
                pl.BlockSpec((nbf, bq, d), qmap3),
                pl.BlockSpec((nbf, bq, 1), qmap3),
                pl.BlockSpec((nbf, bq, 1), qmap3),
            ],
            out_specs=pl.BlockSpec((nbf, 3, bq, d),
                                   lambda b, i, j: (b, j * 0, i, j * 0)),
            out_shape=jax.ShapeDtypeStruct((bh, 3, tp, d), qkv.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=_INTERPRET,
        )(qkvp, qkvp, qkvp, dop, lsep, deltap)
        return dqkv[:, :, :t]

    # multi-tile fallback: role views through the split kernels, stacked at
    # the end (one extra copy — the single-tile path is the hot one)
    q3 = qkv[:, 0]
    k3 = qkv[:, 1]
    v3 = qkv[:, 2]
    dq, dk, dv = _flash_bwd(q3, k3, v3, o, lse, do, scale, causal)
    return jnp.stack([dq, dk, dv], axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _flash_fused(qkv, scale, causal):
    out, _ = _flash_fused_fwd_impl(qkv, scale, causal)
    return out


def _flash_fused_fwd_rule(qkv, scale, causal):
    out, lse = _flash_fused_fwd_impl(qkv, scale, causal)
    return out, (qkv, out, lse)


def _flash_fused_bwd_rule(scale, causal, res, do):
    qkv, out, lse = res
    return (_flash_fused_bwd_impl(qkv, out, lse, do, scale, causal),)


_flash_fused.defvjp(_flash_fused_fwd_rule, _flash_fused_bwd_rule)


def flash_attention_qkv_fused(qkv, causal=True, scale=None):
    """Self-attention on the fused [BH, 3, T, D] qkv tensor (jax arrays)."""
    if scale is None:
        scale = 1.0 / math.sqrt(qkv.shape[-1])
    return _flash_fused(qkv, float(scale), bool(causal))


# -- custom_vjp glue ----------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, scale, causal, window):
    out, _ = _flash_fwd(q, k, v, scale, causal, window)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, window):
    out, lse = _flash_fwd(q, k, v, scale, causal, window)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(scale, causal, window, res, do):
    q, k, v, out, lse = res
    if v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            "flash attention with values of another head size than the "
            "queries is forward only (serving)")
    return _flash_bwd(q, k, v, out, lse, do, scale, causal, window)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# -- public API ---------------------------------------------------------------

def flash_attention_bhtd(q, k, v, causal=True, scale=None, window=None):
    """q: [BH, T, D]; k: [BH / group, T, D]; v: [BH / group, T, Dv] (group
    1, or grouped-query heads: query head i reads KV head i // group; Dv
    other than D in the forward pass only).  `window` (causal only)
    keeps keys t - window + 1 .. t for query t; tiles wholly before the
    window are skipped like those above the diagonal."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None and not causal:
        raise ValueError("window= needs causal=True")
    return _flash(q, k, v, float(scale), bool(causal),
                  None if window is None else int(window))


def flash_attention_bthd(q, k, v, causal=True, scale=None, window=None):
    """Paddle fused-op layout [B, T, H, D] (Tensor or jax.Array in/out);
    k, v may hold fewer heads (a divisor of q's)."""
    from ..core.op import apply_op
    from ..core.tensor import Tensor

    def raw(qv, kv, vv):
        b, tq, h, d = qv.shape
        tk, hk, dv = kv.shape[1], kv.shape[2], vv.shape[3]
        q3 = jnp.transpose(qv, (0, 2, 1, 3)).reshape(b * h, tq, d)
        k3 = jnp.transpose(kv, (0, 2, 1, 3)).reshape(b * hk, tk, d)
        v3 = jnp.transpose(vv, (0, 2, 1, 3)).reshape(b * hk, tk, dv)
        o3 = flash_attention_bhtd(q3, k3, v3, causal=causal, scale=scale,
                                  window=window)
        return jnp.transpose(o3.reshape(b, h, tq, dv), (0, 2, 1, 3))

    if isinstance(q, Tensor):
        return apply_op(raw, "flash_attention", (q, k, v), {})
    return raw(q, k, v)
