"""Paged decode-attention as a Pallas TPU kernel (ISSUE 19) — the fused
read for the serving engine's paged KV pool (docs/serving.md "Paged KV").

The XLA paged read gathers every slot's pages into a ``[B, L_virt, heads,
head_dim]`` temp per layer and (int8 pools) dequantizes as a separate
pass, so HBM streams f32 gather bytes regardless of what the pool stores.
This kernel walks the page table directly instead:

* the per-slot int32 page table and lengths ride as **scalar-prefetch**
  operands (SMEM, available before the body runs), so the K/V block
  index maps read them and Pallas's pipeline DMAs exactly the
  ``[page_size, heads, head_dim]`` page each grid step needs from HBM
  into VMEM, one step ahead of the compute — no ``[B, L_virt, ...]``
  gather temp exists anywhere.  (Blocks whose trailing dims are the
  whole ``(heads, head_dim)`` are legal for any head count and width; a
  hand-rolled ``make_async_copy`` of ``pool.at[page]`` is not — Mosaic
  refuses the slice when ``head_dim`` is 64 or ``heads`` is 12);
* int8 pools dequantize **inside the page read** (``q_i8 * scale`` on the
  VMEM tile), so HBM streams the int8 pool bytes — the stored-bytes
  ratio becomes the streamed-bytes ratio;
* pages past a row's live span (``start + W``) are skipped entirely: the
  index map parks on the row's last live page (an unchanged block index
  is not fetched again) and the body does not run, so bytes scale with
  the tokens actually resident, not the table width.

Grid ``(B, 2, n_pt)``, phases sequential per row (``arbitrary``):

* phase 0 streams the row's K pages and writes masked scaled scores into
  a per-row VMEM scores scratch ``[n_pt, heads, W, page_size]`` — one
  leading-dim slot per page, because a ``page_size``-wide store at a
  dynamic lane offset is only legal at multiples of 128 (position ``p``
  attends to query ``j`` iff ``p <= start + j`` — the
  causal-within-span + validity mask of models/gpt.py's paged branch,
  bit for bit);
* phase 1 softmaxes the **whole** scores row in one shot (same f32
  exp/sum shape as ``_sdpa_ref``'s ``jax.nn.softmax``, which keeps
  greedy argmax aligned with the XLA path), then streams the row's V
  pages and accumulates ``probs @ V`` per page.  While one phase runs,
  the other pool's index map stands still, so K and V are each read
  once.

Two phases read K then V once each — the same HBM traffic as a one-pass
online-softmax kernel, without the rescaling carry.  Sentinel table
entries (``>= num_pages``) clamp to the last physical page exactly like
the XLA gather's ``pt_safe`` clip; parked rows (``start == L_virt``)
produce the same never-read garbage either way.

On the ``cpu`` backend the kernel runs in Pallas interpret mode (the
tier-1 parity gates; :func:`use_interpret_mode` pins it either way);
on every other backend it is compiled by Mosaic, and what Mosaic
cannot take is refused by :func:`check_supported` when the engine is
built (tests/test_kernels_tpu_aot.py keeps the accepted matrix
compiling between chip runs).  The serving engine routes decode through
here only inside :func:`decode_kernel_scope` (``Engine(decode_kernel="pallas")``), the
same trace-local mechanism the multi-LoRA adapter path uses.
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024

# interpret-mode pin: None = by backend (interpret on cpu, compile
# everywhere else); use_interpret_mode() pins it for tests/debugging
_INTERPRET = None


def use_interpret_mode(flag):
    """Pin interpret mode on/off, or ``None`` to restore the default."""
    global _INTERPRET
    _INTERPRET = None if flag is None else bool(flag)


def _interpret_now() -> bool:
    if _INTERPRET is not None:
        return _INTERPRET
    return jax.default_backend() == "cpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def check_supported(*, page_size: int, max_pages_per_slot: int, heads: int,
                    width: int = 1):
    """Raise ``ValueError`` for a configuration Mosaic cannot compile.

    Page size, head count, head_dim and pool dtype are unconstrained
    (tests/test_kernels_tpu_aot.py).  The one limit is VMEM: the scores
    scratch holds a whole row, ``[max_pages_per_slot, heads, W,
    page_size]`` f32 with the trailing ``(W, page_size)`` padded to an
    ``(8, 128)`` tile, and the whole-row softmax needs as much again for
    its temporaries."""
    scratch = (max_pages_per_slot * heads * _round_up(width, 8)
               * _round_up(page_size, 128) * 4)
    if 2 * scratch > _VMEM_LIMIT_BYTES:
        raise ValueError(
            f"decode_kernel='pallas' keeps one row's attention scores in "
            f"VMEM: max_pages_per_slot={max_pages_per_slot} x heads={heads} "
            f"x one (8, 128) f32 tile per page_size={page_size} page needs "
            f"{scratch / 2**20:.0f} MiB (x2 for the softmax), over the "
            f"{_VMEM_LIMIT_BYTES / 2**20:.0f} MiB limit — use a larger "
            f"page_size (fewer, fuller pages) or a shorter virtual length")


# -- trace-local routing scope ------------------------------------------------
#
# The engine enters this scope inside its decode jit (and only there), so
# the model's paged cache branch routes its attention read through the
# kernel for exactly that program — prefill/tail-prefill keep the XLA
# read, and the decode signature count stays at ONE per config (the scope
# is a trace-time routing decision, not an operand).

_TLS = threading.local()


@contextlib.contextmanager
def decode_kernel_scope():
    prev = getattr(_TLS, "active", False)
    _TLS.active = True
    try:
        yield
    finally:
        _TLS.active = prev


def active() -> bool:
    """True while tracing inside :func:`decode_kernel_scope`."""
    return getattr(_TLS, "active", False)


# -- analytic cost registration (observability/perfscope.py) ------------------
#
# XLA's cost_analysis books a pallas custom call at zero flops/bytes, so
# the kernel registers its own analytic numbers once per shape signature
# — the per-program roofline (PR 14) then attributes kernel dispatches
# the same way it does the jit programs around them.

_COSTS_BOOKED = set()
PERFSCOPE_PROGRAM = "kernels.paged_attention"


def _book_cost(B, W, H, D, P, n_pt, pool_dtype):
    quant = pool_dtype == jnp.int8
    key = (f"B{B}xW{W}xH{H}xD{D}/P{P}x{n_pt}"
           + ("/int8" if quant else f"/{jnp.dtype(pool_dtype).name}"))
    if key in _COSTS_BOOKED:
        return
    _COSTS_BOOKED.add(key)
    virt = n_pt * P
    # QK^T + probs@V: 2 matmuls of [W, virt] x [virt, D] per head per row
    flops = 4.0 * B * H * W * virt * D
    esize = jnp.dtype(pool_dtype).itemsize
    pool_bytes = 2.0 * B * virt * H * D * esize      # K + V pages streamed
    if quant:
        pool_bytes += 2.0 * B * virt * 4             # f32 scale sidecars
    io_bytes = 2.0 * B * W * H * D * 4               # q in + out
    try:
        from ..observability import perfscope
        perfscope.register_cost(PERFSCOPE_PROGRAM, key,
                                {"flops": flops,
                                 "bytes accessed": pool_bytes + io_bytes})
    except Exception:  # noqa: BLE001 — observability must never break math
        pass


# -- kernel body --------------------------------------------------------------

def _decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                   P, n_pt, W, scale, quant):
    if quant:
        ks_ref, vs_ref, o_ref, s_ref, acc_ref = rest
    else:
        o_ref, s_ref, acc_ref = rest
    b, ph, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    start = len_ref[b]
    # page i holds positions [i*P, (i+1)*P): live for this row iff any of
    # them is attendable by the widest query (start + W - 1)
    needed = (i * P) < (start + W)

    def _page(ref, sc_ref):
        """This step's K/V page, head-major ``[H, P, D]`` f32, dequantized
        (the ``[P, 1]`` scale column broadcasts along head_dim)."""
        xh = jnp.transpose(ref[0].astype(jnp.float32), (1, 0, 2))
        return xh * sc_ref[0][None] if quant else xh

    @pl.when((ph == 0) & needed)
    def _scores():
        kh = _page(k_ref, ks_ref if quant else None)
        qh = jnp.transpose(q_ref[0].astype(jnp.float32), (1, 0, 2))
        s = jax.lax.dot_general(
            qh, kh, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        col = i * P + jax.lax.broadcasted_iota(jnp.int32, (W, P), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (W, P), 0)
        s_ref[i] = jnp.where((col <= start + row)[None], s,
                             jnp.float32(_NEG_INF))

    @pl.when((ph == 0) & jnp.logical_not(needed))
    def _dead():
        # pages past the live span: their scores are -inf, so phase 1's
        # probs underflow to exactly 0 and the page is skipped
        s_ref[i] = jnp.full(s_ref.shape[1:], _NEG_INF, jnp.float32)

    @pl.when((ph == 1) & (i == 0))
    def _softmax():
        # whole-row softmax in one shot (the _sdpa_ref f32 exp/sum shape)
        # over the [n_pt, H, W, P] scratch: positions span dims 0 and 3;
        # probs overwrite the scores in place
        s = s_ref[...]
        m = jnp.max(jnp.max(s, axis=-1, keepdims=True), axis=0,
                    keepdims=True)
        p = jnp.exp(s - m)
        denom = jnp.sum(jnp.sum(p, axis=-1, keepdims=True), axis=0,
                        keepdims=True)
        s_ref[...] = p / denom
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((ph == 1) & needed)
    def _weighted():
        vh = _page(v_ref, vs_ref if quant else None)
        acc_ref[...] += jax.lax.dot_general(
            s_ref[i], vh, (((2,), (1,)), ((0,), (0,))),     # [H, W, P]
            preferred_element_type=jnp.float32)

    @pl.when((ph == 1) & (i == n_pt - 1))
    def _finish():
        o_ref[0] = jnp.transpose(acc_ref[...], (1, 0, 2)).astype(o_ref.dtype)


# -- public API ---------------------------------------------------------------

def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           k_scale=None, v_scale=None, scale=None):
    """Fused paged attention read for per-slot decode.

    Args:
        q: ``[B, W, heads, head_dim]`` queries (W=1 plain decode, W=k
            speculative verify), already holding the step's new
            positions ``start .. start+W-1``.
        k_pages / v_pages: ``[num_pages, page_size, heads, head_dim]``
            pools, float (model dtype) or int8 — **post-write**: the
            step's scatter must already have landed so the read attends
            over the new positions exactly like the XLA path.
        page_table: ``[B, n_pt]`` int32; entries ``>= num_pages`` are
            sentinels (parked / unallocated).
        lengths: ``[B]`` int32 per-row start positions (parked rows sit
            at ``n_pt * page_size``).
        k_scale / v_scale: ``[num_pages, page_size]`` f32 absmax scales,
            required iff the pools are int8 (serving/kv_quant.py).

    Returns:
        ``[B, W, heads, head_dim]`` attention output in ``q.dtype``.
    """
    B, W, H, D = q.shape
    NP, P = k_pages.shape[0], k_pages.shape[1]
    n_pt = page_table.shape[1]
    quant = k_pages.dtype == jnp.int8
    if quant != (k_scale is not None):
        raise ValueError("int8 pools need k_scale/v_scale and f32 pools "
                         f"must not pass them (pool {k_pages.dtype}, "
                         f"k_scale={'set' if k_scale is not None else None})")
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    _book_cost(B, W, H, D, P, n_pt, k_pages.dtype)

    # Index maps run on the scalar core: every constant is an explicit
    # int32 (x64 is on, so a Python int would trace as i64, which Mosaic
    # does not legalize — hence also the `x * 0` zeros).
    i32 = jnp.int32

    def _last(b, ln):           # the row's last live page slot
        return jnp.minimum(jax.lax.div(ln[b] + i32(W - 1), i32(P)),
                           i32(n_pt - 1))

    def _block(slot_of):
        """Block index of the page the step reads: walks the row's live
        slots during its own phase, stands still otherwise (an unchanged
        index is not fetched again).  Sentinel entries (>= NP) clamp to
        the last physical page — the bytes the XLA gather's pt_safe clip
        reads, masked out by the validity mask."""
        def index_map(b, ph, i, pt, ln):
            pid = jnp.minimum(pt[b, slot_of(b, ph, i, ln)], i32(NP - 1))
            return (pid,) + (ph * 0,) * 3
        return index_map

    def _kslot(b, ph, i, ln):
        last = _last(b, ln)
        return jax.lax.select(ph == 0, jnp.minimum(i, last), last)

    def _vslot(b, ph, i, ln):
        return jax.lax.select(ph == 1, jnp.minimum(i, _last(b, ln)), i * 0)

    def _qmap(b, ph, i, pt, ln):
        return (b, ph * 0, ph * 0, ph * 0)

    kmap, vmap = _block(_kslot), _block(_vslot)
    in_specs = [pl.BlockSpec((1, W, H, D), _qmap),
                pl.BlockSpec((1, P, H, D), kmap),
                pl.BlockSpec((1, P, H, D), vmap)]
    operands = [jnp.asarray(page_table, jnp.int32),
                jnp.asarray(lengths, jnp.int32), q, k_pages, v_pages]
    if quant:
        # the scale sidecar rides as a [P, 1] column per page (positions
        # on sublanes), the shape that broadcasts over a head-major page
        in_specs += [
            pl.BlockSpec((1, P, 1), lambda *a: kmap(*a)[:3]),
            pl.BlockSpec((1, P, 1), lambda *a: vmap(*a)[:3])]
        operands += [k_scale[..., None], v_scale[..., None]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, 2, n_pt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, W, H, D), _qmap),
        scratch_shapes=[
            pltpu.VMEM((n_pt, H, W, P), jnp.float32),  # scores, then probs
            pltpu.VMEM((H, W, D), jnp.float32),        # output accumulator
        ],
    )
    kernel = functools.partial(
        _decode_kernel, P=P, n_pt=n_pt, W=W, scale=float(scale), quant=quant)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, W, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # rows are independent (parallel); the phase/page dims carry
            # the scores scratch and must run sequentially per row
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret_now(),
    )(*operands)
