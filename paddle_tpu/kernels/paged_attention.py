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
compiling between chip runs).  The serving engine chooses the decode
program's read once, when it is built, and hands it to the model as the
static ``read`` field of the layer caches (`models/kv_cache.py`
``KernelRead``): this kernel for ``Engine(decode_kernel="pallas")``.

The **dense** pool's decode read (:func:`dense_decode_attention`, at the
end of this file) shares the interpret-mode rule: one pass over each row's
live blocks, chosen where :func:`dense_read_block` says it applies.  The
**latent** pool's read (:func:`latent_decode_attention`, below it) is the
same walk over one shared row per position.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
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
            required iff the pools are int8 (models/kv_cache.py).

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


# -- the dense pool's decode read ---------------------------------------------
#
# The engine's dense pool is ``[n_rows, max_len, heads, head_dim]`` per
# layer, reserved whole; a decode step attends, per slot row, over the
# positions that row has written.  The XLA read streams all ``max_len``
# positions of every row under a validity mask (and a parked row, whose
# length is ``max_len``, attends to everything).  This kernel streams, per
# row, only the ``DENSE_BLOCK``-position blocks that hold live positions
# ``0 .. length + W - 1`` and nothing at all for a parked row.
#
# The grid is a **work list** (:func:`_work_list`), one step per live block,
# its length a run-time value (a dynamic grid bound): a dead block costs
# nothing, where a skipped step of a static ``(n_rows, max_len / block)``
# grid costs ~0.35 us — 1.3 ms of a 1.3B decode step (PERF.md section 5).
# The step list and the lengths ride as scalar-prefetch operands; a step
# holds the K and the V block of the same positions and folds them into a
# running (max, sum, accumulator) per (query, head): an f32 softmax over the
# whole row, computed blockwise in ONE pass.  K and V stay in the pool's own
# ``[block, heads, head_dim]`` layout: merged to ``[block * heads, head_dim]``
# (a free reshape) they are plain matmul operands, ``q [W * heads, head_dim]``
# against every (position, head) pair, and the mask keeps the pairs whose
# heads agree — the MXU is otherwise idle in a decode step, and no transpose
# or per-head slice of a block is needed.

# positions per block, timed on the v5e at 25 rows x 2048 (PERF.md section
# 5): a step streams its whole block, so a larger one rounds every row's
# read further up; a smaller one pays the ~0.35 us step more often
DENSE_BLOCK = 128


def first_block(lengths, block: int, window=None, ring=None):
    """The first block a row's read needs: 0, or on a sliding-window layer
    the block that holds position ``length - window + 1``, the oldest key
    the row's first new query still reads.  On a ring of ``ring`` positions
    (position p lives at ``p mod ring``) that block's place in the ring."""
    xp = jnp if isinstance(lengths, jax.Array) else np
    lengths = xp.asarray(lengths, xp.int32)
    if window is None:
        return xp.zeros_like(lengths)
    first = xp.maximum(lengths - (window - 1), 0) // block
    if ring is not None:
        first = first % (ring // block)
    return first.astype(xp.int32)


def live_blocks(lengths, width: int, max_len: int, block: int, window=None,
                ring=None):
    """Blocks of ``block`` positions the dense read streams for each row:
    those that hold positions ``0 .. length + width - 1`` (from
    :func:`first_block` on with a ``window``), none for a parked row
    (``length >= max_len``).  On a ring of ``ring`` positions the same
    blocks, each at its place in the ring and none twice: at most the whole
    ring.  The kernel's work list is built from this count and the engine's
    ``decode_kv_read_positions`` sums it (numpy in, numpy out; jax in, jax
    out)."""
    xp = jnp if isinstance(lengths, jax.Array) else np
    lengths = xp.asarray(lengths, xp.int32)
    n = xp.minimum((lengths + (width + block - 1)) // block,
                   max_len // block) - first_block(lengths, block, window)
    if ring is not None:
        n = xp.minimum(n, ring // block)
    return xp.where(lengths >= max_len, 0, n).astype(xp.int32)


def _work_list(nb, n_blk: int, first=None, ring: bool = False):
    """The grid as a list of steps: one per live block, and one for a
    parked row (it only writes the row's zeros).  Returns ``(steps, row,
    blk, held)``: how many steps there are, and per step its row, its block
    within the row's live run (which starts at block ``first[row]`` and, on
    a ``ring``, wraps past the row's last block to its first), and
    the flat index ``row * n_blk + block`` of the pool block it holds — a
    parked row's step keeps the block of the step before it (an unchanged
    block index is not fetched again).  Entries past ``steps`` are
    unused."""
    xp = jnp if isinstance(nb, jax.Array) else np
    B = nb.shape[0]
    if first is None:
        first = xp.zeros_like(nb)
    per_row = xp.maximum(nb, 1)
    ends = xp.cumsum(per_row, dtype=xp.int32)
    t = xp.arange(B * n_blk, dtype=xp.int32)
    # the row of step t: how many rows end at or before it (one compare,
    # no search loop in the decode program)
    row = xp.minimum((t[:, None] >= ends[None, :]).sum(axis=1),
                     B - 1).astype(xp.int32)
    blk = t - (ends - per_row)[row]
    cummax = jax.lax.cummax if xp is jnp else np.maximum.accumulate
    live = blk < nb[row]
    if ring:
        # a run that wraps is not increasing: hold the last live step's block
        held = (row * n_blk + (first[row] + blk) % n_blk)[
            cummax(xp.where(live, t, 0))]
    else:
        held = cummax(xp.where(live, row * n_blk + first[row] + blk, 0))
    return ends[-1], row, blk.astype(xp.int32), held.astype(xp.int32)


def dense_blocks_held(lengths, width: int, max_len: int, block: int,
                      window=None, ring=None):
    """The ``(row, block)`` of the pool the kernel's grid holds at each step,
    in grid order: what the K/V index map reads, evaluated on the host.  A
    step whose block differs from the step before is a fetch (the tests
    count them against :func:`live_blocks`)."""
    n_blk = (max_len if ring is None else ring) // block
    lengths = np.asarray(lengths)
    steps, _, _, held = _work_list(
        live_blocks(lengths, width, max_len, block, window, ring), n_blk,
        first_block(lengths, block, window, ring), ring is not None)
    return [divmod(int(f), n_blk) for f in held[:int(steps)]]


def dense_block(heads: int, kv_heads: int, max_len: int) -> int:
    """Positions per block of the dense pool's decode read.  A pool of
    grouped-query heads (``kv_heads < heads``) holds ``1 / group`` the bytes
    per position, so its block is ``DENSE_BLOCK`` times the largest power
    of two in the group: a step then streams about the bytes
    ``DENSE_BLOCK`` was timed at.  Also the unit a window layer's ring is
    rounded up to (`models/kv_cache.py` `ring_len`), whatever backend
    reads it."""
    return min(DENSE_BLOCK << ((heads // kv_heads).bit_length() - 1), max_len)


def dense_read_block(*, heads: int, head_dim: int, dtype, width: int,
                     max_len: int, kv_heads=None):
    """The block size at which the dense pool's decode read goes through
    the kernel, or ``None`` where it keeps the XLA read: on the ``cpu``
    backend (unless a test pinned the mode), for an int8 pool, for a
    ``max_len`` the block does not divide, or for a span so wide that the
    ``[width * heads, block * kv_heads]`` scores and the double-buffered K
    and V blocks would not fit VMEM.  The size itself is
    :func:`dense_block`'s."""
    if _INTERPRET is None and jax.default_backend() == "cpu":
        return None
    if jnp.dtype(dtype) == jnp.int8:
        return None
    kv_heads = heads if kv_heads is None else kv_heads
    P = dense_block(heads, kv_heads, max_len)
    vmem = (4 * width * heads * P * kv_heads * 4
            + 4 * P * kv_heads * head_dim * jnp.dtype(dtype).itemsize)
    return None if max_len % P or 2 * vmem > _VMEM_LIMIT_BYTES else P


def _dense_kernel(len_ref, nb_ref, row_ref, blk_ref, held_ref, first_ref,
                  q_ref, k_ref, v_ref, col_ref, qrow_ref, o_ref, m_ref,
                  l_ref, acc_ref, *, P, W, H, Hkv, scale, window, ring):
    t = pl.program_id(0)
    r, i = row_ref[t], blk_ref[t]
    n = nb_ref[r]
    D = q_ref.shape[-1]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(i < n)
    def _block():
        k2 = k_ref[0].reshape(P * Hkv, D)
        v2 = v_ref[0].reshape(P * Hkv, D)
        q2 = q_ref[0].reshape(W * H, D).astype(k2.dtype)
        # [W*H, P*Hkv]: query (w, h') against key (p, h); only the pairs
        # whose query head reads that KV head count (h' // group == h)
        s = jax.lax.dot_general(
            q2, k2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        pos, head = col_ref[0:1, :], col_ref[1:2, :]          # [1, P*Hkv]
        qw, qhead = qrow_ref[:, 0:1], qrow_ref[:, 1:2]        # [W*H, 1]
        if ring is None:
            pos = (first_ref[r] + i) * P + pos
            keep = (head == qhead) & (pos <= len_ref[r] + qw)
        else:
            # a ring of `ring` positions: slot s holds the newest position
            # p = s (mod ring) up to the span's last one; keys carry their
            # RoPE, so their order in the ring does not matter
            slot = jax.lax.rem(first_ref[r] + i,
                               jnp.int32(ring // P)) * P + pos
            last = len_ref[r] + (W - 1)
            back = jax.lax.rem(last, jnp.int32(ring)) - slot
            pos = last - jnp.where(back >= 0, back, back + ring)
            keep = (head == qhead) & (pos <= len_ref[r] + qw) & (pos >= 0)
        if window is not None:
            keep &= pos > len_ref[r] + qw - window
        s = jnp.where(keep, s, jnp.float32(_NEG_INF))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a dropped pair underflows to exactly 0: m_new is finite from block
        # 0 on (position 0 is live for every query of a live row).  On a
        # window layer the first block can lie wholly before a later
        # query's window: its m_new stays at the floor, where exp(0) would
        # count the dropped pairs
        p = jnp.exp(s - m_new)
        if window is not None:
            p = jnp.where(keep, p, 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v2.dtype), v2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i + 1 >= n)
    def _finish():
        # the row's last step; a parked row (n == 0) read nothing, and its
        # output, never read, is zeros
        l = l_ref[...]
        out = jnp.where(l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0] = out.reshape(W, H, D).astype(o_ref.dtype)


def dense_decode_attention(q, k_pool, v_pool, lengths, block=None,
                           scale=None, window=None, limit=None):
    """Per-slot decode attention over the dense pool, streaming live blocks
    only.

    Args:
        q: ``[n_rows, W, heads, head_dim]`` queries of the step's new
            positions ``length .. length + W - 1``.
        k_pool / v_pool: ``[n_rows, max_len, kv_heads, head_dim]`` float
            pools, **post-write** like :func:`paged_decode_attention`;
            ``kv_heads`` divides ``heads`` (query head i reads KV head
            ``i // group``; a block is read once for its whole group).
        window: sliding-window layers: a query at position p reads keys
            ``p - window + 1 .. p``; the work list starts at the block that
            holds the first of them, the mask is exact inside it.
        lengths: ``[n_rows]`` int32 start positions; a parked row sits at
            ``max_len`` and reads nothing.
        block: positions per block (default :data:`DENSE_BLOCK`, at most
            ``max_len``); must divide ``max_len``.
        limit: the rows are RINGS of a window layer, shorter than the
            ``limit`` positions a row addresses (a parked row sits at
            ``limit``): position p lives at ``p mod max_len``, the work
            list is the row's live ring blocks and the mask goes by the
            position a slot holds.

    Returns:
        ``[n_rows, W, heads, head_dim]`` in ``q.dtype`` (zeros for a parked
        row).
    """
    B, W, H, D = q.shape
    L, Hkv = k_pool.shape[1], k_pool.shape[2]
    if H % Hkv:
        raise ValueError(f"{H} query heads are no multiple of {Hkv} KV heads")
    P = min(DENSE_BLOCK, L) if block is None else int(block)
    if L % P:
        raise ValueError(f"block={P} does not divide max_len={L}")
    n_blk = L // P
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    lengths = jnp.asarray(lengths, jnp.int32)
    ring = None if limit is None else L
    if ring is not None and (window is None or window + W - 1 > ring):
        raise ValueError(f"a ring of {ring} positions does not hold a "
                         f"window of {window} and a span of {W}")
    nb = live_blocks(lengths, W, L if ring is None else int(limit), P,
                     window, ring)
    first = first_block(lengths, P, window, ring)
    steps, row, blk, held = _work_list(nb, n_blk, first, ring is not None)
    # which (position, KV head) a column of the scores is, and which (query,
    # KV head it reads) a row: int32 operands, fetched once (their block
    # never moves)
    cols = jnp.stack([jnp.repeat(jnp.arange(P, dtype=jnp.int32), Hkv),
                      jnp.tile(jnp.arange(Hkv, dtype=jnp.int32), P)])
    qrows = jnp.stack([jnp.repeat(jnp.arange(W, dtype=jnp.int32), H),
                       jnp.tile(jnp.arange(H, dtype=jnp.int32) // (H // Hkv),
                                W)], axis=1)
    # index maps run on the scalar core: explicit int32 throughout (x64 is
    # on), and `t * 0` for a zero

    def _kvmap(t, ln, nbr, rw, bk, hd, fb):
        nblk = jnp.int32(n_blk)
        return (jax.lax.div(hd[t], nblk), jax.lax.rem(hd[t], nblk),
                t * 0, t * 0)

    def _qmap(t, ln, nbr, rw, bk, hd, fb):
        return (rw[t], t * 0, t * 0, t * 0)

    def _const(t, *_):
        return (t * 0, t * 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(steps,),
        in_specs=[pl.BlockSpec((1, W, H, D), _qmap),
                  pl.BlockSpec((1, P, Hkv, D), _kvmap),
                  pl.BlockSpec((1, P, Hkv, D), _kvmap),
                  pl.BlockSpec((2, P * Hkv), _const),
                  pl.BlockSpec((W * H, 2), _const)],
        out_specs=pl.BlockSpec((1, W, H, D), _qmap),
        scratch_shapes=[pltpu.VMEM((W * H, 1), jnp.float32),   # running max
                        pltpu.VMEM((W * H, 1), jnp.float32),   # running sum
                        pltpu.VMEM((W * H, D), jnp.float32)],  # accumulator
    )
    kernel = functools.partial(
        _dense_kernel, P=P, W=W, H=H, Hkv=Hkv, scale=float(scale),
        window=None if window is None else int(window), ring=ring)
    return pl.pallas_call(
        kernel,
        name="dense_decode_read",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, W, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # every step carries the row's running softmax: sequential
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret_now(),
    )(lengths, nb, row, blk, held, first, q, k_pool, v_pool, cols, qrows)


# -- the latent pool's decode read --------------------------------------------
#
# Multi-head latent attention stores ONE row per position, ``[c | kr]``, that
# every head reads (`models/kv_cache.py` "The latent kind").  In the absorbed
# form the queries already carry ``W_UK``, so a block of the pool is a plain
# matmul operand twice: scores = q [W * heads, width] x block, and the
# values are the block's own leading ``values`` rows.  A block is fetched
# once for all heads; the work list of live blocks is the dense kernel's.
#
# The kernel reads the pool TRANSPOSED, ``[rows, width, max_len]``, positions
# in the lanes: that is how the TPU keeps a ``[rows, max_len, 576]`` array
# anyway (576 is no multiple of 128, so its default layout puts the
# 16,384-long axis minor), and the transpose is then a relabelling; handed
# the array as it is named, XLA copied every layer's whole pool to the other
# layout and back in every decode step (20 copies of 623 MB: PERF.md section
# 6, PR 31).

# positions per block, timed on the v5e at 33 rows x 16,384 of 576 bf16
# numbers, contexts of 1k-11k (PERF.md section 6, PR 31): 1.24 / 0.80 / 0.61
# / 0.52 / 0.50 ms a layer at 128 / 256 / 512 / 1,024 / 2,048 -- the read is
# compute-bound, so a step's fixed cost counts for more than the rounding;
# past 1,024 the rounding at the cell's mean context of 3.7k takes the rest
LATENT_BLOCK = 1024


def latent_read_block(*, width: int, dtype, max_len: int):
    """The block size at which the latent pool's decode read goes through
    the kernel, or ``None`` where it keeps the XLA read: on the ``cpu``
    backend (unless a test pinned the mode) or for a ``max_len`` the block
    does not divide.  `dense_read_block`'s rule for grouped heads does not
    carry over (one shared row of 1,152 bytes, not ``kv_heads`` rows)."""
    if _INTERPRET is None and jax.default_backend() == "cpu":
        return None
    P = min(LATENT_BLOCK, max_len)
    return None if max_len % P else P


def _latent_kernel(len_ref, nb_ref, row_ref, blk_ref, held_ref, q_ref,
                   c_ref, o_ref, m_ref, l_ref, acc_ref, *, P, W, H, V,
                   scale):
    t = pl.program_id(0)
    r, i = row_ref[t], blk_ref[t]
    n = nb_ref[r]
    D = q_ref.shape[-1]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(i < n)
    def _block():
        c = c_ref[0]                                        # [D, P]
        q2 = q_ref[0].reshape(W * H, D).astype(c.dtype)
        s = jax.lax.dot_general(
            q2, c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        pos = i * P + jax.lax.broadcasted_iota(jnp.int32, (W * H, P), 1)
        # the query of a score row: row // H (none to tell apart at W = 1)
        qw = 0 if W == 1 else jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (W * H, P), 0), jnp.int32(H))
        s = jnp.where(pos <= len_ref[r] + qw, s, jnp.float32(_NEG_INF))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a dropped pair underflows to exactly 0: position 0 is live for
        # every query of a live row, so m_new is finite from block 0 on
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(c.dtype), c[:V], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i + 1 >= n)
    def _finish():
        l = l_ref[...]
        out = jnp.where(l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0] = out.reshape(W, H, V).astype(o_ref.dtype)


def latent_decode_attention(q, pool, lengths, block=None, *, scale: float,
                            values: int):
    """Per-slot decode attention over the dense LATENT pool in the absorbed
    form, streaming live blocks only.

    Args:
        q: ``[n_rows, W, heads, width]`` absorbed queries (``q_nope W_UK``
            beside the rotated ``q_rope``) of the step's new positions.
        pool: ``[n_rows, max_len, width]``, post-write: every head reads
            the one row of a position; its first ``values`` columns are
            the values.
        lengths: ``[n_rows]`` int32 start positions; a parked row sits at
            ``max_len`` and reads nothing.
        block: positions per block (default :data:`LATENT_BLOCK`, at most
            ``max_len``); must divide ``max_len``.
        scale: the model's ``1 / sqrt(nope + rope)`` — not ``width``.

    Returns:
        ``[n_rows, W, heads, values]`` in ``q.dtype`` (zeros for a parked
        row); the caller takes it through ``W_UV``.
    """
    B, W, H, D = q.shape
    L, V = pool.shape[1], int(values)
    P = min(LATENT_BLOCK, L) if block is None else int(block)
    if L % P:
        raise ValueError(f"block={P} does not divide max_len={L}")
    n_blk = L // P
    lengths = jnp.asarray(lengths, jnp.int32)
    nb = live_blocks(lengths, W, L, P)
    steps, row, blk, held = _work_list(nb, n_blk)

    def _cmap(t, ln, nbr, rw, bk, hd):
        nblk = jnp.int32(n_blk)
        return (jax.lax.div(hd[t], nblk), t * 0, jax.lax.rem(hd[t], nblk))

    def _qmap(t, ln, nbr, rw, bk, hd):
        return (rw[t], t * 0, t * 0, t * 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(steps,),
        in_specs=[pl.BlockSpec((1, W, H, D), _qmap),
                  pl.BlockSpec((1, D, P), _cmap)],
        out_specs=pl.BlockSpec((1, W, H, V), _qmap),
        scratch_shapes=[pltpu.VMEM((W * H, 1), jnp.float32),   # running max
                        pltpu.VMEM((W * H, 1), jnp.float32),   # running sum
                        pltpu.VMEM((W * H, V), jnp.float32)],  # accumulator
    )
    kernel = functools.partial(_latent_kernel, P=P, W=W, H=H, V=V,
                               scale=float(scale))
    return pl.pallas_call(
        kernel,
        name="latent_decode_read",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, W, H, V), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret_now(),
    )(lengths, nb, row, blk, held, q, jnp.swapaxes(pool, 1, 2))


_WRITE_LANES = 128      # positions of the block a written position lies in


def _latent_write_kernel(len_ref, new_ref, c_ref, o_ref, *, P, W, n_blk):
    b, j = pl.program_id(0), pl.program_id(1)
    blk = jnp.minimum(jax.lax.div(len_ref[b], jnp.int32(P)) + j,
                      jnp.int32(n_blk - 1))
    out = c_ref[0]                                          # [D, P]
    lane = blk * P + jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    new = new_ref[0]                                        # [D, W]
    for w in range(W):
        out = jnp.where(lane == len_ref[b] + w, new[:, w:w + 1], out)
    o_ref[0] = out


def latent_pool_write(pool, new, lengths):
    """The latent pool with ``new [n_rows, W, width]`` stored at each row's
    positions ``length .. length + W - 1``, in place, in the layout the
    read kernel uses (positions in the lanes; a scatter wants the width
    there and XLA then copies the whole pool both ways).  One grid step a
    row (two where a span can cross a block): the 128-position block that
    holds the position is fetched, the column replaced, the block written
    back.  A parked row (``length >= max_len``) rewrites its last block as
    it was: the write drops."""
    B, L, D = pool.shape
    W = new.shape[1]
    P = min(_WRITE_LANES, L)
    if L % P or W > P:
        raise ValueError(f"max_len={L} must be a multiple of {P} and the "
                         f"span {W} at most {P}")
    n_blk, sub = L // P, (1 if W == 1 else 2)

    def _cmap(b, j, ln):
        return (b, b * 0, jnp.minimum(jax.lax.div(ln[b], jnp.int32(P)) + j,
                                      jnp.int32(n_blk - 1)))

    def _nmap(b, j, ln):
        return (b, b * 0, b * 0)

    out = pl.pallas_call(
        functools.partial(_latent_write_kernel, P=P, W=W, n_blk=n_blk),
        name="latent_pool_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, sub),
            in_specs=[pl.BlockSpec((1, D, W), _nmap),
                      pl.BlockSpec((1, D, P), _cmap)],
            out_specs=pl.BlockSpec((1, D, P), _cmap)),
        out_shape=jax.ShapeDtypeStruct((B, D, L), pool.dtype),
        # operand 2 (after the prefetched lengths and `new`) is the pool
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret_now(),
    )(jnp.asarray(lengths, jnp.int32),
      jnp.swapaxes(new, 1, 2).astype(pool.dtype), jnp.swapaxes(pool, 1, 2))
    return jnp.swapaxes(out, 1, 2)
