"""The per-slot cache type and its pool (`models/kv_cache.py` `SlotCache`,
`KVPool`): every layout x precision against a plain reference that gathers
each row's keys and does a masked softmax.

What a write may not do is part of it: a parked row's write drops, a
position past a dense row's end is never clipped onto a live position, and a
position whose page-table entry is the sentinel stays unwritten — the whole
storage is compared with a reference writer's, not the touched rows alone.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.kv_cache import (KernelRead, KVPool, SlotCache,
                                        cache_positions, cached_attention)

L, P, N_PT, N_PAGES = 32, 8, 4, 24      # row length = N_PT * P positions
HKV, H, D = 2, 4, 8
FORMS = {"dense": ("dense", False), "dense-int8": ("dense", True),
         "paged": ("paged", False), "paged-int8": ("paged", True)}
# rows: short | meets the sentinel page (paged) | ends at the row's end |
# parked | mid-row, parked in the tail wave
SPANS = {"one": (1, [5, 24, 30, L, 12]),
         "verify4": (4, [5, 22, 30, L, 12]),
         "tail6-parked": (6, [3, 21, 26, L, L])}


def _quant(x):
    """numpy copy of the int8 scheme: absmax over a position's
    [heads, hd], one float32 scale per position."""
    scale = np.maximum(np.abs(x).max(axis=(-2, -1)).astype(np.float32)
                       / np.float32(127.0), np.float32(1e-8))
    q = np.clip(np.round(x / scale[..., None, None]), -127, 127)
    return q.astype(np.int8), scale


def _tables():
    """Row r owns pages 4r..4r+3; row 1 was given three pages only (its
    last entry is the sentinel), the parked row 3 none."""
    t = np.arange(5 * N_PT, dtype=np.int32).reshape(5, N_PT)
    t[1, 3] = N_PAGES
    t[3] = N_PAGES
    return t


def _storage(layout, quantized, seed):
    """Random resident K/V (float or int8 + scales) in the layout's
    storage shape, as numpy."""
    rs = np.random.RandomState(seed)
    lead = (N_PAGES, P) if layout == "paged" else (5, L)
    k = rs.randn(*lead, HKV, D).astype(np.float32)
    v = rs.randn(*lead, HKV, D).astype(np.float32)
    if not quantized:
        return k, v, None, None
    (kq, ks), (vq, vs) = _quant(k), _quant(v)
    return kq, vq, ks, vs


def _where(layout, tables, r, p):
    """Storage index of position p of row r, None where it is unwritable."""
    if layout == "dense":
        return (r, p) if p < L else None
    if p >= N_PT * P or tables[r, p // P] >= N_PAGES:
        return None
    return (int(tables[r, p // P]), p % P)


def _reference(layout, store, tables, lengths, q, k, v, window):
    """(expected storage after the write, per-row per-query outputs — None
    for a query whose own position could not be written)."""
    ks, vs, kscale, vscale = [None if a is None else a.copy() for a in store]
    quantized = kscale is not None
    t = q.shape[1]
    for r, n in enumerate(lengths):
        for j in range(t):
            at = _where(layout, tables, r, n + j)
            if at is None:
                continue
            if quantized:
                (ks[at], kscale[at]), (vs[at], vscale[at]) = (
                    _quant(k[r, j]), _quant(v[r, j]))
            else:
                ks[at], vs[at] = k[r, j], v[r, j]

    def row(buf, scale, r, p):
        at = _where(layout, tables, r, p)
        x = buf[at].astype(np.float32)
        return x * scale[at] if quantized else x

    outs = []
    for r, n in enumerate(lengths):
        outs.append([])
        for j in range(t):
            pos = n + j
            if _where(layout, tables, r, pos) is None:
                outs[r].append(None)
                continue
            first = 0 if window is None else max(0, pos - window + 1)
            keys = np.stack([row(ks, kscale, r, p)
                             for p in range(first, pos + 1)])  # [n, HKV, D]
            vals = np.stack([row(vs, vscale, r, p)
                             for p in range(first, pos + 1)])
            o = np.zeros((H, D), np.float32)
            for h in range(H):
                s = keys[:, h // (H // HKV)] @ q[r, j, h] / np.sqrt(D)
                w = np.exp(s - s.max())
                o[h] = (w / w.sum()) @ vals[:, h // (H // HKV)]
            outs[r].append(o)
    return (ks, vs, kscale, vscale), outs


def _cache(layout, store, tables, lengths, read=None):
    k, v, ks, vs = [None if a is None else jnp.asarray(a) for a in store]
    return SlotCache(k, v, jnp.asarray(lengths, jnp.int32),
                     jnp.asarray(tables) if layout == "paged" else None,
                     ks, vs, layout, read)


@pytest.mark.parametrize("window", [None, 8], ids=["global", "window8"])
@pytest.mark.parametrize("span", list(SPANS))
@pytest.mark.parametrize("form", list(FORMS))
def test_slot_cache_write_and_read_match_reference(form, span, window):
    layout, quantized = FORMS[form]
    t, lengths = SPANS[span]
    tables = _tables()
    store = _storage(layout, quantized, seed=len(form) + t)
    rs = np.random.RandomState(t)
    q = rs.randn(5, t, H, D).astype(np.float32)
    k = rs.randn(5, t, HKV, D).astype(np.float32)
    v = rs.randn(5, t, HKV, D).astype(np.float32)
    want_store, want = _reference(layout, store, tables, lengths, q, k, v,
                                  window)

    cache = _cache(layout, store, tables, lengths)
    np.testing.assert_array_equal(
        np.asarray(cache_positions(cache, t)),
        np.asarray(lengths)[:, None] + np.arange(t))
    out, new = cached_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                paddle.to_tensor(v), cache, window=window)
    out = np.asarray(out._value)

    assert (new.layout, new.read, new.quantized) == (layout, None, quantized)
    np.testing.assert_array_equal(np.asarray(new.lengths),
                                  np.asarray(lengths) + t)
    # the whole storage: what was to be written is, and nothing else —
    # not the parked rows, not a position past the end, not a page the
    # sentinel stands for
    for got, exp, name in zip((new.k, new.v, new.k_scale, new.v_scale),
                              want_store, "k v k_scale v_scale".split()):
        if exp is None:
            assert got is None, name
        elif exp.dtype == np.int8:      # a rounding tie may fall either way
            assert np.abs(np.asarray(got).astype(np.int32) - exp).max() <= 1
        else:
            np.testing.assert_allclose(np.asarray(got), exp, rtol=1e-6,
                                       atol=0, err_msg=name)
    checked = 0
    for r in range(5):
        for j in range(t):
            if want[r][j] is not None:
                np.testing.assert_allclose(
                    out[r, j], want[r][j], atol=2e-2 if quantized else 2e-5,
                    rtol=0, err_msg=f"row {r} query {j}")
                checked += 1
    # parked rows have no query to check; the sentinel row loses the
    # queries past its last page, on the paged layout alone
    live = sum(n < L for n in lengths)
    lost = (t - (24 - lengths[1])) if layout == "paged" else 0
    lost += max(0, lengths[2] + t - L)
    assert checked == live * t - lost


@pytest.mark.parametrize("form", list(FORMS))
def test_slot_cache_is_a_pytree_with_static_layout_and_read(form):
    """Through `jax.jit` the arrays are traced and the layout, the precision
    and the read are part of the program's key."""
    layout, quantized = FORMS[form]
    read = KernelRead("paged" if layout == "paged" else "dense", P)
    cache = _cache(layout, _storage(layout, quantized, 0), _tables(),
                   [1, 2, 3, 4, 5], read)
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    assert len(leaves) == 3 + (layout == "paged") + 2 * quantized
    back = jax.jit(lambda c: c)(cache)
    assert (back.layout, back.read, back.quantized) == (layout, read,
                                                        quantized)
    other = _cache(layout, _storage(layout, quantized, 0), _tables(),
                   [1, 2, 3, 4, 5])
    assert jax.tree_util.tree_structure(other) != treedef


def _kv_struct(n_layers=2):
    s = jax.ShapeDtypeStruct((1, 1, HKV, D), jnp.float32)
    return [(s, s)] * n_layers


def _pool(form):
    layout, quantized = FORMS[form]
    rows, row_len = (N_PAGES, P) if layout == "paged" else (5, L)
    return KVPool.zeros(_kv_struct(), layout=layout, rows=rows,
                        row_len=row_len, quantized=quantized)


@pytest.mark.parametrize("form", list(FORMS))
def test_pool_allocates_what_it_names(form):
    layout, quantized = FORMS[form]
    pool = _pool(form)
    lead = (N_PAGES, P) if layout == "paged" else (5, L)
    assert (pool.layout, pool.quantized, pool.rows) == (layout, quantized,
                                                        lead[0])
    assert pool.dtype == jnp.float32
    assert all(p.shape == lead + (HKV, D) and
               p.dtype == (jnp.int8 if quantized else jnp.float32)
               for p in pool.k + pool.v)
    cells = lead[0] * lead[1]
    per_layer = 2 * cells * HKV * D * (1 if quantized else 4)
    assert pool.nbytes == 2 * (per_layer + quantized * 2 * cells * 4)
    assert len(pool.groups()) == (4 if quantized else 2)
    caches = pool.caches(jnp.zeros(5, jnp.int32),
                         jnp.asarray(_tables()) if layout == "paged"
                         else None, read=None)
    assert [c.layout for c in caches] == [layout] * 2
    assert pool.updated(caches).k[0] is pool.k[0]


@pytest.mark.parametrize("form", list(FORMS))
def test_pool_prompts_are_read_back_and_padding_lanes_write_nothing(form):
    """`with_prompts` then the per-slot read: a decode step over freshly
    written prompts attends to exactly those prompts' K/V; a lane that
    names the scratch row, or whose table is all sentinel (the engine
    sends none since ISSUE 30: its prefill has one row), changes no live
    row — by the address alone, with no test of the lane."""
    layout, quantized = FORMS[form]
    pool = _pool(form)
    tables = _tables()
    rs = np.random.RandomState(3)
    bucket, plens = 16, np.array([11, 16, 5], np.int32)
    # lanes: row 0, row 2, a padding lane
    if layout == "paged":
        addr = np.stack([tables[0], tables[2], np.full(N_PT, N_PAGES)])
    else:
        addr = np.array([0, 2, 4], np.int32)
    fresh = pool.prompt_caches(3, bucket)
    width = bucket if layout == "paged" else L
    assert fresh[0][2] == 0 and tuple(fresh[0][0].shape) == (3, width, HKV,
                                                            D)
    pk = rs.randn(2, 3, width, HKV, D).astype(np.float32)
    pv = rs.randn(2, 3, width, HKV, D).astype(np.float32)
    filled = [(paddle.to_tensor(pk[i]), paddle.to_tensor(pv[i]), 0)
              for i in range(2)]
    new = pool.with_prompts(filled, jnp.asarray(addr), jnp.asarray(plens))
    if layout == "paged":       # untouched: every page of rows 1, 3, 4
        keep = np.r_[4:8, 12:N_PAGES]
    else:                       # untouched: rows 1 and 3
        keep = np.array([1, 3])
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(pool)):
        np.testing.assert_array_equal(np.asarray(a)[keep],
                                      np.asarray(b)[keep])
    # one decode step over rows 0 and 2 at their prompt lengths
    lengths = np.array([11, L, 16, L, L], np.int32)
    q = rs.randn(5, 1, H, D).astype(np.float32)
    k1 = rs.randn(5, 1, HKV, D).astype(np.float32)
    v1 = rs.randn(5, 1, HKV, D).astype(np.float32)
    t2 = tables.copy()
    t2[2] = [8, 9, 10, N_PAGES]     # 16 positions need pages 8 and 9, +1
    cache = new.caches(jnp.asarray(lengths),
                       jnp.asarray(t2) if layout == "paged" else None)[1]
    out, _ = cached_attention(paddle.to_tensor(q), paddle.to_tensor(k1),
                              paddle.to_tensor(v1), cache)
    for lane, r in ((0, 0), (1, 2)):
        n = int(lengths[r])
        keys = np.concatenate([pk[1, lane, :n], k1[r]])
        vals = np.concatenate([pv[1, lane, :n], v1[r]])
        if quantized:
            keys, vals = [(lambda qs: qs[0] * qs[1][..., None, None])(
                _quant(x)) for x in (keys, vals)]
        for h in range(H):
            s = keys[:, h // 2] @ q[r, 0, h] / np.sqrt(D)
            w = np.exp(s - s.max())
            np.testing.assert_allclose(
                np.asarray(out._value)[r, 0, h],
                (w / w.sum()) @ vals[:, h // 2],
                atol=2e-2 if quantized else 2e-5, rtol=0)


@pytest.mark.parametrize("form", list(FORMS))
def test_pool_copy_is_bitwise_and_sentinel_lanes_are_no_ops(form):
    layout, quantized = FORMS[form]
    store = _storage(layout, quantized, 9)
    one = [jnp.asarray(a) for a in store if a is not None]
    pool = _pool(form).with_groups([[a, a + 1] for a in one])
    sentinel = pool.rows if layout == "paged" else pool.rows - 1
    src = jnp.asarray([1, sentinel, sentinel], jnp.int32)
    dst = jnp.asarray([3, sentinel, sentinel], jnp.int32)
    new = jax.jit(KVPool.copied)(pool, src, dst)
    assert (new.layout, new.quantized) == (layout, quantized)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(pool)):
        want = np.asarray(b).copy()
        want[3] = want[1]
        np.testing.assert_array_equal(np.asarray(a), want)


# -- rings: a window layer's rows of the dense pool (ISSUE 35) ------------------

from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.models.kv_cache import (_ring_mask, _ring_positions,  # noqa: E402
                                        _span_mask, ring_len)

WIN, RBLK = 6, 4            # a window of 6 in blocks of 4: rings of 8 / 12


@pytest.mark.parametrize("window, span, block, row_len, want", [
    (None, 1, 4, 32, 32), (6, 1, 4, 32, 8), (6, 4, 4, 32, 12),
    (8, 1, 4, 32, 8), (8, 2, 4, 32, 12), (4096, 1, 512, 32768, 4096),
    (4096, 4, 512, 16384, 4608), (30, 1, 4, 32, 32), (40, 1, 4, 32, 32),
])
def test_ring_len_is_window_and_span_rounded_up_to_the_block(
        window, span, block, row_len, want):
    assert ring_len(window, span, block, row_len) == want


def test_ring_positions_name_the_newest_position_of_each_slot():
    last = jnp.asarray([[2], [7], [8], [21]])
    pos = np.asarray(_ring_positions(last, 8))
    assert pos[0].tolist() == [0, 1, 2, -5, -4, -3, -2, -1]   # unwritten < 0
    assert pos[1].tolist() == list(range(8))
    assert pos[2].tolist() == [8, 1, 2, 3, 4, 5, 6, 7]
    assert sorted(pos[3].tolist()) == list(range(14, 22))
    assert all(p % 8 == s for s, p in enumerate(pos[3]))


@pytest.mark.parametrize("t", [1, 3])
def test_ring_mask_is_the_span_mask_of_the_positions_held(t):
    ring = ring_len(WIN, t, RBLK, 32)
    lengths = jnp.asarray([0, 3, 9, 20, 29])
    cols = lengths[:, None] + jnp.arange(t)[None, :]
    full = np.asarray(_span_mask(cols, 32, WIN))[:, 0]        # [B, t, 32]
    got = np.asarray(_ring_mask(cols, ring, WIN))[:, 0]       # [B, t, ring]
    pos = np.asarray(_ring_positions(cols[:, -1:], ring))
    for b in range(5):
        for j in range(t):
            admitted = {p for p in range(32) if full[b, j, p]}
            assert {int(pos[b, s]) for s in range(ring)
                    if got[b, j, s]} == admitted


def _ring_pool(quantized=False, span=1):
    kv = [(jax.ShapeDtypeStruct((1, 1, HKV, D), jnp.float32),) * 2] * 3
    return KVPool.zeros(kv, layout="dense", rows=5, row_len=L,
                        quantized=quantized, windows=[None, WIN, WIN],
                        ring_span=span, ring_block=RBLK)


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_pool_window_layers_are_rings(quantized):
    pool = _ring_pool(quantized)
    assert pool.ring_lens == [None, 8, 8] and pool.row_len == L
    assert [k.shape[1] for k in pool.k] == [L, 8, 8]
    assert [v.shape[1] for v in pool.v] == [L, 8, 8]
    if quantized:
        assert [s.shape for s in pool.k_scale] == [(5, L), (5, 8), (5, 8)]
    item = 1 if quantized else 4
    per = 5 * 2 * HKV * D * item + (5 * 2 * 4 if quantized else 0)
    assert pool.layer_nbytes == [L * per, 8 * per, 8 * per]
    assert pool.nbytes == sum(pool.layer_nbytes)
    caches = pool.caches(jnp.zeros((5,), jnp.int32))
    assert [c.limit for c in caches] == [None, L, L]
    assert [c.span for c in caches] == [L, L, L]
    # a prompt's caches: whole rows on the full layer, the bucket on a ring
    assert [c[0].shape[1] for c in pool.prompt_caches(2, 16)] == [L, 16, 16]
    # no windows, no block, or the paged layout: no ring
    kv = [(jax.ShapeDtypeStruct((1, 1, HKV, D), jnp.float32),) * 2] * 2
    for kw in (dict(windows=None, ring_block=RBLK),
               dict(windows=[WIN, WIN], ring_block=None)):
        plain = KVPool.zeros(kv, layout="dense", rows=5, row_len=L,
                             quantized=False, **kw)
        assert plain.ring_lens == [None, None]
    paged = KVPool.zeros(kv, layout="paged", rows=N_PAGES, row_len=P,
                         quantized=False, windows=[WIN, WIN],
                         ring_block=RBLK)
    assert paged.ring_lens == [None, None]
    # a pytree whose ring plan is static
    leaves, treedef = jax.tree_util.tree_flatten(pool)
    assert jax.tree_util.tree_unflatten(treedef, leaves).row_len == L


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_ring_prompts_leave_their_last_positions_each_at_its_slot(quantized):
    pool = _ring_pool(quantized)
    rs = np.random.RandomState(3)
    bucket, plens = 16, np.asarray([5, 13], np.int32)
    caches = []
    for (k, v, _) in pool.prompt_caches(2, bucket):
        caches.append((
            paddle.to_tensor(rs.randn(*k.shape).astype(np.float32)),
            paddle.to_tensor(rs.randn(*v.shape).astype(np.float32)), 0))
    new = pool.with_prompts(caches, jnp.asarray([3, 1]), jnp.asarray(plens))
    for layer in (1, 2):
        src = np.asarray(caches[layer][0]._value)
        for lane, (row, n) in enumerate(zip((3, 1), plens)):
            for p in range(max(0, n - 8), n):       # the last 8 positions
                want = src[lane, p]
                got = np.asarray(new.k[layer][row, p % 8])
                if quantized:
                    q, sc = _quant(want)
                    np.testing.assert_array_equal(got, q)
                    assert new.k_scale[layer][row, p % 8] == sc
                else:
                    np.testing.assert_array_equal(got, want)
        # rows no lane names keep their zeros
        assert not np.asarray(new.k[layer][jnp.asarray([0, 2, 4])]).any()
    # the full layer took whole rows
    np.testing.assert_array_equal(np.asarray(new.k[0][3], np.float32)[:5]
                                  if not quantized else 0, np.asarray(
        caches[0][0]._value)[0, :5] if not quantized else 0)
    # a row copy stays a row copy, ring or not
    copied = new.copied(jnp.asarray([3]), jnp.asarray([0]))
    for layer in range(3):
        np.testing.assert_array_equal(np.asarray(copied.k[layer][0]),
                                      np.asarray(new.k[layer][3]))


@pytest.mark.parametrize("t", [1, 3])
@pytest.mark.parametrize("read", ["xla", "kernel"])
def test_ring_cache_reads_what_the_full_row_reads(read, t):
    """The same history in a full row and in a ring (every position at
    `p mod R`), a span of t new positions written to both: the window's
    attention is the same, a parked row writes nothing, a write past the
    row's addressable end drops."""
    ring = ring_len(WIN, t, RBLK, L)
    rs = np.random.RandomState(t)
    lengths = np.asarray([0, 5, 11, 26, L - t, L], np.int32)   # last: parked
    B = len(lengths)
    full_k = rs.randn(B, L, HKV, D).astype(np.float32)
    full_v = rs.randn(B, L, HKV, D).astype(np.float32)
    ring_k = np.zeros((B, ring, HKV, D), np.float32)
    ring_v = np.zeros((B, ring, HKV, D), np.float32)
    for b, n in enumerate(lengths[:-1]):
        for p in range(max(0, n - ring), n):
            ring_k[b, p % ring], ring_v[b, p % ring] = full_k[b, p], full_v[b, p]
    q = paddle.to_tensor(rs.randn(B, t, H, D).astype(np.float32))
    k = paddle.to_tensor(rs.randn(B, t, HKV, D).astype(np.float32))
    v = paddle.to_tensor(rs.randn(B, t, HKV, D).astype(np.float32))
    if read == "kernel":
        pa.use_interpret_mode(True)
    kr = KernelRead("dense", RBLK) if read == "kernel" else None
    want, new_full = cached_attention(
        q, k, v, SlotCache(jnp.asarray(full_k), jnp.asarray(full_v),
                           jnp.asarray(lengths), read=kr), window=WIN)
    got, new_ring = cached_attention(
        q, k, v, SlotCache(jnp.asarray(ring_k), jnp.asarray(ring_v),
                           jnp.asarray(lengths), read=kr, limit=L),
        window=WIN)
    np.testing.assert_allclose(np.asarray(got._value)[:-1],
                               np.asarray(want._value)[:-1], atol=1e-5,
                               rtol=0)
    assert new_ring.limit == L and new_ring.k.shape[1] == ring
    assert new_ring.lengths.tolist() == (lengths + t).tolist()
    # the parked row's ring is untouched; every written position sits at
    # its slot
    np.testing.assert_array_equal(np.asarray(new_ring.k[-1]), ring_k[-1])
    for b, n in enumerate(lengths[:-1]):
        for j in range(t):
            np.testing.assert_array_equal(
                np.asarray(new_ring.k[b, (n + j) % ring]),
                np.asarray(k._value)[b, j])


def test_ring_work_list_holds_each_live_ring_block_once():
    ring, blk, max_len = 12, 4, 32
    lengths = np.asarray([0, 3, 7, 12, 13, 22, 31, 32])
    nb = pa.live_blocks(lengths, 1, max_len, blk, WIN, ring)
    first = pa.first_block(lengths, blk, WIN, ring)
    # positions length - 5 .. length, block by block
    want = [sorted({(p // blk) % 3 for p in range(max(0, n - 5), n + 1)})
            if n < max_len else [] for n in lengths]
    assert nb.tolist() == [len(w) for w in want]
    held = pa.dense_blocks_held(lengths, 1, max_len, blk, WIN, ring)
    by_row = {}             # fetches: a parked row's step holds the last block
    for i, (row, b) in enumerate(held):
        if i == 0 or held[i - 1] != (row, b):
            by_row.setdefault(row, []).append(b)
    for r, w in enumerate(want[:-1]):
        assert sorted(by_row[r]) == w and len(set(by_row[r])) == len(w)
        assert by_row[r][0] == first[r]
    # a span as wide as the ring allows never asks for more than the ring
    wide = pa.live_blocks(lengths, 7, max_len, blk, WIN, ring)
    assert wide.max() <= ring // blk
    # without a ring nothing changed
    assert pa.live_blocks(lengths, 1, max_len, blk, WIN).tolist() == \
        [min((n + blk) // blk, 8) - max(n - 5, 0) // blk if n < 32 else 0
         for n in lengths]
