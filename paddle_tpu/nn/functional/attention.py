"""Attention functionals.

Reference: the fused CUDA attention family (paddle/fluid/operators/fused/
fused_attention_op.cu, fmha_ref.h) materialises S×S scores; here the default is
a jnp reference implementation, and `scaled_dot_product_attention` routes to
the Pallas flash-attention kernel (paddle_tpu.kernels.flash_attention) on TPU
when shapes allow — the one place this framework hand-writes kernels.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.op import defop

_USE_FLASH = True


class FlashUnsupported(ValueError):
    """Raised by the flash routing when a documented shape/mesh constraint
    rules the Pallas kernel out; the caller then takes the dense reference.
    Any other exception from the kernel path is a real failure and
    propagates — at model scale the dense S x S path is a several-fold
    slowdown or an OOM somewhere else, not a fallback."""


def enable_flash_attention(flag: bool):
    global _USE_FLASH
    _USE_FLASH = bool(flag)


def _sdpa_ref(q, k, v, mask, dropout_p, causal, scale, training,
              window=None):
    # q: [B, T, H, D] (paddle convention); k, v may hold fewer heads
    # (grouped-query attention): query head i reads KV head i // (H / Hkv)
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    qh = jnp.swapaxes(q, 1, 2)  # [B, H, T, D]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        Tq, Tk = scores.shape[-2], scores.shape[-1]
        cm = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        if window is not None:
            # sliding window: query t reads keys t - window + 1 .. t
            cm &= ~jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq - window)
        scores = jnp.where(cm, scores, jnp.array(-1e30, scores.dtype))
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, jnp.array(-1e30, scores.dtype))
        else:
            scores = scores + mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p and training:
        from ...core import random as rnd
        keep = jax.random.bernoulli(rnd.next_key(), 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)  # [B, T, H, D]


def _flash_ok(q) -> bool:
    """Route to the Pallas kernel only on TPU, for non-trivial query lengths,
    and only when the sequence axis isn't sharded (flash needs the full K per
    shard; ring attention covers the 'sep'-sharded case)."""
    if not _USE_FLASH or q.shape[1] < 128:
        return False
    from ...distributed import mesh as mesh_mod
    if any(mesh_mod.axis_bound(a) for a in ("mp", "dcn", "dp", "sharding", "sep")):
        return False  # explicit shard_map mode: local shards, ref math
    mesh = mesh_mod.get_global_mesh()
    if mesh is not None and mesh.shape.get("sep", 1) > 1:
        return False
    return jax.default_backend() != "cpu"


def _flash_spmd(q, k, v, causal, scale, window=None):
    """Pallas call partitioned over the live mesh: batch over dp/sharding,
    heads over mp (a pallas_call is an opaque custom-call to GSPMD, so the
    partitioning must be made explicit with shard_map)."""
    from ...distributed import mesh as mesh_mod
    from jax.sharding import PartitionSpec as P
    from ...kernels.flash_attention import flash_attention_bthd

    mesh = mesh_mod.get_global_mesh()
    live = [a for a in ("dcn", "dp", "sharding", "mp")
            if mesh is not None and a in mesh.axis_names and
            mesh.shape.get(a, 1) > 1]
    if not live:
        return flash_attention_bthd(q, k, v, causal=causal, scale=scale,
                                    window=window)
    batch = tuple(a for a in ("dcn", "dp", "sharding") if a in live)
    heads = "mp" if "mp" in live else None
    n_batch = 1
    for a in batch:
        n_batch *= mesh.shape[a]
    if q.shape[0] % n_batch or (heads and (q.shape[2] % mesh.shape["mp"] or
                                           k.shape[2] % mesh.shape["mp"])):
        raise FlashUnsupported("shapes not divisible by mesh axes")
    spec = P(batch if batch else None, None, heads, None)

    def local(qv, kv, vv):
        return flash_attention_bthd(qv, kv, vv, causal=causal, scale=scale,
                                    window=window)

    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


@defop
def fused_qkv_attention(qkv, dropout_p=0.0, is_causal=True, training=True,
                        name=None):
    """Self-attention on the FUSED head-major qkv tensor
    [batch, seq, heads, 3, head_dim] (the layout GPT/BERT qkv projections
    produce), returning [batch, seq, heads*head_dim].

    Purpose is performance: one whole-qkv transpose (which XLA fuses into
    the projection matmul) replaces the three per-operand layout copies the
    flash custom call otherwise forces, and the flat output feeds the row-
    parallel out-projection without another boundary copy (docs/PERF.md
    layout-copy tax; reference analog: fused_attention_op.cu keeps qkv fused
    for the same reason)."""
    b, t, nh, three, hd = qkv.shape
    scale = 1.0 / math.sqrt(hd)
    from ...distributed import mesh as mesh_mod
    if three == 3 and dropout_p == 0.0 and not mesh_mod.axis_bound("sep") \
            and _flash_ok(qkv) and qkv.shape[1] >= 128:
        try:
            return _fused_flash_spmd(qkv, is_causal, scale)
        except FlashUnsupported:
            pass
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    if mesh_mod.axis_bound("sep"):
        if dropout_p and training:
            raise ValueError(
                "context parallelism (sep axis) supports only dropout-free "
                "attention; set attention_dropout_prob=0 or disable sep")
        from ...kernels.ring_attention import ring_attention
        out = ring_attention(q, k, v, axis_name="sep", causal=is_causal,
                             scale=scale)
    else:
        out = _sdpa_ref(q, k, v, None, dropout_p, is_causal, scale, training)
    return out.reshape(b, t, nh * hd)


def _fused_flash_spmd(qkv, causal, scale):
    """Flash path for the fused tensor, shard_map-partitioned when a mesh is
    live (batch over dp/sharding, heads over mp; output stays head-sharded
    on the flat hidden dim, which is exactly RowParallelLinear's
    input_is_parallel convention)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ...distributed import mesh as mesh_mod
    from ...kernels.flash_attention import flash_attention_qkv_fused

    b, t, nh, _, hd = qkv.shape

    def local(qkv5):
        bl, tl, nhl, _, hdl = qkv5.shape
        # ONE fused operand [BH, 3, T, D]: a single layout copy at the
        # custom-call boundary covers q, k and v
        qkvh = jnp.transpose(qkv5, (0, 2, 3, 1, 4)).reshape(
            bl * nhl, 3, tl, hdl)
        o3 = flash_attention_qkv_fused(qkvh, causal=causal, scale=scale)
        return jnp.transpose(o3.reshape(bl, nhl, tl, hdl),
                             (0, 2, 1, 3)).reshape(bl, tl, nhl * hdl)

    mesh = mesh_mod.get_global_mesh()
    live = [a for a in ("dcn", "dp", "sharding", "mp")
            if mesh is not None and a in mesh.axis_names and
            mesh.shape.get(a, 1) > 1]
    if not live:
        return local(qkv)
    batch = tuple(a for a in ("dcn", "dp", "sharding") if a in live)
    heads = "mp" if "mp" in live else None
    n_batch = 1
    for a in batch:
        n_batch *= mesh.shape[a]
    if qkv.shape[0] % n_batch or (heads and nh % mesh.shape["mp"]):
        raise FlashUnsupported("shapes not divisible by mesh axes")
    in_spec = P(batch if batch else None, None, heads, None, None)
    out_spec = P(batch if batch else None, None, heads)
    return jax.shard_map(local, mesh=mesh, in_specs=(in_spec,),
                         out_specs=out_spec, check_vma=False)(qkv)


@defop
def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 window=None, name=None):
    """Inputs [batch, seq, heads, head_dim] like the reference fused op.
    `key`/`value` may hold fewer heads than `query` (grouped-query
    attention: a divisor; query head i reads KV head i // group).  `window`
    (with `is_causal`) keeps, for query t, the keys t - window + 1 .. t."""
    scale = 1.0 / math.sqrt(query.shape[-1])
    if window is not None and not is_causal:
        raise ValueError("window= needs is_causal=True; a cached read "
                         "states its window in attn_mask")
    if query.shape[2] % key.shape[2]:
        raise ValueError(f"{query.shape[2]} query heads are no multiple of "
                         f"{key.shape[2]} key/value heads")
    from ...distributed import mesh as mesh_mod
    if mesh_mod.axis_bound("sep"):
        if window is not None or query.shape[2] != key.shape[2]:
            raise ValueError("context parallelism (sep axis) supports "
                             "neither a window nor grouped-query heads")
        # sequence axis is sharded (context parallelism): shard-local attention
        # would be globally wrong, so the ring path is mandatory here
        if attn_mask is not None or (dropout_p and training) or \
                query.shape[1] != key.shape[1]:
            raise ValueError(
                "context parallelism (sep axis) supports only mask-free, "
                "dropout-free self-attention with equal q/k lengths; set "
                "attention_dropout_prob=0 (or disable sep) — got "
                f"mask={attn_mask is not None}, dropout_p={dropout_p}, "
                f"tq={query.shape[1]}, tk={key.shape[1]}")
        from ...kernels.ring_attention import ring_attention
        return ring_attention(query, key, value, axis_name="sep",
                              causal=is_causal, scale=scale)
    if attn_mask is None and not (dropout_p and training) and \
            _flash_ok(query):
        try:
            return _flash_spmd(query, key, value, is_causal, scale, window)
        except FlashUnsupported:
            pass  # mesh-divisibility constraint: unfused reference path below
    return _sdpa_ref(query, key, value, attn_mask, dropout_p, is_causal, scale,
                     training, window)


@defop
def rotary_embedding(x, positions, theta=10000.0, name=None):
    """Rotary position encoding in the half-split layout (dimension i pairs
    with i + head_dim / 2): x [batch, seq, heads, head_dim] at integer
    `positions` [batch or 1, seq].  Angles and the rotation are float32."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


@defop
def fused_ln_linear(x, ln_weight, ln_bias, weight, bias=None, eps=1e-5,
                    name=None):
    """Pre-LN fused into its consuming projection: y = LN(x) @ weight
    (+ bias) as ONE pallas custom call (kernels/ln_matmul.py) — the LN
    boundary disappears into the matmul's operand read (docs/PERF.md:
    standalone LN boundaries lose; reference analog: the pre-LN fusion in
    fused_attention_op.cu / fused_feedforward_op.cu).  Falls back to the
    jnp composition when the kernel doesn't apply (CPU, unaligned dims)."""
    from ...distributed import mesh as _mesh_mod
    from ...kernels.ln_matmul import ln_matmul, ln_matmul_ok

    if ln_matmul_ok(x, weight,
                    mesh_free=_mesh_mod.get_global_mesh() is None):
        return ln_matmul(x, ln_weight, ln_bias, weight, bias, eps)
    # promote, never downcast: f64 inputs (x64 gradcheck mode) keep f64
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    d = xf - mu
    var = jnp.mean(d * d, axis=-1, keepdims=True)
    xln = ((d * jax.lax.rsqrt(var + eps)) * ln_weight + ln_bias) \
        .astype(x.dtype)
    y = jnp.matmul(xln, weight)
    return y if bias is None else y + bias
