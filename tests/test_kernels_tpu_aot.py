"""The Pallas kernels compile for the chip — checked with no chip.

libtpu ships in the sandbox, so ``jax.experimental.topologies`` can
describe an abstract ``v5e:2x2`` host and ``jit(...).lower().compile()``
against one of its devices runs the real XLA:TPU + Mosaic compilers under
``JAX_PLATFORMS=cpu``.  This proves COMPILATION only — numerics, run-time
HBM and dispatch stay with ``python chip_smoke.py`` on the chip — but it
is what keeps "kernels that compile" true between chip runs: PR 19's paged
kernel passed every interpret-mode test and was refused by Mosaic in all
three of the configurations the engine and the bench would hand it.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import paddle_tpu  # noqa: F401  (x64 on, as every entry point has it)
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa

f32, bf16, i8 = jnp.float32, jnp.bfloat16, jnp.int8


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"no TPU compiler available: {e}")
    assert len(topo.devices) == 4
    assert "v5" in topo.devices[0].device_kind.lower()
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *structs, precision="default"):
    # conftest pins "highest" for the numpy comparisons; the chip runs
    # the default, so that is the lowering Mosaic must accept
    with jax.default_matmul_precision(precision):
        return (jax.jit(fn, in_shardings=sharding, out_shardings=sharding)
                .trace(*structs).lower(lowering_platforms=("tpu",))
                .compile())


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# -- flash attention, forward + backward, at the shapes the models use -------

_FLASH = [  # (id, batch, seq, heads, head_dim, causal)
    ("gpt3-1.3B", 8, 1024, 16, 128, True),
    ("gpt3-1.3B-t2048", 8, 2048, 16, 128, True),
    ("gpt2-small", 16, 1024, 12, 64, True),
    ("bert-base", 64, 512, 12, 64, False),
]


@pytest.mark.parametrize("b,t,h,d,causal", [c[1:] for c in _FLASH],
                         ids=[c[0] for c in _FLASH])
def test_flash_fused_fwd_bwd_compiles(v5e, b, t, h, d, causal):
    """`flash_attention_qkv_fused` — the path `fused_qkv_attention` (GPT
    and BERT training) routes to."""
    def loss(qkv):
        out = fa.flash_attention_qkv_fused(qkv, causal=causal)
        return jnp.sum(out.astype(f32))

    c = _compile(jax.value_and_grad(loss), v5e,
                 jax.ShapeDtypeStruct((b * h, 3, t, d), bf16))
    assert _mosaic_calls(c) >= 2          # forward + fused backward


@pytest.mark.parametrize("b,t,h,d,causal", [_FLASH[0][1:], _FLASH[3][1:]],
                         ids=[_FLASH[0][0], _FLASH[3][0]])
def test_flash_bthd_fwd_bwd_compiles(v5e, b, t, h, d, causal):
    """`flash_attention_bthd` — the path `scaled_dot_product_attention`
    (serving prefill at >= 128 tokens) routes to."""
    def loss(q, k, v):
        out = fa.flash_attention_bthd(q, k, v, causal=causal)
        return jnp.sum(out.astype(f32))

    s = jax.ShapeDtypeStruct((b, t, h, d), bf16)
    c = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), v5e, s, s, s)
    assert _mosaic_calls(c) >= 2


def test_flash_bf16_compiles_under_highest_precision(v5e):
    """Found on the chip (PR 21): with `jax_default_matmul_precision=
    "highest"` the bf16 dots reached Mosaic asking for an f32-precision
    MXU mode and were refused ("Bad lhs type")."""
    def loss(q, k, v):
        out = fa.flash_attention_bthd(q, k, v, causal=True)
        return jnp.sum(out.astype(f32))

    s = jax.ShapeDtypeStruct((2, 1024, 16, 128), bf16)
    c = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), v5e, s, s, s,
                 precision="highest")
    assert _mosaic_calls(c) >= 2


# -- paged decode attention: the matrix the engine can hand it ---------------

@pytest.fixture()
def compiled_paged():
    pa.use_interpret_mode(False)     # conftest restores the default
    yield


def _paged(sharding, *, B, W, H, D, P, max_len, pool, qdt,
           precision="default"):
    n_pt = -(-max_len // P)
    NP = B * n_pt
    structs = [jax.ShapeDtypeStruct((B, W, H, D), qdt),
               jax.ShapeDtypeStruct((NP, P, H, D), pool),
               jax.ShapeDtypeStruct((NP, P, H, D), pool),
               jax.ShapeDtypeStruct((B, n_pt), jnp.int32),
               jax.ShapeDtypeStruct((B,), jnp.int32)]
    if pool == i8:
        structs += [jax.ShapeDtypeStruct((NP, P), f32)] * 2

    def read(q, k, v, pt, ln, ks=None, vs=None):
        return pa.paged_decode_attention(q, k, v, pt, ln, k_scale=ks,
                                         v_scale=vs)

    pa.check_supported(page_size=P, max_pages_per_slot=n_pt, heads=H,
                       width=W)
    return _compile(read, sharding, *structs, precision=precision)


@pytest.mark.parametrize("pool,qdt", [(f32, f32), (bf16, bf16), (i8, f32),
                                      (i8, bf16)],
                         ids=["f32", "bf16", "int8-f32q", "int8-bf16q"])
@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("H,D", [(16, 128), (12, 64)],
                         ids=["gpt3-1.3B", "gpt2-small"])
def test_paged_decode_compiles_at_engine_page_size(v5e, compiled_paged,
                                                   H, D, W, pool, qdt):
    """Page 16 is what `Engine(paged_kv=True)` uses by default
    (`page_size=None` -> `prefix_block`), 8 slots x 2048 as chip_smoke
    serves."""
    c = _paged(v5e, B=8, W=W, H=H, D=D, P=16, max_len=2048, pool=pool,
               qdt=qdt)
    assert _mosaic_calls(c) == 1


@pytest.mark.parametrize("pool,qdt", [(f32, f32), (i8, bf16)],
                         ids=["f32", "int8-bf16q"])
@pytest.mark.parametrize("H,D", [(16, 128), (12, 64)],
                         ids=["gpt3-1.3B", "gpt2-small"])
def test_paged_decode_compiles_at_full_lane_page(v5e, compiled_paged, H, D,
                                                 pool, qdt):
    c = _paged(v5e, B=8, W=1, H=H, D=D, P=128, max_len=2048, pool=pool,
               qdt=qdt)
    assert _mosaic_calls(c) == 1


@pytest.mark.parametrize("pool", [f32, i8], ids=["f32", "int8"])
def test_paged_decode_compiles_under_highest_precision(v5e, compiled_paged,
                                                       pool):
    """chip_smoke's kernel lane: f32 weights, full-precision matmuls,
    4 slots x 512 — the regime where pallas == xla token for token."""
    c = _paged(v5e, B=5, W=1, H=16, D=128, P=16, max_len=512, pool=pool,
               qdt=f32, precision="highest")
    assert _mosaic_calls(c) == 1


def test_paged_decode_compiles_at_bench_shape(v5e, compiled_paged):
    """`bench._bench_decode_kernel` on the chip: GPT-2-small, int8 pool,
    speculative_k=3, 8 slots x 640, page 16."""
    c = _paged(v5e, B=9, W=3, H=12, D=64, P=16, max_len=640, pool=i8,
               qdt=f32)
    assert _mosaic_calls(c) == 1


# -- the dense pool's decode read (ISSUE 26) ---------------------------------

_DENSE = [  # (id, rows, max_len, heads, head_dim, W, dtype, precision)
    ("serve-cells-bf16", 25, 2048, 16, 128, 1, bf16, "default"),
    ("serve-cells-verify4", 25, 2048, 16, 128, 4, bf16, "default"),
    ("chip-smoke-f32-highest", 9, 2048, 16, 128, 1, f32, "highest"),
    ("gpt2-small-bf16", 9, 640, 12, 64, 1, bf16, "default"),
]


@pytest.mark.parametrize("B,L,H,D,W,dt,precision", [c[1:] for c in _DENSE],
                         ids=[c[0] for c in _DENSE])
def test_dense_decode_read_compiles(v5e, compiled_paged, B, L, H, D, W, dt,
                                    precision):
    """`dense_decode_attention` at the serve cells' shape — 24 slots +
    scratch x 2048, 16 x 128, bf16, `DENSE_BLOCK` — with a run-time grid
    bound; and what `chip_smoke.py` and a 12 x 64 model hand it."""
    assert pa.dense_read_block(heads=H, head_dim=D, dtype=dt, width=W,
                               max_len=L) == pa.DENSE_BLOCK == 128
    pool = jax.ShapeDtypeStruct((B, L, H, D), dt)
    c = _compile(pa.dense_decode_attention, v5e,
                 jax.ShapeDtypeStruct((B, W, H, D), dt), pool, pool,
                 jax.ShapeDtypeStruct((B,), jnp.int32), precision=precision)
    assert _mosaic_calls(c) == 1


def test_engine_refuses_what_the_kernel_cannot_take():
    """The one Mosaic limit (the scores row must fit VMEM) is a ValueError
    at construction that names it — not interpret mode, not the XLA read."""
    from paddle_tpu.models import build_gpt, gpt_config
    from paddle_tpu.serving import Engine

    with pytest.raises(ValueError, match="VMEM"):
        pa.check_supported(page_size=16, max_pages_per_slot=1024, heads=16)
    pa.check_supported(page_size=128, max_pages_per_slot=128, heads=16)

    cfg = gpt_config("gpt-tiny", max_position_embeddings=8192,
                     num_attention_heads=32, hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0)
    model = build_gpt(cfg)
    model.eval()
    with pytest.raises(ValueError, match="page_size"):
        Engine(model, max_slots=2, max_len=8192, paged_kv=True, page_size=8,
               decode_kernel="pallas", auto_start=False)
    eng = Engine(model, max_slots=2, max_len=8192, paged_kv=True,
                 page_size=128, decode_kernel="pallas", auto_start=False)
    eng.shutdown()


def test_paged_kernel_interprets_only_on_cpu(monkeypatch):
    assert pa._interpret_now() is True           # tier-1 runs on the cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "some-new-chip")
    assert pa._interpret_now() is False          # unknown is not cpu
    assert pa._INTERPRET is None                 # decided by backend, not pinned


# -- grouped-query heads and sliding windows (ISSUE 27) ----------------------

_GQA_FLASH = [  # (id, seq, window): 28 query / 4 KV heads of 128
    ("smallthinker-global-16k", 16384, None),
    ("smallthinker-window-16k", 16384, 4096),
    ("smallthinker-window-1k", 1024, 4096),
    ("smallthinker-global-256", 256, None),
]


@pytest.mark.parametrize("t,window", [c[1:] for c in _GQA_FLASH],
                         ids=[c[0] for c in _GQA_FLASH])
def test_flash_grouped_window_fwd_compiles(v5e, t, window):
    """The prefill call of a decoder with 7 query heads per KV head: a grid
    step holds one KV head's tile and its whole group of query heads."""
    def fwd(q, k, v):
        return fa.flash_attention_bthd(q, k, v, causal=True, window=window)

    c = _compile(fwd, v5e, jax.ShapeDtypeStruct((1, t, 28, 128), bf16),
                 jax.ShapeDtypeStruct((1, t, 4, 128), bf16),
                 jax.ShapeDtypeStruct((1, t, 4, 128), bf16))
    assert _mosaic_calls(c) == 1


def test_flash_grouped_window_bwd_compiles(v5e):
    def loss(q, k, v):
        out = fa.flash_attention_bthd(q, k, v, causal=True, window=512)
        return jnp.sum(out.astype(f32))

    c = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), v5e,
                 jax.ShapeDtypeStruct((1, 2048, 28, 128), bf16),
                 jax.ShapeDtypeStruct((1, 2048, 4, 128), bf16),
                 jax.ShapeDtypeStruct((1, 2048, 4, 128), bf16))
    assert _mosaic_calls(c) >= 3          # forward, dq, dk/dv


@pytest.mark.parametrize("window", [None, 4096], ids=["global", "window"])
@pytest.mark.parametrize("W", [1, 4])
def test_dense_decode_read_grouped_compiles(v5e, compiled_paged, window, W):
    """`dense_decode_attention` at the SmallThinker cell's shape: 16 slots +
    scratch x 16384, 28 query / 4 KV heads of 128; the block is 512
    positions (4 x DENSE_BLOCK: a KV block of 4 heads holds the bytes of
    128 positions of 16)."""
    B, L, H, Hkv, D = 17, 16384, 28, 4, 128
    blk = pa.dense_read_block(heads=H, kv_heads=Hkv, head_dim=D, dtype=bf16,
                              width=W, max_len=L)
    assert blk == 4 * pa.DENSE_BLOCK

    def read(q, k, v, ln):
        return pa.dense_decode_attention(q, k, v, ln, block=blk,
                                         window=window)

    pool = jax.ShapeDtypeStruct((B, L, Hkv, D), bf16)
    c = _compile(read, v5e, jax.ShapeDtypeStruct((B, W, H, D), bf16), pool,
                 pool, jax.ShapeDtypeStruct((B,), jnp.int32))
    assert _mosaic_calls(c) == 1


@pytest.mark.parametrize("tokens", [17, 16384], ids=["decode", "prefill"])
def test_dropless_experts_compile_as_grouped_matmul(v5e, tokens):
    """The expert layer at published widths: XLA:TPU lowers `ragged_dot` to
    its grouped-matmul kernel from 128 rows on (the layer pads to that), so
    a decode step's 102 assignments do not become 64 dense products."""
    from paddle_tpu.incubate.distributed.models.moe.dropless import (
        dropless_moe)
    h, f, e = 2560, 768, 64

    def layer(x, wr, wg, wu, wd):
        return dropless_moe.raw(x, x, wr, wg, wu, wd, top_k=6)

    c = _compile(layer, v5e, jax.ShapeDtypeStruct((tokens, h), bf16),
                 jax.ShapeDtypeStruct((h, e), bf16),
                 jax.ShapeDtypeStruct((e, h, f), bf16),
                 jax.ShapeDtypeStruct((e, h, f), bf16),
                 jax.ShapeDtypeStruct((e, f, h), bf16))
    # the group metadata, then gate, up and down (once per chunk of tokens)
    assert _mosaic_calls(c) == 4
    ideal = 6.0 * (-(-tokens * 6 // 128) * 128) * h * f
    assert c.cost_analysis()["flops"] < 1.5 * ideal


# -- the latent-attention decoder's kernels at published widths (PR 31) -------

@pytest.mark.parametrize("t", [2048, 8192])
def test_flash_two_head_sizes_fwd_compiles(v5e, t):
    """The expanded-form prefill call: 128 heads, q and k of 192 (128 + the
    shared 64), v of 128."""
    def fwd(q, k, v):
        return fa.flash_attention_bthd(q, k, v, causal=True)

    c = _compile(fwd, v5e, jax.ShapeDtypeStruct((1, t, 128, 192), bf16),
                 jax.ShapeDtypeStruct((1, t, 128, 192), bf16),
                 jax.ShapeDtypeStruct((1, t, 128, 128), bf16))
    assert _mosaic_calls(c) == 1


@pytest.mark.parametrize("W", [1, 3])
def test_latent_decode_read_compiles(v5e, compiled_paged, W):
    """`latent_decode_attention` at the cell's shape: 32 slots + scratch x
    16384 rows of 576 (512 + 64), 128 absorbed query heads."""
    B, L, H, D, V = 33, 16384, 128, 576, 512
    blk = pa.latent_read_block(width=D, dtype=bf16, max_len=L)
    assert blk == pa.LATENT_BLOCK

    def read(q, pool, ln):
        return pa.latent_decode_attention(q, pool, ln, block=blk,
                                          scale=192 ** -0.5, values=V)

    c = _compile(read, v5e, jax.ShapeDtypeStruct((B, W, H, D), bf16),
                 jax.ShapeDtypeStruct((B, L, D), bf16),
                 jax.ShapeDtypeStruct((B,), jnp.int32))
    assert _mosaic_calls(c) == 1


@pytest.mark.parametrize("tokens", [33, 8192], ids=["decode", "prefill"])
def test_dropless_share_compiles_with_a_bounded_buffer(v5e, tokens):
    """A share of 16 of 256 SwiGLU experts at published widths: the loop's
    body holds `share_rows` gathered rows, never tokens x 8."""
    from paddle_tpu.incubate.distributed.models.moe.dropless import (
        dropless_moe, share_rows)
    h, f, held, e = 7680, 2048, 16, 256

    def layer(x, wr, wg, wu, wd, sg, su, sd):
        return dropless_moe.raw(x, x, wr, wg, wu, wd, top_k=8, first=0,
                                scoring="sigmoid", routed_scale=2.5,
                                activation="silu", shared=(sg, su, sd))

    c = _compile(layer, v5e, jax.ShapeDtypeStruct((tokens, h), bf16),
                 jax.ShapeDtypeStruct((h, e), bf16),
                 jax.ShapeDtypeStruct((held, h, f), bf16),
                 jax.ShapeDtypeStruct((held, h, f), bf16),
                 jax.ShapeDtypeStruct((held, f, h), bf16),
                 jax.ShapeDtypeStruct((h, f), bf16),
                 jax.ShapeDtypeStruct((h, f), bf16),
                 jax.ShapeDtypeStruct((f, h), bf16))
    r = share_rows(tokens * 8, held, e)
    assert r == (128 if tokens == 33 else 5120)
    # the gathered rows, their products and the float32 sum: under the
    # 1.0 GB that tokens x 8 rows of 7,680 alone would take at 8,192 (0.82
    # GB; 0.51 up to 4,096 rows a turn: past half the tokens the add-back
    # keeps a second float32 sum.  The whole 8,192 prefill's temporaries
    # are 2.55 GB either way: its peak is in the attention)
    assert c.memory_analysis().temp_size_in_bytes < (
        0.9e9 if tokens == 8192 else 0.05e9)


@pytest.mark.parametrize("W", [1, 3])
def test_latent_pool_write_compiles_in_place(v5e, compiled_paged, W):
    """The decode step's write into the latent pool: in place (aliased),
    no copy of the pool to another layout around it."""
    B, L, D = 33, 16384, 576

    def write(pool, new, ln):
        return pa.latent_pool_write(pool, new, ln)

    c = jax.jit(write, in_shardings=v5e, out_shardings=v5e,
                donate_argnums=(0,)).trace(
        jax.ShapeDtypeStruct((B, L, D), bf16),
        jax.ShapeDtypeStruct((B, W, D), bf16),
        jax.ShapeDtypeStruct((B,), jnp.int32)).lower(
        lowering_platforms=("tpu",)).compile()
    assert _mosaic_calls(c) == 1
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20
