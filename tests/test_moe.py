"""MoE / expert-parallel tests (reference: unittests test_moe_api style —
gate semantics, dispatch/combine correctness, EP all_to_all over the expert
mesh axis) plus the incubate fused transformer layers."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.distributed as dist
from paddle_tpu.incubate.distributed.models import moe
from paddle_tpu.incubate.distributed.models.moe import (
    ClipGradForMOEByGlobalNorm, MoELayer, NaiveGate, SwitchGate, GShardGate,
    _limit_by_capacity, _number_count, _prune_gate_by_capacity)
from jax import shard_map


@pytest.fixture(autouse=True)
def _reset():
    yield
    dist.collective.destroy_process_group()
    dist.set_global_mesh(None)


def _expert(d_model, d_hidden):
    return nn.Sequential(nn.Linear(d_model, d_hidden), nn.ReLU(),
                         nn.Linear(d_hidden, d_model))


def test_number_count_limit_prune():
    ids = paddle.to_tensor(np.array([0, 1, 1, 3, 3, 3], "int64"))
    counts = _number_count(ids, 4).numpy()
    np.testing.assert_array_equal(counts, [1, 2, 0, 3])

    limited = _limit_by_capacity(paddle.to_tensor(np.array([5, 1, 4, 0], "int64")),
                                 paddle.to_tensor(np.array([2, 2, 2, 2], "int64")),
                                 n_worker=1).numpy()
    np.testing.assert_array_equal(limited, [2, 1, 2, 0])

    pruned = _prune_gate_by_capacity(
        paddle.to_tensor(np.array([0, 0, 0, 1], "int64")),
        paddle.to_tensor(np.array([2, 9], "int64")), 2, 1).numpy()
    np.testing.assert_array_equal(pruned, [0, 0, -1, 1])


def test_naive_gate_topk():
    paddle.seed(0)
    g = NaiveGate(16, 4, 1, topk=2)
    x = paddle.to_tensor(np.random.RandomState(0).randn(10, 16).astype("float32"))
    val, idx = g(x)
    assert tuple(val.shape) == (10, 2) and tuple(idx.shape) == (10, 2)
    assert int(idx.numpy().max()) < 4 and int(idx.numpy().min()) >= 0
    # top-1 score >= top-2 score
    v = val.numpy()
    assert (v[:, 0] >= v[:, 1]).all()


def test_switch_and_gshard_gates_set_loss():
    paddle.seed(0)
    x = paddle.to_tensor(np.random.RandomState(1).randn(32, 8).astype("float32"))
    sg = SwitchGate(8, 4, 1)
    sg.eval()
    _, idx = sg(x)
    assert tuple(idx.shape) == (32, 1)
    assert float(sg.get_loss().numpy()) > 0

    gg = GShardGate(8, 4, 1)
    val, idx = gg(x)
    assert tuple(idx.shape) == (32, 2)
    assert float(gg.get_loss().numpy()) > 0
    # random routing may drop the second expert → -1 allowed
    assert int(idx.numpy()[:, 0].min()) >= 0


def test_moe_layer_forward_eager():
    paddle.seed(3)
    d = 16
    layer = MoELayer(d, [_expert(d, 32) for _ in range(4)],
                     gate={"type": "naive", "top_k": 2},
                     capacity_factor=4.0)
    x = paddle.to_tensor(np.random.RandomState(2).randn(2, 12, d).astype("float32"))
    out = layer(x)
    assert tuple(out.shape) == (2, 12, d)
    assert np.isfinite(out.numpy()).all()


def test_moe_layer_capacity_identity_experts():
    """With identity experts and ample capacity, MoE output == input (combine
    weights sum to 1 for kept tokens)."""
    paddle.seed(5)
    d = 8

    class Identity(nn.Layer):
        def forward(self, x):
            return x

    layer = MoELayer(d, [Identity() for _ in range(2)],
                     gate={"type": "naive", "top_k": 2},
                     capacity_factor=8.0)
    x = paddle.to_tensor(np.random.RandomState(4).randn(20, d).astype("float32"))
    out = layer(x)
    np.testing.assert_allclose(out.numpy(), x.numpy(), rtol=1e-5, atol=1e-5)


def test_switch_top1_scales_by_router_prob():
    """Switch semantics: output = p_top1 * expert(x) (regression: a k=1
    softmax-renormalize would make the scale identically 1)."""
    paddle.seed(9)
    d = 8

    class Identity(nn.Layer):
        def forward(self, x):
            return x

    layer = MoELayer(d, [Identity() for _ in range(4)],
                     gate={"type": "switch"}, capacity_factor=8.0)
    layer.eval()  # no jitter
    x_np = np.random.RandomState(8).randn(12, d).astype("float32")
    out = layer(paddle.to_tensor(x_np)).numpy()
    # recompute expected p_top1 from the gate
    val, _ = layer.gate(paddle.to_tensor(x_np))
    expected = val.numpy()[:, :1] * x_np
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-5)
    assert (np.abs(out - x_np) > 1e-3).any()  # scale really isn't 1


def test_moe_layer_grad_flows():
    paddle.seed(6)
    d = 8
    layer = MoELayer(d, [_expert(d, 16) for _ in range(2)],
                     gate={"type": "naive", "top_k": 2}, capacity_factor=8.0)
    x = paddle.to_tensor(np.random.RandomState(5).randn(6, d).astype("float32"))
    out = layer(x)
    loss = (out * out).sum()
    loss.backward()
    got_grad = [p for p in layer.parameters() if p.grad is not None]
    assert len(got_grad) >= 4  # gate + at least one expert touched


def test_moe_expert_parallel_identity_roundtrip():
    """EP over an 8-way expert axis: with identity experts the
    dispatch → global_scatter (all_to_all) → expert → global_gather → combine
    round trip must reproduce the input exactly (global_scatter_op.cc /
    global_gather_op.cc parity)."""
    d = 8
    mesh = dist.build_mesh([8], ["ep"])
    dist.set_global_mesh(mesh)
    ep_group = dist.new_group(list(range(8)), axis_name="ep")
    paddle.seed(11)
    shared_gate = NaiveGate(d, 1, 8, topk=2)
    gate_w, gate_b = (shared_gate.gate.weight._value,
                      shared_gate.gate.bias._value)

    class Identity(nn.Layer):
        def forward(self, x):
            return x

    x_np = np.random.RandomState(7).randn(32, d).astype("float32")

    def run(x):
        # 1 local expert per rank, 8 global experts; gate weights shared
        local = MoELayer(d, [Identity()],
                         gate=NaiveGate(d, 1, 8, topk=2),
                         moe_group=ep_group, capacity_factor=8.0)
        local.gate.gate.weight._replace_(gate_w, None)
        local.gate.gate.bias._replace_(gate_b, None)
        return local(paddle.to_tensor(x))._value

    out = shard_map(run, mesh=mesh, in_specs=P("ep"), out_specs=P("ep"),
                        check_vma=False)(jnp.asarray(x_np))
    np.testing.assert_allclose(np.asarray(out), x_np, rtol=1e-5, atol=1e-5)


def test_clip_grad_for_moe():
    paddle.seed(1)
    net = _expert(8, 16)
    clip = ClipGradForMOEByGlobalNorm(0.01, is_expert_param_func=lambda p: False)
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 8).astype("float32"))
    loss = (net(x) ** 2).sum()
    loss.backward()
    pg = [(p, p.grad) for p in net.parameters()]
    clipped = clip(pg)
    total = sum(float((g.numpy().astype("float64") ** 2).sum())
                for _, g in clipped if g is not None)
    assert np.sqrt(total) <= 0.0101


def test_fused_transformer_layers():
    import paddle_tpu.incubate.nn as inn
    paddle.seed(2)
    x = paddle.to_tensor(np.random.RandomState(3).randn(2, 6, 16).astype("float32"))

    attn = inn.FusedMultiHeadAttention(16, 4, dropout_rate=0.0,
                                       attn_dropout_rate=0.0)
    attn.eval()
    out = attn(x)
    assert tuple(out.shape) == (2, 6, 16)
    # all projections receive grads (regression: qkv split detached the tape)
    (out * out).sum().backward()
    assert attn.qkv_proj.weight.grad is not None
    assert attn.out_proj.weight.grad is not None
    with pytest.raises(NotImplementedError):
        attn(x, key=x)

    ffn = inn.FusedFeedForward(16, 32, dropout_rate=0.0)
    ffn.eval()
    assert tuple(ffn(x).shape) == (2, 6, 16)

    enc = inn.FusedTransformerEncoderLayer(16, 4, 32, dropout_rate=0.0)
    enc.eval()
    assert tuple(enc(x).shape) == (2, 6, 16)

    multi = inn.FusedMultiTransformer(16, 4, 32, num_layers=2)
    multi.eval()
    assert tuple(multi(x).shape) == (2, 6, 16)

    bdrln = inn.FusedBiasDropoutResidualLayerNorm(16, dropout_rate=0.0)
    bdrln.eval()
    assert tuple(bdrln(x, x).shape) == (2, 6, 16)


def test_fused_moe_layer():
    import paddle_tpu.incubate.nn as inn
    paddle.seed(8)
    layer = inn.FusedMoELayer(16, 32, num_expert=4, top_k=2)
    x = paddle.to_tensor(np.random.RandomState(9).randn(2, 6, 16)
                         .astype("float32"))
    out = layer(x)
    assert tuple(out.shape) == (2, 6, 16)
    (out * out).sum().backward()
    grads = [p for p in layer.parameters() if p.grad is not None]
    assert len(grads) >= 4
