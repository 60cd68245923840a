"""Distributed tests on the 8-device virtual CPU mesh (SURVEY.md §4:
parallel loss == single-device loss — the reference's strongest invariant,
used for TP, DP, and sharding alike)."""
import jax
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed import mesh as M
from paddle_tpu.distributed.train_step import DistributedTrainStep
from paddle_tpu.jit_api import TrainStep
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny


def make_batch(bs=8, seq=16, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, (bs, seq + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def build_model_and_step(mesh=None, stage=1, seed=3, lr=0.01, **cfg_kw):
    paddle.seed(seed)
    cfg = llama_tiny(**cfg_kw)
    model = LlamaForCausalLM(cfg)
    loss_fn = lambda loss: loss  # model returns loss when labels given

    def wrapped_loss(out, labels):
        from paddle_tpu.models.llama import LlamaPretrainingCriterion

        return LlamaPretrainingCriterion()(out, labels)

    opt = optimizer.AdamW(learning_rate=lr, parameters=model.parameters(), weight_decay=0.0)
    if mesh is None:
        step = TrainStep(model, wrapped_loss, opt)
    else:
        step = DistributedTrainStep(model, wrapped_loss, opt, mesh=mesh, sharding_stage=stage)
    return model, step


class TestMesh:
    def test_build_mesh_axes(self):
        m = M.build_mesh(dp=2, mp=2, pp=2)
        assert m.axis_names == ("dcn_dp", "dp", "pp", "sharding", "sep", "mp")
        assert m.shape["dp"] == 2 and m.shape["mp"] == 2 and m.shape["pp"] == 2

    def test_topology_maps_to_mesh(self):
        from paddle_tpu.distributed.fleet.topology import CommunicateTopology

        topo = CommunicateTopology(dims=(2, 2, 1, 1, 2))
        assert topo.world_size() == 8
        assert topo.get_coord(0) == (0, 0, 0, 0, 0)
        ranks = topo.get_axis_list("data", 0)
        assert len(ranks) == 4


class TestCollectives:
    def test_allreduce_inside_shard_map(self):
        m = M.build_mesh(dp=8)
        with M.mesh_guard(m):
            grp = dist.new_group(axis_name="dp")

            def body(x):
                t = paddle.to_tensor(x)
                dist.all_reduce(t, group=grp)
                return t._data

            f = shard_map(body, mesh=m, in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
            x = np.arange(8, dtype=np.float32)
            out = f(x)
            assert np.allclose(np.asarray(out), np.full(8, x.sum()))

    def test_allgather_and_reduce_scatter(self):
        m = M.build_mesh(dp=8)
        with M.mesh_guard(m):
            grp = dist.new_group(axis_name="dp")

            def body(x):
                t = paddle.to_tensor(x)
                gathered = dist.all_gather(t, group=grp)
                return gathered._data

            f = shard_map(body, mesh=m, in_specs=P("dp"), out_specs=P(None), check_vma=False)
            x = np.arange(8, dtype=np.float32)
            out = f(x)
            assert np.allclose(np.asarray(out), x)

    def test_ppermute_ring(self):
        m = M.build_mesh(dp=8)
        with M.mesh_guard(m):

            def body(x):
                return dist.shift(paddle.to_tensor(x), "dp", offset=1)._data

            f = shard_map(body, mesh=m, in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
            x = np.arange(8, dtype=np.float32)
            out = np.asarray(f(x))
            assert np.allclose(out, np.roll(x, 1))


class TestParity:
    """parallel loss == single-device loss (reference hybrid_parallel_mp_layers
    / pp_alexnet test pattern)."""

    def test_dp_parity(self):
        x, y = make_batch()
        _, step_single = build_model_and_step(mesh=None)
        loss_single = step_single(paddle.to_tensor(x), paddle.to_tensor(y))

        m = M.build_mesh(dp=8)
        with M.mesh_guard(m):
            _, step_dp = build_model_and_step(mesh=m, stage=0)
            loss_dp = step_dp(paddle.to_tensor(x), paddle.to_tensor(y))
        assert np.allclose(loss_single.numpy(), loss_dp.numpy(), atol=1e-5)

    def test_tp_parity(self):
        x, y = make_batch()
        _, step_single = build_model_and_step(mesh=None)
        loss_single = step_single(paddle.to_tensor(x), paddle.to_tensor(y))

        m = M.build_mesh(mp=8)
        with M.mesh_guard(m):
            _, step_tp = build_model_and_step(mesh=m, stage=0)
            loss_tp = step_tp(paddle.to_tensor(x), paddle.to_tensor(y))
        # 1e-4, not 1e-5: mp=8 splits every contraction 8 ways and the
        # partitioner's reduction order varies by XLA version (older
        # XLA:CPU lands ~9e-5 off the single-device sum). A wrong TP
        # collective is an order-1 error, still far outside this bound.
        assert np.allclose(loss_single.numpy(), loss_tp.numpy(), atol=1e-4)

    def test_zero_sharding_parity_multi_step(self):
        x, y = make_batch()
        model_s, step_single = build_model_and_step(mesh=None)
        m = M.build_mesh(sharding=8)
        with M.mesh_guard(m):
            model_z, step_zero = build_model_and_step(mesh=m, stage=2)
            for i in range(3):
                ls = step_single(paddle.to_tensor(x), paddle.to_tensor(y))
                lz = step_zero(paddle.to_tensor(x), paddle.to_tensor(y))
                assert np.allclose(ls.numpy(), lz.numpy(), atol=1e-4), i
        # params drift equally
        for (k1, p1), (k2, p2) in zip(
            sorted(model_s.named_parameters()), sorted(model_z.named_parameters())
        ):
            assert np.allclose(p1.numpy(), p2.numpy(), atol=1e-3), k1

    def test_fsdp_stage3_parity(self):
        x, y = make_batch()
        _, step_single = build_model_and_step(mesh=None)
        loss_single = step_single(paddle.to_tensor(x), paddle.to_tensor(y))
        m = M.build_mesh(sharding=4, dp=2)
        with M.mesh_guard(m):
            _, step3 = build_model_and_step(mesh=m, stage=3)
            loss3 = step3(paddle.to_tensor(x), paddle.to_tensor(y))
        assert np.allclose(loss_single.numpy(), loss3.numpy(), atol=1e-5)

    def test_hybrid_tp_dp_sharding(self):
        x, y = make_batch()
        _, step_single = build_model_and_step(mesh=None)
        loss_single = step_single(paddle.to_tensor(x), paddle.to_tensor(y))
        m = M.build_mesh(dp=2, mp=2, sharding=2)
        with M.mesh_guard(m):
            _, step_h = build_model_and_step(mesh=m, stage=2)
            loss_h = step_h(paddle.to_tensor(x), paddle.to_tensor(y))
        assert np.allclose(loss_single.numpy(), loss_h.numpy(), atol=1e-5)

    def test_param_shards_actually_distributed(self):
        m = M.build_mesh(mp=8)
        with M.mesh_guard(m):
            model, step = build_model_and_step(mesh=m, stage=0)
            x, y = make_batch()
            step(paddle.to_tensor(x), paddle.to_tensor(y))
            w = model.llama.layers[0].mlp.gate_proj.weight._data
            # column-parallel weight must be sharded over mp
            shards = w.addressable_shards
            assert len(shards) == 8
            assert shards[0].data.shape[1] == w.shape[1] // 8


class TestRecompute:
    def test_recompute_grads_match(self):
        x, y = make_batch(seed=5)
        paddle.seed(11)
        m1 = LlamaForCausalLM(llama_tiny(use_recompute=False))
        paddle.seed(11)
        m2 = LlamaForCausalLM(llama_tiny(use_recompute=True))
        l1 = m1(paddle.to_tensor(x), labels=paddle.to_tensor(y))
        l2 = m2(paddle.to_tensor(x), labels=paddle.to_tensor(y))
        assert np.allclose(l1.numpy(), l2.numpy(), atol=1e-5)
        l1.backward()
        l2.backward()
        g1 = dict(m1.named_parameters())
        g2 = dict(m2.named_parameters())
        for k in g1:
            assert g1[k].grad is not None and g2[k].grad is not None, k
            assert np.allclose(g1[k].grad.numpy(), g2[k].grad.numpy(), atol=1e-4), k


class TestFleetFacade:
    def test_fleet_init_and_wrappers(self):
        from paddle_tpu.distributed import fleet

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1, "sharding_degree": 2}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_sharding_parallel_world_size() == 2
        model = nn.Linear(4, 4)
        wrapped = fleet.distributed_model(model)
        opt = fleet.distributed_optimizer(optimizer.AdamW(parameters=model.parameters()))
        assert opt.get_lr() is not None
