"""ZeRO sharding stages 1/2/3.

8-device CPU mesh: verify per-device optimizer-state / param memory shrinks
~Nx and loss trajectory matches stage 0.
Reference anchors: group_sharded_stage3.py:85, dygraph_sharding_optimizer.py:44.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as P
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.topology import set_hybrid_communicate_group


def _init_sharding(degree=8, stage=1):
    set_hybrid_communicate_group(None)
    s = dist.fleet.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                        "sharding_degree": degree, "sep_degree": 1}
    s.sharding = True
    s.sharding_configs = {"stage": stage}
    dist.fleet.init(is_collective=True, strategy=s)
    return s


def _per_device_bytes(val):
    return val.addressable_shards[0].data.nbytes


def _train(stage, steps=5):
    if stage == 0:
        set_hybrid_communicate_group(None)
    else:
        _init_sharding(8, stage)
    P.seed(42)
    net = nn.Sequential(nn.Linear(64, 64), nn.ReLU(), nn.Linear(64, 64))
    if stage == 0:
        model = net
        opt = P.optimizer.Adam(0.01, parameters=net.parameters())
    else:
        model = dist.fleet.distributed_model(net)
        opt = dist.fleet.distributed_optimizer(
            P.optimizer.Adam(0.01, parameters=net.parameters()))
    X = P.to_tensor(np.random.RandomState(0).randn(16, 64).astype(np.float32))
    Y = P.to_tensor(np.random.RandomState(1).randn(16, 64).astype(np.float32))
    losses = []
    for _ in range(steps):
        loss = F.mse_loss(model(X), Y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    set_hybrid_communicate_group(None)
    return net, getattr(opt, "_inner", opt), losses


class TestZeroStages:
    def test_stage_classes_are_distinct(self):
        from paddle_tpu.distributed.auto_parallel.api import (
            ShardingStage1, ShardingStage2, ShardingStage3)
        assert ShardingStage1 is not ShardingStage2
        assert ShardingStage2 is not ShardingStage3
        assert ShardingStage1.stage == 1 and ShardingStage2.stage == 2 \
            and ShardingStage3.stage == 3

    @pytest.mark.parametrize("stage", [1, 2, 3])
    def test_loss_parity_with_stage0(self, stage):
        _, _, base = _train(0)
        _, _, got = _train(stage)
        np.testing.assert_allclose(got, base, rtol=1e-4, atol=1e-5)

    @pytest.mark.quick
    def test_stage1_accumulator_memory_shrinks(self):
        net, opt, _ = _train(1)
        w = net[0].weight  # [64, 64] divisible by 8
        m = opt._accumulators["moment1"][id(w)]
        assert _per_device_bytes(m) * 8 == m.nbytes
        assert "sharding" in str(m.sharding.spec)

    def test_stage2_grads_sharded(self):
        _init_sharding(8, 2)
        P.seed(0)
        net = nn.Linear(64, 64)
        opt = dist.fleet.distributed_optimizer(
            P.optimizer.Adam(0.01, parameters=net.parameters()))
        loss = F.mse_loss(net(P.randn([8, 64])), P.randn([8, 64]))
        loss.backward()
        opt.step()
        g = net.weight.grad._value
        assert _per_device_bytes(g) * 8 == g.nbytes
        set_hybrid_communicate_group(None)

    def test_stage3_param_memory_shrinks(self):
        net, opt, _ = _train(3)
        w = net[0].weight._value
        assert _per_device_bytes(w) * 8 == w.nbytes
        assert "sharding" in str(w.sharding.spec)

    def test_stage3_compiled_trainstep(self):
        _init_sharding(8, 3)
        P.seed(7)
        net = nn.Sequential(nn.Linear(64, 64), nn.ReLU(), nn.Linear(64, 64))
        model = dist.fleet.distributed_model(net)
        opt = dist.fleet.distributed_optimizer(
            P.optimizer.AdamW(0.01, parameters=net.parameters()))
        step = P.jit.TrainStep(model, lambda m, x, y: F.mse_loss(m(x), y),
                               getattr(opt, "_inner", opt))
        X, Y = P.randn([16, 64]), P.randn([16, 64])
        l0 = float(step(X, Y).numpy())
        for _ in range(4):
            l1 = float(step(X, Y).numpy())
        assert np.isfinite(l1) and l1 < l0
        # params stay sharded through compiled updates
        w = net[0].weight._value
        assert _per_device_bytes(w) * 8 == w.nbytes
        set_hybrid_communicate_group(None)

    def test_group_sharded_parallel_api(self):
        _init_sharding(8, 1)
        net = nn.Linear(64, 64)
        opt = P.optimizer.Adam(0.01, parameters=net.parameters())
        model, opt2, _ = dist.fleet.group_sharded_parallel(net, opt, "p_g_os")
        w = net.weight._value
        assert _per_device_bytes(w) * 8 == w.nbytes
        set_hybrid_communicate_group(None)
