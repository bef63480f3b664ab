"""Distributed tests on the virtual 8-device CPU mesh (reference analog:
test/collective + test/auto_parallel, run without a real cluster via local
multi-process — here via xla_force_host_platform_device_count)."""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


@pytest.fixture()
def hcg_2dp_4mp():
    strategy = dist.fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4, "pp_degree": 1,
                               "sharding_degree": 1, "sep_degree": 1}
    dist.fleet.init(is_collective=True, strategy=strategy)
    yield dist.fleet.get_hybrid_communicate_group()
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)


class TestTopology:
    def test_env(self):
        assert dist.get_world_size() == 1  # single process SPMD
        assert dist.get_rank() == 0
        import jax

        assert len(jax.devices()) == 8

    @pytest.mark.quick
    def test_hcg_mesh(self, hcg_2dp_4mp):
        hcg = hcg_2dp_4mp
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_model_parallel_world_size() == 4
        assert dict(hcg.mesh.shape) == {"dp": 2, "pp": 1, "sharding": 1, "sep": 1,
                                        "ep": 1, "mp": 4}

    def test_comm_topology_groups(self):
        from paddle_tpu.distributed.topology import CommunicateTopology

        topo = CommunicateTopology(("data", "model"), (2, 4))
        assert topo.world_size() == 8
        groups = topo.get_comm_list("model")
        assert len(groups) == 2 and all(len(g) == 4 for g in groups)
        dgroups = topo.get_comm_list("data")
        assert len(dgroups) == 4 and all(len(g) == 2 for g in dgroups)


class TestShardTensor:
    def _mesh(self):
        return dist.ProcessMesh(np.arange(8).reshape(2, 4), dim_names=["x", "y"])

    def test_shard_and_spec(self):
        mesh = self._mesh()
        t = dist.shard_tensor(P.randn([8, 12]), mesh, [dist.Shard(0), dist.Shard(1)])
        spec = t._value.sharding.spec
        assert spec == ("x", "y") or tuple(spec) == ("x", "y")
        assert dist.is_dist_tensor(t)

    def test_reshard_preserves_values(self):
        mesh = self._mesh()
        data = np.random.randn(8, 12).astype(np.float32)
        t = dist.shard_tensor(P.to_tensor(data), mesh, [dist.Shard(0), dist.Replicate()])
        t2 = dist.reshard(t, mesh, [dist.Replicate(), dist.Shard(1)])
        np.testing.assert_allclose(np.asarray(t2._value), data)

    def test_eager_math_on_sharded(self):
        mesh = self._mesh()
        a_np = np.random.randn(8, 8).astype(np.float32)
        a = dist.shard_tensor(P.to_tensor(a_np), mesh, [dist.Shard(0), dist.Replicate()])
        out = P.matmul(a, a) + 1.0
        np.testing.assert_allclose(out.numpy(), a_np @ a_np + 1, rtol=1e-4, atol=1e-4)

    def test_grad_through_sharded_param(self):
        mesh = self._mesh()
        w = dist.shard_tensor(P.randn([8, 4]), mesh, [dist.Shard(0), dist.Replicate()],
                              stop_gradient=False)
        w.is_parameter = True
        x = P.randn([2, 8])
        loss = P.matmul(x, w).sum()
        loss.backward()
        assert w.grad is not None
        assert w.grad.shape == [8, 4]

    def test_shard_layer(self):
        mesh = self._mesh()
        net = nn.Linear(8, 8)

        def shard_fn(name, sub, m):
            if isinstance(sub, nn.Linear):
                sub.weight = dist.shard_tensor(sub.weight, m, [dist.Replicate(), dist.Shard(1)])

        dist.shard_layer(net, mesh, shard_fn)
        assert dist.is_dist_tensor(net.weight)
        out = net(P.randn([2, 8]))
        assert out.shape == [2, 8]


class TestTPLayers:
    def test_column_row_match_dense(self, hcg_2dp_4mp):
        P.seed(0)
        col = dist.fleet.ColumnParallelLinear(16, 32, gather_output=False)
        row = dist.fleet.RowParallelLinear(32, 16, input_is_parallel=True)
        x = P.randn([8, 16])
        y = row(col(x))
        expect = (x._value @ col.weight._value + col.bias._value) @ row.weight._value + row.bias._value
        np.testing.assert_allclose(np.asarray(y._value), np.asarray(expect), rtol=1e-4, atol=1e-4)

    def test_vocab_parallel_embedding(self, hcg_2dp_4mp):
        emb = dist.fleet.VocabParallelEmbedding(64, 16)
        ids = P.to_tensor([1, 5, 63])
        out = emb(ids)
        np.testing.assert_allclose(
            np.asarray(out._value), np.asarray(emb.weight._value)[[1, 5, 63]], rtol=1e-5
        )

    def test_tp_backward(self, hcg_2dp_4mp):
        col = dist.fleet.ColumnParallelLinear(8, 16, gather_output=False)
        x = P.randn([4, 8])
        col(x).sum().backward()
        assert col.weight.grad is not None
        assert col.weight.grad.shape == [8, 16]

    def test_parallel_cross_entropy(self, hcg_2dp_4mp):
        ce = dist.fleet.ParallelCrossEntropy()
        logits = P.randn([6, 32])
        labels = P.to_tensor(np.random.randint(0, 32, 6))
        loss = ce(logits, labels)
        assert loss.shape == [6]


class TestCollectives:
    def test_all_reduce_in_shard_map(self):
        import jax
        import jax.numpy as jnp
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as PS

        g = dist.new_group(list(range(8)))

        def f(x):
            t = P.Tensor(x)
            dist.all_reduce(t, group=g)
            return t._value

        out = jax.jit(shard_map(f, mesh=g.mesh, in_specs=PS("group"), out_specs=PS("group")))(
            jnp.arange(8.0)
        )
        np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))

    def test_all_gather_in_shard_map(self):
        import jax
        import jax.numpy as jnp
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as PS

        g = dist.new_group(list(range(8)))

        def f(x):
            parts = dist.all_gather(None, P.Tensor(x), group=g)
            return jnp.concatenate([p._value for p in parts])

        out = jax.jit(shard_map(f, mesh=g.mesh, in_specs=PS("group"), out_specs=PS("group")))(
            jnp.arange(8.0)
        )
        np.testing.assert_allclose(np.asarray(out)[:8], np.arange(8.0))

    def test_reduce_scatter_in_shard_map(self):
        import jax
        import jax.numpy as jnp
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as PS

        g = dist.new_group(list(range(8)))

        def f(x):
            out = dist.reduce_scatter(None, P.Tensor(x), group=g)
            return out._value

        arr = jnp.ones((64,))
        out = jax.jit(shard_map(f, mesh=g.mesh, in_specs=PS("group"), out_specs=PS("group")))(arr)
        np.testing.assert_allclose(np.asarray(out), np.full(8, 8.0))

    def test_eager_barrier_and_broadcast(self):
        dist.barrier()
        t = P.ones([4])
        dist.broadcast(t, src=0)
        np.testing.assert_allclose(t.numpy(), np.ones(4))


class TestShardedTraining:
    def test_dp_sharded_train_step(self, hcg_2dp_4mp):
        """Full compiled train step with dp-sharded batch + mp-sharded layer —
        the multichip dryrun contract in miniature."""
        P.seed(0)

        class TPNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.col = dist.fleet.ColumnParallelLinear(16, 32, gather_output=False)
                self.row = dist.fleet.RowParallelLinear(32, 4, input_is_parallel=True)

            def forward(self, x):
                return self.row(self.col(x))

        net = dist.fleet.distributed_model(TPNet())
        opt = P.optimizer.AdamW(learning_rate=0.01, parameters=net.parameters())
        step = P.jit.TrainStep(net, lambda m, x, y: F.mse_loss(m(x), y), opt)
        X = P.randn([16, 16])
        Y = P.randn([16, 4])
        losses = [float(step(X, Y).numpy()) for _ in range(12)]
        assert losses[-1] < losses[0]

    def test_checkpoint_reshard_roundtrip(self, tmp_path):
        mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4), dim_names=["x", "y"])
        data = np.arange(64, dtype=np.float32).reshape(8, 8)
        sd = {"w": dist.shard_tensor(P.to_tensor(data), mesh, [dist.Shard(0), dist.Replicate()])}
        dist.checkpoint.save_state_dict(sd, str(tmp_path / "ckpt"))
        sd2 = {"w": dist.shard_tensor(P.zeros([8, 8]), mesh, [dist.Replicate(), dist.Shard(1)])}
        dist.checkpoint.load_state_dict(sd2, str(tmp_path / "ckpt"))
        np.testing.assert_allclose(np.asarray(sd2["w"]._value), data)


class TestCrossTopologyCheckpoint:
    """Save under {dp=8}, load under {dp=2, mp=2, sharding=2} and train
    (reference: distributed/checkpoint/load_state_dict.py
    resharding-on-load across parallel configs)."""

    def test_dp8_to_hybrid_reshard_and_train(self, tmp_path):
        from paddle_tpu.distributed.topology import set_hybrid_communicate_group
        from paddle_tpu.models import (
            LlamaForCausalLM,
            LlamaPretrainingCriterion,
            llama_tiny,
        )

        # ---- phase 1: pure data parallel (dp=8), train 2 steps, save
        set_hybrid_communicate_group(None)
        s = dist.fleet.DistributedStrategy()
        s.hybrid_configs = {"dp_degree": 8, "mp_degree": 1, "pp_degree": 1,
                            "sharding_degree": 1, "sep_degree": 1}
        dist.fleet.init(is_collective=True, strategy=s)
        P.seed(42)
        cfg = llama_tiny()
        inner = LlamaForCausalLM(cfg)
        model = dist.fleet.distributed_model(inner)
        crit = LlamaPretrainingCriterion()
        opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
        step = P.jit.TrainStep(model, lambda m, i: crit(m(i), i), opt)
        ids = P.to_tensor(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (8, 32)).astype(np.int32))
        step(ids)
        l_dp8 = float(step(ids).numpy())
        sd = model.state_dict()
        dist.checkpoint.save_state_dict(sd, str(tmp_path / "ckpt"))
        ref_w = {k: np.asarray(v._value) for k, v in sd.items()}

        # ---- phase 2: hybrid {dp=2, mp=2, sharding=2} — params TP-sharded
        set_hybrid_communicate_group(None)
        s2 = dist.fleet.DistributedStrategy()
        s2.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                             "sharding_degree": 2, "sep_degree": 1}
        dist.fleet.init(is_collective=True, strategy=s2)
        P.seed(7)  # different init on purpose — the load must overwrite it
        inner2 = LlamaForCausalLM(cfg)
        model2 = dist.fleet.distributed_model(inner2)
        sd2 = model2.state_dict()
        dist.checkpoint.load_state_dict(sd2, str(tmp_path / "ckpt"))

        # loaded values match the dp=8 run, now under mp sharding
        for k, v in sd2.items():
            np.testing.assert_allclose(
                np.asarray(v._value), ref_w[k], rtol=1e-5,
                err_msg=f"reshard mismatch for {k}")
        qw = inner2.llama.layers[0].self_attn.q_proj.weight
        assert "mp" in str(qw._value.sharding.spec), qw._value.sharding.spec

        # and training continues under the new topology
        opt2 = P.optimizer.AdamW(learning_rate=1e-3, parameters=model2.parameters())
        step2 = P.jit.TrainStep(model2, lambda m, i: crit(m(i), i), opt2)
        l0 = float(step2(ids).numpy())
        l1 = float(step2(ids).numpy())
        assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0
        # the resumed loss continues from the dp=8 trajectory, not from the
        # fresh seed-7 init
        assert abs(l0 - l_dp8) < 1.0


class TestAsyncCheckpointHygiene:
    """ISSUE 2 satellites: _pending_saves must not grow without bound
    across async_save=True calls, and background-write errors must surface
    on the NEXT save/load (or via the public wait_all), never silently."""

    def test_pending_saves_pruned_on_each_save(self, tmp_path):
        import paddle_tpu.distributed.checkpoint as ckpt

        sd = {"w": P.to_tensor(np.arange(8, dtype=np.float32))}
        for i in range(5):
            ckpt.save_state_dict(sd, str(tmp_path / f"c{i}"), async_save=True)
        ckpt.wait_all()
        assert ckpt._pending_saves == []
        # finished threads are pruned at the next save even WITHOUT an
        # explicit wait (the unbounded-growth failure mode)
        for i in range(5):
            ckpt.save_state_dict(sd, str(tmp_path / f"d{i}"), async_save=True)
            for t in list(ckpt._pending_saves):
                t.join()  # let the writes land, but don't pop them
        ckpt.save_state_dict(sd, str(tmp_path / "last"))
        assert len(ckpt._pending_saves) == 0

    def test_async_error_surfaces_on_next_save(self, tmp_path, monkeypatch):
        import paddle_tpu.distributed.checkpoint as ckpt

        sd = {"w": P.to_tensor(np.arange(4, dtype=np.float32))}

        def boom(*a, **k):
            raise OSError("disk full (injected)")

        monkeypatch.setattr(ckpt.np, "savez", boom)
        ckpt.save_state_dict(sd, str(tmp_path / "bad"), async_save=True)
        for t in list(ckpt._pending_saves):
            t.join()
        monkeypatch.undo()
        with pytest.raises(RuntimeError, match="async checkpoint save failed"):
            ckpt.save_state_dict(sd, str(tmp_path / "next"))
        # the error is consumed: the save after that succeeds
        ckpt.save_state_dict(sd, str(tmp_path / "next2"))
        ckpt.wait_all()

    def test_async_error_surfaces_on_load_and_wait_all(self, tmp_path,
                                                       monkeypatch):
        import paddle_tpu.distributed.checkpoint as ckpt

        sd = {"w": P.to_tensor(np.arange(4, dtype=np.float32))}
        ckpt.save_state_dict(sd, str(tmp_path / "good"))

        def boom(*a, **k):
            raise OSError("injected")

        monkeypatch.setattr(ckpt.np, "savez", boom)
        ckpt.save_state_dict(sd, str(tmp_path / "bad"), async_save=True)
        for t in list(ckpt._pending_saves):
            t.join()  # the injected failure must fire before savez restores
        monkeypatch.undo()
        tgt = {"w": P.to_tensor(np.zeros(4, dtype=np.float32))}
        with pytest.raises(RuntimeError, match="async checkpoint save failed"):
            ckpt.load_state_dict(tgt, str(tmp_path / "good"))
        # consumed: load now proceeds and fills the tensor
        ckpt.load_state_dict(tgt, str(tmp_path / "good"))
        np.testing.assert_array_equal(np.asarray(tgt["w"]._value),
                                      np.arange(4, dtype=np.float32))
        ckpt.wait_all()
