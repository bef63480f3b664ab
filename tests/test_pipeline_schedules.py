"""Pipeline schedule tests: explicit 1F1B / VPP / ZB-H1
programs, liveness properties, microbatch-gradient equivalence vs no-PP, and
VPP being genuinely distinct from 1F1B."""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.fleet.meta_parallel import LayerDesc, PipelineLayer
from paddle_tpu.distributed.fleet.meta_parallel.schedules import (
    BWD, BWD_INPUT, BWD_WEIGHT, FWD,
    fthenb_schedule, interleaved_1f1b_schedule, max_live_activations,
    one_f_one_b_schedule, zero_bubble_schedule,
)
from paddle_tpu.distributed.topology import set_hybrid_communicate_group


def _init_pp(pp=4):
    set_hybrid_communicate_group(None)
    s = dist.fleet.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 8 // pp, "mp_degree": 1, "pp_degree": pp,
                        "sharding_degree": 1, "sep_degree": 1}
    return s


class TestScheduleGenerators:
    def test_1f1b_bounds_liveness(self):
        g = fthenb_schedule(8, 4)
        o = one_f_one_b_schedule(8, 4)
        assert max_live_activations(g) == 8
        assert max_live_activations(o) == 4  # min(stages, micros)
        # same op multiset
        assert sorted(repr(x) for x in g) == sorted(repr(x) for x in o)

    def test_1f1b_order_contract(self):
        o = one_f_one_b_schedule(6, 2)
        # warmup = 2 forwards, then strictly alternating B/F until drain
        kinds = [op.kind for op in o]
        assert kinds[:2] == [FWD, FWD]
        assert kinds[2:10] == [BWD, FWD] * 4
        assert kinds[10:] == [BWD, BWD]

    def test_vpp_distinct_from_1f1b(self):
        v = interleaved_1f1b_schedule(4, 2, 2)
        o = one_f_one_b_schedule(4, 2)
        assert [repr(x) for x in v] != [repr(x) for x in o]
        # every micro visits every chunk exactly once in each direction
        fwd = [(x.micro, x.chunk) for x in v if x.kind == FWD]
        bwd = [(x.micro, x.chunk) for x in v if x.kind == BWD]
        assert sorted(fwd) == sorted(bwd) == [(m, c) for m in range(4) for c in range(2)]
        # chunk boundaries are respected: F(m,1) after F(m,0); B(m,0) after B(m,1)
        for m in range(4):
            assert v.index(next(x for x in v if x.kind == FWD and x.micro == m and x.chunk == 1)) > \
                   v.index(next(x for x in v if x.kind == FWD and x.micro == m and x.chunk == 0))
            assert v.index(next(x for x in v if x.kind == BWD and x.micro == m and x.chunk == 0)) > \
                   v.index(next(x for x in v if x.kind == BWD and x.micro == m and x.chunk == 1))

    def test_vpp_requires_divisibility(self):
        with pytest.raises(ValueError):
            interleaved_1f1b_schedule(5, 2, 2)

    def test_zero_bubble_splits_backward(self):
        z = zero_bubble_schedule(6, 2)
        kinds = {op.kind for op in z}
        assert BWD_INPUT in kinds and BWD_WEIGHT in kinds and BWD not in kinds
        # every micro gets exactly one Bx and one Bw, Bw after Bx
        for m in range(6):
            bx = z.index(next(x for x in z if x.kind == BWD_INPUT and x.micro == m))
            bw = z.index(next(x for x in z if x.kind == BWD_WEIGHT and x.micro == m))
            assert bw > bx


def _grads_of(net):
    """Grads keyed by global layer index (stage_s.i -> s*per_stage+i) so pp
    and no-pp models compare even though stage grouping differs."""
    out = {}
    per_stage = {}
    for n, p in net.named_parameters():
        s = int(n.split(".")[0].split("_")[1])
        per_stage.setdefault(s, set()).add(int(n.split(".")[1]))
    sizes = [len(per_stage[s]) for s in sorted(per_stage)]
    offs = {s: sum(sizes[:i]) for i, s in enumerate(sorted(per_stage))}
    for n, p in net.named_parameters():
        if p.grad is None:
            continue
        parts = n.split(".")
        s, i = int(parts[0].split("_")[1]), int(parts[1])
        out[(offs[s] + i, parts[2])] = p.grad.numpy().copy()
    return out


class TestPipelineGradEquivalence:
    @pytest.mark.parametrize("mode,chunks", [("FThenB", 1), ("1F1B", 1),
                                             ("ZBH1", 1), ("VPP", 2)])
    def test_matches_no_pp(self, mode, chunks):
        pp = 4
        strat = _init_pp(pp)
        strat.pipeline_configs = {"accumulate_steps": 8, "schedule_mode": mode}
        dist.fleet.init(is_collective=True, strategy=strat)
        P.seed(5)
        descs = [LayerDesc(nn.Linear, 16, 16) for _ in range(8)]
        pipe_layer = PipelineLayer(layers=descs, num_stages=pp,
                                   loss_fn=lambda o, y: F.mse_loss(o, y),
                                   num_virtual_pipeline_stages=chunks)
        pipe = dist.fleet.distributed_model(pipe_layer)
        X = P.to_tensor(np.random.RandomState(0).randn(16, 16).astype(np.float32))
        Y = P.to_tensor(np.random.RandomState(1).randn(16, 16).astype(np.float32))
        loss = pipe.forward_backward_pipeline([X, Y])
        pp_grads = _grads_of(pipe_layer)
        pp_loss = float(loss.numpy())

        # reference: same weights, single-shot full-batch loss
        set_hybrid_communicate_group(None)
        P.seed(5)
        ref_layer = PipelineLayer(layers=[LayerDesc(nn.Linear, 16, 16) for _ in range(8)],
                                  num_stages=1, loss_fn=lambda o, y: F.mse_loss(o, y))
        ref_loss = F.mse_loss(ref_layer(X), Y)
        ref_loss.backward()
        ref_grads = _grads_of(ref_layer)

        assert abs(pp_loss - float(ref_loss.numpy())) < 1e-5
        assert set(pp_grads) == set(ref_grads)
        for k in pp_grads:
            np.testing.assert_allclose(pp_grads[k], ref_grads[k], rtol=1e-4, atol=1e-5,
                                       err_msg=f"{mode} grad mismatch at {k}")
        set_hybrid_communicate_group(None)

    def test_vpp_training_converges(self):
        pp = 2
        strat = _init_pp(pp)
        strat.pipeline_configs = {"accumulate_steps": 4, "schedule_mode": "VPP"}
        dist.fleet.init(is_collective=True, strategy=strat)
        P.seed(9)
        descs = [LayerDesc(nn.Linear, 8, 8) for _ in range(8)]
        pipe = dist.fleet.distributed_model(
            PipelineLayer(layers=descs, num_stages=pp,
                          loss_fn=lambda o, y: F.mse_loss(o, y),
                          num_virtual_pipeline_stages=2))
        opt = P.optimizer.SGD(0.05, parameters=pipe.parameters())
        X, Y = P.randn([16, 8]), P.zeros([16, 8])
        l0 = float(pipe.train_batch([X, Y], opt).numpy())
        for _ in range(10):
            l1 = float(pipe.train_batch([X, Y], opt).numpy())
        assert l1 < l0
        set_hybrid_communicate_group(None)


class Test4DHybridLlama:
    """BASELINE's GPT-3 rung topology: TP inside pipeline stages, dp outside
    (dp=2 x mp=2 x pp=2 over the 8-device mesh)."""

    def test_llama_4d_trains(self):
        from paddle_tpu.distributed.topology import set_hybrid_communicate_group
        from paddle_tpu.models import (
            LlamaPretrainingCriterion,
            llama_pipeline_descs,
            llama_tiny,
        )

        set_hybrid_communicate_group(None)
        # unconditional reset: leaving the mp=2 group active (including on
        # an assertion failure below) would silently turn every LATER
        # test's llama into a TP model (the serving suites build plain
        # single-process models and compare against generate)
        try:
            s = dist.fleet.DistributedStrategy()
            s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 2,
                                "sharding_degree": 1, "sep_degree": 1}
            s.pipeline_configs = {"accumulate_steps": 2, "schedule_mode": "1F1B"}
            dist.fleet.init(is_collective=True, strategy=s)
            P.seed(0)
            cfg = llama_tiny()
            crit = LlamaPretrainingCriterion()
            pipe = PipelineLayer(layers=llama_pipeline_descs(cfg), num_stages=2,
                                 loss_fn=lambda lo, la: crit(lo, la))
            model = dist.fleet.distributed_model(pipe)
            opt = P.optimizer.AdamW(learning_rate=1e-3,
                                    parameters=model.parameters())
            ids = P.to_tensor(np.random.RandomState(0).randint(
                0, cfg.vocab_size, (4, 32)).astype(np.int32))
            l0 = float(model.train_batch([ids, ids], opt).numpy())
            for _ in range(4):
                l1 = float(model.train_batch([ids, ids], opt).numpy())
            assert np.isfinite(l0) and l1 < l0
            # a TP weight inside a pipeline stage is mp-sharded on its SUBMESH
            qw = None
            for lay in pipe._stage_layers[1]:
                for p in lay.parameters():
                    if p.ndim == 2 and "mp" in str(p._value.sharding.spec):
                        qw = p
                        break
            assert qw is not None
            # stage submesh
            assert len(qw._value.sharding.mesh.devices.flatten()) == 4
        finally:
            set_hybrid_communicate_group(None)
