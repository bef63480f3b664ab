"""Compiled pipeline: full microbatch schedule in one XLA program
(reference analog: pipeline_scheduler_pass/)."""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.fleet.meta_parallel import (
    CompiledPipelineTrainStep,
    LayerDesc,
    PipelineLayer,
    pipeline_bubble_fraction,
)
from paddle_tpu.distributed.topology import set_hybrid_communicate_group

# old jax (no top-level jax.shard_map) aborts XLA's SPMD partitioner when
# the compiled pipeline's manual 'pp' axis meets a real (size>1) auto axis;
# CompiledPipelineTrainStep refuses such meshes cleanly, and the tests that
# specifically exercise dp/mp composition only run on modern jax
import jax as _jax

_AUTO_AXES_OK = hasattr(_jax, "shard_map")
needs_auto_axes = pytest.mark.skipif(
    not _AUTO_AXES_OK,
    reason="partial-manual shard_map with size>1 auto axes needs "
           "jax.shard_map (>=0.8)")
# composition degree: tests that WANT a real dp/mp axis keep it on modern
# jax and degrade to 1 (pp-only, still exercising the schedule) on old jax
_D2 = 2 if _AUTO_AXES_OK else 1


def _init(dp, pp):
    set_hybrid_communicate_group(None)
    s = dist.fleet.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": dp, "mp_degree": 1, "pp_degree": pp,
                        "sharding_degree": 1, "sep_degree": 1}
    dist.fleet.init(is_collective=True, strategy=s)


def _mlp_descs(n, width=16):
    return [LayerDesc(nn.Linear, width, width) for _ in range(n)]


class TestCompiledPipeline:
    def test_trains_and_matches_sequential(self):
        _init(dp=_D2, pp=4)
        P.seed(7)
        pipe = PipelineLayer(layers=_mlp_descs(8), num_stages=4,
                             loss_fn=lambda o, y: F.mse_loss(o, y))
        # snapshot weights for the sequential reference
        w0 = [np.asarray(p._value) for ps in
              [[p for l in pipe._stage_layers[s] for p in l.parameters()]
               for s in range(4)] for p in ps]

        opt = P.optimizer.SGD(0.05, parameters=pipe.parameters())
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=4)
        x = P.randn([8, 16])
        y = P.randn([8, 16])
        l0 = float(step(x, y).numpy())

        # sequential single-device reference with identical weights
        set_hybrid_communicate_group(None)
        P.seed(7)
        layers = [nn.Linear(16, 16) for _ in range(8)]
        flat = [p for l in layers for p in l.parameters()]
        for p, v in zip(flat, w0):
            p._value = P.to_tensor(v)._value
        net = nn.Sequential(*layers)
        ref = float(F.mse_loss(net(x), y).numpy())
        np.testing.assert_allclose(l0, ref, rtol=1e-4)

        # trains
        _init(dp=_D2, pp=4)
        for _ in range(10):
            l1 = float(step(x, y).numpy())
        assert l1 < l0

    def test_optimizer_state_is_stacked_and_sync_back(self):
        _init(dp=1, pp=2)
        P.seed(0)
        pipe = PipelineLayer(layers=_mlp_descs(4), num_stages=2,
                             loss_fn=lambda o, y: F.mse_loss(o, y))
        opt = P.optimizer.AdamW(learning_rate=0.01, parameters=pipe.parameters())
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=2)
        x, y = P.randn([4, 16]), P.randn([4, 16])
        step(x, y)
        # accumulators exist per stacked [P, ...] weight
        accs = opt._accumulators.get("moment1") or next(iter(opt._accumulators.values()))
        shapes = {tuple(v.shape) for v in accs.values()}
        assert all(s[0] == 2 for s in shapes), shapes
        # sync back: per-stage tensors updated
        before = np.asarray(pipe._stage_layers[0][0].parameters()[0]._value).copy()
        step.sync_to_model()
        after = np.asarray(pipe._stage_layers[0][0].parameters()[0]._value)
        assert not np.allclose(before, after)

    def test_bubble_fraction(self):
        assert pipeline_bubble_fraction(4, 4) == pytest.approx(3 / 7)
        assert pipeline_bubble_fraction(32, 4) < 0.09

    def test_rejects_heterogeneous_stages(self):
        _init(dp=1, pp=2)
        descs = [LayerDesc(nn.Linear, 16, 16), LayerDesc(nn.Linear, 16, 32)]
        pipe = PipelineLayer(layers=descs, num_stages=2,
                             loss_fn=lambda o, y: F.mse_loss(o, y))
        opt = P.optimizer.SGD(0.1, parameters=pipe.parameters())
        with pytest.raises(ValueError, match="homogeneous"):
            CompiledPipelineTrainStep(pipe, opt, num_micro=2)

    def test_scaler_integration(self):
        _init(dp=1, pp=2)
        P.seed(1)
        pipe = PipelineLayer(layers=_mlp_descs(4), num_stages=2,
                             loss_fn=lambda o, y: F.mse_loss(o, y))
        opt = P.optimizer.SGD(0.05, parameters=pipe.parameters())
        scaler = P.amp.GradScaler(init_loss_scaling=2.0 ** 10)
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=2, scaler=scaler)
        x, y = P.randn([4, 16]), P.randn([4, 16])
        l0 = float(step(x, y).numpy())
        for _ in range(8):
            l1 = float(step(x, y).numpy())
        assert np.isfinite(l1) and l1 < l0


def _init4d(dp, mp, pp):
    set_hybrid_communicate_group(None)
    s = dist.fleet.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": dp, "mp_degree": mp, "pp_degree": pp,
                        "sharding_degree": 1, "sep_degree": 1}
    dist.fleet.init(is_collective=True, strategy=s)


class TestCompiledPipelineRealModel:
    """The compiled pipeline must run the real llama —
    heterogeneous stages (embed head / lm-head tail), tied embeddings, and
    optimizers with existing state / multiple groups."""

    def _llama(self, tie=False, seg="uniform"):
        from paddle_tpu.models import (
            LlamaPretrainingCriterion,
            llama_pipeline_descs,
            llama_tiny,
        )

        cfg = llama_tiny()
        crit = LlamaPretrainingCriterion()
        pipe = PipelineLayer(
            layers=llama_pipeline_descs(cfg, tie_embeddings=tie),
            num_stages=2, loss_fn=lambda lo, la: crit(lo, la), seg_method=seg)
        return cfg, pipe

    @needs_auto_axes
    def test_4d_llama_trains_compiled(self):
        _init4d(dp=2, mp=2, pp=2)
        P.seed(3)
        cfg, pipe = self._llama()
        opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=pipe.parameters())
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=2)
        ids = P.to_tensor(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (4, 16)).astype(np.int32))
        l0 = float(step(ids, ids).numpy())
        assert np.isfinite(l0)
        for _ in range(6):
            l1 = float(step(ids, ids).numpy())
        assert l1 < l0

    def test_compiled_matches_sequential_forward(self):
        _init4d(dp=1, mp=1, pp=2)
        P.seed(11)
        cfg, pipe = self._llama()
        # zero-LR: the compiled loss must equal the eager sequential loss on
        # the very same weights (reference computed BEFORE construction —
        # building the compiled step re-places head/tail params on the full
        # mesh, which the eager per-stage path doesn't expect)
        ids = P.to_tensor(np.random.RandomState(1).randint(
            0, cfg.vocab_size, (4, 16)).astype(np.int32))
        from paddle_tpu.models import LlamaPretrainingCriterion

        crit = LlamaPretrainingCriterion()
        logits = pipe.forward(ids)  # eager sequential through the same stages
        ref = float(crit(logits, ids).numpy())
        opt = P.optimizer.SGD(0.0, parameters=pipe.parameters())
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=2)
        compiled = float(step(ids, ids).numpy())
        np.testing.assert_allclose(compiled, ref, rtol=2e-3)

    def test_tied_embeddings_shared_grad(self):
        _init4d(dp=_D2, mp=_D2, pp=2)
        P.seed(5)
        cfg, pipe = self._llama(tie=True, seg="layer:_PipeDecoder")
        # ONE embedding layer object shared between stage 0 and stage 1
        emb = pipe.get_shared_layer("embed")
        assert any(l is emb for l in pipe._stage_layers[0])
        assert any(l is emb for l in pipe._stage_layers[-1])
        opt = P.optimizer.AdamW(learning_rate=1e-2, parameters=pipe.parameters())
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=2)
        ids = P.to_tensor(np.random.RandomState(2).randint(
            0, cfg.vocab_size, (4, 16)).astype(np.int32))
        w_before = np.asarray(emb.embed_tokens.weight._value).copy()
        l0 = float(step(ids, ids).numpy())
        w_after = np.asarray(emb.embed_tokens.weight._value)
        assert np.isfinite(l0)
        assert not np.allclose(w_before, w_after)  # tied weight got grads
        for _ in range(6):
            l1 = float(step(ids, ids).numpy())
        assert l1 < l0

    def test_existing_optimizer_state_survives(self):
        # momentum accumulated on the eager engine must carry into the
        # compiled engine (restacked [P, ...])
        _init4d(dp=1, mp=1, pp=2)
        P.seed(9)
        cfg, pipe = self._llama()
        opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=pipe.parameters())
        ids = P.to_tensor(np.random.RandomState(3).randint(
            0, cfg.vocab_size, (4, 16)).astype(np.int32))
        # a few eager steps accumulate per-stage state
        from paddle_tpu.models import LlamaPretrainingCriterion

        crit = LlamaPretrainingCriterion()
        for _ in range(2):
            loss = crit(pipe.forward(ids), ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
        moment_sum_before = sum(
            float(np.abs(np.asarray(v)).sum())
            for v in opt._accumulators["moment1"].values())
        assert moment_sum_before > 0
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=2)
        # restacked state: every body accumulator now leads with P=2
        decoder_param_count = len(step._body_segs[0].params)
        stacked_accs = [v for v in opt._accumulators["moment1"].values()
                        if np.ndim(v) > 0 and v.shape[0] == 2]
        assert len(stacked_accs) >= decoder_param_count
        l = float(step(ids, ids).numpy())
        assert np.isfinite(l)

    def test_multiple_param_groups(self):
        _init4d(dp=1, mp=1, pp=2)
        P.seed(13)
        cfg, pipe = self._llama()
        # split params by kind — uniform across stages (decay vs no-decay)
        decay, no_decay = [], []
        for p in pipe.parameters():
            (no_decay if p.ndim <= 1 else decay).append(p)
        opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=[
            {"params": decay, "weight_decay": 0.1},
            {"params": no_decay, "weight_decay": 0.0},
        ])
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=2)
        assert len(opt._param_groups) == 2
        ids = P.to_tensor(np.random.RandomState(4).randint(
            0, cfg.vocab_size, (4, 16)).astype(np.int32))
        l0 = float(step(ids, ids).numpy())
        for _ in range(4):
            l1 = float(step(ids, ids).numpy())
        assert np.isfinite(l1) and l1 < l0

    def test_sync_to_model_restores_eager_engine(self):
        _init4d(dp=1, mp=1, pp=2)
        P.seed(17)
        cfg, pipe = self._llama()
        opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=pipe.parameters())
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=2)
        ids = P.to_tensor(np.random.RandomState(5).randint(
            0, cfg.vocab_size, (4, 16)).astype(np.int32))
        compiled_loss = float(step(ids, ids).numpy())
        step.sync_to_model()
        # eager per-stage engine must run again after the placement restore
        from paddle_tpu.models import LlamaPretrainingCriterion

        crit = LlamaPretrainingCriterion()
        eager_loss = float(crit(pipe.forward(ids), ids).numpy())
        assert np.isfinite(eager_loss)


class TestCompiledVPP:
    """VPP chunks compiled (closing the r4 scope note): weights [C, P, ...],
    chunk-sequential rings with exit hop back to stage 0."""

    def test_vpp_matches_sequential_and_trains(self):
        _init(dp=_D2, pp=2)
        P.seed(21)
        # 8 layers, pp=2, 2 virtual chunks -> 4 segments of 2 layers
        pipe = PipelineLayer(layers=_mlp_descs(8), num_stages=2,
                             num_virtual_pipeline_stages=2,
                             loss_fn=lambda o, y: F.mse_loss(o, y))
        assert pipe._num_chunks == 2 and pipe._num_segments == 4
        w0 = [np.asarray(p._value) for s in range(4)
              for l in pipe._stage_layers[s] for p in l.parameters()]
        opt = P.optimizer.SGD(0.0, parameters=pipe.parameters())  # zero-LR parity
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=2)
        assert step.num_chunks == 2
        x, y = P.randn([4, 16]), P.randn([4, 16])
        compiled = float(step(x, y).numpy())
        # sequential single-device reference with identical weights
        set_hybrid_communicate_group(None)
        layers = [nn.Linear(16, 16) for _ in range(8)]
        for p, v in zip([p for l in layers for p in l.parameters()], w0):
            p._value = P.to_tensor(v)._value
        ref = float(F.mse_loss(nn.Sequential(*layers)(x), y).numpy())
        np.testing.assert_allclose(compiled, ref, rtol=1e-4)
        # trains with a real LR
        _init(dp=_D2, pp=2)
        pipe2 = PipelineLayer(layers=_mlp_descs(8), num_stages=2,
                              num_virtual_pipeline_stages=2,
                              loss_fn=lambda o, y: F.mse_loss(o, y))
        opt2 = P.optimizer.AdamW(learning_rate=0.02, parameters=pipe2.parameters())
        step2 = CompiledPipelineTrainStep(pipe2, opt2, num_micro=2)
        l0 = float(step2(x, y).numpy())
        for _ in range(8):
            l1 = float(step2(x, y).numpy())
        assert l1 < l0
        # accumulators carry the [C, P, ...] leading dims
        accs = opt2._accumulators["moment1"]
        assert any(tuple(v.shape[:2]) == (2, 2) for v in accs.values())

    def test_vpp_interleaved_matches_chunk_sequential(self, monkeypatch):
        """r6: the branch-free interleaved ordering (AUTOMATIC when legal)
        computes the SAME loss as the chunk-sequential
        rings (forced with PADDLE_TPU_VPP_INTERLEAVED=0) and as the r5
        lax.switch interleaved tick
        (PADDLE_TPU_VPP_INTERLEAVED_IMPL=switch)."""
        x, y = P.randn([8, 16]), P.randn([8, 16])

        def run(schedule):
            monkeypatch.delenv("PADDLE_TPU_VPP_INTERLEAVED", raising=False)
            monkeypatch.delenv("PADDLE_TPU_VPP_INTERLEAVED_IMPL",
                               raising=False)
            if schedule == "sequential":
                monkeypatch.setenv("PADDLE_TPU_VPP_INTERLEAVED", "0")
            elif schedule == "switch":
                monkeypatch.setenv("PADDLE_TPU_VPP_INTERLEAVED_IMPL",
                                   "switch")
            _init(dp=_D2, pp=2)
            P.seed(33)
            pipe = PipelineLayer(layers=_mlp_descs(8), num_stages=2,
                                 num_virtual_pipeline_stages=2,
                                 loss_fn=lambda o, y: F.mse_loss(o, y))
            opt = P.optimizer.SGD(0.0, parameters=pipe.parameters())
            step = CompiledPipelineTrainStep(pipe, opt, num_micro=4)
            return float(step(x, y).numpy())

        seq = run("sequential")
        np.testing.assert_allclose(seq, run("auto"), rtol=1e-5)
        np.testing.assert_allclose(seq, run("switch"), rtol=1e-5)

    def test_vpp_interleaved_tied_embeddings_parity(self, monkeypatch):
        """Heterogeneous stages under VPP — tied-embedding head/tail riding
        as shared aux params — must compute the same loss on all three
        schedules: chunk-sequential rings, the branch-free interleaved tick
        (auto-selected), and the lax.switch fallback tick."""
        from paddle_tpu.models import (
            LlamaPretrainingCriterion,
            llama_pipeline_descs,
            llama_tiny,
        )

        cfg = llama_tiny()
        cfg.num_hidden_layers = 4
        ids = P.to_tensor(np.random.RandomState(7).randint(
            0, cfg.vocab_size, (4, 16)).astype(np.int32))

        def build(schedule, lr=0.0):
            monkeypatch.delenv("PADDLE_TPU_VPP_INTERLEAVED", raising=False)
            monkeypatch.delenv("PADDLE_TPU_VPP_INTERLEAVED_IMPL",
                               raising=False)
            if schedule == "sequential":
                monkeypatch.setenv("PADDLE_TPU_VPP_INTERLEAVED", "0")
            elif schedule == "switch":
                monkeypatch.setenv("PADDLE_TPU_VPP_INTERLEAVED_IMPL",
                                   "switch")
            _init(dp=1, pp=2)
            P.seed(41)
            crit = LlamaPretrainingCriterion()
            pipe = PipelineLayer(
                layers=llama_pipeline_descs(cfg, tie_embeddings=True),
                num_stages=2, num_virtual_pipeline_stages=2,
                loss_fn=lambda lo, la: crit(lo, la),
                seg_method="layer:_PipeDecoder")
            opt = P.optimizer.SGD(lr, parameters=pipe.parameters())
            return CompiledPipelineTrainStep(pipe, opt, num_micro=2), pipe

        step, _ = build("sequential")
        assert step._chunks_homogeneous
        ref = float(step(ids, ids).numpy())
        step_i, _ = build("auto")
        np.testing.assert_allclose(float(step_i(ids, ids).numpy()), ref,
                                   rtol=2e-3)
        step_sw, _ = build("switch")
        np.testing.assert_allclose(float(step_sw(ids, ids).numpy()), ref,
                                   rtol=2e-3)

        # the tied weight gets grads through the interleaved schedule too
        _, pipe_t = build("auto", lr=0.0)
        emb = pipe_t.get_shared_layer("embed")
        opt2 = P.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=pipe_t.parameters())
        step_t2 = CompiledPipelineTrainStep(pipe_t, opt2, num_micro=2)
        w_before = np.asarray(emb.embed_tokens.weight._value).copy()
        l0 = float(step_t2(ids, ids).numpy())
        assert np.isfinite(l0)
        assert not np.allclose(w_before,
                               np.asarray(emb.embed_tokens.weight._value))

    def test_vpp_interleaved_optimizer_roundtrip(self):
        """Optimizer state stacks [C, P, ...] under the auto-selected
        interleaved schedule and round-trips through sync_to_model back to
        the eager per-stage engine."""
        _init(dp=1, pp=2)
        P.seed(37)
        pipe = PipelineLayer(layers=_mlp_descs(8), num_stages=2,
                             num_virtual_pipeline_stages=2,
                             loss_fn=lambda o, y: F.mse_loss(o, y))
        opt = P.optimizer.AdamW(learning_rate=0.01,
                                parameters=pipe.parameters())
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=4)
        x, y = P.randn([8, 16]), P.randn([8, 16])
        l0 = float(step(x, y).numpy())
        for _ in range(4):
            l1 = float(step(x, y).numpy())
        assert np.isfinite(l1) and l1 < l0
        accs = opt._accumulators["moment1"]
        assert any(tuple(v.shape[:2]) == (2, 2) for v in accs.values())
        before = np.asarray(
            pipe._stage_layers[3][0].parameters()[0]._value).copy()
        step.sync_to_model()
        after = np.asarray(pipe._stage_layers[3][0].parameters()[0]._value)
        assert not np.allclose(before, after)
        # eager per-stage engine runs again after the placement restore
        eager = float(F.mse_loss(pipe.forward(x), y).numpy())
        assert np.isfinite(eager)

    def test_vpp_sync_to_model(self):
        _init(dp=1, pp=2)
        P.seed(23)
        pipe = PipelineLayer(layers=_mlp_descs(8), num_stages=2,
                             num_virtual_pipeline_stages=2,
                             loss_fn=lambda o, y: F.mse_loss(o, y))
        opt = P.optimizer.SGD(0.05, parameters=pipe.parameters())
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=2)
        x, y = P.randn([4, 16]), P.randn([4, 16])
        step(x, y)
        before = np.asarray(pipe._stage_layers[3][0].parameters()[0]._value).copy()
        step.sync_to_model()
        after = np.asarray(pipe._stage_layers[3][0].parameters()[0]._value)
        assert not np.allclose(before, after)

    def test_vpp_existing_state_restacks_cpxx(self):
        """Eager-accumulated optimizer state restacks [C, P, ...] (review
        regression: it previously stacked [C*P, ...])."""
        _init(dp=1, pp=2)
        P.seed(29)
        pipe = PipelineLayer(layers=_mlp_descs(8), num_stages=2,
                             num_virtual_pipeline_stages=2,
                             loss_fn=lambda o, y: F.mse_loss(o, y))
        opt = P.optimizer.AdamW(learning_rate=0.01, parameters=pipe.parameters())
        x, y = P.randn([4, 16]), P.randn([4, 16])
        # a few eager 1F1B-engine steps accumulate per-segment state
        for _ in range(2):
            loss = F.mse_loss(pipe.forward(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
        step = CompiledPipelineTrainStep(pipe, opt, num_micro=2)
        accs = opt._accumulators["moment1"]
        stacked_shapes = [tuple(v.shape) for v in accs.values() if np.ndim(v) >= 3]
        assert any(s[:2] == (2, 2) for s in stacked_shapes), stacked_shapes
        l = float(step(x, y).numpy())
        assert np.isfinite(l)
