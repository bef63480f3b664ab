"""auto-tuner + auto-parallel Engine tests (both were absent)."""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn as nn
from paddle_tpu.distributed.auto_tuner import (
    AutoTuner, GridSearch, MemoryCostModel, Recorder, default_candidates,
    prune_by_memory, prune_by_mp,
)


class TestCandidatesAndPrune:
    def test_default_candidates_divisors(self):
        c = default_candidates({"num_gpus": 8, "global_batch_size": 16})
        assert c["dp_degree"] == [1, 2, 4, 8]
        assert 16 in c["micro_batch_size"]

    def test_grid_only_valid_factorizations(self):
        cfg = {"num_gpus": 8, "candidates": default_candidates({"num_gpus": 8})}
        gs = GridSearch(cfg)
        for c in gs.all:
            assert c["dp_degree"] * c["mp_degree"] * c["pp_degree"] * c["sharding_degree"] == 8

    def test_prune_by_mp_heads(self):
        assert prune_by_mp({"mp_degree": 3}, num_attention_heads=8)
        assert not prune_by_mp({"mp_degree": 4}, num_attention_heads=8)
        assert prune_by_mp({"mp_degree": 16}, vocab_size=1000, num_attention_heads=16)

    def test_memory_model_monotone(self):
        m = MemoryCostModel(n_params=7e9, hidden=4096, layers=32, seq_len=2048)
        base = {"dp_degree": 8, "mp_degree": 1, "pp_degree": 1, "sharding_degree": 1,
                "sharding_stage": 1, "micro_batch_size": 1, "use_recompute": False}
        sharded = dict(base, mp_degree=8, dp_degree=1)
        assert m.estimate(sharded) < m.estimate(base)
        stage3 = dict(base, sharding_degree=8, dp_degree=1, sharding_stage=3)
        assert m.estimate(stage3) < m.estimate(base)
        # 7B unsharded blows a 16GB chip; stage-3 fits in aggregate
        assert prune_by_memory(base, m, 16e9)

    def test_recorder_best(self):
        r = Recorder()
        r.add({"a": 1}, 10.0)
        r.add({"a": 2}, 30.0)
        r.add({"a": 3}, None, error="oom")
        assert r.best()["cfg"]["a"] == 2
        assert len(r.sort()) == 2


class TestAutoTuner:
    def test_tune_picks_fastest(self):
        tuner = AutoTuner({
            "num_gpus": 8,
            "global_batch_size": 8,
            "micro_batch_size": [1],
            "pp_degree": [1],
            "sharding_degree": [1],
            "num_attention_heads": 8,
            "memory_model": MemoryCostModel(n_params=1e8, hidden=512, layers=4, seq_len=128),
            "hbm_bytes": 16e9,
        })

        def run_fn(cfg):
            # pretend pure-dp is fastest
            return 100.0 if cfg["mp_degree"] == 1 else 50.0

        best = tuner.tune(run_fn)
        assert best is not None
        assert best["cfg"]["mp_degree"] == 1
        assert best["throughput"] == 100.0

    def test_failed_candidates_recorded(self):
        tuner = AutoTuner({"num_gpus": 2, "global_batch_size": 2,
                           "micro_batch_size": [1], "pp_degree": [1],
                           "sharding_degree": [1]})

        def run_fn(cfg):
            if cfg["mp_degree"] == 2:
                raise RuntimeError("boom")
            return 1.0

        best = tuner.tune(run_fn)
        errs = [h for h in tuner.recorder.history if h["error"]]
        assert best["cfg"]["mp_degree"] == 1
        assert any("boom" in h["error"] for h in errs)


class TestStepCostModel:
    """Cost-model pruning beyond HBM — compute/comm/
    bubble estimates rank candidates and prune the clearly-bad tail."""

    def _model(self):
        from paddle_tpu.distributed.auto_tuner import StepCostModel

        return StepCostModel(n_params=1e9, hidden=2048, layers=16,
                             seq_len=1024, global_batch_size=8,
                             flops_per_chip=100e12, ici_bw=4e10)

    def test_cost_monotonicity(self):
        m = self._model()
        dp8 = {"dp_degree": 8, "mp_degree": 1, "pp_degree": 1,
               "sharding_degree": 1, "micro_batch_size": 1}
        pp8 = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 8,
               "sharding_degree": 1, "micro_batch_size": 1}
        mp8 = {"dp_degree": 1, "mp_degree": 8, "pp_degree": 1,
               "sharding_degree": 1, "micro_batch_size": 1}
        mp4 = {"dp_degree": 2, "mp_degree": 4, "pp_degree": 1,
               "sharding_degree": 1, "micro_batch_size": 1}
        # deeper TP = more per-layer activation all-reduces
        assert m.estimate(mp8) > m.estimate(mp4)
        # pipeline bubble shrinks as microbatch count grows: 8x the tokens
        # must cost LESS than 8x the pp8 step ((M+P-1)/M drops 15/8 -> 71/64)
        m2 = self._model()
        m2.gb = 64
        assert m2.estimate(pp8) < 8 * m.estimate(pp8) * 0.7
        # recompute pays the extra forward
        assert m.estimate(dict(dp8, use_recompute=True)) > m.estimate(dp8)
        # sharding stage 3 pays the per-microbatch param all-gather
        s1 = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
              "sharding_degree": 8, "sharding_stage": 1,
              "micro_batch_size": 1}
        assert m.estimate(dict(s1, sharding_stage=3)) > m.estimate(s1)
        # dp grad-sync cost scales with model size
        big = self._model()
        big.n_params = 1e10
        assert big.estimate(dp8) > m.estimate(dp8)

    def test_interleaved_vpp_bubble_term(self):
        """r6: the bubble term knows the interleaved-VPP schedule — with C
        chunks and M % P == 0 (when the compiled engine auto-selects
        interleaving) the bubble is (P-1)/C, not (P-1)."""
        m = self._model()
        m.gb = 8  # M = 8 microbatches at mbs=1, dp=sh=1
        pp8 = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 8,
               "sharding_degree": 1, "micro_batch_size": 1}
        base = m.estimate(pp8)
        il = m.estimate(dict(pp8, vpp_degree=2))
        # exact bubble ratio on the pure-compute config:
        # (M + (P-1)/C) / (M + P-1)
        assert il < base
        assert il / base == pytest.approx((8 + 7 / 2) / (8 + 7), rel=1e-9)
        # M % P != 0 -> interleaved feed cannot tile; no discount
        m2 = self._model()
        m2.gb = 12  # 12 % 8 != 0
        assert (m2.estimate(dict(pp8, vpp_degree=2))
                == m2.estimate(pp8))
        # deeper chunking shrinks the bubble further
        assert (m.estimate(dict(pp8, vpp_degree=4))
                < m.estimate(dict(pp8, vpp_degree=2)))

    def test_cost_model_search_order_and_prune(self):
        from paddle_tpu.distributed.auto_tuner import AutoTuner

        m = self._model()
        tuner = AutoTuner({
            "num_gpus": 8, "global_batch_size": 8, "micro_batch_size": [1],
            "sharding_degree": [1], "search_algo": "cost_model",
            "cost_model": m, "cost_prune_ratio": 1.3,
        })
        # candidates come out cheapest-estimate first
        ests = [m.estimate(c) for c in tuner.algo.all]
        assert ests == sorted(ests)

        measured = []

        def run_fn(cfg):
            measured.append(cfg)
            return 1.0 / m.estimate(cfg)

        best = tuner.tune(run_fn)
        pruned = [h for h in tuner.recorder.history
                  if h["error"] and "cost model" in h["error"]]
        assert pruned, "bad tail should be cost-pruned before measurement"
        pruned_cfgs = [h["cfg"] for h in pruned]
        assert all(c not in pruned_cfgs for c in measured)
        # winner sits inside the cost-plausible region, nothing pruned was
        # measured, and the estimated-worst candidate never ran
        best_est = min(m.estimate(c) for c in tuner.algo.all)
        assert m.estimate(best["cfg"]) <= 1.3 * best_est
        worst = max(tuner.algo.all, key=m.estimate)
        assert worst in pruned_cfgs

    def test_tuner_ranks_bad_below_good_on_cpu_mesh(self):
        """Measured (not modeled) ranking on the virtual 8-device mesh: the
        tuner must rank a known-bad hybrid config (pp=8, 1 microbatch —
        maximal bubble + per-stage dispatch) below the known-good pure-dp
        GSPMD config for a tiny llama step."""
        import time as _t

        import paddle_tpu as P
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.auto_tuner import AutoTuner
        from paddle_tpu.models import LlamaForCausalLM, LlamaPretrainingCriterion, llama_tiny

        crit = LlamaPretrainingCriterion()

        def run_fn(cfg):
            from paddle_tpu.distributed.topology import set_hybrid_communicate_group

            set_hybrid_communicate_group(None)
            s = dist.fleet.DistributedStrategy()
            s.hybrid_configs = {
                "dp_degree": cfg["dp_degree"], "mp_degree": cfg["mp_degree"],
                "pp_degree": cfg["pp_degree"],
                "sharding_degree": cfg["sharding_degree"], "sep_degree": 1}
            if cfg["pp_degree"] > 1:
                s.pipeline_configs = {"accumulate_steps": 4,
                                      "schedule_mode": "1F1B"}
            dist.fleet.init(is_collective=True, strategy=s)
            P.seed(0)
            ids = P.to_tensor(np.random.RandomState(0).randint(
                0, 512, (8, 32)).astype(np.int32))
            if cfg["pp_degree"] > 1:
                # the config really runs as a pipeline: 2-layer tiny llama
                # over 8 stages can't even be segmented -> the tuner records
                # the failure; with fewer stages it pays the eager per-op
                # schedule. Either way it ranks below the compiled dp step.
                from paddle_tpu.distributed.fleet.meta_parallel import PipelineLayer
                from paddle_tpu.models import llama_pipeline_descs

                pipe = PipelineLayer(layers=llama_pipeline_descs(llama_tiny()),
                                     num_stages=cfg["pp_degree"],
                                     loss_fn=lambda lo, la: crit(lo, la))
                model = dist.fleet.distributed_model(pipe)
                opt = P.optimizer.AdamW(learning_rate=1e-4,
                                        parameters=model.parameters())
                model.train_batch([ids, ids], opt)  # warm
                t0 = _t.perf_counter()
                for _ in range(3):
                    loss = model.train_batch([ids, ids], opt)
                float(loss.numpy())
                return 3.0 / (_t.perf_counter() - t0)
            model = dist.fleet.distributed_model(LlamaForCausalLM(llama_tiny()))
            opt = P.optimizer.AdamW(learning_rate=1e-4,
                                    parameters=model.parameters())
            step = P.jit.TrainStep(model, lambda mm, i: crit(mm(i), i), opt)
            float(step(ids).numpy())  # compile
            t0 = _t.perf_counter()
            for _ in range(3):
                loss = step(ids)
            float(loss.numpy())
            return 3.0 / (_t.perf_counter() - t0)  # steps/s

        tuner = AutoTuner({
            "num_gpus": 8, "global_batch_size": 8, "micro_batch_size": [1],
            "dp_degree": [8, 2], "mp_degree": [1], "pp_degree": [1, 4],
            "sharding_degree": [1], "num_attention_heads": 4,
        })
        best = tuner.tune(run_fn)
        ranked = tuner.recorder.sort()
        assert len(ranked) == 2
        assert best["cfg"]["dp_degree"] == 8 and best["cfg"]["pp_degree"] == 1
        assert ranked[-1]["cfg"]["pp_degree"] == 4  # known-bad ranked last


class _XY:
    def __init__(self, n=32):
        rs = np.random.RandomState(0)
        self.x = rs.randn(n, 8).astype(np.float32)
        self.y = (self.x[:, :1] * 1.5).astype(np.float32)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


class TestEngine:
    def test_fit_evaluate_predict(self):
        from paddle_tpu.distributed.auto_parallel import Engine, Strategy

        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
        opt = P.optimizer.Adam(parameters=model.parameters(), learning_rate=0.01)
        strat = Strategy()
        strat.dp_degree = 2
        strat.mp_degree = 2
        strat.sharding_degree = 2
        eng = Engine(model=model,
                     loss=lambda out, y: P.mean((out - y) ** 2),
                     optimizer=opt, strategy=strat)
        eng.prepare()
        hist = eng.fit(_XY(), batch_size=8, epochs=6)
        assert hist["loss"][-1] < hist["loss"][0]
        res = eng.evaluate(_XY(), batch_size=8)
        assert res["loss"] < hist["loss"][0]
        preds = eng.predict(_XY(), batch_size=8)
        assert len(preds) == 4

    def test_save_load_roundtrip(self, tmp_path):
        import os

        from paddle_tpu.distributed.auto_parallel import Engine

        model = nn.Linear(4, 2)
        opt = P.optimizer.SGD(parameters=model.parameters())
        eng = Engine(model=model, loss=lambda o, y: P.mean((o - y) ** 2), optimizer=opt)
        eng.prepare()
        path = os.path.join(str(tmp_path), "ckpt")
        eng.save(path)
        w0 = np.asarray(model.weight._value).copy()
        model.weight.set_value(np.zeros_like(w0))
        eng.load(path)
        np.testing.assert_allclose(np.asarray(model.weight._value), w0)

    def test_strategy_rejects_oversubscription(self):
        from paddle_tpu.distributed.auto_parallel import Engine, Strategy

        s = Strategy()
        s.dp_degree = 64
        eng = Engine(model=nn.Linear(2, 2), strategy=s)
        with pytest.raises(ValueError, match="devices"):
            eng.prepare()
