"""Multi-controller compiled execution: 2 OS processes
x 4 virtual CPU devices cooperate in ONE compiled program, launched through
the repo's own launcher (reference analog:
test/legacy_test/test_parallel_dygraph_dataparallel.py:30 — N local
processes over NCCL).

Worker: tests/workers/multiproc_train_worker.py. Phases:
- train: GSPMD TrainStep over the 8-device global mesh (dp spans the
  process boundary, mp inside each host), per-host batch shards via
  make_array_from_process_local_data, distributed checkpoint where each
  host writes its own shard file, resume into a fresh model.
- pp: CompiledPipelineTrainStep with stage 0 on process 0's devices and
  stage 1 on process 1's — a pipeline crossing the host boundary.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "workers", "multiproc_train_worker.py")


def _launch(tmp_path, phase):
    env = dict(os.environ)
    env["PADDLE_TPU_REPO"] = REPO
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(tmp_path / "logs"),
         WORKER, str(tmp_path), phase],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=540)
    if r.returncode != 0:
        logs = ""
        logdir = tmp_path / "logs"
        if logdir.exists():
            for f in sorted(logdir.iterdir()):
                logs += f"\n--- {f.name} ---\n" + f.read_text()[-3000:]
        raise AssertionError(f"launch failed rc={r.returncode}\n{r.stderr[-2000:]}{logs}")


class TestMultiProcess:
    def test_two_process_gspmd_train_and_checkpoint_resume(self, tmp_path):
        _launch(tmp_path, "train")
        res = [json.load(open(tmp_path / f"result_{r}.json")) for r in (0, 1)]
        # both controllers observed the SAME global computation
        assert res[0]["losses_a"] == res[1]["losses_a"]
        assert res[0]["losses_b"] == res[1]["losses_b"]
        losses = res[0]["losses_a"] + res[0]["losses_b"]
        assert all(np.isfinite(losses))
        # each host wrote its own checkpoint shard
        assert {"shard_0.npz", "shard_1.npz"} <= set(res[0]["shard_file"])
        # all_gather_object crossed the process boundary (r5: was unwired)
        for r in res:
            assert r["gathered_objs"] == [{"rank": 0, "tag": "host0"},
                                          {"rank": 1, "tag": "host1"}]
        # resume from the per-host shards continues the run (tolerance: the
        # recompiled step may pick a different-but-equivalent GSPMD layout,
        # so reductions can differ by ulps)
        np.testing.assert_allclose(res[0]["losses_resume"],
                                   res[0]["losses_b"], rtol=2e-4)

    @pytest.mark.skipif(
        not hasattr(__import__("jax"), "shard_map"),
        reason="compiled pipeline with size>1 auto axes (mp=4 here) needs "
               "jax.shard_map (>=0.8); old jax aborts the SPMD partitioner")
    def test_two_process_compiled_pipeline_across_hosts(self, tmp_path):
        _launch(tmp_path, "pp")
        res = [json.load(open(tmp_path / f"pp_result_{r}.json"))
               for r in (0, 1)]
        assert res[0]["pp_losses"] == res[1]["pp_losses"]
        ls = res[0]["pp_losses"]
        assert len(ls) == 3 and all(np.isfinite(ls))
        assert ls[-1] < ls[0]  # trains across the host boundary
