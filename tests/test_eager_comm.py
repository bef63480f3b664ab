"""Eager cross-process collectives + p2p.

Two real processes on CPU, launched through the paddle_tpu launcher, bring up
the jax distributed runtime via init_parallel_env and exchange actual tensor
data: send/recv (ppermute over the process mesh), all_reduce, reduce(dst),
broadcast. Reference: paddle/phi/core/distributed/collective/process_group.h:48.
"""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, os.environ["PADDLE_TPU_REPO"])
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)
    import numpy as np
    import paddle_tpu as P
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    rank = dist.get_rank()
    assert dist.get_world_size() == 2
    res = {}

    # ---- p2p: rank 0 -> rank 1
    if rank == 0:
        dist.send(P.to_tensor(np.arange(6, dtype=np.float32) * 3), dst=1)
    else:
        buf = P.zeros([6], dtype="float32")
        dist.recv(buf, src=0)
        res["recv"] = buf.numpy().tolist()

    # ---- all_reduce: sum of (rank+1)
    t = P.to_tensor(np.full((4,), float(rank + 1), np.float32))
    dist.all_reduce(t)
    res["all_reduce"] = t.numpy().tolist()

    # ---- reduce to dst=1: rank 0 keeps its input
    r = P.to_tensor(np.full((3,), float(rank + 1), np.float32))
    dist.reduce(r, dst=1)
    res["reduce"] = r.numpy().tolist()

    # ---- broadcast from 0
    b = P.to_tensor(np.full((2,), float(rank * 7 + 5), np.float32))
    dist.broadcast(b, src=0)
    res["broadcast"] = b.numpy().tolist()

    out_dir = sys.argv[1]
    json.dump(res, open(os.path.join(out_dir, f"res_{rank}.json"), "w"))
""")


def test_two_process_eager_comm(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = dict(os.environ)
    env["PADDLE_TPU_REPO"] = REPO
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(tmp_path / "logs"),
         str(script), str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=240, env=env,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    r0 = json.load(open(tmp_path / "res_0.json"))
    r1 = json.load(open(tmp_path / "res_1.json"))
    # p2p delivered real data across the process boundary
    assert r1["recv"] == [0.0, 3.0, 6.0, 9.0, 12.0, 15.0]
    # all_reduce: 1 + 2
    assert r0["all_reduce"] == [3.0] * 4
    assert r1["all_reduce"] == [3.0] * 4
    # reduce(dst=1): rank 0 keeps its input, rank 1 holds the sum
    assert r0["reduce"] == [1.0] * 3
    assert r1["reduce"] == [3.0] * 3
    # broadcast from rank 0
    assert r0["broadcast"] == [5.0] * 2
    assert r1["broadcast"] == [5.0] * 2
