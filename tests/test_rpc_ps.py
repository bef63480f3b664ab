"""RPC + parameter-server sharded embedding (reference:
python/paddle/distributed/rpc/rpc.py:73, distributed/ps/the_one_ps.py)."""
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh_rpc():
    from paddle_tpu.distributed import rpc

    rpc.shutdown()
    return rpc


def test_rpc_sync_async_in_process():
    rpc = _fresh_rpc()
    rpc.init_rpc("solo", rank=0, world_size=1)
    try:
        import operator

        assert rpc.rpc_sync("solo", operator.add, args=(2, 3)) == 5
        fut = rpc.rpc_async("solo", pow, args=(2, 10))
        assert fut.wait() == 1024
        info = rpc.get_worker_info()
        assert info.name == "solo" and info.rank == 0
        # errors propagate
        with pytest.raises(ZeroDivisionError):
            rpc.rpc_sync("solo", operator.truediv, args=(1, 0))
    finally:
        rpc.shutdown()


SERVER = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, os.environ["PADDLE_TPU_REPO"])
    from paddle_tpu.distributed import rpc, ps
    from paddle_tpu.distributed.launch.master import KVClient

    name = sys.argv[1]
    rank = int(sys.argv[2])
    master = sys.argv[3]
    rpc.init_rpc(name, rank=rank, world_size=3, master_endpoint=master)
    ps.start_server(name, dim=4, initializer="uniform", seed=rank)
    kv = KVClient(master)
    kv.put(f"/ps/ready/{name}", "1")
    while kv.get("/ps/done") is None:
        time.sleep(0.1)
    rpc.shutdown()
""")

TRAINER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["PADDLE_TPU_REPO"])
    import numpy as np
    from paddle_tpu.distributed import rpc, ps
    from paddle_tpu.distributed.launch.master import KVClient

    master = sys.argv[1]
    rpc.init_rpc("trainer", rank=2, world_size=3, master_endpoint=master)
    kv = KVClient(master)
    kv.wait_n("/ps/ready/", 2, timeout=60)

    emb = ps.ShardedEmbedding("emb", dim=4, servers=["server0", "server1"])
    ids = np.array([[0, 1], [5, 0]])
    rows = emb.pull(ids)
    assert rows.shape == (2, 2, 4)
    # same id pulls the same row
    np.testing.assert_allclose(rows[0, 0], rows[1, 1])

    # push a sparse gradient: row 0 appears twice -> both updates apply
    g = np.ones((2, 2, 4), np.float32)
    emb.push(ids, g, lr=0.5)
    rows2 = emb.pull(ids)
    np.testing.assert_allclose(rows2[0, 0], rows[0, 0] - 2 * 0.5, atol=1e-6)
    np.testing.assert_allclose(rows2[0, 1], rows[0, 1] - 0.5, atol=1e-6)
    # rows are hash-sharded across both servers (0 -> s0, 1/5 -> s1)
    sizes = emb.server_sizes()
    assert sizes[0] >= 1 and sizes[1] >= 2, sizes

    kv.put("/ps/done", "1")
    rpc.shutdown()
    print("PS_OK")
""")


def test_sharded_embedding_push_pull_cross_process(tmp_path):
    from paddle_tpu.distributed.launch.master import KVServer

    srv = KVServer(0).start()
    master = f"127.0.0.1:{srv.port}"
    env = dict(os.environ)
    env["PADDLE_TPU_REPO"] = REPO
    sfile = tmp_path / "server.py"
    sfile.write_text(SERVER)
    tfile = tmp_path / "trainer.py"
    tfile.write_text(TRAINER)
    procs = [
        subprocess.Popen([sys.executable, str(sfile), f"server{i}", str(i), master],
                         env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True)
        for i in range(2)
    ]
    try:
        r = subprocess.run([sys.executable, str(tfile), master], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
        assert "PS_OK" in r.stdout
        for p in procs:
            out, err = p.communicate(timeout=30)
            assert p.returncode == 0, err[-1500:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        srv.stop()


def test_table_accessors_match_dense_reference():
    """Adagrad/Adam PS accessors == the dense numpy update (PS was SGD-only)."""
    from paddle_tpu.distributed.ps import Table

    rng = np.random.RandomState(0)
    g1 = rng.randn(4).astype(np.float32)
    g2 = rng.randn(4).astype(np.float32)

    # adagrad
    t = Table("t", 4, accessor="adagrad")
    t.push([7], g1[None], lr=0.1)
    t.push([7], g2[None], lr=0.1)
    acc = g1 * g1
    ref = -0.1 * g1 / (np.sqrt(acc) + 1e-8)
    acc = acc + g2 * g2
    ref = ref - 0.1 * g2 / (np.sqrt(acc) + 1e-8)
    np.testing.assert_allclose(t.pull([7])[0], ref, rtol=1e-6)

    # adam
    t = Table("t", 4, accessor="adam")
    t.push([3], g1[None], lr=0.1)
    m = 0.1 * g1
    v = 0.001 * g1 * g1
    ref = -0.1 * (m / (1 - 0.9)) / (np.sqrt(v / (1 - 0.999)) + 1e-8)
    np.testing.assert_allclose(t.pull([3])[0], ref, rtol=1e-5)


def test_table_entry_admission():
    """CountFilterEntry gates row creation until enough pushes arrive."""
    from paddle_tpu.distributed.entry_attr import CountFilterEntry
    from paddle_tpu.distributed.ps import Table

    t = Table("t", 2, accessor="sgd", entry=CountFilterEntry(3))
    g = np.ones((1, 2), np.float32)
    t.push([5], g, lr=1.0)
    t.push([5], g, lr=1.0)
    assert t.size() == 0  # not admitted yet
    t.push([5], g, lr=1.0)  # third sighting admits the row
    assert t.size() == 1
    np.testing.assert_allclose(t.pull([5])[0], [-1.0, -1.0])


def test_table_save_load_roundtrip(tmp_path):
    from paddle_tpu.distributed.ps import Table

    t = Table("t", 3, accessor="adam")
    t.push([1, 9], np.random.RandomState(1).randn(2, 3).astype(np.float32), lr=0.05)
    t.save(str(tmp_path / "shard0"))
    t2 = Table("t", 3, accessor="adam")
    t2.load(str(tmp_path / "shard0"))
    np.testing.assert_allclose(t2.pull([1, 9]), t.pull([1, 9]))
    # optimizer state survived: identical next update
    g = np.random.RandomState(2).randn(2, 3).astype(np.float32)
    t.push([1, 9], g, lr=0.05)
    t2.push([1, 9], g, lr=0.05)
    np.testing.assert_allclose(t2.pull([1, 9]), t.pull([1, 9]), rtol=1e-6)


def test_geo_sharded_embedding_in_process():
    """Geo-async mode: local cache + delta sync every geo_steps pushes."""
    from paddle_tpu.distributed import rpc
    from paddle_tpu.distributed.ps import GeoShardedEmbedding, start_server
    from paddle_tpu.distributed.ps import _worker

    rpc.init_rpc("geo_solo", rank=0, world_size=1)
    try:
        start_server("geo_solo", dim=2, table_name="geo_emb", initializer="zeros")
        emb = GeoShardedEmbedding("geo_emb", 2, ["geo_solo"], geo_steps=2)
        g = np.ones((1, 2), np.float32)
        emb.pull(np.array([4]))
        emb.push(np.array([4]), g, lr=0.5)       # local only
        # server row untouched until the geo sync fires
        np.testing.assert_allclose(_worker.TABLES["geo_emb"].pull([4])[0], [0.0, 0.0])
        emb.push(np.array([4]), g, lr=0.5)       # second push -> geo sync
        server_row = _worker.TABLES["geo_emb"].pull([4])[0]
        np.testing.assert_allclose(server_row, [-1.0, -1.0])  # both deltas merged
        # cache dropped at sync: next pull refetches the merged row
        np.testing.assert_allclose(emb.pull(np.array([4]))[0], [-1.0, -1.0])
    finally:
        rpc.shutdown()


def test_pull_async_overlaps_and_matches_sync():
    """Trainer-side lookups can overlap the XLA step —
    pull_async prefetches on a background thread and returns the same rows
    the synchronous pull would."""
    from paddle_tpu.distributed import rpc
    from paddle_tpu.distributed.ps import ShardedEmbedding, start_server

    rpc.init_rpc("ps_async_solo", rank=0, world_size=1)
    try:
        start_server("ps_async_solo", dim=4, table_name="aemb", seed=3)
        emb = ShardedEmbedding("aemb", 4, ["ps_async_solo"])
        ids = np.arange(64)
        emb.push(ids, np.random.RandomState(0).randn(64, 4).astype(np.float32),
                 lr=0.1)
        fut = emb.pull_async(ids)  # overlaps "the XLA step" (any host work)
        busy = sum(i * i for i in range(10000))  # stand-in for step dispatch
        rows_async = fut.result(timeout=30)
        rows_sync = emb.pull(ids)
        np.testing.assert_array_equal(rows_async, rows_sync)
        assert busy > 0
        emb.close()
        with pytest.raises(RuntimeError, match="close"):
            emb.pull_async(ids)  # fail-loud after close, no pool resurrection
    finally:
        rpc.shutdown()


def test_ps_pull_push_throughput_recorded():
    """Measure (don't just claim) PS pull/push rates.
    In-process loopback, dim=64: prints rows/s and asserts a generous floor
    so a pathological regression (e.g. per-row RPC) fails loudly."""
    import time as _t

    from paddle_tpu.distributed import rpc
    from paddle_tpu.distributed.ps import ShardedEmbedding, start_server

    rpc.init_rpc("ps_bench_solo", rank=0, world_size=1)
    try:
        start_server("ps_bench_solo", dim=64, table_name="bemb")
        emb = ShardedEmbedding("bemb", 64, ["ps_bench_solo"])
        n = 4096
        ids = np.arange(n)
        g = np.ones((n, 64), np.float32)
        emb.push(ids, g, lr=0.1)  # warm/admit
        t0 = _t.perf_counter()
        for _ in range(3):
            emb.pull(ids)
        pull_rps = 3 * n / (_t.perf_counter() - t0)
        t0 = _t.perf_counter()
        for _ in range(3):
            emb.push(ids, g, lr=0.1)
        push_rps = 3 * n / (_t.perf_counter() - t0)
        print(f"\nps throughput: pull {pull_rps:,.0f} rows/s, "
              f"push {push_rps:,.0f} rows/s (dim=64, loopback)")
        assert pull_rps > 2000 and push_rps > 2000
    finally:
        rpc.shutdown()


def test_pull_does_not_bypass_entry_admission():
    """Reads must not admit rows: the standard pull-then-push flow still
    goes through the entry policy (review regression)."""
    from paddle_tpu.distributed.entry_attr import CountFilterEntry
    from paddle_tpu.distributed.ps import Table

    t = Table("t", 2, accessor="sgd", entry=CountFilterEntry(2))
    g = np.ones((1, 2), np.float32)
    np.testing.assert_allclose(t.pull([5])[0], [0.0, 0.0])  # read-only
    assert t.size() == 0
    t.push([5], g, lr=1.0)   # first sighting: below count filter
    assert t.size() == 0
    t.push([5], g, lr=1.0)   # second sighting admits
    assert t.size() == 1
