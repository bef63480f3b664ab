"""Parameter server — CPU-host sharded embedding tables with sparse
push/pull (minimal capability analog of
/root/reference/python/paddle/distributed/ps/the_one_ps.py +
paddle/fluid/distributed/ps/ sharded tables).

TPU-native stance: the PS pattern exists for sparse-recsys workloads whose
embedding tables exceed accelerator memory. Here the tables live in HOST
numpy memory, sharded row-wise across server workers (row r lives on server
r % num_servers — the reference's hash sharding); trainers ``pull`` the rows
a batch touches and ``push`` sparse gradients back (async SGD, the
reference's default mode). Transport is paddle_tpu.distributed.rpc; the
dense model path stays on the XLA side entirely.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import _worker
from .. import rpc as _rpc

__all__ = ["SparseTable", "ShardedEmbedding", "GeoShardedEmbedding",
           "start_server", "Table"]


class Table:
    """One server's shard of a row-sharded table (host memory).

    ``accessor`` selects the per-row sparse optimizer (reference: the PS
    table accessor variants, ps/table/ctr_*accessor + the_one_ps.py):
    'sgd' | 'adagrad' (per-row G2 accumulator) | 'adam' (per-row moments +
    step count). An admission ``entry`` policy
    (paddle_tpu.distributed.entry_attr) gates row creation on push counts —
    the reference's probability/count-filter entries.
    """

    def __init__(self, name: str, dim: int, initializer="zeros", seed: int = 0,
                 accessor: str = "sgd", entry=None,
                 beta1: float = 0.9, beta2: float = 0.999, eps: float = 1e-8):
        self.name = name
        self.dim = dim
        self.rows: Dict[int, np.ndarray] = {}
        self._state: Dict[int, dict] = {}  # accessor state per row
        self._push_counts: Dict[int, int] = {}
        self._init = initializer
        self._seed = seed
        self.accessor = accessor
        self.entry = entry
        self._b1, self._b2, self._eps = beta1, beta2, eps
        self._lock = threading.Lock()

    def _init_row(self, rid: int) -> np.ndarray:
        if self._init == "zeros":
            return np.zeros(self.dim, np.float32)
        # deterministic per-row init (reference: uniform fill)
        rng = np.random.RandomState((self._seed * 1000003 + rid) % (2**31))
        return (rng.rand(self.dim).astype(np.float32) - 0.5) * 0.02

    def _row(self, rid: int) -> np.ndarray:
        row = self.rows.get(rid)
        if row is None:
            row = self.rows[rid] = self._init_row(rid)
        return row

    def pull(self, ids: Sequence[int]) -> np.ndarray:
        """Reads never ADMIT a row: un-admitted ids return their
        deterministic init value without persisting, so the entry policy
        still gates the pull-then-push training flow."""
        with self._lock:
            return np.stack([
                self.rows[i] if (i := int(raw)) in self.rows else self._init_row(i)
                for raw in ids])

    def _apply(self, rid: int, g: np.ndarray, lr: float):
        row = self._row(rid)
        if self.accessor == "adagrad":
            st = self._state.setdefault(rid, {"g2": np.zeros(self.dim, np.float32)})
            st["g2"] += g * g
            row -= lr * g / (np.sqrt(st["g2"]) + self._eps)
        elif self.accessor == "adam":
            st = self._state.setdefault(rid, {
                "m": np.zeros(self.dim, np.float32),
                "v": np.zeros(self.dim, np.float32), "t": 0})
            st["t"] += 1
            st["m"] = self._b1 * st["m"] + (1 - self._b1) * g
            st["v"] = self._b2 * st["v"] + (1 - self._b2) * g * g
            mhat = st["m"] / (1 - self._b1 ** st["t"])
            vhat = st["v"] / (1 - self._b2 ** st["t"])
            row -= lr * mhat / (np.sqrt(vhat) + self._eps)
        else:  # sgd
            row -= lr * g

    def push(self, ids: Sequence[int], grads: np.ndarray, lr: float):
        """Sparse update via the table accessor (async-mode: on arrival)."""
        with self._lock:
            for i, g in zip(ids, np.asarray(grads, np.float32)):
                rid = int(i)
                if self.entry is not None and rid not in self.rows:
                    if getattr(self.entry, "one_shot", False):
                        # rid-keyed draw: rejection is permanent, keep no
                        # per-feature count state for dropped rows
                        if not self.entry.admit(1, rid=rid):
                            continue
                    else:
                        n = self._push_counts.get(rid, 0) + 1
                        self._push_counts[rid] = n
                        if not self.entry.admit(n, rid=rid):
                            continue  # not admitted yet: drop the update
                        self._push_counts.pop(rid, None)
                self._apply(rid, g, lr)

    def push_delta(self, ids: Sequence[int], deltas: np.ndarray):
        """Geo-async merge: add trainer-accumulated deltas directly
        (reference geo-SGD mode — the trainer optimized locally)."""
        with self._lock:
            for i, d in zip(ids, np.asarray(deltas, np.float32)):
                self._row(int(i))[:] += d

    def size(self) -> int:
        return len(self.rows)

    # ---------------------------------------------------------- persistence
    def save(self, path: str):
        """Write rows + accessor state (reference: table save_persistables).
        Locked: the RPC server is multithreaded and pushes may be in flight."""
        with self._lock:
            self._save_locked(path)

    def _save_locked(self, path: str):
        ids = sorted(self.rows)
        arrays = {"ids": np.asarray(ids, np.int64),
                  "rows": (np.stack([self.rows[i] for i in ids])
                           if ids else np.zeros((0, self.dim), np.float32))}
        if self.accessor == "adagrad" and ids:
            arrays["g2"] = np.stack([
                self._state.get(i, {}).get("g2", np.zeros(self.dim, np.float32))
                for i in ids])
        elif self.accessor == "adam" and ids:
            z = np.zeros(self.dim, np.float32)
            arrays["m"] = np.stack([self._state.get(i, {}).get("m", z) for i in ids])
            arrays["v"] = np.stack([self._state.get(i, {}).get("v", z) for i in ids])
            arrays["t"] = np.asarray([self._state.get(i, {}).get("t", 0) for i in ids])
        np.savez(path, **arrays)

    def load(self, path: str):
        with self._lock:
            self._load_locked(path)

    def _load_locked(self, path: str):
        data = np.load(path if path.endswith(".npz") else path + ".npz")
        self.rows = {int(i): data["rows"][k].copy()
                     for k, i in enumerate(data["ids"])}
        self._state = {}
        if "g2" in data:
            for k, i in enumerate(data["ids"]):
                self._state[int(i)] = {"g2": data["g2"][k].copy()}
        elif "m" in data:
            for k, i in enumerate(data["ids"]):
                self._state[int(i)] = {"m": data["m"][k].copy(),
                                       "v": data["v"][k].copy(),
                                       "t": int(data["t"][k])}


def start_server(name: str, dim: int, table_name: str = "emb",
                 initializer: str = "uniform", seed: int = 0,
                 accessor: str = "sgd", entry=None) -> str:
    """Register a table on THIS rpc worker (call after init_rpc)."""
    _worker.TABLES[table_name] = Table(table_name, dim, initializer, seed,
                                       accessor=accessor, entry=entry)
    return table_name


class ShardedEmbedding:
    """Trainer-side handle: pull/push rows sharded over the server workers.

    Row r is owned by servers[r % S] (the reference's hash-sharded table
    accessor)."""

    def __init__(self, table_name: str, dim: int, servers: List[str]):
        self.table_name = table_name
        self.dim = dim
        self.servers = list(servers)
        self._pool_lock = threading.Lock()
        self._prefetch_pool = None  # built lazily by pull_async
        self._prefetch_closed = False

    def _shard(self, ids: np.ndarray):
        ids = np.asarray(ids).reshape(-1).astype(np.int64)
        owner = ids % len(self.servers)
        return ids, owner

    def pull(self, ids) -> np.ndarray:
        """Gather rows for ``ids`` (any shape) -> [*ids.shape, dim]."""
        arr = np.asarray(ids)
        flat, owner = self._shard(arr)
        out = np.zeros((flat.size, self.dim), np.float32)
        futs = []
        for s, server in enumerate(self.servers):
            mask = owner == s
            if not mask.any():
                continue
            futs.append((mask, _rpc.rpc_async(
                server, _worker.table_pull,
                args=(self.table_name, flat[mask].tolist()))))
        for mask, f in futs:
            out[mask] = f.result()
        return out.reshape(*arr.shape, self.dim)

    def push(self, ids, grads, lr: float = 0.01):
        """Scatter sparse gradients back (rows repeated in ids accumulate)."""
        arr = np.asarray(ids)
        flat, owner = self._shard(arr)
        g = np.asarray(grads, np.float32).reshape(flat.size, self.dim)
        futs = []
        for s, server in enumerate(self.servers):
            mask = owner == s
            if not mask.any():
                continue
            futs.append(_rpc.rpc_async(
                server, _worker.table_push,
                args=(self.table_name, flat[mask].tolist(), g[mask], lr)))
        for f in futs:
            f.result()

    def server_sizes(self) -> List[int]:
        return [_rpc.rpc_sync(s, _worker.table_size, args=(self.table_name,))
                for s in self.servers]

    def pull_async(self, ids):
        """Prefetch rows on a background thread so the trainer overlaps the
        sparse lookup with the XLA step (trainer-side lookups
        didn't overlap). Returns a future; ``.result()`` gives the same
        array ``pull`` would. Call :meth:`close` (or drain futures) before
        ``rpc.shutdown()`` so in-flight prefetches don't race teardown."""
        with self._pool_lock:
            if self._prefetch_closed:
                raise RuntimeError(
                    "pull_async after close(): the prefetch pool is shut down")
            if self._prefetch_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._prefetch_pool = ThreadPoolExecutor(
                    max_workers=2, thread_name_prefix="ps-prefetch")
        ids = np.asarray(ids).copy()  # caller may mutate its buffer
        return self._prefetch_pool.submit(self.pull, ids)

    def close(self):
        """Drain and stop the prefetch pool; later pull_async calls raise."""
        with self._pool_lock:
            self._prefetch_closed = True
            if self._prefetch_pool is not None:
                self._prefetch_pool.shutdown(wait=True)
                self._prefetch_pool = None


    # ---------------------------------------------------------- persistence
    def save(self, dirname: str):
        """Each server shard writes its rows+state (reference:
        the_one_ps save mode) to <dirname>/<table>.shard<k>.npz."""
        import os

        os.makedirs(dirname, exist_ok=True)
        for k, server in enumerate(self.servers):
            _rpc.rpc_sync(server, _worker.table_save, args=(
                self.table_name,
                os.path.join(dirname, f"{self.table_name}.shard{k}.npz")))

    def load(self, dirname: str):
        import os

        for k, server in enumerate(self.servers):
            _rpc.rpc_sync(server, _worker.table_load, args=(
                self.table_name,
                os.path.join(dirname, f"{self.table_name}.shard{k}.npz")))


class GeoShardedEmbedding(ShardedEmbedding):
    """Geo-async mode (reference: geo-SGD, the_one_ps GeoStrategy): the
    trainer keeps a LOCAL cache of the rows it touches, optimizes them
    locally every step, and only every ``geo_steps`` steps ships the
    ACCUMULATED deltas to the servers and refreshes its cache — trading
    staleness for far fewer RPC round-trips (the reference's WAN-friendly
    mode)."""

    def __init__(self, table_name: str, dim: int, servers: List[str],
                 geo_steps: int = 8):
        super().__init__(table_name, dim, servers)
        self.geo_steps = geo_steps
        self._cache: Dict[int, np.ndarray] = {}
        self._delta: Dict[int, np.ndarray] = {}
        self._step = 0

    def pull_async(self, ids):
        """Geo mode keeps an UNSYNCHRONIZED local cache that push/geo_sync
        mutate, so a background prefetch would race the trainer thread —
        resolve synchronously instead (same future-shaped contract)."""
        from concurrent.futures import Future

        fut = Future()
        try:
            fut.set_result(self.pull(ids))
        except Exception as e:  # match executor semantics
            fut.set_exception(e)
        return fut

    def pull(self, ids) -> np.ndarray:
        arr = np.asarray(ids)
        flat = arr.reshape(-1).astype(np.int64)
        missing = [int(i) for i in set(flat.tolist()) if int(i) not in self._cache]
        if missing:
            rows = super().pull(np.asarray(missing))
            for i, r in zip(missing, rows):
                self._cache[i] = r.copy()
        out = np.stack([self._cache[int(i)] for i in flat])
        return out.reshape(*arr.shape, self.dim)

    def push(self, ids, grads, lr: float = 0.01):
        """Local SGD on the cache; deltas accumulate until the geo sync."""
        arr = np.asarray(ids)
        flat = arr.reshape(-1).astype(np.int64)
        # never-pulled rows must seed from the SERVER row (it may carry a
        # nonzero initializer or other trainers' merged deltas)
        self.pull(np.asarray(sorted({int(i) for i in flat})))
        g = np.asarray(grads, np.float32).reshape(flat.size, self.dim)
        for i, gi in zip(flat, g):
            i = int(i)
            upd = -lr * gi
            self._cache[i] = self._cache[i] + upd
            self._delta[i] = self._delta.get(i, np.zeros(self.dim, np.float32)) + upd
        self._step += 1
        if self._step % self.geo_steps == 0:
            self.geo_sync()

    def geo_sync(self):
        """Ship accumulated deltas; drop the cache so fresh rows (with other
        trainers' merged deltas) are pulled on next touch."""
        if self._delta:
            ids = np.asarray(sorted(self._delta), np.int64)
            deltas = np.stack([self._delta[int(i)] for i in ids])
            flat, owner = self._shard(ids)
            for sidx, server in enumerate(self.servers):
                mask = owner == sidx
                if mask.any():
                    _rpc.rpc_sync(server, _worker.table_push_delta,
                                  args=(self.table_name, flat[mask].tolist(),
                                        deltas[mask]))
        self._delta.clear()
        self._cache.clear()


# reference-compatible alias
SparseTable = ShardedEmbedding
