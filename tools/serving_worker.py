#!/usr/bin/env python
"""Remote serving replica worker: one ServingEngine in its own process,
driven over RPC by a ServingFleet frontend (possibly on another host).

Boot sequence: pin the platform (CI/fleet default: ``--platform cpu``,
same contract as the standalone-serving test subprocesses), build the
seeded model + engine from ``--spec-json``, install them as this process's
served replica
(``fleet.init_worker``), register with the launch KV master via
``rpc.init_rpc``, then park until the frontend's ``_w_shutdown`` RPC (or
SIGTERM).  All serving traffic — add_request / step / evict / health —
arrives as RPC calls into ``paddle_tpu.inference.fleet``'s ``_w_*``
handlers; this file is only the bootstrap.  One ``_w_step`` RPC drives
one engine step — which, with megastep decode (ISSUE 9), returns up to
``megastep_k`` tokens per running sequence per round trip.

The worker deliberately OUTLIVES its frontend (ISSUE 11): it parks on
the stop event, not on the frontend's liveness, so a crashed frontend
leaves the worker registered and serving-ready.  The recovered frontend
reattaches (``fleet.discover_workers``/``connect_workers`` +
``RemoteReplica``), calls the ``_w_reap_orphans`` handler to evict the
dead frontend's sequences (publishing their KV blocks into the prefix
cache), and re-admits from its write-ahead journal.

Because frontends come and go across one worker life, every control RPC
handler is EPOCH-FENCED (ISSUE 12): ``fleet.init_worker`` arms an
``EpochFence`` that remembers the highest frontend epoch this process
has ever seen, and a call carrying an older epoch — a zombie frontend
resumed after its lease expired and a standby took over — raises the
typed ``StaleEpoch`` instead of touching the engine.  The fence lives
in worker-process memory, which is exactly the failure domain it
protects: it dies only when the worker does, and a restarted worker is
re-fenced by the current frontend's first RPC.  ``_w_shutdown`` is
fenced too (a deposed frontend cannot shut down the new incarnation's
fleet), but SIGTERM still works for operators.

Spec JSON (everything the worker needs to be a bit-identical replica):

    {"seed": 11,
     "model": {"vocab_size": 256, "hidden_size": 64, ...},   # LlamaConfig
     "engine": {"max_batch_size": 2, "max_seq_len": 64, ...},
     "bfloat16": false,
     "role": "prefill",    # optional disaggregation label (or "decode")
     "wire": true}         # optional binary KV data-plane listener
                           # (ISSUE 20): the port rides the launch-KV
                           # registration (/serving/wire/<name>) + every
                           # health reply, next to the role label

Every ``ServingEngine`` kwarg rides ``"engine"`` verbatim — including
the speculative-decoding tier (ISSUE 19): ``{"engine": {"spec_k": 4}}``
arms n-gram draft + multi-token verify on this replica, and
``"prefill_chunk_tokens"`` sets the mixed-phase chunk size (ISSUE 16/19).
Spec-on workers stay token-identical to spec-off ones, so a fleet may
mix them freely; the worker's ``spec_*`` counters fold through
``_w_step`` deltas like the megastep counters.

On a chip host a worker without ``--platform cpu`` claims the host's chips
for itself: a chip belongs to one process at a time, so such workers need
one chip each (and a launcher that has not touched JAX), and a second one
on the same chips fails or hangs.  One process can instead drive several
replicas: ``ServingFrontend([engine, engine, ...])``.

Run standalone (an operator adding capacity from another host):

    python tools/serving_worker.py --master 10.0.0.1:8765 \
        --name worker7 --spec-json "$(cat spec.json)" --platform cpu
"""
import argparse
import json
import os
import signal
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--master", required=True,
                    help="KV master endpoint ip:port (launch KVServer)")
    ap.add_argument("--name", required=True, help="unique worker name")
    ap.add_argument("--spec-json", required=True,
                    help="model/engine spec as inline JSON, or @/path/to.json")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--platform", default=None, choices=(None, "cpu"),
                    help="'cpu' pins JAX_PLATFORMS=cpu (CI / fleet default "
                         "via ServingFleet(cpu_workers=True)); omit to "
                         "inherit the host's jax config")
    ap.add_argument("--warm", action="store_true",
                    help="warm-pool boot (ISSUE 18): pre-compile the "
                         "step/megastep programs with a throwaway request "
                         "BEFORE registering, then park behind a "
                         "/serving/warm/<name> KV marker until a fleet "
                         "claims this worker — scale-up becomes a health "
                         "probe instead of a ~10 s boot")
    args = ap.parse_args(argv)

    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)

    spec = args.spec_json
    if spec.startswith("@"):
        with open(spec[1:]) as f:
            spec = f.read()
    spec = json.loads(spec)

    import paddle_tpu as P
    from paddle_tpu.distributed import rpc
    from paddle_tpu.inference import ServingEngine, fleet
    from paddle_tpu.inference.faults import FaultInjector
    from paddle_tpu.jit import use_compile_cache
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    use_compile_cache()
    P.seed(int(spec.get("seed", 0)))
    model = LlamaForCausalLM(LlamaConfig(**spec.get("model", {})))
    if spec.get("bfloat16"):
        model.bfloat16()
    model.eval()
    # chaos runs arm worker-side failpoints through the spec (the fleet
    # ships the same JSON to every worker, so a fault schedule is part of
    # the replica recipe): {"faults": {"seed": 7, "sites": {...}}}
    faults = spec.get("faults")
    # "replica_namespaces" rides the spec exactly like the env JSON's
    # (FaultInjector.from_env): without it, replica-scoped sites
    # ("r0.step") would fail the arm-time namespace validation at boot
    injector = (FaultInjector(faults.get("sites", {}),
                              seed=faults.get("seed", 0),
                              replica_namespaces=faults.get(
                                  "replica_namespaces", ()))
                if faults else None)
    engine = ServingEngine(model, fault_injector=injector,
                           **spec.get("engine", {}))
    # weights identity labels (ISSUE 18): a worker respawned AFTER a
    # rolling swap boots the new recipe — the spec carries the version
    # label so it reports the version it actually serves, not "v0"
    if "weights_version" in spec:
        engine.weights_version = str(spec["weights_version"])
    if "model_id" in spec:
        engine.model_id = str(spec["model_id"])
    # tracing (ISSUE 15): {"tracing": true} in the spec arms a per-worker
    # flight recorder; the engine's span events (prefill done, megastep
    # boundaries) ship back on every _w_step reply / _w_pop_traces RPC
    if spec.get("tracing"):
        from paddle_tpu.inference.tracing import FlightRecorder

        engine.trace_recorder = FlightRecorder(proc=args.name)
        if injector is not None:
            injector.recorder = engine.trace_recorder

    role = spec.get("role")
    stop = fleet.init_worker(engine, name=args.name, fault_injector=injector,
                             role=role)
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    if args.warm:
        # pre-pay the compile bill BEFORE registering (registration is
        # the pool's ready signal): one throwaway sub-block request
        # drives the prefill program and one decode megastep through
        # XLA.  The prompt is shorter than a block, so no FULL block is
        # ever published — the prefix cache stays empty and a warm
        # attach is token/cache-identical to a cold boot.
        engine.add_request([1], max_new_tokens=2)
        while engine.num_active or engine._queue:
            engine.step()
        engine.pop_finished()
        lp = getattr(engine, "pop_token_logprobs", None)
        if lp is not None:
            lp()
        pt = getattr(engine, "pop_trace_events", None)
        if pt is not None:
            pt()
    wire_server = None
    if spec.get("wire"):
        # binary KV data plane (ISSUE 20): open the worker's blockwire
        # listener before registering, sharing the SAME EpochFence the
        # control RPCs fence through — a deposed frontend's pull is
        # rejected typed on both planes.  Bind all interfaces and
        # advertise the rpc stack's peer-reachable address.
        import socket as _socket

        from paddle_tpu.inference.blockwire import BlockWireServer

        adv = os.environ.get("PADDLE_LOCAL_IP")
        if not adv:
            try:
                adv = _socket.gethostbyname(_socket.gethostname())
            except OSError:
                adv = "127.0.0.1"
        wire_server = BlockWireServer(engine, fence=fleet._WORKER["fence"],
                                      fault_injector=injector,
                                      host="0.0.0.0", advertise_host=adv)
    rpc.init_rpc(args.name, rank=args.rank, world_size=1,
                 master_endpoint=args.master)
    if role is not None:
        # the role label rides the launch-KV registration next to the rpc
        # entry, so discovery (fleet.worker_roles / connect_workers) can
        # rebuild a role-correct fleet on StandbyFrontend takeover even
        # without probing every worker first
        from paddle_tpu.distributed.launch.master import KVClient

        KVClient(args.master).put(f"/serving/roles/{args.name}", role)
    if wire_server is not None:
        # the data-plane endpoint registers next to the role label (and
        # rides every health reply), so peers can pull blocks directly
        from paddle_tpu.distributed.launch.master import KVClient

        KVClient(args.master).put(f"/serving/wire/{args.name}",
                                  wire_server.endpoint)
    if args.warm:
        # the warm marker keeps this worker out of discovery (a
        # recovering frontend must not adopt pool inventory); the
        # claiming fleet deletes it at attach time
        from paddle_tpu.distributed.launch.master import KVClient

        KVClient(args.master).put(f"/serving/warm/{args.name}", "1")
    print(f"WORKER_READY {args.name} pid={os.getpid()}", flush=True)
    stop.wait()
    if wire_server is not None:
        wire_server.close()
    rpc.shutdown()
    print(f"WORKER_EXIT {args.name}", flush=True)


if __name__ == "__main__":
    main()
