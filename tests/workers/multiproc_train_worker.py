"""Multi-controller worker: one OS process of a 2-process JAX job.
Each process owns 4 virtual CPU devices; the global
mesh spans all 8. Proves, across REAL process boundaries:
- one GSPMD-compiled TrainStep (dp spans the two processes, mp inside),
  fed per-host batch shards via jax.make_array_from_process_local_data;
- distributed checkpoint save (each host writes its own shards) + resume
  into a fresh model with bit-identical continued losses.

Launched by tests/test_multiproc.py through the repo's own launcher
(paddle_tpu.distributed.launch), which supplies the PADDLE_TRAINER_* env
contract; init_parallel_env turns that into jax.distributed.initialize
(reference analog: test/legacy_test/test_parallel_dygraph_dataparallel.py:30
spawning local trainers over NCCL).
"""
import json
import os
import sys

_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "xla_force_host_platform_device_count" not in f]
_flags.append("--xla_force_host_platform_device_count=4")
os.environ["XLA_FLAGS"] = " ".join(_flags)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as PS  # noqa: E402

sys.path.insert(0, os.environ.get("PADDLE_TPU_REPO", "/root/repo"))

import paddle_tpu as P  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402
from paddle_tpu.tensor.tensor import Tensor  # noqa: E402


def make_global(t, mesh, spec=PS()):
    """Replicate (or shard) a process-local Tensor onto the global mesh —
    multi-controller jit only accepts global arrays."""
    from paddle_tpu.distributed.multihost import global_device_put

    t._value = global_device_put(np.asarray(t._value),
                                 NamedSharding(mesh, spec))
    return t


def globalize_model_and_opt(model, opt, mesh):
    for p in model.parameters():
        make_global(p, mesh)
    for b in model.buffers():
        if b is not None:
            make_global(b, mesh)
    from paddle_tpu.distributed.multihost import global_device_put

    opt._ensure_state()
    for d in opt._accumulators.values():
        for pid, v in list(d.items()):
            d[pid] = global_device_put(np.asarray(v),
                                       NamedSharding(mesh, PS()))
    for pid, v in list(opt._master_weights.items()):
        opt._master_weights[pid] = global_device_put(
            np.asarray(v), NamedSharding(mesh, PS()))


def main_pp(workdir):
    """Compiled pipeline ACROSS the process boundary: pp=2 with stage 0 on
    process 0's devices and stage 1 on process 1's (mp=4 inside each stage).
    One shard_map program; both processes participate in every step."""
    rank = jax.process_index()
    strategy = dist.fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 4, "pp_degree": 2,
                               "sharding_degree": 1, "sep_degree": 1}
    dist.fleet.init(is_collective=True, strategy=strategy)
    from paddle_tpu.distributed.fleet.meta_parallel import (
        CompiledPipelineTrainStep,
        PipelineLayer,
    )
    from paddle_tpu.distributed.topology import get_hybrid_communicate_group
    from paddle_tpu.models import (
        LlamaPretrainingCriterion,
        llama_pipeline_descs,
        llama_tiny,
    )

    mesh = get_hybrid_communicate_group().mesh
    P.seed(77)
    cfg = llama_tiny()
    crit = LlamaPretrainingCriterion()
    pipe = PipelineLayer(layers=llama_pipeline_descs(cfg), num_stages=2,
                         loss_fn=lambda lo, la: crit(lo, la))
    # buffers (rope tables) ride the traced program as constants — they must
    # be global arrays under multi-controller jit
    for b in pipe.buffers():
        if b is not None:
            make_global(b, mesh)
    opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=pipe.parameters())
    cstep = CompiledPipelineTrainStep(pipe, opt, num_micro=4)
    rng = np.random.RandomState(5)
    ids = Tensor(jax.device_put(
        rng.randint(0, cfg.vocab_size, (8, 32)).astype(np.int32),
        NamedSharding(mesh, PS())))
    losses = []
    for _ in range(3):
        loss = cstep(ids, ids)
        losses.append(float(np.asarray(
            loss._value.addressable_data(0)).reshape(-1)[0]))
    json.dump({"rank": rank, "pp_losses": losses},
              open(os.path.join(workdir, f"pp_result_{rank}.json"), "w"))


def main():
    workdir = sys.argv[1]
    phase = sys.argv[2] if len(sys.argv) > 2 else "train"
    dist.init_parallel_env()
    rank = jax.process_index()
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())
    if phase == "pp":
        return main_pp(workdir)

    strategy = dist.fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4, "pp_degree": 1,
                               "sharding_degree": 1, "sep_degree": 1}
    dist.fleet.init(is_collective=True, strategy=strategy)
    from paddle_tpu.distributed.topology import get_hybrid_communicate_group

    mesh = get_hybrid_communicate_group().mesh

    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    def build():
        P.seed(1234)  # identical init on every process
        model = LlamaForCausalLM(llama_tiny())
        opt = P.optimizer.AdamW(learning_rate=1e-3,
                                parameters=model.parameters())
        globalize_model_and_opt(model, opt, mesh)
        step = P.jit.TrainStep(model,
                               lambda m, ids: m.pretraining_loss(ids), opt)
        return model, opt, step

    model, opt, step = build()

    S, local_b = 16, 4  # global batch 8 = dp2 x 4/host
    in_shard = NamedSharding(mesh, PS("dp", None))

    def batch(i):
        # per-host data: each process materializes ONLY its dp shard
        rng = np.random.RandomState(1000 + 10 * i + rank)
        local = rng.randint(0, 512, (local_b, S)).astype(np.int32)
        return Tensor(jax.make_array_from_process_local_data(in_shard, local))

    def run_steps(st, lo, hi):
        out = []
        for i in range(lo, hi):
            loss = st(batch(i))
            out.append(float(np.asarray(
                loss._value.addressable_data(0)).reshape(-1)[0]))
        return out

    losses_a = run_steps(step, 0, 2)

    # ---- distributed checkpoint: every host writes its own shards
    ckpt = os.path.join(workdir, "ckpt")
    state = {f"model.{k}": v for k, v in model.state_dict().items()}
    state.update({f"opt.{k}": v for k, v in opt.state_dict().items()
                  if hasattr(v, "_value") or isinstance(v, (np.ndarray,))})
    dist.save_state_dict(state, ckpt)

    losses_b = run_steps(step, 2, 4)

    # ---- resume: fresh model/opt, load the sharded checkpoint, same steps
    model2, opt2, step2 = build()
    # perturb to prove the load does the work
    for p in model2.parameters():
        p._value = p._value * 0.0
    # zero-filled load templates from the FRESH objects (the saved dict's
    # tensors were donated away by the later train steps)
    fresh = {f"model.{k}": v for k, v in model2.state_dict().items()}
    fresh.update({f"opt.{k}": v for k, v in opt2.state_dict().items()
                  if hasattr(v, "_value")})
    loaded = {k: Tensor(np.zeros(tuple(v.shape),
                                 np.asarray(v._value).dtype))
              for k, v in fresh.items()}
    dist.load_state_dict(loaded, ckpt)
    model2.set_state_dict({k[len("model."):]: v for k, v in loaded.items()
                           if k.startswith("model.")})
    opt2.set_state_dict({k[len("opt."):]: v for k, v in loaded.items()
                         if k.startswith("opt.")})
    globalize_model_and_opt(model2, opt2, mesh)
    losses_resume = run_steps(step2, 2, 4)

    # cross-host object gather rides the same runtime channel
    objs = []
    dist.all_gather_object(objs, {"rank": rank, "tag": f"host{rank}"})

    json.dump({"rank": rank, "losses_a": losses_a, "losses_b": losses_b,
               "losses_resume": losses_resume,
               "gathered_objs": objs,
               "shard_file": sorted(os.listdir(ckpt))},
              open(os.path.join(workdir, f"result_{rank}.json"), "w"))


if __name__ == "__main__":
    main()
