"""Training cells: ``jit.TrainStep`` over the family's model, one ``step(ids)``
a batch, a new batch every step.

Set-up builds ONE TrainStep with its state and drives it through the first
``check.steps`` batches of the feed by the window's own call, keeping each
step's loss, each leaf's first-gradient norm (from Adam's first moment after
one step) and each leaf's change after the last of them; the same object then
runs the window on the feed where it stands. After the window the device's
peak memory is read, the program's state is dropped, and the plain reference
follows those same first batches from the seed's weights; the norms are
compared by the worst leaf."""
import math

import numpy as np

from benchmark.harness import report
from benchmark.harness.traffic import span


def worst_leaf_gap(got, want):
    """Largest |got - want| over the leaves, each against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    got, want = np.asarray(got, float), np.asarray(want, float)
    floor = float(np.median(want))
    return float(np.max(np.abs(got - want) / np.maximum(want, floor)))


def _norms(arrays):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                             for x in xs])
    return [float(x) for x in fn(list(arrays))]


def compare(checks, cfg, losses, grad_norms, change_norms, ref):
    limits = cfg["check"]["limits"]
    loss_gap = max(abs(a - b) for a, b in zip(losses, ref["losses"]))
    checks.add("loss_gap", loss_gap if all(map(math.isfinite, losses)) else float("nan"),
               limits["loss_gap"])
    checks.add("grad_norm_gap", worst_leaf_gap(grad_norms, ref["grad_norms"]),
               limits["grad_norm_gap"])
    checks.add("change_norm_gap", worst_leaf_gap(change_norms, ref["change_norms"]),
               limits["change_norm_gap"])


def build(cfg, family, seed):
    import paddle_tpu as P
    from paddle_tpu.models import LlamaPretrainingCriterion

    P.seed(0)
    model = family.build_model(cfg, **cfg["train"].get("model", {}))
    family.assign(model, family.make_weights(cfg, seed))
    o = cfg["optimizer"]
    opt = P.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters(), multi_precision=bool(o["multi_precision"]))
    crit = LlamaPretrainingCriterion()
    step = P.jit.TrainStep(model, lambda m, ids: crit(m(ids), ids), opt)
    return model, opt, step


def run(cell, seed, seconds, ctx):
    import gc

    import jax
    import jax.numpy as jnp

    import paddle_tpu as P

    cfg, traffic = cell.config, cell.traffic
    family = cell.module("families", cfg["family"])
    reference = cell.module("references", cfg["family"])
    generator = cell.module("generators", traffic["generator"])
    feed = generator.batches(traffic, seed, cfg["vocab_size"])
    first = [next(feed) for _ in range(int(cfg["check"]["steps"]))]
    o = cfg["optimizer"]

    def follow(**kw):
        return reference.train_steps(
            lambda: family.make_weights(cfg, seed), cfg, first, lr=o["learning_rate"],
            beta1=o["beta1"], beta2=o["beta2"], epsilon=o["epsilon"],
            weight_decay=o["weight_decay"], **kw)

    if ctx.control:
        # the reference in the program's place, its optimizer state held in
        # the lower precision; no program, no window
        low = follow(state_dtype=cfg["control"]["state_dtype"])
        compare(ctx.checks, cfg, low["losses"], low["grad_norms"], low["change_norms"],
                follow())
        now = ctx.clock()
        return {"t_open": now, "t_close": now, "window_s": 0.0, "tokens_in_window": 0,
                "attempted": 0, "failed": 0, "compiles_in_window": 0, "no_window": True}

    model, opt, step = build(cfg, family, seed)
    params = jax.tree_util.tree_leaves(family.params_of(model),
                                       is_leaf=lambda x: hasattr(x, "_value"))

    def call(batch):
        with span("bench.step"):
            return step(P.to_tensor(batch))

    def read(loss):
        with span("bench.loss_read"):
            return float(loss.numpy())

    losses, grad_norms = [], None
    for batch in first:
        losses.append(read(call(batch)))
        if grad_norms is None:
            m1 = [opt._accumulators["moment1"][id(p)] for p in params]
            grad_norms = [n / (1.0 - o["beta1"]) for n in _norms(m1)]
            del m1
    now = [opt._master_weights.get(id(p), p._value) for p in params]
    start = jax.tree_util.tree_leaves(family.make_weights(cfg, seed))
    change = _norms([a.astype(jnp.float32) - b.astype(jnp.float32)
                     for a, b in zip(now, start)])
    del now, start

    # the window: the same object, the same call, the feed where it stands
    every = int(traffic["loss_every"])
    tokens_per_step = int(traffic["batch"]) * int(traffic["seq"])
    snap = ctx.meter.snapshot()
    t_open = ctx.clock()
    steps, bad, loss = 0, 0, None
    while True:
        rel = ctx.clock() - t_open
        if rel >= seconds:
            break
        ctx.trace_tick(rel)
        loss = call(next(feed))
        steps += 1
        if steps % every == 0:
            bad += not math.isfinite(read(loss))
    bad += not math.isfinite(read(loss))
    t_close = ctx.clock()
    ctx.trace_close()
    compiles = ctx.meter.since(snap)["programs_compiled"]
    ctx.read_memory_peak(cell.chips)

    # the program's state goes, the reference comes
    del model, opt, step, params, loss
    gc.collect()
    t0 = ctx.clock()
    ref = follow()
    compare(ctx.checks, cfg, losses, grad_norms, change, ref)
    report.note(losses=losses, reference_losses=ref["losses"],
                reference_seconds=ctx.clock() - t0)
    return {"t_open": t_open, "t_close": t_close, "window_s": t_close - t_open,
            "tokens_in_window": steps * tokens_per_step,
            "attempted": steps, "failed": bad, "compiles_in_window": compiles}
