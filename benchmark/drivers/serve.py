"""Serving cells: ``ServingEngine`` behind ``ServingFrontend``, as a user
builds them, driven by the cell's generator on one thread.

Set-up: model with the benchmark's weights, engine, frontend, then a scripted
warm-up that runs every program the window can reach (prefill step, decode
step, the decode scan at each K bucket, the mixed scan). The window is the
generator's. After it: the device's peak memory is read, the engine is
dropped, and a seeded sample of the requests the window finished (the longest
among them) is scored by the plain reference."""
import gc

import numpy as np

from benchmark.harness import report

COUNTERS = ("megasteps", "megasteps_mixed", "megastep_tokens", "prefill_chunks",
            "prefill_tokens_computed")


class FrontendPort:
    """What a generator may do to the system under test."""

    def __init__(self, fe, eng, sampling):
        self.fe, self.eng = fe, eng
        self.sampling = {k: sampling[k] for k in ("temperature", "top_k", "top_p")
                         if k in sampling}
        self._open = set()

    def submit(self, prompt, max_new, on_token):
        rid = self.fe.submit(prompt, max_new_tokens=max_new, on_token=on_token,
                             **self.sampling)
        self._open.add(rid)
        return rid

    def step(self):
        self.fe.step()

    @property
    def pending(self):
        return self.fe.pending

    def slots_free(self):
        return self.eng.state_summary()["free_slots"]

    def poll(self):
        """[(rid, completed?)] of the requests that ended since the last call."""
        out = []
        for rid in list(self._open):
            res = self.fe.result(rid)
            if res is not None:
                self._open.discard(rid)
                out.append((rid, res.ok))
        return out


def _counters(eng, fe):
    c = {k: getattr(eng, k) for k in COUNTERS}
    c.update({f"phase_{k}": v for k, v in eng.phase_seconds.items()})
    for k in ("rejected_overloaded_total", "shed_deadline_total", "preempted_total"):
        c[k] = fe.metrics.counter(k)
    return c


def warm_up(fe, eng, vocab, k):
    """Every program the window can reach, once: the prefill step, the decode
    step, the decode scan at K = 2, 4, .. k, and the mixed scan."""
    rng = np.random.default_rng(0)

    def prompt(n):
        return rng.integers(1, vocab, n).tolist()

    def finish():
        for _ in range(10_000):
            if not fe.pending:
                return
            fe.step()
        raise RuntimeError("warm-up did not finish")

    news = [2] + [kk + 1 for kk in (2, 4, 8, 16, 32) if kk <= k]
    for new in news:                       # remaining after prefill: new - 1
        fe.submit(prompt(70), max_new_tokens=new)
        finish()
    before = eng.megasteps
    fe.submit(prompt(70), max_new_tokens=4 * k)
    while eng.megasteps == before:
        fe.step()
    fe.submit(prompt(min(300, eng.max_seq_len // 2)), max_new_tokens=4)
    finish()
    if k > 1 and not (eng.megasteps > eng.megasteps_mixed >= 1):
        raise RuntimeError(f"warm-up ran {eng.megasteps} scans, "
                           f"{eng.megasteps_mixed} of them mixed")


class _Hooks:
    """Counters at the window's edges; at every tick a sample of the live
    contexts and of the pool's blocks held by running requests, and the
    profiler's turn."""

    def __init__(self, eng, fe, ctx):
        self.eng, self.fe, self.ctx = eng, fe, ctx
        self.at_open = self.at_close = self.compiles = self.in_window = None
        self.live_tokens, self.live_blocks = [], []

    def on_open(self):
        self.at_open = _counters(self.eng, self.fe)
        self.compiles = self.ctx.meter.snapshot()

    def on_tick(self, rel, log):
        self.live_tokens.append(sum(len(r.prompt) + len(r.tokens)
                                    for r in log.values() if r.tokens and r.ok is None))
        self.live_blocks.append(sum(self.eng.state_summary()["active"].values()))
        self.ctx.trace_tick(rel)

    def on_close(self):
        self.ctx.trace_close()
        self.at_close = _counters(self.eng, self.fe)
        self.in_window = self.ctx.meter.since(self.compiles)


def run(cell, seed, seconds, ctx):
    import paddle_tpu as P
    from paddle_tpu.inference import ServingEngine, ServingFrontend

    cfg, traffic = cell.config, cell.traffic
    family = cell.module("families", cfg["family"])
    reference = cell.module("references", cfg["family"])
    generator = cell.module("generators", traffic["generator"])
    vocab = cfg["vocab_size"]

    P.seed(0)
    model = family.build_model(cfg)          # before the weights: it is built in float32
    weights = family.make_weights(cfg, seed)
    family.assign(model, weights)
    model.eval()
    eng = ServingEngine(model, **cfg["engine"])
    fe = ServingFrontend([eng], **cfg.get("frontend", {}))
    warm_up(fe, eng, vocab, int(cfg["engine"].get("megastep_k", 8)))

    port = FrontendPort(fe, eng, traffic.get("sampling", {}))
    hooks = _Hooks(eng, fe, ctx)
    window = generator.drive(port, traffic, seed, seconds, vocab, ctx.clock, hooks)
    ctx.read_memory_peak()

    t_open, t_close = window["t_open"], window["t_close"]
    delta = {k: hooks.at_close[k] - hooks.at_open[k] for k in hooks.at_open}
    counted = window["counted"]
    result = {"t_open": t_open, "t_close": t_close, "window_s": t_close - t_open,
              "requests": window["requests"], "counted": counted, "counters": delta,
              "attempted": len(counted), "failed": sum(not r.ok for r in counted),
              "compiles_in_window": hooks.in_window["programs_compiled"],
              "live_tokens_mean": (float(np.mean(hooks.live_tokens))
                                   if hooks.live_tokens else 0.0),
              "live_blocks_mean": (float(np.mean(hooks.live_blocks))
                                   if hooks.live_blocks else None),
              "blocks_total": eng.state_summary()["blocks_total"],
              "megastep_k": eng.megastep_k}

    # the sample the reference scores: finished in the window, the longest in it
    sample = pick_sample([r for r in counted if r.ok and r.tokens], seed, cfg["check"])
    del port, hooks, fe, eng, model
    gc.collect()
    score(reference, weights, cfg, sample, ctx.checks, control=ctx.control)
    return result


def pick_sample(done, seed, check):
    """The longest finished request and ``sample_requests - 1`` others drawn
    from the seed, within ``max_tokens`` of reference forward in total."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r.index)
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 611953])
    rng.shuffle(rest)
    out, total = [longest], len(longest.prompt) + len(longest.tokens)
    for r in rest:
        if len(out) >= int(check["sample_requests"]):
            break
        n = len(r.prompt) + len(r.tokens)
        if total + n <= int(check["max_tokens"]):
            out.append(r)
            total += n
    return out


def score(reference, weights, cfg, sample, checks, control=False):
    """Over the sample, by how many nats a served token's logit lies below
    the reference's best at its position: the widest such gap and the mean
    over all served tokens (near-ties in bf16 make the widest swing; the
    mean is steady). Under ``control`` the served tokens are replaced by the
    ones the configuration's lower precision puts first, position by position
    on the same inputs; the precisions under ``also_read`` are read the same
    way and printed, and decide nothing."""
    pad_to = int(cfg["check"]["pad_to"])
    lows = ([cfg["control"]["reference_precision"]] + list(cfg["control"].get("also_read", []))
            if control else [])
    gaps = {name: [] for name in ["served"] + lows}
    for r in sample:
        full = r.prompt + r.tokens
        ids = np.zeros((max(pad_to, len(full)),), np.int32)
        ids[:len(full)] = full
        rows = np.arange(len(r.prompt) - 1, len(full) - 1)
        ref = np.asarray(reference.logits_at(weights, cfg, ids, rows))
        best, at = ref.max(-1), np.arange(len(rows))
        gaps["served"].append(best - ref[at, np.asarray(r.tokens)])
        for low in lows:
            first = np.asarray(reference.logits_at(
                weights, cfg, ids, rows, quant=low, n_prompt=len(r.prompt))).argmax(-1)
            gaps[low].append(best - ref[at, first])
        report.note(checked_request=r.index, prompt=len(r.prompt), new=len(r.tokens),
                    **{f"max_gap_nats.{name}": float(g[-1].max()) for name, g in gaps.items()})
    stats = {}
    for name, per_request in gaps.items():
        if per_request:
            g = np.concatenate(per_request)
            stats[name] = {"max_gap_nats": float(g.max()), "mean_gap_nats": float(g.mean())}
            report.note(gaps=name, tokens=len(g), max=float(g.max()),
                        p99=float(np.percentile(g, 99)), mean=float(g.mean()),
                        argmax_agree=float((g == 0).mean()))
    judged = stats.get(lows[0] if control else "served", {})
    checks.add("sampled_requests", len(sample), 1, at_least=True)
    for number, limit in cfg["check"]["limits"].items():
        checks.add(number, judged.get(number, float("nan")), limit)
