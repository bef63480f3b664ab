"""What the generators share: lengths drawn from a traffic file's own fixed
seed, so that every ``--seed`` carries the same set of sizes, and token ids
drawn from ``--seed``."""
import contextlib
import math

import numpy as np


def span(name):
    """A host span on the profiler's clock (nothing where no trace runs)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:          # the generators also run against fakes
        return contextlib.nullcontext()
    return TraceAnnotation(name)


def draw(spec, count, rng):
    """``count`` whole numbers from {"dist", ..., "min", "max"}, clipped."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], count)
    elif dist == "uniform":
        x = rng.uniform(spec["min"], spec["max"], count)
    elif dist == "fixed":
        x = np.full(count, spec["value"], float)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    lo = spec.get("min", spec.get("value"))
    hi = spec.get("max", spec.get("value"))
    return np.clip(np.rint(x), lo, hi).astype(int)


def sizes(traffic, count):
    """[(prompt tokens, new tokens)] * count: the mix's fixed set."""
    s = traffic["sizes"]
    rng = np.random.default_rng(int(s["seed"]))
    return list(zip(draw(s["prompt"], count, rng).tolist(),
                    draw(s["new_tokens"], count, rng).tolist()))


def prompt_ids(seed, index, length, vocab):
    """The token ids of request ``index``: uniform, never 0."""
    rng = np.random.default_rng([int(seed), 104729, int(index)])
    return rng.integers(1, vocab, length).tolist()


class Request:
    """One request as its client saw it: when it was due and sent, and a stamp
    and a token for every ``on_token`` call."""
    __slots__ = ("index", "rid", "due", "submitted", "prompt", "max_new",
                 "stamps", "tokens", "ok", "done_at")

    def __init__(self, index, due, prompt, max_new):
        self.index, self.due, self.prompt, self.max_new = index, due, prompt, max_new
        self.rid = self.submitted = self.ok = self.done_at = None
        self.stamps, self.tokens = [], []


def sender(port, clock, log):
    """send(request): submit it with a stamping callback, file it in ``log``."""
    def send(req):
        def on_token(_rid, tok, req=req):
            req.stamps.append(clock())
            req.tokens.append(tok)
        with span("bench.submit"):
            req.rid = port.submit(req.prompt, req.max_new, on_token)
        req.submitted = clock()
        log[req.rid] = req
    return send
