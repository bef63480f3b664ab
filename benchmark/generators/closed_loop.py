"""Closed loop: ``clients`` callers, each sends its next request when its last
one completes, so a slower system receives less load. The window opens once
the ramp is over (every slot taken and ``ramp_completions`` requests done), so
that ramp-up is set-up and not throughput; it closes ``seconds`` later at the
end of the frontend step in which that instant falls.

Parameters (the traffic file): clients, ramp_completions, first_wave, sizes
{seed, count, prompt, new_tokens}, sampling. The ``count`` sizes are one
fixed sequence, sent round and round from its first entry in every run;
``--seed`` draws the token ids (and the weights) and nothing of the sizes or
their order. A window's rate depends on which prompts and outputs fall into
it: a free shuffle of a large pool moved it by a fifth from seed to seed, and
a seeded starting point in the fixed sequence still by a tenth (138-150
tokens/s over six starts), while two runs from one starting point agree
within 0.1 % (PERF.md, PR 23). The callers' first requests ask for a share,
in [first_wave, 1] and fixed by position like the sizes, of their new tokens,
as if met mid-flight: the slots then finish at different times from the
start."""
import numpy as np

from benchmark.harness import traffic as T


def drive(port, traffic, seed, seconds, vocab, clock, hooks):
    """-> {"t_open", "t_close", "requests": all sent, "counted": those that
    ended inside the window} (times on ``clock``)."""
    pool = T.sizes(traffic, int(traffic["sizes"]["count"]))
    log, requests = {}, []
    send = T.sender(port, clock, log)
    n_sent = 0

    clients = int(traffic["clients"])
    wave = np.random.default_rng(int(traffic["sizes"]["seed"]) + 1).uniform(
        float(traffic.get("first_wave", 1.0)), 1.0, len(pool))

    def next_request():
        nonlocal n_sent
        at = n_sent % len(pool)
        plen, new = pool[at]
        if n_sent < clients:
            new = max(1, round(new * wave[at]))
        req = T.Request(n_sent, clock(), T.prompt_ids(seed, n_sent, plen, vocab), new)
        n_sent += 1
        requests.append(req)
        send(req)

    for _ in range(clients):
        next_request()
    done = 0
    t_open = None
    ramp = int(traffic.get("ramp_completions", 1))
    while True:
        now = clock()
        if t_open is None and done >= ramp and port.slots_free() == 0:
            t_open = now
            hooks.on_open()
        if t_open is not None:
            if now - t_open >= seconds:
                break
            hooks.on_tick(now - t_open, log)
        with T.span("bench.fe_step"):
            port.step()
        now = clock()
        for rid, ok in port.poll():
            req = log[rid]
            req.ok, req.done_at = ok, now
            done += 1
            next_request()
    hooks.on_close()
    counted = [r for r in requests if r.done_at is not None and r.done_at >= t_open]
    return {"t_open": t_open, "t_close": now, "requests": requests, "counted": counted}
