"""KV cache pool: blocks held by running requests (the engine's
``state_summary()["active"]``, summed, mean over the window's ticks) as a
share of the pool's blocks. Blocks the prefix cache keeps after a request has
ended are not counted: nothing reads them unless a prefix is shared."""


def read(run):
    if run.get("live_blocks_mean") is None or not run.get("blocks_total"):
        return None
    return 100.0 * run["live_blocks_mean"] / run["blocks_total"]
