"""Device time per lock-step iteration of the engine's ``while_loop`` (us):
device busy time inside the engine spans over the iterations those calls
ran (the largest ``n_events`` of each call), a ratio of sums."""


def read(ctx):
    busy = ctx.trace.busy_ns(within="engine")
    if not ctx.lockstep_iters or not busy:
        return None
    return busy / ctx.lockstep_iters / 1e3
