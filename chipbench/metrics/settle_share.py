"""Share of the engine's device time that settle takes (%): the self time of
the device operations under the runner's ``settle`` named scope (finished
tasks and flows, arming flows with the per-iteration volume lookup,
starting ready tasks), over device busy time inside the engine spans.
Each nanosecond goes to the innermost operation running then.

Each engine call is read against its own runner's instruction map by the
rule of ``rate_solve_share``: a name that those runners give different
phases counts for none, and nothing is read where the program has no
``engine_jax.runner_scopes``."""
import bisect

from chipbench.metrics.rate_solve_share import call_phases, self_ns


def scope_share(ctx, scope):
    """Self time of the device ops in phase ``scope`` over device busy
    inside the engine spans (%), or None where nothing can be read."""
    from repro.core import engine_jax

    spans = ctx.trace.spans.get("engine", [])
    busy = ctx.trace.busy_ns(within="engine")
    runner_scopes = getattr(engine_jax, "runner_scopes", None)
    if not spans or not busy or runner_scopes is None:
        return None
    scopes = runner_scopes()
    starts = [s for s, _ in spans]
    call = []  # the engine span each operation starts in, or -1
    names = [set() for _ in spans]
    for name, s, _ in ctx.trace.ops:
        k = bisect.bisect_right(starts, s) - 1
        k = k if k >= 0 and s < spans[k][1] else -1
        call.append(k)
        if k >= 0:
            names[k].add(name)
    phases = [call_phases(scopes, n) for n in names]
    ns = sum(own for (name, _, _), k, own in zip(ctx.trace.ops, call, self_ns(ctx.trace.ops))
             if k >= 0 and phases[k][name] == scope)
    return 100.0 * ns / busy


def read(ctx):
    return scope_share(ctx, "settle")
