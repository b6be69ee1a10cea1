"""Share of the engine's device time that settle takes (%): the self time of
the device operations under the runner's ``settle`` named scope (finished
tasks and flows, arming flows with the per-iteration volume lookup,
starting ready tasks), over device busy time inside the engine spans.
Each nanosecond goes to the innermost operation running then.

Each engine call is read against its own runner's instruction map by the
rule of ``rate_solve_share``: a name that those runners give different
phases counts for none, and nothing is read where the program has no
``engine_jax.runner_scopes``."""
from chipbench.metrics.rate_solve_share import scope_share


def read(ctx):
    return scope_share(ctx, "settle")
