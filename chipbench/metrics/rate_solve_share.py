"""Share of the engine's device time that the rate solve takes (%): the self
time of the device operations under the runner's ``rate_solve`` named scope,
over device busy time inside the engine spans.  Each nanosecond goes to the
innermost operation running then (a ``while`` operation's event encloses
its body's operations).

The program's ``engine_jax.runner_scopes()`` maps each runner's instruction
names to their ``op_name``.  Runners of other widths reuse a name for other
work, so each engine call is read against the runners whose instructions
cover most of the names it ran; a name those runners give different phases
counts for none.  Nothing is read where the program has no such map."""
import bisect

PHASES = ("settle", "rate_solve", "advance")


def phase(op_name):
    """The innermost of ``PHASES`` on an ``op_name`` path, or None."""
    for part in reversed(op_name.split("/")):
        if part in PHASES:
            return part
    return None


def call_phases(scopes, names):
    """{name: phase or None} for the op names one engine call ran, read
    against the runners that hold the most of them."""
    hits = {rid: len(names & m.keys()) for rid, m in scopes.items()}
    best = max(hits.values(), default=0)
    runners = [scopes[rid] for rid, n in hits.items() if n == best and n]
    out = {}
    for name in names:
        found = {phase(m.get(name, "")) for m in runners}
        out[name] = found.pop() if len(found) == 1 else None
    return out


def self_ns(ops):
    """Each operation's self time: the nanoseconds in which it is the
    innermost (latest started) of the operations still running."""
    own = [0] * len(ops)
    stack = []  # open operations, innermost last
    cur = None

    def run_to(t):
        nonlocal cur
        while stack and cur < t:
            i = stack[-1]
            end = ops[i][1] + ops[i][2]
            if end > cur:
                step = min(end, t)
                own[i] += step - cur
                cur = step
            if end <= cur:
                stack.pop()
        cur = t

    for i in sorted(range(len(ops)), key=lambda k: (ops[k][1], -ops[k][2])):
        if cur is None:
            cur = ops[i][1]
        run_to(ops[i][1])
        stack.append(i)
    if ops:
        run_to(max(s + d for _, s, d in ops))
    return own


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
    return scope_share(ctx, "rate_solve")
