"""Host time inside the engine's entry point while the device is idle, per
operation (ms): input assembly, padding, dispatch and result unpacking of
``simulate_batch_jax``.  Read as the engine spans minus the device busy time
inside them."""


def read(ctx):
    if not ctx.ops or not ctx.trace.span_ns("engine"):
        return None
    ns = ctx.trace.span_ns("engine") - ctx.trace.busy_ns(within="engine")
    return ns / ctx.ops / 1e6
