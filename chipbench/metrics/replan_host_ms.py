"""Host time of a warm re-plan outside the engine, per re-plan (ms): the
Replanner's search, migration pricing and remap.  Read as the operation
spans minus the engine spans inside them."""


def read(ctx):
    if ctx.op != "replan" or not ctx.ops:
        return None
    ns = ctx.trace.span_ns("op") - ctx.trace.covered_ns("op", "engine")
    return ns / ctx.ops / 1e6
