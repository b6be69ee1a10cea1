"""Host time of the ETP search outside the engine, per plan (ms): chains,
proposals, memo and cost bookkeeping.  Read as the search spans minus the
engine spans inside them."""


def read(ctx):
    if ctx.op != "plan" or not ctx.ops:
        return None
    ns = ctx.trace.span_ns("search") - ctx.trace.covered_ns("search", "engine")
    return ns / ctx.ops / 1e6
