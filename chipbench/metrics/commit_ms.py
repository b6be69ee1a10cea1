"""Host time of ``plan()`` outside the ETP search, per plan (ms): the numpy
committed schedule, the chain certificate and the traffic summary.  Read as
the plan spans minus the search spans inside them."""


def read(ctx):
    if ctx.op != "plan" or not ctx.ops:
        return None
    ns = ctx.trace.span_ns("op") - ctx.trace.covered_ns("op", "search")
    return ns / ctx.ops / 1e6
