"""Simulated training makespan of the committed schedules (s), averaged
over the traced window's plans: the paper's objective, the quality of what
``plan()`` returns.  A faster plan that searches worse shows here."""


def read(ctx):
    if ctx.op != "plan" or not ctx.records:
        return None
    return sum(r.data["makespan"] for r in ctx.records) / len(ctx.records)
