"""Share of the traced window in which no operation ran on the device (%):
1 - busy / window, with the window from the first operation's start to the
last one's end."""


def read(ctx):
    w = ctx.trace.window
    busy = ctx.trace.busy_ns()
    if w is None or w[1] <= w[0] or not busy:
        return None
    return 100.0 * (1.0 - busy / (w[1] - w[0]))
