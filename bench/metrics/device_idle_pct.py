"""Share of the traced window, in percent, in which no operation ran on
the device: ``100 * (1 - busy / window)``, busy being the union of the
op intervals of the trace's ``XLA Ops`` line."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops_by_device:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
