"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.n_devices:
        return None
    a, b = ctx.trace_window
    return (1 - ctx.busy_s() / (b - a)) * 100
