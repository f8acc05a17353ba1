"""Share of the traced window in which the chip ran no operation."""


def read(ctx):
    if ctx.busy_s is None:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.trace_window_s)
