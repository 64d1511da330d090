"""The device's idle share of the traced window: 1 less the union of the
intervals in which an operation ran, over the window, in %."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
