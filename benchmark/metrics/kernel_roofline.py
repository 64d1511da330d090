"""The placement kernel's share of its roofline: the least time the
window's calls need at the device's peaks (roofline/count.py, from each
call's shapes) over their device time in the trace, in %."""
from roofline.count import least_time


def read(ctx):
    if ctx.trace is None or not ctx.kernel_calls or ctx.peaks is None:
        return None
    k = ctx.trace["kernels"].get(ctx.kernel_pattern)
    if not k or k["s"] <= 0:
        return None
    least = sum(least_time(c, ctx.peaks)[0] for c in ctx.kernel_calls)
    return 100.0 * least / k["s"]
