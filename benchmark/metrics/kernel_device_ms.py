"""The placement kernel's device time: the summed device durations of
its events in the profiler trace (the jitted program whose name holds
the kernel's stable name, ``ctx.kernel_pattern``), per window cycle that
ran it."""


def read(ctx):
    if ctx.trace is None or not ctx.kernel_cycles:
        return None
    k = ctx.trace["kernels"].get(ctx.kernel_pattern)
    if not k or not k["count"]:
        return None
    return k["s"] * 1000.0 / ctx.kernel_cycles
