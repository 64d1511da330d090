"""The placement kernel's readback: spans ``kernel/execute/readback``
(the wait for the device to finish and the copy of the assignment back
to the host), per-cycle mean."""
import spans


def read(ctx):
    ms = None
    for rec in ctx.records:
        for path, s in spans.walk(rec.root):
            if path.endswith("kernel/execute/readback"):
                ms = (ms or 0.0) + s.dur * 1000.0
    return spans.per_cycle(ctx, ms)
