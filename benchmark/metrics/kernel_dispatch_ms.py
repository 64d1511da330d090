"""The placement kernel's dispatch: spans ``kernel/execute/dispatch``
(the kernel call, with its wrapper's host work and argument conversion)
less the union of the ``compile`` spans under them, per-cycle mean."""
import compile_spans
import spans


def read(ctx):
    ms = None
    for rec in ctx.records:
        for path, s in spans.walk(rec.root):
            if path.endswith("kernel/execute/dispatch"):
                ms = (ms or 0.0) + s.dur * 1000.0 - \
                    compile_spans.union_ms(compile_spans.under(s))
    return spans.per_cycle(ctx, ms)
