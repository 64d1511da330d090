"""JAX compiles in every layer: the union of the intervals of the
window's ``compile`` spans (compile_spans.py), per-cycle mean; nothing
where the program records no compiles."""
import compile_spans
import spans


def read(ctx):
    if not compile_spans.recorded():
        return None
    return spans.per_cycle(ctx, sum(compile_spans.union_ms(
        compile_spans.under(rec.root)) for rec in ctx.records))
