"""The solver's context build (node and task tensors, masks, scores):
span ``build_context``, per-cycle mean."""
import spans


def read(ctx):
    return spans.per_cycle(ctx, spans.total_ms(ctx.records,
                                               ["build_context"]))
