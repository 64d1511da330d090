"""Shortlist distillation (ops/prune.py): span ``prune_distill``,
per-cycle mean."""
import spans


def read(ctx):
    return spans.per_cycle(ctx, spans.total_ms(ctx.records,
                                               ["prune_distill"]))
