"""The other actions: spans ``action:enqueue``, ``action:backfill``,
``action:preempt`` and ``action:reclaim``, summed, per-cycle mean."""
import spans

ACTIONS = ("action:enqueue", "action:backfill", "action:preempt",
           "action:reclaim")


def read(ctx):
    return spans.per_cycle(ctx, spans.total_ms(ctx.records, ACTIONS,
                                               top_only=True))
