"""Session close (job status write-back, cache updates): span
``close_session``, per-cycle mean over the window."""
import spans


def read(ctx):
    return spans.per_cycle(ctx, spans.total_ms(ctx.records,
                                               ["close_session"],
                                               top_only=True))
