"""Session open (snapshot of the cache, plugin opens): span
``open_session``, per-cycle mean over the window."""
import spans


def read(ctx):
    return spans.per_cycle(ctx, spans.total_ms(ctx.records, ["open_session"],
                                               top_only=True))
