"""The allocate action's own host work (ordering, staging, decode): span
``action:allocate`` less its ``solver.place`` children, per-cycle mean."""
import spans


def read(ctx):
    ms = None
    for rec in ctx.records:
        for s in rec.root.children or ():
            if s.name != "action:allocate":
                continue
            ms = (ms or 0.0) + s.dur * 1000.0
            for _, c in spans.walk(s):
                if c.name == "solver.place":
                    ms -= c.dur * 1000.0
    return spans.per_cycle(ctx, ms)
