"""Readings of the flight recorder's spans (volcano_tpu/trace/tracer.py)
for the per-layer metrics: each gives a per-cycle mean over the window's
cycles, so that the layers add up toward ``cycle_ms``."""

from __future__ import annotations

from typing import Iterable, List, Optional


def walk(span, path: str = ""):
    """(path, span) for ``span`` and every span under it."""
    p = f"{path}/{span.name}" if path else span.name
    yield p, span
    for c in span.children or ():
        yield from walk(c, p)


def total_ms(records, names: Iterable[str],
             top_only: bool = False) -> Optional[float]:
    """The summed length of the spans so named; None where there is
    none."""
    names = set(names)
    ms, found = 0.0, False
    for rec in records:
        spans = (rec.root.children or ()) if top_only else \
            (s for _, s in walk(rec.root))
        for s in spans:
            if s.name in names:
                ms += s.dur * 1000.0
                found = True
    return ms if found else None


def per_cycle(ctx, ms: Optional[float]) -> Optional[float]:
    """A window total as a mean over the window's cycles; nothing where
    nothing was read."""
    if ms is None or not ctx.n_cycles:
        return None
    return ms / ctx.n_cycles


def host_intervals(records, offset_ns: float) -> List[tuple]:
    """(path, start ns, end ns) of every span, on the profiler's clock."""
    out = []
    for rec in records:
        for path, s in walk(rec.root):
            t0 = s.t0 * 1e9 + offset_ns
            out.append((path, int(t0), int(t0 + s.dur * 1e9)))
    return out
