"""Readings of the flight recorder's ``compile`` spans (JAX's trace,
lowering and backend compile, each under the span open on the compiling
thread). A stage's own nested compiles are its children, so a total over
compile spans is the union of their intervals, not their sum."""

from __future__ import annotations

from typing import Iterable, List


def recorded() -> bool:
    """Whether the program records compiles (it names the counter the
    same listener feeds); where it does not, there is nothing to read."""
    from volcano_tpu.metrics import metrics as m
    return hasattr(m, "JIT_COMPILES")


def under(span) -> List:
    """Every ``compile`` span below ``span``."""
    out = []
    for c in span.children or ():
        if c.name == "compile":
            out.append(c)
        out.extend(under(c))
    return out


def union_ms(compiles: Iterable) -> float:
    """The length of the union of the spans' intervals, in ms."""
    total, end = 0.0, None
    for t0, t1 in sorted((s.t0, s.t0 + s.dur) for s in compiles):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total * 1000.0
