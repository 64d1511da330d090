"""The readers of the compile, dispatch and readback spans against
synthetic flight-recorder records whose values are worked out by hand,
against records of a program that has no such spans, and in a tiny
traced run."""

from types import SimpleNamespace

import pytest

import compile_spans
import harness
from conftest import last_line
from volcano_tpu.trace.tracer import CycleRecord, Span


def span(name, t0, t1, children=(), **tags):
    s = Span(name, t0)
    s.dur = t1 - t0
    s.tags = tags or None
    s.children = list(children) or None
    return s


def compile_(stage, t0, t1, children=()):
    return span("compile", t0, t1, children, fun="f", stage=stage)


def record(seq=1):
    """One cycle of 10 s: a kernel call whose dispatch holds a trace
    (with a nested trace inside it), a lowering and a backend compile, a
    0.9 s readback, and a backend compile in the session's open."""
    dispatch = span("dispatch", 1.0, 3.0, [
        compile_("trace", 1.1, 1.6, [compile_("trace", 1.2, 1.4)]),
        compile_("lower", 1.6, 1.8),
        compile_("backend", 1.8, 2.8)])
    execute = span("execute", 1.0, 4.0,
                   [dispatch, span("readback", 3.0, 3.9)])
    place = span("solver.place", 0.5, 4.5,
                 [span("kernel", 0.9, 4.1, [execute], kernel="k")])
    root = span("cycle", 0.0, 10.0, [
        span("open_session", 5.0, 6.0, [compile_("backend", 5.2, 5.7)]),
        span("action:allocate", 0.2, 4.8, [place])])
    return CycleRecord(seq, 0.0, root)


def ctx(records, n_cycles=2):
    return SimpleNamespace(records=records, n_cycles=n_cycles)


def read(name, c):
    return harness._reader(name)(c)


def test_union_of_nested_and_overlapping_compiles():
    nested = [compile_("trace", 1.1, 1.6), compile_("trace", 1.2, 1.4),
              compile_("lower", 1.5, 1.8), compile_("backend", 2.0, 2.5)]
    assert compile_spans.union_ms(nested) == pytest.approx(1200.0)
    assert compile_spans.union_ms([]) == 0.0


def test_compile_ms_is_the_union_per_cycle():
    # (1.1-1.6) + (1.6-1.8) + (1.8-2.8) + (5.2-5.7) = 2.2 s; the nested
    # trace adds nothing; over 2 cycles
    for name in ("compile_ms", "compile_ms.latency"):
        assert read(name, ctx([record()])) == pytest.approx(1100.0)


def test_dispatch_less_its_compiles_and_readback():
    # dispatch 2.0 s less its 1.7 s of compiles; readback 0.9 s
    c = ctx([record(1), record(2)], n_cycles=4)
    for suffix in ("", ".latency"):
        assert read("kernel_dispatch_ms" + suffix, c) == \
            pytest.approx(2 * 300.0 / 4)
        assert read("kernel_readback_ms" + suffix, c) == \
            pytest.approx(2 * 900.0 / 4)


def test_nothing_to_read_gives_nothing(monkeypatch):
    """A program that has no dispatch, readback or compile spans, as an
    older one has not: each reader gives nothing and does not raise."""
    plain = CycleRecord(1, 0.0, span("cycle", 0.0, 1.0, [
        span("action:allocate", 0.1, 0.9, [span("solver.place", 0.2, 0.8, [
            span("kernel", 0.3, 0.7, [span("execute", 0.3, 0.7)])])])]))
    assert read("kernel_dispatch_ms", ctx([plain])) is None
    assert read("kernel_readback_ms.latency", ctx([plain])) is None
    assert read("compile_ms", ctx([plain])) == 0.0
    monkeypatch.setattr(compile_spans, "recorded", lambda: False)
    assert read("compile_ms", ctx([plain])) is None
    assert read("compile_ms.latency", ctx([])) is None


def test_traced_run_reports_the_new_metrics(tiny, capsys):
    tiny.main(["--workload", "fleet10k-burst", "--seed", str(2 ** 31 + 23),
               "--seconds", "6", "--trace", "1"])
    res = last_line(capsys)
    assert res["correct"] is True
    for name in ("compile_ms.latency", "kernel_dispatch_ms.latency",
                 "kernel_readback_ms.latency"):
        assert res["metrics"][name]["value"] >= 0.0
        assert res["metrics"][name]["unit"] == "ms"
