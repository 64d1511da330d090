"""The trace reduction against a small trace recorded on a v5e chip
(tests/record_trace.py): three calls of the Pallas placement kernel,
each in a ``bench.cycle``, 50 ms of ``bench.wait`` after each."""

from pathlib import Path

import pytest

import device_trace

DATA = Path(__file__).resolve().parent / "data" / "kernel_trace.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return device_trace.reduce(str(DATA), ["gang_allocate_pallas"])


def test_window_and_busy(summary):
    assert summary["devices"] == 1
    assert summary["window_s"] == pytest.approx(0.166531483)
    # three kernel runs of ~2.8 ms: busy well under the window
    assert 0.007 < summary["busy_s"] < 0.010


def test_kernel_events_by_stable_name(summary):
    k = summary["kernels"]["gang_allocate_pallas"]
    assert k["count"] == 3
    assert k["s"] == pytest.approx(3 * 2.8e-3, rel=0.01)
    assert k["s"] >= summary["busy_s"] * 0.99


def test_ops_and_idle_gaps(summary):
    assert summary["device_ops"][0][0] == "_pallas_gang_allocate.1"
    # the three longest gaps are the host's 50 ms waits
    top = summary["idle_gaps"][:3]
    assert [n for n, _ in top] == ["bench.wait"] * 3
    assert all(0.05 < s < 0.06 for _, s in top)
    idle = summary["window_s"] - summary["busy_s"]
    assert sum(s for _, s in summary["idle_gaps"]) <= idle + 1e-9


def test_flight_recorder_spans_name_gaps():
    s = device_trace.reduce(
        str(DATA), [], host_spans=[("cycle", 0, 10 ** 12),
                                   ("cycle/action:allocate", 0, 10 ** 12)])
    assert s["idle_gaps"][0][0] == "cycle/action:allocate"


def test_union():
    assert device_trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
