"""Each cell through run.py at a tiny size on the CPU (conftest.tiny):
the result line's keys, ``correct`` true on a sound run, and false on a
run whose kernel answers were replaced by the bfloat16 control or broken
by a planted fault."""

import pytest

from conftest import last_line

CELLS = ["fleet10k-burst", "gpu5k-backlog"]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(tiny, capsys, cell, seed=2 ** 31 + 17, trace=0, seconds=6):
    tiny.main(["--workload", cell, "--seed", str(seed), "--seconds",
               str(seconds), "--trace", str(trace)])
    return last_line(capsys)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run(tiny, capsys, cell):
    res = _run(tiny, capsys, cell)
    assert list(res)[:5] == KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {"fleet10k-burst": {"setup_s", "pod_latency_p50_ms",
                               "pod_latency_p95_ms"},
            "gpu5k-backlog": {"setup_s", "cycle_ms"}}[cell]
    assert set(res["metrics"]) == want
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def test_traced_run(tiny, capsys):
    res = _run(tiny, capsys, "fleet10k-burst", trace=1)
    assert res["correct"] is True
    assert {"open_session_ms.latency", "allocate_host_ms.latency",
            "prune_distill_ms.latency", "bind_flush_ms"} <= \
        set(res["metrics"])
    assert not any(k.startswith("open_session_ms") and "." not in k
                   for k in res["metrics"])
    # the CPU has no device plane: no device metric is made up
    assert "device_idle_share.latency" not in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("kind,cell", [
    (k, c) for k in ("control", "alter", "drop_half", "mask_drop")
    for c in CELLS] + [("prune_drop", "fleet10k-burst")])
def test_broken_run_is_incorrect(tiny, capsys, cell, kind):
    import faults
    undo = faults.plant(kind)
    try:
        res = _run(tiny, capsys, cell, seed=5)
    finally:
        undo()
    assert res["correct"] is False, res["checks"]
