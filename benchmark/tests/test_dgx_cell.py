"""The ``dgxmig5k-burst`` cell: ten resource dimensions, so the Pallas
kernel's resource axis takes 16 sublanes. The traffic's mix, the cell
through run.py at a tiny size on the CPU (conftest.tiny), and a node table
that lost one node's ``nvidia.com/mig-3g.40gb`` (the ninth column, past the
eight sublanes the kernel held until it took 16) read as a mismatch."""

import numpy as np

import harness
from conftest import last_line

CELL = "dgxmig5k-burst"
MIG = "nvidia.com/mig-3g.40gb"


def test_a_burst_holds_the_stated_mix():
    """One burst of ``mixed-burst`` over ``dgx-mig-5k``: 14,000 jobs,
    19,544 pods, about a third of every accelerator kind, whatever the
    seed (the draw is stratified in blocks of 1,000)."""
    import json
    from traffic.generator import Traffic, quantity
    config = json.loads((harness.BENCH_DIR / "configs" /
                         "dgx-mig-5k.json").read_text())
    mix = json.loads((harness.BENCH_DIR / "traffic" /
                      "mixed-burst.json").read_text())
    _, warm, window = Traffic(config, mix, 2 ** 33 + 1).initial(50.0, [])
    assert len(warm) == len(window) == 1
    jobs = window[0][2]
    assert len(jobs) == 14_000 and sum(j.tasks for j in jobs) == 19_544
    alloc = config["nodes"]["allocatable"]
    n = config["nodes"]["count"]

    def share(r):
        asked = sum(j.tasks * quantity(r, j.requests.get(r, "0"))
                    for j in jobs)
        return asked / (n * quantity(r, alloc[r]))
    for r in ("nvidia.com/gpu", "nvidia.com/mig-1g.10gb",
              "nvidia.com/mig-2g.20gb", "nvidia.com/mig-3g.40gb"):
        assert 0.31 < share(r) < 0.33, r
    assert 0.30 < share("cpu") < 0.32
    assert 0.003 < share("rdma/rdma_shared_device_a") < 0.005
    assert {r for j in jobs for r in j.requests} == set(alloc) - {"pods"}


def _run(tiny, capsys, trace=0, seed=2 ** 33 + 25):
    tiny.main(["--workload", CELL, "--seed", str(seed), "--seconds", "6",
               "--trace", str(trace)])
    return last_line(capsys)


def test_traced_run_is_correct_and_reads_the_burst_layers(tiny, capsys):
    """The burst path's span metrics, as the fleet's burst cell has them."""
    res = _run(tiny, capsys, trace=1)
    assert res["correct"] is True, res["checks"]
    for name in ("open_session_ms.latency", "close_session_ms.latency",
                 "allocate_host_ms.latency", "build_context_ms.latency",
                 "bind_flush_ms"):
        assert name in res["metrics"], sorted(res["metrics"])


def test_bfloat16_control_is_incorrect(tiny, capsys):
    """The plain reference in bfloat16 in the program's place reads
    incorrect on this cell too."""
    import faults
    undo = faults.plant("control")
    try:
        res = _run(tiny, capsys, seed=5)
    finally:
        undo()
    assert res["correct"] is False, res["checks"]


def _zero_mig():
    """The context build's node table loses node 0's MIG 3g.40gb slices."""
    from volcano_tpu.framework.solver import BatchSolver
    orig = BatchSolver._build_context

    def build(self, *args, **kwargs):
        out = orig(self, *args, **kwargs)
        col = self.rindex.index[MIG]
        assert col >= 8
        out[0].allocatable[0, col] = 0.0
        return out

    BatchSolver._build_context = build
    return lambda: setattr(BatchSolver, "_build_context", orig)


def test_zeroed_mig_column_is_a_mismatch(tiny, capsys):
    undo = _zero_mig()
    try:
        res = _run(tiny, capsys, seed=5)
    finally:
        undo()
    assert res["checks"]["input_mismatches"]["value"] > 0
    assert res["correct"] is False
    assert np.isfinite(res["checks"]["placement_gap"]["value"])
