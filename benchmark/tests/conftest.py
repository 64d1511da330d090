"""The benchmark's own tests, outside the repo's tier-1 ``tests/``: CPU
rehearsals at tiny sizes, steered from here (never through an option of
the benchmark's command)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))
sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402


def shrink(spec: dict) -> dict:
    """A cell cut to a size the CPU runs in seconds, with the Pallas
    kernel in interpret mode (``kernel: pallas`` on the CPU)."""
    c, t = spec["config"], spec["traffic"]
    c["solver_arguments"] = {"kernel": "pallas"}
    if t["arrival"]["kind"] == "burst":
        # the burst cell's pruned path, which 48 nodes would not engage
        c["solver_arguments"].update({"prune.enable": "true",
                                      "prune.k": "4"})
        c["nodes"]["count"] = 48
        t["arrival"]["jobs"] = 40
        t["arrival"]["period_s"] = 3.0
        t["completion"]["offset_s"] = 1.0
        t["completion"]["within_s"] = 1.0
        t["drain_grace_s"] = 10.0
    else:
        c["nodes"]["count"] = 40
        t["arrival"]["pending_jobs"] = 30
        c["job_duration_s"] = [2, 8]
        t["warmup"]["cycles"] = 3
    return spec


@pytest.fixture
def tiny(monkeypatch):
    """run.main at tiny sizes on the CPU: the cell shrunk, and the look for
    an accelerator answered with the CPU."""
    import harness
    import run
    orig = harness.load_cell
    monkeypatch.setattr(harness, "load_cell",
                        lambda name, root=harness.ROOT: shrink(orig(name,
                                                                    root)))
    monkeypatch.setattr(run, "require_devices", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    return run


def last_line(capsys) -> dict:
    import json
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
