#!/usr/bin/env python3
"""Readings for the limits (PERF.md): the program's sound runs and the
control's, at the cell's own size, each judged by the harness's verdict.

    python3 benchmark/tests/control.py --dumps '<dir>/*.npz'
    python3 benchmark/tests/control.py --workload <cell> --seconds 12 \\
        --seeds 1,2,3 --control-seeds 1,2,3

With ``--dumps`` it reads the calls that runs of the cell checked
(``collect.py`` keeps them): for each, the sound reading (the program's
answer against the reference on the derived tables) and the control's
(the program's answer replaced by the plain reference computed in
bfloat16, the precision below the float32 that the configuration
states, on the inputs the program was given), each through
``compare.verdict``. Without it, it runs the cell in one process for
each seed as the benchmark does (set-up, warm-up, a short window at the
cell's own load, the drain) and reads the same.
"""

import argparse
import glob
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))
sys.path.insert(0, str(BENCH))


def control_readings(calls) -> dict:
    import ml_dtypes
    from check import compare, reference
    ctl = [dict(c, out=reference.place(c, ml_dtypes.bfloat16))
           for c in calls]
    nums = compare.kernel_readings(ctl)
    ok, _ = compare.verdict(nums)
    return {"correct": ok, **nums}


def sound_readings(calls) -> dict:
    from check import compare
    nums = compare.kernel_readings(calls)
    ok, _ = compare.verdict(nums)
    return {"correct": ok, **nums}


def from_dumps(pattern: str) -> int:
    from check import compare
    for path in sorted(glob.glob(pattern)):
        t = time.perf_counter()
        calls = [compare.load(path)]
        row = {"call": Path(path).name, "sound": sound_readings(calls),
               "control": control_readings(calls),
               "s": None}
        row["s"] = time.perf_counter() - t
        print("reading " + json.dumps(row), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dumps")
    ap.add_argument("--workload")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    if args.dumps:
        return from_dumps(args.dumps)
    import harness
    import run
    spec0 = harness.load_cell(args.workload)
    device = run.require_devices(int(spec0["cell"]["chips"]))
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",") if s):
        spec = harness.load_cell(args.workload)
        t = time.perf_counter()
        out = harness.execute(spec, seed, args.seconds, False, device,
                              time.perf_counter(), keep_sample=True)
        res = out["result"]
        row = {"seed": seed, "correct": res["correct"],
               "sound": {k: c["value"] for k, c in res["checks"].items()},
               "attempted": res["attempted"], "failed": res["failed"],
               "info": out["info"]}
        if seed in controls:
            row["control"] = control_readings(out["sample"])
        row["s"] = time.perf_counter() - t
        print("reading " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
