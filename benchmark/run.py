#!/usr/bin/env python3
"""The benchmark's one command: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (the cluster and every job's objects from the seed, the
compile cache, warm-up through the cell's own traffic), then the window
of ``--seconds``, then the output check. With ``--trace 0`` the result
line holds the cell's end-to-end metrics; with ``--trace 1`` the flight
recorder is on, the window runs under the profiler, and the line holds
the per-layer metrics. The last line of standard output is the result;
the numbers compared for ``correct`` are the last lines of standard
error. With no accelerator, or fewer chips than the cell asks for, it
exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402
from pathlib import Path   # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))
sys.path.insert(0, str(BENCH))
# libtpu's logs stay inside the checkout, not at a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", str(BENCH / ".out" / "tpu_logs"))
# JAX's persistent compile cache at one fixed path inside the checkout,
# whatever the machine sets: the path is part of every cache key
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".out" / "jax_cache")


def require_devices(chips: int) -> dict:
    """The device as JAX reports it; exits 3 without an accelerator or
    with fewer chips than the cell asks for (no fallback to the CPU)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] == "cpu" or len(devs) < chips:
        print(f"[bench] need {chips} accelerator chip(s); JAX has "
              f"{len(devs)} {info['platform']} device(s)", file=sys.stderr)
        sys.exit(3)
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    spec = harness.load_cell(args.workload)
    device = require_devices(int(spec["cell"]["chips"]))
    out = harness.execute(spec, args.seed, args.seconds, bool(args.trace),
                          device, T_START)
    for k, v in out["info"].items():
        print(f"info {k} {json.dumps(v)}", flush=True)
    res = out["result"]
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
