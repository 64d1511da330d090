#!/usr/bin/env python3
"""One run of a cell exactly as ``benchmark/run.py`` makes it, that also
keeps the calls it checked, for ``control.py --dumps`` to read the
control from afterwards:

    python3 benchmark/tests/collect.py --dump <dir> --workload <cell> \\
        --seed <n> --seconds <s> --trace <0|1>

The calls are written after the window and the check, as
``<dir>/<cell>-<seed>-<i>.npz``.
"""

import sys
import time

T_START = time.perf_counter()

from pathlib import Path   # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))
sys.path.insert(0, str(BENCH))

import run   # noqa: E402


def main(argv) -> int:
    i = argv.index("--dump")
    out_dir = argv[i + 1]
    argv = argv[:i] + argv[i + 2:]
    cell = argv[argv.index("--workload") + 1]
    seed = argv[argv.index("--seed") + 1]
    import harness
    from check import compare
    orig = harness.execute

    def execute(*args, **kwargs):
        kwargs["keep_sample"] = True
        out = orig(*args, **kwargs)
        sample = out.pop("sample", None)
        if sample is not None:
            compare.dump(sample, Path(out_dir) / f"{cell}-{seed}")
        return out

    harness.execute = execute
    run.T_START = T_START
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
