#!/usr/bin/env python3
"""Records the small chip trace that tests/test_device_trace.py reads
(``data/kernel_trace.xplane.pb``): three calls of the program's Pallas
placement kernel at a small size, each inside a ``bench.cycle``
annotation, 50 ms of ``bench.wait`` after each, all inside
``bench.window``. Run on the chip:

    python3 benchmark/tests/record_trace.py <out.xplane.pb>
"""

import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from volcano_tpu.ops.pallas_allocate import gang_allocate_pallas
    from volcano_tpu.ops.score import ScoreWeights
    from volcano_tpu.utils.synth import synth_arrays
    sa = synth_arrays(2048, 512, seed=0, n_queues=4)
    w = ScoreWeights.make(sa.group_req.shape[1], binpack=5.0)
    args = [jnp.asarray(a) for a in sa.args] + [w]
    interpret = jax.default_backend() == "cpu"
    run = lambda: gang_allocate_pallas(*args, interpret=interpret)[0]
    run().block_until_ready()            # compile outside the trace
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.cycle"):
                run().block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, out)
    print(f"recorded {out} ({os.path.getsize(out)} bytes) on "
          f"{jax.devices()[0].device_kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
