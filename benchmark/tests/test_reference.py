"""The plain reference against the program's plain scan kernel on seeded
synthetic problems: the same answer, a zero gap, and a control in
bfloat16 that reads far from it. (The test may import the program; the
reference itself does not.)"""

import ml_dtypes
import numpy as np
import pytest

from check import reference


def _call(n_tasks, n_nodes, seed, n_queues):
    import jax.numpy as jnp
    from volcano_tpu.ops.allocate import gang_allocate
    from volcano_tpu.ops.score import ScoreWeights
    from volcano_tpu.utils.synth import synth_arrays
    sa = synth_arrays(n_tasks, n_nodes, seed=seed, n_queues=n_queues,
                      utilization=0.6)
    w = ScoreWeights.make(sa.group_req.shape[1], binpack=5.0)
    out = gang_allocate(*[jnp.asarray(a) for a in sa.args], w,
                        allow_pipeline=True)
    return {"args": [np.asarray(a) for a in sa.args] +
            [tuple(np.asarray(x) for x in w)],
            "kwargs": {"allow_pipeline": True},
            "out": tuple(np.asarray(x) for x in out[:4])}


@pytest.mark.parametrize("n_tasks,n_nodes,seed,n_queues",
                         [(256, 64, 0, 1), (800, 128, 1, 3),
                          (2000, 256, 2, 4)])
def test_reference_agrees_with_scan(n_tasks, n_nodes, seed, n_queues):
    call = _call(n_tasks, n_nodes, seed, n_queues)
    r = reference.check(call)
    assert r["gap"] == 0.0 and r["invalid"] == 0 and r["lost"] == 0
    assert r["tasks"] > 0
    assign = reference.place(call)[0]
    assert np.array_equal(assign, call["out"][0])


def test_bfloat16_control_reads_far():
    call = _call(2000, 256, 2, 4)
    control = reference.place(call, ml_dtypes.bfloat16)
    r = reference.check(dict(call, out=control))
    assert r["gap"] > 1.0 or r["invalid"] > 0
