"""Pallas gang-allocate kernel tests (CPU interpret-mode parity vs the XLA
scan — runs in the default CI loop; the compiled kernel itself is exercised
on TPU hardware by the bench/validation flow).

Equivalence contract vs ops.allocate.gang_allocate: ready/kept match
exactly; assignments may differ only on sub-ulp score near-ties (two
proportionally identical nodes), so the check validates placement
feasibility and per-job score-equivalence instead of bit equality — see
docs/design/tpu-solver.md.
"""

import numpy as np
import pytest


def _run_pair(seed, n_tasks=200, n_nodes=60, gang=4, r=4):
    import jax.numpy as jnp

    from volcano_tpu.ops.allocate import gang_allocate
    from volcano_tpu.ops.pallas_allocate import gang_allocate_pallas
    from volcano_tpu.ops.score import ScoreWeights
    from volcano_tpu.utils.synth import synth_arrays
    sa = synth_arrays(n_tasks, n_nodes, gang_size=gang, seed=seed,
                      utilization=0.4, r=r)
    weights = ScoreWeights.make(sa.group_req.shape[1], binpack=1.0)
    args = [jnp.asarray(a) for a in sa.args] + [weights]
    ref = gang_allocate(*args)
    got = gang_allocate_pallas(*args, interpret=True)
    return sa, [np.asarray(x) for x in ref[:4]], [np.asarray(x) for x in got[:4]]


def _replay_feasible(sa, assign, pipelined):
    """Every committed placement must fit the running capacity: allocated
    tasks consume idle, pipelined tasks consume future (releasing) capacity
    by design, so they replay against node_future instead."""
    idle = np.asarray(sa.node_idle).copy()
    future = np.asarray(sa.node_future).copy()
    task_group = np.asarray(sa.task_group)
    group_req = np.asarray(sa.group_req)
    eps = np.asarray(sa.eps)
    for t in np.where(assign >= 0)[0]:
        req = group_req[task_group[t]]
        future[assign[t]] -= req
        if not pipelined[t]:
            idle[assign[t]] -= req
    tol = -eps[None, :] - 1e-3
    return bool(np.all(idle >= tol) and np.all(future >= tol))


# r = 4 keeps the ids its cases always had; past eight dimensions the
# kernel pads the resource axis to 16 sublanes
CASES = [pytest.param(4, seed, id=str(seed)) for seed in range(4)] + [
    pytest.param(r, seed, id=f"r{r}-{seed}")
    for r in (10, 16) for seed in range(4)]


class TestPallasEquivalence:
    @pytest.mark.parametrize("r,seed", CASES)
    def test_ready_kept_and_feasibility(self, r, seed):
        sa, (a1, p1, r1, k1), (a2, p2, r2, k2) = _run_pair(seed, r=r)
        if r > 8:
            # the gangs ask for the kinds past the eighth
            assert np.asarray(sa.group_req)[:, 8:].any()
        assert np.array_equal(r1, r2), "ready sets must match"
        assert np.array_equal(k1, k2), "kept sets must match"
        # same number of placements per job
        tj = np.asarray(sa.task_job)
        for j in np.where(r1 | k1)[0]:
            span = tj == j
            assert np.sum(a1[span] >= 0) == np.sum(a2[span] >= 0)
        assert _replay_feasible(sa, a2, p2)


@pytest.mark.parametrize("r,pad", [(1, 8), (3, 8), (8, 8), (9, 16),
                                   (16, 16), (17, 24)])
def test_resource_pad(r, pad):
    from volcano_tpu.ops.pallas_allocate import fits_resources, resource_pad
    assert resource_pad(r) == pad
    assert fits_resources(r) is (r <= 16)
