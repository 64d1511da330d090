"""Solver-level kernel selection: `solver` conf arg `kernel: pallas` must
produce the same production-path placements as the XLA scan (interpret mode
off-TPU). This is the parity proof that the Pallas kernel is reachable from
the scheduler's own hot path, not just the bench harness.

Reference hot path: pkg/scheduler/actions/allocate/allocate.go:201-262.
"""

import numpy as np
import pytest

from tests.harness import Harness
from volcano_tpu.utils.test_utils import (build_node, build_pod,
                                          build_pod_group, build_queue,
                                          build_resource_list)

CONF_SCAN = """
actions: "enqueue, allocate"
tiers:
- plugins:
  - name: priority
  - name: gang
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
"""

CONF_PALLAS = CONF_SCAN + """
configurations:
- name: solver
  arguments:
    kernel: pallas
"""


def _populate(h, n_jobs=3, gang=4, n_nodes=8):
    h.add("queues", build_queue("default", weight=1))
    for i in range(n_nodes):
        h.add("nodes", build_node(f"n{i}", {"cpu": "8", "memory": "16Gi"}))
    for j in range(n_jobs):
        h.add("podgroups", build_pod_group(f"pg{j}", "ns1", "default", gang,
                                           phase="Inqueue"))
        for t in range(gang):
            h.add("pods", build_pod(
                "ns1", f"j{j}-t{t}", "", "Pending",
                build_resource_list(str(1 + j), "1Gi"), f"pg{j}"))
    return h


def test_pallas_kernel_conf_selected():
    h = _populate(Harness(CONF_PALLAS))
    ssn = h.open_session()
    assert ssn.solver.kernel == "pallas"
    fn, kwargs = ssn.solver._select_kernel()
    assert fn.__name__ == "gang_allocate_pallas"
    assert kwargs.get("interpret") is True  # CPU backend in tests
    h.close_session()


@pytest.mark.parametrize("conf,n_tasks,kernel", [
    (CONF_SCAN, 50_176, "gang_allocate_pallas"),       # auto, fits SMEM
    (CONF_SCAN, 500_736, "gang_allocate"),             # auto, too large
    (CONF_PALLAS, 500_736, "gang_allocate_chunked"),   # forced, too large
], ids=["auto-fits", "auto-too-large", "forced-too-large"])
def test_pallas_tier_chosen_by_smem_budget(monkeypatch, conf, n_tasks,
                                           kernel):
    """On a TPU the Pallas tier is chosen up front only where its
    scalar-prefetch operands fit SMEM (tests/test_tpu_compile.py brackets
    the bound against the chip's compiler); larger batches go to the XLA
    kernels instead of crashing into the breaker."""
    from types import SimpleNamespace

    from volcano_tpu.framework import solver as solver_mod
    h = _populate(Harness(conf))
    ssn = h.open_session()
    monkeypatch.setattr(solver_mod.jax, "default_backend", lambda: "tpu")
    j = n_tasks // 8
    batch = SimpleNamespace(t_pad=n_tasks, j_pad=j, g_pad=j,
                            pool_queue=np.zeros(8, np.int32))
    fn, kwargs = ssn.solver._select_kernel(batch)
    assert fn.__name__ == kernel
    assert "interpret" not in kwargs or kwargs["interpret"] is False
    h.close_session()


def test_pallas_refuses_a_backend_it_cannot_run_on(monkeypatch):
    """Interpret mode is for the CPU the tests run on; anywhere else a
    forced Pallas kernel fails loudly instead of emulating."""
    from volcano_tpu.framework import solver as solver_mod
    h = _populate(Harness(CONF_PALLAS))
    ssn = h.open_session()
    monkeypatch.setattr(solver_mod.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        ssn.solver._select_kernel()
    h.close_session()


def test_pallas_solver_path_matches_scan():
    h_scan = _populate(Harness(CONF_SCAN))
    h_scan.run_actions("enqueue", "allocate").close_session()
    h_pl = _populate(Harness(CONF_PALLAS))
    h_pl.run_actions("enqueue", "allocate").close_session()
    assert h_scan.binds and h_scan.binds == h_pl.binds


def test_pallas_gang_rollback_matches_scan():
    """An unplaceable gang must roll back identically through both kernels."""
    def env(conf):
        h = Harness(conf)
        h.add("queues", build_queue("default", weight=1))
        h.add("nodes", build_node("n0", {"cpu": "4", "memory": "8Gi"}))
        h.add("podgroups", build_pod_group("big", "ns1", "default", 3,
                                           phase="Inqueue"))
        for t in range(3):
            h.add("pods", build_pod("ns1", f"b{t}", "", "Pending",
                                    build_resource_list("3", "1Gi"), "big"))
        h.add("podgroups", build_pod_group("ok", "ns1", "default", 2,
                                           phase="Inqueue"))
        for t in range(2):
            h.add("pods", build_pod("ns1", f"o{t}", "", "Pending",
                                    build_resource_list("1", "1Gi"), "ok"))
        h.run_actions("enqueue", "allocate").close_session()
        return h
    h_scan, h_pl = env(CONF_SCAN), env(CONF_PALLAS)
    assert h_scan.binds == h_pl.binds
    assert set(h_pl.binds) == {"ns1/o0", "ns1/o1"}


def test_host_context_matches_device_context():
    """build_host_context (the preempt/reclaim path) must produce the
    same predicate mask and static score as the device _build_context."""
    import numpy as np

    h = _populate(Harness(CONF_SCAN), n_jobs=4, gang=3, n_nodes=12)
    # add constraints so selector/taint/fit all engage
    from volcano_tpu.models.objects import Taint
    ssn = h.open_session()
    ordered = [(job, list(job.tasks.values())) for job in ssn.jobs.values()]
    narr_d, batch_d, gmask_d, static_d = ssn.solver._build_context(ordered)
    narr_h, batch_h, gmask_h, static_h = \
        ssn.solver.build_host_context(ordered)
    assert narr_h.names == narr_d.names
    assert batch_h.job_uids == batch_d.job_uids
    np.testing.assert_array_equal(np.asarray(gmask_d), gmask_h)
    np.testing.assert_allclose(np.asarray(static_d), static_h, rtol=1e-6)
    h.close_session()


def test_scheduling_is_deterministic():
    """Same snapshot in, same bindings out (SURVEY §7: seeded tie-breaking
    replaces the reference's rand.Intn node selection)."""
    def run():
        h = _populate(Harness(CONF_SCAN), n_jobs=6, gang=4, n_nodes=16)
        h.run_actions("enqueue", "allocate").close_session()
        return dict(h.binds)
    first = run()
    assert first
    for _ in range(2):
        assert run() == first
