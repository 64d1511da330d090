"""Clusters of more than eight resource dimensions on the Pallas kernel.

The kernel pads the resource axis to 8 sublanes up to eight dimensions and
to 16 up to sixteen; only a wider cluster goes to an XLA kernel. The
end-to-end case is the node shape of ``benchmark/configs/dgx-mig-5k.json``:
a DGX A100 with MIG slices, RDMA and hugepages, ten dimensions, three of
them byte quantities held as milli-units in float32.
"""

import json
from pathlib import Path

import numpy as np
import pytest

from tests.harness import Harness
from volcano_tpu.apiserver import ObjectStore
from volcano_tpu.cache import SchedulerCache
from volcano_tpu.metrics import metrics as m
from volcano_tpu.models.quantity import milli_value, parse_quantity
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.utils.test_utils import (FakeBinder, FakeEvictor, build_node,
                                          build_pod, build_pod_group,
                                          build_queue)

DGX = Path(__file__).resolve().parents[1] / "benchmark" / "configs" / \
    "dgx-mig-5k.json"

CONF = """
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


def _solver_conf(kernel: str) -> str:
    return ("configurations:\n- name: solver\n  arguments:\n"
            f"    kernel: {kernel}\n")


def _cluster(h, r: int, n_nodes: int = 8):
    """Nodes with cpu, memory and ``r - 2`` scalar kinds; one gang asks
    for the last kind."""
    kinds = [f"example.com/k{i:02d}" for i in range(r - 2)]
    alloc = {"cpu": "8", "memory": "16Gi", **{k: "4" for k in kinds}}
    h.add("queues", build_queue("default", weight=1))
    for i in range(n_nodes):
        h.add("nodes", build_node(f"n{i}", dict(alloc)))
    h.add("podgroups", build_pod_group("pg", "ns1", "default", 2,
                                       phase="Inqueue"))
    for t in range(2):
        h.add("pods", build_pod("ns1", f"t{t}", "", "Pending",
                                {"cpu": "1", "memory": "1Gi",
                                 kinds[-1]: "1"}, "pg"))
    return h


@pytest.mark.parametrize("r,kernel", [
    (3, "gang_allocate_pallas"), (8, "gang_allocate_pallas"),
    (10, "gang_allocate_pallas"), (16, "gang_allocate_pallas"),
    (17, None)])
def test_select_kernel_on_tpu_by_resource_width(monkeypatch, r, kernel):
    """On a TPU backend `auto` keeps the Pallas kernel up to 16
    dimensions, and a wider cluster goes to an XLA kernel."""
    from volcano_tpu.framework import solver as solver_mod
    from volcano_tpu.ops.pallas_allocate import resource_pad
    h = _cluster(Harness(CONF), r)
    ssn = h.open_session()
    assert ssn.solver.rindex.r == r
    monkeypatch.setattr(solver_mod.jax, "default_backend", lambda: "tpu")
    fn, kwargs = ssn.solver._select_kernel()
    if kernel is None:
        assert fn.__name__ in ("gang_allocate_chunked", "gang_allocate")
    else:
        assert fn.__name__ == kernel and not kwargs
        assert resource_pad(r) == (8 if r <= 8 else 16)
    h.close_session()


def _draw_jobs(config: dict, n_jobs: int, seed: int):
    """(queue, shape) of ``n_jobs`` jobs drawn from the configuration's
    mix and its queues' demand."""
    rng = np.random.default_rng(seed)
    w = np.array([s["weight"] for s in config["jobs"]], float)
    d = np.array([q["demand"] for q in config["queues"]], float)
    shapes = rng.choice(len(w), n_jobs, p=w / w.sum())
    queues = rng.choice(len(d), n_jobs, p=d / d.sum())
    return [(config["queues"][q]["name"], config["jobs"][s])
            for q, s in zip(queues.tolist(), shapes.tolist())]


def _run_dgx(kernel: str, n_nodes: int = 64, n_jobs: int = 200,
             seed: int = 25):
    config = json.loads(DGX.read_text())
    store = ObjectStore()
    binder = FakeBinder(store)
    cache = SchedulerCache(store, binder=binder, evictor=FakeEvictor(store))
    cache.run()
    sched = Scheduler(store, cache=cache, scheduler_conf=(
        config["scheduler_conf"] + _solver_conf(kernel)))
    for q in config["queues"]:
        store.create("queues", build_queue(q["name"], weight=q["weight"]))
    alloc = config["nodes"]["allocatable"]
    for i in range(n_nodes):
        store.create("nodes", build_node(f"node-{i}", dict(alloc),
                                         labels={"rack": f"rack-{i % 32}"}))
    for j, (queue, shape) in enumerate(_draw_jobs(config, n_jobs, seed)):
        store.create("podgroups", build_pod_group(
            f"j{j}", "default", queue, shape["min_member"]))
        for t in range(shape["tasks"]):
            store.create("pods", build_pod(
                "default", f"j{j}-t{t}", "", "Pending",
                dict(shape["requests"]), groupname=f"j{j}"))
    for _ in range(2):
        sched.run_once()
    cache.flush_executors()
    return config, cache, dict(binder.binds)


def test_dgx_node_shape_pallas_binds_as_the_scan():
    """A 64-node cluster of the DGX node shape and a seeded draw of 200
    jobs of its mix, through ``Scheduler.run_once``: the Pallas kernel
    (interpret mode, 16 sublanes) binds what the scan binds."""
    runs0 = m.counter_total(m.SOLVER_KERNEL_RUNS, kernel="pallas")
    _, _, pallas = _run_dgx("pallas")
    assert m.counter_total(m.SOLVER_KERNEL_RUNS, kernel="pallas") > runs0
    _, _, scan = _run_dgx("scan")
    assert len(scan) > 100
    assert pallas == scan


def test_dgx_node_table_holds_byte_scalars_exactly():
    """The node table's allocatable and, after the binds, its idle rows
    equal the configuration's quantities exactly in float32: scalars in
    milli-units (27Ti of ephemeral-storage is 2.97e16), memory in MiB."""
    from volcano_tpu.framework import close_session, open_session
    from volcano_tpu.framework import parse_scheduler_conf
    from volcano_tpu.models.arrays import NodeArrays
    config, cache, binds = _run_dgx("scan", n_nodes=16, n_jobs=40)
    assert binds
    conf = parse_scheduler_conf(config["scheduler_conf"])
    ssn = open_session(cache, conf.tiers, conf.configurations)
    try:
        rindex = ssn.solver.rindex
        assert rindex.r == 10
        names = sorted(ssn.nodes)
        narr = NodeArrays.build(ssn.nodes, names, rindex)
    finally:
        close_session(ssn)

    def units(name, text):
        if name == "memory":
            return parse_quantity(text) / 2 ** 20
        return milli_value(text)

    alloc = config["nodes"]["allocatable"]
    want = np.array([units(r, alloc[r]) for r in rindex.names])
    assert np.array_equal(narr.allocatable[:len(names)].astype(np.float64),
                          np.broadcast_to(want, (len(names), len(want))))
    pods = {p.metadata.name: p for p in cache.store.list("pods")}
    used = {n: np.zeros(len(want)) for n in names}
    for key, node in binds.items():
        req = pods[key.split("/", 1)[1]].spec.containers[0].requests
        used[node] += [units(r, req.get(r, "0")) for r in rindex.names]
    for i, n in enumerate(names):
        assert np.array_equal(narr.idle[i].astype(np.float64),
                              want - used[n]), n
