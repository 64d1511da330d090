"""The resource width of each placement-kernel call: the ``kernel`` span's
``r`` (the cluster's dimensions) and ``r_pad`` (the sublanes the kernel
gave them) tags."""

import pytest

from tests.harness import Harness
from volcano_tpu.trace import tracer
from volcano_tpu.utils.test_utils import (build_node, build_pod,
                                          build_pod_group, build_queue)

CONF = """
actions: "enqueue, allocate"
tiers:
- plugins:
  - name: gang
- plugins:
  - name: predicates
  - name: proportion
  - name: binpack
configurations:
- name: solver
  arguments:
    kernel: {kernel}
"""


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracer.reset()
    yield
    tracer.disable()
    tracer.reset()


def _walk(span):
    yield span
    for c in span.children or ():
        yield from _walk(c)


def _place(r: int, kernel: str = "pallas") -> Harness:
    """One cycle's allocate over a cluster of ``r`` resource dimensions
    (cpu, memory and ``r - 2`` scalar kinds), inside a flight-recorder
    cycle."""
    kinds = [f"example.com/k{i:02d}" for i in range(r - 2)]
    h = Harness(CONF.format(kernel=kernel))
    h.add("queues", build_queue("default", weight=1))
    for i in range(4):
        h.add("nodes", build_node(f"n{i}", {"cpu": "8", "memory": "16Gi",
                                            **{k: "2" for k in kinds}}))
    h.add("podgroups", build_pod_group("pg", "ns1", "default", 2,
                                       phase="Inqueue"))
    for t in range(2):
        h.add("pods", build_pod("ns1", f"t{t}", "", "Pending",
                                {"cpu": "1", "memory": "1Gi",
                                 **({kinds[-1]: "1"} if kinds else {})},
                                "pg"))
    with tracer.cycle():
        h.run_actions("enqueue", "allocate").close_session()
    assert len(h.binds) == 2
    return h


def _kernel_spans():
    rec = tracer.last_record()
    return [s for s in _walk(rec.root) if s.name == "kernel"]


@pytest.mark.parametrize("r,kernel,r_pad", [
    (3, "pallas", 8), (10, "pallas", 16), (16, "pallas", 16),
    (10, "scan", 10)])
def test_kernel_span_carries_r_and_r_pad(r, kernel, r_pad):
    tracer.enable()
    _place(r, kernel)
    spans = _kernel_spans()
    assert spans
    for s in spans:
        assert s.tags["r"] == r and s.tags["r_pad"] == r_pad
        assert {"g_pad", "n_pad", "t_pad"} <= set(s.tags)
