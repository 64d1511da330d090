"""numaaware plugin tests (reference: pkg/scheduler/plugins/numaaware/
policy/policy_*_test.go + provider/cpumanager/cpu_mng_test.go + an
action-level admission scenario).
"""

import pytest

from tests.harness import Harness
from volcano_tpu.models.objects import (Container, CpuInfo, NumaResInfo,
                                        Numatopology, ObjectMeta)
from volcano_tpu.plugins.numaaware import is_guaranteed
from volcano_tpu.plugins.numaaware.cpumanager import (
    CPUDetails, CpuManager, generate_cpu_topology_hints, guaranteed_cpus,
    take_by_topology)
from volcano_tpu.plugins.numaaware.policy import (
    PolicyBestEffort, PolicyRestricted, PolicySingleNumaNode, TopologyHint,
    mask_bits, mask_of, merge_filtered_hints)
from volcano_tpu.utils.test_utils import (build_node, build_pod,
                                          build_pod_group, build_queue)


def hint(bits, preferred):
    return TopologyHint(mask_of(bits) if bits is not None else None, preferred)


class TestPolicyMerge:
    """policy_best_effort_test.go / policy_restricted_test.go shapes."""

    def test_single_provider_single_hint(self):
        best, admit = PolicyBestEffort([0, 1]).predicate(
            [{"cpu": [hint([0], True)]}])
        assert admit and mask_bits(best.affinity) == [0] and best.preferred

    def test_two_resources_intersect(self):
        best, admit = PolicyBestEffort([0, 1]).predicate(
            [{"cpu": [hint([0, 1], True), hint([0], True)],
              "gpu": [hint([0], True)]}])
        assert admit and mask_bits(best.affinity) == [0] and best.preferred

    def test_best_effort_admits_non_preferred(self):
        best, admit = PolicyBestEffort([0, 1]).predicate(
            [{"cpu": [hint([0, 1], False)]}])
        assert admit and not best.preferred

    def test_restricted_rejects_non_preferred(self):
        best, admit = PolicyRestricted([0, 1]).predicate(
            [{"cpu": [hint([0, 1], False)]}])
        assert not admit

    def test_restricted_admits_preferred(self):
        best, admit = PolicyRestricted([0, 1]).predicate(
            [{"cpu": [hint([1], True)]}])
        assert admit and mask_bits(best.affinity) == [1]

    def test_single_numa_rejects_multi_node_hint(self):
        best, admit = PolicySingleNumaNode([0, 1]).predicate(
            [{"cpu": [hint([0, 1], True)]}])
        assert not admit

    def test_single_numa_admits_single_node(self):
        best, admit = PolicySingleNumaNode([0, 1]).predicate(
            [{"cpu": [hint([0, 1], True), hint([1], True)]}])
        assert admit and mask_bits(best.affinity) == [1]

    def test_no_opinion_provider_is_any_numa(self):
        best, admit = PolicyRestricted([0, 1]).predicate([None])
        assert admit and best.preferred
        assert mask_bits(best.affinity) == [0, 1]

    def test_empty_hint_list_is_unpreferred(self):
        best, admit = PolicyRestricted([0, 1]).predicate(
            [{"cpu": []}])
        assert not admit

    def test_narrower_preferred_wins(self):
        merged = merge_filtered_hints(
            [0, 1], [[hint([0, 1], True), hint([0], True)]])
        assert mask_bits(merged.affinity) == [0]


def make_detail(cpus_per_numa=4, numa_count=2):
    """cpu ids laid out numa-major, 2 cpus per core."""
    detail = {}
    cpu_id = 0
    for numa in range(numa_count):
        for core in range(cpus_per_numa // 2):
            for _ in range(2):
                detail[cpu_id] = CpuInfo(numa_id=numa, socket_id=numa,
                                         core_id=core)
                cpu_id += 1
    return detail


class TestCpuManager:
    def test_take_whole_socket_first(self):
        details = CPUDetails(make_detail())
        taken = take_by_topology(details, set(range(8)), 4)
        # one whole socket (numa 0) taken
        assert taken == {0, 1, 2, 3}

    def test_take_core_packing(self):
        details = CPUDetails(make_detail())
        # cpu 0 already used; ask for 2 -> prefer the fully-free core (2,3)
        taken = take_by_topology(details, set(range(8)) - {0}, 2)
        assert taken == {2, 3}

    def test_take_insufficient_raises(self):
        details = CPUDetails(make_detail())
        with pytest.raises(ValueError):
            take_by_topology(details, {0, 1}, 3)

    def test_guaranteed_cpus_integral_only(self):
        assert guaranteed_cpus(Container(requests={"cpu": "2"})) == 2
        assert guaranteed_cpus(Container(requests={"cpu": "1500m"})) == 0
        assert guaranteed_cpus(Container(requests={})) == 0

    def test_hints_prefer_fewest_numa_nodes(self):
        details = CPUDetails(make_detail())
        hints = generate_cpu_topology_hints(set(range(8)), details, 2)
        by_mask = {tuple(mask_bits(h.affinity)): h.preferred for h in hints}
        assert by_mask[(0,)] is True
        assert by_mask[(1,)] is True
        assert by_mask[(0, 1)] is False

    def test_hints_request_exceeding_single_node(self):
        details = CPUDetails(make_detail())
        hints = generate_cpu_topology_hints(set(range(8)), details, 6)
        by_mask = {tuple(mask_bits(h.affinity)): h.preferred for h in hints}
        assert by_mask == {(0, 1): True}

    def test_allocate_aligns_to_hint(self):
        mng = CpuManager()
        from volcano_tpu.models.numa_info import NumatopoInfo, ResourceInfo
        topo = NumatopoInfo("n1")
        topo.cpu_detail = make_detail()
        container = Container(requests={"cpu": "2"}, limits={"cpu": "2"})
        assign = mng.allocate(container, hint([1], True), topo,
                              {"cpu": set(range(8))})
        assert assign["cpu"] <= {4, 5, 6, 7} and len(assign["cpu"]) == 2


def guaranteed_pod(ns, name, group, cpu="2", policy=""):
    pod = build_pod(ns, name, "", "Pending",
                    {"cpu": cpu, "memory": "1Gi"}, group)
    c = pod.spec.containers[0]
    c.limits = dict(c.requests)
    if policy:
        pod.metadata.annotations["volcano.sh/numa-topology-policy"] = policy
    return pod


def numa_crd(node_name, cpus_per_numa=4, numa_count=2,
             tm_policy="single-numa-node"):
    detail = make_detail(cpus_per_numa, numa_count)
    return Numatopology(
        metadata=ObjectMeta(name=node_name),
        policies={"CPUManagerPolicy": "static",
                  "TopologyManagerPolicy": tm_policy},
        numa_res={"cpu": NumaResInfo(allocatable=sorted(detail.keys()),
                                     capacity=len(detail))},
        cpu_detail=detail)


CONF = """
actions: "allocate"
tiers:
- plugins:
  - name: gang
  - name: priority
- plugins:
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: numa-aware
"""


class TestNumaAwareIntegration:
    def test_guaranteed_pod_respects_single_numa_policy(self):
        """A 6-cpu guaranteed task with single-numa-node policy cannot fit
        one NUMA node of the small node; it must land on the big node."""
        h = Harness(CONF)
        h.add("queues", build_queue("default"))
        h.add("nodes",
              build_node("small", {"cpu": "8", "memory": "16Gi"}),
              build_node("big", {"cpu": "16", "memory": "16Gi"}))
        h.add("numatopologies",
              numa_crd("small", cpus_per_numa=4, numa_count=2),
              numa_crd("big", cpus_per_numa=8, numa_count=2))
        h.add("podgroups", build_pod_group("pg1", "ns1", "default", 1,
                                           phase="Inqueue"))
        h.add("pods", guaranteed_pod("ns1", "p0", "pg1", cpu="6",
                                     policy="single-numa-node"))
        h.run_actions("allocate").close_session()
        assert h.binds == {"ns1/p0": "big"}

    def test_numa_sets_pushed_back_on_close(self):
        h = Harness(CONF)
        h.add("queues", build_queue("default"))
        h.add("nodes", build_node("n1", {"cpu": "8", "memory": "16Gi"}))
        h.add("numatopologies", numa_crd("n1"))
        h.add("podgroups", build_pod_group("pg1", "ns1", "default", 1,
                                           phase="Inqueue"))
        h.add("pods", guaranteed_pod("ns1", "p0", "pg1", cpu="2",
                                     policy="single-numa-node"))
        h.run_actions("allocate").close_session()
        assert h.binds == {"ns1/p0": "n1"}
        node = h.cache.nodes["n1"]
        remaining = node.numa_scheduler_info.numa_res_map["cpu"].allocatable
        assert len(remaining) == 6   # 2 cpus taken out of 8

    def test_policy_mismatch_rejects_node(self):
        """Task wants single-numa-node; the only node runs best-effort."""
        h = Harness(CONF)
        h.add("queues", build_queue("default"))
        h.add("nodes", build_node("n1", {"cpu": "8", "memory": "16Gi"}))
        h.add("numatopologies", numa_crd("n1", tm_policy="best-effort"))
        h.add("podgroups", build_pod_group("pg1", "ns1", "default", 1,
                                           phase="Inqueue"))
        h.add("pods", guaranteed_pod("ns1", "p0", "pg1", cpu="2",
                                     policy="single-numa-node"))
        h.run_actions("allocate").close_session()
        assert h.binds == {}

    def test_burstable_pod_ignored_by_numa(self):
        """Non-guaranteed pods bypass NUMA admission entirely."""
        h = Harness(CONF)
        h.add("queues", build_queue("default"))
        h.add("nodes", build_node("n1", {"cpu": "8", "memory": "16Gi"}))
        h.add("numatopologies", numa_crd("n1"))
        h.add("podgroups", build_pod_group("pg1", "ns1", "default", 1,
                                           phase="Inqueue"))
        # requests != limits -> Burstable
        h.add("pods", build_pod("ns1", "p0", "", "Pending",
                                {"cpu": "2", "memory": "1Gi"}, "pg1"))
        h.run_actions("allocate").close_session()
        assert h.binds == {"ns1/p0": "n1"}


    @pytest.mark.parametrize("policy,bound", [("", True),
                                              ("single-numa-node", False)])
    def test_no_numa_nodes_needs_no_host_sweep(self, policy, bound):
        """Without NUMA topology on any node the plugin's predicate is a
        solver mask: a Guaranteed task with a policy fits nowhere, any
        other task is untouched, and the per-node host sweep never runs."""
        from volcano_tpu.metrics import metrics as m
        h = Harness(CONF)
        h.add("queues", build_queue("default"))
        h.add("nodes", build_node("n1", {"cpu": "8", "memory": "16Gi"}))
        h.add("podgroups", build_pod_group("pg1", "ns1", "default", 1,
                                           phase="Inqueue"))
        h.add("pods", guaranteed_pod("ns1", "p0", "pg1", cpu="2",
                                     policy=policy))
        swept = m.counter_total(m.SOLVER_HOST_PREDICATE)
        h.run_actions("allocate").close_session()
        assert h.binds == ({"ns1/p0": "n1"} if bound else {})
        assert m.counter_total(m.SOLVER_HOST_PREDICATE) == swept


class TestGuaranteedQoS:
    def test_is_guaranteed(self):
        pod = guaranteed_pod("ns", "p", "g")
        assert is_guaranteed(pod)
        pod.spec.containers[0].limits = {}
        assert not is_guaranteed(pod)
