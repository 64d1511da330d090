"""numaaware plugin (reference: pkg/scheduler/plugins/numaaware/
numaaware.go): topology-manager-style NUMA admission and scoring.

Extension points: Predicate (per-task policy admission + tentative CPU-set
assignment), BatchNodeOrder (fewer NUMA nodes spanned scores higher),
EventHandler (allocate/release assigned sets against the session view), and
OnSessionClose (push allocated sets back through the cache,
UpdateSchedulerNumaInfo).

Host-side by design: NUMA admission runs only for Guaranteed pods with a
topology policy — a rare, deeply branchy per-node decision (hint powersets
over <=8 NUMA nodes) that would not tile onto the MXU; the dense task x node
resource fit stays in the vmapped solver kernels (ops/fit.py).
"""

from __future__ import annotations

from typing import Dict, List, Set

import numpy as np

from ...framework.plugin import Plugin
from ...framework.registry import register_plugin_builder
from ...models.resource import CPU, milli_value
from . import policy as numa_policy
from .cpumanager import CPUDetails, CpuManager
from .policy import (CPU_MANAGER_POLICY, POLICY_NONE,
                     TOPOLOGY_MANAGER_POLICY, accumulate_providers_hints,
                     get_policy, mask_bits)

NAME = "numa-aware"
WEIGHT_ARG = "weight"


def is_guaranteed(pod) -> bool:
    """k8s Guaranteed QoS: every container's requests == limits with both
    cpu and memory set (v1qos.GetPodQOS, numaaware.go:117)."""
    containers = pod.spec.containers + pod.spec.init_containers
    if not containers:
        return False
    for c in containers:
        if not c.requests or not c.limits:
            return False
        if CPU not in c.requests or "memory" not in c.requests:
            return False
        for res, req in c.requests.items():
            lim = c.limits.get(res)
            if lim is None or milli_value(lim) != milli_value(req):
                return False
    return True


def generate_numa_nodes(nodes) -> Dict[str, List[int]]:
    """api.GenerateNumaNodes — NUMA node ids per node."""
    out = {}
    for name, node in nodes.items():
        if node.numa_scheduler_info is not None:
            out[name] = CPUDetails(
                node.numa_scheduler_info.cpu_detail).numa_nodes()
    return out


def generate_node_res_numa_sets(nodes) -> Dict[str, Dict[str, Set[int]]]:
    """api.GenerateNodeResNumaSets — allocatable id-sets per node/resource."""
    out = {}
    for name, node in nodes.items():
        if node.numa_scheduler_info is None:
            continue
        out[name] = {res: set(ri.allocatable)
                     for res, ri in node.numa_scheduler_info.numa_res_map.items()}
    return out


def _policy_without_numa_mask(batch, narr, feats):
    """Groups whose task is Guaranteed with a topology policy fit no node
    of a cluster without NUMA topology; None when no group is one."""
    blocked = [g for g, members in enumerate(batch.group_members)
               if batch.tasks[members[0]].topology_policy
               not in ("", POLICY_NONE)
               and is_guaranteed(batch.tasks[members[0]].pod)]
    if not blocked:
        return None
    mask = np.ones((batch.g_pad, narr.n_pad), bool)
    mask[blocked] = False
    return mask


class NumaAwarePlugin(Plugin):
    def __init__(self, arguments=None):
        args = arguments or {}
        if hasattr(args, "get_int"):
            self.weight = args.get_int(WEIGHT_ARG, 1)
        else:
            self.weight = int(args.get(WEIGHT_ARG, 1))
        self.hint_providers = [CpuManager()]
        # taskUID -> {node name -> {res -> set of ids}} (numaaware.go:52-55)
        self.assign_res: Dict[str, Dict[str, Dict[str, Set[int]]]] = {}
        self.node_res_sets: Dict[str, Dict[str, Set[int]]] = {}
        self.task_bind_node: Dict[str, str] = {}

    def name(self) -> str:
        return NAME

    def on_session_open(self, ssn) -> None:
        numa_nodes = generate_numa_nodes(ssn.nodes)
        self.node_res_sets = generate_node_res_numa_sets(ssn.nodes)

        from ...framework.session import EventHandler

        def on_allocate(event) -> None:
            """numaaware.go:86-100. The batch solver evaluates host
            predicates once per task group, so a non-representative task may
            arrive here without a tentative assignment — compute it now
            against the current NUMA view (feasibility was already checked
            group-wide; this keeps per-task CPU sets exact)."""
            task = event.task
            per_node = self.assign_res.get(task.uid)
            sets = per_node.get(task.node_name) if per_node else None
            if sets is None:
                node = ssn.nodes.get(task.node_name)
                if node is None:
                    return
                try:
                    sets = self._compute_assign(task, node, numa_nodes)
                except ValueError:
                    sets = None
                if sets is None:
                    return
                self.assign_res.setdefault(task.uid, {})[task.node_name] = sets
            node_sets = self.node_res_sets.get(task.node_name)
            if node_sets is not None:
                for res, taken in sets.items():
                    node_sets.setdefault(res, set()).difference_update(taken)
            self.task_bind_node[task.uid] = task.node_name

        def on_deallocate(event) -> None:
            """numaaware.go:101-114"""
            task = event.task
            per_node = self.assign_res.get(task.uid)
            if per_node is None:
                return
            sets = per_node.get(task.node_name)
            if sets is None:
                return
            self.task_bind_node.pop(task.uid, None)
            node_sets = self.node_res_sets.get(task.node_name)
            if node_sets is not None:
                for res, returned in sets.items():
                    node_sets.setdefault(res, set()).update(returned)

        ssn.add_event_handler(EventHandler(allocate_func=on_allocate,
                                           deallocate_func=on_deallocate))

        def predicate_fn(task, node) -> None:
            """numaaware.go:116-157 — policy admission + tentative assign."""
            sets = self._compute_assign(task, node, numa_nodes)
            if sets is not None:
                self.assign_res.setdefault(task.uid, {})[node.name] = sets

        ssn.add_predicate_fn(NAME, predicate_fn)
        if ssn.solver is not None and not numa_nodes:
            # no node carries NUMA topology: the predicate then records
            # nothing and vetoes only a Guaranteed task with a topology
            # policy, on every node — a [G, N] mask states that exactly,
            # and the solver skips its per-node Python sweep
            ssn.solver.mark_vectorized(NAME)
            ssn.solver.add_mask_fn(_policy_without_numa_mask)

        def batch_node_order_fn(task, node_infos) -> Dict[str, float]:
            """numaaware.go:160-183 — fewer NUMA nodes spanned is better."""
            scores: Dict[str, float] = {}
            if task.topology_policy in ("", POLICY_NONE):
                return scores
            per_node = self.assign_res.get(task.uid)
            if not per_node:
                return scores
            numa_counts: Dict[str, int] = {}
            for node in node_infos:
                sets = per_node.get(node.name)
                if sets is None or node.numa_scheduler_info is None:
                    continue
                details = CPUDetails(node.numa_scheduler_info.cpu_detail)
                spanned = {details.numa_of(c) for c in sets.get(CPU, set())
                           if c in details.detail}
                numa_counts[node.name] = len(spanned)
            if not numa_counts:
                return scores
            # NormalizeScore(100, reverse=True): fewest NUMA nodes -> 100
            max_count = max(numa_counts.values()) or 1
            for name, count in numa_counts.items():
                scores[name] = (100.0 * (max_count - count) / max_count) \
                    * self.weight
            return scores

        ssn.add_batch_node_order_fn(NAME, batch_node_order_fn)

    def _compute_assign(self, task, node, numa_nodes):
        """Policy admission + per-container CPU-set assignment
        (numaaware.go:116-157). Returns {res: set} or None when the task is
        out of scope; raises ValueError when the node must be rejected."""
        if not is_guaranteed(task.pod):
            return None
        fit, reason = self._filter_node_by_policy(task, node)
        if not fit:
            if reason:
                raise ValueError(reason)
            return None
        res_numa_sets = {res: set(ids) for res, ids in
                         self.node_res_sets.get(node.name, {}).items()}
        task_policy = get_policy(node, numa_nodes.get(node.name, []))
        all_assign: Dict[str, Set[int]] = {}
        for container in task.pod.spec.containers:
            providers_hints = accumulate_providers_hints(
                container, node.numa_scheduler_info, res_numa_sets,
                self.hint_providers)
            best_hint, admit = task_policy.predicate(providers_hints)
            if not admit:
                raise ValueError(
                    f"plugin {NAME} predicates failed for task {task.name} "
                    f"container {container.name} on node {node.name}")
            assign = numa_policy.allocate(
                container, best_hint, node.numa_scheduler_info,
                res_numa_sets, self.hint_providers)
            for res, ids in assign.items():
                all_assign.setdefault(res, set()).update(ids)
                res_numa_sets.setdefault(res, set()).difference_update(ids)
        return all_assign

    def _filter_node_by_policy(self, task, node):
        """numaaware.go:186-225 -> (fit, error_reason|None)"""
        info = node.numa_scheduler_info
        if task.topology_policy not in ("", POLICY_NONE):
            if info is None:
                return False, "numa info is empty"
            if info.policies.get(CPU_MANAGER_POLICY) != "static":
                return False, "cpu manager policy isn't static"
            if task.topology_policy != info.policies.get(TOPOLOGY_MANAGER_POLICY):
                return False, (
                    f"task topology policy[{task.topology_policy}] is "
                    f"different with node"
                    f"[{info.policies.get(TOPOLOGY_MANAGER_POLICY)}]")
            if node.name not in self.node_res_sets:
                return False, "no topo information"
            if not self.node_res_sets[node.name].get(CPU):
                return False, "cpu allocatable map is empty"
            return True, None
        # tasks without a policy: NUMA-manage them only on static+managed
        # nodes, silently skip elsewhere
        if info is None:
            return False, None
        if info.policies.get(CPU_MANAGER_POLICY) != "static":
            return False, None
        if info.policies.get(TOPOLOGY_MANAGER_POLICY, "") in ("", POLICY_NONE):
            return False, None
        return True, None

    def on_session_close(self, ssn) -> None:
        """numaaware.go:251-279 — aggregate bound assignments, push to cache."""
        if not self.task_bind_node:
            return
        allocated: Dict[str, Dict[str, Set[int]]] = {}
        for task_uid, node_name in self.task_bind_node.items():
            sets = self.assign_res.get(task_uid, {}).get(node_name)
            if sets is None:
                continue
            node_alloc = allocated.setdefault(node_name, {})
            for res, ids in sets.items():
                node_alloc.setdefault(res, set()).update(ids)
        ssn.cache.update_scheduler_numa_info(allocated)


register_plugin_builder(NAME, NumaAwarePlugin)
