"""Synthetic cluster generators for benchmarks and scale tests.

Two levels, mirroring the reference's two test tiers (SURVEY.md §4):

* ``synth_arrays``: dense post-snapshot solver inputs (the analogue of a
  populated ``TaskBatch``/``NodeArrays`` pair) for kernel-level benches —
  what the scheduler sees after the cache snapshot has been encoded.
* ``populate_store``: object-level cluster (Nodes/Pods/PodGroups/Queues in
  an ObjectStore) for end-to-end action benches and e2e tests, the analogue
  of the reference e2e harness's kind-cluster fixtures.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..models.arrays import bucket


@dataclass
class SynthArrays:
    """Dense solver inputs for a T-task x N-node synthetic cluster."""
    task_group: np.ndarray      # [T] i32
    task_job: np.ndarray        # [T] i32
    task_valid: np.ndarray      # [T] bool
    group_req: np.ndarray       # [G, R] f32
    group_mask: np.ndarray      # [G, N] bool
    group_static_score: np.ndarray  # [G, N] f32
    task_bucket: np.ndarray     # [T] i32 (-1 = out of bucket)
    group_pack_bonus: np.ndarray  # [G] f32
    job_min_available: np.ndarray   # [J] i32
    job_ready_base: np.ndarray      # [J] i32
    job_task_start: np.ndarray      # [J] i32
    job_n_tasks: np.ndarray         # [J] i32
    job_queue: np.ndarray           # [J] i32
    pool_queue: np.ndarray          # [P] i32 (single-ns: pools == queues)
    pool_ns: np.ndarray             # [P] i32
    pool_job_start: np.ndarray      # [P] i32
    pool_njobs: np.ndarray          # [P] i32
    ns_weight: np.ndarray           # [NS] f32
    ns_alloc0: np.ndarray           # [NS, R] f32
    ns_total: np.ndarray            # [R] f32
    queue_deserved: np.ndarray      # [Q, R] f32
    queue_alloc0: np.ndarray        # [Q, R] f32
    node_idle: np.ndarray       # [N, R] f32
    node_future: np.ndarray     # [N, R] f32
    node_alloc: np.ndarray      # [N, R] f32
    node_ntasks: np.ndarray     # [N] i32
    node_max_tasks: np.ndarray  # [N] i32
    eps: np.ndarray             # [R] f32

    @property
    def args(self) -> list:
        """Positional argument list for ops.allocate.gang_allocate (weights
        excluded)."""
        return [self.task_group, self.task_job, self.task_valid,
                self.group_req, self.group_mask, self.group_static_score,
                self.task_bucket, self.group_pack_bonus,
                self.job_min_available, self.job_ready_base,
                self.job_task_start, self.job_n_tasks, self.job_queue,
                self.pool_queue, self.pool_ns, self.pool_job_start,
                self.pool_njobs, self.ns_weight, self.ns_alloc0,
                self.ns_total, self.queue_deserved,
                self.queue_alloc0, self.node_idle, self.node_future,
                self.node_alloc, self.node_ntasks, self.node_max_tasks,
                self.eps]

    @property
    def shapes(self) -> str:
        return (f"T={self.task_group.shape[0]} N={self.node_idle.shape[0]} "
                f"G={self.group_req.shape[0]} J={self.job_min_available.shape[0]} "
                f"R={self.node_idle.shape[1]}")


def synth_arrays(n_tasks: int, n_nodes: int, *, gang_size: int = 8,
                 n_racks: int = 32, r: int = 4, seed: int = 0,
                 utilization: float = 0.3, node_pad_to: Optional[int] = None,
                 rack_affinity: bool = True, n_queues: int = 1,
                 n_namespaces: int = 1) -> SynthArrays:
    """A gang-heavy pending backlog over a partially utilized cluster.

    Nodes: 64-core/256GiB-shaped with uniform random pre-existing usage around
    ``utilization``; resource dims are [cpu(milli), memory(MiB), pods-slack,
    accelerator], then ``r - 4`` further scalar kinds. Tasks: gangs of
    ``gang_size`` with per-gang resource shapes; each gang is one group
    (homogeneous replicas). Rack-affinity static score prefers a random
    rack per gang (config-5's topology-aware nodeorder).
    """
    rng = np.random.default_rng(seed)
    n_jobs = max(1, n_tasks // gang_size)
    n_tasks = n_jobs * gang_size
    n_groups = n_jobs

    t_pad = bucket(n_tasks, 256)
    g_pad = bucket(n_groups, 16)
    j_pad = bucket(n_jobs + 1, 16)          # + sentinel for padding tasks
    n_pad = node_pad_to if node_pad_to else bucket(n_nodes, 256)

    # nodes
    cap = np.zeros((n_pad, r), np.float32)
    cap[:n_nodes, 0] = 64_000.0                           # 64 cores (milli)
    cap[:n_nodes, 1] = 256 * 1024.0                       # 256 GiB in MiB
    cap[:n_nodes, 2] = 110.0                              # pods dimension
    cap[:n_nodes, 3] = 8.0                                # accelerators
    if r > 4:
        # further scalar kinds (MIG slices, hugepages, ...): each node
        # holds 0, 2 or 4 of each
        cap[:n_nodes, 4:] = rng.choice([0.0, 2.0, 4.0], (n_nodes, r - 4))
    used_frac = rng.uniform(0.0, 2 * utilization, (n_pad, 1)).astype(np.float32)
    used = (cap * used_frac).astype(np.float32)
    idle = cap - used
    node_ntasks = np.zeros(n_pad, np.int32)
    node_ntasks[:n_nodes] = (used_frac[:n_nodes, 0] * 30).astype(np.int32)
    node_max_tasks = np.zeros(n_pad, np.int32)            # uncapped

    # gangs
    group_req = np.zeros((g_pad, r), np.float32)
    group_req[:n_groups, 0] = rng.choice([1000, 2000, 4000, 8000], n_groups)
    group_req[:n_groups, 1] = rng.choice([2048, 4096, 8192, 16384], n_groups)
    group_req[:n_groups, 2] = 1.0
    group_req[:n_groups, 3] = rng.choice([0, 0, 0, 1], n_groups)
    if r > 4:
        # half the gangs ask one unit of one further kind
        kind = rng.integers(4, r, n_groups)
        asks = np.flatnonzero(rng.random(n_groups) < 0.5)
        group_req[asks, kind[asks]] = 1.0

    task_group = np.zeros(t_pad, np.int32)
    task_job = np.full(t_pad, n_jobs, np.int32)           # sentinel fill
    task_valid = np.zeros(t_pad, bool)
    ids = np.arange(n_tasks)
    task_group[:n_tasks] = ids // gang_size
    task_job[:n_tasks] = ids // gang_size
    task_valid[:n_tasks] = True

    job_min_available = np.zeros(j_pad, np.int32)
    job_min_available[:n_jobs] = gang_size
    job_ready_base = np.zeros(j_pad, np.int32)
    job_task_start = np.zeros(j_pad, np.int32)
    job_task_start[:n_jobs] = np.arange(n_jobs) * gang_size
    job_n_tasks = np.zeros(j_pad, np.int32)
    job_n_tasks[:n_jobs] = gang_size

    # queues/namespaces: jobs striped round-robin then regrouped so each
    # (namespace, queue) pool's jobs are contiguous, namespace-major (the
    # encode convention: namespace index order = static selection order)
    q_pad = bucket(n_queues, 8)
    job_queue = np.zeros(j_pad, np.int32)
    job_queue[:n_jobs] = np.arange(n_jobs) % n_queues
    job_ns = np.zeros(j_pad, np.int32)
    if n_namespaces > 1:
        job_ns[:n_jobs] = rng.integers(0, n_namespaces, n_jobs)
    if n_queues > 1 or n_namespaces > 1:
        key = job_ns[:n_jobs].astype(np.int64) * n_queues \
            + job_queue[:n_jobs]
        order = np.argsort(key, kind="stable")
        # rebuild task arrays in regrouped job order
        new_task_order = np.concatenate(
            [np.arange(j * gang_size, (j + 1) * gang_size) for j in order])
        task_group[:n_tasks] = task_group[:n_tasks][new_task_order]
        remap = np.empty(n_jobs, np.int64)
        remap[order] = np.arange(n_jobs)
        task_job[:n_tasks] = remap[task_job[:n_tasks][new_task_order]]
        job_queue[:n_jobs] = job_queue[:n_jobs][order]
        job_ns[:n_jobs] = job_ns[:n_jobs][order]
    queue_deserved = np.full((q_pad, r), np.inf, np.float32)
    queue_alloc0 = np.zeros((q_pad, r), np.float32)
    # pools: contiguous (ns, queue) runs over the regrouped jobs
    run_keys: list = []
    pool_queue_l: list = []
    pool_ns_l: list = []
    pool_start_l: list = []
    pool_n_l: list = []
    for j in range(n_jobs):
        k = (int(job_ns[j]), int(job_queue[j]))
        if not run_keys or run_keys[-1] != k:
            run_keys.append(k)
            pool_ns_l.append(k[0])
            pool_queue_l.append(k[1])
            pool_start_l.append(j)
            pool_n_l.append(0)
        pool_n_l[-1] += 1
    p_pad = bucket(max(1, len(run_keys)), 8)
    pool_queue = np.zeros(p_pad, np.int32)
    pool_queue[:len(run_keys)] = pool_queue_l
    pool_ns = np.zeros(p_pad, np.int32)
    pool_ns[:len(run_keys)] = pool_ns_l
    pool_job_start = np.zeros(p_pad, np.int32)
    pool_job_start[:len(run_keys)] = pool_start_l
    pool_njobs = np.zeros(p_pad, np.int32)
    pool_njobs[:len(run_keys)] = pool_n_l
    ns_pad = max(1, n_namespaces)
    ns_weight = np.ones(ns_pad, np.float32)
    ns_alloc0 = np.zeros((ns_pad, r), np.float32)
    ns_total = cap[:n_nodes].sum(axis=0).astype(np.float32)

    # static predicates: valid nodes only; static score: rack affinity
    group_mask = np.zeros((g_pad, n_pad), bool)
    group_mask[:, :n_nodes] = True
    group_static_score = np.zeros((g_pad, n_pad), np.float32)
    if rack_affinity and n_racks > 0:
        node_rack = rng.integers(0, n_racks, n_nodes)
        gang_rack = rng.integers(0, n_racks, n_groups)
        group_static_score[:n_groups, :n_nodes] = (
            (gang_rack[:, None] == node_rack[None, :]) * 50.0)

    eps = np.array([100.0] + [0.1] * (r - 1), np.float32)

    return SynthArrays(
        task_group=task_group, task_job=task_job, task_valid=task_valid,
        group_req=group_req, group_mask=group_mask,
        group_static_score=group_static_score,
        task_bucket=np.full(t_pad, -1, np.int32),
        group_pack_bonus=np.zeros(g_pad, np.float32),
        job_min_available=job_min_available, job_ready_base=job_ready_base,
        job_task_start=job_task_start, job_n_tasks=job_n_tasks,
        job_queue=job_queue, pool_queue=pool_queue, pool_ns=pool_ns,
        pool_job_start=pool_job_start, pool_njobs=pool_njobs,
        ns_weight=ns_weight, ns_alloc0=ns_alloc0, ns_total=ns_total,
        queue_deserved=queue_deserved, queue_alloc0=queue_alloc0,
        node_idle=idle, node_future=idle.copy(), node_alloc=cap,
        node_ntasks=node_ntasks, node_max_tasks=node_max_tasks, eps=eps)


def populate_store(store, *, n_nodes: int, n_jobs: int, gang_size: int,
                   queues: Optional[List[Tuple[str, int]]] = None,
                   cpu_req: str = "2", mem_req: str = "4Gi",
                   node_cpu: str = "64", node_mem: str = "256Gi",
                   seed: int = 0, namespace: str = "default",
                   phase: str = "Inqueue", zones: int = 0,
                   spread_every: int = 0,
                   anti_every: int = 0) -> Dict[str, int]:
    """Object-level synthetic cluster in an ObjectStore (e2e bench path).

    ``zones`` > 0 labels node i with topology.kubernetes.io/zone =
    zone-<i % zones>; ``spread_every`` / ``anti_every`` give every Nth
    job a hard zone topology-spread constraint / a required one-replica-
    per-zone self-anti-affinity term — the constraint-heavy bench shape
    (docs/design/constraints.md). Deterministic by job index, no rng."""
    from .test_utils import (build_node, build_pod, build_pod_group,
                             build_queue)
    rng = np.random.default_rng(seed)
    queues = queues or [("default", 1)]
    for qname, weight in queues:
        if store.get("queues", qname) is None:
            store.create("queues", build_queue(qname, weight=weight))
    for i in range(n_nodes):
        labels = {"rack": f"rack-{i % 32}"}
        if zones > 0:
            labels["topology.kubernetes.io/zone"] = f"zone-{i % zones}"
        store.create("nodes", build_node(
            f"node-{i}", {"cpu": node_cpu, "memory": node_mem, "pods": "110"},
            labels=labels))
    for j in range(n_jobs):
        qname = queues[j % len(queues)][0]
        pg = build_pod_group(f"pg-{j}", namespace, qname, gang_size,
                             phase=phase)
        store.create("podgroups", pg)
        spread = zones > 0 and spread_every > 0 and j % spread_every == 0
        anti = zones > 0 and anti_every > 0 and not spread \
            and j % anti_every == 1 % max(1, anti_every)
        for t in range(gang_size):
            pod = build_pod(
                namespace, f"job{j}-task{t}", "", "Pending",
                {"cpu": cpu_req, "memory": mem_req}, groupname=f"pg-{j}",
                labels={"synth-job": f"pg-{j}"} if anti else None)
            if spread:
                from ..models.objects import TopologySpreadConstraint
                pod.spec.topology_spread = [TopologySpreadConstraint(
                    max_skew=1,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule")]
            elif anti:
                from ..models.objects import (Affinity,
                                              NodeSelectorRequirement,
                                              PodAffinity, PodAffinityTerm)
                pod.spec.affinity = Affinity(pod_anti_affinity=PodAffinity(
                    required=[PodAffinityTerm(
                        label_selector=[NodeSelectorRequirement(
                            key="synth-job", operator="In",
                            values=[f"pg-{j}"])],
                        topology_key="topology.kubernetes.io/zone")]))
            store.create("pods", pod)
    return {"nodes": n_nodes, "jobs": n_jobs, "tasks": n_jobs * gang_size}
