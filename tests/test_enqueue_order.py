"""enqueue's job order: each queue's pending list, sorted once, gates the
same jobs in the same order as a re-sort before every pop (kept below as
the reference), and costs O(J log J) job-order comparisons."""

import functools
import math
import random

import pytest

from tests.harness import Harness
from volcano_tpu.framework import get_action
from volcano_tpu.models.objects import (SLA_WAITING_TIME_KEY, ObjectMeta,
                                        PodGroupPhase, PriorityClass)
from volcano_tpu.utils.clock import FakeClock
from volcano_tpu.utils.test_utils import (build_node, build_pod,
                                          build_pod_group, build_queue,
                                          build_resource_list)

# every plugin that registers a job order except tdm (whose order reads
# only the job's preemptable flag), with the enqueue voters and observer
CONF = """
actions: "enqueue"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: sla
- plugins:
  - name: overcommit
  - name: drf
  - name: proportion
"""

NOW = 100_000.0
NODE = build_resource_list("8", "32Gi")  # overcommit headroom: 9.6 cpu a node


def _reference_gate(ssn):
    """The per-pop re-sort loop: sort the popped queue's whole pending list,
    then take its head."""
    queue_list, queue_seen, jobs_map = [], set(), {}
    for job in ssn.jobs.values():
        queue = ssn.queues.get(job.queue)
        if queue is None:
            continue
        if queue.uid not in queue_seen:
            queue_seen.add(queue.uid)
            queue_list.append(queue)
        if job.pod_group.status.phase == PodGroupPhase.PENDING:
            jobs_map.setdefault(job.queue, []).append(job)
    queue_key = functools.cmp_to_key(
        lambda a, b: -1 if ssn.queue_order_fn(a, b) else 1)
    job_key = functools.cmp_to_key(
        lambda a, b: -1 if ssn.job_order_fn(a, b) else 1)
    while queue_list:
        queue_list.sort(key=queue_key)
        queue = queue_list.pop(0)
        jobs = jobs_map.get(queue.name)
        if not jobs:
            continue
        jobs.sort(key=job_key)
        job = jobs.pop(0)
        if (job.pod_group.spec.min_resources is None
                or ssn.job_enqueueable(job)):
            ssn.job_enqueued(job)
            job.own_pod_group().status.phase = PodGroupPhase.INQUEUE
            ssn.touched_jobs.add(job.uid)
        queue_list.append(queue)


class _Cluster:
    """Jobs described once and written to a fresh store per run, in a
    seeded shuffled order, so the store's order is not the job order."""

    def __init__(self, nodes=1, queues=("q1",), capability=None):
        self.nodes, self.queues, self.capability = nodes, queues, capability
        self.jobs = []

    def job(self, name, queue="q1", cpu=1, priority="", created=0.0,
            waiting=None, running=0, min_member=1, min_resources=True):
        self.jobs.append(dict(name=name, queue=queue, cpu=cpu,
                              priority=priority, created=created,
                              waiting=waiting, running=running,
                              min_member=min_member,
                              min_resources=min_resources))

    def harness(self):
        h = Harness(CONF)
        h.store.clock = FakeClock(NOW)
        h.add("priorityclasses",
              PriorityClass(metadata=ObjectMeta(name="low"), value=10),
              PriorityClass(metadata=ObjectMeta(name="high"), value=1000))
        for q in self.queues:
            queue = build_queue(q, capability=self.capability)
            queue.metadata.creation_timestamp = NOW - 3600
            h.add("queues", queue)
        h.add("nodes", *(build_node(f"n{i}", NODE)
                         for i in range(self.nodes)))
        jobs = list(self.jobs)
        random.Random(7).shuffle(jobs)
        placed = 0
        for j in jobs:
            pg = build_pod_group(j["name"], "ns", j["queue"], j["min_member"],
                                 phase=PodGroupPhase.PENDING,
                                 priority_class=j["priority"])
            pg.metadata.creation_timestamp = NOW - 600 + j["created"]
            if j["waiting"] is not None:
                pg.metadata.annotations[SLA_WAITING_TIME_KEY] = j["waiting"]
            if j["min_resources"]:
                pg.spec.min_resources = {"cpu": str(j["cpu"]),
                                         "memory": "1Gi"}
            h.add("podgroups", pg)
            for i in range(j["running"]):
                h.add("pods", build_pod("ns", f"{j['name']}-r{i}",
                                        f"n{placed % self.nodes}", "Running",
                                        build_resource_list("1", "1Gi"),
                                        j["name"]))
                placed += 1
            h.add("pods", build_pod("ns", f"{j['name']}-p", "", "Pending",
                                    build_resource_list(str(j["cpu"]), "1Gi"),
                                    j["name"]))
        return h


def _equal_priorities():
    c = _Cluster(nodes=4)
    for i in range(12):
        c.job(f"j{i:02d}")
    return c


def _mixed_priorities():
    c = _Cluster(nodes=4)
    for i in range(12):
        c.job(f"j{i:02d}", priority=("", "low", "high")[i % 3],
              created=i % 4)
    return c


def _gang_ready_and_unready():
    c = _Cluster(nodes=4)
    for i in range(10):
        # ready: as many running pods as its minMember
        c.job(f"j{i:02d}", running=2 if i % 2 else 0, min_member=2)
    return c


def _sla_waiting_times():
    c = _Cluster(nodes=4)
    # creation 10 min ago: 5m has passed (sla permits), 1h has not
    waits = (None, "5m", "1h", None, "1h", "5m", None, "10m")
    for i, w in enumerate(waits):
        c.job(f"j{i:02d}", waiting=w, created=i % 3)
    return c


def _drf_shares():
    c = _Cluster(nodes=4)
    for i in range(12):
        # running cpu 0, 1, 2 or 2: equal and unequal shares
        c.job(f"j{i:02d}", running=(0, 1, 2, 2)[i % 4], min_member=3)
    return c


def _overcommit_exhausted():
    # headroom 9.6 cpu: admitted jobs depend on which come first
    c = _Cluster(nodes=1)
    for i in range(10):
        c.job(f"j{i:02d}", cpu=(1, 2, 3, 4)[i % 4],
              priority=("", "high")[i % 2], created=i % 3)
    return c


def _capability_rejects():
    c = _Cluster(nodes=8, queues=("q1", "q2"),
                 capability={"cpu": "5", "memory": "64Gi"})
    for i in range(10):
        c.job(f"j{i:02d}", queue=("q1", "q2")[i % 2], cpu=(1, 2, 3)[i % 3],
              created=i % 4)
    return c


def _no_min_resources():
    c = _Cluster(nodes=1)
    for i in range(10):
        c.job(f"j{i:02d}", cpu=3, min_resources=bool(i % 3),
              priority=("low", "high")[i % 2])
    return c


def _equal_share_queues():
    c = _Cluster(nodes=2, queues=("qa", "qb", "qc"))
    for i in range(15):
        c.job(f"j{i:02d}", queue=("qa", "qb", "qc")[i % 3], cpu=(1, 2)[i % 2],
              created=i % 5)
    return c


CASES = {
    "equal_priorities": (_equal_priorities, False),
    "mixed_priorities": (_mixed_priorities, False),
    "gang_ready_and_unready": (_gang_ready_and_unready, False),
    "sla_waiting_times": (_sla_waiting_times, False),
    "drf_shares": (_drf_shares, False),
    "overcommit_exhausted": (_overcommit_exhausted, True),
    "capability_rejects": (_capability_rejects, True),
    "no_min_resources": (_no_min_resources, True),
    "equal_share_queues": (_equal_share_queues, True),
}


def _run(cluster, gate):
    h = cluster.harness()
    ssn = h.open_session()
    admitted = []
    enqueued = ssn.job_enqueued

    def record(job):
        admitted.append(job.name)
        enqueued(job)

    ssn.job_enqueued = record
    gate(ssn)
    phases = {j.name: j.pod_group.status.phase for j in ssn.jobs.values()}
    touched = {ssn.jobs[uid].name for uid in ssn.touched_jobs}
    h.close_session()
    return admitted, phases, touched


@pytest.mark.parametrize("case", list(CASES))
def test_sorted_once_gates_as_per_pop_resort(case):
    build, some_rejected = CASES[case]
    got = _run(build(), get_action("enqueue").execute)
    want = _run(build(), _reference_gate)
    assert got == want
    admitted, phases, _ = got
    assert admitted
    pending = [n for n, p in phases.items() if p == PodGroupPhase.PENDING]
    assert bool(pending) == some_rejected


def test_job_order_comparisons_are_j_log_j():
    """~2,000 Pending gangs over 4 queues: one sort per queue, not one per
    pop (which would make ~J^2 / 2Q = 500k comparisons)."""
    c = _Cluster(nodes=4, queues=("q0", "q1", "q2", "q3"))
    for i in range(2000):
        c.job(f"j{i:04d}", queue=f"q{i % 4}", created=i % 7)
    h = c.harness()
    ssn = h.open_session()
    calls = 0
    order = ssn.job_order_fn

    def counted(l, r):
        nonlocal calls
        calls += 1
        return order(l, r)

    ssn.job_order_fn = counted
    h.run_actions("enqueue")
    n = len(c.jobs)
    assert sum(j.pod_group.status.phase == PodGroupPhase.INQUEUE
               for j in ssn.jobs.values()) > 0
    assert 0 < calls <= 2 * n * math.ceil(math.log2(n))
    h.close_session()
