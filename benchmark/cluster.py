"""A configuration's cluster as the apiserver's objects: nodes, queues,
and each job's PodGroup and pods. Objects are built in set-up; the window
only writes them through ``ObjectStore.create`` and ``delete``."""

from __future__ import annotations

from typing import List, Optional

from traffic.generator import Job


def conf_text(config: dict) -> str:
    """The configuration's scheduler conf, with its solver arguments (none
    for the default solver conf)."""
    text = config["scheduler_conf"]
    args = config.get("solver_arguments") or {}
    if args:
        lines = "".join(f'    {k}: "{v}"\n' for k, v in args.items())
        text += f"configurations:\n- name: solver\n  arguments:\n{lines}"
    return text


def node_name(i: int) -> str:
    return f"node-{i}"


def build_nodes(config: dict) -> list:
    from volcano_tpu.utils.test_utils import build_node
    spec = config["nodes"]
    racks = int(spec.get("racks", 0))
    out = []
    for i in range(int(spec["count"])):
        labels = {"rack": f"rack-{i % racks}"} if racks else {}
        out.append(build_node(node_name(i), dict(spec["allocatable"]),
                              labels=labels))
    return out


def build_queues(config: dict) -> list:
    from volcano_tpu.utils.test_utils import build_queue
    return [build_queue(q["name"], weight=int(q["weight"]))
            for q in config["queues"]]


class JobObjects:
    """One job's PodGroup and pods, built ahead of their write."""

    __slots__ = ("job", "podgroup", "pods")

    def __init__(self, job: Job, namespace: str, phase: str,
                 nodes: Optional[List[int]] = None):
        from volcano_tpu.utils.test_utils import build_pod, build_pod_group
        self.job = job
        self.podgroup = build_pod_group(job.name, namespace, job.queue,
                                        job.min_member, phase=phase)
        self.pods = []
        for i, pname in enumerate(job.pod_names()):
            host = node_name(nodes[i]) if nodes is not None else ""
            self.pods.append(build_pod(namespace, pname, host, "Pending",
                                       dict(job.requests),
                                       groupname=job.name))

