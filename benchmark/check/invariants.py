"""What the store holds after the run, judged by the benchmark's own
arithmetic (after chip_smoke.py's check_gangs_and_capacity, rewritten to
import nothing of the program): every gang bound whole or not at all, and
no node past its allocatable resources or pod count."""

from __future__ import annotations

from typing import Dict

from traffic.generator import quantity


def _res(requests: Dict[str, str]) -> Dict[str, int]:
    return {k: quantity(k, v) for k, v in requests.items()}


def check_store(store, config: dict) -> Dict[str, int]:
    """{"partial_gangs": n, "over_capacity_nodes": n, "unknown_nodes": n}."""
    from volcano_tpu.models.objects import GROUP_NAME_ANNOTATION
    alloc = _res(config["nodes"]["allocatable"])
    pods_cap = alloc.pop("pods", None)
    nodes = {n.metadata.name for n in store.list("nodes")}
    per_group: Dict[str, list] = {}
    used: Dict[str, Dict[str, int]] = {}
    count: Dict[str, int] = {}
    for p in store.list("pods"):
        g = p.metadata.annotations.get(GROUP_NAME_ANNOTATION, "")
        e = per_group.setdefault(g, [0, 0])
        e[0] += 1
        host = p.spec.node_name
        if not host:
            continue
        e[1] += 1
        u = used.setdefault(host, {})
        for c in p.spec.containers:
            for k, v in _res(c.requests).items():
                u[k] = u.get(k, 0) + v
        count[host] = count.get(host, 0) + 1
    mins = {pg.metadata.name: pg.spec.min_member
            for pg in store.list("podgroups")}
    partial = sum(1 for g, (n, b) in per_group.items()
                  if 0 < b < min(n, mins.get(g, n)))
    over = 0
    for host, u in used.items():
        if any(v > alloc.get(k, 0) for k, v in u.items()) or \
                (pods_cap is not None and count[host] > pods_cap):
            over += 1
    unknown = sum(1 for h in used if h not in nodes)
    return {"partial_gangs": partial, "over_capacity_nodes": over,
            "unknown_nodes": unknown}
