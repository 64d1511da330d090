"""The work of gang placement, counted from the shapes of one call,
whatever kernel implements it: the least a placement of G gangs over N
candidate nodes in R resource dimensions has to compute and move.

Operations: per gang, per candidate node, one sweep of the feasibility
test (request against idle and against future resources, with the
tolerance: 4R) and of the score (binpack 5R; least-requested,
most-requested and balanced allocation over cpu and memory, 24; the
weighted sum, the static score, the mask and the arg-max, 10). Placing
a gang's further tasks changes one node, so they add nothing per node.

Bytes: the node state read once (idle, future and allocatable [N, R]
float32; task counts and caps [N] int32), the gangs' requests [G, R]
float32, their predicate mask [G, N] bool and static score [G, N]
float32 read once; the task and job vectors read once; the assignment
(int32 and a bool per task) and the ready and kept flags per job written
once.

The peaks are one table keyed by ``device_kind`` (``peaks.json``); a kind
that is not in it is an error."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def placement_work(T: int, G: int, R: int, N: int, J: int) -> Tuple[int, int]:
    """(operations, bytes) of one placement call."""
    ops = G * N * (9 * R + 34)
    node_bytes = N * R * 4 * 3 + N * 4 * 2
    gang_bytes = G * R * 4 + G * N * 1 + G * N * 4
    task_bytes = T * 4 * 4 + J * 4 * 5
    out_bytes = T * (4 + 1) + J * 2
    return ops, node_bytes + gang_bytes + task_bytes + out_bytes


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time(shapes: dict, peak: Dict[str, float]) -> Tuple[float, str]:
    """(seconds, "compute" | "memory"): the larger of operations over peak
    FLOP/s and bytes over peak bandwidth, and which one bounds it."""
    ops, nbytes = placement_work(shapes["T"], shapes["G"], shapes["R"],
                                 shapes["N"], shapes["J"])
    tc = ops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
