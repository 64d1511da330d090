"""The comparison that decides ``correct``: every number below its limit
(check/limits.json).

* From the kernel calls the window drove and whose answer the cycle
  used (the longest, and one drawn from the seed), teacher forced
  against the plain reference (check/reference.py) on tables it derives
  from the cell's own objects: ``placement_gap``, the widest score gap of
  a chosen node below the reference's best on the call's columns;
  ``invalid_placements``, chosen nodes the semantics forbid;
  ``lost_gangs``, gangs left unplaced that the reference places on any
  node of the cluster; ``input_mismatches``, entries of the program's
  tables (requests, allocatable, pod room, node rows, static mask, static
  score, topology buckets, minMember, queue) that differ from the
  derived ones; ``unchecked_calls``, 1 where the window drove no call to
  check.
* From the store after the run (check/invariants.py): ``partial_gangs``,
  ``over_capacity_nodes``, ``unknown_nodes``.
* From the program's counters over the window: ``solver_fallbacks``
  (a placement tier crashed, or pruning crashed) and ``other_tier_runs``
  (placements served by any tier but the cell's).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

from check import invariants, reference

LIMITS = Path(__file__).resolve().parent / "limits.json"
TIERS = ("sharded", "pallas", "native", "chunked", "scan")


def limits() -> Dict[str, float]:
    return json.loads(LIMITS.read_text())["limits"]


def counters() -> Dict[str, float]:
    from volcano_tpu.metrics import metrics as m
    c = {f"runs.{t}": m.counter_total(m.SOLVER_KERNEL_RUNS, kernel=t)
         for t in TIERS}
    c["fallback"] = m.counter_total(m.SOLVER_FALLBACK)
    c["prune_crash"] = m.counter_total(m.PRUNE_FALLBACK, reason="crash")
    return c


def kernel_readings(calls: List[dict], dtype=None) -> Dict[str, float]:
    out = {"placement_gap": 0.0, "invalid_placements": 0, "lost_gangs": 0,
           "input_mismatches": 0, "unchecked_calls": 0 if calls else 1}
    for call in calls:
        r = reference.check(call) if dtype is None else \
            reference.check(call, dtype)
        out["placement_gap"] = max(out["placement_gap"], r["gap"])
        out["invalid_placements"] += r["invalid"]
        out["lost_gangs"] += r["lost"]
        out["input_mismatches"] += r["mismatch"]
    return out


def host_calls(calls: List[dict]) -> List[dict]:
    """The captured calls with every array pulled to the host."""
    import numpy as np
    out = []
    for c in calls:
        args = [np.asarray(a) for a in c["args"][:28]]
        args.append(tuple(np.asarray(x) for x in c["args"][28]))
        o = tuple(np.asarray(x) for x in c["out"][:4])
        out.append({"args": args, "kwargs": c["kwargs"], "out": o,
                    "shapes": c["shapes"], "book": c.get("book")})
    return out


def make_book(labels: dict, jobs: dict, config: dict) -> dict:
    """What the benchmark itself knows of one call (reference.derive):
    each task slot's request, minMember and queue from its pod's job as
    the generator drew it, each node's allocatable and pod room from the
    configuration, and the labels and node snapshot the tap kept."""
    import numpy as np
    import cluster
    from traffic.generator import quantity
    res = labels["resources"]
    queues = {q: i for i, q in enumerate(labels["queues"])}
    T = len(labels["tasks"])
    req = np.zeros((T, len(res)))
    mins = np.zeros(T, np.int64)
    qidx = np.full(T, -1, np.int64)
    for t, task in enumerate(labels["tasks"]):
        job = jobs.get(task.name.rsplit("-t", 1)[0])
        if job is None:
            qidx[t] = -3          # a pod the traffic never made
            continue
        req[t] = [quantity(r, job.requests.get(r, "0")) for r in res]
        mins[t] = job.min_member
        qidx[t] = queues.get(job.queue, -1)
    spec = config["nodes"]
    alloc = dict(spec["allocatable"])
    pods = quantity("pods", alloc.pop("pods", "0"))
    row = [quantity(r, alloc.get(r, "0")) for r in res]
    index = {cluster.node_name(i): i for i in range(int(spec["count"]))}
    known = np.array([n in index for n in labels["nodes"]])
    N = len(labels["nodes"])
    book = {k: labels[k] for k in ("resources", "cols", "idle", "future",
                                   "ntasks", "alloc", "max_tasks")}
    from check.reference import conf_weights
    book.update(req=req, min_member=mins, queue=qidx, known=known,
                weights=conf_weights(config["scheduler_conf"], res),
                alloc_raw=np.where(known[:, None], np.array(row)[None, :],
                                   0.0),
                pods_cap=np.where(known, pods, 0).astype(np.int64))
    assert book["alloc_raw"].shape == (N, len(res))
    return book


def dump(calls: List[dict], stem) -> List[str]:
    """Keep a failed run's checked calls (compressed) for a later look;
    returns the files written."""
    import numpy as np
    out = []
    Path(stem).parent.mkdir(parents=True, exist_ok=True)
    for i, c in enumerate(calls):
        arrs = {f"a{k}": a for k, a in enumerate(c["args"][:28])}
        arrs.update({f"w{k}": np.asarray(x)
                     for k, x in enumerate(c["args"][28])})
        arrs.update({f"o{k}": np.asarray(x) for k, x in enumerate(c["out"])})
        arrs["kw"] = np.array(json.dumps(c["kwargs"]))
        for k, v in (c.get("book") or {}).items():
            arrs[f"b_{k}"] = np.asarray(v)
        path = f"{stem}-{i}.npz"
        np.savez_compressed(path, **arrs)
        out.append(path)
    return out


def load(path) -> dict:
    """A call that ``dump`` wrote, as ``host_calls`` gives it."""
    import numpy as np
    z = np.load(path)
    args = [z[f"a{k}"] for k in range(28)]
    nw = sum(1 for k in z.files if k.startswith("w"))
    args.append(tuple(z[f"w{k}"] for k in range(nw)))
    book = {k[2:]: z[k] for k in z.files if k.startswith("b_")}
    book["resources"] = [str(r) for r in book.get("resources", [])]
    return {"args": args, "kwargs": json.loads(str(z["kw"])),
            "out": tuple(z[f"o{k}"] for k in range(4)),
            "book": book or None}


def judge(calls: List[dict], store, config: dict, before: dict,
          after: dict, tier: str = "pallas") -> Dict[str, float]:
    nums: Dict[str, float] = {}
    nums.update(kernel_readings(calls))
    nums.update(invariants.check_store(store, config))
    d = {k: after[k] - before[k] for k in after}
    nums["solver_fallbacks"] = d["fallback"] + d["prune_crash"]
    nums["other_tier_runs"] = sum(v for k, v in d.items()
                                  if k.startswith("runs.")
                                  and k != f"runs.{tier}")
    return nums


def verdict(nums: Dict[str, float]) -> tuple:
    lim = limits()
    checks = {k: {"value": v, "limit": lim[k]} for k, v in nums.items()}
    ok = all(v <= lim[k] for k, v in nums.items())
    return ok, checks
