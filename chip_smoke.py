#!/usr/bin/env python3
"""Chip smoke: the scheduler's main path on a TPU, through the entry
points a user calls, at the north-star size (50,000 pending tasks in
6,250 gangs of 8 over 10,000 nodes; BASELINE.md config 5).

    python chip_smoke.py             # one chip: allocate, then preempt
    python chip_smoke.py --chips 4   # only the sharded mesh phase

Phase 1 (allocate) populates a store with a seeded cluster, runs the
production Scheduler with examples/scheduler-conf.yaml and the default
solver conf (one cold run_once, then two warm ones on fresh identical
clusters), and checks that the Pallas tier served it with no fallback,
that gangs are whole and nodes within capacity, and that the binds equal
the plain XLA scan's (`kernel: scan`, `prune.enable: "off"`) on the same
cluster. Phase 2 (preempt) checks that the victim kernel served and
evicted exactly what the Python walk evicts. The mesh phase schedules the
phase-1 cluster with the mesh forced over 4 chips, and with pruning off
checks that it binds exactly as the single-device kernel on one chip.

Everything runs in this one process: a chip belongs to one process at a
time. Any failed check exits nonzero without the success line; the last
line of a passing run is the JSON object the chip contract fixes.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
CONF_PATH = REPO / "examples" / "scheduler-conf.yaml"

N_NODES = 10_000
N_GANGS = 6_250
GANG = 8
QUEUES = [("q0", 1), ("q1", 2), ("q2", 3), ("q3", 4)]
SEED = 0
FLUSH_TIMEOUT_S = 600.0
TIERS = ("sharded", "pallas", "native", "chunked", "scan")
REFERENCE_ARGS = {"kernel": "scan", "prune.enable": "off"}


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu() -> dict:
    """The first thing main() does: name the device, and fail on any
    backend but the TPU (on the CPU, `kernel: auto` would quietly pick
    the native solver and the run would look green)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    backend = jax.default_backend()
    check(backend == "tpu", f"JAX backend is {backend!r}, not 'tpu'")
    return info


def conf_text(solver_args: dict | None = None) -> str:
    text = CONF_PATH.read_text()
    if solver_args:
        args = "".join(f'    {k}: "{v}"\n' for k, v in solver_args.items())
        text += f"configurations:\n- name: solver\n  arguments:\n{args}"
    return text


# -- counters ---------------------------------------------------------------

def counters() -> dict:
    from volcano_tpu.metrics import metrics as m
    from volcano_tpu.ops.prune import FALLBACK_REASONS
    c = {f"kernel_runs.{t}": m.counter_total(m.SOLVER_KERNEL_RUNS, kernel=t)
         for t in TIERS}
    c["solver_fallback"] = m.counter_total(m.SOLVER_FALLBACK)
    c["host_predicate"] = m.counter_total(m.SOLVER_HOST_PREDICATE)
    for r in FALLBACK_REASONS:
        c[f"prune_fallback.{r}"] = m.counter_total(m.PRUNE_FALLBACK,
                                                   reason=r)
    c["prune_runs"] = m.counter_total(m.PRUNE_RUNS)
    for mode in ("kernel", "python"):
        c[f"victim_runs.{mode}"] = m.counter_total(m.VICTIM_SELECT_RUNS,
                                                   mode=mode)
    c["kernel_ms"] = m.histogram_total(m.SOLVER_KERNEL_LATENCY)
    return c


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after
            if after[k] != before[k]}


def top_phases(n: int = 12) -> dict:
    """The last cycle's ``n`` longest flight-recorder spans, by path."""
    from volcano_tpu.trace import tracer
    rec = tracer.last_record()
    if rec is None:
        return {}
    phases = sorted(tracer.flat_phases(rec).items(),
                    key=lambda kv: -kv[1]["ms"])[:n]
    return {path: e["ms"] for path, e in phases}


def kernel_spans() -> list:
    """(kernel, ms, tags) of every `kernel` span in the last cycle."""
    from volcano_tpu.trace import tracer
    rec = tracer.last_record()
    out = []

    def walk(s):
        if s.name == "kernel":
            tags = dict(s.tags or {})
            out.append((tags.pop("kernel", None), round(s.dur * 1000.0, 3),
                        tags))
        for c in s.children or ():
            walk(c)

    if rec is not None:
        walk(rec.root)
    return out


# -- phase 1: allocate ------------------------------------------------------

def schedule_cluster(n_nodes: int, n_gangs: int, solver_args=None,
                     tag: str = ""):
    """Populate a seeded cluster, run one Scheduler.run_once and drain the
    bind flush. Returns (binds {pod key: node}, cycle wall s, counter
    delta, kernel spans, store)."""
    from volcano_tpu.apiserver import ObjectStore
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.utils.synth import populate_store

    store = ObjectStore()
    sched = Scheduler(store, scheduler_conf=conf_text(solver_args))
    sched.cache.run()
    try:
        t0 = time.perf_counter()
        populate_store(store, n_nodes=n_nodes, n_jobs=n_gangs,
                       gang_size=GANG, queues=QUEUES, seed=SEED)
        t_pop = time.perf_counter() - t0
        c0 = counters()
        t0 = time.perf_counter()
        sched.run_once()
        wall = time.perf_counter() - t0
        c1 = counters()
        spans = kernel_spans()
        phases = top_phases()
        t0 = time.perf_counter()
        check(sched.cache.flush_executors(timeout=FLUSH_TIMEOUT_S),
              f"{tag}: bind flush did not drain in {FLUSH_TIMEOUT_S:g}s")
        t_flush = time.perf_counter() - t0
    finally:
        sched.cache.stop()
    binds = {f"{p.metadata.namespace}/{p.metadata.name}": p.spec.node_name
             for p in store.list("pods") if p.spec.node_name}
    d = delta(c0, c1)
    log(f"{tag}: populate={t_pop:.2f}s cycle={wall * 1000.0:.1f}ms "
        f"flush={t_flush * 1000.0:.1f}ms binds={len(binds)} "
        f"counters={json.dumps(d, sort_keys=True)}")
    for kernel, ms, tags in spans:
        log(f"{tag}: kernel span {kernel} {ms}ms tags={tags}")
    log(f"{tag}: longest spans (ms) {json.dumps(phases)}")
    return binds, wall, d, spans, store


def _task_order(key: str):
    return tuple(int(x) for x in re.findall(r"\d+", key))


def first_difference(got: dict, want: dict):
    for key in sorted(set(got) | set(want), key=_task_order):
        if got.get(key) != want.get(key):
            return key, got.get(key), want.get(key)
    return None


def check_binds_equal(got: dict, want: dict, what: str) -> None:
    diff = first_difference(got, want)
    if diff is not None:
        n = count_differences(got, want)
        key, g, w = diff
        raise SmokeFailure(f"{what}: {n} binds differ; first task {key}: "
                           f"{g!r} vs {w!r}")


def check_gangs_and_capacity(store, binds: dict) -> None:
    """Every gang bound whole or not at all (minMember = gang size), and
    no node over its allocatable cpu, memory or pod count."""
    from volcano_tpu.models.resource import Resource
    from volcano_tpu.models.objects import GROUP_NAME_ANNOTATION
    per_group: dict = {}
    used: dict = {}
    for p in store.list("pods"):
        key = f"{p.metadata.namespace}/{p.metadata.name}"
        g = p.metadata.annotations.get(GROUP_NAME_ANNOTATION)
        bound = key in binds
        per_group.setdefault(g, [0, 0])
        per_group[g][0] += 1
        per_group[g][1] += bound
        if bound:
            r = used.setdefault(binds[key], [Resource(), 0])
            for c in p.spec.containers:
                r[0].add(Resource.from_resource_list(c.requests))
            r[1] += 1
    broken = [g for g, (n, b) in per_group.items() if b not in (0, n)]
    check(not broken, f"{len(broken)} gangs partly bound, e.g. {broken[:3]}")
    for node in store.list("nodes"):
        u = used.get(node.metadata.name)
        if u is None:
            continue
        alloc = Resource.from_resource_list(node.status.allocatable)
        check(u[0].less_equal(alloc),
              f"node {node.metadata.name} over capacity: {u[0]} > {alloc}")
        pods = int(node.status.allocatable.get("pods", 110))
        check(u[1] <= pods, f"node {node.metadata.name} holds {u[1]} pods "
                            f"> {pods}")


def served_tiers(d: dict) -> list:
    return sorted(k.split(".", 1)[1] for k in d
                  if k.startswith("kernel_runs."))


def check_served(d: dict, tier: str, tag: str) -> None:
    """The cycle's placement ran on ``tier`` alone, with no solver
    fallback, no crashed pruning and no host-predicate sweep."""
    served = served_tiers(d)
    check(served == [tier], f"{tag}: served by {served}, not {tier}: {d}")
    check(not d.get("solver_fallback"), f"{tag}: solver fell back: {d}")
    check(not d.get("prune_fallback.crash"), f"{tag}: pruning crashed: {d}")
    check(not d.get("host_predicate"),
          f"{tag}: host-predicate fallback engaged")


def count_differences(a: dict, b: dict) -> int:
    return sum(1 for k in set(a) | set(b) if a.get(k) != b.get(k))


def phase_allocate(n_nodes: int = N_NODES, n_gangs: int = N_GANGS,
                   solver_args=None, expect_tier: str = "pallas") -> dict:
    """Cold + two warm cycles on the default solver conf. The kernel is
    checked against the scan on the same problem, and, where pruning
    engaged, the same kernel with pruning off against the dense scan
    reference: a truncated shortlist may choose other nodes than the
    dense kernel by design (docs/design/pruning.md section 5)."""
    from volcano_tpu.trace import tracer
    tracer.enable()
    args = dict(solver_args or {})
    log(f"phase allocate: {n_gangs * GANG} tasks x {n_nodes} nodes, conf "
        f"{CONF_PATH.name}, solver args {args or 'default'}")
    binds, cold, d, _, store = schedule_cluster(n_nodes, n_gangs, args,
                                                tag="cold")
    check_served(d, expect_tier, "cold")
    check(len(binds) > 0, "nothing was bound")
    check_gangs_and_capacity(store, binds)
    del store
    warm = []
    for i in (1, 2):
        wbinds, wall, wd, _, _ = schedule_cluster(n_nodes, n_gangs, args,
                                                  tag=f"warm{i}")
        check_served(wd, expect_tier, f"warm{i}")
        check_binds_equal(wbinds, binds, f"warm{i} vs cold")
        warm.append((wall, wd.get("kernel_ms", 0.0)))
    sbinds, _, sd, _, _ = schedule_cluster(
        n_nodes, n_gangs, dict(args, kernel="scan"), tag="scan")
    check_served(sd, "scan", "scan")
    check_binds_equal(binds, sbinds, f"{expect_tier} vs scan")
    pruned = bool(d.get("prune_runs"))
    dense = binds
    if pruned:
        dense, _, dd, _, dstore = schedule_cluster(
            n_nodes, n_gangs, dict(args, **{"prune.enable": "off"}),
            tag="dense")
        check_served(dd, expect_tier, "dense")
        check_gangs_and_capacity(dstore, dense)
        del dstore
    rbinds, rwall, rd, _, _ = schedule_cluster(
        n_nodes, n_gangs, dict(args, **REFERENCE_ARGS), tag="reference")
    check_served(rd, "scan", "reference")
    check_binds_equal(dense, rbinds, f"dense {expect_tier} vs reference")
    check(len(binds) >= len(rbinds),
          f"pruning lost placements: {len(binds)} < {len(rbinds)}")
    out = {"tasks": n_gangs * GANG, "nodes": n_nodes, "binds": len(binds),
           "tier": expect_tier, "pruned": pruned,
           "pruned_vs_dense_differences": count_differences(binds, dense),
           "cold_cycle_s": round(cold, 3),
           "cold_kernel_ms": round(d.get("kernel_ms", 0.0), 3),
           "warm_cycle_ms": [round(w * 1000.0, 3) for w, _ in warm],
           "warm_kernel_ms": [round(k, 3) for _, k in warm],
           "reference_cycle_ms": round(rwall * 1000.0, 3)}
    log(f"phase allocate passed: {json.dumps(out)}")
    return out


# -- phase 2: preempt -------------------------------------------------------

# preempt over a vectorizable plugin chain: every enabled preemptable
# plugin has a compiled form, so the victim kernel (ops/victims.py) serves
# it unless `victims.kernel: "off"` forces the Python walk
CONF_VICTIMS = """
actions: "preempt"
tiers:
- plugins:
  - name: priority
  - name: conformance
  - name: gang
- plugins:
  - name: predicates
  - name: nodeorder
"""


def victim_env(conf_src: str, vn_nodes: int, n_low: int, n_high: int):
    """Preemption under pressure: ``n_low`` low-priority gangs of 8
    (minAvailable 4) fill every node, and ``n_high`` high-priority gangs
    of 8 wait for room. Returns (store, cache, conf)."""
    from volcano_tpu.apiserver import ObjectStore
    from volcano_tpu.cache import SchedulerCache
    from volcano_tpu.framework import parse_scheduler_conf
    from volcano_tpu.models.objects import ObjectMeta, PriorityClass
    from volcano_tpu.utils.test_utils import (FakeBinder, FakeEvictor,
                                              build_node, build_pod,
                                              build_pod_group, build_queue)
    store = ObjectStore()
    cache = SchedulerCache(store, binder=FakeBinder(store),
                           evictor=FakeEvictor(store))
    cache.run()
    store.create("queues", build_queue("default", weight=1))
    store.create("priorityclasses", PriorityClass(
        metadata=ObjectMeta(name="high"), value=100))
    store.create("priorityclasses", PriorityClass(
        metadata=ObjectMeta(name="low"), value=1))
    for i in range(vn_nodes):
        store.create("nodes", build_node(
            f"node-{i}", {"cpu": "16", "memory": "32Gi"}))
    for j in range(n_low):
        store.create("podgroups", build_pod_group(
            f"lo-{j}", "ns1", "default", 4, phase="Running",
            priority_class="low"))
        for t in range(8):
            store.create("pods", build_pod(
                "ns1", f"lo-{j}-{t}", f"node-{(j * 8 + t) % vn_nodes}",
                "Running", {"cpu": "14", "memory": "28Gi"}, f"lo-{j}"))
    for j in range(n_high):
        store.create("podgroups", build_pod_group(
            f"hi-{j}", "ns1", "default", 8, phase="Inqueue",
            priority_class="high"))
        for t in range(8):
            store.create("pods", build_pod(
                "ns1", f"hi-{j}-{t}", "", "Pending",
                {"cpu": "14", "memory": "28Gi"}, f"hi-{j}"))
    return store, cache, parse_scheduler_conf(conf_src)


def preempt_evictions(conf_src: str, vn_nodes: int, n_low: int,
                      n_high: int, tag: str):
    from volcano_tpu.framework import close_session, get_action, open_session
    store, cache, conf = victim_env(conf_src, vn_nodes, n_low, n_high)
    try:
        c0 = counters()
        ssn = open_session(cache, conf.tiers, conf.configurations)
        t0 = time.perf_counter()
        get_action("preempt").execute(ssn)
        ms = (time.perf_counter() - t0) * 1000.0
        close_session(ssn)
        check(cache.flush_executors(timeout=FLUSH_TIMEOUT_S),
              f"{tag}: evict flush did not drain")
        d = delta(c0, counters())
        evicts = sorted(cache.evictor.evicts)
    finally:
        cache.stop()
    log(f"{tag}: preempt action {ms:.1f}ms evictions={len(evicts)} "
        f"counters={json.dumps(d, sort_keys=True)}")
    return evicts, ms, d


def phase_preempt(vn_nodes: int = 2000, n_low: int = 250,
                  n_high: int = 125) -> dict:
    log(f"phase preempt: {n_high * GANG} high-priority tasks x "
        f"{vn_nodes} nodes full of low-priority gangs")
    evicts, ms, d = preempt_evictions(CONF_VICTIMS, vn_nodes, n_low,
                                      n_high, "kernel")
    check(d.get("victim_runs.kernel", 0) >= 1,
          f"victim kernel did not run: {d}")
    check(not d.get("victim_runs.python"), f"Python walk ran: {d}")
    check(evicts, "the preempt scenario evicted nothing")
    off = CONF_VICTIMS + ("configurations:\n- name: solver\n  arguments:\n"
                          '    victims.kernel: "off"\n')
    ref, rms, rd = preempt_evictions(off, vn_nodes, n_low, n_high, "python")
    check(rd.get("victim_runs.python", 0) >= 1, f"reference walk: {rd}")
    if evicts != ref:
        only_k = sorted(set(evicts) - set(ref))[:3]
        only_p = sorted(set(ref) - set(evicts))[:3]
        raise SmokeFailure(f"evictions differ: kernel {len(evicts)} vs "
                           f"python {len(ref)}; kernel only {only_k}, "
                           f"python only {only_p}")
    out = {"evictions": len(evicts), "kernel_action_ms": round(ms, 3),
           "python_action_ms": round(rms, 3)}
    log(f"phase preempt passed: {json.dumps(out)}")
    return out


# -- mesh phase (--chips 4) -------------------------------------------------

def device_memory(tag: str) -> list:
    import jax
    rows = []
    for dv in jax.devices():
        st = dv.memory_stats() or {}
        rows.append((dv.id, st.get("bytes_in_use"),
                     st.get("peak_bytes_in_use")))
    log(f"{tag}: device memory (id, bytes_in_use, peak_bytes_in_use) "
        f"{rows}")
    return rows


def phase_mesh(n_nodes: int = N_NODES, n_gangs: int = N_GANGS,
               n_devices: int = 4) -> dict:
    """The mesh forced over ``n_devices`` chips. With pruning off, it
    must bind exactly as the single-device kernel does on one chip; with
    the default pruning, the sharded tier must serve the shortlist-union
    problem. Pruning over the mesh distils its shortlists per mesh
    partition, so pruned mesh-on and mesh-off runs solve different
    reduced problems and are not compared. Also reports the tier the
    default conf picks with the devices visible."""
    import jax
    from volcano_tpu.trace import tracer
    tracer.enable()
    check(len(jax.devices()) >= n_devices,
          f"need {n_devices} devices, have {len(jax.devices())}")
    log(f"phase mesh: {n_gangs * GANG} tasks x {n_nodes} nodes over "
        f"{n_devices} devices")
    mesh = {"mesh.enable": "true", "mesh.devices": n_devices}
    dense = {"prune.enable": "off"}
    on, won, don, _, store = schedule_cluster(n_nodes, n_gangs, mesh,
                                              tag="mesh-on")
    check_served(don, "sharded", "mesh-on")
    check(don.get("prune_runs", 0) >= 1 or n_nodes < 4096,
          f"mesh-on: pruning did not engage: {don}")
    peaks = [peak for _, _, peak in device_memory("mesh-on")[:n_devices]]
    if None not in peaks:   # the CPU backend reports no memory stats
        check(all(peaks), f"a mesh device held no memory: {peaks}")
    check_gangs_and_capacity(store, on)
    del store
    ond, wond, dond, _, _ = schedule_cluster(
        n_nodes, n_gangs, dict(mesh, **dense), tag="mesh-on-dense")
    check_served(dond, "sharded", "mesh-on-dense")
    device_memory("mesh-on-dense")
    off, woff, doff, _, _ = schedule_cluster(
        n_nodes, n_gangs, dict(dense, **{"mesh.enable": "false"}),
        tag="mesh-off-dense")
    off_tiers = served_tiers(doff)
    check(len(off_tiers) == 1 and off_tiers != ["sharded"],
          f"mesh-off-dense tiers: {off_tiers}")
    check_served(doff, off_tiers[0], "mesh-off-dense")
    check(len(ond) > 0, "nothing was bound")
    check_binds_equal(ond, off, "mesh on vs mesh off, pruning off")
    check(len(on) >= len(off),
          f"pruning lost placements: {len(on)} < {len(off)}")
    _, wdef, ddef, _, _ = schedule_cluster(n_nodes, n_gangs, None,
                                           tag="default-conf")
    out = {"binds": len(on), "mesh_cycle_ms": round(won * 1000.0, 3),
           "mesh_dense_cycle_ms": round(wond * 1000.0, 3),
           "single_dense_cycle_ms": round(woff * 1000.0, 3),
           "single_tier": off_tiers[0],
           "default_conf_tiers": served_tiers(ddef),
           "default_conf_cycle_ms": round(wdef * 1000.0, 3)}
    log(f"phase mesh passed: {json.dumps(out)}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded mesh phase")
    args = ap.parse_args(argv)
    try:
        info = require_tpu()
        from volcano_tpu.utils.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        t0 = time.perf_counter()
        if args.chips == 4:
            phase_mesh(n_devices=4)
        else:
            phase_allocate()
            phase_preempt()
        log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
