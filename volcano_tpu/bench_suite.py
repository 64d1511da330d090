"""The five BASELINE.md benchmark configs + full-cycle measurements.

Run via ``python bench.py --all`` (writes BENCH_DETAILS.json). The driver's
headline metric stays the single 50k x 10k kernel line from ``bench.py``;
this suite reports the full table:

  1 example/job.yaml-shaped single PodGroup gang (cycle sanity)
  2 1k tasks x 100 nodes, predicates + binpack (full cycle)
  3 DRF multi-queue fair-share: 4 queues, 5k tasks (full cycle)
  4 preempt victim selection: 5k starving tasks x 10k nodes (action)
  5 50k tasks x 10k nodes topology-aware (rack affinity static score):
    gang-allocate kernel, plus the node-axis-sharded variant on the mesh

plus the end-to-end ``runOnce`` (snapshot -> encode -> place -> commit)
latency at 50k x 10k — the reference's 1 s --schedule-period budget covers
runOnce (pkg/scheduler/scheduler.go:90), not just the placement math.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, List, Optional

CONF_FULL = """
actions: "enqueue, allocate"
tiers:
- plugins:
  - name: priority
  - name: gang
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
"""

CONF_PREEMPT = """
actions: "preempt"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: predicates
  - name: nodeorder
"""


def log(msg: str) -> None:
    print(f"[bench-suite] {msg}", file=sys.stderr, flush=True)


def _platform() -> str:
    import jax
    return jax.devices()[0].platform


def _cycle_env(conf_text: str):
    from volcano_tpu.apiserver import ObjectStore
    from volcano_tpu.cache import SchedulerCache
    from volcano_tpu.framework import parse_scheduler_conf
    from volcano_tpu.utils.test_utils import FakeBinder, FakeEvictor

    store = ObjectStore()
    binder = FakeBinder(store)
    cache = SchedulerCache(store, binder=binder,
                           evictor=FakeEvictor(store))
    cache.run()
    return store, cache, binder, parse_scheduler_conf(conf_text)


def _run_cycle(cache, conf) -> float:
    """One measured cycle under the production GC policy: the scheduler
    loop freezes the long-lived graph and pauses cyclic GC inside runOnce
    (scheduler.py run/run_once), so the bench does the same."""
    import gc

    from volcano_tpu.framework import close_session, get_action, open_session
    from volcano_tpu.trace import tracer as tr
    from volcano_tpu.utils import gcguard

    gc.collect()
    gc.freeze()
    gcguard.pause()   # nest-safe vs the cache executor's own GC pause
    try:
        t0 = time.perf_counter()
        with tr.cycle():   # flight recorder (no-op unless tracer.enable())
            cache.begin_cycle()
            try:
                ssn = open_session(cache, conf.tiers, conf.configurations,
                                   actions=conf.actions)
                try:
                    for name in conf.actions:
                        action = get_action(name)
                        if action is not None:
                            with tr.span(f"action:{name}", action=name):
                                action.execute(ssn)
                finally:
                    close_session(ssn)
            finally:
                cache.end_cycle()
        ms = (time.perf_counter() - t0) * 1000.0
        if tr.is_enabled():
            # /debug/timeseries sample per cycle — the bench drives
            # cycles directly (no Scheduler.run_once), so it samples
            # here; the ring tail rides the bench JSON row
            from volcano_tpu.metrics import timeseries
            timeseries.sample(time.time(), extra={
                "cycle_ms": round(ms, 3), "seq": tr.current_seq()})
        return ms
    finally:
        gcguard.resume()
        gc.unfreeze()


def _populate(store, n_nodes, n_jobs, gang, queues=None, cpu="2",
              mem="4Gi", node_cpu="64", node_mem="256Gi", **constraints):
    """``constraints`` forwards populate_store's constraint-shape kwargs
    (zones / spread_every / anti_every — docs/design/constraints.md)."""
    from volcano_tpu.utils.synth import populate_store
    populate_store(store, n_nodes=n_nodes, n_jobs=n_jobs, gang_size=gang,
                   queues=queues, cpu_req=cpu, mem_req=mem,
                   node_cpu=node_cpu, node_mem=node_mem, **constraints)



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


def victim_env(conf_text, vn_nodes=2000, n_low=250, n_high=125):
    """Preemption under pressure: ``n_low`` low-priority gangs of 8
    (minAvailable 4) fill every node, and ``n_high`` high-priority gangs
    of 8 wait for room. Returns _cycle_env's (store, cache, binder,
    conf)."""
    from volcano_tpu.models.objects import ObjectMeta, PriorityClass
    from volcano_tpu.utils.test_utils import (build_node, build_pod,
                                              build_pod_group, build_queue)
    store, cache, binder, conf = _cycle_env(conf_text)
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
    return store, cache, binder, conf


def _warm_cycle(conf_text: str, runs: int = 3, flush_timeout: float = 120.0,
                **populate_kwargs):
    """Cold cycle (compile) on one env, then measured warm cycles on fresh
    identical envs with the previous env's executor drained first. Takes
    the min of ``runs`` warm measurements — single-shot wall numbers on a
    shared machine carry +-25% co-tenant noise (same protocol as
    bench.py's cycle_worker). Returns
    (ms, flush_ms, binder, cache, conf, trace_record) of the winning env
    (trace_record is the flight-recorder CycleRecord of the winning cycle,
    None unless tracing is enabled)."""
    from volcano_tpu.trace import tracer as tr

    store, cache, binder, conf = _cycle_env(conf_text)
    _populate(store, **populate_kwargs)
    _run_cycle(cache, conf)                # includes compile
    cache.flush_executors(timeout=flush_timeout)
    cache.stop()                           # free the cold env before the
    #                                        measured runs — the executor
    #                                        thread pins the env alive, so
    #                                        without stop() every env
    #                                        leaks and later runs pay the
    #                                        accumulated heap pressure
    del store, cache, binder
    best = (float("inf"), 0.0, None, None, None, None)
    for _ in range(runs):
        store2, cache2, binder2, conf2 = _cycle_env(conf_text)
        _populate(store2, **populate_kwargs)
        ms = _run_cycle(cache2, conf2)
        rec = tr.last_record() if tr.is_enabled() else None
        t0 = time.perf_counter()
        cache2.flush_executors(timeout=flush_timeout)
        flush_ms = (time.perf_counter() - t0) * 1000.0
        if ms < best[0]:
            if best[3] is not None:
                best[3].stop()             # non-winning env: release it
            best = (ms, flush_ms, binder2, cache2, conf2, rec)
        else:
            cache2.stop()
    return best


def config_1() -> Dict:
    """Single gang-of-3 PodGroup (example/job.yaml shape), full cycle."""
    ms, _, binder, _, _, _ = _warm_cycle(CONF_FULL, n_nodes=4,
                                         n_jobs=1, gang=3, node_cpu="8",
                                         node_mem="16Gi")
    assert len(binder.binds) == 3, binder.binds
    return {"config": 1, "desc": "single gang-of-3 PodGroup, full cycle",
            "value_ms": round(ms, 2), "binds": len(binder.binds),
            "platform": _platform()}


def config_2() -> Dict:
    """1k tasks x 100 nodes, predicates + binpack, full cycle."""
    ms, _, binder, _, _, _ = _warm_cycle(CONF_FULL, n_nodes=100,
                                         n_jobs=125, gang=8)
    return {"config": 2, "desc": "1k tasks x 100 nodes full cycle",
            "value_ms": round(ms, 2), "binds": len(binder.binds),
            "platform": _platform()}


def config_3() -> Dict:
    """DRF multi-queue fair share: 4 queues, 5k tasks, full cycle."""
    queues = [(f"q{i}", w) for i, w in enumerate([1, 2, 3, 4])]
    ms, _, binder, _, _, _ = _warm_cycle(CONF_FULL, n_nodes=1000,
                                         n_jobs=625, gang=8, queues=queues)
    return {"config": 3,
            "desc": "drf 4-queue fair share, 5k tasks x 1k nodes full cycle",
            "value_ms": round(ms, 2), "binds": len(binder.binds),
            "platform": _platform()}


def config_4(n_nodes=10000, n_low=1250, n_high=625) -> Dict:
    """Preempt victim selection at 5k starving tasks x 10k nodes."""
    from volcano_tpu.framework import get_action, open_session
    from volcano_tpu.models.objects import ObjectMeta, PriorityClass
    from volcano_tpu.utils.test_utils import (build_node, build_pod,
                                              build_pod_group, build_queue)

    store, cache, binder, conf = _cycle_env(CONF_PREEMPT)
    store.create("queues", build_queue("default", weight=1))
    store.create("priorityclasses",
                 PriorityClass(metadata=ObjectMeta(name="high"), value=100))
    store.create("priorityclasses",
                 PriorityClass(metadata=ObjectMeta(name="low"), value=1))
    for i in range(n_nodes):
        store.create("nodes", build_node(f"node-{i}",
                                         {"cpu": "16", "memory": "32Gi"}))
    for j in range(n_low):
        store.create("podgroups", build_pod_group(
            f"lo-{j}", "ns1", "default", 8, phase="Running",
            priority_class="low"))
        for t in range(8):
            store.create("pods", build_pod(
                "ns1", f"lo-{j}-{t}", f"node-{(j * 8 + t) % n_nodes}",
                "Running", {"cpu": "14", "memory": "28Gi"}, f"lo-{j}"))
    for j in range(n_high):
        store.create("podgroups", build_pod_group(
            f"hi-{j}", "ns1", "default", 8, phase="Inqueue",
            priority_class="high"))
        for t in range(8):
            store.create("pods", build_pod(
                "ns1", f"hi-{j}-{t}", "", "Pending",
                {"cpu": "8", "memory": "16Gi"}, f"hi-{j}"))
    cache.begin_cycle()    # production runs actions inside a cycle window
    try:
        ssn = open_session(cache, conf.tiers, conf.configurations)
        t0 = time.perf_counter()
        get_action("preempt").execute(ssn)
        ms = (time.perf_counter() - t0) * 1000.0
    finally:
        cache.end_cycle()
    from volcano_tpu.models.job_info import TaskStatus
    evicted = sum(1 for j in ssn.jobs.values() for t in j.tasks.values()
                  if t.status == TaskStatus.Releasing)
    return {"config": 4,
            "desc": f"preempt {n_high * 8} starving x {n_nodes} nodes",
            "value_ms": round(ms, 2), "evicted": evicted,
            "platform": _platform()}


def config_5(n_tasks=50_000, n_nodes=10_000, runs=3,
             sharded_devices: Optional[int] = None) -> List[Dict]:
    """50k x 10k rack-affinity kernel: single device + sharded mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from volcano_tpu.ops.allocate import gang_allocate_chunked
    from volcano_tpu.ops.score import ScoreWeights
    from volcano_tpu.utils.synth import synth_arrays

    out: List[Dict] = []
    sa = synth_arrays(n_tasks, n_nodes, gang_size=8, seed=42,
                      utilization=0.3, rack_affinity=True)
    weights = ScoreWeights.make(sa.group_req.shape[1], binpack=1.0)
    args = [jnp.asarray(a) for a in sa.args] + [weights]
    r = gang_allocate_chunked(*args)
    jax.block_until_ready(r[0])
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        r = gang_allocate_chunked(*args)
        jax.block_until_ready(r[0])
        best = min(best, (time.perf_counter() - t0) * 1000.0)
    out.append({"config": 5,
                "desc": f"{n_tasks // 1000}k x {n_nodes // 1000}k "
                        "rack-affinity gang-allocate kernel (chunked)",
                "value_ms": round(best, 2),
                "platform": _platform()})

    # the off-TPU production kernel (solver `auto` picks it): native C++ —
    # decisions verified against the XLA result on this exact production
    # shape, every bench run (a divergent solver must never publish a
    # fast number for wrong placements). Equality is up to sub-ulp score
    # ties: XLA's fused-emission float results are context-dependent, so
    # bit-identical argmax on EXACT ties is unattainable across backends
    # (the Pallas kernel carries the same contract —
    # tests/test_pallas_allocate.py); gang outcomes and placement counts
    # must match exactly and every native placement must replay feasibly.
    from volcano_tpu.ops.native import available, gang_allocate_native
    if _platform() != "tpu" and available():
        r2 = gang_allocate_native(*sa.args, weights)
        a1, a2 = np.asarray(r[0]), r2[0]
        assert np.array_equal(np.asarray(r[2]), r2[2]) \
            and np.array_equal(np.asarray(r[3]), r2[3]), \
            "native solver gang outcomes diverged at 50k x 10k"
        assert int((a1 >= 0).sum()) == int((a2 >= 0).sum()), \
            "native solver placement count diverged at 50k x 10k"
        ndiff = int((a1 != a2).sum())
        if ndiff:
            log(f"config_5: native vs XLA differ on {ndiff} sub-ulp "
                "score-tie placements (contract: tie-equivalent)")
            idle_chk = np.asarray(sa.node_idle, np.float32).copy()
            gr = np.asarray(sa.group_req, np.float32)
            tg = np.asarray(sa.task_group)
            for t in np.flatnonzero(a2 >= 0):
                idle_chk[a2[t]] -= gr[tg[t]]
            assert (idle_chk >= -np.asarray(sa.eps)[None, :] - 1e-3).all(), \
                "native placements do not replay feasibly"
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            r2 = gang_allocate_native(*sa.args, weights)
            best = min(best, (time.perf_counter() - t0) * 1000.0)
        out.append({"config": 5,
                    "desc": f"{n_tasks // 1000}k x {n_nodes // 1000}k "
                            "rack-affinity kernel (native C++, the "
                            "off-TPU production path)",
                    "value_ms": round(best, 2),
                    "platform": _platform()})

    if sharded_devices and len(jax.devices()) >= sharded_devices:
        from jax.sharding import Mesh

        from volcano_tpu.ops.sharded import (make_sharded_gang_allocate,
                                             shard_synth)
        mesh = Mesh(np.array(jax.devices()[:sharded_devices]), ("nodes",))
        n_pad = ((n_nodes + sharded_devices - 1) // sharded_devices) \
            * sharded_devices
        sa2 = synth_arrays(n_tasks, n_nodes, gang_size=8, seed=42,
                           utilization=0.3, rack_affinity=True,
                           node_pad_to=max(n_pad, 256))
        fn = make_sharded_gang_allocate(mesh)
        sargs = shard_synth(mesh, sa2)
        r = fn(*sargs, weights)
        jax.block_until_ready(r[0])
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            r = fn(*sargs, weights)
            jax.block_until_ready(r[0])
            best = min(best, (time.perf_counter() - t0) * 1000.0)
        out.append({"config": 5,
                    "desc": f"same, node-axis sharded over "
                            f"{sharded_devices}-device mesh",
                    "value_ms": round(best, 2),
                    "platform": _platform()})
    return out


def full_cycle_50k(n_tasks=50_000, n_nodes=10_000) -> Dict:
    """End-to-end runOnce at 50k x 10k through the store-backed cache."""
    from volcano_tpu.trace import tracer as tr

    tr.enable()   # BENCH rows carry per-phase attribution from now on
    log(f"building {n_tasks}x{n_nodes} cluster through the store "
        "(this takes a while)")
    warm, flush_ms, binder2, cache2, conf2, rec = _warm_cycle(
        CONF_FULL, flush_timeout=600.0,
        n_nodes=n_nodes, n_jobs=n_tasks // 8, gang=8)
    # the steady-state duty cycle: everything bound, nothing pending —
    # what the scheduler runs every period between arrivals (on the
    # winning env, whose flush completed)
    steady = min(_run_cycle(cache2, conf2) for _ in range(2))
    out = {"config": "full_cycle",
           "desc": f"end-to-end runOnce {n_tasks // 1000}k tasks x "
                   f"{n_nodes // 1000}k nodes (snapshot+encode+place+"
                   "commit; min of 3 warm runs; async bind flush "
                   "reported separately)",
           "value_ms": round(warm, 2),
           "steady_state_ms": round(steady, 2),
           "bind_flush_ms": round(flush_ms, 2),
           "binds": len(binder2.binds),
           "platform": _platform()}
    if rec is not None:
        out["phases"] = tr.flat_phases(rec)
        out["flush_phases"] = tr.async_phases(rec)
        out["trace_coverage"] = tr.summary(rec)["coverage"]
    return out


def churn_load(n_nodes=10_000, resident_jobs=6_250, gang=8,
               arrival_jobs=125, cycles=50) -> Dict:
    """Sustained-churn duty cycle: ``arrival_jobs`` gangs arrive and the
    oldest as many complete EVERY cycle against a full resident cluster,
    with node churn on; cycles run back-to-back (the executor's
    write-behind backlog competes with the foreground exactly as in a
    sustained burst). Reports p50/p95 runOnce latency over ``cycles``
    measured cycles — the headline duty-cycle number (a quiet-cluster
    steady state flatters the scheduler; real clusters churn)."""
    import numpy as np

    from volcano_tpu.utils.test_utils import (build_node, build_pod,
                                              build_pod_group)

    store, cache, binder, conf = _cycle_env(CONF_FULL)
    log(f"churn_load: building resident {resident_jobs * gang} tasks "
        f"x {n_nodes} nodes")
    _populate(store, n_nodes=n_nodes, n_jobs=resident_jobs, gang=gang)
    _run_cycle(cache, conf)            # compile + place the resident set
    cache.flush_executors(timeout=600.0)

    live_jobs = list(range(resident_jobs))
    next_job = resident_jobs
    next_node = n_nodes
    lat = []
    t_wall = time.perf_counter()
    for c in range(cycles):
        # arrivals: new Inqueue gangs
        for j in range(next_job, next_job + arrival_jobs):
            store.create("podgroups", build_pod_group(
                f"pg-{j}", "default", "default", gang, phase="Inqueue"))
            for t in range(gang):
                store.create("pods", build_pod(
                    "default", f"job{j}-task{t}", "", "Pending",
                    {"cpu": "2", "memory": "4Gi"}, groupname=f"pg-{j}"))
            live_jobs.append(j)
        next_job += arrival_jobs
        # completions: the oldest gangs finish and their objects go away
        for j in live_jobs[:arrival_jobs]:
            for t in range(gang):
                try:
                    store.delete("pods", f"job{j}-task{t}", "default",
                                 skip_admission=True)
                except KeyError:
                    pass
            try:
                store.delete("podgroups", f"pg-{j}", "default",
                             skip_admission=True)
            except KeyError:
                pass
        live_jobs = live_jobs[arrival_jobs:]
        # node churn: one node leaves, a fresh one joins
        try:
            store.delete("nodes", f"node-{(next_node - n_nodes) % n_nodes}",
                         skip_admission=True)
        except KeyError:
            pass
        store.create("nodes", build_node(
            f"node-{next_node}", {"cpu": "64", "memory": "256Gi",
                                  "pods": "110"}))
        next_node += 1
        ms = _run_cycle(cache, conf)
        lat.append(ms)
    wall_s = time.perf_counter() - t_wall
    t0 = time.perf_counter()
    cache.flush_executors(timeout=600.0)
    drain_ms = (time.perf_counter() - t0) * 1000.0
    p50, p95 = np.percentile(lat, [50, 95])
    return {"config": "churn_load",
            "desc": f"sustained churn: {arrival_jobs * gang} arrivals + "
                    f"completions/cycle at {resident_jobs * gang} resident "
                    f"x {n_nodes} nodes, node churn on, {cycles} "
                    "back-to-back cycles",
            "p50_ms": round(float(p50), 2), "p95_ms": round(float(p95), 2),
            "max_ms": round(float(max(lat)), 2),
            "wall_s": round(wall_s, 1),
            "final_drain_ms": round(drain_ms, 2),
            "binds": len(binder.binds), "platform": _platform()}


CONF_RECLAIM = """
actions: "reclaim"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
"""


def config_reclaim(n_nodes=10_000, n_running=1_250, n_pending=625) -> Dict:
    """Cross-queue reclaim at scale (reclaim.go:84-188): q-over holds the
    whole cluster with Running gangs while q-under's pending jobs reclaim
    their deserved share; measures the reclaim action's execute latency."""
    from volcano_tpu.framework import get_action, open_session
    from volcano_tpu.utils.test_utils import (build_node, build_pod,
                                              build_pod_group, build_queue)

    store, cache, binder, conf = _cycle_env(CONF_RECLAIM)
    store.create("queues", build_queue("q-over", weight=1))
    store.create("queues", build_queue("q-under", weight=1))
    for i in range(n_nodes):
        store.create("nodes", build_node(f"node-{i}",
                                         {"cpu": "16", "memory": "32Gi"}))
    for j in range(n_running):
        store.create("podgroups", build_pod_group(
            f"ov-{j}", "ns1", "q-over", 8, phase="Running"))
        for t in range(8):
            store.create("pods", build_pod(
                "ns1", f"ov-{j}-{t}", f"node-{(j * 8 + t) % n_nodes}",
                "Running", {"cpu": "14", "memory": "28Gi"}, f"ov-{j}"))
    for j in range(n_pending):
        store.create("podgroups", build_pod_group(
            f"un-{j}", "ns1", "q-under", 8, phase="Inqueue"))
        for t in range(8):
            store.create("pods", build_pod(
                "ns1", f"un-{j}-{t}", "", "Pending",
                {"cpu": "8", "memory": "16Gi"}, f"un-{j}"))
    cache.begin_cycle()
    try:
        ssn = open_session(cache, conf.tiers, conf.configurations)
        t0 = time.perf_counter()
        get_action("reclaim").execute(ssn)
        ms = (time.perf_counter() - t0) * 1000.0
    finally:
        cache.end_cycle()
    from volcano_tpu.models.job_info import TaskStatus
    evicted = sum(1 for j in ssn.jobs.values() for t in j.tasks.values()
                  if t.status == TaskStatus.Releasing)
    return {"config": "reclaim",
            "desc": f"cross-queue reclaim {n_pending * 8} reclaimers x "
                    f"{n_nodes} nodes ({n_running * 8} running victims "
                    "pool)",
            "value_ms": round(ms, 2), "evicted": evicted,
            "platform": _platform()}


def machine_calibration() -> Dict:
    """Co-tenant load fingerprint: wall time of a fixed single-core numpy
    workload, recorded alongside the suite so readers can compare two
    captures' machine conditions. This box is SHARED: same-day A/B ran
    identical round-4 code at 655 ms (round-4 capture) vs 1528 ms
    (round-5 re-run) on the preempt config — up to ~2.3x wall drift.
    Round-5 observed range for this fingerprint: ~32-40 ms."""
    import numpy as np
    rng = np.random.default_rng(0)
    a = rng.random(2_000_000)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.sort(a.copy())
        best = min(best, (time.perf_counter() - t0) * 1000.0)
    return {"config": "machine_calibration",
            "desc": "fixed numpy sort (2M f64), min of 3 — compare across "
                    "captures; round-5 observed ~32-40 ms",
            "value_ms": round(best, 2)}


def run_all(full_scale: bool = True) -> List[Dict]:
    import jax

    results: List[Dict] = []

    def run(name, fn):
        """Per-config isolation: one failing config must not abort the
        suite (the artifact write happens only after run_all returns)."""
        log(f"running {name}")
        try:
            r = fn()
        except Exception as e:
            log(f"{name} FAILED: {e!r}")
            results.append({"config": name, "error": repr(e)[:300]})
            return
        results.extend(r if isinstance(r, list) else [r])
        log(f"{name}: {results[-1]}")

    results.append(machine_calibration())
    log(f"calibration: {results[-1]}")
    run("config_1", config_1)
    run("config_2", config_2)
    run("config_3", config_3)
    run("config_4", config_4 if full_scale else
        lambda: config_4(n_nodes=2000, n_low=250, n_high=125))
    run("config_reclaim", config_reclaim if full_scale else
        lambda: config_reclaim(n_nodes=2000, n_running=250, n_pending=125))
    n_dev = len(jax.devices())
    run("config_5", (lambda: config_5(
        sharded_devices=n_dev if n_dev >= 2 else None)) if full_scale else
        (lambda: config_5(5_000, 1_000,
                          sharded_devices=n_dev if n_dev >= 2 else None)))
    if full_scale:
        run("full_cycle_50k", full_cycle_50k)
        run("churn_load", churn_load)
    else:
        run("churn_load", lambda: churn_load(
            n_nodes=1000, resident_jobs=625, arrival_jobs=25, cycles=10))
    results.append(machine_calibration())   # load may drift over the run
    log(f"calibration (end): {results[-1]}")
    return results
