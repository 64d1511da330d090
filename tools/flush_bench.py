"""Flush-only micro-benchmark: time a coalesced bind flush through the
production cache + store (write-behind applies, sharded three-stage
patch pipeline, bulk echo ingest) WITHOUT a scheduling cycle — seconds,
not minutes, so it can gate every CI run (`make flush-bench`, wired into
`make sim-smoke`).

Default shape is the 5k-bind CI gate; ``--tasks/--nodes`` scale it up to
the full 50k x 10k regime so the commit path can be measured standalone
(``python tools/flush_bench.py --tasks 50000 --nodes 10000``), and
``--profile`` wraps the flush in cProfile and prints the top cumulative
entries — the fastest way to see where the remaining flush wall-clock
lives without paying a full scheduling cycle.

Runs the identical burst TWICE on fresh envs and fails (exit 1) unless
the two runs are bit-identical — same journal (rv, action, key,
node_name) sequence, same per-pod resource_versions, same bind set, and
the same lifecycle-LEDGER aggregate fingerprint (the store runs on a
virtual clock here, so ledger stamps are reproducible) — which is
exactly the determinism contract the sharded pipeline promises the churn
simulator (docs/design/bind_pipeline.md): shard assignment, rv
reservation, publish order and echo delivery order are pure functions of
the input burst.

Prints one JSON line: {"metric": "bind_flush_<n>_ms", "value": <best ms>,
"runs": [...], "binds": n, "deterministic": true}.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GANG = 8
FLUSH_TIMEOUT_S = 600.0


def build_env(n_nodes: int, n_jobs: int):
    from volcano_tpu.apiserver import ObjectStore
    from volcano_tpu.cache import SchedulerCache
    from volcano_tpu.utils.clock import FakeClock
    from volcano_tpu.utils.test_utils import (FakeBinder, FakeEvictor,
                                              build_node, build_pod,
                                              build_pod_group, build_queue)

    # virtual clock: ledger stamps (submitted/bind_staged/...) become a
    # pure function of the burst, so the double-run gate can hold the
    # ledger aggregate fingerprint bit-identical alongside the journal
    store = ObjectStore(clock=FakeClock(start=1.0))
    binder = FakeBinder(store)
    cache = SchedulerCache(store, binder=binder, evictor=FakeEvictor(store))
    cache.run()
    store.create("queues", build_queue("default", weight=1))
    for i in range(n_nodes):
        store.create("nodes", build_node(
            f"node-{i}", {"cpu": "64", "memory": "256Gi", "pods": "110"}))
    for j in range(n_jobs):
        store.create("podgroups", build_pod_group(
            f"pg-{j}", "default", "default", GANG, phase="Inqueue"))
        for t in range(GANG):
            store.create("pods", build_pod(
                "default", f"job{j}-task{t}", "", "Pending",
                {"cpu": "2", "memory": "4Gi"}, groupname=f"pg-{j}"))
    return store, cache, binder


def run_once(n_tasks: int, n_nodes: int, profile: bool = False) -> dict:
    """One populated env -> one coalesced bind burst -> full flush."""
    from volcano_tpu.trace import ledger
    n_jobs = n_tasks // GANG
    store, cache, binder = build_env(n_nodes, n_jobs)
    ledger.reset()
    ledger.enable()
    # the ledger only tracks pods it saw submitted; stamp them the way
    # watch ingest would have (build_env predates enable())
    with cache.mutex:
        for job in cache.jobs.values():
            for t in job.tasks.values():
                ledger.stamp(t.key(), "submitted", store.clock.now(),
                             job=t.job)
    # stage the bind pairs exactly as the allocate action's commit does:
    # per-gang bind_batch calls against the live cache tasks, nodes
    # assigned round-robin (~5 pods per node at every supported shape)
    with cache.mutex:
        jobs = sorted(cache.jobs.values(), key=lambda j: j.uid)
        gangs = []
        i = 0
        for job in jobs:
            tasks = sorted(job.tasks.values(), key=lambda t: t.uid)
            pairs = []
            for t in tasks:
                pairs.append((t, f"node-{i % n_nodes}"))
                i += 1
            gangs.append(pairs)
    prof = prof_echo = unhook = None
    if profile:
        # the flush executes on the cache's executor thread and the
        # store's echo-delivery worker, not here — hook one profiler
        # around the drain and a second around the per-shard deliveries
        import cProfile

        from volcano_tpu.apiserver.store import ObjectStore
        from volcano_tpu.cache.cache import SchedulerCache
        prof = cProfile.Profile()
        prof_echo = cProfile.Profile()
        orig_drain = SchedulerCache._drain_binds
        orig_deliver = ObjectStore._deliver_patch_pairs

        def profiled_drain(self):
            prof.enable()
            try:
                orig_drain(self)
            finally:
                prof.disable()

        def profiled_deliver(self, watches, prs):
            try:
                prof_echo.enable()
            except ValueError:
                return orig_deliver(self, watches, prs)  # on drain thread
            try:
                return orig_deliver(self, watches, prs)
            finally:
                prof_echo.disable()

        SchedulerCache._drain_binds = profiled_drain
        ObjectStore._deliver_patch_pairs = profiled_deliver

        def unhook():
            SchedulerCache._drain_binds = orig_drain
            ObjectStore._deliver_patch_pairs = orig_deliver
    t0 = time.perf_counter()
    try:
        for pairs in gangs:
            cache.bind_batch(pairs)
        if not cache.flush_executors(timeout=FLUSH_TIMEOUT_S):
            print(json.dumps({"metric": f"bind_flush_{n_tasks}_ms",
                              "value": None, "flush_timeout": True}))
            sys.exit(1)
        ms = (time.perf_counter() - t0) * 1000.0
    finally:
        if unhook is not None:
            unhook()
    if prof is not None:
        import pstats
        print("== executor thread ==", file=sys.stderr)
        pstats.Stats(prof, stream=sys.stderr).sort_stats(
            "cumulative").print_stats(45)
        print("== echo delivery thread ==", file=sys.stderr)
        pstats.Stats(prof_echo, stream=sys.stderr).sort_stats(
            "cumulative").print_stats(30)

    h = hashlib.sha256()
    with store._lock:
        for rv, action, kind, o in store._journal:
            h.update(f"{rv}|{action}|{kind}|{store.key_of(kind, o)}|"
                     f"{getattr(o.spec, 'node_name', '')}\n".encode())
        tail_ok = store._journal_tail == store._rv \
            and not store._journal_parked \
            and not any(store._inflight.values())
    for p in sorted(store.list_refs("pods"),
                    key=lambda p: p.metadata.key()):
        h.update(f"{p.metadata.key()}|{p.metadata.resource_version}|"
                 f"{p.spec.node_name}\n".encode())
    unbound = sum(1 for p in store.list_refs("pods")
                  if not p.spec.node_name)
    ledger_fp = ledger.fingerprint()
    ledger_stats = ledger.stats()
    h.update(ledger_fp.encode())
    cache.stop()
    ledger.disable()
    ledger.reset()
    return {"ms": ms, "binds": len(binder.binds),
            "fingerprint": h.hexdigest(), "unbound": unbound,
            "journal_ok": tail_ok, "ledger_fingerprint": ledger_fp,
            "ledger_completed": ledger_stats["completed"],
            "ledger_open": ledger_stats["open"]}


def main() -> None:
    ap = argparse.ArgumentParser(
        description="flush-only bind-commit micro-benchmark")
    ap.add_argument("--tasks", type=int, default=5_000,
                    help="binds per run (gangs of 8; default: the 5k CI "
                         "gate shape, 50000 = the full paper regime)")
    ap.add_argument("--nodes", type=int, default=1_000,
                    help="nodes in the env (default 1000; 10000 = full "
                         "regime)")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile the first run's flush and print the "
                         "top cumulative entries to stderr")
    args = ap.parse_args()
    n_tasks = (args.tasks // GANG) * GANG

    runs = [run_once(n_tasks, args.nodes, profile=args.profile),
            run_once(n_tasks, args.nodes)]
    deterministic = runs[0]["fingerprint"] == runs[1]["fingerprint"]
    ok = deterministic \
        and all(r["binds"] == n_tasks for r in runs) \
        and all(r["unbound"] == 0 for r in runs) \
        and all(r["journal_ok"] for r in runs) \
        and all(r["ledger_completed"] == n_tasks for r in runs) \
        and all(r["ledger_open"] == 0 for r in runs)
    print(json.dumps({
        "metric": f"bind_flush_{n_tasks}_ms",
        "value": round(min(r["ms"] for r in runs), 2),
        "unit": "ms",
        "runs": [round(r["ms"], 2) for r in runs],
        "binds": runs[0]["binds"],
        "deterministic": deterministic,
        "journal_ok": all(r["journal_ok"] for r in runs),
        "ledger_completed": runs[0]["ledger_completed"],
        "fingerprint": runs[0]["fingerprint"][:16],
        "ledger_fingerprint": runs[0]["ledger_fingerprint"][:16],
    }))
    if not ok:
        for i, r in enumerate(runs):
            print(f"[flush-bench] run {i}: binds={r['binds']} "
                  f"unbound={r['unbound']} journal_ok={r['journal_ok']} "
                  f"ledger={r['ledger_completed']}/{r['ledger_open']} open "
                  f"fingerprint={r['fingerprint'][:16]} "
                  f"ledger_fp={r['ledger_fingerprint'][:16]}",
                  file=sys.stderr)
        print("[flush-bench] FAILED: non-deterministic or incomplete flush",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
