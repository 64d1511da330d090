"""Pod lifecycle telemetry tests: ledger transition ordering, hop-sum ==
e2e under a virtual clock (bit-identical double run), /debug/latency +
/debug/timeseries over HTTP, correlation IDs across the store seam
(RemoteStore round-trip, scheduler restart), solver profiling counters,
and the vcctl debug CLI. The PR 1 <2% tracer-overhead gate
(tests/test_trace.py::test_tracer_overhead_under_two_percent) covers the
ledger too: tracer.enable()/disable() toggles both."""

import json
import os
import time
import urllib.error
import urllib.request

import jax
import pytest

from volcano_tpu.apiserver import ObjectStore
from volcano_tpu.cache import SchedulerCache
from volcano_tpu.metrics import metrics as m
from volcano_tpu.metrics import timeseries
from volcano_tpu.metrics.server import MetricsServer
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.trace import ledger, tracer
from volcano_tpu.utils.clock import FakeClock
from volcano_tpu.utils.test_utils import (FakeBinder, FakeEvictor,
                                          build_node, build_pod,
                                          build_pod_group, build_queue)

CONF = """
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


@pytest.fixture(autouse=True)
def _clean():
    tracer.reset()
    tracer.set_budgets({})
    ledger.reset()
    timeseries.reset()
    m.reset()
    yield
    tracer.disable()
    tracer.reset()
    tracer.set_budgets({})
    ledger.reset()
    timeseries.reset()


def _env(clock=None, n_nodes=4, n_gangs=2, gang=3, conf=CONF):
    clock = clock if clock is not None else FakeClock(start=1.0)
    store = ObjectStore(clock=clock)
    binder = FakeBinder(store)
    cache = SchedulerCache(store, binder=binder, evictor=FakeEvictor(store))
    cache.run()
    sched = Scheduler(store, scheduler_conf=conf, cache=cache, clock=clock)
    store.create("queues", build_queue("default", weight=1))
    for i in range(n_nodes):
        store.create("nodes", build_node(f"n{i}", {"cpu": "8",
                                                   "memory": "16Gi"}))
    for j in range(n_gangs):
        store.create("podgroups", build_pod_group(
            f"pg-{j}", "default", "default", gang, phase="Inqueue"))
        for t in range(gang):
            store.create("pods", build_pod(
                "default", f"pg-{j}-{t}", "", "Pending",
                {"cpu": "1", "memory": "1Gi"}, groupname=f"pg-{j}"))
    return store, cache, binder, sched, clock


# -- ledger core -------------------------------------------------------------


def test_tracer_switch_covers_ledger():
    assert not ledger.is_enabled()
    tracer.enable()
    assert ledger.is_enabled()
    tracer.disable()
    assert not ledger.is_enabled()


def test_transition_ordering_and_creation_rules():
    ledger.enable()
    # only "submitted" creates entries: a stray later-stage stamp (e.g. a
    # store_committed arriving after the entry completed) is ignored
    ledger.stamp("ns/p0", "bind_staged", 5.0)
    assert ledger.stats()["open"] == 0
    ledger.stamp("ns/p0", "submitted", 1.0)
    assert ledger.stats()["open"] == 1
    # stages stamp once and never regress
    ledger.stamp("ns/p0", "kernel_placed", 3.0)
    ledger.stamp("ns/p0", "session_eligible", 2.0)   # late: ignored
    ledger.stamp("ns/p0", "bind_staged", 4.0)
    ledger.confirm("ns/p0", 6.0, queue="q")
    rep = ledger.report()
    assert ledger.stats() == {"enabled": True, "open": 0, "completed": 1,
                              "dropped": 0, "detours": {}}
    r = rep["recent"][0]
    # hops between consecutive PRESENT stamps only (session_eligible and
    # enqueued were skipped), and their sum is exactly the e2e
    assert set(r["hops"]) == {"submitted->kernel_placed",
                              "kernel_placed->bind_staged",
                              "bind_staged->store_committed",
                              "store_committed->echo_confirmed"}
    assert abs(sum(r["hops"].values()) - r["e2e_ms"]) < 1e-9
    assert r["e2e_ms"] == pytest.approx(5000.0)
    assert rep["per_queue_e2e"]["q"]["count"] == 1


def test_ledger_real_cycle_virtual_clock_hops_and_orphans():
    tracer.enable()
    store, cache, binder, sched, clock = _env()
    clock.advance(2.0)          # submission -> first eligible cycle
    sched.run_once()
    # NO advance before the flush barrier: the executor drains on its
    # own thread, so only clock advances WE make are deterministic —
    # every cycle/flush/echo stamp lands at the same virtual instant
    assert cache.flush_executors()
    assert len(binder.binds) == 6
    stats = ledger.stats()
    assert stats["completed"] == 6 and stats["open"] == 0
    rep = ledger.report()
    assert rep["hops"]["e2e"]["count"] == 6
    # the virtual clock makes the hops exact: submission waited 2.0 s,
    # everything after it happened "instantly"
    for r in rep["recent"]:
        assert abs(sum(r["hops"].values()) - r["e2e_ms"]) < 1e-6
        assert r["e2e_ms"] == pytest.approx(2000.0)
        assert r["hops"]["submitted->session_eligible"] == \
            pytest.approx(2000.0)
        assert r["queue"] == "default"
        assert r["trace"] == "bind-1"
    assert ledger.orphans(store) == []
    cache.stop()


def test_ledger_double_run_bit_identical():
    fingerprints = []
    for _ in range(2):
        tracer.reset()
        ledger.reset()
        tracer.enable()
        store, cache, binder, sched, clock = _env()
        clock.advance(1.0)
        sched.run_once()
        # no advance before the barrier: executor thread timing must not
        # race a clock mutation (the sim advances only at tick barriers
        # for the same reason)
        assert cache.flush_executors()
        cache.stop()
        fingerprints.append(ledger.fingerprint())
        tracer.disable()
    assert fingerprints[0] == fingerprints[1]


def test_pod_delete_drops_open_entry():
    tracer.enable()
    store, cache, binder, sched, clock = _env()
    # synthetically unschedulable: stays open in the ledger
    store.create("podgroups", build_pod_group(
        "stuck", "default", "default", 1, phase="Inqueue"))
    store.create("pods", build_pod(
        "default", "stuck-0", "", "Pending",
        {"cpu": "64", "memory": "1Gi"}, groupname="stuck"))
    sched.run_once()
    cache.flush_executors()
    assert ledger.stats()["open"] == 1
    store.delete("pods", "stuck-0", "default", skip_admission=True)
    stats = ledger.stats()
    assert stats["open"] == 0 and stats["dropped"] == 1
    assert ledger.orphans(store) == []
    cache.stop()


# -- correlation IDs ---------------------------------------------------------


def test_bind_correlation_joins_ledger_and_store_journal():
    tracer.enable()
    store, cache, binder, sched, clock = _env()
    sched.run_once()
    assert cache.flush_executors()
    rep = ledger.report()
    traces = {r["trace"] for r in rep["recent"]}
    assert traces == {"bind-1"}
    # the bind patch's rv joins back to the same correlation ID through
    # the store's journal trace map (FakeBinder leaves the bound pod's rv
    # at the bind write)
    pod = store.get("pods", "pg-0-0", "default")
    assert pod.spec.node_name
    assert store.trace_of(pod.metadata.resource_version) == "bind-1"
    cache.stop()


def test_correlation_id_remote_store_roundtrip():
    from volcano_tpu.apiserver.http import StoreHTTPServer
    from volcano_tpu.apiserver.remote import RemoteStore
    server_store = ObjectStore()
    server = StoreHTTPServer(server_store, port=0)
    server.start()
    try:
        remote = RemoteStore(f"http://127.0.0.1:{server.port}",
                             poll_timeout=1.0)
        remote.run()
        pod = build_pod("default", "r-0", "", "Pending",
                        {"cpu": "1", "memory": "1Gi"}, groupname="rj")
        created = remote.create("pods", pod)
        created.spec.node_name = "n0"
        updated = remote.update("pods", created, trace="corr-42")
        rv = updated.metadata.resource_version
        # server side: the ?trace= query param landed in the journal map
        assert server_store.trace_of(rv) == "corr-42"
        # client side: the watch stream echoes it back as the event's
        # "trace" field and the mirror records it by server rv
        deadline = time.time() + 10.0
        while remote.trace_of(rv) is None and time.time() < deadline:
            time.sleep(0.05)
        assert remote.trace_of(rv) == "corr-42"
        remote.stop()
    finally:
        server.stop()


def test_correlation_id_survives_scheduler_restart():
    tracer.enable()
    store, cache, binder, sched, clock = _env()
    sched.run_once()
    assert cache.flush_executors()
    pod = store.get("pods", "pg-0-0", "default")
    rv = pod.metadata.resource_version
    assert store.trace_of(rv) == "bind-1"
    # stateless restart: the cache dies, a fresh one rebuilds from the
    # surviving store (the PR 5 scheduler_kill shape) — the journal's
    # correlation record must still resolve, and the module-global
    # ledger keeps the completed bind's trace
    cache.stop()
    cache2 = SchedulerCache(store, binder=binder,
                            evictor=FakeEvictor(store))
    cache2.run()
    assert store.trace_of(rv) == "bind-1"
    assert any(r["trace"] == "bind-1" for r in ledger.report()["recent"])
    # and the restarted incarnation's own binds stamp fresh IDs
    store.create("podgroups", build_pod_group(
        "late", "default", "default", 1, phase="Inqueue"))
    store.create("pods", build_pod(
        "default", "late-0", "", "Pending",
        {"cpu": "1", "memory": "1Gi"}, groupname="late"))
    sched2 = Scheduler(store, scheduler_conf=CONF, cache=cache2,
                       clock=clock)
    sched2.run_once()
    assert cache2.flush_executors()
    late = store.get("pods", "late-0", "default")
    assert late.spec.node_name
    assert store.trace_of(late.metadata.resource_version) == "bind-1"
    cache2.stop()


# -- debug endpoints + timeseries --------------------------------------------


def test_debug_latency_timeseries_http_and_404_body():
    tracer.enable()
    store, cache, binder, sched, clock = _env()
    sched.run_once()
    assert cache.flush_executors()
    server = MetricsServer(port=0)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"

        def get(path):
            return json.loads(urllib.request.urlopen(
                base + path, timeout=5).read().decode())

        lat = get("/debug/latency")
        assert lat["enabled"] and lat["completed"] == 6
        assert lat["hops"]["e2e"]["count"] == 6
        for agg in lat["hops"].values():
            assert {"count", "mean_ms", "p50", "p95", "p99"} <= set(agg)
        assert lat["per_queue_e2e"]["default"]["count"] == 6

        ts = get("/debug/timeseries")
        assert len(ts["samples"]) == 1
        row = ts["samples"][0]
        assert row["cycle_ms"] > 0 and row["seq"] >= 1
        assert get("/debug/timeseries?limit=1")["samples"] == [row]

        index = get("/debug")
        assert "/debug/latency" in index["endpoints"]
        assert "/debug/timeseries" in index["endpoints"]

        # unknown paths answer 404 WITH a JSON error body
        try:
            urllib.request.urlopen(base + "/nope", timeout=5)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
            body = json.loads(e.read().decode())
            assert body["error"] == "not found"
            assert "/debug/latency" in body["endpoints"]

        # prometheus exposition carries the new histograms
        metrics_body = urllib.request.urlopen(
            base + "/metrics", timeout=5).read().decode()
        assert "volcano_pod_e2e_latency_milliseconds_count" in metrics_body
        assert 'volcano_pod_hop_latency_milliseconds_count{hop=' \
            in metrics_body
    finally:
        server.stop()
        cache.stop()


def test_timeseries_counters_accumulate_across_cycles():
    tracer.enable()
    store, cache, binder, sched, clock = _env()
    sched.run_once()
    cache.flush_executors()
    clock.advance(1.0)
    sched.run_once()
    rows = timeseries.series()
    assert len(rows) == 2
    assert rows[1]["t"] > rows[0]["t"]
    assert rows[1][m.SCHEDULE_ATTEMPTS] >= 2
    assert rows[1][f"{m.POD_E2E_LATENCY}_count"] == 6


# -- solver profiling hooks --------------------------------------------------


def test_compile_cache_and_transfer_metrics():
    """The real compile counter and the kernel's compile spans: a first
    batch compiles the placement kernel's program under its ``kernel``
    span, and a second batch of IDENTICAL shape (same gang count/size
    over the same nodes) adds no backend compile under its execute.
    (The second batch's tensor build may still compile: the persistent
    node buffers take their first dirty-row update then.)"""
    tracer.enable()
    # the chunked XLA kernel is a jitted program (the CPU default is the
    # native solver, which compiles nothing); cleared caches make the
    # first batch's compile this test's own
    store, cache, binder, sched, clock = _env(conf=CONF + """
configurations:
- name: solver
  arguments:
    kernel: "chunked"
""")
    jax.clear_caches()

    def backend_compiles_under(name):
        rec = tracer.last_record()
        out = []

        def walk(s, under):
            under = under or s.name == name
            if under and s.name == "compile" and \
                    s.tags["stage"] == "backend":
                out.append(s)
            for c in s.children or ():
                walk(c, under)
        walk(rec.root, False)
        return out

    c0 = m.counter_total(m.JIT_COMPILES)
    sched.run_once()
    cache.flush_executors()
    first = backend_compiles_under("kernel")
    assert [c.tags["fun"] for c in backend_compiles_under("execute")] == \
        ["jit(gang_allocate_chunked)"]
    assert m.counter_total(m.JIT_COMPILES) - c0 >= len(first)
    for j in (2, 3):
        store.create("podgroups", build_pod_group(
            f"pg-{j}", "default", "default", 3, phase="Inqueue"))
        for t in range(3):
            store.create("pods", build_pod(
                "default", f"pg-{j}-{t}", "", "Pending",
                {"cpu": "1", "memory": "1Gi"}, groupname=f"pg-{j}"))
    sched.run_once()
    cache.flush_executors()
    assert len(binder.binds) == 12
    assert backend_compiles_under("execute") == []
    assert m.counter_total(m.DEVICE_TRANSFER_BYTES) > 0
    cache.stop()


def test_backend_probe_structured_phases():
    from volcano_tpu.ops.backend_probe import run_probe
    verdict = run_probe(timeout_s=120.0,
                        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    # CPU-only box: the probe completes every phase but reports the
    # platform honestly (alive means TPU specifically)
    assert not verdict["timed_out"]
    assert verdict["last_phase"] == "device_op"
    names = [p["phase"] for p in verdict["phases"]]
    assert names == ["import_jax", "backend_init", "device_op"]
    assert verdict["alive"] is (verdict["platform"] == "tpu")


# -- vcctl debug -------------------------------------------------------------


def test_vcctl_debug_cli(capsys):
    tracer.enable()
    store, cache, binder, sched, clock = _env()
    sched.run_once()
    assert cache.flush_executors()
    server = MetricsServer(port=0)
    server.start()
    try:
        from volcano_tpu.cli.vcctl import main as vcctl_main
        base = f"http://127.0.0.1:{server.port}"
        assert vcctl_main(["debug", "latency", "--metrics", base,
                           "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["completed"] == 6
        assert vcctl_main(["debug", "latency", "--metrics", base]) == 0
        out = capsys.readouterr().out
        assert "e2e" in out and "p95" in out
        assert vcctl_main(["debug", "timeseries", "--metrics", base]) == 0
        assert "cycle_ms" in capsys.readouterr().out
        assert vcctl_main(["debug", "health", "--metrics", base]) == 0
    finally:
        server.stop()
        cache.stop()
