"""Flight-recorder tests for the host time around the device: JAX
compiles as ``compile`` spans and the always-on compile counter, the
placement kernel's ``execute`` split into ``dispatch`` and ``readback``,
preempt's phase spans, and the spans mirrored into the profiler's host
plane."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from tests.harness import Harness
from volcano_tpu.apiserver import ObjectStore
from volcano_tpu.cache import SchedulerCache
from volcano_tpu.metrics import metrics as m
from volcano_tpu.models.objects import ObjectMeta, PodGroupPhase, PriorityClass
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.trace import tracer
from volcano_tpu.utils.test_utils import (FakeBinder, FakeEvictor, build_node,
                                          build_pod, build_pod_group,
                                          build_queue, build_resource_list)

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
def _clean_tracer():
    tracer.reset()
    yield
    tracer.disable()
    tracer.reset()


def _walk(span, path=""):
    p = f"{path}/{span.name}" if path else span.name
    yield p, span
    for c in span.children or ():
        yield from _walk(c, p)


def _env(n_nodes=4, n_gangs=2, gang=3):
    store = ObjectStore()
    binder = FakeBinder(store)
    cache = SchedulerCache(store, binder=binder, evictor=FakeEvictor(store))
    cache.run()
    sched = Scheduler(store, scheduler_conf=CONF, cache=cache)
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
    return store, cache, binder, sched


def _fresh_jit():
    """A jitted function no earlier call has compiled, calling another."""
    inner = jax.jit(lambda x: x * 3.0)
    return jax.jit(lambda x: inner(x) + 1.0)


# -- compiles ----------------------------------------------------------------


def test_new_shape_jit_records_compile_spans_under_open_span():
    tracer.enable()
    f = _fresh_jit()
    x = jnp.ones(11)
    with tracer.cycle():
        with tracer.span("outer"):
            f(x).block_until_ready()
    outer = tracer.last_record().root.children[0]
    comp = [s for _, s in _walk(outer) if s.name == "compile"]
    assert comp, "a first call records its compile"
    assert {s.tags["stage"] for s in comp} >= {"trace", "lower", "backend"}
    assert all(s.tags["fun"] for s in comp)
    for s in comp:
        assert outer.t0 <= s.t0
        assert s.t0 + s.dur <= outer.t0 + outer.dur + 1e-9
    # the trace of the outer function holds the trace of the one it calls
    traces = [s for s in outer.children
              if s.name == "compile" and s.tags["stage"] == "trace"]
    assert any(c.name == "compile" for s in traces
               for c in s.children or ())


def test_identical_second_call_records_no_compile():
    tracer.enable()
    f = _fresh_jit()
    x = jnp.ones(13)
    f(x).block_until_ready()
    with tracer.cycle():
        with tracer.span("outer"):
            f(x).block_until_ready()
    paths = [p for p, _ in _walk(tracer.last_record().root)]
    assert not any(p.endswith("/compile") for p in paths), paths


def test_jit_compile_counter_counts_with_recorder_off():
    assert not tracer.is_enabled()
    c0 = m.counter_total(m.JIT_COMPILES)
    s0 = m.counter_total(m.JIT_COMPILE_SECONDS, stage="backend")
    f = _fresh_jit()
    f(jnp.ones(17)).block_until_ready()
    assert m.counter_total(m.JIT_COMPILES) >= c0 + 1
    assert m.counter_total(m.JIT_COMPILE_SECONDS, stage="backend") > s0
    c1 = m.counter_total(m.JIT_COMPILES)
    f(jnp.ones(17)).block_until_ready()
    assert m.counter_total(m.JIT_COMPILES) == c1
    assert tracer.last_record() is None


# -- the kernel's execute ----------------------------------------------------


def test_served_placement_execute_has_dispatch_and_readback():
    tracer.enable()
    _, cache, binder, sched = _env()
    sched.run_once()
    cache.flush_executors()
    assert len(binder.binds) == 6
    rec = tracer.last_record()
    execs = [s for p, s in _walk(rec.root) if p.endswith("kernel/execute")]
    assert execs
    for ex in execs:
        names = [c.name for c in ex.children or ()]
        assert names[:1] == ["dispatch"] and names[-1:] == ["readback"]
        assert sum(c.dur for c in ex.children) <= ex.dur


# -- preempt -----------------------------------------------------------------


def test_traced_preempt_records_its_phases():
    tracer.enable()
    h = Harness("""
actions: "preempt"
tiers:
- plugins:
  - name: conformance
  - name: gang
""")
    rl = build_resource_list("1", "1Gi")
    h.add("priorityclasses",
          PriorityClass(metadata=ObjectMeta(name="low-priority"), value=100),
          PriorityClass(metadata=ObjectMeta(name="high-priority"),
                        value=1000))
    h.add("queues", build_queue("q1"))
    h.add("podgroups",
          build_pod_group("pg1", "c1", "q1", 1, phase=PodGroupPhase.INQUEUE,
                          priority_class="low-priority"),
          build_pod_group("pg2", "c1", "q1", 1, phase=PodGroupPhase.INQUEUE,
                          priority_class="high-priority"))
    h.add("nodes", build_node("n1", build_resource_list("2", "2Gi")))
    h.add("pods",
          build_pod("c1", "preemptee1", "n1", "Running", rl, "pg1"),
          build_pod("c1", "preemptee2", "n1", "Running", rl, "pg1"),
          build_pod("c1", "preemptor1", "", "Pending", rl, "pg2"),
          build_pod("c1", "preemptor2", "", "Pending", rl, "pg2"))
    h.open_session()
    with tracer.cycle():
        with tracer.span("action:preempt"):
            h.run_actions("preempt")
    h.close_session()
    assert len(h.evicts) == 1
    action = tracer.last_record().root.children[0]
    phases = [c.name for c in action.children]
    assert phases == ["preempt.scan", "preempt.encode", "preempt.inter_job",
                      "preempt.intra_job", "preempt.victim_tasks"]
    by_name = {c.name: c for c in action.children}
    assert by_name["preempt.scan"].tags["starving"] == 1
    inter = by_name["preempt.inter_job"].tags
    assert inter["attempts"] >= 1 and inter["select_ms"] >= 0.0
    assert action.tags["attempts"] == inter["attempts"] + \
        by_name["preempt.intra_job"].tags["attempts"]


# -- one clock with the device trace -----------------------------------------


def test_profiled_cycle_mirrors_spans_into_host_plane(tmp_path):
    tracer.enable()
    _, cache, _, sched = _env()
    jax.profiler.start_trace(str(tmp_path))
    try:
        sched.run_once()
    finally:
        jax.profiler.stop_trace()
    cache.flush_executors()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in pd.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"cycle", "solver.place", "kernel", "execute", "dispatch",
            "readback"} <= names
    # made after the fact, compile spans stay out of the profile
    assert "compile" not in names
