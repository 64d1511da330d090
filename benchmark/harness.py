"""One run of one cell: set-up, warm-up, the measured window, the checks.

The window drives the production ``Scheduler.run_once`` over an
in-process ``ObjectStore`` at production pacing: a cycle starts every
``SCHEDULE_PERIOD_S`` or at once when the previous one overran (as
``Scheduler.run`` paces it). Between cycles this thread applies the
traffic events that are due through ``ObjectStore.create`` and
``delete``, the apiserver's own entry points, and it wakes for whichever
comes first. The bind flush runs on the cache's executor as in
production. A watch on pods records each bind's commit (key and time).

Warm-up is the same loop over the traffic's warm-up part, ending where
the window starts, so the window continues a process already in its
steady state and every shape it uses has compiled.
"""

from __future__ import annotations

import gc
import heapq
import json
import math
import random
import sys
import threading
import time
from collections import deque
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
OUT_DIR = BENCH_DIR / ".out"      # traces and scratch, git-ignored
# the reference's --schedule-period (options.go:86)
SCHEDULE_PERIOD_S = 1.0
WARMUP_MAX_S = 300.0

from traffic.generator import Job, Traffic      # noqa: E402
import cluster                                   # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry, its configuration and its traffic mix, found by
    name from BENCHMARK.json."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic}


# -- compile counting ---------------------------------------------------------

class CompileCounter:
    """Programs JAX needed that were not yet in memory, counted through its
    monitoring events: each is compiled, or loaded from the persistent
    cache (JAX times both as a backend compile; a load is also a hit)."""

    def __init__(self):
        import jax.monitoring as mon
        self.needed = 0
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event.endswith("backend_compile_duration"):
                self.needed += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def counts(self) -> tuple:
        """(compiled, loaded from the cache)."""
        return self.needed - self.cache_hits, self.cache_hits


class WindowCache:
    """The persistent compile cache serves set-up only. Inside the window
    it is neither read nor written, so a program that the window needs
    for the first time in this process is compiled there, whatever
    earlier runs of the checkout left in the cache: the window's work
    depends on the seed alone. (JAX decides once per process whether the
    cache is used, so its two entry points are switched here.)"""

    def __init__(self):
        from jax._src import compilation_cache as cc
        self.cc = cc
        self.saved = (cc.get_executable_and_time, cc.put_executable_and_time)

    def close(self) -> None:
        self.cc.get_executable_and_time = lambda *a, **k: (None, None)
        self.cc.put_executable_and_time = lambda *a, **k: None

    def open(self) -> None:
        self.cc.get_executable_and_time, self.cc.put_executable_and_time = \
            self.saved


# -- the kernel tap -----------------------------------------------------------

class KernelTap:
    """Wraps the placement kernel the timed path calls, to keep what the
    window's calls were given and returned: the shapes of every call (for
    the roofline), and two calls for the output check, the longest and
    one drawn from the seed, of those whose answer the cycle used (a
    pruned call whose answer the program's loss guard threw away is not
    one). The solver's ladder is wrapped too, so that each call keeps its
    labels (pods, nodes, columns, queues, resources) and a copy of the
    node state it was given: the reference derives the rest itself."""

    def __init__(self, seed: int):
        import functools
        from volcano_tpu.framework.solver import BatchSolver
        from volcano_tpu.ops import pallas_allocate as pa
        self.module = pa
        self.original = pa.gang_allocate_pallas
        self.solver_cls = BatchSolver
        self.ladder = BatchSolver._execute_ladder
        self.rng = random.Random(seed)
        self.active = False
        self.calls: List[dict] = []       # shapes, every call in the window
        self.longest: Optional[dict] = None
        self.drawn: Optional[dict] = None
        self.pending: Optional[dict] = None   # answer not yet known used
        self.seen = 0
        self.ctx = None
        orig, ladder = self.original, self.ladder

        @functools.wraps(orig)
        def tapped(*args, **kwargs):
            out = orig(*args, **kwargs)
            if self.active:
                self._record(args, kwargs, out)
            return out

        @functools.wraps(ladder)
        def tapped_ladder(solver, batch, narr, *args, **kwargs):
            self.ctx = (solver, batch, narr, kwargs.get("reduced"),
                        self._nodes(narr) if self.active else None)
            try:
                return ladder(solver, batch, narr, *args, **kwargs)
            finally:
                self.ctx = None

        pa.gang_allocate_pallas = tapped
        BatchSolver._execute_ladder = tapped_ladder

    @staticmethod
    def _nodes(narr) -> dict:
        """A copy of the node state the ladder was given."""
        n = len(narr.names)
        return {"idle": narr.idle[:n].copy(),
                "future": narr.future_idle[:n].copy(),
                "ntasks": narr.n_tasks[:n].copy(),
                "alloc": narr.allocatable[:n].copy(),
                "max_tasks": narr.max_tasks[:n].copy()}

    def _labels(self) -> Optional[dict]:
        import numpy as np
        if self.ctx is None or self.ctx[4] is None:
            return None
        solver, batch, narr, red, nodes = self.ctx
        n = len(narr.names)
        if red is not None:
            cols = np.where(red.live, red.union_padded, -1)
        else:
            cols = np.arange(narr.n_pad)
            cols[n:] = -1
        return {"tasks": batch.tasks, "queues": list(batch.queue_names),
                "resources": list(solver.rindex.names),
                "nodes": list(narr.names), "cols": cols, "prune": red,
                **nodes}

    def _record(self, args, kwargs, out) -> None:
        shapes = {"T": int(args[0].shape[0]), "G": int(args[3].shape[0]),
                  "R": int(args[3].shape[1]), "N": int(args[22].shape[0]),
                  "J": int(args[8].shape[0])}
        self.calls.append(shapes)
        labels = self._labels()
        self._admit()
        if labels is not None:
            # the node rows as the kernel read them: on the CPU a device
            # array may share the persistent host rows, which later
            # cycles rewrite in place
            import numpy as np
            args = tuple(np.array(a) if 22 <= i <= 26 else a
                         for i, a in enumerate(args))
            self.pending = {"args": args, "kwargs": dict(kwargs),
                            "out": out, "shapes": shapes,
                            "labels": labels,
                            "index": len(self.calls) - 1}

    def _admit(self) -> None:
        """The last call joins the sample's candidates once its answer is
        known to be used: the longest call by tasks, then one other drawn
        from the seed (a reservoir of one over the rest)."""
        call, self.pending = self.pending, None
        if call is None:
            return
        red = call["labels"].pop("prune")
        if red is not None and red.fallback is not None:
            return
        if self.longest is None or \
                call["shapes"]["T"] > self.longest["shapes"]["T"]:
            call, self.longest = self.longest, call
        if call is not None:
            self.seen += 1
            if self.rng.random() * self.seen < 1.0:
                self.drawn = call

    def sample(self) -> List[dict]:
        self._admit()
        return [c for c in (self.longest, self.drawn) if c is not None]

    def restore(self) -> None:
        self.module.gang_allocate_pallas = self.original
        self.solver_cls._execute_ladder = self.ladder


# -- the run ------------------------------------------------------------------

class Run:
    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool):
        self.spec = spec
        self.cell = spec["cell"]
        self.config = spec["config"]
        self.params = spec["traffic"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.period = SCHEDULE_PERIOD_S
        self.ns = self.config.get("namespace", "default")
        self.phase = "Pending"            # a PodGroup as a user submits it
        self.heap: list = []               # (abs due, seq, kind, [Job])
        self._seq = 0
        self.objects: Dict[str, cluster.JobObjects] = {}
        self.live: Dict[str, Job] = {}     # submitted, not completed
        self.bound_pods: Dict[str, int] = {}   # job -> pods bound
        self.bind_time: Dict[str, float] = {}  # pod -> first bind commit
        self.due_abs: Dict[str, float] = {}    # job -> abs due
        self.binds = deque()               # (t, [pod names]) from the watch
        self.evicted = deque()             # (t, pod name) not ours
        self._ours = threading.local()
        self.wake = threading.Event()
        self.cycles: List[tuple] = []      # (start abs, dur, ok) window
        self.lag: List[float] = []         # generator lateness, window
        self.evictions = 0
        self.resubmits = 0
        self.t0: Optional[float] = None    # the window's start
        self.origin = 0.0                  # of dynamic relative times
        self._deferred: list = []          # (rel t0, kind, jobs)
        self._tasks_of: Dict[str, int] = {}
        self.jobs: Dict[str, Job] = {}     # every job the store was given

    # -- watch ------------------------------------------------------------

    def _on_bulk(self, pairs) -> None:
        t = time.perf_counter()
        names = [n.metadata.name for o, n in pairs
                 if n.spec.node_name and not o.spec.node_name]
        if names:
            self.binds.append((t, names))
            self.wake.set()

    def _on_update(self, old, new) -> None:
        if new.spec.node_name and not old.spec.node_name:
            self.binds.append((time.perf_counter(), [new.metadata.name]))
            self.wake.set()

    def _on_delete(self, old) -> None:
        if not getattr(self._ours, "deleting", False):
            self.evicted.append((time.perf_counter(), old.metadata.name))
            self.wake.set()

    # -- objects ----------------------------------------------------------

    def _push(self, due: float, kind: str, jobs: List[Job]) -> None:
        self._seq += 1
        heapq.heappush(self.heap, (due, self._seq, kind, jobs))

    def _build(self, jobs: List[Job]) -> None:
        for j in jobs:
            self.objects[j.name] = cluster.JobObjects(j, self.ns, self.phase)

    def _submit(self, jobs: List[Job], due_abs: float) -> None:
        create = self.store.create
        for j in jobs:
            ob = self.objects.pop(j.name, None)
            if ob is None:
                ob = cluster.JobObjects(j, self.ns, self.phase)
            create("podgroups", ob.podgroup)
            for p in ob.pods:
                create("pods", p)
            self.live[j.name] = j
            self.jobs[j.name] = j
            self.due_abs[j.name] = due_abs
            self._tasks_of[j.name] = j.tasks

    def _complete(self, jobs: List[Job]) -> None:
        delete = self.store.delete
        self._ours.deleting = True
        try:
            for j in jobs:
                if self.live.pop(j.name, None) is None:
                    continue        # evicted and resubmitted meanwhile
                for pname in j.pod_names():
                    try:
                        delete("pods", pname, self.ns, skip_admission=True)
                    except KeyError:
                        pass
                try:
                    delete("podgroups", j.name, self.ns, skip_admission=True)
                except KeyError:
                    pass
        finally:
            self._ours.deleting = False

    # -- set-up -----------------------------------------------------------

    def setup(self) -> None:
        from volcano_tpu.apiserver import ObjectStore
        from volcano_tpu.scheduler import Scheduler
        from volcano_tpu.utils.compile_cache import enable_compile_cache
        import jax
        jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
        # every program goes to the cache, however quick its compile, so
        # that a run after the first compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # no eviction: an LRU bound (which a machine may set) reads every
        # entry's access time on each write, so set-up would slow as the
        # checkout's cache grows
        jax.config.update("jax_compilation_cache_max_size", -1)
        self.compiles = CompileCounter()
        self.cache = WindowCache()
        self.tap = KernelTap(self.seed)
        self.store = ObjectStore()
        self.sched = Scheduler(self.store,
                               scheduler_conf=cluster.conf_text(self.config),
                               schedule_period=self.period)
        self.sched.cache.run()
        for q in cluster.build_queues(self.config):
            self.store.create("queues", q)
        for n in cluster.build_nodes(self.config):
            self.store.create("nodes", n)
        self.traffic = Traffic(self.config, self.params, self.seed)
        residents, lead = self.traffic.residents()
        self.store.watch("pods", on_update=self._on_update,
                         on_delete=self._on_delete,
                         on_bulk_update=self._on_bulk, sync=False)
        for job, nodes in residents:
            ob = cluster.JobObjects(job, self.ns, "Running", nodes)
            self.store.create("podgroups", ob.podgroup)
            for p in ob.pods:
                p.status.phase = "Running"
                self.store.create("pods", p)
            self.live[job.name] = job
            self.jobs[job.name] = job
            self.bound_pods[job.name] = job.tasks
        self.n_residents = len(residents)
        submit, warm, window = self.traffic.initial(self.seconds, lead)
        self._build(submit)
        for _, _, jobs in warm + window:
            self._build(jobs)
        self._window_events = window
        self._warm_events = warm
        self._setup_submit = submit
        # residents complete their residual run time after the window's
        # start (see _take_binds)
        for job, _ in residents:
            self._deferred.append((job.complete_at, "complete", [job]))

    def warmup(self) -> None:
        """The traffic's warm-up part through the same loop: the set-up
        submissions and the warm-up events at once, until ``warmup.cycles``
        cycles have run and (with ``warmup.until_bound``) every warm-up job
        is bound and flushed, or ``WARMUP_MAX_S`` has passed. The window
        starts where it ends."""
        w = self.params["warmup"]
        start = time.perf_counter()
        self.origin = start
        if self._setup_submit:
            self._submit(self._setup_submit, start)
        for _, kind, jobs in self._warm_events:
            self._push(start, kind, jobs)
        gc.collect()
        gc.freeze()
        self.next_cycle = start
        self.n_cycles = 0
        warm_jobs = [j for _, _, jobs in self._warm_events for j in jobs]
        while True:
            self.loop(time.perf_counter() + self.period, measure=False)
            if time.perf_counter() - start > WARMUP_MAX_S:
                log("warm-up ran out of time")
                break
            if self.n_cycles < int(w["cycles"]):
                continue
            if w.get("until_bound") and any(
                    self.bound_pods.get(j.name, 0) < j.tasks
                    for j in warm_jobs):
                continue
            if w.get("until_bound") and \
                    not self.sched.cache.flush_executors(timeout=0.0):
                continue
            break
        self.warm_cycles = self.n_cycles

    def start_window(self) -> None:
        """t0 is now: the window's events and the completions that were
        waiting for it go on the heap."""
        self.t0 = time.perf_counter()
        self.origin = self.t0
        for due, kind, jobs in self._window_events:
            self._push(self.t0 + due, kind, jobs)
        for rel, kind, jobs in self._deferred:
            self._push(self.t0 + rel, kind, jobs)
        self._deferred = []

    # -- the loop ---------------------------------------------------------

    def _take_binds(self) -> None:
        """Fold the watch's bind records into per-job counts; a whole gang
        bound sets off its completion (and, for a backlog, the next job)."""
        while self.binds:
            t, names = self.binds.popleft()
            for pname in names:
                if pname in self.bind_time:
                    continue
                self.bind_time[pname] = t
                jname = pname.rsplit("-t", 1)[0]
                job = self.live.get(jname)
                if job is None:
                    continue
                c = self.bound_pods.get(jname, 0) + 1
                self.bound_pods[jname] = c
                if c == job.tasks:
                    for due, kind, jobs in self.traffic.on_bound(
                            job, t - self.origin):
                        if kind == "submit":
                            self._build(jobs)
                        if kind == "complete" and self.t0 is None:
                            # nothing completes in warm-up: a job bound
                            # there runs from the window's start, so the
                            # window's state does not hang on how long
                            # warm-up took
                            rel = due if self.traffic.static_completion \
                                else jobs[0].duration
                            self._deferred.append((rel, kind, jobs))
                        elif kind == "complete" and \
                                self.traffic.static_completion:
                            self._push(self.t0 + due, kind, jobs)
                        else:
                            self._push(self.origin + due, kind, jobs)

    def _take_evictions(self) -> None:
        """An evicted pod's job is torn down and resubmitted pending, as
        its job controller would."""
        while self.evicted:
            t, pname = self.evicted.popleft()
            jname = pname.rsplit("-t", 1)[0]
            job = self.live.get(jname)
            if job is None:
                continue
            self.evictions += 1
            self._complete([job])
            again = self.traffic.resubmit(job, t - self.origin)
            self.resubmits += 1
            self._build([again])
            self._push(t, "submit", [again])

    def _run_cycle(self, measure: bool) -> None:
        import jax
        start = time.perf_counter()
        ok = True
        with jax.profiler.TraceAnnotation("bench.cycle"):
            try:
                self.sched.run_once()
            except Exception as e:   # a failed cycle is counted, not fatal
                ok = False
                log(f"cycle failed: {type(e).__name__}: {e}")
        dur = time.perf_counter() - start
        self.n_cycles += 1
        every = self.sched.anti_entropy_every
        if every and self.n_cycles % every == 0:
            self.sched.cache.anti_entropy()
        gc.collect(0)
        if measure:
            self.cycles.append((start, dur, ok))
        # the next cycle one period after this one started, or at once
        self.next_cycle = start + self.period

    def loop(self, until: float, measure: bool, submit: bool = True) -> None:
        import jax
        while True:
            self._take_binds()
            self._take_evictions()
            now = time.perf_counter()
            if now >= until:
                return
            if submit and self.heap and self.heap[0][0] <= now:
                batch = []
                while self.heap and self.heap[0][0] <= now:
                    batch.append(heapq.heappop(self.heap))
                with jax.profiler.TraceAnnotation("bench.events"):
                    for due, _, kind, jobs in batch:
                        if measure:
                            self.lag.append(time.perf_counter() - due)
                        if kind == "submit":
                            self._submit(jobs, due)
                        else:
                            self._complete(jobs)
                continue
            if now >= self.next_cycle:
                self._run_cycle(measure)
                continue
            nxt = min(self.next_cycle, until)
            if submit and self.heap:
                nxt = min(nxt, self.heap[0][0])
            with jax.profiler.TraceAnnotation("bench.wait"):
                self.wake.wait(max(0.0, nxt - now))
            self.wake.clear()

    # -- window -----------------------------------------------------------

    def window(self) -> None:
        import jax
        from volcano_tpu.trace import tracer
        before = self.compiles.counts()
        self.compiles_setup = before[0]
        self.tap.active = True
        self.cache.close()
        if self.trace:
            tracer.enable(capacity=4096)
            tracer.reset()
            OUT_DIR.mkdir(exist_ok=True)
            self.trace_dir = OUT_DIR / f"trace-{self.cell['name']}"
            import shutil
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            self.window_mark = time.perf_counter()
            self.start_window()
            t_end = self.t0 + self.seconds
            # the first cycle of the window starts with it
            self.next_cycle = min(self.next_cycle, self.t0)
            self.loop(t_end, measure=True)
            # a cycle that started inside the window runs to its end
        self.t_end = t_end
        self.window_close = time.perf_counter()
        if self.trace:
            jax.profiler.stop_trace()
        self.tap.active = False
        self.cache.open()
        self.compiles_window, self.cache_loads_window = (
            a - b for a, b in zip(self.compiles.counts(), before))

    def drain(self) -> None:
        """After the window: cycles at production pacing until every pod
        due in the window is bound or the grace ends; no new traffic."""
        grace = float(self.params.get("drain_grace_s", 0.0))
        if grace > 0:
            deadline = time.perf_counter() + grace
            while time.perf_counter() < deadline:
                self._take_binds()
                if not self.unbound_due():
                    break
                self.loop(min(deadline, time.perf_counter() + self.period),
                          measure=False, submit=False)
        self.sched.cache.flush_executors(timeout=120.0)
        self._take_binds()

    def due_pods(self) -> List[tuple]:
        """(pod, due abs) of every pod due inside the window."""
        out = []
        for jname, due in self.due_abs.items():
            if self.t0 <= due < self.t_end:
                tasks = self._tasks_of.get(jname)
                if tasks is None:
                    continue
                for i in range(tasks):
                    out.append((f"{jname}-t{i}", due))
        return out

    def unbound_due(self) -> int:
        return sum(1 for p, _ in self.due_pods() if p not in self.bind_time)

    def stop(self) -> None:
        self.sched.cache.stop()
        self.tap.restore()
        from volcano_tpu.trace import tracer
        tracer.disable()


# -- results ------------------------------------------------------------------

KERNEL = "gang_allocate_pallas"
NEVER = 1e300     # ms: the latency of a pod that never bound


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest rank."""
    if not sorted_vals:
        return math.nan
    k = max(0, min(len(sorted_vals) - 1,
                   int(math.ceil(q / 100.0 * len(sorted_vals))) - 1))
    return sorted_vals[k]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _reader(name: str):
    """``metrics/<name>.py``; a metric split by the end-to-end metric it
    moves (``<base>.<suffix>``) is read by its base's reader unless it has
    one of its own."""
    import importlib.util
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH_DIR / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def memory_peak() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def execute(spec: dict, seed: int, seconds: float, trace: bool,
            device: dict, t_start: float, keep_sample: bool = False) -> dict:
    """One whole run; returns the result line's object (``checks`` last)
    and the information lines printed before it."""
    from check import compare
    cell = spec["cell"]["name"]
    bench = spec["bench"]
    run = Run(spec, seed, seconds, trace)
    run.setup()
    run.warmup()
    setup_s = time.perf_counter() - t_start
    c_before = compare.counters()
    run.window()
    c_after = compare.counters()
    run.drain()
    run._take_evictions()
    run.sched.cache.flush_executors(timeout=120.0)
    dev = dict(device)
    dev["memory_peak_bytes"] = memory_peak()
    info = {"cycles": len(run.cycles), "compiles_in_setup":
            run.compiles_setup, "compiles_in_window":
            run.compiles_window, "cache_loads_in_window":
            run.cache_loads_window, "kernel_calls": len(run.tap.calls),
            "evictions": run.evictions, "resubmits": run.resubmits,
            "residents": run.n_residents, "warm_cycles": run.warm_cycles}
    lag = sorted(run.lag)
    info["generator_lag_ms"] = {
        "max": (lag[-1] * 1000.0) if lag else 0.0,
        "p50": _percentile(lag, 50) * 1000.0 if lag else 0.0,
        "batches": len(lag)}
    from volcano_tpu.trace import tracer
    records = [r for r in tracer.records()
               if run.t0 <= r.root.t0 < run.t_end] if trace else []
    metrics: Dict[str, dict] = {}
    breakdown = None
    if not trace:
        cyc = [d for _, d, _ in run.cycles]
        values = {"setup_s": setup_s,
                  "cycle_ms": 1000.0 * sum(cyc) / len(cyc) if cyc
                  else None}
        if run.params["attempted"] == "pods":
            lat = []
            for pname, due in run.due_pods():
                t = run.bind_time.get(pname)
                lat.append(math.inf if t is None else (t - due) * 1000.0)
            lat.sort()
            # a pod never bound is infinitely late; JSON has no infinity
            values["pod_latency_p50_ms"] = min(_percentile(lat, 50), NEVER)
            values["pod_latency_p95_ms"] = min(_percentile(lat, 95), NEVER)
            info["pods_due"] = len(lat)
        for m in bench["end_to_end"]:
            if values.get(m["name"]) is None:
                continue
            if _applies(m, cell):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
            else:
                info[m["name"]] = values[m["name"]]
    else:
        import device_trace
        import spans
        from roofline.count import peaks
        summary = None
        path = device_trace.find_xplane(str(run.trace_dir))
        if path is not None:
            offset = None
            # the window annotation opened at run.window_mark (perf clock)
            _, host = device_trace.read(path)
            for name, s, _ in host:
                if name == device_trace.WINDOW:
                    offset = s - run.window_mark * 1e9
            hs = spans.host_intervals(records, offset or 0.0)
            summary = device_trace.reduce(path, [KERNEL], hs)
            dev["busy_s"] = summary["busy_s"]
            dev["window_s"] = summary["window_s"]
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
        kernel_cycles = sum(
            1 for r in records
            if any(s.name == "kernel" and (s.tags or {}).get("kernel") ==
                   KERNEL for _, s in spans.walk(r.root)))
        try:
            pk = peaks(device["kind"])
        except KeyError:
            if device["platform"] == "tpu":
                raise
            pk = None
        # what the per-layer readers (metrics/<name>.py) read
        ctx = SimpleNamespace(records=records, n_cycles=len(run.cycles),
                              trace=summary, kernel_calls=run.tap.calls,
                              kernel_cycles=kernel_cycles, peaks=pk,
                              kernel_pattern=KERNEL)
        for m in bench["per_layer"]:
            if not _applies(m, cell):
                continue
            v = _reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    # the program's state is freed before the reference runs
    sample = run.tap.sample()
    for c in sample:
        c["book"] = compare.make_book(c.pop("labels"), run.jobs, run.config)
    sample = compare.host_calls(sample)
    run.stop()
    nums = compare.judge(sample, run.store, run.config, c_before, c_after)
    if run.params["attempted"] == "pods":
        due = run.due_pods()
        attempted = len(due)
        failed = sum(1 for p, _ in due if p not in run.bind_time)
    else:
        attempted = len(run.cycles)
        failed = sum(1 for _, _, ok in run.cycles if not ok)
    del run
    gc.unfreeze()
    gc.collect()
    ok, checks = compare.verdict(nums)
    if not ok:
        info["dumped"] = compare.dump(sample, OUT_DIR / f"failed-{cell}-{seed}")
    out = {"correct": ok, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    ret = {"result": out, "info": info}
    if keep_sample:
        ret["sample"] = sample
    return ret

