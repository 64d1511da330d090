"""The one traffic generator. A traffic mix is a data file beside this one
(``<name>.json``); a configuration's file gives the job shapes, the queues
and their demand. Every draw comes from ``--seed``: the jobs' shapes, their
queues, their order, their durations and their completion times.

The draws are those of ``volcano_tpu/sim/workload.py`` (burst arrivals,
categorical sizes and shapes, log-uniform durations), copied so that the
yardstick stays under the benchmark's own paths. The draw is stratified:
each block of ``BLOCK`` jobs holds the configured shares exactly and the
seed orders them, so that every seed gives the same set of sizes, queues
and durations in another order.

Arrival kinds: ``burst`` (``jobs`` due together every ``period_s``, the
first at the window's start, ``warmup_bursts`` before it) and ``backlog``
(``pending_jobs`` submitted in set-up; each job that binds lets the next
job of the stream in, due at its bind).
Completion kinds: ``after_next_arrival`` (a burst's jobs complete at a
seeded time in the ``within_s`` that start ``offset_s`` after the next
burst is due) and ``duration`` (a job completes its configured duration
after it bound). Nothing completes in warm-up: a job bound there counts
its duration from the window's start.
Residents: ``none``, or ``fill`` (the stream's first jobs, packed by first
fit until one does not fit, with a residual run time from the window's
start drawn as in a steady state).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

# resources a placement is counted in, and their units (milli-cpu, bytes)
_SUFFIX = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40,
           "k": 10 ** 3, "M": 10 ** 6, "G": 10 ** 9}


def quantity(name: str, text: str) -> int:
    """A Kubernetes quantity as an integer: milli-units for cpu, bytes for
    memory, plain units for everything else."""
    s = str(text)
    mult = 1
    for suf, m in _SUFFIX.items():
        if s.endswith(suf):
            s, mult = s[:-len(suf)], m
            break
    if s.endswith("m"):
        return int(float(s[:-1]) * mult)
    v = float(s) * mult
    return int(round(v * 1000)) if name == "cpu" else int(round(v))


@dataclass
class Job:
    name: str
    queue: str
    shape: int            # index into the configuration's "jobs"
    tasks: int
    min_member: int
    requests: Dict[str, str]
    duration: float       # seconds of running after the whole gang bound
    due: float = 0.0      # seconds from the window's start
    complete_at: Optional[float] = None

    def pod_names(self) -> List[str]:
        return [f"{self.name}-t{i}" for i in range(self.tasks)]


BLOCK = 1000      # jobs per stratified block


def _apportion(weights, n: int) -> np.ndarray:
    """Largest-remainder counts of ``n`` items over ``weights``."""
    w = np.asarray(weights, np.float64)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(np.int64)
    rest = n - int(counts.sum())
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:rest]] += 1
    return counts


class Stream:
    """The seeded stream of jobs, drawn block by block."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.rng = np.random.default_rng(int(seed) % (2 ** 64))
        self.shapes = config["jobs"]
        self.queues = [q["name"] for q in config["queues"]]
        self.demand = [float(q.get("demand", 1.0)) for q in config["queues"]]
        self.dur = config.get("job_duration_s")
        self._buf: List[Job] = []
        self.n = 0

    def _draw_block(self) -> None:
        b, rng = BLOCK, self.rng
        sw = [s["weight"] for s in self.shapes]
        shape = rng.permutation(np.repeat(np.arange(len(sw)),
                                          _apportion(sw, b)))
        queue = rng.permutation(np.repeat(np.arange(len(self.queues)),
                                          _apportion(self.demand, b)))
        u = rng.permutation((np.arange(b) + rng.random(b)) / b)
        if self.dur:
            lo, hi = math.log(self.dur[0]), math.log(self.dur[1])
            durations = np.exp(lo + u * (hi - lo))
        else:
            durations = np.zeros(b)
        for s, q, d in zip(shape.tolist(), queue.tolist(), durations.tolist()):
            sh = self.shapes[s]
            self._buf.append(Job(
                name=f"j{self.n}", queue=self.queues[q], shape=s,
                tasks=int(sh["tasks"]), min_member=int(sh["min_member"]),
                requests=dict(sh["requests"]), duration=float(d)))
            self.n += 1

    def take(self, n: int) -> List[Job]:
        while len(self._buf) < n:
            self._draw_block()
        out, self._buf = self._buf[:n], self._buf[n:]
        return out


class FirstFit:
    """The harness's own packing of residents (set-up only): nodes in
    index order, each task on the first node it fits."""

    def __init__(self, config: dict):
        alloc = config["nodes"]["allocatable"]
        self.dims = sorted(alloc)
        cap = np.array([quantity(d, alloc[d]) for d in self.dims], np.int64)
        self.free = np.tile(cap, (int(config["nodes"]["count"]), 1))
        self.smallest = np.min(np.stack([self.vec(j["requests"])
                                         for j in config["jobs"]]), axis=0)
        self.cursor = 0

    def vec(self, requests: Dict[str, str]) -> np.ndarray:
        v = np.array([quantity(d, requests.get(d, "0")) for d in self.dims],
                     np.int64)
        if "pods" in self.dims:
            v[self.dims.index("pods")] = 1
        return v

    def place(self, job: Job) -> Optional[List[int]]:
        """Node indices for the job's tasks, or None (nothing is taken)."""
        req = self.vec(job.requests)
        taken: List[int] = []
        for _ in range(job.tasks):
            ok = np.flatnonzero(np.all(self.free[self.cursor:] >= req, axis=1))
            if ok.size == 0:
                for n in taken:
                    self.free[n] += req
                return None
            n = self.cursor + int(ok[0])
            self.free[n] -= req
            taken.append(n)
        # nodes before the first with room for the smallest shape never
        # open again in set-up
        while self.cursor < len(self.free) and \
                not np.all(self.free[self.cursor] >= self.smallest):
            self.cursor += 1
        return taken


Event = Tuple[float, str, List[Job]]   # (due s, "submit" | "complete", jobs)


class Traffic:
    """What the harness applies: residents, set-up submissions and timed
    events, and the events that follow a bind or an eviction."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.params = traffic
        self.stream = Stream(config, traffic, seed)
        self.rng = np.random.default_rng((int(seed) + 0x9E3779B9) % (2 ** 64))
        self.arrival = traffic["arrival"]
        self.completion = traffic["completion"]
        # completions fixed relative to the window's start, not to a bind
        self.static_completion = \
            self.completion["kind"] == "after_next_arrival"

    # -- set-up -----------------------------------------------------------

    def residents(self) -> Tuple[List[Tuple[Job, List[int]]], List[Job]]:
        """(placed residents with their node indices, the first job that
        did not fit, which leads the backlog)."""
        kind = self.params.get("residents", {"kind": "none"})["kind"]
        if kind == "none":
            return [], []
        if kind != "fill":
            raise ValueError(f"unknown residents kind {kind!r}")
        ff = FirstFit(self.config)
        placed = []
        while True:
            job = self.stream.take(1)[0]
            nodes = ff.place(job)
            if nodes is None:
                return placed, [job]
            # a resident is part-way through its run: a uniform fraction of
            # its duration is left, as in a steady state
            job.complete_at = float(self.rng.random()) * job.duration
            placed.append((job, nodes))

    def initial(self, seconds: float, lead: List[Job]
                ) -> Tuple[List[Job], List[Event], List[Event]]:
        """(jobs submitted in set-up, warm-up events, window events); due
        times in seconds from the window's start, warm-up ones below 0."""
        kind = self.arrival["kind"]
        if kind == "burst":
            per = float(self.arrival["period_s"])
            n = int(self.arrival["jobs"])
            warm: List[Event] = []
            window: List[Event] = []
            first = -int(self.arrival.get("warmup_bursts", 0))
            last = int(math.ceil(seconds / per))
            for k in range(first, last):
                jobs = self.stream.take(n)
                due = k * per
                for j in jobs:
                    j.due = due
                    j.complete_at = self._timed_completion(due + per)
                (warm if k < 0 else window).append((due, "submit", jobs))
            return [], warm, window
        if kind == "backlog":
            n = int(self.arrival["pending_jobs"])
            jobs = lead + self.stream.take(n - len(lead))
            for j in jobs:
                j.due = -math.inf
            return jobs, [], []
        raise ValueError(f"unknown arrival kind {kind!r}")

    def _timed_completion(self, after: float) -> Optional[float]:
        if self.completion["kind"] != "after_next_arrival":
            return None
        return after + float(self.completion.get("offset_s", 0.0)) + \
            float(self.rng.random()) * float(self.completion["within_s"])

    # -- while it runs ----------------------------------------------------

    def on_bound(self, job: Job, t: float) -> List[Event]:
        """Events that a whole gang's bind at ``t`` sets off. A timed
        completion (``after_next_arrival``) is relative to the window's
        start; every other time is relative to the same origin as ``t``."""
        out: List[Event] = []
        if self.completion["kind"] == "duration":
            job.complete_at = t + job.duration
        if job.complete_at is not None:
            out.append((job.complete_at, "complete", [job]))
        if self.arrival["kind"] == "backlog":
            nxt = self.stream.take(1)[0]
            nxt.due = t
            out.append((t, "submit", [nxt]))
        return out

    def resubmit(self, job: Job, t: float) -> Job:
        """An evicted job comes back pending, as its controller would
        recreate it, under a new name."""
        again = Job(name=f"{job.name}r", queue=job.queue, shape=job.shape,
                    tasks=job.tasks, min_member=job.min_member,
                    requests=dict(job.requests), duration=job.duration,
                    due=t)
        return again
