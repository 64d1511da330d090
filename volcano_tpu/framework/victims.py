"""Batched preempt/reclaim evaluation context.

The reference evaluates each preemptor with a full PredicateNodes +
PrioritizeNodes sweep and a per-node victim collection loop
(pkg/scheduler/actions/preempt/preempt.go:192-271). Round 1 replicated that
shape — one ``BatchSolver._build_context`` (full snapshot re-encode) and a
Python sweep over every node's tasks *per preemptor task* — which is
O(preemptors x nodes) re-encoding.

This module batches the whole action:

* ONE context build per action invocation: node arrays, predicate mask and
  static score computed for every preemptor group at once (the same batched
  encode allocate uses);
* a ``VictimIndex`` built once: every Running candidate task flattened into
  node-sliced arrays (resource vectors, integer job/queue codes, eviction
  order preserved per node) — updated incrementally as the action stages
  evictions, with per-preemptor *vectorized* candidate selection and
  segment-summed victim totals (no Python loop over nodes);
* per preemptor: one vectorized feasibility pass over all nodes
  (victim-total + future-idle cover test — the ValidateVictims bound,
  scheduler_helper.go:239-252), then *lazy exact descent*: nodes visited in
  score order, the plugin victim filter (``ssn.preemptable`` /
  ``ssn.reclaimable`` — host-side, arbitrary plugins) runs only for visited
  nodes until the first truly feasible one. Identical results to evaluating
  every node (per-node feasibility is independent; argmax-by-score = first
  feasible in score order), but the plugin chain runs O(1) times per
  preemptor instead of O(nodes).

Node-state deltas the action stages (evict -> releasing grows future idle;
pipeline -> pipelined shrinks it) are applied to the context's arrays
directly, so no re-encode ever happens mid-action.
"""

from __future__ import annotations

import functools
import time as _time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..metrics import metrics as _m
from ..models.job_info import JobInfo, TaskInfo, TaskStatus
from ..ops.score import node_score

INTER_JOB = "inter_job"    # same queue, different job (preempt.go:83-143)
INTRA_JOB = "intra_job"    # same job (preempt.go:146-183)
CROSS_QUEUE = "cross_queue"  # different, reclaimable queue (reclaim.go)


class VictimIndex:
    """Flattened Running-task candidates, node-sliced, eviction-ordered."""

    def __init__(self, ssn, narr, rindex, evict_key):
        self.rindex = rindex
        n_real = len(narr.names)
        self.n_pad = narr.idle.shape[0]
        self.job_code: Dict[str, int] = {}
        self.queue_code: Dict[str, int] = {}
        self.queue_reclaimable: List[bool] = []

        tasks: List[TaskInfo] = []
        node_of: List[int] = []
        job_of: List[int] = []
        queue_of: List[int] = []
        self.node_start = np.zeros(n_real + 1, np.int64)
        for i, name in enumerate(narr.names):
            self.node_start[i] = len(tasks)
            node = ssn.nodes.get(name)
            if node is None:
                continue
            cands = [t for t in node.tasks.values()
                     if t.status == TaskStatus.Running
                     and not t.resreq.is_empty()]
            cands.sort(key=evict_key)
            for t in cands:
                vj = ssn.jobs.get(t.job)
                qname = vj.queue if vj is not None else ""
                jc = self.job_code.setdefault(t.job, len(self.job_code))
                qc = self.queue_code.get(qname)
                if qc is None:
                    qc = len(self.queue_code)
                    self.queue_code[qname] = qc
                    q = ssn.queues.get(qname)
                    self.queue_reclaimable.append(
                        bool(q.reclaimable()) if q is not None else False)
                tasks.append(t)
                node_of.append(i)
                job_of.append(jc)
                queue_of.append(qc)
        self.node_start[n_real] = len(tasks)

        m = len(tasks)
        self.tasks = tasks
        self.node_of = np.asarray(node_of, np.int64) if m else \
            np.zeros(0, np.int64)
        self.job_of = np.asarray(job_of, np.int32) if m else \
            np.zeros(0, np.int32)
        self.queue_of = np.asarray(queue_of, np.int32) if m else \
            np.zeros(0, np.int32)
        self.res = np.stack([rindex.vec(t.resreq) for t in tasks]) if m \
            else np.zeros((0, rindex.r), np.float32)
        self.alive = np.ones(m, bool)
        self.q_reclaimable = np.asarray(self.queue_reclaimable, bool) if \
            self.queue_code else np.zeros(0, bool)
        self._uid_row = {t.uid: v for v, t in enumerate(tasks)}
        self._build_sums()

    def codes_for(self, ssn, task: TaskInfo) -> Tuple[int, int]:
        """(job_code, queue_code) of a preemptor; -1 when unseen (no
        candidate shares its job/queue)."""
        job = ssn.jobs.get(task.job)
        qname = job.queue if job is not None else ""
        return (self.job_code.get(task.job, -1),
                self.queue_code.get(qname, -1))

    # structural filters live in node_candidates (per-node slices) and
    # totals_for (incremental sums); no [M]-wide mask is ever materialized

    def _build_sums(self) -> None:
        """Incremental per-node victim sums: by queue, and rows by job —
        recomputing an [M]-wide selection + segment sum per preemptor is the
        dominant cost at 5k preemptors x 10k victims."""
        qn = max(1, len(self.queue_code))
        self.queue_sum = np.zeros((self.n_pad, qn, self.rindex.r), np.float32)
        if len(self.node_of):
            np.add.at(self.queue_sum, (self.node_of, self.queue_of), self.res)
        # running sum over RECLAIMABLE queues (the cross-queue totals'
        # common part): totals_for's per-queue loop was O(Q x N x R) per
        # reclaimer place() call
        self.reclaimable_sum = np.zeros((self.n_pad, self.rindex.r),
                                        np.float32)
        for qc in range(len(self.queue_code)):
            if self.q_reclaimable[qc]:
                self.reclaimable_sum += self.queue_sum[:, qc]
        self.rows_by_job: Dict[int, np.ndarray] = {}
        for jc in range(len(self.job_code)):
            self.rows_by_job[jc] = np.flatnonzero(self.job_of == jc)

    def _flip_sum(self, row: int, sign: float) -> None:
        qc = self.queue_of[row]
        self.queue_sum[self.node_of[row], qc] += sign * self.res[row]
        if self.q_reclaimable[qc]:
            self.reclaimable_sum[self.node_of[row]] += sign * self.res[row]

    def totals_for(self, mode: str, pj: int, pq: int) -> np.ndarray:
        """[N_pad, R] summed alive candidate resources per node under the
        mode's structural filter, from the incremental sums."""
        r = self.rindex.r
        if mode == INTER_JOB:
            if pq < 0:
                return np.zeros((self.n_pad, r), np.float32)
            out = self.queue_sum[:, pq].copy()
            rows = self.rows_by_job.get(pj)
            if rows is not None and len(rows):
                live = rows[self.alive[rows]]
                if len(live):
                    np.add.at(out, self.node_of[live], -self.res[live])
            return out
        if mode == INTRA_JOB:
            out = np.zeros((self.n_pad, r), np.float32)
            rows = self.rows_by_job.get(pj)
            if rows is not None and len(rows):
                live = rows[self.alive[rows]]
                if len(live):
                    np.add.at(out, self.node_of[live], self.res[live])
            return out
        # cross-queue reclaim: all reclaimable queues except the claimer's
        out = self.reclaimable_sum.copy()
        if 0 <= pq < len(self.queue_code) and self.q_reclaimable[pq]:
            out -= self.queue_sum[:, pq]
        return out

    def node_candidates(self, i: int, mode: str, pj: int, pq: int):
        """(tasks, res rows) of alive filter-passing candidates on node i,
        eviction order preserved."""
        s, e = int(self.node_start[i]), int(self.node_start[i + 1])
        if e - s <= 8:
            # tiny segment (the common case: a handful of running tasks per
            # node): plain-Python filtering beats seven numpy dispatches
            rows = []
            for v in range(s, e):
                if not self.alive[v]:
                    continue
                jv, qv = self.job_of[v], self.queue_of[v]
                if mode == INTER_JOB:
                    if qv != pq or jv == pj:
                        continue
                elif mode == INTRA_JOB:
                    if jv != pj:
                        continue
                else:
                    if qv == pq or not self.q_reclaimable[qv]:
                        continue
                rows.append(v)
            return [self.tasks[v] for v in rows], self.res[rows]
        sel = self.alive[s:e].copy()
        jseg = self.job_of[s:e]
        qseg = self.queue_of[s:e]
        if mode == INTER_JOB:
            sel &= (qseg == pq) & (jseg != pj)
        elif mode == INTRA_JOB:
            sel &= jseg == pj
        else:
            sel &= qseg != pq
            if len(self.q_reclaimable):
                sel &= self.q_reclaimable[qseg]
        rows = np.flatnonzero(sel) + s
        return [self.tasks[v] for v in rows], self.res[rows]



class PreemptContext:
    """One per action execution: batched encode + live node-state mirror."""

    def __init__(self, ssn,
                 ordered_jobs: List[Tuple[JobInfo, List[TaskInfo]]]):
        self.ssn = ssn
        solver = ssn.solver
        self.rindex = solver.rindex
        # host-native context: the preempt/reclaim walk reads a handful of
        # mask/score rows in numpy; building on-device and pulling [G, N]
        # matrices back from the device costs seconds at 5k x 10k
        self.narr, self.batch, self.gmask, self.static = \
            solver.build_host_context(ordered_jobs)
        self.weights = solver.score_weights().host()
        # live mirrors, sync'd to session state at build time
        self.idle = self.narr.idle.copy()
        self.future = self.narr.future_idle.copy()
        self.n_tasks = self.narr.n_tasks.copy()
        self.alloc = self.narr.allocatable
        self.max_tasks = self.narr.max_tasks
        self.task_group: Dict[str, int] = {}
        for t_idx, t in enumerate(self.batch.tasks):
            self.task_group[t.uid] = int(self.batch.task_group[t_idx])
        evict_key = functools.cmp_to_key(
            lambda a, b: -1 if not ssn.task_order_fn(a, b) else 1)
        self.victims = VictimIndex(ssn, self.narr, self.rindex, evict_key)
        self.eps = self.rindex.eps
        self.node_idx = {name: i for i, name in enumerate(self.narr.names)}
        self._log: List[tuple] = []
        # plugin-rejection cache, scoped to one preemptor job: for the
        # builtin plugins a node rejected for task k of a job stays rejected
        # for task k+1 (drf's preemptor share only grows, gang budgets only
        # shrink, priority/conformance are static) as long as the node's
        # candidate set is untouched. Cleared on job switch, rollback, and
        # per-node on any state delta. Cuts the dominant cost at scale:
        # straggler nodes DRF refuses to break up get re-dispatched for
        # every preemptor of the job otherwise.
        self._reject_mask = np.zeros(self.narr.idle.shape[0], bool)
        self._reject_key: Optional[tuple] = None
        # per-group full-cluster score rows, computed once per action:
        # preempt/reclaim never touch the idle mirror (evictions grow
        # *future* idle, pipelines consume it), so node_score inputs are
        # invariant for the whole action — recomputing + argsorting ~N
        # scores per preemptor was the dominant cost at 5k x 10k
        self._score_cache: Dict[object, np.ndarray] = {}
        # with no static score contributions (the common preempt conf),
        # score rows depend only on the request vector — share them across
        # the per-job groups instead of recomputing ~4 O(N) terms per job
        self._static_trivial = not self.static.any()
        # cross-job persistent rejections, keyed (mode, group): sound when
        # every enabled preemptable plugin's per-victim acceptance only
        # shrinks along the action's job-order pop sequence —
        #   gang: victim-job occupancy only drops (evictions);
        #   conformance: static; priority: preemptor priority non-increasing
        #   in pop order; drf: preemptor shares non-decreasing (pop-min
        #   water-fill) and victim shares non-increasing — but only while
        #   priority ties keep the share sequence monotone.
        # Out-of-tree preemptable plugins disable persistence (their
        # acceptance may grow mid-action); rollback clears it (restored
        # state can flip verdicts). Without it, every preemptor job
        # re-discovers the same drained nodes: 269k node visits for 5k
        # preemptors x 10k nodes at the config-4 benchmark.
        self._persistent_reject: Dict[tuple, np.ndarray] = {}
        # resumable walk for consecutive same-(job, mode, req) preemptors:
        # scores are static and a node's future+totals cover only shrinks
        # during a job (evictions move resources from totals to future,
        # pipelines consume future), so an initially-infeasible node can
        # never become feasible mid-job — the masked score array from task
        # k's walk is a valid starting point for task k+1, with per-node
        # exact re-tests at visit time catching staleness the other way
        self._walk_key: Optional[tuple] = None
        self._walk_masked: Optional[np.ndarray] = None
        # shared descending-score visit order per score key: scores are
        # action-invariant (see _score_cache), so one stable argsort serves
        # every walk with that key — the pointer walk below replaces a
        # masked argmax per visited node (~N floats per visit at 10k nodes)
        self._order_cache: Dict[object, np.ndarray] = {}
        self._walk_order: Optional[np.ndarray] = None
        self._walk_ptr: int = 0
        # per-group predicate-row hash: lets walks key on CONTENT so
        # consecutive preemptor jobs with identical (mode, request, queue,
        # predicate row) and no own-job candidates share one walk state —
        # sound under the same monotonicity that backs _persistent_reject
        # (scores static; cover/caps/candidates only shrink; rollback
        # clears the state)
        self._gmask_hash: Dict[int, int] = {}
        self._gmask_intern: Dict[bytes, int] = {}
        enabled = set()
        for tier in ssn.tiers:
            for opt in tier.plugins:
                if opt.is_enabled("enabledPreemptable") and \
                        opt.name in ssn.preemptable_fns:
                    enabled.add(opt.name)
        monotone = {"gang", "conformance", "priority", "drf"}
        self._persist_ok = enabled <= monotone
        if "drf" in enabled and self._persist_ok:
            prios = {j.priority for j, _ in ordered_jobs}
            self._persist_ok = len(prios) <= 1
        # cross-queue (reclaim) empty-victim persistence: sound when every
        # enabled reclaimable plugin's per-victim acceptance only SHRINKS
        # over the action's eviction sequence —
        #   proportion: evictions only lower a victim queue's allocated
        #     toward deserved, so the above-deserved test and the
        #     less_partly(reclaimer.resreq) guard only reject more. The
        #     one acceptance-GROWING event is a reclaimer PIPELINE: it
        #     raises the reclaimer queue's allocated, which can flip that
        #     queue's victims eligible for OTHER reclaimers —
        #     apply_pipeline invalidates the affected persist bits;
        #   gang: victim-job occupancy only drops (the pipelined
        #     reclaimer's own job is never a cross-queue candidate);
        #   conformance: static.
        # drf's hierarchical what-if tree has no such monotonicity, and
        # out-of-tree plugins may grow acceptance — both disable it.
        enabled_r = set()
        for tier in ssn.tiers:
            for opt in tier.plugins:
                if opt.is_enabled("enabledReclaimable") and \
                        opt.name in ssn.reclaimable_fns:
                    enabled_r.add(opt.name)
        self._persist_ok_reclaim = \
            enabled_r <= {"gang", "conformance", "proportion"}
        # vectorized victim selection (ops/victims.py): replaces the lazy
        # Python walk below when every enabled preemptable/reclaimable
        # plugin has a compiled form; `victims.kernel: off` (solver conf)
        # forces the Python reference, and a kernel crash falls back to
        # it for the rest of the action (breaker semantics)
        self._victim_kernel = None
        self._victim_kernel_broken = False
        conf = "auto"
        args = (getattr(ssn, "configurations", None) or {}).get("solver")
        if args is not None and hasattr(args, "get_str"):
            conf = (args.get_str("victims.kernel", "auto")
                    or "auto").strip().lower()
        self._victim_kernel_conf = conf

    # -- state deltas (mirror Statement.evict / pipeline) ------------------
    # Deltas are logged so a Statement.discard can be mirrored exactly:
    # checkpoint() marks a rollback point, rollback() reverts to it,
    # commit() drops the log.

    def checkpoint(self) -> None:
        self._log: List[tuple] = []

    def commit(self) -> None:
        self._log = []

    def rollback(self) -> None:
        for kind, i, vec, row in reversed(self._log):
            if kind == "evict":
                if i is not None:
                    self.future[i] -= vec
                if row is not None:
                    self.victims.alive[row] = True
                    self.victims._flip_sum(row, +1.0)
                    if self._victim_kernel is not None:
                        self._victim_kernel.note_revive(row)
            else:   # pipeline
                if i is not None:
                    self.future[i] += vec
                    self.n_tasks[i] -= 1
                    if self._victim_kernel is not None:
                        self._victim_kernel.note_node(i)
        self._log = []
        self._reject_mask[:] = False   # restored state can flip rejections
        self._persistent_reject.clear()
        self._walk_key = None
        self._walk_masked = None
        self._walk_order = None
        self._walk_ptr = 0
        if self._victim_kernel is not None:
            self._victim_kernel.reset_walk()

    def mark_dead(self, victim: TaskInfo) -> None:
        """Drop a victim from the candidate index without any node-state
        delta (the session eviction failed, e.g. the task vanished)."""
        row = self.victims._uid_row.get(victim.uid)
        if row is not None and self.victims.alive[row]:
            self.victims.alive[row] = False
            self.victims._flip_sum(row, -1.0)
            if self._victim_kernel is not None:
                self._victim_kernel.note_evict(row)

    def apply_evict(self, node_name: str, victim: TaskInfo) -> None:
        """Running -> Releasing: future idle grows by the victim's request."""
        i = self.node_idx.get(node_name)
        vec = self.rindex.vec(victim.resreq)
        if i is not None:
            self.future[i] += vec
        row = self.victims._uid_row.get(victim.uid)
        if row is not None:
            self.victims.alive[row] = False
            self.victims._flip_sum(row, -1.0)
            if self._victim_kernel is not None:
                self._victim_kernel.note_evict(row)
        self._log.append(("evict", i, vec, row))
        if i is not None:
            self._reject_mask[i] = False
            for mask in self._persistent_reject.values():
                mask[i] = False

    def apply_pipeline(self, node_name: str, task: TaskInfo) -> None:
        """Pipelined consumes future idle and a pod slot."""
        i = self.node_idx.get(node_name)
        vec = self.rindex.vec(task.resreq)
        if i is not None:
            self.future[i] -= vec
            self.n_tasks[i] += 1
            if self._victim_kernel is not None:
                self._victim_kernel.note_node(i)
        self._log.append(("pipeline", i, vec, None))
        if i is not None:
            self._reject_mask[i] = False
            for mask in self._persistent_reject.values():
                mask[i] = False
        # the pipeline's allocate event raised the task's queue's live
        # allocated (proportion), which can flip that queue's victims from
        # ineligible to eligible for OTHER reclaimers: clear cross-queue
        # persisted rejections on every node holding live candidates of
        # that queue (reclaim.go re-runs Reclaimable per walk and would
        # accept them)
        job = self.ssn.jobs.get(task.job)
        qname = job.queue if job is not None else ""
        qc = self.victims.queue_code.get(qname)
        if qc is not None and self._persistent_reject:
            rows = np.flatnonzero((self.victims.queue_of == qc)
                                  & self.victims.alive)
            if len(rows):
                n_real = len(self.narr.names)
                nodes = np.unique(self.victims.node_of[rows])
                nodes = nodes[nodes < n_real]
                for pkey, mask in self._persistent_reject.items():
                    if pkey[0] == CROSS_QUEUE and pkey[3] != qc:
                        mask[nodes] = False
                # a resumed cross-queue walk may also hold stale exclusions
                if self._walk_key is not None \
                        and self._walk_key[0] == CROSS_QUEUE:
                    self._walk_key = None
                    self._walk_masked = None
                if self._victim_kernel is not None:
                    self._victim_kernel.reset_walk()

    # -- per-preemptor evaluation ------------------------------------------

    def place(self, preemptor: TaskInfo, mode: str,
              victim_cb: Optional[Callable] = None):
        """Best node for ``preemptor`` via victim eviction.

        Preempt modes (INTER_JOB/INTRA_JOB): None, or one
        (node_name, victims_to_evict, True) — a node is returned only when
        a victim prefix makes the request fit FutureIdle.

        CROSS_QUEUE: None, or the next (node_name, victims, covered) step
        of the reference's node walk — reclaim evicts each visited node's
        victims even when they don't cover the request (evictions stick,
        reclaim.go:156-166). The caller applies the step (so later plugin
        filtering sees post-eviction state, exactly like the sequential
        reference walk) and calls again until covered or None.

        ValidateVictims semantics: a node needs >=1 plugin-approved victim
        (zero-eviction placement is allocate's job, preempt.go:239-245).
        """
        g = self.task_group.get(preemptor.uid)
        if g is None:
            return None
        ssn = self.ssn
        pj, pq = self.victims.codes_for(ssn, preemptor)
        if mode == INTER_JOB and pq < 0:
            return None
        if mode == INTRA_JOB and pj < 0:
            return None

        # the group's encoded request (== vec(init_resreq): groups key on
        # the request and pending tasks have resreq == init_resreq)
        req = self.batch.group_req[g]
        n_real = len(self.narr.names)
        use_cache = mode != CROSS_QUEUE

        skey = req.tobytes() if self._static_trivial else g
        score = self._score_cache.get(skey)
        if score is None:
            score = np.asarray(node_score(req, self.idle, self.alloc,
                                          self.weights, self.static[g],
                                          xp=np))[:n_real]
            self._score_cache[skey] = score

        # vectorized victim-selection kernel: one task x node pass over
        # every candidate instead of the per-node plugin-chain walk;
        # bit-identical by construction (tests/test_constraints.py).
        # Runs BEFORE the walk's resume-key/persistent-reject setup: the
        # kernel never reads them, and allocating a per-(job, request)
        # reject mask per place made apply_evict/apply_pipeline sweep a
        # growing mask dict the kernel path never consults.
        if self._victim_kernel_conf != "off" \
                and not self._victim_kernel_broken:
            vk = self._victim_kernel
            if vk is None:
                from ..ops.victims import VictimKernel
                vk = self._victim_kernel = VictimKernel(self)
            if vk.supports(mode):
                t0 = _time.perf_counter()
                try:
                    return vk.place(preemptor, mode, g, pj, pq, req,
                                    score, victim_cb=victim_cb)
                except Exception:
                    import logging
                    logging.getLogger(__name__).exception(
                        "victim-selection kernel crashed; falling back "
                        "to the Python walk for this action")
                    self._victim_kernel_broken = True
                finally:
                    _m.observe(_m.VICTIM_SELECT_LATENCY,
                               (_time.perf_counter() - t0) * 1000.0)
        _m.inc(_m.VICTIM_SELECT_RUNS, mode="python")

        # walk resume key: content-keyed when persistence is sound (see
        # _gmask_hash) so identical consecutive jobs resume one walk; else
        # the group id, which encodes (job, task spec, request, scheduling
        # constraints) — a resumed masked-score array can never leak one
        # group's predicate mask to another either way. CROSS_QUEUE keys
        # on the reclaimer itself: its multi-step walk (the caller applies
        # evictions between place() calls) resumes instead of rebuilding —
        # sound unconditionally because it mirrors the reference's single
        # pass over the node list per reclaimer (reclaim.go:114-182), and
        # unvisited nodes' future/totals are untouched by the walk's own
        # evictions
        if use_cache and self._persist_ok and self._static_trivial:
            h = self._gmask_hash.get(g)
            if h is None:
                row = self.gmask[g].tobytes()
                h = self._gmask_intern.setdefault(
                    row, len(self._gmask_intern))
                self._gmask_hash[g] = h
            key = (mode, req.tobytes(), pj, pq, h)
        elif use_cache:
            key = (mode, g)
        else:
            key = (mode, preemptor.uid)
        persist = None
        if (use_cache and self._persist_ok) or \
                (mode == CROSS_QUEUE and self._persist_ok_reclaim):
            # keyed by (mode, request, preemptor job/queue codes), NOT by
            # group: a victim-empty verdict depends on the preemptor's
            # request (drf's ls term), its structural filter identity
            # (node_candidates excludes the preemptor's own job / queue),
            # and the victims' monotonically-shrinking acceptance — so
            # preemptors of different jobs with the same request AND the
            # same candidate-set shape share rejections
            pkey = (mode, req.tobytes(), pj, pq)
            persist = self._persistent_reject.get(pkey)
            if persist is None:
                persist = np.zeros(n_real, bool)
                self._persistent_reject[pkey] = persist

        if key == self._walk_key and self._walk_masked is not None:
            # resume task k's walk for task k+1 (same job/mode/request), or
            # the same reclaimer's next step (CROSS_QUEUE): per-node
            # staleness is re-tested at visit below
            masked = self._walk_masked
        else:
            # invalidate any prior resume state up front: the early
            # returns below must not leave a stale key paired with
            # another walk's order/masked
            self._walk_key = None
            self._walk_masked = None
            if use_cache:
                # descending-score visit order, shared across walks with
                # this score key (stable sort == argmax's first-index
                # tie-break); dead/rejected nodes are skipped via masked
                order = self._order_cache.get(skey)
                if order is None:
                    order = np.argsort(-score, kind="stable")
                    self._order_cache[skey] = order
            pods_ok = (self.max_tasks == 0) | (self.n_tasks < self.max_tasks)
            mask = self.gmask[g] & pods_ok
            mask[n_real:] = False
            totals = self.victims.totals_for(mode, pj, pq)
            has_victims = totals.any(axis=1)
            # column-wise cover test (req <= future + totals + eps): avoids
            # the [N, R] broadcast temporaries of the np.all formulation
            opt_ok = mask & has_victims
            for c in range(self.rindex.r):
                opt_ok &= (self.future[:, c] + totals[:, c]) >= \
                    (req[c] - self.eps[c])
            if not opt_ok.any():
                return None
            # rejection cache key: same job AND mode AND request — drf's
            # allowance depends on the preemptor's resreq (ls =
            # share(allocated + resreq)), so a smaller later task must not
            # inherit rejections recorded for a bigger one; CROSS_QUEUE
            # persistence is separately gated (_persist_ok_reclaim)
            if use_cache:
                if key != self._reject_key:
                    self._reject_mask[:] = False
                    self._reject_key = key
                visit_ok = opt_ok[:n_real] & ~self._reject_mask[:n_real]
            else:
                visit_ok = opt_ok[:n_real]
            if persist is not None:
                visit_ok &= ~persist
            if not visit_ok.any():
                return None
            masked = np.where(visit_ok, score, -np.inf)
            if use_cache:
                # seek past the already-consumed/-rejected prefix in one
                # vector op — per-position Python stepping is O(jobs x
                # consumed) across the action
                self._walk_order = order
                self._walk_ptr = int(np.argmax(masked[order] != -np.inf))
            else:
                self._walk_order = None
            self._walk_key, self._walk_masked = key, masked

        select = ssn.reclaimable if mode == CROSS_QUEUE else ssn.preemptable
        # lazy best-first walk. use_cache: pointer sweep over the shared
        # descending-score order (each position consumed once per job; a
        # winning node holds its position so the job's next task re-tests
        # it). CROSS_QUEUE: masked argmax per visit, with the masked array
        # resuming across the reclaimer's multi-step walk.
        neg_inf = -np.inf
        order = self._walk_order if use_cache else None
        n_order = len(order) if order is not None else 0
        while True:
            if use_cache:
                ptr = self._walk_ptr
                while ptr < n_order and masked[order[ptr]] == neg_inf:
                    ptr += 1
                self._walk_ptr = ptr
                if ptr >= n_order:
                    break
                i = int(order[ptr])
            else:
                i = int(np.argmax(masked))
                if masked[i] == neg_inf:
                    break
            masked[i] = -np.inf
            if self.max_tasks[i] and self.n_tasks[i] >= self.max_tasks[i]:
                continue   # pod-slot cap re-test (stale on a resumed walk)
            cands, res = self.victims.node_candidates(i, mode, pj, pq)
            if not cands:
                continue
            victims = select(preemptor, cands)
            if victim_cb is not None:
                victim_cb(victims)
            if not victims:
                if use_cache:
                    self._reject_mask[i] = True
                if persist is not None:
                    persist[i] = True
                continue
            # eviction order + smallest feasible prefix (the victim_prefix /
            # reclaim_prefix kernel semantics, ops/preempt.py)
            uid_pos = {t.uid: v for v, t in enumerate(cands)}
            victims.sort(key=lambda t: uid_pos[t.uid])
            if mode != CROSS_QUEUE and len(victims) <= 4:
                # scalar prefix walk: at 1-4 victims (the common shape) the
                # np.stack/cumsum/all formulation is five array dispatches
                # for a handful of floats
                fut = self.future[i]
                run = [float(fut[c]) for c in range(self.rindex.r)]
                k = -1
                for p in range(len(victims) + 1):
                    if all(req[c] <= run[c] + self.eps[c]
                           for c in range(self.rindex.r)):
                        k = p
                        break
                    if p < len(victims):
                        row = res[uid_pos[victims[p].uid]]
                        for c in range(self.rindex.r):
                            run[c] += float(row[c])
                if k < 0:
                    continue
                masked[i] = score[i]
                return self.narr.names[i], victims[:k], True
            vres = np.stack([res[uid_pos[t.uid]] for t in victims])
            if mode == CROSS_QUEUE:
                if not np.all(req <= self.future[i] + vres.sum(axis=0)
                              + self.eps):
                    continue   # ValidateVictims against the filtered set
                cum = np.cumsum(vres, axis=0)
                covers = np.all(req[None, :] <= cum + self.eps[None, :],
                                axis=-1)
                covered = bool(covers.any())
                k = int(np.argmax(covers)) + 1 if covered else len(victims)
                return self.narr.names[i], victims[:k], covered
            cum0 = np.concatenate(
                [np.zeros((1, self.rindex.r), np.float32),
                 np.cumsum(vres, axis=0)], axis=0)
            fits = np.all(req[None, :] <= self.future[i][None, :] + cum0
                          + self.eps[None, :], axis=-1)
            if not fits.any():
                continue
            # keep the winning node visitable for the job's next task (the
            # resumed walk re-tests it exactly)
            masked[i] = score[i]
            return self.narr.names[i], victims[:int(np.argmax(fits))], True
        return None
