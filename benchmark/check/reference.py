"""The plain reference for gang placement, in numpy, importing nothing of
the program.

It states the semantics of Volcano's allocate loop (allocate.go:123-270)
as the placement kernel is given them, one task at a time:

* jobs are taken by the two-level rule: the namespace (live weighted
  dominant share when ``ns_live``, else encode order), then the non-
  overused queue of least dominant share (proportion.go), then that
  queue's next job; ties go to the lower index;
* a task may use a node that passes the static predicates, has pod room
  and fits its request in idle resources (or, where no node does and
  pipelining is allowed, in future resources);
* among those, the node of highest score: binpack (binpack.go),
  least-requested and most-requested and balanced-allocation (k8s, via
  nodeorder.go) by their weights, plus the static score; the lowest
  index wins a tie;
* at a job's end the gang commits if its ready count reaches minMember,
  and is otherwise rolled back (statement.go Commit/Discard).

``check`` replays the program's own answer for one kernel call, teacher
forced: jobs in the reference's order, each task on the node the program
chose, and at each task it measures how far the chosen node's score lies
below the best that the reference sees (``gap``), whether the chosen node
was allowed at all (``invalid``), and, for each job the program left
unplaced, whether the reference could place it (``lost``). Queues whose
shares tie within a relative 1e-5 (the rounding of a share computed on
the chip; bfloat16 rounds at 4e-3) may be taken in either order on the
chip: there the one whose next job the program's answer fits is taken,
and a tied job taken later is measured on the better of its own state and
the state before the tie, so that a tie broken by rounding is not read as
a fault.

``place`` is the reference's own answer; with ``dtype`` below float32 it
is the control.

With a ``book`` (what the benchmark itself knows of the call: each task
slot's pod, each node's name, the column labels and the program's
snapshot of node state), ``check`` does not take the call's tables from
the program: it derives them from the cell's objects (``derive``). Each
request from the pod's job, each node's allocatable and pod room from
the configuration, the static mask as the capability fit (the cells'
pods carry no selector, affinity, toleration, port or topology
annotation and their nodes no taint, so nothing else masks a node), a
static score of zero and no topology bucket, each job's minMember and
queue from its PodGroup, the score weights from the scheduler conf. Where the program's tables differ from these,
``mismatch`` counts the differing entries. A job the program left
unplaced is then judged over every node of the cluster, not over the
shortlist the program ran on (``lost``): pruning may pick other nodes,
but it may not lose a gang. What stays the program's: the snapshot of
node state (idle, future idle, task counts), the queues' allocated and
deserved shares, the namespaces' shares, the order of jobs inside a
queue, each job's count of tasks already ready, and the shortlist over
which ``gap`` is read.
"""

from __future__ import annotations

import numpy as np

NAMES = ("task_group", "task_job", "task_valid", "group_req", "group_mask",
         "static", "task_bucket", "pack_bonus", "job_min", "job_base",
         "job_start", "job_n", "job_queue", "pool_queue", "pool_ns",
         "pool_start", "pool_njobs", "ns_weight", "ns_alloc0", "ns_total",
         "q_deserved", "q_alloc0", "idle", "future", "alloc", "ntasks",
         "max_tasks", "eps")
FLOATS = ("group_req", "static", "pack_bonus", "ns_weight", "ns_alloc0",
          "ns_total", "q_deserved", "q_alloc0", "idle", "future", "alloc",
          "eps")
TIE = 1e-5


class Problem:
    """One kernel call's inputs as host arrays in ``dtype``."""

    def __init__(self, args, kwargs, dtype=np.float32):
        self.dt = np.dtype(dtype)
        for name, a in zip(NAMES, args[:28]):
            a = np.asarray(a)
            if name in FLOATS:
                a = a.astype(self.dt)
            setattr(self, name, a)
        w = [np.asarray(x) for x in args[28]]
        self.w_res = w[0].astype(self.dt)
        self.w_binpack, self.w_least, self.w_most, self.w_balanced = (
            self.dt.type(x) for x in w[1:5])
        self.allow_pipeline = bool(kwargs.get("allow_pipeline", True))
        self.ns_live = bool(kwargs.get("ns_live", False))
        self.T = int(self.task_group.shape[0])
        self.N = int(self.idle.shape[0])
        self.R = int(self.idle.shape[1])
        self.pack = bool((self.task_bucket[self.task_valid.astype(bool)]
                          >= 0).any())


def _scores(P: Problem, g, req, idle, ntasks, pack_nodes, sl=slice(None)):
    """(fits idle, fits future, score) of group ``g`` over nodes ``sl``."""
    dt = P.dt.type
    alloc = P.alloc[sl]
    idle = idle[sl]
    base = P.group_mask[g, sl] & ((P.max_tasks[sl] == 0) |
                                  (ntasks[sl] < P.max_tasks[sl]))
    eps = P.eps
    fits_idle = np.all(req[None, :] <= idle + eps[None, :], axis=-1) & base
    used = alloc - idle
    # binpack
    requested = (req > 0) & (P.w_res > 0)
    frac = np.where(alloc > 0, (used + req[None, :]) /
                    np.maximum(alloc, dt(1e-9)), dt(2.0))
    per = np.where(frac <= 1, frac * dt(100.0), dt(0.0))
    w = np.where(requested, P.w_res, dt(0.0))
    wsum = np.maximum(np.sum(w), dt(1e-9))
    binpack = np.sum(per * w[None, :], axis=-1) / wsum
    a = alloc[:, 0:2]
    u = used[:, 0:2] + req[None, 0:2]
    am = np.maximum(a, dt(1e-9))
    pos = a > 0
    two = dt(2.0)
    lf = np.where(pos, np.maximum(a - u, dt(0.0)) / am, dt(0.0)) * dt(100.0)
    least = (lf[:, 0] + lf[:, 1]) / two
    mf = np.where(pos, np.minimum(np.maximum(u, dt(0.0)), a) / am,
                  dt(0.0)) * dt(100.0)
    most = (mf[:, 0] + mf[:, 1]) / two
    f = np.where(pos, u / am, dt(0.0))
    balanced = dt(100.0) - np.abs(f[:, 0] - f[:, 1]) * dt(100.0)
    static = dt(0.0) if P.static is None else P.static[g, sl]
    if P.pack:
        static = static + pack_nodes[sl] * P.pack_bonus[g]
    score = (P.w_binpack * binpack + P.w_least * least + P.w_most * most +
             P.w_balanced * balanced + static)
    return fits_idle, base, score.astype(P.dt)


def _row(P: Problem, g, req, n: int, idle, ntasks):
    """``_scores`` for the one node ``n``, in scalars of the same type and
    order (the sweep after a placement changes that node alone)."""
    dt = P.dt.type
    z, hundred, tiny = dt(0.0), dt(100.0), dt(1e-9)
    alloc, idl, eps, wres = P.alloc[n], idle[n], P.eps, P.w_res
    mt = P.max_tasks[n]
    base = bool(P.group_mask[g, n]) and (mt == 0 or ntasks[n] < mt)
    fits = base
    acc, wsum = z, z
    used = []
    for r in range(P.R):
        q, a, i = req[r], alloc[r], idl[r]
        if not q <= i + eps[r]:
            fits = False
        u = a - i
        used.append(u)
        frac = (u + q) / max(a, tiny) if a > 0 else dt(2.0)
        per = frac * hundred if frac <= 1 else z
        w = wres[r] if (q > 0 and wres[r] > 0) else z
        acc = acc + per * w
        wsum = wsum + w
    binpack = acc / max(wsum, tiny)
    lf, mf, f = [], [], []
    for r in (0, 1):
        a = alloc[r]
        u = used[r] + req[r]
        am = max(a, tiny)
        pos = a > 0
        lf.append((max(a - u, z) / am if pos else z) * hundred)
        mf.append((min(max(u, z), a) / am if pos else z) * hundred)
        f.append(u / am if pos else z)
    two = dt(2.0)
    least = (lf[0] + lf[1]) / two
    most = (mf[0] + mf[1]) / two
    balanced = hundred - abs(f[0] - f[1]) * hundred
    static = z if P.static is None else P.static[g, n]
    score = (P.w_binpack * binpack + P.w_least * least + P.w_most * most +
             P.w_balanced * balanced + static)
    return fits, base, dt(score)


# The program's units, from a quantity's (milli-cpu, bytes, plain units):
# milli-cpu, MiB, and milli-units for every other resource (Volcano's
# resource_info.go); a fit is allowed a tenth of a milli-unit or byte.
UNITS = {"cpu": 1.0, "memory": 1.0 / 2 ** 20}
SCALAR_UNIT = 1000.0
EPS = {"memory": 0.1 / 2 ** 20}
EPS_OTHER = 0.1


def conf_weights(conf_text: str, resources) -> np.ndarray:
    """The score weights a scheduler conf states, as Volcano reads them
    (binpack.go:105-150, nodeorder.go): [per-resource binpack weights...,
    binpack, leastrequested, mostrequested, balancedresource]; a plugin
    the conf does not name weighs nothing."""
    import yaml
    args = {}
    for tier in (yaml.safe_load(conf_text) or {}).get("tiers") or []:
        for pl in tier.get("plugins") or []:
            args[pl["name"]] = pl.get("arguments") or {}
    w_res = np.zeros(len(resources))
    w_bp = 0.0
    if "binpack" in args:
        a = args["binpack"]
        w_bp = float(a.get("binpack.weight", 1))
        per = {"cpu": a.get("binpack.cpu", 1),
               "memory": a.get("binpack.memory", 1)}
        for r in str(a.get("binpack.resources", "") or "").split(","):
            if r.strip():
                per[r.strip()] = a.get(f"binpack.resources.{r.strip()}", 1)
        w_res = np.array([float(per.get(r, 0)) for r in resources])
    least = most = bal = 0.0
    if "nodeorder" in args:
        a = args["nodeorder"]
        least = float(a.get("leastrequested.weight", 1))
        most = float(a.get("mostrequested.weight", 0))
        bal = float(a.get("balancedresource.weight", 1))
    return np.concatenate([w_res, [w_bp, least, most, bal]])


class _FitMask:
    """The static mask ``[g, nodes]`` of a problem whose pods carry no
    constraint: the group's request fits the node's allocatable, on a node
    the configuration knows; built row by row as the walk asks."""

    def __init__(self, req, alloc, known, eps):
        self.req, self.alloc, self.known, self.eps = req, alloc, known, eps
        self.rows: dict = {}

    def __getitem__(self, key):
        g, idx = key
        row = self.rows.get(g)
        if row is None:
            row = np.all(self.req[g][None, :] <= self.alloc +
                         self.eps[None, :], axis=-1) & self.known
            self.rows[g] = row
        return row[idx]


def derive(call: dict, book: dict, dtype=np.float32):
    """(the reduced problem with the derived tables, the same over every
    node of the cluster, the count of program entries that differ from
    the derived ones, by table). ``book`` holds, for the call: ``req`` [T, R] each
    task slot's request in raw units and ``min_member``, ``queue`` [T] its
    job's (the queue as an index into the call's queue labels, -1 for a
    name not among them), all from the cell's own objects; ``resources``
    the column labels; ``alloc_raw`` [N, R], ``pods_cap`` [N] and
    ``known`` [N] each node's allocatable from the configuration;
    ``weights`` the conf's score weights (``conf_weights``);
    ``cols`` [U] the node of each column of the call (-1: none); and the
    program's snapshot of every node: ``idle``, ``future``, ``ntasks``,
    ``alloc``, ``max_tasks``."""
    P = Problem(call["args"], call["kwargs"], dtype)
    dt = P.dt
    unit = np.array([UNITS.get(r, SCALAR_UNIT) for r in book["resources"]])
    eps = np.array([EPS.get(r, EPS_OTHER) for r in book["resources"]], dt)
    valid = P.task_valid.astype(bool)
    n_t = int(book["req"].shape[0])
    tv = np.flatnonzero(valid[:n_t])
    req_t = (book["req"] * unit[None, :]).astype(dt)
    parts = {}
    # requests: the group's from its first task; every task of it alike
    G = P.group_req.shape[0]
    greq = P.group_req.copy()
    used = np.zeros(G, bool)
    first = {}
    for t in tv.tolist():
        first.setdefault(int(P.task_group[t]), t)
    for g, t in first.items():
        greq[g] = req_t[t]
        used[g] = True
    parts["task_request"] = int(
        np.any(req_t[tv] != greq[P.task_group[tv]], axis=-1).sum())
    parts["group_request"] = int(
        np.any(P.group_req[used] != greq[used], axis=-1).sum())
    # nodes: allocatable and pod room from the configuration
    alloc_d = (book["alloc_raw"] * unit[None, :]).astype(dt)
    maxt_d = np.asarray(book["pods_cap"], np.int64)
    known = np.asarray(book["known"], bool)
    parts["unknown_node"] = int((~known).sum())
    parts["node_table"] = int(
        (np.any(np.asarray(book["alloc"], dt) != alloc_d, axis=-1)
         | (np.asarray(book["max_tasks"]) != maxt_d)).sum())
    # the call's columns: the program's node rows must be the snapshot's
    cols = np.asarray(book["cols"], np.int64)
    live = cols >= 0
    c = np.where(live, cols, 0)
    idle = np.where(live[:, None], np.asarray(book["idle"], dt)[c], 0)
    future = np.where(live[:, None], np.asarray(book["future"], dt)[c], 0)
    ntasks = np.where(live, np.asarray(book["ntasks"])[c], 0)
    parts["node_row"] = int(
        (np.any(P.idle[live] != idle[live], axis=-1) |
         np.any(P.future[live] != future[live], axis=-1) |
         (P.ntasks[live] != ntasks[live])).sum())
    alloc_c = np.where(live[:, None], alloc_d[c], 0).astype(dt)
    maxt_c = np.where(live, maxt_d[c], 0)
    # the static mask, score and topology buckets
    fit = _FitMask(greq, alloc_c, live & known[c], eps)
    mask = np.zeros((G, cols.shape[0]), bool)
    for g in np.flatnonzero(used).tolist():
        mask[g] = fit[g, slice(None)]
    parts["mask"] = int((P.group_mask[used] != mask[used]).sum())
    parts["static_score"] = int((P.static[used] != 0).sum())
    parts["bucket"] = int((P.task_bucket[tv] >= 0).sum())
    # each job's minMember and queue (and the pool it is filed under)
    J = P.job_min.shape[0]
    jmin = P.job_min.copy()
    jq = np.full(J, -1, np.int64)
    seen = np.zeros(J, bool)
    for t in tv.tolist():
        j = int(P.task_job[t])
        jmin[j] = int(book["min_member"][t])
        jq[j] = int(book["queue"][t])
        seen[j] = True
    parts["min_member"] = int((P.job_min[seen] != jmin[seen]).sum())
    parts["job_queue"] = int((P.job_queue[seen] != jq[seen]).sum())
    for p in range(P.pool_queue.shape[0]):
        s0, n = int(P.pool_start[p]), int(P.pool_njobs[p])
        js = np.arange(s0, s0 + n)
        js = js[(js < J) & seen[np.clip(js, 0, J - 1)]]
        parts["pool_queue"] = parts.get("pool_queue", 0) + \
            int((jq[js] != int(P.pool_queue[p])).sum())
    # the score weights the conf states
    w = np.asarray(book["weights"], np.float64)
    R = len(book["resources"])
    prog = np.concatenate([np.asarray(P.w_res, np.float64),
                           [float(P.w_binpack), float(P.w_least),
                            float(P.w_most), float(P.w_balanced)]])
    parts["score_weights"] = int((prog != w).sum())
    P.w_res = w[:R].astype(dt)
    P.w_binpack, P.w_least, P.w_most, P.w_balanced = (
        dt.type(x) for x in w[R:R + 4])
    # the reduced problem on the derived tables
    P.group_req, P.group_mask, P.static = greq, mask, None
    P.task_bucket = np.full_like(P.task_bucket, -1)
    P.pack_bonus = np.zeros_like(P.pack_bonus)
    P.pack = False
    P.job_min = jmin
    P.idle, P.future, P.ntasks = idle, future, ntasks
    P.alloc, P.max_tasks, P.eps = alloc_c, maxt_c, eps
    # the same over every node of the cluster
    F = Problem.__new__(Problem)
    F.__dict__.update(P.__dict__)
    F.N = int(alloc_d.shape[0])
    F.alloc, F.max_tasks = alloc_d, maxt_d
    F.idle = np.asarray(book["idle"], dt).copy()
    F.future = np.asarray(book["future"], dt).copy()
    F.ntasks = np.asarray(book["ntasks"]).astype(np.int64)
    F.group_mask = _FitMask(greq, alloc_d, known, eps)
    F.cols = cols
    return P, F, parts


class _State:
    def __init__(self, P: Problem):
        self.idle = P.idle.copy()
        self.future = P.future.copy()
        self.ntasks = P.ntasks.astype(np.int64).copy()
        self.pack_nodes = np.zeros(P.N, P.dt)
        self.cur_bucket = -1
        self.q_alloc = P.q_alloc0.copy()
        self.ns_alloc = P.ns_alloc0.copy()
        self.p_cursor = np.zeros_like(P.pool_njobs)

    def checkpoint(self):
        """What a gang's rollback restores (the kernel's checkpoint)."""
        return (self.idle.copy(), self.future.copy(), self.ntasks.copy())

    def rollback(self, saved) -> None:
        self.idle, self.future, self.ntasks = (a.copy() for a in saved)

    def scratch(self):
        """Everything a hypothetical walk may change."""
        return self.checkpoint() + (self.pack_nodes.copy(), self.cur_bucket)

    def unscratch(self, saved) -> None:
        self.rollback(saved[:3])
        self.pack_nodes, self.cur_bucket = saved[3].copy(), saved[4]


def _queue_keys(P: Problem, st: _State):
    """(pool keys [P] with BIG where not eligible, whether anything is)."""
    dt = P.dt.type
    d, qa = P.q_deserved, st.q_alloc
    frac = np.where(np.isinf(d), dt(0.0),
                    np.where(d == 0, np.where(qa == 0, dt(0.0), dt(1.0)),
                             qa / np.where(d == 0, dt(1.0), d)))
    share = np.max(frac, axis=-1)
    le = (qa <= d + P.eps[None, :]) | np.isinf(d)
    over = ~np.all(le, axis=-1)
    pool_ok = (st.p_cursor < P.pool_njobs) & ~over[P.pool_queue]
    n_ns = P.ns_weight.shape[0]
    ns_has = np.zeros(n_ns, bool)
    np.logical_or.at(ns_has, P.pool_ns, pool_ok)
    if P.ns_live:
        tot = P.ns_total[None, :]
        nfrac = np.where(tot > 0, st.ns_alloc / np.where(tot > 0, tot,
                                                         dt(1.0)),
                         np.where(st.ns_alloc == 0, dt(0.0), dt(1.0)))
        ns_key = np.max(nfrac, axis=-1) / P.ns_weight
    else:
        ns_key = np.arange(n_ns, dtype=P.dt)
    big = dt(1e30)
    ns_sel = int(np.argmin(np.where(ns_has, ns_key, big)))
    if not ns_has[ns_sel]:
        return None, False
    key = np.where(pool_ok & (P.pool_ns == ns_sel), share[P.pool_queue], big)
    return key, True


class Checker:
    """Walks one kernel call's jobs; ``program`` = (assign, pipelined,
    ready, kept) to check it teacher forced, None to place by itself."""

    def __init__(self, P: Problem, program=None, full=None):
        self.P = P
        self.st = _State(P)
        self.full = full
        self.program = None
        if program is not None:
            a, pipe, ready, kept = (np.asarray(x) for x in program[:4])
            self.program = (a.astype(np.int64), pipe.astype(bool),
                            ready.astype(bool), kept.astype(bool))
        self.gap = 0.0
        self.invalid = 0
        self.lost = 0
        self.tasks = 0
        self.jobs = 0
        T, J = P.T, P.job_min.shape[0]
        self.assign = np.full(T, -1, np.int64)
        self.pipelined = np.zeros(T, bool)
        self.ready = np.zeros(J, bool)
        self.kept = np.zeros(J, bool)
        self.visited = np.zeros(J, bool)
        # tied jobs not taken at their tie -> the state before that pick
        self.before: dict = {}

    # -- one job ----------------------------------------------------------

    def _tasks(self, job):
        P = self.P
        s, n = int(P.job_start[job]), int(P.job_n[job])
        for off in range(n):
            t = min(max(s + off, 0), P.T - 1)
            if P.task_valid[t]:
                yield t

    def _walk(self, job, follow: bool, measure: bool):
        """Place ``job``'s tasks on the live state: on the program's nodes
        when ``follow``, else on the reference's best. Returns (placed,
        placed on idle, resources placed, gap, invalid, choices)."""
        P, st = self.P, self.st
        placed = placed_alloc = 0
        placed_res = np.zeros(P.R, P.dt)
        gap, invalid = 0.0, 0
        choices = []
        cache = None        # (group, fits idle, base, score) for the sweep
        zeros = np.zeros(P.N, P.dt)
        for t in self._tasks(job):
            g = int(P.task_group[t])
            req = P.group_req[g]
            b = int(P.task_bucket[t])
            pack = st.pack_nodes if (b >= 0 and b == st.cur_bucket) \
                else zeros
            if P.pack or cache is None or cache[0] != g:
                fi, base, sc = _scores(P, g, req, st.idle, st.ntasks, pack)
                cache = [g, fi, base, sc]
            _, fi, base, sc = cache
            any_idle = bool(fi.any())
            if any_idle or not P.allow_pipeline:
                cand = fi
            else:
                cand = np.all(req[None, :] <= st.future + P.eps[None, :],
                              axis=-1) & base
            pipelined = P.allow_pipeline and not any_idle and bool(cand.any())
            st.cur_bucket = b
            if follow:
                a, pipe = self.program[0], self.program[1]
                sel = int(a[t])
                if sel < 0:
                    if cand.any():
                        invalid += 1
                    st.pack_nodes = pack.copy() if P.pack else st.pack_nodes
                    choices.append((t, -1, False))
                    continue
                if not (0 <= sel < P.N) or not cand[sel]:
                    invalid += 1
                else:
                    if measure:
                        best = float(np.max(np.where(cand, sc, -np.inf)))
                        gap = max(gap, best - float(sc[sel]))
                    if bool(pipe[t]) != pipelined:
                        invalid += 1
                pipelined = bool(pipe[t])
                if not (0 <= sel < P.N):
                    choices.append((t, -1, False))
                    continue
            else:
                if not cand.any():
                    st.pack_nodes = pack.copy() if P.pack else st.pack_nodes
                    choices.append((t, -1, False))
                    continue
                sel = int(np.argmax(np.where(cand, sc, P.dt.type(-1e30))))
            take_idle = not pipelined
            if take_idle:
                st.idle[sel] -= req
            st.future[sel] -= req
            st.ntasks[sel] += 1
            if P.pack:
                st.pack_nodes = pack.copy()
                st.pack_nodes[sel] += 1
            placed += 1
            placed_alloc += int(take_idle)
            placed_res = placed_res + req
            choices.append((t, sel, pipelined))
            if not P.pack:
                # only the chosen node changed: refresh its row
                fi1, base1, sc1 = _row(P, g, req, sel, st.idle, st.ntasks)
                cache[1][sel], cache[2][sel], cache[3][sel] = fi1, base1, sc1
        return placed, placed_alloc, placed_res, gap, invalid, choices

    def _gap_of(self, job) -> float:
        """How far the program's answer for ``job`` lies from the
        reference's best on the current state (the state is unchanged)."""
        saved = self.st.scratch()
        _, _, _, gap, invalid, _ = self._walk(job, True, True)
        self.st.unscratch(saved)
        return math_inf if invalid else gap

    def _pick(self, key):
        """(pool to take next, jobs it tied with). The reference's own walk
        takes the least key, lowest index first. Teacher forced, pools whose
        keys lie within TIE of the least are a tie that rounding on the chip
        may break either way: the first of them, by key, whose next job the
        program's answer fits exactly is taken (else the best fitting)."""
        if self.program is None:
            return int(np.argmin(key)), []
        kmin = float(np.min(key))
        tied = np.flatnonzero(key <= kmin + TIE * max(1.0, abs(kmin)))
        if len(tied) == 1:
            return int(tied[0]), []
        tied = sorted(tied.tolist(), key=lambda q: (float(key[q]), q))
        jobs = [int(self.P.pool_start[q] + self.st.p_cursor[q]) for q in tied]
        best, pick = None, tied[0]
        for q, job in zip(tied, jobs):
            if not self._program_placed(job):
                g = 0.0 if not self._could_place(job) else math_inf
            else:
                g = self._gap_of(job)
            if g == 0.0:
                pick = q
                break
            if best is None or g < best:
                best, pick = g, q
        return pick, jobs

    def _program_placed(self, job) -> bool:
        _, _, ready, kept = self.program
        return bool(ready[job] or kept[job])

    def _could_place(self, job) -> bool:
        P = self.P
        saved = self.st.scratch()
        placed, placed_alloc, _, _, _, _ = self._walk(job, False, False)
        self.st.unscratch(saved)
        base, m = int(P.job_base[job]), int(P.job_min[job])
        return base + placed_alloc >= m or base + placed >= m

    def _could_place_anywhere(self, job) -> bool:
        """``_could_place`` over every node of the cluster: the state of
        the nodes outside the call's columns is the snapshot's (the call
        places nothing there), inside them the walk's own."""
        F = self.full
        if F is None:
            return self._could_place(job)
        st = _State(F)
        live = F.cols >= 0
        g = F.cols[live]
        st.idle[g] = self.st.idle[live]
        st.future[g] = self.st.future[live]
        st.ntasks[g] = self.st.ntasks[live]
        P, own = self.P, self.st
        self.P, self.st = F, st
        try:
            placed, placed_alloc, _, _, _, _ = self._walk(job, False, False)
        finally:
            self.P, self.st = P, own
        base, m = int(F.job_base[job]), int(F.job_min[job])
        return base + placed_alloc >= m or base + placed >= m

    # -- all jobs ---------------------------------------------------------

    def run(self) -> "Checker":
        P, st = self.P, self.st
        while True:
            key, ok = _queue_keys(P, st)
            if not ok:
                break
            p, tied = self._pick(key)
            job = int(P.pool_start[p] + st.p_cursor[p])
            if tied:
                # the state before this pick: a tied job that the chip took
                # first was placed on it (the earliest such state is kept)
                snap = st.scratch()
                for j in tied:
                    if j != job:
                        self.before.setdefault(j, snap)
            self.visited[job] = True
            self.jobs += 1
            base, m = int(P.job_base[job]), int(P.job_min[job])
            if self.program is not None and not self._program_placed(job):
                if self._could_place_anywhere(job):
                    self.lost += 1
                keep = False
                placed_res = None
            else:
                saved = st.checkpoint()
                follow = self.program is not None
                if follow and job in self.before:
                    # the order against a tied job is open: the gap is the
                    # smaller of this state's and the state before the tie
                    alt = self.before.pop(job)
                    now = st.scratch()
                    st.unscratch(alt)
                    _, _, _, g_alt, _, _ = self._walk(job, True, True)
                    st.unscratch(now)
                else:
                    g_alt = math_inf
                placed, placed_alloc, placed_res, gap, invalid, choices = \
                    self._walk(job, follow, follow)
                gap = min(gap, g_alt)
                self.gap = max(self.gap, gap)
                self.invalid += invalid
                self.tasks += len(choices)
                is_ready = base + placed_alloc >= m
                is_kept = base + placed >= m
                keep = is_ready or is_kept
                if follow and not keep:
                    self.invalid += 1     # the program kept a broken gang
                    keep = True           # teacher forced: as it did
                if keep:
                    self.ready[job] |= is_ready
                    self.kept[job] |= is_kept
                    for t, sel, pipe in choices:
                        self.assign[t] = sel
                        self.pipelined[t] = pipe and sel >= 0
                else:
                    st.rollback(saved)
            if keep:
                q = int(P.pool_queue[p])
                ns = int(P.pool_ns[p])
                st.q_alloc[q] = st.q_alloc[q] + placed_res
                st.ns_alloc[ns] = st.ns_alloc[ns] + placed_res
            st.p_cursor[p] += 1
        if self.program is not None:
            _, _, ready, kept = self.program
            self.invalid += int(((ready | kept) & ~self.visited).sum())
        return self


math_inf = float("inf")


def check(call: dict, dtype=np.float32) -> dict:
    """The teacher-forced reading of one captured kernel call: on the
    tables derived from the call's ``book`` where it has one, else on the
    call's own."""
    book = call.get("book")
    if book is None:
        P, full, parts = Problem(call["args"], call["kwargs"], dtype), None, {}
    else:
        P, full, parts = derive(call, book, dtype)
    c = Checker(P, call["out"], full).run()
    return {"gap": c.gap, "invalid": c.invalid, "lost": c.lost,
            "mismatch": sum(parts.values()), "mismatch_parts": parts,
            "tasks": c.tasks, "jobs": c.jobs}


def place(call: dict, dtype=np.float32) -> tuple:
    """The reference's own answer for one captured call's inputs:
    (assign, pipelined, ready, kept)."""
    P = Problem(call["args"], call["kwargs"], dtype)
    c = Checker(P, None).run()
    return c.assign, c.pipelined, c.ready, c.kept
