"""Candidate pruning + two-level hierarchical placement (the kernel
scale wall, docs/design/pruning.md).

At 500k x 50k the sharded kernel once took 624.7 s of a 637.5 s CPU
cycle, and the cost is the dense [G, N] tasks x nodes product itself —
every scan step sweeps the whole node axis. This module shrinks the
problem BEFORE the kernel runs, following the packing-and-placement
structure of arxiv 2004.00518 and Tesserae's scalable-policy framing
(arxiv 2508.04953):

* **Shortlist distillation** — per gang (per (gang, topology-domain)
  pair when the constraint compiler's slot tensors are live), the top-k
  candidate nodes by the session-open masked score, via the SAME fused
  ``jax.lax.top_k`` pass the placement explainer already runs
  (``trace/explain.py:_topk_fn``) — mask -> shortlist is a reduction
  over the compiled [G, N] mask/score tensors PR 10 builds, never a new
  predicate pass. The pass runs in fixed-size pair blocks so the 10x
  shape never materializes a [G, N] float score at once.

* **Two-level placement (sharded path)** — when the device mesh is
  live, the ShardPlan's contiguous node ranges are the partition
  structure: level 1 scores each partition's best masked score per pair
  (one scatter-max) and keeps the top ``prune.partitions`` winners;
  level 2 distills the shortlist from the winning partitions only — the
  main kernel then runs only inside winning partitions.

* **Reduced kernel batch** — the union of every pair's shortlist,
  sorted ascending (so the kernels' lowest-global-index tie-break maps
  1:1), padded to a bucket, becomes the node axis the UNMODIFIED
  dense/chunked/scan/sharded kernels run over ([G, M] instead of
  [G, N]); ``framework/solver.py`` gathers the mask/score/node tensors
  down and maps placements back through the union.

* **Shortlist-loss guard** — pruning must never lose a placement the
  dense kernel would have made: a pair whose score-mass coverage at k
  falls under ``prune.coverage_floor`` falls the whole place() back to
  full width BEFORE the kernel (reason ``low_coverage``); after the
  reduced run, any unplaced task whose pair's shortlist was TRUNCATED
  (feasible > kept candidates — the "shortlist emptied while the dense
  mask had survivors" signature) falls the place() back to the
  full-width kernel for the cycle (reason ``shortlist_exhausted``).
  Every fallback bumps ``volcano_prune_fallback_total{reason}``.

Exactness: when every pair's shortlist is COMPLETE (k >= its feasible
node count and no partition was masked away), the reduced problem is
the dense problem restricted to columns no gang can use — placements,
tie-breaks included, are bit-identical (tests/test_prune.py pins it).
With truncated shortlists the kernel's in-scan score dynamics can
re-rank beyond the shortlist; the divergence is bounded by the guard
(placements are never lost, only node choices may differ) and PR 14's
per-gang provenance records are the debugging tool — see
docs/design/pruning.md for the full parity contract.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

NEG = -1e30

# pair-block height for the distillation pass: bounds the transient
# [B, N] score materialization (~200 MB at B=1024 x N=51.2k f32) while
# keeping the jit shape stable across blocks and cycles
PAIR_BLOCK = 1024

_twolevel_cache: Dict[tuple, object] = {}
_score_rows_cache: Dict[tuple, object] = {}

# demand-aware shortlist sizing: capacity headroom over the estimated
# nodes the pair's tasks will drain (the post-kernel guard catches an
# estimate that still came up short)
DEMAND_HEADROOM = 1.5

# every way a place() can fall back to the full-width kernel (the
# volcano_prune_fallback_total{reason} label set — bench, the smoke
# gate and the tests all read this one tuple)
FALLBACK_REASONS = ("low_coverage", "shortlist_exhausted", "wide_union",
                    "empty_union", "crash")


@dataclass
class PruneConf:
    """The ``solver`` conf's ``prune.*`` arguments.

    ``prune.enable`` "auto" (default) engages above ``prune.min_nodes``
    ready nodes, except where the batch's tier is the compiled
    single-chip Pallas kernel (the solver sends those to full width:
    each reduced width would be a program of its own); "true" forces it
    at any scale and on every tier; "false"/"off" restores the exact
    unpruned path (distillation never runs).
    ``prune.demand_aware`` (default on) widens a shortlist past
    ``prune.k`` when the tasks that will drain it need more capacity
    than k nodes can hold — a 500k-task uniform batch drains far more
    than 64 nodes, and a static top-k would exhaust (and guard-fall
    back) every cycle. ``prune.guard`` exists for tests proving the
    loss guard red/green — production keeps it on."""
    mode: str = "auto"
    k: int = 64
    coverage_floor: float = 0.9
    min_nodes: int = 4096
    max_union_frac: float = 0.6
    partitions: int = 2
    guard: bool = True
    demand_aware: bool = True

    @classmethod
    def from_args(cls, solver_args) -> "PruneConf":
        conf = cls()
        if solver_args is None:
            return conf
        if hasattr(solver_args, "get_str"):
            conf.mode = (solver_args.get_str("prune.enable", "auto")
                         or "auto").strip().lower()
            conf.guard = (solver_args.get_str("prune.guard", "on")
                          or "on").strip().lower() not in (
                "off", "false", "0", "no")
            conf.demand_aware = (solver_args.get_str(
                "prune.demand_aware", "on") or "on").strip().lower() \
                not in ("off", "false", "0", "no")
        if hasattr(solver_args, "get_int"):
            conf.k = max(1, solver_args.get_int("prune.k", cls.k))
            conf.min_nodes = solver_args.get_int(
                "prune.min_nodes", cls.min_nodes)
            conf.partitions = max(1, solver_args.get_int(
                "prune.partitions", cls.partitions))
        if hasattr(solver_args, "get_float"):
            conf.coverage_floor = solver_args.get_float(
                "prune.coverage_floor", cls.coverage_floor)
            conf.max_union_frac = solver_args.get_float(
                "prune.max_union_frac", cls.max_union_frac)
        return conf

    @property
    def off(self) -> bool:
        return self.mode in ("off", "false", "0", "no")

    @property
    def forced(self) -> bool:
        return self.mode in ("true", "1", "yes", "on")

    def active(self, n_nodes: int) -> bool:
        """Does pruning engage for a place() over ``n_nodes`` ready
        nodes? Force ("true") still needs a node to prune toward."""
        if self.off or n_nodes <= 0:
            return False
        return self.forced or n_nodes >= self.min_nodes


class PruneContext:
    """One place() call's distilled shortlists + union reduction."""

    __slots__ = ("conf", "level", "k", "k_max", "n_real", "n_pad",
                 "pair_g", "pair_s", "pair_of_task",
                 "feasible", "count", "coverage",
                 "union", "m_real", "u_pad", "union_padded", "live",
                 "fallback", "fallback_pairs")

    def __init__(self, conf, level, k, n_real, n_pad, pair_g, pair_s,
                 pair_of_task, feasible, count, coverage):
        self.conf = conf
        self.level = level          # "single" | "two_level"
        self.k = k
        self.k_max = k              # widest demand-sized shortlist
        self.fallback_pairs = 0     # pairs behind a pre-guard fallback
        self.n_real = n_real
        self.n_pad = n_pad
        self.pair_g = pair_g
        self.pair_s = pair_s        # None when no slot tensors are live
        self.pair_of_task = pair_of_task   # [T_real] -> pair index (-1)
        self.feasible = feasible    # [P] full-mask feasible node count
        self.count = count          # [P] live shortlist entries kept
        self.coverage = coverage    # [P] score-mass coverage at k
        self.union = None
        self.m_real = 0
        self.u_pad = 0
        self.union_padded = None
        self.live = None
        self.fallback = None

    # -- union reduction ---------------------------------------------------

    def set_union(self, union: np.ndarray, bucket_size: int = 256) -> None:
        from ..models.arrays import bucket
        self.union = union
        self.m_real = int(union.shape[0])
        self.u_pad = bucket(max(self.m_real, 1), bucket_size)
        padded = np.zeros(self.u_pad, np.int64)
        padded[:self.m_real] = union
        self.union_padded = padded
        live = np.zeros(self.u_pad, bool)
        live[:self.m_real] = True
        self.live = live

    @property
    def truncated(self) -> np.ndarray:
        """[P] bool: the pair's shortlist kept fewer candidates than its
        dense mask had survivors (k truncation or a masked-out
        partition) — the pairs the post-kernel guard watches."""
        return self.feasible > self.count

    # -- guards --------------------------------------------------------------

    def pre_guard(self) -> Optional[tuple]:
        """(reason, count) when the place() must fall back BEFORE the
        kernel, else None."""
        if self.m_real == 0:
            # nothing feasible anywhere: the dense kernel decides (it
            # will place nothing too, but fit errors must come from the
            # exact reference path)
            return ("empty_union", 1)
        if self.conf.mode == "auto" and self.m_real >= max(
                1.0, self.conf.max_union_frac * self.n_real):
            # the union approaches full width: the gather tax buys
            # nothing (heterogeneous shortlists covering the fleet).
            # An economy guard, not a loss guard — forced mode
            # (`prune.enable: "true"`, tests/smokes) skips it.
            return ("wide_union", 1)
        low = int((self.coverage < self.conf.coverage_floor).sum())
        if low and self.conf.guard:
            return ("low_coverage", low)
        return None

    def post_guard(self, assign_full: np.ndarray, batch) -> bool:
        """True when the reduced run must be discarded: ANY valid task
        with a statically feasible pair went unplaced while ANY pair's
        shortlist was truncated. The trigger is deliberately
        batch-wide, not per-pair: a truncated gang's different node
        choices shift the state every later gang sees, so even a
        COMPLETE-shortlist gang's lost placement can be downstream of
        someone else's truncation — the dense rerun is the only sound
        answer. Tasks whose own pair has zero feasible nodes never
        trigger (the dense kernel cannot place them either), and a
        batch with no truncation anywhere cannot trigger (the reduced
        problem saw every node any gang could use)."""
        if not self.conf.guard:
            return False
        if not self.truncated.any():
            return False
        n = self.pair_of_task.shape[0]
        a = np.asarray(assign_full[:n])
        valid = np.asarray(batch.task_valid[:n], bool)
        pt = self.pair_of_task
        unplaced = (a < 0) & valid & (pt >= 0)
        if not unplaced.any():
            return False
        return bool((self.feasible[pt[unplaced]] > 0).any())

    # -- mapping --------------------------------------------------------------

    def map_assign(self, assign) -> np.ndarray:
        """Reduced node indices -> global node indices (padding columns
        are infeasible by construction, so only live entries appear)."""
        a = np.asarray(assign)
        lut = np.full(self.u_pad, -1, np.int64)
        lut[:self.m_real] = self.union
        return np.where(a >= 0, lut[np.clip(a, 0, self.u_pad - 1)],
                        -1).astype(np.int32)

    # -- reporting ------------------------------------------------------------

    def summary(self) -> dict:
        cov = self.coverage
        return {
            "level": self.level,
            "k": int(self.k),
            "k_max": int(self.k_max),
            "pairs": int(self.pair_g.shape[0]),
            "union": int(self.m_real),
            "nodes": int(self.n_real),
            "truncated_pairs": int(self.truncated.sum()),
            "coverage_min": round(float(cov.min()), 6) if cov.size else 1.0,
            "coverage_mean": round(float(cov.mean()), 6)
            if cov.size else 1.0,
            "fallback": self.fallback,
            "fallback_pairs": int(self.fallback_pairs),
        }


def _partition_ids(plan, n_pad: int) -> np.ndarray:
    """Partition id per node column from the ShardPlan's contiguous
    bounds (columns past the plan's rows keep the last partition)."""
    bounds = np.asarray(plan.bounds, np.int64)
    pid = np.searchsorted(bounds, np.arange(n_pad), side="right") - 1
    return np.clip(pid, 0, max(plan.n_devices - 1, 0)).astype(np.int32)


def _twolevel_restrict_fn(n_sel: int, n_part: int):
    """Jitted level-1 pass: per-pair partition scatter-max over the
    masked session-open score, keep the top ``n_sel`` of the ``n_part``
    partitions, and return the mask restricted to the winning
    partitions plus the FULL-mask stats (feasible count, min score,
    shifted total) the coverage guard is measured against."""
    key = (int(n_sel), int(n_part))
    fn = _twolevel_cache.get(key)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    from .score import node_score

    sel = max(1, min(int(n_sel), int(n_part)))

    @jax.jit
    def restrict(group_req, idle, alloc, static, mask, weights, pid):
        score = jax.vmap(
            lambda req, srow: node_score(req, idle, alloc, weights, srow)
        )(group_req, static)
        neg = jnp.float32(NEG)
        masked = jnp.where(mask, score, neg)
        feasible = mask.sum(axis=1)
        minf = jnp.min(jnp.where(mask, score, jnp.float32(1e30)), axis=1)
        total = jnp.where(mask, score - minf[:, None], 0.0).sum(axis=1)
        b = masked.shape[0]
        pm = jnp.full((b, n_part), neg, masked.dtype)
        pm = pm.at[:, pid].max(masked)
        vals, idxs = jax.lax.top_k(pm, sel)
        win = jnp.zeros((b, n_part), bool)
        win = win.at[jnp.arange(b)[:, None], idxs].set(vals > neg * 0.5)
        restricted = mask & win[:, pid]
        return restricted, feasible, minf, total

    _twolevel_cache[key] = restrict
    return restrict


def _score_rows_fn():
    """Jitted masked-score rows (no top-k): the host-side wide-shortlist
    extension selects from these with argpartition — device ``top_k``
    is O(N x k) on CPU and a demand-sized k can reach thousands."""
    key = ("score_rows",)
    fn = _score_rows_cache.get(key)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    from .score import node_score

    @jax.jit
    def rows(group_req, idle, alloc, static, mask, weights):
        score = jax.vmap(
            lambda req, srow: node_score(req, idle, alloc, weights, srow)
        )(group_req, static)
        return jnp.where(mask, score, jnp.float32(NEG))

    _score_rows_cache[key] = rows
    return rows


def _demand_k(conf, batch, narr, rep_g, rep_of_pair, pair_of_task,
              n_pairs: int, k: int, n_pad: int) -> np.ndarray:
    """Per-representative shortlist width: at least ``k``, widened so
    the shortlist's ESTIMATED capacity covers the tasks that will drain
    it. A 500k-task uniform batch collapses onto one shortlist — a
    static top-64 holds ~2k task slots and would exhaust (and
    guard-fall back) every cycle. The estimate is the fleet-median
    per-node headroom for the rep's request row; the post-kernel guard
    remains the safety net for fleets the median misrepresents."""
    n_reps = int(rep_g.shape[0])
    k_eff = np.full(n_reps, k, np.int64)
    if not conf.demand_aware:
        return k_eff
    valid_pairs = pair_of_task[pair_of_task >= 0]
    demand_pair = np.bincount(valid_pairs, minlength=n_pairs)
    demand_rep = np.bincount(rep_of_pair, weights=demand_pair,
                             minlength=n_reps)
    n_real = len(narr.names)
    if n_real == 0:
        return k_eff
    med_idle = np.median(np.asarray(narr.idle[:n_real], np.float64),
                         axis=0)
    # vectorized over reps: without the dedupe license this runs per
    # gang (~60k at the 10x shape) inside the kernel-latency window
    req = np.asarray(batch.group_req, np.float64)[rep_g]
    pos = req > 1e-9
    ratios = np.where(pos, med_idle[None, :] / np.where(pos, req, 1.0),
                      np.inf)
    per_node = np.maximum(np.floor(ratios.min(axis=1)), 1.0)
    need = np.ceil(demand_rep * DEMAND_HEADROOM / per_node)
    has_pos = pos.any(axis=1)   # zero-demand requests keep k candidates
    k_eff[has_pos] = np.minimum(
        n_pad, np.maximum(k, need[has_pos])).astype(np.int64)
    return k_eff


def _extend_wide_reps(batch, narr, gmask, static_score, weights, plan,
                      conf, rep_g, rep_s, k_eff, k, two_level,
                      rep_feasible, rep_count, rep_coverage,
                      union_parts, pods_ok) -> None:
    """Host-side selection for the reps whose demand-sized width
    exceeds the fused pass's k: pull their masked score rows and
    argpartition (O(N) selection — shortlist MEMBERSHIP on score ties
    is deterministic but unspecified, which only matters for truncated
    shortlists, i.e. inside the documented-divergence regime). The
    two-level restriction is applied host-side over the ShardPlan's
    contiguous bounds. Overwrites the fused stats for those reps."""
    import jax.numpy as jnp

    wide = np.flatnonzero(k_eff > k)
    if wide.size == 0:
        return
    rows_fn = _score_rows_fn()
    gmask_d = jnp.asarray(gmask)
    static_d = jnp.asarray(static_score)
    idle_d = jnp.asarray(narr.idle)
    alloc_d = jnp.asarray(narr.allocatable)
    group_req_d = jnp.asarray(batch.group_req)
    slot_rows_d = jnp.asarray(batch.slot_rows) \
        if rep_s is not None else None
    pods_ok_d = jnp.asarray(pods_ok)
    n_pad = int(narr.idle.shape[0])
    bounds = np.asarray(plan.bounds, np.int64) if two_level else None
    block = 128
    for lo in range(0, wide.size, block):
        sel = wide[lo:lo + block]
        b = sel.shape[0]
        pg = np.zeros(block, np.int32)
        pg[:b] = rep_g[sel]
        pg_d = jnp.asarray(pg)
        mask_rows = jnp.take(gmask_d, pg_d, axis=0) & pods_ok_d[None, :]
        if rep_s is not None:
            ps = np.full(block, batch.slot_rows.shape[0] - 1, np.int32)
            ps[:b] = rep_s[sel]
            mask_rows = mask_rows & jnp.take(slot_rows_d,
                                             jnp.asarray(ps), axis=0)
        masked = np.asarray(rows_fn(
            jnp.take(group_req_d, pg_d, axis=0), idle_d, alloc_d,
            jnp.take(static_d, pg_d, axis=0), mask_rows, weights))[:b]
        for j in range(b):
            r = int(sel[j])
            row = masked[j]
            live_full = row > NEG * 0.5
            feas = int(live_full.sum())
            rep_feasible[r] = feas
            if feas == 0:
                rep_count[r] = 0
                rep_coverage[r] = 1.0
                continue
            minf = row[live_full].min()
            shifted_total = float((row[live_full] - minf).sum())
            pool = row
            if two_level:
                # level 1 host-side: partitions are contiguous node
                # ranges, so a reduceat over the bounds is the
                # scatter-max
                widths = bounds[1:] - bounds[:-1]
                pm = np.full(len(widths), NEG)
                nz = widths > 0
                pm[nz] = np.maximum.reduceat(
                    row[:bounds[-1]], bounds[:-1][nz])
                n_sel = max(1, min(conf.partitions, len(widths)))
                # stable sort on -pm: ties pick the LOWEST partition
                # index, matching lax.top_k's tie order in the fused
                # two-level pass
                win = np.argsort(-pm, kind="stable")[:n_sel]
                keep = np.zeros(n_pad, bool)
                for d in win:
                    if pm[d] > NEG * 0.5:
                        keep[bounds[d]:bounds[d + 1]] = True
                pool = np.where(keep, row, NEG)
            ke = int(min(k_eff[r], n_pad))
            if ke >= n_pad:
                cand = np.arange(n_pad)
            else:
                cand = np.argpartition(pool, n_pad - ke)[n_pad - ke:]
            live = pool[cand] > NEG * 0.5
            cand = cand[live]
            rep_count[r] = int(cand.shape[0])
            if shifted_total > 0.0:
                rep_coverage[r] = float(
                    np.maximum(pool[cand] - minf, 0.0).sum()
                    / shifted_total)
            else:
                rep_coverage[r] = 1.0
            if cand.size:
                union_parts.append(np.unique(cand.astype(np.int64)))


def _build_pairs(batch):
    """The (group, slot) pairs the shortlists are distilled per: one
    per real group without slot tensors; one per distinct (group,
    domain-row) among valid tasks when the constraint compiler's
    per-task domains are live (a domain-rotating spread gang needs
    candidates in EVERY domain its tasks may use, not just its first
    task's)."""
    n_tasks = len(batch.tasks)
    tg = np.asarray(batch.task_group[:n_tasks], np.int64)
    valid = np.asarray(batch.task_valid[:n_tasks], bool)
    if batch.task_slot is None or batch.slot_rows is None:
        n_groups = int(batch.n_groups)
        pair_g = np.arange(n_groups, dtype=np.int32)
        pair_s = None
        pair_of_task = np.where(
            valid & (tg < n_groups), tg, -1).astype(np.int32)
        return pair_g, pair_s, pair_of_task
    ts = np.asarray(batch.task_slot[:n_tasks], np.int64)
    keys = np.stack([tg[valid], ts[valid]], axis=1)
    uniq, inv = np.unique(keys, axis=0, return_inverse=True)
    pair_of_task = np.full(n_tasks, -1, np.int32)
    pair_of_task[valid] = inv.astype(np.int32)
    return uniq[:, 0].astype(np.int32), uniq[:, 1].astype(np.int32), \
        pair_of_task


def _dedupe_reps(batch, pair_g, pair_s):
    """Exact pair dedupe under the solver's license (identical request
    rows imply identical mask/score rows — framework/solver.py sets
    ``_prune_dedupe_ok`` only when no mask or score contribution beyond
    the capability fit ran): representatives keyed on (req-row bytes,
    slot). Returns (rep_g, rep_s, rep_of_pair)."""
    keys: Dict[tuple, int] = {}
    rep_of_pair = np.zeros(pair_g.shape[0], np.int64)
    rep_rows: List[int] = []
    req = np.asarray(batch.group_req)
    for p in range(pair_g.shape[0]):
        s = int(pair_s[p]) if pair_s is not None else -1
        key = (req[pair_g[p]].tobytes(), s)
        r = keys.get(key)
        if r is None:
            r = len(rep_rows)
            keys[key] = r
            rep_rows.append(p)
        rep_of_pair[p] = r
    rep_idx = np.asarray(rep_rows, np.int64)
    rep_g = pair_g[rep_idx]
    rep_s = pair_s[rep_idx] if pair_s is not None else None
    return rep_g, rep_s, rep_of_pair


def distill(batch, narr, gmask, static_score, weights,
            conf: PruneConf, plan=None, dedupe: bool = False
            ) -> PruneContext:
    """Distill per-pair top-k shortlists from the compiled [G, N]
    mask/score tensors and reduce them to the union candidate set.

    ``plan`` (the sharded path's persistent ShardPlan) switches on
    two-level mode: shortlists come from each pair's winning partitions
    only. ``dedupe`` (granted by the solver ONLY when mask/score rows
    are a pure function of the request row) collapses identical pairs
    onto one representative — the uniform 50k x 10k bench batch is a
    single fused row instead of 6k. Returns a :class:`PruneContext`;
    the caller applies the pre/post guards and the union gather."""
    import jax.numpy as jnp

    from ..models.arrays import bucket
    from ..trace.explain import _topk_fn

    n_real = len(narr.names)
    n_pad = int(narr.idle.shape[0])
    k = min(int(conf.k), n_pad)
    pair_g, pair_s, pair_of_task = _build_pairs(batch)
    n_pairs = int(pair_g.shape[0])
    if n_pairs == 0:
        ctx = PruneContext(conf, "single", k, n_real, n_pad, pair_g,
                           pair_s, pair_of_task,
                           np.zeros(0, np.int64), np.zeros(0, np.int64),
                           np.zeros(0, np.float32))
        ctx.set_union(np.zeros(0, np.int64))
        return ctx

    if dedupe:
        rep_g, rep_s, rep_of_pair = _dedupe_reps(batch, pair_g, pair_s)
    else:
        rep_g, rep_s = pair_g, pair_s
        rep_of_pair = np.arange(n_pairs, dtype=np.int64)
    n_reps = int(rep_g.shape[0])
    k_eff = _demand_k(conf, batch, narr, rep_g, rep_of_pair,
                      pair_of_task, n_pairs, k, n_pad)

    two_level = plan is not None and plan.n_devices > 1
    level = "two_level" if two_level else "single"
    pods_ok = (narr.max_tasks == 0) | (narr.n_tasks < narr.max_tasks)
    pods_ok_d = jnp.asarray(pods_ok)
    idle_d = jnp.asarray(narr.idle)
    alloc_d = jnp.asarray(narr.allocatable)
    gmask_d = jnp.asarray(gmask)
    static_d = jnp.asarray(static_score)
    group_req_d = jnp.asarray(batch.group_req)
    slot_rows_d = jnp.asarray(batch.slot_rows) \
        if rep_s is not None else None
    pid_d = jnp.asarray(_partition_ids(plan, n_pad)) if two_level else None
    fused = _topk_fn(k, (k,))
    restrict = _twolevel_restrict_fn(conf.partitions, plan.n_devices) \
        if two_level else None

    rep_feasible = np.zeros(n_reps, np.int64)
    rep_count = np.zeros(n_reps, np.int64)
    rep_coverage = np.ones(n_reps, np.float32)
    union_parts: List[np.ndarray] = []

    # block height bounds the transient [B, N] score materialization;
    # small rep sets (the deduped uniform batch) use a small bucketed
    # shape instead of paying the full block
    block = min(PAIR_BLOCK, bucket(n_reps, 128))
    for lo in range(0, n_reps, block):
        hi = min(lo + block, n_reps)
        b = hi - lo
        # fixed block height for stable jit shapes: pad the tail with
        # rep 0 and discard its rows after the device pull
        pg = np.zeros(block, np.int32)
        pg[:b] = rep_g[lo:hi]
        pg_d = jnp.asarray(pg)
        mask_rows = jnp.take(gmask_d, pg_d, axis=0) & pods_ok_d[None, :]
        if rep_s is not None:
            ps = np.full(block, batch.slot_rows.shape[0] - 1, np.int32)
            ps[:b] = rep_s[lo:hi]
            mask_rows = mask_rows & jnp.take(slot_rows_d,
                                             jnp.asarray(ps), axis=0)
        req_rows = jnp.take(group_req_d, pg_d, axis=0)
        static_rows = jnp.take(static_d, pg_d, axis=0)
        if two_level:
            restricted, feas_d, minf_d, total_d = restrict(
                req_rows, idle_d, alloc_d, static_rows, mask_rows,
                weights, pid_d)
            _, vals_d, idx_d, _ = fused(
                req_rows, idle_d, alloc_d, static_rows, restricted,
                weights)
            vals = np.asarray(vals_d[:b])
            idx = np.asarray(idx_d[:b])
            live = vals > NEG * 0.5
            minf = np.asarray(minf_d[:b])
            total = np.asarray(total_d[:b])
            shifted = np.where(live, np.maximum(vals - minf[:, None], 0.0),
                               0.0)
            cov = np.where(total > 0.0, shifted.sum(axis=1)
                           / np.where(total > 0.0, total, 1.0), 1.0)
            rep_feasible[lo:hi] = np.asarray(feas_d[:b])
        else:
            feas_d, vals_d, idx_d, cov_d = fused(
                req_rows, idle_d, alloc_d, static_rows, mask_rows,
                weights)
            vals = np.asarray(vals_d[:b])
            idx = np.asarray(idx_d[:b])
            live = vals > NEG * 0.5
            cov = np.asarray(cov_d[:b, 0])
            rep_feasible[lo:hi] = np.asarray(feas_d[:b])
        rep_count[lo:hi] = live.sum(axis=1)
        rep_coverage[lo:hi] = cov
        if live.any():
            union_parts.append(np.unique(idx[live]))

    # demand-sized widths past k: host-side argpartition extension
    # (overwrites those reps' stats and contributes their candidates)
    _extend_wide_reps(batch, narr, gmask, static_score, weights, plan,
                      conf, rep_g, rep_s, k_eff, k, two_level,
                      rep_feasible, rep_count, rep_coverage,
                      union_parts, pods_ok)

    ctx = PruneContext(conf, level, k, n_real, n_pad, pair_g, pair_s,
                       pair_of_task, rep_feasible[rep_of_pair],
                       rep_count[rep_of_pair], rep_coverage[rep_of_pair])
    ctx.k_max = int(k_eff.max()) if k_eff.size else k
    union = np.unique(np.concatenate(union_parts)) if union_parts \
        else np.zeros(0, np.int64)
    # candidates land on real rows only (padding columns are masked
    # False before the top-k), but clip defensively
    union = union[(union >= 0) & (union < n_pad)]
    ctx.set_union(union)
    return ctx
