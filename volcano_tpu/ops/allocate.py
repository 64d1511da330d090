"""The gang-allocate kernel: one compiled scan runs the entire allocate loop
— dynamic queue selection, fair-share budget gating, task placement, and
per-job gang commit/rollback.

TPU-native replacement for the allocate action's hot loop
(pkg/scheduler/actions/allocate/allocate.go:123-270): the reference picks,
for every job, the currently least-loaded non-overused queue
(QueueOrderFn/Overused re-evaluated after each job because plugin event
handlers update shares live), pops that queue's next job, then places its
tasks one by one — predicates, scoring, best-node argmax — and finally
commits or discards the whole gang via the Statement
(framework/statement.go:350-393).

All of that happens inside one ``lax.scan``:

* the carry holds the node state (idle/future/task counts), the per-queue
  allocation matrix, per-queue job cursors and the current job's progress;
* each step places one task of the current job (argmax over all nodes of the
  masked score, exactly the sequential semantics — every placement changes
  ``idle`` for the next);
* when the current job's span ends, the gang check either keeps the
  placements or restores the checkpoint (Statement.Commit/Discard), charges
  the queue's (and namespace's) allocation, and the next job is selected by
  the reference's two-level rule — the in-kernel equivalent of its
  namespace and queue priority queues;
* queues whose allocation exceeds their deserved budget (the proportion
  plugin's Overused gate) stop being selected, at job granularity, exactly
  like allocate.go:141-146.

Namespace fairness (allocate.go:120-162's outer namespace priority queue)
is first-class in the kernel: jobs are encoded in (namespace, queue)
POOLS, and at every job boundary the next namespace is re-selected — by
live weighted dominant share (``ns_live=True``, drf's NamespaceOrderFn
over in-scan allocations) or by the encode's static namespace order (the
host's session-open NamespaceOrderFn sort, matching the reference's
priority queue when no live order fn is registered) — then the best
non-overused queue within it by live share (QueueOrderFn), then that
pool's next job. A single-namespace batch degenerates to pools == queues
and reproduces the previous queue-only selection exactly, ties included.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .fdiv import div_rn
from .score import ScoreWeights, node_score

NEG = -1e30   # plain floats: no backend init at import
BIG = 1e30


class AllocState(NamedTuple):
    idle: jax.Array          # [N, R]
    future: jax.Array        # [N, R] = idle + releasing - pipelined
    n_tasks: jax.Array       # [N] i32
    ckpt_idle: jax.Array     # checkpoint for gang rollback
    ckpt_future: jax.Array
    ckpt_ntasks: jax.Array
    cur_bucket: jax.Array    # i32 task-topology bucket of the running chain
    pack_nodes: jax.Array    # [N] f32 current-bucket placements per node
    q_alloc: jax.Array       # [Q, R] live queue allocations
    ns_alloc: jax.Array      # [NS, R] live namespace allocations
    p_cursor: jax.Array      # [P] i32 next-job offset per (ns, queue) pool
    cur_pool: jax.Array      # i32 selected pool (-1 when done)
    cur_job: jax.Array       # i32 selected job (-1 when done)
    t_off: jax.Array         # i32 offset inside the current job's span
    placed: jax.Array        # i32 tasks placed for cur_job (any kind)
    placed_alloc: jax.Array  # i32 of those, on real idle
    placed_res: jax.Array    # [R] resources placed for cur_job
    ready: jax.Array         # [J] bool JobReady   -> commit (bind)
    kept: jax.Array          # [J] bool JobPipelined -> keep session claims


def queue_share(q_alloc: jax.Array, q_deserved: jax.Array) -> jax.Array:
    """Dominant share per queue: max_r alloc/deserved with 0/0=0, x/0=1;
    unbudgeted (+inf deserved) dims contribute 0 (proportion.go:196-209).
    Divided as IEEE divides (``fdiv.div_rn``), so that shares IEEE makes
    equal tie on the chip too."""
    finite = ~jnp.isinf(q_deserved) & (q_deserved != 0.0)
    frac = jnp.where(
        jnp.isinf(q_deserved), 0.0,
        jnp.where(q_deserved == 0.0,
                  jnp.where(q_alloc == 0.0, 0.0, 1.0),
                  div_rn(q_alloc, jnp.where(finite, q_deserved, 1.0))))
    return jnp.max(frac, axis=-1)


def queue_overused(q_alloc: jax.Array, q_deserved: jax.Array,
                   eps: jax.Array) -> jax.Array:
    """allocated > deserved in any dimension (proportion.go:238-250)."""
    le = (q_alloc <= q_deserved + eps[None, :]) | jnp.isinf(q_deserved)
    return ~jnp.all(le, axis=-1)


def namespace_share(ns_alloc: jax.Array, ns_total: jax.Array,
                    ns_weight: jax.Array) -> jax.Array:
    """Weighted dominant share per namespace: max_r alloc/total with
    0/0=0, x/0=1, divided by the namespace weight (drf.py _share_of +
    namespace_order_fn; reference drf.go:621-646 + namespace ordering),
    divided as IEEE divides (``fdiv.div_rn``)."""
    frac = jnp.where(ns_total[None, :] > 0.0,
                     div_rn(ns_alloc, jnp.where(ns_total[None, :] > 0.0,
                                                ns_total[None, :], 1.0)),
                     jnp.where(ns_alloc == 0.0, 0.0, 1.0))
    return div_rn(jnp.max(frac, axis=-1), ns_weight)


def make_pool_select(queue_deserved, pool_queue, pool_ns, pool_job_start,
                     pool_njobs, ns_weight, ns_total, eps, ns_live: bool):
    """The two-level (namespace, queue) job selection closure shared by the
    scan and sharded kernel bodies (allocate.go:120-162): first the
    namespace — live weighted share when ``ns_live`` (drf's
    NamespaceOrderFn), else the static encode rank (the host's session-open
    namespace sort, i.e. a priority queue over fixed keys) — then the best
    non-overused queue with jobs left inside it, by live queue share, then
    that pool's next job. Ties break toward the lower encode index at both
    levels. Returns (pool, job), -1/-1 when nothing is selectable."""
    n_ns = ns_weight.shape[0]

    def select(q_alloc, ns_alloc, p_cursor):
        share = queue_share(q_alloc, queue_deserved)           # [Q]
        over = queue_overused(q_alloc, queue_deserved, eps)    # [Q]
        pool_ok = (p_cursor < pool_njobs) & ~over[pool_queue]  # [P]
        ns_has = jnp.zeros(n_ns, jnp.int32).at[pool_ns].max(
            pool_ok.astype(jnp.int32)) > 0
        if ns_live:
            ns_key = namespace_share(ns_alloc, ns_total, ns_weight)
        else:
            ns_key = jnp.arange(n_ns, dtype=jnp.float32)
        ns_sel = jnp.argmin(jnp.where(ns_has, ns_key, BIG)).astype(jnp.int32)
        pool_key = share[pool_queue]
        eligible = pool_ok & (pool_ns == ns_sel)
        p = jnp.argmin(jnp.where(eligible, pool_key, BIG)).astype(jnp.int32)
        ok = ns_has[ns_sel]
        job = pool_job_start[p] + p_cursor[p]
        return jnp.where(ok, p, -1), jnp.where(ok, job, -1)
    return select


@partial(jax.jit, static_argnames=("allow_pipeline", "ns_live"))
def gang_allocate(task_group: jax.Array,      # [T] i32
                  task_job: jax.Array,        # [T] i32 (padding -> sentinel)
                  task_valid: jax.Array,      # [T] bool
                  group_req: jax.Array,       # [G, R] f32
                  group_mask: jax.Array,      # [G, N] bool static predicates
                  group_static_score: jax.Array,  # [G, N] f32
                  task_bucket: jax.Array,     # [T] i32 topology bucket (-1 none)
                  group_pack_bonus: jax.Array,  # [G] f32 per-mate pack score
                  job_min_available: jax.Array,   # [J] i32
                  job_ready_base: jax.Array,      # [J] i32 occupied count
                  job_task_start: jax.Array,      # [J] i32 span start
                  job_n_tasks: jax.Array,         # [J] i32 span length
                  job_queue: jax.Array,           # [J] i32
                  pool_queue: jax.Array,          # [P] i32 queue of pool
                  pool_ns: jax.Array,             # [P] i32 namespace of pool
                  pool_job_start: jax.Array,      # [P] i32 jobs grouped/pool
                  pool_njobs: jax.Array,          # [P] i32
                  ns_weight: jax.Array,           # [NS] f32
                  ns_alloc0: jax.Array,           # [NS, R] f32
                  ns_total: jax.Array,            # [R] f32 cluster total
                  queue_deserved: jax.Array,      # [Q, R] f32 (+inf ungated)
                  queue_alloc0: jax.Array,        # [Q, R] f32
                  node_idle: jax.Array,       # [N, R] f32
                  node_future: jax.Array,     # [N, R] f32
                  node_alloc: jax.Array,      # [N, R] f32
                  node_ntasks: jax.Array,     # [N] i32
                  node_max_tasks: jax.Array,  # [N] i32 (0 = uncapped)
                  eps: jax.Array,             # [R] f32
                  weights: ScoreWeights,
                  allow_pipeline: bool = True,
                  ns_live: bool = False,
                  task_slot: jax.Array = None,  # [T] i32 slot row (S = none)
                  slot_ok: jax.Array = None):   # [S+1, N] bool domain rows
    """Returns (assign [T] node-or--1, pipelined [T] bool, ready [J] bool,
    kept [J] bool, final AllocState).

    ``task_slot``/``slot_ok`` are the constraint compiler's per-task
    topology-domain restriction (ops/constraints.py): task t may only
    use nodes where ``slot_ok[task_slot[t]]`` holds; row S is all-true
    and unconstrained tasks carry slot S. Keeping the restriction per
    TASK (instead of splitting task groups per assigned domain) keeps
    the group axis at its base size, which is what lets the candidate-
    table kernels amortize their refresh sweeps across a gang."""
    T = task_group.shape[0]
    J = job_min_available.shape[0]

    select = make_pool_select(queue_deserved, pool_queue, pool_ns,
                              pool_job_start, pool_njobs, ns_weight,
                              ns_total, eps, ns_live)

    p0, j0 = select(queue_alloc0, ns_alloc0, jnp.zeros_like(pool_njobs))
    init = AllocState(
        idle=node_idle, future=node_future, n_tasks=node_ntasks,
        ckpt_idle=node_idle, ckpt_future=node_future, ckpt_ntasks=node_ntasks,
        cur_bucket=jnp.int32(-1),
        pack_nodes=jnp.zeros(node_ntasks.shape[0], jnp.float32),
        q_alloc=queue_alloc0, ns_alloc=ns_alloc0,
        p_cursor=jnp.zeros_like(pool_njobs),
        cur_pool=p0, cur_job=j0, t_off=jnp.int32(0),
        placed=jnp.int32(0), placed_alloc=jnp.int32(0),
        placed_res=jnp.zeros_like(eps),
        ready=jnp.zeros(J, bool), kept=jnp.zeros(J, bool))

    def step(state: AllocState, _):
        active = state.cur_job >= 0
        job = jnp.maximum(state.cur_job, 0)
        t_idx = jnp.clip(job_task_start[job] + state.t_off, 0, T - 1)
        g = task_group[t_idx]
        # guard zero-task jobs (they still consume a step, so callers must
        # exclude them from the encoding to preserve the T-step budget)
        valid = task_valid[t_idx] & active & \
            (state.t_off < job_n_tasks[job])

        req = group_req[g]                       # [R]
        static_ok = group_mask[g]                # [N]
        if task_slot is not None:
            static_ok = static_ok & slot_ok[task_slot[t_idx]]
        pods_ok = (node_max_tasks == 0) | (state.n_tasks < node_max_tasks)
        base_ok = static_ok & pods_ok & valid

        fits_idle = jnp.all(req[None, :] <= state.idle + eps[None, :],
                            axis=-1) & base_ok
        fits_future = jnp.all(req[None, :] <= state.future + eps[None, :],
                              axis=-1) & base_ok

        # task-topology packing: same-bucket placements earlier in the scan
        # attract this task to their nodes (the in-kernel form of the
        # reference's per-task bucket.node rescoring, topology.go:152-153)
        b = task_bucket[t_idx]
        same_bucket = (b >= 0) & (b == state.cur_bucket)
        pack = jnp.where(same_bucket, state.pack_nodes, 0.0)
        score = node_score(req, state.idle, node_alloc, weights,
                           group_static_score[g] + pack * group_pack_bonus[g])

        any_idle = jnp.any(fits_idle)
        if allow_pipeline:
            cand = jnp.where(any_idle, fits_idle, fits_future)
        else:
            cand = fits_idle
        sel = jnp.argmax(jnp.where(cand, score, NEG))
        placed_ok = jnp.any(cand)
        pipelined = placed_ok & ~any_idle if allow_pipeline \
            else jnp.bool_(False)

        take_idle = placed_ok & ~pipelined
        idle = state.idle.at[sel].add(jnp.where(take_idle, -req, 0.0))
        future = state.future.at[sel].add(jnp.where(placed_ok, -req, 0.0))
        n_tasks = state.n_tasks.at[sel].add(jnp.where(placed_ok, 1, 0))

        state = state._replace(
            idle=idle, future=future, n_tasks=n_tasks,
            cur_bucket=jnp.where(valid, b, state.cur_bucket),
            pack_nodes=pack.at[sel].add(
                jnp.where(placed_ok & valid, 1.0, 0.0)),
            t_off=state.t_off + jnp.where(active, 1, 0),
            placed=state.placed + placed_ok.astype(jnp.int32),
            placed_alloc=state.placed_alloc + take_idle.astype(jnp.int32),
            placed_res=state.placed_res + jnp.where(placed_ok, req, 0.0))

        # ---- job boundary: gang commit/rollback + charges + select
        complete = active & (state.t_off >= job_n_tasks[job])
        base = job_ready_base[job]
        minavail = job_min_available[job]
        is_ready = complete & (base + state.placed_alloc >= minavail)
        is_kept = complete & (base + state.placed >= minavail)
        keep = is_ready | is_kept
        roll = complete & ~keep

        idle = jnp.where(roll, state.ckpt_idle, state.idle)
        future = jnp.where(roll, state.ckpt_future, state.future)
        n_tasks = jnp.where(roll, state.ckpt_ntasks, state.n_tasks)
        p = jnp.maximum(state.cur_pool, 0)
        q = pool_queue[p]
        ns = pool_ns[p]
        charged = jnp.where(keep, state.placed_res, 0.0)
        q_alloc = state.q_alloc.at[q].add(charged)
        ns_alloc = state.ns_alloc.at[ns].add(charged)
        p_cursor = state.p_cursor.at[p].add(jnp.where(complete, 1, 0))
        ready = state.ready.at[job].set(is_ready | state.ready[job])
        kept = state.kept.at[job].set(is_kept | state.kept[job])

        np_, nj = select(q_alloc, ns_alloc, p_cursor)
        cur_pool = jnp.where(complete, np_, state.cur_pool)
        cur_job = jnp.where(complete, nj, state.cur_job)

        state = state._replace(
            idle=idle, future=future, n_tasks=n_tasks,
            ckpt_idle=jnp.where(complete, idle, state.ckpt_idle),
            ckpt_future=jnp.where(complete, future, state.ckpt_future),
            ckpt_ntasks=jnp.where(complete, n_tasks, state.ckpt_ntasks),
            q_alloc=q_alloc, ns_alloc=ns_alloc, p_cursor=p_cursor,
            cur_pool=cur_pool, cur_job=cur_job,
            t_off=jnp.where(complete, 0, state.t_off),
            placed=jnp.where(complete, 0, state.placed),
            placed_alloc=jnp.where(complete, 0, state.placed_alloc),
            placed_res=jnp.where(complete, 0.0, state.placed_res),
            ready=ready, kept=kept)
        emit_t = jnp.where(valid, t_idx, T)
        emit_sel = jnp.where(placed_ok, sel.astype(jnp.int32), -1)
        return state, (emit_t, emit_sel, pipelined)

    state, (emit_t, emit_sel, emit_pipe) = jax.lax.scan(
        step, init, None, length=T)

    # scatter per-step placements back to task order (slot T absorbs no-ops)
    assign = jnp.full(T + 1, -1, jnp.int32).at[emit_t].set(emit_sel)[:T]
    pipelined = jnp.zeros(T + 1, bool).at[emit_t].set(emit_pipe)[:T]

    ok = (state.ready[task_job] | state.kept[task_job]) & task_valid
    assign = jnp.where(ok, assign, -1)
    pipelined = pipelined & ok
    return assign, pipelined, state.ready, state.kept, state


@partial(jax.jit, static_argnames=("allow_pipeline", "ns_live", "chunk"))
def gang_allocate_chunked(*args, allow_pipeline: bool = True,
                          ns_live: bool = False, chunk: int = 16,
                          task_slot: jax.Array = None,
                          slot_ok: jax.Array = None):
    """Chunked-candidate form of :func:`gang_allocate`: identical
    semantics (ops/sharded.py holds the exactness argument), but each
    scan step works on a top-``chunk``-per-fit-class candidate table that
    refreshes once per chunk/group-change/rollback — the O(N) node sweep
    (fit compares, scoring, argmax) runs once per chunk instead of once
    per task. Same positional arguments as :func:`gang_allocate`; the
    fifth output is the final node idle matrix rather than the full
    AllocState."""
    from .sharded import _sharded_body_chunked
    return _sharded_body_chunked(*args, allow_pipeline=allow_pipeline,
                                 ns_live=ns_live, axis=None, chunk=chunk,
                                 task_slot=task_slot, slot_ok=slot_ok)
