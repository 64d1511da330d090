"""Node-axis-sharded gang-allocate: the multi-chip scheduling step.

The reference scales its per-task node sweep with a 16-goroutine fan-out and
adaptive node *sampling* (pkg/scheduler/util/scheduler_helper.go:49-68,121).
The TPU-native scale-out instead shards the node axis across the device mesh
(ICI) and evaluates every node exhaustively: each chip owns N/D nodes' state,
the scan carry stays resident per-chip, and the only cross-chip traffic per
scan step is an all-gather of one (score, index) candidate pair per chip plus
a psum'd bit — a few dozen bytes over ICI, with the node-dimension compute
(fit compares + scoring) fully parallel.

This is the project's "sequence parallelism": the long axis (nodes, 10k+) is
blockwise-decomposed across chips exactly like ring attention decomposes
sequence — SURVEY.md §5.7.

Queue/job bookkeeping (dynamic queue selection by live share, fair-share
budget gating, gang commit/rollback — see ops/allocate.py) is replicated:
every chip runs the identical small-state math, so job selection needs no
communication. Semantics match ops/allocate.gang_allocate bit-for-bit
(ties broken by the lowest global node index, which is also what
argmax-over-concatenated-shards yields).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .allocate import make_pool_select
from .score import ScoreWeights, node_score

NEG = -1e30   # plain floats: no backend init at import
BIG = 1e30


class ShardState(NamedTuple):
    idle: jax.Array          # [Nl, R] local shard
    future: jax.Array        # [Nl, R]
    n_tasks: jax.Array       # [Nl]
    ckpt_idle: jax.Array
    ckpt_future: jax.Array
    ckpt_ntasks: jax.Array
    cur_bucket: jax.Array    # i32 replicated
    pack_nodes: jax.Array    # [Nl] f32 local current-bucket placements
    q_alloc: jax.Array       # [Q, R] replicated
    ns_alloc: jax.Array      # [NS, R] replicated
    p_cursor: jax.Array      # [P] replicated
    cur_pool: jax.Array      # i32 replicated
    cur_job: jax.Array       # i32 replicated
    t_off: jax.Array
    placed: jax.Array
    placed_alloc: jax.Array
    placed_res: jax.Array    # [R]
    ready: jax.Array         # [J] bool replicated
    kept: jax.Array          # [J] bool replicated


def _init_shard_state(select, node_idle, node_future, node_ntasks,
                      queue_alloc0, ns_alloc0, pool_njobs, eps, n_jobs):
    Nl = node_idle.shape[0]
    p0, j0 = select(queue_alloc0, ns_alloc0, jnp.zeros_like(pool_njobs))
    return ShardState(
        idle=node_idle, future=node_future, n_tasks=node_ntasks,
        ckpt_idle=node_idle, ckpt_future=node_future, ckpt_ntasks=node_ntasks,
        cur_bucket=jnp.int32(-1),
        pack_nodes=jnp.zeros(Nl, jnp.float32),
        q_alloc=queue_alloc0, ns_alloc=ns_alloc0,
        p_cursor=jnp.zeros_like(pool_njobs),
        cur_pool=p0, cur_job=j0, t_off=jnp.int32(0),
        placed=jnp.int32(0), placed_alloc=jnp.int32(0),
        placed_res=jnp.zeros_like(eps),
        ready=jnp.zeros(n_jobs, bool), kept=jnp.zeros(n_jobs, bool))


def _job_boundary(state: ShardState, select, active, job, pool_queue,
                  pool_ns, job_n_tasks, job_ready_base, job_min_available):
    """Gang commit/rollback + next-job selection at a job boundary
    (replicated math, no communication). Shared by both sharded bodies.
    Returns (state, roll)."""
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
    charged = jnp.where(keep, state.placed_res, 0.0)
    q_alloc = state.q_alloc.at[pool_queue[p]].add(charged)
    ns_alloc = state.ns_alloc.at[pool_ns[p]].add(charged)
    p_cursor = state.p_cursor.at[p].add(jnp.where(complete, 1, 0))
    ready = state.ready.at[job].set(is_ready | state.ready[job])
    kept = state.kept.at[job].set(is_kept | state.kept[job])

    np_, nj = select(q_alloc, ns_alloc, p_cursor)
    cur_pool = jnp.where(complete, np_, state.cur_pool)
    cur_job = jnp.where(complete, nj, state.cur_job)

    return state._replace(
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
        ready=ready, kept=kept), roll


def _finalize_outputs(state: ShardState, emit_t, emit_sel, emit_pipe,
                      task_job, task_valid, T):
    assign = jnp.full(T + 1, -1, jnp.int32).at[emit_t].set(emit_sel)[:T]
    pipelined = jnp.zeros(T + 1, bool).at[emit_t].set(emit_pipe)[:T]
    ok = (state.ready[task_job] | state.kept[task_job]) & task_valid
    assign = jnp.where(ok, assign, -1)
    pipelined = pipelined & ok
    return assign, pipelined, state.ready, state.kept, state.idle


def _sharded_body(task_group, task_job, task_valid, group_req, group_mask,
                  group_static_score, task_bucket, group_pack_bonus,
                  job_min_available, job_ready_base,
                  job_task_start, job_n_tasks, job_queue, pool_queue,
                  pool_ns, pool_job_start, pool_njobs, ns_weight,
                  ns_alloc0, ns_total, queue_deserved, queue_alloc0,
                  node_idle, node_future, node_alloc, node_ntasks,
                  node_max_tasks, eps, weights, allow_pipeline: bool,
                  ns_live: bool, axis: str, task_slot=None, slot_ok=None):
    """Runs inside shard_map: node-axis arrays are the local shard.

    ``task_slot``/``slot_ok`` are the per-task topology-domain rows of
    the constraint compiler (ops/allocate.gang_allocate documents the
    contract); ``slot_ok`` is sharded along the node axis like every
    other [*, N] input."""
    T = task_group.shape[0]
    J = job_min_available.shape[0]
    Nl = node_idle.shape[0]
    shard = jax.lax.axis_index(axis)
    offset = shard * Nl

    select = make_pool_select(queue_deserved, pool_queue, pool_ns,
                              pool_job_start, pool_njobs, ns_weight,
                              ns_total, eps, ns_live)
    init = _init_shard_state(select, node_idle, node_future, node_ntasks,
                             queue_alloc0, ns_alloc0, pool_njobs, eps, J)

    def step(state: ShardState, _):
        active = state.cur_job >= 0
        job = jnp.maximum(state.cur_job, 0)
        t_idx = jnp.clip(job_task_start[job] + state.t_off, 0, T - 1)
        g = task_group[t_idx]
        # guard zero-task jobs (see ops/allocate.py)
        valid = task_valid[t_idx] & active & \
            (state.t_off < job_n_tasks[job])

        req = group_req[g]
        static_ok = group_mask[g]                      # [Nl]
        if task_slot is not None:
            static_ok = static_ok & slot_ok[task_slot[t_idx]]
        pods_ok = (node_max_tasks == 0) | (state.n_tasks < node_max_tasks)
        base_ok = static_ok & pods_ok & valid

        fits_idle = jnp.all(req[None, :] <= state.idle + eps[None, :],
                            axis=-1) & base_ok
        fits_future = jnp.all(req[None, :] <= state.future + eps[None, :],
                              axis=-1) & base_ok

        # task-topology packing on the local shard (see ops/allocate.py)
        b = task_bucket[t_idx]
        same_bucket = (b >= 0) & (b == state.cur_bucket)
        pack = jnp.where(same_bucket, state.pack_nodes, 0.0)
        score = node_score(req, state.idle, node_alloc, weights,
                           group_static_score[g] + pack * group_pack_bonus[g])

        # -- cross-chip: ONE all-gather of a [4] payload per chip carries
        # both candidate sets' (score, global index) pairs; the idle-vs-
        # future choice is made globally from the gathered idle scores.
        # Identical semantics to the psum + two all_gathers formulation
        # (prefer idle fits anywhere; ties by lowest global node index:
        # per-chip argmax picks the lowest local index, min-index across
        # chips picks the lowest global) at a third of the per-step ICI
        # latency. Node indices ride as f32 (exact to 2^24 nodes).
        masked_idle = jnp.where(fits_idle, score, NEG)
        li = jnp.argmax(masked_idle)
        if allow_pipeline:
            masked_fut = jnp.where(fits_future, score, NEG)
            lf = jnp.argmax(masked_fut)
        else:
            masked_fut = jnp.full_like(masked_idle, NEG)
            lf = jnp.int32(0)
        payload = jnp.stack([
            masked_idle[li], (offset + li).astype(jnp.float32),
            masked_fut[lf], (offset + lf).astype(jnp.float32)])
        gathered = jax.lax.all_gather(payload, axis)         # [D, 4]
        any_idle = jnp.any(gathered[:, 0] > NEG * 0.5)
        scores = jnp.where(any_idle, gathered[:, 0], gathered[:, 2])
        gidxs = jnp.where(any_idle, gathered[:, 1],
                          gathered[:, 3]).astype(jnp.int32)
        best_score = jnp.max(scores)
        winner = scores >= best_score
        sel_g = jnp.min(jnp.where(winner, gidxs, jnp.int32(2**30)))
        placed_ok = best_score > NEG * 0.5
        pipelined = placed_ok & ~any_idle if allow_pipeline \
            else jnp.bool_(False)

        # owner-shard applies the placement to its local state
        is_owner = (sel_g >= offset) & (sel_g < offset + Nl)
        sel_l = jnp.clip(sel_g - offset, 0, Nl - 1)
        take_idle = placed_ok & ~pipelined
        idle = state.idle.at[sel_l].add(
            jnp.where(is_owner & take_idle, -req, 0.0))
        future = state.future.at[sel_l].add(
            jnp.where(is_owner & placed_ok, -req, 0.0))
        n_tasks = state.n_tasks.at[sel_l].add(
            jnp.where(is_owner & placed_ok, 1, 0))

        state = state._replace(
            idle=idle, future=future, n_tasks=n_tasks,
            cur_bucket=jnp.where(valid, b, state.cur_bucket),
            pack_nodes=pack.at[sel_l].add(
                jnp.where(is_owner & placed_ok & valid, 1.0, 0.0)),
            t_off=state.t_off + jnp.where(active, 1, 0),
            placed=state.placed + placed_ok.astype(jnp.int32),
            placed_alloc=state.placed_alloc + take_idle.astype(jnp.int32),
            placed_res=state.placed_res + jnp.where(placed_ok, req, 0.0))

        state, _ = _job_boundary(state, select, active, job, pool_queue,
                                 pool_ns, job_n_tasks,
                                 job_ready_base, job_min_available)
        emit_t = jnp.where(valid, t_idx, T)
        emit_sel = jnp.where(placed_ok, sel_g, -1)
        return state, (emit_t, emit_sel, pipelined)

    state, (emit_t, emit_sel, emit_pipe) = jax.lax.scan(
        step, init, None, length=T)
    return _finalize_outputs(state, emit_t, emit_sel, emit_pipe,
                             task_job, task_valid, T)


def _sharded_body_chunked(task_group, task_job, task_valid, group_req,
                          group_mask, group_static_score, task_bucket,
                          group_pack_bonus, job_min_available,
                          job_ready_base, job_task_start, job_n_tasks,
                          job_queue, pool_queue, pool_ns, pool_job_start,
                          pool_njobs, ns_weight, ns_alloc0, ns_total,
                          queue_deserved, queue_alloc0, node_idle,
                          node_future, node_alloc, node_ntasks,
                          node_max_tasks, eps, weights,
                          allow_pipeline: bool, ns_live: bool, axis: str,
                          chunk: int, n_dev: int = 1,
                          task_slot=None, slot_ok=None):
    """Chunked-candidate variant of :func:`_sharded_body`: instead of one
    all-gather per scan step, each shard gathers its top-``chunk``
    candidates per fit class (idle / future) into a replicated candidate
    table, and up to ``chunk`` consecutive placements are served from the
    table with no communication. The table refreshes on group change,
    after a gang rollback, or when ``chunk`` steps have been served.

    This is EXACT, tie-breaks included, not an approximation: within a
    chunk only placed-on nodes change score/feasibility, and every placed
    node is in the table (placements are chosen from it). For an untouched
    node outside the table, its shard kept ``chunk`` statically-better
    candidates, of which at most ``chunk - 1`` have been touched — so an
    untouched, at-least-as-good (score, then lower global index) candidate
    remains in the table whenever the outside node would have won.
    ``lax.top_k``'s lowest-index tie order matches the kernel's global
    lowest-node-index tie-break.

    Per-task topology domains (``task_slot``/``slot_ok``) join the
    refresh condition: a slot change refreshes the table with the slot
    row folded into the mask, so every serve's table was built under the
    serving task's own domain — the membership half of the exactness
    argument is untouched. (The NATIVE solver instead keeps per-slot
    sub-tables so rotating-domain gangs don't refresh per task; here the
    chunked tier is the fallback/parity path, not the at-scale one.)
    """
    T = task_group.shape[0]
    J = job_min_available.shape[0]
    Nl = node_idle.shape[0]
    R = node_idle.shape[1]
    C = min(chunk, Nl)   # a shard can't offer more candidates than nodes
    if axis is None:     # single-device form (ops.allocate.gang_allocate_chunked)
        offset = jnp.int32(0)
        n_dev = 1
    else:
        # n_dev arrives statically from make_sharded_gang_allocate
        # (mesh.size): the candidate-table height K must be a static
        # shape, and jax.lax.axis_size does not exist on every
        # supported jax version (0.4.x lacks it — the former dynamic
        # lookup made every sharded chunked call crash)
        offset = jax.lax.axis_index(axis) * Nl
    K = 2 * C * n_dev
    F = 5 + 3 * R   # gidx, static, pack, ntasks, maxtasks, idle, future, alloc

    select = make_pool_select(queue_deserved, pool_queue, pool_ns,
                              pool_job_start, pool_njobs, ns_weight,
                              ns_total, eps, ns_live)
    init = _init_shard_state(select, node_idle, node_future, node_ntasks,
                             queue_alloc0, ns_alloc0, pool_njobs, eps, J)
    cand0 = jnp.full((K, F), NEG, jnp.float32).at[:, 0].set(-1.0)
    carry0 = (init, cand0, jnp.int32(C), jnp.int32(-1), jnp.int32(-1),
              jnp.int32(-1), jnp.bool_(True))

    def step(carry, _):
        state, cand, since, prev_g, prev_b, prev_s, force = carry
        active = state.cur_job >= 0
        job = jnp.maximum(state.cur_job, 0)
        t_idx = jnp.clip(job_task_start[job] + state.t_off, 0, T - 1)
        g = task_group[t_idx]
        b = task_bucket[t_idx]
        slot = task_slot[t_idx] if task_slot is not None else jnp.int32(-1)
        valid = task_valid[t_idx] & active & \
            (state.t_off < job_n_tasks[job])
        req = group_req[g]

        need = force | (since >= C) | (g != prev_g) | (b != prev_b) | \
            (slot != prev_s)

        def refresh(_):
            static_ok = group_mask[g]
            if task_slot is not None:
                static_ok = static_ok & slot_ok[slot]
            pods_ok = (node_max_tasks == 0) | \
                (state.n_tasks < node_max_tasks)
            base_ok = static_ok & pods_ok
            pack_eff = jnp.where((b >= 0) & (b == state.cur_bucket),
                                 state.pack_nodes, 0.0)
            score = node_score(req, state.idle, node_alloc, weights,
                               group_static_score[g])
            fits_idle = jnp.all(req[None, :] <= state.idle + eps[None, :],
                                axis=-1) & base_ok
            fits_fut = jnp.all(req[None, :] <= state.future + eps[None, :],
                               axis=-1) & base_ok
            # the top-C ranking must use the same order as the in-chunk
            # argmax: score including the pack bonus, ties by index
            score_b = score + pack_eff * group_pack_bonus[g]
            rows = []
            for m in (jnp.where(fits_idle, score_b, NEG),
                      jnp.where(fits_fut, score_b, NEG)
                      if allow_pipeline else jnp.full(Nl, NEG)):
                vals, idxs = jax.lax.top_k(m, C)
                ok_row = vals > NEG * 0.5
                row = jnp.concatenate([
                    jnp.where(ok_row, (offset + idxs).astype(jnp.float32),
                              -1.0)[:, None],
                    group_static_score[g][idxs][:, None],
                    pack_eff[idxs][:, None],
                    state.n_tasks[idxs].astype(jnp.float32)[:, None],
                    node_max_tasks[idxs].astype(jnp.float32)[:, None],
                    state.idle[idxs], state.future[idxs],
                    node_alloc[idxs]], axis=1)
                rows.append(row)
            local = jnp.concatenate(rows, axis=0)        # [2C, F]
            if axis is None:
                return local
            return jax.lax.all_gather(local, axis).reshape(K, F)

        cand = jax.lax.cond(need, refresh, lambda _: cand, None)
        since = jnp.where(need, 1, since + 1)

        gidx_f = cand[:, 0]
        row_live = gidx_f >= 0.0
        ntasks_c = cand[:, 3]
        maxt_c = cand[:, 4]
        idle_c = cand[:, 5:5 + R]
        fut_c = cand[:, 5 + R:5 + 2 * R]
        alloc_c = cand[:, 5 + 2 * R:]
        pods_ok_c = (maxt_c == 0) | (ntasks_c < maxt_c)
        sb = (b >= 0) & (b == state.cur_bucket)
        static_eff = cand[:, 1] + \
            jnp.where(sb, cand[:, 2], 0.0) * group_pack_bonus[g]
        score_c = node_score(req, idle_c, alloc_c, weights, static_eff)
        base_c = row_live & pods_ok_c & valid
        fits_idle_c = jnp.all(req[None, :] <= idle_c + eps[None, :],
                              axis=-1) & base_c
        if allow_pipeline:
            fits_fut_c = jnp.all(req[None, :] <= fut_c + eps[None, :],
                                 axis=-1) & base_c
        else:
            fits_fut_c = jnp.zeros_like(fits_idle_c)
        any_idle = jnp.any(fits_idle_c)
        cls = jnp.where(any_idle, fits_idle_c, fits_fut_c)
        scores = jnp.where(cls, score_c, NEG)
        best_score = jnp.max(scores)
        winner = scores >= best_score
        gidx_i = gidx_f.astype(jnp.int32)
        sel_g = jnp.min(jnp.where(winner, gidx_i, jnp.int32(2**30)))
        placed_ok = best_score > NEG * 0.5
        pipelined = placed_ok & ~any_idle if allow_pipeline \
            else jnp.bool_(False)

        # apply to the candidate table (every row of the selected node)
        hit = placed_ok & (gidx_i == sel_g) & row_live
        take_idle = placed_ok & ~pipelined
        cand = cand.at[:, 5:5 + R].add(
            jnp.where((hit & take_idle)[:, None], -req[None, :], 0.0))
        cand = cand.at[:, 5 + R:5 + 2 * R].add(
            jnp.where(hit[:, None], -req[None, :], 0.0))
        cand = cand.at[:, 3].add(jnp.where(hit, 1.0, 0.0))
        cand = cand.at[:, 2].add(jnp.where(hit & valid, 1.0, 0.0))

        # apply to the owner shard's local state (as in _sharded_body)
        is_owner = (sel_g >= offset) & (sel_g < offset + Nl)
        sel_l = jnp.clip(sel_g - offset, 0, Nl - 1)
        idle = state.idle.at[sel_l].add(
            jnp.where(is_owner & take_idle, -req, 0.0))
        future = state.future.at[sel_l].add(
            jnp.where(is_owner & placed_ok, -req, 0.0))
        n_tasks = state.n_tasks.at[sel_l].add(
            jnp.where(is_owner & placed_ok, 1, 0))
        pack = jnp.where(sb, state.pack_nodes, 0.0)
        state = state._replace(
            idle=idle, future=future, n_tasks=n_tasks,
            cur_bucket=jnp.where(valid, b, state.cur_bucket),
            pack_nodes=pack.at[sel_l].add(
                jnp.where(is_owner & placed_ok & valid, 1.0, 0.0)),
            t_off=state.t_off + jnp.where(active, 1, 0),
            placed=state.placed + placed_ok.astype(jnp.int32),
            placed_alloc=state.placed_alloc + take_idle.astype(jnp.int32),
            placed_res=state.placed_res + jnp.where(placed_ok, req, 0.0))

        state, roll = _job_boundary(state, select, active, job,
                                    pool_queue, pool_ns,
                                    job_n_tasks, job_ready_base,
                                    job_min_available)
        emit_t = jnp.where(valid, t_idx, T)
        emit_sel = jnp.where(placed_ok, sel_g, -1)
        return (state, cand, since, g, b, slot, roll), \
            (emit_t, emit_sel, pipelined)

    (state, *_), (emit_t, emit_sel, emit_pipe) = jax.lax.scan(
        step, carry0, None, length=T)
    return _finalize_outputs(state, emit_t, emit_sel, emit_pipe,
                             task_job, task_valid, T)


def make_sharded_gang_allocate(mesh: Mesh, axis: str = "nodes",
                               allow_pipeline: bool = True,
                               chunk: int = 16, ns_live: bool = False,
                               with_slots: bool = False):
    """Build the jitted node-sharded gang-allocate for a device mesh.

    Node-axis inputs ([N,...] and [G,N]) must be padded so N divides the mesh
    size. Same argument order as ops.allocate.gang_allocate (minus the
    weights keyword); returns (assign [T] global node index, pipelined [T],
    ready [J], kept [J], final node idle [N,R]).

    ``with_slots`` appends two trailing positional inputs — the
    constraint compiler's ``task_slot`` [T] (replicated) and ``slot_ok``
    [S+1, N] (node-sharded like the other [*, N] inputs).
    """
    n = P(axis)               # [N] vectors
    nr = P(axis, None)        # [N, R]
    gn = P(None, axis)        # [G, N]
    rep = P()
    in_specs = (rep, rep, rep, rep, gn, gn, rep, rep,
                rep, rep, rep, rep, rep,
                rep, rep, rep, rep, rep, rep, rep,
                rep, rep,
                nr, nr, nr, n, n, rep,
                ScoreWeights(rep, rep, rep, rep, rep))
    if with_slots:
        in_specs = in_specs + (rep, gn)
    out_specs = (rep, rep, rep, rep, nr)
    if chunk and chunk > 1:
        base = _sharded_body_chunked
        if with_slots:
            def base(*args, **kw):
                *pos, tslot, sok = args
                return _sharded_body_chunked(*pos, task_slot=tslot,
                                             slot_ok=sok, **kw)
        body = partial(base, allow_pipeline=allow_pipeline,
                       ns_live=ns_live, axis=axis, chunk=int(chunk),
                       n_dev=int(mesh.devices.size))
    else:
        base = _sharded_body
        if with_slots:
            def base(*args, **kw):
                *pos, tslot, sok = args
                return _sharded_body(*pos, task_slot=tslot, slot_ok=sok,
                                     **kw)
        body = partial(base, allow_pipeline=allow_pipeline,
                       ns_live=ns_live, axis=axis)
    sm = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return jax.jit(sm)


# -- topology-aware node partition (docs/design/sharded_kernel.md) -----------

class ShardPlan:
    """Contiguous node-range partition of the (padded) node axis over the
    device mesh, balanced by per-node task pressure instead of a naive
    N/D split.

    shard_map still requires EQUAL per-device shard widths, so the plan
    materializes a *layout*: device ``d`` owns the contiguous node rows
    ``[bounds[d], bounds[d+1])`` placed at layout rows ``[d*Nl, d*Nl +
    len_d)`` with inert padding rows (gather index -1) filling the rest
    of its block. Because every range is contiguous and the blocks are
    in node order, the layout index is strictly increasing over real
    rows — the kernel's lowest-global-index tie-break therefore equals
    the single-device node-order tie-break, keeping the sharded run
    bit-identical regardless of where the boundaries fall.

    The plan is persistent: it is rebuilt only on STRUCTURAL node
    changes (membership/order churn invalidates the persistent host
    arrays wholesale, and the plan with them), so the per-device
    resident kernel-input buffers keep their dirty-row scatter path
    across steady-state cycles.
    """

    __slots__ = ("n_devices", "n_rows", "rows_per_shard", "bounds",
                 "gather", "layout_of_node", "pressure_per_shard")

    def __init__(self, n_devices: int, n_rows: int, bounds):
        self.n_devices = int(n_devices)
        self.n_rows = int(n_rows)
        self.bounds = np.asarray(bounds, np.int64)
        widths = self.bounds[1:] - self.bounds[:-1]
        nl = int(widths.max()) if len(widths) else 1
        self.rows_per_shard = max(nl, 1)
        gather = np.full(self.n_devices * self.rows_per_shard, -1, np.int64)
        layout_of_node = np.full(self.n_rows, -1, np.int64)
        for d in range(self.n_devices):
            lo, hi = int(self.bounds[d]), int(self.bounds[d + 1])
            base = d * self.rows_per_shard
            gather[base:base + (hi - lo)] = np.arange(lo, hi)
            layout_of_node[lo:hi] = np.arange(base, base + (hi - lo))
        self.gather = gather
        self.layout_of_node = layout_of_node
        self.pressure_per_shard = None

    @property
    def n_layout(self) -> int:
        return self.n_devices * self.rows_per_shard

    def take(self, a, axis: int = 0, fill=0):
        """Gather a node-axis numpy array into layout order; padding rows
        get ``fill``."""
        a = np.asarray(a)
        if self.n_rows == 0:
            # empty plan (zero ready nodes): all layout rows are padding
            shape = list(a.shape)
            shape[axis] = self.n_layout
            return np.full(shape, fill, a.dtype)
        idx = np.clip(self.gather, 0, self.n_rows - 1)
        out = np.take(a, idx, axis=axis)
        pad = self.gather < 0
        if pad.any():
            sl = [slice(None)] * out.ndim
            sl[axis] = pad
            out[tuple(sl)] = fill
        return out

    def take_device(self, a, axis: int = 1, fill=0.0):
        """Device-side gather for arrays already on the accelerator
        (gmask / static_score are products of the context build)."""
        if self.n_rows == 0:
            shape = list(a.shape)
            shape[axis] = self.n_layout
            return jnp.full(shape, fill, a.dtype)
        idx = jnp.asarray(np.clip(self.gather, 0, self.n_rows - 1))
        out = jnp.take(a, idx, axis=axis)
        pad = jnp.asarray(self.gather < 0)
        shape = [1] * out.ndim
        shape[axis] = pad.shape[0]
        return jnp.where(pad.reshape(shape), fill, out)


def build_shard_plan(n_rows: int, n_devices: int, pressure=None,
                     max_skew: float = 2.0) -> ShardPlan:
    """Partition ``n_rows`` node rows into ``n_devices`` contiguous
    ranges whose per-shard summed ``pressure`` (resident task count per
    node from the snapshot rollups, +1 so empty nodes still carry their
    sweep cost) is as balanced as a prefix-sum split can make it.

    ``max_skew`` bounds the layout blow-up: no range may exceed
    ``max_skew * ceil(n/D)`` rows, so a pathologically skewed pressure
    profile cannot make one shard own most of the cluster (the layout is
    D * max-range wide). ``pressure=None`` degrades to the naive equal
    split."""
    n_rows = int(n_rows)
    d = max(int(n_devices), 1)
    if n_rows <= 0:
        return ShardPlan(d, 0, [0] * (d + 1))
    w_max = max(1, int(np.ceil(n_rows / d * max_skew)))
    if pressure is None:
        step = int(np.ceil(n_rows / d))
        bounds = [min(i * step, n_rows) for i in range(d + 1)]
        bounds[-1] = n_rows
        return ShardPlan(d, n_rows, bounds)
    p = np.maximum(np.asarray(pressure, np.float64), 0.0) + 1.0
    if p.shape[0] < n_rows:            # padding rows carry pressure 1.0
        p = np.concatenate([p, np.ones(n_rows - p.shape[0])])
    p = p[:n_rows]
    prefix = np.concatenate([[0.0], np.cumsum(p)])
    total = prefix[-1]
    bounds = [0]
    for i in range(1, d):
        target = total * i / d
        b = int(np.searchsorted(prefix, target))
        # monotonic + width cap forward; leave room for the remaining
        # shards to absorb the tail under the same cap
        b = max(b, bounds[-1])
        b = min(b, bounds[-1] + w_max, n_rows)
        b = max(b, n_rows - (d - i) * w_max)
        bounds.append(b)
    bounds.append(n_rows)
    plan = ShardPlan(d, n_rows, bounds)
    plan.pressure_per_shard = [
        float(prefix[bounds[i + 1]] - prefix[bounds[i]])
        for i in range(d)]
    return plan


def synth_shardings(mesh: Mesh, axis: str = "nodes") -> list:
    """The NamedSharding of each SynthArrays.args entry on ``mesh``: node
    axes split over the mesh, everything else replicated."""
    n = NamedSharding(mesh, P(axis))
    nr = NamedSharding(mesh, P(axis, None))
    gn = NamedSharding(mesh, P(None, axis))
    rep = NamedSharding(mesh, P())
    return [rep, rep, rep, rep, gn, gn, rep, rep, rep, rep, rep, rep, rep,
            rep, rep, rep, rep, rep, rep, rep, rep, rep, nr, nr, nr, n, n,
            rep]


def shard_synth(mesh: Mesh, sa, axis: str = "nodes"):
    """Device-put a SynthArrays set with node-axis sharding over ``mesh``.
    Returns the argument list for make_sharded_gang_allocate's fn, minus
    weights."""
    return [jax.device_put(a, s)
            for a, s in zip(sa.args, synth_shardings(mesh, axis))]
