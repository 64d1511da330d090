"""Pallas TPU kernel for the gang-allocate scan.

Same semantics as :func:`volcano_tpu.ops.allocate.gang_allocate` (one task
placed per step, live queue fair-share selection, gang commit/rollback) but
compiled as ONE kernel with a sequential grid over task steps:

* node state (idle/future/checkpoints, [R, N] resource-major) lives in VMEM
  scratch that persists across grid steps — no per-step HLO dispatch, which
  is what limits the XLA ``lax.scan`` formulation to ~20-45 us/step;
* per-task/job/queue integer metadata rides in SMEM via scalar prefetch;
* the per-group masked static score row ([N], -1e30 for predicate-failed
  nodes) is DMA'd HBM->VMEM only when the group changes (gang mates reuse
  the row);
* per-step placement decisions stream out through a small SMEM row; the
  final assign/ready/kept arrays are reconstructed with one vectorized
  scatter outside the kernel.

The scoring formula mirrors ops/score.py node_score exactly (binpack /
least / most / balanced + static bonus), with the resource loop unrolled
over the real resource dimensions. The resource axis rides on sublanes,
padded to 8 for up to 8 dimensions and to 16 for up to 16
(:func:`resource_pad`); a wider cluster goes to an XLA kernel.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fdiv import div_rn
from .score import ScoreWeights

NEG = -1e30
MASK_THRESH = -1e29      # static rows below this mean "predicate failed"
BIG = 1e30
LANE = 128
R_PAD_MAX = 16           # widest resource axis the kernel holds
W_RES = 8                # lane of the first per-resource binpack weight
assert W_RES + R_PAD_MAX <= LANE, "per-resource weights overflow the row"
# the one-hot matmuls carry queue shares: at the MXU's default precision
# (bf16 inputs) shares 1e-3 apart tie or swap, and the fair-share order
# left the XLA kernels' on the chip (exact at HIGHEST)
HIGHEST = jax.lax.Precision.HIGHEST

# emission row layout (one [1, 8] i32 row per grid step)
E_TIDX, E_SEL, E_PIPE, E_DJOB, E_READY, E_KEPT = 0, 1, 2, 3, 4, 5

# scalar memory (SMEM) the kernel's scalar-prefetch operands and SMEM
# scratch must fit: 1 MiB on v5e, less a margin for Mosaic's own use and
# its padding (bracketed by tests/test_tpu_compile.py: 140k tasks in
# gangs of 8 compile, 150k are refused)
SMEM_BUDGET_BYTES = (1 << 20) - (64 << 10)


def smem_bytes(t_pad: int, j_pad: int, p_pad: int, g_pad: int) -> int:
    """SMEM bytes of one kernel call: i32 task groups [T], four job rows
    [J], four pool rows plus the pool cursor [P8], two group rows [G], 16
    scalar slots and one (8, 8) emission block."""
    p8 = max(8, -(-p_pad // 8) * 8)
    return 4 * (t_pad + 4 * j_pad + 5 * p8 + 2 * g_pad + 16 + 64)


def fits_smem(t_pad: int, j_pad: int, p_pad: int, g_pad: int) -> bool:
    return smem_bytes(t_pad, j_pad, p_pad, g_pad) <= SMEM_BUDGET_BYTES


def resource_pad(r: int) -> int:
    """Sublanes the kernel gives ``r`` resource dimensions: the next
    multiple of 8, at least 8, so that a cluster of up to 8 dimensions
    runs the same program as it always has."""
    return max(8, -(-r // 8) * 8)


def fits_resources(r: int) -> bool:
    """Whether the kernel holds ``r`` resource dimensions."""
    return resource_pad(r) <= R_PAD_MAX


def _pad_to(x, size, axis, value=0):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _first_argmin(v):
    """Lowest index of the minimum of a 1-D vector. On the chip Mosaic's
    argmin and argmax break ties toward a later index (an all-equal row
    gives lane 127, maxima at 5 and 200 give 200), and every XLA kernel
    breaks them toward the lowest, so the kernel spells that out."""
    ids = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], 1), 0)[:, 0]
    return jnp.min(jnp.where(v == jnp.min(v), ids, v.shape[0]))


def _kernel(# scalar prefetch (SMEM)
            s_task_group,     # [T] i32, -1 for invalid/padding slots
            s_job_start,      # [J] i32
            s_job_ntasks,     # [J] i32
            s_job_minavail,   # [J] i32
            s_job_base,       # [J] i32
            s_pool_jstart,    # [P8] i32
            s_pool_njobs,     # [P8] i32
            s_pool_queue,     # [P8] i32
            s_pool_ns,        # [P8] i32
            s_group_bucket,   # [G] i32
            s_pack_milli,     # [G] i32 pack bonus * 1024
            # VMEM inputs
            group_req_ref,    # [G8, RP] f32 (RP = resource_pad(R))
            qdes_ref,         # [Q8, LANE] f32 (+inf for ungated dims)
            qalloc0_ref,      # [Q8, LANE] f32
            pnjobs_ref,       # [P8, LANE] i32 (lane-broadcast)
            pq_onehot_ref,    # [P8, Q8] f32 pool -> queue one-hot
            pn_onehot_ref,    # [NS8, P8] f32 namespace -> pools incidence
            nsalloc0_ref,     # [NS8, LANE] f32
            nstotal_ref,      # [1, LANE] f32 (first R lanes; 0 elsewhere)
            nsweight_ref,     # [NS8, LANE] f32 (lane-broadcast)
            idle0_ref,        # [RP, Np] f32
            future0_ref,      # [RP, Np] f32
            alloc_ref,        # [RP, Np] f32
            ntasks0_ref,      # [1, Np] i32
            maxtasks_ref,     # [1, Np] i32
            eps_ref,          # [1, LANE] f32 (first R lanes)
            w_ref,            # [1, LANE] f32 packed weights
            gscore_hbm,       # [G, Np] f32 in HBM (masked static scores)
            # outputs
            emit_ref,         # [1, 8] i32 SMEM block for this step
            # scratch
            v_idle, v_future, v_ck_idle, v_ck_future,    # [RP, Np] f32
            v_ntasks, v_ck_ntasks,                       # [1, Np] i32
            v_pack,                                      # [1, Np] f32
            v_grow,                                      # [1, Np] f32 group row
            v_qalloc,                                    # [Q8, LANE] f32
            v_nsalloc,                                   # [NS8, LANE] f32
            v_pcursor,                                   # [P8, LANE] i32
            v_placedres,                                 # [1, LANE] f32
            sc,                                          # SMEM (16,) i32
            sc_cursor,                                   # SMEM (P8,) i32
            sem,                                         # DMA semaphore
            *, n_res: int, allow_pipeline: bool, ns_live: bool):
    t = pl.program_id(0)
    T = pl.num_programs(0)

    # SMEM scalar slots
    CUR_P, CUR_JOB, T_OFF, PLACED, PLACED_ALLOC, CUR_BUCKET, PREV_G = range(7)

    def pool_select():
        """The two-level (namespace, queue) job selection
        (ops/allocate.make_pool_select): namespace first — live weighted
        dominant share (drf's NamespaceOrderFn) when ``ns_live``, else the
        static encode rank — then the best non-overused pool within it by
        live queue share. Returns the pool scalar, -1 when none eligible."""
        alloc = v_qalloc[:, :]
        des = qdes_ref[:, :]
        eps = eps_ref[0:1, :]
        inf_des = des >= BIG
        zero_des = des == 0.0
        # divided as IEEE divides: shares IEEE makes equal tie on the chip
        frac = jnp.where(
            inf_des, 0.0,
            jnp.where(zero_des, jnp.where(alloc == 0.0, 0.0, 1.0),
                      div_rn(alloc, jnp.where(zero_des | inf_des, 1.0,
                                              des))))
        share = jnp.max(frac, axis=1)                       # [Q8]
        over = jnp.any(~((alloc <= des + eps) | inf_des), axis=1)
        # map per-queue share/over onto pools via the one-hot matmul
        pool_share = jnp.dot(pq_onehot_ref[:, :], share[:, None],
                             preferred_element_type=jnp.float32,
                             precision=HIGHEST)[:, 0]
        pool_over = jnp.dot(pq_onehot_ref[:, :],
                            over.astype(jnp.float32)[:, None],
                            preferred_element_type=jnp.float32,
                            precision=HIGHEST)[:, 0] > 0.0
        cursor = v_pcursor[:, 0]
        njobs = pnjobs_ref[:, 0]
        pool_ok = (cursor < njobs) & ~pool_over             # [P8]
        ns_has = jnp.dot(pn_onehot_ref[:, :],
                         pool_ok.astype(jnp.float32)[:, None],
                         preferred_element_type=jnp.float32,
                         precision=HIGHEST)[:, 0] > 0.0
        if ns_live:
            ns_alloc = v_nsalloc[:, :]
            total = nstotal_ref[0:1, :]
            nfrac = jnp.where(total > 0.0,
                              div_rn(ns_alloc,
                                     jnp.where(total > 0.0, total, 1.0)),
                              jnp.where(ns_alloc == 0.0, 0.0, 1.0))
            ns_key = div_rn(jnp.max(nfrac, axis=1), nsweight_ref[:, 0])
        else:
            # Mosaic's iota is integer-only: build it as i32, then cast
            ns_key = jax.lax.broadcasted_iota(
                jnp.int32, (ns_has.shape[0], 1), 0)[:, 0].astype(jnp.float32)
        ns_sel = _first_argmin(jnp.where(ns_has, ns_key, BIG))
        ns_row = pn_onehot_ref[pl.ds(ns_sel, 1), :]         # [1, P8]
        eligible = pool_ok & (ns_row[0, :] > 0.0)
        p = _first_argmin(jnp.where(eligible, pool_share, BIG))
        ok = jnp.any(eligible)
        return jnp.where(ok, p, -1)

    @pl.when(t == 0)
    def _init():
        v_idle[:, :] = idle0_ref[:, :]
        v_future[:, :] = future0_ref[:, :]
        v_ck_idle[:, :] = idle0_ref[:, :]
        v_ck_future[:, :] = future0_ref[:, :]
        v_ntasks[:, :] = ntasks0_ref[:, :]
        v_ck_ntasks[:, :] = ntasks0_ref[:, :]
        v_pack[:, :] = jnp.zeros_like(v_pack)
        v_qalloc[:, :] = qalloc0_ref[:, :]
        v_nsalloc[:, :] = nsalloc0_ref[:, :]
        v_pcursor[:, :] = jnp.zeros_like(v_pcursor)
        v_placedres[:, :] = jnp.zeros_like(v_placedres)
        for pi in range(sc_cursor.shape[0]):
            sc_cursor[pi] = 0
        sc[CUR_BUCKET] = -1
        sc[PREV_G] = -1
        sc[T_OFF] = 0
        sc[PLACED] = 0
        sc[PLACED_ALLOC] = 0
        p0 = pool_select()
        sc[CUR_P] = p0
        sc[CUR_JOB] = jnp.where(p0 >= 0, s_pool_jstart[jnp.maximum(p0, 0)], -1)

    active = sc[CUR_JOB] >= 0
    job = jnp.maximum(sc[CUR_JOB], 0)
    t_off = sc[T_OFF]
    t_idx = jnp.clip(s_job_start[job] + t_off, 0, s_task_group.shape[0] - 1)
    g = s_task_group[t_idx]
    valid = (g >= 0) & active & (t_off < s_job_ntasks[job])
    g_safe = jnp.maximum(g, 0)

    # fetch the group's masked static-score row when the group changes
    @pl.when(g_safe != sc[PREV_G])
    def _fetch():
        dma = pltpu.make_async_copy(gscore_hbm.at[g_safe], v_grow, sem)
        dma.start()
        dma.wait()

    sc[PREV_G] = g_safe

    req_row = group_req_ref[pl.ds(g_safe, 1), :]            # [1, RP]
    static_row = v_grow[0:1, :]                             # [1, Np]
    static_ok = static_row > MASK_THRESH

    pods_ok = (maxtasks_ref[0:1, :] == 0) | \
        (v_ntasks[0:1, :] < maxtasks_ref[0:1, :])
    base_ok = static_ok & pods_ok & valid

    # fits + score terms, resource loop unrolled (static python range)
    fits_idle = base_ok
    fits_future = base_ok
    bp_num = jnp.zeros_like(static_row)        # binpack weighted sum
    bp_wsum = jnp.float32(1e-9)
    lr_sum = jnp.zeros_like(static_row)        # least/most (cpu+mem)
    mr_sum = jnp.zeros_like(static_row)
    frac_cpu = jnp.zeros_like(static_row)
    frac_mem = jnp.zeros_like(static_row)
    for r in range(n_res):
        req_r = req_row[0, r]
        eps_r = eps_ref[0, r]
        idle_r = v_idle[r:r + 1, :]
        fut_r = v_future[r:r + 1, :]
        alloc_r = alloc_ref[r:r + 1, :]
        fits_idle = fits_idle & (req_r <= idle_r + eps_r)
        fits_future = fits_future & (req_r <= fut_r + eps_r)
        used_r = alloc_r - idle_r
        # binpack (score.py binpack_score)
        w_r = w_ref[0, W_RES + r]
        requested = (req_r > 0) & (w_r > 0)
        denom_ok = alloc_r > 0
        frac = jnp.where(denom_ok,
                         (used_r + req_r) / jnp.maximum(alloc_r, 1e-9), 2.0)
        # a dim that overflows scores 0 (binpack.go: usedFinally >
        # capacity), tested on the operands: the chip's division can
        # round a / a above 1
        per_res = jnp.where(used_r + req_r <= alloc_r, frac * 100.0, 0.0)
        bp_num = bp_num + jnp.where(requested, w_r, 0.0) * per_res
        bp_wsum = bp_wsum + jnp.where(requested, w_r, 0.0)
        if r < 2:
            a = alloc_r
            u = used_r + req_r
            lr = jnp.where(a > 0,
                           jnp.clip(a - u, 0.0, None) / jnp.maximum(a, 1e-9),
                           0.0)
            mr = jnp.where(a > 0,
                           jnp.clip(u, 0.0, a) / jnp.maximum(a, 1e-9), 0.0)
            lr_sum = lr_sum + lr * 100.0
            mr_sum = mr_sum + mr * 100.0
            fr = jnp.where(a > 0, u / jnp.maximum(a, 1e-9), 0.0)
            if r == 0:
                frac_cpu = fr
            else:
                frac_mem = fr

    w_binpack = w_ref[0, 0]
    w_least = w_ref[0, 1]
    w_most = w_ref[0, 2]
    w_balanced = w_ref[0, 3]
    score = w_binpack * (bp_num / bp_wsum) \
        + w_least * (lr_sum / 2.0) \
        + w_most * (mr_sum / 2.0) \
        + w_balanced * (100.0 - jnp.abs(frac_cpu - frac_mem) * 100.0)

    # task-topology pack attraction
    b = s_group_bucket[g_safe]
    same_bucket = (b >= 0) & (b == sc[CUR_BUCKET])
    pack_bonus = s_pack_milli[g_safe].astype(jnp.float32) / 1024.0
    pack = jnp.where(same_bucket, v_pack[0:1, :], 0.0)
    score = score + static_row + pack * pack_bonus

    any_idle = jnp.any(fits_idle)
    if allow_pipeline:
        # boolean algebra instead of where(): Mosaic cannot select i1 vectors
        cand = (fits_idle & any_idle) | (fits_future & ~any_idle)
    else:
        cand = fits_idle
    masked = jnp.where(cand, score, NEG)
    lane_ids = jax.lax.broadcasted_iota(jnp.int32, v_pack.shape, 1)
    sel = jnp.min(jnp.where(masked == jnp.max(masked), lane_ids,
                            masked.shape[1]))   # lowest index of the max
    placed_ok = jnp.any(cand)
    if allow_pipeline:
        pipelined = placed_ok & ~any_idle
    else:
        pipelined = jnp.bool_(False)
    take_idle = placed_ok & ~pipelined

    sel_lane = lane_ids == sel                              # [1, Np]

    for r in range(n_res):
        req_r = req_row[0, r]
        v_idle[r:r + 1, :] = v_idle[r:r + 1, :] - jnp.where(
            sel_lane & take_idle, req_r, 0.0)
        v_future[r:r + 1, :] = v_future[r:r + 1, :] - jnp.where(
            sel_lane & placed_ok, req_r, 0.0)
    v_ntasks[:, :] = v_ntasks[:, :] + jnp.where(
        sel_lane & placed_ok, 1, 0)
    sc[CUR_BUCKET] = jnp.where(valid, b, sc[CUR_BUCKET])
    v_pack[:, :] = pack + jnp.where(
        sel_lane & placed_ok & valid, 1.0, 0.0)

    new_t_off = t_off + jnp.where(active, 1, 0)
    placed = sc[PLACED] + placed_ok.astype(jnp.int32)
    placed_alloc = sc[PLACED_ALLOC] + take_idle.astype(jnp.int32)
    # placed_res accumulates on the first RP lanes of a [1, LANE] row
    req_as_row = jnp.pad(req_row, ((0, 0), (0, LANE - req_row.shape[1])))
    v_placedres[:, :] = v_placedres[:, :] + jnp.where(placed_ok, req_as_row, 0.0)

    # ---- job boundary: gang commit/rollback + queue charge + next select
    complete = active & (new_t_off >= s_job_ntasks[job])
    base = s_job_base[job]
    minavail = s_job_minavail[job]
    is_ready = complete & (base + placed_alloc >= minavail)
    is_kept = complete & (base + placed >= minavail)
    keep = is_ready | is_kept
    roll = complete & ~keep

    v_idle[:, :] = jnp.where(roll, v_ck_idle[:, :], v_idle[:, :])
    v_future[:, :] = jnp.where(roll, v_ck_future[:, :], v_future[:, :])
    v_ntasks[:, :] = jnp.where(roll, v_ck_ntasks[:, :], v_ntasks[:, :])
    v_ck_idle[:, :] = jnp.where(complete, v_idle[:, :], v_ck_idle[:, :])
    v_ck_future[:, :] = jnp.where(complete, v_future[:, :], v_ck_future[:, :])
    v_ck_ntasks[:, :] = jnp.where(complete, v_ntasks[:, :], v_ck_ntasks[:, :])

    p = jnp.maximum(sc[CUR_P], 0)
    q = s_pool_queue[p]
    ns = s_pool_ns[p]
    qrow_ids = jax.lax.broadcasted_iota(jnp.int32, v_qalloc.shape, 0)
    charge = jnp.where((qrow_ids == q) & keep, v_placedres[0:1, :], 0.0)
    v_qalloc[:, :] = v_qalloc[:, :] + charge
    nsrow_ids = jax.lax.broadcasted_iota(jnp.int32, v_nsalloc.shape, 0)
    v_nsalloc[:, :] = v_nsalloc[:, :] + jnp.where(
        (nsrow_ids == ns) & keep, v_placedres[0:1, :], 0.0)
    prow_ids = jax.lax.broadcasted_iota(jnp.int32, v_pcursor.shape, 0)
    v_pcursor[:, :] = v_pcursor[:, :] + jnp.where(
        (prow_ids == p) & complete, 1, 0)
    sc_cursor[p] = sc_cursor[p] + jnp.where(complete, 1, 0)

    # next (pool, job)
    np_ = pool_select()
    np_safe = jnp.maximum(np_, 0)
    njob = jnp.where(np_ >= 0,
                     s_pool_jstart[np_safe] + sc_cursor[np_safe], -1)
    sc[CUR_P] = jnp.where(complete, np_, sc[CUR_P])
    sc[CUR_JOB] = jnp.where(complete, njob, sc[CUR_JOB])
    sc[T_OFF] = jnp.where(complete, 0, new_t_off)
    sc[PLACED] = jnp.where(complete, 0, placed)
    sc[PLACED_ALLOC] = jnp.where(complete, 0, placed_alloc)
    v_placedres[:, :] = jnp.where(complete, 0.0, v_placedres[:, :])

    # ---- emit this step's decisions (8 steps share one SMEM block row-wise)
    row = t % 8
    emit_ref[row, E_TIDX] = jnp.where(valid, t_idx, -1)
    emit_ref[row, E_SEL] = jnp.where(placed_ok & valid, sel, -1)
    emit_ref[row, E_PIPE] = (pipelined & valid).astype(jnp.int32)
    emit_ref[row, E_DJOB] = jnp.where(complete, job, -1)
    emit_ref[row, E_READY] = is_ready.astype(jnp.int32)
    emit_ref[row, E_KEPT] = is_kept.astype(jnp.int32)
    emit_ref[row, 6] = 0
    emit_ref[row, 7] = 0


@functools.partial(jax.jit,
                   static_argnames=("allow_pipeline", "n_res", "ns_live",
                                    "interpret"))
def _pallas_gang_allocate(s_task_group, s_job_start, s_job_ntasks,
                          s_job_minavail, s_job_base, s_pool_jstart,
                          s_pool_njobs, s_pool_queue, s_pool_ns,
                          s_group_bucket, s_pack_milli,
                          group_req, qdes, qalloc0, pnjobs,
                          pq_onehot, pn_onehot, nsalloc0, nstotal, nsweight,
                          idle0, future0, alloc, ntasks0, maxtasks,
                          eps_row, w_row, gscore,
                          *, n_res: int, allow_pipeline: bool,
                          ns_live: bool, interpret: bool = False):
    T = int(s_task_group.shape[0])
    kernel = functools.partial(_kernel, n_res=n_res,
                               allow_pipeline=allow_pipeline,
                               ns_live=ns_live)
    RP, Np = idle0.shape
    Q8 = qdes.shape[0]
    P8 = pnjobs.shape[0]
    NS8 = nsalloc0.shape[0]
    emits = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=11,
            grid=(T,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),   # group_req
                pl.BlockSpec(memory_space=pltpu.VMEM),   # qdes
                pl.BlockSpec(memory_space=pltpu.VMEM),   # qalloc0
                pl.BlockSpec(memory_space=pltpu.VMEM),   # pnjobs
                pl.BlockSpec(memory_space=pltpu.VMEM),   # pq_onehot
                pl.BlockSpec(memory_space=pltpu.VMEM),   # pn_onehot
                pl.BlockSpec(memory_space=pltpu.VMEM),   # nsalloc0
                pl.BlockSpec(memory_space=pltpu.VMEM),   # nstotal
                pl.BlockSpec(memory_space=pltpu.VMEM),   # nsweight
                pl.BlockSpec(memory_space=pltpu.VMEM),   # idle0
                pl.BlockSpec(memory_space=pltpu.VMEM),   # future0
                pl.BlockSpec(memory_space=pltpu.VMEM),   # alloc
                pl.BlockSpec(memory_space=pltpu.VMEM),   # ntasks0
                pl.BlockSpec(memory_space=pltpu.VMEM),   # maxtasks
                pl.BlockSpec(memory_space=pltpu.VMEM),   # eps
                pl.BlockSpec(memory_space=pltpu.VMEM),   # weights
                pl.BlockSpec(memory_space=pl.ANY),    # gscore (HBM)
            ],
            out_specs=pl.BlockSpec((8, 8), lambda t, *_: (t // 8, 0),
                                   memory_space=pltpu.SMEM),
            scratch_shapes=[
                pltpu.VMEM((RP, Np), jnp.float32),       # v_idle
                pltpu.VMEM((RP, Np), jnp.float32),       # v_future
                pltpu.VMEM((RP, Np), jnp.float32),       # v_ck_idle
                pltpu.VMEM((RP, Np), jnp.float32),       # v_ck_future
                pltpu.VMEM((1, Np), jnp.int32),          # v_ntasks
                pltpu.VMEM((1, Np), jnp.int32),          # v_ck_ntasks
                pltpu.VMEM((1, Np), jnp.float32),        # v_pack
                pltpu.VMEM((1, Np), jnp.float32),        # v_grow
                pltpu.VMEM((Q8, LANE), jnp.float32),     # v_qalloc
                pltpu.VMEM((NS8, LANE), jnp.float32),    # v_nsalloc
                pltpu.VMEM((P8, LANE), jnp.int32),       # v_pcursor
                pltpu.VMEM((1, LANE), jnp.float32),      # v_placedres
                pltpu.SMEM((16,), jnp.int32),            # sc
                pltpu.SMEM((P8,), jnp.int32),            # sc_cursor
                pltpu.SemaphoreType.DMA(()),             # sem
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((((T + 7) // 8) * 8, 8), jnp.int32),
        interpret=interpret,
    )(s_task_group, s_job_start, s_job_ntasks, s_job_minavail, s_job_base,
      s_pool_jstart, s_pool_njobs, s_pool_queue, s_pool_ns, s_group_bucket,
      s_pack_milli,
      group_req, qdes, qalloc0, pnjobs, pq_onehot, pn_onehot, nsalloc0,
      nstotal, nsweight, idle0, future0, alloc, ntasks0,
      maxtasks, eps_row, w_row, gscore)
    return emits


def gang_allocate_pallas(task_group, task_job, task_valid, group_req,
                         group_mask, group_static_score, task_bucket,
                         group_pack_bonus, job_min_available, job_ready_base,
                         job_task_start, job_n_tasks, job_queue,
                         pool_queue, pool_ns, pool_job_start, pool_njobs,
                         ns_weight, ns_alloc0, ns_total, queue_deserved,
                         queue_alloc0, node_idle, node_future, node_alloc,
                         node_ntasks, node_max_tasks, eps,
                         weights: ScoreWeights, allow_pipeline: bool = True,
                         ns_live: bool = False, interpret: bool = False):
    """Drop-in for ops.allocate.gang_allocate, returning
    (assign, pipelined, ready, kept, None).

    Namespace fairness is first-class: jobs are encoded in (namespace,
    queue) POOLS and every job boundary re-selects the namespace — by live
    weighted dominant share over the in-kernel ns allocations when
    ``ns_live`` (drf's NamespaceOrderFn, allocate.go:120-139), else by the
    encode's static namespace rank — then the best non-overused queue
    within it (single-namespace batches degenerate to the previous
    queue-only selection exactly).

    The group-bucket reduction needs host numpy (scatter by group), so it
    runs here; everything else is one jitted program — the wrapper's ~30
    individual op dispatches cost real latency per call."""
    G = int(group_req.shape[0])
    # group_bucket from per-task buckets (uniform within a group by
    # construction; see solver.place bucket_fn keyed on job+task annotations)
    tb = np.asarray(task_bucket)
    tg = np.asarray(task_group)
    gb = np.full(G, -1, np.int32)
    valid_np = np.asarray(task_valid, bool)
    sel = valid_np & (tb >= 0)
    gb[tg[sel]] = tb[sel]
    return _gang_allocate_pallas_jit(
        jnp.asarray(task_group, jnp.int32), jnp.asarray(task_job),
        jnp.asarray(task_valid, bool), jnp.asarray(group_req, jnp.float32),
        jnp.asarray(group_mask, bool),
        jnp.asarray(group_static_score, jnp.float32),
        jnp.asarray(gb), jnp.asarray(group_pack_bonus, jnp.float32),
        jnp.asarray(job_min_available, jnp.int32),
        jnp.asarray(job_ready_base, jnp.int32),
        jnp.asarray(job_task_start, jnp.int32),
        jnp.asarray(job_n_tasks, jnp.int32),
        jnp.asarray(pool_queue, jnp.int32),
        jnp.asarray(pool_ns, jnp.int32),
        jnp.asarray(pool_job_start, jnp.int32),
        jnp.asarray(pool_njobs, jnp.int32),
        jnp.asarray(ns_weight, jnp.float32),
        jnp.asarray(ns_alloc0, jnp.float32),
        jnp.asarray(ns_total, jnp.float32),
        jnp.asarray(queue_deserved, jnp.float32),
        jnp.asarray(queue_alloc0, jnp.float32),
        jnp.asarray(node_idle, jnp.float32),
        jnp.asarray(node_future, jnp.float32),
        jnp.asarray(node_alloc, jnp.float32),
        jnp.asarray(node_ntasks, jnp.int32),
        jnp.asarray(node_max_tasks, jnp.int32),
        jnp.asarray(eps, jnp.float32), weights,
        allow_pipeline=allow_pipeline, ns_live=bool(ns_live),
        interpret=interpret)


@partial(jax.jit, static_argnames=("allow_pipeline", "ns_live", "interpret"))
def _gang_allocate_pallas_jit(task_group, task_job, task_valid, group_req,
                              group_mask, group_static_score, gb,
                              group_pack_bonus, job_min_available,
                              job_ready_base, job_task_start, job_n_tasks,
                              pool_queue, pool_ns, pool_job_start,
                              pool_njobs, ns_weight, ns_alloc0, ns_total,
                              queue_deserved, queue_alloc0, node_idle,
                              node_future, node_alloc, node_ntasks,
                              node_max_tasks, eps, weights: ScoreWeights,
                              allow_pipeline: bool = True,
                              ns_live: bool = False,
                              interpret: bool = False):
    T = int(task_group.shape[0])
    J = int(job_min_available.shape[0])
    G = int(group_req.shape[0])
    N = int(node_idle.shape[0])
    R = int(group_req.shape[1])
    assert fits_resources(R), \
        f"resource axis {R} exceeds the kernel's {R_PAD_MAX} sublanes"
    RP = resource_pad(R)
    Np = ((N + LANE - 1) // LANE) * LANE
    Q = int(queue_deserved.shape[0])
    Q8 = max(8, ((Q + 7) // 8) * 8)
    P = int(pool_queue.shape[0])
    P8 = max(8, ((P + 7) // 8) * 8)
    NS = int(ns_weight.shape[0])
    NS8 = max(8, ((NS + 7) // 8) * 8)
    G8 = ((G + 7) // 8) * 8

    s_task_group = jnp.where(jnp.asarray(task_valid, bool),
                             task_group, -1).astype(jnp.int32)
    pack_milli = (jnp.asarray(group_pack_bonus, jnp.float32) * 1024.0)
    pack_milli = _pad_to(pack_milli.astype(jnp.int32), G, 0)

    # masked static score rows: -1e30 where predicates fail or lanes padded.
    # Shape [G, 1, Np]: row DMA slices must cover whole (8,128) tiles, so
    # the tiled trailing dims are (1, Np) and .at[g] is a full-tile slice.
    gscore = jnp.where(jnp.asarray(group_mask, bool),
                       jnp.asarray(group_static_score, jnp.float32), NEG)
    gscore = _pad_to(gscore, Np, axis=1, value=NEG)[:, None, :]

    group_req_p = _pad_to(_pad_to(jnp.asarray(group_req, jnp.float32),
                                  RP, 1), G8, 0)

    def tr_nodes(x):   # [N, R] -> [RP, Np]
        x = jnp.asarray(x, jnp.float32)
        return _pad_to(_pad_to(x, RP, 1).T, Np, 1)

    def row_nodes(x, dtype=jnp.int32):   # [N] -> [1, Np]
        return _pad_to(jnp.asarray(x, dtype)[None, :], Np, 1)

    qdes = _pad_to(_pad_to(jnp.asarray(queue_deserved, jnp.float32),
                           LANE, 1, value=np.inf), Q8, 0, value=np.inf)
    qdes = jnp.where(jnp.isinf(qdes), BIG * 2.0, qdes)
    qalloc0_p = _pad_to(_pad_to(jnp.asarray(queue_alloc0, jnp.float32),
                                LANE, 1), Q8, 0)
    pnjobs = jnp.broadcast_to(
        _pad_to(jnp.asarray(pool_njobs, jnp.int32), P8, 0)[:, None],
        (P8, LANE))
    pq_p = _pad_to(jnp.asarray(pool_queue, jnp.int32), P8, 0)
    pns_p = _pad_to(jnp.asarray(pool_ns, jnp.int32), P8, 0)
    pjs_p = _pad_to(jnp.asarray(pool_job_start, jnp.int32), P8, 0)
    pnj_p = _pad_to(jnp.asarray(pool_njobs, jnp.int32), P8, 0)
    # one-hot maps for the in-kernel share/eligibility matmuls; padding
    # pools keep all-zero rows (their njobs is 0 -> never eligible)
    live_pool = (jnp.arange(P8) < P)[:, None]
    pq_onehot = jnp.where(
        live_pool & (jnp.arange(Q8)[None, :] == pq_p[:, None]),
        1.0, 0.0).astype(jnp.float32)                        # [P8, Q8]
    pn_onehot = jnp.where(
        (jnp.arange(NS8)[:, None] == pns_p[None, :]) & live_pool.T,
        1.0, 0.0).astype(jnp.float32)                        # [NS8, P8]
    nsalloc0_p = _pad_to(_pad_to(jnp.asarray(ns_alloc0, jnp.float32),
                                 LANE, 1), NS8, 0)
    nstotal_row = _pad_to(jnp.asarray(ns_total, jnp.float32)[None, :],
                          LANE, 1)
    nsweight_p = jnp.broadcast_to(
        _pad_to(jnp.maximum(jnp.asarray(ns_weight, jnp.float32), 1e-9),
                NS8, 0, value=1.0)[:, None], (NS8, LANE))

    eps_row = _pad_to(jnp.asarray(eps, jnp.float32)[None, :], LANE, 1)
    w_row = jnp.zeros((1, LANE), jnp.float32)
    w_row = w_row.at[0, 0].set(weights.binpack)
    w_row = w_row.at[0, 1].set(weights.least)
    w_row = w_row.at[0, 2].set(weights.most)
    w_row = w_row.at[0, 3].set(weights.balanced)
    w_row = jax.lax.dynamic_update_slice(
        w_row, _pad_to(weights.binpack_res[None, :], RP, 1), (0, W_RES))

    emits = _pallas_gang_allocate(
        s_task_group,
        jnp.asarray(job_task_start, jnp.int32),
        jnp.asarray(job_n_tasks, jnp.int32),
        jnp.asarray(job_min_available, jnp.int32),
        jnp.asarray(job_ready_base, jnp.int32),
        pjs_p, pnj_p, pq_p, pns_p,
        jnp.asarray(gb), pack_milli,
        group_req_p, qdes, qalloc0_p, pnjobs,
        pq_onehot, pn_onehot, nsalloc0_p, nstotal_row, nsweight_p,
        tr_nodes(node_idle), tr_nodes(node_future), tr_nodes(node_alloc),
        row_nodes(node_ntasks), row_nodes(node_max_tasks),
        eps_row, w_row, gscore,
        n_res=R, allow_pipeline=allow_pipeline, ns_live=ns_live,
        interpret=interpret)

    # reconstruct task-order outputs from the per-step emission stream
    emits = emits[:T]   # drop the padded tail rows (never written)
    emit_t = emits[:, E_TIDX]
    emit_sel = emits[:, E_SEL]
    emit_pipe = emits[:, E_PIPE].astype(bool)
    done_job = emits[:, E_DJOB]
    done_ready = emits[:, E_READY].astype(bool)
    done_kept = emits[:, E_KEPT].astype(bool)

    slot_t = jnp.where(emit_t >= 0, emit_t, T)
    assign = jnp.full(T + 1, -1, jnp.int32).at[slot_t].set(emit_sel)[:T]
    pipelined = jnp.zeros(T + 1, bool).at[slot_t].set(emit_pipe)[:T]
    slot_j = jnp.where(done_job >= 0, done_job, J)
    ready = jnp.zeros(J + 1, bool).at[slot_j].max(done_ready)[:J]
    kept = jnp.zeros(J + 1, bool).at[slot_j].max(done_kept)[:J]

    ok = (ready[jnp.asarray(task_job)] | kept[jnp.asarray(task_job)]) \
        & jnp.asarray(task_valid, bool)
    assign = jnp.where(ok, assign, -1)
    pipelined = pipelined & ok
    return assign, pipelined, ready, kept, None
