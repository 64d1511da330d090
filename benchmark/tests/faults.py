"""Faults planted underneath the timed path, for the control and the
fault tests: the program's placement kernel is wrapped so that what the
rest of the cycle receives is altered where it is produced.

* ``control``: the program's answer replaced by the plain reference's,
  computed in bfloat16 (the precision below the float32 that the
  configuration states) on the same inputs;
* ``alter``: one placed task (the middle one) moved to another node;
* ``drop_half``: every other placed job dropped, as if half the batch
  were left out.

And two faults above the kernel, in the layers the cells' ``why`` names:

* ``prune_drop``: pruning's shortlist cut to one node, and every pair
  marked complete and fully covered, so that the loss guards stay
  silent;
* ``mask_drop``: the context build's static mask loses one node for
  every group.
"""

import numpy as np


def _host(args, kwargs, out):
    a = [np.asarray(x) for x in args[:28]]
    a.append(tuple(np.asarray(x) for x in args[28]))
    return {"args": a, "kwargs": kwargs,
            "out": tuple(np.asarray(x) for x in out[:4])}


def _control(call):
    import ml_dtypes
    from check import reference
    return reference.place(call, ml_dtypes.bfloat16)


def _alter(call):
    assign, pipe, ready, kept = (x.copy() for x in call["out"])
    placed = np.flatnonzero(assign >= 0)
    if placed.size:
        t = placed[placed.size // 2]
        n = call["args"][22].shape[0]
        assign[t] = (assign[t] + n // 2) % n
    return assign, pipe, ready, kept


def _drop_half(call):
    assign, pipe, ready, kept = (x.copy() for x in call["out"])
    task_job = call["args"][1]
    jobs = np.flatnonzero(ready | kept)
    drop = jobs[::2]
    ready[drop] = False
    kept[drop] = False
    gone = np.isin(task_job, drop)
    assign[gone] = -1
    pipe[gone] = False
    return assign, pipe, ready, kept


KINDS = {"control": _control, "alter": _alter, "drop_half": _drop_half}


def _prune_drop():
    from volcano_tpu.ops import prune
    orig = prune.distill

    def distill(*args, **kwargs):
        ctx = orig(*args, **kwargs)
        if ctx.m_real > 1:
            ctx.set_union(ctx.union[:1])
            ctx.count = ctx.feasible.copy()
            ctx.coverage = np.ones_like(ctx.coverage)
        return ctx

    prune.distill = distill
    return lambda: setattr(prune, "distill", orig)


def _mask_drop():
    from volcano_tpu.framework.solver import BatchSolver
    orig = BatchSolver._apply_masks_and_scores

    def apply(self, gmask, *args, **kwargs):
        gmask, static = orig(self, gmask, *args, **kwargs)
        if isinstance(gmask, np.ndarray):
            gmask = gmask.copy()
            gmask[:, 0] = False
        else:
            gmask = gmask.at[:, 0].set(False)
        return gmask, static

    BatchSolver._apply_masks_and_scores = apply
    return lambda: setattr(BatchSolver, "_apply_masks_and_scores", orig)


LAYERS = {"prune_drop": _prune_drop, "mask_drop": _mask_drop}


def plant(kind: str):
    """Plant the fault; returns the function that takes it out."""
    if kind in LAYERS:
        return LAYERS[kind]()
    import functools
    import jax.numpy as jnp
    from volcano_tpu.ops import pallas_allocate as pa
    orig = pa.gang_allocate_pallas
    change = KINDS[kind]

    @functools.wraps(orig)
    def faulty(*args, **kwargs):
        out = orig(*args, **kwargs)
        new = change(_host(args, kwargs, out))
        return tuple(jnp.asarray(x) for x in new) + (None,)

    pa.gang_allocate_pallas = faulty

    def undo():
        pa.gang_allocate_pallas = orig
    return undo
