"""Victim-selection kernels for preempt/reclaim.

TPU-native replacement for the reference's per-node victim loops
(pkg/scheduler/actions/preempt/preempt.go:237-251 "evict cheapest-first
until FutureIdle fits" and pkg/scheduler/actions/reclaim/reclaim.go:153-166
"evict until reclaimed covers the request"): the eviction-ordered victim
resources are cumulatively summed along the victim axis and the smallest
feasible prefix found with one comparison + argmax per node -- the
cumsum/searchsorted form of the sequential pop-until-fit loop -- with all
nodes evaluated at once.

ValidateVictims (pkg/scheduler/util/scheduler_helper.go:239-252) is folded
in: a node is only feasible when it has at least one victim and the full
victim set plus the base availability covers the request.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30   # plain float: a module-level jnp constant would
              # initialize the device backend at import time


@jax.jit
def victim_prefix(req: jax.Array,          # [R] preemptor request
                  node_ok: jax.Array,      # [N] bool (predicates passed)
                  base_avail: jax.Array,   # [N, R] avail before any eviction
                  victim_res: jax.Array,   # [N, V, R] eviction-order sorted
                  victim_valid: jax.Array,  # [N, V] bool
                  eps: jax.Array):         # [R]
    """Per node, the smallest victim prefix whose release makes ``req`` fit.

    Returns (feasible [N] bool, n_evict [N] i32):
      feasible: node passed predicates, has >=1 victim, and evicting *all*
        its victims (plus base_avail) would cover req -- ValidateVictims;
      n_evict: length of the shortest feasible prefix (0 when req already
        fits base_avail), clipped to the valid victim count.
    """
    v = victim_res.shape[1]
    vmask = victim_valid[..., None]
    cum = jnp.cumsum(jnp.where(vmask, victim_res, 0.0), axis=1)   # [N,V,R]
    cum0 = jnp.concatenate([jnp.zeros_like(cum[:, :1]), cum], axis=1)
    avail = base_avail[:, None, :] + cum0                          # [N,V+1,R]
    fits = jnp.all(req[None, None, :] <= avail + eps[None, None, :],
                   axis=-1)                                        # [N,V+1]
    n_valid = jnp.sum(victim_valid, axis=1).astype(jnp.int32)      # [N]
    ks = jnp.arange(v + 1, dtype=jnp.int32)
    feas_k = fits & (ks[None, :] <= n_valid[:, None])
    any_k = jnp.any(feas_k, axis=1)
    n_evict = jnp.argmax(feas_k, axis=1).astype(jnp.int32)
    feasible = node_ok & (n_valid > 0) & any_k
    return feasible, jnp.where(feasible, n_evict, 0)


@jax.jit
def pick_best_node(feasible: jax.Array, score: jax.Array):
    """Highest-scoring feasible node or -1 (SortNodes + first-feasible,
    preempt.go:206-267)."""
    best = jnp.argmax(jnp.where(feasible, score, NEG)).astype(jnp.int32)
    return jnp.where(jnp.any(feasible), best, -1)


@jax.jit
def reclaim_prefix(req: jax.Array,          # [R]
                   node_ok: jax.Array,      # [N] bool
                   future_idle: jax.Array,  # [N, R] for ValidateVictims
                   victim_res: jax.Array,   # [N, V, R] plugin-order
                   victim_valid: jax.Array,  # [N, V] bool
                   eps: jax.Array):
    """Reclaim's variant (reclaim.go:149-181): victims are evicted in plugin
    order until their summed resources *alone* cover the request (FutureIdle
    is only consulted by ValidateVictims, not the stop condition).

    Returns (feasible [N], n_evict [N], covered [N]):
      n_evict: victims to evict (all valid ones when coverage never reached);
      covered: whether the evicted prefix's sum covers req (pipeline gate).
    """
    v = victim_res.shape[1]
    vmask = victim_valid[..., None]
    cum = jnp.cumsum(jnp.where(vmask, victim_res, 0.0), axis=1)    # [N,V,R]
    covers = jnp.all(req[None, None, :] <= cum + eps[None, None, :],
                     axis=-1)                                       # [N,V]
    n_valid = jnp.sum(victim_valid, axis=1).astype(jnp.int32)
    ks = jnp.arange(1, v + 1, dtype=jnp.int32)
    feas_k = covers & (ks[None, :] <= n_valid[:, None])
    any_k = jnp.any(feas_k, axis=1)
    first = jnp.argmax(feas_k, axis=1).astype(jnp.int32) + 1       # prefix len
    n_evict = jnp.where(any_k, first, n_valid)
    # ValidateVictims: future idle + all victims covers req, >=1 victim
    total = jnp.sum(jnp.where(vmask, victim_res, 0.0), axis=1)
    validate = jnp.all(req[None, :] <= future_idle + total + eps[None, :],
                       axis=-1)
    feasible = node_ok & (n_valid > 0) & validate
    return feasible, jnp.where(feasible, n_evict, 0), any_k & feasible
