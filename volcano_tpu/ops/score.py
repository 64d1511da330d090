"""Node scoring kernels.

TPU-native replacement for the reference's map/reduce node scorers
(pkg/scheduler/util/scheduler_helper.go:130-192 PrioritizeNodes invoking the
nodeorder plugin's weighted k8s scorers, pkg/scheduler/plugins/nodeorder/
nodeorder.go:39-135, and binpack, pkg/scheduler/plugins/binpack/
binpack.go:200-260).

Dynamic terms (binpack / least / most / balanced) read the *current* idle
state, so they are evaluated inside the allocate scan as each placement
changes the landscape -- exactly the semantics of the reference's
task-at-a-time loop, but with the node dimension vectorized. Static terms
(node-affinity preference, taint PreferNoSchedule, task-topology buckets)
are precomputed per group x node and passed in as ``static_score``.

Weights are data (a ScoreWeights pytree), not compile-time constants, so
re-tuning plugin weights never recompiles.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class ScoreWeights(NamedTuple):
    """Score-term weights; zeros disable a term.

    binpack_res [R]: per-resource binpack weights (binpack.weight.cpu, ...)
    binpack [ ]   : overall binpack plugin weight
    least [ ]     : nodeorder leastrequested weight
    most [ ]      : nodeorder mostrequested weight
    balanced [ ]  : nodeorder balancedresource weight
    """
    binpack_res: jax.Array
    binpack: jax.Array
    least: jax.Array
    most: jax.Array
    balanced: jax.Array

    @classmethod
    def make(cls, r: int, binpack_res=None, binpack=0.0, least=1.0, most=0.0,
             balanced=1.0):
        import numpy as np
        br = np.ones(r, np.float32) if binpack_res is None else np.asarray(binpack_res, np.float32)
        return cls(jnp.asarray(br), jnp.float32(binpack), jnp.float32(least),
                   jnp.float32(most), jnp.float32(balanced))

    def host(self) -> "ScoreWeights":
        """Host-value copy (numpy array + python floats) for xp=numpy
        callers — converts device values ONCE instead of per call."""
        import numpy as np
        return ScoreWeights(np.asarray(self.binpack_res),
                            float(self.binpack), float(self.least),
                            float(self.most), float(self.balanced))


def binpack_score(req, used, alloc, w_res, xp=jnp):
    """Best-fit packing score, 0..100 (binpack.go:200-260).

    score_r = (used_r + req_r) * 100 / alloc_r for requested dims, weighted
    by w_res and normalized by the sum of participating weights.
    req [R], used [N,R], alloc [N,R] -> [N]. ``xp`` selects the array
    backend: jnp inside the kernels, numpy for host-side evaluation
    (framework/victims.py) — ONE implementation, no hand-kept mirror.
    """
    requested = (req > 0) & (w_res > 0)
    denom_ok = alloc > 0
    used_final = used + req[None, :]
    frac = xp.where(denom_ok, used_final / xp.maximum(alloc, 1e-9), 2.0)
    # nodes where a requested dim overflows alloc contribute 0 (binpack
    # returns 0 when usedFinally > allocatable), tested on the operands:
    # the chip's division can round a / a above 1
    per_res = xp.where(used_final <= alloc, frac * 100.0, 0.0)  # [N, R]
    w = xp.where(requested, w_res, 0.0)[None, :]               # [1, R]
    wsum = xp.maximum(xp.sum(xp.where(requested, w_res, 0.0)), 1e-9)
    return xp.sum(per_res * w, axis=-1) / wsum                 # [N]


def least_requested_score(req, used, alloc, xp=jnp):
    """(capacity - requested) * 100 / capacity over cpu+memory, averaged
    (k8s LeastAllocated via nodeorder.go)."""
    cpu_mem = slice(0, 2)
    a = alloc[:, cpu_mem]
    u = used[:, cpu_mem] + req[None, cpu_mem]
    frac = xp.where(a > 0, xp.clip((a - u), 0.0, None) / xp.maximum(a, 1e-9), 0.0)
    return xp.mean(frac * 100.0, axis=-1)


def most_requested_score(req, used, alloc, xp=jnp):
    cpu_mem = slice(0, 2)
    a = alloc[:, cpu_mem]
    u = used[:, cpu_mem] + req[None, cpu_mem]
    frac = xp.where(a > 0, xp.clip(u, 0.0, a) / xp.maximum(a, 1e-9), 0.0)
    return xp.mean(frac * 100.0, axis=-1)


def balanced_allocation_score(req, used, alloc, xp=jnp):
    """100 - |cpu_fraction - mem_fraction| * 100 (k8s BalancedAllocation)."""
    a = alloc[:, 0:2]
    u = used[:, 0:2] + req[None, 0:2]
    frac = xp.where(a > 0, u / xp.maximum(a, 1e-9), 0.0)
    return 100.0 - xp.abs(frac[:, 0] - frac[:, 1]) * 100.0


def node_score(req, idle, alloc, weights: ScoreWeights, static_bonus,
               xp=jnp):
    """Combined per-node score for one task against the current node state.

    used is derived from the idle/alloc invariant (used = alloc - idle for
    schedulable accounting), so the scan carries only idle.
    req [R], idle [N,R], alloc [N,R], static_bonus [N] -> [N].
    With xp=numpy, ``weights`` must hold host values (see
    ScoreWeights.host()).
    """
    used = alloc - idle
    s = weights.binpack * binpack_score(req, used, alloc, weights.binpack_res,
                                        xp)
    s = s + weights.least * least_requested_score(req, used, alloc, xp)
    s = s + weights.most * most_requested_score(req, used, alloc, xp)
    s = s + weights.balanced * balanced_allocation_score(req, used, alloc, xp)
    return s + static_bonus
