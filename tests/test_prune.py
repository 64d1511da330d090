"""Candidate pruning + two-level hierarchical placement
(docs/design/pruning.md, ops/prune.py): pruned-vs-dense bind parity
across shortlist widths on constrained and unconstrained fleets, the
shortlist-loss guard's fallback paths (proven RED without the guard),
two-level partition-winner correctness on skewed ShardPlans, and
breaker-ladder composition under pruning."""

import numpy as np
import pytest

from tests.harness import Harness
from volcano_tpu.metrics import metrics as m
from volcano_tpu.models.objects import TopologySpreadConstraint
from volcano_tpu.utils.test_utils import (build_node, build_pod,
                                          build_pod_group, build_queue)

ZONE = "topology.kubernetes.io/zone"

BASE_CONF = """
actions: "enqueue, allocate"
tiers:
- plugins:
  - name: priority
  - name: gang
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
"""


def conf_with_solver(**args):
    lines = "\n".join(f"    {k}: \"{v}\"" for k, v in args.items())
    return BASE_CONF + f"""
configurations:
- name: solver
  arguments:
{lines}
"""


def uniform_cluster(h, n_nodes=16, n_jobs=6, gang=4):
    h.add("queues", build_queue("default", weight=1))
    for i in range(n_nodes):
        h.add("nodes", build_node(f"node-{i}",
                                  {"cpu": "16", "memory": "32Gi"}))
    for j in range(n_jobs):
        h.add("podgroups", build_pod_group(f"pg-{j}", "ns1", "default",
                                           gang, phase="Inqueue"))
        for t in range(gang):
            h.add("pods", build_pod("ns1", f"p{j}-{t}", "", "Pending",
                                    {"cpu": "2", "memory": "4Gi"},
                                    f"pg-{j}"))
    return h


def constrained_cluster(h, zones=4, per_zone=4, n_jobs=8, gang=4):
    """Zoned topology + a hard-spread / plain mix (the constraint
    compiler's slot tensors engage, so the distillation must shortlist
    per (gang, domain) pair, not per gang)."""
    h.add("queues", build_queue("default", weight=1))
    i = 0
    for z in range(zones):
        for _ in range(per_zone):
            h.add("nodes", build_node(
                f"node-{i}", {"cpu": "16", "memory": "32Gi"},
                labels={ZONE: f"zone-{z}"}))
            i += 1
    for j in range(n_jobs):
        h.add("podgroups", build_pod_group(f"pg-{j}", "ns1", "default",
                                           gang, phase="Inqueue"))
        for t in range(gang):
            pod = build_pod("ns1", f"p{j}-{t}", "", "Pending",
                            {"cpu": "2", "memory": "4Gi"}, f"pg-{j}")
            if j % 2 == 0:
                pod.spec.topology_spread = [TopologySpreadConstraint(
                    max_skew=1, topology_key=ZONE,
                    when_unsatisfiable="DoNotSchedule")]
            h.add("pods", pod)
    return h


def run_cluster(build, conf):
    h = build(Harness(conf))
    h.run_actions("enqueue", "allocate").close_session()
    return h


def walk(span):
    yield span
    for c in span.children or ():
        yield from walk(c)


def run_traced(h):
    """One enqueue + allocate cycle under the flight recorder; returns
    every span of the cycle."""
    from volcano_tpu.trace import tracer
    h.open_session()
    tracer.enable()
    try:
        with tracer.cycle():
            h.run_actions("enqueue", "allocate")
        root = tracer.last_record().root
    finally:
        tracer.disable()
        tracer.reset()
    h.close_session()
    return list(walk(root))


def fallback_totals():
    from volcano_tpu.ops.prune import FALLBACK_REASONS
    return {r: m.counter_total(m.PRUNE_FALLBACK, reason=r)
            for r in FALLBACK_REASONS}


def prune_runs():
    return (m.counter_total(m.PRUNE_RUNS, level="single")
            + m.counter_total(m.PRUNE_RUNS, level="two_level"))


# ---------------------------------------------------------------------------
# pruned-vs-dense parity
# ---------------------------------------------------------------------------


class TestPrunedParity:
    @pytest.mark.parametrize("k", [4, 16, 64, 256])
    def test_uniform_fleet_bind_parity(self, k):
        """Bind-for-bind equivalence across the k sweep (k=256 covers
        the k >= N complete-shortlist case, which is bit-identical by
        construction); the pruned path must provably serve — a crash
        fallback would make the parity vacuous."""
        r0 = prune_runs()
        f0 = fallback_totals()
        pruned = run_cluster(uniform_cluster, conf_with_solver(
            **{"prune.enable": "true", "prune.k": k}))
        assert prune_runs() > r0
        assert fallback_totals() == f0
        dense = run_cluster(uniform_cluster, conf_with_solver(
            **{"prune.enable": "off"}))
        assert pruned.binds == dense.binds
        assert len(pruned.binds) == 24

    @pytest.mark.parametrize("k", [4, 16, 64])
    def test_constrained_fleet_bind_parity(self, k):
        """Same sweep on a zoned hard-spread fleet: the (gang, domain)
        pair shortlists must keep candidates in EVERY domain a rotating
        spread gang uses."""
        r0 = prune_runs()
        pruned = run_cluster(constrained_cluster, conf_with_solver(
            **{"prune.enable": "true", "prune.k": k}))
        assert prune_runs() > r0
        dense = run_cluster(constrained_cluster, conf_with_solver(
            **{"prune.enable": "off"}))
        assert pruned.binds == dense.binds
        assert len(pruned.binds) == 32

    def test_pruned_double_run_deterministic(self):
        a = run_cluster(constrained_cluster, conf_with_solver(
            **{"prune.enable": "true", "prune.k": 8}))
        b = run_cluster(constrained_cluster, conf_with_solver(
            **{"prune.enable": "true", "prune.k": 8}))
        assert a.binds == b.binds

    def test_off_restores_exact_path(self, monkeypatch):
        """`prune.enable: off` must never even distill."""
        import volcano_tpu.ops.prune as prune_mod

        def boom(*a, **k):
            raise AssertionError("distill ran with prune.enable: off")

        monkeypatch.setattr(prune_mod, "distill", boom)
        h = run_cluster(uniform_cluster, conf_with_solver(
            **{"prune.enable": "off"}))
        assert len(h.binds) == 24

    def test_auto_floor_keeps_small_fleets_unpruned(self, monkeypatch):
        """The default auto mode stays off below prune.min_nodes — the
        production default changes nothing for existing deployments
        under the floor."""
        import volcano_tpu.ops.prune as prune_mod

        def boom(*a, **k):
            raise AssertionError("distill ran below the auto floor")

        monkeypatch.setattr(prune_mod, "distill", boom)
        h = run_cluster(uniform_cluster, BASE_CONF)
        assert len(h.binds) == 24


# ---------------------------------------------------------------------------
# the shortlist-loss guard (red without it, green with it)
# ---------------------------------------------------------------------------


def tight_cluster(h):
    """Two IDENTICAL nodes and two single-task jobs that each need more
    than half a node: the session-open scores tie, so a k=1 shortlist
    holds only node-0 (lowest-index tie-break) for BOTH jobs — job 2
    can only place if the loss guard falls the cycle back to full
    width (the dense kernel would have placed it on node-1)."""
    h.add("queues", build_queue("default", weight=1))
    h.add("nodes", build_node("node-0", {"cpu": "16", "memory": "32Gi"}),
          build_node("node-1", {"cpu": "16", "memory": "32Gi"}))
    for j in range(2):
        h.add("podgroups", build_pod_group(f"pg-{j}", "ns1", "default", 1,
                                           phase="Inqueue"))
        h.add("pods", build_pod("ns1", f"p{j}", "", "Pending",
                                {"cpu": "10", "memory": "8Gi"}, f"pg-{j}"))
    return h


def skewed_cluster(h):
    """Three nodes at distinct fill levels -> distinct binpack scores ->
    nonzero shifted score mass beyond the top-1, and one pending job."""
    h.add("queues", build_queue("default", weight=1))
    for i, used in enumerate(("2", "6", "10")):
        h.add("nodes", build_node(f"node-{i}",
                                  {"cpu": "16", "memory": "32Gi"}))
        h.add("podgroups", build_pod_group(
            f"fill-{i}", "ns1", "default", 1, phase="Running"))
        h.add("pods", build_pod(
            "ns1", f"fill-{i}", f"node-{i}", "Running",
            {"cpu": used, "memory": "1Gi"}, f"fill-{i}"))
    h.add("podgroups", build_pod_group("pg-0", "ns1", "default", 1,
                                       phase="Inqueue"))
    h.add("pods", build_pod("ns1", "p0", "", "Pending",
                            {"cpu": "2", "memory": "2Gi"}, "pg-0"))
    return h


# a k=1 shortlist against a 0.99 coverage floor: the pre-kernel guard
LOW_COVERAGE_CONF = conf_with_solver(
    **{"prune.enable": "true", "prune.k": 1, "prune.coverage_floor": 0.99,
       "prune.demand_aware": "off"})


class TestLossGuard:
    def test_exhausted_shortlist_red_without_guard(self):
        """Proves the guard is load-bearing: with `prune.guard: off`
        (and the demand-aware widening off, so the raw k=1 truncation
        is what runs) the shortlist LOSES job 2's placement — node-0
        is full after job 1 and node-1 never made the shortlist."""
        f0 = fallback_totals()
        unguarded = run_cluster(tight_cluster, conf_with_solver(
            **{"prune.enable": "true", "prune.k": 1,
               "prune.guard": "off", "prune.coverage_floor": 0.0,
               "prune.demand_aware": "off"}))
        dense = run_cluster(tight_cluster, conf_with_solver(
            **{"prune.enable": "off"}))
        assert len(dense.binds) == 2
        assert len(unguarded.binds) == 1          # the lost placement
        assert fallback_totals() == f0

    def test_exhausted_shortlist_green_with_guard(self):
        f0 = fallback_totals()
        guarded = run_cluster(tight_cluster, conf_with_solver(
            **{"prune.enable": "true", "prune.k": 1,
               "prune.coverage_floor": 0.0,
               "prune.demand_aware": "off"}))
        dense = run_cluster(tight_cluster, conf_with_solver(
            **{"prune.enable": "off"}))
        assert guarded.binds == dense.binds
        assert len(guarded.binds) == 2
        f1 = fallback_totals()
        assert f1["shortlist_exhausted"] > f0["shortlist_exhausted"]

    def test_low_coverage_falls_back_before_the_kernel(self):
        """A k=1 shortlist over distinct static scores covers less of
        the feasible score mass than the floor: the pre-kernel guard
        must fall back (and the binds must equal the dense run's)."""
        f0 = fallback_totals()
        pruned = run_cluster(skewed_cluster, LOW_COVERAGE_CONF)
        dense = run_cluster(skewed_cluster, conf_with_solver(
            **{"prune.enable": "off"}))
        assert pruned.binds == dense.binds
        f1 = fallback_totals()
        assert f1["low_coverage"] > f0["low_coverage"]

    def test_pre_guard_fallback_tags_the_place_span(self):
        """A traced cycle says which guard sent the call to full width:
        the open ``solver.place`` span carries the reason and the pairs
        behind it."""
        h = skewed_cluster(Harness(LOW_COVERAGE_CONF))
        places = [s for s in run_traced(h) if s.name == "solver.place"]
        assert places
        assert places[0].tags["prune_fallback"] == "low_coverage"
        assert places[0].tags["fallback_pairs"] >= 1
        assert len(h.binds) == 1

    def test_demand_aware_widening_avoids_exhaustion(self):
        """A batch whose capacity demand exceeds k nodes would exhaust
        a static top-k shortlist every cycle; the demand-aware width
        must absorb it — every task places off the pruned run, no
        fallback fires."""
        def big_batch(h):
            return uniform_cluster(h, n_nodes=32, n_jobs=24, gang=4)

        f0 = fallback_totals()
        r0 = prune_runs()
        pruned = run_cluster(big_batch, conf_with_solver(
            **{"prune.enable": "true", "prune.k": 2}))
        assert len(pruned.binds) == 96
        assert prune_runs() > r0
        assert fallback_totals() == f0
        from volcano_tpu.trace import explain as ex
        last = ex.prune_report()["last"]
        assert last["k_max"] > 2          # the widening engaged

    def test_fallbacks_surface_on_the_explain_report(self):
        from volcano_tpu.trace import explain as ex
        ex.reset()
        run_cluster(tight_cluster, conf_with_solver(
            **{"prune.enable": "true", "prune.k": 1,
               "prune.coverage_floor": 0.0,
               "prune.demand_aware": "off"}))
        rep = ex.prune_report()
        assert rep["totals"]["fallbacks"].get("shortlist_exhausted")
        assert rep["last"]["fallback"] == "shortlist_exhausted"
        assert rep["last"]["k"] == 1
        ex.reset()


# ---------------------------------------------------------------------------
# two-level (partitioned) distillation
# ---------------------------------------------------------------------------


class _StubBatch:
    """Minimal TaskBatch surface for ops/prune.distill."""

    def __init__(self, group_req, task_group):
        self.group_req = np.asarray(group_req, np.float32)
        self.task_group = np.asarray(task_group, np.int32)
        self.task_valid = np.ones(len(task_group), bool)
        self.tasks = list(range(len(task_group)))
        self.n_groups = self.group_req.shape[0]
        self.task_slot = None
        self.slot_rows = None


class _StubNarr:
    def __init__(self, idle, allocatable):
        self.idle = np.asarray(idle, np.float32)
        self.allocatable = np.asarray(allocatable, np.float32)
        n = self.idle.shape[0]
        self.names = [f"n{i}" for i in range(n)]
        self.max_tasks = np.zeros(n, np.int32)
        self.n_tasks = np.zeros(n, np.int32)


class TestTwoLevel:
    def _problem(self, n=16):
        # one gang, one task; node 11 is the global best (emptiest under
        # least-requested scoring? use a static score ramp instead)
        import jax.numpy as jnp

        from volcano_tpu.ops.prune import PruneConf, distill
        from volcano_tpu.ops.score import ScoreWeights
        idle = np.full((n, 2), 8.0, np.float32)
        alloc = np.full((n, 2), 16.0, np.float32)
        static = np.zeros((1, n), np.float32)
        static[0] = np.arange(n)            # node n-1 is the global best
        gmask = np.ones((1, n), bool)
        batch = _StubBatch([[1.0, 1.0]], [0])
        narr = _StubNarr(idle, alloc)
        weights = ScoreWeights.make(2)
        return batch, narr, jnp.asarray(gmask), jnp.asarray(static), \
            weights, PruneConf, distill

    def test_skewed_plan_winner_partition_holds_global_best(self):
        """On a skewed ShardPlan (2-node partition 0 vs 14-node
        partition 1) the level-1 winner must be the partition holding
        the globally best node, and every distilled candidate must lie
        inside winning partitions."""
        from volcano_tpu.ops.sharded import ShardPlan
        batch, narr, gmask, static, weights, PruneConf, distill = \
            self._problem()
        plan = ShardPlan(2, 16, [0, 2, 16])     # skewed: 2 vs 14 rows
        conf = PruneConf(mode="true", k=4, partitions=1)
        ctx = distill(batch, narr, gmask, static, weights, conf,
                      plan=plan)
        assert ctx.level == "two_level"
        # partitions=1: all candidates from partition 1 (rows 2..15),
        # which holds the global best node 15
        assert 15 in ctx.union.tolist()
        assert all(u >= 2 for u in ctx.union.tolist())
        assert ctx.count[0] == 4
        assert ctx.feasible[0] == 16            # full-mask feasibility
        assert ctx.truncated.all()              # 16 feasible > 4 kept

    def test_skewed_plan_best_in_small_partition(self):
        """Flip the ramp: the best node lives in the 2-row partition —
        the scatter-max must pick the small partition, not the wide
        one."""
        import jax.numpy as jnp

        from volcano_tpu.ops.prune import PruneConf, distill
        from volcano_tpu.ops.score import ScoreWeights
        from volcano_tpu.ops.sharded import ShardPlan
        n = 16
        static = np.zeros((1, n), np.float32)
        static[0] = -np.arange(n)               # node 0 is the best
        batch = _StubBatch([[1.0, 1.0]], [0])
        narr = _StubNarr(np.full((n, 2), 8.0), np.full((n, 2), 16.0))
        plan = ShardPlan(2, 16, [0, 2, 16])
        conf = PruneConf(mode="true", k=2, partitions=1)
        ctx = distill(batch, narr, jnp.asarray(np.ones((1, n), bool)),
                      jnp.asarray(static), ScoreWeights.make(2), conf,
                      plan=plan)
        assert sorted(ctx.union.tolist()) == [0, 1]

    def test_two_level_bind_parity_with_dense_mesh(self):
        """End-to-end: forced mesh + pruning (two-level) is bind-for-
        bind identical with the dense forced-mesh run."""
        pruned = run_cluster(uniform_cluster, conf_with_solver(
            **{"prune.enable": "true", "prune.k": 8,
               "mesh.enable": "true", "mesh.min_nodes": 0}))
        dense = run_cluster(uniform_cluster, conf_with_solver(
            **{"mesh.enable": "true", "mesh.min_nodes": 0}))
        assert pruned.binds == dense.binds
        assert len(pruned.binds) == 24


# ---------------------------------------------------------------------------
# breaker-ladder composition
# ---------------------------------------------------------------------------


class TestBreakerComposition:
    def test_sharded_crash_under_pruning_lands_on_fallback_tier(
            self, monkeypatch):
        """An injected sharded crash during a PRUNED place must fall to
        the next tier with the SAME reduced inputs, land identical
        binds, open the breaker — and the pruned path still serves."""
        import volcano_tpu.framework.solver as solver_mod
        from volcano_tpu.framework.solver import (breaker_state,
                                                  reset_breaker)
        reset_breaker()
        real = solver_mod.BatchSolver._run_sharded

        def boom(*a, **k):
            raise RuntimeError("injected sharded-tier crash")

        monkeypatch.setattr(solver_mod.BatchSolver, "_run_sharded", boom)
        r0 = prune_runs()
        fell0 = m.counter_total(m.SOLVER_FALLBACK,
                                **{"from": "sharded", "to": "chunked"})
        crashed = run_cluster(uniform_cluster, conf_with_solver(
            **{"prune.enable": "true", "prune.k": 8,
               "mesh.enable": "true", "mesh.min_nodes": 0}))
        assert prune_runs() > r0          # pruning survived the crash
        assert m.counter_total(
            m.SOLVER_FALLBACK,
            **{"from": "sharded", "to": "chunked"}) > fell0
        assert "sharded" in breaker_state()
        monkeypatch.setattr(solver_mod.BatchSolver, "_run_sharded", real)
        reset_breaker()
        dense = run_cluster(uniform_cluster, conf_with_solver(
            **{"mesh.enable": "true", "mesh.min_nodes": 0}))
        assert crashed.binds == dense.binds
        assert len(crashed.binds) == 24
        reset_breaker()


# ---------------------------------------------------------------------------
# coverage-width registration (the operator's k is never flying blind)
# ---------------------------------------------------------------------------


class TestCoverageKs:
    def test_prune_k_joins_recorded_coverage_widths(self):
        from volcano_tpu.trace import explain as ex
        ex.reset()
        ex.enable()
        try:
            h = run_cluster(uniform_cluster, conf_with_solver(
                **{"prune.enable": "true", "prune.k": 32,
                   "explain.enable": "true"}))
            assert len(h.binds) == 24
            assert 32 in ex.coverage_ks()
            agg = ex.aggregates()
            assert "32" in agg["topk_coverage"]
            assert 32 in agg["coverage_ks"]
            rec = next(iter(ex.report(limit=0)["jobs"].values()))
            assert "32" in rec["groups"][0]["coverage"]
            # the per-cycle shortlist-loss surface rides the aggregates
            assert agg["prune"]["totals"]["runs"].get("single")
        finally:
            ex.disable()
            ex.reset()


# ---------------------------------------------------------------------------
# auto mode on the compiled single-chip Pallas tier
# ---------------------------------------------------------------------------


def auto_fleet(h, busy=False):
    """The auto floor's 4,096 nodes and eight pending gangs of 4; with
    ``busy`` a running pod on every third node, whose fill spreads the
    session-open scores so far that a top-k shortlist trips the
    ``low_coverage`` guard (as the burst cells' resident burst does)."""
    h.add("queues", build_queue("default", weight=1))
    for i in range(4096):
        h.add("nodes", build_node(f"node-{i}",
                                  {"cpu": "16", "memory": "32Gi"}))
    for i in range(0, 4096, 3) if busy else ():
        h.add("podgroups", build_pod_group(
            f"fill-{i}", "ns1", "default", 1, phase="Running"))
        h.add("pods", build_pod(
            "ns1", f"fill-{i}", f"node-{i}", "Running",
            {"cpu": "4", "memory": "1Gi"}, f"fill-{i}"))
    for j in range(8):
        h.add("podgroups", build_pod_group(f"pg-{j}", "ns1", "default", 4,
                                           phase="Inqueue"))
        for t in range(4):
            h.add("pods", build_pod("ns1", f"p{j}-{t}", "", "Pending",
                                    {"cpu": "2", "memory": "4Gi"},
                                    f"pg-{j}"))
    return h


def select_compiled_pallas(monkeypatch):
    """Make every batch select the compiled (not interpreted) Pallas
    kernel of one chip. The CPU cannot run Mosaic, so the kernel under
    that name is the chunked kernel, which places identically."""
    from volcano_tpu.framework.solver import BatchSolver
    from volcano_tpu.ops.allocate import gang_allocate_chunked

    def gang_allocate_pallas(*args, **kwargs):
        return gang_allocate_chunked(*args, **kwargs)

    monkeypatch.setattr(BatchSolver, "_select_kernel",
                        lambda self, batch=None: (gang_allocate_pallas, {}))


def tap_node_axes(monkeypatch):
    """Record the node axis each ``_execute_ladder`` call hands the
    kernel, beside the full padded width."""
    from volcano_tpu.framework.solver import BatchSolver
    real = BatchSolver._execute_ladder
    axes = []

    def tapped(self, batch, narr, *args, reduced=None, **kwargs):
        axes.append((reduced.u_pad if reduced is not None
                     else int(narr.idle.shape[0]), int(narr.n_pad)))
        return real(self, batch, narr, *args, reduced=reduced, **kwargs)

    monkeypatch.setattr(BatchSolver, "_execute_ladder", tapped)
    return axes


def skipped_total():
    return m.counter_total(m.PRUNE_SKIPPED, reason="pallas_full_width")


class TestAutoOnCompiledPallas:
    # (tier, prune.enable, distils): auto skips pruning only where the
    # full-width ladder is the compiled single-chip Pallas kernel
    @pytest.mark.parametrize("tier,mode,distils", [
        ("pallas", "auto", False),
        ("pallas", "true", True),
        ("chunked", "auto", True),
        ("native", "auto", True),
        ("mesh", "auto", True),
    ])
    def test_auto_prunes_off_the_compiled_pallas_tier(
            self, monkeypatch, tier, mode, distils):
        """On the compiled Pallas tier `auto` runs no distillation, counts
        the skip on volcano_prune_skipped_total and tags the place span,
        and places what `prune.enable: off` places; forced pruning and
        every other tier (chunked, native, the mesh) still distil."""
        args = {"mesh.enable": "true" if tier == "mesh" else "false"}
        if tier == "pallas":
            select_compiled_pallas(monkeypatch)
        elif tier != "mesh":
            args["kernel"] = tier
        s0 = skipped_total()
        h = auto_fleet(Harness(conf_with_solver(
            **args, **{"prune.enable": mode})))
        spans = run_traced(h)
        distilled = [s for s in spans if s.name == "prune_distill"]
        place = next(s for s in spans if s.name == "solver.place")
        if distils:
            assert distilled
            assert skipped_total() == s0
            assert "prune_skipped" not in (place.tags or {})
            return
        assert not distilled
        assert skipped_total() == s0 + 1
        assert place.tags["prune_skipped"] == "pallas_full_width"
        kernels = [s for s in spans if s.name == "kernel"]
        assert kernels and not any(s.tags["pruned"] for s in kernels)
        dense = run_cluster(auto_fleet, conf_with_solver(
            **args, **{"prune.enable": "off"}))
        assert h.binds == dense.binds
        assert len(h.binds) == 32

    def test_pallas_node_axis_does_not_follow_occupancy(self, monkeypatch):
        """The burst cells' warm-up places on an empty fleet and their
        window on one a third busy. Pruned, the first call runs on a
        shortlist union and the second trips `low_coverage` to full
        width: two node axes, so two Pallas programs. Under `auto` on the
        compiled Pallas tier both calls get the full padded axis."""
        select_compiled_pallas(monkeypatch)
        axes = tap_node_axes(monkeypatch)
        for busy in (False, True):
            run_cluster(lambda h: auto_fleet(h, busy),
                        conf_with_solver(**{"mesh.enable": "false"}))
        assert len(axes) == 2
        assert all(n_axis == n_pad for n_axis, n_pad in axes)
        assert axes[0] == axes[1]
        # the same two calls pruned: the axis moves with occupancy
        axes.clear()
        f0 = fallback_totals()
        for busy in (False, True):
            run_cluster(lambda h: auto_fleet(h, busy), conf_with_solver(
                **{"mesh.enable": "false", "prune.enable": "true"}))
        assert fallback_totals()["low_coverage"] == f0["low_coverage"] + 1
        assert axes[0][0] < axes[0][1]
        assert axes[-1][0] == axes[-1][1]
