"""BatchSolver: the session's TPU placement context.

This is the TPU-native replacement for the reference's per-task scheduling
helpers (pkg/scheduler/util/scheduler_helper.go: PredicateNodes,
PrioritizeNodes, SelectBestNode): instead of 16-way goroutine fan-out per
task, the whole ordered task batch is placed by one jitted gang-allocate
scan over dense snapshot arrays (models/arrays.py, ops/allocate.py).

Builtin plugins contribute during OnSessionOpen:
  * score weights (binpack / nodeorder terms) -> ``set_weight``
  * extra feasibility masks [G, N]            -> ``add_mask_fn``
  * static score terms [G, N]                 -> ``add_static_score_fn``

Plugins that only register host-side predicate fns (out-of-tree ones) are
honored through a per-group fallback sweep, trading speed for generality.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..models.arrays import (NodeArrays, PredicateFeatures, ResourceIndex,
                             TaskBatch)
from ..models.job_info import JobInfo, TaskInfo
from ..models.unschedule_info import FitError, FitErrors
from ..ops.allocate import gang_allocate
from ..ops.fit import group_fit_mask, selector_mask, taint_mask
from ..ops.score import ScoreWeights
from ..trace import tracer as trace

import logging
import time

_logger = logging.getLogger(__name__)
_logged_once: set = set()

# rotating start offset for the sampling window (the reference's package-
# level node cursor, scheduler_helper.go:95); advances per sampled session
_node_cursor = 0

# fleet fragmentation gauge cadence (docs/design/observability.md): the
# O(N x R) numpy pass runs every place() when the explainer is on, else
# once per this many place() calls so the steady-state cycle never pays
# it on the measured path
FRAG_EVERY = 16

# -- solver circuit breaker (docs/design/resilience.md) ----------------------
# A kernel tier that CRASHES mid-place (the known native-kernel divergence
# class) is retried with the next tier of the degradation ladder
# (pallas/native/sharded -> chunked -> scan) within the same cycle, and a
# breaker opens over the crashed tier: it is skipped for `breaker.window`
# subsequent placement calls, then half-open — one probe; success closes
# the breaker, another crash re-opens it. State is module-level because
# BatchSolver instances are per-session; the counter advances once per
# place() call (>= once per cycle).
_place_counter = 0
_breaker_open_until: Dict[str, int] = {}

_TIER_OF_KERNEL = {"gang_allocate_pallas": "pallas",
                   "gang_allocate_native": "native",
                   "gang_allocate_chunked": "chunked",
                   "gang_allocate": "scan"}


def reset_breaker() -> None:
    """Drop all circuit-breaker state (tests / process reinit)."""
    global _place_counter
    _place_counter = 0
    _breaker_open_until.clear()


# -- multi-chip mesh (docs/design/sharded_kernel.md) --------------------------
# The sharded kernel is the PRODUCTION DEFAULT whenever more than one
# device is visible and the node axis is large enough to pay for the
# per-chunk candidate all-gather; below the floor the single-device
# kernels (native/chunked/scan, exhaustively proven faster at small N)
# keep the cycle. `mesh.enable: "true"` forces the mesh regardless of
# size, `"false"` disables it, `mesh.min_nodes` moves the floor.
MESH_MIN_NODES = 4096

# Mesh and jitted-kernel caches are module-level: BatchSolver instances
# are per-session (one per cycle), and rebuilding the shard_map + jit
# wrapper each cycle would recompile the kernel every time.
_mesh_cache: Dict[tuple, object] = {}
_sharded_fn_cache: Dict[tuple, Callable] = {}


def _get_mesh(devices):
    from jax.sharding import Mesh
    key = tuple(d.id for d in devices)
    mesh = _mesh_cache.get(key)
    if mesh is None:
        mesh = Mesh(np.array(devices), ("nodes",))
        _mesh_cache[key] = mesh
    return mesh


def _get_sharded_fn(mesh, allow_pipeline: bool, ns_live: bool, chunk: int,
                    with_slots: bool = False):
    key = (tuple(d.id for d in mesh.devices.flat),
           bool(allow_pipeline), bool(ns_live), int(chunk),
           bool(with_slots))
    fn = _sharded_fn_cache.get(key)
    if fn is None:
        from ..ops.sharded import make_sharded_gang_allocate
        fn = make_sharded_gang_allocate(mesh, allow_pipeline=allow_pipeline,
                                        ns_live=ns_live, chunk=chunk,
                                        with_slots=with_slots)
        _sharded_fn_cache[key] = fn
    return fn


# -- incremental node tensors (docs/design/incremental_cycle.md) -------------

class _IncrNodeState:
    """Persistent host NodeArrays + device-resident kernel-input buffers
    reused across steady-state cycles. One per SchedulerCache (the
    BatchSolver itself is per-session): each incremental snapshot's
    patched-node set accumulates into ``pending``; the next session's
    FIRST context build re-encodes only those host rows and scatters only
    those device rows, so the steady-state host→device transfer drops to
    ~the dirty rows instead of the full [N, R] snapshot. Any shape/order/
    rindex change — or a full snapshot rebuild — invalidates wholesale."""

    __slots__ = ("seq", "narr", "rindex", "names", "pending", "dev",
                 "dev_dirty_rows", "plan", "shard_dev", "shard_dirty_rows")

    def __init__(self):
        self.seq = -1
        self.narr = None           # host NodeArrays of the last first-build
        self.rindex = None
        self.names = None          # node order the arrays encode
        self.pending = set()       # node names needing host row re-encode
        self.dev = None            # {field: device array} or None
        self.dev_dirty_rows = set()  # row indices needing device scatter
        # sharded (multi-chip) twin of the dense device buffers: the
        # topology-aware ShardPlan and PER-DEVICE resident node tensors
        # in layout order, scatter-updated so a dirty row's bytes travel
        # only to the owning shard. The plan is rebuilt ONLY when the
        # persistent host arrays rebuild (structural node change), so
        # the buffers keep their dirty-row scatter path across cycles.
        self.plan = None
        self.shard_dev = None      # {field: sharded device array} or None
        self.shard_dirty_rows = set()

    def drop_sharded(self) -> None:
        self.plan = None
        self.shard_dev = None
        self.shard_dirty_rows = set()


def note_incremental_snapshot(cache, snap) -> None:
    """Fold one snapshot's invalidation surface into the cache's
    persistent solver state (called once per cycle by open_session)."""
    state = getattr(cache, "_incr_solver_state", None)
    if state is None:
        state = cache._incr_solver_state = _IncrNodeState()
    if snap.incr_seq == state.seq:
        return
    state.seq = snap.incr_seq
    if snap.incr_mode != "incremental":
        state.narr = None
        state.dev = None
        state.pending.clear()
        state.dev_dirty_rows.clear()
        state.drop_sharded()
    else:
        state.pending |= snap.patched_nodes
    # the constraint compiler's persistent node rows (topology codes,
    # tier mass) ride the same dirty sets (ops/constraints.py)
    from ..ops import constraints as _constraints
    _constraints.note_snapshot(cache, snap)


def breaker_state() -> Dict[str, int]:
    """{tier: open-until placement-counter} of currently open breakers."""
    return dict(_breaker_open_until)

# shared all-zeros [G, N] device buffers by shape (read-only: the kernels
# never write their static-score input); one slot — shapes are bucketed so
# consecutive cycles at a stable scale reuse the same buffer
_zeros_cache: Dict[tuple, object] = {}


def _shared_zeros(shape: tuple):
    buf = _zeros_cache.get(shape)
    if buf is None:
        if len(_zeros_cache) > 4:   # bound: shape churn must not leak
            _zeros_cache.clear()
        buf = jnp.zeros(shape, jnp.float32)
        _zeros_cache[shape] = buf
    return buf


@jax.jit
def _fused_static_mask(group_req, uniq_cap, inv, valid, eps):
    """valid & capability-fit for every group x node, via unique capability
    rows, fused to one [G, N] output."""
    fit_u = group_fit_mask(group_req, uniq_cap, eps)      # [G, U]
    return valid[None, :] & fit_u[:, inv]


def _log_once(msg: str) -> None:
    if msg not in _logged_once:
        _logged_once.add(msg)
        _logger.warning(msg)


class Placement(NamedTuple):
    # NamedTuple over dataclass: a cycle materializes one per placed task
    # (50k at the target scale) and tuple allocation is ~3x cheaper
    task: TaskInfo
    node_name: str
    pipelined: bool


@dataclass
class PlacementResult:
    batch: TaskBatch
    committed: Dict[str, bool]                  # job uid -> JobReady (bind)
    kept: Dict[str, bool]                       # job uid -> JobPipelined (keep)
    placements: Dict[str, List[Placement]]      # job uid -> placements
    unplaced: Dict[str, List[TaskInfo]]         # job uid -> tasks left pending
    # vectorized accounting for the staging fast path (avoids one
    # Resource.add per placed task — 100k+ calls per 50k-burst cycle):
    narr: Optional[NodeArrays] = None
    job_total_vec: Optional[Dict[str, np.ndarray]] = None  # uid -> [R]
    node_alloc_vec: Optional[np.ndarray] = None  # [N_pad, R] idle-claims


class BatchSolver:
    def __init__(self, ssn, rindex: Optional[ResourceIndex] = None):
        self.ssn = ssn
        # the incremental snapshot maintains the cycle's ResourceIndex
        # (same scalar-name derivation, kept object-identical while the
        # name set is stable); legacy full snapshots rescan everything
        self.rindex = rindex if rindex is not None \
            else ResourceIndex.from_cluster(ssn.nodes, ssn.jobs)
        self._weights: Dict[str, float] = {"binpack": 0.0, "least": 0.0,
                                           "most": 0.0, "balanced": 0.0}
        self._binpack_res: Optional[np.ndarray] = None
        self.mask_fns: List[Callable] = []
        self.static_score_fns: List[Callable] = []
        self.queue_budget_fns: List[Callable] = []
        self.namespace_budget_fn: Optional[Callable] = None
        self.bucket_fn: Optional[Callable] = None
        self.vectorized_plugins: set = set()
        self.enable_default_predicates = False
        # node-axis sharding over a device mesh (SURVEY §7 step 6,
        # docs/design/sharded_kernel.md): the PRODUCTION DEFAULT — with
        # `mesh.enable: "auto"` (the default) the mesh is built whenever
        # >1 device is visible, the node axis clears `mesh.min_nodes`,
        # and no explicit single-device kernel was forced. Conf:
        #   configurations:
        #   - name: solver
        #     arguments: {mesh.enable: "auto"|"true"|"false",
        #                 mesh.devices: 8, mesh.chunk: 16,
        #                 mesh.min_nodes: 4096}
        # The sharded kernel (ops/sharded.py) is exact vs the single-device
        # scan; tests/test_sharded.py holds the parity proof, and the tier
        # ladder below degrades sharded -> chunked -> scan mid-cycle.
        self.mesh = None
        self.mesh_chunk = 16
        self.mesh_min_nodes = MESH_MIN_NODES
        mesh_mode = "auto"
        mesh_devices = 0
        # kernel selection (the production analogue of the reference's hot
        # path always running in-process, allocate.go:201-262):
        #   configurations:
        #   - name: solver
        #     arguments: {kernel: pallas|chunked|scan|auto}
        # `auto` (default) picks the Pallas kernel on a TPU backend when the
        # resource axis fits its sublane budget, else the chunked-candidate
        # scan (gang_allocate_chunked); `pallas` forces Pallas (interpret
        # mode off-TPU, for parity tests); `scan` forces the plain scan.
        self.kernel = "auto"
        # deferred object-model apply (Session.materialize): allocate
        # records placements as per-job deltas + node_name strings and the
        # 50k-task object staging runs only if something reads session
        # placement state. `apply: eager` restores immediate staging.
        self.deferred_apply = True
        # adaptive node sampling (the reference's CPU cost-control,
        # pkg/scheduler/util/scheduler_helper.go:49-68 +
        # --percentage-nodes-to-find): OFF by default — the TPU kernels
        # evaluate every node exhaustively. A non-TPU deployment that must
        # meet the 1 s cycle budget can opt in:
        #   configurations:
        #   - name: solver
        #     arguments: {sampling.enable: "true",
        #                 sampling.percentage: 0,    # 0 = adaptive
        #                 sampling.minNodes: 100}
        # Each session considers a rotating window of the node list
        # (the reference's moving node cursor), trading placement quality
        # for cycle latency exactly like the reference does.
        self.sampling = False
        self.sampling_pct = 0.0
        self.sampling_min = 100
        # circuit-breaker window: placements a crashed kernel tier is
        # skipped for before its half-open probe (resilience.md);
        #   configurations:
        #   - name: solver
        #     arguments: {breaker.window: 20}
        self.breaker_window = 20
        solver_args = (ssn.configurations or {}).get("solver")
        # placement explainer (trace/explain.py): decision provenance +
        # pruning-readiness aggregates, derived from the [G, N] tensors
        # this solver compiles. `explain.enable` (solver conf) overrides
        # the module switch; when off the only hot-path residue is this
        # cached bool.
        from ..trace import explain as _explain
        self.explain = _explain.session_enabled(solver_args)
        self._explain_stages = None
        if solver_args is not None:
            if hasattr(solver_args, "get_int"):
                self.breaker_window = solver_args.get_int(
                    "breaker.window", 20)
                mesh_devices = solver_args.get_int("mesh.devices", 0)
                # collective cadence: one candidate all-gather per `chunk`
                # placements (ops/sharded.py chunked kernel; exact)
                self.mesh_chunk = solver_args.get_int("mesh.chunk", 16)
                self.mesh_min_nodes = solver_args.get_int(
                    "mesh.min_nodes", MESH_MIN_NODES)
            if hasattr(solver_args, "get_str"):
                mesh_mode = (solver_args.get_str("mesh.enable", "auto")
                             or "auto").strip().lower()
            self.kernel = solver_args.get_str("kernel", "auto") \
                if hasattr(solver_args, "get_str") else "auto"
            if hasattr(solver_args, "get_str") and \
                    solver_args.get_str("apply", "deferred") == "eager":
                self.deferred_apply = False
            if getattr(solver_args, "get_bool",
                       lambda *_: False)("sampling.enable", False):
                self.sampling = True
                self.sampling_pct = solver_args.get_float(
                    "sampling.percentage", 0.0)
                self.sampling_min = solver_args.get_int(
                    "sampling.minNodes", 100)
        # candidate pruning + two-level hierarchical placement
        # (ops/prune.py, docs/design/pruning.md): per-gang top-k node
        # shortlists distilled from the compiled [G, N] mask/score
        # tensors shrink the kernel's node axis to the shortlist union;
        # `auto` leaves the compiled one-chip Pallas tier at full width,
        # `true` prunes on every tier, and `prune.enable: off` restores
        # the exact unpruned path.
        #   configurations:
        #   - name: solver
        #     arguments: {prune.enable: "auto"|"true"|"off",
        #                 prune.k: 64, prune.coverage_floor: 0.9,
        #                 prune.min_nodes: 4096, prune.partitions: 2,
        #                 prune.max_union_frac: 0.6,
        #                 prune.demand_aware: "on"}
        from ..ops.prune import PruneConf
        self.prune = PruneConf.from_args(solver_args)
        if not self.prune.off:
            # the operator-chosen shortlist width must always be one of
            # the recorded coverage widths (the loss-budget surface)
            _explain.register_prune_k(self.prune.k)
        self.mesh_forced = False
        if mesh_mode in ("true", "1", "yes", "on"):
            self.mesh = self._build_mesh(mesh_devices)
            self.mesh_forced = self.mesh is not None
        elif mesh_mode not in ("false", "0", "no", "off"):
            # auto (the production default): shard whenever >1 device is
            # visible and the node axis clears the floor — but an
            # explicitly forced single-device kernel (`kernel:` conf) or
            # node sampling wins over auto-selection
            if self.kernel == "auto" and not self.sampling \
                    and len(ssn.node_list) >= self.mesh_min_nodes:
                self.mesh = self._build_mesh(mesh_devices)
        self._sampled_names: Optional[List[str]] = None
        self._mask_contributed = False
        self._prune_dedupe_ok = False

    def _build_mesh(self, n_dev: int = 0):
        """The cached device mesh, or None when <2 devices are visible
        (or mesh construction fails — degrading to the dense kernels
        must never cost the cycle)."""
        try:
            devices = jax.devices()
            devices = devices[:n_dev] if n_dev else devices
            if len(devices) < 2:
                return None
            return _get_mesh(devices)
        except Exception as e:
            _log_once(f"device mesh construction failed ({e!r}); "
                      "falling back to single-device kernels")
            return None

    def _node_order(self) -> List[str]:
        """The node-name order the contexts are built over: every ready
        node, or — with sampling enabled — a rotating window of
        max(minNodes, pct% of N) names (CalculateNumOfFeasibleNodesToFind:
        adaptive pct = 50 - N/125 clamped to >= 5, scheduler_helper.go:
        36,49-68; the window start advances like the reference's node
        cursor so successive cycles cover the whole cluster)."""
        if self.sampling and self._sampled_names is not None:
            return self._sampled_names       # stable within the session
        names = [n.name for n in self.ssn.node_list]
        if not self.sampling:
            return names
        n = len(names)
        k = n
        if n > self.sampling_min:
            pct = self.sampling_pct or max(5.0, 50.0 - n / 125.0)
            k = min(n, max(self.sampling_min, int(n * pct / 100.0)))
        if k >= n:
            self._sampled_names = names
            return names
        global _node_cursor
        start = _node_cursor % n
        _node_cursor += k
        window = names[start:start + k]
        if len(window) < k:
            window += names[:k - len(window)]
        self._sampled_names = window
        return window

    # -- plugin contribution API ------------------------------------------

    def set_weight(self, term: str, value: float) -> None:
        self._weights[term] = float(value)

    def add_weight(self, term: str, value: float) -> None:
        self._weights[term] = self._weights.get(term, 0.0) + float(value)

    def set_binpack_resources(self, weights_by_name: Dict[str, float]) -> None:
        w = np.zeros(self.rindex.r, np.float32)
        for name, weight in weights_by_name.items():
            i = self.rindex.index.get(name)
            if i is not None:
                w[i] = weight
        self._binpack_res = w

    def add_mask_fn(self, fn: Callable) -> None:
        """fn(batch, node_arrays, features) -> [G, N] bool"""
        self.mask_fns.append(fn)

    def add_static_score_fn(self, fn: Callable) -> None:
        """fn(batch, node_arrays, features) -> [G, N] float"""
        self.static_score_fns.append(fn)

    def add_queue_budget_fn(self, fn: Callable) -> None:
        """fn(queue_name, rindex) -> None | (allocated [R], deserved [R]).

        Feeds the kernel's live fair-share gate: a job is only selected while
        its queue's in-scan allocation stays within deserved (the proportion
        plugin's Overused semantics, at job granularity)."""
        self.queue_budget_fns.append(fn)

    def set_namespace_budget_fn(self, fn: Callable) -> None:
        """fn(ns_name, rindex) -> None | (allocated [R], weight).

        Feeds the kernel's LIVE namespace re-selection (drf's
        NamespaceOrderFn, allocate.go:120-139): at every job boundary the
        namespace with the lowest weighted dominant share — over these
        session-open allocations plus in-scan placements — is chosen first.
        Without this hook the kernel selects namespaces by the encode's
        static order (the host's session-open namespace sort), matching the
        reference's priority queue when no namespace order fn is live."""
        self.namespace_budget_fn = fn

    def set_bucket_fn(self, fn: Callable) -> None:
        """fn(task) -> None | (bucket_key, per_mate_bonus). Tasks sharing a
        bucket_key attract each other inside the allocate scan: every
        same-bucket placement on a node adds per_mate_bonus to that node's
        score for subsequent bucket mates (the task-topology plugin's
        packing term)."""
        self.bucket_fn = fn

    def mark_vectorized(self, plugin_name: str) -> None:
        self.vectorized_plugins.add(plugin_name)

    def score_weights(self) -> ScoreWeights:
        br = self._binpack_res if self._binpack_res is not None \
            else np.ones(self.rindex.r, np.float32)
        return ScoreWeights(jnp.asarray(br),
                            jnp.float32(self._weights.get("binpack", 0.0)),
                            jnp.float32(self._weights.get("least", 0.0)),
                            jnp.float32(self._weights.get("most", 0.0)),
                            jnp.float32(self._weights.get("balanced", 0.0)))

    # -- placement ---------------------------------------------------------

    def _host_predicate_mask(self, batch: TaskBatch, narr: NodeArrays) -> Optional[np.ndarray]:
        """Fallback for plugins that registered only host predicate fns.

        O(G x N) Python — out-of-tree plugins trade solver speed for
        generality here, so the first use logs which plugins forced the
        sweep. A predicate veto is a raised exception (the reference's
        PredicateFn error contract, scheduler_helper.go:95-127); veto
        types are FitException and the assertion/lookup/runtime errors a
        filter naturally raises — anything else is a plugin bug and is
        logged (once per plugin) and re-raised rather than silently read
        as "node infeasible"."""
        extra = {name: fn for name, fn in self.ssn.predicate_fns.items()
                 if name not in self.vectorized_plugins}
        if not extra:
            return None
        from ..metrics import metrics as m
        from ..plugins.predicates import FitException
        m.inc(m.SOLVER_HOST_PREDICATE)
        veto_types = (FitException, AssertionError, KeyError, RuntimeError,
                      ValueError)
        _log_once("host-predicate fallback active for plugins "
                  f"{sorted(extra)}: per-node Python sweep (register a "
                  "vectorized mask_fn for solver-speed predicates)")
        mask = np.ones((batch.g_pad, narr.n_pad), bool)
        for g, members in enumerate(batch.group_members):
            rep = batch.tasks[members[0]]
            for name, node in self.ssn.nodes.items():
                i = narr.name_to_idx.get(name)
                if i is None:
                    continue
                for pname, fn in extra.items():
                    try:
                        fn(rep, node)
                    except veto_types:
                        mask[g, i] = False
                        break
                    except Exception:
                        _log_once(f"host predicate {pname!r} raised an "
                                  "unexpected error (plugin bug?)")
                        raise
        return mask

    def _context_arrays(self, ordered_jobs, slot_tensors: bool = False):
        """Shared front half of both context builds: materialize deferred
        placements, then the SoA encodes. The FIRST build of an
        incremental session reuses the persistent NodeArrays with only
        the patched rows re-encoded; later builds in the same cycle see
        session-mutated nodes and always encode fresh.

        ``slot_tensors`` (the _place/device path) lowers hard topology
        spread / self-anti-affinity domains to the kernels' per-task
        ``task_slot``/``slot_rows`` inputs with groups keeping their
        BASE sigs — the candidate-table kernels then amortize refreshes
        across a domain-rotating gang exactly like an unconstrained one.
        Without it (host contexts, ``constraints.compile: off``, a
        SLOT_CAP overflow, or a tensor-build crash), the REFERENCE
        lowering runs: per-domain derived group sigs whose mask rows
        ride the selector feature pairs — bit-identical placements, per-
        task refresh cost."""
        ssn = self.ssn
        ssn.materialize()   # deferred placements must be visible to arrays
        narr = None
        if not getattr(ssn, "_narr_first_done", False):
            ssn._narr_first_done = True
            narr = self._incremental_node_arrays()
        if narr is None:
            narr = NodeArrays.build(ssn.nodes, self._node_order(),
                                    self.rindex)
        sig_override = None
        use_tensors = False
        from ..metrics import metrics as m
        from ..ops import constraints as _constraints
        if _constraints.has_constraints(ordered_jobs):
            use_tensors = slot_tensors \
                and _constraints.compile_conf(ssn) != "off"
            if use_tensors:
                try:
                    _constraints.assign_spread_slots(
                        ssn, ordered_jobs, narr.names, split=False)
                    if _constraints.count_batch_slots(
                            ssn, ordered_jobs) > _constraints.SLOT_CAP:
                        use_tensors = False
                        sig_override = _constraints.derive_sig_overrides(
                            ssn, ordered_jobs)
                except Exception:
                    _logger.exception(
                        "constraint slot-tensor lowering crashed; falling "
                        "back to the split reference lowering")
                    m.inc(m.CONSTRAINT_FALLBACK)
                    use_tensors = False
                    sig_override, ordered_jobs = \
                        _constraints.split_assign_or_exclude(
                            ssn, ordered_jobs, narr.names)
            else:
                sig_override, ordered_jobs = \
                    _constraints.split_assign_or_exclude(
                        ssn, ordered_jobs, narr.names)
        batch = TaskBatch.build(ordered_jobs, self.rindex,
                                sig_override=sig_override)
        if use_tensors:
            try:
                slot_data = _constraints.build_slot_tensors(ssn, batch,
                                                            narr)
            except Exception:
                # the batch was built on base sigs, which are only sound
                # with the per-task tensors: rebuild it under the split
                # reference lowering
                _logger.exception(
                    "constraint slot-tensor build crashed; rebuilding "
                    "the batch under the split reference lowering")
                m.inc(m.CONSTRAINT_FALLBACK)
                slot_data = None
                use_tensors = False
                sig_override = _constraints.derive_sig_overrides(
                    ssn, ordered_jobs)
                batch = TaskBatch.build(ordered_jobs, self.rindex,
                                        sig_override=sig_override)
            if slot_data is not None:
                batch.task_slot, batch.slot_rows = slot_data
            else:
                use_tensors = False
        # slot-assigned domains lower through the selector feature pairs
        # (compact [G, F] x [F, N] matmul) in split mode, or through the
        # batch's task_slot/slot_rows kernel inputs in tensor mode;
        # compile_mask sees the flag and skips its dense slot rows
        slots = getattr(ssn, "_constraint_slots", None) \
            if sig_override else None
        if slots or batch.task_slot is not None:
            ssn._constraint_slots_lowered = True
        feats = PredicateFeatures.build(ssn.nodes, narr, batch,
                                        slot_entries=slots)
        return narr, batch, feats

    def _incr_state(self) -> Optional[_IncrNodeState]:
        if self.ssn.cache is None:
            return None
        return getattr(self.ssn.cache, "_incr_solver_state", None)

    def _incremental_node_arrays(self) -> Optional[NodeArrays]:
        """The session's first node encode, through the persistent
        host-array cache when live; None falls back to a fresh build
        (which is then installed as the new persistent state)."""
        ssn = self.ssn
        state = self._incr_state()
        if state is None or getattr(ssn, "incr_mode", None) is None \
                or self.sampling:
            return None
        order = self._node_order()
        if ssn.incr_mode == "incremental" and state.narr is not None \
                and state.rindex is self.rindex \
                and state.names == order \
                and not ssn.touched_nodes \
                and len(state.pending) <= max(64, len(order) // 4):
            rows = state.narr.update_rows(ssn.nodes, state.pending)
            state.pending = set()
            state.dev_dirty_rows.update(rows)
            state.shard_dirty_rows.update(rows)
            return state.narr
        # STRUCTURAL rebuild: membership/order/rindex changed (or the
        # dirty set outgrew the scatter path) — the persistent device
        # buffers AND the shard plan are invalidated wholesale; this is
        # the only point the topology-aware partition rebalances.
        narr = NodeArrays.build(ssn.nodes, order, self.rindex)
        state.narr = narr
        state.rindex = self.rindex
        state.names = list(order)
        state.pending = set()
        state.dev = None
        state.dev_dirty_rows = set()
        state.drop_sharded()
        return narr

    _DEV_NODE_FIELDS = ("idle", "future_idle", "allocatable", "n_tasks",
                        "max_tasks")

    def _device_node_inputs(self, narr: NodeArrays):
        """The five node tensors the kernels consume, as device arrays:
        scatter-updates only the dirty rows of the persistent buffers
        when the host arrays are the persistent ones, else a plain full
        upload. Returns ({field: device array}, host->device bytes)."""
        from ..metrics import metrics as m

        def full_host():
            return {"idle": narr.idle, "future_idle": narr.future_idle,
                    "allocatable": narr.allocatable,
                    "n_tasks": narr.n_tasks, "max_tasks": narr.max_tasks}

        state = self._incr_state()
        if state is None or state.narr is not narr:
            host = full_host()
            return {f: jnp.asarray(a) for f, a in host.items()}, \
                sum(int(a.nbytes) for a in host.values())
        if state.dev is None:
            host = full_host()
            state.dev = {f: jnp.asarray(a) for f, a in host.items()}
            state.dev_dirty_rows = set()
            m.inc(m.SOLVER_DEVICE_BUFFER, event="rebuild")
            return dict(state.dev), \
                sum(int(a.nbytes) for a in host.values())
        xfer = 0
        rows = sorted(state.dev_dirty_rows)
        if rows:
            idx = jnp.asarray(np.asarray(rows, np.int32))
            host_rows = {
                "idle": narr.idle[rows],
                "future_idle": narr.idle[rows] + narr.releasing[rows]
                - narr.pipelined[rows],
                "allocatable": narr.allocatable[rows],
                "n_tasks": narr.n_tasks[rows],
                "max_tasks": narr.max_tasks[rows]}
            for f in self._DEV_NODE_FIELDS:
                hr = host_rows[f]
                state.dev[f] = state.dev[f].at[idx].set(jnp.asarray(hr))
                xfer += int(hr.nbytes)
            state.dev_dirty_rows = set()
        m.inc(m.SOLVER_DEVICE_BUFFER, event="reuse")
        return dict(state.dev), xfer

    def _apply_masks_and_scores(self, gmask, batch, narr, feats, xp,
                                stages=None):
        """Shared back half of both context builds — ONE formulation of
        the feature masks, plugin mask/score contributions and the host
        predicate fallback; ``xp`` (jnp or numpy) decides only where the
        arrays live. Contributions return None when trivially
        pass-through: a dense [G, N] array is tens-to-hundreds of MB at
        50k x 10k, and all-ones feature masks skip their matmuls
        entirely.

        ``stages`` (explain mode only) collects the cumulative mask
        ladder as ``(label, survivors [G])`` pairs — each stage is
        reduced to its per-group survivor count EAGERLY (an async [G]
        device reduce), so the [G, N] intermediates keep their normal
        XLA lifetime instead of being pinned until the post-place
        capture (a 5-stage constrained ladder at 50k x 10k would
        otherwise hold multiple ~500 MB masks live at once).

        Side channel: ``self._mask_contributed`` records whether ANY
        stage beyond the capability fit contributed — when none did,
        every group's mask row is a pure function of its request row,
        which is the exact-dedupe license the shortlist distillation
        uses (ops/prune.py)."""
        contributed = [False]

        def cap(label, g):
            contributed[0] = True
            if stages is not None:
                stages.append((label, g.sum(axis=1)))
            return g

        if self.enable_default_predicates:
            if feats.group_require_counts.any():
                gmask = cap("selector", gmask & selector_mask(
                    xp.asarray(feats.node_pairs),
                    xp.asarray(feats.group_requires),
                    xp.asarray(feats.group_require_counts)))
            if feats.node_taints.any():
                gmask = cap("taint", gmask & taint_mask(
                    xp.asarray(feats.node_taints),
                    xp.asarray(feats.group_tolerates)))
            if feats.group_affinity_ok is not None:
                gmask = cap("node_affinity",
                            gmask & xp.asarray(feats.group_affinity_ok))
        for fn in self.mask_fns:
            contrib = fn(batch, narr, feats)
            if contrib is not None:
                gmask = cap(getattr(fn, "explain_label", "plugin"),
                            gmask & xp.asarray(contrib))
        host_mask = self._host_predicate_mask(batch, narr)
        if host_mask is not None:
            gmask = cap("host_predicates", gmask & xp.asarray(host_mask))

        static_score = None
        for fn in self.static_score_fns:
            contrib = fn(batch, narr, feats)
            if contrib is not None:
                contrib = xp.asarray(contrib)
                static_score = contrib if static_score is None \
                    else static_score + contrib
        self._mask_contributed = contributed[0]
        return gmask, static_score

    def _build_context(self, ordered_jobs: List[Tuple[JobInfo, List[TaskInfo]]],
                       slot_tensors: bool = False):
        """Snapshot the session's current node state and compute the static
        predicate mask + static score for the batch: (narr, batch, gmask,
        static_score) — the DEVICE formulation (the [G, N] arrays stay on
        the accelerator; only the small inputs cross the link).
        ``slot_tensors`` picks the per-task domain lowering for the
        placement kernels (see _context_arrays)."""
        with trace.span("build_context"):
            return self._build_context_inner(ordered_jobs, slot_tensors)

    def _build_context_inner(self, ordered_jobs, slot_tensors=False):
        narr, batch, feats = self._context_arrays(ordered_jobs,
                                                  slot_tensors=slot_tensors)
        eps = jnp.asarray(self.rindex.eps)
        # capability fit through unique capability rows: clusters have a
        # handful of node shapes, so the [G,N,R] broadcast reduce becomes
        # [G,U,R] (tiny) + one [G,N] gather; the whole chain is one jitted
        # program so XLA fuses it into a single [G,N] materialization
        # (separate dispatches each produced a 64 MB intermediate at
        # 50k x 10k)
        uniq_cap, inv = np.unique(narr.capability, axis=0,
                                  return_inverse=True)
        gmask = _fused_static_mask(jnp.asarray(batch.group_req),
                                   jnp.asarray(uniq_cap),
                                   jnp.asarray(inv.astype(np.int32)),
                                   jnp.asarray(narr.valid), eps)
        stages = [("fit", gmask.sum(axis=1))] \
            if (slot_tensors and self.explain) else None
        gmask, static_score = self._apply_masks_and_scores(
            gmask, batch, narr, feats, jnp, stages=stages)
        self._explain_stages = stages
        # the shortlist distillation's exact-dedupe license
        # (ops/prune.py): no mask contributions beyond the capability
        # fit AND no static score contributions means identical request
        # rows have identical mask/score rows by construction
        self._prune_dedupe_ok = not self._mask_contributed \
            and static_score is None
        if static_score is None:
            # no static contributions (the common conf): a [G, N] zeros is
            # ~256 MB at 50k x 10k and allocating one per context build
            # dominated the encode — share one cached buffer per shape
            # (the kernels only ever READ static rows)
            static_score = _shared_zeros((batch.g_pad, narr.n_pad))
        return narr, batch, gmask, static_score

    def build_host_context(self, ordered_jobs: List[Tuple[JobInfo, List[TaskInfo]]]):
        """Numpy twin of :meth:`_build_context` for host-driven actions
        (preempt/reclaim): they walk nodes in Python reading a handful of
        mask/score rows, and pulling [G, N] matrices back from the device
        costs seconds at 50k x 10k. The feature/contribution semantics
        are the SAME code (_apply_masks_and_scores); only the capability
        fit differs — column-wise numpy without [G, N, R] temporaries —
        and tests/test_solver_kernel.py's
        test_host_context_matches_device_context pins that equivalence."""
        with trace.span("build_context", host=True):
            return self._build_host_context_inner(ordered_jobs)

    def _build_host_context_inner(self, ordered_jobs):
        narr, batch, feats = self._context_arrays(ordered_jobs)
        eps = self.rindex.eps
        gmask = np.ones((batch.g_pad, narr.n_pad), bool)
        gmask &= narr.valid[None, :]
        for c in range(self.rindex.r):
            # group_fit_mask, column-wise (no [G, N, R] temporaries)
            gmask &= batch.group_req[:, c:c + 1] <= \
                (narr.capability[None, :, c] + eps[c])
        gmask, static_score = self._apply_masks_and_scores(
            gmask, batch, narr, feats, np)
        if static_score is None:
            static_score = np.zeros((batch.g_pad, narr.n_pad), np.float32)
        return narr, batch, gmask, static_score

    def task_feasibility(self, job: JobInfo, task: TaskInfo):
        """Predicate mask + score over all nodes for a single task against
        the session's current node state (the PredicateNodes +
        PrioritizeNodes pair used by preempt/reclaim, preempt.go:202-206).

        Returns (narr, mask [N_pad] np.bool, score [N_pad] np.ndarray).
        """
        from ..ops.score import node_score
        narr, batch, gmask, static_score = self._build_context([(job, [task])])
        g = int(batch.task_group[0])
        req = jnp.asarray(batch.group_req[g])
        score = node_score(req, jnp.asarray(narr.idle),
                           jnp.asarray(narr.allocatable),
                           self.score_weights(), static_score[g])
        pods_ok = (narr.max_tasks == 0) | (narr.n_tasks < narr.max_tasks)
        mask = np.asarray(gmask[g]) & pods_ok
        return narr, mask, np.asarray(score)

    def _select_kernel(self, batch: Optional[TaskBatch] = None
                       ) -> Tuple[Callable, Dict]:
        """Resolve the placement kernel per the `solver` conf: the Pallas
        TPU kernel when requested (or `auto` on a TPU backend) and the
        batch fits its sublane and SMEM budgets; off-TPU `auto` prefers
        the native C++ solver (ops/native.py, bit-exact vs the scan) and
        falls back to the chunked-candidate XLA scan; `chunked`/`scan`/
        `native` force a specific kernel. All kernels carry the
        namespace-primary pool selection (multi-namespace batches
        included)."""
        from ..ops.allocate import gang_allocate_chunked
        from ..ops.pallas_allocate import (R_PAD_MAX, fits_resources,
                                           fits_smem, gang_allocate_pallas)
        backend = jax.default_backend()
        pallas_ok = fits_resources(self.rindex.r) and (
            batch is None or fits_smem(batch.t_pad, batch.j_pad,
                                       len(batch.pool_queue), batch.g_pad))
        if not pallas_ok and (self.kernel == "pallas" or (
                self.kernel == "auto" and backend == "tpu")):
            _log_once(f"the batch exceeds the Pallas kernel's {R_PAD_MAX} "
                      "resource dimensions or its SMEM budget; an XLA "
                      "kernel places it")
        if self.kernel == "pallas":
            if not pallas_ok:
                return gang_allocate_chunked, {}
            # only the CPU, which the tests use, interprets the kernel; on
            # any other backend a kernel that cannot run there must fail
            # loudly, not slowly emulate
            if backend not in ("tpu", "cpu"):
                raise RuntimeError(
                    f"solver kernel=pallas needs a TPU (backend {backend!r})")
            return gang_allocate_pallas, {"interpret": backend == "cpu"}
        if self.kernel in ("auto", "native"):
            on_tpu = backend == "tpu"
            if self.kernel == "auto" and on_tpu and pallas_ok:
                return gang_allocate_pallas, {}
            # native is the off-TPU path only: on a TPU backend `auto`
            # stays on the XLA kernels when the Pallas gate fails (running
            # the host solver there would ship every device input back)
            if self.rindex.r <= 8 and (not on_tpu or self.kernel == "native"):
                from ..ops.native import available, gang_allocate_native
                if available():
                    return gang_allocate_native, {}
                if self.kernel == "native":
                    _log_once("solver kernel=native but the native library "
                              "is unavailable; falling back to chunked")
            elif self.kernel == "native":
                _log_once("solver kernel=native but resource dims exceed "
                          "the native solver's budget (r>8); falling back "
                          "to chunked")
            # the candidate-table refresh only pays off once the node
            # sweep is expensive; small clusters keep the plain scan
            if self.kernel == "native" or len(self.ssn.nodes) >= 1024:
                return gang_allocate_chunked, {}
        if self.kernel == "chunked":
            return gang_allocate_chunked, {}
        return gang_allocate, {}

    def _compiled_pallas_tier(self, batch: TaskBatch, slot_kwargs) -> bool:
        """Does the full-width ladder run ``batch`` on the compiled Pallas
        kernel of one chip: no mesh, no constraint slots (those run the
        chunked kernel) and the kernel not interpreted?"""
        if self.mesh is not None or slot_kwargs:
            return False
        kernel_fn, kernel_kwargs = self._select_kernel(batch)
        return kernel_fn.__name__ == "gang_allocate_pallas" and \
            not kernel_kwargs.get("interpret", False)

    def place(self, ordered_jobs: List[Tuple[JobInfo, List[TaskInfo]]],
              allow_pipeline: bool = True) -> PlacementResult:
        """Run the gang-allocate kernel for the ordered job/task batch against
        the session's *current* node state."""
        with trace.span("solver.place", jobs=len(ordered_jobs)):
            result = self._place(ordered_jobs, allow_pipeline)
            trace.add_tags(
                placed=sum(len(p) for p in result.placements.values()),
                committed=sum(1 for ok in result.committed.values() if ok))
            return result

    def _place(self, ordered_jobs: List[Tuple[JobInfo, List[TaskInfo]]],
               allow_pipeline: bool = True) -> PlacementResult:
        narr, batch, gmask, static_score = self._build_context(
            ordered_jobs, slot_tensors=True)
        explain_stages, self._explain_stages = self._explain_stages, None
        eps = jnp.asarray(self.rindex.eps)

        # queue fair-share budgets (live Overused gate inside the scan)
        q_deserved = np.full((batch.q_pad, self.rindex.r), np.inf, np.float32)
        q_alloc0 = np.zeros((batch.q_pad, self.rindex.r), np.float32)
        for qi, qname in enumerate(batch.queue_names):
            for fn in self.queue_budget_fns:
                budget = fn(qname, self.rindex)
                if budget is not None:
                    allocated, deserved = budget
                    q_alloc0[qi] = allocated
                    q_deserved[qi] = deserved
                    break

        # namespace fairness state (live weighted-share re-selection when
        # the drf namespace order is active; static encode order otherwise);
        # bucket-padded like the other axes so namespace-count churn does
        # not recompile the kernel (padding rows have no pools -> inert)
        from ..models.arrays import bucket as _bucket
        ns_pad = _bucket(max(1, len(batch.ns_names)), 8)
        ns_weight = np.ones(ns_pad, np.float32)
        ns_alloc0 = np.zeros((ns_pad, self.rindex.r), np.float32)
        ns_live = self.namespace_budget_fn is not None \
            and len(batch.ns_names) > 1
        if ns_live:
            for ni, nsname in enumerate(batch.ns_names):
                budget = self.namespace_budget_fn(nsname, self.rindex)
                if budget is not None:
                    allocated, weight = budget
                    ns_alloc0[ni] = allocated
                    ns_weight[ni] = max(float(weight), 1e-9)
        ns_total = self.rindex.vec(self.ssn.total_resource) \
            if getattr(self.ssn, "total_resource", None) is not None \
            else np.ones(self.rindex.r, np.float32)

        # task-topology buckets: same-bucket tasks attract within the scan
        task_bucket = np.full(batch.task_group.shape[0], -1, np.int32)
        pack_bonus = np.zeros(batch.g_pad, np.float32)
        if self.bucket_fn is not None:
            keys: Dict = {}
            for t_idx in range(len(batch.tasks)):
                if not batch.task_valid[t_idx]:
                    continue
                res = self.bucket_fn(batch.tasks[t_idx])
                if res is None:
                    continue
                key, bonus = res
                task_bucket[t_idx] = keys.setdefault(key, len(keys))
                pack_bonus[batch.task_group[t_idx]] = bonus

        from ..metrics import metrics as m

        # tier ladder + circuit breaker (resilience.md): the selected
        # kernel first, then chunked, then the plain scan as last resort;
        # breaker-open tiers are skipped until their half-open window
        global _place_counter
        _place_counter += 1
        # kernel cost attribution (docs/design/observability.md): padded
        # vs live rows per kernel axis, and the fleet fragmentation
        # gauge (every place when the explainer is on, else amortized)
        n_real_nodes = len(narr.names)
        m.set_gauge(m.PADDED_WASTE, round(
            1.0 - n_real_nodes / max(1, narr.n_pad), 4), axis="nodes")
        m.set_gauge(m.PADDED_WASTE, round(
            1.0 - batch.n_groups / max(1, batch.g_pad), 4), axis="groups")
        m.set_gauge(m.PADDED_WASTE, round(
            1.0 - len(batch.tasks) / max(1, int(batch.task_group.shape[0])),
            4), axis="tasks")
        if self.explain or _place_counter % FRAG_EVERY == 0:
            from ..trace import explain as _explain
            _explain.note_fragmentation(narr)
        # per-task topology-domain inputs (ops/constraints.py): every
        # kernel consumes the same (task_slot, slot_ok) pair uniformly
        slot_kwargs = {}
        if batch.task_slot is not None:
            slot_kwargs = {"task_slot": jnp.asarray(batch.task_slot),
                           "slot_ok": jnp.asarray(batch.slot_rows)}
        # candidate pruning (ops/prune.py, docs/design/pruning.md): the
        # shortlist distillation, reduced-width kernel run, and the loss
        # guard's full-width fallback all land inside the kernel-latency
        # window — the bench's kernel_ms must price the whole placement
        # decision, pruned or not
        t_kernel = time.perf_counter()
        out = None
        if self.prune.active(n_real_nodes):
            if not self.prune.forced and \
                    self._compiled_pallas_tier(batch, slot_kwargs):
                # the Pallas program specialises on the node axis, so
                # every union width (and the guard's full width) is a
                # compile of seconds, against at most the full-width
                # kernel's device time saved: auto sends the batch
                # straight to full width
                m.inc(m.PRUNE_SKIPPED, reason="pallas_full_width")
                trace.add_tags(prune_skipped="pallas_full_width")
            else:
                out = self._place_pruned(
                    batch, narr, gmask, static_score, task_bucket,
                    pack_bonus, q_deserved, q_alloc0, ns_weight, ns_alloc0,
                    ns_total, ns_live, eps, allow_pipeline, slot_kwargs)
        if out is None:
            out = self._execute_ladder(
                batch, narr, gmask, static_score, task_bucket, pack_bonus,
                q_deserved, q_alloc0, ns_weight, ns_alloc0, ns_total,
                ns_live, eps, allow_pipeline, slot_kwargs)
        assign, pipelined, ready, kept, served_tier = out
        m.observe(m.SOLVER_KERNEL_LATENCY,
                  (time.perf_counter() - t_kernel) * 1000.0)
        pipelined_np = np.asarray(pipelined)
        ready_np = np.asarray(ready)
        kept_np = np.asarray(kept)

        uid_to_j = {uid: j for j, uid in enumerate(batch.job_uids)}
        result = PlacementResult(batch=batch, committed={}, kept={},
                                 placements={}, unplaced={}, narr=narr)
        unplaced_records: List[Tuple[JobInfo, TaskInfo, int]] = []
        all_tasks = batch.tasks
        task_group_np = batch.task_group
        # one pass over the assign vector instead of a span scan per job:
        # placed/unplaced indices are global sorted arrays, each job reads
        # its window via searchsorted boundaries
        n_real = len(all_tasks)
        a_real = assign[:n_real]
        placed_all = np.flatnonzero(a_real >= 0)
        unplaced_all = np.flatnonzero(a_real < 0)
        if placed_all.size:
            # vectorized per-job and per-node placement totals (consumed by
            # the staging fast path instead of per-task Resource sums)
            rows_req = batch.group_req[task_group_np[placed_all]]
            jt = np.zeros((len(batch.job_uids), self.rindex.r), np.float32)
            np.add.at(jt, batch.task_job[placed_all], rows_req)
            result.job_total_vec = {uid: jt[j]
                                    for uid, j in uid_to_j.items()
                                    if jt[j].any()}
            alloc_rows = ~pipelined_np[placed_all].astype(bool)
            if alloc_rows.any():
                nv = np.zeros((narr.idle.shape[0], self.rindex.r),
                              np.float32)
                np.add.at(nv, a_real[placed_all][alloc_rows],
                          rows_req[alloc_rows])
                result.node_alloc_vec = nv
        names_obj = np.empty(narr.idle.shape[0], object)
        names_obj[:len(narr.names)] = narr.names
        if placed_all.size:
            pnames = names_obj[a_real[placed_all]].tolist()
            ppipe = pipelined_np[placed_all].astype(bool).tolist()
        else:
            pnames, ppipe = [], []
        pidx = placed_all.tolist()
        uidx = unplaced_all.tolist()
        plo = np.searchsorted(placed_all, batch.job_task_start).tolist()
        phi = np.searchsorted(placed_all, batch.job_task_end).tolist()
        ulo = np.searchsorted(unplaced_all, batch.job_task_start).tolist()
        uhi = np.searchsorted(unplaced_all, batch.job_task_end).tolist()
        starts = batch.job_task_start.tolist()
        ends = batch.job_task_end.tolist()
        ready_list = ready_np.astype(bool).tolist()
        kept_list = kept_np.astype(bool).tolist()
        for job, jtasks in ordered_jobs:
            j = uid_to_j.get(job.uid, -1)
            if not jtasks or j < 0:
                # job contributed no tasks to the scan: readiness is decided
                # by its pre-existing occupancy alone
                ok = job.ready_task_num() >= job.min_available
                result.committed[job.uid] = ok
                result.kept[job.uid] = ok
                result.placements[job.uid] = []
                result.unplaced[job.uid] = []
                continue
            ok = ready_list[j]
            was_kept = kept_list[j]
            result.committed[job.uid] = ok
            result.kept[job.uid] = was_kept
            if ok or was_kept:
                placements = [
                    Placement(all_tasks[pidx[k]], pnames[k], ppipe[k])
                    for k in range(plo[j], phi[j])]
                un_iter = (uidx[k] for k in range(ulo[j], uhi[j]))
            else:
                placements = []
                un_iter = range(starts[j], ends[j])
            unplaced = []
            for t_idx in un_iter:
                task = all_tasks[t_idx]
                unplaced.append(task)
                unplaced_records.append(
                    (job, task, int(task_group_np[t_idx])))
            result.placements[job.uid] = placements
            result.unplaced[job.uid] = unplaced
        if unplaced_records:
            # fit errors need the predicate mask rows of only the unplaced
            # groups — a full [G, N] device->host pull costs seconds, so
            # gather just those rows in one transfer
            with trace.span("fit_errors", tasks=len(unplaced_records)):
                gs = sorted({g for _, _, g in unplaced_records})
                rows = np.asarray(gmask[jnp.asarray(np.array(gs, np.int32))])
                row_of = {g: rows[i] for i, g in enumerate(gs)}
                for job, task, g in unplaced_records:
                    self._record_fit_errors(job, task, narr, row_of[g])
        if self.explain:
            # decision provenance (trace/explain.py): derived from the
            # SAME mask/score tensors this place compiled, via a few
            # reductions; a capture failure costs log noise, never the
            # cycle's placements
            from ..trace import explain as _explain
            with trace.span("explain_capture"):
                try:
                    _explain.record_place(
                        self.ssn, batch, narr,
                        explain_stages or [("fit", gmask.sum(axis=1))],
                        gmask, static_score, self.score_weights(),
                        assign, result, served_tier)
                except Exception:
                    _logger.exception(
                        "placement explain capture failed "
                        "(placements unaffected)")
        return result

    def _execute_ladder(self, batch, narr, gmask, static_score, task_bucket,
                        pack_bonus, q_deserved, q_alloc0, ns_weight,
                        ns_alloc0, ns_total, ns_live, eps, allow_pipeline,
                        slot_kwargs, reduced=None):
        """The tier ladder + circuit breaker over one set of kernel
        inputs: the selected kernel first, then chunked, then the plain
        scan as last resort; breaker-open tiers are skipped until their
        half-open window (resilience.md).

        ``reduced`` (an ops/prune.PruneContext) runs the SAME ladder on
        the shortlist-union problem: the [G, N] mask/score tensors,
        slot rows and node state are gathered down to the union columns
        (sorted ascending, so the kernels' lowest-global-index
        tie-break maps 1:1 back to node order) and the returned assign
        indexes the REDUCED axis — the caller maps it back through the
        union. The sharded tier composes: a forced mesh (or a union
        still above the mesh floor) runs the reduced problem through
        shard_map over a fresh equal-width plan, and a crashing tier
        falls to the next one with the same reduced inputs.

        Returns (assign [T] np, pipelined, ready, kept, served_tier)."""
        from ..metrics import metrics as m
        from ..ops.allocate import gang_allocate_chunked
        from ..ops.pallas_allocate import resource_pad

        reduced_host = None
        reduced_plan = None
        if reduced is not None:
            gmask, static_score, slot_kwargs, reduced_host = \
                self._reduced_inputs(batch, narr, gmask, static_score,
                                     reduced)
            n_axis = reduced.u_pad
            # the reduced problem re-shards only when the operator
            # FORCED the mesh: level 1 already did the partition work
            # at distillation, and re-paying the per-step collective
            # sync over a pruned axis is pure loss on the auto path
            # (the 10x CPU emulation measured the dense sharded kernel
            # at 624 s where the reduced single-device native kernel
            # clears the same placements in seconds)
            use_mesh = self.mesh is not None and self.mesh_forced
            if use_mesh:
                from ..ops.sharded import build_shard_plan
                reduced_plan = build_shard_plan(
                    n_axis, self.mesh.devices.size,
                    pressure=reduced_host["n_tasks"])
        else:
            n_axis = int(narr.idle.shape[0])
            use_mesh = self.mesh is not None

        if use_mesh:
            ladder = [("sharded", None, {})]
        else:
            kernel_fn, kernel_kwargs = self._select_kernel(batch)
            if slot_kwargs and kernel_fn.__name__ == "gang_allocate_pallas":
                # the Pallas TPU kernel has no slot inputs (yet): a
                # constrained batch runs the chunked XLA kernel instead
                _log_once("solver kernel=pallas with per-task constraint "
                          "slots; running the chunked kernel for this "
                          "batch")
                kernel_fn, kernel_kwargs = gang_allocate_chunked, {}
            ladder = [(_TIER_OF_KERNEL.get(kernel_fn.__name__, "scan"),
                       kernel_fn, kernel_kwargs)]
        if ladder[0][0] != "scan":
            if ladder[0][0] != "chunked":
                ladder.append(("chunked", gang_allocate_chunked, {}))
            ladder.append(("scan", gang_allocate, {}))
        ladder_names = {t[0] for t in ladder}
        # a breaker whose window expired but whose tier is no longer
        # selected at all (kernel selection moved on) will never get a
        # half-open probe: retire it so the open-gauge doesn't stick
        for tname in [k for k, until in _breaker_open_until.items()
                      if _place_counter >= until
                      and k not in ladder_names]:
            del _breaker_open_until[tname]
            m.set_gauge(m.SOLVER_BREAKER_OPEN, 0.0, kernel=tname)
        eligible = [t for t in ladder
                    if _place_counter >= _breaker_open_until.get(t[0], 0)]
        if not eligible:
            eligible = ladder[-1:]   # every tier open: still try the last

        kernel_inputs = None
        account_transfer = False
        n_res = self.rindex.r
        for i, (tier, kfn, kkwargs) in enumerate(eligible):
            span_name = "sharded" if tier == "sharded" else kfn.__name__
            r_pad = resource_pad(n_res) if tier == "pallas" else n_res
            try:
                with trace.span("kernel", kernel=span_name,
                                g_pad=int(batch.g_pad), n_pad=n_axis,
                                t_pad=int(batch.task_group.shape[0]),
                                r=n_res, r_pad=r_pad,
                                pruned=reduced is not None):
                    if tier == "sharded":
                        assign, pipelined, ready, kept = self._run_sharded(
                            batch, narr, gmask, static_score, task_bucket,
                            pack_bonus, q_deserved, q_alloc0, ns_weight,
                            ns_alloc0, ns_total, ns_live, eps,
                            allow_pipeline, slot_kwargs=slot_kwargs,
                            plan=reduced_plan, node_host=reduced_host)
                    else:
                        if kernel_inputs is None:
                            account_transfer = True
                            # per-tier sub-phase attribution: the input
                            # tensor assembly and the host->device node
                            # staging get their own spans
                            with trace.span("tensor_build"):
                                with trace.span("transfer"):
                                    if reduced_host is not None:
                                        # the reduced union rows: a tiny
                                        # fresh upload beats touching
                                        # the full persistent buffers
                                        dev_nodes = {
                                            f: jnp.asarray(a) for f, a
                                            in reduced_host.items()}
                                        node_xfer = sum(
                                            int(a.nbytes) for a
                                            in reduced_host.values())
                                    else:
                                        dev_nodes, node_xfer = \
                                            self._device_node_inputs(narr)
                                kernel_inputs = (
                                    jnp.asarray(batch.task_group),
                                    jnp.asarray(batch.task_job),
                                    jnp.asarray(batch.task_valid),
                                    jnp.asarray(batch.group_req),
                                    gmask, static_score,
                                    jnp.asarray(task_bucket),
                                    jnp.asarray(pack_bonus),
                                    jnp.asarray(batch.job_min_available),
                                    jnp.asarray(batch.job_ready_base),
                                    jnp.asarray(batch.job_task_start),
                                    jnp.asarray(batch.job_n_tasks),
                                    jnp.asarray(batch.job_queue),
                                    jnp.asarray(batch.pool_queue),
                                    jnp.asarray(batch.pool_ns),
                                    jnp.asarray(batch.pool_job_start),
                                    jnp.asarray(batch.pool_njobs),
                                    jnp.asarray(ns_weight),
                                    jnp.asarray(ns_alloc0),
                                    jnp.asarray(ns_total),
                                    jnp.asarray(q_deserved),
                                    jnp.asarray(q_alloc0),
                                    dev_nodes["idle"],
                                    dev_nodes["future_idle"],
                                    dev_nodes["allocatable"],
                                    dev_nodes["n_tasks"],
                                    dev_nodes["max_tasks"], eps,
                                    self.score_weights())
                        if account_transfer:
                            # host->device staging bytes for this place
                            # (gmask/static_score at indices 4-5 are
                            # device-born — products of the context
                            # build — and the node tensors at 22-26 may
                            # be persistent device buffers whose real
                            # transfer node_xfer already measured as the
                            # scattered dirty rows)
                            account_transfer = False
                            xfer = node_xfer + sum(
                                int(getattr(a, "nbytes", 0))
                                for i, a in enumerate(kernel_inputs)
                                if i not in (4, 5, 22, 23, 24, 25, 26))
                            xfer += sum(int(getattr(a, "nbytes", 0))
                                        for a in slot_kwargs.values())
                            m.inc(m.DEVICE_TRANSFER_BYTES, float(xfer))
                            trace.add_tags(transfer_bytes=xfer)
                        # dispatch: the kernel call, with its wrapper's
                        # host work and any compile it triggers;
                        # readback: the wait for the device and the copy
                        with trace.span("execute"):
                            with trace.span("dispatch"):
                                assign, pipelined, ready, kept, _ = kfn(
                                    *kernel_inputs,
                                    allow_pipeline=allow_pipeline,
                                    ns_live=ns_live, **slot_kwargs,
                                    **kkwargs)
                            # blocks until the device finishes (a
                            # deferred kernel crash surfaces here,
                            # inside the tier's try)
                            with trace.span("readback"):
                                assign = np.asarray(assign)
            except Exception:
                if i + 1 >= len(eligible):
                    raise   # last resort crashed too: fail the cycle
                nxt = eligible[i + 1][0]
                _breaker_open_until[tier] = \
                    _place_counter + self.breaker_window
                m.inc(m.SOLVER_FALLBACK, **{"from": tier, "to": nxt})
                m.set_gauge(m.SOLVER_BREAKER_OPEN, 1.0, kernel=tier)
                _logger.exception(
                    "solver kernel %r crashed; falling back to %r for "
                    "this cycle (breaker open for the next %d placements)",
                    tier, nxt, self.breaker_window)
                continue
            if tier in _breaker_open_until:
                # half-open probe succeeded: close the breaker
                del _breaker_open_until[tier]
                m.set_gauge(m.SOLVER_BREAKER_OPEN, 0.0, kernel=tier)
                _logger.warning(
                    "solver kernel %r recovered; breaker closed", tier)
            m.inc(m.SOLVER_KERNEL_RUNS, kernel=tier)
            return np.asarray(assign), pipelined, ready, kept, tier

    def _reduced_inputs(self, batch, narr, gmask, static_score, reduced):
        """Gather the node-axis inputs down to the shortlist union:
        mask/score/slot columns device-side (they are device-born), the
        five node tensors host-side (the union is small — a fresh
        M-row upload is cheaper than scattering the persistent full
        buffers). Padding columns are forced infeasible, so the kernels
        can only select live union entries."""
        u_idx = jnp.asarray(reduced.union_padded.astype(np.int32))
        live = jnp.asarray(reduced.live)
        gmask_r = jnp.take(jnp.asarray(gmask), u_idx, axis=1) \
            & live[None, :]
        if _zeros_cache.get(tuple(static_score.shape)) is static_score:
            # the shared all-zeros buffer: a reduced-width shared zeros
            # beats gathering columns out of a multi-GB zeros array
            static_r = _shared_zeros((int(static_score.shape[0]),
                                      reduced.u_pad))
        else:
            static_r = jnp.take(jnp.asarray(static_score), u_idx, axis=1)
        slot_r = {}
        if batch.task_slot is not None:
            rows = np.take(batch.slot_rows, reduced.union_padded, axis=1)
            rows[:, ~reduced.live] = False
            slot_r = {"task_slot": jnp.asarray(batch.task_slot),
                      "slot_ok": jnp.asarray(rows)}
        uidx = reduced.union_padded
        host = {"idle": narr.idle[uidx],
                "future_idle": narr.future_idle[uidx],
                "allocatable": narr.allocatable[uidx],
                "n_tasks": narr.n_tasks[uidx],
                "max_tasks": narr.max_tasks[uidx]}
        return gmask_r, static_r, slot_r, host

    def _place_pruned(self, batch, narr, gmask, static_score, task_bucket,
                      pack_bonus, q_deserved, q_alloc0, ns_weight,
                      ns_alloc0, ns_total, ns_live, eps, allow_pipeline,
                      slot_kwargs):
        """One pruned placement attempt (docs/design/pruning.md):
        distill the per-gang shortlists, run the ladder on the union-
        reduced problem, and map placements back. Returns None whenever
        the full-width kernel must decide the cycle instead — a distill
        or ladder crash, a pre-kernel loss guard (low coverage / wide
        union / empty union), or the post-kernel exhaustion guard (a
        feasible valid task went unplaced while any pair's shortlist
        was truncated) — every fallback counted once on
        volcano_prune_fallback_total{reason}, so pruning can never lose
        a placement the dense kernel would have made. A fallback also
        tags the open ``solver.place`` span with ``prune_fallback`` (the
        reason) and ``fallback_pairs``."""
        from ..metrics import metrics as m
        from ..ops import prune as _prune
        from ..trace import explain as _explain

        def fall_back(reason, pairs=0):
            m.inc(m.PRUNE_FALLBACK, reason=reason)
            trace.add_tags(prune_fallback=reason, fallback_pairs=int(pairs))

        plan = None
        if self.mesh is not None:
            # the ShardPlan's contiguous ranges are the two-level
            # partition structure; its construction must never cost the
            # cycle (single-level distillation is the degraded mode)
            try:
                plan = self._shard_plan(narr, self.mesh.devices.size)
            except Exception:
                plan = None
        try:
            with trace.span("prune_distill", k=self.prune.k):
                ctx = _prune.distill(batch, narr, gmask, static_score,
                                     self.score_weights(), self.prune,
                                     plan=plan,
                                     dedupe=self._prune_dedupe_ok)
        except Exception:
            _logger.exception("shortlist distillation crashed; running "
                              "the full-width kernel for this cycle")
            fall_back("crash")
            return None
        guard = ctx.pre_guard()
        if guard is not None:
            # one fallback per place(), whatever the reason — the pair
            # count behind it rides the summary (fallback_pairs), not
            # the counter, so the reasons stay unit-comparable
            reason, count = guard
            ctx.fallback = reason
            ctx.fallback_pairs = int(count)
            fall_back(reason, count)
            _explain.note_prune(ctx.summary())
            return None
        try:
            with trace.span("pruned_kernel", union=ctx.m_real,
                            level=ctx.level):
                out = self._execute_ladder(
                    batch, narr, gmask, static_score, task_bucket,
                    pack_bonus, q_deserved, q_alloc0, ns_weight, ns_alloc0,
                    ns_total, ns_live, eps, allow_pipeline, slot_kwargs,
                    reduced=ctx)
        except Exception:
            _logger.exception("pruned kernel ladder crashed at every "
                              "tier; running the full-width kernel")
            ctx.fallback = "crash"
            fall_back("crash")
            _explain.note_prune(ctx.summary())
            return None
        assign_r, pipelined, ready, kept, tier = out
        assign = ctx.map_assign(assign_r)
        if ctx.post_guard(assign, batch):
            ctx.fallback = "shortlist_exhausted"
            fall_back("shortlist_exhausted", ctx.truncated.sum())
            _explain.note_prune(ctx.summary())
            return None
        m.inc(m.PRUNE_RUNS, level=ctx.level)
        m.set_gauge(m.PRUNE_UNION_WIDTH, float(ctx.m_real))
        _explain.note_prune(ctx.summary())
        return assign, pipelined, ready, kept, tier

    def _shard_plan(self, narr: NodeArrays, n_devices: int):
        """The topology-aware node partition for this place: reused from
        the persistent solver state while the host arrays persist
        (rebalance ONLY on structural node change — the per-device
        buffers keep their dirty-row scatter path), rebuilt from the
        snapshot's per-node resident-task pressure otherwise."""
        from ..ops.sharded import build_shard_plan
        state = self._incr_state()
        if state is not None and state.narr is narr \
                and state.plan is not None \
                and state.plan.n_devices == n_devices \
                and state.plan.n_rows == narr.idle.shape[0]:
            return state.plan
        plan = build_shard_plan(narr.idle.shape[0], n_devices,
                                pressure=narr.n_tasks)
        self._note_shard_gauges(plan, narr)
        if state is not None and state.narr is narr:
            state.plan = plan
            state.shard_dev = None
            state.shard_dirty_rows = set()
        return plan

    @staticmethod
    def _note_shard_gauges(plan, narr: NodeArrays) -> None:
        """Per-shard occupancy (real rows vs the equal-width layout
        block) and resident-task pressure off a freshly built ShardPlan,
        plus the max/mean pressure-imbalance gauge — published once per
        rebalance (the plan is persistent across steady-state cycles)."""
        from ..metrics import metrics as m
        if plan.n_devices <= 0:
            return
        pressures = []
        for d in range(plan.n_devices):
            lo, hi = int(plan.bounds[d]), int(plan.bounds[d + 1])
            width = hi - lo
            # the same pressure model build_shard_plan balances on:
            # resident tasks + 1 per row
            pressure = float(narr.n_tasks[lo:hi].sum()) + width
            pressures.append(pressure)
            m.set_gauge(m.SHARD_OCCUPANCY,
                        round(width / max(1, plan.rows_per_shard), 4),
                        shard=str(d))
            m.set_gauge(m.SHARD_PRESSURE, pressure, shard=str(d))
        mean = sum(pressures) / len(pressures)
        m.set_gauge(m.SHARD_PRESSURE_IMBALANCE,
                    round(max(pressures) / mean, 4) if mean > 0 else 1.0)

    def _sharded_device_node_inputs(self, narr: NodeArrays, plan, mesh):
        """Sharded twin of :meth:`_device_node_inputs`: the five node
        tensors in LAYOUT order as per-device resident buffers. On a
        steady-state cycle only the dirty rows are scattered — the
        update is routed to the owning shard (the scatter indices land
        inside one device's layout block per node). Returns
        ({field: device array}, host->device bytes)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..metrics import metrics as m
        n = NamedSharding(mesh, P("nodes"))
        nr = NamedSharding(mesh, P("nodes", None))
        sharding_of = {"idle": nr, "future_idle": nr, "allocatable": nr,
                       "n_tasks": n, "max_tasks": n}

        def full_host():
            return {"idle": plan.take(narr.idle, 0),
                    "future_idle": plan.take(narr.future_idle, 0),
                    "allocatable": plan.take(narr.allocatable, 0),
                    "n_tasks": plan.take(narr.n_tasks, 0),
                    "max_tasks": plan.take(narr.max_tasks, 0)}

        state = self._incr_state()
        if state is None or state.narr is not narr \
                or state.plan is not plan:
            host = full_host()
            return {f: jax.device_put(a, sharding_of[f])
                    for f, a in host.items()}, \
                sum(int(a.nbytes) for a in host.values())
        if state.shard_dev is None:
            host = full_host()
            state.shard_dev = {f: jax.device_put(a, sharding_of[f])
                               for f, a in host.items()}
            state.shard_dirty_rows = set()
            m.inc(m.SOLVER_DEVICE_BUFFER, event="rebuild")
            return dict(state.shard_dev), \
                sum(int(a.nbytes) for a in host.values())
        xfer = 0
        rows = sorted(r for r in state.shard_dirty_rows
                      if r < plan.n_rows)
        if rows:
            lrows = plan.layout_of_node[rows]
            idx = jnp.asarray(lrows.astype(np.int32))
            host_rows = {
                "idle": narr.idle[rows],
                "future_idle": narr.idle[rows] + narr.releasing[rows]
                - narr.pipelined[rows],
                "allocatable": narr.allocatable[rows],
                "n_tasks": narr.n_tasks[rows],
                "max_tasks": narr.max_tasks[rows]}
            for f in self._DEV_NODE_FIELDS:
                hr = host_rows[f]
                state.shard_dev[f] = \
                    state.shard_dev[f].at[idx].set(jnp.asarray(hr))
                xfer += int(hr.nbytes)
            state.shard_dirty_rows = set()
        m.inc(m.SOLVER_DEVICE_BUFFER, event="reuse")
        return dict(state.shard_dev), xfer

    def _run_sharded(self, batch, narr, gmask, static_score, task_bucket,
                     pack_bonus, q_deserved, q_alloc0, ns_weight, ns_alloc0,
                     ns_total, ns_live, eps, allow_pipeline,
                     slot_kwargs=None, plan=None, node_host=None):
        """Node-axis-sharded placement over the device mesh: each chip
        owns a topology-aware contiguous node range's scan state (the
        ShardPlan balances per-shard resident-task pressure, not a naive
        N/D split), collectives ride ICI (ops/sharded.py). Placement
        indices come back in layout order and are mapped to node order
        through the plan's gather.

        ``plan``/``node_host`` override the persistent topology plan and
        node tensors for the PRUNED reduced-axis run (ops/prune.py): the
        caller passes a fresh equal-width plan over the shortlist union
        and the five union-gathered host node arrays — the persistent
        full-width buffers stay untouched, and the returned assign
        indexes the reduced axis."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        d = mesh.devices.size
        if plan is None:
            plan = self._shard_plan(narr, d)

        with_slots = bool(slot_kwargs)
        fn = _get_sharded_fn(mesh, allow_pipeline, ns_live,
                             getattr(self, "mesh_chunk", 16),
                             with_slots=with_slots)

        gn = NamedSharding(mesh, P(None, "nodes"))
        rep = NamedSharding(mesh, P())

        from ..metrics import metrics as m
        # sub-phase attribution: the node-tensor staging + layout
        # gathers are the sharded tier's "tensor build" (the small
        # replicated put()s ride the execute/dispatch span).
        # try/finally: a crashing build must pop its span — the tier
        # ladder catches the crash and the fallback tier's spans would
        # otherwise nest under a dead parent
        tb = trace.span("tensor_build")
        tb.__enter__()
        try:
            with trace.span("transfer"):
                if node_host is None:
                    dev_nodes, node_xfer = self._sharded_device_node_inputs(
                        narr, plan, mesh)
                else:
                    n_s = NamedSharding(mesh, P("nodes"))
                    nr_s = NamedSharding(mesh, P("nodes", None))
                    sharding_of = {"idle": nr_s, "future_idle": nr_s,
                                   "allocatable": nr_s, "n_tasks": n_s,
                                   "max_tasks": n_s}
                    host = {f: plan.take(node_host[f], 0)
                            for f in self._DEV_NODE_FIELDS}
                    dev_nodes = {f: jax.device_put(a, sharding_of[f])
                                 for f, a in host.items()}
                    node_xfer = sum(int(a.nbytes) for a in host.values())
            xfer = [node_xfer]

            def put(a, s):
                # host->device byte accounting: numpy inputs are genuine
                # transfers; already-device arrays (gmask/static_score) are
                # reshards and don't count
                if isinstance(a, np.ndarray):
                    xfer[0] += int(a.nbytes)
                return jax.device_put(a, s)

            # [G, N] -> [G, layout] gathers run device-side (gmask and
            # static_score are products of the device context build)
            gmask_l = plan.take_device(jnp.asarray(gmask), axis=1, fill=False)
            score_l = plan.take_device(jnp.asarray(static_score), axis=1,
                                       fill=0.0)
            slot_args = ()
            if with_slots:
                # slot rows ride the same node-axis layout gather; the
                # all-true row's padding columns go False with fill, which
                # is inert (gmask already excludes layout padding rows)
                srows_l = plan.take_device(
                    jnp.asarray(slot_kwargs["slot_ok"]), axis=1, fill=False)
                slot_args = (put(np.asarray(batch.task_slot), rep),
                             put(srows_l, gn))
        finally:
            tb.__exit__()

        with trace.span("execute"):
            with trace.span("dispatch"):
                assign, pipelined, ready, kept, _idle = fn(
                    put(batch.task_group, rep), put(batch.task_job, rep),
                    put(batch.task_valid, rep), put(batch.group_req, rep),
                    put(gmask_l, gn), put(score_l, gn),
                    put(task_bucket, rep), put(pack_bonus, rep),
                    put(batch.job_min_available, rep),
                    put(batch.job_ready_base, rep),
                    put(batch.job_task_start, rep),
                    put(batch.job_n_tasks, rep),
                    put(batch.job_queue, rep), put(batch.pool_queue, rep),
                    put(batch.pool_ns, rep), put(batch.pool_job_start, rep),
                    put(batch.pool_njobs, rep), put(ns_weight, rep),
                    put(ns_alloc0, rep), put(ns_total, rep),
                    put(q_deserved, rep), put(q_alloc0, rep),
                    dev_nodes["idle"], dev_nodes["future_idle"],
                    dev_nodes["allocatable"], dev_nodes["n_tasks"],
                    dev_nodes["max_tasks"],
                    put(np.asarray(eps), rep), self.score_weights(),
                    *slot_args)
            with trace.span("readback"):
                a = np.asarray(assign)
        if xfer[0]:
            m.inc(m.DEVICE_TRANSFER_BYTES, float(xfer[0]))
            trace.add_tags(transfer_bytes=xfer[0])
        # layout index -> node index (the gather is strictly increasing
        # over real rows, so tie-breaks already matched node order)
        assign = np.where(a >= 0,
                          plan.gather[np.clip(a, 0, plan.n_layout - 1)],
                          -1).astype(np.int32)
        return assign, pipelined, ready, kept

    def _record_fit_errors(self, job: JobInfo, task: TaskInfo,
                           narr: NodeArrays, mask_row: np.ndarray) -> None:
        """Summarize why a task found no node (FitErrors analogue)."""
        fe = FitErrors()
        n_real = len(narr.names)
        blocked = int(n_real - mask_row[:n_real].sum())
        if blocked:
            fe.set_error(f"{blocked}/{n_real} nodes are unavailable for task "
                         f"{task.namespace}/{task.name}: predicates failed "
                         f"or insufficient resources")
        else:
            fe.set_error("gang rollback or all feasible nodes already full")
        job.nodes_fit_errors[task.uid] = fe
