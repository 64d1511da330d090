# CI-style gates (the reference's Makefile:115-141 equivalents).

PYTHON ?= python

.PHONY: test unit-test e2e multichip-dryrun \
	deploy deploy-up trace-smoke sim-smoke flush-bench chaos-smoke \
	failover-smoke obs-smoke incr-smoke multichip-smoke constraint-smoke \
	storm-smoke explain-smoke prune-smoke federation-smoke \
	federation-proc-smoke durability-smoke lint sanitize

# one-command deployment (the reference's installer/volcano-development.yaml
# analogue): bring up apiserver + webhook-manager (TLS admission) +
# controller-manager + scheduler, run a smoke job through the full path,
# tear down. `make deploy-up` leaves the control plane running.
deploy:
	$(PYTHON) -m volcano_tpu.cmd.deploy

deploy-up:
	$(PYTHON) -m volcano_tpu.cmd.deploy --keep

# invariant lint suite (the `go vet` equivalent,
# docs/design/static_analysis.md): AST-enforced clock / lock /
# native-fallback / seeded-randomness / jit-purity contracts over
# volcano_tpu/. Nonzero exit on any finding or stale baseline entry.
lint:
	$(PYTHON) -m volcano_tpu.lint

# native sanitizer gate (the `go test -race` equivalent for the C hot
# path): rebuilds fastmodel.c + solver.cc under ASan/UBSan at a
# distinct artifact hash and re-runs the native parity suites with the
# runtimes LD_PRELOADed (tools/sanitize_gate.py). ~2 min.
sanitize:
	JAX_PLATFORMS=cpu $(PYTHON) tools/sanitize_gate.py

# the standard unit gate (reference: make unit-test, go test -p 8 -race ...)
# tests force the virtual 8-device CPU mesh (tests/conftest.py); the
# concurrency suite is the -race-equivalent adversarial gate; the lint
# suite runs first — a contract violation fails the gate before the
# (much slower) pytest sweep starts
test: unit-test

unit-test: lint
	$(PYTHON) -m pytest tests/ -q

# the multi-process control-plane e2e alone (four OS processes)
e2e:
	$(PYTHON) -m pytest tests/test_multiprocess.py tests/test_e2e_sim.py -q

# flight-recorder smoke gate: one small traced cycle, /debug/trace +
# /debug/pending fetched over HTTP and validated against the span schema,
# plus the <2% tracer-overhead regression check. The same tests run in
# tier-1 (tests/test_trace.py); this target is the fast standalone gate.
trace-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_trace.py -q \
		-k "smoke or overhead"

# bind-flush micro-gate: a 5k-bind coalesced flush through the
# production cache + store (sharded three-stage pipeline with the
# native publish/echo/apply passes on, bulk echo ingest), run TWICE on
# fresh envs — exit 1 unless the journal / rv / bind / lifecycle-ledger
# fingerprints are bit-identical (the pipeline's determinism contract,
# docs/design/bind_pipeline.md). Seconds. `--tasks 50000 --nodes 10000`
# measures the full paper regime standalone; `--profile` attributes it.
flush-bench:
	JAX_PLATFORMS=cpu $(PYTHON) tools/flush_bench.py

# churn-simulator smoke gate: 200 virtual-time ticks of seeded churn
# (>=2k tasks through 512 nodes, node flaps + bind-failure + evict-storm
# injection) with the invariant catalog on, run TWICE — the second run
# must reproduce the first's bind sequence bit-identically. Exit 1 on
# any invariant violation (a repro bundle lands in CWD) or determinism
# break. ~55 s on an idle machine. Runs the flush-bench double-run
# first: the sharded bind flush must prove its determinism before the
# sim's own double-run relies on it.
sim-smoke: flush-bench
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli smoke

# commit-path resilience gate (docs/design/resilience.md), after
# sim-smoke: a churn run with 2% injected bind failures PLUS a targeted
# poison pod. Exit 1 unless gang atomicity held with NO bind-failure
# waiver (partial gangs healed by the commit path), the poison pod
# landed in quarantine with a why-pending reason, and a double run from
# the same seed was bit-identical.
chaos-smoke: sim-smoke
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli chaos

# control-plane failover gate (docs/design/failover.md), after
# chaos-smoke: leader-lease lapse with a mid-flush crash, stateless and
# snapshot-restore scheduler kills, watch-delivery drops and bind
# failures together under leader election on the virtual clock. Exit 1
# unless every audited tick stayed invariant-clean (crash-left partial
# gangs reconverged, no silent rebinds, journal gap-free), the deposed
# incarnation's stale-token write was rejected by the fence, at least
# one watch-fault divergence was detected AND repaired by anti-entropy,
# the standby window surfaced its why-pending reason, and a double run
# from the same seed was bit-identical.
failover-smoke: chaos-smoke
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli failover

# observability gate (docs/design/observability.md), after
# failover-smoke: a short churn run asserting the pod lifecycle ledger
# fills (nonzero e2e + per-hop histograms), leaves ZERO orphaned
# entries, stamps traceable bind correlation IDs (scheduler -> store
# journal join), and double-runs bit-identically on both the bind
# sequence AND the ledger aggregate fingerprint.
obs-smoke: failover-smoke
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli obs

# incremental-cycle gate (docs/design/incremental_cycle.md), after
# obs-smoke: 200 ticks of seeded churn (bursty backlog, node flaps, a
# quiet tail) executed TWICE — once on the incremental persistent
# snapshot, once with full rebuilds forced every tick. Exit 1 unless the
# two runs' bind sequences AND lifecycle-ledger aggregates are
# bit-identical, both stay invariant-clean (incl. journal order), and
# the incremental/quiet fast paths demonstrably engaged.
incr-smoke: obs-smoke
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli incr

# multi-chip sharded-default gate (docs/design/sharded_kernel.md),
# after incr-smoke: the same seeded 200-tick churn (flaps, gang pod
# losses, quiet tail) run on the 8-device sharded solver TWICE and on
# the single-device solver once. Exit 1 unless every audited tick
# stayed invariant-clean in all three runs, the sharded kernel provably
# served the mesh runs' placements, the mesh runs' bind AND
# lifecycle-ledger fingerprints are bit-identical with the
# single-device run (the exactness contract under churn), and the
# sharded double run reproduced itself bit-identically.
multichip-smoke: incr-smoke
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -m volcano_tpu.sim.cli mesh

# constraint-kernel gate (docs/design/constraints.md), after
# multichip-smoke: seeded churn of zone-spread gangs, one-per-zone
# anti-affinity pairs and a priority preemption/reclaim storm over
# elastic filler, run THREE times — compiled constraint tensors +
# vmapped victim-selection kernel (twice, for determinism) and with the
# per-task Python predicate path + Python victim walk forced. Exit 1
# unless every audited tick is clean on the whole invariant catalog
# (incl. the spread_skew / anti_affinity checkers), both kernels
# provably ran with zero crash fallbacks, evictions happened, and all
# three runs' bind+evict AND lifecycle-ledger fingerprints are
# bit-identical.
constraint-smoke: multichip-smoke
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli constraints

# watcher-storm serving gate (docs/design/serving.md), after
# constraint-smoke: the real scheduler churns through a bind-flush
# storm while the serving hub fans the journal out to 1k+ subscribers
# across tenants, with seeded frame-layer drops and a mid-storm journal
# gap. Exit 1 unless every subscriber cursor converges to the final
# store rv with ZERO unrecovered frame-chain gaps, the structured
# relist path was taken, at least one tenant was throttled at the
# admission edge, bursts arrived as coalesced frames (events per frame
# >> 1), the engine's invariant catalog stayed clean, and a double run
# was bit-identical on bind AND ledger fingerprints.
storm-smoke: constraint-smoke
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli storm

# placement-explainer gate (docs/design/observability.md), after
# storm-smoke: constrained churn plus a preemption storm with the
# explainer on. Exit 1 unless every placed gang carries a provenance
# record (winning node, per-constraint elimination ladder, top-k
# candidates with score-term decomposition), every record's
# eliminations sum exactly to the node axis, victim decisions were
# recorded off the vectorized victim kernel, the explain fingerprint
# is bit-identical across a same-seed double run, and the off-mode
# hook overhead measures <2% on the steady cycle.
explain-smoke: storm-smoke
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli explain

# candidate-pruning gate (docs/design/pruning.md), after explain-smoke:
# seeded constrained churn (zoned topology, hard/soft spread gangs,
# one-per-zone anti pairs) run three ways — pruned (prune.enable true
# at k = the node count, the complete-shortlist exactness regime), a
# pruned double run, and a dense-forced control. Exit 1 unless every
# audited tick stayed invariant-clean in all three runs, the pruned
# kernel provably served (and the control provably did not), zero
# prune crash/guard fallbacks fired, and the bind AND lifecycle-ledger
# fingerprints are bit-identical across all three runs.
prune-smoke: explain-smoke
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli prune

# federated-control-plane gate (docs/design/federation.md), after
# prune-smoke: a seeded bind storm on the leader store while the
# journal replicates to two follower mirrors and 1k+ subscribers watch
# across all three replicas' hubs. Mid-storm one follower is killed
# (every cursor it served hands off to a live peer), the leader
# journal is force-cleared (followers bootstrap from snapshot), and an
# election advances the epoch while the deposed leader ships one more
# frame (the mirrors must fence it). Exit 1 unless every surviving
# cursor converged, zero unrecovered gaps, >=1 fenced stale-leader
# frame, the cross-replica anti-entropy audit reports every settled
# mirror fingerprint-identical to the leader, and a double run is
# bit-identical on bind AND ledger fingerprints.
federation-smoke: prune-smoke
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli federation

# federation PROCESS-mode chaos gate, after federation-smoke: three
# real vc-apiserver OS processes behind deterministic fault-injecting
# TCP proxies (seeded connection resets, byte stalls, mid-frame
# truncations, half-open partitions, lease-push drops), elector-driven
# epochs end-to-end. Episode A half-open-partitions the leader until a
# follower's elector takes the lease (fencing token bumped) and one
# deposed-regime write is rejected 412; episode B SIGKILLs the new
# leader mid-flush (writes fail fast with 503 + Retry-After, the
# original replica takes over, the supervisor restarts the corpse as a
# snapshot-bootstrapping follower). Exit 1 unless both takeovers are
# elector-driven, every watch cursor converged with zero lost or
# duplicated events, every acked write survived (post-replay diff
# empty), the cross-replica audit is identical, and a double run is
# bit-identical on the bind AND ledger content fingerprints — the
# whole gate watchdogged.
federation-proc-smoke: federation-smoke
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli federation --procs

# WAL durability gate (docs/design/durability.md), after
# federation-proc-smoke: the crash-consistency story end to end.
# In-process: a torn final record is truncated (recovered store
# bit-identical to the durable prefix), a mid-log bit flip makes
# recovery REFUSE with segment/offset/CRC evidence, and an ENOSPC
# episode flips the store read-only (structured 503 + Retry-After over
# HTTP) then heals on freed space with a contiguous log. Process tier:
# a real vc-apiserver --data-dir child is SIGKILLed at each of three
# injection points (pre-fsync, post-fsync-pre-rename, mid-compaction),
# supervised back up, and must replay its local WAL; after the writer
# reconciles its acked-op map, the journal/bind/ledger content
# fingerprints must be bit-identical to an uninterrupted run of the
# same seeded plan — and the whole gate double-runs bit-identically.
durability-smoke: federation-proc-smoke
	JAX_PLATFORMS=cpu $(PYTHON) -m volcano_tpu.sim.cli durability

# multi-chip sharding dryrun on the virtual CPU mesh (the raw
# shard_map program + full-pipeline one-shot; multichip-smoke is the
# gated churn version)
multichip-dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
