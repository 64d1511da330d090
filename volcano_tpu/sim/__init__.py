"""Cluster churn simulator: an event-driven workload/fault harness that
drives the real scheduler (cache, store, actions, plugins — no mocks)
under a virtual clock, audits invariants after every tick, and shrinks
any failure to a deterministic ``{seed, tick}`` repro.

See docs/design/simulation.md for the event model, invariant catalog and
repro-bundle format; ``vcctl sim run|smoke|replay`` and
``python -m volcano_tpu.sim.cli`` are the entry points.

Attribute access is lazy (PEP 562): ``vcctl`` registers the ``sim``
argparse group on every invocation, and importing the engine eagerly
would drag the whole scheduler stack (jax included, ~2.4 s) into
``vcctl job list``.
"""

_EXPORTS = {
    "DEFAULT_CONF": "engine", "SimConfig": "engine", "SimEngine": "engine",
    "SimResult": "engine", "run_sim": "engine",
    "Event": "events", "EventQueue": "events", "make_event": "events",
    "FaultConfig": "faults", "FlakyBinder": "faults",
    "CycleContext": "invariants", "Violation": "invariants",
    "check_all": "invariants",
    "load_bundle": "replay", "replay_bundle": "replay",
    "write_repro_bundle": "replay",
    "WorkloadConfig": "workload", "dump_trace": "workload",
    "load_trace": "workload",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    module = _EXPORTS.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib
    mod = importlib.import_module(f".{module}", __name__)
    value = getattr(mod, name)
    globals()[name] = value   # cache: subsequent access skips __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
