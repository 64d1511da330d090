"""vc-scheduler binary equivalent (reference: cmd/scheduler/app/server.go).

Runs the scheduler component alone against an embedded store with leader
election and a Prometheus endpoint. For a full control plane in one
process use cmd.cluster; this entry point exists for component-parity and
HA topologies where several scheduler candidates share one store.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading

from ..apiserver.store import ObjectStore
from ..scheduler import Scheduler
from ..utils.leaderelection import LeaderElector


def add_flags(parser: argparse.ArgumentParser) -> None:
    """cmd/scheduler/app/options/options.go:81-108"""
    parser.add_argument("--server", default=None,
                        help="remote apiserver URL (multi-process mode, "
                             "docs/deployment.md); default: embedded store")
    parser.add_argument("--scheduler-name", default="volcano")
    parser.add_argument("--scheduler-conf", default=None)
    parser.add_argument("--schedule-period", type=float, default=1.0)
    parser.add_argument("--default-queue", default="default")
    parser.add_argument("--leader-elect", action="store_true")
    parser.add_argument("--lock-object-namespace", default="volcano-system")
    parser.add_argument("--listen-address", default=":8080")
    parser.add_argument("--plugins-dir", default=None)
    parser.add_argument("--percentage-nodes-to-find", type=int, default=0,
                        help="accepted for flag parity; the TPU solver "
                             "evaluates all nodes exhaustively")
    parser.add_argument("--enable-tracing", action="store_true",
                        help="turn on the cycle flight recorder + pod "
                             "lifecycle ledger + metrics timeseries "
                             "(/debug/trace, /debug/cycles, /debug/pending, "
                             "/debug/latency, /debug/timeseries on "
                             "--listen-address; <2%% cycle overhead); "
                             "also enabled by VOLCANO_TRACE=1")
    parser.add_argument("--trace-cycles", type=int, default=None,
                        help="flight-recorder ring buffer: how many recent "
                             "cycles to keep (default 64, or "
                             "VOLCANO_TRACE_CAPACITY when set)")
    parser.add_argument("--version", action="store_true")


def run_scheduler(store: ObjectStore, args) -> Scheduler:
    if args.plugins_dir:
        from ..framework.registry import load_plugins_dir
        load_plugins_dir(args.plugins_dir)
    scheduler = Scheduler(store, scheduler_name=args.scheduler_name,
                          scheduler_conf_path=args.scheduler_conf,
                          schedule_period=args.schedule_period)
    if args.leader_elect:
        identity = f"{os.uname().nodename}-{os.getpid()}"
        elector = LeaderElector(
            store, identity, lease_name="vc-scheduler",
            on_started_leading=scheduler.start,
            on_stopped_leading=scheduler.stop)
        # lease fencing (docs/design/failover.md): run_once no-ops while
        # standby, and bind/patch writes carry the elector's token so a
        # deposed incarnation can't commit after a takeover
        scheduler.elector = elector
        scheduler.cache.fence_source = lambda: elector.fencing_token
        elector.start()
    else:
        scheduler.start()
    return scheduler


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="vc-scheduler")
    add_flags(parser)
    args = parser.parse_args(argv)
    if args.version:
        from ..version import print_version_and_exit
        print_version_and_exit()
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from ..trace import tracer
    if args.enable_tracing:
        # an explicit --trace-cycles wins; else VOLCANO_TRACE_CAPACITY;
        # else the tracer's default (64)
        cap = args.trace_cycles
        if cap is None:
            cap = tracer.env_capacity()
        tracer.enable(capacity=cap)
    elif tracer.enable_from_env() and args.trace_cycles is not None:
        tracer.configure(args.trace_cycles)
    if args.server:
        from ..apiserver.remote import RemoteStore
        store = RemoteStore(args.server)
        store.run()
    else:
        store = ObjectStore()
    run_scheduler(store, args)
    from ..metrics.server import MetricsServer
    host, _, port_s = args.listen_address.rpartition(":")
    try:
        MetricsServer(host or "127.0.0.1", int(port_s)).start()
    except OSError as e:
        # a second candidate on the same host must not die over the
        # metrics port (the reference runs candidates in separate pods);
        # leader election and scheduling proceed without exposition
        print(f"metrics endpoint unavailable ({e}); continuing without",
              file=sys.stderr)
    print("vc-scheduler running against "
          + (args.server or "embedded store"), flush=True)
    threading.Event().wait()
    return 0


if __name__ == "__main__":
    sys.exit(main())
