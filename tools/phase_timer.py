"""Phase-level timing of one full runOnce at bench scale (CPU by default).

Historical note: this tool used to monkeypatch the live code paths with
perf_counter wrappers from the outside. The production cycle now records
itself through the flight recorder (volcano_tpu/trace): every phase below
comes from the REAL spans the scheduler emits — the same data `/debug/trace`
serves in production — so the table here is exactly what a Perfetto load of
the trace shows.

Usage:  JAX_PLATFORMS=cpu python tools/phase_timer.py [n_tasks] [n_nodes]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    n_tasks = int(sys.argv[1]) if len(sys.argv) > 1 else 50_000
    n_nodes = int(sys.argv[2]) if len(sys.argv) > 2 else 10_000

    from volcano_tpu import bench_suite as bs
    from volcano_tpu.trace import tracer

    def log(msg):
        print(f"[phase] {msg}", file=sys.stderr, flush=True)

    tracer.enable()

    # cold env: compile
    log(f"building cold env {n_tasks}x{n_nodes}")
    store, cache, binder, conf = bs._cycle_env(bs.CONF_FULL)
    bs._populate(store, n_nodes=n_nodes, n_jobs=n_tasks // 8, gang=8)
    log("cold cycle (compile)")
    bs._run_cycle(cache, conf)
    cache.flush_executors(timeout=600.0)
    del store, cache, binder

    log(f"building measured env {n_tasks}x{n_nodes}")
    store, cache, binder, conf = bs._cycle_env(bs.CONF_FULL)
    bs._populate(store, n_nodes=n_nodes, n_jobs=n_tasks // 8, gang=8)
    log("measured cycle")
    ms = bs._run_cycle(cache, conf)
    rec = tracer.last_record()
    t0 = time.perf_counter()
    cache.flush_executors(timeout=600.0)
    flush_ms = (time.perf_counter() - t0) * 1000.0

    phases = tracer.flat_phases(rec)
    summary = tracer.summary(rec)
    print(f"\n=== phase table ({n_tasks}x{n_nodes}, "
          f"binds={len(binder.binds)}) ===")
    print(f"{'full runOnce':<46} {ms:>10.1f} ms")
    for path in sorted(phases):
        depth = path.count("/")
        label = "  " * (depth + 1) + path.rsplit("/", 1)[-1]
        e = phases[path]
        count = f" x{e['count']}" if e["count"] > 1 else ""
        print(f"{label + count:<46} {e['ms']:>10.1f} ms")
    print(f"{'bind flush (background)':<46} {flush_ms:>10.1f} ms")
    print(f"span coverage of cycle wall time: "
          f"{summary['coverage'] * 100:.1f}%  "
          f"(tags: {summary['tags']})")
    # steady-state cycle after flush
    steady = min(bs._run_cycle(cache, conf) for _ in range(2))
    print(f"{'steady-state runOnce':<46} {steady:>10.1f} ms")


if __name__ == "__main__":
    main()
