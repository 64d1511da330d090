"""Instrumented, timeout-bounded accelerator backend-init probe.

A parent process that must stay off JAX (a chip belongs to one
process at a time) can ask this probe whether the machine has a TPU
that answers. The probe runs in a child process that emits one JSON line
per init phase —

    hw_scan       is a TPU device node (/dev/accel*, /dev/vfio) present?
    import_jax    import jax (wheel load, plugin discovery)
    backend_init  jax.devices() (runtime handshake)
    device_op     first op on the device (executable path proven)

— so a failed or hung bring-up names the phase it stopped in
(``last_phase`` is the last phase that COMPLETED). The parent runs the
child under a hard timeout and kill, records
``volcano_backend_probe_total{outcome="alive"|"dead"|"hang"}``, and
returns a structured verdict dict for the caller to log. Where the libtpu plug-in is installed but no TPU device node
exists (a CPU-only machine with the same installation, such as a
development sandbox), the verdict is ``dead`` with a named
``root_cause`` in about a second, without attempting the init
(`VOLCANO_PROBE_FORCE_INIT=1` forces it). On a hang the child's
``faulthandler`` dump rides the verdict as ``hang_stack``.

Run standalone:  python -m volcano_tpu.ops.backend_probe [--timeout 120]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

DEFAULT_TIMEOUT_S = 120.0

# The child runs as `python -c` with NO volcano_tpu import: importing
# this module's own package (volcano_tpu.ops) pulls jax at import time,
# which would both pre-pay the import the "import_jax" phase is supposed
# to measure and drag jax into any parent that merely wants run_probe.
_CHILD_CODE = r"""
import faulthandler, json, os, sys, time
t0 = time.monotonic()

# a hang must name its wedged frame: dump every thread's stack to
# stderr shortly before the parent's kill lands (the parent folds the
# dump into the verdict as hang_stack)
try:
    budget = float(os.environ.get("VOLCANO_PROBE_STACK_AFTER", "0"))
    if budget > 0:
        faulthandler.dump_traceback_later(budget, exit=False,
                                          file=sys.stderr)
except Exception:
    pass

def emit(phase, **extra):
    rec = {"phase": phase, "ms": round((time.monotonic() - t0) * 1000.0, 1)}
    rec.update(extra)
    print(json.dumps(rec), flush=True)

import jax
emit("import_jax", version=getattr(jax, "__version__", "?"))
devs = jax.devices()
emit("backend_init", platform=devs[0].platform, devices=len(devs))
import jax.numpy as jnp
x = jnp.arange(8)
jax.block_until_ready(x + 1)
emit("device_op", platform=devs[0].platform)
"""


def _tpu_hw_scan() -> dict:
    """Host-side TPU presence scan, no jax import: the PJRT TPU plugin
    wedges backend_init when installed without hardware, so the probe
    checks the hardware story FIRST. ``/dev/accel*`` is a definitive
    TPU signal; ``/dev/vfio/*`` is AMBIGUOUS (newer TPU VMs attach via
    vfio, but so does GPU passthrough), so vfio presence keeps the real
    init attempt — only a host with neither gets the fast dead verdict.
    Returns {plugin_installed, device_nodes, accel_nodes,
    tpu_hw_present}."""
    import glob
    import importlib.util
    plugin = any(importlib.util.find_spec(m) is not None
                 for m in ("libtpu",))
    accel = sorted(glob.glob("/dev/accel*"))
    nodes = accel + sorted(glob.glob("/dev/vfio/*"))
    return {"plugin_installed": plugin,
            "device_nodes": nodes,
            "accel_nodes": accel,
            "tpu_hw_present": bool(nodes)}


_NO_HW_ROOT_CAUSE = (
    "libtpu PJRT plugin installed but no TPU device node exists "
    "(/dev/accel*, /dev/vfio absent): jax.devices() blocks forever in "
    "xla_client.initialize_pjrt_plugin — the TPU client init has no "
    "device-discovery timeout (docs/design/sharded_kernel.md)")


def run_probe(timeout_s: Optional[float] = None, env: Optional[dict] = None,
              log=None) -> dict:
    """Probe backend bring-up in a killable child. Returns::

        {"alive": bool, "platform": str|None, "timed_out": bool,
         "last_phase": str|None, "phases": [{"phase", "ms", ...}],
         "rc": int|None}

    ``alive`` means every phase completed AND the platform is "tpu".
    Without an explicit ``env`` the child runs under the current
    environment MINUS JAX_PLATFORMS, so the probe sees the real backend;
    an explicit ``env`` is used verbatim (tests pin the CPU backend this
    way). ``log`` is an optional line sink for progress telemetry.
    """
    from ..metrics import metrics as m
    if timeout_s is None:
        timeout_s = float(os.environ.get("VOLCANO_BENCH_TPU_PROBE_TIMEOUT",
                                         DEFAULT_TIMEOUT_S))
    if env is not None:
        child_env = dict(env)
    else:
        child_env = dict(os.environ)
        child_env.pop("JAX_PLATFORMS", None)
    t0 = time.monotonic()

    # phase 0: hardware scan — the diagnosed no-hardware hang is decided
    # in ~1 ms instead of burning the whole init timeout per bench
    hw = _tpu_hw_scan()
    force_init = bool(child_env.get("VOLCANO_PROBE_FORCE_INIT")
                      or (env or {}).get("JAX_PLATFORMS"))
    if hw["plugin_installed"] and not hw["tpu_hw_present"] \
            and not force_init:
        try:
            m.inc(m.BACKEND_PROBE, outcome="dead")
        except Exception:
            pass
        verdict = {"alive": False, "platform": None, "timed_out": False,
                   "last_phase": "hw_scan",
                   "phases": [dict(phase="hw_scan", ms=0.0, **hw)],
                   "rc": None, "hw_scan": hw,
                   "root_cause": _NO_HW_ROOT_CAUSE,
                   "wall_s": round(time.monotonic() - t0, 1)}
        if log is not None:
            log("backend probe: TPU plugin installed but NO TPU device "
                "nodes — skipping the (known-hanging) init; "
                "VOLCANO_PROBE_FORCE_INIT=1 forces it")
            log(f"backend probe root cause: {_NO_HW_ROOT_CAUSE}")
        return verdict

    # arm the child's hang-stack dump just inside the kill window
    child_env.setdefault("VOLCANO_PROBE_STACK_AFTER",
                         str(max(1.0, float(timeout_s) - 5.0)))
    cmd = [sys.executable, "-c", _CHILD_CODE]
    timed_out = False
    rc: Optional[int] = None
    out = ""
    err = ""
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout_s, env=child_env)
        rc = r.returncode
        out = r.stdout or ""
        err = r.stderr or ""
    except subprocess.TimeoutExpired as e:
        timed_out = True
        raw = e.stdout or b""
        out = raw.decode(errors="replace") if isinstance(raw, bytes) \
            else raw
        raw_err = e.stderr or b""
        err = raw_err.decode(errors="replace") \
            if isinstance(raw_err, bytes) else raw_err
    phases = []
    for line in out.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue   # runtime banners and warnings
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if "phase" in rec:
            phases.append(rec)
    last_phase = phases[-1]["phase"] if phases else None
    platform = next((p.get("platform") for p in reversed(phases)
                     if p.get("platform")), None)
    alive = (not timed_out and rc == 0 and last_phase == "device_op"
             and platform == "tpu")
    outcome = "alive" if alive else ("hang" if timed_out else "dead")
    try:
        m.inc(m.BACKEND_PROBE, outcome=outcome)
    except Exception:
        pass
    verdict = {"alive": alive, "platform": platform,
               "timed_out": timed_out, "last_phase": last_phase,
               "phases": phases, "rc": rc, "hw_scan": hw,
               "wall_s": round(time.monotonic() - t0, 1)}
    if timed_out:
        # the faulthandler dump names the wedged frame; keep the tail
        # (the main thread's innermost frames) bounded for the JSON row
        stack = [ln for ln in err.splitlines()
                 if ln.strip().startswith(("Thread", "Current thread",
                                           "File "))]
        if stack:
            verdict["hang_stack"] = stack[-12:]
        # no definitive TPU node: a vfio-only host that hung is most
        # likely the same plugin-without-TPU wedge (vfio can belong to
        # GPU passthrough), so name the root cause there too
        if hw["plugin_installed"] and not hw.get("accel_nodes"):
            verdict["root_cause"] = _NO_HW_ROOT_CAUSE
    if log is not None:
        for p in phases:
            log(f"backend probe phase {p['phase']}: {p['ms']} ms "
                + " ".join(f"{k}={v}" for k, v in p.items()
                           if k not in ("phase", "ms")))
        if timed_out:
            log(f"backend probe HUNG after {timeout_s:.0f}s; last "
                f"completed phase: {last_phase or '(none — import hung)'}")
        else:
            log(f"backend probe: rc={rc} platform={platform!r} -> "
                f"{outcome}")
    return verdict


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    timeout = DEFAULT_TIMEOUT_S
    if "--timeout" in argv:
        timeout = float(argv[argv.index("--timeout") + 1])
    verdict = run_probe(timeout_s=timeout,
                        log=lambda s: print(s, file=sys.stderr))
    # ONE compact line: callers that subprocess this module (to keep
    # jax — and therefore this package — out of their own process)
    # parse stdout's last line
    print(json.dumps(verdict))
    return 0 if verdict["alive"] else 1


if __name__ == "__main__":
    sys.exit(main())
