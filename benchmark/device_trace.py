"""The benchmark's reduction of a profiler trace to device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` and nothing else, and gives:

* ``window_s``: the length of the harness's ``bench.window`` annotation;
* ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices that ran any;
* ``kernels``: per name pattern, the summed device time and count of the
  events whose name holds the pattern (XLA modules first, then ops);
* ``device_ops``: the ten op names with the most device time;
* ``idle_gaps``: the ten longest gaps in the busy union, each named by
  what the host was doing at its middle: the deepest flight-recorder span
  given in ``host_spans``, else the harness's innermost ``bench.*``
  annotation.

Event times in the file are on one clock for host and device planes.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
WINDOW = "bench.window"


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def op_name(text: str) -> str:
    """An XLA op's event name without its HLO text: ``%fusion.29 = f32[..]
    fusion(..)`` gives ``fusion.29``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.duration_ns)


def read(path: str):
    """(device planes [(name, {line: [(name, start, dur)]})], host
    annotations [(name, start, end)])."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            lines = {ln.name: list(_events(ln)) for ln in plane.lines}
            devices.append((plane.name, lines))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for name, s, d in _events(ln):
                    if name.startswith("bench."):
                        host.append((name, s, s + d))
    return devices, host


def reduce(path: str, kernel_patterns: Sequence[str] = (),
           host_spans: Sequence[Tuple[str, int, int]] = ()) -> dict:
    devices, host = read(path)
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW} annotation in {path}")
    w0, w1 = win[0]
    out: Dict[str, object] = {"window_s": (w1 - w0) / 1e9, "devices": 0}
    busy_total = 0
    ops: Dict[str, int] = {}
    kernels = {p: [0, 0] for p in kernel_patterns}
    all_busy: List[Tuple[int, int]] = []
    for _, lines in devices:
        op_line = next((lines[n] for n in OP_LINES if n in lines), None)
        mod_line = next((lines[n] for n in MODULE_LINES if n in lines), None)
        src = op_line if op_line is not None else mod_line
        if not src:
            continue
        iv = [(max(s, w0), min(s + d, w1)) for _, s, d in src
              if s + d > w0 and s < w1]
        u = _union([x for x in iv if x[1] > x[0]])
        if not u:
            continue
        out["devices"] += 1
        busy_total += sum(e - s for s, e in u)
        all_busy.extend(u)
        for name, s, d in (op_line or []):
            if s + d > w0 and s < w1:
                short = op_name(name)
                ops[short] = ops.get(short, 0) + d
        for pat in kernel_patterns:
            for line in (mod_line, op_line):
                hits = [(s, d) for n, s, d in (line or [])
                        if pat in n and s + d > w0 and s < w1]
                if hits:
                    kernels[pat][0] += sum(d for _, d in hits)
                    kernels[pat][1] += len(hits)
                    break
    n_dev = max(1, int(out["devices"]))
    out["busy_s"] = busy_total / n_dev / 1e9
    out["kernels"] = {p: {"s": v[0] / n_dev / 1e9, "count": v[1] / n_dev}
                      for p, v in kernels.items()}
    out["device_ops"] = [[n, d / 1e9] for n, d in
                         sorted(ops.items(), key=lambda kv: -kv[1])[:10]]
    busy = _union(all_busy)
    gaps, prev = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    named = []
    for s, e in gaps[:10]:
        named.append([_doing((s + e) // 2, host, host_spans), (e - s) / 1e9])
    out["idle_gaps"] = named
    return out


def _doing(t: int, host, host_spans) -> str:
    """The deepest span active at ``t``: a flight-recorder path, else the
    innermost bench.* annotation other than the window, else "host"."""
    best, depth = None, -1
    for name, s, e in host_spans:
        if s <= t < e and name.count("/") > depth:
            best, depth = name, name.count("/")
    if best is not None:
        return best
    inner, width = "host", None
    for name, s, e in host:
        if name != WINDOW and s <= t < e and (width is None or e - s < width):
            inner, width = name, e - s
    return inner
