"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO instruction, named by the instruction's text
(``%qmatmul.51 = f32[8,896] custom-call(...)``).  An op's family is the
instruction name without its numeric suffix (``qmatmul``, ``fusion``).
Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``s
(``bench.*``) on the host plane, on the same clock.

The traced window is the ``bench.window`` span.  Busy time is the union
of the op intervals inside it, averaged over the device planes that ran
anything; idle time is the rest of the window, split by the host span
in progress at each gap's midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

_FAMILY = re.compile(r"^%(.+?)(?:\.\d+)*\s=")
# instructions whose events enclose others'
_CONTAINERS = {"while", "conditional", "call"}
WINDOW = "bench.window"


def family(name: str) -> str:
    m = _FAMILY.match(name)
    return m.group(1) if m else name.split(" ", 1)[0]


def newest_xplane(log_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def load_events(path: str):
    """(device ops per plane [(start_ns, end_ns, name)], host spans
    [(start_ns, end_ns, name)]) of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for line in plane.lines for e in line.events
                     if e.name.startswith("bench.")]
    return devices, host


def reduce_events(devices, host) -> Dict:
    """The traced window's busy and idle time, op time by family, and
    idle time by host span."""
    win = [(a, b) for a, b, n in host if n == WINDOW]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(a for ops in devices for a, _, _ in ops)
        w1 = max(b for ops in devices for _, b, _ in ops)
    spans = sorted((a, b, n) for a, b, n in host if n != WINDOW)
    ops_s: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    idle: Dict[str, float] = {}
    busy_total = 0.0
    for ops in devices:
        inside = [(max(a, w0), min(b, w1), n) for a, b, n in ops
                  if b > w0 and a < w1]
        for a, b, n in inside:
            fam = family(n)
            if fam in _CONTAINERS:
                continue
            ops_s[fam] = ops_s.get(fam, 0.0) + (b - a) * 1e-9
            calls[fam] = calls.get(fam, 0) + 1
        busy = _union([(a, b) for a, b, _ in inside])
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            label = next((n for a, b, n in reversed(spans)
                          if a <= mid < b), "bench.none")
            idle[label] = idle.get(label, 0.0) + (g1 - g0) * 1e-9
    n_dev = max(len(devices), 1)
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_total / n_dev,
            "ops_s": {k: v / n_dev for k, v in ops_s.items()},
            "calls": calls, "idle_s": {k: v / n_dev for k, v in
                                       idle.items()},
            "devices": len(devices)}


def reduce(path: str) -> Dict:
    return reduce_events(*load_events(path))


def breakdown(red: Dict) -> Dict:
    """The result line's ``breakdown``: the ten op families that took
    most device time and the ten host spans under which the device sat
    idle longest, each [name, seconds]."""
    top = sorted(red["ops_s"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(red["idle_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}
