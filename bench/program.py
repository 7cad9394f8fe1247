"""Reduce a profiler trace to the program's own layers, on one clock.

The program marks its layers with ``repro.obs`` spans (``engine.*`` in
the serving engine, ``train.*`` in the train step), which reach the
profiler's host plane as annotations whenever a profile is taken, and
names its jitted programs (``jit_engine_decode``, ``jit_engine_prefill``,
``jit_engine_splice``, ``jit_engine_first_token``, ``jit_train_step``).
This module reads them from the trace file that ``bench/trace.py``
reduced, and adds nothing to ``trace.py``'s own numbers.

* Clock offset (device minus host).  The trace pairs each executed
  module (``XLA Modules`` line, stat ``_c``) with the host's
  ``DoEnqueueProgram`` that issued it (stat ``_p``, the same number) and
  with the host's ``CompleteCallbacks`` for it (stat ``_c``).  No module
  starts before its enqueue, nor ends after its callback starts: the
  offset is the largest that keeps every module after its enqueue
  (``hi``), and no smaller than ``lo``, the least that keeps every
  module ending before its callback.  Device events are shifted by it.
* Idle by span.  Busy is the union of the (shifted) op intervals in the
  ``bench.window`` span; each stretch of idle time goes, instant by
  instant, to the innermost program span in progress, else to the
  innermost ``bench.*`` span, else to ``bench.none``.
* Device time per named program, from ``XLA Modules`` (the ``jit_``
  prefix and the fingerprint dropped).
* The spans the readers need: the host time of each ``engine.step`` less
  its ``engine.step.sync`` child, each ``train.dispatch``, and the
  ``start`` and ``n`` of each ``engine.prefill_chunk``.

``of(rec)`` gives a reader the reduction for its record: the record's
own ``program`` key where it has one, else the trace file the harness
wrote for this run (the newest under ``chiprun_out/bench``, checked
against the record's traced window), else None.
"""
from __future__ import annotations

import json
import os
import re
import statistics
import warnings
from typing import Dict, List, Optional, Tuple

from harness import ROOT, load_module, trace_lib

PROGRAM = ("engine.", "train.")
BENCH = "bench."
NONE = "bench.none"
_MODULE = re.compile(r"^(?:jit_)?(.+?)(?:\(\d+\))?$")


def program_name(module: str) -> str:
    """``jit_engine_prefill(1712...)`` -> ``engine_prefill``."""
    return _MODULE.match(module).group(1)


# ------------------------------------------------------------------ load --

def load_events(path: str) -> Dict:
    """The events this module reads, from one trace file: per device
    plane its ops [(start, end)] and modules [(start, end, name, _c)];
    host spans [(start, end, name, stats, line)] of the program and the
    benchmark; enqueue and callback starts by correlation id."""
    from jax.profiler import ProfileData
    devices, host, enqueue, callbacks = [], [], {}, {}
    with warnings.catch_warnings():
        # the reader's stats type warns as it is first built
        warnings.simplefilter("ignore", DeprecationWarning)
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                ops, mods = [], []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops += [(e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
                    elif line.name == "XLA Modules":
                        mods += [(e.start_ns, e.start_ns + e.duration_ns,
                                  program_name(e.name),
                                  dict(e.stats).get("_c"))
                                 for e in line.events]
                if ops:
                    devices.append({"ops": ops, "modules": mods})
            elif plane.name.startswith("/host:"):
                for k, line in enumerate(plane.lines):
                    for e in line.events:
                        n = e.name
                        if n.startswith(PROGRAM) or n.startswith(BENCH):
                            host.append((e.start_ns,
                                         e.start_ns + e.duration_ns, n,
                                         dict(e.stats), k))
                        elif n == "DoEnqueueProgram":
                            enqueue[dict(e.stats).get("_p")] = e.start_ns
                        elif n == "CompleteCallbacks":
                            callbacks[dict(e.stats).get("_c")] = e.start_ns
    enqueue.pop(None, None)
    callbacks.pop(None, None)
    return {"devices": devices, "host": host, "enqueue": enqueue,
            "callbacks": callbacks}


# ----------------------------------------------------------------- clock --

def clock_offset(devices: List[Dict], enqueue: Dict, callbacks: Dict
                 ) -> Tuple[float, Optional[float], Optional[float], int]:
    """(offset, lo, hi, pairs) in ns: device clock minus host clock.
    ``hi`` is the largest offset under which no module starts before its
    enqueue, ``lo`` the least under which none ends after its callback
    starts; the offset is ``hi``, else ``lo``, else 0."""
    hi = lo = None
    pairs = 0
    for d in devices:
        for a, b, _, c in d["modules"]:
            if c in enqueue:
                pairs += 1
                hi = a - enqueue[c] if hi is None else min(hi, a - enqueue[c])
            if c in callbacks:
                lo = b - callbacks[c] if lo is None else \
                    max(lo, b - callbacks[c])
    off = hi if hi is not None else (lo if lo is not None else 0.0)
    return off, lo, hi, pairs


# ------------------------------------------------------------ timeline --

def _innermost(spans: List[Tuple]) -> Tuple:
    return max(spans, key=lambda s: (s[0], -s[1]))


def _label(active: List[Tuple]) -> str:
    prog = [s for s in active if s[2].startswith(PROGRAM)]
    if prog:
        return _innermost(prog)[2]
    bench = [s for s in active if s[2] != trace_lib.WINDOW]
    return _innermost(bench)[2] if bench else NONE


def timeline(host: List[Tuple], w0: float, w1: float
             ) -> List[Tuple[float, float, str]]:
    """The window cut into stretches [(start, end, label)], each labelled
    with the innermost program span in progress, else the innermost
    ``bench.*`` span, else ``bench.none``."""
    spans = [s for s in host if s[1] > w0 and s[0] < w1]
    cuts = sorted({w0, w1} | {t for s in spans for t in s[:2]
                              if w0 < t < w1})
    starts = sorted(range(len(spans)), key=lambda i: spans[i][0])
    ends = sorted(range(len(spans)), key=lambda i: spans[i][1])
    active = set()
    si = ei = 0
    out = []
    for a, b in zip(cuts, cuts[1:]):
        while si < len(starts) and spans[starts[si]][0] <= a:
            active.add(starts[si])
            si += 1
        while ei < len(ends) and spans[ends[ei]][1] <= a:
            active.discard(ends[ei])
            ei += 1
        label = _label([spans[i] for i in active])
        if out and out[-1][2] == label:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def split_gaps(gaps: List[Tuple[float, float]],
               stretches: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` (sorted, disjoint) under each label."""
    out: Dict[str, float] = {}
    j = 0
    for g0, g1 in gaps:
        while j < len(stretches) and stretches[j][1] <= g0:
            j += 1
        k = j
        while k < len(stretches) and stretches[k][0] < g1:
            a, b, label = stretches[k]
            t = min(b, g1) - max(a, g0)
            if t > 0:
                out[label] = out.get(label, 0.0) + t
            k += 1
    return out


# ---------------------------------------------------------------- reduce --

def _inside(host, name, w0, w1):
    return [s for s in host if s[2] == name and w0 <= s[0] < w1]


def reduce_events(ev: Dict) -> Dict:
    """The program's layers in the traced window (see the module doc);
    seconds throughout, the offset in ms."""
    host, devices = ev["host"], ev["devices"]
    off, lo, hi, pairs = clock_offset(devices, ev["enqueue"],
                                      ev["callbacks"])
    win = [(a, b) for a, b, n, _, _ in host if n == trace_lib.WINDOW]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(a - off for d in devices for a, _ in d["ops"])
        w1 = max(b - off for d in devices for _, b in d["ops"])
    stretches = timeline(host, w0, w1)
    idle: Dict[str, float] = {}
    busy = 0.0
    programs: Dict[str, Dict[str, float]] = {}
    for d in devices:
        iv = [(max(a - off, w0), min(b - off, w1)) for a, b in d["ops"]
              if b - off > w0 and a - off < w1]
        union = trace_lib._union(iv)
        busy += sum(b - a for a, b in union)
        edges = [w0] + [t for u in union for t in u] + [w1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for k, v in split_gaps(gaps, stretches).items():
            idle[k] = idle.get(k, 0.0) + v
        for a, b, name, _ in d["modules"]:
            a, b = max(a - off, w0), min(b - off, w1)
            if b > a:
                p = programs.setdefault(name, {"s": 0.0, "calls": 0})
                p["s"] += b - a
                p["calls"] += 1
    n_dev = max(len(devices), 1)
    syncs = [s for s in host if s[2] == "engine.step.sync"]
    ticks = []
    for a, b, _, _, line in _inside(host, "engine.step", w0, w1):
        inner = sum(s1 - s0 for s0, s1, _, _, k in syncs
                    if k == line and a <= s0 and s1 <= b)
        ticks.append((b - a - inner) * 1e-9)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * 1e-9 / n_dev,
        "idle_s": sum(idle.values()) * 1e-9 / n_dev,
        "idle_by_span": {k: v * 1e-9 / n_dev for k, v in idle.items()},
        "clock_offset_ms": off * 1e-6,
        "clock_offset_lo_ms": None if lo is None else lo * 1e-6,
        "clock_offset_hi_ms": None if hi is None else hi * 1e-6,
        "clock_pairs": pairs,
        "programs": {k: {"s": v["s"] * 1e-9 / n_dev, "calls": v["calls"]}
                     for k, v in programs.items()},
        "program_spans": sum(1 for s in host if s[2].startswith(PROGRAM)),
        "tick_host_s": ticks,
        "dispatch_s": [(b - a) * 1e-9 for a, b, _, _, _ in
                       _inside(host, "train.dispatch", w0, w1)],
        "prefill_chunks": [(s[3]["start"], s[3]["n"]) for s in
                           _inside(host, "engine.prefill_chunk", w0, w1)],
    }


def reduce(path: str) -> Dict:
    return reduce_events(load_events(path))


def idle_share(red: Dict, prefix: str, but: str = "\0") -> float:
    """Per cent of the window idle under spans named ``prefix*``, leaving
    out those that end in ``but``."""
    s = sum(v for k, v in red["idle_by_span"].items()
            if k.startswith(prefix) and not k.endswith(but))
    return 100.0 * s / red["window_s"]


# ------------------------------------------------------------ the record --

_CACHE: Dict[Tuple[str, float], Dict] = {}


def of(rec: Dict) -> Optional[Dict]:
    """The reduction for a reader's record, with the ``workload`` it ran
    (see the module doc); None where the run left no program spans."""
    red = rec.get("program")
    if red is None:
        red = _from_trace(rec.get("trace"))
    if not red or not red["program_spans"]:
        return None
    return red


def _from_trace(t: Optional[Dict]) -> Optional[Dict]:
    if not t or not t.get("devices"):
        return None
    base = os.path.join(ROOT, "chiprun_out", "bench")
    path = trace_lib.newest_xplane(base)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        red = reduce(path)
        red["workload"] = os.path.relpath(path, base).split(os.sep)[0]
        _CACHE[key] = red
    red = _CACHE[key]
    if abs(red["window_s"] - t["window_s"]) > 1e-9 * t["window_s"]:
        return None                       # not this run's trace
    return red


def model_of(workload: str) -> Dict:
    """The model of a cell's configuration, as ``bench/work.py`` counts
    it (its reference's ``model(cfg)``)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        cell = {w["name"]: w for w in json.load(fh)["workloads"]}[workload]
    cdir = os.path.join(ROOT, "bench", "configs")
    with open(os.path.join(cdir, f"{cell['config']}.json")) as fh:
        cfg = json.load(fh)
    ref = load_module(os.path.join(cdir, cfg["reference"]),
                      "bench_reference")
    return ref.model(cfg)


def median_ms(values: List[float]) -> Optional[float]:
    return 1e3 * statistics.median(values) if values else None
