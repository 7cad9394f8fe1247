"""Spans and counters at the program's layer boundaries.

The serving engine and the train step mark their layers with
``span(name, **attrs)`` and count work with ``count(name, n)``:

    from repro import obs

    obs.start()
    eng.submit(req); eng.step()
    rec = obs.stop()          # Records(spans=[...], counters={...})

Off (the default), ``span`` returns one shared no-op context manager and
``count`` returns at once, unless a JAX profiler trace is being taken:
then each span also enters ``jax.profiler.TraceAnnotation(name,
**attrs)``, so the program's layers land on the profiler's host plane,
on the same clock as the device's ops.

On (between ``start()`` and ``stop()``), each span records its name, its
start and end (``time.perf_counter_ns``), the index of its parent (the
innermost span open when it began, -1 for none) and its attributes, and
always enters the ``TraceAnnotation``.  While recording, every XLA
backend compile adds to ``jax.compiles`` and ``jax.compile_s``, and to
``jax.compiles@<innermost open span>``, so a recompile names the step
that caused it.

The recorder belongs to the context (thread, or asyncio task) that
called ``start()``: the one that drives the engine or the train loop.
"""
from __future__ import annotations

import dataclasses
import time
from contextvars import ContextVar
from typing import Any, Dict, List, Optional, Union

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

Number = Union[int, float]


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int                  # -1 while open
    parent: int                  # index into Records.spans, -1 for none
    attrs: Dict[str, Any]


@dataclasses.dataclass
class Records:
    spans: List[Span] = dataclasses.field(default_factory=list)
    counters: Dict[str, Number] = dataclasses.field(default_factory=dict)

    def children(self, i: int) -> List[Span]:
        return [s for s in self.spans if s.parent == i]


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class _Recorder:
    def __init__(self):
        self.records = Records()
        self.open: List[int] = []          # indices of the open spans

    def add(self, name: str, n: Number) -> None:
        c = self.records.counters
        c[name] = c.get(name, 0) + n

    def on_duration(self, event: str, seconds: float, **_) -> None:
        if event != COMPILE_EVENT:
            return
        self.add("jax.compiles", 1)
        self.add("jax.compile_s", seconds)
        if self.open:
            self.add("jax.compiles@" + self.records.spans[self.open[-1]].name,
                     1)


class _Recorded:
    """One span while recording: a record plus its TraceAnnotation."""
    __slots__ = ("rec", "name", "attrs", "idx", "ann")

    def __init__(self, rec: _Recorder, name: str, attrs: Dict[str, Any]):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        rec = self.rec
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self.ann.__enter__()
        self.idx = len(rec.records.spans)
        rec.records.spans.append(Span(
            self.name, time.perf_counter_ns(), -1,
            rec.open[-1] if rec.open else -1, self.attrs))
        rec.open.append(self.idx)
        return None

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.rec.records.spans[self.idx].end_ns = end
        self.rec.open.pop()
        self.ann.__exit__(*exc)
        return False


_RECORDER: ContextVar[Optional[_Recorder]] = ContextVar("repro.obs",
                                                        default=None)


def span(name: str, **attrs):
    """A context manager around one layer's work (see the module doc)."""
    rec = _RECORDER.get()
    if rec is not None:
        return _Recorded(rec, name, attrs)
    if jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(name, **attrs)
    return _NOOP


def count(name: str, n: Number = 1) -> None:
    """Add ``n`` to counter ``name`` while recording."""
    rec = _RECORDER.get()
    if rec is not None:
        rec.add(name, n)


def start() -> None:
    """Start recording spans and counters (an error if already on)."""
    if _RECORDER.get() is not None:
        raise RuntimeError("repro.obs is already recording")
    rec = _Recorder()
    _RECORDER.set(rec)
    jax.monitoring.register_event_duration_secs_listener(rec.on_duration)


def stop() -> Records:
    """Stop recording; return what was recorded and clear it (empty
    records when nothing was being recorded)."""
    rec = _RECORDER.get()
    if rec is None:
        return Records()
    _RECORDER.set(None)
    jax.monitoring.unregister_event_duration_listener(rec.on_duration)
    return rec.records
