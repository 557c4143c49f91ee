"""Span tracing: nested host-side phase timing feeding registry, journal,
goodput ledger and the profiler's clock.

`with obs.span("epoch/eval"):` times the block, records the duration into
the `span_seconds` histogram (labeled with the full nested path) and
journals a `span` event.  Nesting composes paths — a span opened inside
`span("epoch")` named "eval" journals as "epoch/eval" — so one stream
reconstructs where wall time went across phases.

The same interval is also stamped on the profiler's own clock: where JAX
is loaded, a span holds a `jax.profiler.TraceAnnotation("shifu:<path>")`
open for its duration, so a device trace (train/profiler.py, the
benchmark's traced runs) shows beside the device's operations which phase
the host was in.  No profiler session is started here; without one the
annotation is the runtime's no-op.  This module imports without JAX (the
fleet tools read journals with no `jax` import).

A hot span (`journal=False`) that closes while a goodput ledger is open
(obs/goodput.py) is one of the epoch's phases: its seconds and a count go
to the ledger under its path and ride the epoch's one `goodput` event —
no journal record and no histogram observation per batch.  `GcPhases`
times the garbage collector's pauses apart from the ledger (a collection
may start where its thread holds the ledger's lock); the loop folds them
in under `gc/gen<N>` where it closes the epoch.

Thread-local nesting: the prefetch producer thread's spans nest
independently of the main thread's — each thread reads as its own
coherent phase stack.  A generator must not `yield` inside an open span:
the stack would be left mid-path while it is suspended.
"""

from __future__ import annotations

import gc
import sys
import threading
import time

from . import goodput
from . import metrics as metrics_mod

ANNOTATION_PREFIX = "shifu:"

_state = threading.local()


def current_path() -> str:
    """The active nested span path ("" at top level)."""
    return "/".join(getattr(_state, "stack", ()))


def _annotate(path: str):
    """An entered profiler annotation for `path`, or None where JAX is not
    loaded (never imported from here) or has no profiler to offer."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        note = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + path)
        note.__enter__()
        return note
    except Exception:
        return None  # telemetry must never fail the phase it measures


def emit(path: str, dur_s: float, journal: bool = True, **fields) -> None:
    """Record one completed span: `span_seconds` histogram observation +
    (optionally) a `span` journal event.  Never raises."""
    try:
        metrics_mod.histogram(
            "span_seconds",
            "host-side phase durations by nested span path",
        ).observe(dur_s, span=path)
        if journal:
            from . import _sinks
            _sinks.event("span", span=path, dur_s=round(dur_s, 6), **fields)
    except Exception:
        pass  # telemetry must never fail the phase it measures


class span:
    """`with span(name):` times a phase.  `fields` ride into the journal
    event (e.g. `span("epoch/train", epoch=3)`).  Set `journal=False` for
    hot spans: one that closes while an epoch's goodput ledger is open is
    a phase of that ledger and goes nowhere else (a few microseconds a
    span, so that a span a batch stays under a thousandth of the epoch);
    with no ledger open it feeds the histogram alone.  `with span(...) as
    s:` leaves the block's duration in `s.seconds`."""

    __slots__ = ("_name", "_journal", "_fields", "_stack", "_path",
                 "_note", "_t0", "seconds")

    def __init__(self, name: str, journal: bool = True, **fields) -> None:
        self._name, self._journal, self._fields = name, journal, fields

    def __enter__(self) -> "span":
        stack = getattr(_state, "stack", None)
        if stack is None:
            stack = _state.stack = []
        stack.append(self._name)
        self._stack = stack
        self._path = "/".join(stack)
        self._note = _annotate(self._path)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dur = self.seconds = time.perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(None, None, None)
        self._stack.pop()
        if self._journal or not goodput.note_phase(self._path, dur):
            emit(self._path, dur, journal=self._journal, **self._fields)


_GC_PATHS = ("gc/gen0", "gc/gen1", "gc/gen2")


class GcPhases:
    """One `gc.callbacks` hook: each collection's pause, by generation, in
    its own `[seconds, count]` cells and as an annotation on the profiler's
    clock.  It reads the collector and changes none of its thresholds.

    A collection runs on whichever thread trips it, at any bytecode — also
    while that thread holds the goodput ledger's lock — so the hook takes
    no lock and touches no ledger (collections are serial under the GIL).
    The loop calls `fold()` where it closes an epoch's ledger: the cells go
    there as phases `gc/gen<N>` and start again from zero.  Installed on
    construction; `close()` removes it."""

    def __init__(self) -> None:
        self._t0 = 0.0
        self._note = None
        self._cells = [[0.0, 0] for _ in _GC_PATHS]
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._note = _annotate(_GC_PATHS[info["generation"]])
            self._t0 = time.perf_counter()
            return
        dur = time.perf_counter() - self._t0
        note, self._note = self._note, None
        if note is not None:
            note.__exit__(None, None, None)
        cell = self._cells[info["generation"]]
        cell[0] += dur
        cell[1] += 1

    def fold(self, ledger=None) -> None:
        """Move the pauses since the last fold into `ledger`'s phases; with
        no ledger, drop them (those before an epoch's ledger opened)."""
        cells, self._cells = self._cells, [[0.0, 0] for _ in _GC_PATHS]
        if ledger is None:
            return
        for path, (seconds, count) in zip(_GC_PATHS, cells):
            if count:
                ledger.add_phase(path, seconds, count)

    def close(self) -> None:
        try:
            gc.callbacks.remove(self)
        except ValueError:
            pass  # closed twice
