"""Goodput ledger: classify every epoch's wall time into named buckets.

Raw step time says a PR made "the job" slower; it cannot say WHICH part.
Pod-scale TPU practice (MLPerf-0.6 on v3 pods, arXiv:1909.09756; the
TensorFlow system paper, arXiv:1605.08695) optimizes *utilization* —
what fraction of the wall the chips spent on model math — not wall time
alone.  This module is that accounting for shifu_tpu:

- **Buckets** (`BUCKETS`): `compile` (XLA compiles, reported by
  obs/introspect.py), `input` (host-side input wait), `step` (device
  step/scan dispatch-to-done, compile time subtracted), `checkpoint`
  (save), `restore` (mid-run restore/recovery — chaos drills land
  here), `eval` (validation pass), `other` (the unclassified residue:
  tier setup, shuffles, journal flushes).  Buckets sum to the epoch
  wall by construction (`other` absorbs the remainder).
- **Goodput fraction** = step seconds / wall: the fraction of the epoch
  the devices spent advancing the model.
- **Phases**: finer host intervals inside the buckets.  A
  `obs.span(..., journal=False)` that closes while a ledger is open
  (obs/spans.py) adds seconds and a count under its full nested path,
  and the loop folds in its garbage-collection hook's pauses where it
  closes the epoch (the hook itself never takes this ledger's lock: a
  collection can start under it); the epoch's one `goodput`
  event carries them as `"phases": {"<path>": [seconds, count]}`.  A
  phase's parent is its path's prefix and its epoch is the event's
  `epoch`, so its self time is its seconds less those of the paths
  under it.  Phases are raw host seconds: a compile or a collection
  (`gc/gen<N>`) that ran inside a phase is in that phase's seconds too.

The interval before the first epoch has a ledger of its own:
`begin_startup()` opens one at `train()`'s entry and the first
`begin_epoch()` takes its place, so the same hot spans (`startup/ingest`,
`startup/init_state`, `startup/tiers/...`) and the same compile notes make
startup's phases.  The loop keeps that ledger and writes it into its one
`startup` event (train/loop.py); it feeds no counter and journals no
`goodput` event.

Every epoch journals ONE `goodput` event and feeds the
`goodput_bucket_seconds_total{bucket=...}` counter plus the
`goodput_fraction` gauge, so `shifu-tpu profile` and `shifu-tpu status`
read the same record (docs/OBSERVABILITY.md "Goodput ledger").
"""

from __future__ import annotations

import threading
from typing import Optional

BUCKETS = ("compile", "input", "step", "checkpoint", "restore", "eval",
           "other")


class GoodputLedger:
    """One epoch's wall-time classification.  Threads may `add`
    concurrently (the prefetch producer compiles its device_put path;
    checkpoint saves may run from hooks)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._seconds: dict[str, float] = {}
        self._phases: dict[str, list] = {}   # path -> [seconds, count]
        self._compiles = 0

    def add(self, bucket: str, seconds: float) -> None:
        # `not (seconds > 0)` rather than `<= 0`: it also rejects NaN (a
        # clock hiccup upstream must not poison the whole ledger, the
        # bucket counters, and every artifact field derived from them)
        if not (seconds > 0) or seconds == float("inf"):
            return
        with self._lock:
            self._seconds[bucket] = self._seconds.get(bucket, 0.0) + seconds
            if bucket == "compile":
                self._compiles += 1

    def add_phase(self, path: str, seconds: float, count: int = 1) -> None:
        """`count` closed intervals of phase `path`, `seconds` in all (see
        the module docstring).  A zero-length one still counts; a NaN or a
        negative one does not."""
        if not (seconds >= 0) or seconds == float("inf"):
            return
        with self._lock:
            cell = self._phases.get(path)
            if cell is None:
                self._phases[path] = [seconds, count]
            else:
                cell[0] += seconds
                cell[1] += count

    def phase_seconds(self, path: str) -> float:
        """The seconds credited to phase `path` so far (0.0 if none)."""
        with self._lock:
            cell = self._phases.get(path)
            return cell[0] if cell is not None else 0.0

    def summary(self, wall_s: float) -> dict:
        """The goodput record for an epoch of `wall_s` seconds.  Compile
        time happens INSIDE the timed step/eval dispatches (a compiling
        call's wall includes its compile), so it is subtracted from
        `step` first, then `eval` — the buckets stay disjoint and sum to
        the wall, with `other` absorbing the unclassified residue."""
        with self._lock:
            b = dict(self._seconds)
            phases = {k: [round(v[0], 6), v[1]]
                      for k, v in self._phases.items()}
            compiles = self._compiles
        compile_s = b.get("compile", 0.0)
        overlap = min(compile_s, b.get("step", 0.0))
        b["step"] = b.get("step", 0.0) - overlap
        b["eval"] = max(b.get("eval", 0.0) - (compile_s - overlap), 0.0)
        buckets = {k: round(b.get(k, 0.0), 6) for k in BUCKETS
                   if k != "other"}
        classified = sum(buckets.values())
        buckets["other"] = round(max(wall_s - classified, 0.0), 6)
        return {
            "wall_s": round(wall_s, 6),
            "buckets": buckets,
            "goodput_fraction": round(buckets["step"] / wall_s, 4)
            if wall_s > 0 else None,
            "compiles": compiles,
            "phases": phases,
        }


_lock = threading.Lock()
_current: Optional[GoodputLedger] = None


def begin_epoch() -> GoodputLedger:
    """Open a fresh ledger as the process's active epoch ledger."""
    global _current
    with _lock:
        _current = GoodputLedger()
        return _current


def begin_startup() -> GoodputLedger:
    """Open a ledger for the interval before the first epoch: active until
    the first `begin_epoch()` replaces it, and the caller's to read."""
    return begin_epoch()


def current() -> Optional[GoodputLedger]:
    return _current


def note(bucket: str, seconds: float) -> None:
    """Credit `seconds` to `bucket` on the active ledger; no-op between
    epochs — instrumented call sites (checkpoint saves, compiles) never
    check whether a ledger is open.  Never raises."""
    led = _current
    if led is not None:
        try:
            led.add(bucket, seconds)
        except Exception:
            pass


def note_phase(path: str, seconds: float) -> bool:
    """Credit one closed interval of phase `path` to the active ledger.
    False where none is open to take it (between epochs, outside a
    `train()` call).  Never raises."""
    led = _current
    if led is None:
        return False
    try:
        led.add_phase(path, seconds)
    except Exception:
        pass
    return True


def end_epoch(epoch: int, wall_s: float) -> Optional[dict]:
    """Close the active ledger: journal the `goodput` event, feed the
    registry, return the record (None when no ledger is open)."""
    global _current
    with _lock:
        led = _current
        _current = None
    if led is None:
        return None
    try:
        from . import _sinks, metrics as metrics_mod
        rec = led.summary(wall_s)
        rec["epoch"] = int(epoch)
        sec = metrics_mod.counter(
            "goodput_bucket_seconds_total",
            "epoch wall seconds by goodput bucket")
        for bucket, s in rec["buckets"].items():
            sec.inc(s, bucket=bucket)
        if rec["goodput_fraction"] is not None:
            metrics_mod.gauge(
                "goodput_fraction",
                "last epoch's device-step fraction of wall time",
            ).set(rec["goodput_fraction"])
        _sinks.event("goodput", **rec)
        return rec
    except Exception:
        return None  # telemetry must never fail the epoch it measures


def reset_for_tests() -> None:
    """Drop any ledger left open by an aborted epoch (obs.reset_for_tests
    calls this — a mid-epoch exception must not leak state across
    tests)."""
    global _current
    with _lock:
        _current = None
