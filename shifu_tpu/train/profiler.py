"""Tracing / profiling subsystem.

Parity-plus over the reference's hand-rolled timing (per-epoch wall clock in
the metrics line, slowest-worker sort in the AM — SURVEY.md section 5.1;
reference: resources/ssgd_monitor.py:270-293, appmaster/TensorflowSession.java:
538-546; TensorBoard support was vestigial, ssgd_monitor.py:493-502):

- `StepTimer`: cheap per-step wall timing with percentile summaries — the
  straggler view's SPMD successor (under SPMD the interesting skew is
  host-side input time vs device step time, both captured here).
- `straggler_line`: the cross-host per-epoch timing table.

The `jax.profiler` seam itself (the real version of the reference's dead
start_tensorboard) is obs/devprof.py's `epoch_capture`, on the
`obs.trace_epochs` schedule.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import numpy as np


class StepTimer:
    """Accumulates per-step host/device timings for one epoch.

    `on_chunk(input_s, step_s)`, when given, is called at every
    mark_step_done with the chunk's input wait and dispatch-to-done time
    — the device flight recorder's feed (obs/devprof.py: ring buffer +
    anomaly detector), so every input tier gets anomaly detection
    without per-tier loop changes.  The callback must be cheap (it runs
    on the chunk boundary) and never raise (exceptions are swallowed —
    timing must not fail the chunk it times)."""

    def __init__(self, on_chunk: Optional[Callable[[float, float],
                                                   None]] = None) -> None:
        self.input_times: list[float] = []
        self.step_times: list[float] = []
        self._t: Optional[float] = None
        self._on_chunk = on_chunk

    def start(self) -> None:
        self._t = time.perf_counter()

    def mark_input_ready(self) -> None:
        now = time.perf_counter()
        if self._t is not None:
            self.input_times.append(now - self._t)
        self._t = now

    def mark_step_done(self) -> None:
        now = time.perf_counter()
        if self._t is not None:
            self.step_times.append(now - self._t)
        self._t = now
        if self._on_chunk is not None and self.step_times:
            try:
                self._on_chunk(
                    self.input_times[-1] if self.input_times else 0.0,
                    self.step_times[-1])
            except Exception:
                pass

    def emit(self, prefix: str = "train", **labels) -> None:
        """Feed this epoch's per-step timings into the telemetry registry
        (obs/metrics.py): `<prefix>_input_seconds` / `<prefix>_step_seconds`
        histograms — the unified home the per-epoch console line used to be
        the only view of.  Call once per epoch; an empty epoch is a no-op."""
        from .. import obs

        hin = obs.histogram(f"{prefix}_input_seconds",
                            "host input wait per step/chunk")
        hstep = obs.histogram(f"{prefix}_step_seconds",
                              "device step/chunk dispatch-to-done time")
        for v in self.input_times:
            if v == v and v != float("inf"):  # finite only, like summary()
                hin.observe(v, **labels)
        for v in self.step_times:
            if v == v and v != float("inf"):
                hstep.observe(v, **labels)

    def summary(self) -> dict[str, float]:
        def stats(xs: list[float], prefix: str) -> dict[str, float]:
            # finite samples only: one NaN timing (a clock hiccup, a
            # poisoned mark) would otherwise propagate into EVERY field
            # via mean/percentile, and a single-chunk epoch (the scan
            # tiers dispatch once per epoch) must still produce a
            # well-formed record — p50 == p99 == the sample, never NaN
            arr = np.asarray([x for x in xs if x == x and x != float("inf")],
                             dtype=np.float64)
            if arr.size == 0:
                return {}
            if arr.size == 1:
                v_ms = float(arr[0]) * 1e3
                return {f"{prefix}_mean_ms": v_ms,
                        f"{prefix}_p50_ms": v_ms,
                        f"{prefix}_p99_ms": v_ms,
                        f"{prefix}_total_s": float(arr[0])}
            return {
                f"{prefix}_mean_ms": float(arr.mean() * 1e3),
                f"{prefix}_p50_ms": float(np.percentile(arr, 50) * 1e3),
                f"{prefix}_p99_ms": float(np.percentile(arr, 99) * 1e3),
                f"{prefix}_total_s": float(arr.sum()),
            }
        out = {}
        out.update(stats(self.input_times, "input"))
        out.update(stats(self.step_times, "step"))
        if "input_total_s" in out and "step_total_s" in out:
            total = out["input_total_s"] + out["step_total_s"]
            out["input_fraction"] = float(out["input_total_s"]
                                          / max(total, 1e-9))
        return out

    def console_line(self) -> str:
        s = self.summary()
        if not s:
            return "timing: no steps"
        return (f"timing: input p50 {s.get('input_p50_ms', 0):.2f}ms "
                f"step p50 {s.get('step_p50_ms', 0):.2f}ms "
                f"input fraction {s.get('input_fraction', 0):.1%}")


def straggler_line(epoch: int, epoch_time: float, valid_time: float,
                   input_seconds: float, console,
                   extra: Optional[dict] = None) -> None:
    """Cross-host per-epoch timing aggregation — the successor of the
    reference AM's slowest-first worker sort (appmaster/
    TensorflowSession.java:515-549: every worker's TrainingIntermediateResult
    collected, epoch times summed/averaged, then sorted slowest-first into
    one log line).  Every rank contributes (input_seconds, epoch_time,
    valid_time, hostname) through ONE small allgather; the chief prints
    hosts slowest-first so a degraded disk/NIC shows up as a named straggler
    instead of silently stalling the gang.

    Sorted by HOST INPUT SECONDS, not epoch time — a deliberate deviation
    from the reference's epoch-time sort: its workers ran async SGD, so a
    slow worker's epoch genuinely took longer; under SPMD every collective
    synchronizes the gang, epoch wall time converges on every rank, and the
    only per-host-attributable cost is host-side input production (SURVEY
    §5.1: "per-host input-pipeline timing still matters").

    COLLECTIVE: every process must call this each epoch (the train loop
    does, gated on multihost); only process 0 prints.

    Implementation lives in obs/aggregate.py since the telemetry
    unification: the same gather also journals a `host_skew` event, so the
    table survives the run as structured data, not just a log line.

    `extra` fields (pod data plane: cumulative ingest bytes/seconds, epoch
    order digest, shard-assignment digest) ride each host's row through the
    same gather — one allgather per epoch, never two."""
    from .. import obs

    obs.aggregate.epoch_skew(epoch, input_seconds, epoch_time, valid_time,
                             console=console, extra=extra)
