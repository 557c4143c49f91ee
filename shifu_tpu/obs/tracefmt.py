"""Trace-event parsing: jax.profiler captures -> per-kernel device rollups.

`jax.profiler.start_trace(dir)` emits, per capture, a timestamped run under
`<dir>/plugins/profile/<run>/` holding an xplane protobuf AND a Chrome
trace-event JSON (`*.trace.json.gz`).  The protobuf needs the tensorboard
profile plugin to read; the Chrome trace is plain gzip+JSON — this module
parses THAT, with stdlib only, so the device flight recorder works in any
checkout (no profiler-plugin dependency, no jax import).

What counts as a *device* event: XLA's trace converter tags every executed
kernel with `args.hlo_op` (+ `args.hlo_module`).  Host-side Python/dispatch
events carry no such tag, and the duplicate grouping lanes a TPU trace adds
(per-module rows, step rows) don't either — so filtering on `hlo_op`
selects exactly one record per kernel execution on every backend this has
been checked against (CPU TFRT, TPU).

The rollup is the `device_profile` journal event's payload (obs/devprof.py
adds the roofline join): per-kernel name/module/calls/device-µs/fraction of
the traced window, top-K by device time with the tail folded into
`other_us` — bounded output no matter how many distinct kernels a trace
holds.
"""

from __future__ import annotations

import gzip
import json
import os
from typing import Iterable, Optional

TRACE_SUFFIXES = (".trace.json.gz", ".trace.json")
DEFAULT_TOP_K = 16


def find_trace_files(log_dir: str) -> list[str]:
    """Every Chrome-trace file under a profiler log dir (any nesting —
    captures land in timestamped run subdirs), newest run last."""
    out: list[str] = []
    for root, _dirs, files in os.walk(log_dir):
        for name in files:
            if name.endswith(TRACE_SUFFIXES):
                out.append(os.path.join(root, name))
    return sorted(out)


def load_trace_events(path: str) -> list[dict]:
    """The `traceEvents` list of one Chrome-trace file (gzip or plain)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:  # type: ignore[operator]
        doc = json.loads(f.read().decode("utf-8", "replace"))
    events = doc.get("traceEvents", []) if isinstance(doc, dict) else []
    return events if isinstance(events, list) else []


def device_events(events: Iterable[dict]) -> list[dict]:
    """Complete ("X") events that are device kernel executions — the
    records carrying `args.hlo_op` (see module docstring)."""
    out = []
    for e in events:
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        args = e.get("args")
        if isinstance(args, dict) and args.get("hlo_op"):
            out.append(e)
    return out


def _self_times(lane_events: list[tuple]) -> list[tuple]:
    """(ts, dur, self_us, name, module) per event of ONE lane.

    Device traces nest: a scan's `while` op spans its inner dots on the
    same lane, so summing raw durations double-counts every level of the
    flame.  Classic stack reconstruction — events sorted by (start,
    -dur); an event starting before the stack top ends is its child and
    subtracts from the parent's SELF time — makes per-kernel times sum
    to the lane's busy time exactly.
    """
    ordered = sorted(lane_events, key=lambda e: (e[0], -e[1]))
    out = [[ts, dur, dur, name, module] for ts, dur, name, module in ordered]
    stack: list[list] = []
    for rec in out:
        ts, dur = rec[0], rec[1]
        while stack and ts >= stack[-1][0] + stack[-1][1] - 1e-9:
            stack.pop()
        if stack:
            stack[-1][2] -= dur  # child time is not the parent's self time
        stack.append(rec)
    return [(ts, dur, max(self_us, 0.0), name, module)
            for ts, dur, self_us, name, module in out]


def kernel_rollup(events: Iterable[dict],
                  top_k: int = DEFAULT_TOP_K) -> Optional[dict]:
    """Per-kernel device-time rollup of one capture's device events.

    Returns None when the capture holds no device events (a trace window
    that bracketed no dispatch).  Per-kernel `device_us` is SELF time
    (nested children subtracted — see _self_times), so kernels sum to
    the device-busy time, never above it.  Fractions are of the traced
    window — first device-event start to last end — divided across
    `lanes` (the distinct (pid, tid) execution rows device events ran
    on), so they sum to <= 1 even when kernels on different devices
    overlap in wall time.
    """
    devs = device_events(events)
    if not devs:
        return None
    by_lane: dict[tuple, list[tuple]] = {}
    for e in devs:
        try:
            ts = float(e.get("ts", 0.0))
            dur = float(e.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        if not (dur >= 0.0) or dur == float("inf"):
            continue
        args = e.get("args") or {}
        name = str(e.get("name") or args.get("hlo_op") or "?")
        module = str(args.get("hlo_module") or "")
        by_lane.setdefault((e.get("pid"), e.get("tid")), []).append(
            (ts, dur, name, module))
    per: dict[tuple, dict] = {}  # (name, module) -> {calls, us}
    mod_totals: dict[str, float] = {}  # module -> us over ALL its kernels
    lanes = set(by_lane)
    t_lo = float("inf")
    t_hi = float("-inf")
    total_us = 0.0
    for lane, lane_events in by_lane.items():
        for ts, dur, self_us, name, module in _self_times(lane_events):
            k = per.setdefault((name, module), {"calls": 0, "us": 0.0})
            k["calls"] += 1
            k["us"] += self_us
            total_us += self_us
            if module:
                mod_totals[module] = mod_totals.get(module, 0.0) + self_us
            t_lo = min(t_lo, ts)
            t_hi = max(t_hi, ts + dur)
    if not per:
        return None
    window_us = max(t_hi - t_lo, 0.0)
    denom = window_us * max(len(lanes), 1)
    ranked = sorted(per.items(), key=lambda kv: -kv[1]["us"])
    kernels = [{
        "name": name,
        "module": module or None,
        "calls": v["calls"],
        "device_us": round(v["us"], 3),
        "fraction": round(v["us"] / denom, 6) if denom > 0 else None,
    } for (name, module), v in ranked[:max(top_k, 1)]]
    other_us = sum(v["us"] for _k, v in ranked[max(top_k, 1):])
    return {
        "window_us": round(window_us, 3),
        "device_us_total": round(total_us, 3),
        "device_fraction": (round(total_us / denom, 6) if denom > 0
                            else None),
        "lanes": len(lanes),
        "kernel_count": len(per),
        "kernels": kernels,
        "other_us": round(other_us, 3),
        # per-module device time over ALL kernels, before the top-K cut:
        # the roofline denominators (devprof.roofline_join) must cover a
        # module's tail kernels too, or its fractions overstate
        "modules": {m: round(us, 3)
                    for m, us in sorted(mod_totals.items(),
                                        key=lambda kv: -kv[1])},
    }


def rollup_trace_dir(log_dir: str,
                     top_k: int = DEFAULT_TOP_K) -> Optional[dict]:
    """Rollup over every trace file under `log_dir` (one capture = one
    run subdir; merging multiple runs merges their kernels).  None when
    no file yields device events.

    Memory: each file is parsed and immediately FILTERED to its device
    events (the Chrome trace is dominated by host Python events — often
    100x the device rows), so the retained working set is one file's
    decoded document plus the device events, not every file's full
    event list.  Long epoch windows on dispatch-heavy jobs still decode
    one large document; schedule such windows sparingly
    (obs.trace_epochs) rather than every epoch.
    """
    merged: list[dict] = []
    for path in find_trace_files(log_dir):
        try:
            merged.extend(device_events(load_trace_events(path)))
        except (OSError, ValueError):
            continue  # a torn capture must not hide the readable ones
    return kernel_rollup(merged, top_k=top_k)
