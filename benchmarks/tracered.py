"""From a profiler trace to the numbers the per-layer readers use: device
busy and idle time, the operations that took most of it, the device time of
one program's executions, and the longest idle gaps named by what the host
was doing.

The reduction works on plain events, `(plane, line, name, start_ns,
dur_ns)`, so that it can be checked on a small recorded trace (a JSON list
of such events under `tests/`); `load_events` makes them from the
`.xplane.pb` that `jax.profiler` writes, with nothing but JAX.

On a TPU every chip is a plane `/device:TPU:<n>`.  Its line `XLA Ops` holds
one event per executed operation; operations nest (a `while` spans its
body), so an operation's own time is its duration less its children's.  Its
line `XLA Modules` holds one event per executed program.  Host threads are
the lines of the `/host:CPU` plane; the harness's own epoch marks
(`jax.profiler.TraceAnnotation`) land there under the names it gave them.
"""

from __future__ import annotations

import glob
import os
from typing import Iterable, NamedTuple, Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "/host:"
MARK_PREFIX = "perfbench/"
TOP = 10


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def short_name(name: str) -> str:
    """An operation's event is named by its whole HLO line, `%fusion.7 =
    bf16[...] fusion(...)`: keep the name before the `=`."""
    return name.split(" = ", 1)[0].lstrip("%")[:96]


def load_events(xplane_path: str) -> list[Event]:
    from jax.profiler import ProfileData

    out: list[Event] = []
    for plane in ProfileData.from_file(xplane_path).planes:
        keep_all = plane.name.startswith(HOST_PREFIX)
        if not (keep_all or plane.name.startswith(DEVICE_PREFIX)):
            continue
        for line in plane.lines:
            if not keep_all and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                out.append(Event(plane.name, line.name, short_name(ev.name),
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _self_times(events: list[Event]) -> list[tuple[Event, float]]:
    """Each event of one line with its own time: its duration less that of
    the events nested in it."""
    ordered = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    own = [e.dur_ns for e in ordered]
    stack: list[int] = []
    for i, e in enumerate(ordered):
        while stack and e.start_ns >= ordered[stack[-1]].end_ns:
            stack.pop()
        if stack:
            own[stack[-1]] -= e.dur_ns
        stack.append(i)
    return [(e, max(t, 0.0)) for e, t in zip(ordered, own)]


def _clip(e: Event, lo: float, hi: float) -> Optional[tuple[float, float]]:
    a, b = max(e.start_ns, lo), min(e.end_ns, hi)
    return (a, b) if b > a else None


def reduce(events: list[Event], module_prefix: Optional[str] = None) -> dict:
    """The traced window is what the harness's marks span; without marks,
    what the device events span.

    Returns seconds: `window_s`, `busy_s` (the union of the intervals in
    which an operation ran, averaged over the chips), `device_ops` and
    `idle_gaps` (the breakdown's two lists), and, where `module_prefix` is
    given, `module_s` and `module_runs`: the summed device time, and the
    count, of the executions of programs whose name starts with it that lie
    wholly inside the window, averaged over the chips."""
    marks = sorted((e for e in events if e.plane.startswith(HOST_PREFIX)
                    and e.name.startswith(MARK_PREFIX)),
                   key=lambda e: e.start_ns)
    ops = [e for e in events if e.plane.startswith(DEVICE_PREFIX)
           and e.line == OPS_LINE]
    if not ops:
        return {}
    if marks:
        lo, hi = marks[0].start_ns, max(m.end_ns for m in marks)
    else:
        lo, hi = min(e.start_ns for e in ops), max(e.end_ns for e in ops)
    chips = sorted({e.plane for e in ops})
    busy_ns = 0.0
    by_name: dict[str, float] = {}
    gaps: list[tuple[float, float, str]] = []
    for chip in chips:
        mine = [e for e in ops if e.plane == chip]
        clipped = [c for c in (_clip(e, lo, hi) for e in mine) if c]
        merged = _union(clipped)
        busy_ns += sum(b - a for a, b in merged)
        for e, own in _self_times(mine):
            if e.end_ns > lo and e.start_ns < hi:
                by_name[e.name] = by_name.get(e.name, 0.0) + own
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, chip))
    n = len(chips)
    out = {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "chips": n,
        "device_ops": [[name, ns / n / 1e9] for name, ns in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_name_gap(events, marks, a, a + d, chip), d / 1e9]
                      for d, a, chip in sorted(gaps, reverse=True)[:TOP]],
    }
    if module_prefix:
        runs = [e for e in events if e.plane.startswith(DEVICE_PREFIX)
                and e.line == MODULES_LINE
                and e.name.startswith(module_prefix)
                and e.start_ns >= lo and e.end_ns <= hi]
        out["module_s"] = sum(e.dur_ns for e in runs) / n / 1e9
        out["module_runs"] = len(runs) / n
    return out


def _name_gap(events: list[Event], marks: list[Event], lo: float, hi: float,
              chip: str) -> str:
    """`<chip>:<mark>:<what the host did>`: the host event, marks apart,
    that covers most of the gap; of those that cover it alike, the
    shortest, which says most."""
    mark = next((m.name[len(MARK_PREFIX):] for m in marks
                 if m.start_ns <= lo < m.end_ns), "-")
    best, best_key = "host_idle", (0.0, 0.0)
    for e in events:
        if not e.plane.startswith(HOST_PREFIX) or \
                e.name.startswith(MARK_PREFIX):
            continue
        c = _clip(e, lo, hi)
        if c is None:
            continue
        key = (round((c[1] - c[0]) / (hi - lo), 1), -e.dur_ns)
        if key > best_key:
            best, best_key = e.name, key
    name = f"d{chip[len(DEVICE_PREFIX):]}:{mark}:{best}"
    return name.replace(" ", "_")[:96]
