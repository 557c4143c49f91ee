"""The one `startup` event of the window's `train()` call, for the readers
that open `setup_s`.

`train()` journals it once, right after the first trained epoch's
`epoch_callback` has returned (shifu_tpu/train/loop.py), so the window's
records - which the driver marks inside that callback - begin with it:

- `wall_s`: from the call's entry to a stamp just before that callback, the
  point where the harness's `setup_s` ends;
- `phases`: `{"<path>": [seconds, count]}` of the startup ledger
  (`startup/ingest`, `startup/restore`, `startup/init_state`, `startup/tiers`
  and its children `flags`, `blocks`, `h2d`, `eval_tier`);
- `first_epoch`: the first trained epoch's `wall_s`, `buckets`, `phases`;
- `compiles`: one entry a compile before the stamp - `fn`, the `span` path
  it ran under, and JAX's own `trace_s`, `lower_s`, `backend_compile_s`,
  `cache_retrieval_s` (shifu_tpu/obs/introspect.py) - and `cache`.

A reader gives None where the records hold no such event (a program from
before it), and 0 for a path or a sum absent from an event that is there.
"""

from __future__ import annotations

import sys

COMPILE_FIELDS = ("trace_s", "lower_s", "backend_compile_s",
                  "cache_retrieval_s")


def event(run: dict):
    """The window's `startup` record, or None."""
    for r in run["journal"]:
        if r.get("kind") == "startup":
            return r
    return None


def phase_s(ev: dict, *paths: str) -> float:
    """Seconds of the startup phases `paths` (each whole, its children in
    it)."""
    phases = ev.get("phases") or {}
    return float(sum(phases.get(p, (0.0, 0))[0] for p in paths))


def compile_s(ev: dict, *fields: str, under=None) -> float:
    """Σ of `fields` over the event's compiles: all of them, or those that
    ran under the span path `under` ("" for those under no span)."""
    def counts(c: dict) -> bool:
        span = str(c.get("span") or "")
        return (under is None or span == under
                or (under != "" and span.startswith(under + "/")))
    return float(sum(c.get(f) or 0.0 for c in ev.get("compiles") or []
                     if counts(c) for f in fields))


def uncovered_s(ev: dict) -> float:
    """What of `wall_s` no top-level startup phase and not the first epoch
    covers (a compile runs inside one of the two, or shows here)."""
    phases = ev.get("phases") or {}
    top = [p for p in phases if p.rsplit("/", 1)[0] not in phases]
    first = (ev.get("first_epoch") or {}).get("wall_s") or 0.0
    return float(ev["wall_s"]) - phase_s(ev, *top) - float(first)


def say(text: str) -> None:
    print(f"perfbench: startup: {text}", file=sys.stderr, flush=True)
