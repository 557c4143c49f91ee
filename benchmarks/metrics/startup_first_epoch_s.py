"""Seconds of the first trained epoch that are not compiling: the `startup`
event's `first_epoch.wall_s` less its `compile` bucket (the epoch's goodput
record, obs/goodput.py) - the epoch's steps, the wait for what the puts left
unfinished, its evaluate()."""

from benchmarks import startup


def read(run: dict):
    ev = startup.event(run)
    if ev is None:
        return None
    first = ev.get("first_epoch") or {}
    return max(float(first.get("wall_s") or 0.0)
               - float((first.get("buckets") or {}).get("compile") or 0.0),
               0.0)
