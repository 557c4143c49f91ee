"""The phases inside an epoch's goodput buckets, for the per-layer readers.

`train()` journals one `goodput` event an epoch (shifu_tpu/obs/goodput.py).
Since the phase spans (shifu_tpu/obs/spans.py) it carries
`"phases": {"<path>": [seconds, count]}`: the host seconds a hot span of the
loop spent under its full nested path (`epoch/eval/prep`, ...,
`epoch/train/device_wait`) and the collector's pauses (`gc/gen<N>`).  A
phase's parent is its path's prefix, so its self time is its seconds less
those of the paths under it.
"""

from __future__ import annotations


def phase_share(run: dict, *paths: str):
    """Share in % of the window's wall of the seconds that the window's
    `goodput` events give the phases `paths`.  None only where no event
    carries a `phases` field at all: a program without the spans says
    nothing.  A path absent from phases that are there reads 0 (a window
    with no collection has a `gc_pause_share` of 0)."""
    good = [r for r in run["journal"]
            if r.get("kind") == "goodput" and "phases" in r]
    if not good or run["wall_s"] <= 0:
        return None
    total = sum(r["phases"].get(p, (0.0, 0))[0] for r in good for p in paths)
    return 100.0 * total / run["wall_s"]
