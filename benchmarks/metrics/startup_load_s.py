"""Seconds of set-up that load the data: startup phases `startup/ingest`
(the blocking load when `train()` is given no dataset) and `startup/tiers`
(`_prepare_tiers()`, whole: the label and weight passes, stacking and the
wire cast, the puts of the train blocks, the resident eval tier).  Standard
error gets the four children.  A put that returns before its bytes have
landed leaves the rest in the first epoch's `epoch/train/device_wait`."""

from benchmarks import startup

CHILDREN = ("flags", "blocks", "h2d", "eval_tier")


def read(run: dict):
    ev = startup.event(run)
    if ev is None:
        return None
    startup.say("load: " + ", ".join(
        f"{c} {startup.phase_s(ev, 'startup/tiers/' + c):.3f} s"
        for c in CHILDREN)
        + f"; ingest {startup.phase_s(ev, 'startup/ingest'):.3f} s")
    return startup.phase_s(ev, "startup/ingest", "startup/tiers")
