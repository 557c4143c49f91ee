"""Seconds from `train()`'s entry to the stamp just before its first trained
epoch's `epoch_callback`: the `startup` event's `wall_s` (train/loop.py), the
part of `setup_s` that is the program's.  `setup_s` less this is the
harness's and the machine's side: imports, reaching the chip, the rows.
Standard error gets the part of it that no startup phase and not the first
epoch covers, and the compile seconds that ran under no span."""

from benchmarks import startup


def read(run: dict):
    ev = startup.event(run)
    if ev is None:
        return None
    wall = float(ev["wall_s"])
    left = startup.uncovered_s(ev)
    loose = startup.compile_s(ev, *startup.COMPILE_FIELDS, under="")
    startup.say(f"train call {wall:.3f} s; in no startup phase and not in "
                f"the first epoch: {left:.3f} s "
                f"({100.0 * left / wall if wall > 0 else 0.0:.1f} %); "
                f"compiles under no span: {loose:.3f} s")
    return wall
