"""Share of the window's wall inside Python's garbage collector: phases
`gc/gen0`, `gc/gen1` and `gc/gen2` (the `gc.callbacks` hook `train()` holds
while it runs).  A pause also counts in the phase it interrupted."""

from benchmarks.phases import phase_share


def read(run: dict):
    return phase_share(run, "gc/gen0", "gc/gen1", "gc/gen2")
