"""Share of the window's wall outside any epoch's own wall: what the loop
does between one epoch's ledger closing and the next one's opening.  (The
load of the resident tier is before the window, in `setup_s`.)"""

from benchmarks.harness import goodput_share


def read(run: dict):
    inside = goodput_share(run, "wall_s")
    return None if inside is None else max(100.0 - inside, 0.0)
