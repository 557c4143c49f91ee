"""Share of the window's wall in the train step's dispatch-to-done: the
`step` bucket of the window's `goodput` journal events (train/step.py)."""

from benchmarks.harness import goodput_share


def read(run: dict):
    return goodput_share(run, "step")
