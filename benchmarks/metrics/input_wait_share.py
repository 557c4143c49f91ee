"""Share of the window's wall the loop waited for input: the `input` bucket
of the window's `goodput` journal events (data/pipeline.py feeds it)."""

from benchmarks.harness import goodput_share


def read(run: dict):
    return goodput_share(run, "input")
