"""Share of the window's wall spent in the per-epoch evaluate: the `eval`
bucket of the window's `goodput` journal events (train/loop.py)."""

from benchmarks.harness import goodput_share


def read(run: dict):
    return goodput_share(run, "eval")
