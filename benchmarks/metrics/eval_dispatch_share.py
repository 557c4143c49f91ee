"""Share of the window's wall in `evaluate()`'s dispatches: phase
`epoch/eval/dispatch` (train/loop.py: batch placement and the `eval_step`
call - the host's re-tiling, the H2D enqueue and the dispatch)."""

from benchmarks.phases import phase_share


def read(run: dict):
    return phase_share(run, "epoch/eval/dispatch")
