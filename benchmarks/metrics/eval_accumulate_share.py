"""Share of the window's wall in `evaluate()`'s host accumulation: phase
`epoch/eval/accumulate` (train/loop.py: `StreamingMetrics.update` and its
final reduction, the `eval_rows_total` count, the score sketch)."""

from benchmarks.phases import phase_share


def read(run: dict):
    return phase_share(run, "epoch/eval/accumulate")
