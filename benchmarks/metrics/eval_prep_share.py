"""Share of the window's wall in `evaluate()`'s host-side batch preparation:
phase `epoch/eval/prep` (train/loop.py: the next slice of the valid set,
`pad_to_batch`, the wire cast)."""

from benchmarks.phases import phase_share


def read(run: dict):
    return phase_share(run, "epoch/eval/prep")
