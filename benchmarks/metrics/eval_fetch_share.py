"""Share of the window's wall `evaluate()` waited for scores: phase
`epoch/eval/fetch` (train/loop.py: `jax.device_get` of the oldest in-flight
score vector - the wait for the device plus the D2H)."""

from benchmarks.phases import phase_share


def read(run: dict):
    return phase_share(run, "epoch/eval/fetch")
