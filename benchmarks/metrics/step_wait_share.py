"""Share of the window's wall the loop waited for the device to finish the
epoch's steps: phase `epoch/train/device_wait` (train/loop.py: the epoch's
one loss readback).  Part of the `step` bucket, so never above
`step_share`."""

from benchmarks.phases import phase_share


def read(run: dict):
    return phase_share(run, "epoch/train/device_wait")
