"""The trace reduction on small recorded traces with known answers."""

import json
import os

import pytest

from benchmarks import tracered
from benchmarks.tracered import Event

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
OPS, MODS = tracered.OPS_LINE, tracered.MODULES_LINE
S = 1e9


def _trace():
    """One chip, a 10 s window under two marks.  Epoch 1: a 3 s program
    whose `while` spans a 2 s fusion; epoch 2: a 1 s eval op.  Busy 4 s,
    idle 6 s; the longest gap, 4 s, lies under the garbage collector."""
    return [
        Event(HOST, "main", "perfbench/e1", 0 * S, 5 * S),
        Event(HOST, "main", "perfbench/e2", 5 * S, 5 * S),
        Event(HOST, "main", "CollectGarbage", 3.2 * S, 3.6 * S),
        Event(HOST, "main", "TransferToDevice", 8.1 * S, 1.5 * S),
        Event(DEV0, MODS, "jit_epoch_step(1)", 0 * S, 3 * S),
        Event(DEV0, MODS, "jit_score(2)", 7 * S, 1 * S),
        Event(DEV0, OPS, "while.1", 0 * S, 3 * S),
        Event(DEV0, OPS, "fusion.7", 0.5 * S, 2 * S),
        Event(DEV0, OPS, "fusion.9", 7 * S, 1 * S),
        # outside the marks: not counted
        Event(DEV0, OPS, "fusion.7", 11 * S, 1 * S),
        Event(DEV0, MODS, "jit_epoch_step(1)", 11 * S, 1 * S),
    ]


def test_busy_idle_ops_and_gaps():
    r = tracered.reduce(_trace(), "jit_epoch_step")
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(4.0)
    assert r["chips"] == 1
    ops = dict(r["device_ops"])
    assert ops["fusion.7"] == pytest.approx(2.0)    # its own time
    assert ops["while.1"] == pytest.approx(1.0)     # less its child's
    assert ops["fusion.9"] == pytest.approx(1.0)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    assert r["module_s"] == pytest.approx(3.0) and r["module_runs"] == 1
    gaps = r["idle_gaps"]
    assert [round(g[1], 6) for g in gaps] == [4.0, 2.0]
    assert gaps[0][0] == "d0:e1:CollectGarbage"
    assert gaps[1][0] == "d0:e2:TransferToDevice"
    assert sum(g[1] for g in gaps) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_busy_is_averaged_over_the_chips():
    second = [Event(DEV1, e.line, e.name, e.start_ns, e.dur_ns / 2)
              for e in _trace() if e.plane == DEV0]
    r = tracered.reduce(_trace() + second, "jit_epoch_step")
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((4.0 + 2.0) / 2)
    assert r["module_s"] == pytest.approx((3.0 + 1.5) / 2)
    assert r["module_runs"] == 1


def test_a_trace_with_no_device_operation_reads_nothing():
    host_only = [e for e in _trace() if e.plane == HOST]
    assert tracered.reduce(host_only, "jit_epoch_step") == {}


def test_without_marks_the_window_is_what_the_operations_span():
    r = tracered.reduce([e for e in _trace() if e.plane != HOST])
    assert r["window_s"] == pytest.approx(12.0)
    assert r["busy_s"] == pytest.approx(5.0)
    assert "module_s" not in r


def test_recorded_chip_trace():
    """A slice of a trace recorded on the TPU v5e (PR 24): the reduction's
    numbers on it are pinned, so that a change to the reduction shows."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "recorded_trace.json")
    with open(path) as f:
        doc = json.load(f)
    events = [Event(*e) for e in doc["events"]]
    r = tracered.reduce(events, doc["module_prefix"])
    for key, want in doc["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"][0][0] == doc["expect_top_op"][0]
    assert r["device_ops"][0][1] == pytest.approx(doc["expect_top_op"][1])
    assert r["idle_gaps"][0][0] == doc["expect_longest_gap"][0]
    assert r["idle_gaps"][0][1] == pytest.approx(doc["expect_longest_gap"][1])
    # the operations' own times and the gaps fill the window between them
    own = sum(t for _, t in tracered.reduce(events)["device_ops"])
    assert own <= r["busy_s"] * (1 + 1e-9)
