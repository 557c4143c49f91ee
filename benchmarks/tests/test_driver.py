"""The rest of a run, with the harness's look for a chip skipped: a sound
program comes out correct, a broken one does not; and run.py itself refuses
to report from the CPU."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from benchmarks import harness
from benchmarks.tests import util


@pytest.mark.parametrize("cell", util.cells())
def test_a_sound_run_is_correct_and_reports_its_metrics(cell):
    line, out = util.drive(cell)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {
        m["name"] for m in harness.cell_metrics(
            harness.load_benchmark(), cell, "end_to_end")}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "checks"       # the numbers compared come last
    assert out.run["compiles_in_window"] == 0
    # throughput is all the rows over the whole wall of the window's call
    assert line["metrics"]["train_samples_per_s_per_chip"]["value"] == \
        pytest.approx(out.run["rows"] / out.run["wall_s"])
    assert out.run["rows"] == (out.run["epochs"] * util.TINY_STEPS
                               * util.TINY_BATCH)


def test_a_traced_run_reports_the_per_layer_metrics_it_can_read():
    cell = util.cells()[0]
    line, _ = util.drive(cell, trace=True)
    names = set(line["metrics"])
    # the CPU has no device plane: the trace's readers say nothing, and
    # never 0 for a share of a roofline
    assert "step_roofline_share" not in names
    assert {"eval_share", "step_share", "input_wait_share",
            "outside_epochs_share", "compiles_in_window.train",
            "train_mfu_share"} <= names
    shares = [line["metrics"][n]["value"] for n in
              ("eval_share", "step_share", "input_wait_share",
               "outside_epochs_share")]
    assert all(0 <= s <= 100 for s in shares) and sum(shares) <= 100.0001


def _unchanged_state(job, mesh=None, donate=True):
    def epoch_step(state, blocks, order):
        return state, jnp.float32(0.25 * order.shape[0])
    return epoch_step


def _half_batch_loss(make_loss_fn):
    def make(job):
        loss_fn = make_loss_fn(job)

        def faulty(params, apply_fn, batch, step=None):
            w = batch.get("weight")
            if w is None:
                w = jnp.ones((batch["target"].shape[0], 1), jnp.float32)
            batch = dict(batch, weight=w.at[w.shape[0] // 2:].set(0.0))
            return loss_fn(params, apply_fn, batch, step)
        return faulty
    return make


@pytest.mark.parametrize("cell", util.cells())
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        cell, monkeypatch):
    from shifu_tpu.train import step as step_lib

    monkeypatch.setattr(step_lib, "make_device_epoch_step", _unchanged_state)
    line, _ = util.drive(cell)
    assert line["correct"] is False
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", util.cells())
def test_half_of_the_batch_left_out_is_not_correct(cell, monkeypatch):
    from shifu_tpu.train import step as step_lib

    monkeypatch.setattr(step_lib, "make_loss_fn",
                        _half_batch_loss(step_lib.make_loss_fn))
    line, _ = util.drive(cell)
    assert line["correct"] is False
    failed = {k for k, c in line["checks"].items()
              if not c["value"] <= c["limit"]}
    assert failed, line["checks"]


def test_run_py_refuses_to_report_from_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", util.cells()[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == harness.EXIT_NO_DEVICE
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_run_py_names_an_unknown_workload():
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", "no_such.cell", "--seed", "1", "--seconds", "1"],
        cwd=harness.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2 and proc.stdout.strip() == ""
    assert "no_such.cell" in proc.stderr


def test_result_line_is_json_and_holds_the_contracts_keys():
    line, _ = util.drive(util.cells()[0])
    again = json.loads(json.dumps(line))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(again)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(again["device"])


def test_run_py_reports_nothing_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths`, a run exits non-zero and prints no result."""
    import shutil

    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", util.cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == harness.EXIT_NO_PROGRAM
    assert proc.stdout.strip() == ""
