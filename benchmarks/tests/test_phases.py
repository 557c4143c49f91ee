"""The readers of the loop's phase spans: on a small recorded list of
`goodput` events, with and without `phases`, and through the driver at the
tiny size."""

import copy
import json
import os

import pytest

from benchmarks import harness, phases
from benchmarks.tests import util

with open(os.path.join(os.path.dirname(__file__), "data",
                       "goodput_phases.json")) as f:
    RECORDED = json.load(f)
READERS = sorted(RECORDED["expected"])
EVAL_PHASES = ("eval_prep_share", "eval_dispatch_share", "eval_fetch_share",
               "eval_accumulate_share")


def _without_phases(run: dict) -> dict:
    """The same window as a program without the spans journals it."""
    run = copy.deepcopy(run)
    for r in run["journal"]:
        r.pop("phases", None)
    return run


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_the_recorded_share(name):
    value = harness.load_metric(name).read(RECORDED)
    assert value == pytest.approx(RECORDED["expected"][name])


@pytest.mark.parametrize("name", READERS)
def test_reader_says_nothing_without_phases(name):
    assert harness.load_metric(name).read(_without_phases(RECORDED)) is None
    assert harness.load_metric(name).read(
        {"wall_s": 1.0, "journal": []}) is None


def test_a_path_absent_from_phases_that_are_there_reads_zero():
    run = copy.deepcopy(RECORDED)
    for r in run["journal"]:
        if "phases" in r:
            r["phases"] = {k: v for k, v in r["phases"].items()
                           if not k.startswith("gc/")}
    assert harness.load_metric("gc_pause_share").read(run) == 0.0
    assert phases.phase_share(run, "epoch/eval/fetch") == pytest.approx(2.4)


def test_the_entries_name_both_cells_and_a_reader_each():
    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in READERS:
        assert entries[name]["workloads"] == cells
        assert entries[name]["source"] == "program_span"
        assert entries[name]["unit"] == "%"


def test_a_traced_run_reports_the_phases_and_they_fit_their_buckets():
    cell = util.cells()[0]
    bench, ctx = util.tiny_context(cell, trace=True)
    out = harness.load_module("drivers", ctx.traffic["driver"]).run(ctx)
    line = harness.result_line(bench, ctx, out)
    got = {n: line["metrics"][n]["value"] for n in READERS}
    assert all(0.0 <= v <= 100.0 for v in got.values())
    in_eval = sum(got[n] for n in EVAL_PHASES)
    assert 0.0 < in_eval <= line["metrics"]["eval_share"]["value"] + 1e-6
    assert 0.0 < got["step_wait_share"] \
        <= line["metrics"]["step_share"]["value"] + 1e-6
    # the same run as a program without the spans journals it: the line is
    # printed all the same, without the six
    out.run = _without_phases(out.run)
    line = harness.result_line(bench, ctx, out)
    assert not set(READERS) & set(line["metrics"])
    assert "eval_share" in line["metrics"]
