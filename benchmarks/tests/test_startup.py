"""The seven readers that open `setup_s`: on a recorded `startup` event, on
records without one (the parent's side of a comparison), and through the
driver at the tiny size."""

import copy
import json
import os

import pytest

from benchmarks import harness, startup
from benchmarks.tests import util

with open(os.path.join(os.path.dirname(__file__), "data",
                       "startup_event.json")) as f:
    RECORDED = json.load(f)
READERS = sorted(RECORDED["expected"])


def _without_event(run: dict) -> dict:
    """The same window as a program from before the event journals it."""
    run = copy.deepcopy(run)
    run["journal"] = [r for r in run["journal"] if r["kind"] != "startup"]
    return run


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_the_recorded_seconds(name):
    value = harness.load_metric(name).read(RECORDED)
    assert value == pytest.approx(RECORDED["expected"][name])


@pytest.mark.parametrize("name", READERS)
def test_reader_says_nothing_without_the_event(name):
    assert harness.load_metric(name).read(_without_event(RECORDED)) is None
    assert harness.load_metric(name).read(
        {"wall_s": 1.0, "journal": []}) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_zero_for_what_an_event_that_is_there_lacks(name):
    run = copy.deepcopy(RECORDED)
    run["journal"][0].update(phases={}, compiles=[], first_epoch=None)
    want = RECORDED["expected"]["startup_train_call_s"] \
        if name == "startup_train_call_s" else 0.0
    assert harness.load_metric(name).read(run) == want


def test_the_uncovered_part_is_the_wall_less_top_phases_and_first_epoch(
        capsys):
    ev = startup.event(RECORDED)
    assert startup.uncovered_s(ev) == pytest.approx(RECORDED["uncovered_s"])
    harness.load_metric("startup_train_call_s").read(RECORDED)
    harness.load_metric("startup_load_s").read(RECORDED)
    said = capsys.readouterr().err
    assert "1.000 s (2.5 %)" in said                 # uncovered, of 40 s
    assert "compiles under no span: 0.500 s" in said
    assert "h2d 18.000 s" in said and "blocks 4.000 s" in said
    assert "flags 0.500 s" in said and "eval_tier 1.000 s" in said


def test_compile_seconds_by_span():
    ev = startup.event(RECORDED)
    every = startup.compile_s(ev, *startup.COMPILE_FIELDS)
    assert every == pytest.approx(7.5)
    assert startup.compile_s(ev, *startup.COMPILE_FIELDS,
                             under="startup/init_state") == pytest.approx(1.75)
    assert startup.compile_s(ev, *startup.COMPILE_FIELDS,
                             under="epoch") == pytest.approx(5.25)
    assert startup.compile_s(ev, *startup.COMPILE_FIELDS,
                             under="") == pytest.approx(0.5)


def test_the_entries_name_every_cell_and_move_setup_s():
    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in READERS}
    for name in READERS:
        assert entries[name]["workloads"] == cells
        assert entries[name]["moves"] == "setup_s"
        assert entries[name]["unit"] == "s"
        assert entries[name]["better"] == "lower"
        assert entries[name]["layer"] in layers
        assert entries[name]["source"] in ("program_span", "program_counter")
    # additions only: the seven are the table's last entries
    assert sorted(m["name"] for m in bench["per_layer"][-7:]) == READERS


def test_a_traced_run_reports_the_seven_and_they_fit_in_the_call():
    cell = util.cells()[0]
    bench, ctx = util.tiny_context(cell, trace=True)
    out = harness.load_module("drivers", ctx.traffic["driver"]).run(ctx)
    line = harness.result_line(bench, ctx, out)
    got = {n: line["metrics"][n]["value"] for n in READERS}
    assert all(v >= 0.0 for v in got.values())
    call = got["startup_train_call_s"]
    assert 0.0 < call <= out.end_to_end["setup_s"]
    assert got["startup_load_s"] + got["startup_init_s"] \
        + got["startup_first_epoch_s"] <= call
    assert got["startup_trace_lower_s"] > 0
    # the event is the first of the window's records: the driver marks the
    # journal inside the callback that the event is written after
    assert out.run["journal"][0]["kind"] == "startup"
    # the same run as the parent's program journals it: the line is printed
    # all the same, without the seven
    out.run = dict(out.run, journal=_without_event(out.run)["journal"])
    line = harness.result_line(bench, ctx, out)
    assert not set(READERS) & set(line["metrics"])
    assert "eval_share" in line["metrics"]
