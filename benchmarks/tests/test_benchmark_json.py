"""BENCHMARK.json against the contract's limits, and every file it names."""

import os
import re

import pytest

from benchmarks import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

BENCH = harness.load_benchmark()
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    # a full check with the full 24 cells has to fit into 43,200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, cells // 4)


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in entry.get("reduced", []):
        assert NAME.match(key)
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]


def test_names_are_unique():
    for section in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_bounds():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_its_cells_report(metric):
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert metric["workloads"] and set(metric["workloads"]) <= cells
    moved = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
    for cell in metric["workloads"]:
        assert "workloads" not in moved or cell in moved["workloads"]
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"
    # the reader is found by the metric's name
    assert callable(harness.load_metric(metric["name"]).read)


def test_a_whole_step_mfu_stands_beside_the_rooflines():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert any("mfu" in n.replace(".", "_").split("_") for n in names)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_file_of_a_cell_is_found_by_name(cell):
    _, entry = harness.find_cell(BENCH, cell["name"])
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    config = harness.load_config(entry)
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        assert key in config and key in config["source_values"]
    traffic = harness.load_traffic(cell["traffic"])
    assert traffic["name"] == cell["traffic"]
    params = harness.cell_params(config, traffic)
    assert params["train_rows"] > 0
    assert callable(harness.load_module("drivers", traffic["driver"]).run)
    counts = harness.load_module("counts", config["model_type"])
    assert counts.flops_per_sample(config) > 0
    assert counts.bytes_per_step(config, config["batch_size"]) > 0
    ref = harness.load_module("reference", config["model_type"])
    assert callable(ref.init_params) and callable(ref.make_forward)
    limits = harness.load_limits(cell["name"])["limits"]
    assert limits and all(v >= 0 for v in limits.values())
    assert 1 <= len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
    # the cell reports setup_s, one more end-to-end metric, a per-layer one
    e2e = harness.cell_metrics(BENCH, cell["name"], "end_to_end")
    assert {"setup_s"} < {m["name"] for m in e2e}
    assert harness.cell_metrics(BENCH, cell["name"], "per_layer")


def test_no_file_of_the_benchmark_branches_on_a_name():
    """run.py and the shared files know no cell or configuration by name."""
    names = [e["name"] for e in BENCH["configs"] + BENCH["workloads"]]
    for fn in ("run.py", "harness.py", "refrun.py", "compare.py",
               "datagen.py", "tracered.py", "calibrate.py",
               "drivers/resident_epochs.py"):
        with open(os.path.join(harness.BENCH_DIR, fn)) as f:
            text = f.read()
        for name in names:
            assert name not in text, (fn, name)


def test_unknown_device_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.load_peaks("cpu")
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
