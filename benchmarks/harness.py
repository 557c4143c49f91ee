"""What every driver shares: finding a cell's files by the names in
`BENCHMARK.json`, the look for a chip, the result line.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name.  A later PR adds `configs/<config>.json`, `traffic/<traffic>.json`,
`drivers/<driver>.py`, `metrics/<metric>.py`, `counts/<model_type>.py`,
`reference/<model_type>.py`, `limits/<cell>.json` and the entries in
`BENCHMARK.json` that name them; it edits no file that is there.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
from typing import Any

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

EXIT_NO_DEVICE = 3
EXIT_NO_PROGRAM = 4


class BenchError(Exception):
    """A fault of the benchmark's own files or arguments."""


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, name: str) -> tuple[dict, dict]:
    """(the `workloads` entry, the `configs` entry) of cell `name`."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"there are: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise BenchError(f"workload {name!r} names the configuration "
                         f"{cell['config']!r}, which BENCHMARK.json lacks")
    return cell, configs[cell["config"]]


def load_config(entry: dict, root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, entry["file"]))


def load_traffic(name: str) -> dict:
    return _read_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def cell_params(config: dict, traffic: dict) -> dict:
    """A cell's traffic parameters: the mix's defaults, then what the mix
    says for this configuration, then what the configuration's own file
    says for this mix - so either side can be the new file."""
    out = dict(traffic.get("defaults", {}))
    out.update(traffic.get("per_config", {}).get(config["name"], {}))
    out.update(config.get("traffic", {}).get(traffic["name"], {}))
    return out


def load_cell(name: str) -> tuple:
    """(BENCHMARK.json, the cell's entry, its configuration, its traffic
    mix, the cell's traffic parameters, its driver's module)."""
    bench = load_benchmark()
    cell, entry = find_cell(bench, name)
    config = load_config(entry)
    traffic = load_traffic(cell["traffic"])
    return (bench, cell, config, traffic, cell_params(config, traffic),
            load_module("drivers", traffic["driver"]))


def load_limits(cell_name: str) -> dict:
    return _read_json(os.path.join(BENCH_DIR, "limits", f"{cell_name}.json"))


def load_module(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py` as a module of its package."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def load_metric(name: str):
    """`benchmarks/metrics/<name>.py`, by path: a metric's name may hold
    dots, so it is no module name."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None or not os.path.exists(path):
        raise BenchError(f"per-layer metric {name!r} has no reader at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(device_kind: str) -> dict:
    """The chip's peaks, from the one table.  A device that is not in it is
    an error, and no environment variable stands in."""
    table = _read_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"benchmarks/peaks.json ({sorted(table)})")
    return table[device_kind]


def goodput_share(run: dict, key: str):
    """Share in % of the window's wall that the window's `goodput` journal
    events (train/loop.py, one an epoch) give `key`: a bucket's name, or
    "wall_s" for the epochs' own walls.  None where there is none."""
    good = [r for r in run["journal"] if r.get("kind") == "goodput"]
    if not good or run["wall_s"] <= 0:
        return None
    total = sum(r["wall_s"] if key == "wall_s" else r["buckets"][key]
                for r in good)
    return 100.0 * total / run["wall_s"]


def cell_metrics(bench: dict, cell_name: str, section: str) -> list[dict]:
    """The metrics of `section` that cell `cell_name` reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def require_devices(chips: int) -> list:
    """The accelerators this cell runs on, or exit: a run that finds no
    accelerator, or fewer chips than the cell asks for, prints no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        print(f"perfbench: JAX found no accelerator (platform "
              f"{devices[0].platform!r}); a CPU run reports nothing",
              file=sys.stderr)
        raise SystemExit(EXIT_NO_DEVICE)
    if len(devices) < chips:
        print(f"perfbench: the cell asks for {chips} chips, JAX has "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(EXIT_NO_DEVICE)
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


@dataclasses.dataclass
class Context:
    """What `run.py` hands a driver."""

    cell: dict
    config: dict
    traffic: dict
    params: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    t_start: float                 # time.time() at process start
    log: Any = None                # callable(str): progress, to stderr
    prepared: Any = None           # what the driver's `prepare` started


@dataclasses.dataclass
class Outcome:
    """What a driver hands back."""

    checks: dict                   # name -> (value, limit)
    attempted: int
    failed: int
    end_to_end: dict               # name -> value, the driver's own timing
    run: dict                      # what the per-layer readers read;
    #                                "trace" holds `tracered.reduce`'s dict
    memory_peak_bytes: int


def is_correct(checks: dict) -> bool:
    return all(v is not None and v == v and v <= lim
               for v, lim in checks.values())


def result_line(bench: dict, ctx: Context, out: Outcome) -> dict:
    """The contract's last line."""
    name = ctx.cell["name"]
    metrics: dict = {}
    if ctx.trace:
        for m in cell_metrics(bench, name, "per_layer"):
            value = load_metric(m["name"]).read(out.run)
            if value is not None:   # a reader that finds nothing says nothing
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, name, "end_to_end"):
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    d0 = ctx.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    trace = out.run.get("trace") or {}
    if ctx.trace:
        device["busy_s"] = trace.get("busy_s")
        device["window_s"] = trace.get("window_s")
    line = {"correct": is_correct(out.checks), "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if ctx.trace and trace:
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line
