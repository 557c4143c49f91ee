"""Run one cell of BENCHMARK.json once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by name (`harness.py`), looks for the chips it asks
for, hands the rest to the driver its traffic file names, and prints the
result as one JSON object on the last line of standard output.  Progress and
the numbers compared go to standard error.
"""

from __future__ import annotations

import time

_T_START = time.time()  # before any import that costs: set-up starts here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks import harness  # noqa: E402


def _log(msg: str) -> None:
    print(f"[perfbench {time.time() - _T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        bench, cell, config, traffic, params, driver = harness.load_cell(
            args.workload)
    except (harness.BenchError, OSError, ValueError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    try:
        import shifu_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"perfbench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return harness.EXIT_NO_PROGRAM

    # set-up that needs no chip may start while JAX reaches it
    prepared = (driver.prepare(config, params, args.seed)
                if hasattr(driver, "prepare") else None)
    devices = harness.require_devices(int(cell["chips"]))
    ctx = harness.Context(
        cell=cell, config=config, traffic=traffic, params=params,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, peaks=harness.load_peaks(devices[0].device_kind),
        t_start=_T_START, log=_log, prepared=prepared)
    out = driver.run(ctx)
    line = harness.result_line(bench, ctx, out)
    for name, c in line["checks"].items():
        print(f"perfbench: check {name} = {c['value']!r} (limit "
              f"{c['limit']!r})", file=sys.stderr)
    print(f"perfbench: correct = {line['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
