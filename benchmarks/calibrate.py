"""Read, on the chip at a cell's own size, the two readings each limit of
`limits/<cell>.json` is set between ("How `correct` is decided", steps 3-5).

    python3 benchmarks/calibrate.py --workload <name> --seeds 12 --controls 3

For each seed: the rows, one `train()` call of one epoch (the timed
path's first epoch; no measured window is needed for training's readings),
the plain reference over the same rows, and the gaps between them: the lower
readings.  For the first `--controls` seeds also the control - the reference
in float8, the nearest precision below the bfloat16 the configurations
state, in the program's place - and the planted fault (half of every batch
left out, the reference in bfloat16 in the program's place): the upper
readings.  One process reads them all, since set-up is most of a run.
Prints one JSON line a seed and a summary; writes nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks import compare, harness, refrun  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147483900)
    ap.add_argument("--seed-list", default="",
                    help="these seeds, comma-separated, in place of --seeds "
                         "seeds counted up from --first-seed")
    args = ap.parse_args(argv)
    seeds = ([int(s) for s in args.seed_list.split(",")] if args.seed_list
             else [args.first_seed + 7919 * i for i in range(args.seeds)])

    _, cell, config, _, params, driver = harness.load_cell(args.workload)
    devices = harness.require_devices(int(cell["chips"]))

    from shifu_tpu.train import train
    from shifu_tpu.utils.compilecache import enable_persistent_cache
    enable_persistent_cache(min_compile_time_secs=0.0)

    t_start = time.time()

    def log(msg):
        print(f"[calibrate {time.time() - t_start:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.time()
        train_rows, valid_rows, train_ds, valid_ds = driver._datasets(
            config, params, seed)
        log(f"seed {seed}: rows made")
        prog = driver.first_epoch_state(train, config, params, seed,
                                        train_ds, valid_ds, devices)
        del train_ds, valid_ds
        log("the program's first epoch read")
        ref = refrun.first_epoch(config, seed, train_rows, valid_rows,
                                 log=log)
        gaps, where = compare.training_gaps(prog, ref)
        out = {"seed": seed, "program": gaps, "where": where}
        if i < args.controls:
            ctl = refrun.first_epoch(config, seed, train_rows, valid_rows,
                                     compute="float8", log=log)
            out["control_float8"] = compare.training_gaps(ctl, ref)[0]
            flt = refrun.first_epoch(config, seed, train_rows, valid_rows,
                                     compute="bfloat16", fault="half_batch",
                                     log=log)
            out["fault_half_batch"] = compare.training_gaps(flt, ref)[0]
            b16 = refrun.first_epoch(config, seed, train_rows, valid_rows,
                                     compute="bfloat16", log=log)
            out["reference_bfloat16"] = compare.training_gaps(b16, ref)[0]
        out["seconds"] = round(time.time() - t0, 1)
        rows.append(out)
        print(json.dumps(out), flush=True)
        del train_rows, valid_rows

    summary = {}
    for side, pick in (("program", max), ("control_float8", min),
                       ("fault_half_batch", min),
                       ("reference_bfloat16", max)):
        have = [r[side] for r in rows if side in r]
        if have:
            summary[side] = {k: pick(h[k] for h in have) for k in have[0]}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
