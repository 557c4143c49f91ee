"""`calibrate.py` for cells of the `resident_sequences` mix: reads, on the
chip at the cell's own size, the two readings each limit of
`limits/<cell>.json` is set between.

    python3 benchmarks/calibrate_sequences.py --workload <name> --seeds 6 --controls 3 [--sides control_float8,fault_half_batch,fault_no_routed]

`calibrate.py` drives `refrun.first_epoch`, which evaluates in batches of
65,536 rows; this drives the driver's own `reference_first_epoch` and adds
the configuration's own planted fault.  For each seed: the rows, one
`train()` call of one epoch, the plain reference over the same rows, the
gaps between them (the lower readings).  For the first `--controls` seeds
also the upper readings, each the reference in the program's place: in
float8 (the control); in bfloat16 with half of every batch left out; in
bfloat16 with the routed experts' sum left out; and in bfloat16 with no
fault, which has to pass (`--sides` names those wanted).  Prints one JSON
line a seed (the gaps, the leaves they were widest on, the leaves left out
as `sparse_leaves` with the reference's gradient on them, and what the
program routed in that epoch) and a summary; writes nothing.
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

from benchmarks import harness  # noqa: E402

SIDES = (("control_float8", "float8", "", min),
         ("fault_half_batch", "bfloat16", "half_batch", min),
         ("fault_no_routed", "bfloat16", "no_routed", min),
         ("reference_bfloat16", "bfloat16", "", max))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147483900)
    ap.add_argument("--sides", default=",".join(s[0] for s in SIDES))
    args = ap.parse_args(argv)
    sides = [s for s in SIDES if s[0] in args.sides.split(",")]
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]

    _, cell, config, _, params, driver = harness.load_cell(args.workload)
    devices = harness.require_devices(int(cell["chips"]))

    from shifu_tpu import obs
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
        journal = obs.RunJournal(None)   # in memory, for the `moe` event
        obs.set_journal(journal)
        prog = driver.first_epoch_state(train, config, params, seed,
                                        train_ds, valid_ds, devices)
        obs.set_journal(None)
        del train_ds, valid_ds
        log(f"seed {seed}: the program's first epoch read")
        ref = driver.reference_first_epoch(config, seed, train_rows,
                                           valid_rows, log=log)
        gaps, where = driver.training_gaps(prog, ref)
        out = {"seed": seed, "program": gaps, "where": where,
               "routing": driver.first_epoch_routing(journal.records)}
        if i < args.controls:
            for name, compute, fault, _ in sides:
                side = driver.reference_first_epoch(
                    config, seed, train_rows, valid_rows, compute=compute,
                    fault=fault, log=log)
                out[name] = driver.training_gaps(side, ref)[0]
        out["seconds"] = round(time.time() - t0, 1)
        rows.append(out)
        print(json.dumps(out), flush=True)

    summary = {}
    for name, pick in (("program", max), *((s[0], s[3]) for s in SIDES)):
        have = [r[name] for r in rows if name in r]
        if have:
            summary[name] = {k: pick(h[k] for h in have) for k in have[0]}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
