"""`drivers/resident_sequences.py` for a sequence model of any `model_type`:
the job's `model` group is worded by `benchmarks/jobs/<model_type>.py`
(`model_group(config)`), found by the configuration's `model_type`, where
the older driver words one model's keys itself.

Everything else is that driver's and `drivers/resident_epochs.py`'s,
imported and handed on under the names `calibrate_sequences.py` and the
tests call: the row generator (`make_rows`, `prepare`, `_datasets`), the
reference run and its planted faults (`reference_first_epoch`, `FAULTS`),
the comparison (`training_gaps`, `sparse_leaves`), the readers of the `moe`
events (`first_epoch_routing`, `tokens_dropped`), the window, the traced
slice, the count of compiles, `observe_state`.  So the traffic is the
accepted sequence cell's: the same rows, the same window, the same
`correct`.

Three functions are its own, because each reaches `build_job`:
`build_job` (the one statement that differs: where the `model` group comes
from), and `first_epoch_state` and `run`, which are statement-for-statement
copies of `drivers/resident_sequences.py`'s that call this module's
`build_job`.  Folding the two drivers into one, with
`nemotron3_nano_ep16` moved onto `jobs/nemotron_h.py`, edits files the
benchmark has, so it is a `benchmark` issue's (ROADMAP Queue 3).
"""

from __future__ import annotations

import gc

import numpy as np

from .. import harness
from . import resident_epochs as shared
from .resident_sequences import (  # noqa: F401  handed on to the callers
    CHUNK_ROWS, FAULTS, TRAIN_STREAM, VALID_STREAM, _datasets,
    first_epoch_routing, make_reference_epoch, make_rows, prepare,
    reference_first_epoch, sparse_leaves, tokens_dropped, training_gaps)

observe_state = shared.observe_state


def build_job(config: dict, params: dict, seed: int, epochs: int):
    job = {k: dict(v) for k, v in config.get("job", {}).items()}
    job.setdefault("model", {}).update(harness.load_module(
        "jobs", config["model_type"]).model_group(config))
    return shared.build_job(dict(config, job=job), params, seed, epochs)


def first_epoch_state(train, config, params, seed, train_ds, valid_ds,
                      devices) -> dict:
    """What the program says after one epoch from the seed: its errors and
    the norms of its state, which is freed."""
    job = build_job(config, params, seed, 1)
    res = train(job, train_ds, valid_ds,
                mesh=shared.build_mesh(job, devices), console=lambda s: None)
    prog = observe_state(res.state, config, seed)
    prog["train_error"] = res.history[0].train_error
    prog["valid_error"] = res.history[0].valid_error
    return prog


def run(ctx: harness.Context) -> harness.Outcome:
    from shifu_tpu import obs
    from shifu_tpu.train import train
    from shifu_tpu.utils.compilecache import enable_persistent_cache

    log = ctx.log or (lambda s: None)
    config, params, seed = ctx.config, ctx.params, ctx.seed
    enable_persistent_cache(min_compile_time_secs=0.0)
    shared._compiles_not_served()
    journal = obs.RunJournal(None)   # in memory: the readers get the records
    obs.set_journal(journal)

    # -- set-up: the rows, then the call's start and its first epoch --------
    train_rows, valid_rows, train_ds, valid_ds = _datasets(
        config, params, seed, ctx.prepared)
    log(f"rows made: {train_ds.num_rows} train, {valid_ds.num_rows} valid")
    tracer = None
    if ctx.trace:
        tracer = shared._SliceTrace(int(params.get("trace_after_epoch", 1)),
                                    int(params.get("trace_epochs", 2)))
    win = shared._Window(ctx.seconds, ctx.t_start, journal, tracer)
    job = build_job(config, params, seed, shared._MANY_EPOCHS)
    try:
        train(job, train_ds, valid_ds,
              mesh=shared.build_mesh(job, ctx.devices),
              console=lambda s: None, epoch_callback=win)
    except shared._WindowClosed:
        pass    # -- the window closed at an epoch boundary ----------------
    finally:
        if tracer:
            tracer.stop()
    if win.first is None or not win.history:
        raise harness.BenchError("the window's call ended before its window")
    batch = int(config["batch_size"])
    steps_per_epoch = train_ds.num_rows // batch
    epochs_done = len(win.history)
    rows_trained = epochs_done * steps_per_epoch * batch
    window_records = journal.records[win.mark:]
    finite = [np.isfinite(m.train_error) for m in win.history]
    peak = harness.memory_peak_bytes(ctx.devices)
    log(f"set-up {win.setup_s:.2f} s; window: {epochs_done} epochs, "
        f"{rows_trained} rows, {win.wall_s:.3f} s")
    obs.set_journal(None)
    gc.collect()    # the ended call's state and resident blocks go

    # -- correct: the first epoch again, then the reference over it ---------
    trace = tracer.reduce(params["step_module"]) if tracer else {}
    prog = first_epoch_state(train, config, params, seed, train_ds, valid_ds,
                             ctx.devices)
    del train_ds, valid_ds
    ref = reference_first_epoch(config, seed, train_rows, valid_rows, log=log)
    gaps, notes = training_gaps(prog, ref)
    gaps["replay_gap"] = max(
        abs(win.first.train_error - prog["train_error"]),
        abs(win.first.valid_error - prog["valid_error"]))
    gaps["compiles_in_window"] = float(win.compiles)
    gaps["tokens_dropped"] = tokens_dropped(journal.records)
    limits = harness.load_limits(ctx.cell["name"])["limits"]
    checks = {k: (gaps.get(k), lim) for k, lim in limits.items()}
    log(f"widest leaves: {notes}")
    log("the first epoch's routing, (choices, on held experts) an E layer: "
        f"{first_epoch_routing(journal.records)}")
    log(f"read, and held to no limit: "
        f"{ {k: v for k, v in gaps.items() if k not in limits} }")

    chips = len(ctx.devices)
    counts = harness.load_module("counts", config["model_type"])
    run_view = {
        "wall_s": win.wall_s, "rows": rows_trained, "chips": chips,
        "steps_per_epoch": steps_per_epoch, "epochs": epochs_done,
        "journal": window_records, "trace": trace, "peaks": ctx.peaks,
        "flops_per_sample": counts.flops_per_sample(config),
        "bytes_per_step": counts.bytes_per_step(config, batch),
        "batch": batch, "memory_peak_bytes": peak,
        "compiles_in_window": win.compiles,
    }
    return harness.Outcome(
        checks=checks,
        attempted=epochs_done * steps_per_epoch,
        failed=sum(steps_per_epoch for ok in finite if not ok),
        end_to_end={
            "train_samples_per_s_per_chip":
                rows_trained / win.wall_s / chips,
            "setup_s": win.setup_s},
        run=run_view, memory_peak_bytes=peak)
