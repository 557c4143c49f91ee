#!/usr/bin/env python3
"""chip_smoke.py: train -> export -> serve on the chip, through the entry
points a user calls, as the standing proof that the system starts there.

    python3 chip_smoke.py                  # needs a TPU; exits non-zero without
    python3 chip_smoke.py --rehearsal      # CPU, tiny sizes, never passes

The parent imports the standard library and numpy only and runs every phase
as a child process, one at a time, so the chip always belongs to exactly one
process and each phase proves the one before it released the chip:

  device    assert a TPU backend; print platform, device_kind, count, versions,
            which parser serves ingest
  train     `launcher.cli train` on seeded Shifu-format data, flagship width
            (mlp 3x100 relu, 30 features, bf16, weighted MSE, Adadelta, global
            batch 65,536, 3 epochs of >= 4 steps): once with defaults
            (streamed first epoch, then the device-resident tier) and once
            with shifu.data.device-resident-bytes=0 (the staged tier).
            Asserted from the journal and metrics.jsonl, not from stdout
  cache     the default train again on the same compile cache: the epoch
            program must classify `hit`
  serve     `launcher.cli serve --engine jax` holds the chip; a client child
            pinned to the CPU scores mixed-size requests over the wire and
            holds them to the host-side numpy scorer; SIGINT must exit 0
  kernels   every Pallas kernel natively (a tpu_custom_call in the module),
            forward and gradient against f64 numpy oracles at product shapes;
            the kernels engaged through train(); the three timing premises;
            cost_analysis() against the analytic FLOP count
  multichip (>= 4 devices only) one-chip vs all-chip valid_error, and a
            data=2 x model=2 DeepFM through the CLI

Adadelta runs at its canonical lr 1.0, not the parity job's 0.003: at 0.003
twelve optimizer steps cannot move AUC off chance, and the learning rate does
not change the work.

The last line of stdout is the one JSON result, printed only when every
assertion held.  Any failure raises: there is no degraded pass.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
PHASES = ("device", "train", "cache", "serve", "kernels", "multichip")
# what a phase reads from an earlier one (a --phases subset must hold both)
NEEDS = {"train": ("device",), "cache": ("train",), "serve": ("train",),
         "multichip": ("train",)}
EPOCHS = 3
NUM_FEATURES = 30
MIN_AUC = 0.6

# what a chip run uses, and the cut a CPU rehearsal of the plumbing uses
REAL = dict(rows=300_000, batch=65_536, requests=20,
            request_sizes=(1, 7, 64, 1000), dfm_rows=40_000,
            dfm_batch=8192, dfm_vocab=200_000)
TINY = dict(rows=12_000, batch=1024, requests=8,
            request_sizes=(1, 7, 64, 200), dfm_rows=6000, dfm_batch=1024,
            dfm_vocab=512)

_prefix = ""
_children: list = []   # every Popen this process started


class SmokeFailed(Exception):
    pass


def say(msg: str) -> None:
    print(f"{_prefix}{msg}", flush=True)


def fact(name: str, value) -> None:
    """One `fact name=value` line: what a run established.  A child's go
    to its log, and the parent echoes them."""
    say(f"fact {name}="
        f"{value if isinstance(value, str) else json.dumps(value)}")


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailed(msg)


# ---------------------------------------------------------------- children


def child_env(**extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    # every job journals where --output / --work says, not where an
    # inherited telemetry override points
    env.pop("SHIFU_TPU_METRICS_DIR", None)
    env.update(extra)
    return env


def start_child(name: str, argv: list, work: str, **env_extra):
    log_path = os.path.join(work, "logs", f"{name}.log")
    log = open(log_path, "w")
    proc = subprocess.Popen(argv, stdout=log, stderr=subprocess.STDOUT,
                            env=child_env(**env_extra), cwd=ROOT,
                            start_new_session=True)
    log.close()
    _children.append(proc)
    return proc, log_path


def log_tail(path: str, nbytes: int = 6000) -> str:
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        f.seek(max(0, f.tell() - nbytes))
        return f.read().decode(errors="replace")


def run_child(name: str, argv: list, work: str, timeout: float = 900.0,
              **env_extra) -> str:
    """Run one child to its end; a non-zero exit or a timeout fails the
    smoke.  Returns the log path."""
    t0 = time.monotonic()
    proc, log_path = start_child(name, argv, work, **env_extra)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise SmokeFailed(f"{name}: no exit after {timeout:.0f}s\n"
                          + log_tail(log_path))
    dt = time.monotonic() - t0
    for line in open(log_path, errors="replace"):
        if line.startswith("fact "):
            say(line.rstrip("\n"))
    say(f"phase {name}: rc={rc} wall={dt:.1f}s")
    check(rc == 0, f"{name}: exit code {rc}\n" + log_tail(log_path))
    return log_path


def stop_children() -> None:
    """Stop every process group this script started that is still alive."""
    for proc in _children:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


def phase_argv(phase: str, work: str, rehearsal: bool, *extra) -> list:
    argv = [sys.executable, os.path.abspath(__file__), "--phase", phase,
            "--work", work, *extra]
    return argv + (["--rehearsal"] if rehearsal else [])


# ------------------------------------------------------- inputs from a seed


def write_flagship_inputs(work: str, size: dict) -> dict:
    """Shifu-format inputs of the parity MLP: gzip pipe-delimited rows plus
    ModelConfig.json / ColumnConfig.json, all from a seed."""
    from shifu_tpu.data import synthetic

    base = os.path.join(work, "inputs", "mlp")
    os.makedirs(base, exist_ok=True)
    schema = synthetic.make_schema(num_features=NUM_FEATURES)
    rows = synthetic.make_rows(size["rows"], schema, seed=21, noise=0.25)
    data = os.path.join(base, "data")
    synthetic.write_files(rows, data, num_files=8)
    model_config = {
        "dataSet": {"targetColumnName": "target"},
        "train": {"validSetRate": 0.1, "numTrainEpochs": EPOCHS,
                  "algorithm": "NN",
                  "params": {"NumHiddenLayers": 3,
                             "NumHiddenNodes": [100, 100, 100],
                             "ActivationFunc": ["relu", "relu", "relu"],
                             "LearningRate": 1.0, "Optimizer": "adadelta",
                             "Loss": "squared"}}}
    columns = [{"columnNum": 0, "columnName": "target",
                "columnFlag": "Target"}]
    for i in range(NUM_FEATURES):
        columns.append({"columnNum": 1 + i, "columnName": f"f{i}",
                        "columnType": "N", "finalSelect": True})
    return _write_configs(base, data, model_config, columns)


def write_deepfm_inputs(work: str, size: dict) -> dict:
    """A high-cardinality DeepFM (24 numeric + 6 categorical features,
    hidden 100x100, embedding 16), with a vocab
    large and a batch small enough that the tables and their optimizer
    slots are most of what a device holds: whether they are split across
    the model axis then shows in each device's bytes_in_use."""
    from shifu_tpu.data import synthetic

    base = os.path.join(work, "inputs", "deepfm")
    os.makedirs(base, exist_ok=True)
    n_cat, vocab = 6, size["dfm_vocab"]
    schema = synthetic.make_schema(num_features=NUM_FEATURES,
                                   num_categorical=n_cat, vocab_size=vocab)
    rows = synthetic.make_rows(size["dfm_rows"], schema, seed=22, noise=0.25)
    data = os.path.join(base, "data")
    synthetic.write_files(rows, data, num_files=8)
    model_config = {
        "dataSet": {"targetColumnName": "target"},
        "train": {"validSetRate": 0.1, "numTrainEpochs": EPOCHS,
                  "algorithm": "NN",
                  "params": {"ModelType": "deepfm", "NumHiddenLayers": 2,
                             "NumHiddenNodes": [100, 100],
                             "ActivationFunc": ["relu", "relu"],
                             "EmbeddingDim": 16, "LearningRate": 1.0,
                             "Optimizer": "adadelta", "Loss": "squared"}}}
    columns = [{"columnNum": 0, "columnName": "target",
                "columnFlag": "Target"}]
    for i in range(NUM_FEATURES):
        entry = {"columnNum": 1 + i, "columnName": f"f{i}",
                 "columnType": "N", "finalSelect": True}
        if i >= NUM_FEATURES - n_cat:
            entry["columnType"] = "C"
            entry["columnBinning"] = {
                "binCategory": [str(k) for k in range(vocab - 1)]}
        columns.append(entry)
    return _write_configs(base, data, model_config, columns)


def _write_configs(base: str, data: str, model_config: dict,
                   columns: list) -> dict:
    paths = {"data": data,
             "modelconfig": os.path.join(base, "ModelConfig.json"),
             "columnconfig": os.path.join(base, "ColumnConfig.json")}
    with open(paths["modelconfig"], "w") as f:
        json.dump(model_config, f)
    with open(paths["columnconfig"], "w") as f:
        json.dump(columns, f)
    return paths


def write_globalconfig(path: str, props: dict) -> str:
    body = "".join(f"<property><name>{k}</name><value>{v}</value></property>"
                   for k, v in props.items())
    with open(path, "w") as f:
        f.write(f"<configuration>{body}</configuration>\n")
    return path


# --------------------------------------------------------- reading a job dir


def read_journal(tele_dir: str) -> list:
    """The events journaled under `tele_dir` so far ([] before the file
    exists), by the repo's own reader: it imports no jax and skips a
    partial last line, so a live journal can be polled."""
    from shifu_tpu.obs.journal import JOURNAL_FILE, read_journal as read

    path = os.path.join(tele_dir, JOURNAL_FILE)
    return read(path) if os.path.exists(path) else []


def of_kind(events: list, kind: str) -> list:
    return [e for e in events if e.get("kind") == kind]


def train_job(ctx, name: str, inputs: dict, batch: int, *extra) -> str:
    job_dir = os.path.join(ctx.work, "jobs", name)
    argv = [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
            "--modelconfig", inputs["modelconfig"],
            "--columnconfig", inputs["columnconfig"],
            "--data", inputs["data"], "--output", job_dir,
            "--batch-size", str(batch), *extra]
    run_child(f"train_{name}", argv, ctx.work, **ctx.jax_env)
    return job_dir


def check_train(job_dir: str, device: dict, *, tiers: tuple, holders: int,
                min_auc: float = MIN_AUC) -> dict:
    """The assertions every train run must meet, read from the job's own
    records.  `tiers` are the input tiers epochs 0..2 must report;
    `holders` is how many devices must hold bytes at every epoch end."""
    name = os.path.basename(job_dir)
    events = read_journal(os.path.join(job_dir, "telemetry"))
    with open(os.path.join(job_dir, "metrics.jsonl")) as f:
        metrics = [json.loads(line) for line in f if line.strip()]

    ends = of_kind(events, "run_end")
    check(len(ends) == 1 and ends[0]["exit"] == 0, f"{name}: run_end {ends}")
    starts = of_kind(events, "train_start")
    check(len(starts) == 1, f"{name}: {len(starts)} train_start events")
    start = starts[0]
    check((start["platform"], start["device_kind"], start["device_count"])
          == (device["platform"], device["kind"], device["count"]),
          f"{name}: train_start says {start}, the device child {device}")

    check(len(metrics) == EPOCHS, f"{name}: {len(metrics)} epochs")
    for m in metrics:
        for key in ("train_error", "valid_error", "valid_auc"):
            check(math.isfinite(m[key]), f"{name}: {key}={m[key]}")
    for key in ("train_error", "valid_error"):
        check(metrics[-1][key] < metrics[0][key],
              f"{name}: {key} did not fall: "
              f"{[round(m[key], 6) for m in metrics]}")
    check(metrics[-1]["valid_auc"] > min_auc,
          f"{name}: valid_auc {metrics[-1]['valid_auc']:.4f} <= {min_auc}")

    got = tuple(e["tier"] for e in sorted(of_kind(events, "overlap_report"),
                                          key=lambda e: e["epoch"]))
    check(got == tiers, f"{name}: input tiers {got}, expected {tiers}")

    marks = of_kind(events, "hbm_watermark")
    check(len(marks) == EPOCHS, f"{name}: {len(marks)} hbm_watermark events")
    if device["platform"] == "tpu":
        for w in marks:
            check(w["source"] == "memory_stats",
                  f"{name}: hbm_watermark source {w['source']!r}")
            check(w["device_count"] == device["count"],
                  f"{name}: hbm_watermark sees {w['device_count']} devices")
            check(all(d["kind"] == device["kind"] for d in w["devices"]),
                  f"{name}: hbm_watermark kinds {w['devices']}")
            # an idle chip still reports a few KB of runtime bookkeeping
            # (27,136 B on a v5e), so "holds the job's bytes" means within
            # 8x of the fullest device, not merely above zero
            most = max(d["bytes_in_use"] for d in w["devices"])
            holding = sum(1 for d in w["devices"]
                          if d["bytes_in_use"] > 0
                          and d["bytes_in_use"] * 8 >= most)
            check(holding == holders,
                  f"{name}: {holding} devices hold the job's bytes at "
                  f"epoch {w['epoch']}, expected {holders}: {w['devices']}")
    check(os.path.isfile(os.path.join(job_dir, "final_model",
                                      "weights.npz")),
          f"{name}: no final_model")

    compiles = of_kind(events, "xla_compile")
    out = {
        "final_valid_error": metrics[-1]["valid_error"],
        "final_valid_auc": metrics[-1]["valid_auc"],
        "compile_s": round(sum(e["compile_s"] for e in compiles), 3),
        "epoch_program_cache": {
            e["fn"]: e["cache"] for e in compiles
            if e["fn"] in ("device_epoch_step", "epoch_scan_step")},
        "hbm_bytes_in_use": [d["bytes_in_use"]
                             for d in marks[-1]["devices"]],
        "ingest": [{k: e.get(k) for k in ("mode", "rows", "tiers")}
                   for e in of_kind(events, "ingest_report")],
    }
    fact(f"train.{name}", out)
    return out


# -------------------------------------------------------------- the phases


def phase_device(ctx) -> None:
    run_child("device", phase_argv("device", ctx.work, ctx.rehearsal),
              ctx.work, **ctx.jax_env)
    with open(os.path.join(ctx.work, "device.json")) as f:
        ctx.device = json.load(f)


def phase_train(ctx) -> None:
    t0 = time.monotonic()
    ctx.inputs = write_flagship_inputs(ctx.work, ctx.size)
    say(f"inputs: {ctx.size['rows']} rows x {NUM_FEATURES} features written "
        f"in {time.monotonic() - t0:.1f}s")
    n = ctx.device["count"]
    batch = ctx.size["batch"]

    job = train_job(ctx, "resident", ctx.inputs, batch)
    ctx.resident = check_train(
        job, ctx.device, holders=n, tiers=("stream", "resident", "resident"))
    ctx.artifact = os.path.join(job, "final_model")

    xml = write_globalconfig(
        os.path.join(ctx.work, "inputs", "staged.xml"),
        {"shifu.data.device-resident-bytes": 0})
    job = train_job(ctx, "staged", ctx.inputs, batch, "--globalconfig", xml)
    staged = check_train(job, ctx.device, holders=n,
                         tiers=("stream", "staged", "staged"))
    a, b = ctx.resident["final_valid_error"], staged["final_valid_error"]
    check(abs(a - b) <= 0.02 * a,
          f"resident {a:.6f} vs staged {b:.6f} valid_error differ > 2%")
    fact("train.tier_agreement", f"resident {a:.6f} staged {b:.6f}")


def phase_cache(ctx) -> None:
    """The default job again on the same compile cache.  When the first
    run compiled cold (its epoch program classified `miss`), the summed
    compile seconds must also fall; a run that started on an already warm
    cache can only show hits."""
    job = train_job(ctx, "warm", ctx.inputs, ctx.size["batch"])
    warm = check_train(job, ctx.device, holders=ctx.device["count"],
                       tiers=("stream", "resident", "resident"))
    first = ctx.resident
    check(warm["epoch_program_cache"]
          and set(warm["epoch_program_cache"].values()) == {"hit"},
          f"warm run's epoch programs: {warm['epoch_program_cache']}")
    if "miss" in first["epoch_program_cache"].values():
        # a rehearsal's tiny CPU programs compile in noise; only the chip's
        # multi-second compiles are held to the fall
        check(ctx.rehearsal or warm["compile_s"] < first["compile_s"],
              f"compile seconds did not fall: cold {first['compile_s']} "
              f"warm {warm['compile_s']}")
        fact("cache.compile_s",
             f"cold {first['compile_s']} warm {warm['compile_s']}")
    else:
        fact("cache.compile_s", f"first run already warm "
             f"{first['compile_s']}, second {warm['compile_s']}")


def phase_serve(ctx) -> None:
    tele = os.path.join(ctx.work, "serve_telemetry")
    argv = [sys.executable, "-m", "shifu_tpu.launcher.cli", "serve",
            ctx.artifact, "--engine", "jax", "--port", "0",
            "--host", "127.0.0.1"]
    t0 = time.monotonic()
    proc, log_path = start_child("serve", argv, ctx.work,
                                 SHIFU_TPU_METRICS_DIR=tele, **ctx.jax_env)
    start = None
    while start is None:
        check(proc.poll() is None,
              f"serve exited rc={proc.returncode} before serve_start\n"
              + log_tail(log_path))
        check(time.monotonic() - t0 < 600, "serve: no serve_start in 600s\n"
              + log_tail(log_path))
        time.sleep(0.25)
        found = of_kind(read_journal(tele), "serve_start")
        start = found[0] if found else None
    say(f"serve: up in {time.monotonic() - t0:.1f}s on port {start['port']}")
    dev = ctx.device
    check((start["engine"], start["platform"], start["device_kind"],
           start["device_count"])
          == ("jax", dev["platform"], dev["kind"], dev["count"]),
          f"serve_start says {start}, the device child {dev}")
    check(start["pid"] == proc.pid, "serve_start pid is not the child's")

    # the client and its numpy oracle import jax through the package
    # __init__s: pinned to the CPU so the serve child keeps the chip
    run_child("client", phase_argv("client", ctx.work, ctx.rehearsal,
                                   "--port", str(start["port"]),
                                   "--artifact", ctx.artifact),
              ctx.work, JAX_PLATFORMS="cpu")
    with open(os.path.join(ctx.work, "client.json")) as f:
        got = json.load(f)
    check(got["replies"] == ctx.size["requests"],
          f"client got {got['replies']} of {ctx.size['requests']} replies")
    check(0.0 < got["min_score"] and got["max_score"] < 1.0,
          f"scores outside (0, 1): {got}")
    # bf16 compute on the device against the f32 numpy scorer: 8 mantissa
    # bits through three 100-wide layers and a sigmoid stays under 2e-2
    check(got["max_abs_err"] <= 2e-2,
          f"device scores differ from the numpy scorer by "
          f"{got['max_abs_err']}")
    fact("serve.client", got)

    if not ctx.rehearsal:
        second_process_probe(ctx)

    os.kill(start["pid"], signal.SIGINT)
    try:
        rc = proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        raise SmokeFailed("serve: still alive 120s after SIGINT\n"
                          + log_tail(log_path))
    check(rc == 0, f"serve: exit code {rc} after SIGINT\n"
          + log_tail(log_path))
    say("serve: SIGINT -> exit 0")


def second_process_probe(ctx, limit: float = 60.0) -> None:
    """What a second process meets while the serve child holds the chip:
    an error, or a hang cut off at `limit` seconds.  Recorded, not judged:
    it is the runtime's behaviour, and the reason
    launcher/pod.require_one_chip_owner exists."""
    proc, log_path = start_child(
        "probe", phase_argv("probe", ctx.work, False), ctx.work)
    t0 = time.monotonic()
    try:
        rc = proc.wait(timeout=limit)
        tail = log_tail(log_path, 600).strip().splitlines()
        fact("ownership.second_process", f"exit {rc} after "
             f"{time.monotonic() - t0:.1f}s: {tail[-1] if tail else ''}")
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
        fact("ownership.second_process",
             f"hang: no result in {limit:.0f}s, killed")


def phase_kernels(ctx) -> None:
    run_child("kernels", phase_argv("kernels", ctx.work, ctx.rehearsal),
              ctx.work, timeout=1200, **ctx.jax_env)


def phase_multichip(ctx) -> None:
    n = ctx.device["count"]
    if n < 4:
        say(f"multichip: {n} device(s) present, phase needs >= 4 — not run")
        return
    job = train_job(ctx, "one_chip", ctx.inputs, ctx.size["batch"],
                    "--devices", "1")
    one = check_train(job, ctx.device, holders=1,
                      tiers=("stream", "resident", "resident"))
    a, b = one["final_valid_error"], ctx.resident["final_valid_error"]
    check(abs(a - b) <= 0.02 * a,
          f"one chip {a:.6f} vs {n} chips {b:.6f} valid_error differ > 2%")
    fact("multichip.valid_error", f"one chip {a:.6f}, {n} chips {b:.6f}")

    t0 = time.monotonic()
    inputs = write_deepfm_inputs(ctx.work, ctx.size)
    say(f"deepfm inputs written in {time.monotonic() - t0:.1f}s")
    xml = write_globalconfig(
        os.path.join(ctx.work, "inputs", "mesh.xml"),
        {"shifu.mesh.data": 2, "shifu.mesh.model": 2})
    job = train_job(ctx, "deepfm_mesh", inputs, ctx.size["dfm_batch"],
                    "--globalconfig", xml)
    # 4 of the n devices form the mesh.  No AUC floor here: twelve steps
    # do not settle 1.2M embedding rows, and this run is about the layout
    dfm = check_train(job, ctx.device, holders=4, min_auc=0.0,
                      tiers=("stream", "resident", "resident"))
    events = read_journal(os.path.join(job, "telemetry"))
    mesh = of_kind(events, "run_start")[0]["mesh"]
    check(mesh["data"] == 2 and mesh["model"] == 2, f"mesh {mesh}")
    if ctx.device["platform"] == "tpu":
        # table + two Adadelta slots, f32: replicated, every device would
        # hold all of it on top of its working set; split over the model
        # axis, half (171 MB against 230 MB on a v5e 2x2, PR 21)
        table = 3 * 6 * ctx.size["dfm_vocab"] * 16 * 4
        worst = max(dfm["hbm_bytes_in_use"])
        check(worst < table,
              f"a device holds {worst} bytes; the table and its slots are "
              f"{table}: not split across the model axis")
        fact("multichip.deepfm", {"table_and_slot_bytes": table,
                                  "max_device_bytes_in_use": worst})


# ------------------------------------------------------------ the children
#
# Everything below runs in a child process and may import jax.


def child_device(args) -> int:
    import importlib.metadata as md

    import jax

    from shifu_tpu.data import native_parser

    d = jax.devices()[0]
    want = "cpu" if args.rehearsal else "tpu"
    if d.platform != want:
        print(f"chip_smoke needs a {want} backend; JAX found {d.platform!r} "
              f"({d.device_kind})", file=sys.stderr)
        return 1
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
    fact("device", info)
    fact("versions", {pkg: md.version(pkg)
                      for pkg in ("jax", "jaxlib", "libtpu", "flax")})
    fact("ingest_parser", "native" if native_parser.available()
         else f"numpy ({native_parser.unavailable_reason()})")
    with open(os.path.join(args.work, "device.json"), "w") as f:
        json.dump(info, f)
    return 0


def child_probe(args) -> int:
    import jax

    try:
        d = jax.devices()[0]
    except RuntimeError as e:
        print("could not open the backend: "
              + " ".join(str(e).split())[:400])
        return 1
    print(f"opened {d.platform} ({d.device_kind})")
    return 0


def child_client(args) -> int:
    """Score mixed-size requests over the wire and hold every reply to the
    host-side numpy scorer.  Runs with JAX_PLATFORMS=cpu: the serve child
    owns the chip."""
    from shifu_tpu.export.scorer import Scorer
    from shifu_tpu.runtime.serve_wire import DTYPE_F32, ServeClient

    size = TINY if args.rehearsal else REAL
    oracle = Scorer(args.artifact)
    rng = np.random.default_rng(23)
    worst, lo, hi, replies = 0.0, 1.0, 0.0, 0
    with ServeClient("127.0.0.1", args.port, timeout=120.0) as client:
        for i in range(size["requests"]):
            n = size["request_sizes"][i % len(size["request_sizes"])]
            rows = rng.standard_normal((n, NUM_FEATURES)).astype(np.float32)
            got = np.asarray(client.score_rows(rows, dtype=DTYPE_F32))
            want = np.asarray(oracle.compute_batch(rows))
            check(got.shape == want.shape == (n, 1),
                  f"request {i}: reply {got.shape}, oracle {want.shape}")
            check(bool(np.all(np.isfinite(got))), f"request {i}: non-finite")
            worst = max(worst, float(np.max(np.abs(got - want))))
            lo, hi = min(lo, float(got.min())), max(hi, float(got.max()))
            replies += 1
    with open(os.path.join(args.work, "client.json"), "w") as f:
        json.dump({"replies": replies, "max_abs_err": worst,
                   "min_score": lo, "max_score": hi}, f)
    return 0


# --- f64 numpy oracles.  Not ops/attention.mha: XLA's f32 einsums run
# single-pass bf16 on the MXU, so on the chip the "reference" would be the
# looser side of the comparison.


def attention_oracle(q, k, v, g, scale):
    """softmax(q k^T * scale) v and its gradients under cotangent g, in
    f64, one head at a time so an (S, S) score matrix is all that lives."""
    q, k, v, g = (np.asarray(a, np.float64) for a in (q, k, v, g))
    out, dq, dk, dv = (np.empty_like(q) for _ in range(4))
    for h in range(q.shape[1]):
        qh, kh, vh, gh = q[:, h], k[:, h], v[:, h], g[:, h]
        s = np.matmul(qh, np.swapaxes(kh, -1, -2)) * scale
        s -= s.max(-1, keepdims=True)
        p = np.exp(s)
        p /= p.sum(-1, keepdims=True)
        out[:, h] = np.matmul(p, vh)
        dv[:, h] = np.matmul(np.swapaxes(p, -1, -2), gh)
        dp = np.matmul(gh, np.swapaxes(vh, -1, -2))
        ds = p * (dp - (dp * p).sum(-1, keepdims=True))
        dq[:, h] = np.matmul(ds, kh) * scale
        dk[:, h] = np.matmul(np.swapaxes(ds, -1, -2), qh) * scale
    return out, dq, dk, dv


def ft_block_oracle(x, p, heads):
    """One pre-LN transformer block (LN, qkv, attention, proj, residual,
    LN, FFN with tanh gelu, residual) in f64 — the math of
    ops/pallas_ft_block._block_math written independently."""
    def ln(z, scale, bias):
        m = z.mean(-1, keepdims=True)
        var = ((z - m) ** 2).mean(-1, keepdims=True)
        return (z - m) / np.sqrt(var + 1e-6) * scale + bias

    b, s, d = x.shape
    dh = d // heads
    qkv = ln(x, p["ln_attn_scale"], p["ln_attn_bias"]) @ p["qkv_kernel"] \
        + p["qkv_bias"]
    q, k, v = (qkv[..., i * d:(i + 1) * d].reshape(b, s, heads, dh)
               .transpose(0, 2, 1, 3) for i in range(3))
    sc = np.matmul(q, np.swapaxes(k, -1, -2)) * dh ** -0.5
    sc -= sc.max(-1, keepdims=True)
    pr = np.exp(sc)
    pr /= pr.sum(-1, keepdims=True)
    attn = np.matmul(pr, v).transpose(0, 2, 1, 3).reshape(b, s, d)
    x2 = x + attn @ p["proj_kernel"] + p["proj_bias"]
    y = ln(x2, p["ln_mlp_scale"], p["ln_mlp_bias"]) @ p["mlp_in_kernel"] \
        + p["mlp_in_bias"]
    y = 0.5 * y * (1.0 + np.tanh(np.sqrt(2.0 / np.pi)
                                 * (y + 0.044715 * y ** 3)))
    return x2 + y @ p["mlp_out_kernel"] + p["mlp_out_bias"]


def rel_err(got, want) -> float:
    """max |got - want| over the largest |want|: one number per tensor."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def child_kernels(args) -> int:
    import statistics

    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from shifu_tpu.config import (DataConfig, JobConfig, ModelSpec,
                                  OptimizerConfig, TrainConfig)
    from shifu_tpu.data import pipeline, reader, synthetic
    from shifu_tpu.ops import pallas_embedding as pe
    from shifu_tpu.ops.pallas_attention import flash_attention
    from shifu_tpu.ops.pallas_common import on_tpu
    from shifu_tpu.ops.pallas_ft_block import (fused_block_engaged,
                                               fused_transformer_block)
    from shifu_tpu.ops.pallas_int8_matmul import (fused_engaged,
                                                  int8_matmul_dequant)
    from shifu_tpu.ops.pallas_small_attention import small_token_attention
    from shifu_tpu.train import init_state, make_train_step, train

    from shifu_tpu.utils.compilecache import enable_persistent_cache

    enable_persistent_cache()   # a warm smoke skips this child's compiles
    t_start = time.perf_counter()

    def lap(what: str) -> None:   # to the log: where this child's time goes
        print(f"[{time.perf_counter() - t_start:7.1f}s] {what}", flush=True)

    tpu = not args.rehearsal
    check(on_tpu() == tpu, f"on_tpu()={on_tpu()} in a "
          f"{'chip' if tpu else 'rehearsal'} run")
    check(jax.devices()[0].platform == ("tpu" if tpu else "cpu"),
          f"kernel child on {jax.devices()[0].platform}")
    rng = np.random.default_rng(24)
    f32 = np.float32

    def native(fn, *a):
        """jit `fn`; on the chip its lowered module must hold a Mosaic
        custom call: the kernel really is a kernel, not the interpreter."""
        jitted = jax.jit(fn)
        if tpu:
            check("tpu_custom_call" in jitted.lower(*a).as_text(),
                  "no tpu_custom_call in the lowered module")
        return jitted

    over: list = []     # every kernel reports before any failure raises

    def held(name: str, errs: dict, tol: float, why: str) -> None:
        fact(f"kernel.{name}", {"rel_err": {k: float(f"{v:.3g}")
                                            for k, v in errs.items()},
                                "tol": tol, "native": tpu})
        bad = {k: v for k, v in errs.items() if not v <= tol}
        if bad:
            over.append(f"{name}: {bad} over {tol} ({why})")

    # --- the three premises the timing code rests on, as numbers
    one = jax.jit(lambda x: x + 1)
    x0 = jnp.zeros((), jnp.float32)
    one(x0).block_until_ready()
    laps = []
    for _ in range(100):
        t0 = time.perf_counter()
        one(x0).block_until_ready()
        laps.append(time.perf_counter() - t0)
    fact("premise.dispatch_ms_median_of_100",
         round(statistics.median(laps) * 1e3, 4))

    n_mm = 4096 if tpu else 256

    @jax.jit
    def long_program(a):
        def body(_, c):
            return jnp.tanh(c @ a)
        return jnp.sum(jax.lax.fori_loop(0, 256 if tpu else 4, body, a))

    a = jnp.asarray(rng.standard_normal((n_mm, n_mm)), jnp.bfloat16)
    float(long_program(a))
    t0 = time.perf_counter()
    float(long_program(a))
    t_read = time.perf_counter() - t0
    t0 = time.perf_counter()
    r = long_program(a)
    r.block_until_ready()
    t_block = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(r)
    t_after = time.perf_counter() - t0
    fact("premise.block_until_ready",
         {"program_s_by_readback": round(t_read, 4),
          "block_until_ready_s": round(t_block, 4),
          "readback_after_block_s": round(t_after, 5),
          "blocks_until_done": bool(t_block >= 0.9 * t_read)})

    payload = rng.integers(-127, 128, (256 if tpu else 8) << 20, np.int8)
    jax.device_put(payload[:1 << 20]).block_until_ready()
    t0 = time.perf_counter()
    jax.device_put(payload).block_until_ready()
    t_h2d = time.perf_counter() - t0
    fact("premise.h2d", {"bytes": int(payload.nbytes),
                         "seconds": round(t_h2d, 4),
                         "gb_per_s": round(payload.nbytes / t_h2d / 1e9, 3)})
    del payload

    lap("premises done")

    # --- each kernel alone: native, at the product's shape, forward and
    # gradient against f64.  The kernel computes the whole shape; the
    # oracle, which costs minutes at these sizes on the host, is held
    # against a seeded sample of independent slices (batch rows, heads).
    def attention_case(name, fn, shape, rows, heads_kept, tol, why):
        q, k, v, g = (rng.standard_normal(shape).astype(f32)
                      for _ in range(4))
        out = native(fn, q, k, v)(q, k, v)
        grads = jax.jit(jax.grad(
            lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_) * g),
            argnums=(0, 1, 2)))(q, k, v)
        pick = np.ix_(np.sort(rng.choice(shape[0], rows, replace=False)),
                      np.sort(rng.choice(shape[1], heads_kept,
                                         replace=False)))
        want = attention_oracle(q[pick], k[pick], v[pick], g[pick],
                                shape[-1] ** -0.5)
        held(name, {n_: rel_err(np.asarray(a_)[pick], w_) for n_, a_, w_
                    in zip(("out", "dq", "dk", "dv"), (out, *grads), want)},
             tol, why)
        lap(name)

    use = None if tpu else True      # auto gate on the chip; forced off it
    attention_case(
        "small_token_attention",
        lambda q, k, v: small_token_attention(q, k, v, use_pallas=use),
        *(((8192, 8, 31, 8), 1024, 8) if tpu else ((256, 2, 7, 8), 64, 2)),
        1e-4, "f32 VPU arithmetic end to end, no MXU pass")
    attention_case(
        "flash_attention",
        lambda q, k, v: flash_attention(q, k, v, use_pallas=True),
        *(((1, 8, 8192, 64), 1, 2) if tpu else ((1, 2, 256, 16), 1, 2)),
        2e-2, "f32 operands take bf16 passes through the MXU")

    # fused FT block: 30 features + CLS
    b_ft, s_ft, d_ft, heads, ratio = (8192 if tpu else 16), 31, 64, 8, 4
    spec = ModelSpec(model_type="ft_transformer", token_dim=d_ft,
                     num_layers=3, num_attention_heads=heads,
                     mlp_ratio=ratio, compute_dtype="bfloat16")
    if tpu:
        check(fused_block_engaged(spec, s_ft),
              f"fused_block=auto does not engage at {s_ft} tokens on a TPU")
    check(not fused_block_engaged(spec, 120),
          "wide_demo's 120 tokens engage the fused block (MAX_TOKENS 64)")
    shapes = {"ln_attn_scale": (d_ft,), "ln_attn_bias": (d_ft,),
              "qkv_kernel": (d_ft, 3 * d_ft), "qkv_bias": (3 * d_ft,),
              "proj_kernel": (d_ft, d_ft), "proj_bias": (d_ft,),
              "ln_mlp_scale": (d_ft,), "ln_mlp_bias": (d_ft,),
              "mlp_in_kernel": (d_ft, ratio * d_ft),
              "mlp_in_bias": (ratio * d_ft,),
              "mlp_out_kernel": (ratio * d_ft, d_ft),
              "mlp_out_bias": (d_ft,)}
    params = {k_: (rng.standard_normal(sh) / np.sqrt(sh[0])).astype(f32)
              if len(sh) == 2 else
              (1.0 + 0.1 * rng.standard_normal(sh)).astype(f32)
              for k_, sh in shapes.items()}
    x = rng.standard_normal((b_ft, s_ft, d_ft)).astype(f32)
    # the cotangent is zero outside a seeded sample of batch rows, so the
    # loss (and with it the parameter gradient) involves only those rows
    # and the oracle evaluates only them; the kernel still runs all rows
    rows = np.sort(rng.choice(b_ft, 512 if tpu else 8, replace=False))
    g = np.zeros(x.shape, f32)
    g[rows] = rng.standard_normal((len(rows), s_ft, d_ft))

    def block(x_, p_):
        return fused_transformer_block(x_, p_, spec, use_pallas=True)

    out = native(block, x, params)(x, params)
    dx, dp = jax.jit(jax.grad(
        lambda x_, p_: jnp.sum(block(x_, p_) * g), argnums=(0, 1)))(x, params)
    dx = np.asarray(dx, np.float64)
    x64 = x[rows].astype(np.float64)
    p64 = {k_: v_.astype(np.float64) for k_, v_ in params.items()}
    g64 = g[rows].astype(np.float64)
    errs = {"out": rel_err(np.asarray(out)[rows],
                           ft_block_oracle(x64, p64, heads))}
    check(not np.any(np.delete(dx, rows, axis=0)),
          "ft block: input gradient leaks across batch rows")
    # gradient: the kernel's directional derivative along a random
    # direction against the f64 oracle's central difference
    eps = 1e-4
    vx = rng.standard_normal(x64.shape)
    vp = {k_: rng.standard_normal(v_.shape) * np.abs(v_).mean()
          for k_, v_ in p64.items()}

    def loss64(x_, p_):
        return float(np.sum(ft_block_oracle(x_, p_, heads) * g64))

    fd_x = (loss64(x64 + eps * vx, p64) - loss64(x64 - eps * vx, p64)) \
        / (2 * eps)
    fd_p = (loss64(x64, {k_: p64[k_] + eps * vp[k_] for k_ in p64})
            - loss64(x64, {k_: p64[k_] - eps * vp[k_] for k_ in p64})) \
        / (2 * eps)
    got_x = float(np.sum(dx[rows] * vx))
    got_p = float(sum(np.sum(np.asarray(dp[k_], np.float64) * vp[k_])
                      for k_ in p64))
    errs["d_input"] = abs(got_x - fd_x) / abs(fd_x)
    errs["d_params"] = abs(got_p - fd_p) / abs(fd_p)
    held("ft_fused_block", errs, 2e-2,
         "f32 operands take bf16 passes through the MXU, forward in the "
         "kernel and backward in XLA")
    del x, g, out, dx, dp, x64, g64, vx
    lap("ft_fused_block")

    # fused int8 dequant + first-layer matmul at the flagship's shape
    m_i8, f_i8, n_i8 = (65536 if tpu else 512), NUM_FEATURES, 100
    bf16 = ml_dtypes.bfloat16
    q8 = rng.integers(-127, 128, (m_i8, f_i8), np.int8)
    w = (rng.standard_normal((f_i8, n_i8)) / np.sqrt(f_i8)).astype(f32)
    bias = (0.1 * rng.standard_normal(n_i8)).astype(f32)
    scale = np.full((f_i8,), 8.0 / 127.0, f32)
    gy = rng.standard_normal((m_i8, n_i8)).astype(f32)
    if tpu:
        check(fused_engaged(f_i8, n_i8), "int8 fused gate off on a TPU")

    def dense(w_, b_):
        return int8_matmul_dequant(q8, w_, b_, scale, None,
                                   use_pallas=use)

    y = native(dense, w, bias)(w, bias)
    dw, db = jax.jit(jax.grad(
        lambda w_, b_: jnp.sum(dense(w_, b_).astype(jnp.float32) * gy),
        argnums=(0, 1)))(w, bias)

    def as_bf16(a_):     # the flax-Dense promotion the kernel reproduces
        return np.asarray(a_, f32).astype(bf16).astype(np.float64)

    xq = as_bf16(q8.astype(f32) * scale)
    y_want = as_bf16(as_bf16(xq @ as_bf16(w)) + as_bf16(bias))
    held("int8_matmul_dequant",
         {"out": rel_err(np.asarray(y).astype(f32), y_want),
          "dw": rel_err(dw, xq.T @ as_bf16(gy)),
          "db": rel_err(db, as_bf16(gy).sum(0))}, 1.6e-2,
         "outputs are bf16: two ulps of 2^-8 at the largest magnitude")
    del q8, gy, y, xq, y_want
    lap("int8_matmul_dequant")

    # fused rows-touched Adadelta update, D a lane multiple
    nc, vocab, dim = 6, (100_000 if tpu else 512), 128
    n_ids = 32_768 if tpu else 64
    table = rng.standard_normal((nc, vocab, dim)).astype(f32)
    accu = rng.random((nc, vocab, dim)).astype(f32)
    delta = rng.random((nc, vocab, dim)).astype(f32)
    g_rows = rng.standard_normal((n_ids, nc, dim)).astype(f32)
    ids = np.full((n_ids, nc), vocab, np.int32)      # tail: the sentinel
    live = n_ids * 3 // 4
    for f in range(nc):
        ids[:live, f] = rng.choice(vocab, live, replace=False)
    lr = 1.0

    def rows_update(t_, a_, d_):
        return pe.fused_rows_update(t_, (a_, d_), jnp.asarray(g_rows),
                                    jnp.asarray(ids), "adadelta", lr,
                                    use_pallas=use)

    native(rows_update, table, accu, delta)
    new_t, (new_a, new_d) = jax.jit(rows_update, donate_argnums=(0, 1, 2))(
        jnp.asarray(table), jnp.asarray(accu), jnp.asarray(delta))
    new_t, new_a, new_d = (np.asarray(a_) for a_ in (new_t, new_a, new_d))
    errs = {"table": 0.0, "accu": 0.0, "delta_accu": 0.0}
    for f in range(nc):
        i_f = ids[:live, f]
        g_f, a_f, d_f, t_f = (a_.astype(np.float64) for a_ in (
            g_rows[:live, f], accu[f, i_f], delta[f, i_f], table[f, i_f]))
        want_a = 0.95 * a_f + 0.05 * g_f * g_f
        upd = g_f * np.sqrt(d_f + 1e-8) / np.sqrt(want_a + 1e-8)
        for key, got, want in (
                ("table", new_t, t_f - lr * upd), ("accu", new_a, want_a),
                ("delta_accu", new_d, 0.95 * d_f + 0.05 * upd * upd)):
            errs[key] = max(errs[key], rel_err(got[f, i_f], want))
        rest = np.ones(vocab, bool)
        rest[i_f] = False
        check(all(np.array_equal(n_[f][rest], o_[f][rest]) for n_, o_ in (
            (new_t, table), (new_a, accu), (new_d, delta))),
            f"rows update: field {f} changed rows no id touched")
    held("embedding_fused_rows_update", errs, 1e-5,
         "f32 elementwise on the VPU")
    del table, accu, delta, new_t, new_a, new_d
    lap("embedding_fused_rows_update")

    check(not over, "kernels off their f64 oracle: " + "; ".join(over))

    # --- the kernels engaged through the model, defaults, three steps
    adadelta = OptimizerConfig(name="adadelta", learning_rate=1.0)

    def three_steps(name, job, marker):
        schema, bs = job.schema, job.data.batch_size
        rows = synthetic.make_rows(4 * bs, schema, seed=25, noise=0.25)
        cols = reader.project_columns(rows, schema)
        full = pipeline.TabularDataset(cols["features"], cols["target"],
                                       cols["weight"])
        result = train(job, full.take(np.arange(3 * bs)),
                       full.take(np.arange(3 * bs, 4 * bs)),
                       console=lambda _s: None)
        m = result.history[-1]
        check(int(result.state.step) == 3, f"{name}: {result.state.step} "
              "optimizer steps")
        check(math.isfinite(m.train_error) and math.isfinite(m.valid_error),
              f"{name}: non-finite error {m}")
        in_module = None
        if marker:
            state = init_state(job, schema.feature_count, None)
            wcast = pipeline.wire_cast_fn(job.schema, job.data,
                                          job.model.compute_dtype)
            batch = wcast({k_: v_[:bs] for k_, v_ in cols.items()})
            text = make_train_step(job).lower(state, batch).as_text()
            in_module = marker in text and "tpu_custom_call" in text
            check(in_module == tpu, f"{name}: {marker} in the train "
                  f"step's module: {in_module}")
        fact(f"engaged.{name}", {"steps": 3, "train_error": m.train_error,
                                 "valid_error": m.valid_error,
                                 "kernel_in_train_step": in_module})
        lap(f"engaged.{name}")

    plain = synthetic.make_schema(num_features=NUM_FEATURES)
    three_steps("ft_transformer_ladder", JobConfig(
        schema=plain, data=DataConfig(batch_size=8192 if tpu else 32),
        model=spec,
        train=TrainConfig(epochs=1, loss="weighted_mse",
                          optimizer=adadelta)).validate(), "ft_fused_block")
    flagship = ModelSpec(model_type="mlp", hidden_nodes=(100, 100, 100),
                         activations=("relu",) * 3, compute_dtype="bfloat16")
    mlp_bs = 65_536 if tpu else 256
    three_steps("mlp_int8_wire", JobConfig(
        schema=plain, data=DataConfig(batch_size=mlp_bs, wire_dtype="int8"),
        model=flagship,
        train=TrainConfig(epochs=1, loss="weighted_mse",
                          optimizer=adadelta)).validate(),
        "int8_matmul_dequant")

    # DeepFM at the 100k-vocab ladder shape: which strategies auto picks
    dfm_vocab = 100_000 if tpu else 4096
    fact("deepfm_100k_vocab.embedding_strategy", {
        "forward": "one-hot matmul" if pe._onehot_ok(dfm_vocab, 0)
        else "XLA gather",
        "backward": "one-hot matmul" if pe._onehot_ok(dfm_vocab, 0)
        else ("segment_sum, a field at a time" if on_tpu()
              else "scatter-add")})
    three_steps("deepfm_100k_vocab", JobConfig(
        schema=synthetic.make_schema(num_features=NUM_FEATURES,
                                     num_categorical=6,
                                     vocab_size=dfm_vocab),
        data=DataConfig(batch_size=32_768 if tpu else 64),
        model=ModelSpec(model_type="deepfm", hidden_nodes=(100, 100),
                        activations=("relu", "relu"), embedding_dim=16,
                        compute_dtype="bfloat16"),
        train=TrainConfig(epochs=1, loss="weighted_mse",
                          optimizer=adadelta)).validate(), None)

    # --- cost_analysis() of the flagship train step against the analytic
    # count (fwd 2*m*k*n per dense layer; bwd twice that)
    job = JobConfig(schema=plain, data=DataConfig(batch_size=mlp_bs),
                    model=flagship,
                    train=TrainConfig(epochs=1, loss="weighted_mse",
                                      optimizer=adadelta)).validate()
    state = init_state(job, NUM_FEATURES, None)
    batch = {"features": jnp.zeros((mlp_bs, NUM_FEATURES), jnp.bfloat16),
             "target": jnp.zeros((mlp_bs, 1), jnp.float32),
             "weight": jnp.ones((mlp_bs, 1), jnp.float32)}
    cost = make_train_step(job).lower(state, batch).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    dims = [NUM_FEATURES, 100, 100, 100, 1]
    analytic = 3.0 * sum(2 * a_ * b_ for a_, b_ in zip(dims, dims[1:]))
    fact("cost_analysis.flagship_train_step", {
        "xla_flops_per_sample": round(float(cost["flops"]) / mlp_bs, 1),
        "analytic_train_flops_per_sample": analytic,
        "ratio": round(float(cost["flops"]) / mlp_bs / analytic, 4)})
    return 0


CHILDREN = {"device": child_device, "client": child_client,
            "probe": child_probe, "kernels": child_kernels}


# --------------------------------------------------------------- the parent


class Context:
    def __init__(self, work: str, rehearsal: bool):
        self.work = work
        self.rehearsal = rehearsal
        self.size = TINY if rehearsal else REAL
        self.jax_env = {"JAX_PLATFORMS": "cpu"} if rehearsal else {}
        self.device = self.inputs = self.resident = self.artifact = None


def parent(args) -> int:
    global _prefix
    if args.rehearsal:
        _prefix = "REHEARSAL "
    phases = tuple(args.phases.split(",")) if args.phases else PHASES
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        raise SystemExit(f"unknown phase(s) {unknown}; known: {PHASES}")
    for i, name in enumerate(phases):
        missing = [n for n in NEEDS.get(name, ()) if n not in phases[:i]]
        if missing:
            raise SystemExit(f"phase {name} needs {missing} before it")
    work = os.path.abspath(args.work)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(os.path.join(work, "logs"))
    ctx = Context(work, args.rehearsal)
    t0 = time.monotonic()
    try:
        for name in phases:
            say(f"== {name}")
            globals()[f"phase_{name}"](ctx)
    except SmokeFailed as e:
        print(f"{_prefix}FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        stop_children()
        # keep logs and journals; drop what is large
        for big in [os.path.join(work, "inputs"),
                    *glob.glob(os.path.join(work, "jobs", "*", "*_model"))]:
            shutil.rmtree(big, ignore_errors=True)
    say(f"all phases held in {time.monotonic() - t0:.1f}s")
    if args.rehearsal or phases != PHASES:
        say("not a chip pass: a rehearsal or a subset of phases never "
            "prints the result line")
        return 0
    d = ctx.device
    print(json.dumps({"ok": True, "device": {
        "platform": d["platform"], "kind": d["kind"], "count": d["count"]}}),
        flush=True)
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU, tiny sizes: proves the plumbing, never passes")
    p.add_argument("--phases", default="",
                   help="comma list to run a subset while debugging "
                        "(a subset never prints the result line)")
    p.add_argument("--work", default=os.path.join(ROOT, "chiprun_out",
                                                  "chip_smoke"),
                   help="scratch directory (emptied first)")
    p.add_argument("--phase", help=argparse.SUPPRESS)     # child entry
    p.add_argument("--port", type=int, help=argparse.SUPPRESS)
    p.add_argument("--artifact", help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.phase:
        return CHILDREN[args.phase](args)
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
