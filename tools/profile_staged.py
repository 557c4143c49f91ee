"""On-rig profiler for the staged input tier (VERDICT r4 weak #2/#3).

Times, per wire format (bf16 / int8 / int8-compact), for each chunk of a
staged epoch: host block assembly (gather+cast), device_put, and the scan
dispatch — plus epoch walls and the raw H2D probe — so the missing
roofline fraction can be attributed to a specific phase instead of
guessed at.  Run on the chip: `python tools/profile_staged.py`.

Results ride the unified telemetry layer (ISSUE 3): each format emits
ONE `goodput` journal event (`source="profile_staged"`, the inline
phase seconds mapped onto the ledger's input/step buckets) and the
instrumented scan programs journal their own `xla_compile` events — so
`shifu-tpu profile <dir>` renders a profiling session exactly like a
training run.  With SHIFU_TPU_METRICS_DIR set the journal lands there;
otherwise the collected events print as JSONL at the end
(docs/PERF.md "Goodput & MFU").
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def main() -> None:
    import jax

    from shifu_tpu.config import (DataConfig, JobConfig, ModelSpec,
                                  OptimizerConfig, TrainConfig)
    from shifu_tpu.data import pipeline as pipe
    from shifu_tpu.data import synthetic
    from shifu_tpu.train import init_state, make_epoch_scan_step
    from shifu_tpu.utils.compilecache import enable_persistent_cache

    enable_persistent_cache()

    # telemetry sinks: SHIFU_TPU_METRICS_DIR when set (journal + scrape on
    # disk, like a training job), else an in-memory journal whose records
    # print as JSONL at the end — structured either way, no ad-hoc prints
    from shifu_tpu import obs
    metrics_dir = obs.resolve_metrics_dir()
    if metrics_dir:
        obs.configure(metrics_dir)
    else:
        obs.set_journal(obs.RunJournal(None))

    num_features = 30
    batch_size = 98304
    schema = synthetic.make_schema(num_features=num_features)

    def make_job(wire):
        return JobConfig(
            schema=schema, data=DataConfig(batch_size=batch_size,
                                           wire_dtype=wire),
            model=ModelSpec(model_type="mlp", hidden_nodes=(100, 100, 100),
                            activations=("relu",) * 3,
                            compute_dtype="bfloat16"),
            train=TrainConfig(epochs=1, loss="weighted_mse",
                              optimizer=OptimizerConfig(
                                  name="adadelta", learning_rate=0.003)),
        ).validate()

    rng = np.random.default_rng(0)
    n_chips = len(jax.devices())

    # ~6 bf16 chunks worth of rows (the bench's staged sizing)
    chunk_bf = max(1, (32 << 20) // (batch_size * (num_features * 2 + 8)))
    rows = 6 * chunk_bf * batch_size
    ds = pipe.TabularDataset(
        rng.standard_normal((rows, num_features)).astype(np.float32),
        (rng.random((rows, 1)) < 0.5).astype(np.float32),
        np.ones((rows, 1), np.float32))

    # raw H2D probe (both before and after, to see drift)
    from bench import _h2d_bandwidth_bytes_per_sec
    h2d0 = _h2d_bandwidth_bytes_per_sec()
    obs.event("h2d_probe", when="before",
              mb_per_sec=round(h2d0 / 1e6, 1))

    results = {}
    for name, wire, compact in (("bf16", "auto", False),
                                ("int8", "int8", False),
                                ("int8c", "int8", True)):
        job = make_job(wire)
        wcast_feat = pipe.wire_cast_fn(schema, job.data,
                                       job.model.compute_dtype)
        # pre-encode features once, as load_datasets does at parse time
        if wire == "int8":
            feats = wcast_feat({"features": ds.features})["features"]
        else:
            import ml_dtypes
            feats = ds.features.astype(ml_dtypes.bfloat16)
        dsw = pipe.TabularDataset(feats, ds.target, ds.weight)
        cast = (pipe.wire_cast_fn(schema, job.data,
                                  job.model.compute_dtype, compact=True)
                if compact else wcast_feat)
        row_b = pipe.wire_row_bytes(schema, job.data,
                                    job.model.compute_dtype,
                                    compact=compact)
        chunk = max(1, (32 << 20) // (batch_size * row_b))
        scan = make_epoch_scan_step(job, None)
        state = init_state(job, num_features, None)

        phase = {"assemble": [], "put": [], "dispatch": [], "sync": []}

        def epoch(e, record=True):
            nonlocal state
            last = None
            gen = pipe.staged_epoch_blocks(dsw, batch_size, epoch=e,
                                           block_batches=chunk)
            # run the producer INLINE (no prefetch thread) so each phase
            # times cleanly; overlap is measured separately below
            while True:
                t0 = time.perf_counter()
                blk = next(gen, None)
                if blk is None:
                    break
                blk = cast(blk) if cast else blk
                t1 = time.perf_counter()
                dev = {k: jax.device_put(v) for k, v in blk.items()}
                t2 = time.perf_counter()
                state, last = scan(state, dev)
                t3 = time.perf_counter()
                if record:
                    phase["assemble"].append(t1 - t0)
                    phase["put"].append(t2 - t1)
                    phase["dispatch"].append(t3 - t2)
            t0 = time.perf_counter()
            val = float(last)
            if record:
                phase["sync"].append(time.perf_counter() - t0)
            return val

        epoch(0, record=False)  # compile
        t0 = time.perf_counter()
        epoch(1)
        wall_inline = time.perf_counter() - t0

        # overlapped (product) epoch: prefetch thread does cast+put
        put_fn = (lambda b: {k: jax.device_put(v)
                             for k, v in (cast(b) if cast else b).items()})
        st2 = init_state(job, num_features, None)

        def epoch_pref(e):
            nonlocal st2
            last = None
            for blk in pipe.prefetch_to_device(
                    pipe.staged_epoch_blocks(dsw, batch_size, epoch=e,
                                             block_batches=chunk),
                    None, size=2, put_fn=put_fn):
                st2, last = scan(st2, blk)
            float(last)

        epoch_pref(0)  # compile any remaining shapes
        walls = []
        for e in (1, 2, 3):
            t0 = time.perf_counter()
            epoch_pref(e)
            walls.append(time.perf_counter() - t0)
        wire_bytes_epoch = (rows // batch_size) * batch_size * row_b
        best = min(walls)
        results[name] = {
            "row_bytes": row_b, "chunk_batches": chunk,
            "n_chunks": -(-(rows // batch_size) // chunk),
            "assemble_s": round(sum(phase["assemble"]), 3),
            "put_s": round(sum(phase["put"]), 3),
            "dispatch_s": round(sum(phase["dispatch"]), 3),
            "sync_s": round(sum(phase["sync"]), 3),
            "put_mb_per_s": round(
                wire_bytes_epoch / max(sum(phase["put"]), 1e-9) / 1e6, 1),
            "wall_inline_s": round(wall_inline, 3),
            "wall_prefetch_s": [round(w, 3) for w in walls],
            "rate_prefetch": round(rows / best / n_chips, 1),
        }
        # the inline epoch's phases mapped onto the ledger's buckets
        # (obs/goodput.py): assemble+put are host input work the device
        # waited on (the inline epoch runs the producer serially by
        # design), dispatch+sync is device step time
        input_s = sum(phase["assemble"]) + sum(phase["put"])
        step_s = sum(phase["dispatch"]) + sum(phase["sync"])
        obs.event(
            "goodput", source="profile_staged", wire=name,
            wall_s=round(wall_inline, 6),
            buckets={"compile": 0.0, "input": round(input_s, 6),
                     "step": round(step_s, 6), "checkpoint": 0.0,
                     "restore": 0.0, "eval": 0.0,
                     "other": round(max(wall_inline - input_s - step_s,
                                        0.0), 6)},
            goodput_fraction=(round(step_s / wall_inline, 4)
                              if wall_inline > 0 else None),
            mfu=None, **results[name])

    h2d1 = _h2d_bandwidth_bytes_per_sec()
    obs.event("h2d_probe", when="after", mb_per_sec=round(h2d1 / 1e6, 1))
    for name, r in results.items():
        # explicit before/after keys: probe-derived key names would
        # collide (and drop one fraction) whenever the two probes round
        # to the same MB/s — exactly the no-drift case
        frac = lambda h2d: (round(r["rate_prefetch"] * n_chips
                                  * r["row_bytes"] / h2d, 3)
                            if h2d > 0 else None)
        obs.event("staged_roofline", wire=name,
                  fraction_at_before_probe=frac(h2d0),
                  fraction_at_after_probe=frac(h2d1),
                  before_mb_per_sec=round(h2d0 / 1e6, 1),
                  after_mb_per_sec=round(h2d1 / 1e6, 1))
    obs.flush()
    j = obs.get_journal()
    if j is not None and j.path is None:
        for rec in j.records:  # no metrics dir: the JSONL goes to stdout
            print(json.dumps(rec), flush=True)
    elif j is not None:
        print(f"telemetry written to {j.path}", flush=True)


if __name__ == "__main__":
    main()
