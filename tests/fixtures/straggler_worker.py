"""Worker for the cross-host straggler-aggregation test.

Launched (4x, one virtual CPU device each) by
tests/test_multiprocess_distributed.py::test_straggler_line_names_slow_rank
with the SHIFU_TPU_* env contract.  Runs the REAL multihost train loop
(staged tier) end-to-end; the rank named by STRAGGLER_SLOW_RANK injects a
sleep into its input pipeline (a degraded-disk stand-in), and the chief's
console must print the slowest-first per-host line naming that rank first —
the successor of the reference AM's worker-stats sort
(appmaster/TensorflowSession.java:515-549).

Prints RESULT {"process": i, "lines": [straggler lines seen]}.
"""

import json
import os
import sys
import time

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 1)
try:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
except Exception:
    print("RESULT-SKIP no gloo cpu collectives in this jax build", flush=True)
    sys.exit(0)

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

from shifu_tpu.parallel import distributed


def main() -> None:
    assert distributed.initialize(), "env contract must trigger distributed init"
    nproc = jax.process_count()
    rank = jax.process_index()
    slow_rank = int(os.environ["STRAGGLER_SLOW_RANK"])

    import numpy as np

    from shifu_tpu.config import (DataConfig, JobConfig, MeshConfig,
                                  ModelSpec, OptimizerConfig, TrainConfig)
    from shifu_tpu.config.schema import RuntimeConfig
    from shifu_tpu.data import pipeline as pipe
    from shifu_tpu.data import synthetic
    from shifu_tpu.parallel import make_mesh
    from shifu_tpu.train import train

    if rank == slow_rank:
        # degraded-disk stand-in: this rank's staged input generator stalls
        # before producing, inflating ITS epoch wall time only
        orig = pipe.staged_epoch_blocks

        def slow_blocks(*a, **k):
            time.sleep(2.0)
            yield from orig(*a, **k)

        pipe.staged_epoch_blocks = slow_blocks

    schema = synthetic.make_schema(num_features=6)
    rows = synthetic.make_rows(256, schema, seed=100 + rank)
    feats = rows[:, 1:].astype(np.float32)
    tds = pipe.TabularDataset(feats, rows[:, :1].astype(np.float32),
                              np.ones((len(rows), 1), np.float32))
    vds = pipe.TabularDataset(feats[:32], rows[:32, :1].astype(np.float32),
                              np.ones((32, 1), np.float32))

    job = JobConfig(
        schema=schema,
        data=DataConfig(batch_size=8 * nproc, device_resident_bytes=0,
                        block_batches=4),  # force the staged tier
        model=ModelSpec(model_type="mlp", hidden_nodes=(8,),
                        activations=("relu",), compute_dtype="float32"),
        train=TrainConfig(epochs=2, loss="weighted_mse",
                          optimizer=OptimizerConfig(name="adadelta",
                                                    learning_rate=0.1)),
        runtime=RuntimeConfig(mesh=MeshConfig(data=nproc)),
    ).validate()
    mesh = make_mesh(MeshConfig(data=nproc), jax.devices())

    lines: list[str] = []
    r = train(job, train_ds=tds, valid_ds=vds, mesh=mesh,
              console=lines.append)
    assert np.isfinite(r.history[-1].train_error)
    straggler = [l for l in lines if "hosts by input time" in l]

    # -- streamed multihost first epoch: the tier where disk parse actually
    # happens.  The slow rank stalls in ITS OWN first_epoch_blocks producer
    # (before the round allgather), so only the timed local pull — not the
    # gang-synchronizing agreement — may enter the straggler sort.  The
    # data dir is SHARED (written by the test before spawn): file-shard
    # round-robin needs every host to see the same global listing.
    tmp = os.environ["STRAGGLER_DATA_DIR"]
    if rank == slow_rank:
        # restore the staged-tier injection first: only the STREAMED pull
        # may be slow in this run, so the assertion isolates the streamed
        # path's timing
        pipe.staged_epoch_blocks = orig
        orig_blocks = pipe.StreamingLoader.first_epoch_blocks

        def slow_first_epoch_blocks(self, *a, **k):
            time.sleep(2.0)
            yield from orig_blocks(self, *a, **k)

        pipe.StreamingLoader.first_epoch_blocks = slow_first_epoch_blocks

    import dataclasses
    sjob = job.replace(data=dataclasses.replace(
        job.data, paths=(tmp,), valid_ratio=0.1, stream_first_epoch=True))
    slines: list[str] = []
    rs = train(sjob, mesh=mesh, console=slines.append)
    assert np.isfinite(rs.history[-1].train_error)
    stream_straggler = [l for l in slines if "hosts by input time" in l]
    streamed = any("Streaming first epoch" in l for l in slines)

    distributed.barrier()
    print("RESULT " + json.dumps({"process": rank, "lines": straggler,
                                  "stream_lines": stream_straggler,
                                  "streamed": streamed}),
          flush=True)


if __name__ == "__main__":
    main()
