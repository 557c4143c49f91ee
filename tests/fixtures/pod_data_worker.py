"""Worker for the real two-host pod data-plane test.

Launched (2x, one virtual CPU device each) by
tests/test_pod_data_plane.py::test_real_two_host_train_journals_pod_plane
with the SHIFU_TPU_* env contract.  Runs the REAL multihost train loop
over a SHARED on-disk dataset (written by the test before spawn): each
rank ingests only its file shard, and the chief's `host_skew` journal
rows must carry every host's ingest extras plus agreeing order/shard
digests, next to a `dcn_placement` event for the per-host input
construction.

Prints RESULT {"process": i, "epochs": n} on success, RESULT-SKIP when
the jax build has no gloo CPU collectives.
"""

import json
import os
import sys

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

    import numpy as np

    from shifu_tpu.config import (DataConfig, JobConfig, MeshConfig,
                                  ModelSpec, OptimizerConfig, TrainConfig)
    from shifu_tpu.config.schema import RuntimeConfig
    from shifu_tpu.data import synthetic
    from shifu_tpu.obs import _sinks
    from shifu_tpu.parallel import make_mesh
    from shifu_tpu.train import train

    out = os.environ["POD_OUT_DIR"]
    tele = (os.path.join(out, "telemetry") if rank == 0
            else os.path.join(out, "telemetry", f"rank-{rank}"))
    _sinks.configure(tele)

    schema = synthetic.make_schema(num_features=6)
    job = JobConfig(
        schema=schema,
        data=DataConfig(paths=(os.environ["POD_DATA_DIR"],),
                        batch_size=8 * nproc, valid_ratio=0.1,
                        device_resident_bytes=0,
                        block_batches=4,  # force the staged tier
                        stream_first_epoch=False,  # every epoch must carry
                        # the deterministic order digest the test audits
                        host_shard="rotate"),
        model=ModelSpec(model_type="mlp", hidden_nodes=(8,),
                        activations=("relu",), compute_dtype="float32"),
        train=TrainConfig(epochs=2, loss="weighted_mse",
                          optimizer=OptimizerConfig(name="adadelta",
                                                    learning_rate=0.1)),
        runtime=RuntimeConfig(mesh=MeshConfig(data=nproc)),
    ).validate()
    mesh = make_mesh(MeshConfig(data=nproc), jax.devices())

    lines: list[str] = []
    r = train(job, mesh=mesh, console=lines.append)
    assert np.isfinite(r.history[-1].train_error)

    from shifu_tpu import obs
    obs.flush()
    distributed.barrier()
    print("RESULT " + json.dumps({"process": rank,
                                  "epochs": len(r.history)}), flush=True)


if __name__ == "__main__":
    main()
