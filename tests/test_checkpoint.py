"""Checkpoint / auto-resume tests — the SPMD fault-tolerance story replacing
the reference's hot-standby backup workers (SURVEY.md section 5.3: parity =
health monitoring + automatic checkpoint-restart)."""

import numpy as np
import pytest

import jax

from shifu_tpu.config import CheckpointConfig, RuntimeConfig
from shifu_tpu.train import train


def _with_ckpt(job, directory, epochs=None, async_save=False,
               save_every_seconds=0, data=None):
    out = job.replace(
        train=job.train.__class__(epochs=epochs or job.train.epochs,
                                  optimizer=job.train.optimizer),
        runtime=RuntimeConfig(checkpoint=CheckpointConfig(
            directory=directory, save_every_epochs=1, async_save=async_save,
            save_every_seconds=save_every_seconds)),
    )
    return out.replace(data=data) if data is not None else out


def test_save_and_auto_resume(tmp_path, small_job, small_data):
    train_ds, valid_ds = small_data
    job = _with_ckpt(small_job, str(tmp_path / "ckpt"), epochs=3)

    r1 = train(job, train_ds, valid_ds, console=lambda s: None)
    assert len(r1.history) == 3

    # second run: everything done, restores and runs 0 epochs
    lines = []
    r2 = train(job, train_ds, valid_ds, console=lines.append)
    assert r2.resumed_from_epoch == 3
    assert len(r2.history) == 0
    assert any("Resumed" in l for l in lines)


def test_resume_continues_training(tmp_path, small_job, small_data):
    """Interrupted run (2 of 4 epochs) resumes at epoch 2 and matches the
    uninterrupted run's final state — deterministic restart."""
    train_ds, valid_ds = small_data
    d_interrupted = str(tmp_path / "a")
    job4 = _with_ckpt(small_job, d_interrupted, epochs=4)
    job2 = _with_ckpt(small_job, d_interrupted, epochs=2)

    train(job2, train_ds, valid_ds, console=lambda s: None)      # "crash" after 2
    r_resumed = train(job4, train_ds, valid_ds, console=lambda s: None)
    assert r_resumed.resumed_from_epoch == 2
    assert [m.epoch for m in r_resumed.history] == [2, 3]

    job4b = _with_ckpt(small_job, str(tmp_path / "b"), epochs=4)
    r_straight = train(job4b, train_ds, valid_ds, console=lambda s: None)

    p1 = jax.tree_util.tree_leaves(r_resumed.state.params)
    p2 = jax.tree_util.tree_leaves(r_straight.state.params)
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_terminal_checkpoint_holds_best_params(tmp_path, small_job, small_data):
    """With early stopping, the checkpoint written at the stop must hold the
    same best-measured params the returned state does — the export CLI's
    recovery path restores from that checkpoint and must ship the identical
    artifact the train tail exports (ADVICE round 1, train/loop.py)."""
    import dataclasses

    from shifu_tpu.train import checkpoint as ckpt_lib

    train_ds, valid_ds = small_data
    d = str(tmp_path / "ckpt")
    opt = dataclasses.replace(small_job.train.optimizer, name="sgd",
                              learning_rate=50.0)  # bounces: best != last
    job = _with_ckpt(small_job, d, epochs=6)
    job = job.replace(train=dataclasses.replace(
        job.train, optimizer=opt, early_stop_patience=2))
    result = train(job, train_ds, valid_ds, console=lambda s: None)
    assert len(result.history) < 6  # early stop actually fired

    mgr = ckpt_lib.make_manager(d)
    restored, _ = ckpt_lib.restore_latest(mgr, result.state)
    for a, b in zip(jax.tree_util.tree_leaves(restored.params),
                    jax.tree_util.tree_leaves(result.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # an early-stopped run is COMPLETE: re-running must resume as done (the
    # rolled-back params carry the last trajectory's optimizer moments, so
    # continuing training from them would apply mismatched updates)
    r2 = train(job, *small_data, console=lambda s: None)
    assert r2.resumed_from_epoch == 6
    assert len(r2.history) == 0

    # raising the epochs budget past the terminal checkpoint continues
    # training — with a FRESH optimizer (the saved moments belong to the
    # last trajectory, not the rolled-back best params)
    job10 = job.replace(train=dataclasses.replace(job.train, epochs=10,
                                                  early_stop_patience=0))
    lines = []
    r3 = train(job10, *small_data, console=lines.append)
    assert r3.resumed_from_epoch == 6
    assert any("optimizer state reinitialized" in l for l in lines)
    assert len(r3.history) == 4
    assert np.isfinite(r3.history[-1].train_error)


def test_resume_disabled(tmp_path, small_job, small_data):
    train_ds, valid_ds = small_data
    d = str(tmp_path / "ckpt")
    job = _with_ckpt(small_job, d, epochs=2)
    train(job, train_ds, valid_ds, console=lambda s: None)
    job_no_resume = job.replace(runtime=RuntimeConfig(
        checkpoint=CheckpointConfig(directory=d, resume=False)))
    r = train(job_no_resume, train_ds, valid_ds, console=lambda s: None)
    assert r.resumed_from_epoch == 0
    assert len(r.history) == 2


def test_staged_tier_saves_mid_epoch(tmp_path, small_job, small_data):
    """The staged (out-of-HBM) tier hits the time-cadence save point at
    CHUNK boundaries, not just epoch ends — its epochs are long, which is
    exactly where mid-epoch durability matters (round-3 addition).  And
    when the LAST chunk's cadence save lands on the same step the terminal
    save targets, the terminal save must still win (orbax would otherwise
    silently no-op it): the finished job must resume as DONE."""
    import dataclasses

    from shifu_tpu.train import checkpoint as ckpt_lib

    train_ds, valid_ds = small_data
    d = str(tmp_path / "ckpt")
    job = _with_ckpt(
        small_job, d, epochs=1, save_every_seconds=1e-6,
        data=dataclasses.replace(small_job.data, batch_size=256,
                                 device_resident_bytes=0,  # force staged
                                 block_batches=2))
    train(job, train_ds, valid_ds, console=lambda s: None)
    mgr = ckpt_lib.make_manager(d)
    # multiple chunk-boundary saves, not just the terminal one
    assert len(mgr.all_steps()) > 1, mgr.all_steps()
    # the terminal save overwrote the colliding cadence save: a restart
    # sees the job complete and trains ZERO further epochs
    r2 = train(job, train_ds, valid_ds, console=lambda s: None)
    assert r2.resumed_from_epoch == 1
    assert r2.history == []


def test_save_same_step_wins(tmp_path, small_job):
    """A checkpoint.save whose step collides with an existing one must still
    WIN (orbax's default silently no-ops): the save key bumps past the
    collision — never delete-then-save, which would destroy the newest
    durable checkpoint while its replacement is in flight — so restore
    returns the NEW extra and the PROGRESS marker never points ahead of
    what restore delivers (round-3 review findings, confirmed)."""
    import json
    import os

    from shifu_tpu.train import checkpoint as ckpt_lib
    from shifu_tpu.train import init_state

    d = str(tmp_path / "ckpt")
    mgr = ckpt_lib.make_manager(d)
    state = init_state(small_job, 30)
    ckpt_lib.save(mgr, 5, state, extra={"epoch": 0}, block=True)
    ckpt_lib.save(mgr, 5, state, extra={"epoch": 1}, block=True)
    _st, extra, step = ckpt_lib.restore_latest(mgr, state, with_extra=True)
    assert extra["epoch"] == 1
    assert step >= 5  # bumped key: ordering only, true step is in the state
    with open(os.path.join(d, ckpt_lib.PROGRESS_MARKER)) as f:
        assert json.load(f)["epoch"] == 1


def test_async_save_defers_progress_marker(tmp_path, small_job):
    """The PROGRESS marker must record only DURABLY saved epochs: with
    block=False the marker is written at the next wait point (next save or
    finalize), never while the save may still be in flight — otherwise the
    supervisors' durable-progress probe could reset the restart budget on
    progress a crash then discards."""
    import json
    import os

    from shifu_tpu.train import checkpoint as ckpt_lib
    from shifu_tpu.train import init_state

    d = str(tmp_path / "ckpt")
    mgr = ckpt_lib.make_manager(d)
    state = init_state(small_job, 30)
    marker = os.path.join(d, ckpt_lib.PROGRESS_MARKER)

    ckpt_lib.save(mgr, 1, state, extra={"epoch": 0}, block=False)
    # async: marker may exist only from PREVIOUS durable saves — epoch 0 is
    # not durable yet, so it must not be visible
    assert not os.path.exists(marker)

    # even if the process dies after the async save COMMITS but before the
    # marker flush, the supervisors' probe must still see the progress: the
    # committed step's own extra metadata is the authority
    from shifu_tpu.launcher.supervisor import checkpoint_progress
    mgr.wait_until_finished()  # commit WITHOUT flushing the marker
    assert not os.path.exists(marker)
    assert checkpoint_progress(d) == 0

    ckpt_lib.save(mgr, 2, state, extra={"epoch": 1}, block=False)
    # the wait inside save() made step-1 durable -> its marker flushes
    with open(marker) as f:
        assert json.load(f)["epoch"] == 0

    ckpt_lib.finalize(mgr)
    with open(marker) as f:
        assert json.load(f)["epoch"] == 1


def test_async_save_resume_equivalence(tmp_path, small_job, small_data):
    """async_save overlaps IO with compute but must leave the same durable
    checkpoints: an interrupted async run resumes identically to sync."""
    train_ds, valid_ds = small_data

    d = str(tmp_path / "async")
    train(_with_ckpt(small_job, d, epochs=2, async_save=True),
          train_ds, valid_ds, console=lambda s: None)
    r = train(_with_ckpt(small_job, d, epochs=4, async_save=True),
              train_ds, valid_ds, console=lambda s: None)
    assert r.resumed_from_epoch == 2
    assert [m.epoch for m in r.history] == [2, 3]

    sync_job = _with_ckpt(small_job, str(tmp_path / "sync"), epochs=4)
    r_sync = train(sync_job, train_ds, valid_ds, console=lambda s: None)
    for a, b in zip(jax.tree_util.tree_leaves(r.state.params),
                    jax.tree_util.tree_leaves(r_sync.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_resume_across_mesh_topologies(tmp_path, small_data):
    """Elastic re-provision: a checkpoint written while training on an
    8-way data-parallel mesh resumes on a 2x2 (data x model) mesh — and on
    no mesh at all — matching the uninterrupted single-topology run.

    The reference could only swap in hot-standby containers of the same
    cluster shape (TensorflowSession.java:748-781); checkpoint-restart under
    SPMD must survive the slice shape changing between attempts."""
    from shifu_tpu.config import (
        DataConfig, JobConfig, ModelSpec, OptimizerConfig, TrainConfig)
    from shifu_tpu.data import synthetic
    from shifu_tpu.parallel.mesh import MeshConfig, make_mesh

    # embeddings included so the model-axis sharding rule actually applies
    schema = synthetic.make_schema(num_features=12, num_categorical=4,
                                   vocab_size=64)
    def job_for(ckpt_dir, epochs):
        return _with_ckpt(JobConfig(
            schema=schema,
            data=DataConfig(batch_size=64, valid_ratio=0.1),
            model=ModelSpec(model_type="deepfm", hidden_nodes=(16,),
                            activations=("relu",), embedding_dim=8,
                            compute_dtype="float32"),
            train=TrainConfig(epochs=epochs, optimizer=OptimizerConfig(
                name="adam", learning_rate=3e-3)),
        ).validate(), ckpt_dir, epochs=epochs)

    rows = synthetic.make_rows(1024, schema, seed=9)
    from shifu_tpu.data import pipeline, reader
    cols = reader.project_columns(rows, schema)
    full = pipeline.TabularDataset(cols["features"], cols["target"],
                                   cols["weight"])
    tr, va = full.take(np.arange(896)), full.take(np.arange(896, 1024))

    mesh8 = make_mesh(MeshConfig(data=8))
    # a *smaller* slice with a different axis split (2x2 of the 8 devices)
    mesh22 = make_mesh(MeshConfig(data=2, model=2), devices=jax.devices()[:4])

    d = str(tmp_path / "elastic")
    train(job_for(d, 2), tr, va, mesh=mesh8, console=lambda s: None)
    r_22 = train(job_for(d, 3), tr, va, mesh=mesh22, console=lambda s: None)
    assert r_22.resumed_from_epoch == 2
    assert [m.epoch for m in r_22.history] == [2]

    # single-topology reference run
    d2 = str(tmp_path / "straight")
    r_ref = train(job_for(d2, 3), tr, va, mesh=mesh8, console=lambda s: None)

    p1 = jax.tree_util.tree_leaves(r_22.state.params)
    p2 = jax.tree_util.tree_leaves(r_ref.state.params)
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    # ...and resume once more on no mesh at all (single device)
    r_single = train(job_for(d, 4), tr, va, mesh=None, console=lambda s: None)
    assert r_single.resumed_from_epoch == 3
    assert [m.epoch for m in r_single.history] == [3]


@pytest.mark.slow
def test_resume_across_pipeline_trunk_layout(tmp_path, eight_devices):
    """A checkpoint written by a pipeline-parallel run (stacked trunk)
    resumes a non-pipelined run of the same model — and vice versa — with
    weights converted exactly (pipeline_stages is a layout choice, not part
    of the model)."""
    from shifu_tpu.config import (DataConfig, JobConfig, MeshConfig,
                                  ModelSpec, OptimizerConfig, TrainConfig)
    from shifu_tpu.data import reader, synthetic
    from shifu_tpu.data.pipeline import TabularDataset
    from shifu_tpu.parallel import make_mesh

    schema = synthetic.make_schema(num_features=7, num_categorical=2,
                                   vocab_size=16)
    rows = synthetic.make_rows(256, schema, seed=9)
    cols = reader.project_columns(rows, schema)
    full = TabularDataset(cols["features"], cols["target"], cols["weight"])
    train_ds, valid_ds = full.take(np.arange(224)), full.take(np.arange(224, 256))

    def make_job(stages, epochs, mesh_cfg=None):
        return JobConfig(
            schema=schema, data=DataConfig(batch_size=16),
            model=ModelSpec(model_type="ft_transformer", hidden_nodes=(8,),
                            activations=("relu",), token_dim=8,
                            num_attention_heads=2, num_layers=2,
                            pipeline_stages=stages, compute_dtype="float32"),
            train=TrainConfig(epochs=epochs, loss="weighted_mse",
                              optimizer=OptimizerConfig(name="adadelta",
                                                        learning_rate=0.01)),
            runtime=RuntimeConfig(
                mesh=mesh_cfg or MeshConfig(),
                checkpoint=CheckpointConfig(directory=str(tmp_path / "ckpt"),
                                            save_every_epochs=1)),
        ).validate()

    # phase 1: pipeline-parallel run writes a stacked-trunk checkpoint
    mesh_cfg = MeshConfig(data=4, pipe=2)
    mesh = make_mesh(mesh_cfg, devices=eight_devices)
    r1 = train(make_job(2, 2, mesh_cfg), train_ds, valid_ds, mesh=mesh,
               console=lambda s: None)
    assert len(r1.history) == 2

    # phase 2: non-pipelined run resumes from it (stacked -> per-block)
    lines = []
    r2 = train(make_job(1, 3), train_ds, valid_ds, console=lines.append)
    assert r2.resumed_from_epoch == 2
    assert any("trunk-layout change" in l for l in lines)
    assert np.isfinite(r2.history[-1].train_error)

    # phase 3: pipelined run resumes from phase 2's per-block checkpoint
    # (the reverse conversion)
    lines3 = []
    r3 = train(make_job(2, 4, mesh_cfg), train_ds, valid_ds, mesh=mesh,
               console=lines3.append)
    assert r3.resumed_from_epoch == 3
    assert any("trunk-layout change" in l for l in lines3)
    assert np.isfinite(r3.history[-1].train_error)


def test_incompatible_checkpoint_raises(tmp_path, small_job, small_data):
    """A genuinely incompatible checkpoint (changed topology, no layout
    conversion available) must surface, not silently restart from scratch
    and evict the good checkpoints."""
    train_ds, valid_ds = small_data
    job = _with_ckpt(small_job, str(tmp_path / "ckpt"), epochs=1)
    train(job, train_ds, valid_ds, console=lambda s: None)

    import dataclasses
    bigger = small_job.replace(model=dataclasses.replace(
        small_job.model, hidden_nodes=(32, 32)))
    job2 = _with_ckpt(bigger, str(tmp_path / "ckpt"), epochs=2)
    with pytest.raises(Exception):
        train(job2, train_ds, valid_ds, console=lambda s: None)


def test_time_based_checkpoint_cadence(tmp_path, small_job, small_data):
    """save_every_seconds adds mid-epoch saves on the per-batch tier —
    reference parity with Supervisor(save_model_secs=10), ssgd.py:124-128."""
    import dataclasses

    from shifu_tpu.config import DataConfig
    from shifu_tpu.train import checkpoint as ckpt_lib

    train_ds, valid_ds = small_data
    d = str(tmp_path / "ckpt")
    job = small_job.replace(
        # per-batch tier (staged off) with a 0-second cadence: every batch
        # boundary is "due", so mid-epoch steps get checkpointed
        data=dataclasses.replace(small_job.data, staged=False,
                                 device_resident_bytes=0),
        train=small_job.train.__class__(epochs=1,
                                        optimizer=small_job.train.optimizer),
        runtime=RuntimeConfig(checkpoint=CheckpointConfig(
            directory=d, save_every_epochs=1, save_every_seconds=1)))
    import time as time_mod
    orig = time_mod.monotonic
    # monotonic time advances 10s per call: every cadence check fires
    tick = {"t": 0.0}
    def fake_monotonic():
        tick["t"] += 10.0
        return tick["t"]
    time_mod.monotonic = fake_monotonic
    try:
        train(job, train_ds, valid_ds, console=lambda s: None)
    finally:
        time_mod.monotonic = orig
    mgr = ckpt_lib.make_manager(d)
    steps = sorted(mgr.all_steps())
    # mid-epoch steps present, not just the end-of-epoch save
    assert len(steps) > 1, steps


def _sigterm_after_epoch(epoch):
    """An `epoch_callback` that sends this process SIGTERM once `epoch` has
    closed: train()'s handler is installed by then and epochs remain, which
    a wall-clock timer promises of neither (a SIGTERM during init takes the
    default terminate action, by design)."""
    import os
    import signal

    def callback(m):
        if m.epoch == epoch:
            os.kill(os.getpid(), signal.SIGTERM)

    return callback


def test_sigterm_saves_and_exits_75(tmp_path, small_job, small_data):
    """SIGTERM mid-training checkpoints the current state and exits with
    code 75 so the supervisor restarts the job (preemption awareness)."""
    train_ds, valid_ds = small_data
    d = str(tmp_path / "ckpt")
    job = small_job.replace(
        train=small_job.train.__class__(epochs=50,
                                        optimizer=small_job.train.optimizer),
        runtime=RuntimeConfig(checkpoint=CheckpointConfig(directory=d)))

    lines = []
    with pytest.raises(SystemExit) as exc:
        train(job, train_ds, valid_ds, console=lines.append,
              epoch_callback=_sigterm_after_epoch(1))
    assert exc.value.code == 75
    assert any("SIGTERM" in l for l in lines)
    from shifu_tpu.train import checkpoint as ckpt_lib
    mgr = ckpt_lib.make_manager(d)
    assert mgr.latest_step() is not None
    # and the job resumes from that checkpoint
    job2 = job.replace(train=small_job.train.__class__(
        epochs=3, optimizer=small_job.train.optimizer),
        runtime=RuntimeConfig(checkpoint=CheckpointConfig(directory=d)))
    r = train(job2, train_ds, valid_ds, console=lambda s: None)
    assert r.resumed_from_epoch >= 1


def test_sigterm_without_checkpoint_dir_still_exits(small_job, small_data):
    """SIGTERM must terminate the run even when no checkpoint manager is
    configured (the drain point fires without a save)."""
    train_ds, valid_ds = small_data
    job = small_job.replace(train=small_job.train.__class__(
        epochs=200, optimizer=small_job.train.optimizer))
    lines = []
    with pytest.raises(SystemExit) as exc:
        train(job, train_ds, valid_ds, console=lines.append,
              epoch_callback=_sigterm_after_epoch(1))
    assert exc.value.code == 75
    assert any("no checkpoint directory" in l for l in lines)
