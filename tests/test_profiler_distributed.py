"""Tests for the tracing subsystem, device prefetch, and multi-host init
(single-process behaviors; multi-host contract is env-var driven)."""

import os

import numpy as np
import pytest

import jax

from shifu_tpu.data.pipeline import TabularDataset, batch_iterator, prefetch_to_device
from shifu_tpu.parallel import data_parallel_mesh
from shifu_tpu.parallel import distributed as dist
from shifu_tpu.train.profiler import StepTimer


def _ds(n=100, f=4):
    return TabularDataset(
        features=np.arange(n * f, dtype=np.float32).reshape(n, f),
        target=np.zeros((n, 1), np.float32),
        weight=np.ones((n, 1), np.float32),
    )


def test_prefetch_preserves_order_and_content():
    ds = _ds(96)
    host = list(batch_iterator(ds, 32, shuffle=False))
    dev = list(prefetch_to_device(iter(host), mesh=None, size=2))
    assert len(dev) == 3
    for h, d in zip(host, dev):
        np.testing.assert_array_equal(h["features"], np.asarray(d["features"]))
        assert isinstance(d["features"], jax.Array)


def test_prefetch_with_mesh_shards(eight_devices):
    mesh = data_parallel_mesh(8)
    ds = _ds(64)
    dev = list(prefetch_to_device(batch_iterator(ds, 32, shuffle=False),
                                  mesh=mesh, size=2))
    assert dev[0]["features"].sharding.shard_shape((32, 4)) == (4, 4)


def test_prefetch_propagates_errors():
    def bad_iter():
        yield {"features": np.zeros((4, 2), np.float32)}
        raise RuntimeError("boom in producer")

    it = prefetch_to_device(bad_iter(), size=2)
    next(it)
    with pytest.raises(RuntimeError, match="boom in producer"):
        next(it)


def test_prefetch_size_zero_synchronous():
    ds = _ds(32)
    out = list(prefetch_to_device(batch_iterator(ds, 16, shuffle=False), size=0))
    assert len(out) == 2


def test_step_timer_summary():
    t = StepTimer()
    t.start()
    for _ in range(5):
        t.mark_input_ready()
        t.mark_step_done()
    s = t.summary()
    assert set(s) >= {"input_mean_ms", "step_p50_ms", "input_fraction"}
    assert "input fraction" in t.console_line()


def test_trace_writes_profile(tmp_path):
    """The one trace seam: `obs.trace_epochs=first` leaves the first
    epoch's raw profiler files under `obs.trace_dir`, and an unscheduled
    epoch writes nothing."""
    import jax.numpy as jnp
    from shifu_tpu.config import ObsConfig
    from shifu_tpu.obs import devprof
    d = str(tmp_path / "prof")
    dp = devprof.DeviceProfiler(ObsConfig(trace_epochs="first", trace_dir=d))
    with dp.epoch_capture(1):
        pass
    assert not os.path.exists(d)
    with dp.epoch_capture(0):
        jnp.ones((8, 8)).sum().block_until_ready()
    found = []
    for root, _, files in os.walk(d):
        found.extend(files)
    assert found, "no profile files written"


def test_distributed_single_process_noop():
    assert dist.initialize() is False  # no coordinator env, single host
    assert dist.is_chief()
    dist.barrier()  # no-op, must not hang


def test_train_timing_line(small_job, small_data, monkeypatch):
    from shifu_tpu.train import train
    monkeypatch.setenv("SHIFU_TPU_TIMING", "1")
    train_ds, valid_ds = small_data
    lines = []
    job = small_job.replace(train=small_job.train.__class__(epochs=1))
    train(job, train_ds, valid_ds, console=lines.append)
    assert any(l.startswith("timing:") for l in lines)
