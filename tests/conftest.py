"""Test harness config: virtual 8-device CPU mesh.

Must run before jax is imported anywhere: tests exercise the multi-chip SPMD
paths on 8 virtual CPU devices (the single-process stand-in for a TPU slice —
SURVEY.md section 4's testability requirement the reference never met).
"""

import os
import sys

# tests run on the CPU whatever the machine holds (the chip is reached only
# through chip_smoke.py); subprocesses a test spawns inherit both variables
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_NUM_CPU_DEVICES", "8")

# repo root importable regardless of how pytest is invoked
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import numpy as np
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow (multi-process gangs, supervisor "
             "e2e, big demos)")


# the benchmark's reader tests (benchmarks/README.md): what a ledger line
# is computed from — the cell table, the FLOP and byte counts, the phase
# spans and the trace reduction.  They ride every run of the whole of
# tests/, which is the tier-1 command; the benchmark's other tests run whole
# cut-down cells for minutes and stay with benchmarks/README.md's command.
_BENCHMARK_READER_TESTS = ("test_benchmark_json.py", "test_counts.py",
                           "test_phases.py", "test_tracered.py",
                           "test_startup.py")


def pytest_configure(config):
    here = os.path.dirname(os.path.abspath(__file__))
    asked = {os.path.abspath(a.split("::")[0]) for a in config.args}
    if here not in asked:
        return  # a file or a test was named: run that and nothing else
    for name in _BENCHMARK_READER_TESTS:
        path = os.path.join(_ROOT, "benchmarks", "tests", name)
        if path not in asked:  # an xdist worker is handed the grown list
            config.args.append(path)


def pytest_collection_modifyitems(config, items):
    """Test tiering: the default run stays fast for iteration (round-1
    VERDICT weak #8 — the full suite overran 10 minutes); slow e2e tests
    run with --runslow or SHIFU_TPU_RUN_SLOW=1 (CI / pre-round full pass)."""
    if config.getoption("--runslow") or os.environ.get("SHIFU_TPU_RUN_SLOW"):
        return
    skip = pytest.mark.skip(
        reason="slow tier: pass --runslow or set SHIFU_TPU_RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def small_job():
    """A tiny WDBC-like job config: 30 features, 2x16 MLP."""
    from shifu_tpu.config import DataConfig, JobConfig, ModelSpec, OptimizerConfig, TrainConfig
    from shifu_tpu.data import synthetic

    schema = synthetic.make_schema(num_features=30)
    return JobConfig(
        schema=schema,
        data=DataConfig(batch_size=64, valid_ratio=0.1),
        model=ModelSpec(model_type="mlp", hidden_nodes=(16, 16),
                        activations=("tanh", "tanh"), compute_dtype="float32"),
        train=TrainConfig(epochs=3, optimizer=OptimizerConfig(name="adam", learning_rate=3e-3)),
    ).validate()


@pytest.fixture(scope="session")
def small_data(small_job):
    from shifu_tpu.data import pipeline, reader, synthetic

    rows = synthetic.make_rows(4096, small_job.schema, seed=7, noise=0.3)
    cols = reader.project_columns(rows, small_job.schema)
    full = pipeline.TabularDataset(cols["features"], cols["target"], cols["weight"])
    n = full.num_rows
    split_at = int(n * 0.9)
    train = full.take(np.arange(split_at))
    valid = full.take(np.arange(split_at, n))
    return train, valid
