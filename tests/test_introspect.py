"""Goodput ledger + XLA cost introspection (ISSUE 3): cost/memory capture
on CPU jit, goodput bucket arithmetic, the CPU train smoke the acceptance
criteria pin (>=1 `xla_compile` event, per-epoch `goodput` events whose
buckets sum to within 5% of the epoch wall), `shifu-tpu profile` text +
`--json` round-trip, and StepTimer single-chunk well-formedness.
"""

import json
import os

import numpy as np
import pytest

from shifu_tpu import obs
from shifu_tpu.obs import devprof as devprof_mod
from shifu_tpu.obs import goodput as goodput_mod
from shifu_tpu.obs import introspect as introspect_mod
from shifu_tpu.obs import render as obs_render


@pytest.fixture(autouse=True)
def _reset_obs():
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()


# ------------------------------------------------------------- introspect


def test_instrumented_jit_captures_cost_and_memory(tmp_path):
    """A compile journals one `xla_compile` event carrying cost_analysis
    FLOPs/bytes and memory_analysis sizes; cache-hit calls journal
    nothing; a new shape compiles (and journals) again."""
    import jax.numpy as jnp

    obs.configure(str(tmp_path))
    fn = introspect_mod.instrument_jit(lambda x: (x @ x.T).sum(), "probe")
    fn(jnp.ones((8, 8), jnp.float32))
    fn(jnp.ones((8, 8), jnp.float32))  # cached: no second event
    fn(jnp.ones((4, 8), jnp.float32))  # new signature: second compile
    obs.flush()
    recs = [r for r in obs.read_journal(str(tmp_path / "journal.jsonl"))
            if r["kind"] == "xla_compile"]
    assert len(recs) == 2
    for r in recs:
        assert r["fn"] == "probe"
        assert r["compile_s"] > 0
        assert r["flops"] > 0
        assert r["bytes_accessed"] > 0
        assert r["peak_bytes"] >= 0
        # JAX's own verdict and durations (tests/test_startup_spans.py
        # reads them cold and warm); no persistent cache is on here
        assert r["cache"] in ("off", "hit", "miss")
        assert r["trace_s"] > 0 and r["lower_s"] > 0
        assert (r["trace_s"] + r["lower_s"] + r["backend_compile_s"]
                + r["cache_retrieval_s"]) <= r["compile_s"] + 1e-3
    # the registry's counter and histogram ride along; the program's cost
    # is the event's and `stats()`'s, not a gauge's
    reg = obs.default_registry()
    assert reg.counter("xla_compiles_total").value(fn="probe") == 2
    assert reg.histogram("xla_compile_seconds").count(fn="probe") == 2
    st = introspect_mod.stats()["probe"]
    assert st["compiles"] == 2 and st["compile_s"] > 0
    assert st["flops"] > 0 and st["peak_bytes"] >= 0


def test_instrumented_jit_credits_ledger_compile():
    import jax.numpy as jnp

    fn = introspect_mod.instrument_jit(lambda x: x * 2.0, "ledgered")
    led = goodput_mod.begin_epoch()
    fn(jnp.ones((4,), jnp.float32))   # compile + 1 dispatch
    fn(jnp.ones((4,), jnp.float32))   # cached dispatch: no second compile
    rec = goodput_mod.end_epoch(0, wall_s=1.0)
    assert rec is not None and led is not None
    assert rec["buckets"]["compile"] > 0
    assert rec["compiles"] == 1


def test_compile_span_journals_event(tmp_path):
    obs.configure(str(tmp_path))
    with introspect_mod.compile_span("export_probe"):
        pass
    obs.flush()
    recs = [r for r in obs.read_journal(str(tmp_path / "journal.jsonl"))
            if r["kind"] == "xla_compile"]
    assert len(recs) == 1 and recs[0]["fn"] == "export_probe"


# ---------------------------------------------------------------- goodput


def test_goodput_bucket_arithmetic_sums_to_wall():
    led = goodput_mod.begin_epoch()
    led.add("input", 1.0)
    led.add("step", 6.0)
    led.add("checkpoint", 0.5)
    led.add("eval", 1.5)
    rec = goodput_mod.end_epoch(3, wall_s=10.0)
    assert rec["epoch"] == 3
    assert abs(sum(rec["buckets"].values()) - 10.0) < 1e-6
    assert abs(rec["buckets"]["other"] - 1.0) < 1e-6
    assert rec["goodput_fraction"] == pytest.approx(0.6)
    # counters accumulate per bucket
    sec = obs.default_registry().counter("goodput_bucket_seconds_total")
    assert sec.value(bucket="step") == pytest.approx(6.0)


def test_goodput_compile_subtracts_from_step_not_double_counted():
    led = goodput_mod.begin_epoch()
    led.add("step", 5.0)      # the timed dispatches INCLUDE the compile
    led.add("compile", 2.0)   # credited separately by introspect
    rec = goodput_mod.end_epoch(0, wall_s=6.0)
    assert rec["buckets"]["compile"] == pytest.approx(2.0)
    assert rec["buckets"]["step"] == pytest.approx(3.0)
    assert abs(sum(rec["buckets"].values()) - 6.0) < 1e-6


def test_peak_table_lookup_and_env_override(monkeypatch):
    """The one peak table (obs/devprof.PEAKS): lookup by device-kind
    substring, `v5p` before `v5`, an unknown part gives None for both
    axes, and nothing in the environment moves a peak."""
    assert devprof_mod.peaks("TPU v5e") == (197.0, 819.0)
    assert devprof_mod.peaks("TPU v5 lite") == (197.0, 819.0)
    assert devprof_mod.peaks("TPU v5p") == (459.0, 2765.0)
    assert devprof_mod.peaks("weird accelerator") == (None, None)
    assert devprof_mod.peaks() == (None, None)  # the CPU backend
    for name in ("PEAK_TFLOPS", "PEAK_HBM_GBPS"):
        monkeypatch.setenv("SHIFU_TPU_" + name, "123.5")
    assert devprof_mod.peaks("TPU v5e") == (197.0, 819.0)
    assert devprof_mod.peaks("weird accelerator") == (None, None)


def test_goodput_ledger_rejects_non_finite_seconds():
    """One NaN timing upstream must not poison the buckets, the
    goodput_bucket_seconds_total counter, or the artifact fields
    derived from them."""
    led = goodput_mod.begin_epoch()
    led.add("input", float("nan"))
    led.add("step", float("inf"))
    led.add("step", 2.0)
    rec = goodput_mod.end_epoch(0, wall_s=4.0)
    assert rec["buckets"]["input"] == 0.0
    assert rec["buckets"]["step"] == pytest.approx(2.0)
    total = sum(rec["buckets"].values())
    assert total == total and total == pytest.approx(4.0)


def test_goodput_note_is_noop_between_epochs():
    goodput_mod.note("checkpoint", 1.0)  # no ledger open: must not raise
    assert goodput_mod.end_epoch(0, wall_s=1.0) is None


# --------------------------------------------------------------- StepTimer


def test_step_timer_single_chunk_summary_well_formed():
    """An epoch with ONE chunk (the scan tiers dispatch once per epoch)
    must produce finite mean/p50/p99 — the 1-sample percentile case."""
    from shifu_tpu.train.profiler import StepTimer

    t = StepTimer()
    t.input_times = [0.25]
    t.step_times = [0.75]
    s = t.summary()
    for k, v in s.items():
        assert v == v and v != float("inf"), (k, v)
    assert s["input_p50_ms"] == s["input_p99_ms"] == s["input_mean_ms"]
    assert s["step_p50_ms"] == pytest.approx(750.0)
    assert s["input_fraction"] == pytest.approx(0.25)
    assert "no steps" not in t.console_line()


def test_step_timer_filters_non_finite_samples():
    from shifu_tpu.train.profiler import StepTimer

    t = StepTimer()
    t.input_times = [float("nan"), 0.1]
    t.step_times = [0.3, float("inf"), 0.1]
    s = t.summary()
    for k, v in s.items():
        assert v == v and v != float("inf"), (k, v)
    assert s["step_total_s"] == pytest.approx(0.4)
    assert s["input_fraction"] == pytest.approx(0.2)
    t.emit()  # histograms must only see the finite samples
    h = obs.default_registry().histogram("train_step_seconds")
    assert h.count() == 2
    assert h.sum() == pytest.approx(0.4)


# ------------------------------------------------- CPU train smoke (gate)


def _train_tiny(tmp_path, monkeypatch, epochs=2, ckpt=False):
    import dataclasses

    from shifu_tpu.config import (DataConfig, JobConfig, ModelSpec,
                                  OptimizerConfig, TrainConfig)
    from shifu_tpu.data import pipeline, reader, synthetic
    from shifu_tpu.train import train

    tele = str(tmp_path / "telemetry")
    monkeypatch.setenv("SHIFU_TPU_METRICS_DIR", tele)
    schema = synthetic.make_schema(num_features=10)
    rows = synthetic.make_rows(512, schema, seed=3, noise=0.3)
    cols = reader.project_columns(rows, schema)
    ds = pipeline.TabularDataset(cols["features"], cols["target"],
                                 cols["weight"])
    job = JobConfig(
        schema=schema, data=DataConfig(batch_size=64),
        model=ModelSpec(model_type="mlp", hidden_nodes=(8,),
                        activations=("relu",), compute_dtype="float32"),
        train=TrainConfig(epochs=epochs,
                          optimizer=OptimizerConfig(name="adam",
                                                    learning_rate=1e-2)))
    if ckpt:
        rt = dataclasses.replace(
            job.runtime, checkpoint=dataclasses.replace(
                job.runtime.checkpoint,
                directory=str(tmp_path / "ckpt")))
        job = job.replace(runtime=rt)
    job = job.validate()
    train(job, train_ds=ds.take(np.arange(448)),
          valid_ds=ds.take(np.arange(448, 512)), console=lambda s: None)
    obs.shutdown()
    return tele


def test_train_smoke_journals_compiles_and_goodput(tmp_path, monkeypatch):
    """THE acceptance criterion: a CPU train run journals >=1 xla_compile
    event and per-epoch goodput events whose bucket seconds sum to within
    5% of the epoch wall."""
    tele = _train_tiny(tmp_path, monkeypatch, epochs=2, ckpt=True)
    recs = obs.read_journal(os.path.join(tele, "journal.jsonl"))
    compiles = [r for r in recs if r["kind"] == "xla_compile"]
    assert len(compiles) >= 1
    assert any(r["fn"] == "device_epoch_step" for r in compiles)
    assert all(r.get("flops") for r in compiles
               if r["fn"] != "export_stablehlo")  # CPU: capture is on

    goodput = [r for r in recs if r["kind"] == "goodput"]
    assert [r["epoch"] for r in goodput] == [0, 1]
    for r in goodput:
        # the record's fields, exactly (the benchmark's readers take
        # `buckets`, `phases` and `wall_s` from it)
        assert set(r) - {"ts", "seq", "kind", "host", "span"} == {
            "epoch", "wall_s", "buckets", "goodput_fraction", "compiles",
            "phases"}, sorted(r)
        total = sum(r["buckets"].values())
        assert abs(total - r["wall_s"]) <= 0.05 * r["wall_s"] + 1e-6, r
        assert 0.0 <= r["goodput_fraction"] <= 1.0
    # epoch 0 paid the compiles; epoch 1 must not have
    assert goodput[0]["buckets"]["compile"] > 0
    assert goodput[0]["compiles"] >= 1
    assert goodput[1]["compiles"] == 0
    # checkpoint bucket: the terminal save lands inside epoch 1's ledger
    assert goodput[-1]["buckets"]["checkpoint"] > 0

    # scrape file carries the ledger gauges/counters
    prom = open(os.path.join(tele, "metrics.prom")).read()
    totals = obs_render.parse_scrape_totals(prom)
    assert totals["goodput_bucket_seconds_total"] > 0
    assert "goodput_fraction" in totals
    assert totals["xla_compiles_total"] >= 1


def test_profile_cli_text_and_json_roundtrip(tmp_path, monkeypatch, capsys):
    """`shifu-tpu profile <job_dir>` renders the bucket table + compiled
    functions; `--json` round-trips against profile_summary (the golden
    machine contract)."""
    from shifu_tpu.launcher import cli

    _train_tiny(tmp_path, monkeypatch, epochs=2)
    capsys.readouterr()
    assert cli.main(["profile", str(tmp_path)]) == 0
    text = capsys.readouterr().out
    for col in ("epoch", "compile", "input", "step", "goodput"):
        assert col in text, col
    assert "compiled functions (by cost):" in text
    assert "device_epoch_step" in text and "eval_step" in text

    assert cli.main(["profile", str(tmp_path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc == obs_render.profile_summary(str(tmp_path))
    assert [e["epoch"] for e in doc["epochs"]] == [0, 1]
    assert set(doc["epochs"][0]["buckets"]) == set(goodput_mod.BUCKETS)
    assert doc["compiled_functions"]["device_epoch_step"]["compiles"] == 1
    assert doc["goodput_fraction_mean"] is not None
    # epoch bucket totals aggregate across epochs
    assert doc["bucket_totals_s"]["step"] > 0

    # missing dir: clean failure, no traceback
    assert cli.main(["profile", str(tmp_path / "nope")]) == 1
    assert "no telemetry journal" in capsys.readouterr().err


def test_status_quick_summary_carries_goodput(tmp_path, monkeypatch):
    from shifu_tpu.launcher import detach

    _train_tiny(tmp_path, monkeypatch, epochs=1)
    tele = detach._telemetry_quick_summary(
        str(tmp_path / "telemetry" / "journal.jsonl"))
    assert tele["goodput"]["epoch"] == 0
    assert 0.0 <= tele["goodput"]["goodput_fraction"] <= 1.0
    assert set(tele["goodput"]) == {"epoch", "goodput_fraction"}
