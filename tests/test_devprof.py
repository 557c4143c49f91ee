"""Device flight recorder (ISSUE 6): Chrome-trace parsing self-time
arithmetic, the trace-epoch schedule grammar, the anomaly detector's
quiet/spike contract, the CPU trace-capture train smoke the acceptance
criteria pin (>=1 `device_profile` with a non-empty kernel rollup whose
fractions sum to <= 1, >=1 `hbm_watermark`), the chaos `obs.trace`
fallback, and `shifu-tpu trace` rendering.
"""

import json
import os

import numpy as np
import pytest

from shifu_tpu import chaos, obs
from shifu_tpu.config import ObsConfig
from shifu_tpu.config.schema import ConfigError
from shifu_tpu.obs import devprof, render as obs_render, tracefmt


@pytest.fixture(autouse=True)
def _reset_obs():
    obs.reset_for_tests()
    chaos.reset_for_tests()
    yield
    obs.reset_for_tests()
    chaos.reset_for_tests()


# ---------------------------------------------------------------- tracefmt


def _trace_doc(events):
    return {"traceEvents": events}


def _dev(name, ts, dur, module="jit_step", pid=1, tid=7):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
            "name": name, "args": {"hlo_op": name, "hlo_module": module}}


def test_kernel_rollup_self_time_never_double_counts():
    """A scan's `while` spans its inner kernels on the SAME lane (the CPU
    backend emits the nest) — per-kernel times must be SELF times, so the
    rollup sums to the busy window, not 2x it."""
    events = [
        _dev("while.1", 0.0, 100.0),       # parent spanning 0..100
        _dev("dot.1", 10.0, 60.0),         # child
        _dev("fusion.1", 75.0, 20.0),      # child
        _dev("copy.1", 120.0, 30.0),       # a sibling root after the while
        {"ph": "X", "pid": 1, "tid": 9, "ts": 0, "dur": 999,
         "name": "host_python_stuff"},     # no hlo_op: not a device event
    ]
    r = tracefmt.kernel_rollup(events)
    by = {k["name"]: k for k in r["kernels"]}
    assert by["while.1"]["device_us"] == pytest.approx(20.0)  # 100-60-20
    assert by["dot.1"]["device_us"] == pytest.approx(60.0)
    assert by["copy.1"]["device_us"] == pytest.approx(30.0)
    assert r["device_us_total"] == pytest.approx(130.0)
    assert r["window_us"] == pytest.approx(150.0)
    assert r["lanes"] == 1
    frac_sum = sum(k["fraction"] for k in r["kernels"])
    assert frac_sum <= 1.0 + 1e-6
    assert r["device_fraction"] == pytest.approx(130.0 / 150.0, rel=1e-4)


def test_kernel_rollup_top_k_folds_tail_and_multi_lane():
    events = [_dev(f"op.{i}", 10.0 * i, 5.0) for i in range(10)]
    events += [_dev("big", 0.0, 50.0, pid=2, tid=1)]  # second device lane
    r = tracefmt.kernel_rollup(events, top_k=3)
    assert len(r["kernels"]) == 3
    assert r["kernels"][0]["name"] == "big"
    assert r["kernel_count"] == 11
    assert r["other_us"] == pytest.approx(5.0 * 8)
    assert r["lanes"] == 2
    # fractions divide across lanes: sum over ALL kernels <= 1
    assert r["device_fraction"] <= 1.0 + 1e-6
    # per-module totals cover ALL kernels, including the folded tail —
    # the roofline denominators must not shrink with top_k
    assert r["modules"]["jit_step"] == pytest.approx(10 * 5.0 + 50.0)


def test_kernel_rollup_empty_and_dir_roundtrip(tmp_path):
    assert tracefmt.kernel_rollup([]) is None
    assert tracefmt.kernel_rollup([{"ph": "M", "name": "process_name"}]) \
        is None
    # a dir round-trip through the gzip spelling jax.profiler uses
    import gzip
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    with gzip.open(run / "host.trace.json.gz", "wb") as f:
        f.write(json.dumps(_trace_doc([_dev("dot.9", 0.0, 4.0)])).encode())
    r = tracefmt.rollup_trace_dir(str(tmp_path))
    assert r and r["kernels"][0]["name"] == "dot.9"
    assert tracefmt.rollup_trace_dir(str(tmp_path / "nope")) is None


# ----------------------------------------------------- schedule + config


def test_parse_trace_epochs_grammar():
    off = devprof.parse_trace_epochs("off")
    assert not off(0, 0) and not off(1, 0)
    first = devprof.parse_trace_epochs("first")
    assert first(3, 3) and not first(4, 3)  # the first TRAINED epoch
    lst = devprof.parse_trace_epochs("0, 2")
    assert lst(0, 0) and lst(2, 0) and not lst(1, 0)
    ev = devprof.parse_trace_epochs("every:2")
    assert ev(0, 0) and not ev(1, 0) and ev(2, 0)
    with pytest.raises(ValueError):
        devprof.parse_trace_epochs("every:0")
    with pytest.raises(ValueError):
        devprof.parse_trace_epochs("sometimes")


def test_obs_config_validates():
    ObsConfig().validate()
    ObsConfig(trace_epochs="every:5").validate()
    with pytest.raises(ConfigError):
        ObsConfig(trace_epochs="bogus").validate()
    with pytest.raises(ConfigError):
        ObsConfig(anomaly_window=2).validate()
    with pytest.raises(ConfigError):
        ObsConfig(anomaly_zscore=0.0).validate()
    with pytest.raises(ConfigError):
        ObsConfig(trace_top_k=0).validate()


def test_xml_keys_map_to_obs_config():
    from shifu_tpu.config import JobConfig
    from shifu_tpu.utils import xmlconfig

    job = xmlconfig.apply_to_job(JobConfig(), {
        xmlconfig.KEY_OBS_TRACE_EPOCHS: "first",
        xmlconfig.KEY_OBS_TRACE_DIR: "/tmp/tr",
        xmlconfig.KEY_OBS_TRACE_TOP_K: "8",
        xmlconfig.KEY_OBS_HBM_WATERMARKS: "false",
        xmlconfig.KEY_OBS_ANOMALY_WINDOW: "16",
        xmlconfig.KEY_OBS_ANOMALY_ZSCORE: "4.5",
    })
    assert job.obs.trace_epochs == "first"
    assert job.obs.trace_dir == "/tmp/tr"
    assert job.obs.trace_top_k == 8
    assert job.obs.hbm_watermarks is False
    assert job.obs.anomaly_window == 16
    assert job.obs.anomaly_zscore == 4.5
    # untouched configs keep the defaults object
    assert xmlconfig.apply_to_job(JobConfig(), {}).obs == ObsConfig()


# --------------------------------------------------------- flight recorder


def test_flight_recorder_quiet_series_never_fires():
    """Near-constant timings (MAD ~ 0) with scheduler jitter must produce
    ZERO anomalies — the min_ratio guard."""
    fr = devprof.FlightRecorder(window=16, zscore=6.0, min_chunks=8)
    rng = np.random.default_rng(0)
    for i in range(200):
        assert fr.record(0, 0.001, 0.010 + rng.normal(0, 1e-5)) is None
    assert fr.anomalies == 0


def test_flight_recorder_spike_fires_exactly_once():
    """One injected 10x step-time spike in a steady series -> exactly one
    anomaly, carrying the ring; the spike entering the ring must not make
    the following normal chunks anomalous (robust median/MAD)."""
    fr = devprof.FlightRecorder(window=16, zscore=6.0, min_chunks=8)
    verdicts = []
    for i in range(30):
        step = 0.100 if i == 20 else 0.010 + (i % 3) * 1e-4
        v = fr.record(0, 0.002, step)
        if v is not None:
            verdicts.append(v)
    assert len(verdicts) == 1 and fr.anomalies == 1
    v = verdicts[0]
    assert v["chunk"] == 21  # 1-based
    assert v["step_s"] == pytest.approx(0.1)
    assert v["zscore"] > 6.0
    # ring schema: the last K chunks BEFORE the spike, oldest first
    assert len(v["ring"]) == 16
    for r in v["ring"]:
        assert set(r) == {"epoch", "chunk", "input_s", "step_s"}
    assert v["ring"][-1]["chunk"] == 20


def test_flight_recorder_needs_min_chunks():
    fr = devprof.FlightRecorder(window=8, zscore=3.0, min_chunks=8)
    for _ in range(7):
        fr.record(0, 0.0, 0.01)
    assert fr.record(0, 0.0, 10.0) is None  # only 7 prior chunks
    assert fr.anomalies == 0


def test_step_timer_feeds_chunk_hook():
    from shifu_tpu.train.profiler import StepTimer

    seen = []
    t = StepTimer(on_chunk=lambda i, s: seen.append((i, s)))
    t.start()
    t.mark_input_ready()
    t.mark_step_done()
    t.mark_input_ready()
    t.mark_step_done()
    assert len(seen) == 2
    assert seen[0][0] == t.input_times[0]
    assert seen[0][1] == t.step_times[0]
    # a raising hook must not break the timer
    t2 = StepTimer(on_chunk=lambda i, s: 1 / 0)
    t2.start()
    t2.mark_input_ready()
    t2.mark_step_done()
    assert len(t2.step_times) == 1


def test_anomaly_journals_event_and_oneshot_trace(tmp_path):
    """A spike through DeviceProfiler.note_chunk journals ONE `anomaly`
    event and, with tracing enabled, arms a one-shot capture that the
    next chunk closes into a `device_profile` with trigger='anomaly'."""
    import jax.numpy as jnp

    obs.configure(str(tmp_path))
    cfg = ObsConfig(trace_epochs="first", trace_dir=str(tmp_path / "tr"),
                    anomaly_window=8, anomaly_min_chunks=4)
    dp = devprof.DeviceProfiler(cfg)
    assert dp.tracing_enabled
    for _ in range(6):
        dp.note_chunk(0, 0.001, 0.010)
    dp.note_chunk(0, 0.001, 0.500)          # the spike: anomaly + one-shot
    (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    dp.note_chunk(0, 0.001, 0.010)          # closes the one-shot
    dp.end_epoch(0)
    obs.flush()
    recs = obs.read_journal(str(tmp_path / "journal.jsonl"))
    anomalies = [r for r in recs if r["kind"] == "anomaly"]
    assert len(anomalies) == 1
    assert anomalies[0]["ring"]
    shots = [r for r in recs if r["kind"] == "device_profile"
             and r.get("trigger") == "anomaly"]
    assert len(shots) == 1 and shots[0]["kernels"]
    assert obs.default_registry().counter("anomaly_total").total() == 1


def test_fresh_capture_dir_never_merges_stale_runs(tmp_path):
    """A resumed job re-tracing epoch 0 must capture into a FRESH dir:
    rollup_trace_dir walks the whole dir, and merging a previous
    process's run would stretch window_us across the gap between them."""
    cfg = ObsConfig(trace_epochs="first", trace_dir=str(tmp_path))
    dp = devprof.DeviceProfiler(cfg)
    base = os.path.join(str(tmp_path), "epoch00000")
    assert dp._fresh_capture_dir(base) == base
    os.makedirs(base)
    assert dp._fresh_capture_dir(base) == base + "-r1"
    os.makedirs(base + "-r1")
    assert dp._fresh_capture_dir(base) == base + "-r2"


def test_chaos_obs_trace_degrades_to_fallback(tmp_path):
    """An injected `obs.trace` fault must not fail the epoch: the capture
    degrades to a journaled `trace_fallback` and the body still runs."""
    obs.configure(str(tmp_path))
    chaos.configure(chaos.parse_plan(
        {"faults": [{"site": "obs.trace", "every": 1}]}))
    cfg = ObsConfig(trace_epochs="first", trace_dir=str(tmp_path / "tr"))
    dp = devprof.DeviceProfiler(cfg)
    ran = []
    with dp.epoch_capture(0):
        ran.append(True)
    assert ran == [True]
    obs.flush()
    recs = obs.read_journal(str(tmp_path / "journal.jsonl"))
    fb = [r for r in recs if r["kind"] == "trace_fallback"]
    assert len(fb) == 1 and fb[0]["stage"] == "start"
    assert [r for r in recs if r["kind"] == "chaos_inject"]
    assert not [r for r in recs if r["kind"] == "device_profile"]
    reg = obs.default_registry()
    assert reg.counter("trace_fallback_total").total() == 1


# ------------------------------------------------- CPU train smoke (gate)


def _train_traced(tmp_path, monkeypatch, obs_cfg=None, epochs=2):
    import dataclasses  # noqa: F401  (parity with test_introspect helper)

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
    # device_resident_bytes=0 forces the STAGED tier: the traced module
    # is then `jit_epoch_step` wrapping epoch_scan_step — the alias-table
    # match (and multi-chunk ring feed) the resident tier can't exercise
    job = JobConfig(
        schema=schema, data=DataConfig(batch_size=64,
                                       device_resident_bytes=0),
        model=ModelSpec(model_type="mlp", hidden_nodes=(8,),
                        activations=("relu",), compute_dtype="float32"),
        train=TrainConfig(epochs=epochs,
                          optimizer=OptimizerConfig(name="adam",
                                                    learning_rate=1e-2)),
        obs=obs_cfg or ObsConfig(trace_epochs="first")).validate()
    train(job, train_ds=ds.take(np.arange(448)),
          valid_ds=ds.take(np.arange(448, 512)), console=lambda s: None)
    obs.shutdown()
    return tele


def test_train_smoke_journals_device_profile_and_watermarks(
        tmp_path, monkeypatch):
    """THE acceptance criterion: a CPU train run with tracing enabled
    journals >=1 `device_profile` whose per-kernel fractions sum to
    <= 1.0 (+ tolerance) of the traced window, and >=1 `hbm_watermark`."""
    tele = _train_traced(tmp_path, monkeypatch)
    recs = obs.read_journal(os.path.join(tele, "journal.jsonl"))

    profiles = [r for r in recs if r["kind"] == "device_profile"]
    assert len(profiles) >= 1
    p = profiles[0]
    assert p["trigger"] == "schedule" and p["epoch"] == 0
    assert p["kernels"], "kernel rollup must be non-empty"
    fracs = [k["fraction"] for k in p["kernels"]
             if isinstance(k.get("fraction"), (int, float))]
    assert fracs and 0.0 < sum(fracs) <= 1.0 + 0.01
    assert p["window_us"] > 0 and p["device_us_total"] > 0
    # the epoch-scan module joins the introspected cost: intensity rides
    # on its kernels even where platform peaks are unknown (CPU), and
    # the window's dispatch count scales the per-dispatch cost
    joined = [k for k in p["kernels"]
              if k.get("intensity_flops_per_byte")]
    assert joined
    assert all(k.get("window_dispatches", 0) >= 1 for k in joined)
    # pre-truncation per-module totals ride for the rooflines
    assert p.get("modules")
    # epoch 1 is unscheduled ("first"): exactly one scheduled capture
    assert all(r["epoch"] == 0 for r in profiles
               if r.get("trigger") == "schedule")

    wm = [r for r in recs if r["kind"] == "hbm_watermark"]
    assert len(wm) >= 1
    assert [r["epoch"] for r in wm] == list(range(len(wm)))
    for r in wm:
        assert r["source"] in ("memory_stats", "xla_estimate")
        assert r["peak_bytes"] >= 0
    # CPU backend: the xla_estimate fallback must carry the instrumented
    # programs' memory-analysis peak, not silently report 0
    assert wm[-1]["peak_bytes"] > 0

    # no anomalies on a healthy tiny run
    assert not [r for r in recs if r["kind"] == "anomaly"]


def test_watermark_gauges_present(tmp_path, monkeypatch):
    tele = _train_traced(tmp_path, monkeypatch, epochs=1)
    prom = open(os.path.join(tele, "metrics.prom")).read()
    totals = obs_render.parse_scrape_totals(prom)
    assert totals.get("hbm_peak_bytes", 0) > 0
    assert "hbm_bytes_in_use" in totals
    assert totals.get("device_profiles_total", 0) >= 1


def test_trace_cli_text_and_json_roundtrip(tmp_path, monkeypatch, capsys):
    """`shifu-tpu trace <job_dir>` renders the kernel table, watermark,
    and anomaly log; `--json` round-trips against trace_summary."""
    from shifu_tpu.launcher import cli

    _train_traced(tmp_path, monkeypatch)
    capsys.readouterr()
    assert cli.main(["trace", str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert "device profile: epoch 0 trigger=schedule" in text
    assert "kernel" in text and "bound" in text
    assert "hbm: peak" in text

    assert cli.main(["trace", str(tmp_path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc == obs_render.trace_summary(str(tmp_path))
    assert doc["profiles"][0]["kernels"]
    assert doc["hbm_peak_bytes"] > 0

    # profile view carries the device rollup next to goodput
    assert cli.main(["profile", str(tmp_path)]) == 0
    ptext = capsys.readouterr().out
    assert "device:" in ptext and "hbm peak" in ptext

    # missing dir: clean failure, no traceback
    assert cli.main(["trace", str(tmp_path / "nope")]) == 1
    assert "no telemetry journal" in capsys.readouterr().err


def test_trace_off_by_default_still_watermarks(tmp_path, monkeypatch):
    """Default ObsConfig: no trace capture (no profiler overhead), but
    the ring and the HBM watermarks stay on."""
    tele = _train_traced(tmp_path, monkeypatch, obs_cfg=ObsConfig(),
                         epochs=1)
    recs = obs.read_journal(os.path.join(tele, "journal.jsonl"))
    assert not [r for r in recs if r["kind"] == "device_profile"]
    assert [r for r in recs if r["kind"] == "hbm_watermark"]


# --------------------------------------------------------------- roofline


def test_roofline_join_classifies_with_peaks(monkeypatch):
    """With platform peaks pinned, a high-intensity module classifies
    compute-bound and a low-intensity one HBM-bound."""
    monkeypatch.setattr(devprof, "peaks",
                        lambda kind=None: (100.0, 1000.0))
    # balance = 100e12 / 1000e9 = 100 flops/byte
    rollup = {"kernels": [
        {"name": "dot.1", "module": "jit_compute", "device_us": 1000.0,
         "calls": 1},
        {"name": "copy.1", "module": "jit_memory", "device_us": 1000.0,
         "calls": 1},
    ]}
    stats = {"compute": {"flops": 1e12, "bytes_accessed": 1e9},   # 1000 f/B
             "memory": {"flops": 1e9, "bytes_accessed": 1e9}}     # 1 f/B
    devprof.roofline_join(rollup, stats=stats)
    by = {k["name"]: k for k in rollup["kernels"]}
    assert by["dot.1"]["bound"] == "compute"
    assert by["copy.1"]["bound"] == "hbm"
    assert by["dot.1"]["flops_frac"] > by["dot.1"]["hbm_frac"]
    assert rollup["peak_tflops"] == 100.0
    assert rollup["peak_hbm_gbps"] == 1000.0
    # no dispatches given: one dispatch per module assumed
    assert by["dot.1"]["window_dispatches"] == 1
    # 1e12 flops over 1ms at 100 TFLOP/s peak = 10x real-time per
    # dispatch -> frac 10 with one dispatch
    assert by["dot.1"]["flops_frac"] == pytest.approx(10.0)


def test_roofline_join_scales_by_window_dispatches(monkeypatch):
    """cost_analysis FLOPs are PER DISPATCH: a window holding N
    dispatches must multiply by N, or a busy program reads as N-x
    under-utilized (and the module denominator must come from the
    pre-truncation `modules` totals, not just the kept kernels)."""
    monkeypatch.setattr(devprof, "peaks",
                        lambda kind=None: (100.0, 1000.0))
    rollup = {
        "kernels": [{"name": "dot.1", "module": "jit_step",
                     "device_us": 600.0, "calls": 10}],
        # the module really spent 1000us (400 folded into other_us)
        "modules": {"jit_step": 1000.0},
    }
    stats = {"train_step": {"flops": 1e10, "bytes_accessed": 1e9}}
    devprof.roofline_join(rollup, stats=stats,
                          dispatches={"train_step": 10})
    k = rollup["kernels"][0]
    assert k["window_dispatches"] == 10
    # 1e10 flops x 10 dispatches over 1000us (the module total, NOT
    # the kept kernel's 600us) = 100 TFLOP/s -> exactly the peak
    assert k["flops_frac"] == pytest.approx(1.0)
    # bytes: 1e9 x 10 over 1ms = 10 TB/s -> 10x the 1000 GB/s peak
    assert k["hbm_frac"] == pytest.approx(10.0)
    assert k["bound"] == "hbm"
    # a matched module whose fn never dispatched in the window gets
    # no fractions (honest null), intensity still rides
    rollup2 = {"kernels": [{"name": "dot.1", "module": "jit_step",
                            "device_us": 600.0, "calls": 1}],
               "modules": {"jit_step": 600.0}}
    devprof.roofline_join(rollup2, stats=stats,
                          dispatches={"other_fn": 5})
    k2 = rollup2["kernels"][0]
    assert "flops_frac" not in k2 and k2["bound"] is None
    assert k2["intensity_flops_per_byte"] == pytest.approx(10.0)


def test_introspect_counts_dispatches():
    import jax.numpy as jnp

    from shifu_tpu.obs import introspect as introspect_mod

    fn = introspect_mod.instrument_jit(lambda x: x + 1.0, "disp_probe")
    for _ in range(4):
        fn(jnp.ones((4,), jnp.float32))
    assert introspect_mod.dispatch_counts()["disp_probe"] == 4


def test_match_stats_covers_every_step_tier():
    """jit names modules after the INNER fn — all three scan tiers wrap
    one literally named `epoch_step`, so the alias table must route
    `jit_epoch_step` to whichever instrumented tier is live (the CLI's
    staged tier regressed to unmatched before this pin)."""
    stats = {"epoch_scan_step": {"flops": 2.0}, "train_step": {"flops": 1.0}}
    assert devprof._match_stats("jit_epoch_step", stats)[0] \
        == "epoch_scan_step"
    assert devprof._match_stats("jit_step", stats)[0] == "train_step"
    assert devprof._match_stats(
        "jit_epoch_step", {"device_epoch_step": {}})[0] == "device_epoch_step"
    assert devprof._match_stats(
        "jit_epoch_step", {"local_sgd_epoch_step": {}})[0] \
        == "local_sgd_epoch_step"
    assert devprof._match_stats("jit_score", {"eval_step": {}})[0] \
        == "eval_step"
    assert devprof._match_stats("jit__lambda_", stats) is None
    assert devprof._match_stats(None, stats) is None


def test_status_quick_summary_carries_hbm(tmp_path, monkeypatch):
    from shifu_tpu.launcher import detach

    _train_traced(tmp_path, monkeypatch, epochs=1)
    tele = detach._telemetry_quick_summary(
        str(tmp_path / "telemetry" / "journal.jsonl"))
    assert tele["hbm"]["peak_bytes"] > 0
    assert tele["hbm"]["source"] in ("memory_stats", "xla_estimate")
