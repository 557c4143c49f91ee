"""Model-quality & data-drift observatory tests (obs/sketch.py,
obs/drift.py, the serving-side hooks — ISSUE 18).

Covers: the mergeable sketch substrate (int8 wire bytes bin identically
to the floats they encode, merge == single pass, profile round-trip),
the StreamingMetrics merge/state contract the windowed live-AUC leans
on, the DriftEngine's fire-once/latch/resolve discipline on injected
timestamps (feature PSI and score KL objectives, idle unlatch), the
quiet-traffic contract (healthy load fires ZERO drift alerts), the
overhead guard (drift disabled -> zero drift events and p50 within
5% + 1ms; enabled path is one bincount per batch), the fleet-verify
baseline-digest audit, and the end-to-end drill: train -> export
(artifact carries baseline_profile.json) -> serve -> loadtest with
--drift-after shifting two features -> exactly ONE firing drift_alert
naming them, auc_decay journaled from the feedback path, and
`shifu-tpu drift --json` + `top --once --json` rendering it all in a
jax-masked subprocess."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from shifu_tpu import chaos, obs
from shifu_tpu.config.schema import ConfigError, DriftConfig, ServingConfig
from shifu_tpu.obs import drift as drift_mod
from shifu_tpu.obs import render as render_mod
from shifu_tpu.obs import sketch as sketch_mod
from shifu_tpu.ops.metrics import StreamingMetrics
from shifu_tpu.runtime import loadtest as loadtest_mod
from shifu_tpu.runtime.fleet import fleet_verify_events
from shifu_tpu.runtime.serve import ModelRegistry, ScoringDaemon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos_and_obs():
    chaos.reset_for_tests()
    obs.reset_for_tests()
    obs.default_registry().clear()
    yield
    chaos.reset_for_tests()
    obs.reset_for_tests()
    obs.default_registry().clear()


# ------------------------------------------------------- sketch substrate


def test_feature_sketch_int8_matches_float():
    """int8 wire bytes bin to the SAME histogram as the floats they
    encode — the no-dequant serving path is exact, not approximate."""
    rng = np.random.default_rng(0)
    f = 6
    scale, offset = sketch_mod.default_grid(f)
    x = rng.standard_normal((500, f)).astype(np.float32) * 2.0
    q = np.clip(np.rint((x - offset) / scale), -127, 127).astype(np.int8)

    sk_f = sketch_mod.FeatureSketch(f)
    sk_f.update(x)
    sk_i = sketch_mod.FeatureSketch(f)
    sk_i.update(q)
    assert np.array_equal(sk_f.hist, sk_i.hist)
    assert sk_f.rows == sk_i.rows == 500
    # moments off the grid track the raw data within grid resolution
    mean, var = sk_f.moments()
    assert np.allclose(mean, x.mean(axis=0), atol=float(scale[0]))
    assert np.allclose(np.sqrt(var), x.std(axis=0), atol=2 * float(scale[0]))


def test_sketch_merge_equals_single_pass():
    rng = np.random.default_rng(1)
    a = rng.standard_normal((300, 4)).astype(np.float32)
    b = rng.standard_normal((200, 4)).astype(np.float32) + 1.0

    one = sketch_mod.FeatureSketch(4)
    one.update(a)
    one.update(b)
    sa = sketch_mod.FeatureSketch(4)
    sa.update(a)
    sb = sketch_mod.FeatureSketch(4)
    sb.update(b)
    sa.merge(sb)
    assert np.array_equal(one.hist, sa.hist)
    assert one.rows == sa.rows == 500
    m1, v1 = one.moments()
    m2, v2 = sa.moments()
    assert np.allclose(m1, m2) and np.allclose(v1, v2)

    ss_one = sketch_mod.ScoreSketch()
    ss_one.update(rng.random(300))
    snap = ss_one.to_dict()
    ss_a = sketch_mod.ScoreSketch.from_dict(snap)
    ss_b = sketch_mod.ScoreSketch()
    more = rng.random(100)
    ss_one.update(more)
    ss_b.update(more)
    ss_a.merge(ss_b)
    assert np.array_equal(ss_one.hist, ss_a.hist)
    assert ss_a.n == ss_one.n == 400
    assert ss_a.mean() == pytest.approx(ss_one.mean())

    with pytest.raises(ValueError):
        sa.merge(sketch_mod.FeatureSketch(5))
    with pytest.raises(ValueError):
        ss_a.merge(sketch_mod.ScoreSketch(bins=32))


def test_psi_math_and_profile_roundtrip():
    rng = np.random.default_rng(2)
    base = sketch_mod.FeatureSketch(3)
    base.update(rng.standard_normal((4000, 3)).astype(np.float32))
    same = sketch_mod.FeatureSketch(3)
    same.update(rng.standard_normal((4000, 3)).astype(np.float32))
    shifted = sketch_mod.FeatureSketch(3)
    x = rng.standard_normal((4000, 3)).astype(np.float32)
    x[:, 1] += 2.5
    shifted.update(x)

    p_same = sketch_mod.psi(base.hist, same.hist)
    p_shift = sketch_mod.psi(base.hist, shifted.hist)
    assert p_same.shape == (3,) and p_shift.shape == (3,)
    assert float(p_same.max()) < 0.1           # "stable" reading
    assert float(p_shift[1]) > 0.25            # "significant" reading
    assert float(p_shift[0]) < 0.1 and float(p_shift[2]) < 0.1
    # KL of a distribution against itself is ~0; against a shift, not
    ss = sketch_mod.ScoreSketch()
    ss.update(rng.random(2000))
    ss2 = sketch_mod.ScoreSketch()
    ss2.update(rng.random(2000) * 0.3)
    assert sketch_mod.kl_divergence(ss.hist, ss.hist) < 1e-6
    assert sketch_mod.kl_divergence(ss.hist, ss2.hist) > 0.1

    prof = sketch_mod.build_profile(base, ss,
                                    feature_names=["a", "b", "c"],
                                    train_auc=0.91, train_error=0.1,
                                    epoch=2)
    blob = json.loads(json.dumps(prof))     # must survive JSON exactly
    f2, s2 = sketch_mod.profile_sketches(blob)
    assert np.array_equal(f2.hist, base.hist)
    assert np.array_equal(s2.hist, ss.hist)
    assert drift_mod.feature_names(blob) == ["a", "b", "c"]
    assert blob["train_auc"] == 0.91 and blob["epoch"] == 2
    with pytest.raises(ValueError):
        sketch_mod.validate_profile({"kind": "something_else"})
    with pytest.raises(ValueError):
        sketch_mod.validate_profile(
            {"kind": sketch_mod.PROFILE_KIND,
             "version": sketch_mod.PROFILE_VERSION + 1,
             "features": {}, "score": {}})


def test_streaming_metrics_merge_matches_single_pass():
    """The satellite contract: merge(a, b) == one pass over the
    concatenated chunks, and state_dict round-trips exactly."""
    rng = np.random.default_rng(3)
    s1, s2 = rng.random(5000), rng.random(3000)
    l1 = (rng.random(5000) < s1).astype(np.float64)
    l2 = (rng.random(3000) < 0.5).astype(np.float64)
    w1 = rng.random(5000)
    w2 = np.ones(3000)

    single = StreamingMetrics(bins=1 << 12)
    single.update(np.concatenate([s1, s2]), np.concatenate([l1, l2]),
                  np.concatenate([w1, w2]))
    a = StreamingMetrics(bins=1 << 12)
    a.update(s1, l1, w1)
    b = StreamingMetrics(bins=1 << 12)
    b.update(s2, l2, w2)
    a.merge(b)
    assert a.rows == single.rows == 8000
    assert a.auc() == pytest.approx(single.auc(), abs=1e-12)
    assert a.weighted_error() == pytest.approx(single.weighted_error(),
                                               rel=1e-12)
    # serializable state: exact round-trip
    back = StreamingMetrics.from_state(
        json.loads(json.dumps(a.state_dict())))
    assert back.rows == a.rows
    assert back.auc() == pytest.approx(a.auc(), abs=1e-12)
    assert back.weighted_error() == pytest.approx(a.weighted_error())
    with pytest.raises(ValueError):
        a.merge(StreamingMetrics(bins=1 << 10))


# ---------------------------------------------- engine alert discipline


def _mk_profile(num_features=4, rows=6000, seed=5, train_auc=0.9):
    rng = np.random.default_rng(seed)
    fs = sketch_mod.FeatureSketch(num_features)
    fs.update(rng.standard_normal((rows, num_features)).astype(np.float32))
    ss = sketch_mod.ScoreSketch()
    ss.update(rng.random(rows))
    return sketch_mod.build_profile(
        fs, ss, feature_names=[f"c{j}" for j in range(num_features)],
        train_auc=train_auc)


def _mk_engine(profile=None, **cfg_kw):
    profile = profile or _mk_profile()
    base = dict(fast_window_s=10.0, slow_window_s=30.0, min_rows=50,
                psi_threshold=0.2, score_kl_threshold=0.0)
    base.update(cfg_kw)
    mon = drift_mod.DriftMonitor(profile, model_id="m", version=1,
                                 digest="d0")
    return drift_mod.DriftEngine(mon, DriftConfig(**base))


def test_drift_engine_fires_once_latches_and_resolves():
    eng = _mk_engine()
    rng = np.random.default_rng(6)

    def healthy(n=400):
        x = rng.standard_normal((n, 4)).astype(np.float32)
        eng.monitor.observe_batch(x, rng.random(n))

    def shifted(n=400):
        x = rng.standard_normal((n, 4)).astype(np.float32)
        x[:, 1] += 3.0
        x[:, 3] += 3.0
        eng.monitor.observe_batch(x, rng.random(n))

    fired, resolved = [], []

    def run(t):
        alerts, _rep = eng.tick(t)
        for a in alerts:
            (fired if a["state"] == "firing" else resolved).append(a)

    run(0.0)
    for t in (5.0, 10.0, 15.0, 20.0):
        healthy()
        run(t)
    assert not fired and not resolved

    # shift two features: exactly ONE firing once BOTH windows violate
    for t in (25.0, 30.0, 35.0, 40.0, 45.0, 50.0, 55.0, 60.0):
        shifted()
        run(t)
    assert len(fired) == 1, fired
    ev = fired[0]
    assert ev["objective"] == drift_mod.OBJ_FEATURE_PSI
    assert {f["feature"] for f in ev["features"]} == {"c1", "c3"}
    assert all(f["psi_fast"] >= 0.2 and f["psi_slow"] >= 0.2
               for f in ev["features"])
    assert not resolved

    # back to healthy: one resolved once the FAST window is clean again
    for t in (65.0, 70.0, 75.0, 80.0, 85.0):
        healthy()
        run(t)
    assert len(fired) == 1
    assert len(resolved) == 1
    assert resolved[0]["objective"] == drift_mod.OBJ_FEATURE_PSI

    # report carries the per-feature table + the alert bookkeeping
    rep = eng.report(eng.monitor.window(85.0, 10.0),
                     eng.monitor.window(85.0, 30.0))
    assert rep["model"] == "m" and rep["baseline_digest"] == "d0"
    assert rep["worst"] and {"feature", "psi_fast", "psi_slow"} <= set(
        rep["worst"][0])
    assert rep["firing"] == []
    assert eng.alerts_fired == 1


def test_drift_engine_score_kl_objective_and_auc_decay():
    eng = _mk_engine(psi_threshold=0.0, score_kl_threshold=0.1)
    rng = np.random.default_rng(7)

    fired = []
    run = lambda t: fired.extend(
        a for a in eng.tick(t)[0] if a["state"] == "firing")

    run(0.0)
    for t in (5.0, 10.0, 15.0):
        x = rng.standard_normal((2000, 4)).astype(np.float32)
        eng.monitor.observe_batch(x, rng.random(2000))
        run(t)
    assert not fired

    # the model's OUTPUT collapses toward 0 while inputs stay healthy —
    # score KL is the objective that catches it; feedback feeds auc_live
    for t in (20.0, 25.0, 30.0, 35.0, 40.0, 45.0, 50.0):
        x = rng.standard_normal((2000, 4)).astype(np.float32)
        s = rng.random(2000) * 0.2
        eng.monitor.observe_batch(x, s)
        labels = (rng.random(2000) < 0.5).astype(np.float64)
        eng.monitor.observe_feedback(s, labels)
        run(t)
    assert len(fired) == 1
    assert fired[0]["objective"] == drift_mod.OBJ_SCORE_KL
    assert fired[0]["score_kl_fast"] >= 0.1
    rep = eng.report(eng.monitor.window(50.0, 10.0),
                     eng.monitor.window(50.0, 30.0))
    # coin-flip labels on a 0.9-AUC baseline: live ~0.5, decay ~0.4
    assert rep["auc_live"] is not None and 0.3 < rep["auc_live"] < 0.7
    assert rep["auc_decay"] == pytest.approx(0.9 - rep["auc_live"],
                                             abs=1e-6)
    assert rep["feedback_rows_fast"] > 0


def test_drift_engine_idle_unlatch():
    """A latched alert must not outlive the traffic that caused it:
    when the fast window drops below min_rows, it resolves."""
    eng = _mk_engine()
    rng = np.random.default_rng(8)
    out = []
    for t in (0.0, 5.0, 10.0, 15.0, 20.0, 25.0, 30.0, 35.0, 40.0):
        x = rng.standard_normal((400, 4)).astype(np.float32)
        x[:, 0] += 3.0
        eng.monitor.observe_batch(x, rng.random(400))
        out.extend(eng.tick(t)[0])
    assert [a["state"] for a in out] == ["firing"]
    # traffic stops; ticks keep coming
    for t in (45.0, 50.0, 55.0, 60.0):
        out.extend(eng.tick(t)[0])
    states = [a["state"] for a in out]
    assert states == ["firing", "resolved"]
    assert "min_rows" in out[-1]["note"]


def test_drift_config_validation_and_xml_keys():
    with pytest.raises(ConfigError):
        DriftConfig(fast_window_s=10.0, slow_window_s=5.0).validate()
    with pytest.raises(ConfigError):
        DriftConfig(psi_threshold=-1.0).validate()
    with pytest.raises(ConfigError):
        DriftConfig(min_rows=0).validate()
    from shifu_tpu.utils import xmlconfig
    cfg = xmlconfig.drift_config_from_conf({
        "shifu.drift.enabled": "true",
        "shifu.drift.fast-window-s": "15",
        "shifu.drift.slow-window-s": "90",
        "shifu.drift.psi-threshold": "0.3",
        "shifu.drift.score-kl-threshold": "0",
        "shifu.drift.top-k": "3",
        "shifu.drift.min-rows": "64",
        "shifu.drift.feedback": "false",
    })
    assert cfg.fast_window_s == 15.0 and cfg.slow_window_s == 90.0
    assert cfg.psi_threshold == 0.3 and cfg.score_kl_threshold == 0.0
    assert cfg.top_k == 3 and cfg.min_rows == 64
    assert cfg.enabled is True and cfg.feedback is False
    # and the serving layer threads it through
    sv = xmlconfig.serving_config_from_conf(
        {"shifu.drift.psi-threshold": "0.4"})
    assert sv.drift.psi_threshold == 0.4


# ------------------------------------------------ daemon-level contracts


class StubScorer:
    engine = "stub"
    static_shapes = False
    num_features = 4

    def compute_batch(self, rows, n_valid=None):
        x = np.asarray(rows, np.float32)
        # a bounded, feature-dependent "score" so the score sketch and
        # the feedback path see a real distribution
        return np.ascontiguousarray(
            1.0 / (1.0 + np.exp(-x[:, :1])))


def _stub_daemon(**cfg_kw) -> ScoringDaemon:
    registry = ModelRegistry(loader=lambda _d, _e: StubScorer())
    registry.load("stub://", model_id="default")
    base = dict(engine="numpy", report_every_s=0.0,
                latency_budget_ms=1.0)
    drift = cfg_kw.pop("drift", None)
    base.update(cfg_kw)
    if drift is not None:
        base["drift"] = drift
    return ScoringDaemon(registry=registry, config=ServingConfig(**base))


def test_quiet_traffic_fires_zero_drift_alerts(tmp_path):
    """Healthy load vs a matching baseline: drift_reports flow, ZERO
    drift_alert events — the observatory must not page on noise."""
    obs.configure(str(tmp_path / "tele"))
    d = _stub_daemon(drift=DriftConfig(
        fast_window_s=0.4, slow_window_s=0.8, min_rows=300,
        psi_threshold=0.2, score_kl_threshold=0.1)).start()
    # the baseline's score sketch must match what the stub emits
    rng = np.random.default_rng(11)
    base_fs = sketch_mod.FeatureSketch(4)
    x_base = rng.standard_normal((6000, 4)).astype(np.float32)
    base_fs.update(x_base)
    base_ss = sketch_mod.ScoreSketch()
    base_ss.update(1.0 / (1.0 + np.exp(-x_base[:, 0])))
    prof = sketch_mod.build_profile(
        base_fs, base_ss, feature_names=["c0", "c1", "c2", "c3"],
        train_auc=0.9)
    eng = d.set_drift_baseline(prof, digest="abc")
    assert eng is not None
    t_end = time.time() + 1.6
    while time.time() < t_end:
        d.score_batch(rng.standard_normal((256, 4)).astype(np.float32))
        time.sleep(0.02)
    time.sleep(0.5)
    stats = d.stats()
    d.stop()
    obs.flush()
    events = obs.read_journal(str(tmp_path / "tele" / "journal.jsonl"))
    kinds = [e["kind"] for e in events]
    assert "drift_alert" not in kinds
    assert "drift_report" in kinds
    rep = [e for e in events if e["kind"] == "drift_report"][-1]
    assert rep["worst_psi"] is not None and rep["worst_psi"] < 0.2
    assert rep["firing"] == []
    # the operator snapshot face
    dr = stats["drift"]
    assert dr["baseline_digest"] == "abc" and dr["firing"] == []
    assert dr["rows"] > 0


def test_drift_disabled_zero_events_and_overhead(tmp_path):
    """The overhead guard: kill switch off -> NO drift events of any
    kind, and loadtest p50 within 5% + 1ms of the enabled build; the
    enabled hot path is one flattened bincount per batch."""
    obs.configure(str(tmp_path / "off"))
    d_off = _stub_daemon(drift=DriftConfig(enabled=False)).start()
    assert d_off.set_drift_baseline(_mk_profile()) is None
    rep_off = loadtest_mod.run_loadtest(daemon=d_off, rate=1500.0,
                                        duration=1.0, senders=1)
    d_off.stop()
    obs.flush()
    events = obs.read_journal(str(tmp_path / "off" / "journal.jsonl"))
    assert not [e for e in events if e["kind"].startswith("drift")]
    with pytest.raises(ValueError):
        d_off.feedback([0.5], [1.0])

    obs.reset_for_tests()
    obs.default_registry().clear()
    obs.configure(str(tmp_path / "on"))
    d_on = _stub_daemon(drift=DriftConfig(
        fast_window_s=0.4, slow_window_s=0.8, min_rows=50,
        psi_threshold=0.2, score_kl_threshold=0.0)).start()
    assert d_on.set_drift_baseline(_mk_profile(num_features=4,
                                               seed=11)) is not None
    rep_on = loadtest_mod.run_loadtest(daemon=d_on, rate=1500.0,
                                       duration=1.0, senders=1)
    d_on.stop()
    assert rep_on["p50_ms"] <= rep_off["p50_ms"] * 1.05 + 1.0, (
        f"drift accounting moved p50: {rep_off['p50_ms']}ms -> "
        f"{rep_on['p50_ms']}ms")

    # enabled-path cost: one bincount per batch, vectorized — a
    # max_batch-sized observe is bounded even on a 1-core CI host
    mon = drift_mod.DriftMonitor(_mk_profile(num_features=30, seed=12))
    big = np.random.default_rng(0).standard_normal(
        (4096, 30)).astype(np.float32)
    scores = np.random.default_rng(0).random(4096)
    mon.observe_batch(big, scores)  # warm
    t0 = time.perf_counter()
    for _ in range(10):
        mon.observe_batch(big, scores)
    per_batch = (time.perf_counter() - t0) / 10
    assert per_batch < 0.02, f"observe_batch cost {per_batch * 1e3}ms"
    assert mon.totals()["rows"] == 4096 * 11


# ------------------------------------------------- fleet baseline audit


def _ev(kind, **kw):
    kw["kind"] = kind
    return kw


def test_fleet_verify_baseline_digest_consistency():
    consistent = [
        _ev("fleet_member_swap", member="m0", generation=1, via="fanout",
            baseline_digest="aaa"),
        _ev("fleet_member_swap", member="m1", generation=1, via="fanout",
            baseline_digest="aaa"),
        _ev("fleet_member_swap", member="m2", generation=1, via="fanout",
            baseline_digest=None),  # no profile served: excused
        _ev("fleet_swap", generation=1, swapped=["m0", "m1", "m2"],
            failed=[]),
    ]
    r = fleet_verify_events(consistent)
    check = [c for c in r["checks"]
             if c["check"] == "baseline_profile_consistent"][0]
    assert check["ok"], check
    assert r["verdict"] == "PASS"

    split = [
        _ev("fleet_member_swap", member="m0", generation=1, via="fanout",
            baseline_digest="aaa"),
        _ev("fleet_member_swap", member="m1", generation=1, via="fanout",
            baseline_digest="bbb"),
        _ev("fleet_swap", generation=1, swapped=["m0", "m1"], failed=[]),
    ]
    r = fleet_verify_events(split)
    check = [c for c in r["checks"]
             if c["check"] == "baseline_profile_consistent"][0]
    assert not check["ok"]
    assert "gen1" in check["detail"]
    assert r["verdict"] == "FAIL"


# ------------------------------------------------------- the e2e drill


@pytest.fixture(scope="module")
def drill_artifact(tmp_path_factory):
    """Train a small model and export it WITH the frozen baseline — the
    front half of the acceptance drill (train -> export)."""
    from shifu_tpu.config import (DataConfig, JobConfig, ModelSpec,
                                  OptimizerConfig, TrainConfig)
    from shifu_tpu.data import pipeline, reader, synthetic
    from shifu_tpu.export import save_artifact
    from shifu_tpu.train import train

    schema = synthetic.make_schema(num_features=12)
    job = JobConfig(
        schema=schema,
        data=DataConfig(batch_size=64, valid_ratio=0.1),
        model=ModelSpec(model_type="mlp", hidden_nodes=(8,),
                        activations=("tanh",), compute_dtype="float32"),
        train=TrainConfig(epochs=2, optimizer=OptimizerConfig(
            name="adam", learning_rate=3e-3)),
    ).validate()
    rows = synthetic.make_rows(2048, schema, seed=9, noise=0.3)
    cols = reader.project_columns(rows, schema)
    full = pipeline.TabularDataset(cols["features"], cols["target"],
                                   cols["weight"])
    split = int(full.num_rows * 0.9)
    result = train(job, full.take(np.arange(split)),
                   full.take(np.arange(split, full.num_rows)),
                   console=lambda s: None)
    assert result.baseline_profile is not None
    export_dir = str(tmp_path_factory.mktemp("drill") / "model")
    save_artifact(result.state.params, job, export_dir,
                  baseline_profile=result.baseline_profile)
    return export_dir


def test_export_freezes_baseline_profile(drill_artifact):
    """The artifact carries baseline_profile.json, it validates, and
    its digest rides the sync manifest for fleet-verify."""
    path = os.path.join(drill_artifact, drift_mod.BASELINE_FILE)
    assert os.path.isfile(path)
    loaded = drift_mod.load_baseline(drill_artifact)
    assert loaded is not None
    profile, digest = loaded
    assert profile["num_features"] == 12
    assert profile["rows"] > 0
    assert "train_auc" in profile
    assert digest == drift_mod.baseline_digest(path)
    from shifu_tpu.runtime.fleet import read_sync_manifest
    manifest = read_sync_manifest(drill_artifact)
    assert manifest is not None
    assert drift_mod.BASELINE_FILE in manifest["files"]


def test_e2e_drift_drill(drill_artifact, tmp_path):
    """The acceptance drill, back half: serve the trained artifact,
    loadtest with --drift-after shifting two features, and get exactly
    ONE firing drift_alert naming them (un-shifted features stay below
    threshold), auc_decay journaled from the feedback path — then
    `shifu-tpu drift --json` and `top --once --json` render it in a
    subprocess with jax MASKED."""
    tele = tmp_path / "tele"
    obs.configure(str(tele))
    cfg = ServingConfig(
        engine="numpy", report_every_s=0.3, latency_budget_ms=1.0,
        drift=DriftConfig(fast_window_s=0.5, slow_window_s=1.0,
                          min_rows=300, psi_threshold=0.2,
                          # the drill shifts INPUTS; a score-KL alert
                          # would break the exactly-ONE contract
                          score_kl_threshold=100.0))
    d = ScoringDaemon(drill_artifact, config=cfg).start()
    try:
        assert d.drift_baseline_digest() is not None
        report = loadtest_mod.run_loadtest(
            daemon=d, rate=1200.0, duration=3.0, senders=2, seed=4,
            drift_after=1.2, drift_shift=2.5, drift_features=(2, 7),
            feedback=True)
        # let the engine tick over the post-run window (feedback lands
        # after the drain; a report fires on the fast-window cadence)
        time.sleep(1.2)
    finally:
        d.stop()
    obs.flush()

    # the drill is self-describing in its own report
    assert report["drift_after_s"] == 1.2
    assert report["drift_features"] == [2, 7]
    assert report["feedback_rows"] > 0

    events = obs.read_journal(str(tele / "journal.jsonl"))
    profile, _ = drift_mod.load_baseline(drill_artifact)
    names = drift_mod.feature_names(profile)
    expected = {names[2], names[7]}

    firing = [e for e in events if e["kind"] == "drift_alert"
              and e["state"] == "firing"]
    assert len(firing) == 1, firing
    alert = firing[0]
    assert alert["objective"] == drift_mod.OBJ_FEATURE_PSI
    # fire-once latches on the FIRST over-threshold tick; if that tick's
    # fast window still mixes pre- and post-shift rows, only one of the
    # two shifted features may have crossed yet — the alert must name a
    # non-empty subset of them and never a false feature
    named = {f["feature"] for f in alert["features"]}
    assert named and named <= expected, (named, expected)
    assert all(f["psi_fast"] >= 0.2 for f in alert["features"])

    # un-shifted features stay below threshold in the reports, and both
    # shifted features go hot in at least one report
    reports = [e for e in events if e["kind"] == "drift_report"]
    assert reports
    seen_hot = set()
    for rep in reports:
        for w in rep["worst"]:
            if w["feature"] not in expected:
                assert w["psi_fast"] < 0.2, w
            elif w["psi_fast"] is not None and w["psi_fast"] >= 0.2:
                seen_hot.add(w["feature"])
    assert seen_hot == expected, (seen_hot, expected)
    # auc_decay journaled from the feedback path
    decayed = [r for r in reports if r.get("auc_decay") is not None]
    assert decayed, "no drift_report carried auc_decay"
    assert decayed[-1]["auc_live"] is not None
    assert decayed[-1]["train_auc"] == profile["train_auc"]

    # jax-masked subprocess: drift --json AND top --once --json
    mask = ("import sys, json\n"
            "sys.modules['jax'] = None\n"
            "from shifu_tpu.launcher.cli import main\n")
    out = subprocess.run(
        [sys.executable, "-c", mask +
         f"sys.exit(main(['drift', {str(tele)!r}, '--json']))"],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert out.returncode == 0, out.stderr
    summary = json.loads(out.stdout)
    assert summary["models"], summary
    model = next(iter(summary["models"].values()))
    assert model["report"]["worst_psi"] >= 0.2
    assert {a["objective"] for a in model["firing"]} <= {
        drift_mod.OBJ_FEATURE_PSI}
    assert model["alerts_total"] >= 1

    out = subprocess.run(
        [sys.executable, "-c", mask +
         f"sys.exit(main(['top', {str(tele)!r}, '--once', '--json']))"],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert out.returncode == 0, out.stderr
    top = json.loads(out.stdout)
    assert top["drift"]["worst"] is not None
    assert top["drift"]["worst"] >= 0.2

    # the human rendering names the drifted features too
    text = render_mod.render_drift_text(
        render_mod.drift_summary(str(tele)))
    for name in expected:
        assert name in text
