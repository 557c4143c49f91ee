"""Serving SLO engine + lifecycle tracing tests (obs/slo.py,
runtime/serve.py stage chain, `shifu-tpu top` — ISSUE 8).

Covers: the burn-rate engine's fire-once/latch/resolve contract on
injected timestamps, the stage chain's sum-to-e2e invariant (shared
stamps make a gap or overlap impossible — the test pins it end to end),
the chaos dispatch-slowdown drill (`delay` action at
`runtime.serve.dispatch` drives exactly one `slo_alert` and a one-shot
`device_profile` with trigger="slo"), the quiet-traffic contract (no
alerts, zero sampled traces, bounded always-on overhead), the loadtest
stage decomposition, the multi-daemon rollup, and `shifu-tpu top --once
--json` rendering all of it WITHOUT importing jax (subprocess with jax
masked — the acceptance spelling)."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from shifu_tpu import chaos, obs
from shifu_tpu.chaos import plan as plan_mod
from shifu_tpu.config.schema import ConfigError, ServingConfig
from shifu_tpu.obs import aggregate as aggregate_mod
from shifu_tpu.obs import render as render_mod
from shifu_tpu.obs import slo as slo_mod
from shifu_tpu.obs.slo import STAGES, SloEngine, SloObjectives
from shifu_tpu.runtime import loadtest as loadtest_mod
from shifu_tpu.runtime.serve import ModelRegistry, ScoringDaemon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the serving latency bucket table (index 4 = 1ms, index 9 = 25ms)
from shifu_tpu.export.scorer import SCORE_LATENCY_BUCKETS  # noqa: E402

N_BUCKETS = len(SCORE_LATENCY_BUCKETS) + 1


@pytest.fixture(autouse=True)
def _clean_chaos_and_obs():
    chaos.reset_for_tests()
    obs.reset_for_tests()
    obs.default_registry().clear()
    yield
    chaos.reset_for_tests()
    obs.reset_for_tests()
    obs.default_registry().clear()


class StubScorer:
    engine = "stub"
    static_shapes = False
    num_features = 4

    def __init__(self, delay: float = 0.0):
        self.delay = delay

    def compute_batch(self, rows, n_valid=None):
        x = np.asarray(rows, np.float32)
        if self.delay:
            time.sleep(self.delay)
        return np.ascontiguousarray(x[:, :1])


def _stub_daemon(stub=None, **cfg_kw) -> ScoringDaemon:
    stub = stub or StubScorer()
    registry = ModelRegistry(loader=lambda _d, _e: stub)
    registry.load("stub://", model_id="default")
    base = dict(engine="numpy", report_every_s=0.0)
    base.update(cfg_kw)
    return ScoringDaemon(registry=registry, config=ServingConfig(**base))


def _counts(fast_idx: int, n: int, prev=None):
    c = list(prev) if prev is not None else [0] * N_BUCKETS
    c[fast_idx] += n
    return c


# ------------------------------------------------------------ SloEngine


def test_slo_engine_fires_once_latches_and_resolves():
    eng = SloEngine(SloObjectives(p99_ms=5.0, fast_window_s=1.0,
                                  slow_window_s=3.0, burn_threshold=2.0,
                                  min_requests=5))
    t, req, counts = 0.0, 0, [0] * N_BUCKETS
    # healthy traffic: everything in the 1ms bucket
    for _ in range(8):
        t += 0.5
        req += 100
        counts = _counts(4, 100, counts)
        eng.observe(t, req, 0, 0, counts)
        assert eng.evaluate(t) == []
    # sustained violation: everything lands in the 25ms bucket
    fired = []
    for _ in range(8):
        t += 0.5
        req += 100
        counts = _counts(9, 100, counts)
        eng.observe(t, req, 0, 0, counts)
        fired += eng.evaluate(t)
        if fired:
            break
    assert len(fired) == 1 and fired[0]["state"] == "firing"
    assert fired[0]["objective"] == "p99_latency"
    assert fired[0]["burn_fast"] >= 2.0 and fired[0]["burn_slow"] >= 2.0
    # latched: continued violation re-emits NOTHING (once per episode)
    for _ in range(4):
        t += 0.5
        req += 100
        counts = _counts(9, 100, counts)
        eng.observe(t, req, 0, 0, counts)
        assert eng.evaluate(t) == []
    assert eng.state()["firing"] == ["p99_latency"]
    # recovery: healthy fast window resolves exactly once
    resolved = []
    for _ in range(10):
        t += 0.5
        req += 100
        counts = _counts(4, 100, counts)
        eng.observe(t, req, 0, 0, counts)
        resolved += eng.evaluate(t)
        if resolved:
            break
    assert len(resolved) == 1 and resolved[0]["state"] == "resolved"
    assert eng.state()["firing"] == []
    assert eng.alerts_fired == 1


def test_slo_engine_error_rate_and_availability():
    eng = SloEngine(SloObjectives(error_rate=0.01, availability=0.99,
                                  fast_window_s=1.0, slow_window_s=2.0,
                                  burn_threshold=2.0, min_requests=5))
    t, req, errs, rej = 0.0, 0, 0, 0
    for _ in range(4):
        t += 0.5
        req += 100
        eng.observe(t, req, rej, errs, None)
        assert eng.evaluate(t) == []
    # 10% errors + heavy rejection: both objectives burn
    for _ in range(6):
        t += 0.5
        req += 90
        errs += 10
        rej += 50
        eng.observe(t, req, rej, errs, None)
        evs = eng.evaluate(t)
        if evs:
            break
    objectives = sorted(e["objective"] for e in evs)
    assert objectives == ["availability", "error_rate"]
    assert all(e["state"] == "firing" for e in evs)
    er = [e for e in evs if e["objective"] == "error_rate"][0]
    # the firing window can straddle the healthy phase — the observed
    # rate is diluted but still far past the 1% objective
    assert er["observed_error_rate"] > 0.01


def test_slo_engine_resolves_when_traffic_stops():
    """A latched alert must not survive its traffic: when the window
    falls below min_requests (load drill ended, daemon idle), the firing
    alert resolves instead of showing stale FIRING forever."""
    eng = SloEngine(SloObjectives(p99_ms=5.0, fast_window_s=1.0,
                                  slow_window_s=2.0, burn_threshold=2.0,
                                  min_requests=5))
    t, req, counts = 0.0, 0, [0] * N_BUCKETS
    evs = []
    for _ in range(8):
        t += 0.5
        req += 100
        counts = _counts(9, 100, counts)  # sustained violation
        eng.observe(t, req, 0, 0, counts)
        evs += eng.evaluate(t)
        if evs:
            break
    assert evs and evs[0]["state"] == "firing"
    # traffic stops: counters freeze, windows empty out
    resolved = []
    for _ in range(8):
        t += 0.5
        eng.observe(t, req, 0, 0, counts)
        resolved += eng.evaluate(t)
        if resolved:
            break
    assert len(resolved) == 1 and resolved[0]["state"] == "resolved"
    assert "traffic stopped" in resolved[0]["note"]
    assert eng.state()["firing"] == []


def test_slo_engine_ignores_near_empty_windows():
    """A quiet daemon (fewer than min_requests per window) is never
    judged — scheduler jitter on 3 requests must not page anyone."""
    eng = SloEngine(SloObjectives(p99_ms=5.0, fast_window_s=1.0,
                                  slow_window_s=2.0, min_requests=20))
    t, req, counts = 0.0, 0, [0] * N_BUCKETS
    for _ in range(10):
        t += 0.5
        req += 2
        counts = _counts(9, 2, counts)  # all "slow", but only 2/tick
        eng.observe(t, req, 0, 0, counts)
        assert eng.evaluate(t) == []
    assert eng.state()["firing"] == []


def test_serving_config_slo_validation_and_xml_keys(tmp_path):
    with pytest.raises(ConfigError):
        ServingConfig(trace_sample=-1).validate()
    with pytest.raises(ConfigError):
        ServingConfig(slo_error_rate=1.5).validate()
    with pytest.raises(ConfigError):
        ServingConfig(slo_fast_window_s=10.0,
                      slo_slow_window_s=5.0).validate()
    with pytest.raises(ConfigError):
        ServingConfig(slo_burn_threshold=0.5).validate()
    ServingConfig(trace_sample=100, slo_p99_ms=10.0, slo_error_rate=0.001,
                  slo_availability=0.999).validate()

    from shifu_tpu.utils import xmlconfig
    xml = tmp_path / "serving.xml"
    props = {
        xmlconfig.KEY_SERVING_TRACE_SAMPLE: "50",
        xmlconfig.KEY_SERVING_SLO_P99_MS: "10",
        xmlconfig.KEY_SERVING_SLO_ERROR_RATE: "0.001",
        xmlconfig.KEY_SERVING_SLO_AVAILABILITY: "0.999",
        xmlconfig.KEY_SERVING_SLO_FAST_WINDOW_S: "30",
        xmlconfig.KEY_SERVING_SLO_SLOW_WINDOW_S: "120",
        xmlconfig.KEY_SERVING_SLO_BURN_THRESHOLD: "3",
    }
    xmlconfig.write_configuration_xml(props, str(xml))
    cfg = xmlconfig.serving_config_from_conf(
        xmlconfig.parse_configuration_xml(str(xml)))
    assert cfg.trace_sample == 50
    assert cfg.slo_p99_ms == 10.0
    assert cfg.slo_error_rate == 0.001
    assert cfg.slo_availability == 0.999
    assert cfg.slo_fast_window_s == 30.0
    assert cfg.slo_slow_window_s == 120.0
    assert cfg.slo_burn_threshold == 3.0
    cfg.validate()


# ------------------------------------------------- lifecycle stage chain


def test_stage_chain_sums_exactly_to_e2e(tmp_path):
    """The acceptance invariant: every sampled request_trace's stage
    durations (admission/queue/coalesce/dispatch/device/reply) sum to
    its end-to-end latency — shared stamps, no gap, no overlap."""
    obs.configure(str(tmp_path / "tele"))
    d = _stub_daemon(StubScorer(delay=0.002), trace_sample=1,
                     latency_budget_ms=1.0).start()
    futs = [d.submit(np.zeros(4, np.float32)) for _ in range(30)]
    for f in futs:
        f.result(timeout=10)
    # futures resolve BEFORE the worker books the stage histograms (the
    # reply stamp closes the chain after set_result) — wait the tail out
    deadline = time.time() + 10
    stats = d.stats()
    while time.time() < deadline and (
            set(stats.get("stages") or ()) != set(STAGES)  # booked in turn
            or any(s["count"] < 30 for s in stats["stages"].values())):
        time.sleep(0.01)
        stats = d.stats()
    d.stop()
    obs.flush()
    events = obs.read_journal(str(tmp_path / "tele" / "journal.jsonl"))
    traces = [e for e in events if e["kind"] == "request_trace"]
    assert len(traces) == 30  # 1-in-1 sampling
    for tr in traces:
        ssum = sum(tr[f"{s}_ms"] for s in STAGES)
        assert ssum == pytest.approx(tr["e2e_ms"], abs=0.01)
        assert tr["batch"] >= 1 and tr["engine"] == "stub"
        assert tr["model_version"] == 1
    # the always-on histograms saw every request, stage by stage
    stages = stats.get("stages")
    assert stages and set(stages) == set(STAGES)
    assert all(s["count"] == 30 for s in stages.values())
    # the stub sleeps 2ms per batch: the device stage carries it
    assert stages["device"]["mean_ms"] >= 1.5


def test_quiet_traffic_contract(tmp_path):
    """Quiet traffic with sampling off and objectives on: ZERO sampled
    traces, ZERO alerts — and the always-on stage accounting stays far
    under the ~2%-style overhead budget (one vectorized bin + one lock
    per stage per batch)."""
    obs.configure(str(tmp_path / "tele"))
    d = _stub_daemon(trace_sample=0, slo_p99_ms=25.0,
                     slo_fast_window_s=0.3, slo_slow_window_s=0.6,
                     latency_budget_ms=1.0).start()
    for _ in range(50):
        d.score(np.zeros(4, np.float32), timeout=10)
    time.sleep(0.8)  # several SLO evaluation ticks at healthy latency
    d.stop()
    obs.flush()
    events = obs.read_journal(str(tmp_path / "tele" / "journal.jsonl"))
    kinds = {e["kind"] for e in events}
    assert "request_trace" not in kinds
    assert "slo_alert" not in kinds
    # overhead: the whole stage-observation path on a max_batch-sized
    # dispatch is bounded (vectorized — microseconds in practice; the
    # bound is deliberately loose for 1-core CI hosts)
    vals = {"admission": np.full(4096, 1e-4), "queue": np.full(4096, 1e-4),
            "coalesce": np.full(4096, 1e-4), "dispatch": 1e-4,
            "device": 1e-3, "reply": 1e-5}
    t0 = time.perf_counter()
    for _ in range(10):
        slo_mod.observe_stage_seconds(vals, 4096)
    per_batch = (time.perf_counter() - t0) / 10
    assert per_batch < 0.02, f"stage accounting cost {per_batch * 1e3}ms"


# ---------------------------------------------------- the slowdown drill


def test_dispatch_slowdown_drill(tmp_path):
    """The ISSUE-8 acceptance drill, end to end from artifacts alone: an
    injected `delay` at the dispatch probe drives (a) sampled
    request_trace events whose dispatch stage carries the slowdown and
    whose stages sum to e2e, (b) exactly ONE firing slo_alert with the
    violated objective and burn rate, (c) a one-shot device_profile with
    trigger="slo" — then `shifu-tpu top --once --json` renders all of it
    in a subprocess with jax MASKED (the no-jax contract)."""
    tele = tmp_path / "tele"
    obs.configure(str(tele))
    chaos.configure(plan_mod.parse_plan({"faults": [
        {"site": "runtime.serve.dispatch", "every": 1, "action": "delay",
         "delay_s": 0.03}]}))
    d = _stub_daemon(trace_sample=3, latency_budget_ms=1.0,
                     slo_p99_ms=10.0, slo_fast_window_s=0.5,
                     slo_slow_window_s=1.0, report_every_s=0.4).start()
    code = (
        "import sys, json\n"
        "sys.modules['jax'] = None  # any jax import would explode\n"
        "from shifu_tpu.launcher.cli import main\n"
        f"sys.exit(main(['top', {str(tele)!r}, '--once', '--json']))\n")
    import threading

    pump_stop = threading.Event()

    def pump():
        # traffic must keep flowing while the live frame is captured —
        # a pause would (correctly) resolve the alert as a new episode
        while not pump_stop.is_set():
            try:
                d.submit(np.zeros(4, np.float32), need_future=False)
            except RuntimeError:
                return
            time.sleep(0.01)

    pump_t = threading.Thread(target=pump, daemon=True)
    pump_t.start()
    frame_live = None
    t0 = time.time()
    while time.time() - t0 < 10.0:
        if d._slo.state()["firing"]:
            # the alert just fired (and flushed): capture the LIVE `top`
            # frame — `--once --json` with jax MASKED, the acceptance
            # spelling — while the violation is still active
            time.sleep(0.3)  # let a cadenced report land stage data
            obs.flush()
            r = subprocess.run([sys.executable, "-c", code],
                               capture_output=True, text=True, cwd=REPO)
            assert r.returncode == 0, r.stderr
            frame_live = json.loads(r.stdout)
            break
        time.sleep(0.05)
    pump_stop.set()
    pump_t.join(timeout=10)
    d.stop()
    obs.flush()
    events = obs.read_journal(str(tele / "journal.jsonl"))

    alerts = [e for e in events if e["kind"] == "slo_alert"]
    firing = [a for a in alerts if a["state"] == "firing"]
    assert firing, alerts
    # the latch contract — exactly ONE firing per violation episode:
    # states strictly alternate firing/resolved (a 1-core host can
    # legitimately see >1 episode when the subprocess starves traffic
    # long enough to resolve, but never two firings back to back)
    states = [a["state"] for a in alerts]
    assert states[0] == "firing"
    assert all(x != y for x, y in zip(states, states[1:])), states
    a = firing[0]
    assert a["objective"] == "p99_latency"
    assert a["burn_fast"] >= 2.0 and a["burn_slow"] >= 2.0
    assert a["observed_p99_ms"] > 10.0

    traces = [e for e in events if e["kind"] == "request_trace"]
    assert traces, "sampling produced no request_trace events"
    slowed = [t for t in traces if "error" not in t]
    assert slowed
    for tr in slowed:
        ssum = sum(tr[f"{s}_ms"] for s in STAGES)
        assert ssum == pytest.approx(tr["e2e_ms"], abs=0.02)
    # the injected slowdown is attributed to the dispatch stage
    assert max(t["dispatch_ms"] for t in slowed) >= 25.0

    profiles = [e for e in events if e["kind"] == "device_profile"]
    slo_profiles = [p for p in profiles if p.get("trigger") == "slo"]
    assert len(slo_profiles) == len(firing), profiles  # one per episode
    assert slo_profiles[0].get("objective") == "p99_latency"

    # the live frame rendered the episode + stage decomposition.  On a
    # 1-core host the subprocess's own startup can starve traffic long
    # enough to resolve the alert before the frame is read, so the
    # frame shows EITHER the still-active alert or the counted episode
    # — both spell "the excursion is visible in top".
    assert frame_live is not None, "alert never fired within the drill"
    assert frame_live["mode"] == "serving"
    assert frame_live["request_traces"] > 0
    assert frame_live["stages"]["dispatch"]["mean_ms"] >= 20.0
    slo_frame = frame_live["slo"]
    active = [x["objective"] for x in slo_frame["active"]]
    assert active == ["p99_latency"] or slo_frame["alerts_total"] >= 1, \
        slo_frame

    # text mode renders the stage table and an slo line (ALERT while the
    # last episode was still latched at stop, `slo: ok` when the final
    # idle tick resolved it first — stop() mid-episode is legal; the
    # deterministic idle-resolution contract is pinned by
    # test_slo_engine_resolves_when_traffic_stops)
    r = subprocess.run([sys.executable, "-c", code.replace(
        ", '--json'", "")], capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert "dispatch" in r.stdout
    assert "ALERT p99_latency" in r.stdout or "slo: ok" in r.stdout


def test_chaos_delay_action_plan():
    spec = plan_mod.FaultSpec(site="runtime.serve.dispatch", every=1,
                              action="delay", delay_s="0.01").validate()
    assert spec.delay_s == 0.01  # string coerced at load, never mid-run
    with pytest.raises(plan_mod.ChaosPlanError):
        plan_mod.FaultSpec(site="x", every=1, action="delay",
                           delay_s=-1).validate()
    chaos.configure(plan_mod.parse_plan({"faults": [
        {"site": "t.delay", "every": 1, "action": "delay",
         "delay_s": 0.05}]}))
    t0 = time.perf_counter()
    chaos.maybe_fail("t.delay")  # returns (a slowdown, not a failure)
    assert time.perf_counter() - t0 >= 0.045


# ------------------------------------------------- loadtest decomposition


def test_loadtest_reports_stage_decomposition(tmp_path):
    obs.configure(str(tmp_path / "tele"))
    d = _stub_daemon(latency_budget_ms=1.0).start()
    try:
        report = loadtest_mod.run_loadtest(daemon=d, rate=2000.0,
                                           duration=0.5, senders=1)
    finally:
        d.stop()
    assert report["completed"] > 0
    stages = report["stages"]
    for s in ("queue", "coalesce", "dispatch", "device", "reply"):
        assert s in stages
        assert stages[s]["count"] == report["completed"]
        assert stages[s]["mean_ms"] is not None
    text = loadtest_mod.render_report(report)
    assert "stages (mean/p99)" in text and "device" in text


# --------------------------------------------- multi-daemon rollup + top


def _run_stub_daemon_into(tele_dir, n_requests=40, delay=0.0):
    obs.reset_for_tests()
    obs.default_registry().clear()
    obs.configure(str(tele_dir))
    d = _stub_daemon(StubScorer(delay=delay), latency_budget_ms=1.0,
                     report_every_s=0.2).start()
    for _ in range(n_requests):
        d.score(np.zeros(4, np.float32), timeout=10)
        time.sleep(0.005)
    d.stop()
    obs.flush()


def test_serving_rollup_and_fleet_top(tmp_path):
    """N serving telemetry dirs join into one fleet view — file reads
    only (pod scale-out prep for the launcher dispatch of daemons)."""
    d1, d2 = tmp_path / "daemon1", tmp_path / "daemon2"
    _run_stub_daemon_into(d1)
    _run_stub_daemon_into(d2, delay=0.002)
    rollup = aggregate_mod.serving_rollup([str(d1), str(d2)])
    assert rollup["fleet"]["daemons"] == 2
    assert rollup["fleet"]["active_alerts"] == 0
    assert len(rollup["daemons"]) == 2
    for drow in rollup["daemons"]:
        assert drow["mode"] == "serving"
        assert drow["serving"]["requests"] == 40
    text = render_mod.render_top_fleet_text(rollup)
    assert "fleet: 2 daemon(s)" in text
    # the CLI spelling: multiple dirs -> the fleet frame
    from shifu_tpu.launcher.cli import main as cli_main
    rc = cli_main(["top", str(d1), str(d2), "--once", "--json"])
    assert rc == 0


def test_top_train_mode(tmp_path):
    """`shifu-tpu top` on a TRAIN job dir renders epoch progress +
    goodput from the same journal-tail contract."""
    tele = tmp_path / "telemetry"
    tele.mkdir(parents=True)
    with open(tele / "journal.jsonl", "w") as f:
        for rec in (
                {"kind": "run_start", "ts": 1.0, "command": "train"},
                {"kind": "epoch", "ts": 2.0, "epoch": 0,
                 "train_error": 0.25, "valid_error": 0.24,
                 "valid_auc": 0.81, "epoch_time": 3.2},
                {"kind": "goodput", "ts": 2.1, "epoch": 0,
                 "goodput_fraction": 0.7}):
            f.write(json.dumps(rec) + "\n")
    summary = render_mod.top_summary(str(tmp_path))
    assert summary["mode"] == "train"
    assert summary["epoch"]["valid_auc"] == 0.81
    assert summary["goodput"] == {"epoch": 0, "goodput_fraction": 0.7}
    text = render_mod.render_top_text(summary)
    assert "epoch 0" in text and "goodput 70.0%" in text


def test_status_shows_slo_state(tmp_path):
    """`shifu-tpu status` surfaces the serving daemon's SLO state from
    the journal tail (detach._telemetry_quick_summary)."""
    from shifu_tpu.launcher import detach as detach_lib

    tele = tmp_path / "telemetry"
    tele.mkdir(parents=True)
    with open(tele / "journal.jsonl", "w") as f:
        for rec in (
                {"kind": "serve_start", "ts": 1.0, "port": 8571},
                {"kind": "serving_report", "ts": 2.0, "requests": 100,
                 "scores_per_sec": 5000.0, "p99_ms": 42.0,
                 "queue_depth": 3, "errors": 0},
                {"kind": "slo_alert", "ts": 2.5, "objective":
                 "p99_latency", "state": "firing", "burn_fast": 8.0,
                 "observed_p99_ms": 42.0}):
            f.write(json.dumps(rec) + "\n")
    tele_summary = detach_lib._telemetry_quick_summary(
        str(tele / "journal.jsonl"))
    assert tele_summary["serving"]["p99_ms"] == 42.0
    assert tele_summary["slo"]["firing"] == ["p99_latency"]
    # a resolved alert clears the firing set (newest state wins)
    with open(tele / "journal.jsonl", "a") as f:
        f.write(json.dumps({"kind": "slo_alert", "ts": 3.0,
                            "objective": "p99_latency",
                            "state": "resolved"}) + "\n")
    tele_summary = detach_lib._telemetry_quick_summary(
        str(tele / "journal.jsonl"))
    assert tele_summary["slo"]["firing"] == []


def test_parse_scrape_histograms_roundtrip():
    """The scrape-file histogram parser recovers exactly what the
    registry rendered — the `top` stage math runs on files alone."""
    from shifu_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("serve_stage_seconds", "t",
                      buckets=SCORE_LATENCY_BUCKETS)
    for v in (0.0001, 0.002, 0.002, 0.04, 99.0):
        h.observe(v, stage="device")
    h.observe(0.001, stage="queue")
    parsed = render_mod.parse_scrape_histograms(reg.to_prometheus_text())
    dev = parsed["serve_stage_seconds"]["stage=device"]
    assert dev["count"] == 5
    assert sum(dev["counts"]) == 5
    assert dev["counts"][-1] == 1  # the 99s observation rides +Inf
    assert dev["sum"] == pytest.approx(0.0441 + 99.0, rel=1e-6)
    assert parsed["serve_stage_seconds"]["stage=queue"]["count"] == 1
    # a +Inf-only histogram (legal exposition, e.g. a third-party
    # exporter sharing the dir) parses instead of crashing the frame
    only_inf = ('x_bucket{le="+Inf"} 5\nx_sum 1.0\nx_count 5\n')
    parsed = render_mod.parse_scrape_histograms(only_inf)
    assert parsed["x"][""]["counts"] == [5]
    assert parsed["x"][""]["bounds"] == []


def test_top_renders_loadtest_only_dir(tmp_path):
    """A socket loadtest's own telemetry dir (loadtest_report only, no
    serving_report) renders as a serving frame, not a train one."""
    tele = tmp_path / "telemetry"
    tele.mkdir(parents=True)
    with open(tele / "journal.jsonl", "w") as f:
        f.write(json.dumps({
            "kind": "loadtest_report", "ts": 1.0, "mode": "socket",
            "completed": 500, "rejected": 0, "errors": 2,
            "p50_ms": 1.2, "p99_ms": 6.5,
            "achieved_scores_per_sec": 4100.0, "engine": "numpy",
            "stages": {"device": {"mean_ms": 0.4, "p99_ms": 1.0,
                                  "count": 500}}}) + "\n")
    summary = render_mod.top_summary(str(tmp_path))
    assert summary["mode"] == "serving"
    assert summary["serving"]["p99_ms"] == 6.5
    assert summary["serving"]["scores_per_sec"] == 4100.0
    assert summary["stages"]["device"]["mean_ms"] == 0.4


# -------------------------------------------- fleet-view degradation


def test_top_marks_stale_daemon_down(tmp_path):
    """The stale-frame fix: a daemon whose lease is older than its own
    ttl renders DOWN (last frame flagged, not shown as live), and the
    fleet rollup excludes it from the live totals."""
    from shifu_tpu.obs import aggregate as aggregate_mod
    from shifu_tpu.runtime import fleet as fleet_lib

    old = time.time() - 100.0
    dead = tmp_path / "dead"
    dead.mkdir()
    with open(dead / "journal.jsonl", "w") as f:
        f.write(json.dumps({"kind": "serving_report", "ts": old,
                            "requests": 500, "scores_per_sec": 9000.0,
                            "p99_ms": 2.0, "queue_depth": 1,
                            "errors": 0}) + "\n")
    fleet_lib.write_lease(str(dead), "member-0", seq=9, ttl_s=0.3)
    # age the lease in place (write_lease stamps now)
    rec = fleet_lib.read_lease(str(dead))
    rec["ts"] = old
    with open(dead / fleet_lib.LEASE_FILE, "w") as f:
        json.dump(rec, f)

    live = tmp_path / "live"
    live.mkdir()
    with open(live / "journal.jsonl", "w") as f:
        f.write(json.dumps({"kind": "serving_report", "ts": time.time(),
                            "requests": 300, "scores_per_sec": 4000.0,
                            "p99_ms": 3.0, "queue_depth": 0,
                            "errors": 0}) + "\n")

    s = render_mod.top_summary(str(dead))
    assert s["down"] is True
    assert s["stale_s"] > 0.3
    assert s["lease"]["member"] == "member-0"
    assert "DOWN" in render_mod.render_top_text(s)
    # the live dir (no lease, fresh events) is NOT down by default...
    assert "down" not in render_mod.top_summary(str(live))
    # ...but an explicit --stale-after can flag anything
    assert render_mod.top_summary(str(live),
                                  stale_after_s=3600.0).get("down") \
        is None

    roll = aggregate_mod.serving_rollup([str(live), str(dead)])
    assert roll["fleet"]["daemons"] == 2
    assert roll["fleet"]["down"] == 1
    # the dead member's 9000/s last frame is NOT in the live rate
    assert roll["fleet"]["scores_per_sec"] == 4000.0
    text = render_mod.render_top_fleet_text(roll)
    assert "(1 DOWN)" in text and "DOWN" in text


def test_top_survives_torn_journal_and_corrupt_scrape(tmp_path):
    """A torn mid-line journal tail (writer died mid-record) and a
    corrupt scrape file both degrade gracefully: the frame renders from
    what parsed, flagged — never an exception."""
    from shifu_tpu.obs import aggregate as aggregate_mod

    tele = tmp_path / "tele"
    tele.mkdir()
    with open(tele / "journal.jsonl", "w") as f:
        f.write(json.dumps({"kind": "serving_report", "ts": time.time(),
                            "requests": 100, "scores_per_sec": 1000.0,
                            "p99_ms": 5.0, "errors": 0}) + "\n")
        f.write('{"kind": "serving_report", "ts": 99, "requ')  # torn
    with open(tele / "metrics.prom", "w") as f:
        # a bucket bound that is not a float raises inside the
        # histogram parser — the frame must flag it, not die
        f.write('serve_stage_seconds_bucket{stage="device",'
                'le="garbage"} 5\n')
    s = render_mod.top_summary(str(tele))
    assert s["mode"] == "serving"
    assert s["serving"]["p99_ms"] == 5.0     # the intact line rendered
    assert s.get("scrape_error") is True
    assert s.get("stages") is None
    # the rollup carries the degraded frame instead of crashing, and a
    # dir with no journal at all becomes an error row
    roll = aggregate_mod.serving_rollup(
        [str(tele), str(tmp_path / "missing")])
    assert roll["fleet"]["daemons"] == 2
    assert roll["daemons"][0]["serving"]["p99_ms"] == 5.0
    assert "error" in roll["daemons"][1]
    render_mod.render_top_fleet_text(roll)   # renders, no exception
