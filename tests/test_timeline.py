"""Distributed tracing + skew-corrected fleet timeline tests
(obs/tracing.py, obs/timeline.py, the router hop spans in
runtime/router.py, wire v2 trace frames in runtime/serve_wire.py,
`shifu-tpu timeline` — docs/OBSERVABILITY.md "Fleet timeline").

Covers: TraceContext wire pack/unpack (malformed frames degrade to
untraced, never raise), the skew-corrected journal merge (a member
whose clock runs slow stops reordering causally-later events once the
manager's `fleet_clock_skew` offset is applied — and `fleet-verify` on
deliberately skewed journals flips FAIL -> PASS with the correction),
happens-before nudging, incident reconstruction (failover chain
lease_expiry -> failover -> promotion -> recovery, SLO episodes,
degraded swaps, chaos root-cause hints), loadtest p99 trace exemplars,
the tracing overhead guard (sample=0 journals NOTHING and costs ~nothing), and the
acceptance drill: a `local:2` fleet under open-loop load with a chaos
`delay` inducing a hedged retry, rendered by `shifu-tpu timeline
--json` in a subprocess with jax MASKED — the hedged trace shows both
hop spans and hops + queueing sum to the client-observed e2e."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from shifu_tpu import chaos, obs
from shifu_tpu.chaos import plan as plan_mod
from shifu_tpu.config.schema import FleetConfig, ServingConfig
from shifu_tpu.obs import timeline, tracing
from shifu_tpu.runtime import loadtest as loadtest_mod
from shifu_tpu.runtime import serve as serve_mod
from shifu_tpu.runtime.fleet import FleetManager, fleet_verify_events
from shifu_tpu.runtime.serve import ModelRegistry, ScoringDaemon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos_and_obs():
    chaos.reset_for_tests()
    obs.reset_for_tests()
    yield
    chaos.reset_for_tests()
    obs.reset_for_tests()


class _StubScorer:
    engine = "stub"
    static_shapes = False
    num_features = 4

    def compute_batch(self, rows, n_valid=None):
        x = np.asarray(rows, np.float32)
        return np.ascontiguousarray(x[:, :1])

    def close(self):
        pass


def _stub_daemon(**cfg_kw) -> ScoringDaemon:
    registry = ModelRegistry(loader=lambda _d, _e: _StubScorer())
    registry.load("stub://", model_id="default")
    base = dict(engine="numpy", report_every_s=0.0)
    base.update(cfg_kw)
    return ScoringDaemon(registry=registry, config=ServingConfig(**base))


# --------------------------------------------------------- trace context


def test_trace_context_wire_roundtrip():
    ctx = tracing.mint()
    assert len(ctx.trace_id) == 16
    assert int(ctx.trace_id, 16) >= 0   # hex
    assert ctx.sampled and ctx.attempt == 0
    raw = ctx.with_attempt(3).pack()
    assert len(raw) == tracing.WIRE_EXT_BYTES
    back = tracing.unpack(raw)
    assert back is not None
    assert back.trace_id == ctx.trace_id
    assert back.attempt == 3 and back.sampled


def test_trace_context_malformed_frames_degrade_to_none():
    # wrong length, non-ascii, non-hex: all None, never an exception
    assert tracing.unpack(b"") is None
    assert tracing.unpack(b"\x00" * 7) is None
    assert tracing.unpack(b"\xff" * tracing.WIRE_EXT_BYTES) is None
    bad_hex = tracing.TraceContext(trace_id="zz" * 8).pack()
    assert tracing.unpack(bad_hex) is None
    # uppercase hex is rejected too (mint() emits lowercase only)
    upper = tracing.TraceContext(trace_id="AB" * 8).pack()
    assert tracing.unpack(upper) is None


# ------------------------------------------------- skew-corrected merge


def _src(*events):
    return [dict(e) for e in events]


def test_merge_sources_applies_clock_offsets():
    # manager (reference clock) observed h2 running 10s slow
    mgr_evs = _src(
        {"ts": 1000.0, "seq": 1, "kind": "fleet_swap", "generation": 1},
        {"ts": 1000.5, "seq": 2, "kind": "fleet_clock_skew",
         "host": "h2", "offset_s": 10.0},
    )
    # member on h2: a LATER swap stamped 995 by its slow clock
    m_evs = _src({"ts": 995.0, "seq": 1, "kind": "fleet_member_swap",
                  "member": "m1", "generation": 2})
    raw = timeline.merge_sources([(mgr_evs, ""), (m_evs, "h2")],
                                 skew_correct=False)
    assert [e.get("generation") for e in raw
            if "swap" in e["kind"]] == [2, 1]   # the lie
    cor = timeline.merge_sources([(mgr_evs, ""), (m_evs, "h2")])
    assert [e.get("generation") for e in cor
            if "swap" in e["kind"]] == [1, 2]   # causal order restored
    member_ev = [e for e in cor if e["kind"] == "fleet_member_swap"][0]
    assert member_ev["ts_fleet"] == pytest.approx(1005.0)
    assert member_ev["host"] == "h2"   # annotated from the journal


def test_merge_sources_clamps_absurd_offsets():
    mgr_evs = _src({"ts": 10.0, "seq": 1, "kind": "fleet_clock_skew",
                    "host": "h2", "offset_s": 9999.0})
    m_evs = _src({"ts": 10.0, "seq": 1, "kind": "serve_start"})
    cor = timeline.merge_sources([(mgr_evs, ""), (m_evs, "h2")],
                                 max_offset_s=60.0)
    member_ev = [e for e in cor if e["kind"] == "serve_start"][0]
    assert member_ev["ts_fleet"] == pytest.approx(70.0)


def test_merge_keeps_ts_less_events_in_journal_order():
    evs = _src({"kind": "fleet_member_swap", "member": "m0",
                "generation": 1, "via": "fanout"},
               {"kind": "fleet_member_swap", "member": "m0",
                "generation": 1, "via": "retry"},
               {"kind": "fleet_swap", "generation": 1,
                "swapped": ["m0"], "failed": []})
    merged = timeline.merge_sources([(evs, "")])
    assert [e["kind"] for e in merged] == [e["kind"] for e in evs]
    # the double-application journal still FAILS verify after a merge
    assert fleet_verify_events(merged)["verdict"] == "FAIL"


def test_happens_before_nudges_promotion_past_failover():
    # promotion stamped BEFORE its failover by residual clock error:
    # the protocol edge overrides the clocks
    evs = _src(
        {"ts": 100.0, "seq": 1, "kind": "fleet_member_swap",
         "member": "s0", "via": "promote", "generation": 1},
        {"ts": 100.2, "seq": 2, "kind": "fleet_failover",
         "member": "m0", "standby": "s0"},
    )
    merged = timeline.merge_sources([(evs, "")])
    kinds = [e["kind"] for e in merged]
    assert kinds.index("fleet_failover") < kinds.index("fleet_member_swap")


# ----------------------------------------------- incident reconstruction


def test_reconstruct_incidents_failover_chain():
    evs = timeline.merge_sources([(_src(
        {"ts": 10.0, "seq": 1, "kind": "chaos_inject",
         "site": "fleet.lease", "action": "raise"},
        {"ts": 12.0, "seq": 2, "kind": "fleet_failover", "member": "m0",
         "standby": "s0", "host": "h1", "lease_age_s": 2.5, "ttl_s": 2.0},
        {"ts": 12.4, "seq": 3, "kind": "fleet_member_swap",
         "member": "s0", "via": "promote", "host": "h2", "generation": 1},
        {"ts": 13.0, "seq": 4, "kind": "route_trace",
         "trace_id": "ab" * 8, "hedged": True, "outcome": "ok",
         "hops": [], "e2e_ms": 50.0, "queue_ms": 1.0},
        {"ts": 15.0, "seq": 5, "kind": "fleet_rejoin", "member": "m0",
         "generation": 1, "caught_up": True},
    ), "")])
    incs = timeline.reconstruct_incidents(evs)
    assert len(incs) == 1
    inc = incs[0]
    assert inc["id"] == "inc-001"
    assert inc["kind"] == "fleet_failover"
    assert inc["root"]["event"] == "lease_expiry"
    assert [s["step"] for s in inc["chain"]] == \
        ["lease_expiry", "failover", "promotion", "recovery"]
    assert inc["chain"][-1]["via"] == "rejoin"
    assert inc["resolved"] is True
    assert inc["recovery_s"] == pytest.approx(3.0, abs=0.01)
    assert inc["affected_traces"] == ["ab" * 8]
    assert inc["suspect_chaos"]["site"] == "fleet.lease"


def test_reconstruct_incidents_slo_and_degraded_episodes():
    evs = timeline.merge_sources([(_src(
        {"ts": 1.0, "seq": 1, "kind": "slo_alert",
         "objective": "p99_latency", "state": "firing"},
        {"ts": 4.0, "seq": 2, "kind": "slo_alert",
         "objective": "p99_latency", "state": "resolved"},
        {"ts": 5.0, "seq": 3, "kind": "fleet_swap_degraded",
         "member": "m0", "error": "sync: digest mismatch"},
        {"ts": 7.5, "seq": 4, "kind": "fleet_readmit", "member": "m0",
         "generation": 2},
        {"ts": 9.0, "seq": 5, "kind": "slo_alert",
         "objective": "availability", "state": "firing"},
    ), "")])
    incs = timeline.reconstruct_incidents(evs)
    assert [i["kind"] for i in incs] == \
        ["slo_alert", "fleet_swap_degraded", "slo_alert"]
    assert incs[0]["resolved"] and incs[0]["recovery_s"] == \
        pytest.approx(3.0)
    assert incs[1]["resolved"] and \
        [s["step"] for s in incs[1]["chain"]] == \
        ["swap_degraded", "readmit"]
    assert not incs[2]["resolved"]   # still OPEN
    assert incs[2]["recovery_s"] is None
    # ids re-numbered in root-ts order
    assert [i["id"] for i in incs] == ["inc-001", "inc-002", "inc-003"]


def test_unpromoted_failover_stays_open():
    evs = timeline.merge_sources([(_src(
        {"ts": 2.0, "seq": 1, "kind": "fleet_failover", "member": "m0",
         "standby": None, "host": "h1"})
    , "")])
    incs = timeline.reconstruct_incidents(evs)
    assert len(incs) == 1
    assert not incs[0]["resolved"]
    assert [s["step"] for s in incs[0]["chain"]] == \
        ["lease_expiry", "failover"]


# ----------------------------------------- fleet-verify skew regression


def _write_journal(path, events):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def _skewed_fleet_dir(tmp_path):
    """Two journals with a deliberately slow member clock: generation 2
    applied on h2 is stamped BEFORE the manager's generation-1 barrier.
    Raw merge FAILS the generation-ordering audit; the manager's
    observed +10s offset for h2 restores causal order."""
    d = tmp_path / "tele"
    _write_journal(str(d / "journal.jsonl"), [
        {"ts": 1000.0, "seq": 1, "kind": "fleet_member_swap",
         "member": "m1", "generation": 1, "via": "fanout"},
        {"ts": 1000.1, "seq": 2, "kind": "fleet_swap", "generation": 1,
         "swapped": ["m1"], "failed": []},
        {"ts": 1000.5, "seq": 3, "kind": "fleet_clock_skew",
         "host": "h2", "offset_s": 10.0, "rtt_bound_s": 0.1,
         "samples": 4},
        {"ts": 1002.0, "seq": 4, "kind": "fleet_swap", "generation": 2,
         "swapped": ["m1"], "failed": []},
    ])
    _write_journal(str(d / "m1" / "journal.jsonl"), [
        # stamped 995 by the slow clock; true time ~1005 (after gen-1)
        {"ts": 995.0, "seq": 1, "kind": "fleet_member_swap",
         "member": "m1", "generation": 2, "via": "fanout"},
    ])
    with open(d / "m1" / "lease.json", "w") as f:
        json.dump({"member": "m1", "ts": 995.0, "ttl_s": 3.0,
                   "host": "h2"}, f)
    return d


def test_fleet_verify_skew_regression(tmp_path, capsys):
    from shifu_tpu.launcher import cli

    d = _skewed_fleet_dir(tmp_path)
    # raw clocks: gen-2 application appears BEFORE gen-1 -> the
    # per-member monotonic check fails on the lie
    raw = timeline.merged_fleet_events(str(d), skew_correct=False)
    assert fleet_verify_events(raw)["verdict"] == "FAIL"
    # corrected: the same journals PASS (and the CLI consumes the
    # merged timeline, so its verdict is the corrected one)
    assert cli.main(["fleet-verify", str(d), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["verdict"] == "PASS"
    assert report["skew_correct"] is True
    assert len(report["journals"]) == 2


def test_timeline_summary_reports_offsets_and_trace_filter(tmp_path):
    d = _skewed_fleet_dir(tmp_path)
    s = timeline.timeline_summary(str(d))
    assert s is not None
    assert s["offsets"] == {"h2": 10.0}
    assert s["hosts"] == ["", "h2"]
    assert len(s["journals"]) == 2
    assert timeline.timeline_summary(str(tmp_path / "nope")) is None


# --------------------------------------------- loadtest trace exemplars


def test_loadtest_inproc_reports_trace_exemplars(tmp_path):
    obs.configure(str(tmp_path / "tele"))
    d = _stub_daemon(latency_budget_ms=5.0).start()
    try:
        report = loadtest_mod.run_loadtest(
            daemon=d, rate=500.0, duration=0.6, senders=2, seed=3,
            trace_sample=2, trace_exemplars=4)
    finally:
        d.stop()
    ex = report.get("trace_exemplars")
    assert ex, report
    assert len(ex) <= 4
    for e in ex:
        assert len(e["trace_id"]) == 16
        assert e["ms"] >= 0
    # slowest-first ordering
    assert [e["ms"] for e in ex] == sorted((e["ms"] for e in ex),
                                           reverse=True)
    assert "slowest traces" in loadtest_mod.render_report(report)
    # sampling off: no exemplars key, nothing minted
    d2 = _stub_daemon(latency_budget_ms=5.0).start()
    try:
        r2 = loadtest_mod.run_loadtest(daemon=d2, rate=200.0,
                                       duration=0.3, senders=1, seed=3)
    finally:
        d2.stop()
    assert "trace_exemplars" not in r2


# -------------------------------------------------- wire v2 + daemon hop


def test_request_trace_carries_trace_id_and_hop(tmp_path):
    """A trace context submitted with a request forces sampling: the
    journaled request_trace carries the distributed trace_id + hop."""
    obs.configure(str(tmp_path / "tele"))
    d = _stub_daemon(trace_sample=0).start()   # cadence sampling OFF
    try:
        ctx = tracing.mint().with_attempt(1)
        d.score(np.zeros(4, np.float32), timeout=5, trace=ctx)
        d.score(np.zeros(4, np.float32), timeout=5)   # untraced
    finally:
        d.stop()
    obs.flush()
    evs = obs.read_journal(str(tmp_path / "tele" / "journal.jsonl"))
    traces = [e for e in evs if e["kind"] == "request_trace"]
    assert len(traces) == 1   # the forced one only: cadence is off
    assert traces[0]["trace_id"] == ctx.trace_id
    assert traces[0]["hop"] == 1


# ------------------------------------------------ tracing overhead guard


def test_tracing_off_adds_no_events_and_bounded_overhead(tmp_path):
    """The zero-cost-when-off contract: trace_sample=0 journals ZERO
    route_trace/request_trace events, and the added per-request work is
    a couple of `is None` checks — p50 stays within noise of an
    identical untraced run (loose bound: 5% + 1ms for CI hosts)."""
    obs.configure(str(tmp_path / "tele"))
    p50s = []
    for _ in range(2):
        d = _stub_daemon(trace_sample=0, latency_budget_ms=2.0).start()
        try:
            r = loadtest_mod.run_loadtest(daemon=d, rate=800.0,
                                          duration=0.5, senders=2,
                                          seed=5, trace_sample=0)
        finally:
            d.stop()
        p50s.append(r["p50_ms"])
    assert abs(p50s[1] - p50s[0]) <= max(p50s) * 0.05 + 1.0, p50s
    obs.flush()
    evs = obs.read_journal(str(tmp_path / "tele" / "journal.jsonl"))
    kinds = {e["kind"] for e in evs}
    assert "request_trace" not in kinds
    assert "route_trace" not in kinds


def test_tracing_on_journal_bytes_bounded(tmp_path):
    """Sampling ON: journal growth is bounded by the sample cadence —
    ~one request_trace per sampled request, not one per request."""
    obs.configure(str(tmp_path / "tele"))
    d = _stub_daemon(trace_sample=0, latency_budget_ms=2.0).start()
    n = 60
    sample = 10
    try:
        for k in range(n):
            ctx = tracing.mint() if k % sample == 0 else None
            d.score(np.zeros(4, np.float32), timeout=5, trace=ctx)
    finally:
        d.stop()
    obs.flush()
    evs = obs.read_journal(str(tmp_path / "tele" / "journal.jsonl"))
    traces = [e for e in evs if e["kind"] == "request_trace"]
    assert len(traces) == n // sample
    jbytes = os.path.getsize(str(tmp_path / "tele" / "journal.jsonl"))
    # ~250B per trace row; the whole journal stays far under 1 line/req
    assert jbytes < 64 * 1024, jbytes


# --------------------------------------- acceptance: hedged trace e2e


class _TagScorer:
    engine = "stub"
    static_shapes = False
    num_features = 4

    def compute_batch(self, rows, n_valid=None):
        x = np.asarray(rows, np.float32)
        return np.ascontiguousarray(x[:, :1])

    def close(self):
        pass


@pytest.mark.chaos
def test_timeline_cli_shows_hedged_trace_jax_masked(tmp_path):
    """ISSUE-16 acceptance: a `local:2` fleet under open-loop load with
    a chaos `delay` at the dispatch probe long enough to trip the route
    timeout -> the router hedges to the surviving candidate.  The
    sampled trace journals TWO hop spans under ONE trace_id, hops +
    queueing sum to the client-observed e2e, and `shifu-tpu timeline
    --json` renders it all in a subprocess with jax MASKED."""
    tele = tmp_path / "tele"
    obs.configure(str(tele))
    # one delayed dispatch >> route_timeout: attempt 0 times out on the
    # wire, the hedge lands on the other member
    chaos.configure(plan_mod.parse_plan({"faults": [
        {"site": serve_mod.CHAOS_DISPATCH_SITE, "every": 1,
         "max_times": 1, "action": "delay", "delay_s": 0.8}]}))
    mgr = FleetManager(
        "stub://v0",
        fleet=FleetConfig(n_daemons=2, standbys=0, hosts="local:2",
                          heartbeat_every_s=0.2, heartbeat_misses=10,
                          route_timeout_ms=250),
        serving=ServingConfig(engine="numpy", report_every_s=0.0,
                              trace_sample=1),
        root_dir=str(tmp_path / "fleet"),
        loader=lambda _p, _e: _TagScorer())
    mgr.start()
    try:
        assert mgr.router.trace_sample == 1
        for _ in range(6):
            out = mgr.router.score_rows(np.ones((1, 4), np.float32))
            assert np.asarray(out).shape == (1, 1)
    finally:
        mgr.stop()
    obs.flush()

    evs = obs.read_journal(str(tele / "journal.jsonl"))
    routes = [e for e in evs if e["kind"] == "route_trace"]
    assert len(routes) == 6
    hedged = [r for r in routes if r["hedged"]]
    assert len(hedged) == 1, routes
    h = hedged[0]
    assert len(h["hops"]) == 2
    assert h["hops"][0]["outcome"] != "ok"
    assert h["hops"][1]["outcome"] == "ok"
    assert h["hops"][0]["attempt"] == 0 and h["hops"][1]["attempt"] == 1
    # the decomposition invariant: hops + queueing == client e2e
    hop_ms = sum(x["ms"] for x in h["hops"])
    assert hop_ms + h["queue_ms"] == pytest.approx(h["e2e_ms"], abs=0.05)
    # both member-side stage decompositions joined under the trace
    member_rows = [e for e in evs if e["kind"] == "request_trace"
                   and e.get("trace_id") == h["trace_id"]]
    assert sorted(r["hop"] for r in member_rows) == [0, 1]

    code = (
        "import sys, json\n"
        "sys.modules['jax'] = None  # any jax import would explode\n"
        "from shifu_tpu.launcher.cli import main\n"
        f"rc = main(['timeline', {str(tele)!r}, '--json'])\n"
        "assert rc == 0, rc\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    row = [t for t in doc["traces"] if t["trace_id"] == h["trace_id"]]
    assert len(row) == 1 and row[0]["hedged"]
    assert len(row[0]["hops"]) == 2
    assert len(row[0]["requests"]) == 2
    # --trace-id narrows to the one trace
    code2 = (
        "import sys, json\n"
        "sys.modules['jax'] = None\n"
        "from shifu_tpu.launcher.cli import main\n"
        f"rc = main(['timeline', {str(tele)!r}, '--json',\n"
        f"           '--trace-id', {h['trace_id']!r}])\n"
        "assert rc == 0, rc\n")
    out2 = subprocess.run([sys.executable, "-c", code2], cwd=REPO,
                          capture_output=True, text=True, timeout=60)
    assert out2.returncode == 0, out2.stderr
    doc2 = json.loads(out2.stdout)
    assert [t["trace_id"] for t in doc2["traces"]] == [h["trace_id"]]
