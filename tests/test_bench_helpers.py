"""Unit tests for bench.py's timing helpers.

The two-point deconvolution (`_sustained_rate`) is what makes every
device-rate number bench.py reports mean "sustained device throughput"
rather than "dispatch latency": these tests pin that it recovers the true
per-call cost from windows polluted by a large fixed dispatch/readback
overhead, and that it degrades to a plain long-window average when there
is nothing to solve.
"""

from __future__ import annotations

import time

import bench


class _FakeClock:
    """Deterministic perf_counter: call() costs `w` seconds, sync() costs
    `c` seconds — so a window of r calls takes exactly w*r + c."""

    def __init__(self, w: float, c: float):
        self.now = 0.0
        self.w = w
        self.c = c

    def call(self):
        self.now += self.w
        return "handle"

    def sync(self, h):
        assert h == "handle"
        self.now += self.c


def test_sustained_rate_deconvolves_fixed_overhead(monkeypatch):
    clk = _FakeClock(w=0.005, c=0.060)  # 60 ms fixed cost, 5 ms true work
    monkeypatch.setattr(time, "perf_counter", lambda: clk.now)
    rate, diag = bench._sustained_rate(clk.call, clk.sync, 1000.0)
    # naive short windows would report ~1000/0.035 = 28k; the solve must
    # recover the true 1000/0.005 = 200k
    assert abs(rate - 200_000.0) / 200_000.0 < 0.01
    assert abs(diag["fixed_overhead_ms"] - 60.0) < 1.0
    # the corroborating long window is within a few percent of the solve
    assert diag["long_window_rate"] > 0.8 * rate


def test_sustained_rate_degenerate_fixed_cost_only(monkeypatch):
    # per-call work below the solver's resolution: must not divide by ~0 or
    # return a wild extrapolation — falls back to the long-window average
    clk = _FakeClock(w=0.0, c=0.050)
    monkeypatch.setattr(time, "perf_counter", lambda: clk.now)
    rate, diag = bench._sustained_rate(clk.call, clk.sync, 1000.0)
    assert rate > 0
    r_lo, r_hi = diag["reps"]
    assert rate <= 1000.0 * r_hi / 0.050 * 1.01  # bounded by window math


def test_sustained_rate_reps_grow_to_target(monkeypatch):
    # with tiny per-call cost the adaptive reps must grow far beyond the
    # 2-call probe so the device-work term dominates the window
    clk = _FakeClock(w=0.0005, c=0.060)
    monkeypatch.setattr(time, "perf_counter", lambda: clk.now)
    rate, diag = bench._sustained_rate(clk.call, clk.sync, 100.0)
    r_lo, r_hi = diag["reps"]
    assert r_hi >= 100
    assert abs(rate - 100.0 / 0.0005) / (100.0 / 0.0005) < 0.01


def test_headline_is_capture_proof():
    """The stdout line must stay under the driver's tail-capture budget no
    matter how many tiers the full record grows — and must always carry the
    metric/value/vs_baseline triple the round artifact hangs on."""
    import json

    full = {"metric": "tabular_train_samples_per_sec_per_chip",
            "value": 531e6, "unit": "samples/sec/chip", "vs_baseline": 849.6,
            "n_chips": 1, "global_batch": 98304, "model": "mlp"}
    # bloat the record with every optional key plus 200 junk tiers
    for k in bench._HEADLINE_OPTIONAL:
        full.setdefault(k, 123456.789)
    for i in range(200):
        full[f"tier_{i}_diagnostic"] = "x" * 50
    line = json.dumps(bench._headline(full))
    assert len(line) <= bench._HEADLINE_BUDGET
    parsed = json.loads(line)
    for k in ("metric", "value", "vs_baseline"):
        assert k in parsed
    # junk diagnostics never reach the headline
    assert not any(k.startswith("tier_") for k in parsed)
    # priority fields made it in ahead of the tail
    assert "mfu" in parsed
    assert "e2e_cached_disk_samples_per_sec_per_chip" in parsed


def test_rate_stats_fields(monkeypatch):
    """_rate_stats records best/median/min so a cross-round delta is
    classifiable as noise or regression from the artifact alone."""
    times = iter([0.0, 1.0, 1.0, 3.0, 3.0, 7.0, 7.0, 9.0, 9.0, 13.0])
    monkeypatch.setattr(time, "perf_counter", lambda: next(times))
    extras = {}
    bench._rate_stats(extras, "k", lambda: None, 100, trials=5, reps=1)
    # windows: 1s, 2s, 4s, 2s, 4s -> rates 100, 50, 25, 50, 25
    assert extras["k"] == 100.0
    assert extras["k_median"] == 50.0
    assert extras["k_min"] == 25.0


def test_rung_hbm_model_dominated_by_table_at_high_vocab():
    """At CTR-scale vocab the dense-grad + Adadelta term (8x table bytes)
    dominates the model — the property that makes fraction-of-HBM the
    honest lens for the 100k-vocab rung."""
    import dataclasses

    spec = type("S", (), {"embedding_dim": 16})()
    b = bench._rung_hbm_bytes_per_step(spec, 32768, 30, 6, 100_000)
    table = 6 * 100_000 * 16 * 4
    assert b >= 8 * table
    assert 8 * table / b > 0.5


def test_per_tier_deadline_fractions(monkeypatch):
    """The soft budget is allocated by tier priority: a congested run
    skips the mid-priority tiers (small fractions) while the north-star
    e2e tier (frac 1.0) still has budget — the capture-protection the
    fractions exist for."""
    monkeypatch.setenv("SHIFU_TPU_BENCH_DEADLINE", "100")
    # 60s elapsed: ladder slice (0.55) is spent, the e2e slice is not
    monkeypatch.setattr(bench, "_BENCH_START",
                        bench.time.monotonic() - 60.0)
    assert bench._past_deadline(0.55) is True
    assert bench._past_deadline(0.45) is True
    assert bench._past_deadline() is False
    # 101s elapsed: even the full budget is spent
    monkeypatch.setattr(bench, "_BENCH_START",
                        bench.time.monotonic() - 101.0)
    assert bench._past_deadline() is True
    # a bad env value falls back to the default budget instead of raising
    monkeypatch.setenv("SHIFU_TPU_BENCH_DEADLINE", "not-a-number")
    monkeypatch.setattr(bench, "_BENCH_START", bench.time.monotonic())
    assert bench._past_deadline() is False
