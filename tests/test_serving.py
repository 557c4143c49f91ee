"""Serving-plane tests (runtime/serve.py, serve_wire.py, loadtest.py —
docs/SERVING.md).

Covers the ISSUE-7 acceptance seams: the micro-batcher's latency-budget
contract (a lone request never waits past the budget), batched-vs-single
score parity, hot-swap under in-flight load (and the chaos `runtime.serve`
drill: a failing load degrades to the previous version, never a dropped
request), the cache-v2 int8 wire roundtrip, the TCP front-end, the shared
`score_latency_seconds` metric schema, and a loadtest smoke on the Python
engine."""

import json
import os
import threading
import time

import numpy as np
import pytest

from shifu_tpu import chaos, obs
from shifu_tpu.chaos import plan as plan_mod
from shifu_tpu.config.schema import ConfigError, ServingConfig
from shifu_tpu.runtime import serve as serve_mod
from shifu_tpu.runtime import serve_wire as wire_mod
from shifu_tpu.runtime.serve import (ModelRegistry, ScoringDaemon,
                                     ServeOverload, bucket_for,
                                     bucket_ladder)


@pytest.fixture(autouse=True)
def _clean_chaos_and_obs():
    chaos.reset_for_tests()
    obs.reset_for_tests()
    yield
    chaos.reset_for_tests()
    obs.reset_for_tests()


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory):
    """Two export artifacts of the SAME schema with different weights —
    the hot-swap pair."""
    import jax

    from shifu_tpu.config import JobConfig, ModelSpec
    from shifu_tpu.data import synthetic
    from shifu_tpu.export import save_artifact
    from shifu_tpu.train import init_state, make_forward_fn

    schema = synthetic.make_schema(num_features=12)
    job = JobConfig(
        schema=schema,
        model=ModelSpec(model_type="mlp", hidden_nodes=(8, 6),
                        activations=("tanh", "leakyrelu"),
                        compute_dtype="float32"),
    ).validate()
    state = init_state(job, 12)
    root = tmp_path_factory.mktemp("serving")
    dir_a = str(root / "model_a")
    save_artifact(state.params, job, dir_a,
                  forward_fn=make_forward_fn(job, state.apply_fn))
    params_b = jax.tree_util.tree_map(lambda x: x + 0.05, state.params)
    dir_b = str(root / "model_b")
    save_artifact(params_b, job, dir_b)
    return dir_a, dir_b


def _cfg(**kw) -> ServingConfig:
    base = dict(engine="numpy", report_every_s=0.0)
    base.update(kw)
    return ServingConfig(**base)


class StubScorer:
    """Recording engine for batcher-contract tests."""

    engine = "stub"
    static_shapes = False
    num_features = 4

    def __init__(self, delay: float = 0.0, heads: int = 1):
        self.delay = delay
        self.heads = heads
        self.calls: list[tuple[float, int]] = []  # (t_called, batch_rows)
        self.closed = False

    def compute_batch(self, rows, n_valid=None):
        x = np.asarray(rows, np.float32)
        self.calls.append((time.perf_counter(), x.shape[0]))
        if self.delay:
            time.sleep(self.delay)
        # score = first feature, tiled over the head count
        return np.ascontiguousarray(
            np.repeat(x[:, :1], self.heads, axis=1))

    def close(self):
        self.closed = True


def _stub_daemon(stub, **cfg_kw) -> ScoringDaemon:
    cfg = _cfg(**cfg_kw)
    ladder = bucket_ladder(cfg.min_batch_bucket, cfg.max_batch)
    registry = ModelRegistry(
        loader=lambda _d, _e: stub,
        warm_ladder=ladder if cfg.prewarm_ladder else None)
    registry.load("stub://", model_id="default")
    return ScoringDaemon(registry=registry, config=cfg)


# ------------------------------------------------------------- batcher


def test_bucket_ladder():
    ladder = bucket_ladder(16, 4096)
    assert ladder == (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
    assert bucket_for(1, ladder) == 16
    assert bucket_for(16, ladder) == 16
    assert bucket_for(17, ladder) == 32
    assert bucket_for(5000, ladder) == 4096
    assert bucket_ladder(8, 8) == (8,)


def test_lone_request_never_waits_past_budget():
    """The latency-budget contract: with an empty queue, one request is
    dispatched at most `latency_budget_ms` after admission (plus
    scheduling slack — this is a wall-clock test on a shared host)."""
    stub = StubScorer()
    with _stub_daemon(stub, latency_budget_ms=80.0) as daemon:
        t0 = time.perf_counter()
        score = daemon.score(np.ones(4, np.float32), timeout=10)
        wait = time.perf_counter() - t0
    assert score[0] == pytest.approx(1.0)
    # budget 80ms + generous scheduling slack, but far below e.g. a 1s
    # "waits for more traffic forever" failure mode
    assert wait < 0.6, f"lone request waited {wait * 1e3:.0f}ms"
    # the dispatch honored the budget window: exactly one non-warm call
    assert [rows for _t, rows in stub.calls] == [1, 1]  # warm + request


def test_adaptive_batching_coalesces_under_load():
    """While one batch scores, arrivals accumulate and dispatch as a
    single coalesced batch — requests >> compute calls."""
    stub = StubScorer(delay=0.03)
    with _stub_daemon(stub, latency_budget_ms=10.0) as daemon:
        futs = [daemon.submit(np.full(4, i, np.float32))
                for i in range(200)]
        results = [f.result(timeout=30) for f in futs]
    for i, r in enumerate(results):
        assert r[0] == pytest.approx(float(i))
    batch_sizes = [rows for _t, rows in stub.calls[1:]]  # skip warm
    assert sum(batch_sizes) == 200
    assert len(batch_sizes) < 60  # coalescing happened
    assert max(batch_sizes) > 1


def test_padded_buckets_bound_static_shapes(artifacts):
    """A static-shape engine only ever sees bucket-ladder batch sizes
    (the jit-cache bound), and padding never leaks into results."""
    stub = StubScorer(delay=0.02)
    stub.static_shapes = True
    with _stub_daemon(stub, latency_budget_ms=10.0,
                      min_batch_bucket=8) as daemon:
        futs = [daemon.submit(np.full(4, i, np.float32))
                for i in range(37)]
        results = [f.result(timeout=30) for f in futs]
    for i, r in enumerate(results):
        assert r[0] == pytest.approx(float(i))
    rungs = bucket_ladder(8, 4096)
    ladder = set(rungs)  # pre-warm covers rungs; no 1-row warm anymore
    for _t, rows in stub.calls:
        assert rows in ladder, f"non-bucket batch shape {rows}"
    # the full-ladder pre-warm hits every rung exactly once, up front
    warm = sorted(rows for _t, rows in stub.calls[:len(rungs)])
    assert warm == sorted(rungs)

    # On a real jit engine the pre-warm bounds the compile cache to
    # exactly the ladder's executables: one compile per rung at load,
    # zero compiles while serving traffic afterwards.
    import os

    from shifu_tpu.obs import introspect

    dir_a, _ = artifacts
    if not os.path.exists(os.path.join(dir_a, "scoring.jaxexport")):
        pytest.skip("jax.export serialization unavailable")
    cfg = _cfg(engine="jax", min_batch_bucket=8, max_batch=64,
               latency_budget_ms=1.0)
    before = introspect.stats().get("jax_scorer", {}).get("compiles", 0)
    with ScoringDaemon(dir_a, config=cfg) as daemon:
        loaded = introspect.stats().get("jax_scorer", {}).get("compiles", 0)
        assert loaded - before == len(bucket_ladder(8, 64))
        for i in range(23):
            daemon.score(np.full(12, 0.1 * i, np.float32), timeout=30)
    after = introspect.stats().get("jax_scorer", {}).get("compiles", 0)
    assert after == loaded, "live traffic compiled outside the ladder"


def test_padding_not_counted_as_scored_traffic(artifacts):
    """Pad rows on a static-shape engine must not inflate
    score_rows_total / the per-row rates the serving story measures."""
    import os

    dir_a, _ = artifacts
    if not os.path.exists(os.path.join(dir_a, "scoring.jaxexport")):
        pytest.skip("jax.export serialization unavailable")
    cfg = _cfg(engine="stablehlo", min_batch_bucket=16,
               latency_budget_ms=1.0)
    with ScoringDaemon(dir_a, config=cfg) as daemon:
        for _ in range(3):
            daemon.score(np.zeros(12, np.float32), timeout=30)
    rows_total = obs.default_registry().counter(
        "score_rows_total").value(engine="stablehlo")
    # 3 requests only: the full-ladder pre-warm reports n_valid=0, so
    # warm traffic (like pad rows) never counts as scored traffic.
    assert rows_total == 3


def test_daemon_matches_direct_scorer(artifacts):
    """Batched-vs-single parity: scores through the daemon (coalesced,
    padded, micro-batched) equal the library's compute_batch to 1e-6."""
    from shifu_tpu.export import load_scorer

    dir_a, _ = artifacts
    rng = np.random.default_rng(3)
    rows = rng.standard_normal((128, 12)).astype(np.float32)
    want = load_scorer(dir_a).compute_batch(rows)
    with ScoringDaemon(dir_a, config=_cfg()) as daemon:
        futs = [daemon.submit(r) for r in rows]
        got = np.stack([f.result(timeout=30) for f in futs])
        direct = daemon.score_batch(rows)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(direct, want, atol=1e-6)


def test_submit_rejects_bad_width(artifacts):
    dir_a, _ = artifacts
    with ScoringDaemon(dir_a, config=_cfg()) as daemon:
        with pytest.raises(ValueError, match="expected 12 features"):
            daemon.submit(np.zeros(5, np.float32))


def test_overload_backpressure():
    """Beyond queue_limit the daemon rejects with ServeOverload instead
    of queueing unbounded latency."""
    gate = threading.Event()

    class Blocking(StubScorer):
        def compute_batch(self, rows, n_valid=None):
            x = np.asarray(rows, np.float32)
            self.calls.append((time.perf_counter(), x.shape[0]))
            if len(self.calls) > 1:  # let the warm call through
                gate.wait(10)
            return np.ascontiguousarray(x[:, :1])

    stub = Blocking()
    daemon = _stub_daemon(stub, queue_limit=4, latency_budget_ms=1.0)
    daemon.start()
    try:
        futs = []
        overloaded = False
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            try:
                futs.append(daemon.submit(np.zeros(4, np.float32)))
            except ServeOverload:
                overloaded = True
                break
            time.sleep(0.001)
        assert overloaded, "queue_limit never produced ServeOverload"
    finally:
        gate.set()
        daemon.stop()
    for f in futs:
        assert f.result(timeout=10) is not None
    assert daemon._snapshot()["rejected"] >= 1


# ------------------------------------------------------------- hot swap


def test_hot_swap_under_inflight_load(artifacts, tmp_path):
    """Swap while requests are in flight: no request fails, every score
    matches model A or model B exactly, post-swap scores are B's, and
    the journal records the versioned model_swap."""
    from shifu_tpu.export import load_scorer

    dir_a, dir_b = artifacts
    obs.configure(str(tmp_path / "tele"))
    rng = np.random.default_rng(7)
    rows = rng.standard_normal((400, 12)).astype(np.float32)
    want_a = load_scorer(dir_a).compute_batch(rows)
    want_b = load_scorer(dir_b).compute_batch(rows)
    assert np.abs(want_a - want_b).max() > 1e-4  # genuinely different

    daemon = ScoringDaemon(dir_a, config=_cfg(latency_budget_ms=1.0))
    daemon.start()
    futs = []
    stop = threading.Event()

    def pump():
        i = 0
        while not stop.is_set():
            futs.append((i % 400, daemon.submit(rows[i % 400])))
            i += 1
            time.sleep(0.0005)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    time.sleep(0.05)
    result = daemon.swap(dir_b)
    assert result["ok"] and result["version"] == 2
    time.sleep(0.05)
    stop.set()
    t.join(timeout=10)
    scores = [(i, f.result(timeout=30)) for i, f in futs]
    daemon.stop()
    assert len(scores) > 20
    for i, s in scores:
        ok_a = np.allclose(s, want_a[i], atol=1e-6)
        ok_b = np.allclose(s, want_b[i], atol=1e-6)
        assert ok_a or ok_b, f"request {i} matches neither model"
    # the tail of the stream is served by B
    i_last, s_last = scores[-1]
    assert np.allclose(s_last, want_b[i_last], atol=1e-6)
    obs.flush()
    events = obs.read_journal(str(tmp_path / "tele" / "journal.jsonl"))
    swaps = [e for e in events if e.get("kind") == "model_swap"]
    assert [e.get("version") for e in swaps] == [1, 2]
    assert swaps[1]["old_version"] == 1


def test_chaos_failed_swap_keeps_previous_version(artifacts, tmp_path):
    """The `runtime.serve` drill: an injected load failure on swap keeps
    version 1 serving (no dropped requests), journals chaos_inject +
    model_swap_failed, and a later swap succeeds."""
    from shifu_tpu.export import load_scorer

    dir_a, dir_b = artifacts
    obs.configure(str(tmp_path / "tele"))
    chaos.configure(plan_mod.parse_plan({
        "faults": [{"site": "runtime.serve", "at_call": 2,
                    "action": "raise"}]}))
    rng = np.random.default_rng(11)
    rows = rng.standard_normal((16, 12)).astype(np.float32)
    want_a = load_scorer(dir_a).compute_batch(rows)

    daemon = ScoringDaemon(dir_a, config=_cfg())  # call 1: initial load
    daemon.start()
    try:
        result = daemon.swap(dir_b)                # call 2: injected
        assert not result["ok"]
        assert "chaos" in result["error"].lower() \
            or "ChaosError" in result["error"]
        assert result["kept_version"] == 1
        # still serving, still model A
        got = np.stack([daemon.submit(r).result(timeout=30)
                        for r in rows])
        np.testing.assert_allclose(got, want_a, atol=1e-6)
        # recovery: the next swap attempt (call 3) installs B
        result = daemon.swap(dir_b)
        assert result["ok"] and result["version"] == 2
    finally:
        daemon.stop()
    obs.flush()
    events = obs.read_journal(str(tmp_path / "tele" / "journal.jsonl"))
    kinds = [e.get("kind") for e in events]
    assert "chaos_inject" in kinds
    assert "model_swap_failed" in kinds
    failed = next(e for e in events
                  if e.get("kind") == "model_swap_failed")
    assert failed["kept_version"] == 1
    reg = obs.default_registry()
    assert reg.counter("serve_swap_failed_total").total() >= 1


def test_swap_rejects_schema_drift(artifacts, tmp_path_factory):
    """A replacement artifact with a different feature width must not
    install — the wire schema is part of the serving contract."""
    from shifu_tpu.config import JobConfig, ModelSpec
    from shifu_tpu.data import synthetic
    from shifu_tpu.export import save_artifact
    from shifu_tpu.train import init_state

    dir_a, _ = artifacts
    schema = synthetic.make_schema(num_features=9)
    job = JobConfig(schema=schema,
                    model=ModelSpec(model_type="mlp", hidden_nodes=(4,),
                                    activations=("tanh",),
                                    compute_dtype="float32")).validate()
    state = init_state(job, 9)
    dir_w = str(tmp_path_factory.mktemp("drift") / "model_w9")
    save_artifact(state.params, job, dir_w)
    with ScoringDaemon(dir_a, config=_cfg()) as daemon:
        result = daemon.swap(dir_w)
        assert not result["ok"]
        assert "feature-width mismatch" in result["error"]
        assert result["kept_version"] == 1


def test_swap_rejects_head_count_drift():
    """A replacement whose warm score has a different head count is
    refused — the RESPONSE schema is part of the serving contract too."""
    stubs = [StubScorer(heads=1), StubScorer(heads=3),
             StubScorer(heads=1)]
    it = iter(stubs)
    registry = ModelRegistry(loader=lambda _d, _e: next(it))
    registry.load("v1://")
    with pytest.raises(ValueError, match="head-count mismatch"):
        registry.load("v2_bad://")
    assert stubs[1].closed           # the refused scorer was freed
    assert registry.current().version == 1
    registry.load("v2_ok://")        # same heads: installs
    assert registry.current().version == 2
    registry.close()


def test_registry_retires_old_version_after_drain():
    """The swapped-out scorer is closed once its in-flight work drains."""
    stubs = [StubScorer(), StubScorer()]
    it = iter(stubs)
    registry = ModelRegistry(loader=lambda _d, _e: next(it))
    registry.load("v1://")
    h1 = registry.acquire()        # simulated in-flight batch
    registry.load("v2://")         # hot swap while v1 is in flight
    assert not stubs[0].closed     # still referenced
    registry.release(h1)
    assert stubs[0].closed         # drained -> closed
    assert not stubs[1].closed
    registry.close()
    assert stubs[1].closed


# ------------------------------------------------------------- wire


def test_wire_roundtrip_f32_and_int8():
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((7, 5)).astype(np.float32)
    payload, scale, offset = wire_mod.encode_rows(rows,
                                                  dtype=wire_mod.DTYPE_F32)
    out = wire_mod.decode_rows(payload, wire_mod.DTYPE_F32, 7, 5, scale,
                               offset)
    np.testing.assert_array_equal(out, rows)
    payload, scale, offset = wire_mod.encode_rows(
        rows, dtype=wire_mod.DTYPE_INT8, clip=8.0)
    assert len(payload) == 7 * 5  # quarter the f32 bytes
    out = wire_mod.decode_rows(payload, wire_mod.DTYPE_INT8, 7, 5, scale,
                               offset)
    # one int8 grid step of error, exactly the training wire's contract
    np.testing.assert_allclose(out, np.clip(rows, -8, 8),
                               atol=(8.0 / 127.0) / 2 + 1e-6)
    with pytest.raises(wire_mod.WireError, match="payload"):
        wire_mod.decode_rows(payload[:-1], wire_mod.DTYPE_INT8, 7, 5,
                             scale, offset)


def test_wire_int8_matches_data_plane_encoder():
    """The serving wire IS the cache-v2 encoding: encode_rows equals
    data/pipeline.wire_quantize on the static grid."""
    from shifu_tpu.data.pipeline import wire_dequantize, wire_quantize

    rng = np.random.default_rng(1)
    rows = rng.standard_normal((4, 6)).astype(np.float32) * 3
    payload, scale, offset = wire_mod.encode_rows(
        rows, dtype=wire_mod.DTYPE_INT8, clip=8.0)
    q_serve = np.frombuffer(payload, np.int8).reshape(4, 6)
    q_train = wire_quantize(rows, np.float32(8.0 / 127.0), np.float32(0))
    np.testing.assert_array_equal(q_serve, q_train)
    np.testing.assert_array_equal(
        wire_dequantize(q_train, 8.0 / 127.0, 0.0),
        wire_mod.decode_rows(payload, wire_mod.DTYPE_INT8, 4, 6, scale,
                             offset))


def test_socket_server_end_to_end(artifacts):
    """TCP front-end: ping, single-row (micro-batched) and multi-row
    (direct) scoring, stats, swap, and a clean error frame."""
    from shifu_tpu.export import load_scorer

    dir_a, dir_b = artifacts
    rng = np.random.default_rng(5)
    rows = rng.standard_normal((6, 12)).astype(np.float32)
    want = load_scorer(dir_a).compute_batch(rows)
    daemon = ScoringDaemon(dir_a, config=_cfg(latency_budget_ms=1.0))
    daemon.start()
    server = wire_mod.ServeServer(daemon, port=0).start()
    try:
        with wire_mod.ServeClient(port=server.port) as client:
            assert client.ping()
            got = client.score_rows(rows, dtype=wire_mod.DTYPE_F32)
            np.testing.assert_allclose(got, want, atol=1e-6)
            one = client.score_rows(rows[0], dtype=wire_mod.DTYPE_F32)
            np.testing.assert_allclose(one, want[:1], atol=1e-6)
            stats = client.stats()
            assert stats["num_features"] == 12
            # the daemon counts a batch after it has resolved the batch's
            # futures, so a reply can reach the client before the count
            deadline = time.monotonic() + 5.0
            while stats["requests"] < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
                stats = client.stats()
            assert stats["requests"] >= 1
            with pytest.raises(wire_mod.WireError,
                               match="expected 12 features"):
                client.score_rows(np.zeros((2, 4), np.float32),
                                  dtype=wire_mod.DTYPE_F32)
            result = client.swap(dir_b)
            assert result["ok"] and result["version"] == 2
            got_b = client.score_rows(rows, dtype=wire_mod.DTYPE_F32)
            assert np.abs(got_b - want).max() > 1e-4  # it's model B now
    finally:
        server.close()
        daemon.stop()


def test_wire_swap_gate_and_payload_caps(artifacts):
    """Trust model: a server with wire swaps disabled refuses SWAP
    frames; a SCORE header whose payload length contradicts its row
    geometry is rejected before any buffer is allocated."""
    import socket
    import struct

    dir_a, dir_b = artifacts
    daemon = ScoringDaemon(dir_a, config=_cfg(latency_budget_ms=1.0))
    daemon.start()
    server = wire_mod.ServeServer(daemon, port=0,
                                  allow_swap=False).start()
    try:
        with wire_mod.ServeClient(port=server.port) as client:
            with pytest.raises(wire_mod.WireError,
                               match="wire swap disabled"):
                client.swap(dir_b)
            # still serving; registry untouched
            assert client.stats()["version"] == 1
        # geometry-contradicting SCORE header: server answers an error
        # frame without allocating the claimed payload
        raw = socket.create_connection(("127.0.0.1", server.port))
        try:
            raw.sendall(struct.pack(
                "<IHBBIIffI", wire_mod.MAGIC, wire_mod.VERSION,
                wire_mod.OP_SCORE, wire_mod.DTYPE_F32, 1, 12,
                1.0, 0.0, 1 << 29))
            hdr = wire_mod._recv_exact(raw, wire_mod._RSP.size)
            _m, _v, status, _p, _rn, _rc, plen = wire_mod._RSP.unpack(hdr)
            assert status == 1
            assert b"payload" in wire_mod._recv_exact(raw, plen)
        finally:
            raw.close()
    finally:
        server.close()
        daemon.stop()


# ------------------------------------------------------------- telemetry


def test_score_latency_shared_schema():
    """Library calls and daemon requests land in ONE histogram
    (`score_latency_seconds`), separated only by the engine label."""
    from shifu_tpu.export.scorer import (SCORE_LATENCY_BUCKETS,
                                         observe_request_latencies,
                                         observe_scoring)

    observe_scoring("numpy", 64, 0.004)
    observe_request_latencies("serve", [0.001, 0.002, 0.008, 0.02])
    hist = obs.default_registry().histogram("score_latency_seconds",
                                            buckets=SCORE_LATENCY_BUCKETS)
    assert hist.count(engine="numpy") == 1
    assert hist.count(engine="serve") == 4
    assert hist.sum(engine="serve") == pytest.approx(0.031)
    p50 = hist.quantile(0.5, engine="serve")
    assert 0.001 <= p50 <= 0.01


def test_histogram_observe_many_matches_loop():
    from shifu_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    buckets = (0.001, 0.01, 0.1)
    h1 = reg.histogram("a", buckets=buckets)
    h2 = reg.histogram("b", buckets=buckets)
    values = [0.0005, 0.001, 0.005, 0.05, 0.5, 2.0]
    for v in values:
        h1.observe(v, k="x")
    h2.observe_many(values, k="x")
    assert h1._snapshot() == {**h2._snapshot(), "type": "histogram"}
    assert h1._series[h1._series.__iter__().__next__()][0] == \
        h2._series[list(h2._series)[0]][0]
    # merge_counts agrees too
    h3 = reg.histogram("c", buckets=buckets)
    h3.merge_counts([1, 1, 1, 1], 0.1615, 4, k="x")
    assert h3.count(k="x") == 4
    with pytest.raises(ValueError, match="buckets"):
        h3.merge_counts([1, 2], 0.1, 3, k="x")


def test_serving_report_journaled(artifacts, tmp_path):
    dir_a, _ = artifacts
    obs.configure(str(tmp_path / "tele"))
    daemon = ScoringDaemon(dir_a, config=_cfg(report_every_s=0.2))
    daemon.start()
    rng = np.random.default_rng(2)
    for _ in range(3):
        daemon.score(rng.standard_normal(12).astype(np.float32),
                     timeout=10)
    time.sleep(0.45)
    daemon.stop()
    obs.flush()
    events = obs.read_journal(str(tmp_path / "tele" / "journal.jsonl"))
    reports = [e for e in events if e.get("kind") == "serving_report"]
    assert reports, "no serving_report journaled"
    final = reports[-1]
    assert final["requests"] == 3
    assert final["engine"] == "numpy"
    assert final.get("final") is True
    windowed = [r for r in reports if "scores_per_sec" in r]
    assert windowed, "no windowed serving_report"
    reg = obs.default_registry()
    assert reg.counter("serve_requests_total").total() == 3


# ------------------------------------------------------------- loadtest


def test_loadtest_smoke_python_engine(artifacts, tmp_path):
    """Open-loop smoke on the numpy engine: every admitted request
    completes, the report carries rate + exact percentiles, and the run
    journals a loadtest_report."""
    from shifu_tpu.runtime import loadtest as lt

    dir_a, _ = artifacts
    obs.configure(str(tmp_path / "tele"))
    report = lt.run_loadtest(dir_a, engine="numpy", rate=3000,
                             duration=0.5, senders=1)
    assert report["mode"] == "inproc"
    assert report["submitted"] >= 1000
    assert report["completed"] == report["submitted"]
    assert report["errors"] == 0
    assert report["achieved_scores_per_sec"] > 500
    assert report["p50_ms"] is not None
    assert report["p99_ms"] >= report["p50_ms"]
    assert report["engine"] == "numpy"
    obs.flush()
    events = obs.read_journal(str(tmp_path / "tele" / "journal.jsonl"))
    assert any(e.get("kind") == "loadtest_report" for e in events)


def test_loadtest_socket_mode(artifacts):
    dir_a, _ = artifacts
    from shifu_tpu.runtime import loadtest as lt

    daemon = ScoringDaemon(dir_a, config=_cfg(latency_budget_ms=1.0))
    daemon.start()
    server = wire_mod.ServeServer(daemon, port=0).start()
    try:
        report = lt.run_loadtest(connect=f"127.0.0.1:{server.port}",
                                 rate=300, duration=0.4, senders=2)
        assert report["mode"] == "socket"
        assert report["completed"] > 0
        assert report["errors"] == 0
        assert report["p99_ms"] is not None
    finally:
        server.close()
        daemon.stop()


def test_poisson_schedule_is_open_loop():
    from shifu_tpu.runtime.loadtest import _poisson_schedule

    rng = np.random.default_rng(0)
    sched = _poisson_schedule(1000.0, 2.0, rng)
    assert len(sched) == 2000
    assert (np.diff(sched) > 0).all()
    # mean inter-arrival ~ 1/rate
    assert np.diff(sched).mean() == pytest.approx(1e-3, rel=0.15)


# ------------------------------------------------------------- config/CLI


def test_serving_config_validation():
    ServingConfig().validate()
    with pytest.raises(ConfigError, match="engine"):
        ServingConfig(engine="tensorflow").validate()
    with pytest.raises(ConfigError, match="latency_budget_ms"):
        ServingConfig(latency_budget_ms=0).validate()
    with pytest.raises(ConfigError, match="min_batch_bucket"):
        ServingConfig(min_batch_bucket=512, max_batch=64).validate()
    with pytest.raises(ConfigError, match="port"):
        ServingConfig(port=99999).validate()


def test_serving_config_from_xml_keys():
    from shifu_tpu.utils import xmlconfig

    cfg = xmlconfig.serving_config_from_conf({
        xmlconfig.KEY_SERVING_ENGINE: "Numpy",
        xmlconfig.KEY_SERVING_LATENCY_BUDGET_MS: "3.5",
        xmlconfig.KEY_SERVING_MAX_BATCH: "1024",
        xmlconfig.KEY_SERVING_QUEUE_LIMIT: "5000",
        xmlconfig.KEY_SERVING_WORKERS: "2",
        xmlconfig.KEY_SERVING_PORT: "9000",
        xmlconfig.KEY_SERVING_HOST: "0.0.0.0",
    })
    assert cfg.engine == "numpy"
    assert cfg.latency_budget_ms == 3.5
    assert cfg.max_batch == 1024
    assert cfg.queue_limit == 5000
    assert cfg.workers == 2
    assert cfg.port == 9000
    assert cfg.host == "0.0.0.0"
    # untouched keys keep their defaults; no keys -> the base object
    assert cfg.min_batch_bucket == ServingConfig().min_batch_bucket
    base = ServingConfig(engine="jax")
    assert xmlconfig.serving_config_from_conf({}, base) is base


def test_cli_loadtest_end_to_end(artifacts, capsys):
    from shifu_tpu.launcher import cli

    dir_a, _ = artifacts
    rc = cli.main(["loadtest", "--model", dir_a, "--engine", "numpy",
                   "--rate", "2000", "--duration", "0.3",
                   "--senders", "1", "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["completed"] > 0
    assert report["p99_ms"] is not None
    # contradictory / missing target args fail cleanly
    assert cli.main(["loadtest", "--rate", "10"]) == 1


def test_cli_serve_parser_and_config_layering(tmp_path):
    from shifu_tpu.launcher import cli
    from shifu_tpu.utils import xmlconfig

    xml = tmp_path / "global.xml"
    xmlconfig.write_configuration_xml(
        {xmlconfig.KEY_SERVING_LATENCY_BUDGET_MS: "7.0",
         xmlconfig.KEY_SERVING_MAX_BATCH: "512"}, str(xml))
    args = cli.build_parser().parse_args(
        ["serve", "/tmp/model", "--engine", "numpy", "--port", "0",
         "--globalconfig", str(xml), "--budget-ms", "4"])
    cfg = cli._serving_config(args)
    assert cfg.engine == "numpy"
    assert cfg.port == 0
    assert cfg.latency_budget_ms == 4.0   # flag beats XML
    assert cfg.max_batch == 512           # XML beats default
