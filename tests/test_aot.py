"""AOT serving-executable pack tests (export/aot.py, the `aot` engine
tier in runtime/serve.py — docs/SERVING.md "Cold start & AOT pack").

Covers the ISSUE-19 acceptance seams:

- pack + load roundtrip: `save_artifact(aot_pack=True)` writes the
  compiled bucket grid, `try_load_aot` deserializes it with ZERO live
  XLA compiles, and scores are bit-identical to the jit scorer (same
  forward, same sigmoid — not merely close);
- fingerprint-mismatch fallback: a pack stamped with a different jaxlib
  version journals `aot_fallback` and the daemon transparently serves
  correct scores through the jit tier — never a refused load;
- corrupt-pack digest guard: a flipped byte in a bucket file is caught
  by the per-file blake2b check (local load) AND by the fleet sync
  plane's digest verify (`fleet.sync` corrupt drill — the pack rides
  `sync_manifest.json` like any other artifact file);
- hot-swap with an AOT-packed v2 under in-flight load: no dropped
  requests, the tail of the stream is v2's scores, `aot_load` journaled;
- jax-masked rendering: `top --once --json` and `profile --json` show
  the `aot_load` / `aot_fallback` rows without importing jax.
"""

import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from shifu_tpu import chaos, obs
from shifu_tpu.chaos import plan as plan_mod
from shifu_tpu.config.schema import ServingConfig
from shifu_tpu.export import aot as aot_mod
from shifu_tpu.obs import introspect
from shifu_tpu.runtime.serve import ScoringDaemon, bucket_ladder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PACK_BUCKETS = (16, 32, 64)


@pytest.fixture(autouse=True)
def _clean_chaos_and_obs():
    chaos.reset_for_tests()
    obs.reset_for_tests()
    yield
    chaos.reset_for_tests()
    obs.reset_for_tests()


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_compile_cache():
    """This file's packs are made and loaded with the persistent compile
    cache off.  A test earlier on the same worker may have left it on (a
    benchmark driver run in process does), and on the CPU an executable
    that was loaded from a warm cache does not serialize whole: the pack
    made from it fails at its first call."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def packed(tmp_path_factory):
    """Two AOT-packed artifacts of the same schema with different
    weights (the hot-swap pair), packed over PACK_BUCKETS."""
    jax = pytest.importorskip("jax")

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
    root = tmp_path_factory.mktemp("aot")
    dir_a = str(root / "model_a")
    save_artifact(state.params, job, dir_a,
                  forward_fn=make_forward_fn(job, state.apply_fn),
                  aot_pack=True, aot_buckets=PACK_BUCKETS)
    params_b = jax.tree_util.tree_map(lambda x: x + 0.05, state.params)
    dir_b = str(root / "model_b")
    save_artifact(params_b, job, dir_b,
                  forward_fn=make_forward_fn(job, state.apply_fn),
                  aot_pack=True, aot_buckets=PACK_BUCKETS)
    if not aot_mod.has_pack(dir_a):
        pytest.skip("executable serialization unavailable on this build")
    return dir_a, dir_b


def _cfg(**kw) -> ServingConfig:
    base = dict(engine="aot", report_every_s=0.0,
                min_batch_bucket=16, max_batch=64)
    base.update(kw)
    return ServingConfig(**base)


def _jit_scorer(export_dir):
    from shifu_tpu.export.scorer import JaxScorer
    return JaxScorer(export_dir)


def _events(tmp_path):
    return obs.read_journal(str(tmp_path / "tele" / "journal.jsonl"))


def _jit_compiles() -> int:
    return introspect.stats().get("jax_scorer", {}).get("compiles", 0)


# ----------------------------------------------------- pack + load tier


def test_pack_layout_and_manifest(packed):
    dir_a, _ = packed
    d = aot_mod.pack_dir(dir_a)
    with open(os.path.join(d, aot_mod.AOT_MANIFEST)) as f:
        manifest = json.load(f)
    assert manifest["format"] == aot_mod.AOT_FORMAT
    assert tuple(manifest["buckets"]) == PACK_BUCKETS
    assert manifest["num_features"] == 12
    assert manifest["algo"] == "blake2b-16"
    host = aot_mod.host_fingerprint()
    for field in ("jax_version", "jaxlib_version", "platform",
                  "device_kind"):
        assert manifest[field] == host[field]
    # one serialized executable per rung, each digest-pinned
    names = sorted(manifest["files"])
    assert names == [f"bucket-{b:06d}.bin" for b in PACK_BUCKETS]
    for name, want in manifest["files"].items():
        with open(os.path.join(d, name), "rb") as f:
            assert aot_mod._digest(f.read()) == want
    # the pack rides the sync plane: every aot/ file is in the
    # exporter's sync manifest with a matching digest
    from shifu_tpu.runtime.fleet import read_sync_manifest
    sync = read_sync_manifest(dir_a)["files"]
    for name, want in manifest["files"].items():
        assert sync[os.path.join(aot_mod.AOT_DIR, name)] == want
    assert os.path.join(aot_mod.AOT_DIR, aot_mod.AOT_MANIFEST) in sync


def test_load_bit_identical_to_jit_and_zero_compiles(packed, tmp_path):
    """The tentpole contract: deserialized executables answer with the
    jit scorer's EXACT bits, without a single live XLA compile."""
    dir_a, _ = packed
    obs.configure(str(tmp_path / "tele"))
    rng = np.random.default_rng(3)
    batches = [rng.standard_normal((n, 12)).astype(np.float32)
               for n in (1, 16, 40, 64, 150)]  # exact rung, padded, chunked
    want = _jit_scorer(dir_a)
    expected = [want.compute_batch(rows) for rows in batches]

    before = _jit_compiles()
    scorer = aot_mod.try_load_aot(dir_a)
    assert scorer is not None and scorer.engine == "aot"
    assert scorer.buckets == PACK_BUCKETS
    for rows, exp in zip(batches, expected):
        got = scorer.compute_batch(rows)
        assert got.shape == (rows.shape[0], 1)
        assert np.array_equal(got, exp)
    # the AOT path never touched the jit tier
    assert _jit_compiles() == before
    obs.flush()
    evs = _events(tmp_path)
    loads = [e for e in evs if e["kind"] == "aot_load"]
    assert len(loads) == 1
    assert loads[0]["buckets"] == list(PACK_BUCKETS)
    assert sorted(loads[0]["bucket_ms"]) == [str(b) for b in PACK_BUCKETS]
    assert loads[0]["wall_ms"] > 0
    assert not [e for e in evs if e["kind"] == "aot_fallback"]


def test_daemon_aot_engine_serves_without_compiling(packed, tmp_path):
    dir_a, _ = packed
    obs.configure(str(tmp_path / "tele"))
    rng = np.random.default_rng(5)
    rows = rng.standard_normal((40, 12)).astype(np.float32)
    want = _jit_scorer(dir_a).compute_batch(rows)
    before = _jit_compiles()
    with ScoringDaemon(dir_a, config=_cfg()) as daemon:
        got = daemon.score_batch(rows)
    assert np.allclose(got, want, atol=1e-6)
    assert _jit_compiles() == before  # pre-warm + traffic: all AOT


# ------------------------------------------------- fallback ladder


def _tamper_manifest(export_dir, **fields):
    path = os.path.join(aot_mod.pack_dir(export_dir), aot_mod.AOT_MANIFEST)
    with open(path) as f:
        manifest = json.load(f)
    manifest.update(fields)
    with open(path, "w") as f:
        json.dump(manifest, f)


def test_fingerprint_mismatch_falls_back_to_jit(packed, tmp_path):
    """A pack from the wrong toolchain (jaxlib version drift) journals
    `aot_fallback` and the daemon serves CORRECT scores via jit — a
    stale pack degrades, it never refuses a load."""
    dir_a, _ = packed
    stale = str(tmp_path / "stale")
    shutil.copytree(dir_a, stale)
    _tamper_manifest(stale, jaxlib_version="9.9.9")
    obs.configure(str(tmp_path / "tele"))

    assert aot_mod.try_load_aot(stale) is None
    before = _jit_compiles()
    rng = np.random.default_rng(7)
    rows = rng.standard_normal((40, 12)).astype(np.float32)
    with ScoringDaemon(stale, config=_cfg()) as daemon:
        got = daemon.score_batch(rows)
    assert np.array_equal(got, _jit_scorer(dir_a).compute_batch(rows))
    assert _jit_compiles() > before  # the jit tier really took over
    obs.flush()
    evs = _events(tmp_path)
    falls = [e for e in evs if e["kind"] == "aot_fallback"]
    assert falls and all("jaxlib_version" in e["reason"] for e in falls)
    assert "9.9.9" in falls[0]["reason"]
    assert not [e for e in evs if e["kind"] == "aot_load"]


def test_corrupt_bucket_file_digest_guard(packed, tmp_path):
    dir_a, _ = packed
    bad = str(tmp_path / "bad")
    shutil.copytree(dir_a, bad)
    victim = os.path.join(aot_mod.pack_dir(bad),
                          aot_mod._bucket_file(PACK_BUCKETS[1]))
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(victim, "wb") as f:
        f.write(bytes(blob))
    obs.configure(str(tmp_path / "tele"))
    assert aot_mod.try_load_aot(bad) is None
    obs.flush()
    falls = [e for e in _events(tmp_path) if e["kind"] == "aot_fallback"]
    assert len(falls) == 1
    assert "digest mismatch" in falls[0]["reason"]
    assert aot_mod._bucket_file(PACK_BUCKETS[1]) in falls[0]["reason"]


def test_missing_pack_is_a_quiet_single_fallback(packed, tmp_path):
    """engine="aot" on a packless artifact: one journaled fallback with
    the missing-manifest reason, then jit serves."""
    dir_a, _ = packed
    bare = str(tmp_path / "bare")
    shutil.copytree(dir_a, bare)
    shutil.rmtree(aot_mod.pack_dir(bare))
    obs.configure(str(tmp_path / "tele"))
    with ScoringDaemon(bare, config=_cfg()) as daemon:
        out = daemon.score(np.zeros(12, np.float32), timeout=30)
    assert out.shape == (1,)
    obs.flush()
    falls = [e for e in _events(tmp_path) if e["kind"] == "aot_fallback"]
    assert len(falls) == 1
    assert "manifest.json missing" in falls[0]["reason"]


# ------------------------------------------- fleet sync digest drill


@pytest.mark.chaos
def test_pack_rides_sync_and_corrupt_pull_is_caught(packed, tmp_path):
    """`fleet.sync` corrupt drill over an AOT-packed artifact: the
    per-host pull digest-verifies the aot/ files, a corrupted pull
    raises SyncError (never publishes), and the retried pull lands a
    copy whose pack deserializes on this host."""
    from shifu_tpu.runtime import fleet as fleet_mod
    from shifu_tpu.runtime.fleet import SyncError, sync_artifact

    dir_a, _ = packed
    obs.configure(str(tmp_path / "tele"))
    cache = str(tmp_path / "hostcache")
    chaos.configure(plan_mod.parse_plan({"faults": [
        {"site": fleet_mod.SYNC_SITE, "every": 1, "max_times": 1,
         "action": "corrupt"}]}))
    with pytest.raises(SyncError):
        sync_artifact(dir_a, cache, 1)
    assert not os.path.isdir(os.path.join(cache, "gen-000001"))
    # fault exhausted: the retry verifies and publishes, pack included
    dest = sync_artifact(dir_a, cache, 1)
    assert aot_mod.has_pack(dest)
    scorer = aot_mod.try_load_aot(dest)
    assert scorer is not None
    rows = np.ones((4, 12), np.float32)
    assert np.array_equal(scorer.compute_batch(rows),
                          _jit_scorer(dir_a).compute_batch(rows))


# --------------------------------------------- hot swap under load


def test_hot_swap_to_aot_packed_v2_under_load(packed, tmp_path):
    """Swap to an AOT-packed v2 while requests are in flight: no
    request fails, every score matches A or B exactly, the tail is B's,
    and the new version loaded through the AOT tier (aot_load, zero new
    jit compiles after the swap)."""
    dir_a, dir_b = packed
    obs.configure(str(tmp_path / "tele"))
    rng = np.random.default_rng(11)
    rows = rng.standard_normal((200, 12)).astype(np.float32)
    want_a = _jit_scorer(dir_a).compute_batch(rows)
    want_b = _jit_scorer(dir_b).compute_batch(rows)
    assert np.abs(want_a - want_b).max() > 1e-4

    daemon = ScoringDaemon(dir_a, config=_cfg(latency_budget_ms=1.0))
    daemon.start()
    futs = []
    stop = threading.Event()

    def pump():
        i = 0
        while not stop.is_set():
            futs.append((i % 200, daemon.submit(rows[i % 200])))
            i += 1
            time.sleep(0.0005)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    time.sleep(0.05)
    before = _jit_compiles()
    result = daemon.swap(dir_b)
    assert result["ok"] and result["version"] == 2
    time.sleep(0.05)
    stop.set()
    t.join(timeout=10)
    scores = [(i, f.result(timeout=30)) for i, f in futs]
    daemon.stop()
    assert _jit_compiles() == before  # v2 landed via AOT, no jit
    assert len(scores) > 20
    for i, s in scores:
        assert (np.allclose(s, want_a[i], atol=1e-6)
                or np.allclose(s, want_b[i], atol=1e-6)), \
            f"request {i} matches neither model"
    i_last, s_last = scores[-1]
    assert np.allclose(s_last, want_b[i_last], atol=1e-6)
    obs.flush()
    evs = _events(tmp_path)
    loads = [e for e in evs if e["kind"] == "aot_load"]
    assert len(loads) == 2  # v1 at start + v2 on swap
    swaps = [e for e in evs if e.get("kind") == "model_swap"]
    assert [e.get("version") for e in swaps] == [1, 2]


# ------------------------------------------------- jax-masked render


def test_top_and_profile_render_aot_rows_jax_masked(tmp_path):
    """The aot_load / aot_fallback journal rows render in `top` and
    `profile` from a process where jax is masked out — the operator's
    laptop view needs no accelerator toolchain."""
    from shifu_tpu.obs import render as render_mod

    tele = tmp_path / "tele"
    obs.configure(str(tele))
    obs.event("serve_start", path="/x", port=0, engine="aot")
    obs.event("aot_load", path="/x", buckets=[16, 32, 64],
              bucket_ms={"16": 1.0, "32": 1.2, "64": 2.0}, wall_ms=4.2,
              num_features=12, num_heads=1)
    obs.event("aot_fallback", path="/y",
              reason="fingerprint mismatch: jaxlib_version: "
                     "pack='9.9.9' host='0.0.0'")
    obs.event("model_prewarm", model="default", engine="aot",
              buckets=[16, 32, 64],
              bucket_ms={"16": 0.3, "32": 0.4, "64": 0.6}, wall_ms=1.3)
    obs.flush()

    # in-process render first: the summaries carry the rows
    top = render_mod.top_summary(str(tele))
    assert top["mode"] == "serving"
    assert top["aot"]["loads"] == 1
    assert top["aot"]["fallbacks"] == 1
    assert top["aot"]["buckets"] == [16, 32, 64]
    assert top["aot"]["load_ms"] == 4.2
    assert "jaxlib_version" in top["aot"]["last_fallback_reason"]
    text = render_mod.render_top_text(top)
    assert "zero-compile load(s)" in text
    assert "FALLBACK(s) to jit" in text
    prof = render_mod.profile_summary(str(tele))
    assert prof["aot"]["loads"] == 1
    assert prof["aot"]["fallbacks"] == 1
    assert prof["aot"]["prewarm"]["buckets"] == [16, 32, 64]
    ptext = render_mod.render_profile_text(prof)
    assert "aot executables:" in ptext
    assert "pre-warm [aot]" in ptext

    # jax-masked subprocess: the CLI spellings of the same two views
    mask = ("import sys, json\n"
            "sys.modules['jax'] = None\n"
            "from shifu_tpu.launcher.cli import main\n")
    out = subprocess.run(
        [sys.executable, "-c", mask +
         f"sys.exit(main(['top', {str(tele)!r}, '--once', '--json']))"],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert out.returncode == 0, out.stderr
    frame = json.loads(out.stdout)
    assert frame["aot"]["loads"] == 1
    assert frame["aot"]["fallbacks"] == 1
    out = subprocess.run(
        [sys.executable, "-c", mask +
         f"sys.exit(main(['profile', {str(tele)!r}, '--json']))"],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert out.returncode == 0, out.stderr
    prof = json.loads(out.stdout)
    assert prof["aot"]["loads"] == 1
    assert "jaxlib_version" in prof["aot"]["last_fallback"]["reason"]
