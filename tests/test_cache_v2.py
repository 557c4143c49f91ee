"""Columnar cache v2 + parallel cold ingest (ISSUE 5).

Pins: (1) v2 entries store the wire format (int8 features, compact
u8/elided target+weight) yet reconstruct BIT-IDENTICAL arrays — batches
with cache v2 on equal cache off for the staged and per-batch tiers,
including across a kill+resume; (2) the cache-key invalidation matrix
(format version, wire grid, schema projection, source mtime/size,
concurrent writers) never serves stale bytes; (3) legacy v1 entries are
transparently upgraded, not orphaned; (4) a corrupted/chaos-faulted v2
entry falls back to re-parse and journals `cache_fallback`; (5) the
`shifu-tpu cache` subcommand lists and prunes; (6) the ingest pool's
`ingest_report` schema and config keys.
"""

import dataclasses
import json
import os
import threading

import numpy as np
import pytest

from shifu_tpu import chaos, obs
from shifu_tpu.chaos import plan as plan_mod
from shifu_tpu.config import (ConfigError, DataConfig, JobConfig, ModelSpec,
                              OptimizerConfig, TrainConfig)
from shifu_tpu.data import cache as cache_lib
from shifu_tpu.data import load_datasets, pipeline as pipe, synthetic


@pytest.fixture(autouse=True)
def _clean_chaos_and_obs():
    chaos.reset_for_tests()
    obs.reset_for_tests()
    yield
    chaos.reset_for_tests()
    obs.reset_for_tests()


def _arrays(n=64, f=5, u8_target=True, unit_weight=True, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "features": rng.standard_normal((n, f)).astype(np.float32),
        "target": ((rng.random((n, 1)) < 0.5).astype(np.float32)
                   if u8_target else
                   rng.random((n, 1)).astype(np.float32) + 0.25),
        "weight": (np.ones((n, 1), np.float32) if unit_weight
                   else rng.random((n, 1)).astype(np.float32) + 0.5),
        "valid_mask": rng.random(n) < 0.1,
    }


NAME = "abcd1234abcd1234-ffff0000ffff0000-p0123456789abcdef.npd"


# ------------------------------------------------------ v2 entry format

def test_v2_entry_compact_layout_and_exact_roundtrip(tmp_path):
    """Binary labels store as uint8 and an all-ones weight column is
    elided — ¼ / 0 of their float32 bytes — yet the load reconstructs
    byte-identical float32 arrays (the parity contract)."""
    cdir = str(tmp_path / "c")
    arrays = _arrays()
    cache_lib.write_projected_entry(cdir, NAME, dict(arrays))
    entry = os.path.join(cdir, NAME)
    manifest = json.load(open(os.path.join(entry, "entry.json")))
    assert manifest["version"] == cache_lib.CACHE_FORMAT_VERSION == 2
    assert manifest["target_dtype"] == "uint8"
    assert manifest["weight_mode"] == "elided"
    stored_t = np.load(os.path.join(entry, "target.npy"))
    assert stored_t.dtype == np.uint8
    assert not os.path.exists(os.path.join(entry, "weight.npy"))

    out = cache_lib.load_projected_entry(cdir, NAME)
    for k in ("features", "target", "weight", "valid_mask"):
        assert out[k].dtype == arrays[k].dtype
        assert np.asarray(out[k]).tobytes() == arrays[k].tobytes()
    assert not out["features"].flags.writeable  # mmap'd read-only


def test_v2_entry_noncompactable_columns_stay_float32(tmp_path):
    """Fractional targets / non-unit weights must NOT compact — stored
    f32, served f32, byte-identical."""
    cdir = str(tmp_path / "c")
    arrays = _arrays(u8_target=False, unit_weight=False)
    cache_lib.write_projected_entry(cdir, NAME, dict(arrays))
    entry = os.path.join(cdir, NAME)
    manifest = json.load(open(os.path.join(entry, "entry.json")))
    assert manifest["target_dtype"] == "float32"
    assert manifest["weight_mode"] == "float32"
    out = cache_lib.load_projected_entry(cdir, NAME)
    for k in ("target", "weight"):
        assert np.asarray(out[k]).tobytes() == arrays[k].tobytes()


def test_v2_entry_int8_and_bf16_features(tmp_path):
    """Wire-format features round-trip: int8 directly, bf16 via the
    tagged uint16 member (npy has no bf16)."""
    import ml_dtypes
    cdir = str(tmp_path / "c")
    a = _arrays()
    a["features"] = np.arange(-64, 64, dtype=np.int8).reshape(64, 2)
    cache_lib.write_projected_entry(cdir, NAME, dict(a))
    out = cache_lib.load_projected_entry(cdir, NAME)
    assert out["features"].dtype == np.int8
    np.testing.assert_array_equal(out["features"], a["features"])

    b = _arrays()
    b["features"] = b["features"].astype(ml_dtypes.bfloat16)
    name2 = NAME[:-5] + "0.npd"
    cache_lib.write_projected_entry(cdir, name2, dict(b))
    out2 = cache_lib.load_projected_entry(cdir, name2)
    assert out2["features"].dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(out2["features"].view(np.uint16),
                                  b["features"].view(np.uint16))


def test_cache_format_1_pins_legacy_layout(tmp_path):
    """DataConfig.cache_format=1 writes v1-keyed entries in the legacy
    column layout (raw float32 target, weight never elided — byte-compat
    with the pre-v2 reader, which ignores the manifest), still loads them
    hot, and the manifest keeps them classifiable as LIVE: `--prune` must
    not reclaim a pinned job's entries as pre-v2 leftovers."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(300, schema, seed=3)
    paths = synthetic.write_files(rows, str(tmp_path / "d"), num_files=2)
    cdir = str(tmp_path / "c")
    cfg1 = DataConfig(paths=tuple(paths), cache_dir=cdir, cache_format=1)
    t1, v1 = load_datasets(schema, cfg1)
    entries = [e for e in os.listdir(cdir) if e.endswith(".npd")]
    assert entries
    for e in entries:
        with open(os.path.join(cdir, e, "entry.json")) as f:
            manifest = json.load(f)
        assert manifest["version"] == 1
        # legacy column layout: no compact encoding at version 1
        assert os.path.exists(os.path.join(cdir, e, "weight.npy"))
        assert np.load(os.path.join(cdir, e, "target.npy")).dtype \
            == np.float32
    # live pinned entries classify ok and survive a prune
    recs = {r["name"]: r for r in cache_lib.scan_cache(cdir)
            if r["name"].endswith(".npd")}
    assert all(r["status"] == "ok" and r["version"] == 1
               for r in recs.values())
    assert cache_lib.prune_cache(cdir) == []
    assert pipe.projected_cache_complete(schema, cfg1)
    t2, _v2 = load_datasets(schema, cfg1)  # served hot from the v1 layout
    assert t2.features.tobytes() == t1.features.tobytes()
    with pytest.raises(ConfigError, match="cache_format"):
        DataConfig(cache_format=3).validate()


# --------------------------------------------------- invalidation matrix

def _pname(path, schema, feature_dtype="float32", version=None,
           valid_ratio=0.1, split_seed=0, file_idx=0):
    return cache_lib.projected_entry_name(
        path, "|", file_idx, schema, valid_ratio, split_seed,
        feature_dtype, version=version)


def test_invalidation_matrix_key_changes(tmp_path):
    """Every axis of the cache key produces a distinct entry name:
    format-version bump, wire-grid change (the clip rides in the
    feature_dtype string), schema projection change, and source
    mtime/size change — a changed input can never be served stale."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(100, schema, seed=1)
    (path,) = synthetic.write_files(rows, str(tmp_path / "d"), num_files=1)

    base = _pname(path, schema, "int8c8")
    assert base != _pname(path, schema, "int8c8", version=1)   # format bump
    assert base != _pname(path, schema, "int8c4")              # wire grid
    schema2 = dataclasses.replace(
        schema, selected_indices=schema.selected_indices[:-1])
    assert base != _pname(path, schema2, "int8c8")             # projection
    assert base != _pname(path, schema, "int8c8", valid_ratio=0.2)
    assert base != _pname(path, schema, "int8c8", split_seed=7)
    assert base != _pname(path, schema, "int8c8", file_idx=1)
    os.utime(path, ns=(123456789, 123456789))                  # mtime
    assert base != _pname(path, schema, "int8c8")


def test_wire_grid_change_requantizes_not_stale(tmp_path):
    """Functional stale-serve check: populate the cache under one int8
    clip, change the grid, and the next load must requantize — identical
    to a cache-off load under the new grid, never the old grid's bytes."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(400, schema, seed=2)
    paths = synthetic.write_files(rows, str(tmp_path / "d"), num_files=2)
    cdir = str(tmp_path / "c")

    def load(clip, cache):
        cfg = DataConfig(paths=tuple(paths), cache_dir=cache,
                         wire_dtype="int8", wire_int8_clip=clip)
        return load_datasets(schema, cfg, feature_dtype=f"int8c{clip:g}")

    t8, _ = load(8.0, cdir)          # populates under clip=8
    t4_cached, _ = load(4.0, cdir)   # different grid: must rebuild
    t4_fresh, _ = load(4.0, None)
    assert t4_cached.features.dtype == np.int8
    assert t4_cached.features.tobytes() == t4_fresh.features.tobytes()
    assert t4_cached.features.tobytes() != t8.features.tobytes()


def test_source_change_serves_fresh(tmp_path):
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(200, schema, seed=4)
    (path,) = synthetic.write_files(rows, str(tmp_path / "d"), num_files=1)
    cdir = str(tmp_path / "c")
    cfg = DataConfig(paths=(path,), cache_dir=cdir)
    t0, v0 = load_datasets(schema, cfg)
    n0 = t0.num_rows + v0.num_rows
    rows2 = synthetic.make_rows(300, schema, seed=5)
    synthetic.write_files(rows2, str(tmp_path / "d"), num_files=1)
    os.utime(path, ns=(7, 7))
    t1, v1 = load_datasets(schema, cfg)
    assert t1.num_rows + v1.num_rows == 300 != n0


def test_concurrent_writers_race_on_publish(tmp_path):
    """Two writers racing on the same entry (projected: one-rename
    publish; raw: os.replace) — the loser discards its tmp, the entry
    stays valid, nothing leaks."""
    cdir = str(tmp_path / "c")
    arrays = _arrays(n=512)
    errs = []

    def write():
        try:
            cache_lib.write_projected_entry(cdir, NAME, dict(arrays))
        except Exception as e:  # write_projected_entry must never raise
            errs.append(e)

    threads = [threading.Thread(target=write) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    out = cache_lib.load_projected_entry(cdir, NAME)
    assert out is not None
    assert np.asarray(out["features"]).tobytes() == \
        arrays["features"].tobytes()
    leftovers = [e for e in os.listdir(cdir) if e.endswith(".tmp")]
    assert leftovers == []

    # raw tier: concurrent read_file_cached misses race through os.replace
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(200, schema, seed=6)
    (path,) = synthetic.write_files(rows, str(tmp_path / "d"), num_files=1)
    rdir = str(tmp_path / "raw")
    results = []
    threads = [threading.Thread(
        target=lambda: results.append(
            cache_lib.read_file_cached(path, cache_dir=rdir)))
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    hit = cache_lib.read_file_cached(path, cache_dir=rdir)
    for r in results:
        np.testing.assert_array_equal(np.asarray(r), np.asarray(hit))


# ------------------------------------------------------- v1 -> v2 upgrade

def test_legacy_v1_projected_entry_upgraded_in_place(tmp_path):
    """A v1-keyed projected entry serves once through the old path, is
    rewritten as v2, and the v1 bytes are pruned — upgraded, never
    orphaned (ISSUE 5 satellite fix)."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(300, schema, seed=7)
    paths = synthetic.write_files(rows, str(tmp_path / "d"), num_files=2)
    cdir = str(tmp_path / "c")
    cfg_v1 = DataConfig(paths=tuple(paths), cache_dir=cdir, cache_format=1)
    cfg = DataConfig(paths=tuple(paths), cache_dir=cdir)
    t1, _ = load_datasets(schema, cfg_v1)          # populate v1 layout
    v1_entries = sorted(e for e in os.listdir(cdir) if e.endswith(".npd"))
    assert v1_entries
    # the default-format job still counts the v1 layout as hot...
    assert pipe.projected_cache_complete(schema, cfg)
    t2, _ = load_datasets(schema, cfg)             # serve + upgrade
    assert t2.features.tobytes() == t1.features.tobytes()
    after = sorted(e for e in os.listdir(cdir) if e.endswith(".npd"))
    assert after and after != v1_entries           # v2 names, v1 pruned
    for e in after:
        assert os.path.exists(os.path.join(cdir, e, "entry.json"))
    assert obs.default_registry().counter(
        "data_cache_upgraded_total").total() == 2
    # ...and a third load is a pure v2 hit
    obs.reset_for_tests()
    t3, _ = load_datasets(schema, cfg)
    assert t3.features.tobytes() == t1.features.tobytes()
    reg = obs.default_registry()
    assert reg.counter("data_cache_hits_total").total() == 2
    assert reg.counter("data_cache_misses_total").total() == 0


def test_legacy_v1_raw_entry_upgraded(tmp_path, monkeypatch):
    """A v1-keyed raw .npy serves without re-parse and is republished
    under the v2 key (the v1 file pruned)."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(100, schema, seed=8)
    (path,) = synthetic.write_files(rows, str(tmp_path / "d"), num_files=1)
    cdir = str(tmp_path / "c")
    parsed = cache_lib.read_file_cached(path, cache_dir=None)
    v1name = cache_lib.cache_entry_name(path, "|", version=1)
    os.makedirs(cdir)
    np.save(os.path.join(cdir, v1name), parsed)

    import shifu_tpu.data.reader as reader_mod
    monkeypatch.setattr(reader_mod, "read_file", lambda *a, **k: (_ for _ in
                        ()).throw(AssertionError("v1 hit must not parse")))
    served = cache_lib.read_file_cached(path, cache_dir=cdir)
    np.testing.assert_array_equal(np.asarray(served), parsed)
    v2name = cache_lib.cache_entry_name(path, "|")
    assert os.path.exists(os.path.join(cdir, v2name))
    assert not os.path.exists(os.path.join(cdir, v1name))


def test_mixed_format_jobs_share_cache_without_eviction(tmp_path):
    """A v1-pinned job (cache_format=1) and a default-v2 job sharing one
    cache dir must not mutually prune each other's live entries into a
    perpetual re-parse cycle: after one upgrade round-trip, both formats
    coexist and both jobs hit."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(300, schema, seed=21)
    paths = synthetic.write_files(rows, str(tmp_path / "d"), num_files=2)
    cdir = str(tmp_path / "c")
    cfg1 = DataConfig(paths=tuple(paths), cache_dir=cdir, cache_format=1)
    cfg2 = DataConfig(paths=tuple(paths), cache_dir=cdir)

    load_datasets(schema, cfg1)   # v1 entries
    load_datasets(schema, cfg2)   # upgrade: v1 replaced by v2
    load_datasets(schema, cfg1)   # v1 re-written — must NOT evict v2
    entries = sorted(e for e in os.listdir(cdir) if e.endswith(".npd"))

    def gen(e):
        with open(os.path.join(cdir, e, "entry.json")) as f:
            return json.load(f)["version"]
    v2 = [e for e in entries if gen(e) >= 2]
    v1 = [e for e in entries if gen(e) == 1]
    assert len(v2) == 2 and len(v1) == 2  # both generations live

    obs.reset_for_tests()
    load_datasets(schema, cfg2)   # pure v2 hits, nothing pruned
    load_datasets(schema, cfg1)   # pure v1 hits
    reg = obs.default_registry()
    assert reg.counter("data_cache_hits_total").total() == 4
    assert reg.counter("data_cache_misses_total").total() == 0
    assert reg.counter("data_cache_upgraded_total").total() == 0


def test_scan_cache_never_touches_unknown_dotfiles(tmp_path):
    """Only our own temp names (`*.tmp`, `.building-*`) classify as tmp —
    and only once old enough that no live writer can own them; any other
    dotfile or unknown name is never listed and never pruned."""
    cdir = tmp_path / "c"
    cdir.mkdir()
    (cdir / ".gitignore").write_text("x")
    (cdir / ".nfs0000123").write_text("placeholder")
    (cdir / "notes.txt").write_text("mine")
    (cdir / "half.tmp").mkdir()
    (cdir / ".building-abc").mkdir()
    # fresh tmp dirs may belong to a LIVE writer: invisible to scan/prune
    assert cache_lib.scan_cache(str(cdir)) == []
    old = 1_000_000_000
    os.utime(cdir / "half.tmp", (old, old))
    os.utime(cdir / ".building-abc", (old, old))
    entries = cache_lib.scan_cache(str(cdir))
    assert sorted(e["name"] for e in entries) == [".building-abc",
                                                  "half.tmp"]
    removed = cache_lib.prune_cache(str(cdir), entries)
    assert len(removed) == 2
    assert sorted(os.listdir(cdir)) == [".gitignore", ".nfs0000123",
                                        "notes.txt"]


def test_raw_cache_hit_reports_cache_load_not_parse(tmp_path):
    """A file projected from a raw `.npy` hit (no re-parse) must report
    tier `raw_cache` with its load wall in the cache_load phase — never
    phantom parse seconds with zero source bytes."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(200, schema, seed=22)
    (path,) = synthetic.write_files(rows, str(tmp_path / "d"), num_files=1)
    cdir = str(tmp_path / "c")
    cache_lib.read_file_cached(path, cache_dir=cdir)  # raw entry only
    tele = tmp_path / "tele"
    obs.configure(str(tele), flush_every=1)
    cfg = DataConfig(paths=(path,), cache_dir=cdir, ingest_workers=1)
    load_datasets(schema, cfg)
    obs.flush()
    (rep,) = [r for r in obs.read_journal(str(tele / "journal.jsonl"))
              if r["kind"] == "ingest_report"]
    assert rep["tiers"] == {"raw_cache": 1}
    assert rep["parse_s"] == 0.0 and rep["inflate_s"] == 0.0
    reg = obs.default_registry()
    assert reg.counter("ingest_seconds_total").value(phase="parse") == 0.0
    assert reg.counter("ingest_seconds_total").value(
        phase="cache_load") > 0.0
    assert reg.counter("ingest_source_bytes_total").total() == 0.0


def test_manifest_records_absolute_source(tmp_path, monkeypatch):
    """Entries written under a RELATIVE data path record the abspath in
    entry.json — `shifu-tpu cache` runs from an arbitrary cwd, and a
    verbatim relative source would classify every live entry 'orphaned'
    (then --prune would delete the warm cache)."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(200, schema, seed=31)
    synthetic.write_files(rows, str(tmp_path / "d"), num_files=1)
    cdir = str(tmp_path / "c")
    monkeypatch.chdir(tmp_path)
    (rel,) = [os.path.join("d", f) for f in sorted(os.listdir("d"))]
    load_datasets(schema, DataConfig(paths=(rel,), cache_dir=cdir))
    (entry,) = [e for e in os.listdir(cdir) if e.endswith(".npd")]
    with open(os.path.join(cdir, entry, "entry.json")) as f:
        src = json.load(f)["source"]
    assert os.path.isabs(src) and os.path.exists(src)
    monkeypatch.chdir("/")  # classification must not depend on cwd
    recs = cache_lib.scan_cache(cdir)
    assert [r["status"] for r in recs if r["name"] == entry] == ["ok"]
    assert cache_lib.prune_cache(cdir) == []


def test_remote_ingest_counts_source_bytes(tmp_path):
    """Remote reads count their fetched (compressed) payload into
    ingest_source_bytes_total / last_io_stats — the cold-ingest MB/s
    metric must not silently vanish for gs://-style datasets."""
    import gzip

    from pyarrow import fs as pafs

    from shifu_tpu.data import fsio, reader

    filesystem, _ = pafs.FileSystem.from_uri("mock://seed")
    with fsio._fs_lock:
        fsio._fs_cache[("mock", "")] = filesystem
    try:
        filesystem.create_dir("bucket/data")
        rows = synthetic.make_rows(50, synthetic.make_schema(num_features=4),
                                   seed=5)
        text = "\n".join("|".join(str(v) for v in r) for r in rows) + "\n"
        payload = gzip.compress(text.encode())
        with filesystem.open_output_stream("bucket/data/part-0.gz") as s:
            s.write(payload)
        arr = reader.read_file("mock://bucket/data/part-0.gz")
        assert arr.shape[0] == 50
        st = reader.last_io_stats()
        assert st["tier"] == "remote"
        assert st["source_bytes"] == len(payload)
    finally:
        with fsio._fs_lock:
            fsio._fs_cache.pop(("mock", ""), None)


# ------------------------------------------- corruption / chaos fallback

def test_corrupt_v2_entry_falls_back_and_journals(tmp_path):
    """A bit-rotted v2 entry re-parses (bit-identical result) and the
    recovery is journaled as `cache_fallback` — the docs/ROBUSTNESS.md
    catalog contract for the data.cache site's failure domain."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(300, schema, seed=9)
    (path,) = synthetic.write_files(rows, str(tmp_path / "d"), num_files=1)
    cdir = str(tmp_path / "c")
    cfg = DataConfig(paths=(path,), cache_dir=cdir)
    t0, _ = load_datasets(schema, cfg)
    (entry,) = [e for e in os.listdir(cdir) if e.endswith(".npd")]
    with open(os.path.join(cdir, entry, "features.npy"), "wb") as f:
        f.write(b"rotten")
    tele = tmp_path / "tele"
    obs.configure(str(tele), flush_every=1)
    t1, _ = load_datasets(schema, cfg)
    obs.flush()
    assert t1.features.tobytes() == t0.features.tobytes()
    recs = obs.read_journal(str(tele / "journal.jsonl"))
    assert any(r["kind"] == "cache_fallback" for r in recs)
    assert obs.default_registry().counter(
        "cache_fallback_total").total() >= 1
    # the corrupt entry was replaced: next load is a clean hit
    obs.reset_for_tests()
    t2, _ = load_datasets(schema, cfg)
    assert t2.features.tobytes() == t0.features.tobytes()
    assert obs.default_registry().counter(
        "data_cache_hits_total").total() == 1


def test_chaos_read_fault_falls_back_to_reparse(tmp_path):
    """The `data.cache` chaos site: an injected read fault on a HOT entry
    degrades to re-parse (fresh bytes, job unharmed) and journals both
    the injection and the `cache_fallback` recovery."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(300, schema, seed=10)
    (path,) = synthetic.write_files(rows, str(tmp_path / "d"), num_files=1)
    cdir = str(tmp_path / "c")
    cfg = DataConfig(paths=(path,), cache_dir=cdir, ingest_workers=1)
    t0, _ = load_datasets(schema, cfg)

    chaos.configure(plan_mod.parse_plan({"faults": [
        {"site": "data.cache", "at_call": 1, "action": "raise"}]}))
    tele = tmp_path / "tele"
    obs.configure(str(tele), flush_every=1)
    t1, _ = load_datasets(schema, cfg)
    obs.flush()
    assert t1.features.tobytes() == t0.features.tobytes()
    recs = obs.read_journal(str(tele / "journal.jsonl"))
    assert any(r["kind"] == "chaos_inject" and r["site"] == "data.cache"
               for r in recs)
    assert any(r["kind"] == "cache_fallback" for r in recs)


def test_chaos_write_fault_drops_write_not_job(tmp_path):
    """An injected write fault loses the cache entry, never the ingest:
    the load succeeds and the next (fault-free) run re-caches."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(200, schema, seed=11)
    (path,) = synthetic.write_files(rows, str(tmp_path / "d"), num_files=1)
    cdir = str(tmp_path / "c")
    cfg = DataConfig(paths=(path,), cache_dir=cdir, ingest_workers=1)
    chaos.configure(plan_mod.parse_plan({"faults": [
        {"site": "data.cache", "every": 1, "action": "raise"}]}))
    t0, _ = load_datasets(schema, cfg)  # every cache op faulted
    assert t0.num_rows > 0
    assert not (os.path.isdir(cdir)
                and [e for e in os.listdir(cdir) if e.endswith(".npd")])
    chaos.reset_for_tests()
    t1, _ = load_datasets(schema, cfg)
    assert [e for e in os.listdir(cdir) if e.endswith(".npd")]
    assert t1.features.tobytes() == t0.features.tobytes()


# ----------------------------------------------------- parity (the gate)

def _file_job(paths, cdir, *, epochs=2, staged=True, ckpt=None):
    schema = synthetic.make_schema(num_features=8)
    job = JobConfig(
        schema=schema,
        data=DataConfig(paths=tuple(paths), batch_size=64, valid_ratio=0.1,
                        cache_dir=cdir, wire_dtype="int8",
                        device_resident_bytes=0, staged=staged,
                        stream_first_epoch=False),
        model=ModelSpec(model_type="mlp", hidden_nodes=(8,),
                        activations=("relu",), compute_dtype="float32"),
        train=TrainConfig(epochs=epochs,
                          optimizer=OptimizerConfig(name="adam",
                                                    learning_rate=1e-2)))
    if ckpt:
        job = job.replace(runtime=dataclasses.replace(
            job.runtime, checkpoint=dataclasses.replace(
                job.runtime.checkpoint, directory=str(ckpt))))
    return job.validate()


def _run_files(job, tmp_path, tag):
    from shifu_tpu.train import train
    tele = tmp_path / f"tele_{tag}"
    obs.reset_for_tests()
    obs.configure(str(tele), flush_every=1)
    r = train(job, console=lambda s: None)
    obs.flush()
    recs = obs.read_journal(str(tele / "journal.jsonl"))
    obs.shutdown()
    return r, recs


def _digests(recs):
    return {r["epoch"]: (r["tier"], r["order_digest"]) for r in recs
            if r["kind"] == "overlap_report"}


@pytest.fixture
def parity_files(tmp_path):
    schema = synthetic.make_schema(num_features=8)
    rows = synthetic.make_rows(1536, schema, seed=5, noise=0.3)
    return synthetic.write_files(rows, str(tmp_path / "d"), num_files=3)


def test_cache_v2_parity_staged_tier(parity_files, tmp_path):
    """THE acceptance gate: staged-tier batches with cache v2 on (cold
    populate, then warm int8-mmap serve) are byte-identical to cache off
    — same wire bytes at the dataset level, same journaled order digests,
    same loss/AUC trajectory."""
    cdir = str(tmp_path / "cache")
    job_off = _file_job(parity_files, None)
    job_on = _file_job(parity_files, cdir)

    # dataset-level wire bytes: cold-populate, warm-serve, and cache-off
    # loads are byte-identical (int8 features quantized on the static grid)
    t_off, v_off = load_datasets(job_off.schema, job_off.data,
                                 feature_dtype="int8c8")
    t_cold, _ = load_datasets(job_on.schema, job_on.data,
                              feature_dtype="int8c8")
    t_warm, v_warm = load_datasets(job_on.schema, job_on.data,
                                   feature_dtype="int8c8")
    assert t_off.features.dtype == np.int8
    for a, b in ((t_cold, t_off), (t_warm, t_off)):
        assert np.asarray(a.features).tobytes() == \
            np.asarray(b.features).tobytes()
        assert np.asarray(a.target).tobytes() == \
            np.asarray(b.target).tobytes()
        assert np.asarray(a.weight).tobytes() == \
            np.asarray(b.weight).tobytes()
    assert np.asarray(v_warm.features).tobytes() == \
        np.asarray(v_off.features).tobytes()
    # and the staged blocks drawn from them are byte-identical
    for blk_a, blk_b in zip(
            pipe.staged_epoch_blocks(t_warm, 64, seed=0, epoch=1),
            pipe.staged_epoch_blocks(t_off, 64, seed=0, epoch=1)):
        for k in blk_a:
            assert np.asarray(blk_a[k]).tobytes() == \
                np.asarray(blk_b[k]).tobytes()

    r_off, recs_off = _run_files(job_off, tmp_path, "off")
    r_cold, _recs_cold = _run_files(job_on, tmp_path, "cold2")
    r_warm, recs_warm = _run_files(job_on, tmp_path, "warm")
    for a, b in zip(r_off.history, r_warm.history):
        assert a.train_error == pytest.approx(b.train_error, rel=1e-6)
        assert a.valid_auc == pytest.approx(b.valid_auc, abs=1e-6)
    for a, b in zip(r_off.history, r_cold.history):
        assert a.train_error == pytest.approx(b.train_error, rel=1e-6)
    d_off, d_warm = _digests(recs_off), _digests(recs_warm)
    assert d_off == d_warm
    assert all(t == "staged" and d is not None
               for t, d in d_warm.values())


def test_cache_v2_parity_perbatch_tier(parity_files, tmp_path):
    """Same gate for the per-batch dispatch tier (staged=False)."""
    cdir = str(tmp_path / "cache")
    job_off = _file_job(parity_files, None, staged=False)
    job_on = _file_job(parity_files, cdir, staged=False)
    r_off, recs_off = _run_files(job_off, tmp_path, "pb_off")
    _r_cold, _ = _run_files(job_on, tmp_path, "pb_cold")
    r_warm, recs_warm = _run_files(job_on, tmp_path, "pb_warm")
    for a, b in zip(r_off.history, r_warm.history):
        assert a.train_error == pytest.approx(b.train_error, rel=1e-6)
        assert a.valid_auc == pytest.approx(b.valid_auc, abs=1e-6)
    assert _digests(recs_off) == _digests(recs_warm)
    assert all(t == "batch" for t, _d in _digests(recs_warm).values())


def test_cache_v2_parity_across_kill_resume(parity_files, tmp_path):
    """Kill+resume with cache v2 on: the warm resume draws the same
    per-epoch order (digests) and the same metrics as an uninterrupted
    cache-OFF run — restart determinism survives the cache tier."""
    cdir = str(tmp_path / "cache")
    ckpt = tmp_path / "ckpt"
    job2 = _file_job(parity_files, cdir, epochs=2, ckpt=ckpt)
    _run_files(job2, tmp_path, "first")          # terminal at epoch 2
    job4 = _file_job(parity_files, cdir, epochs=4, ckpt=ckpt)
    r_resumed, recs_resumed = _run_files(job4, tmp_path, "resumed")
    assert r_resumed.resumed_from_epoch == 2
    job4_off = _file_job(parity_files, None, epochs=4)
    r_straight, recs_straight = _run_files(job4_off, tmp_path, "straight")
    d_res, d_str = _digests(recs_resumed), _digests(recs_straight)
    for ep in (2, 3):
        assert d_res[ep] == d_str[ep]
        assert d_res[ep][1] is not None
    straight_tail = {m.epoch: m for m in r_straight.history}
    for m in r_resumed.history:
        assert m.train_error == pytest.approx(
            straight_tail[m.epoch].train_error, rel=1e-5)
        assert m.valid_auc == pytest.approx(
            straight_tail[m.epoch].valid_auc, abs=1e-5)


# ------------------------------------------------- ingest pool + report

def test_ingest_report_schema_and_tiers(tmp_path):
    """One `ingest_report` per ingest: pool shape, per-phase seconds,
    which cache tier served each file, capped per-file table
    (docs/OBSERVABILITY.md)."""
    schema = synthetic.make_schema(num_features=6)
    rows = synthetic.make_rows(600, schema, seed=12)
    paths = synthetic.write_files(rows, str(tmp_path / "d"), num_files=3)
    cdir = str(tmp_path / "c")
    cfg = DataConfig(paths=tuple(paths), cache_dir=cdir, ingest_workers=2)
    tele = tmp_path / "tele"
    obs.configure(str(tele), flush_every=1)
    load_datasets(schema, cfg)
    load_datasets(schema, cfg)
    obs.flush()
    recs = [r for r in obs.read_journal(str(tele / "journal.jsonl"))
            if r["kind"] == "ingest_report"]
    assert len(recs) == 2
    cold, warm = recs
    for r in recs:
        assert r["mode"] == "load"
        assert r["files"] == 3
        assert r["pool_width"] == 2
        assert r["rows"] == 600
        for k in ("wall_s", "parse_s", "inflate_s", "write_s"):
            assert isinstance(r[k], (int, float)) and r[k] >= 0
        assert len(r["per_file"]) == 3
        assert r["per_file_truncated"] is False
        for pf in r["per_file"]:
            assert {"file", "tier", "rows", "parse_s", "inflate_s",
                    "write_s"} <= set(pf)
    assert cold["tiers"] == {"parse": 3}
    assert warm["tiers"] == {"cache": 3}
    # the cold-ingest phase counters
    reg = obs.default_registry()
    assert reg.counter("ingest_seconds_total").value(phase="parse") > 0
    assert reg.counter("ingest_seconds_total").value(
        phase="cache_load") > 0


def test_ingest_pool_width_policy_and_xml_keys():
    from shifu_tpu.data import native_parser
    from shifu_tpu.utils import xmlconfig

    cpu = os.cpu_count() or 1
    assert pipe.ingest_pool_width(DataConfig(), 8) == min(8, cpu)
    assert pipe.ingest_pool_width(DataConfig(ingest_workers=3), 8) == 3
    assert pipe.ingest_pool_width(DataConfig(ingest_workers=16), 4) == 4
    assert pipe.ingest_pool_width(DataConfig(read_threads=2), 8) == 2
    # ingest_workers wins over the legacy read_threads spelling
    assert pipe.ingest_pool_width(
        DataConfig(ingest_workers=5, read_threads=2), 8) == 5
    assert pipe.ingest_pool_width(DataConfig(), 0) == 1
    with pytest.raises(ConfigError, match="ingest_workers"):
        DataConfig(ingest_workers=-1).validate()

    # intra-file parser threads scale inversely with the pool width
    assert native_parser.pool_parser_threads(cpu) == 1
    assert native_parser.pool_parser_threads(1) == cpu
    assert native_parser.pool_parser_threads(10 * cpu) == 1

    job = xmlconfig.apply_to_job(JobConfig(), {
        "shifu.data.ingest-workers": "6",
        "shifu.data.cache-format": "1",
    })
    assert job.data.ingest_workers == 6
    assert job.data.cache_format == 1


def test_resolved_cache_format():
    assert pipe.resolved_cache_format(DataConfig()) == \
        cache_lib.CACHE_FORMAT_VERSION
    assert pipe.resolved_cache_format(DataConfig(cache_format=1)) == 1


# ------------------------------------------------- out-of-core rides v2

def test_outofcore_rides_v2_entries_no_raw_duplication(tmp_path):
    """The out-of-core tier consolidates FROM the shared v2 projected
    entries — no raw-float32 double-write — and stores features in the
    wire dtype (int8: ¼ the old consolidated bytes)."""
    schema = synthetic.make_schema(num_features=6)
    rows = synthetic.make_rows(2000, schema, seed=13)
    paths = synthetic.write_files(rows, str(tmp_path / "d"), num_files=4)
    cdir = str(tmp_path / "c")
    ooc = DataConfig(paths=tuple(paths), cache_dir=cdir, out_of_core=True,
                     wire_dtype="int8")
    t_ooc, v_ooc = load_datasets(schema, ooc, feature_dtype="int8c8")
    assert isinstance(t_ooc.features, np.memmap)
    assert t_ooc.features.dtype == np.int8
    # no raw-float32 duplication: only v2 projected entries + the
    # consolidated dataset live in the cache dir
    assert not [e for e in os.listdir(cdir) if e.endswith(".npy")]
    assert [e for e in os.listdir(cdir) if e.endswith(".npd")]
    # same rows as the in-RAM loader under the same wire format
    ram = DataConfig(paths=tuple(paths), wire_dtype="int8")
    t_ram, v_ram = load_datasets(schema, ram, feature_dtype="int8c8")
    np.testing.assert_array_equal(np.asarray(v_ooc.features),
                                  np.asarray(v_ram.features))

    def sorted_rows(ds):
        allc = np.concatenate([np.asarray(ds.features, np.float32),
                               ds.target, ds.weight], axis=1)
        return allc[np.lexsort(allc.T[::-1])]

    np.testing.assert_array_equal(sorted_rows(t_ooc), sorted_rows(t_ram))


def test_outofcore_rebuilds_from_damaged_and_legacy_entries(tmp_path):
    """The consolidation build honors the fallback contract: a damaged
    per-file entry re-parses (rebuild once, never crash), and a legacy
    `.npz`-form entry under a pinned cache_format=1 is materialized into
    the directory form the chunk copy mmaps."""
    import shutil

    from shifu_tpu.data import pipeline as pipe_mod

    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(800, schema, seed=23)
    paths = synthetic.write_files(rows, str(tmp_path / "d"), num_files=2)
    cdir = str(tmp_path / "c")
    ooc = DataConfig(paths=tuple(paths), cache_dir=cdir, out_of_core=True)
    t0, v0 = load_datasets(schema, ooc)

    (ds_dir,) = [e for e in os.listdir(cdir) if e.startswith("dataset-")]
    shutil.rmtree(os.path.join(cdir, ds_dir))  # force a re-consolidation
    npd = sorted(e for e in os.listdir(cdir) if e.endswith(".npd"))[0]
    os.remove(os.path.join(cdir, npd, "target.npy"))  # damage one entry
    t1, v1 = load_datasets(schema, ooc)
    np.testing.assert_array_equal(np.asarray(v1.features),
                                  np.asarray(v0.features))

    # legacy npz-form entries under cache_format=1 serve the build
    cdir2 = str(tmp_path / "c2")
    os.makedirs(cdir2)
    cfg_nocache = DataConfig(paths=tuple(paths))
    for i, p in enumerate(paths):
        cols, mask = pipe_mod._load_one_projected(
            (i, p), schema, cfg_nocache, "float32", False)
        name = cache_lib.projected_entry_name(
            p, "|", i, schema, cfg_nocache.valid_ratio,
            cfg_nocache.split_seed, "float32", version=1)
        np.savez(cache_lib.legacy_projected_path(
            os.path.join(cdir2, name)), **cols, valid_mask=mask)
    cfg1 = DataConfig(paths=tuple(paths), cache_dir=cdir2,
                      out_of_core=True, cache_format=1)
    t2, v2 = load_datasets(schema, cfg1)
    np.testing.assert_array_equal(np.asarray(v2.features),
                                  np.asarray(v0.features))


def test_superseded_dataset_dir_classified_stale_and_pruned(tmp_path):
    """A consolidated dataset dir is keyed on source state, so a source
    rewrite supersedes it — meta.json's recorded per-file (size,
    mtime_ns) lets scan/prune reclaim the old dataset-sized dir instead
    of leaking one per rewrite."""
    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(600, schema, seed=29)
    paths = synthetic.write_files(rows, str(tmp_path / "d"), num_files=2)
    cdir = str(tmp_path / "c")
    ooc = DataConfig(paths=tuple(paths), cache_dir=cdir, out_of_core=True)
    load_datasets(schema, ooc)
    recs = [r for r in cache_lib.scan_cache(cdir) if r["tier"] == "dataset"]
    assert [r["status"] for r in recs] == ["ok"]
    os.utime(paths[0])  # rewrite: new mtime -> new key next run
    recs = [r for r in cache_lib.scan_cache(cdir) if r["tier"] == "dataset"]
    assert [r["status"] for r in recs] == ["stale"]
    removed = cache_lib.prune_cache(cdir)
    assert [r["tier"] for r in removed if r["tier"] == "dataset"] \
        == ["dataset"]
    assert not [e for e in os.listdir(cdir) if e.startswith("dataset-")]


# --------------------------------------------------- `shifu-tpu cache`

def test_cache_cli_list_and_prune(tmp_path, capsys):
    from shifu_tpu.launcher import cli

    schema = synthetic.make_schema(num_features=5)
    rows = synthetic.make_rows(400, schema, seed=14)
    paths = synthetic.write_files(rows, str(tmp_path / "d"), num_files=2)
    gone = synthetic.write_files(rows, str(tmp_path / "gone"),
                                 num_files=1)
    cdir = str(tmp_path / "c")
    cfg = DataConfig(paths=tuple(paths), cache_dir=cdir)
    load_datasets(schema, cfg)                       # 2 live v2 entries
    load_datasets(schema, DataConfig(paths=tuple(gone), cache_dir=cdir))
    cache_lib.read_file_cached(paths[0], cache_dir=cdir)  # 1 raw entry
    os.remove(gone[0])                               # orphan its entry
    os.makedirs(os.path.join(cdir, "half.tmp"))      # crashed writer
    os.utime(os.path.join(cdir, "half.tmp"),         # aged past the live-
             (1_000_000_000, 1_000_000_000))         # writer grace window
    np.savez(os.path.join(cdir, "aaaa-bbbb-pcccc.npz"),
             features=np.zeros((2, 5), np.float32))  # legacy npz

    assert cli.main(["cache", cdir]) == 0
    out = capsys.readouterr().out
    assert "projected" in out and "raw" in out
    assert "orphaned" in out and "legacy" in out and "tmp" in out
    assert "--prune" in out

    assert cli.main(["cache", cdir, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    tiers = {e["tier"] for e in doc["entries"]}
    assert {"projected", "raw", "tmp"} <= tiers
    assert doc["total_bytes"] > 0
    by_status = {e["status"] for e in doc["entries"]}
    assert {"ok", "orphaned", "legacy", "tmp"} <= by_status

    assert cli.main(["cache", cdir, "--prune", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert len(doc["pruned"]) == 3  # orphan + tmp + legacy npz
    assert all(e["status"] == "ok" for e in doc["entries"])
    # the live entries survived and still serve
    obs.reset_for_tests()
    t, _ = load_datasets(schema, cfg)
    assert t.num_rows > 0
    assert obs.default_registry().counter(
        "data_cache_misses_total").total() == 0

    assert cli.main(["cache", str(tmp_path / "nope")]) == 1
