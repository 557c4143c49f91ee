"""In-memory dataset + batch pipeline feeding the SPMD train step.

The reference's pipeline is: per-worker file shard -> full in-RAM Python lists
-> feed_dict minibatches (reference: resources/ssgd_monitor.py:348-454,268-276).
Here: per-host file shard -> vectorized parse -> contiguous numpy arrays ->
static-shape batches (drop-remainder) handed to jax.device_put with a
data-axis NamedSharding.  Epoch shuffles are deterministic in (seed, epoch),
so a restart resumes with identical batch order.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Iterator, Optional, Sequence

import numpy as np

from .. import obs
from ..config.schema import DataConfig, DataSchema
from . import reader, split


def fast_take(a: np.ndarray, idx) -> np.ndarray:
    """Fancy-index `a[idx]` at native speed for non-native dtypes.

    numpy routes ml_dtypes.bfloat16 gathers through a per-element fallback
    (~84 MB/s measured on the bench host vs ~700 MB/s for int8) — an order
    of magnitude off memcpy, which made the staged bf16 tier's host block
    assembly its hidden bottleneck at high H2D bandwidth.  Gathering a
    same-itemsize integer VIEW takes numpy's native path and views back,
    bit-identical."""
    if a.dtype.name == "bfloat16":
        return a.view(np.uint16)[idx].view(a.dtype)
    return a[idx]


@dataclasses.dataclass
class TabularDataset:
    """Feature/target/weight arrays for one partition (train or valid)."""

    features: np.ndarray  # (N, F) float32
    target: np.ndarray    # (N, 1) float32
    weight: np.ndarray    # (N, 1) float32

    @property
    def num_rows(self) -> int:
        return int(self.features.shape[0])

    @property
    def num_features(self) -> int:
        return int(self.features.shape[1])

    def take(self, idx: np.ndarray) -> "TabularDataset":
        return TabularDataset(fast_take(self.features, idx),
                              self.target[idx], self.weight[idx])


def resolved_cache_format(data: DataConfig) -> int:
    """The cache entry format generation this job writes/keys by:
    DataConfig.cache_format, 0 meaning the current CACHE_FORMAT_VERSION."""
    from . import cache as cache_lib
    return int(getattr(data, "cache_format", 0)) \
        or cache_lib.CACHE_FORMAT_VERSION


def ingest_pool_width(data: DataConfig, n_files: int) -> int:
    """Width of the cold-ingest parse pool (how many part-files
    inflate+parse concurrently): DataConfig.ingest_workers, falling back to
    the legacy read_threads spelling, else one worker per file capped at
    cpu_count.  Intra-file parser threads scale inversely
    (native_parser.pool_parser_threads) so total parallelism stays ~cores.
    """
    if n_files <= 0:
        return 1
    width = data.ingest_workers or data.read_threads \
        or min(n_files, os.cpu_count() or 1)
    return max(1, min(int(width), n_files))


def _write_projected(writer, cache_dir: str, name: str, arrays: dict,
                     source: str, delimiter: str, version: int,
                     rec: Optional[dict],
                     supersedes: Optional[str] = None) -> None:
    """Route one v2 entry write through the async writer (cold ingest:
    inflate+parse of the next file overlaps this write) or do it inline;
    either way the wall lands in the ingest_report's per-file write_s and
    the `write` phase counter."""
    from . import cache as cache_lib
    wsec = obs.counter("ingest_seconds_total",
                       "cold-ingest wall seconds by phase "
                       "(docs/OBSERVABILITY.md ingest_report)")

    def record(dt: float) -> None:
        wsec.inc(dt, phase="write")
        if rec is not None:
            rec["write_s"] = rec.get("write_s", 0.0) + dt

    if writer is not None:
        writer.submit(cache_dir, name, arrays, source=source,
                      delimiter=delimiter, version=version,
                      supersedes=supersedes, record=record)
        return
    t0 = time.perf_counter()
    cache_lib.write_projected_entry(cache_dir, name, arrays, source=source,
                                    delimiter=delimiter, version=version,
                                    supersedes=supersedes)
    record(time.perf_counter() - t0)


def _load_one_projected(item: tuple[int, str], schema: DataSchema,
                        data: DataConfig, feature_dtype: str,
                        threaded: bool, parser_threads: Optional[int] = None,
                        stats: Optional[list] = None, writer=None):
    """Parse + project + split + wire-cast ONE file; the raw (N, C) matrix
    dies here, so peak memory is (in-flight raw files) + (projected
    columns), never all raw matrices at once.  With a cache_dir the fully
    PROJECTED result is cached (data/cache.py v2 entries: wire-format
    features, compact target/weight): a hit replaces
    parse + project + split + quantize with one mmap-backed load.  A v1
    entry under the old key serves once and is rewritten as v2 (the
    transparent upgrade; the v1 entry is pruned by the write).  `stats`
    collects the per-file ingest_report record; `writer` (an
    AsyncEntryWriter) overlaps entry writes with the pool's parses."""
    from . import cache as cache_lib
    file_idx, path = item
    cache_dir = cache_lib.resolve_cache_dir(data.cache_dir)
    version = resolved_cache_format(data)
    rec = {"file": os.path.basename(path), "tier": "parse", "rows": 0,
           "inflate_s": 0.0, "parse_s": 0.0, "write_s": 0.0}
    if stats is not None:
        stats.append(rec)
    isec = obs.counter("ingest_seconds_total",
                       "cold-ingest wall seconds by phase "
                       "(docs/OBSERVABILITY.md ingest_report)")
    name = None
    if cache_dir is not None:
        name = cache_lib.projected_entry_name(
            path, data.delimiter, file_idx, schema, data.valid_ratio,
            data.split_seed, feature_dtype, version=version)
        if name is not None:
            t_load = time.perf_counter()
            hit = cache_lib.load_projected_entry(cache_dir, name)
            upgraded = False
            if hit is None and version >= 2:
                # transparent v1 upgrade: serve the legacy-keyed entry once,
                # republish it as v2 (which prunes the v1 bytes)
                v1name = cache_lib.projected_entry_name(
                    path, data.delimiter, file_idx, schema, data.valid_ratio,
                    data.split_seed, feature_dtype, version=1)
                if v1name is not None:
                    hit = cache_lib.load_projected_entry(cache_dir, v1name)
                    upgraded = hit is not None
            if hit is not None:
                isec.inc(time.perf_counter() - t_load, phase="cache_load")
                mask = hit.pop("valid_mask")
                rec.update(tier="cache_v1" if upgraded else "cache",
                           rows=int(hit["features"].shape[0]))
                obs.counter("data_cache_hits_total",
                            "projected-cache hits (one entry load "
                            "replaced parse+project+split+cast)").inc()
                obs.counter("data_rows_read_total",
                            "rows ingested into datasets").inc(
                    int(hit["features"].shape[0]), source="cache")
                if upgraded:
                    obs.counter("data_cache_upgraded_total",
                                "legacy v1 projected entries rewritten "
                                "as v2").inc()
                    # supersedes=v1name: the upgrade removes exactly the
                    # old-key entry it replaced — the generic prune spares
                    # other format generations (v1-pinned jobs may share
                    # the dir)
                    _write_projected(writer, cache_dir, name,
                                     {**hit, "valid_mask": mask}, path,
                                     data.delimiter, version, rec,
                                     supersedes=v1name)
                return hit, mask
        obs.counter("data_cache_misses_total",
                    "projected-cache misses (full parse path taken)").inc()
    t_parse = time.perf_counter()
    if parser_threads is None and threaded:
        parser_threads = 1  # legacy callers: file-level pool, 1 thread each
    reader._note_io("raw_cache", 0.0, 0.0, 0)  # raw hits skip read_file;
    # a stale record from this thread's previous parse must not be charged
    # write=False when a projected entry will land: the v2 entry IS the
    # warm-start intermediate, and duplicating the matrix as raw float32
    # would cost 4x its bytes again on disk (raw hits — this job's earlier
    # format, or another job's read_files cache — are still served)
    rows = cache_lib.read_file_cached(
        path, data.delimiter, cache_dir=data.cache_dir,
        parser_threads=parser_threads, write=(name is None))
    parse_wall = time.perf_counter() - t_parse
    io_stats = reader.last_io_stats()
    rec["rows"] = int(rows.shape[0])
    if io_stats.get("tier") == "raw_cache":
        # the sentinel survived: no parse ran — a raw `.npy` entry served
        # (another job's read_files cache, or a pre-v2 run).  Its np.load
        # wall is cache time, not parse time: charging it to `parse` would
        # put phantom parse seconds with zero source bytes into the
        # cold-ingest throughput the perf gate guards
        rec["tier"] = "raw_cache"
        isec.inc(parse_wall, phase="cache_load")
    else:
        inflate_s = min(max(io_stats.get("inflate_s", 0.0), 0.0),
                        parse_wall)
        rec["parse_s"] = round(parse_wall - inflate_s, 6)
        rec["inflate_s"] = round(inflate_s, 6)
        rec["bytes"] = int(io_stats.get("source_bytes", 0))
        isec.inc(inflate_s, phase="inflate")
        isec.inc(parse_wall - inflate_s, phase="parse")
        obs.counter("ingest_source_bytes_total",
                    "source (compressed) bytes cold ingest read").inc(
            int(io_stats.get("source_bytes", 0)))
    obs.histogram("data_file_parse_seconds",
                  "per-file parse (or raw-cache load) latency").observe(
        parse_wall)
    obs.counter("data_files_read_total", "data files parsed").inc()
    obs.counter("data_rows_read_total",
                "rows ingested into datasets").inc(
        int(rows.shape[0]), source="parse")
    obs.counter("data_bytes_read_total",
                "parsed matrix bytes produced by ingest").inc(
        int(rows.nbytes))
    cols = reader.project_columns(rows, schema)
    if feature_dtype == "bfloat16":
        import ml_dtypes
        cols["features"] = cols["features"].astype(ml_dtypes.bfloat16)
    elif feature_dtype.startswith("int8"):
        # quantize ONCE at load (the grid is static — wire_params — so this
        # equals quantizing at device_put time): 1/4 the host RAM, 1/4 the
        # projected-cache bytes, zero per-epoch encode cost
        scale, offset = wire_params(schema, data)
        cols["features"] = wire_quantize(cols["features"], scale, offset)
    n = cols["features"].shape[0]
    row_ids = ((np.uint64(file_idx) << np.uint64(40))
               + np.arange(n, dtype=np.uint64))
    _, valid_mask = split.train_valid_mask(row_ids, data.valid_ratio,
                                           data.split_seed)
    if cache_dir is not None and name is not None:
        _write_projected(writer, cache_dir, name,
                         {**cols, "valid_mask": valid_mask}, path,
                         data.delimiter, version, rec)
    return cols, valid_mask


def _emit_ingest_report(stats: list, pool_width: int, wall_s: float,
                        mode: str) -> None:
    """One `ingest_report` journal event per completed ingest: the pool
    shape, the per-phase cost split, which cache tier served each file,
    and a (capped) per-file table — the observable record of the cold/warm
    ingest gap docs/DATA.md "Columnar cache" reasons about.  Never raises."""
    try:
        files = sorted(stats, key=lambda r: r["file"])
        tiers: dict[str, int] = {}
        for r in files:
            tiers[r["tier"]] = tiers.get(r["tier"], 0) + 1
        per_file = [
            {k: (round(v, 6) if isinstance(v, float) else v)
             for k, v in r.items()} for r in files[:32]]
        obs.event(
            "ingest_report", mode=mode, files=len(files),
            pool_width=int(pool_width), wall_s=round(wall_s, 6),
            rows=int(sum(r["rows"] for r in files)),
            parse_s=round(sum(r["parse_s"] for r in files), 6),
            inflate_s=round(sum(r["inflate_s"] for r in files), 6),
            write_s=round(sum(r["write_s"] for r in files), 6),
            source_bytes=int(sum(r.get("bytes", 0) for r in files)),
            host_index=int(os.environ.get("SHIFU_TPU_PROCESS_ID", 0) or 0),
            tiers=tiers, per_file=per_file,
            per_file_truncated=len(files) > 32)
    except Exception:
        pass  # telemetry must never fail the ingest it measures


def _run_ingest_pool(items: Sequence[tuple[int, str]], schema: DataSchema,
                     data: DataConfig, feature_dtype: str, width: int,
                     on_result) -> list:
    """The bounded multi-file ingest pool: `width` part-files inflate+parse
    concurrently (native parser per file, intra-file threads scaled so
    total parallelism stays ~cores), with v2 cache writes overlapped on a
    dedicated writer thread — the cold path never serializes parse behind
    cache IO.  Each per-file result is handed to `on_result` in file order
    as soon as it completes (Executor.map yields in submit order while
    workers run ahead), so a streaming consumer starts before the pool
    drains.  The writer is closed — every entry durable — before this
    returns (or before an error propagates); returns the ingest stats."""
    from . import cache as cache_lib, native_parser
    stats: list = []
    writer = (cache_lib.AsyncEntryWriter()
              if cache_lib.resolve_cache_dir(data.cache_dir) else None)
    threaded = width > 1 and len(items) > 1
    pt = native_parser.pool_parser_threads(width) if threaded else None
    try:
        def load_one(item):
            return _load_one_projected(item, schema, data, feature_dtype,
                                       threaded, parser_threads=pt,
                                       stats=stats, writer=writer)

        if threaded:
            from concurrent.futures import ThreadPoolExecutor
            with ThreadPoolExecutor(max_workers=width) as pool:
                for res in pool.map(load_one, items):
                    on_result(res)
        else:
            for item in items:
                on_result(load_one(item))
    finally:
        if writer is not None:
            writer.close()
    return stats


def _pool_load_projected(mine: Sequence[tuple[int, str]], schema: DataSchema,
                         data: DataConfig, feature_dtype: str,
                         width: int) -> tuple[list, list]:
    """_run_ingest_pool collecting into a list: (per-file results in file
    order, ingest stats)."""
    results: list = []
    stats = _run_ingest_pool(mine, schema, data, feature_dtype, width,
                             results.append)
    return results, stats


def shard_rotation(seed: int, epoch: int, num_hosts: int) -> int:
    """Deterministic rotation offset of the host<->file-shard round-robin
    for `epoch` — a pure function of (seed, epoch, num_hosts) so every
    host (including one rejoining after an elastic reshape) derives the
    same offset with no coordination.  Epoch 0 is pinned to 0: a cold
    start is bit-identical to the legacy fixed round-robin, so cache and
    out-of-core entry keys written before the rotating plane stay valid."""
    if num_hosts <= 1 or epoch <= 0:
        return 0
    rng = np.random.default_rng(
        np.random.PCG64([int(seed), int(epoch), int(num_hosts), 0x51A4D]))
    return int(rng.integers(num_hosts))


def host_shard_assignment(n_files: int, host_index: int, num_hosts: int,
                          *, seed: int = 0, epoch: int = 0,
                          mode: str = "static") -> list[int]:
    """Global file indices host `host_index` owns for `epoch` — THE pure
    shard-assignment function of the pod data plane (ISSUE 20): a function
    of (process_index, process_count, seed, epoch) and nothing else.  Each
    host reads/decompresses/projects only its ~1/N slice of the source
    bytes; after an elastic reshape the surviving hosts re-derive the
    assignment from the new NUM_PROCESSES at the next epoch boundary, and
    a rejoining host picks its slice back up from the same formula.

    mode "static" (and "auto"): the fixed round-robin `i % num_hosts` —
    the legacy scheme, unchanged across epochs.
    mode "rotate": the round-robin rotated by `shard_rotation(seed, epoch,
    num_hosts)` — across epochs every host visits every slice (page-cache
    diversity after a reshape) while epoch 0 stays identical to "static".

    Either way the assignment is a PARTITION: every file owned by exactly
    one host, global file INDICES preserved (row ids `(file_idx << 40) +
    row` and the train/valid split keyed on them never depend on which
    host reads a file)."""
    if num_hosts <= 1:
        return list(range(n_files))
    r = (shard_rotation(seed, epoch, num_hosts)
         if mode == "rotate" else 0)
    return [i for i in range(n_files)
            if (i + r) % num_hosts == host_index]


def shard_assignment_digest(n_files: int, num_hosts: int, *, seed: int = 0,
                            epoch: int = 0, mode: str = "static") -> str:
    """Digest of the COMPLETE global file->host assignment for `epoch` —
    identical on every host iff the gang agrees on (n_files, num_hosts,
    seed, epoch, mode).  Journaled per epoch (host_skew row / the
    data-dryrun's shard_assign event) and compared by `pod-verify`: a host
    that desynced its shard view (stale file listing, wrong contract env)
    shows up as a digest split instead of silently double- or un-reading
    files."""
    import hashlib
    h = hashlib.blake2b(digest_size=16)
    h.update(f"{n_files}:{num_hosts}:{seed}:{epoch}:{mode}".encode())
    for host in range(num_hosts):
        idx = host_shard_assignment(n_files, host, num_hosts, seed=seed,
                                    epoch=epoch, mode=mode)
        h.update(np.asarray(idx, np.int64).tobytes())
        h.update(b";")
    return h.hexdigest()


def count_source_files(data: DataConfig) -> int:
    """Number of source data files the config resolves to — the `n_files`
    input of host_shard_assignment / shard_assignment_digest."""
    n = 0
    for p in data.paths:
        n += len(reader.list_data_files(p))
    return n


def host_file_shard(data: DataConfig, host_index: int = 0,
                    num_hosts: int = 1, *,
                    epoch: int = 0) -> list[tuple[int, str]]:
    """This host's (global file idx, path) list: paths expanded in config
    order and assigned by GLOBAL index through `host_shard_assignment`
    (successor of yarn/appmaster/TrainingDataSet.java:65-82).  The ONE
    source of the shard scheme — load_datasets, StreamingLoader, the
    out-of-core build, and the cache-hot probe must agree, or row ids (and
    the train/valid split keyed on them) would diverge across entry
    points.  Chaos site `data.host_shard` probes here: the elastic
    training drill kills one host exactly where its slice is derived."""
    from .. import chaos
    chaos.maybe_fail("data.host_shard", epoch=epoch)
    paths: list[str] = []
    for p in data.paths:
        paths.extend(reader.list_data_files(p))
    own = host_shard_assignment(
        len(paths), host_index, num_hosts,
        seed=data.shuffle_seed, epoch=epoch,
        mode=getattr(data, "host_shard", "auto"))
    own_set = set(own)
    return [(i, p) for i, p in enumerate(paths) if i in own_set]


def load_datasets(
    schema: DataSchema,
    data: DataConfig,
    host_index: int = 0,
    num_hosts: int = 1,
    feature_dtype: str = "float32",
) -> tuple[TabularDataset, TabularDataset]:
    """Load (train, valid) datasets for this host.

    Files are round-robined across hosts (successor of
    yarn/appmaster/TrainingDataSet.java:65-82); rows are split train/valid by
    the deterministic hash in `split` (fixes the re-drawn random split quirk,
    ssgd_monitor.py:395).  `feature_dtype` "bfloat16" stores features in the
    wire dtype (see wire_cast_fn) — half the host RAM and H2D bytes.
    """
    if data.out_of_core:
        from .outofcore import load_datasets_out_of_core
        return load_datasets_out_of_core(schema, data, host_index, num_hosts,
                                         feature_dtype=feature_dtype)

    # global row ids must be stable across hosts: derive from (file idx, row idx);
    # shard by index so duplicate path strings still get distinct ids
    mine = host_file_shard(data, host_index, num_hosts)
    t_ingest = time.perf_counter()
    num_threads = ingest_pool_width(data, len(mine))
    results, stats = _pool_load_projected(mine, schema, data, feature_dtype,
                                          num_threads)
    _emit_ingest_report(stats, num_threads,
                        time.perf_counter() - t_ingest, mode="load")

    feats, targs, weights, masks_v = [], [], [], []
    for cols, valid_mask in results:
        feats.append(cols["features"])
        targs.append(cols["target"])
        weights.append(cols["weight"])
        masks_v.append(valid_mask)

    if feats:
        features = np.concatenate(feats)
        target = np.concatenate(targs)
        weight = np.concatenate(weights)
        valid_mask = np.concatenate(masks_v)
    else:
        features = np.zeros((0, schema.feature_count), np.float32)
        target = np.zeros((0, 1), np.float32)
        weight = np.zeros((0, 1), np.float32)
        valid_mask = np.zeros((0,), bool)

    full = TabularDataset(features, target, weight)
    # one-time global row shuffle of the training partition: staged epochs
    # then only permute batch order per epoch (staged_epoch_blocks), which
    # together approximates row-level shuffling at a fraction of the host
    # cost.  The split-select and the shuffle COMPOSE into one gather
    # (train_idx[perm]) — a separate take(~mask) then take(perm) would
    # copy the whole training partition twice
    train_idx = np.nonzero(~valid_mask)[0]
    if len(train_idx) > 1:
        perm = np.random.default_rng(np.random.PCG64(
            data.split_seed ^ 0xC0FFEE)).permutation(len(train_idx))
        train_idx = train_idx[perm]
    train = full.take(train_idx)
    valid = full.take(np.nonzero(valid_mask)[0])
    return train, valid


def projected_cache_complete(schema: DataSchema, data: DataConfig,
                             host_index: int = 0, num_hosts: int = 1,
                             feature_dtype: str = "float32") -> bool:
    """True when EVERY file in this host's shard has a hot projected-cache
    entry — ingest will then run at npz-load speed (tens of millions of
    rows/s), so the streamed first epoch's parse/compute overlap buys
    nothing and the loaded tiers (device-resident / staged) are strictly
    better: they overlap nothing because there is nothing left to hide.
    Cost: one os.stat per source file plus one os.path.exists per entry.
    False on any miss, un-keyable file, or when no cache dir resolves."""
    from . import cache as cache_lib
    cache_dir = cache_lib.resolve_cache_dir(data.cache_dir)
    if cache_dir is None or not os.path.isdir(cache_dir):
        return False
    try:
        mine = host_file_shard(data, host_index, num_hosts)
        if not mine:
            return False
        version = resolved_cache_format(data)
        for file_idx, path in mine:
            # a v1-keyed entry (or a legacy r4-format .npz under either
            # key) is just as hot: the loader serves it — and upgrades it
            # to v2 — in one mmap-speed load, so counting only the current
            # form would permanently disable the fast path for caches
            # written by earlier formats
            versions = (version, 1) if version >= 2 else (version,)
            hot = False
            for v in versions:
                name = cache_lib.projected_entry_name(
                    path, data.delimiter, file_idx, schema, data.valid_ratio,
                    data.split_seed, feature_dtype, version=v)
                if name is None:
                    return False
                entry = os.path.join(cache_dir, name)
                if (os.path.exists(entry)
                        or os.path.exists(cache_lib.legacy_projected_path(
                            entry))):
                    hot = True
                    break
            if not hot:
                return False
        return True
    except OSError:
        return False


def wire_mode(schema: DataSchema, data: DataConfig,
              model_compute_dtype: str) -> str:
    """Resolved wire format for the FEATURES array: "float32" (no cast),
    "bfloat16", or "int8".  "auto" picks bfloat16 exactly when the model
    computes in bfloat16 (the model casts inputs to compute_dtype first —
    models/base.py — so the math is bit-identical) and no categorical id
    columns ride in the feature matrix (integer ids above 256 are not
    bf16-representable)."""
    mode = data.wire_dtype
    if mode == "auto":
        return ("bfloat16" if (model_compute_dtype == "bfloat16"
                               and not schema.categorical_indices)
                else "float32")
    if mode == "int8" and schema.categorical_indices:
        # JobConfig.validate rejects this combination up front; a direct
        # DataConfig user degrades to f32 rather than corrupting ids
        return "float32"
    return mode


def resident_feature_format(schema: DataSchema, data: DataConfig,
                            model_compute_dtype: str) -> str:
    """Resolved in-HBM feature format for the device-resident tier:
    "float32", "bfloat16", or "int8".  "auto"/"wire" keep whatever format
    the wire delivered (no silent precision change); "int8" forces the
    wire_params grid at tier build even when the per-batch wire is wider —
    quartering resident HBM vs f32 staging — with the dequant fused into
    the first-layer matmul where ops/pallas_int8_matmul is engaged
    (train/step.make_wire_decode's XLA op otherwise).  Categorical ids
    cannot ride the affine grid, so such schemas degrade to the wire
    format (mirror of wire_mode's guard; JobConfig.validate rejects the
    config up front)."""
    if data.resident_format == "int8" and not schema.categorical_indices:
        return "int8"
    return wire_mode(schema, data, model_compute_dtype)


def wire_quantize(x: np.ndarray, scale: np.ndarray,
                  offset: np.ndarray) -> np.ndarray:
    """The ONE int8 wire encoder (grid contract single-sourced: callers at
    parse time and per-block cast time share it; the
    device-side inverse is train/step.make_wire_decode):
    round((x - offset) / scale), saturated to [-127, 127], int8."""
    xf = np.asarray(x, np.float32)
    q = np.clip(np.rint((xf - offset) * (1.0 / scale)), -127, 127)
    return q.astype(np.int8)


def wire_dequantize(q: np.ndarray, scale, offset) -> np.ndarray:
    """Host-side inverse of wire_quantize: int8 grid values -> float32
    features (`x = q * scale + offset`).  The device-side inverse is
    train/step.make_wire_decode; this one is the SERVING ingest seam —
    runtime/serve_wire.py decodes request payloads that ride the same
    cache-v2 int8 wire encoding the training data plane stores on disk."""
    return (np.asarray(q, np.float32) * np.asarray(scale, np.float32)
            + np.asarray(offset, np.float32))


def wire_params(schema: DataSchema,
                data: DataConfig) -> tuple[np.ndarray, np.ndarray]:
    """Per-column (scale, offset) vectors for the int8 wire grid.

    The grid is STATIC — a pure function of config, not of data statistics
    — so every host, every block, every tier, and every resume quantizes
    identically (a data-derived grid would diverge across hosts in the
    streamed multihost epoch, whose blocks assemble into one global batch).
    Values encode as round((x - offset) / scale) clipped to [-127, 127];
    the default symmetric clip (DataConfig.wire_int8_clip, 8.0) never
    saturates ZSCALE-normalized data (Shifu clamps at 4-6 sigma upstream).
    """
    f = schema.feature_count
    scale = np.full((f,), float(data.wire_int8_clip) / 127.0, np.float32)
    offset = np.zeros((f,), np.float32)
    return scale, offset


def target_u8_exact(t: np.ndarray) -> bool:
    """True when every target value is an integer in [0, 255] — i.e. a u8
    wire cast round-trips bit-exactly (always true for binary labels)."""
    tf = np.asarray(t)
    if tf.dtype == np.uint8:
        return True
    if tf.dtype.kind not in "fiu":
        return False
    lo, hi = (tf.min(), tf.max()) if tf.size else (0.0, 0.0)
    if not (0.0 <= lo and hi <= 255.0):
        return False
    return bool(np.all(tf == np.floor(tf)))


def weight_all_ones(w: np.ndarray) -> bool:
    """True when every weight is exactly 1.0 — the column carries no
    information and can be elided from the wire (the device step
    synthesizes ones; weighted losses are bit-identical)."""
    wf = np.asarray(w)
    return bool(np.all(wf == 1.0))


def _compact_cols(b: dict, label_on, weight_on) -> dict:
    """Apply the compact target/weight wire to one block.  `label_on` /
    `weight_on` are tri-state: True (apply unconditionally — the caller
    proved the whole dataset qualifies, e.g. via the multihost agreement),
    False (off), or None (detect per block — content-driven and
    deterministic, so resume/replay compacts identically).

    Never raises on unqualified data: forced modes ("uint8"/"elide") are
    enforced DATASET-wide by the train loop's _prepare_tiers — a per-block
    raise would false-positive on legitimately synthetic rows, e.g. the
    zero-WEIGHT padding of a streamed epoch's tail block under all-ones
    user weights."""
    t = b.get("target")
    if t is not None and t.dtype != np.uint8 and label_on is not False:
        if label_on or target_u8_exact(t):
            b = dict(b)
            b["target"] = np.asarray(t).astype(np.uint8)
    w = b.get("weight")
    if w is not None and weight_on is not False:
        if weight_on or weight_all_ones(w):
            b = dict(b)
            del b["weight"]
    return b


def wire_row_bytes(schema: DataSchema, data: DataConfig,
                   model_compute_dtype: str,
                   compact: bool = True) -> int:
    """Bytes one row costs on the H2D wire under the resolved formats (the
    compact target/weight wire assumed applicable when `compact`) — used to
    size staged chunks by bytes rather than rows."""
    mode = wire_mode(schema, data, model_compute_dtype)
    per_feat = {"int8": 1, "bfloat16": 2}.get(mode, 4)
    n_tgt = max(len(schema.all_target_indices), 1)
    tgt = (1 if (compact and data.wire_label_dtype != "float32") else 4)
    wgt = (0 if (compact and data.wire_weight_mode != "float32") else 4)
    return schema.feature_count * per_feat + n_tgt * tgt + wgt


def wire_cast_fn(schema: DataSchema, data: DataConfig,
                 model_compute_dtype: str, compact=False):
    """Host-side cast applied to batches/blocks before device_put, or None.

    bfloat16 wire halves H2D bytes and the device-resident tier's HBM
    footprint; int8 wire (see wire_params) quarters them, dequantized on
    device by the step builders (train/step.py make_wire_decode).

    `compact` additionally engages the target/weight wire
    (DataConfig.wire_label_dtype / wire_weight_mode): targets ride as u8
    when exactly representable and all-ones weight columns are elided —
    38 -> 31 B/row on the int8 wire for a 30-feature schema.  Pass True for
    per-block detection (single-host paths: content-driven, deterministic
    across resume/replay), or an explicit (label_ok, weight_ok) bool pair
    when the decision was made dataset-wide (the multihost tiers agree via
    allgather — per-block detection there could diverge across hosts and
    deadlock the gang on mismatched program signatures).  False (the
    default) keeps the r4 wire: features-only casting, so eval paths and
    external callers are unchanged.
    """
    mode = wire_mode(schema, data, model_compute_dtype)
    if compact is False or compact is None:
        label_on = weight_on = False
    else:
        if compact is True:
            label_on = weight_on = None  # per-block detection
        else:
            label_on, weight_on = compact
        if data.wire_label_dtype == "float32":
            label_on = False
        if data.wire_weight_mode == "float32":
            weight_on = False
    compacting = label_on is not False or weight_on is not False

    def compact_fn(b: dict) -> dict:
        if not compacting:
            return b
        return _compact_cols(b, label_on, weight_on)

    if mode == "int8":
        scale, offset = wire_params(schema, data)

        def cast_q(b: dict) -> dict:
            f = b.get("features")
            if f is not None and f.dtype != np.int8:  # not yet wire dtype
                b = dict(b)
                b["features"] = wire_quantize(f, scale, offset)
            return compact_fn(b)

        return cast_q
    if mode != "bfloat16":
        return compact_fn if compacting else None
    import ml_dtypes

    def cast(b: dict) -> dict:
        f = b.get("features")
        if f is not None and f.dtype == np.float32:  # not yet wire dtype
            b = dict(b)
            b["features"] = f.astype(ml_dtypes.bfloat16)
        return compact_fn(b)

    return cast


class StreamingLoader:
    """Background-parse loader for the streamed first epoch.

    Parses the host's file shard on a background pool (same per-file
    parse/project/split as load_datasets) and exposes the results two ways:

    - `first_epoch_blocks(batch_size, block_batches)`: a generator yielding
      stacked (nb, B, ...) TRAIN blocks as soon as enough rows have parsed —
      the staged-tier feed that lets the first epoch's device compute overlap
      the remaining files' parse.  Rows arrive in file order (the global
      shuffle is applied to the retained dataset afterwards); a remainder
      that doesn't fill a batch carries over to the next block, and the
      final partial batch is trained only via the retained dataset's later
      epochs (drop-remainder semantics, same as staged_epoch_blocks).
    - `datasets()`: blocks until every file parsed; returns the SAME
      (train, valid) pair load_datasets would have built (identical split,
      identical global permutation), for epochs after the first.
    """

    def __init__(self, schema: DataSchema, data: DataConfig,
                 feature_dtype: str = "float32",
                 host_index: int = 0, num_hosts: int = 1):
        self._schema = schema
        self._data = data
        self._feature_dtype = feature_dtype
        # same round-robin + GLOBAL file index as load_datasets, so row ids
        # (and therefore the train/valid split) are identical either way
        self._items = host_file_shard(data, host_index, num_hosts)
        self._results: list[tuple[dict, np.ndarray]] = []
        self._datasets: Optional[tuple[TabularDataset, TabularDataset]] = None
        self.real_batches = 0  # set by first_epoch_blocks

        import queue
        import threading
        # parse-result queue depth: DataConfig.prefetch_depth (auto=0 keeps
        # the historical 4 — the parse queue has no per-epoch ledger to
        # adapt from; only the cross-epoch feeder resizes itself)
        self._q: "queue.Queue" = queue.Queue(maxsize=data.prefetch_depth or 4)
        self._abort = False  # see abort_blocks()
        self._thread = threading.Thread(target=self._produce, daemon=True)
        self._thread.start()

    def _produce(self) -> None:
        data = self._data
        t_ingest = time.perf_counter()
        num_threads = ingest_pool_width(data, len(self._items))
        try:
            # the pool's writer is closed (entries durable) before the
            # stats return — i.e. before the hot-cache probe can run —
            # and before an error is forwarded to the consumer
            stats = _run_ingest_pool(self._items, self._schema, data,
                                     self._feature_dtype, num_threads,
                                     self._q.put)
        except BaseException as e:  # surface parse errors to the consumer
            self._q.put(e)
            return
        _emit_ingest_report(stats, num_threads,
                            time.perf_counter() - t_ingest, mode="stream")
        self._q.put(None)

    def first_epoch_blocks(self, batch_size: int, block_batches: int,
                           pad_tail: bool = True) -> Iterator[dict]:
        """Stacked train blocks in arrival order; retains every result for
        datasets().  Must be consumed before datasets() is called.

        Every yielded block has the SAME static shape (block_batches,
        batch_size, ...) so the scan step compiles exactly once.  With
        `pad_tail` the final partial block is completed with ZERO-WEIGHT
        rows — exact for the weight-normalized losses (weighted_mse divides
        by count(w != 0), weighted_bce by sum(w); zero-weight rows add zero
        loss and zero gradient), so every parsed train row trains in the
        streamed epoch.  Callers whose loss/regularizer is not
        weight-gated (bce ignores weights; an L2 penalty applies per step
        regardless) pass pad_tail=False and the tail rows simply wait for
        the retained dataset's later epochs.  `real_batches` counts batches
        containing at least one real row (the train_error denominator)."""
        self.real_batches = 0
        buf: list[dict] = []
        buffered = 0
        target_rows = batch_size * block_batches

        def take_rows(take: int) -> dict:
            nonlocal buffered
            parts: list[dict] = []
            got = 0
            while got < take:
                head = buf[0]
                need = take - got
                n = head["features"].shape[0]
                if n <= need:
                    parts.append(buf.pop(0))
                    got += n
                else:
                    parts.append({k: v[:need] for k, v in head.items()})
                    buf[0] = {k: v[need:] for k, v in head.items()}
                    got += need
            buffered -= take
            return {k: np.concatenate([p[k] for p in parts])
                    for k in parts[0]}

        def as_block(flat: dict) -> dict:
            return {k: v.reshape(block_batches, batch_size, *v.shape[1:])
                    for k, v in flat.items()}

        import queue as queue_lib
        while True:
            try:
                item = self._q.get(timeout=0.1)
            except queue_lib.Empty:
                if self._abort:
                    return
                continue
            if item is None:
                break
            if isinstance(item, BaseException):
                raise item
            cols, valid_mask = item
            self._results.append((cols, valid_mask))
            if self._abort:
                # cooperative shutdown (abort_blocks): the item was already
                # RETAINED above, so nothing is lost; the caller's _drain
                # takes over the queue from here
                return
            tm = ~valid_mask
            if tm.any():
                buf.append({k: v[tm] for k, v in cols.items()})
                buffered += int(tm.sum())
            while buffered >= target_rows:
                self.real_batches += block_batches
                yield as_block(take_rows(target_rows))
        if buffered and pad_tail:
            n_real = buffered
            flat = take_rows(n_real)
            pad = target_rows - n_real
            padded = {}
            for k, v in flat.items():
                padded[k] = np.concatenate(
                    [v, np.zeros((pad,) + v.shape[1:], v.dtype)])
            padded["weight"][n_real:] = 0.0
            self.real_batches += -(-n_real // batch_size)
            yield as_block(padded)

    def _drain(self) -> None:
        """Join the background parse, collecting anything the block
        generator did not consume.  Timed gets, no unconditional blocking:
        the None sentinel may already have been consumed by
        first_epoch_blocks (a bare get() would hang forever), and the
        producer may be blocked on a full queue (a bare join() first would
        deadlock) — the loop drains and watches thread liveness together."""
        import queue as queue_lib
        done = False
        while not done:
            try:
                item = self._q.get(timeout=0.1)
            except queue_lib.Empty:
                if not self._thread.is_alive():
                    done = True
                continue
            if item is None:
                done = True
            elif isinstance(item, BaseException):
                raise item
            else:
                self._results.append(item)
        self._thread.join()

    def _partition(self, want_valid: bool) -> TabularDataset:
        feats, targs, weights = [], [], []
        for cols, valid_mask in self._results:
            m = valid_mask if want_valid else ~valid_mask
            if m.any():
                feats.append(cols["features"][m])
                targs.append(cols["target"][m])
                weights.append(cols["weight"][m])
        if not feats:
            return TabularDataset(
                np.zeros((0, self._schema.feature_count), np.float32),
                np.zeros((0, 1), np.float32), np.zeros((0, 1), np.float32))
        return TabularDataset(np.concatenate(feats), np.concatenate(targs),
                              np.concatenate(weights))

    def abort_blocks(self) -> None:
        """Cooperative shutdown of a first_epoch_blocks consumer running in
        ANOTHER thread (the streamed epoch's prefetch producer): the
        generator exits at its next poll instead of blocking on the parse
        queue forever, so datasets()/_drain never race it for items.
        Safe because every item the generator consumed was already appended
        to the retained results before any early return."""
        self._abort = True

    def train_rows_total(self) -> int:
        """Total TRAIN rows this host parsed (drains the background parse;
        counts masks only — no array assembly), for skipped-row accounting
        when a streamed epoch ends early."""
        if self._datasets is not None:
            return self._datasets[0].num_rows
        self._drain()
        return int(sum(int((~m).sum()) for _, m in self._results))

    def valid_dataset(self) -> TabularDataset:
        """The valid partition only — cheap (a few % of the rows), so the
        streamed epoch's end-of-epoch eval does not pay for the full train
        assembly."""
        if self._datasets is not None:
            return self._datasets[1]
        if not hasattr(self, "_valid"):
            self._drain()
            self._valid = self._partition(want_valid=True)
        return self._valid

    def train_dataset(self) -> TabularDataset:
        """The train partition with the same global shuffle load_datasets
        applies — deferred until an epoch actually needs the retained
        dataset (an epochs=1 streamed job never assembles it)."""
        return self.datasets()[0]

    def datasets(self) -> tuple[TabularDataset, TabularDataset]:
        """(train, valid), identical to load_datasets' output.  Joins the
        background parse if first_epoch_blocks was not (fully) consumed."""
        if self._datasets is not None:
            return self._datasets
        self._drain()
        valid = self.valid_dataset()
        train = self._partition(want_valid=False)
        if train.num_rows > 1:  # same global shuffle as load_datasets
            perm = np.random.default_rng(np.random.PCG64(
                self._data.split_seed ^ 0xC0FFEE)).permutation(train.num_rows)
            train = train.take(perm)
        self._results = []
        self._datasets = (train, valid)
        return self._datasets


def epoch_permutation(n: int, *, shuffle: bool = True, seed: int = 0,
                      epoch: int = 0) -> np.ndarray:
    """THE per-epoch order stream — a pure function of (seed, epoch), so
    every host and every restart agrees.  Single-sourced: batch_iterator
    (row order), staged_epoch_blocks (block order), the device-resident
    tier (train/loop.py), and epoch_order_digest all draw from HERE, so
    the journaled order fingerprint can never silently drift from the
    order the tiers actually train in."""
    if not shuffle:
        return np.arange(n)
    return np.random.default_rng(
        np.random.PCG64(seed * 1_000_003 + epoch)).permutation(n)


def staged_epoch_offset(num_rows: int, batch_size: int, *,
                        shuffle: bool = True, epoch: int = 0) -> int:
    """The staged tier's per-epoch row-offset rotation (batch composition
    drifts across epochs when rows don't divide the batch evenly) —
    single-sourced next to epoch_permutation for the same reason."""
    nb_total = num_rows // batch_size
    slack = num_rows - nb_total * batch_size
    return (epoch * 997) % (slack + 1) if (shuffle and slack > 0) else 0


def batch_iterator(
    ds: TabularDataset,
    batch_size: int,
    *,
    shuffle: bool = True,
    seed: int = 0,
    epoch: int = 0,
    drop_remainder: bool = True,
) -> Iterator[dict[str, np.ndarray]]:
    """Yield {'features','target','weight'} batches with static shapes.

    Shuffle order is a pure function of (seed, epoch) so every host and every
    restart agrees.  drop_remainder keeps shapes static for XLA; the dropped
    tail rotates across epochs because the permutation changes per epoch.
    """
    n = ds.num_rows
    if n == 0:
        return
    order = epoch_permutation(n, shuffle=shuffle, seed=seed, epoch=epoch)
    num_full = n // batch_size
    end = num_full * batch_size if drop_remainder else n
    for start in range(0, end, batch_size):
        idx = order[start:start + batch_size]
        yield {
            "features": fast_take(ds.features, idx),
            "target": ds.target[idx],
            "weight": ds.weight[idx],
        }


def prefetch_to_device(batches: Iterator[dict[str, np.ndarray]],
                       mesh=None, size: int = 2, put_fn=None) -> Iterator[dict]:
    """Background-thread device feed: host batches are device_put (with
    data-axis sharding when a mesh is given) ahead of consumption, so host
    parse/shuffle overlaps device compute — the double-buffering the
    reference's feed_dict loop could never do (ssgd_monitor.py:271-276
    blocked the worker on every batch).

    `put_fn` overrides the host->device placement (used by the staged-epoch
    path, whose arrays shard on their second axis).
    """
    import queue
    import threading

    import jax

    from ..parallel import sharding as shard_lib

    if put_fn is None:
        def put_fn(b):
            if mesh is not None:
                return shard_lib.shard_batch(b, mesh)
            return {k: jax.device_put(v) for k, v in b.items()}

    # per-batch host latency (produce + wire-cast + device placement),
    # observed in the producer so the histogram sees the true host cost
    # rather than the consumer's (usually zero) queue wait
    lat = obs.histogram("data_batch_latency_seconds",
                        "host batch production + device placement latency")

    def timed_put(b):
        t0 = time.perf_counter()
        out = put_fn(b)
        lat.observe(time.perf_counter() - t0)
        return out

    if size <= 0:
        for b in batches:
            yield timed_put(b)
        return

    q: "queue.Queue" = queue.Queue(maxsize=size)
    _END = object()

    def producer() -> None:
        try:
            for b in batches:
                q.put(timed_put(b))
        except BaseException as e:  # surface errors to the consumer
            q.put(e)
            return
        q.put(_END)

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    while True:
        item = q.get()
        if item is _END:
            return
        if isinstance(item, BaseException):
            raise item
        yield item


def next_prefetch_depth(current: int, exposed_fraction: float,
                        lo: int = 2, hi: int = 8) -> int:
    """Auto prefetch-depth policy (DataConfig.prefetch_depth == 0): one
    step per epoch, driven by the goodput ledger's exposed-input fraction
    (the share of the epoch wall the device sat waiting for input).
    Resizes the feeder's DEVICE staging gate — the HBM-side run-ahead
    (the host queue keeps its fixed depth).  Visible starvation doubles
    the depth — a starved consumer needs more run-ahead NOW, and a
    half-step would leave it starved for several more epochs; a fully
    hidden input path decays one step per epoch toward `lo`, releasing
    the HBM the extra staged chunks pin.  `hi`=8 bounds worst-case
    run-ahead to 8 chunks (~32 MB wire each — ~256 MB HBM), a deliberate
    ceiling since this gate supersedes DataConfig.prefetch in auto mode."""
    if exposed_fraction > 0.05:
        return min(max(current * 2, lo), hi)
    if exposed_fraction < 0.01 and current > lo:
        return current - 1
    return current


def epoch_order_digest(tier: str, num_rows: int, batch_size: int, *,
                       shuffle: bool = True, seed: int = 0,
                       epoch: int = 0) -> Optional[str]:
    """blake2b hex digest of THE batch order a tier draws for (seed, epoch)
    — the restart/resume determinism contract made checkable: overlap on
    vs off, and a resumed epoch vs the uninterrupted run, must journal the
    same digest (`overlap_report.order_digest`).

    Built from the SAME epoch_permutation / staged_epoch_offset the tiers
    themselves draw from (pinned against the real iterators by
    tests/test_overlap.py): `staged` = block permutation + row-offset
    rotation (staged_epoch_blocks); `batch` = batch_iterator's row
    permutation; `resident` = the train loop's block order.  None when
    the tier has no deterministic (seed, epoch) order (the streamed
    first epoch trains in file-arrival order)."""
    import hashlib

    if tier == "staged":
        nb_total = num_rows // batch_size
        if nb_total == 0:
            return None
        offset = staged_epoch_offset(num_rows, batch_size, shuffle=shuffle,
                                     epoch=epoch)
        order = epoch_permutation(nb_total, shuffle=shuffle, seed=seed,
                                  epoch=epoch)
        payload = np.concatenate([[offset], order]).astype(np.int64)
    elif tier in ("batch", "resident"):
        n = num_rows if tier == "batch" else num_rows // batch_size
        if n == 0:
            return None
        payload = np.asarray(epoch_permutation(n, shuffle=shuffle, seed=seed,
                                               epoch=epoch), np.int64)
    else:
        return None  # "stream" and unknown tiers: no (seed, epoch) order
    return hashlib.blake2b(payload.tobytes(), digest_size=16).hexdigest()


def interleaved_epoch_order(host_row_ids: Sequence[np.ndarray],
                            local_batch_size: int, *,
                            shuffle: bool = True, seed: int = 0,
                            epoch: int = 0) -> np.ndarray:
    """The pod data plane's deterministic global batch order, as row ids.

    Global batch `b` of `epoch` is the rank-order concatenation of every
    host's rows `local_perm[b*lbs : (b+1)*lbs]`, where `local_perm` is the
    SAME `epoch_permutation(min_rows, ...)` stream on every host (same
    (min_rows, seed, epoch) on each rank — exactly what the cross-host
    order-digest agreement in the `host_skew` row pins).  A single process
    emulating N shards through this function therefore reproduces a real
    N-host run's global order bit-for-bit — the loss/AUC-identity contract
    of the sharded ingest plane (tests/test_pod_data_plane.py).

    `host_row_ids[h]` holds host h's global row ids in its local storage
    order; rows past `min_rows` (imbalanced shards) and the batch-tail
    remainder are dropped, matching the train loop's min-host-rows
    agreement and drop-remainder semantics.  Returns a flat (steps *
    n_hosts * lbs,) id array; reshape to (steps, n_hosts, lbs) for
    per-batch views."""
    if not host_row_ids:
        return np.zeros((0,), np.int64)
    min_rows = min(len(r) for r in host_row_ids)
    steps = min_rows // local_batch_size
    if steps == 0:
        return np.zeros((0,), np.int64)
    perm = epoch_permutation(min_rows, shuffle=shuffle, seed=seed,
                             epoch=epoch)
    take = perm[: steps * local_batch_size]
    cols = [np.asarray(r, np.int64)[take].reshape(steps, local_batch_size)
            for r in host_row_ids]
    return np.stack(cols, axis=1).reshape(-1)


class _DepthGate:
    """Resizable counting gate bounding the feeder's device queue: the
    placement thread acquires a slot per staged item, the consumer releases
    one per item drained.  A plain Queue(maxsize=) cannot do this — the
    auto mode resizes the bound BETWEEN epochs (next_prefetch_depth), and
    queue maxsize is fixed at construction.  Shrinking records a deficit
    that absorbs future releases instead of blocking anyone."""

    def __init__(self, depth: int):
        import threading
        self._sem = threading.Semaphore(depth)
        self._lock = threading.Lock()
        self._deficit = 0
        self.depth = depth

    def acquire(self, timeout: float) -> bool:
        return self._sem.acquire(timeout=timeout)

    def release(self) -> None:
        with self._lock:
            if self._deficit > 0:
                self._deficit -= 1
                return
        self._sem.release()

    def resize(self, depth: int) -> None:
        with self._lock:
            delta = depth - self.depth
            self.depth = depth
            if delta < 0:
                self._deficit += -delta
                return
            # pay down an outstanding shrink deficit BEFORE releasing new
            # permits: a cancelled absorption already restores one unit of
            # future capacity, and releasing on top of it would transiently
            # admit more in-flight items than the new bound
            paid = min(self._deficit, delta)
            self._deficit -= paid
            delta -= paid
        for _ in range(delta):
            self._sem.release()


class FeederError(RuntimeError):
    """The persistent feeder died without delivering its epoch — raised in
    the CONSUMER so a dead producer thread fails the epoch loudly instead
    of deadlocking the queue (docs/ROBUSTNESS.md site `data.feeder`)."""


class EpochFeeder:
    """Persistent cross-epoch input feeder — the overlap engine's producer
    side (docs/DATA.md "Overlap engine").

    Replaces the per-epoch producer thread prefetch_to_device spins up:
    ONE pair of host threads lives for the whole job and runs ahead across
    epoch boundaries, so epoch N+1's shuffle + block assembly (and its
    first device_put staging) happen while epoch N is still executing on
    device and while its eval dispatch tail drains — the serialized wall
    between epochs the reference's train→eval→shuffle loop paid every
    epoch (ssgd_monitor.py-style).  Two pipeline stages double-buffer the
    H2D staging itself:

      assembly thread:  epoch_source(epoch) → host items   (shuffle+gather)
      placement thread: put_fn(item) → device items        (cast+device_put)

    so chunk k+1 assembles while chunk k stages.  Determinism is untouched:
    `epoch_source` draws each epoch's order as a pure function of
    (seed, epoch) exactly as the per-epoch path did, and items are
    delivered strictly in epoch order — a restart/resume consumes
    byte-identical batches (pinned by tests/test_overlap.py).

    Bounds: the host staging queue holds `host_depth` assembled chunks
    (DataConfig.prefetch_depth; host RAM), the device queue `depth` staged
    chunks (DataConfig.prefetch; HBM).  `set_depth` resizes the device
    bound between epochs (the auto mode, next_prefetch_depth).

    Failure contract: an assembly/placement exception (including the
    `data.feeder` chaos probe, evaluated at each epoch's assembly start)
    is forwarded and re-raised in the consumer; a thread that dies without
    a sentinel raises FeederError at the consumer's next poll — never a
    silent deadlock.  `close()` (idempotent; the train loop's finally)
    aborts both threads and discards anything produced ahead."""

    _POLL_S = 0.1

    def __init__(self, epoch_source, put_fn, epochs, *,
                 depth: int = 2, host_depth: int = 4):
        import queue
        import threading

        self._source = epoch_source
        self._put_fn = put_fn
        self._epochs = list(epochs)
        self._abort = threading.Event()
        self._hostq: "queue.Queue" = queue.Queue(maxsize=max(host_depth, 1))
        self._devq: "queue.Queue" = queue.Queue()  # bounded by _gate
        self._gate = _DepthGate(max(depth, 1))
        self._staged_lock = threading.Lock()
        self._staged = 0  # 'item' records in devq (sentinels excluded)
        self._prod_s: dict[int, float] = {}  # epoch -> host seconds
        self._lat = obs.histogram(
            "data_batch_latency_seconds",
            "host batch production + device placement latency")
        self._threads = [
            threading.Thread(target=self._assemble, daemon=True,
                             name="shifu-feeder-assemble"),
            threading.Thread(target=self._place, daemon=True,
                             name="shifu-feeder-place"),
        ]
        for t in self._threads:
            t.start()

    # -- producer side ------------------------------------------------------

    def _put(self, q, item) -> bool:
        import queue as queue_lib
        while not self._abort.is_set():
            try:
                q.put(item, timeout=self._POLL_S)
                return True
            except queue_lib.Full:
                continue
        return False

    def _assemble(self) -> None:
        from .. import chaos
        try:
            for ep in self._epochs:
                if self._abort.is_set():
                    return
                # chaos site "data.feeder": the feeder thread boundary —
                # a raise here must fail the epoch in the CONSUMER
                chaos.maybe_fail("data.feeder", epoch=ep)
                prod = 0.0
                t0 = time.perf_counter()
                for item in self._source(ep):
                    prod += time.perf_counter() - t0
                    if not self._put(self._hostq, ("item", ep, item, prod)):
                        return
                    prod = 0.0
                    t0 = time.perf_counter()
                prod += time.perf_counter() - t0
                if not self._put(self._hostq, ("end", ep, None, prod)):
                    return
            self._put(self._hostq, ("done", None, None, 0.0))
        except BaseException as e:  # forwarded, re-raised by the consumer
            self._put(self._hostq, ("error", None, e, 0.0))

    def _host_get(self):
        """Next host-queue record, or None when assembly is gone for good.
        The dead-thread check re-polls the queue non-blocking FIRST: the
        assembly thread's final sentinel ('done'/'error') may land between
        a get timeout and its exit, and returning on liveness alone would
        drop it — the consumer would then see a generic FeederError instead
        of the original error (same defense _get applies device-side)."""
        import queue as queue_lib
        while not self._abort.is_set():
            try:
                return self._hostq.get(timeout=self._POLL_S)
            except queue_lib.Empty:
                if not self._threads[0].is_alive():
                    try:
                        return self._hostq.get_nowait()
                    except queue_lib.Empty:
                        return None
        return None

    def _place(self) -> None:
        place_s: dict[int, float] = {}
        try:
            while not self._abort.is_set():
                item = self._host_get()
                if item is None:
                    return
                tag, ep, payload, prod = item
                if tag == "item":
                    t0 = time.perf_counter()
                    dev = self._put_fn(payload)
                    dt = time.perf_counter() - t0
                    self._lat.observe(prod + dt)
                    place_s[ep] = place_s.get(ep, 0.0) + prod + dt
                    while not self._abort.is_set():
                        if self._gate.acquire(timeout=self._POLL_S):
                            with self._staged_lock:
                                self._staged += 1
                            self._devq.put(("item", ep, dev))
                            break
                    continue
                if tag == "end":
                    total = place_s.pop(ep, 0.0) + prod
                    self._devq.put(("end", ep, total))
                    continue
                self._devq.put((tag, ep, payload))  # done / error
                return
        except BaseException as e:
            self._devq.put(("error", None, e))

    # -- consumer side ------------------------------------------------------

    def _get(self):
        import queue as queue_lib
        while True:
            try:
                return self._devq.get(timeout=self._POLL_S)
            except queue_lib.Empty:
                if self._abort.is_set() or not any(
                        t.is_alive() for t in self._threads):
                    # one last non-blocking look: the sentinel may have
                    # landed between the timeout and the liveness check
                    try:
                        return self._devq.get_nowait()
                    except queue_lib.Empty:
                        raise FeederError(
                            "input feeder died without delivering its "
                            "epoch (producer thread gone; see the journal "
                            "for a chaos_inject or the original error)")

    def epoch(self, epoch: int) -> Iterator:
        """Device items for `epoch`, in deterministic order.  Epochs must
        be consumed in the order the feeder was constructed with."""
        while True:
            tag, ep, payload = self._get()
            if tag == "error":
                self._abort.set()
                raise payload
            if tag == "done":
                raise FeederError(
                    f"feeder exhausted before epoch {epoch} (consumed out "
                    "of order?)")
            if ep != epoch:
                self._abort.set()
                raise FeederError(
                    f"feeder/consumer epoch mismatch: got {ep}, "
                    f"expected {epoch}")
            if tag == "end":
                self._prod_s[epoch] = payload
                return
            with self._staged_lock:
                self._staged -= 1
            try:
                yield payload
            finally:
                self._gate.release()

    def production_seconds(self, epoch: int) -> float:
        """Host seconds this epoch's items cost to assemble + stage (the
        producer-side, per-host-attributable input cost — the straggler
        line's lens), regardless of WHEN they ran; 0.0 until the epoch's
        end marker was consumed."""
        return self._prod_s.get(epoch, 0.0)

    def ready_ahead(self) -> int:
        """Items already staged on device beyond what the consumer pulled —
        at an epoch boundary this is the NEXT epoch's prefetched chunks
        (the boundary work the overlap hid).  Counts real items only
        (epoch-end sentinels in the queue never held gate slots and would
        overstate the report)."""
        with self._staged_lock:
            return max(self._staged, 0)

    @property
    def depth(self) -> int:
        return self._gate.depth

    def set_depth(self, depth: int) -> None:
        """Resize the device-queue bound (auto mode; between epochs)."""
        self._gate.resize(max(int(depth), 1))

    def close(self) -> None:
        """Abort both threads and discard run-ahead items (early stop,
        SIGTERM drain, mid-epoch exceptions).  Idempotent."""
        import queue as queue_lib
        self._abort.set()
        deadline = time.monotonic() + 10.0
        while (any(t.is_alive() for t in self._threads)
               and time.monotonic() < deadline):
            try:  # drain so a producer blocked on a full gate/queue exits
                self._devq.get_nowait()
                self._gate.release()
            except queue_lib.Empty:
                time.sleep(self._POLL_S / 2)
        for t in self._threads:
            t.join(timeout=1.0)


def staged_epoch_blocks(
    ds: TabularDataset,
    batch_size: int,
    *,
    shuffle: bool = True,
    seed: int = 0,
    epoch: int = 0,
    block_batches: int = 32,
) -> Iterator[dict[str, np.ndarray]]:
    """Yield {'features': (nb, B, F), ...} stacked blocks for the staged
    (scan-on-device) epoch path.

    Host cost per block is a gather of whole contiguous batches (large
    memcpys), not per-row fancy indexing: the dataset is viewed as
    (num_batches, B, ...) and only the *batch order* is permuted per epoch,
    with a cheap row-offset rotation so batch composition drifts across
    epochs.  Row-level shuffling happens once at load time (load_datasets
    applies a global permutation), which together with batch-order shuffling
    is the standard approximation for large-scale SGD.
    """
    n = ds.num_rows
    nb_total = n // batch_size
    if nb_total == 0:
        return
    offset = staged_epoch_offset(n, batch_size, shuffle=shuffle, epoch=epoch)

    def as_blocks(arr: np.ndarray) -> np.ndarray:
        return arr[offset:offset + nb_total * batch_size].reshape(
            nb_total, batch_size, *arr.shape[1:])

    feats = as_blocks(ds.features)
    targ = as_blocks(ds.target)
    wgt = as_blocks(ds.weight)

    order = epoch_permutation(nb_total, shuffle=shuffle, seed=seed,
                              epoch=epoch)

    for start in range(0, nb_total, block_batches):
        idx = order[start:start + block_batches]
        yield {
            "features": fast_take(feats, idx),
            "target": targ[idx],
            "weight": wgt[idx],
        }


def num_batches(ds: TabularDataset, batch_size: int, drop_remainder: bool = True) -> int:
    if drop_remainder:
        return ds.num_rows // batch_size
    return -(-ds.num_rows // batch_size)


def pad_to_batch(batch: dict[str, np.ndarray], batch_size: int) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """Pad a short batch up to batch_size; returns (padded, validity mask).

    Padding rows get weight 0 so they contribute nothing to weighted losses or
    metrics — used by full-dataset eval so no validation row is dropped (the
    reference evaluates the full valid set each epoch, ssgd_monitor.py:281-284).
    """
    n = batch["features"].shape[0]
    if n == batch_size:
        return batch, np.ones((batch_size,), bool)
    pad = batch_size - n
    out = {}
    for k, v in batch.items():
        out[k] = np.concatenate([v, np.zeros((pad,) + v.shape[1:], v.dtype)])
    out["weight"][n:] = 0.0
    mask = np.zeros((batch_size,), bool)
    mask[:n] = True
    return out, mask
