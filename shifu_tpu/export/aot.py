"""AOT serving-executable pack: the compiled bucket grid inside the artifact.

A serving daemon padding batches up the power-of-two bucket ladder runs a
*finite, enumerable* set of XLA programs — one per rung.  Today a freshly
spawned fleet member (standby, scale-up, failover promotion) pays a live
jit compile for every rung it meets; this module moves that wall to export
time: `build_aot_pack` lowers+compiles the scoring forward for every rung
of `bucket_ladder(min_batch_bucket, max_batch)` and serializes the
executables (jax.experimental.serialize_executable) into an `aot/`
directory inside the artifact:

    <export_dir>/aot/
      manifest.json        # compatibility fingerprint + per-file blake2b
      bucket-000016.bin    # pickled {payload, in_tree, out_tree} per rung
      bucket-000032.bin
      ...

`save_artifact` writes the pack BEFORE `sync_manifest.json`, so the pack
files ride PR 14's atomic per-host sync and are digest-verified like any
other artifact file — a corrupt pack never publishes.

Load side (`try_load_aot`, called by runtime/serve.load_engine's `aot`
tier and the auto ladder): the manifest fingerprint (jax/jaxlib version,
XLA platform + device kind, feature width/heads, bucket grid) must match
the serving host exactly and every bucket file must match its digest —
then each executable is deserialized with NO compile (journaled
`aot_load`, per-bucket deserialize wall).  ANY mismatch or
deserialization error journals `aot_fallback` and returns None so the
caller falls back to the jit tier transparently: a stale pack degrades to
today's behavior, never a refused load.

Serialized executables are machine-pinned by design (XLA emits host code);
the fingerprint is what turns "undefined behavior on the wrong host" into
a clean journaled fallback.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import time
from typing import Any, Optional

import numpy as np

AOT_DIR = "aot"
AOT_MANIFEST = "manifest.json"
AOT_FORMAT = 1

_DIGEST_ALGO = "blake2b-16"  # same spelling as fleet's sync_manifest.json


def _digest(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=16).hexdigest()


def _bucket_file(bucket: int) -> str:
    return f"bucket-{int(bucket):06d}.bin"


def pack_dir(export_dir: str) -> str:
    return os.path.join(export_dir, AOT_DIR)


def has_pack(export_dir: str) -> bool:
    """Cheap existence probe for the auto engine ladder."""
    return os.path.isfile(os.path.join(export_dir, AOT_DIR, AOT_MANIFEST))


def host_fingerprint() -> dict:
    """The serving host's compatibility tuple.  A serialized executable
    is native code for ONE (jaxlib, platform, device kind); every field
    must match the pack manifest byte-for-byte or the load falls back."""
    import jax
    import jaxlib

    devices = jax.devices()
    return {
        "jax_version": jax.__version__,
        "jaxlib_version": getattr(jaxlib, "__version__", "unknown"),
        "platform": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "unknown",
    }


def _sorted_weight_keys(flat: dict) -> list[str]:
    return sorted(flat)


def _leaf_fn(forward_fn, keys: list[str]):
    """(leaves, feats) -> scores over a PLAIN list of weight arrays in
    sorted-key order.  Lowering over a list (not the model's nested
    params tree) pins the call convention to something weights.npz can
    reproduce exactly at load time — no pytree-structure drift between
    the exporting process and a serving host years later."""
    from .scorer import _unflatten

    def fn(leaves, feats):
        params = _unflatten({k: leaf for k, leaf in zip(keys, leaves)})
        return forward_fn(params, feats)

    return fn


def build_aot_pack(export_dir: str, forward_fn, params: Any,
                   num_features: int, num_heads: int,
                   buckets: tuple[int, ...]) -> Optional[dict]:
    """Compile + serialize one executable per bucket rung into
    `<export_dir>/aot/`; returns the pack manifest, or None when the
    toolchain can't serialize (journaled `aot_pack_failed` — the
    artifact still serves through the jit tiers).

    Best-effort by the same contract as export_stablehlo: packing is an
    export-time optimization, never an export failure."""
    from .. import obs
    from ..obs.introspect import compile_span
    from .artifact import _flatten_params

    try:
        import jax
        import jax.numpy as jnp
        from jax.experimental.serialize_executable import serialize

        flat = _flatten_params(params)
        keys = _sorted_weight_keys(flat)
        leaf_avals = [jax.ShapeDtypeStruct(flat[k].shape, flat[k].dtype)
                      for k in keys]
        jfn = jax.jit(_leaf_fn(forward_fn, keys))

        out_dir = pack_dir(export_dir)
        os.makedirs(out_dir, exist_ok=True)
        files: dict[str, str] = {}
        bucket_ms: dict[str, float] = {}
        grid = sorted({int(b) for b in buckets}, reverse=True)  # largest 1st
        t0 = time.perf_counter()
        for b in grid:
            feats = jax.ShapeDtypeStruct((b, int(num_features)), jnp.float32)
            t_b = time.perf_counter()
            with compile_span("aot_pack", bucket=b):
                compiled = jfn.lower(leaf_avals, feats).compile()
            payload, in_tree, out_tree = serialize(compiled)
            buf = io.BytesIO()
            pickle.dump({"payload": payload, "in_tree": in_tree,
                         "out_tree": out_tree}, buf,
                        protocol=pickle.HIGHEST_PROTOCOL)
            blob = buf.getvalue()
            name = _bucket_file(b)
            with open(os.path.join(out_dir, name), "wb") as f:
                f.write(blob)
            files[name] = _digest(blob)
            bucket_ms[str(b)] = round((time.perf_counter() - t_b) * 1e3, 3)
        manifest = {
            "format": AOT_FORMAT,
            **host_fingerprint(),
            "num_features": int(num_features),
            "num_heads": int(num_heads),
            "buckets": sorted(grid),
            "weight_keys_digest": _digest("\n".join(keys).encode()),
            "algo": _DIGEST_ALGO,
            "files": files,
        }
        with open(os.path.join(out_dir, AOT_MANIFEST), "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
        obs.event("aot_pack", path=export_dir, buckets=sorted(grid),
                  bucket_ms=bucket_ms,
                  wall_ms=round((time.perf_counter() - t0) * 1e3, 3))
        return manifest
    except Exception as e:  # noqa: BLE001 — packing must not fail export
        try:
            obs.event("aot_pack_failed", path=export_dir,
                      error=f"{type(e).__name__}: {e}"[:300])
        except Exception:
            pass
        return None


class AotScorer:
    """Scores through the artifact's pre-compiled bucket executables —
    zero XLA compiles, ever.  Implements the BatchScorer surface the
    serving daemon wraps (engine/static_shapes/num_features +
    compute_batch) without inheriting: construction happens in
    `try_load_aot` after the fingerprint/digest gauntlet, and a bucket
    grid narrower than the serve-time ladder is handled by chunking
    batches through the largest packed rung."""

    engine = "aot"
    static_shapes = True

    def __init__(self, export_dir: str, manifest: dict,
                 loaded: dict[int, Any], leaves: list[np.ndarray]):
        self.export_dir = export_dir
        self.num_features = int(manifest["num_features"])
        self.num_heads = int(manifest["num_heads"])
        self.buckets = tuple(sorted(int(b) for b in manifest["buckets"]))
        self._loaded = loaded
        self._leaves = leaves

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _run(self, bucket: int, x: np.ndarray) -> np.ndarray:
        return np.asarray(self._loaded[bucket](self._leaves, x))

    def _score_batch(self, x: np.ndarray) -> np.ndarray:
        n = x.shape[0]
        top = self.buckets[-1]
        outs = []
        i = 0
        while i < n:
            take = min(n - i, top)
            b = self._bucket_for(take)
            if take == b:
                xb = x[i:i + take]
            else:
                xb = np.zeros((b, self.num_features), np.float32)
                xb[:take] = x[i:i + take]
            outs.append(self._run(b, xb)[:take])
            i += take
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    def compute_batch(self, rows: np.ndarray,
                      n_valid: Optional[int] = None) -> np.ndarray:
        from .scorer import observe_scoring

        x = np.asarray(rows, dtype=np.float32)
        if x.ndim == 1:
            x = x[None, :]
        if x.shape[1] != self.num_features:
            raise ValueError(
                f"expected {self.num_features} features, got {x.shape[1]}")
        t0 = time.perf_counter()
        out = self._score_batch(x)
        observe_scoring(self.engine,
                        out.shape[0] if n_valid is None else n_valid,
                        time.perf_counter() - t0)
        return out

    def compute(self, row) -> float:
        return float(self.compute_batch(
            np.asarray(row, dtype=np.float64))[0, 0])


def _fingerprint_mismatches(manifest: dict, topo: dict) -> list[str]:
    """Field-by-field compatibility check; [] means safe to deserialize."""
    bad = []
    host = host_fingerprint()
    for field in ("jax_version", "jaxlib_version", "platform",
                  "device_kind"):
        want, got = manifest.get(field), host.get(field)
        if want != got:
            bad.append(f"{field}: pack={want!r} host={got!r}")
    n_feat = int(topo.get("num_features", -1))
    if int(manifest.get("num_features", -2)) != n_feat:
        bad.append(f"num_features: pack={manifest.get('num_features')} "
                   f"artifact={n_feat}")
    n_heads = topo.get("num_heads")
    if n_heads is not None \
            and int(manifest.get("num_heads", -2)) != int(n_heads):
        bad.append(f"num_heads: pack={manifest.get('num_heads')} "
                   f"artifact={n_heads}")
    return bad


def try_load_aot(export_dir: str):
    """The AOT load tier: fingerprint match -> deserialize every bucket
    executable (no compile; journaled `aot_load` with per-bucket
    deserialize wall) and return an AotScorer.  Any mismatch, missing or
    corrupt file, or deserialization error journals `aot_fallback` with
    the reason and returns None — the caller's jit tier takes over, so a
    stale or damaged pack can never refuse a load."""
    from .. import obs

    def fallback(reason: str):
        obs.event("aot_fallback", path=export_dir, reason=reason[:400])
        return None

    d = pack_dir(export_dir)
    manifest_path = os.path.join(d, AOT_MANIFEST)
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except FileNotFoundError:
        return fallback("no aot pack (aot/manifest.json missing)")
    except Exception as e:
        return fallback(f"unreadable aot manifest: "
                        f"{type(e).__name__}: {e}")
    try:
        if int(manifest.get("format", -1)) != AOT_FORMAT:
            return fallback(
                f"aot pack format {manifest.get('format')!r} "
                f"(this build reads {AOT_FORMAT})")
        from .artifact import TOPOLOGY
        with open(os.path.join(export_dir, TOPOLOGY)) as f:
            topo = json.load(f)
        bad = _fingerprint_mismatches(manifest, topo)
        if bad:
            return fallback("fingerprint mismatch: " + "; ".join(bad))

        import jax
        from jax.experimental.serialize_executable import \
            deserialize_and_load

        from .artifact import WEIGHTS
        with np.load(os.path.join(export_dir, WEIGHTS)) as z:
            flat = {k: z[k] for k in z.files}
        keys = _sorted_weight_keys(flat)
        if _digest("\n".join(keys).encode()) \
                != manifest.get("weight_keys_digest"):
            return fallback("weight key set differs from the pack's "
                            "lowering order")
        leaves = [flat[k] for k in keys]

        # the pack was compiled for ONE device; left to its default,
        # deserialize_and_load would spread it over every device of the
        # backend and the first call would fail on a multi-chip host
        device = jax.devices()[:1]
        loaded: dict[int, Any] = {}
        bucket_ms: dict[str, float] = {}
        t0 = time.perf_counter()
        for b in sorted(int(x) for x in manifest["buckets"]):
            name = _bucket_file(b)
            want = manifest.get("files", {}).get(name)
            try:
                with open(os.path.join(d, name), "rb") as f:
                    blob = f.read()
            except OSError as e:
                return fallback(f"missing pack file {name}: {e}")
            if want is None or _digest(blob) != want:
                return fallback(f"digest mismatch on {name} "
                                "(corrupt or tampered pack)")
            t_b = time.perf_counter()
            rec = pickle.loads(blob)
            loaded[b] = deserialize_and_load(
                rec["payload"], rec["in_tree"], rec["out_tree"],
                execution_devices=device)
            bucket_ms[str(b)] = round(
                (time.perf_counter() - t_b) * 1e3, 3)
        scorer = AotScorer(export_dir, manifest, loaded, leaves)
        obs.event("aot_load", path=export_dir,
                  buckets=list(scorer.buckets), bucket_ms=bucket_ms,
                  wall_ms=round((time.perf_counter() - t0) * 1e3, 3),
                  num_features=scorer.num_features,
                  num_heads=scorer.num_heads)
        return scorer
    except Exception as e:  # noqa: BLE001 — degrade, never refuse
        return fallback(f"deserialize failed: {type(e).__name__}: {e}")
