"""Pure-numpy scorer for exported artifacts — no JAX/TF at score time.

Functional replacement for the reference's eval module
(shifu-tensorflow-eval/src/main/java/ml/shifu/shifu/tensorflow/
TensorflowModel.java): `init` loads the artifact (:112-172), `compute` scores
one row double->float->double in [0,1] (:52-109).  Improvements over the
reference: batch scoring (`compute_batch`), zero native runtime dependency
for the Python path, and the same op-list program is also executed by the
native C++ scorer (shifu_tpu/runtime) for JVM callers.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Optional, Sequence

import numpy as np

from .artifact import SIDE_CAR, TOPOLOGY, WEIGHTS


# Serving-grade latency buckets (seconds): 50us floor, single-digit-ms
# resolution through the 10ms p99 budget.  The registry's DEFAULT_BUCKETS
# start at 500us — too coarse to tell a 2ms p99 from an 8ms one, which is
# exactly the band the serving daemon's budget lives in.  One bucket table
# shared by library calls and the daemon so their percentiles merge.
SCORE_LATENCY_BUCKETS = (
    0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
    0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0)


def observe_scoring(engine: str, n_rows: int, seconds: float) -> None:
    """One telemetry write per scored batch, shared by every engine tier
    (numpy / stablehlo / jax here, native in runtime/native_scorer.py, the
    serving daemon in runtime/serve.py): rows counter + per-call latency
    histograms, labeled by engine.  `score_latency_seconds` is the ONE
    latency schema daemon p99 and library-call scoring share — same name,
    same buckets, distinguished only by the engine label."""
    from .. import obs

    obs.counter("score_rows_total", "rows scored").inc(
        max(int(n_rows), 0), engine=engine)
    obs.histogram("score_batch_seconds",
                  "batch scoring latency by engine").observe(
        seconds, engine=engine)
    obs.histogram("score_latency_seconds",
                  "per-call scoring latency by engine (shared schema: "
                  "library batches and serving-daemon requests)",
                  buckets=SCORE_LATENCY_BUCKETS).observe(
        seconds, engine=engine)


_LATENCY_BOUNDS = np.asarray(SCORE_LATENCY_BUCKETS, np.float64)


def observe_request_latencies(engine: str, latencies) -> None:
    """Bulk write of per-REQUEST latencies into the shared
    `score_latency_seconds` schema — the serving daemon records one value
    per admitted request (admission -> response).  Binning is vectorized
    here (searchsorted == the histogram's bisect_left rule) and merged
    under ONE lock, so a 4k-row dispatch costs microseconds, not a
    4k-iteration Python loop on the dispatch thread."""
    from .. import obs

    lat = np.asarray(latencies, np.float64)
    if lat.size == 0:
        return
    idx = np.searchsorted(_LATENCY_BOUNDS, lat, side="left")
    counts = np.bincount(idx, minlength=len(SCORE_LATENCY_BUCKETS) + 1)
    obs.histogram("score_latency_seconds",
                  "per-call scoring latency by engine (shared schema: "
                  "library batches and serving-daemon requests)",
                  buckets=SCORE_LATENCY_BUCKETS).merge_counts(
        counts.tolist(), float(lat.sum()), int(lat.size), engine=engine)

_LEAKY_ALPHA = 0.2  # keep in sync with ops/activations.py
_LN_EPS = 1e-6      # flax nn.LayerNorm default


def _act(name: str, x: np.ndarray) -> np.ndarray:
    if name == "sigmoid":
        # numerically stable piecewise sigmoid
        out = np.empty_like(x)
        pos = x >= 0
        out[pos] = 1.0 / (1.0 + np.exp(-x[pos]))
        ex = np.exp(x[~pos])
        out[~pos] = ex / (1.0 + ex)
        return out
    if name == "tanh":
        return np.tanh(x)
    if name == "relu":
        return np.maximum(x, 0.0)
    if name == "leakyrelu":
        return np.where(x >= 0, x, _LEAKY_ALPHA * x)
    if name == "gelu":
        # tanh approximation — flax nn.gelu default (approximate=True)
        c = np.float32(np.sqrt(2.0 / np.pi))
        return 0.5 * x * (1.0 + np.tanh(c * (x + 0.044715 * x * x * x)))
    if name == "softmax":
        return _softmax(x)  # rowwise over the last axis (moe gate)
    if name in (None, "", "linear"):
        return x
    raise ValueError(f"unknown activation {name!r}")


def _layernorm(x: np.ndarray, scale: np.ndarray, bias: np.ndarray) -> np.ndarray:
    mean = x.mean(axis=-1, keepdims=True)
    var = x.var(axis=-1, keepdims=True)
    return (x - mean) / np.sqrt(var + _LN_EPS) * scale + bias


def _softmax(x: np.ndarray) -> np.ndarray:
    m = x.max(axis=-1, keepdims=True)
    e = np.exp(x - m)
    return e / e.sum(axis=-1, keepdims=True)


def _transformer_block(op: dict, w: dict[str, np.ndarray], x: np.ndarray
                       ) -> np.ndarray:
    """Pre-LN MHA + residual, then pre-LN gelu-MLP + residual — the exact
    forward of models/ft_transformer.py TransformerBlock (float32)."""
    b, s, d = x.shape
    h = int(op["num_heads"])
    dh = d // h
    y = _layernorm(x, w[op["ln_attn_scale"]], w[op["ln_attn_bias"]])
    qkv = y @ w[op["qkv_kernel"]] + w[op["qkv_bias"]]
    q, k, v = np.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    scores = (q @ k.transpose(0, 1, 3, 2)) * np.float32(1.0 / np.sqrt(dh))
    attn = (_softmax(scores) @ v).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + attn @ w[op["proj_kernel"]] + w[op["proj_bias"]]
    y = _layernorm(x, w[op["ln_mlp_scale"]], w[op["ln_mlp_bias"]])
    y = _act("gelu", y @ w[op["mlp_in_kernel"]] + w[op["mlp_in_bias"]])
    return x + y @ w[op["mlp_out_kernel"]] + w[op["mlp_out_bias"]]


def _reject_extra_inputs(sidecar: dict, tier: str) -> None:
    """Tiers that replay the traced single-input forward (jax rebuild,
    compiled StableHLO) cannot bind sidecar extra inputs; scoring without
    them would silently diverge from the numpy/native engines — fail loudly
    instead (the multi-input contract: TensorflowModel.java:74-87)."""
    extra = sidecar.get("inputnames", ["shifu_input_0"])[1:]
    if extra:
        raise ValueError(
            f"artifact declares extra named inputs {extra} (fed from "
            f"GenericModelConfig properties); the {tier!r} tier replays the "
            "single-input traced forward and cannot bind them — score with "
            "--engine numpy or native")


def extra_inputs_from_sidecar(sidecar: dict) -> dict[str, np.ndarray]:
    """Auxiliary named inputs per the reference contract: inputnames[1:]
    take their VALUES from GenericModelConfig properties
    (TensorflowModel.java:74-87).  Single source of truth for both engines —
    the numpy Scorer binds these at call time, pack_native lowers them to
    kConstant ops.  A listed name with no property value fails loudly."""
    out: dict[str, np.ndarray] = {}
    props = sidecar.get("properties", {})
    for name in sidecar.get("inputnames", [])[1:]:
        if name not in props:
            raise ValueError(
                f"sidecar lists extra input {name!r} but its value is "
                "missing from GenericModelConfig properties "
                "(TensorflowModel.java:74-87 contract)")
        value = np.asarray(props[name], np.float32).ravel()
        if value.size == 0:
            raise ValueError(f"extra input {name!r} has an empty value")
        out[name] = value
    return out


def run_program(program: list[dict], weights: dict[str, np.ndarray],
                x: np.ndarray,
                extra_inputs: dict[str, np.ndarray] | None = None
                ) -> np.ndarray:
    """Execute an artifact op-list on (B, F) float32 rows.

    Handles both format v1 (implicit dense chain, no src/out fields) and the
    general v2 SSA form (export/program.py).  This interpreter and the native
    C++ engine (runtime/csrc/shifu_scorer.cc) are semantically pinned to each
    other by tests/test_native_scorer.py.

    `extra_inputs` are the sidecar's auxiliary named inputs
    (TensorflowModel.java:74-87): each becomes a per-row-broadcast buffer
    `input:<name>` the program may reference.
    """
    bufs: dict[str, np.ndarray] = {"input": x}
    for name, value in (extra_inputs or {}).items():
        bufs[f"input:{name}"] = np.broadcast_to(
            np.asarray(value, np.float32).ravel()[None, :],
            (x.shape[0], np.asarray(value).size))
    cur = x
    for op in program:
        kind = op["op"]
        src = bufs[op["src"]] if "src" in op else cur
        w = weights
        if kind == "dense":
            out = src @ w[op["kernel"]] + w[op["bias"]]
            out = _act(op.get("activation"), out)
        elif kind == "gather_cols":
            out = src[:, np.asarray(op["positions"], dtype=np.int64)]
        elif kind == "embed_lookup":
            pos = np.asarray(op["positions"], dtype=np.int64)
            vocab = np.asarray(op["vocabs"], dtype=np.int32)
            ids = src[:, pos].astype(np.int32)
            ids = np.clip(ids, 0, vocab - 1)              # (B, Nc)
            table = w[op["table"]]                        # (Nc, maxV, D)
            out = table[np.arange(len(pos))[None, :], ids]  # (B, Nc, D)
        elif kind == "numeric_embed":
            out = src[:, :, None] * w[op["weight"]][None] + w[op["bias"]][None]
        elif kind == "concat":
            out = np.concatenate([bufs[s] for s in op["srcs"]], axis=1)
        elif kind == "flatten":
            out = src.reshape(src.shape[0], -1)
        elif kind == "sum_fields":
            out = src.sum(axis=1)
        elif kind == "add":
            parts = [bufs[s] for s in op["srcs"]]
            out = parts[0]
            for p in parts[1:]:
                out = out + p                              # (B,1) broadcasts
        elif kind == "fm_pair":
            sum_sq = np.square(src.sum(axis=1))
            sq_sum = np.square(src).sum(axis=1)
            out = 0.5 * (sum_sq - sq_sum).sum(axis=-1, keepdims=True)
        elif kind == "activation":
            out = _act(op.get("fn"), src)
        elif kind == "cls_prepend":
            token = np.broadcast_to(
                w[op["token"]].reshape(1, 1, -1),
                (src.shape[0], 1, src.shape[2]))
            out = np.concatenate([token, src], axis=1)
        elif kind == "layernorm":
            out = _layernorm(src, w[op["scale"]], w[op["bias"]])
        elif kind == "select_token":
            out = src[:, int(op["index"]), :]
        elif kind == "transformer_block":
            out = _transformer_block(op, w, src)
        elif kind == "expert_dense":
            kernel = w[op["kernel"]]              # (E, I, O)
            if src.ndim == 2:                     # first layer: shared input
                out = np.einsum("bi,eio->beo", src, kernel)
            else:                                 # (B, E, I) per-expert
                out = np.einsum("bei,eio->beo", src, kernel)
            out = _act(op.get("activation"), out + w[op["bias"]][None])
        elif kind == "moe_combine":
            h, gate = (bufs[s] for s in op["srcs"])  # (B,E,H), (B,E)
            out = np.einsum("beh,be->bh", h, gate)
        else:
            raise ValueError(f"unknown op {kind!r}")
        out = np.asarray(out, dtype=np.float32)
        if "out" in op:
            bufs[op["out"]] = out
        cur = out
    return cur


class BatchScorer:
    """The ONE batch-dispatch seam every scoring engine shares (numpy /
    stablehlo / jax here, native C++ in runtime/native_scorer.py) and the
    serving daemon (runtime/serve.py) wraps.

    Subclasses set `engine` (the telemetry label), `num_features`, and
    implement `_score_batch(x)` on a validated (N, F) float32 matrix;
    the seam owns input coercion, width validation (one error string for
    all tiers), timing, and observe_scoring — previously re-implemented
    per engine, which is exactly what a daemon cannot wrap uniformly.

    `static_shapes` tells the micro-batcher whether this engine compiles
    per batch shape (jax/stablehlo tiers) — True means the daemon pads
    batches to bucket sizes so the jit cache stays bounded.
    """

    engine = "base"
    static_shapes = False
    num_features: int

    def _score_batch(self, x: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def _as_batch(self, rows: np.ndarray) -> np.ndarray:
        x = np.asarray(rows, dtype=np.float32)
        if x.ndim == 1:
            x = x[None, :]
        if x.shape[1] != self.num_features:
            raise ValueError(
                f"expected {self.num_features} features, got {x.shape[1]}")
        return x

    def compute_batch(self, rows: np.ndarray,
                      n_valid: Optional[int] = None) -> np.ndarray:
        """Score (N, F) float rows -> (N, num_heads) probabilities.

        `n_valid` overrides the row count reported to telemetry: the
        serving daemon pads batches up its bucket ladder for
        static-shape engines, and the pad rows must not inflate
        `score_rows_total` / the per-row rates the serving story is
        measured by."""
        x = self._as_batch(rows)
        t0 = time.perf_counter()
        out = self._score_batch(x)
        observe_scoring(self.engine,
                        out.shape[0] if n_valid is None else n_valid,
                        time.perf_counter() - t0)
        return out

    def compute(self, row: Sequence[float]) -> float:
        """Single-row double score in [0,1] — the reference's exact call shape
        (double[] in, single double out, TensorflowModel.java:63-91)."""
        return float(self.compute_batch(np.asarray(row, dtype=np.float64))[0, 0])


class Scorer(BatchScorer):
    """Loads an artifact directory and scores rows.

    API parity with TensorflowModel: `compute(row) -> float` for one row
    (TensorflowModel.java:52-109); `compute_batch(rows) -> (N, H)` is the
    batch extension the reference lacked.
    """

    engine = "numpy"

    def __init__(self, export_dir: str):
        with open(os.path.join(export_dir, TOPOLOGY)) as f:
            self.topology = json.load(f)
        with open(os.path.join(export_dir, SIDE_CAR)) as f:
            self.sidecar = json.load(f)
        if self.topology.get("format_version") != 1:
            raise ValueError(f"unsupported artifact format: "
                             f"{self.topology.get('format_version')}")
        with np.load(os.path.join(export_dir, WEIGHTS)) as z:
            self.weights = {k: z[k].astype(np.float32) for k in z.files}
        self.num_features = int(self.topology["num_features"])
        self.program = self.topology["program"]
        self.input_names = self.sidecar.get("inputnames", ["shifu_input_0"])
        self.output_name = self.sidecar.get("properties", {}).get(
            "outputnames", "shifu_output_0")
        # auxiliary named inputs: values come from the sidecar PROPERTIES,
        # exactly the reference's contract (TensorflowModel.java:74-87)
        self.extra_inputs = extra_inputs_from_sidecar(self.sidecar)

    def _score_batch(self, x: np.ndarray) -> np.ndarray:
        return run_program(self.program, self.weights, x,
                           extra_inputs=self.extra_inputs)


class JaxScorer(BatchScorer):
    """Rebuilds the Flax model from the artifact's stored spec and scores
    through a jitted forward on the default JAX backend — the TPU where
    there is one (`serve --engine jax` is the device serving path), the CPU
    otherwise.  Serves every model family, including the non-chain ones
    (wide_deep/deepfm/multitask/ft_transformer) the op-list engines only
    cover as their ops are lowered, at the cost of a jax dependency."""

    engine = "jax"
    static_shapes = True  # jit compiles per batch shape — daemon pads

    def __init__(self, export_dir: str):
        import jax
        import jax.numpy as jnp

        from ..config.schema import DataSchema, ModelSpec, _from_dict
        from ..models.registry import build_model

        with open(os.path.join(export_dir, TOPOLOGY)) as f:
            self.topology = json.load(f)
        with open(os.path.join(export_dir, SIDE_CAR)) as f:
            self.sidecar = json.load(f)
        _reject_extra_inputs(self.sidecar, "jax")
        spec = _from_dict(ModelSpec, self.topology["model_spec"])
        schema = _from_dict(DataSchema, self.topology["schema"])
        self.num_features = int(self.topology["num_features"])
        model = build_model(spec, schema)

        with np.load(os.path.join(export_dir, WEIGHTS)) as z:
            flat = {k: z[k] for k in z.files}
        params = _unflatten(flat)

        def fwd(feats):
            return jax.nn.sigmoid(model.apply({"params": params}, feats))

        from ..obs.introspect import instrument_jit
        self._fwd = instrument_jit(fwd, "jax_scorer")
        self._jnp = jnp

    def _score_batch(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(self._fwd(self._jnp.asarray(x)))


class StableHloScorer(BatchScorer):
    """Scores through the serialized jax.export artifact (`scoring.jaxexport`)
    — the compiled-graph tier.  Unlike JaxScorer it does NOT rebuild the Flax
    model from source, so artifacts stay scoreable even if the model classes
    drift; unlike the op-list engines it runs the exact traced computation
    XLA saw at export time.  Succeeds the reference's SavedModel+TF-runtime
    pairing (TensorflowModel.java:169) with a versioned StableHLO module.

    Dtype semantics: this tier replays the model's trained compute_dtype —
    for bfloat16-trained models its scores carry bf16 rounding (~1e-3) and
    are the bit-faithful mirror of the training forward, while the op-list
    tiers (numpy Scorer / native C++) evaluate the same weights in float32.
    For float32-trained models all tiers agree to float32 roundoff."""

    engine = "stablehlo"
    # the export usually carries a symbolic batch dim, but replay still
    # dispatches through jit per concrete shape — padded buckets keep the
    # executable cache bounded either way, at negligible pad compute
    static_shapes = True

    def __init__(self, export_dir: str):
        from jax import export as jax_export

        from .artifact import JAX_EXPORT

        with open(os.path.join(export_dir, TOPOLOGY)) as f:
            self.topology = json.load(f)
        sidecar_path = os.path.join(export_dir, SIDE_CAR)
        if os.path.exists(sidecar_path):
            with open(sidecar_path) as f:
                _reject_extra_inputs(json.load(f), "stablehlo")
        self.num_features = int(self.topology["num_features"])
        path = os.path.join(export_dir, JAX_EXPORT)
        with open(path, "rb") as f:
            self._exported = jax_export.deserialize(bytearray(f.read()))

    def _score_batch(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(self._exported.call(x))


def _unflatten(flat: dict[str, np.ndarray]) -> dict:
    out: dict = {}
    for key, value in flat.items():
        parts = key.split("/")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return out


def load_scorer(export_dir: str):
    """Scorer for an artifact, best tier first: op-list interpreter when the
    program exists, the AOT executable pack (export/aot.py — fingerprint
    match means zero compiles) when shipped, the serialized compiled graph
    (StableHloScorer — no model classes needed) when present, JaxScorer
    (model rebuild) as last resort."""
    from .artifact import JAX_EXPORT

    with open(os.path.join(export_dir, TOPOLOGY)) as f:
        topo = json.load(f)
    if topo.get("program"):
        return Scorer(export_dir)
    from .aot import has_pack, try_load_aot
    if has_pack(export_dir):
        scorer = try_load_aot(export_dir)
        if scorer is not None:
            return scorer  # mismatch journaled aot_fallback; jit below
    if os.path.exists(os.path.join(export_dir, JAX_EXPORT)):
        try:
            return StableHloScorer(export_dir)
        except Exception:
            pass  # deserialization unavailable in this jax — rebuild instead
    return JaxScorer(export_dir)
