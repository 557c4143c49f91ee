"""Model export: the scoring artifact + Shifu sidecar.

Replaces the reference chief worker's end-of-training export
(resources/ssgd_monitor.py:302-345 rebuild-graph + SavedModel write, sidecar
at :457-490): after training, the framework writes a self-contained artifact
directory that the eval side scores WITHOUT any TF/JAX runtime:

    <export_dir>/
      GenericModelConfig.json   # byte-compatible sidecar fields (inputnames=
                                # [shifu_input_0], outputnames=shifu_output_0,
                                # normtype=ZSCALE, tags=[serve])
      topology.json             # format v1: an op-list "program" + metadata
      weights.npz               # flat params, keys referenced by the program
      scoring.mlir              # StableHLO of the scoring fn (AOT/native path)

The op-list program (format v2, export/program.py) is the artifact's
executable spec: an SSA-style op sequence over named buffers (dense,
embedding lookup, FM interaction, layernorm, transformer block, ...) that
lowers every ladder model — MLP, Wide&Deep, DeepFM, multi-task,
FT-Transformer — and is executed identically (float32-roundoff parity) by
the numpy interpreter (export/scorer.py) and the native C++ engine
(runtime/csrc/shifu_scorer.cc).
"""

from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax
import numpy as np

from ..config.schema import JobConfig, ModelSpec

FORMAT_VERSION = 1
SIDE_CAR = "GenericModelConfig.json"
TOPOLOGY = "topology.json"
WEIGHTS = "weights.npz"
STABLEHLO = "scoring.mlir"
JAX_EXPORT = "scoring.jaxexport"
BASELINE_PROFILE = "baseline_profile.json"


def _key_name(entry: Any) -> str:
    if hasattr(entry, "key"):
        return str(entry.key)
    if hasattr(entry, "idx"):
        return str(entry.idx)
    return str(entry)


def _flatten_params(params: Any) -> dict[str, np.ndarray]:
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {"/".join(_key_name(e) for e in kp): np.asarray(jax.device_get(leaf))
            for kp, leaf in flat}


def build_program(spec: ModelSpec, schema=None) -> Optional[list[dict[str, Any]]]:
    """The op-list program for the artifact (format v2, export/program.py).

    Lowers every ladder model type — MLP, Wide&Deep, DeepFM, multi-task,
    FT-Transformer — to the portable tensor program executed by the numpy
    interpreter and the native C++ engine.  The trailing sigmoid reproduces
    the reference's scoring head (ssgd_monitor.py:121).  Returns None only
    for unknown model types (those score through JaxScorer).
    """
    from .program import build_program_v2
    return build_program_v2(spec, schema)


def export_stablehlo(forward_fn, params, num_features: int, path: str,
                     batch: int = 1) -> bool:
    """Serialize the scoring fn to StableHLO text plus the binary jax.export
    artifact (`scoring.jaxexport`, executable by export/scorer.py
    StableHloScorer without the model class).  The batch dimension is
    exported symbolically so one artifact serves any row count.
    Best-effort: returns False when jax.export is unavailable."""
    try:
        from jax import export as jax_export
        import jax.numpy as jnp

        fn = lambda feats: forward_fn(params, feats)
        exported = None
        from ..obs.introspect import compile_span
        with compile_span("export_stablehlo"):
            try:  # symbolic batch: score any (N, F) without re-export
                (dim,) = jax_export.symbolic_shape("batch")
                shape = jax.ShapeDtypeStruct((dim, num_features), jnp.float32)
                exported = jax_export.export(jax.jit(fn))(shape)
            except Exception:
                pass  # fall back to a concrete batch below
            if exported is None:
                shape = jax.ShapeDtypeStruct((batch, num_features),
                                             jnp.float32)
                exported = jax_export.export(jax.jit(fn))(shape)
        with open(path, "w") as f:
            f.write(exported.mlir_module())
        try:
            blob = exported.serialize()
            with open(os.path.join(os.path.dirname(path), JAX_EXPORT),
                      "wb") as f:
                f.write(blob)
        except Exception:
            pass  # text form still written; StableHloScorer tier unavailable
        return True
    except Exception:
        return False


def save_artifact(params: Any, job: JobConfig, export_dir: str,
                  forward_fn=None, algorithm: str = "tensorflow",
                  extra_inputs: Optional[dict] = None,
                  baseline_profile: Optional[dict] = None,
                  aot_pack: bool = False,
                  aot_buckets: Optional[tuple] = None) -> str:
    """Write the full scoring artifact; returns export_dir.

    `baseline_profile` (obs/sketch.build_profile — the frozen stats
    epoch from the train loop) is written as `baseline_profile.json`
    BEFORE the sync manifest so its digest rides `sync_manifest.json`
    and `fleet-verify` can audit that every fleet member served the
    same baseline.  None (checkpoint-recovery re-exports, external
    artifacts) just means the drift observatory stays dormant.

    `algorithm` defaults to "tensorflow" for byte-level sidecar parity with
    the reference (ssgd_monitor.py:476-490) so an unmodified Shifu eval step
    routes the model to its generic scorer the same way.

    `aot_pack` (the `shifu.serving.aot-pack` key / `--aot-pack` flag)
    additionally compiles the scorer for every rung of the serving
    bucket ladder and ships the serialized executables in `aot/`
    (export/aot.py) — written BEFORE the sync manifest, so the pack is
    digest-verified by the per-host fleet sync like any other artifact
    file.  `aot_buckets` overrides the rung grid (default: the
    ServingConfig-default ladder).  Requires `forward_fn`; best-effort
    like the StableHLO export.

    `extra_inputs` maps auxiliary input names to constant values; they are
    recorded as additional sidecar inputnames whose VALUES live in the
    properties map — the reference's multi-input contract, where
    TensorflowModel.compute feeds inputNames[1:] from GenericModelConfig
    properties (TensorflowModel.java:74-87).  Scorers bind them as named
    buffers (`input:<name>`) the op-list program can reference.
    """
    import dataclasses as _dc
    from ..config.schema import refuse_training_only
    refuse_training_only(job.model.model_type, "export")
    if (job.model.model_type == "ft_transformer"
            and job.model.pipeline_stages > 1):
        # pipeline parallelism is a training-time layout: export ships the
        # canonical per-block artifact (identical scoring graph + weights)
        from ..models.ft_transformer import canonicalize_params
        params = canonicalize_params(dict(jax.device_get(params)), job.model)
        job = job.replace(model=_dc.replace(job.model, pipeline_stages=1,
                                            pipeline_microbatches=0))
        if forward_fn is not None:
            from ..train.step import make_forward_fn
            forward_fn = make_forward_fn(job)
    os.makedirs(export_dir, exist_ok=True)

    flat = _flatten_params(params)
    np.savez(os.path.join(export_dir, WEIGHTS), **flat)

    program = build_program(job.model, job.schema)
    if program is not None:
        from .program import weight_keys
        missing = [k for k in weight_keys(program) if k not in flat]
        if missing:
            raise ValueError(f"program references missing weights: {missing}; "
                             f"have {sorted(flat)}")

    import dataclasses
    topology = {
        "format_version": FORMAT_VERSION,
        "program_version": 2 if program is not None else None,
        "model_type": job.model.model_type,
        "num_features": job.schema.feature_count,
        "num_heads": job.model.num_heads,
        "head_names": list(job.model.head_names),
        "selected_indices": list(job.schema.selected_indices),
        "program": program,
        # full specs for the JAX-fallback scorer (and future op-list lowerings)
        "model_spec": dataclasses.asdict(job.model),
        "schema": dataclasses.asdict(job.schema),
    }
    with open(os.path.join(export_dir, TOPOLOGY), "w") as f:
        json.dump(topology, f, indent=2)

    sidecar = {
        "inputnames": ["shifu_input_0"],
        "properties": {
            "algorithm": algorithm,
            "tags": ["serve"],
            "outputnames": "shifu_output_0",
            "normtype": "ZSCALE",
        },
    }
    for name, value in (extra_inputs or {}).items():
        if name in sidecar["properties"] or name == sidecar["inputnames"][0]:
            raise ValueError(
                f"extra input name {name!r} collides with a reserved "
                "sidecar field (algorithm/tags/outputnames/normtype/"
                "shifu_input_0)")
        arr = np.asarray(value, dtype=np.float32).ravel()
        if arr.size == 0:
            raise ValueError(f"extra input {name!r} has an empty value")
        sidecar["inputnames"].append(name)
        sidecar["properties"][name] = arr.tolist()
    with open(os.path.join(export_dir, SIDE_CAR), "w") as f:
        json.dump(sidecar, f, indent=4)

    if baseline_profile is not None:
        from ..obs import sketch as _sketch
        _sketch.validate_profile(baseline_profile)
        with open(os.path.join(export_dir, BASELINE_PROFILE), "w") as f:
            json.dump(baseline_profile, f)

    if forward_fn is not None:
        export_stablehlo(forward_fn, params, job.schema.feature_count,
                         os.path.join(export_dir, STABLEHLO))
        if aot_pack:
            from ..runtime.serve import bucket_ladder
            from .aot import build_aot_pack
            if aot_buckets is None:
                from ..config.schema import ServingConfig
                _sc = ServingConfig()
                aot_buckets = bucket_ladder(_sc.min_batch_bucket,
                                            _sc.max_batch)
            build_aot_pack(export_dir, forward_fn, params,
                           job.schema.feature_count, job.model.num_heads,
                           tuple(aot_buckets))
    try:
        # digest manifest for cross-host fleet pulls (runtime/fleet.py
        # sync_artifact verifies against it); best-effort — a local-only
        # artifact serves fine without one
        from ..runtime.fleet import write_sync_manifest
        write_sync_manifest(export_dir)
    except Exception:
        pass
    return export_dir
