"""Model factory: ModelSpec.model_type -> Flax module.

The model ladder tracks BASELINE.md's benchmark configs: MLP (parity with the
reference trainer), Wide&Deep, DeepFM, multi-task heads, FT-Transformer; and
`block_stack`, a causal sequence scorer over rows of token ids, built from a
layer-pattern string (training only).
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn

from ..config.schema import DataSchema, ModelSpec

_BUILDERS: dict[str, Callable[[ModelSpec, DataSchema], nn.Module]] = {}


def register(name: str):
    def deco(fn):
        _BUILDERS[name] = fn
        return fn
    return deco


def build_model(spec: ModelSpec, schema: DataSchema, mesh=None,
                wire=None) -> nn.Module:
    """`mesh` (jax.sharding.Mesh) is forwarded to models that can exploit it
    (FT-Transformer sequence-parallel attention).  Every registered builder
    must accept (spec, schema, mesh=None) and may ignore the mesh.  Scoring/
    export paths pass no mesh and get the single-host local-attention
    graph.

    `wire` is the int8 grid (scale_tuple, offset_tuple_or_None) from
    data/pipeline.wire_params when the training loop feeds wire-format
    int8 features into the model (train/step.wire_fused_into_model); the
    MLP builder attaches it to layer 0 so dequantization fuses into the
    first matmul.  Builders that never see wire inputs ignore it — the
    param tree is unchanged either way."""
    try:
        builder = _BUILDERS[spec.model_type]
    except KeyError:
        raise KeyError(
            f"unknown model_type {spec.model_type!r}; available: {sorted(_BUILDERS)}") from None
    if spec.model_type == "mlp" and wire is not None:
        return builder(spec, schema, mesh=mesh, wire=wire)
    return builder(spec, schema, mesh=mesh)


@register("mlp")
def _build_mlp(spec: ModelSpec, schema: DataSchema,
               mesh=None, wire=None) -> nn.Module:
    from .mlp import ShifuMLP
    return ShifuMLP(spec=spec, wire=wire)


@register("wide_deep")
def _build_wide_deep(spec: ModelSpec, schema: DataSchema,
                     mesh=None) -> nn.Module:
    from .embedding import field_layout
    from .wide_deep import WideDeep
    return WideDeep(spec=spec, layout=field_layout(schema))


@register("deepfm")
def _build_deepfm(spec: ModelSpec, schema: DataSchema,
                  mesh=None) -> nn.Module:
    from .deepfm import DeepFM
    from .embedding import field_layout
    return DeepFM(spec=spec, layout=field_layout(schema))


@register("multitask")
def _build_multitask(spec: ModelSpec, schema: DataSchema,
                     mesh=None) -> nn.Module:
    from .multitask import MultiTask
    return MultiTask(spec=spec)


@register("moe_mlp")
def _build_moe_mlp(spec: ModelSpec, schema: DataSchema,
                   mesh=None) -> nn.Module:
    from .moe import MoEMLP
    return MoEMLP(spec=spec)


@register("ft_transformer")
def _build_ft_transformer(spec: ModelSpec, schema: DataSchema,
                          mesh=None) -> nn.Module:
    from .embedding import field_layout
    from .ft_transformer import FTTransformer
    return FTTransformer(spec=spec, layout=field_layout(schema), mesh=mesh)


@register("block_stack")
def _build_block_stack(spec: ModelSpec, schema: DataSchema,
                       mesh=None) -> nn.Module:
    from .block_stack import BlockStack
    from .embedding import field_layout
    return BlockStack(spec=spec,
                      vocab_size=max(field_layout(schema).vocab_sizes))
