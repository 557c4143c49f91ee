"""Wide&Deep tabular model (BASELINE ladder config #2: ~1000-column
risk-scoring).  New capability over the reference (which only had the MLP);
wired through the same Shifu config/data contracts.

Wide: a linear model over numeric features + per-field categorical biases
(degree-1 memorization).  Deep: the ModelConfig MLP trunk over
[numeric, flattened categorical embeddings] (generalization).  Output head is
the reference-named `shifu_output_0` sigmoid (applied in the loss/scorer).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config.schema import DataSchema, ModelSpec
from ..ops.initializers import xavier_uniform
from .base import MLPTrunk, ShifuDense, dtype_of
from .embedding import (FieldLayout, field_layout, paired_cat_embed,
                        split_features)


class WideDeep(nn.Module):
    spec: ModelSpec
    layout: FieldLayout

    @nn.compact
    def __call__(self, features: jax.Array, *, train: bool = False) -> jax.Array:
        cdt = dtype_of(self.spec.compute_dtype)
        numeric, ids = split_features(features, self.layout)
        numeric = numeric.astype(cdt)

        # -- wide: linear numeric + categorical per-id bias ------------------
        wide = ShifuDense(features=self.spec.num_heads, activation=None,
                          xavier_bias=self.spec.xavier_bias_init,
                          param_dtype=self.spec.param_dtype,
                          compute_dtype=self.spec.compute_dtype,
                          name="wide_linear")(numeric)
        # wide per-id bias + deep embedding read the SAME ids, each with a
        # lookup of its own (embedding.paired_cat_embed)
        emb = None
        if self.layout.num_categorical:
            emb, cat_bias = paired_cat_embed(
                self.layout, self.spec, "deep_embedding",
                "wide_cat_embedding", ids)
            wide = wide + jnp.sum(cat_bias, axis=1)

        # -- deep: MLP over [numeric, cat embeddings] ------------------------
        deep_in = numeric
        if emb is not None:
            deep_in = jnp.concatenate(
                [numeric, emb.reshape(emb.shape[0], -1)], axis=-1)
        deep = MLPTrunk(spec=self.spec, name="trunk")(deep_in, train=train)
        deep = ShifuDense(features=self.spec.num_heads, activation=None,
                          xavier_bias=self.spec.xavier_bias_init,
                          param_dtype=self.spec.param_dtype,
                          compute_dtype=self.spec.compute_dtype,
                          name="shifu_output_0")(deep)

        return (wide + deep).astype(jnp.float32)
