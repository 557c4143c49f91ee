"""DeepFM tabular model (BASELINE ladder config #3: CTR with
high-cardinality categoricals and a sharded embedding table).

Every selected column is a "field" with a k-dim latent vector: categorical
fields via table lookup, numeric fields via value-scaled vectors
(models/embedding.py).  Components share those vectors:

- first-order: sum of per-field scalar weights,
- FM second-order: 0.5 * ((sum_f v_f)^2 - sum_f v_f^2) summed over dims —
  all pairwise interactions in O(fields * dim),
- deep: the ModelConfig MLP trunk over the flattened field vectors.

The embedding tables match parallel/sharding.py's DEFAULT_RULES (vocab axis
on `model`) — the fresh design SURVEY.md section 7.3 called for, succeeding
PS-side variable placement.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config.schema import ModelSpec
from .base import MLPTrunk, ShifuDense, dtype_of
from .embedding import (FieldLayout, NumericEmbed, paired_cat_embed,
                        split_features)


class DeepFM(nn.Module):
    spec: ModelSpec
    layout: FieldLayout

    @nn.compact
    def __call__(self, features: jax.Array, *, train: bool = False) -> jax.Array:
        numeric, ids = split_features(features, self.layout)

        # field vectors (B, F, k): numeric + categorical share the FM space.
        # The k-dim FM/deep table and the scalar first-order table read the
        # SAME ids, each with a lookup of its own (embedding.paired_cat_embed)
        vecs = []
        cat_first = None
        if self.layout.num_numeric:
            vecs.append(NumericEmbed(layout=self.layout, dim=self.spec.embedding_dim,
                                     param_dtype=self.spec.param_dtype,
                                     compute_dtype=self.spec.compute_dtype,
                                     name="numeric_embedding")(numeric))
        if self.layout.num_categorical:
            cat_vec, cat_first = paired_cat_embed(
                self.layout, self.spec, "cat_embedding", "first_order_cat",
                ids)
            vecs.append(cat_vec)
        v = jnp.concatenate(vecs, axis=1)  # (B, F, k)

        # first-order terms (B, H)
        first = ShifuDense(features=self.spec.num_heads, activation=None,
                           xavier_bias=self.spec.xavier_bias_init,
                           param_dtype=self.spec.param_dtype,
                           compute_dtype=self.spec.compute_dtype,
                           name="first_order_numeric")(
            numeric.astype(dtype_of(self.spec.compute_dtype)))
        if cat_first is not None:
            first = first + jnp.sum(cat_first, axis=1)

        # FM second-order: 0.5 * ((sum v)^2 - sum v^2), summed over k -> (B, 1)
        sum_sq = jnp.square(jnp.sum(v, axis=1))
        sq_sum = jnp.sum(jnp.square(v), axis=1)
        fm = 0.5 * jnp.sum(sum_sq - sq_sum, axis=-1, keepdims=True)

        # deep over flattened field vectors
        deep = MLPTrunk(spec=self.spec, name="trunk")(v.reshape(v.shape[0], -1),
                                                      train=train)
        deep = ShifuDense(features=self.spec.num_heads, activation=None,
                          xavier_bias=self.spec.xavier_bias_init,
                          param_dtype=self.spec.param_dtype,
                          compute_dtype=self.spec.compute_dtype,
                          name="shifu_output_0")(deep)

        return (first + fm.astype(jnp.float32) + deep).astype(jnp.float32)
