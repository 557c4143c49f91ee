"""Feature embeddings for the tabular model ladder.

Nothing like this exists in the reference (its MLP consumes pre-normalized
floats only — resources/ssgd_monitor.py:113-121); the design is fresh for the
BASELINE ladder's Wide&Deep / DeepFM / FT-Transformer rungs.  TPU-first
choices: one fused table per categorical field; lookups are `jnp.take` so XLA
lowers them to gathers that shard cleanly when tables carry a
`PartitionSpec("model", None)` (parallel/sharding.py DEFAULT_RULES) — the
successor of the reference's variables-on-PS placement
(ssgd_monitor.py:202-206), with the gather's collective riding ICI.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config.schema import DataSchema, ModelSpec
from ..ops.initializers import xavier_uniform
from .base import dtype_of


@dataclasses.dataclass(frozen=True)
class FieldLayout:
    """Positions of numeric vs categorical fields inside the (B, F) feature
    matrix (categorical cells hold integer ids stored as floats)."""

    numeric_positions: tuple[int, ...]
    categorical_positions: tuple[int, ...]
    vocab_sizes: tuple[int, ...]

    @property
    def num_numeric(self) -> int:
        return len(self.numeric_positions)

    @property
    def num_categorical(self) -> int:
        return len(self.categorical_positions)

    @property
    def num_fields(self) -> int:
        return self.num_numeric + self.num_categorical


def field_layout(schema: DataSchema) -> FieldLayout:
    cat_set = set(schema.categorical_indices)
    by_index = {c.index: c for c in schema.columns}
    numeric, cats, vocabs = [], [], []
    for pos, idx in enumerate(schema.selected_indices):
        if idx in cat_set:
            cats.append(pos)
            v = by_index[idx].vocab_size
            vocabs.append(v if v > 0 else 1024)  # hashed fallback vocab
        else:
            numeric.append(pos)
    return FieldLayout(tuple(numeric), tuple(cats), tuple(vocabs))


def split_features(features: jax.Array, layout: FieldLayout
                   ) -> tuple[jax.Array, jax.Array]:
    """(B, F) float -> (numeric (B, Nn) float, categorical ids (B, Nc) int32).

    Ids clip into [0, vocab): out-of-range/unseen ids land in the last bucket,
    matching Shifu's unseen-category bin behavior.  embed/dedup.host_ids is
    the host-side (numpy) replica of this extraction — the feeder's
    unique-id compaction must yield EXACTLY the forward's touched-row set,
    so any change here must land there too."""
    num = features[:, jnp.array(layout.numeric_positions, dtype=jnp.int32)] \
        if layout.num_numeric else jnp.zeros((features.shape[0], 0), features.dtype)
    if layout.num_categorical:
        raw = features[:, jnp.array(layout.categorical_positions, dtype=jnp.int32)]
        ids = raw.astype(jnp.int32)
        vocab = jnp.array(layout.vocab_sizes, dtype=jnp.int32)
        ids = jnp.clip(ids, 0, vocab - 1)
    else:
        ids = jnp.zeros((features.shape[0], 0), jnp.int32)
    return num, ids


class CategoricalEmbed(nn.Module):
    """Per-field embedding tables: ids (B, Nc) -> (B, Nc, dim).

    Tables are stacked per field (ragged vocabs padded to the max) so one
    gather serves all fields — fewer, larger ops for XLA, and a single
    sharding rule puts the vocab axis on `model`.  `table()` exposes the
    compute-dtype table so a caller holding several embeds over the SAME
    ids can concat along dim and pay ONE lookup (see fused_lookup) — the
    per-update cost of a gather/segment-grad pair is mostly per-row, not
    per-byte, so two lookups cost nearly twice one.
    """

    layout: FieldLayout
    dim: int
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    def setup(self):
        if self.layout.num_categorical:
            max_vocab = max(self.layout.vocab_sizes)
            # one stacked table (num_fields, max_vocab, dim); per-field rows
            # beyond that field's vocab are dead weight but keep shapes static
            self.embedding = self.param(
                "embedding", xavier_uniform,
                (self.layout.num_categorical, max_vocab, self.dim),
                dtype_of(self.param_dtype))

    def table(self) -> jax.Array:
        return self.embedding.astype(dtype_of(self.compute_dtype))

    def __call__(self, ids: jax.Array) -> jax.Array:
        if self.layout.num_categorical == 0:
            return jnp.zeros((ids.shape[0], 0, self.dim),
                             dtype_of(self.compute_dtype))
        # gather per field: ids (B, Nc) -> (B, Nc, dim).  Routed through
        # ops/pallas_embedding.embedding_lookup: XLA gather by default, the
        # manual-DMA Pallas kernel under SHIFU_TPU_PALLAS=1.
        from ..ops.pallas_embedding import embedding_lookup
        with jax.named_scope("embed_gather"):
            return embedding_lookup(self.table(), ids.astype(jnp.int32))


def fused_lookup(embeds: Sequence[CategoricalEmbed], ids: jax.Array
                 ) -> list[jax.Array]:
    """One lookup for several CategoricalEmbeds sharing the same ids.

    Concats the tables along dim (cheap: HBM copy, exact), gathers once,
    splits the result back per embed.  Identical values to calling each
    embed separately; roughly halves the sparse-path cost for the models
    that pair a k-dim FM/deep table with a scalar first-order table over
    the same fields (DeepFM, Wide&Deep).

    Under the SHIFU_TPU_PALLAS=1 opt-in the embeds are looked up
    separately instead: the manual-DMA kernel requires D % 128 == 0, and
    a concat of a 128-aligned table with a scalar one would silently
    demote BOTH to the XLA gather.
    """
    from ..ops.pallas_embedding import embedding_lookup
    from ..ops.pallas_common import pallas_opt_in

    if pallas_opt_in():
        return [e(ids) for e in embeds]
    with jax.named_scope("embed_gather"):
        fused = embedding_lookup(
            jnp.concatenate([e.table() for e in embeds], axis=-1),
            ids.astype(jnp.int32))
    outs, off = [], 0
    for e in embeds:
        outs.append(fused[..., off:off + e.dim])
        off += e.dim
    return outs


def paired_cat_embed(layout: FieldLayout, spec: ModelSpec, big_name: str,
                     small_name: str, ids: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """The (embedding_dim table, num_heads table) pair over shared ids
    that DeepFM and Wide&Deep both use, through one fused lookup.
    Returns ((B, Nc, embedding_dim), (B, Nc, num_heads))."""
    big, small = fused_lookup(
        [CategoricalEmbed(layout=layout, dim=spec.embedding_dim,
                          param_dtype=spec.param_dtype,
                          compute_dtype=spec.compute_dtype, name=big_name),
         CategoricalEmbed(layout=layout, dim=spec.num_heads,
                          param_dtype=spec.param_dtype,
                          compute_dtype=spec.compute_dtype,
                          name=small_name)], ids)
    return big, small


class NumericEmbed(nn.Module):
    """Numeric feature tokens: x_j -> x_j * w_j + b_j, (B, Nn) -> (B, Nn, dim).

    Used by DeepFM (value-scaled field vectors) and FT-Transformer (numeric
    tokenizer)."""

    layout: FieldLayout
    dim: int
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    @nn.compact
    def __call__(self, numeric: jax.Array) -> jax.Array:
        if self.layout.num_numeric == 0:
            return jnp.zeros((numeric.shape[0], 0, self.dim),
                             dtype_of(self.compute_dtype))
        w = self.param("weight", xavier_uniform,
                       (self.layout.num_numeric, self.dim),
                       dtype_of(self.param_dtype))
        b = self.param("bias", nn.initializers.zeros,
                       (self.layout.num_numeric, self.dim),
                       dtype_of(self.param_dtype))
        x = numeric.astype(dtype_of(self.compute_dtype))
        return x[:, :, None] * w[None, :, :] + b[None, :, :]
