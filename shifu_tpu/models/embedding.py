"""Feature embeddings for the tabular model ladder.

Nothing like this exists in the reference (its MLP consumes pre-normalized
floats only — resources/ssgd_monitor.py:113-121); the design is fresh for the
BASELINE ladder's Wide&Deep / DeepFM / FT-Transformer rungs.  TPU-first
choices: one stacked table per model input; lookups are XLA gathers
(ops/pallas_embedding.py) that shard cleanly when tables carry a
`PartitionSpec("model", None)` (parallel/sharding.py DEFAULT_RULES) — the
successor of the reference's variables-on-PS placement
(ssgd_monitor.py:202-206), with the gather's collective riding ICI.
"""

from __future__ import annotations

import dataclasses

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
    sharding rule puts the vocab axis on `model`.  The lookup reads the
    rows where the table lives: it gathers from the `param_dtype`
    parameter and casts the (B, Nc, dim) rows it got, so nothing the size
    of the table is made on the way in, and the gradient comes back in
    the parameter's dtype, summed at the rows (ops/pallas_embedding.py).
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

    def __call__(self, ids: jax.Array) -> jax.Array:
        return lookup_embeds([self], ids)[0]


def lookup_embeds(embeds, ids: jax.Array) -> list[jax.Array]:
    """ids (B, Nc) -> one (B, Nc, dim) per embed, for CategoricalEmbeds of
    one layout and compute dtype that read the same ids.  Routed through
    ops/pallas_embedding.lookup_rows: an XLA gather from each parameter by
    default (the manual-DMA Pallas kernel under SHIFU_TPU_PALLAS=1), one
    MXU product a field for all of them together at small vocabularies."""
    from ..ops.pallas_embedding import lookup_rows

    first = embeds[0]
    cdt = dtype_of(first.compute_dtype)
    if first.layout.num_categorical == 0:
        return [jnp.zeros((ids.shape[0], 0, e.dim), cdt) for e in embeds]
    with jax.named_scope("embed_gather"):
        return lookup_rows([e.embedding for e in embeds],
                           ids.astype(jnp.int32), cdt)


def paired_cat_embed(layout: FieldLayout, spec: ModelSpec, big_name: str,
                     small_name: str, ids: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """The (embedding_dim table, num_heads table) pair over shared ids
    that DeepFM and Wide&Deep both use, each looked up in its own
    parameter (joining them along dim for one gather makes, splits and
    re-lays-out whole tables, forward and backward: far more than a second
    gather and scatter-add of a batch's rows cost; `lookup_rows` joins
    them only where the one-hot strategy serves).
    Returns ((B, Nc, embedding_dim), (B, Nc, num_heads))."""
    big, small = lookup_embeds(
        [CategoricalEmbed(layout=layout, dim=dim,
                          param_dtype=spec.param_dtype,
                          compute_dtype=spec.compute_dtype, name=name)
         for dim, name in ((spec.embedding_dim, big_name),
                           (spec.num_heads, small_name))], ids)
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
