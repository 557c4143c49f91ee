"""Vocab-sharded embedding tables + shard-local rows-touched updates.

For 10M+-row vocabularies a replicated (Nc, V, D) table (plus two Adadelta
moment slots) is the HBM budget — so the engine shards the table's VOCAB
axis across the model mesh axis and keeps the rows-touched update
shard-local, per the cross-replica weight-update sharding design (arxiv
2004.13336): each device owns rows [s*V/S, (s+1)*V/S), receives the
(replicated, batch-proportional) unique-id list, routes ids to itself by
offset arithmetic, and applies the update rule to ITS slice only.  No
device ever materializes the full table, no step all-gathers it — the
only vocab-proportional object anywhere is the sharded table itself.

The DEFAULT_RULES spelling (parallel/sharding.py) shards the stacked
table's axis 0 — the FIELD axis — which caps parallelism at Nc and leaves
each device a full-vocab slice; VOCAB_SHARD_RULES overrides it (prepended
by train/loop.init_state when a sharded sparse plan engages, first match
wins) to split axis 1, the vocab.  Moment slots follow the table's
sharding automatically (init_state places slots with p.sharding).
"""

from __future__ import annotations

from typing import Optional

from jax.sharding import PartitionSpec

from ..parallel.mesh import MODEL_AXIS

# prepended to the rule list by init_state when the sparse plan engages
# sharded: stacked CategoricalEmbed tables (Nc, V, D) split the vocab axis
VOCAB_SHARD_RULES = (
    (r".*[Ee]mbedding.*", PartitionSpec(None, MODEL_AXIS, None)),)


def make_sharded_rows_update(mesh, *, nc: int, vocab: int, shards: int,
                             rule: str, use_pallas: Optional[bool] = None):
    """fn(table, slots, g, ids, lr) -> (new_table, new_slots) over GLOBAL
    vocab-sharded arrays, computed shard-locally under shard_map.

    table/slots/g: (Nc, V, D) sharded P(None, model, None); ids: (U, Nc)
    replicated unique ids (sentinel >= V for padding); lr: scalar.
    Requires vocab % shards == 0 (resolve_plan enforces it with the fix
    spelled out).  Each shard rebases ids by its row offset and maps every
    foreign/sentinel id to the LOCAL sentinel V/S, so the per-shard update
    (fused kernel or XLA reference, ops/pallas_embedding) drops them —
    id→shard routing is pure offset arithmetic, no collective.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops.pallas_embedding import fused_rows_update

    if vocab % shards != 0:
        raise ValueError(f"vocab {vocab} not divisible by {shards} shards")
    vloc = vocab // shards
    tspec = P(None, MODEL_AXIS, None)
    slots_spec = (tspec, tspec) if rule == "adadelta" else ()

    def local(table_l, slots_l, g_l, ids, lr):
        shard = jax.lax.axis_index(MODEL_AXIS)
        lo = shard * vloc
        rebased = ids - lo
        # foreign shards' ids and the dedup sentinel both land on the local
        # sentinel vloc: gathered then dropped, identical to the
        # replicated path's handling of the global sentinel
        local_ids = jnp.where((rebased >= 0) & (rebased < vloc),
                              rebased, vloc)
        safe = jnp.clip(local_ids, 0, vloc - 1)
        g_rows = jnp.stack(
            [g_l[f, safe[:, f]].astype(jnp.float32) for f in range(nc)],
            axis=1)                                          # (U, Nc, D)
        return fused_rows_update(table_l, slots_l, g_rows, local_ids,
                                 rule, lr, use_pallas)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(tspec, slots_spec, tspec, P(), P()),
                       out_specs=(tspec, slots_spec),
                       # axis_index + replicated-by-construction outputs:
                       # the per-device results agree across unmentioned
                       # axes, but the static replication checker can't
                       # see it
                       check_vma=False)

    def update(table, slots, g, ids, lr):
        return fn(table, slots, g, ids, jnp.asarray(lr, jnp.float32))

    return update


def assert_vocab_sharded(table, shards: int) -> None:
    """Test/debug assertion: every addressable shard of the table holds
    V/shards vocab rows — i.e. the full table is never materialized per
    device (ISSUE acceptance criterion)."""
    nc, v, d = table.shape
    for s in table.addressable_shards:
        got = s.data.shape
        if got[1] != v // shards:
            raise AssertionError(
                f"table shard on device {s.device} holds {got} — expected "
                f"vocab slice of {v // shards} rows ({shards} shards)")
