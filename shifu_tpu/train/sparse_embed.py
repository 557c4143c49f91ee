"""Rows-touched-only optimizer updates for embedding tables.

The r4 bench decomposition showed the DeepFM 100k-vocab rung dominated not
by the gather but by the OPTIMIZER: optax applies Adadelta densely, so
params + 2 moment slots are read+written over the full (Nc, V, D) table
every step — 8x the table bytes — although only the gathered rows have
nonzero gradient.  The reference got sparse updates for free from TF's
IndexedSlices path (its embedding vars lived on the PS and
`resources/ssgd_monitor.py:203-206` applied per-row updates); this module
is the SPMD successor: the tables are masked out of the optax
transformation (optax.masked), their moment slots live on the TrainState
(`table_slots`), and each step gathers the touched rows, applies the
update rule to those rows only, and scatters them back — with buffer
donation the scatter is in-place, so steady-state table traffic is
batch-proportional instead of vocab-proportional.

Semantics are TF's "lazy" sparse semantics (the reference's): untouched
rows see NO moment decay.  SGD is bit-identical to the dense update
(untouched rows get zero gradient either way); Adadelta matches the dense
update exactly on the first step from zero state and diverges only in the
lazy-decay sense afterwards — tests/test_sparse_embed.py pins both plus an
equal-loss A/B.

Duplicate-id safety: the backward (ops/pallas_embedding) already SUMS
per-row gradients (segment_sum / one-hot matmul), so every duplicate id
gathers the same grad row, computes the same update, and the scatter
writes the same value — `.at[].set` with duplicate indices is therefore
deterministic.

This module is the POLICY layer of the sparse embedding engine
(shifu_tpu/embed/, docs/EMBEDDING.md): it decides when the plan engages
and wires the engine's mechanisms into the step — the fused rows-touched
Pallas kernel (ops/pallas_embedding.fused_rows_update) when the feeder's
unique-id dedup vouches for duplicate-free ids, the vocab-sharded
shard-local update (embed/shard) when the table lives split over the
model mesh axis, and the per-field XLA reference otherwise.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config.schema import ConfigError, JobConfig
from ..embed.dedup import UNIQUE_KEY

# TF 1.4 Adadelta defaults, matching train/optimizers.py
_RHO = 0.95
_EPS = 1e-8

# "auto" engages only where the FUSED rows-update kernel can serve the
# scatter (and the vocab is big enough that dense optimizer traffic
# dominates).  The measured negative result for the XLA-scatter path
# stands (r05 rig, not re-measured since): the dense fused adadelta
# elementwise runs at ~760M table-rows/s on a v5e while XLA:TPU scatters
# run at ~30M rows/s AND degrade with table height, so the scatter-based
# sparse path measured 0.2x dense at V=100k/B=32k and still 0.71x at
# V=4M/B=4096 — there is no in-HBM regime where the SCATTER wins.  The
# embed/ engine's kernel sidesteps it: touched rows move by per-row DMA
# with the rule fused in, table traffic batch-proportional, duplicates
# removed upstream by the feeder dedup.  Where the kernel cannot run
# (TPU with an unaligned dim, no CPU opt-in), "auto" stays
# off and "on" keeps the reference path for its IndexedSlices lazy-
# update SEMANTICS (untouched rows see no decay), exactly as before.
_AUTO_MIN_VOCAB = 100_000


def _auto_engages(job: JobConfig) -> bool:
    from ..models.embedding import field_layout
    from ..ops.pallas_common import on_tpu, pallas_opt_in
    from ..ops.pallas_embedding import fused_update_available
    vocabs = field_layout(job.schema).vocab_sizes
    if not vocabs or max(vocabs) < _AUTO_MIN_VOCAB:
        return False
    if not fused_update_available(job.model.embedding_dim):
        return False
    # off-TPU the kernel runs in interpret mode — correct but slow, so it
    # stays behind the same explicit opt-in as every other Pallas kernel
    return on_tpu() or pallas_opt_in()


# model types that build stacked CategoricalEmbed tables the sparse rule
# can own (models/embedding.py paired_cat_embed users)
_TABLE_MODELS = ("wide_deep", "deepfm")


@dataclasses.dataclass(frozen=True)
class SparseEmbedPlan:
    """Resolved sparse-update plan: which update rule, at what lr, over
    tables matching (num_categorical, max_vocab) leaves named 'embedding'."""

    rule: str                    # "adadelta" | "sgd"
    learning_rate: Any           # float or optax schedule (fn of step)
    layout: Any                  # models.embedding.FieldLayout
    shards: int = 1              # model-mesh vocab shards (1 = replicated)

    @property
    def num_categorical(self) -> int:
        return self.layout.num_categorical

    @property
    def max_vocab(self) -> int:
        return max(self.layout.vocab_sizes) if self.layout.vocab_sizes else 0


def _is_table_leaf(path, leaf, plan: SparseEmbedPlan) -> bool:
    """A sparse-updatable table: the stacked CategoricalEmbed param
    (models/embedding.py setup: name 'embedding', shape (Nc, V, D))."""
    names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
    return (bool(names) and names[-1] == "embedding"
            and hasattr(leaf, "ndim") and leaf.ndim == 3
            and leaf.shape[0] == plan.num_categorical
            and leaf.shape[1] == plan.max_vocab)


def resolve_plan(job: JobConfig) -> Optional[SparseEmbedPlan]:
    """The job's sparse-embedding plan, or None (dense updates).

    "auto" engages when every structural requirement holds AND the vocab
    is big enough that dense optimizer traffic dominates; "on" demands the
    structural requirements and raises with the specific blocker
    otherwise; "off" is None.
    """
    mode = job.train.sparse_embedding_update
    if mode == "off":
        return None
    opt = job.train.optimizer
    name = opt.name.lower()
    rule = {"adadelta": "adadelta", "sgd": "sgd",
            "gradientdescent": "sgd"}.get(name)

    def blocker() -> Optional[str]:
        if not job.schema.categorical_indices:
            return "the schema has no categorical columns"
        if job.model.model_type not in _TABLE_MODELS:
            return (f"model {job.model.model_type!r} has no stacked "
                    f"embedding tables (supported: "
                    f"{', '.join(_TABLE_MODELS)})")
        if rule is None:
            return f"optimizer {opt.name!r} has no sparse rule " \
                   "(supported: adadelta, sgd)"
        if opt.grad_clip_norm > 0:
            return "grad_clip_norm needs the full gradient tree"
        if opt.accumulate_steps > 1:
            return "gradient accumulation buffers dense gradients"
        if job.train.local_sgd_window > 0:
            return "local-SGD replicas stack params on the data axis"
        if job.runtime.mesh.model > 1:
            # vocab-sharded tables (embed/shard): the padded max vocab
            # must split evenly over the model axis — shard-local id
            # routing is pure offset arithmetic over equal slices
            from ..models.embedding import field_layout
            v = max(field_layout(job.schema).vocab_sizes)
            if v % job.runtime.mesh.model != 0:
                return (f"vocab-sharded tables need max vocab ({v}) "
                        f"divisible by the model axis "
                        f"({job.runtime.mesh.model})")
        if job.model.pipeline_stages > 1:
            return "pipeline-stacked trunks reshape the param tree"
        return None

    why_not = blocker()
    if mode == "on":
        if why_not is not None:
            raise ConfigError(
                f"sparse_embedding_update=on but {why_not}")
    else:  # auto
        if why_not is not None:
            return None

    if mode == "auto" and not _auto_engages(job):
        return None
    from ..models.embedding import field_layout
    from .optimizers import _learning_rate
    return SparseEmbedPlan(rule=rule, learning_rate=_learning_rate(opt),
                           layout=field_layout(job.schema),
                           shards=max(job.runtime.mesh.model, 1))


def dense_mask(params, plan: SparseEmbedPlan):
    """Pytree of bools for optax.masked: True = the dense optimizer owns
    the leaf, False = a sparse-updated table."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: not _is_table_leaf(path, leaf, plan), params)


def init_table_slots(params, plan: SparseEmbedPlan):
    """Moment slots for the sparse-updated tables: zeros shaped like each
    table (accu, delta_accu) for adadelta, None-equivalent empty tuple for
    sgd.  Lives on TrainState.table_slots; placed alongside the tables by
    init_state."""
    if plan.rule == "sgd":
        return ()

    def slots(path, leaf):
        if _is_table_leaf(path, leaf, plan):
            # two DISTINCT zero buffers: (z, z) would alias one buffer into
            # both slots, and donating the state then donates that buffer
            # twice — the TPU runtime rejects the program at execution
            return (jnp.zeros(leaf.shape, jnp.float32),
                    jnp.zeros(leaf.shape, jnp.float32))
        return None
    return jax.tree_util.tree_map_with_path(slots, params)


def extract_ids(features: jax.Array, plan: SparseEmbedPlan) -> jax.Array:
    """(B, F) float features -> (B, Nc) clipped int32 ids — THE model-side
    extraction (models/embedding.split_features, not a re-implementation),
    so the touched-row set always equals the forward's gathered rows."""
    from ..models.embedding import split_features
    return split_features(features, plan.layout)[1]


def make_sparse_apply(job: JobConfig, mesh=None) -> Optional[Callable]:
    """None, or fn(state, grads, batch) -> new TrainState applying the
    masked dense transformation to non-table leaves and the sparse
    rows-touched-only rule to the tables.  `batch` is the step's batch
    dict (or the bare (B, F) DECODED feature matrix — categorical jobs
    always ride the f32 wire, wire_mode refuses bf16/int8 for id
    columns).  When the feeder attached the dedup keys (embed/dedup),
    the update runs over the compacted unique-id set — which is also
    what licenses the fused Pallas kernel (its DMA write-back has no
    deterministic duplicate resolution); raw-id batches keep the XLA
    reference.  Vocab-sharded plans (shards > 1) run the update
    shard-locally under shard_map (embed/shard)."""
    import optax

    plan = resolve_plan(job)
    if plan is None:
        return None
    rule = plan.rule
    lr_of = (plan.learning_rate if callable(plan.learning_rate)
             else (lambda _step, _lr=plan.learning_rate: _lr))
    nc = plan.num_categorical
    vocab = plan.max_vocab
    embed_cfg = getattr(job, "embed", None)
    dedup_on = embed_cfg is None or embed_cfg.dedup != "off"
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        replicated = NamedSharding(mesh, PartitionSpec())
    else:
        replicated = None

    from ..ops.pallas_embedding import fused_rows_update
    sharded = {}
    if plan.shards > 1:
        if mesh is None:
            raise ConfigError(
                f"sparse plan wants {plan.shards} vocab shards but no "
                "mesh was built")
        from ..embed.shard import make_sharded_rows_update
        for deduped in (False, True):
            # the fused kernel's unique-id contract holds only for
            # dedup'd batches; raw-id batches pin the reference path
            sharded[deduped] = make_sharded_rows_update(
                mesh, nc=nc, vocab=vocab, shards=plan.shards, rule=rule,
                use_pallas=None if deduped else False)

    def update_table(table, slots, g, ids, step, deduped):
        # rows-touched only: gather the touched rows' grads (per-FIELD
        # 2-D gathers, the same decomposition the backward's segment path
        # prefers on TPU), then one fused-or-reference rule application
        # (ops/pallas_embedding) writes them back.  Dedup-sentinel ids
        # (>= vocab) gather-clamp garbage and drop on the write.
        lr = lr_of(step)
        slots_t = slots if rule != "sgd" else ()
        if plan.shards > 1:
            t2, s2 = sharded[deduped](table, slots_t, g, ids, lr)
        else:
            g_rows = jnp.stack(
                [g[f, ids[:, f]].astype(jnp.float32) for f in range(nc)],
                axis=1)                                      # (U, Nc, D)
            t2, s2 = fused_rows_update(table, slots_t, g_rows, ids, rule,
                                       lr, None if deduped else False)
        return t2, (s2 if rule != "sgd" else slots)

    def apply(state, grads, batch):
        if isinstance(batch, dict):
            features = batch["features"]
            unique = batch.get(UNIQUE_KEY) if dedup_on else None
        else:
            features, unique = batch, None
        if unique is not None:
            ids, deduped = unique, True
        else:
            ids, deduped = extract_ids(features, plan), False
        if replicated is not None:
            # ids replicated: under a data-sharded batch each device holds
            # its shard's ids, but every replica of the table must receive
            # EVERY row's update — the constraint makes XLA all-gather ids
            # (B*Nc ints: batch-proportional, vs the vocab-proportional
            # dense update being replaced)
            ids = jax.lax.with_sharding_constraint(ids, replicated)
        # optax.masked passes masked-out (table) leaves' updates through
        # UNCHANGED, so for table leaves `updates` carries the raw summed
        # gradient — exactly the g the sparse rule needs
        updates, new_opt = state.tx.update(grads, state.opt_state,
                                           state.params)
        flat, treedef = jax.tree_util.tree_flatten_with_path(state.params)
        paths = [p for p, _ in flat]
        leaves_p = [l for _, l in flat]
        leaves_u = treedef.flatten_up_to(updates)
        leaves_s = (treedef.flatten_up_to(state.table_slots)
                    if rule != "sgd" else [None] * len(leaves_p))
        new_p, new_s = [], []
        for path, p, u, s in zip(paths, leaves_p, leaves_u, leaves_s):
            if _is_table_leaf(path, p, plan):
                p2, s2 = update_table(p, s, u, ids, state.step, deduped)
                new_p.append(p2)
                new_s.append(s2)
            else:
                new_p.append(optax.apply_updates(p, u))
                new_s.append(s)
        params = jax.tree_util.tree_unflatten(treedef, new_p)
        slots = (jax.tree_util.tree_unflatten(treedef, new_s)
                 if rule != "sgd" else state.table_slots)
        return state.replace(step=state.step + 1, params=params,
                             opt_state=new_opt, table_slots=slots)

    return apply
