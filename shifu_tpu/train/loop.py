"""The training loop: one SPMD program from data to exported model.

Collapses the reference's five-process pipeline (client -> AM -> container
executor -> python trainer -> PS; SURVEY.md section 1) into one function.  The
per-epoch console line keeps the reference's operator UX — epoch, weighted
train/valid error, epoch wall time (fields of
core/TrainingIntermediateResult.java:41-43, aggregated by
appmaster/TensorflowSession.java:515-549) — plus AUC.
"""

from __future__ import annotations

import dataclasses
import os
import re
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import chaos, obs
from ..config.schema import ConfigError, JobConfig
from ..data import pipeline as pipe
from ..models.registry import build_model
from ..ops import metrics as metrics_lib
from ..parallel import mesh as mesh_lib
from ..parallel import sharding as shard_lib
from . import checkpoint as ckpt_lib
from .optimizers import build_optimizer
from .step import (make_epoch_scan_step, make_eval_step,
                   make_resident_eval_step, make_train_step, split_readback)
from .train_state import TrainState

Console = Callable[[str], None]


@dataclasses.dataclass
class EpochMetrics:
    epoch: int
    train_error: float
    valid_error: float
    valid_auc: float
    epoch_time: float
    valid_time: float

    def console_line(self, total_epochs: int = 0) -> str:
        # Reference line shape: worker_index,time,current_epoch,training_loss,
        # valid_loss,valid_time (ssgd_monitor.py:287-293) aggregated by the AM;
        # progress % mirrors the AM's globalEpoch/totalEpochs report incl.
        # resumed-epoch offset (AMRMCallbackHandler.java:224-244).
        progress = (f" progress={100.0 * (self.epoch + 1) / total_epochs:.0f}%"
                    if total_epochs > 0 else "")
        return (f"Epoch {self.epoch}: train_error={self.train_error:.6f} "
                f"valid_error={self.valid_error:.6f} valid_auc={self.valid_auc:.4f} "
                f"time={self.epoch_time:.2f}s valid_time={self.valid_time:.2f}s"
                f"{progress}")


@dataclasses.dataclass
class TrainResult:
    state: Any
    history: list[EpochMetrics]
    job: JobConfig
    resumed_from_epoch: int = 0
    # the frozen stats epoch (obs/sketch.build_profile): training-feature
    # + score-distribution sketches from the LAST evaluated epoch, frozen
    # into the export artifact as baseline_profile.json so the serving
    # drift engine has something to diff live traffic against.  None when
    # the run never evaluated (no valid rows) or features were unreadable.
    baseline_profile: Optional[dict] = None


def init_state(job: JobConfig, num_features: int,
               mesh: Optional[Mesh] = None) -> TrainState:
    """Build model + optimizer and initialize (optionally mesh-placed) state."""
    if (mesh is not None and job.model.pipeline_stages > 1
            and int(mesh.shape.get("pipe", 1)) > 1
            and int(mesh.shape["pipe"]) != job.model.pipeline_stages):
        # the effective stage count IS the mesh's pipe axis: demand the
        # config agree rather than silently running a different split or
        # crashing inside shard_map with a bare divisibility error
        raise ConfigError(
            f"mesh pipe axis ({int(mesh.shape['pipe'])}) must equal "
            f"model.pipeline_stages ({job.model.pipeline_stages})")
    if job.model.pipeline_stages > 1:
        # fail at init with the fix spelled out, not at the first train step
        # deep inside shard_map with a bare divisibility error
        n_micro = (job.model.pipeline_microbatches
                   or job.model.pipeline_stages)
        n_data = int(mesh.shape.get("data", 1)) if mesh is not None else 1
        bs = job.data.batch_size
        if bs % n_micro != 0 or (bs // n_micro) % n_data != 0:
            raise ConfigError(
                f"batch_size ({bs}) must be divisible by pipeline "
                f"microbatches ({n_micro}) x data axis ({n_data}); "
                f"use a multiple of {n_micro * n_data}")
    wire = None
    from .step import wire_fused_into_model
    if wire_fused_into_model(job):
        # int8 features reach the model natively: attach the static wire
        # grid so layer 0 fuses the dequant into its matmul
        # (models/base._WireDense); param tree and init values are
        # identical to the unfused build
        scale, offset = pipe.wire_params(job.schema, job.data)
        wire = (tuple(float(v) for v in scale),
                tuple(float(v) for v in offset) if np.any(offset) else None)
    model = build_model(job.model, job.schema, mesh, wire=wire)
    tx = build_optimizer(job.train.optimizer)
    rng = jax.random.PRNGKey(job.train.seed)
    # init batch must divide the data axis: a mesh-aware model (sequence-
    # parallel attention) shard_maps the batch dimension even at init —
    # and the pipelined trunk additionally splits it into microbatches
    init_batch = int(mesh.shape.get("data", 1)) if mesh is not None else 1
    if job.model.pipeline_stages > 1:
        init_batch *= (job.model.pipeline_microbatches
                       or job.model.pipeline_stages)
    dummy = jnp.zeros((init_batch, num_features), jnp.float32)
    # one program, and only the parameters come out of it: the forward pass
    # that shapes them is dead code there, where op-by-op it would run (and
    # compile) every operation of a deep model once
    params = jax.jit(lambda r, x: model.init(r, x)["params"])(rng, dummy)
    # sparse embedding updates (train/sparse_embed.py): tables are masked
    # OUT of the dense optax transformation and their moment slots live on
    # TrainState.table_slots, updated rows-touched-only by the step
    table_slots = None
    from . import sparse_embed as sparse_lib
    sparse_plan = sparse_lib.resolve_plan(job)
    if sparse_plan is not None and not all(jax.tree_util.tree_leaves(
            sparse_lib.dense_mask(params, sparse_plan))):
        import optax
        tx = optax.masked(tx, lambda p: sparse_lib.dense_mask(p, sparse_plan))
        table_slots = sparse_lib.init_table_slots(params, sparse_plan)
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx,
                              table_slots=table_slots)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        rules: tuple = ()
        # config-supplied rules first (first match wins in param_specs):
        # the operator's tensor-parallel placements override the built-ins
        for pattern, axes in job.runtime.param_sharding_rules:
            try:
                re.compile(pattern)
            except re.error as e:
                raise ConfigError(
                    f"shifu.sharding.rules: bad path regex {pattern!r}: {e}")
            for axis in axes:
                if axis is not None and axis not in mesh.shape:
                    raise ConfigError(
                        f"sharding rule {pattern!r}: axis {axis!r} not in "
                        f"mesh axes {sorted(mesh.shape)}")
            rules += ((pattern, P(*axes)),)
        if sparse_plan is not None and sparse_plan.shards > 1:
            # sparse engine owns the tables: split the VOCAB axis (not the
            # DEFAULT_RULES field axis) so the rows-touched update runs
            # shard-local over V/shards rows per device (embed/shard);
            # table_slots placement below follows the table's sharding
            from ..embed.shard import VOCAB_SHARD_RULES
            rules += tuple(VOCAB_SHARD_RULES)
        if job.runtime.mesh.model > 1:
            rules += tuple(shard_lib.DEFAULT_RULES)
            if job.model.model_type in ("moe_mlp", "block_stack"):
                # expert parallelism: stacked expert trunks shard by expert
                # over `model`; XLA inserts the psum of the gated combine
                rules += ((r".*\bexperts/.*", P("model")),)
        if (job.model.pipeline_stages > 1
                and int(mesh.shape.get("pipe", 1)) > 1):
            # stacked trunk layers shard by stage: each device holds (and
            # updates) only its own pipeline stage's parameters
            rules += ((r".*\bblocks\b.*", P("pipe")),)
        placed_params = shard_lib.place_params(state.params, mesh, rules)
        # optimizer slots follow their param's sharding (a vocab-sharded
        # embedding or stage-sharded pipeline trunk keeps its optimizer
        # memory sharded too, instead of replicating it on every device)
        placed_opt = shard_lib.place_opt_state(state.opt_state, state.params,
                                               mesh, rules)
        placed_slots = state.table_slots
        if placed_slots is not None and placed_slots != ():
            # sparse-table moment slots follow their table's sharding
            flat_pp, treedef = jax.tree_util.tree_flatten(placed_params)
            slot_objs = treedef.flatten_up_to(placed_slots)
            placed_slot_objs = [
                s if s is None else tuple(
                    jax.device_put(x, p.sharding) for x in s)
                for p, s in zip(flat_pp, slot_objs)]
            placed_slots = jax.tree_util.tree_unflatten(
                treedef, placed_slot_objs)
        state = state.replace(
            params=placed_params,
            opt_state=placed_opt,
            table_slots=placed_slots,
            step=jax.device_put(state.step, shard_lib.replicated(mesh)),
        )
    return state


def restore_latest_any_layout(manager, state: TrainState, job: JobConfig,
                              console: "Console"):
    """restore_latest with the ft_transformer trunk-layout fallback: returns
    (state_like, extra, step) or None (no checkpoint); re-raises the original
    restore error when the checkpoint is genuinely incompatible.  Shared by
    the train loop's resume and the export CLI's recovery path."""
    try:
        return ckpt_lib.restore_latest(
            manager, jax.tree_util.tree_map(lambda x: x, state),
            with_extra=True)
    except Exception:
        restored = _restore_across_trunk_layout(manager, state, job, console)
        if restored is None:
            raise
        return restored


def _restore_across_trunk_layout(manager, state: TrainState, job: JobConfig,
                                 console: "Console"):
    """Resume an ft_transformer run from a checkpoint written with the OTHER
    trunk layout (per-block vs pipeline-stacked — `pipeline_stages` is a
    layout choice, not part of the model).  Weights convert exactly
    (models/ft_transformer canonicalize/stack); optimizer slots restart
    fresh, which the console notes.  Returns (state, extra, step) or None.
    """
    if job.model.model_type != "ft_transformer":
        return None
    from ..models import ft_transformer as ftt
    from ..models.registry import build_model

    cur = job.model
    if cur.pipeline_stages > 1:
        alt_model = dataclasses.replace(cur, pipeline_stages=1,
                                        pipeline_microbatches=0)
        convert = ftt.stack_block_params
    else:
        stages = next((s for s in range(2, cur.num_layers + 1)
                       if cur.num_layers % s == 0), 1)
        if stages == 1:
            return None  # single layer: only one layout exists
        alt_model = dataclasses.replace(cur, pipeline_stages=stages)
        convert = ftt.canonicalize_params
    try:
        # abstract restore target in the alternate layout: eval_shape costs
        # no compute/memory and skips batch-geometry validation (irrelevant
        # to the stored tree — only shapes matter to orbax)
        model = build_model(alt_model, job.schema)
        tx = build_optimizer(job.train.optimizer)

        def make_template():
            dummy = jnp.zeros((1, job.schema.feature_count), jnp.float32)
            variables = model.init(jax.random.PRNGKey(job.train.seed), dummy)
            return TrainState.create(apply_fn=model.apply,
                                     params=variables["params"], tx=tx)

        alt_abstract = jax.eval_shape(make_template)
        restored = ckpt_lib.restore_latest(manager, alt_abstract,
                                           with_extra=True)
    except Exception:
        return None  # not the other layout either: caller re-raises
    if restored is None:
        return None
    r_state, extra, step = restored

    def to_host(tree):
        # restored leaves may be cross-process sharded on multi-host runs;
        # device_get alone would raise "not fully addressable"
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            return multihost_utils.process_allgather(tree)
        return jax.device_get(tree)

    params = convert(dict(to_host(r_state.params)), cur)
    placed = jax.tree_util.tree_map(
        lambda host, curp: jax.device_put(np.asarray(host), curp.sharding),
        params, state.params)
    step_val = jax.device_put(to_host(r_state.step), state.step.sharding)
    direction = ("stacked -> per-block" if cur.pipeline_stages == 1
                 else "per-block -> stacked")
    console(f"Resuming across a trunk-layout change ({direction}): weights "
            "converted exactly, optimizer slots reinitialized")
    return (state.replace(params=placed, step=step_val), extra, step)


def _baseline_feature_sketch(job: JobConfig, ds, cap: int = 1 << 18):
    """FeatureSketch of the training partition on the int8 wire grid —
    the feature half of the frozen baseline profile.  Stride-sampled to
    at most `cap` rows (the grid is static, so a uniform stride is an
    unbiased histogram sample).  Best-effort: None when features are not
    materialized (exotic tiers) — the artifact just ships no profile."""
    try:
        feats = getattr(ds, "features", None)
        if feats is None or feats.shape[0] == 0:
            return None
        scale, offset = pipe.wire_params(job.schema, job.data)
        sk = obs.sketch.FeatureSketch(feats.shape[1], scale=scale,
                                      offset=offset)
        step = max(1, -(-int(feats.shape[0]) // int(cap)))
        sk.update(np.asarray(feats[::step][:cap]))
        return sk
    except Exception:
        return None


def _baseline_feature_names(schema, num_features: int):
    """Selected-column names for the profile (None when the schema
    doesn't carry per-column metadata, e.g. synthetic datasets)."""
    by_index = {c.index: c.name for c in schema.columns}
    names = [by_index.get(i, f"f{i}") for i in schema.selected_indices]
    return names if len(names) == num_features else None


def _tree_sum(acc, new):
    """`acc + new` over what an epoch's steps hand back (a loss sum, or a
    loss sum with counters beside it); `acc` None is the first."""
    if acc is None:
        return new
    return jax.tree_util.tree_map(jnp.add, acc, new)


def _moe_layers(counters: dict) -> list[dict]:
    """The `moe` journal event's layers from the model's summed counters
    (models/block_stack._dispatch_counters), a leading axis an expert
    layer; `tokens` and `tokens_unrouted` where the layers count them (no
    shared expert).  `block_rows`, the rows a block of the layer's dispatch
    holds, is the same every step and is carried, not summed: the summed
    `live_rows` over the summed `live_blocks`."""
    per = np.asarray(counters["tokens_per_expert"])
    layers = []
    for i in range(per.shape[0]):
        n = {k: int(np.asarray(counters[k])[i]) for k in
             ("routed_slots", "held_slots", "tokens_dropped", "live_blocks",
              "live_rows", "tokens", "tokens_unrouted") if k in counters}
        n["block_rows"] = n.pop("live_rows") // max(n["live_blocks"], 1)
        layers.append({"tokens_per_expert": [int(v) for v in per[i]], **n})
    return layers


def _accumulate_streaming(triples, score_sink=None,
                          sketch=None) -> tuple[float, float]:
    """THE eval accumulation: one StreamingMetrics over (scores, labels,
    weights) chunks, shared by the single-host and multihost branches of
    `evaluate` — the two used to carry their own copies, so eval
    instrumentation (and any accumulator fix) had to land twice.  Binned
    AUC matches the exact statistic to < 1e-6 at the default 2^20 bins.

    `sketch` (an obs.sketch.ScoreSketch, the baseline profile's) takes the
    scores of the rows with weight > 0 in the same pass over a chunk
    (zero-weight padding would skew the frozen score distribution);
    `score_sink` is called with them, a copy a chunk."""
    sm = metrics_lib.StreamingMetrics()
    # nonzero-weight rows: the one definition that reads the same on every
    # topology (the multihost branch's gathered global batches keep their
    # zero-weight padding; the single-host branch pre-trims real rows —
    # counting raw lengths would make the counter topology-dependent)
    rows = obs.counter("eval_rows_total", "rows evaluated (nonzero weight)")
    native = obs.counter(
        "eval_rows_native_total",
        "of eval_rows_total, the rows the native one-pass accumulation "
        "reduced")
    # phase `accumulate` opens per chunk, never across the generator's
    # resumption: `triples` opens its own phases (prep, dispatch, fetch)
    for chunk in triples:
        with obs.span("accumulate", journal=False):
            s, t, w = chunk
            seen, seen_native = sm.nonzero_rows, sm.native_rows
            counted = sm.update(s, t, w, sketch)  # the rows with weight > 0
            rows.inc(sm.nonzero_rows - seen)
            native.inc(sm.native_rows - seen_native)
            if score_sink is not None:
                score_sink(np.asarray(s)[counted])
            del chunk, s, t, w  # the chunk's buffers go inside the phase
    with obs.span("accumulate", journal=False):  # the final reduction
        return sm.weighted_error(), sm.auc()


def _eval_batch_size(job: JobConfig, ds: pipe.TabularDataset,
                     mesh: Optional[Mesh], multihost: bool,
                     batch_size: Optional[int] = None) -> int:
    """Rows of one eval batch (static shapes), on either eval tier."""
    # an eval batch holds at least 4,096 rows, which amortizes a tabular
    # batch's dispatch; a row that is itself that wide is a sequence of
    # thousands of positions, a batch's worth of work alone, and there the
    # floor is the train batch, whose activations are known to fit
    floor = 4096 if ds.num_features < 4096 else job.data.batch_size
    bs = batch_size or max(job.data.batch_size, floor)
    if not multihost and ds.num_rows < bs:
        # a huge train batch must not size the eval batch: padding a small
        # valid set up to a 100k-row batch wastes H2D bytes and device work
        # on zero-weight rows every epoch.  Cap at the dataset rounded up
        # to a quantum of the floor (static shapes; single-host only —
        # multihost derives collective step counts from the shared bs, and
        # a host-local row count there would diverge the program)
        bs = max(-(-ds.num_rows // floor) * floor, floor)
    if mesh is not None:
        # keep the per-device shard static
        bs = -(-bs // mesh.size) * mesh.size
    if job.model.pipeline_stages > 1:
        # the pipelined trunk splits every batch into microbatches
        n_micro = job.model.pipeline_microbatches or job.model.pipeline_stages
        quantum = n_micro * (mesh.size if mesh is not None else 1)
        bs = -(-bs // quantum) * quantum
    return bs


#: the share of an epoch's wall that the eval's accumulation (the host's own
#: work, with nothing for the device in it) has to take before the next
#: epoch's scan is dispatched ahead of it.  Under it there is nothing to
#: hide that a run could show (every cell's rate spreads by more), and the
#: sequential order keeps the device idle at every boundary, which is what
#: a trace taken from `epoch_callback` and a prompt SIGTERM drain want.
_AHEAD_MIN_HOST_SHARE = 0.05


@dataclasses.dataclass
class _ScanAhead:
    """An epoch's resident scan dispatched between the two halves of the
    previous epoch's resident eval (`train()`'s `run_ahead`), until that
    epoch's iteration takes it over: its loss sum still on the device, its
    batches, and the StepTimer that timed the dispatch as the epoch's one
    chunk."""
    loss_acc: Any
    loss_n: int
    timer: Any
    t_dispatched: float


@dataclasses.dataclass
class ResidentEval:
    """The resident eval tier: the valid set's features where the train rows
    are, placed once by `train()` as `(nvb, eval batch, F)` blocks in the
    wire format (the tail padded with zero rows), and the one program that
    scores them (`make_resident_eval_step`).  Targets and weights stay on
    the host: `evaluate` reads them as views of the dataset's columns."""
    features: jax.Array
    step: Callable


def place_resident_eval(ds: Optional[pipe.TabularDataset], job: JobConfig,
                        mesh: Optional[Mesh],
                        budget_bytes: int) -> Optional[ResidentEval]:
    """The resident eval tier for `ds`, or None where its blocks do not
    fit `budget_bytes` (what `data.device_resident_bytes` leaves beside the
    train blocks): `evaluate` then streams the batches from the host."""
    if ds is None or ds.num_rows == 0:
        return None
    bs = _eval_batch_size(job, ds, mesh, multihost=False)
    nvb = pipe.num_batches(ds, bs, drop_remainder=False)
    # the wire cast evaluate()'s streamed batches get, features only
    wcast = pipe.wire_cast_fn(job.schema, job.data, job.model.compute_dtype)

    def to_wire(f: np.ndarray) -> np.ndarray:
        return f if wcast is None else wcast({"features": f})["features"]

    wire_dtype = to_wire(ds.features[:0]).dtype
    if nvb * bs * ds.num_features * wire_dtype.itemsize > budget_bytes:
        return None
    blocks = np.empty((nvb * bs, ds.num_features), wire_dtype)
    blocks[:ds.num_rows] = to_wire(ds.features)
    blocks[ds.num_rows:] = 0    # the tail's padding: rows no metric reads
    blocks = blocks.reshape(nvb, bs, ds.num_features)
    placed = (shard_lib.shard_blocks({"features": blocks}, mesh)["features"]
              if mesh is not None else jax.device_put(blocks))
    return ResidentEval(placed, make_resident_eval_step(job))


def _dispatch_resident_eval(state: TrainState, ds: pipe.TabularDataset,
                            resident: ResidentEval) -> tuple[list, list]:
    """The dispatching half of a resident eval pass: views of the host's
    label and weight columns, a block each, and the pass's one dispatch
    with the D2H of every slice of scores asked for.  Nothing here waits
    for the device, so what the caller dispatches next (`train()`: the
    next epoch's scan) queues behind the pass and runs while
    `_fetch_resident_eval` brings the scores in."""
    bs = resident.features.shape[1]
    with obs.span("prep", journal=False):
        # no gather, no copy, no padding: the padded tail's scores are
        # dropped by row count in the fetching half
        tgt, wgt = ds.target[:, 0], ds.weight[:, 0]
        views = [(tgt[lo:lo + bs], wgt[lo:lo + bs])
                 for lo in range(0, ds.num_rows, bs)]
    with obs.span("dispatch", journal=False):
        slices = list(resident.step(state, resident.features))
        # every slice's transfer is asked for at once, so that the later
        # ones land while the earlier are accumulated
        for s in slices:
            s.copy_to_host_async()
    obs.counter("eval_resident_passes_total",
                "eval passes scored from the resident eval tier").inc()
    return views, slices


def _fetch_resident_eval(views: list, slices: list):
    """The finishing half: (scores, labels, weights) chunks of a dispatched
    resident eval pass, one a block, each slice accumulated while the next
    is in flight."""
    chunks = iter(views)
    for i in range(len(slices)):
        with obs.span("fetch", journal=False):
            # the wait for the device and the D2H; a slice's device buffer
            # is released inside the phase
            host = np.asarray(slices[i])
            slices[i] = None
        for row, (t, w) in zip(host, chunks):
            yield row[:t.shape[0]], t, w


def evaluate(state: TrainState, ds: pipe.TabularDataset, job: JobConfig,
             eval_step, mesh: Optional[Mesh] = None,
             batch_size: Optional[int] = None,
             score_sink=None,
             resident: Optional[ResidentEval] = None,
             sketch=None) -> tuple[float, float]:
    """(weighted_error, auc) over the full dataset — every row counted, the
    tail padded with zero-weight rows (reference evaluates the full valid set
    per epoch, ssgd_monitor.py:281-284).

    Multi-host: `ds` is this host's shard; every process contributes its
    rows to global eval batches, runs the same number of collective steps
    (shorter hosts feed zero-weight padding), and the gathered scores give
    identical global metrics on every host.

    Four hot spans split the pass on every topology — `prep` (slice, pad,
    wire cast), `dispatch` (batch placement and the `eval_step` call: the
    host's re-tiling, the H2D enqueue and the dispatch), `fetch` (the wait
    for the oldest in-flight scores and their D2H) and `accumulate` —
    nested under the caller's span (`epoch/eval/...` from `train`), each
    opened and closed within one resumption of `triples`.

    With `resident` (single process; `ds` is the set it was placed from)
    the batches come from the device and not from the host: the same
    forward, the same chunks into the same accumulation, and the same four
    spans, which then time the views of the host's label and weight
    columns, the pass's one dispatch, the wait for the device with the
    D2H of the scores, and the accumulation.

    `sketch` and `score_sink` take the scores of the rows with weight > 0
    (`_accumulate_streaming`)."""
    multihost = jax.process_count() > 1 and mesh is not None
    if not multihost and ds.num_rows == 0:
        return float("nan"), float("nan")
    if resident is not None and not multihost:
        return _accumulate_streaming(
            _fetch_resident_eval(*_dispatch_resident_eval(state, ds, resident)),
            score_sink, sketch)
    bs = _eval_batch_size(job, ds, mesh, multihost, batch_size)
    # same wire cast as training (model casts inputs to compute_dtype first,
    # so scores are bit-identical; H2D bytes halve)
    wcast = pipe.wire_cast_fn(job.schema, job.data, job.model.compute_dtype)
    if not multihost:
        # streaming accumulation (O(bins), not O(valid set)) through the
        # shared _accumulate_streaming helper.  ASYNC dispatch: score
        # fetches run one bounded window behind the dispatches, so the
        # device pipelines the whole eval instead of draining after every
        # batch (the old per-batch jax.device_get serialized dispatch →
        # sync → host accumulate → dispatch, and that blocking tail is
        # exactly the dead epoch-boundary time the overlap engine hides —
        # the `gather3` collective path already fetched this way).  The
        # window bounds in-flight device memory to `window` input batches
        # + score vectors; host accumulation stays O(bins).
        window = 8

        def triples():
            from collections import deque

            pend: "deque" = deque()

            def fetch():
                with obs.span("fetch", journal=False):
                    s, n, tgt, wgt = pend.popleft()
                    out = (np.asarray(jax.device_get(s))[:n, 0], tgt, wgt)
                    del s  # the device buffer is released inside the phase
                return out

            batches = pipe.batch_iterator(ds, bs, shuffle=False,
                                          drop_remainder=False)
            while True:
                with obs.span("prep", journal=False):
                    batch = next(batches, None)
                    if batch is not None:
                        padded, mask = pipe.pad_to_batch(batch, bs)
                        if wcast is not None:
                            padded = wcast(padded)
                if batch is None:
                    break
                with obs.span("dispatch", journal=False):
                    if mesh is not None:
                        padded = shard_lib.shard_batch(padded, mesh)
                    pend.append((eval_step(state, padded), int(mask.sum()),
                                 batch["target"][:, 0],
                                 batch["weight"][:, 0]))
                if len(pend) >= window:
                    yield fetch()
            while pend:
                yield fetch()

        return _accumulate_streaming(triples(), score_sink, sketch)

    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec

    nproc = jax.process_count()
    local_bs = max(bs // nproc, 1)
    n_steps = int(np.max(multihost_utils.process_allgather(
        np.asarray(-(-ds.num_rows // local_bs) if ds.num_rows else 0))))
    if n_steps == 0:
        return float("nan"), float("nan")
    replicated = NamedSharding(mesh, PartitionSpec())
    # one collective fetch per eval step: scores + labels + weights ride the
    # same all-gather so the row pairing is identical on every host.
    # Accumulation is STREAMING (O(bins), not O(valid set)): at the 1B-row
    # scale a per-host concat of every epoch's gathered scores would cost
    # O(valid-set) host memory per epoch (round-1 VERDICT weak #7).
    gather3 = jax.jit(lambda a, b, c: (a, b, c),
                      out_shardings=(replicated, replicated, replicated))

    def triples():
        for i in range(n_steps):
            with obs.span("prep", journal=False):
                lo = min(i * local_bs, ds.num_rows)
                hi = min(lo + local_bs, ds.num_rows)
                local = {"features": ds.features[lo:hi],
                         "target": ds.target[lo:hi],
                         "weight": ds.weight[lo:hi]}
                # zero-weight tail
                local, _ = pipe.pad_to_batch(local, local_bs)
                if wcast is not None:
                    local = wcast(local)
            with obs.span("dispatch", journal=False):
                gbatch = shard_lib.shard_batch_process_local(local, mesh)
                scores = eval_step(state, gbatch)
            with obs.span("fetch", journal=False):
                s, t, w = gather3(scores, gbatch["target"], gbatch["weight"])
                out = (np.asarray(s.addressable_data(0))[:, 0],
                       np.asarray(t.addressable_data(0))[:, 0],
                       np.asarray(w.addressable_data(0))[:, 0])
            yield out

    return _accumulate_streaming(triples(), score_sink, sketch)


def train(job: JobConfig,
          train_ds: Optional[pipe.TabularDataset] = None,
          valid_ds: Optional[pipe.TabularDataset] = None,
          mesh: Optional[Mesh] = None,
          console: Optional[Console] = None,
          epoch_callback: Optional[Callable[[EpochMetrics], None]] = None) -> TrainResult:
    """Run the full training job; returns final state + per-epoch history.

    Datasets may be passed directly (tests, bench) or loaded from
    job.data.paths with per-host file sharding.
    """
    # startup, opened (docs/OBSERVABILITY.md "Startup"): from here to the
    # first epoch's ledger, hot spans under `startup/...` and compile notes
    # go to a ledger of their own, and the first trained epoch's boundary
    # journals them, with every compile so far, as one `startup` event
    t_entry = time.perf_counter()
    startup_led = obs.goodput.begin_startup()
    compile_mark = obs.introspect.compile_mark()
    job = job.validate()
    console = console or (lambda s: print(s, flush=True))

    # features-on-the-wire cast (bf16 when the model computes bf16 anyway;
    # int8 quantization when configured): halves/quarters H2D bytes and the
    # resident tier's HBM footprint.  The loaders store features directly
    # in the wire dtype (bf16 cast or int8 quantize at parse time), so the
    # per-block cast below only fires for in-memory datasets callers pass
    # in as f32
    multihost = jax.process_count() > 1 and mesh is not None
    if jax.process_index() == 0:
        # lazy env hook: a bare SHIFU_TPU_METRICS_DIR is enough for library
        # callers (the CLI configures sinks explicitly before calling in);
        # non-chief ranks keep their registry in memory and journal nothing
        obs.configure_from_env()
    devices = jax.devices()
    obs.event("train_start", model=job.model.model_type,
              epochs=job.train.epochs, batch_size=job.data.batch_size,
              processes=jax.process_count(),
              devices=len(devices) if mesh is None else mesh.size,
              platform=devices[0].platform,
              device_kind=devices[0].device_kind,
              device_count=len(devices))
    wmode = pipe.wire_mode(job.schema, job.data, job.model.compute_dtype)
    # streamed-path cast: per-BLOCK compact target/weight detection
    # (content-driven, so a resume replays identical formats) on a single
    # host; a multihost streamed epoch keeps the uncompacted wire — block
    # formats are part of the collective program signature, and per-block
    # detection could diverge across hosts mid-epoch (the dataset-wide
    # agreement happens in _prepare_tiers, once shards are fully loaded)
    wcast_stream = pipe.wire_cast_fn(job.schema, job.data,
                                     job.model.compute_dtype,
                                     compact=not multihost)
    # tier cast: reassigned by _prepare_tiers with dataset-wide (multihost:
    # allgather-agreed) compact flags
    wcast = pipe.wire_cast_fn(job.schema, job.data, job.model.compute_dtype)
    if wmode == "bfloat16":
        feature_dtype = "bfloat16"
    elif wmode == "int8":
        # loaders quantize at parse time; the clip rides in the cache key so
        # a changed grid never reuses stale quantized cache entries
        feature_dtype = f"int8c{job.data.wire_int8_clip:g}"
    else:
        feature_dtype = "float32"

    # streamed first epoch: defer the (blocking) load and start training on
    # parsed blocks while the rest of the files parse in the background.
    # Multihost streams too: every host parses its own file shard and the
    # gang agrees per round — one small allgather — whether every host has
    # a full chunk ready (chunks are collective dispatches, so counts must
    # match everywhere; the first host to run dry ends the streamed epoch
    # for all, leftover rows training via the retained dataset's epochs).
    # A fully hot projected cache skips the streamed epoch instead: ingest
    # then runs at npz-load speed, so there is no parse latency left to
    # hide and the loaded tiers (device-resident / staged) are strictly
    # faster than training in file order behind a pointless pipeline.
    stream_loader = None
    pending_ingest_s = 0.0  # blocking pre-loop ingest, charged to epoch 1
    if train_ds is None:
        host, nhosts = mesh_lib.host_shard_info(mesh) if mesh else (0, 1)
        rate = job.train.bagging_sample_rate
        want_stream = (job.data.stream_first_epoch
                       and not job.data.out_of_core
                       and (jax.process_count() == 1 or mesh is not None)
                       and job.data.staged and job.data.drop_remainder
                       and not (0.0 < rate < 1.0))
        if want_stream:
            cache_hot = pipe.projected_cache_complete(
                job.schema, job.data, host, nhosts, feature_dtype)
            if multihost:
                # the stream-vs-load split is collective: every host must
                # agree (a host streaming against a host loading would
                # deadlock the per-round allgather)
                from jax.experimental import multihost_utils
                cache_hot = bool(np.min(multihost_utils.process_allgather(
                    np.asarray(cache_hot))))
            if cache_hot:
                console("Projected cache is hot for every input file: "
                        "skipping the streamed first epoch")
                want_stream = False
        if want_stream:
            stream_loader = pipe.StreamingLoader(job.schema, job.data,
                                                 feature_dtype,
                                                 host_index=host,
                                                 num_hosts=nhosts)
        else:
            # blocking ingest (hot cache / loaded tiers / out-of-core):
            # credited to the FIRST epoch's goodput input bucket below —
            # the cold-start tax must show up in the ledger, not vanish
            # into unaccounted pre-epoch wall (docs/DATA.md "Columnar cache")
            with obs.span("startup/ingest", journal=False) as ingest:
                train_ds, valid_ds = pipe.load_datasets(
                    job.schema, job.data, host, nhosts,
                    feature_dtype=feature_dtype)
            pending_ingest_s = ingest.seconds
    assert valid_ds is not None or stream_loader is not None

    # Shifu train.baggingSampleRate: deterministic per-run subsample of the
    # TRAIN partition (valid stays complete).  Positions are stable for a
    # given dataset order, so resume sees the same subsample.  The reference
    # carried the field but never honored it.  (Streamed loading is gated
    # off when bagging is active, so train_ds is always concrete here.)
    rate = job.train.bagging_sample_rate
    if train_ds is not None and 0.0 < rate < 1.0 and train_ds.num_rows > 0:
        from ..data.split import bagging_mask
        keep = np.nonzero(bagging_mask(
            np.arange(train_ds.num_rows, dtype=np.uint64),
            rate, seed=job.train.seed))[0]
        console(f"Bagging: {len(keep)}/{train_ds.num_rows} train rows "
                f"(baggingSampleRate={rate:g})")
        train_ds = train_ds.take(keep)

    num_features = (train_ds.num_features if train_ds is not None else 0) \
        or job.schema.feature_count
    with obs.span("startup/init_state", journal=False):
        # with the wait: the parameters' initialisation is a device program
        state = jax.block_until_ready(init_state(job, num_features, mesh))

    # auto-resume (successor of MonitoredTrainingSession restore-on-start)
    start_epoch = 0
    manager = None
    if job.runtime.checkpoint.directory:
        manager = ckpt_lib.make_manager(job.runtime.checkpoint.directory,
                                        job.runtime.checkpoint.max_to_keep)
        if job.runtime.checkpoint.resume:
            with obs.span("startup/restore", journal=False):
                restored = restore_latest_any_layout(manager, state, job,
                                                     console)
            if restored is not None:
                r_state, extra, step = restored
                fresh_opt = state.opt_state  # before the restore discards it
                state = state.replace(params=r_state.params,
                                      opt_state=r_state.opt_state,
                                      step=r_state.step)
                start_epoch = int((extra or {}).get("epoch", 0))
                console(f"Resumed from checkpoint step {step} (epoch {start_epoch})")
                obs.event("train_resume", step=int(step), epoch=start_epoch)
                if ((extra or {}).get("best_restored")
                        and start_epoch < job.train.epochs):
                    # the terminal checkpoint's params were rolled back to
                    # the best-measured epoch, but its optimizer moments
                    # belong to the LAST trajectory — continuing training
                    # (epochs budget raised) with that pairing would apply
                    # mismatched updates; restart the optimizer fresh
                    state = state.replace(opt_state=fresh_opt)
                    console("Resuming past a best-params terminal "
                            "checkpoint: optimizer state reinitialized")

    # streaming serves only the FIRST epoch of a FRESH run: a resumed epoch
    # must replay the same globally shuffled, drop-remainder epoch an
    # uninterrupted run would execute (the streamed pass trains in file
    # order with a padded tail — fine for epoch 0, a determinism break for
    # a resume); a complete checkpoint leaves nothing to stream at all
    if stream_loader is not None and start_epoch > 0:
        train_ds, valid_ds = stream_loader.datasets()
        stream_loader = None

    local_sgd = job.train.local_sgd_window > 0
    # one scan-step object shared by the streamed first epoch and the staged
    # tier: equal block shapes then compile exactly once
    if local_sgd:
        from .step import make_local_sgd_epoch_step
        epoch_scan_step = make_local_sgd_epoch_step(job, mesh)
        k_win = job.train.local_sgd_window
        staged_block_batches = -(-job.data.block_batches // k_win) * k_win
    else:
        # donate_blocks: every streamed/staged chunk is consumed exactly
        # once, so its device buffers are donated through the scan — the
        # runtime reclaims each chunk's HBM at dispatch instead of at
        # Python GC, and steady-state H2D cycles a fixed buffer set
        epoch_scan_step = make_epoch_scan_step(job, mesh,
                                               donate_blocks=True)
        staged_block_batches = job.data.block_batches
    # cap chunks near ~32 MB of WIRE bytes so H2D stays sub-second per
    # chunk and overlaps compute.  Byte-based, not row-based: the compact
    # int8 wire carries ~4x the rows of f32 per byte, and a row-count cap
    # would shrink its chunks until fixed per-chunk costs (dispatch
    # latency, host gather, queue handoff) dominate the transfer window —
    # exactly the r4 staged_int8 roofline-fraction gap (VERDICT weak #2).
    # Keep the local-SGD window multiple so no sync window truncates
    # mid-chunk.
    row_wire_b = pipe.wire_row_bytes(job.schema, job.data,
                                     job.model.compute_dtype)
    chunk_cap = max(1, (32 << 20) // max(job.data.batch_size * row_wire_b, 1))
    if local_sgd:
        chunk_cap = max(k_win, (chunk_cap // k_win) * k_win)
    staged_block_batches = max(1, min(staged_block_batches, chunk_cap))

    # tier plumbing is resolved by _prepare_tiers() once train_ds exists —
    # immediately on the loaded path, after the streamed first epoch on the
    # streaming path
    nproc = jax.process_count() if multihost else 1
    min_host_rows = 0
    bs = local_bs = job.data.batch_size
    steps_per_epoch = None
    use_resident = use_staged = False
    resident_blocks = None
    resident_eval: Optional[ResidentEval] = None
    device_epoch_step = None
    train_step = None
    staged_put_fn = None
    staged_source = None

    # cross-epoch overlap engine (data/pipeline.EpochFeeder): ONE persistent
    # feeder replaces the per-epoch prefetch producer for the staged and
    # per-batch tiers — epoch N+1's shuffle + assembly + first H2D staging
    # run while epoch N computes and while its eval dispatch tail drains.
    # Created lazily at the first epoch whose tier it serves (tiers resolve
    # only once train_ds exists); batch order stays a pure function of
    # (seed, epoch), byte-identical to the non-overlapped path.
    use_overlap = job.data.overlap_epochs
    feeder: Optional[pipe.EpochFeeder] = None
    # host staging depth: prefetch_depth (0 = auto adapts the DEVICE gate
    # per epoch from the ledger's exposed-input fraction, starting shallow)
    feeder_host_depth = job.data.prefetch_depth or 4
    feeder_dev_depth = (job.data.prefetch if job.data.prefetch_depth
                        else 2)

    def _staged_host_blocks(ep: int):
        """Assembly-thread source for one staged epoch (same order
        derivation as the per-epoch path — staged_source may copy a
        deterministic per-epoch subset on imbalanced multihost shards)."""
        return pipe.staged_epoch_blocks(
            staged_source(ep), local_bs, shuffle=job.data.shuffle,
            seed=job.data.shuffle_seed, epoch=ep,
            block_batches=staged_block_batches)

    def _perbatch_host_batches(ep: int):
        import itertools
        hb = pipe.batch_iterator(
            train_ds, local_bs, shuffle=job.data.shuffle,
            seed=job.data.shuffle_seed, epoch=ep,
            drop_remainder=job.data.drop_remainder or multihost)
        if multihost:
            hb = itertools.islice(hb, steps_per_epoch)
        return hb

    # sparse embedding engine: when a sparse plan engages and embed.dedup
    # allows, the per-batch feeder compacts each batch's ids host-side
    # (embed/dedup) and ships (embed_unique, embed_inverse) alongside the
    # features — the step's rows-touched update then touches each row once,
    # which also licenses the fused Pallas update kernel.  The scan tiers
    # (staged/resident blocks) skip dedup; their batches fall back to
    # raw-id extraction inside the sparse apply (docs/EMBEDDING.md).
    _embed_dedup = None
    if getattr(job, "embed", None) is not None and job.embed.dedup != "off":
        from ..train import sparse_embed as _sparse_plan_lib
        _dplan = _sparse_plan_lib.resolve_plan(job)
        if _dplan is not None:
            from ..embed.dedup import attach_dedup
            _embed_dedup = attach_dedup(_dplan.layout, _dplan.max_vocab)

    def _feed_put_fn(shard_local, shard_global, cast):
        """Device placement for host arrays — blocks or batches, mesh or
        not, multihost or not — with the wire cast composed in (runs inside
        the prefetch producer thread).  ONE definition so the block and
        batch tiers can never diverge on placement/cast rules.  `cast` is
        passed explicitly: the streamed epoch uses the per-block-detecting
        cast, the loaded tiers the dataset-wide agreed one."""
        if multihost:
            put = lambda b: shard_global(b, mesh)
        elif mesh is not None:
            put = lambda b: shard_local(b, mesh)
        else:
            put = lambda b: {k: jax.device_put(v) for k, v in b.items()}
        if cast is None:
            return put
        return lambda b: put(cast(b))

    def _block_put_fn(cast):
        return _feed_put_fn(shard_lib.shard_blocks,
                            shard_lib.shard_blocks_process_local, cast)

    def _prepare_tiers():
        # multi-host: every process holds a disjoint file shard, so batches
        # are assembled process-locally into global arrays and the step
        # count is agreed across hosts (collective input path; single-host
        # tiers assume the whole dataset is visible locally).  ALL sizing
        # decisions below derive from globally agreed numbers — a host
        # deciding from its local row count alone would diverge on shapes
        # and deadlock the collectives.
        nonlocal min_host_rows, bs, local_bs, steps_per_epoch, use_resident, \
            use_staged, resident_blocks, resident_eval, device_epoch_step, \
            train_step, staged_put_fn, staged_source, wcast
        # dataset-wide compact-wire flags: u8 label / elided weight apply to
        # the loaded tiers only when EVERY row qualifies — and in multihost,
        # only when every HOST's shard qualifies (block formats are part of
        # the collective program signature; the flags ride the same
        # allgather round as min_host_rows).  One full pass over the
        # target/weight columns, at memory bandwidth, once per job.
        with obs.span("flags", journal=False):
            label_ok = (job.data.wire_label_dtype in ("auto", "uint8")
                        and pipe.target_u8_exact(train_ds.target))
            weight_ok = (job.data.wire_weight_mode in ("auto", "elide")
                         and pipe.weight_all_ones(train_ds.weight))
        if multihost:
            from jax.experimental import multihost_utils
            agreed = np.min(multihost_utils.process_allgather(np.asarray(
                [train_ds.num_rows, int(label_ok), int(weight_ok)])), axis=0)
            min_host_rows = int(agreed[0])
            label_ok, weight_ok = bool(agreed[1]), bool(agreed[2])
        else:
            min_host_rows = train_ds.num_rows
        if job.data.wire_label_dtype == "uint8" and not label_ok:
            raise ValueError(
                "wire_label_dtype=uint8 but targets are not integers in "
                "[0, 255] on every host — use wire_label_dtype=auto or "
                "float32")
        if job.data.wire_weight_mode == "elide" and not weight_ok:
            raise ValueError(
                "wire_weight_mode=elide but weights are not all 1.0 on "
                "every host — use wire_weight_mode=auto or float32")
        wcast = pipe.wire_cast_fn(job.schema, job.data,
                                  job.model.compute_dtype,
                                  compact=(label_ok, weight_ok))
        if min_host_rows == 0:
            raise ValueError("a training data shard has 0 rows — nothing to "
                             "train on" if multihost else
                             "training dataset has 0 rows — nothing to train on")

        bs = job.data.batch_size
        mesh_size = mesh.size if mesh is not None else 1
        global_capacity = min_host_rows * nproc  # rows every host can cover
        if bs > global_capacity and job.data.drop_remainder:
            # A dataset smaller than the batch would silently train zero
            # steps; clamp down (keeping per-device divisibility) and say
            # so.  The agreed min_host_rows keeps every host choosing the
            # same bs.
            bs = max((global_capacity // mesh_size) * mesh_size, mesh_size)
            console(f"batch_size {job.data.batch_size} > {global_capacity} "
                    f"usable rows; clamped to {bs}")
        if mesh is not None:
            bs = -(-bs // mesh.size) * mesh.size  # divisible per-device shards

        local_bs = bs
        steps_per_epoch = None
        if multihost:
            # mesh.size = nproc * local_devices, and bs is a mesh.size
            # multiple, so bs always divides evenly across processes
            local_bs = bs // nproc
            steps_per_epoch = min_host_rows // max(local_bs, 1)
            if steps_per_epoch == 0:
                raise ValueError(
                    f"a host has < {local_bs} rows (global batch {bs} / "
                    f"{nproc} processes) — lower the batch size or "
                    "rebalance file shards")

        # input-path tier selection: device-resident (dataset fits HBM
        # budget) > staged blocks > per-batch host feed.  Multi-host
        # supports all three — resident/staged stack each host's shard into
        # (nb, local_B, ...) blocks that assemble into global arrays, with
        # nb agreed across hosts — so distributed epochs are collective
        # scans, not per-batch dispatches, even when the dataset exceeds HBM.
        rows_for_blocks = min_host_rows if multihost else train_ds.num_rows
        # agreed across hosts: per-row bytes are schema-determined
        # (identical everywhere), and the tier only stages the usable
        # rows_for_blocks prefix — a host deciding from its raw local shard
        # size could pick a different tier and deadlock the collectives
        feat_row_bytes = train_ds.features.nbytes // max(train_ds.num_rows, 1)
        # the resident tier's budget check sizes against its IN-HBM format
        # (resident_format=int8 quarters it even under a wider wire); for
        # "auto"/"wire" this is exactly the wire mode as before
        rfmt = pipe.resident_feature_format(job.schema, job.data,
                                            job.model.compute_dtype)
        if train_ds.features.dtype == np.float32:
            if rfmt == "int8":
                feat_row_bytes //= 4  # int8 on device
            elif rfmt == "bfloat16":
                feat_row_bytes //= 2  # bf16 on device (loader may pre-cast)
        tgt_row_bytes = train_ds.target.nbytes // max(train_ds.num_rows, 1)
        if label_ok:
            tgt_row_bytes //= 4  # u8 target on device
        wgt_row_bytes = (0 if weight_ok  # weight column elided entirely
                         else train_ds.weight.nbytes
                         // max(train_ds.num_rows, 1))
        per_row_bytes = feat_row_bytes + tgt_row_bytes + wgt_row_bytes
        ds_bytes = per_row_bytes * rows_for_blocks
        use_resident = (job.data.staged and job.data.drop_remainder
                        and 0 < ds_bytes <= job.data.device_resident_bytes
                        and rows_for_blocks // local_bs > 0)
        use_staged = (job.data.staged and job.data.drop_remainder
                      and not use_resident)
        resident_blocks = resident_eval = None
        if local_sgd and not (use_resident or use_staged):
            raise ValueError(
                "local_sgd_window (SAGN mode) needs the staged or "
                "device-resident input tier: set data.staged=True and "
                "data.drop_remainder=True (local replicas are synchronized "
                "by epoch scans, not per-batch dispatches)")
        if use_resident:
            if jax.process_count() == 1:
                # the resident eval tier: the same budget covers the valid
                # rows, placed once beside the train blocks where both
                # fit; where they do not, every epoch's evaluate() streams
                # them
                with obs.span("eval_tier", journal=False):
                    resident_eval = place_resident_eval(
                        valid_ds, job, mesh,
                        job.data.device_resident_bytes - ds_bytes)
            from .step import make_device_epoch_step, make_local_sgd_epoch_step
            device_epoch_step = (
                make_local_sgd_epoch_step(job, mesh, with_order=True)
                if local_sgd else make_device_epoch_step(job, mesh))
            nb_total = rows_for_blocks // local_bs

            def stack(arr):
                return arr[:nb_total * local_bs].reshape(
                    nb_total, local_bs, *arr.shape[1:])
            with obs.span("blocks", journal=False):
                host_blocks = {"features": stack(train_ds.features),
                               "target": stack(train_ds.target),
                               "weight": stack(train_ds.weight)}
                raw_features = host_blocks["features"]
                if wcast is not None:
                    host_blocks = wcast(host_blocks)
                if (rfmt == "int8"
                        and host_blocks["features"].dtype != np.int8):
                    # forced int8 residency under a wider wire: quantize
                    # the stacked blocks once to the same static grid the
                    # int8 wire uses — from the RAW features, not the
                    # wire-cast ones (a bf16 wire cast first would shift
                    # values across int8 buckets and break parity with the
                    # int8-wire run)
                    scale, offset = pipe.wire_params(job.schema, job.data)
                    host_blocks = dict(host_blocks)
                    host_blocks["features"] = pipe.wire_quantize(
                        raw_features, scale, offset)
            # no barrier: where a put returns before the bytes have landed,
            # the first epoch's `epoch/train/device_wait` holds the rest
            with obs.span("h2d", journal=False):
                if multihost:
                    resident_blocks = shard_lib.shard_blocks_process_local(
                        host_blocks, mesh)
                elif mesh is not None:
                    resident_blocks = shard_lib.shard_blocks(host_blocks,
                                                             mesh)
                else:
                    resident_blocks = {k: jax.device_put(v)
                                       for k, v in host_blocks.items()}
        if use_staged:
            # loop-invariant staged-tier plumbing (the per-epoch subset
            # below still varies when shards are imbalanced)
            staged_put_fn = _block_put_fn(wcast)

            def staged_source(epoch: int) -> pipe.TabularDataset:
                """This host's rows for one staged epoch.  Multihost hosts
                must contribute exactly min_host_rows each (agreed block
                counts); a host with MORE rows draws a fresh epoch-seeded
                subset so its tail rows are still sampled across epochs
                (the per-batch path reshuffles the whole shard per epoch —
                a fixed prefix would silently never train the excess)."""
                if not multihost or train_ds.num_rows <= min_host_rows:
                    return train_ds
                if job.data.shuffle:
                    rng = np.random.default_rng(
                        np.random.PCG64(job.data.shuffle_seed * 9176 + epoch))
                    keep = np.sort(rng.permutation(
                        train_ds.num_rows)[:min_host_rows])
                else:
                    keep = np.arange(min_host_rows)
                return train_ds.take(keep)
        elif not use_resident:
            # donate_batch: the loop consumes each prefetched batch once
            train_step = make_train_step(job, mesh, donate_batch=True)

    if train_ds is not None:
        with obs.span("startup/tiers", journal=False):
            _prepare_tiers()
    eval_step = make_eval_step(job)

    from . import profiler as prof_lib

    timing_on = bool(os.environ.get("SHIFU_TPU_TIMING")) or job.train.log_every_steps > 0

    # device flight recorder (obs/devprof.py): scheduled jax.profiler
    # windows rolled into per-kernel `device_profile` events, an always-on
    # per-chunk anomaly ring (fed through StepTimer's chunk hook), and
    # epoch-boundary HBM watermarks.  Chief only: the profiler traces the
    # local runtime, and non-chief ranks journal nothing anyway — per-host
    # HBM still reaches the chief through the skew-table row below.
    devprof = obs.devprof.DeviceProfiler(job.obs, start_epoch=start_epoch,
                                         enabled=jax.process_index() == 0)

    # Preemption awareness: on SIGTERM (TPU preemption, scheduler kill) save
    # a checkpoint at the next safe point and exit 75 (EX_TEMPFAIL) so the
    # supervisor restarts the job elsewhere — the SPMD successor of hot
    # standbys absorbing container revocation.  Single-host main thread
    # only: a multihost gang must NOT catch SIGTERM (one host draining
    # while its peers keep issuing collectives would deadlock the step, and
    # divergent exits are worse than the default immediate terminate).
    import signal as _signal
    term_flag = {"hit": False}
    old_term = None
    if not multihost:
        try:
            old_term = _signal.signal(
                _signal.SIGTERM, lambda *_: term_flag.update(hit=True))
        except ValueError:
            pass  # not the main thread (tests/embedded use): no handler

    save_secs = job.runtime.checkpoint.save_every_seconds
    last_save = time.monotonic()

    def maybe_midtrain_save(epoch: int) -> None:
        """Mid-epoch save point: time-based cadence + SIGTERM drain.  A
        mid-epoch save records the CURRENT epoch, so resume replays the
        interrupted epoch from its start — a bounded re-application window,
        the price of mid-epoch durability (the reference's Supervisor
        restore had equally coarse step semantics)."""
        nonlocal last_save
        # chaos site "train.chunk": the safe-point boundary itself — a
        # crash here models dying between a chunk's compute and its save
        chaos.maybe_fail("train.chunk", echo=console, epoch=epoch)
        if ahead is not None:
            # the state in hand is the one after the scan in flight, an
            # epoch past this label, and that epoch has no record yet: the
            # drain and the time cadence wait for its boundary, where
            # `may_run_ahead` dispatches nothing further (what a SIGTERM
            # during a resident scan has always waited for)
            return
        if term_flag["hit"]:
            if manager is not None:
                cur = int(jax.device_get(state.step))
                saved = False
                if (ckpt_lib.latest_step(manager) or -1) < cur:
                    ckpt_lib.save(manager, cur, state,
                                  extra={"epoch": epoch}, block=True)
                    saved = True
                ckpt_lib.finalize(manager)
                # preemption grace: the journal records WHERE the drain
                # landed, so an operator (and chaos-verify) can confirm the
                # resume point is the grace-saved step, not the prior
                # epoch boundary
                obs.event("preemption_grace", epoch=int(epoch),
                          step=cur, saved=saved)
                obs.flush()
                console("SIGTERM: checkpoint saved, exiting for restart")
            else:
                console("SIGTERM: exiting (no checkpoint directory)")
            raise SystemExit(75)
        if manager is None or save_secs <= 0:
            return
        if time.monotonic() - last_save >= save_secs:
            cur = int(jax.device_get(state.step))
            if (ckpt_lib.latest_step(manager) or -1) < cur:  # durable yet?
                # `<`: a collision-bumped save key can sit ABOVE the raw
                # step (checkpoint.save bumps instead of deleting), and
                # that still means this step's state is durable
                ckpt_lib.save(manager, cur, state, extra={"epoch": epoch},
                              block=True)
            last_save = time.monotonic()

    # host-side input production seconds for THIS epoch (reset per epoch):
    # timed around each next() of the host block/batch generator — pure
    # host work, before any cross-process array assembly, so it is the
    # per-host-attributable cost the straggler line sorts by.  Appended
    # from the prefetch producer thread; read after the epoch joins it.
    host_input_times: list[float] = []

    def _timed_source(gen):
        def run():
            while True:
                t0 = time.perf_counter()
                try:
                    item = next(gen)
                except StopIteration:
                    return
                host_input_times.append(time.perf_counter() - t0)
                yield item
        return run()

    def dispatch_resident_epoch(ep: int, timer):
        """The resident tier's one dispatch of epoch `ep`, timed by `timer`
        as the epoch's one chunk: (loss sum still on the device, batches).
        `state` is donated to the scan and rebound to what it leaves."""
        nonlocal state
        nb_total = resident_blocks["features"].shape[0]
        # THE shared per-epoch order stream (pipeline.py): the journaled
        # order_digest derives from the same function
        order = pipe.epoch_permutation(
            nb_total, shuffle=job.data.shuffle,
            seed=job.data.shuffle_seed, epoch=ep).astype(np.int32)
        timer.mark_input_ready()
        state, loss_acc = device_epoch_step(
            state, resident_blocks, jnp.asarray(order))
        timer.mark_step_done()
        return loss_acc, nb_total

    # the resident tiers pipelined one epoch deep (docs/DATA.md "Overlap
    # engine"): `ahead` is epoch e+1's scan, dispatched once epoch e's eval
    # pass is queued and before the host fetches and accumulates its
    # scores, so that the device trains while the host counts.  The device
    # runs its queue in order: the eval pass reads the state after epoch e,
    # and the scan that takes that state by donation runs behind it.
    ahead: Optional[_ScanAhead] = None
    # what the look-ahead can hide, as last observed: the accumulation's
    # share of the wall of the newest epoch that evaluated
    host_share: Optional[float] = None

    def may_run_ahead(epoch: int) -> bool:
        """Whether epoch `epoch`+1's scan may be dispatched at the close of
        `epoch`: both tiers resident in one process, an accumulation that
        is worth hiding, and nothing at this boundary that reads `state` on
        the host or may end the run."""
        nxt = epoch + 1
        ck = job.runtime.checkpoint
        return (use_overlap and use_resident and resident_eval is not None
                and pending_loader is None
                and host_share is not None
                and host_share >= _AHEAD_MIN_HOST_SHARE
                and job.train.early_stop_patience == 0
                and nxt < job.train.epochs
                and (manager is None or not (
                    nxt % ck.save_every_epochs == 0
                    or (save_secs > 0
                        and time.monotonic() - last_save >= save_secs)))
                and not term_flag["hit"]
                # a scheduled device capture is to see its scan whole
                and not devprof.will_capture(nxt))

    def run_ahead(ep: int) -> None:
        """Dispatch epoch `ep`'s scan from inside epoch `ep` - 1's eval."""
        nonlocal ahead
        timer = prof_lib.StepTimer(on_chunk=devprof.chunk_hook(ep))
        timer.start()
        with obs.span("scan_ahead", journal=False):
            loss_acc, nb = dispatch_resident_epoch(ep, timer)
        ahead = _ScanAhead(loss_acc, nb, timer, time.perf_counter())

    history: list[EpochMetrics] = []
    # drift baseline (obs/sketch.py): the training-feature sketch is
    # computed once (the features don't change across epochs); the score
    # sketch refreshes at every evaluated epoch so the frozen profile
    # reflects the exported model's actual output distribution
    feat_sketch = None
    baseline_profile: Optional[dict] = None
    # early stopping (TrainConfig.early_stop_patience): best valid error seen
    # and evaluated epochs since it improved by at least min_delta.  Counters
    # reset on resume — patience then applies to the remaining epochs.  The
    # best epoch's params are snapshotted to host (device buffers may be
    # donated by the next step) and restored at the end, so the returned /
    # exported model is the best one measured, not the last.
    best_valid = float("inf")
    evals_since_best = 0
    best_params_host = None
    pending_loader = None  # streamed loader whose train set is not yet built
    pending_thread = None  # background assembly of the retained dataset
    pending_assembly: dict = {}
    # the collector's pauses, by generation, on the profiler's clock and
    # (folded in at each epoch's close) as phases of the epoch's ledger;
    # its thresholds stay as they are
    gc_phases = obs.spans.GcPhases()
    first_epoch: Optional[dict] = None   # its goodput record, for `startup`
    startup_rec: Optional[dict] = None   # built at the first boundary
    try:
      for epoch in range(start_epoch, job.train.epochs):
        # chaos site "train.epoch_start": the epoch boundary BEFORE any
        # work — a crash here must lose nothing (the previous epoch's save
        # already landed); distinct from the CLI's post-epoch "train.epoch"
        chaos.maybe_fail("train.epoch_start", echo=console, epoch=epoch)
        t0 = time.perf_counter()
        # goodput ledger (obs/goodput.py): this epoch's wall gets
        # classified into compile/input/step/checkpoint/restore/eval/other
        # buckets; instrumented compiles and checkpoint saves credit it
        # from their own call sites while it is open
        led_open = obs.goodput.begin_epoch()
        gc_phases.fold()  # a pause between two ledgers is in neither
        # the blocking dataset load that ran before the loop is charged to
        # the first epoch it fed: its seconds go to the input bucket and
        # its wall extends this epoch's wall at close, so the buckets
        # still sum to the (extended) wall
        ingest_wall_s, pending_ingest_s = pending_ingest_s, 0.0
        if ingest_wall_s > 0:
            led_open.add("input", ingest_wall_s)
        if pending_loader is not None and epoch > start_epoch:
            # first epoch after the streamed one: the retained dataset's
            # assembly + global shuffle either ran in the background thread
            # the streamed epoch kicked off (overlap engine: it was hidden
            # behind that epoch's eval) or runs here, serialized
            if pending_thread is not None:
                pending_thread.join()
                pending_thread = None
                if "error" in pending_assembly:
                    raise pending_assembly["error"]
                train_ds = pending_assembly.pop("train_ds")
            else:
                train_ds = pending_loader.train_dataset()
            pending_loader = None
            with obs.span("epoch/tiers", journal=False):
                _prepare_tiers()
        # loss accumulates on device; host sync happens once per epoch so
        # async dispatch keeps the chips busy
        loss_acc = None
        loss_n = 0
        host_input_times.clear()
        ran_ahead, ahead = ahead, None
        if ran_ahead is not None:
            # this epoch's scan has been in flight since the previous
            # epoch's eval, where its dispatch was timed
            timer = ran_ahead.timer
        else:
            timer = prof_lib.StepTimer(on_chunk=devprof.chunk_hook(epoch))
            timer.start()
        # trace seam: the flight recorder's schedule decides
        # (obs.trace_epochs — a scheduled epoch's capture closes into a
        # `device_profile` journal event)
        with devprof.epoch_capture(epoch), \
                obs.span("epoch/train", epoch=epoch):
            streamed_this_epoch = False
            if stream_loader is not None and epoch == start_epoch:
                # streamed first epoch: train on stacked blocks as files
                # parse in the background — parse, H2D (in the prefetch
                # producer thread), and device compute overlap instead of
                # running serially
                stream_bs = bs
                if mesh is not None:
                    stream_bs = -(-stream_bs // mesh.size) * mesh.size
                # same chunk shape as the staged tier (staged_block_batches
                # already carries the ~32MB-wire overlap cap), so the
                # streamed epoch and later staged epochs usually share ONE
                # compiled scan program.  Known bounded exceptions when the
                # compact wire engages: a pad-tail block keeps its (zeroed)
                # weight column, and a multihost streamed epoch sends the
                # uncompacted wire while the agreed staged tier compacts —
                # each costs at most one extra scan compile per job, which
                # the H2D bytes saved every later epoch repay
                nb_stream = staged_block_batches
                console(f"Streaming first epoch: training overlaps the "
                        f"background parse (batch {stream_bs}, "
                        f"{nb_stream} batches/chunk)")
                if multihost:
                    # collective streamed epoch: each round every host pulls
                    # ONE local chunk (blocking — so "no chunk" means its
                    # stream ENDED, not that it is slow) and an allgather
                    # agrees whether all have one; the first dry host stops
                    # the round for everyone.  No tail padding: partial
                    # chunks stay in the retained dataset for later epochs.
                    # prefetch_to_device(size=1) runs the pull AND the H2D
                    # placement (process-local; only the scan dispatch is
                    # collective) in its producer thread, so round N+1's
                    # chunk overlaps round N's compute, with the shared
                    # helper's error forwarding (a corrupt file fails this
                    # host — the pod launcher tears the gang down — instead
                    # of hanging everyone).
                    from jax.experimental import multihost_utils
                    local_stream_bs = stream_bs // nproc
                    stream_end = object()
                    it = pipe.prefetch_to_device(
                        stream_loader.first_epoch_blocks(
                            local_stream_bs, nb_stream, pad_tail=False),
                        mesh, size=1, put_fn=_block_put_fn(wcast_stream))
                    while True:
                        # time the local pull ONLY (the allgather below
                        # synchronizes the gang, so including it would make
                        # every rank report the slowest rank's input time
                        # and blind the straggler line)
                        t_in = time.perf_counter()
                        pending = next(it, stream_end)
                        host_input_times.append(time.perf_counter() - t_in)
                        have = np.asarray(0 if pending is stream_end else 1)
                        if int(np.min(multihost_utils.process_allgather(
                                have))) == 0:
                            # a peer ran dry: shut the producer down BEFORE
                            # the loader is touched again (it would race
                            # _drain for parse results and pin its pending
                            # device chunks in HBM for the rest of the job)
                            stream_loader.abort_blocks()
                            for _ in it:
                                pass  # frees the <=2 in-flight device blocks
                            break
                        timer.mark_input_ready()
                        state, loss_sum_blk = epoch_scan_step(state, pending)
                        loss_acc = _tree_sum(loss_acc, loss_sum_blk)
                        loss_n += nb_stream
                        timer.mark_step_done()
                    if epoch + 1 >= job.train.epochs:
                        # epochs=1: there IS no later epoch to train the
                        # rows the agreed rounds did not cover
                        skipped = (stream_loader.train_rows_total()
                                   - loss_n * local_stream_bs)
                        if skipped > 0:
                            console(
                                f"streamed epoch left {skipped} of this "
                                "host's rows untrained (the gang stops when "
                                "the smallest shard runs dry) and no later "
                                "epoch will train them — rebalance file "
                                "shards or run more epochs")
                else:
                    # zero-weight tail padding is exact only for weight-
                    # gated losses without a per-step L2 term (see
                    # first_epoch_blocks)
                    pad_tail = (job.train.loss in ("weighted_mse",
                                                   "weighted_bce")
                                and job.model.l2_scale <= 0)
                    for blocks in pipe.prefetch_to_device(
                            stream_loader.first_epoch_blocks(
                                stream_bs, nb_stream, pad_tail=pad_tail),
                            mesh, size=job.data.prefetch,
                            put_fn=_block_put_fn(wcast_stream)):
                        timer.mark_input_ready()
                        state, loss_sum_blk = epoch_scan_step(state, blocks)
                        loss_acc = _tree_sum(loss_acc, loss_sum_blk)
                        timer.mark_step_done()
                        # chunk boundary = consistent state: SIGTERM drain
                        # + time-cadence saves mid-epoch (long first epochs
                        # must not lose an hour to a preemption)
                        maybe_midtrain_save(epoch)
                    # batches that held at least one real row (pad-only
                    # batches contribute zero loss, must not skew the error)
                    loss_n = stream_loader.real_batches
                # end-of-epoch eval needs only the (small) valid partition;
                # the train partition's assembly + global shuffle waits for
                # the next epoch that actually consumes it (an epochs=1 job
                # never pays it)
                valid_ds = stream_loader.valid_dataset()
                pending_loader, stream_loader = stream_loader, None
                streamed_this_epoch = loss_n > 0
                if (streamed_this_epoch and use_overlap
                        and epoch + 1 < job.train.epochs):
                    # overlap engine: assemble + globally shuffle the
                    # retained dataset on a background thread NOW, so the
                    # work hides behind this epoch's eval instead of
                    # serializing at the next epoch's start (the loader is
                    # quiescent — valid_dataset() above already drained the
                    # parse, and only this thread touches it until the join)
                    import threading as _threading

                    def _assemble_retained(loader=pending_loader,
                                           box=pending_assembly):
                        try:
                            box["train_ds"] = loader.train_dataset()
                        except BaseException as e:  # re-raised at the join
                            box["error"] = e

                    pending_thread = _threading.Thread(
                        target=_assemble_retained, daemon=True,
                        name="shifu-retained-assembly")
                    pending_thread.start()
                if not streamed_this_epoch:
                    # empty stream (no train rows at all): assemble now so
                    # _prepare_tiers can clamp or raise its usual errors
                    train_ds = pending_loader.train_dataset()
                    pending_loader = None
                    with obs.span("tiers", journal=False):
                        _prepare_tiers()
                    console(f"streamed first epoch had no full batch of "
                            f"{stream_bs}; re-running epoch {epoch} with "
                            f"batch {bs}")
            if streamed_this_epoch:
                pass
            elif ran_ahead is not None:
                # what is left of it is the wait below
                loss_acc, loss_n = ran_ahead.loss_acc, ran_ahead.loss_n
            elif use_resident:
                loss_acc, loss_n = dispatch_resident_epoch(epoch, timer)
            elif use_staged:
                # multihost: every host streams blocks of its OWN shard's
                # epoch subset (exactly min_host_rows rows), so the
                # block-count sequence (a pure function of
                # num_rows/batch/seed/epoch) is identical everywhere and
                # each chunk's scan is one agreed collective dispatch — the
                # out-of-HBM successor of the per-batch collective path, at
                # scan-tier dispatch rates
                if use_overlap:
                    if feeder is None:
                        feeder = pipe.EpochFeeder(
                            _staged_host_blocks, staged_put_fn,
                            range(epoch, job.train.epochs),
                            depth=feeder_dev_depth,
                            host_depth=feeder_host_depth)
                    block_iter = feeder.epoch(epoch)
                else:
                    t_src = time.perf_counter()
                    epoch_src = staged_source(epoch)  # epoch-subset copy?
                    host_blocks = pipe.staged_epoch_blocks(
                        epoch_src, local_bs, shuffle=job.data.shuffle,
                        seed=job.data.shuffle_seed, epoch=epoch,
                        block_batches=staged_block_batches)
                    if multihost:  # single-host never reads the times
                        host_input_times.append(time.perf_counter() - t_src)
                        host_blocks = _timed_source(host_blocks)
                    block_iter = pipe.prefetch_to_device(
                        host_blocks, mesh, size=job.data.prefetch,
                        put_fn=staged_put_fn)
                for blocks in block_iter:
                    timer.mark_input_ready()
                    nb = blocks["features"].shape[0]
                    state, loss_sum_blk = epoch_scan_step(state, blocks)
                    loss_acc = _tree_sum(loss_acc, loss_sum_blk)
                    loss_n += nb
                    timer.mark_step_done()
                    if not multihost:
                        # chunk boundary = consistent state: SIGTERM drain +
                        # time-cadence saves for out-of-HBM epochs, whose
                        # length is exactly why mid-epoch durability matters
                        maybe_midtrain_save(epoch)
            else:
                bcast = wcast
                if _embed_dedup is not None:
                    # dedup BEFORE the wire cast: it reads decoded f32
                    # features (categorical jobs ride the f32 wire anyway)
                    bcast = (_embed_dedup if wcast is None else
                             (lambda b, _c=wcast: _c(_embed_dedup(b))))
                put_fn = _feed_put_fn(shard_lib.shard_batch,
                                      shard_lib.shard_batch_process_local,
                                      bcast)
                if use_overlap:
                    if feeder is None:
                        feeder = pipe.EpochFeeder(
                            _perbatch_host_batches, put_fn,
                            range(epoch, job.train.epochs),
                            depth=feeder_dev_depth,
                            host_depth=feeder_host_depth)
                    batch_iter = feeder.epoch(epoch)
                else:
                    # every host runs the SAME number of collective steps
                    # (_perbatch_host_batches islices to the agreed count)
                    host_batches = _perbatch_host_batches(epoch)
                    if multihost:  # single-host never reads the times
                        host_batches = _timed_source(iter(host_batches))
                    batch_iter = pipe.prefetch_to_device(
                        host_batches, mesh, size=job.data.prefetch,
                        put_fn=put_fn)
                for batch in batch_iter:
                    timer.mark_input_ready()
                    state, step_metrics = train_step(state, batch)
                    loss_acc = _tree_sum(
                        loss_acc, step_metrics if "counters" in step_metrics
                        else step_metrics["loss"])
                    loss_n += 1
                    timer.mark_step_done()
                    if not multihost:  # collectives forbid divergent exits
                        maybe_midtrain_save(epoch)
            if loss_n == 0:
                raise ValueError(
                    f"epoch {epoch} produced 0 batches "
                    f"({train_ds.num_rows} rows, batch_size {bs}, "
                    f"drop_remainder={job.data.drop_remainder})")
            # the epoch's one host sync.  The scan tiers' dispatches return
            # before the device is done, so the wait for it is here: its
            # seconds belong to the ledger's `step` bucket (dispatch-to-
            # done on every tier), not to `other`
            with obs.span("device_wait", journal=False) as device_wait:
                loss_sum, step_counters = split_readback(
                    jax.device_get(loss_acc))
        epoch_time = time.perf_counter() - t0

        tv0 = time.perf_counter()
        eval_tier = None  # which source of batches this epoch's eval read
        eval_beside_scan_s = 0.0  # the eval's host half, next scan queued
        if epoch % job.train.eval_every_epochs == 0 or epoch == job.train.epochs - 1:
            score_sketch = obs.sketch.ScoreSketch()
            eval_tier = "streamed" if resident_eval is None else "resident"
            with obs.span("epoch/eval", epoch=epoch):
                if may_run_ahead(epoch):
                    # evaluate()'s resident branch with the next epoch's
                    # scan queued between its two halves
                    dispatched = _dispatch_resident_eval(
                        state, valid_ds, resident_eval)
                    run_ahead(epoch + 1)
                    valid_error, valid_auc = _accumulate_streaming(
                        _fetch_resident_eval(*dispatched),
                        sketch=score_sketch)
                else:
                    valid_error, valid_auc = evaluate(
                        state, valid_ds, job, eval_step, mesh,
                        resident=resident_eval, sketch=score_sketch)
            if ahead is not None:
                eval_beside_scan_s = time.perf_counter() - ahead.t_dispatched
                obs.counter(
                    "eval_overlapped_epochs_total",
                    "epochs whose eval's host half (fetch, accumulate) ran "
                    "beside the next epoch's scan").inc()
        else:
            score_sketch = None
            valid_error, valid_auc = float("nan"), float("nan")
        valid_time = time.perf_counter() - tv0

        m = EpochMetrics(
            epoch=epoch,
            train_error=loss_sum / max(loss_n, 1),
            valid_error=valid_error,
            valid_auc=valid_auc,
            epoch_time=epoch_time,
            valid_time=valid_time,
        )
        history.append(m)
        console(m.console_line(job.train.epochs))
        # per-epoch telemetry: the journal carries the structured epoch
        # record (what the console line prints, machine-readable), the
        # registry the step-level distributions and headline gauges
        timer.emit()
        obs.counter("train_epochs_total", "completed training epochs").inc()
        obs.counter("train_batches_total",
                    "train batches consumed (scan tiers count batches "
                    "inside each chunk)").inc(loss_n)
        obs.gauge("train_error", "last epoch's weighted train error").set(
            m.train_error)
        if valid_error == valid_error:  # evaluated this epoch, not NaN
            obs.gauge("valid_error",
                      "last evaluated weighted valid error").set(valid_error)
        if valid_auc == valid_auc:
            obs.gauge("valid_auc", "last evaluated valid AUC").set(valid_auc)
        obs.event("epoch", **dataclasses.asdict(m))
        if score_sketch is not None and score_sketch.n > 0:
            # the frozen stats epoch: journal a compact summary every
            # evaluated epoch; the LAST one rides the export artifact as
            # baseline_profile.json (obs/drift.py diffs live traffic
            # against it)
            if feat_sketch is None:
                feat_sketch = _baseline_feature_sketch(job, train_ds)
            if feat_sketch is not None:
                baseline_profile = obs.sketch.build_profile(
                    feat_sketch, score_sketch,
                    feature_names=_baseline_feature_names(
                        job.schema, feat_sketch.num_features),
                    train_auc=valid_auc, train_error=m.train_error,
                    epoch=epoch)
                obs.event("baseline_profile",
                          **obs.sketch.profile_summary(baseline_profile))
        # epoch-cadence flush: the scrape file must reflect a RUNNING job
        # (`shifu-tpu metrics` / a textfile collector mid-run), and a later
        # SIGKILL (liveness hard-kill) must not erase the whole run's
        # metrics — one atomic small-file rewrite per epoch
        obs.flush()
        if timing_on:
            console(timer.console_line())
        # epoch identity, computed once and shared by the straggler line's
        # cross-host skew row and the overlap report below: which tier
        # actually served the epoch, and the determinism digest of its
        # global batch order
        tier = ("stream" if streamed_this_epoch else
                "resident" if use_resident else
                "staged" if use_staged else "batch")
        digest_rows = 0
        if train_ds is not None:
            digest_rows = (min_host_rows
                           if multihost and tier in ("staged", "resident")
                           else train_ds.num_rows)
        order_digest = pipe.epoch_order_digest(
            tier, digest_rows, local_bs, shuffle=job.data.shuffle,
            seed=job.data.shuffle_seed, epoch=epoch)
        if multihost:
            # slowest-first per-host line on the chief (collective — every
            # rank contributes; successor of the AM's worker-stats sort,
            # TensorflowSession.java:515-549).  Host input seconds from the
            # timed source when a tier used one (staged/per-batch), else
            # the consumer-side input waits (streamed/resident epochs)
            if feeder is not None:
                # overlap engine: producer-side host seconds per epoch are
                # tracked by the feeder itself (production may have run
                # DURING the previous epoch — attribution is by epoch, not
                # by when the threads happened to do the work)
                input_s = feeder.production_seconds(epoch)
            elif host_input_times:
                input_s = sum(host_input_times)
            else:
                input_s = sum(timer.input_times)
            # pod data plane extras ride the skew row's allgather: each
            # host's cumulative source-ingest cost (a slow-ingest host is
            # visible as the straggler cause), its epoch order digest, and
            # its view of the global shard assignment — the chief journals
            # per-epoch cross-host agreement on both digests in the
            # host_skew row (obs/aggregate.epoch_skew)
            reg = obs.default_registry()
            try:
                shard_digest = pipe.shard_assignment_digest(
                    pipe.count_source_files(job.data), nproc,
                    seed=job.data.shuffle_seed, epoch=epoch,
                    mode=job.data.host_shard)
            except OSError:
                shard_digest = None  # source paths gone mid-run: skew row
                # still ships, the audit marks the digest unavailable
            prof_lib.straggler_line(
                epoch, epoch_time, valid_time, input_s, console,
                extra={
                    "ingest_bytes": int(reg.counter(
                        "ingest_source_bytes_total").total()),
                    "ingest_s": round(reg.counter(
                        "ingest_seconds_total").total(), 3),
                    "order_digest": order_digest,
                    "shard_digest": shard_digest,
                })

        # early-stopping bookkeeping runs BEFORE the terminal checkpoint
        # save so that checkpoint holds the same best-measured params the
        # returned/exported state does — the export CLI recovery path
        # restores from the checkpoint, and it must ship the same artifact
        # the train tail exports (docs/CONFIG.md "best params are restored")
        patience = job.train.early_stop_patience
        early_stop_now = False
        if patience > 0 and valid_error == valid_error:  # evaluated, not NaN
            if valid_error < best_valid - job.train.early_stop_min_delta:
                best_valid = valid_error
                evals_since_best = 0
                best_params_host = jax.device_get(state.params)
            else:
                evals_since_best += 1
                if evals_since_best >= patience:
                    early_stop_now = True
                    console(f"Early stop at epoch {epoch}: no valid_error "
                            f"improvement > {job.train.early_stop_min_delta:g} "
                            f"in {patience} evaluated epochs "
                            f"(best {best_valid:.6f})")

        terminal = early_stop_now or epoch == job.train.epochs - 1
        best_restored = False
        if (terminal and best_params_host is not None
                and best_valid < float("inf")):
            best_restored = True
            # restore the best-measured params (same shardings as the
            # current state's leaves) before the terminal save, so
            # checkpoint, returned state, and export all agree.  The
            # terminal checkpoint records epoch=epochs (training COMPLETE,
            # even when stopping early): the rolled-back params ride with
            # the last trajectory's optimizer moments, so resuming training
            # from this state would apply mismatched updates — an
            # early-stopped run must resume as done, not as epoch E+1
            state = state.replace(params=jax.tree_util.tree_map(
                lambda host, cur: jax.device_put(host, cur.sharding),
                best_params_host, state.params))

        # save before the callback so external kills (timeout, fault
        # injection, preemption) never lose the completed epoch; async_save
        # trades that guarantee for overlap with the next epoch's compute
        if manager is not None and (
                terminal
                or (epoch + 1) % job.runtime.checkpoint.save_every_epochs == 0):
            extra = {"epoch": (job.train.epochs if terminal else epoch + 1)}
            if best_restored:
                extra["best_restored"] = True
            ckpt_lib.save(manager, int(jax.device_get(state.step)), state,
                          extra=extra,
                          block=(early_stop_now
                                 or not job.runtime.checkpoint.async_save))
            last_save = time.monotonic()
        if not multihost:
            # epoch boundary is the safe SIGTERM drain point for the
            # on-device scan tiers (the epoch itself is one dispatch)
            maybe_midtrain_save(epoch + 1)

        # close the goodput ledger over the FULL epoch wall (train + eval
        # + saves): input is the consumer-visible wait (the gap the device
        # sat idle before each dispatch — producer-side host_input_times
        # overlap compute and are the straggler line's lens, not this
        # one's), step is dispatch-to-done (the dispatches plus the
        # epoch's device_wait); compile/checkpoint/restore
        # were credited in-flight; `other` absorbs the residue so the
        # buckets always sum to the wall
        led = obs.goodput.current()
        if led is not None:
            # a scan dispatched ahead was timed inside the previous epoch's
            # wall (its eval bucket, phase `epoch/eval/scan_ahead`): what
            # this epoch's wall holds of it is the device_wait
            in_wall = ran_ahead is None
            led.add("input", sum(timer.input_times) if in_wall else 0.0)
            led.add("step", (sum(timer.step_times) if in_wall else 0.0)
                    + device_wait.seconds)
            led.add("eval", valid_time)
            gc_phases.fold(led)
            wall_s = time.perf_counter() - t0
            if eval_tier is not None and wall_s > 0:
                host_share = led.phase_seconds(
                    "epoch/eval/accumulate") / wall_s
            good = obs.goodput.end_epoch(epoch, wall_s + ingest_wall_s)
            if startup_led is not None and good is not None:
                # the first trained epoch, as its goodput event has it, less
                # the blocking ingest charged to it: `startup/ingest` here
                first_epoch = {
                    "wall_s": round(good["wall_s"] - ingest_wall_s, 6),
                    "buckets": dict(
                        good["buckets"],
                        input=round(max(good["buckets"]["input"]
                                        - ingest_wall_s, 0.0), 6)),
                    "phases": good["phases"]}
        if "moe" in step_counters:
            # where the routed expert layers' tokens went this epoch, one
            # entry an expert layer: summed on the device, read with the loss
            obs.event("moe", epoch=epoch, layers=_moe_layers(
                step_counters["moe"]))

        # flight-recorder epoch boundary: close a one-shot anomaly trace
        # still open (anomaly on the epoch's last chunk) and journal the
        # HBM watermark next to the goodput record it annotates
        devprof.end_epoch(epoch)

        # overlap report: what the engine hid vs what the device still
        # waited for this epoch (docs/OBSERVABILITY.md).  `exposed` is the
        # consumer-visible input wait (same lens as the ledger's input
        # bucket); `production` is the host seconds the epoch's items cost
        # to assemble + stage wherever they ran; `hidden` is the
        # difference — host input work that overlapped device compute.
        # `order_digest` pins the determinism contract: a pure function of
        # (seed, epoch, tier), byte-identical with overlap on or off and
        # across a restart resume (tests/test_overlap.py).  `tier`,
        # `digest_rows` and `order_digest` were computed above, before the
        # straggler line that shares them.
        exposed_s = sum(timer.input_times)
        if feeder is not None:
            prod_s = feeder.production_seconds(epoch)
        elif host_input_times:
            prod_s = sum(host_input_times)
        else:
            prod_s = exposed_s  # untimed producer: nothing provably hidden
        hidden_s = max(prod_s - exposed_s, 0.0)
        eff = (hidden_s / (hidden_s + exposed_s)
               if hidden_s + exposed_s > 0 else None)
        obs.event("overlap_report", epoch=epoch, tier=tier,
                  eval_tier=eval_tier, overlap=feeder is not None,
                  prefetch_depth=(feeder.depth if feeder is not None
                                  else job.data.prefetch),
                  input_exposed_s=round(exposed_s, 6),
                  input_production_s=round(prod_s, 6),
                  input_hidden_s=round(hidden_s, 6),
                  eval_s=round(valid_time, 6),
                  eval_overlapped=ahead is not None,
                  eval_beside_scan_s=round(eval_beside_scan_s, 6),
                  prefetched_chunks=(feeder.ready_ahead()
                                     if feeder is not None else 0),
                  overlap_efficiency=(round(eff, 4) if eff is not None
                                      else None),
                  order_digest=order_digest,
                  resident_format=(
                      pipe.resident_feature_format(
                          job.schema, job.data, job.model.compute_dtype)
                      if use_resident else None))
        if multihost:
            # DCN placement ledger, next to the overlap report it refines:
            # per-host batch construction (shard_batch_process_local /
            # shard_blocks_process_local) lands each host's slice on its
            # OWN devices' DATA-axis shards, so steady-state input traffic
            # crosses zero DCN links — the analytic savings vs a
            # replicated input plane (every host shipping every batch) is
            # (n_hosts - 1) x the local wire bytes.  The local-SGD window
            # piggybacks its own DCN savings: each skipped per-step grad
            # sync would have moved ~param_bytes across the slice boundary.
            topo = mesh_lib.dcn_topology(mesh)
            local_input_b = int(digest_rows) * int(row_wire_b)
            spe = int(steps_per_epoch or 0)
            k_win_now = int(job.train.local_sgd_window)
            sync_rounds = (spe // k_win_now if k_win_now > 0 else spe)
            sync_skipped = max(spe - sync_rounds, 0) if k_win_now > 0 else 0
            param_b = sum(
                int(leaf.size) * int(np.dtype(leaf.dtype).itemsize)
                for leaf in jax.tree_util.tree_leaves(state.params))
            obs.event("dcn_placement", epoch=epoch, tier=tier,
                      hosts=topo["processes"], slices=topo["slices"],
                      local_devices=topo["local_devices"],
                      input_local_bytes=local_input_b,
                      input_dcn_bytes=0,
                      input_dcn_saved_bytes=(
                          (topo["processes"] - 1) * local_input_b),
                      local_sgd_window=k_win_now,
                      sync_rounds=sync_rounds,
                      sync_rounds_skipped=sync_skipped,
                      dcn_sync_saved_bytes=sync_skipped * param_b)
        hid_c = obs.counter("overlap_hidden_seconds_total",
                            "input seconds hidden behind device compute "
                            "by the overlap engine")
        exp_c = obs.counter("overlap_exposed_seconds_total",
                            "epoch-boundary seconds still exposed on the "
                            "critical path (device idle)")
        hid_c.inc(hidden_s, kind="input")
        exp_c.inc(exposed_s, kind="input")
        if ahead is None:
            # an eval with the next scan queued behind it is in neither
            # counter: the host cannot see when that scan ended (the next
            # epoch's device_wait and a device trace can)
            exp_c.inc(valid_time, kind="eval")
        if eff is not None:
            obs.gauge("overlap_efficiency",
                      "last epoch's hidden / (hidden + exposed) input "
                      "fraction").set(round(eff, 4))
        wall_now = time.perf_counter() - t0
        if (feeder is not None and job.data.prefetch_depth == 0
                and wall_now > 0):
            # auto mode: one depth step per epoch from the ledger's
            # exposed-input fraction (data/pipeline.next_prefetch_depth)
            feeder.set_depth(pipe.next_prefetch_depth(
                feeder.depth, exposed_s / wall_now))

        if startup_led is not None:
            # stamped here, where a caller's set-up ends (the callback)
            call_s = time.perf_counter() - t_entry
            startup_rec = {
                "wall_s": round(call_s, 6),
                "phases": startup_led.summary(call_s)["phases"],
                "first_epoch": first_epoch,
                "compiles": obs.introspect.compiles_since(compile_mark),
                "epoch": epoch}
            startup_led = None
        try:
            if epoch_callback is not None:
                epoch_callback(m)
        finally:
            if startup_rec is not None:
                # after the callback, also where it ends the call: a caller
                # that marks the journal in its first callback (the
                # benchmark's window) finds the event in what follows
                obs.event("startup", **startup_rec)
                startup_rec = None

        if early_stop_now:
            break
    finally:
      gc_phases.close()
      # never leave jax.profiler tracing, however the loop exits (an open
      # trace would poison the next capture in this process)
      devprof.close()
      if feeder is not None:
          # however the loop exits (done, early stop, SIGTERM drain, error):
          # abort the persistent feeder and free its run-ahead device blocks
          feeder.close()
      if _embed_dedup is not None:
          # flush the tail embed_dedup_report (runs shorter than the report
          # cadence would otherwise never journal their dedup story)
          _embed_dedup.finalize()
      if pending_thread is not None:
          # bounded-courtesy join only: if the loop is exiting with the
          # background retained-dataset assembly unconsumed (early stop,
          # SIGTERM drain, error), nobody will ever use its result — a
          # long join here would eat the 15s preemption-grace window on a
          # multi-GB shuffle.  The thread is a daemon doing pure host
          # compute; it finishes (or dies with the process) on its own.
          pending_thread.join(timeout=1.0)
      if old_term is not None:
          _signal.signal(_signal.SIGTERM, old_term)
      if manager is not None:
        # async saves must be durable (and their errors surfaced) no matter
        # how the loop exits — a mid-loop exception must not abandon an
        # in-flight write of a completed epoch
        ckpt_lib.finalize(manager)
      # journal + scrape file reflect the run however the loop exits (the
      # CLI flushes again at run_end with the exit code)
      obs.event("train_end", epochs_completed=len(history))
      obs.flush()
    return TrainResult(state=state, history=history, job=job,
                       resumed_from_epoch=start_epoch,
                       baseline_profile=baseline_profile)
