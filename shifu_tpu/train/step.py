"""Jitted train / eval step builders.

One step here is the successor of the reference's
`sess.run([train_step, loss, global_step], feed_dict)` round trip
(resources/ssgd_monitor.py:271-276), which cost a worker->PS gRPC pull/push
plus the SyncReplicasOptimizer token-queue barrier per batch.  Under SPMD the
whole update is a single XLA program: forward+backward on the data-sharded
batch, a mean-gradient all-reduce over ICI (inserted by XLA from the
shardings), and the optimizer update — no parameter server, no token queue.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config.schema import JobConfig
from ..ops import losses as losses_lib
from ..parallel import sharding as shard_lib
from .train_state import TrainState

Batch = dict[str, jax.Array]


def wire_fused_into_model(job: JobConfig) -> bool:
    """True when the model consumes int8 wire features NATIVELY — its first
    layer applies the wire grid inside the matmul (models/base._WireDense
    over ops/pallas_int8_matmul) — so the step builders must skip the
    separate decode dispatch entirely.  Requires: int8 features actually
    reach the device (int8 wire or an int8-resident tier), a model whose
    first layer is the wire-capable dense (the MLP ladder), and the fused
    kernel engaged on this platform/shape.  Anywhere this is False the
    decode path runs exactly as before — the bit-identical fallback."""
    from ..data import pipeline as pipe
    from ..ops.pallas_int8_matmul import fused_engaged

    if job.model.model_type != "mlp" or not job.model.hidden_nodes:
        return False
    cdt = job.model.compute_dtype
    if (pipe.wire_mode(job.schema, job.data, cdt) != "int8"
            and pipe.resident_feature_format(job.schema, job.data,
                                             cdt) != "int8"):
        return False
    return fused_engaged(job.schema.feature_count, job.model.hidden_nodes[0])


def make_wire_decode(job: JobConfig):
    """On-device inverse of the int8 wire quantization (x = q*scale +
    offset, computed in f32 before the model's own compute-dtype cast), or
    None when no int8 features ever reach the device (neither the wire nor
    the resident tier's in-HBM format is int8) — composing an identity op
    into every step just wastes a dispatch.  Also None when the model
    consumes the wire natively (wire_fused_into_model): the first-layer
    kernel applies the grid itself.  The grid is the same static per-column
    one the host encoded with (data/pipeline.wire_params), so decode needs
    no data-dependent state — it closes over two (F,) constants and fuses
    into the first layer's HLO."""
    from ..data import pipeline as pipe

    cdt = job.model.compute_dtype
    if (pipe.wire_mode(job.schema, job.data, cdt) != "int8"
            and pipe.resident_feature_format(job.schema, job.data,
                                             cdt) != "int8"):
        return None
    if wire_fused_into_model(job):
        return None
    scale, offset = pipe.wire_params(job.schema, job.data)
    s = jnp.asarray(scale)
    o = jnp.asarray(offset) if np.any(offset) else None

    def decode(features: jax.Array) -> jax.Array:
        if features.dtype != jnp.int8:  # static: raw-f32 callers pass through
            return features
        x = features.astype(jnp.float32) * s
        return x if o is None else x + o

    return decode


def split_readback(got) -> tuple[float, dict]:
    """(loss sum, counters) of what an epoch's step handed back: the loss
    sum alone, or with the sums of what the model sowed beside it."""
    if isinstance(got, dict):
        return float(got["loss"]), got.get("counters", {})
    return float(got), {}


def _with_counters(loss, counters):
    return {"loss": loss, "counters": counters} if counters else loss


def make_loss_fn(job: JobConfig):
    """Training loss.  With ModelConfig DropoutRate > 0 the forward pass
    runs with `train=True` and a per-update dropout rng derived from
    (train.seed, global step) — deterministic across resume/replay, distinct
    every optimizer step.  Eval/export never pass `train`, so scoring stays
    deterministic."""
    base = losses_lib.get_loss(job.train.loss)
    if job.model.num_heads > 1:
        base = losses_lib.multitask_loss(base)
    l2 = job.model.l2_scale
    use_dropout = job.model.dropout_rate > 0
    drop_seed = job.train.seed ^ 0x6B0_D0_1  # distinct from init's key stream
    decode = make_wire_decode(job)

    def loss_fn(params, apply_fn, batch: Batch,
                step: Optional[jax.Array] = None) -> jax.Array:
        feats = batch["features"]
        if decode is not None:
            feats = decode(feats)
        if use_dropout:
            rng = jax.random.fold_in(
                jax.random.PRNGKey(drop_seed),
                step if step is not None else jnp.int32(0))
            logits = apply_fn({"params": params}, feats,
                              train=True, rngs={"dropout": rng})
        else:
            logits = apply_fn({"params": params}, feats)
        target, weight = decode_target_weight(batch)
        loss = base(logits, target, weight)
        if l2 > 0:
            loss = loss + losses_lib.l2_penalty(params, l2)
        return loss

    return loss_fn


def decode_target_weight(batch: Batch) -> tuple[jax.Array, jax.Array]:
    """On-device inverse of the compact target/weight wire
    (data/pipeline.wire_cast_fn compact mode): integer-dtype targets (u8 on
    the wire — exact for Shifu's 0/1 labels) cast back to f32, and an
    elided all-ones weight column is synthesized.  Both branches are static
    per jit signature (dtype / pytree structure), so a job whose blocks all
    compact compiles exactly one program."""
    target = batch["target"]
    if jnp.issubdtype(target.dtype, jnp.integer):
        target = target.astype(jnp.float32)
    weight = batch.get("weight")
    if weight is None:
        weight = jnp.ones((target.shape[0], 1), jnp.float32)
    return target, weight


def make_apply_gradients(job: JobConfig, mesh: Optional[Mesh] = None):
    """(state, grads, batch) -> new state: the dense optax apply; the fused
    Adadelta apply for the large leaves where it engages (one in-place
    kernel pass a leaf, train/optimizers.py); or the sparse
    rows-touched-only table apply when the job's plan engages
    (train/sparse_embed.py — tables masked out of optax, moments on
    TrainState.table_slots, touched rows gathered/updated/scattered)."""
    from .optimizers import fused_adadelta_engages, make_fused_adadelta_apply
    from .sparse_embed import make_sparse_apply

    sparse = make_sparse_apply(job, mesh)
    if sparse is None:
        if fused_adadelta_engages(job.train.optimizer, mesh):
            fused = make_fused_adadelta_apply(job.train.optimizer)
            return lambda st, grads, batch: fused(st, grads)
        return lambda st, grads, batch: st.apply_gradients(grads)
    # the whole batch dict: the sparse apply reads features and, when the
    # feeder attached them, the embed_unique compacted ids (embed/dedup)
    return lambda st, grads, batch: sparse(st, grads, batch)


def _catching_counters(loss_fn):
    """`loss_fn` as `(loss, counters)`: the model is applied with its
    `counters` collection mutable and what it sowed there (per-call counts
    for the step to sum over an epoch: the block stack's routed expert
    layers count where their tokens went; most models sow nothing, and {}
    comes out) is handed out beside the loss, whatever `loss_fn` does
    around the call (it stays a scalar loss, as `make_loss_fn` promises its
    other callers)."""
    def with_counters(params, apply_fn, xs, step):
        sown = {}

        def apply(variables, *args, **kwargs):
            out, state = apply_fn(variables, *args, mutable=["counters"],
                                  **kwargs)
            sown.update(state.get("counters", {}))
            return out

        return loss_fn(params, apply, xs, step), sown

    return with_counters


def _fwd_bwd_and_update(loss_fn, apply_grads, st: TrainState, xs: Batch):
    """One optimizer step, the body every step builder shares: (new state,
    loss, what the model sowed in `counters`: {} for most).  Its two parts
    carry stable device-side names (`jax.named_scope`: HLO metadata only,
    the program is the same) so that a profile's operations can be rolled up
    by part whatever the fusion numbering."""
    with jax.named_scope("fwd_bwd"):
        (loss, counters), grads = jax.value_and_grad(
            _catching_counters(loss_fn), has_aux=True)(
            st.params, st.apply_fn, xs, st.step)
    with jax.named_scope("optimizer"):
        st = apply_grads(st, grads, xs)
    return st, loss, counters


def _scan_steps(body_step, state: TrainState, xs):
    """Scan `body_step(state, x) -> (state, loss, counters)` over `xs`:
    (new state, the loss sum, or with the counters' sums beside it)."""
    def body(carry, x):
        st, acc = carry
        st, loss, counters = body_step(st, x)
        return (st, acc + loss), counters

    (state, acc), counters = jax.lax.scan(body, (state, jnp.float32(0.0)), xs)
    return state, _with_counters(
        acc, jax.tree_util.tree_map(lambda v: jnp.sum(v, axis=0), counters))


def _input_donate_argnums(donate: bool, donate_batch: bool) -> tuple:
    """donate_argnums for a (state, batch/blocks) step.  Donating the INPUT
    pytree (argnum 1) marks each chunk's device buffers dead at dispatch,
    so the runtime reclaims their HBM for the next prefetched chunk as soon
    as the scan consumes them instead of when the Python reference dies —
    steady-state H2D then cycles through a fixed set of buffers rather than
    growing a fresh allocation per chunk.  Callers that REUSE a batch
    across calls (the device-resident tier's blocks)
    must keep donate_batch=False: a donated buffer is deleted after its
    first use."""
    out = (0,) if donate else ()
    if donate_batch:
        out += (1,)
    return out


# NOTE: input-chunk donation rarely aliases an output (int8/bf16 blocks vs
# f32 state), so XLA warns once per compile that the donation went unused.
# Expected and inert here (the donation is for early HBM reclaim, not
# aliasing) — the test config filters it in pyproject.toml; the library
# deliberately does NOT install a process-global filter (an embedding
# application must keep the warning for its own jitted functions, where an
# unused donation IS the lost-aliasing bug it exists to flag).


def make_train_step(job: JobConfig, mesh: Optional[Mesh] = None,
                    donate: bool = True, donate_batch: bool = False,
                    ) -> Callable[[TrainState, Batch], tuple[TrainState, dict]]:
    """Build the jitted train step.

    With a mesh: batch in data-axis sharding, state sharded per its own
    (replicated/ruled) placement; XLA inserts the grad all-reduce.  Without a
    mesh: plain single-device jit.
    """
    loss_fn = make_loss_fn(job)
    apply_grads = make_apply_gradients(job, mesh)

    def step(state: TrainState, batch: Batch):
        new_state, loss, counters = _fwd_bwd_and_update(
            loss_fn, apply_grads, state, batch)
        metrics = {"loss": loss}
        if counters:
            metrics["counters"] = counters
        return new_state, metrics

    # Shardings ride on the input arrays themselves (state placed by
    # init_state, batches device_put by the loop with data-axis sharding);
    # XLA propagates them and inserts the grad all-reduce; `mesh` feeds
    # only the sparse apply's replication constraint and donation hints.
    from ..obs.introspect import instrument_jit
    return instrument_jit(
        step, "train_step",
        donate_argnums=_input_donate_argnums(donate, donate_batch))


def make_epoch_scan_step(job: JobConfig, mesh: Optional[Mesh] = None,
                         donate: bool = True, donate_blocks: bool = False):
    """Staged-epoch step: scan the train update over a stacked block of
    batches entirely on device.

    Input: {'features': (nb, B, F), 'target': (nb, B, H), 'weight': (nb, B, 1)}
    (sharded on the batch axis over `data` when a mesh is in play).  Returns
    (new_state, loss_sum over the nb batches; where the model sows counters,
    `{"loss": loss_sum, "counters": their sums}` - `split_readback` reads
    either).  One jit dispatch and one H2D
    transfer cover nb optimizer steps — the input-path design that closes the
    gap between host-fed (~5M samples/s) and compute-bound (~650M samples/s)
    throughput on a v5e chip.
    """
    loss_fn = make_loss_fn(job)
    apply_grads = make_apply_gradients(job, mesh)

    def epoch_step(state: TrainState, blocks: Batch):
        return _scan_steps(
            lambda st, xs: _fwd_bwd_and_update(loss_fn, apply_grads, st, xs),
            state, blocks)

    from ..obs.introspect import instrument_jit
    return instrument_jit(
        epoch_step, "epoch_scan_step",
        donate_argnums=_input_donate_argnums(donate, donate_blocks))


def make_device_epoch_step(job: JobConfig, mesh: Optional[Mesh] = None,
                           donate: bool = True):
    """Device-resident epoch: the whole training partition lives in HBM as
    (nb, B, ...) blocks; each epoch is ONE jit call that reorders batches on
    device (a local gather — axis 0 is unsharded) and scans the train update
    across all of them.  Steady-state host traffic: a (nb,)-int permutation.

    This is the zero-input-overhead tier (DataConfig.device_resident_bytes):
    measured on a v5e chip it runs within a few percent of the pure-compute
    ceiling, vs ~100x slower when every batch crosses the host link.
    """
    loss_fn = make_loss_fn(job)
    apply_grads = make_apply_gradients(job, mesh)

    def epoch_step(state: TrainState, blocks: Batch, order: jax.Array):
        def one(st, idx):
            # dynamic slice (no dataset copy): axis 0 is unsharded, so this
            # is a local HBM read on every device
            xs = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, idx, axis=0,
                                                       keepdims=False),
                blocks)
            return _fwd_bwd_and_update(loss_fn, apply_grads, st, xs)

        return _scan_steps(one, state, order)

    from ..obs.introspect import instrument_jit
    donate_argnums = (0,) if donate else ()
    return instrument_jit(epoch_step, "device_epoch_step",
                          donate_argnums=donate_argnums)


def make_local_sgd_epoch_step(job: JobConfig, mesh: Optional[Mesh] = None,
                              donate: bool = True, with_order: bool = False):
    """True local SGD over one epoch — the reference's SAGN trainer
    (resources/SAGN.py:110-196): each data shard runs `local_sgd_window`
    plain-SGD updates on its OWN parameter replica, then the replicas sync
    by global parameter all-mean (equivalent to SAGN's "average the
    window's accumulated grads, apply through SyncReplicasOptimizer,
    re-sync global->local" with an SGD apply at learning rate K*lr — it
    divides the window sum by K, SAGN.py:137-142; shifu_compat divides a
    migrated SAGN config's LearningRate by K accordingly).  KNOWN
    deviation: the reference's local and global applies both use Adam
    (SAGN.py:107-108,158-159); adaptive state on diverged replicas has no
    sound averaging semantic, so this tier is plain SGD — TrainConfig
    validation enforces it and PARITY.md documents it.

    TPU-native formulation: replicas live as ONE stacked pytree with a
    leading shard axis sharded over `data` (each existing param axis keeps
    its own sharding, so TP rules compose); local updates are a vmap over
    that axis — zero communication, XLA runs them device-local — and the
    periodic sync is a mean over the stacked axis, for which XLA inserts
    the same ICI all-reduce a synchronous step would pay, just K times
    less often.  State in/out is a standard TrainState: replicas stack at
    epoch start and average back at epoch end (an epoch boundary is always
    a sync point), so eval/checkpoint/export see ordinary params.

    Signature matches make_epoch_scan_step, or make_device_epoch_step when
    `with_order` (the device-resident tier's shuffled block order).
    """
    from ..parallel.mesh import DATA_AXIS

    loss_fn = make_loss_fn(job)
    K = job.train.local_sgd_window
    lr = job.train.optimizer.learning_rate
    n_shards = int(mesh.shape.get(DATA_AXIS, 1)) if mesh is not None else 1

    # Param shardings must be read from CONCRETE arrays before tracing —
    # inside jit the leaves are tracers whose .sharding is unavailable, and
    # falling back to P('data', None, ...) would silently drop TP/model-axis
    # placements.  The jitted step is therefore built on first call, closed
    # over the shardings of the state actually passed in (init_state placed
    # it per the job's rules); `param_shardings` holds (stacked, original).
    param_shardings = []  # mutated once, at first call, before jit traces

    def leaf_shardings(leaf: jax.Array):
        sh = getattr(leaf, "sharding", None)
        if mesh is None or not isinstance(sh, NamedSharding):
            orig = None if mesh is None else NamedSharding(mesh, P())
            stk = (None if mesh is None
                   else NamedSharding(mesh, P(DATA_AXIS)))
            return stk, orig
        spec = tuple(sh.spec) + (None,) * (leaf.ndim - len(sh.spec))
        return NamedSharding(mesh, P(DATA_AXIS, *spec)), sh

    def constrain(tree, which: int):
        if mesh is None:
            return tree
        shardings = jax.tree_util.tree_unflatten(
            param_shardings[1], [s[which] for s in param_shardings[0]])
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, tree, shardings)

    def epoch_step(state: TrainState, blocks: Batch, order=None):
        nb, bs = blocks["features"].shape[:2]
        local_bs = bs // n_shards

        stacked = constrain(
            jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(p[None], (n_shards,) + p.shape),
                state.params),
            0)

        def shard_loss(params_i, feats, tgt, wgt, step):
            return loss_fn(params_i, state.apply_fn,
                           {"features": feats, "target": tgt, "weight": wgt},
                           step)

        # step maps per-shard (in_axes=0): (step, shard) -> a UNIQUE rng
        # fold value, so replicas draw distinct dropout masks each local
        # update instead of all sharing shard 0's pattern
        vgrad = jax.vmap(jax.value_and_grad(shard_loss),
                         in_axes=(0, 0, 0, 0, 0))

        def sync(params_p):
            return constrain(
                jax.tree_util.tree_map(
                    lambda p: jnp.broadcast_to(jnp.mean(p, axis=0)[None],
                                               p.shape), params_p),
                0)

        def body(carry, xs):
            params_p, acc, i = carry
            if with_order:
                xs = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, xs, axis=0, keepdims=False), blocks)
            # (B, ...) -> (shards, B/shards, ...): row-major leading split
            # matches the data-axis layout, so this is a local reshape
            resh = {k: v.reshape(n_shards, local_bs, *v.shape[1:])
                    for k, v in xs.items()}
            wgt = resh.get("weight")
            if wgt is None:  # elided all-ones weight wire
                wgt = jnp.ones((n_shards, local_bs, 1), jnp.float32)
            shard_steps = ((state.step + i) * n_shards
                           + jnp.arange(n_shards, dtype=jnp.int32))
            with jax.named_scope("fwd_bwd"):
                losses, grads = vgrad(params_p, resh["features"],
                                      resh["target"], wgt, shard_steps)
            with jax.named_scope("optimizer"):
                params_p = constrain(
                    jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                           params_p, grads),
                    0)
            params_p = jax.lax.cond((i + 1) % K == 0, sync,
                                    lambda pp: pp, params_p)
            return (params_p, acc + jnp.mean(losses), i + 1), None

        xs_in = jnp.asarray(order) if with_order else blocks
        (params_p, acc, _), _ = jax.lax.scan(
            body, (stacked, jnp.float32(0.0), jnp.int32(0)), xs_in)
        # epoch boundary = sync point: average back to one replica, restored
        # to the original per-param shardings
        params = constrain(
            jax.tree_util.tree_map(lambda p: jnp.mean(p, axis=0), params_p),
            1)
        new_state = state.replace(params=params, step=state.step + nb)
        return new_state, acc

    donate_argnums = (0,) if donate else ()
    cache: dict[str, Any] = {"fn": None, "shardings": None}

    def call(state: TrainState, blocks: Batch, order=None):
        # the traced sharding constraints close over the CURRENT leaves'
        # concrete placements; keyed on them so a state whose leaves carry
        # different shardings (e.g. after a cross-topology restore) rebuilds
        # the jit instead of silently applying stale first-call constraints
        flat, treedef = jax.tree_util.tree_flatten(state.params)
        observed = [getattr(l, "sharding", None) for l in flat]
        if cache["fn"] is None or observed != cache["shardings"]:
            param_shardings.clear()
            param_shardings.append([leaf_shardings(l) for l in flat])
            param_shardings.append(treedef)
            cache["shardings"] = observed
            from ..obs.introspect import instrument_jit
            if with_order:
                cache["fn"] = instrument_jit(epoch_step,
                                             "local_sgd_epoch_step",
                                             donate_argnums=donate_argnums)
            else:
                cache["fn"] = instrument_jit(
                    lambda st, bl: epoch_step(st, bl),
                    "local_sgd_epoch_step",
                    donate_argnums=donate_argnums)
        if with_order:
            return cache["fn"](state, blocks, order)
        return cache["fn"](state, blocks)

    return call


def _make_forward(job: JobConfig) -> Callable[[TrainState, jax.Array], jax.Array]:
    """The eval forward pass over one batch's features, wire decode
    included: what both eval programs below compile."""
    decode = make_wire_decode(job)

    def forward(state: TrainState, feats: jax.Array) -> jax.Array:
        if decode is not None:
            feats = decode(feats)
        logits = state.apply_fn({"params": state.params}, feats)
        return jax.nn.sigmoid(logits)

    return forward


def make_eval_step(job: JobConfig) -> Callable[[TrainState, Batch], jax.Array]:
    """Scores (sigmoid probabilities) for a batch — the eval forward pass.
    Accepts int8 wire batches (same decode as training, so eval sees the
    exact features the train step saw)."""
    forward = _make_forward(job)

    def score(state: TrainState, batch: Batch) -> jax.Array:
        return forward(state, batch["features"])

    from ..obs.introspect import instrument_jit
    return instrument_jit(score, "eval_step")


#: the resident eval program hands its scores back in slices of about this
#: many bytes, so that the host accumulates one while the next is in flight
EVAL_SLICE_BYTES = 4 << 20


def make_resident_eval_step(job: JobConfig):
    """The eval forward pass over a valid set that lives on the device as
    `(nvb, B, F)` feature blocks (train/loop.py's resident eval tier): ONE
    program maps `make_eval_step`'s forward over the leading axis and hands
    back head 0's scores dense, `(nvb, B)` cut along the leading axis into
    a tuple of slices of about EVAL_SLICE_BYTES — one dispatch and a few
    dense D2H transfers an epoch, where the streamed path pays a batch's
    preparation, H2D and a `(B, 1)` fetch nvb times."""
    forward = _make_forward(job)

    def score_blocks(state: TrainState, features: jax.Array) -> tuple:
        out = jax.lax.map(lambda f: forward(state, f)[:, 0], features)
        per = max(1, EVAL_SLICE_BYTES // (out.shape[1] * out.dtype.itemsize))
        return tuple(out[i:i + per] for i in range(0, out.shape[0], per))

    from ..obs.introspect import instrument_jit
    return instrument_jit(score_blocks, "resident_eval_step")


def make_forward_fn(job: JobConfig,
                    apply_fn=None) -> Callable[[Any, jax.Array], jax.Array]:
    """Pure (params, features) -> scores fn for export/AOT paths.

    With apply_fn=None the model is rebuilt WITHOUT a mesh, which is what
    export wants: a training apply_fn may embed sequence-parallel shard_map
    collectives (ModelSpec.attention_impl), and the scoring artifact must be
    a single-host graph."""
    if apply_fn is None:
        from ..models.registry import build_model
        apply_fn = build_model(job.model, job.schema).apply

    def forward(params, features: jax.Array) -> jax.Array:
        return jax.nn.sigmoid(apply_fn({"params": params}, features))

    return forward
