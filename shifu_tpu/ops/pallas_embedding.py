"""Pallas TPU kernel: stacked-table embedding lookup.

Hot-op kernel for the embedding models (Wide&Deep / DeepFM / FT-Transformer):
gathers `table[f, ids[b, f], :]` for every (batch row b, categorical field f)
— the op CategoricalEmbed otherwise issues as an XLA gather
(models/embedding.py).

Kernel design (TPU-first): the ids are a *scalar-prefetch* argument, so each
grid step's BlockSpec index_map reads the id and the Pallas pipeline DMAs
exactly the selected table row HBM->VMEM, double-buffered across grid steps —
the table itself never materializes in VMEM.  Per grid step the kernel body
is a pure VMEM copy of one (1, 1, D) row.  The backward pass picks one of
three gradient strategies under a custom VJP: small-vocab tables become
one-hot matmuls on the MXU (`_onehot_grad`), large-vocab tables on TPU use
1-D segment reductions, a field at a time (`_segment_grad`), and CPU (or an
explicit use_pallas=False reference request) keeps the plain XLA `.at[].add`
scatter (`_scatter_grad`).  Models whose compute dtype is not their
parameters' come in through `lookup_rows`: float32 rows are gathered and
cast, and the gradient is summed in float32 at the rows.

CPU/testing: falls back to `interpret=True` off-TPU so the same code path is
unit-tested on the virtual CPU mesh.  On real TPU hardware the kernel is
validated exact vs the XLA gather for 128-lane-aligned embedding dims; for
smaller dims (tabular default D=16) Mosaic's DMA tiling cannot slice a
single row, so the XLA gather serves (see _forward).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .pallas_common import on_tpu, pallas_opt_in, pltpu


# Scalar-prefetch ids live in SMEM (1 MiB on a v5e).  A 2-D (rows, Nc)
# int32 array pads its minor dim to 128 lanes there — 2,048 rows of 6
# fields already overflow (Mosaic: "Ran out of memory in memory space
# smem") — so ids ride FLAT (row-major, 4 B per id) and a call covers at
# most this many of them; longer id lists run as sequential calls.
_SMEM_IDS_PER_CALL = 64 * 1024


def _row_chunks(n_rows: int, nc: int, rows_per_step: int):
    """[(start, stop)] row ranges whose flat ids fit the SMEM budget, each
    a multiple of `rows_per_step` (which already divides n_rows)."""
    per_call = max(rows_per_step,
                   (_SMEM_IDS_PER_CALL // nc) // rows_per_step
                   * rows_per_step)
    return [(lo, min(lo + per_call, n_rows))
            for lo in range(0, n_rows, per_call)]


def _make_lookup_kernel(nc: int, rows_per_step: int):
    def kernel(ids_ref, table_ref, out_ref, sem_ref):
        # table_ref lives in HBM (ANY); for each (row, field) this grid step
        # covers, DMA the selected (dim,) table row straight into the VMEM
        # output block.  All nc*rows copies are started before any wait, so
        # the DMAs overlap.
        i = pl.program_id(0)
        dmas = []
        for r in range(rows_per_step):
            b_idx = i * rows_per_step + r
            for f in range(nc):
                dma = pltpu.make_async_copy(
                    table_ref.at[f, ids_ref[b_idx * nc + f]],
                    out_ref.at[r, f],
                    sem_ref.at[r, f],
                )
                dma.start()
                dmas.append(dma)
        for dma in dmas:
            dma.wait()
    return kernel


def _pallas_lookup(table: jax.Array, ids: jax.Array,
                   interpret: bool, rows_per_step: int = 8) -> jax.Array:
    nc, vocab, dim = table.shape
    b = ids.shape[0]
    while b % rows_per_step != 0:
        rows_per_step //= 2  # degrade gracefully for odd batch sizes

    def call(ids_c):
        n = ids_c.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,           # flat ids (SMEM)
            grid=(n // rows_per_step,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),      # table stays in HBM
            ],
            out_specs=pl.BlockSpec(
                (rows_per_step, nc, dim),
                lambda i, ids_ref: (i, 0, 0),
            ),
            scratch_shapes=[pltpu.SemaphoreType.DMA((rows_per_step, nc))],
        )
        return pl.pallas_call(
            _make_lookup_kernel(nc, rows_per_step),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, nc, dim), table.dtype),
            interpret=interpret,
        )(ids_c.reshape(-1), table)

    parts = [call(ids[lo:hi]) for lo, hi in _row_chunks(b, nc, rows_per_step)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _xla_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """The XLA gather, `table[f, ids[b, f], :]` for every (b, f): ids in
    [-V, 0) wrap, anything outside [-V, V) NaN-fills.

    A table whose rows are one value wide (DeepFM's and Wide&Deep's
    first-order tables) is gathered as scalars, the unit axis indexed and
    not sliced: same values, same device time.  The row gather asks XLA:TPU
    for such a table re-tiled ({1,0,2:T(8,128)} where the runtime keeps it
    {1,2,0:T(1,128)}); inside an epoch's scan that is a second copy of the
    table and of both its optimizer slots for the length of the loop, and
    Criteo-size state has no room for it (PERF.md, PR 28)."""
    field = jnp.arange(table.shape[0], dtype=ids.dtype)[None, :]
    if table.shape[-1] == 1:
        return table.at[field, ids, 0].get(
            mode="fill", fill_value=jnp.nan)[..., None]
    return table.at[field, ids].get(mode="fill", fill_value=jnp.nan)


# One-hot-matmul strategy caps: the one-hot operand's size (and the matmul's
# FLOPs) scale with the vocab, so the MXU formulation wins only for small
# vocabs — measured 2.3x the XLA gather at V=1000/D=16/B=32k on a v5e chip
# (15.1M -> 35.1M lookup-rows/s); gathers win as V grows past a few thousand.
# The byte bound sizes BATCH CHUNKS: the materialized (B, Nc, V) one-hot
# operand (f32 in the backward) must not eat HBM on wide/many-field
# batches, so oversized batches process in sequential chunks that each fit
# the budget — the MXU formulation keeps its ~5x win at ANY batch size
# instead of falling off a cliff to the gather past a threshold.
_ONEHOT_MAX_VOCAB = 2048
_ONEHOT_MAX_BYTES = 1 << 30  # f32 one-hot operand budget PER CHUNK


def _onehot_ok(vocab: int, n_lookups: int) -> bool:
    import os
    del n_lookups  # any size: the strategy chunks the batch to the budget
    try:
        cap = int(os.environ.get("SHIFU_TPU_ONEHOT_EMBED_MAX_VOCAB",
                                 _ONEHOT_MAX_VOCAB))
    except ValueError:
        cap = _ONEHOT_MAX_VOCAB
    return on_tpu() and 0 < vocab <= cap


def _onehot_num_chunks(n_lookups: int, vocab: int) -> int:
    return max(1, -(-(n_lookups * vocab * 4) // _ONEHOT_MAX_BYTES))


def _onehot_lookup_chunk(table: jax.Array, ids: jax.Array) -> jax.Array:
    # MXU formulation of the lookup: rows select via one_hot @ table.  The
    # one-hot row has a single exact 1.0, so the result is bit-identical to
    # the gather — including its out-of-range semantics (take_along_axis:
    # ids in [-V, 0) wrap, anything outside [-V, V) NaN-fills), so dirty
    # ids behave identically whichever strategy the auto path picks.
    v = table.shape[1]
    wrapped = jnp.where(ids < 0, ids + v, ids)
    valid = (ids >= -v) & (ids < v)
    oh = jax.nn.one_hot(wrapped, v, dtype=table.dtype)  # invalid -> zero row
    out = jnp.einsum("bfv,fvd->bfd", oh, table)
    return jnp.where(valid[..., None], out,
                     jnp.asarray(jnp.nan, out.dtype))


def _onehot_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    ids = ids.astype(jnp.int32)
    b = ids.shape[0]
    k = _onehot_num_chunks(ids.size, table.shape[1])
    if k <= 1 or b < 2 * k:
        return _onehot_lookup_chunk(table, ids)
    # sequential batch chunks (lax.map = scan): per-row independent, so the
    # chunked result is bit-identical to the unchunked one
    chunk = -(-b // k)
    k = -(-b // chunk)
    idsp = jnp.pad(ids, ((0, chunk * k - b), (0, 0)))  # pad ids are valid 0s
    out = jax.lax.map(lambda c: _onehot_lookup_chunk(table, c),
                      idsp.reshape(k, chunk, *ids.shape[1:]))
    return out.reshape(chunk * k, *out.shape[2:])[:b]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def embedding_lookup(table: jax.Array, ids: jax.Array,
                     use_pallas: Optional[bool] = None) -> jax.Array:
    """(Nc, V, D) table, (B, Nc) int32 ids -> (B, Nc, D).

    use_pallas: None = auto (SHIFU_TPU_PALLAS=1 opt-in); True selects the
    kernel (interpret mode off-TPU); False forces the XLA gather.  On real
    TPU hardware the kernel additionally requires D % 128 == 0 (Mosaic DMA
    tiling cannot slice a narrower HBM row) — other D fall back to the XLA
    gather even with use_pallas=True.
    """
    return _forward(table, ids, use_pallas)


def _forward(table, ids, use_pallas):
    auto = use_pallas is None
    if auto:
        use_pallas = pallas_opt_in()  # SHIFU_TPU_PALLAS=1
    if use_pallas:
        if on_tpu() and table.shape[-1] % 128 != 0:
            # Mosaic DMA tiling: an HBM row slice needs its minor dim
            # 128-lane aligned, so sub-128 embedding dims (the tabular
            # default D=16) cannot use the per-row DMA design — the XLA
            # gather serves those; the kernel pays off for D >= 128 tables.
            return _xla_lookup(table, ids.astype(jnp.int32))
        return _pallas_lookup(table, ids.astype(jnp.int32),
                              interpret=not on_tpu())
    # one-hot strategy only on the AUTO path: an explicit use_pallas=False
    # keeps its documented "force the XLA gather" contract (the reference
    # implementation validation/benchmarks compare against)
    if auto and _onehot_ok(table.shape[1], ids.size):
        return _onehot_lookup(table, ids)
    return _xla_lookup(table, ids.astype(jnp.int32))


def _fwd(table, ids, use_pallas):
    # dtype carried via an empty array (dtypes aren't valid residual leaves)
    dtype_carrier = jnp.zeros((0,), table.dtype)
    return _forward(table, ids, use_pallas), (ids, table.shape, dtype_carrier)


def _onehot_grad_chunk(ids: jax.Array, v: int, g: jax.Array) -> jax.Array:
    wrapped = jnp.where(ids < 0, ids + v, ids)
    oh = jax.nn.one_hot(wrapped, v, dtype=jnp.float32)
    return jnp.einsum("bfv,bfd->fvd", oh, g.astype(jnp.float32))


def _onehot_grad(ids: jax.Array, table_shape, g: jax.Array) -> jax.Array:
    """MXU gradient: dtable = one_hot(ids)^T @ g — the scatter-add expressed
    as a matmul.  Matches the scatter path's out-of-range handling exactly:
    ids in [-V, 0) wrap (`.at[].add` wraps negatives), anything outside
    [-V, V) contributes nothing (one_hot's zero row == the scatter drop).
    Oversized batches accumulate over sequential chunks (float32 partial
    sums — same dtype the single einsum accumulates in; chunking only
    reassociates the additions)."""
    v = table_shape[1]
    ids = ids.astype(jnp.int32)
    b = ids.shape[0]
    k = _onehot_num_chunks(ids.size, v)
    if k <= 1 or b < 2 * k:
        return _onehot_grad_chunk(ids, v, g)
    chunk = -(-b // k)
    k = -(-b // chunk)
    pad = chunk * k - b
    idsp = jnp.pad(ids, ((0, pad), (0, 0)))
    gp = jnp.pad(g, ((0, pad),) + ((0, 0),) * (g.ndim - 1))  # zero grads

    def body(acc, xs):
        ids_c, g_c = xs
        return acc + _onehot_grad_chunk(ids_c, v, g_c), None

    out, _ = jax.lax.scan(
        body, jnp.zeros(table_shape, jnp.float32),
        (idsp.reshape(k, chunk, *ids.shape[1:]),
         gp.reshape(k, chunk, *g.shape[1:])))
    return out


def _scatter_grad(ids: jax.Array, table_shape, g: jax.Array) -> jax.Array:
    """Scatter-add gradient into the stacked table: for each field f, add
    g[b, f, :] at row ids[b, f] (JAX semantics: negative ids wrap like the
    forward gather; out-of-bounds-high updates drop, matching the forward's
    NaN-fill poisoning)."""
    nc = table_shape[0]
    grad = jnp.zeros(table_shape, dtype=jnp.float32)
    field_idx = jnp.broadcast_to(
        jnp.arange(nc, dtype=ids.dtype)[None, :], ids.shape)
    return grad.at[field_idx.reshape(-1), ids.reshape(-1)].add(
        g.reshape(-1, table_shape[-1]).astype(jnp.float32))


def _segment_grad(ids: jax.Array, table_shape, g: jax.Array) -> jax.Array:
    """The same gradient as `_scatter_grad`, accumulated a field at a time:
    a 1-D segment reduction of that field's rows into its own (V, D)
    table, the fields walked by `lax.map` and stacked.  Id semantics match
    the scatter exactly: negative ids wrap once, anything outside [-V, V)
    contributes nothing (segment_sum drops out-of-range segment ids the way
    `.at[].add` drops out-of-bounds updates).

    Why a field at a time, on a v5e (PERF.md, PR 28): XLA:TPU lowers every
    scatter-add, this one included, onto a (D, rows) buffer, rows minor.
    One reduction over all fields at once - flat ids `field * V + id`, or a
    2-D scatter into the stacked table, which the compiler flattens the
    same way - therefore hands back (D, Nc*V), and turning that into the
    stacked (Nc, V, D) table the optimizer reads is a re-tiling of the
    whole gradient by slice updates: 75 ms a step at Criteo size against
    21 ms for the reduction.  A field's (D, V) result is already the
    field's slice of the stacked table as the device lays it out, so
    stacking is a plain copy.  The walk is one loop whatever the field
    count (an unrolled one grew the program, and its compile, with the
    schema's width), and no combined id exists to overflow int32."""
    _, v, _ = table_shape
    ids = ids.astype(jnp.int32)
    wrapped = jnp.where(ids < 0, ids + v, ids)
    gf = jnp.swapaxes(g.astype(jnp.float32), 0, 1)      # (Nc, B, D)
    return jax.lax.map(
        lambda x: jax.ops.segment_sum(x[0], x[1], num_segments=v),
        (gf, wrapped.T))


def _bwd(use_pallas, res, g):
    ids, table_shape, dtype_carrier = res
    table_dtype = dtype_carrier.dtype
    auto = use_pallas is None
    if auto and _onehot_ok(table_shape[1], ids.size):
        return _onehot_grad(ids, table_shape, g).astype(table_dtype), None
    if auto and on_tpu():
        # CPU scatters fine; TPU does not.  Auto-path only: an explicit
        # use_pallas=False keeps the reference scatter-add for A/Bs.
        return _segment_grad(ids, table_shape, g).astype(table_dtype), None
    return _scatter_grad(ids, table_shape, g).astype(table_dtype), None


embedding_lookup.defvjp(_fwd, _bwd)


def lookup_rows(tables, ids: jax.Array, dtype) -> list[jax.Array]:
    """`embedding_lookup(table, ids)` in `dtype` for each of `tables` (same
    fields and vocabulary, the same ids), for a model whose compute dtype
    is not its parameters'.

    The rows are gathered from each parameter itself and cast once
    gathered: a cast commutes with a gather, so the values are those of a
    lookup in the cast table, while nothing table-sized is made on the way
    in, and on the way back the gradient is summed at the rows in the
    parameter's dtype and never rounded to `dtype`.

    The small-vocab one-hot strategy is the exception, by the predicate
    `embedding_lookup` itself goes by: it multiplies by the table on the
    MXU, wants it in `dtype` (exact row copies only then), and serves any
    number of tables with one product a field - so there the tables are
    cast and joined along dim first, which costs less than the batch's
    rows do (such a table is smaller than they are), and split after."""
    tables = list(tables)
    if not pallas_opt_in() and _onehot_ok(tables[0].shape[1], ids.size):
        joined = embedding_lookup(
            jnp.concatenate([t.astype(dtype) for t in tables], axis=-1), ids)
        ends = np.cumsum([t.shape[-1] for t in tables])
        return jnp.split(joined, ends[:-1], axis=-1)
    return [embedding_lookup(t, ids).astype(dtype) for t in tables]


# ---------------------------------------------------------------------------
# Fused rows-touched optimizer update (the sparse embedding engine's update
# leg, shifu_tpu/embed/).  One pass per touched row: DMA the row (params +
# adadelta moment slots) HBM->VMEM, apply the update rule on the VPU, and
# DMA the new row back to the SAME HBM buffer (input_output_aliases) — no
# XLA scatter, no dense (Nc, V, D) read-modify-write.  Ids arrive as a
# scalar-prefetch argument like the lookup kernel's; out-of-range ids (the
# dedup sentinel V pads unique-id batches to a static size) are skipped via
# pl.when, matching the XLA reference's scatter-drop semantics.
#
# CALLER CONTRACT: within one call the in-range ids must be unique per
# field (the engine's host-side dedup guarantees it) — duplicate rows in
# one grid step would race their write-back DMAs, where the XLA `.at[].set`
# reference resolves duplicates deterministically.

# TF 1.4 Adadelta constants — must match train/optimizers.py and
# train/sparse_embed.py (the exactness pins compare all three).
_ADADELTA_RHO = 0.95
_ADADELTA_EPS = 1e-8


def rows_update_reference(table: jax.Array, slots, g_rows: jax.Array,
                          ids: jax.Array, rule: str, lr):
    """XLA reference rows-touched update (the exactness baseline the fused
    kernel is pinned against, and the fallback where it cannot run).

    table (Nc, V, D); slots = (accu, delta_accu) f32 for adadelta, () for
    sgd; g_rows (U, Nc, D) per-touched-row gradients; ids (U, Nc) int32.
    Out-of-range ids (>= V — the dedup sentinel) gather clamped garbage and
    their scatter DROPS (JAX default), so padded entries are no-ops.
    Returns (new_table, new_slots); math in f32, stored in table.dtype.
    """
    nc, v, _d = table.shape
    lr = jnp.asarray(lr, jnp.float32)
    if rule == "sgd":
        parts = []
        for f in range(nc):
            i_f = ids[:, f]
            p_rows = table[f, i_f].astype(jnp.float32)
            g_f = g_rows[:, f].astype(jnp.float32)
            parts.append(table[f].at[i_f].set(
                (p_rows - lr * g_f).astype(table.dtype)))
        return jnp.stack(parts), slots
    accu, delta = slots
    t_parts, a_parts, d_parts = [], [], []
    for f in range(nc):
        i_f = ids[:, f]
        g_f = g_rows[:, f].astype(jnp.float32)
        a_rows = accu[f, i_f]
        d_rows = delta[f, i_f]
        p_rows = table[f, i_f].astype(jnp.float32)
        new_a = _ADADELTA_RHO * a_rows + (1.0 - _ADADELTA_RHO) * g_f * g_f
        upd = g_f * jnp.sqrt(d_rows + _ADADELTA_EPS) \
            / jnp.sqrt(new_a + _ADADELTA_EPS)
        new_d = _ADADELTA_RHO * d_rows + (1.0 - _ADADELTA_RHO) * upd * upd
        t_parts.append(table[f].at[i_f].set(
            (p_rows - lr * upd).astype(table.dtype)))
        a_parts.append(accu[f].at[i_f].set(new_a))
        d_parts.append(delta[f].at[i_f].set(new_d))
    return jnp.stack(t_parts), (jnp.stack(a_parts), jnp.stack(d_parts))


def _make_rows_update_kernel(nc: int, rows_per_step: int, vocab: int,
                             rule: str):
    """Kernel body: per (row, field) — predicated on the id being in range
    — DMA the touched table row (and moment rows) into VMEM scratch, apply
    the rule as one vector op over the whole scratch block, and DMA the new
    rows back.  Reads all complete before any write starts (the id sets of
    one grid step are unique, and grid steps run sequentially)."""
    adadelta = rule == "adadelta"

    def kernel(ids_ref, lr_ref, g_ref, *refs):
        if adadelta:
            (table_ref, accu_ref, delta_ref, table_out, accu_out, delta_out,
             t_s, a_s, d_s, sems) = refs
            ins = ((table_ref, t_s, 0), (accu_ref, a_s, 1),
                   (delta_ref, d_s, 2))
            outs = ((t_s, table_out, 0), (a_s, accu_out, 1),
                    (d_s, delta_out, 2))
        else:
            table_ref, table_out, t_s, sems = refs
            ins = ((table_ref, t_s, 0),)
            outs = ((t_s, table_out, 0),)
        i = pl.program_id(0)

        def each_valid(fn):
            for r in range(rows_per_step):
                u = i * rows_per_step + r
                for f in range(nc):
                    idx = ids_ref[u * nc + f]
                    valid = (idx >= 0) & (idx < vocab)

                    @pl.when(valid)
                    def _(r=r, f=f, idx=idx):
                        fn(r, f, idx)

        # phase 1: start every in-range row read (params + slots)
        each_valid(lambda r, f, idx: [
            pltpu.make_async_copy(src.at[f, idx], dst.at[r, f],
                                  sems.at[k, r, f]).start()
            for src, dst, k in ins])
        # phase 2: drain the reads (same descriptors — wait on the sems)
        each_valid(lambda r, f, idx: [
            pltpu.make_async_copy(src.at[f, idx], dst.at[r, f],
                                  sems.at[k, r, f]).wait()
            for src, dst, k in ins])
        # phase 3: the rule, one vector op over the scratch block (invalid
        # slots compute garbage that phase 4 never writes back)
        lr = lr_ref[0, 0]
        g = g_ref[...].astype(jnp.float32)
        if adadelta:
            a = a_s[...]
            d = d_s[...]
            new_a = _ADADELTA_RHO * a + (1.0 - _ADADELTA_RHO) * g * g
            upd = g * jnp.sqrt(d + _ADADELTA_EPS) \
                / jnp.sqrt(new_a + _ADADELTA_EPS)
            d_s[...] = _ADADELTA_RHO * d + (1.0 - _ADADELTA_RHO) * upd * upd
            a_s[...] = new_a
            t_s[...] = t_s[...] - lr * upd
        else:
            t_s[...] = t_s[...] - lr * g
        # phase 4/5: write the new rows back to the aliased HBM buffers
        each_valid(lambda r, f, idx: [
            pltpu.make_async_copy(src.at[r, f], dst.at[f, idx],
                                  sems.at[k, r, f]).start()
            for src, dst, k in outs])
        each_valid(lambda r, f, idx: [
            pltpu.make_async_copy(src.at[r, f], dst.at[f, idx],
                                  sems.at[k, r, f]).wait()
            for src, dst, k in outs])

    return kernel


def _pallas_rows_update(table, slots, g_rows, ids, rule, lr,
                        interpret: bool, rows_per_step: int = 8):
    nc, vocab, dim = table.shape
    u = ids.shape[0]
    while u % rows_per_step != 0:
        rows_per_step //= 2  # degrade gracefully for odd unique counts
    adadelta = rule == "adadelta"
    n_bufs = 3 if adadelta else 1
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    ids = ids.astype(jnp.int32)
    g_rows = g_rows.astype(jnp.float32)
    bufs = [table] + (list(slots) if adadelta else [])
    # alias table (+slots) inputs onto the outputs: the update is in-place,
    # so steady-state table traffic is touched-rows only.  Operand indices
    # count every pallas_call argument incl. the scalar-prefetch ids.
    aliases = {3 + k: k for k in range(n_bufs)}
    row_block = pl.BlockSpec((rows_per_step, nc, dim),
                             lambda i, ids_ref: (i, 0, 0))
    kernel = _make_rows_update_kernel(nc, rows_per_step, vocab, rule)
    # sequential calls over SMEM-sized id chunks; each updates the same
    # aliased buffers (in-range ids are unique across the WHOLE id list,
    # so chunks never touch the same row)
    for lo, hi in _row_chunks(u, nc, rows_per_step):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,               # flat ids (SMEM)
            grid=((hi - lo) // rows_per_step,),
            in_specs=[
                pl.BlockSpec((1, 1), lambda i, ids_ref: (0, 0),
                             memory_space=pltpu.SMEM),          # lr
                row_block,                                      # g_rows
            ] + [pl.BlockSpec(memory_space=pl.ANY)] * n_bufs,   # table, slots
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_bufs,
            scratch_shapes=[
                pltpu.VMEM((rows_per_step, nc, dim), jnp.float32)
            ] * n_bufs + [
                pltpu.SemaphoreType.DMA((n_bufs, rows_per_step, nc))],
        )
        bufs = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(b.shape, b.dtype) for b in bufs],
            input_output_aliases=aliases,
            interpret=interpret,
        )(ids[lo:hi].reshape(-1), lr_arr, g_rows[lo:hi], *bufs)
    if adadelta:
        return bufs[0], (bufs[1], bufs[2])
    return bufs[0], slots


def fused_update_available(dim: int) -> bool:
    """True where the fused rows-touched update kernel can actually run:
    any interpret context, or a TPU with a 128-lane-aligned embedding dim
    (the same Mosaic DMA constraint as the lookup kernel — a narrower HBM
    row cannot be sliced).  train/sparse_embed.py's auto gate keys off
    this."""
    return dim % 128 == 0 if on_tpu() else True


def fused_rows_update(table: jax.Array, slots, g_rows: jax.Array,
                      ids: jax.Array, rule: str, lr,
                      use_pallas: Optional[bool] = None):
    """Rows-touched optimizer update: gather touched rows + apply the
    Adadelta/SGD rule + scatter back, fused into one Pallas pass
    (interpret mode off-TPU).  Falls back to `rows_update_reference` when
    the kernel cannot run (unaligned D on a TPU, non-f32 table) or when
    use_pallas=False.  In-range ids must be unique per
    field within a call (see the kernel contract above); out-of-range ids
    (the dedup sentinel V) are skipped, matching the reference's
    scatter-drop.  use_pallas=None auto-selects: the kernel wherever
    `fused_update_available` holds AND the Pallas opt-in
    (SHIFU_TPU_PALLAS) is set off-TPU."""
    if rule not in ("sgd", "adadelta"):
        raise ValueError(f"fused_rows_update: unknown rule {rule!r}")
    if use_pallas is None:
        use_pallas = on_tpu() or pallas_opt_in()
    kernel_ok = (use_pallas and fused_update_available(table.shape[-1])
                 and table.dtype == jnp.float32)
    if not kernel_ok:
        return rows_update_reference(table, slots, g_rows, ids, rule, lr)
    return _pallas_rows_update(table, slots, g_rows, ids, rule, lr,
                               interpret=not on_tpu())
