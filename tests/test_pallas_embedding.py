"""Pallas embedding-lookup kernel tests (interpret mode on the CPU mesh;
on TPU the same kernel is opted into via SHIFU_TPU_PALLAS=1, which routes
models/embedding.CategoricalEmbed through it)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from shifu_tpu.ops.pallas_embedding import _xla_lookup, embedding_lookup


def _data(b=16, nc=5, vocab=32, dim=8, seed=0):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((nc, vocab, dim)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, vocab, (b, nc)), jnp.int32)
    return table, ids


def test_pallas_matches_xla_gather():
    table, ids = _data()
    out_pallas = embedding_lookup(table, ids, True)   # interpret mode on CPU
    out_xla = embedding_lookup(table, ids, False)
    np.testing.assert_allclose(np.asarray(out_pallas), np.asarray(out_xla))
    # and against a hand-rolled loop
    want = np.stack([[np.asarray(table)[f, int(ids[b, f])]
                      for f in range(table.shape[0])]
                     for b in range(ids.shape[0])])
    np.testing.assert_allclose(np.asarray(out_pallas), want)


def test_lookup_grad_is_scatter_add():
    table, ids = _data(b=8, nc=3, vocab=10, dim=4, seed=1)

    def loss(t):
        return jnp.sum(embedding_lookup(t, ids, True) * 2.0)

    g = jax.grad(loss)(table)
    # each (f, id) row accumulates 2.0 per occurrence
    counts = np.zeros((3, 10)); ids_np = np.asarray(ids)
    for b in range(8):
        for f in range(3):
            counts[f, ids_np[b, f]] += 1
    want = np.repeat(counts[:, :, None], 4, axis=2) * 2.0
    np.testing.assert_allclose(np.asarray(g), want)


def test_grad_matches_xla_path():
    table, ids = _data(b=8, nc=3, vocab=10, dim=4, seed=2)

    def loss_with(t, use_pallas):
        out = embedding_lookup(t, ids, use_pallas)
        return jnp.sum(jnp.sin(out))

    g_pallas = jax.grad(lambda t: loss_with(t, True))(table)
    g_plain = jax.grad(lambda t: jnp.sum(jnp.sin(_xla_lookup(t, ids))))(table)
    np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_plain),
                               rtol=1e-6, atol=1e-6)


def test_jit_compatible():
    table, ids = _data()
    f = jax.jit(lambda t, i: embedding_lookup(t, i, True))
    np.testing.assert_allclose(np.asarray(f(table, ids)),
                               np.asarray(_xla_lookup(table, ids)))


def test_onehot_lookup_matches_gather_exactly(monkeypatch):
    """The small-vocab MXU strategy (one_hot @ table) must be bit-identical
    to the XLA gather — forward rows AND the production backward branches —
    including the gather's exact out-of-range semantics (negative ids wrap,
    ids outside [-V, V) NaN-fill forward / drop in the gradient).  The auto
    path must never change numbers vs any other configuration."""
    from shifu_tpu.ops import pallas_embedding as pe

    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.standard_normal((4, 50, 16)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-60, 70, (64, 4)).astype(np.int32))  # dirty

    ref = np.asarray(pe._xla_lookup(table, ids))  # RAW ids: production path
    got = np.asarray(pe._onehot_lookup(table, ids))
    np.testing.assert_array_equal(np.isnan(got), np.isnan(ref))
    np.testing.assert_array_equal(np.nan_to_num(got), np.nan_to_num(ref))

    # bf16 table: still an exact row copy (single exact 1.0 in the one-hot)
    tb16 = table.astype(jnp.bfloat16)
    g16 = np.asarray(pe._onehot_lookup(tb16, ids).astype(jnp.float32))
    r16 = np.asarray(pe._xla_lookup(tb16, ids).astype(jnp.float32))
    np.testing.assert_array_equal(np.isnan(g16), np.isnan(r16))
    np.testing.assert_array_equal(np.nan_to_num(g16), np.nan_to_num(r16))

    # gradient parity through the PRODUCTION _bwd branches: force the
    # one-hot route (CPU backend would refuse) and compare to the scatter
    # route, dirty ids included (wrap + drop semantics must agree)
    g = jnp.asarray(rng.standard_normal((64, 4, 16)).astype(np.float32))
    carrier = jnp.zeros((0,), jnp.float32)
    monkeypatch.setattr(pe, "_onehot_ok", lambda v, n: True)
    onehot_grad, _ = pe._bwd(None, (ids, table.shape, carrier), g)
    monkeypatch.setattr(pe, "_onehot_ok", lambda v, n: False)
    scatter_grad, _ = pe._bwd(None, (ids, table.shape, carrier), g)
    np.testing.assert_allclose(np.asarray(onehot_grad),
                               np.asarray(scatter_grad),
                               rtol=1e-6, atol=1e-6)

    # explicit use_pallas=False keeps its contract (scatter grad, gather fwd)
    monkeypatch.setattr(pe, "_onehot_ok", lambda v, n: True)
    forced_grad, _ = pe._bwd(False, (ids, table.shape, carrier), g)
    np.testing.assert_allclose(np.asarray(forced_grad),
                               np.asarray(scatter_grad), rtol=1e-6, atol=1e-6)

    # budget predicate: vocab cap only — batch size no longer disqualifies
    # (oversized batches chunk to the byte budget instead)
    monkeypatch.undo()
    assert not pe._onehot_ok(pe._ONEHOT_MAX_VOCAB + 1, 10)
    assert pe._onehot_num_chunks(
        (pe._ONEHOT_MAX_BYTES // (2048 * 4)) + 1, 2048) == 2


def test_onehot_chunked_matches_unchunked(monkeypatch):
    """Past the per-chunk byte budget the one-hot strategy processes the
    batch in sequential chunks: forward bit-identical (rows are
    independent), gradient equal to the scatter reference within f32
    accumulation reassociation."""
    from shifu_tpu.ops import pallas_embedding as pe

    rng = np.random.default_rng(5)
    table = jnp.asarray(rng.standard_normal((3, 40, 8)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-50, 60, (101, 3)).astype(np.int32))
    # shrink the budget so this small batch needs ~4 chunks (incl. padding)
    monkeypatch.setattr(pe, "_ONEHOT_MAX_BYTES", 101 * 3 * 40)
    assert pe._onehot_num_chunks(ids.size, 40) > 1
    got = np.asarray(pe._onehot_lookup(table, ids))
    monkeypatch.setattr(pe, "_ONEHOT_MAX_BYTES", 1 << 30)
    want = np.asarray(pe._onehot_lookup(table, ids))
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    np.testing.assert_array_equal(np.nan_to_num(got), np.nan_to_num(want))

    g = jnp.asarray(rng.standard_normal((101, 3, 8)).astype(np.float32))
    monkeypatch.setattr(pe, "_ONEHOT_MAX_BYTES", 101 * 3 * 40)
    chunked = np.asarray(pe._onehot_grad(ids, table.shape, g))
    ref = np.asarray(pe._scatter_grad(ids, table.shape, g))
    np.testing.assert_allclose(chunked, ref, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("table_shape", [(4, 37, 8), (20, 37, 8), (3, 41, 1)])
def test_segment_grad_matches_scatter_grad(table_shape):
    """The TPU gather-path gradient (a segment reduction a field, the
    fields walked by one loop whatever their count) equals the scatter-add
    reference for every id class: in-range, duplicate, negative-wrapping
    [-V, 0), and dropped outside [-V, V) - an id >= V must DROP, not land
    in the next field's table, and an id < -V must drop, not shift into
    the previous field's."""
    from shifu_tpu.ops import pallas_embedding as pe

    nc, v, d = table_shape
    rng = np.random.default_rng(11)
    # dense duplicates plus every boundary class
    ids = rng.integers(-2 * v - 6, 2 * v + 16, (257, nc)).astype(np.int32)
    ids[0, :3] = [0, v - 1, -1]             # wrap boundaries
    ids[1, :3] = [-v, v, v + 3]             # last wrap, then dropped
    ids[2, :3] = [-v - 1, 2 * v + 15, -2 * v - 6]   # all dropped
    ids[3] = ids[4] = 5                     # duplicates
    g = rng.standard_normal((257, nc, d)).astype(np.float32)
    got = pe._segment_grad(jnp.asarray(ids), table_shape, jnp.asarray(g))
    assert got.shape == table_shape and got.dtype == jnp.float32
    want = np.asarray(pe._scatter_grad(jnp.asarray(ids), table_shape,
                                       jnp.asarray(g)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


def test_segment_grad_is_one_loop_at_any_width():
    """The program does not grow with the schema's width: the walk over
    the fields is one `scan` around one scatter-add, and nothing in it is
    as large as the stacked table but the scan's own result."""
    from shifu_tpu.ops import pallas_embedding as pe

    def eqns(nc):
        jaxpr = jax.make_jaxpr(
            lambda i, g: pe._segment_grad(i, (nc, 64, 4), g))(
            jnp.zeros((8, nc), jnp.int32), jnp.zeros((8, nc, 4)))
        return [e.primitive.name for e in jaxpr.jaxpr.eqns]

    assert eqns(3) == eqns(50)
    assert eqns(50).count("scan") == 1
    assert "scatter-add" not in eqns(50)     # it is inside the loop


def _force_branch(monkeypatch, branch):
    """Steer `_bwd` without a chip: 'scatter' is the CPU's own branch,
    'segment' the TPU's for a vocabulary over the one-hot cap (`on_tpu`
    forced; the gathers and reductions themselves run anywhere)."""
    from shifu_tpu.ops import pallas_embedding as pe

    if branch == "segment":
        monkeypatch.setattr(pe, "on_tpu", lambda: True)
        monkeypatch.setenv("SHIFU_TPU_ONEHOT_EMBED_MAX_VOCAB", "0")


_BRANCHES = ["scatter", "segment"]
_DIMS = [5, 1]      # a table of rows, and one of scalars (its own gather)


@pytest.mark.parametrize("dim", _DIMS)
@pytest.mark.parametrize("branch", _BRANCHES)
def test_lookup_rows_matches_cast_then_gather(branch, dim, monkeypatch):
    """`lookup_rows` gathers from the float32 table and casts the rows:
    bit-identical to a lookup in the cast table, dirty ids included."""
    from shifu_tpu.ops import pallas_embedding as pe

    _force_branch(monkeypatch, branch)
    rng = np.random.default_rng(21)
    table = jnp.asarray(rng.standard_normal((3, 40, dim)), jnp.float32)
    ids = jnp.asarray(rng.integers(-50, 60, (33, 3)), jnp.int32)
    got, = pe.lookup_rows([table], ids, jnp.bfloat16)
    # the row gather, spelled out: the formula `lookup_rows` replaced
    want = jnp.take_along_axis(table.astype(jnp.bfloat16)[None],
                               ids[:, :, None, None], axis=2)[:, :, 0, :]
    assert got.dtype == jnp.bfloat16
    g32, w32 = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    np.testing.assert_array_equal(np.isnan(g32), np.isnan(w32))
    np.testing.assert_array_equal(np.nan_to_num(g32), np.nan_to_num(w32))


@pytest.mark.parametrize("dim", _DIMS)
@pytest.mark.parametrize("branch", _BRANCHES)
def test_lookup_rows_grad_is_float32_at_the_rows(branch, dim, monkeypatch):
    """The gradient with respect to the float32 table is the float32
    scatter-add of the rows' cotangents (`_scatter_grad`), comes back
    float32 and has not been through the compute dtype: two cotangents a
    bfloat16 holds each, 1 and 2**-9, land on one row, and their sum,
    which bfloat16 cannot hold, is there to the bit.  Duplicates sum, a
    negative id wraps once, an id outside [-V, V) drops."""
    from shifu_tpu.ops import pallas_embedding as pe

    _force_branch(monkeypatch, branch)
    nc, v, d = 3, 40, dim
    rng = np.random.default_rng(22)
    table = jnp.asarray(rng.standard_normal((nc, v, d)), jnp.float32)
    ids = rng.integers(8, v, (64, nc)).astype(np.int32)   # row 7 kept free
    ids[0] = ids[1] = [7, 7, 7]          # duplicates: the unrepresentable sum
    ids[2] = [-1, -v, 39]                # wraps to 39, 0, and 39 itself
    ids[3] = [v, -v - 1, 1000]           # all dropped
    w = rng.standard_normal((64, nc, d)).astype(np.float32)
    w = np.array(jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32))
    w[0], w[1] = 1.0, 2.0 ** -9
    ids, w = jnp.asarray(ids), jnp.asarray(w)

    def loss(t):
        out, = pe.lookup_rows([t], ids, jnp.bfloat16)
        # the forward NaN-fills rows of dropped ids: keep them out of the sum
        return jnp.sum(jnp.where(jnp.isnan(out), 0, out).astype(jnp.float32)
                       * w)

    g = jax.grad(loss)(table)
    assert g.dtype == jnp.float32 and g.shape == table.shape
    np.testing.assert_array_equal(
        np.asarray(g[:, 7]), np.full((nc, d), 1.0 + 2.0 ** -9, np.float32))
    want = pe._scatter_grad(ids, table.shape, w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # the dropped row of ids contributed nowhere, the wrapped one did
    clean = ids.at[3].set(0)
    w_clean = w.at[3].set(0.0)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(pe._scatter_grad(clean, table.shape,
                                                   w_clean)),
        rtol=1e-6, atol=1e-6)


def test_lookup_rows_joins_the_tables_only_for_the_one_hot_strategy(monkeypatch):
    """Several tables over the same ids: where the one-hot strategy serves
    (forced here; a TPU with a small vocabulary), one product a field over
    the tables cast and joined along dim; everywhere else a gather from
    each parameter.  The rows are the same to the bit either way."""
    from shifu_tpu.ops import pallas_embedding as pe

    rng = np.random.default_rng(23)
    tables = [jnp.asarray(rng.standard_normal((3, 40, d)), jnp.float32)
              for d in (6, 1)]
    ids = jnp.asarray(rng.integers(-50, 60, (33, 3)), jnp.int32)

    def run():
        seen = []
        real = pe.embedding_lookup
        monkeypatch.setattr(pe, "embedding_lookup",
                            lambda t, i: seen.append((t.shape, t.dtype))
                            or real(t, i))
        outs = pe.lookup_rows(tables, ids, jnp.bfloat16)
        monkeypatch.setattr(pe, "embedding_lookup", real)
        return seen, [np.asarray(o.astype(jnp.float32)) for o in outs]

    seen, apart = run()
    assert seen == [((3, 40, 6), jnp.float32), ((3, 40, 1), jnp.float32)]
    monkeypatch.setattr(pe, "_onehot_ok", lambda v, n: True)
    seen, joined = run()
    assert seen == [((3, 40, 7), jnp.bfloat16)]
    for a, j, t in zip(apart, joined, tables):
        assert a.shape == j.shape == (33, 3, t.shape[-1])
        np.testing.assert_array_equal(np.isnan(a), np.isnan(j))
        np.testing.assert_array_equal(np.nan_to_num(a), np.nan_to_num(j))
