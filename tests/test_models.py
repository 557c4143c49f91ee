"""Model ladder tests (BASELINE configs 2-5): every rung initializes, runs a
jitted forward with the right shapes, and learns past chance on synthetic
data wired through the same Shifu schema/data contracts as the MLP."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from shifu_tpu.config import DataConfig, JobConfig, ModelSpec, OptimizerConfig, TrainConfig
from shifu_tpu.data import reader, synthetic
from shifu_tpu.data.pipeline import TabularDataset
from shifu_tpu.models import build_model, field_layout
from shifu_tpu.train import train


def _job(schema, model_type, epochs=4, **model_kw):
    defaults = dict(hidden_nodes=(16, 16), activations=("relu", "relu"),
                    compute_dtype="float32", embedding_dim=8)
    defaults.update(model_kw)
    return JobConfig(
        schema=schema,
        data=DataConfig(batch_size=128),
        model=ModelSpec(model_type=model_type, **defaults),
        train=TrainConfig(epochs=epochs,
                          optimizer=OptimizerConfig(name="adam", learning_rate=5e-3)),
    ).validate()


def _datasets(schema, n=4096, seed=7):
    rows = synthetic.make_rows(n, schema, seed=seed, noise=0.3)
    cols = reader.project_columns(rows, schema)
    full = TabularDataset(cols["features"], cols["target"], cols["weight"])
    cut = int(n * 0.9)
    return full.take(np.arange(cut)), full.take(np.arange(cut, n))


@pytest.mark.parametrize("model_type", ["wide_deep", "deepfm"])
def test_embedding_models_learn(model_type):
    schema = synthetic.make_schema(num_features=12, num_categorical=4, vocab_size=20)
    job = _job(schema, model_type)
    train_ds, valid_ds = _datasets(schema)
    result = train(job, train_ds, valid_ds, console=lambda s: None)
    assert result.history[-1].valid_auc > 0.62, result.history[-1]


@pytest.mark.slow
def test_ft_transformer_learns():
    schema = synthetic.make_schema(num_features=10, num_categorical=2, vocab_size=12)
    job = _job(schema, "ft_transformer", num_layers=2, num_attention_heads=4,
               token_dim=32)
    train_ds, valid_ds = _datasets(schema, n=3072)
    result = train(job, train_ds, valid_ds, console=lambda s: None)
    assert result.history[-1].valid_auc > 0.6, result.history[-1]


def test_multitask_learns_both_heads():
    schema = synthetic.make_schema(num_features=10, num_targets=2)
    job = _job(schema, "multitask", epochs=10, num_heads=2,
               head_names=("shifu_output_0", "shifu_output_1"))
    train_ds, valid_ds = _datasets(schema)
    assert train_ds.target.shape[1] == 2
    result = train(job, train_ds, valid_ds, console=lambda s: None)
    # evaluate() reports head 0; check head 1 directly
    from shifu_tpu.train import make_eval_step
    eval_step = make_eval_step(job)
    from shifu_tpu.ops import auc
    scores = np.asarray(jax.device_get(eval_step(result.state, {
        "features": jnp.asarray(valid_ds.features),
        "target": jnp.asarray(valid_ds.target),
        "weight": jnp.asarray(valid_ds.weight)})))
    assert auc(scores[:, 0], valid_ds.target[:, 0]) > 0.6
    assert auc(scores[:, 1], valid_ds.target[:, 1]) > 0.6


def test_all_ladder_models_forward_shapes():
    schema = synthetic.make_schema(num_features=8, num_categorical=3, vocab_size=10)
    feats = jnp.asarray(synthetic.make_rows(16, schema, seed=1)[:, 1:9])
    for model_type in ("mlp", "wide_deep", "deepfm", "ft_transformer",
                       "moe_mlp"):
        spec = ModelSpec(model_type=model_type, hidden_nodes=(8,),
                         activations=("relu",), embedding_dim=4,
                         token_dim=16, num_attention_heads=4, num_layers=1,
                         compute_dtype="float32")
        model = build_model(spec, schema)
        variables = model.init(jax.random.PRNGKey(0), feats)
        out = jax.jit(lambda v, x: model.apply(v, x))(variables, feats)
        assert out.shape == (16, 1), model_type
        assert out.dtype == jnp.float32


def test_field_layout_positions():
    schema = synthetic.make_schema(num_features=6, num_categorical=2, vocab_size=9)
    layout = field_layout(schema)
    assert layout.num_numeric == 4
    assert layout.num_categorical == 2
    assert layout.vocab_sizes == (9, 9)
    # categorical are the LAST features in make_schema's layout
    assert layout.categorical_positions == (4, 5)


def test_deepfm_embedding_sharded_on_mesh(eight_devices):
    """DeepFM trains with its embedding tables sharded over the model axis —
    the high-cardinality scale-out design (SURVEY.md section 7.3 item 3)."""
    from jax.sharding import PartitionSpec as P
    from shifu_tpu.config import MeshConfig
    from shifu_tpu.parallel import make_mesh, shard_batch
    from shifu_tpu.parallel.sharding import DEFAULT_RULES, place_params
    from shifu_tpu.train import init_state, make_train_step

    schema = synthetic.make_schema(num_features=8, num_categorical=4, vocab_size=64)
    job = _job(schema, "deepfm")
    mesh = make_mesh(MeshConfig(data=4, model=2), devices=eight_devices)

    state = init_state(job, 8, mesh)
    state = state.replace(params=place_params(
        jax.device_get(state.params), mesh, DEFAULT_RULES))
    # embedding tables actually sharded on model axis
    emb = state.params["cat_embedding"]["embedding"]
    assert emb.sharding.spec[0] == "model"

    rows = synthetic.make_rows(256, schema, seed=2)
    cols = reader.project_columns(rows, schema)
    batch = shard_batch(cols, mesh)
    step = make_train_step(job, mesh, donate=False)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # update preserved the sharding
    assert new_state.params["cat_embedding"]["embedding"].sharding.spec[0] == "model"


@pytest.mark.slow
def test_remat_matches_unremat_gradients():
    """ModelSpec.remat recomputes block activations in the backward pass;
    forward and gradients must be identical to the stored-activation model
    (both per-block and stacked/pipelined trunks)."""
    schema = synthetic.make_schema(num_features=7, num_categorical=2,
                                   vocab_size=16)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (8, schema.feature_count)).astype(np.float32))

    for stages in (1, 2):
        spec = ModelSpec(model_type="ft_transformer", hidden_nodes=(8,),
                         activations=("relu",), token_dim=8,
                         num_attention_heads=2, num_layers=2,
                         pipeline_stages=stages, compute_dtype="float32")
        base = build_model(spec, schema)
        variables = base.init(jax.random.PRNGKey(0), x)
        import dataclasses
        rem = build_model(dataclasses.replace(spec, remat=True), schema)

        def loss(model):
            return lambda p: jnp.sum(model.apply({"params": p}, x) ** 2)

        l0, g0 = jax.value_and_grad(loss(base))(variables["params"])
        l1, g1 = jax.value_and_grad(loss(rem))(variables["params"])
        assert float(l0) == pytest.approx(float(l1), rel=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


def test_remat_with_dropout_initializes():
    """remat must keep `train` static: dropout's `deterministic=not train`
    is a Python branch and must not see a tracer under jax.checkpoint."""
    schema = synthetic.make_schema(num_features=7, num_categorical=2,
                                   vocab_size=16)
    spec = ModelSpec(model_type="ft_transformer", hidden_nodes=(8,),
                     activations=("relu",), token_dim=8,
                     num_attention_heads=2, num_layers=2, dropout_rate=0.1,
                     remat=True, compute_dtype="float32")
    model = build_model(spec, schema)
    x = jnp.zeros((4, schema.feature_count), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(variables, x)  # train=False: deterministic
    assert out.shape == (4, 1)


def test_shifu_remat_string_values():
    from shifu_tpu.utils.xmlconfig import parse_bool
    assert parse_bool("true") and parse_bool("1") and parse_bool(True)
    assert not parse_bool("false") and not parse_bool("0")
    assert not parse_bool("no") and not parse_bool(False)


def test_moe_mlp_learns():
    schema = synthetic.make_schema(num_features=10)
    job = _job(schema, "moe_mlp", epochs=6, num_experts=4)
    train_ds, valid_ds = _datasets(schema)
    result = train(job, train_ds, valid_ds, console=lambda s: None)
    assert result.history[-1].valid_auc > 0.62, result.history[-1]


def _table_model(model_type, vocab=50, **spec_kw):
    """A small table model at bfloat16 compute over float32 parameters,
    its variables and a batch whose categorical cells hold ids."""
    schema = synthetic.make_schema(num_features=12, num_categorical=4,
                                   vocab_size=vocab)
    x = np.random.default_rng(3).standard_normal((16, 12)).astype(np.float32)
    x[:, 8:] = np.random.default_rng(4).integers(0, vocab, (16, 4))
    x = jnp.asarray(x)
    kw = dict(hidden_nodes=(8,), activations=("relu",), embedding_dim=16,
              token_dim=8, num_attention_heads=2, num_layers=1,
              param_dtype="float32", compute_dtype="bfloat16")
    kw.update(spec_kw)
    model = build_model(ModelSpec(model_type=model_type, **kw), schema)
    return model, model.init(jax.random.PRNGKey(0), x), x


@pytest.mark.parametrize("one_hot", [False, True],
                         ids=["gather", "one_hot_forced"])
@pytest.mark.parametrize("model_type",
                         ["deepfm", "wide_deep", "ft_transformer"])
def test_lookup_from_param_matches_cast_then_gather(model_type, one_hot,
                                                    monkeypatch):
    """The lookup gathers float32 rows and casts the rows it got (or, where
    the one-hot strategy serves - forced here, a TPU with a small
    vocabulary in life - multiplies by the cast tables); the logits are
    bit-identical to the formula it replaced, kept frozen here: cast the
    whole table to the compute dtype, then gather (for the paired tables of
    DeepFM / Wide&Deep: cast both, concatenate along dim, gather once,
    split)."""
    from shifu_tpu.ops import pallas_embedding as pe

    model, variables, x = _table_model(model_type)
    if one_hot:
        monkeypatch.setattr(pe, "_onehot_ok", lambda v, n: True)
    got = model.apply(variables, x)

    seen = []

    def cast_then_gather(tables, ids, dtype):
        tables = list(tables)
        seen.append([t.shape for t in tables])
        assert all(t.dtype == jnp.float32 for t in tables)  # the parameters
        field = jnp.arange(ids.shape[1])[None, :]
        fused = jnp.concatenate([t.astype(dtype) for t in tables],
                                axis=-1)[field, ids]
        ends = np.cumsum([t.shape[-1] for t in tables])[:-1]
        return jnp.split(fused, ends, axis=-1)

    monkeypatch.setattr(pe, "lookup_rows", cast_then_gather)
    frozen = model.apply(variables, x)
    assert len(seen) == 1 and len(seen[0]) == (
        1 if model_type == "ft_transformer" else 2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(frozen))


def _walk(jaxpr, scope=""):
    """(equation, its name stack, nested one level deeper per enclosing
    loop or call) for every equation of `jaxpr` and of the jaxprs inside
    its equations' parameters."""
    for eqn in jaxpr.eqns:
        stack = scope + "/" + str(eqn.source_info.name_stack)
        yield eqn, stack
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, stack)


def test_train_step_makes_nothing_table_sized_but_the_gradient(monkeypatch):
    """One train step of a DeepFM whose vocabulary is over the one-hot cap,
    traced as the TPU runs it: under `fwd_bwd`, the only equations whose
    result is as large as a table are, for each of the two tables, the loop
    over its fields that stacks their gradients - and inside that loop the
    only results as large as one field's table are its zero fill and the
    scatter-add into it.  No cast, concatenation, slice, reshape or copy of
    a table: the forward gathers rows from the parameter itself."""
    from shifu_tpu.ops import pallas_embedding as pe
    from shifu_tpu.train.loop import init_state
    from shifu_tpu.train.step import (_fwd_bwd_and_update,
                                      make_apply_gradients, make_loss_fn)

    monkeypatch.setattr(pe, "on_tpu", lambda: True)
    nc, vocab, dim, batch = 4, 4096, 10, 64
    assert vocab > pe._ONEHOT_MAX_VOCAB
    schema = synthetic.make_schema(num_features=12, num_categorical=nc,
                                   vocab_size=vocab)
    job = JobConfig(
        schema=schema, data=DataConfig(batch_size=batch),
        model=ModelSpec(model_type="deepfm", hidden_nodes=(16,),
                        activations=("relu",), embedding_dim=dim,
                        param_dtype="float32", compute_dtype="bfloat16"),
        train=TrainConfig(epochs=1, optimizer=OptimizerConfig(
            name="adadelta", learning_rate=1.0))).validate()
    state = init_state(job, 12)
    xs = {"features": jnp.zeros((batch, 12), jnp.float32),
          "target": jnp.zeros((batch, 1), jnp.float32),
          "weight": jnp.ones((batch, 1), jnp.float32)}
    loss_fn, apply_grads = make_loss_fn(job), make_apply_gradients(job)
    jaxpr = jax.make_jaxpr(
        lambda st, x: _fwd_bwd_and_update(loss_fn, apply_grads, st, x)[:2])(
        state, xs)

    def size(eqn):
        return max((int(np.prod(v.aval.shape)) for v in eqn.outvars
                    if hasattr(v.aval, "shape")), default=0)

    small_table, field_table = nc * vocab * 1, vocab * 1
    fwd_bwd = [(e, s) for e, s in _walk(jaxpr.jaxpr) if "fwd_bwd" in s]
    assert any(e.primitive.name == "gather" for e, _ in fwd_bwd)
    loops = [e for e, _ in fwd_bwd if e.primitive.name == "scan"]
    in_loops = {id(e) for loop in loops
                for e, _ in _walk(loop.params["jaxpr"].jaxpr)}
    table_sized = [e for e, _ in fwd_bwd
                   if size(e) >= small_table and id(e) not in in_loops]
    assert sorted(e.primitive.name for e in table_sized) == ["scan", "scan"]
    assert sorted(tuple(e.outvars[0].aval.shape) for e in table_sized) == [
        (nc, vocab, 1), (nc, vocab, dim)]
    assert all(e.outvars[0].aval.dtype == jnp.float32 for e in table_sized)
    for loop in table_sized:
        inner = [e.primitive.name
                 for e, _ in _walk(loop.params["jaxpr"].jaxpr)
                 if size(e) >= field_table]
        assert sorted(inner) == ["broadcast_in_dim", "scatter-add"], inner
    # and the optimizer does read table-sized gradients: the walk sees them
    assert any(size(e) >= small_table for e, s in _walk(jaxpr.jaxpr)
               if "optimizer" in s)
