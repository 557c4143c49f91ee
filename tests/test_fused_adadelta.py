"""The fused Adadelta apply (ops/pallas_adadelta.py, ISSUE 37), in interpret
mode on the CPU: one in-place kernel pass a large leaf must give what
`optax.adadelta` + `optax.apply_updates` give, to 2 ULP, over five steps;
the optimizer state keeps optax's structure; and the rule that decides
where it engages keeps optax everywhere else."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from shifu_tpu import obs
from shifu_tpu.config.schema import OptimizerConfig
from shifu_tpu.obs import introspect
from shifu_tpu.ops import pallas_common
from shifu_tpu.ops.pallas_adadelta import adadelta_apply
from shifu_tpu.train import optimizers
from shifu_tpu.train.train_state import TrainState

RHO, EPS = 0.95, 1e-8


def _close(got, want):
    """Within 1e-6 of each value, or 2 ULP of the leaf's largest: where
    p - lr u cancels, one rounding of the step is all the difference."""
    want = np.asarray(want)
    scale = float(np.max(np.abs(want))) if want.size else 0.0
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                               atol=2 * np.finfo(np.float32).eps * scale)


@pytest.mark.parametrize("shape", [
    (2, 40000, 10),     # stacked table, D=10: 3 lane blocks, the last partial
    (2, 40000, 1),      # first-order table, D=1: 2 lane blocks
    (1100, 1000),       # a (V, H) leaf: bands of whole rows
    (1000, 1100),       # held 1000-minor, so it goes in swapped
    (4, 300, 520),      # an expert leaf: whole matrices
    (4, 520, 300),      # held 520-minor
], ids=["table_d10", "table_d1", "dense_2d", "dense_2d_swapped",
        "experts_3d", "experts_3d_swapped"])
def test_kernel_matches_optax_over_five_steps(shape):
    rng = np.random.default_rng(sum(shape))
    p = jnp.asarray(rng.normal(size=shape), jnp.float32)
    tx = optax.adadelta(0.5, rho=RHO, eps=EPS)
    st = tx.init(p)
    q, e_g, e_x = p, jnp.zeros_like(p), jnp.zeros_like(p)
    for _ in range(5):
        g = jnp.asarray(rng.normal(size=shape), jnp.float32)
        u, st = tx.update(g, st, p)
        p = optax.apply_updates(p, u)
        q, e_g, e_x = adadelta_apply(q, g, e_g, e_x, 0.5, rho=RHO, eps=EPS,
                                     interpret=True)
    _close(q, p)
    _close(e_g, st[1].e_g)
    _close(e_x, st[1].e_x)


def _params():
    """Three leaves the fused apply takes (2**20 elements or more) and two
    it leaves to optax."""
    rng = np.random.default_rng(7)
    shapes = {"table": (2, 60000, 10), "dense": (1100, 1000),
              "experts": (4, 512, 520), "small": (30, 100), "bias": (100,)}
    return {k: jnp.asarray(rng.normal(size=s), jnp.float32)
            for k, s in shapes.items()}


@pytest.mark.parametrize("schedule,clip", [
    ("constant", 0.0), ("cosine", 0.0), ("constant", 50.0), ("cosine", 50.0),
])
def test_fused_apply_matches_the_optax_apply(schedule, clip):
    cfg = OptimizerConfig(name="adadelta", learning_rate=0.5,
                          schedule=schedule, decay_steps=4,
                          grad_clip_norm=clip)
    params = _params()
    tx = optimizers.build_optimizer(cfg)
    want = TrainState.create(apply_fn=None, params=params, tx=tx)
    got = want
    fused = jax.jit(optimizers.make_fused_adadelta_apply(cfg))
    rng = np.random.default_rng(11)
    for _ in range(5):
        grads = {k: jnp.asarray(rng.normal(size=v.shape), jnp.float32)
                 for k, v in params.items()}
        want = want.apply_gradients(grads)
        got = fused(got, grads)
    assert (jax.tree_util.tree_structure(got.opt_state)
            == jax.tree_util.tree_structure(want.opt_state))
    assert int(got.step) == int(want.step) == 5
    for a, b in zip(jax.tree_util.tree_leaves((got.params, got.opt_state)),
                    jax.tree_util.tree_leaves((want.params, want.opt_state))):
        assert a.shape == b.shape and a.dtype == b.dtype
        _close(a, b)


def test_fused_apply_notes_its_leaves_on_the_compile_event(tmp_path):
    """The traced program's `xla_compile` event carries the leaves and
    parameter bytes under the kernel; the gauges read the same."""
    obs.reset_for_tests()
    obs.configure(str(tmp_path))
    cfg = OptimizerConfig(name="adadelta", learning_rate=0.5)
    params = _params()
    st = TrainState.create(apply_fn=None, params=params,
                           tx=optimizers.build_optimizer(cfg))
    step = introspect.instrument_jit(
        optimizers.make_fused_adadelta_apply(cfg), "fused_probe")
    step(st, jax.tree_util.tree_map(jnp.ones_like, params))
    obs.flush()
    (rec,) = [r for r in obs.read_journal(str(tmp_path / "journal.jsonl"))
              if r["kind"] == "xla_compile" and r["fn"] == "fused_probe"]
    nbytes = 4 * (2 * 60000 * 10 + 1100 * 1000 + 4 * 512 * 520)
    assert rec["adadelta_fused_leaves"] == 3
    assert rec["adadelta_fused_bytes"] == nbytes
    reg = obs.default_registry()
    assert reg.gauge("adadelta_fused_leaves").value() == 3
    assert reg.gauge("adadelta_fused_bytes").value() == nbytes
    obs.reset_for_tests()


def _engages(monkeypatch, tpu: bool, mesh=None, **cfg) -> bool:
    monkeypatch.setattr(pallas_common.jax, "default_backend",
                        lambda: "tpu" if tpu else "cpu")
    return optimizers.fused_adadelta_engages(
        dataclasses.replace(OptimizerConfig(name="adadelta"), **cfg), mesh)


@pytest.mark.parametrize("case", [
    "engages", "off_tpu", "accumulate_steps", "multi_device_mesh",
    "one_device_mesh", "adam", "small_leaf", "rank_1", "bfloat16"])
def test_selection_rule(case, monkeypatch):
    """Where the fused apply takes over: a TPU, plain Adadelta, one device,
    and then float32 leaves of rank 2 or more and 2**20 elements or more.
    Everywhere else the optax apply stays."""
    from jax.sharding import Mesh

    big = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    if case == "engages":
        assert _engages(monkeypatch, True)
        assert optimizers.fused_leaf(big)
    elif case == "off_tpu":
        assert not _engages(monkeypatch, False)
    elif case == "accumulate_steps":
        assert not _engages(monkeypatch, True, accumulate_steps=2)
    elif case == "multi_device_mesh":
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        assert not _engages(monkeypatch, True, mesh=mesh)
    elif case == "one_device_mesh":
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        assert _engages(monkeypatch, True, mesh=mesh)
    elif case == "adam":
        assert not _engages(monkeypatch, True, name="adam")
    elif case == "small_leaf":
        assert not optimizers.fused_leaf(
            jax.ShapeDtypeStruct((1023, 1024), jnp.float32))
    elif case == "rank_1":
        assert not optimizers.fused_leaf(
            jax.ShapeDtypeStruct((1 << 21,), jnp.float32))
    elif case == "bfloat16":
        assert not optimizers.fused_leaf(
            jax.ShapeDtypeStruct((1024, 1024), jnp.bfloat16))


def test_no_large_leaf_keeps_the_optax_program():
    """A job whose leaves are all small (mlp30's largest is 100 x 100)
    traces exactly the optax apply: the same program text."""
    cfg = OptimizerConfig(name="adadelta", learning_rate=0.5)
    params = {"w": jnp.ones((100, 100)), "b": jnp.ones((100,))}
    st = TrainState.create(apply_fn=None, params=params,
                           tx=optimizers.build_optimizer(cfg))
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    def apply(s, g):    # the name the module takes, as the fused one's
        return s.apply_gradients(g)

    fused = optimizers.make_fused_adadelta_apply(cfg)
    text = lambda f: jax.jit(f).lower(st, grads).as_text()
    assert text(fused) == text(apply)
