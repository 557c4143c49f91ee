"""Pallas flash-attention kernel tests (interpret mode on the CPU backend,
same gating pattern as tests/test_pallas_embedding.py): the blockwise
streaming-softmax forward and the two-kernel flash backward must match the
XLA reference `mha` exactly in math — including unaligned sequence lengths
that exercise the padding/masking paths."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from shifu_tpu.ops.attention import mha
from shifu_tpu.ops.pallas_attention import flash_attention


def _qkv(b=2, h=2, s=64, d=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, h, s, d)), dtype=dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("s", [8, 31, 64, 130])
def test_flash_forward_matches_mha(s):
    """Aligned and unaligned sequence lengths, multi-block when s > block."""
    q, k, v = _qkv(s=s, seed=s)
    out = flash_attention(q, k, v, use_pallas=True, block_q=32, block_k=32)
    want = mha(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_flash_forward_bf16():
    q, k, v = _qkv(s=96, d=32, seed=9, dtype=jnp.bfloat16)
    out = np.asarray(
        flash_attention(q, k, v, use_pallas=True, block_q=32, block_k=32),
        dtype=np.float32)
    want = np.asarray(mha(q, k, v), dtype=np.float32)
    np.testing.assert_allclose(out, want, rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("s", [16, 31, 96])
def test_flash_gradients_match_mha(s):
    """The flash backward kernels (dq / dk+dv) against jax.grad of mha."""
    q, k, v = _qkv(b=1, h=2, s=s, d=8, seed=100 + s)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, use_pallas=True, block_q=32, block_k=32)
        return jnp.sum(jnp.sin(o))  # non-trivial cotangent

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(mha(q, k, v)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=2e-4, atol=2e-5,
            err_msg=f"d{name} mismatch")


def test_flash_under_jit_and_vmap_composition():
    q, k, v = _qkv(s=40, seed=3)
    f = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, use_pallas=True, block_q=32, block_k=32))
    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               np.asarray(mha(q, k, v)),
                               rtol=2e-5, atol=2e-6)
    # vmap over an extra leading axis: the interpret-mode pallas_call +
    # custom_vjp pair must batch, not just jit
    Q = jnp.stack([q, q * 0.5])
    K = jnp.stack([k, k])
    V = jnp.stack([v, v * 2.0])
    vf = jax.vmap(lambda q, k, v: flash_attention(
        q, k, v, use_pallas=True, block_q=32, block_k=32))
    vref = jax.vmap(lambda q, k, v: mha(q, k, v))
    np.testing.assert_allclose(np.asarray(vf(Q, K, V)),
                               np.asarray(vref(Q, K, V)),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("bq,bk", [(96, 64), (64, 96), (32, 48)])
def test_flash_mismatched_block_sizes(bq, bk):
    """Block sizes that do not divide each other: padding must go to a
    common multiple or key blocks / output rows silently go missing."""
    q, k, v = _qkv(s=96, seed=77)
    out = flash_attention(q, k, v, use_pallas=True, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(mha(q, k, v)),
                               rtol=2e-5, atol=2e-6)


def test_pallas_env_zero_means_off(monkeypatch):
    """SHIFU_TPU_PALLAS=0 must disable, not enable, the kernels."""
    from shifu_tpu.ops.pallas_common import pallas_opt_in
    for val, want in (("0", False), ("", False), ("false", False),
                      ("1", True), ("tpu", True)):
        monkeypatch.setenv("SHIFU_TPU_PALLAS", val)
        assert pallas_opt_in() is want, (val, want)
    monkeypatch.delenv("SHIFU_TPU_PALLAS")
    assert pallas_opt_in() is False


def test_flash_gated_off_routes_to_mha(monkeypatch):
    """Without the opt-in env (and use_pallas unset) the public entry point
    must route to the XLA path."""
    monkeypatch.delenv("SHIFU_TPU_PALLAS", raising=False)
    q, k, v = _qkv(s=12)
    np.testing.assert_allclose(np.asarray(flash_attention(q, k, v)),
                               np.asarray(mha(q, k, v)), rtol=1e-6, atol=1e-7)


def test_ft_transformer_flash_impl_matches_local(monkeypatch):
    """attention_impl="flash" wires through the model registry and produces
    the same forward as "local" at identical params."""
    monkeypatch.delenv("SHIFU_TPU_PALLAS", raising=False)
    from shifu_tpu.config import ModelSpec
    from shifu_tpu.data import synthetic
    from shifu_tpu.models.registry import build_model

    schema = synthetic.make_schema(num_features=7, num_categorical=2,
                                   vocab_size=16)
    feats = synthetic.make_rows(16, schema, seed=2)
    from shifu_tpu.data import reader
    batch = reader.project_columns(feats, schema)
    x = jnp.asarray(batch["features"])

    outs = {}
    for impl in ("local", "flash"):
        spec = ModelSpec(model_type="ft_transformer", hidden_nodes=(8,),
                         activations=("relu",), token_dim=8,
                         num_attention_heads=2, num_layers=1,
                         attention_impl=impl, compute_dtype="float32")
        model = build_model(spec, schema)
        variables = model.init(jax.random.PRNGKey(0), x)
        outs[impl] = np.asarray(model.apply(variables, x))
    # local path: flash falls back to mha unless opted in -> exact equality
    np.testing.assert_allclose(outs["flash"], outs["local"],
                               rtol=1e-6, atol=1e-7)


@pytest.mark.slow
def test_ft_transformer_flash_forced_kernel(monkeypatch):
    """With the kernel forced on (interpret mode on CPU), training-style
    forward+grad through the FT-Transformer stays finite and close to the
    XLA path."""
    monkeypatch.setenv("SHIFU_TPU_PALLAS", "1")
    from shifu_tpu.config import ModelSpec
    from shifu_tpu.data import reader, synthetic
    from shifu_tpu.models.registry import build_model

    schema = synthetic.make_schema(num_features=6, num_categorical=0)
    rows = synthetic.make_rows(8, schema, seed=4)
    x = jnp.asarray(reader.project_columns(rows, schema)["features"])
    spec = ModelSpec(model_type="ft_transformer", hidden_nodes=(8,),
                     activations=("relu",), token_dim=8,
                     num_attention_heads=2, num_layers=1,
                     attention_impl="flash", compute_dtype="float32")
    model = build_model(spec, schema)
    variables = model.init(jax.random.PRNGKey(1), x)

    def loss(params):
        out = model.apply({"params": params}, x)
        return jnp.mean(out ** 2)

    val, grads = jax.value_and_grad(loss)(variables["params"])
    assert np.isfinite(float(val))
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in leaves)


# -- batch-in-lanes small-token attention kernel ----------------------------

from shifu_tpu.ops.pallas_small_attention import (  # noqa: E402
    _run_bwd, _run_fwd, small_attention_applicable, small_token_attention)


@pytest.mark.parametrize("s,d,h", [(31, 8, 8), (16, 8, 2), (33, 4, 4),
                                   (64, 16, 1), (7, 2, 3)])
def test_small_attention_forward_matches_mha(s, d, h):
    """The lanes kernel (interpret mode) == mha for small tokens/head dims,
    including non-sublane-aligned S (masked pad rows) and non-128 B."""
    q, k, v = _qkv(b=37, h=h, s=s, d=d, seed=1)
    out = _run_fwd(q, k, v, d ** -0.5, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(mha(q, k, v)),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("s,d,h", [(31, 8, 8), (12, 4, 2)])
def test_small_attention_gradients_match_mha(s, d, h):
    q, k, v = _qkv(b=19, h=h, s=s, d=d, seed=2)
    g = _qkv(b=19, h=h, s=s, d=d, seed=3)[0]
    dq, dk, dv = _run_bwd(q, k, v, g, d ** -0.5, True)
    ref = jax.grad(lambda a, b, c: jnp.sum(mha(a, b, c) * g),
                   argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip((dq, dk, dv), ref, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_small_attention_custom_vjp_roundtrip():
    """The public wrapper with use_pallas=True (interpret on CPU) is
    differentiable end to end and matches mha's value+grad."""
    q, k, v = _qkv(b=8, h=2, s=9, d=4, seed=4)

    def loss(fn):
        return jax.value_and_grad(
            lambda a: jnp.sum(fn(a, k, v) ** 2))(q)

    val_k, grad_k = loss(lambda a, b, c: small_token_attention(
        a, b, c, use_pallas=True))
    val_r, grad_r = loss(mha)
    np.testing.assert_allclose(float(val_k), float(val_r), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad_k), np.asarray(grad_r),
                               rtol=2e-4, atol=2e-5)


def test_small_attention_gating(monkeypatch):
    """Auto mode: CPU routes to mha (interpret would be orders slower);
    shapes outside the small-token envelope are not applicable; the env
    escape hatch disables."""
    assert small_attention_applicable(31, 8)
    assert not small_attention_applicable(128, 8)   # S too large
    assert not small_attention_applicable(31, 64)   # D too large
    monkeypatch.setenv("SHIFU_TPU_NO_SMALL_ATTENTION", "1")
    assert not small_attention_applicable(31, 8)
    monkeypatch.delenv("SHIFU_TPU_NO_SMALL_ATTENTION")
    # on the CPU backend auto never selects the kernel
    q, k, v = _qkv(b=4, h=2, s=8, d=4, seed=5)
    np.testing.assert_allclose(np.asarray(small_token_attention(q, k, v)),
                               np.asarray(mha(q, k, v)), rtol=1e-6)


@pytest.mark.slow
def test_flash_wide_token_axis_gradients():
    """Token counts far beyond the block size (513 = a wide table's 512
    feature tokens + CLS, not block-aligned): the multi-block grid must
    agree with the reference in forward and gradient."""
    q, k, v = _qkv(b=1, s=513, seed=5)
    fl = lambda a, b, c: flash_attention(a, b, c, use_pallas=True,
                                         block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(fl(q, k, v)),
                               np.asarray(mha(q, k, v)), rtol=2e-4, atol=2e-5)
    g_fl = jax.grad(lambda a: jnp.sum(fl(a, k, v) ** 2))(q)
    g_rf = jax.grad(lambda a: jnp.sum(mha(a, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_rf),
                               rtol=2e-3, atol=2e-4)
