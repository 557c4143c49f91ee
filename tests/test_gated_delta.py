"""The delta rule's chunk systems (ops/gated_delta.py `unit_lower_inverse`):
`(I + A)^-1` by blocks of float32 products, against the triangular solve it
replaced, which lives on here as the frozen reference.

The systems are built as `_delta_row` builds `A` (`A_ij = beta_i exp(G_i -
G_j) (k_i . k_j)` below the diagonal, d = 128, beta in U(0.3, 1), a decay of
up to 0.05 a position), from keys `k_i = unit(c b + (1 - c) n_i)` that share
a direction `b`: independent keys, `c` = 0.5, `c` = 0.9, and one key
repeated with beta 1 and no decay (`A` all ones below the diagonal).  Hot
tokens repeat, so correlated keys are the traffic; they are also where a
product form goes wrong: the nilpotent series over the whole chunk holds
powers of `A` whose entries near 1e17 have to cancel, and the last test
holds it to fail, so that nobody "simplifies" the blocks away.

Tolerances: the worst entry of `T` within 2e-5 of the solve's (`T` is cast
to bfloat16, whose step is 4e-3, right after), the solve itself within 1e-6
of a float64 solve; gradients within 1e-5 of the norm of the solve's.  A CPU
computes a float32 product in float32: what the chip's matmul precision does
to these is read on the chip (PERF.md section 6, PR 33).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tpu.ops.gated_delta import gated_delta_rule, unit_lower_inverse

KEYS = {"independent": 0.0, "common_0.5": 0.5, "common_0.9": 0.9,
        "repeated": None}
LIMIT = 2e-5


def _system(keys: str, c: int, d: int = 128, seed: int = 0) -> np.ndarray:
    """A (c, c), float64, strictly lower."""
    rng = np.random.default_rng(seed)
    mix = KEYS[keys]
    if mix is None:
        k = np.tile(rng.normal(size=(1, d)), (c, 1))
        beta, g = np.ones(c), np.zeros(c)
    else:
        shared = rng.normal(size=(1, d))
        shared /= np.linalg.norm(shared)
        own = rng.normal(size=(c, d))
        own /= np.linalg.norm(own, axis=-1, keepdims=True)
        k = mix * shared + (1 - mix) * own
        beta = rng.uniform(0.3, 1.0, c)
        g = -rng.uniform(0.0, 0.05, c)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    cs = np.cumsum(g)
    a = (k @ k.T) * np.exp(cs[:, None] - cs[None, :]) * beta[:, None]
    return np.tril(a, -1)


def _solve(a: jax.Array) -> jax.Array:
    """What `_delta_row` did until PR 33: the frozen reference."""
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    return jax.lax.linalg.triangular_solve(
        eye + a, jnp.broadcast_to(eye, a.shape), left_side=True, lower=True,
        unit_diagonal=True)


def _rule_inputs(t: int, seed: int):
    """q, k, v, log alpha, beta of two rows of `t` positions, float32."""
    rng = np.random.default_rng(seed)
    b, hk, hv, dk, dv = 2, 2, 4, 8, 6
    q, k = (jnp.asarray(rng.normal(size=(b, t, hk, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(b, t, hv, dv)), jnp.float32)
    g = -jnp.asarray(rng.uniform(size=(b, t, hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(size=(b, t, hv)), jnp.float32)
    return q, k, v, g, beta


def _worst(x, y) -> float:
    return float(np.max(np.abs(np.asarray(x, np.float64)
                               - np.asarray(y, np.float64))))


@pytest.mark.parametrize("c", [5, 8, 13, 16, 64])
@pytest.mark.parametrize("keys", list(KEYS))
def test_the_inverse_by_blocks_is_the_solves(keys, c):
    a64 = _system(keys, c)
    a = jnp.asarray(a64, jnp.float32)
    want = _solve(a)
    assert want.dtype == jnp.float32
    assert _worst(want, np.linalg.inv(np.eye(c) + a64)) < 1e-6
    got = jax.jit(unit_lower_inverse)(a)
    assert got.shape == (c, c) and got.dtype == jnp.float32
    assert _worst(got, want) < LIMIT
    assert _worst(jnp.triu(got, 1), jnp.zeros((c, c))) == 0.0


def test_the_series_over_the_whole_chunk_fails_the_limit():
    """`(I - A)(I + A^2)(I + A^4)...(I + A^32)` is exact in exact arithmetic
    (`A^64 = 0`) and useless in float32 once the keys share a direction."""
    a = jnp.asarray(_system("common_0.9", 64), jnp.float32)
    eye = jnp.eye(64, dtype=jnp.float32)
    series, power = eye - a, a
    for _ in range(5):
        power = power @ power
        series = series @ (eye + power)
    want = _solve(a)
    assert _worst(series, want) > 1e3 * LIMIT
    assert _worst(unit_lower_inverse(a), want) < LIMIT


@pytest.mark.parametrize("c", [5, 13, 64])
@pytest.mark.parametrize("keys", ["independent", "common_0.9"])
def test_the_closed_form_backward_is_the_solves_gradient(keys, c):
    """`dA = -strict_lower(T^T dT T^T)` against autodiff through the solve,
    over a batch of systems as the row function hands them over."""
    rng = np.random.default_rng(c)
    a = jnp.asarray(np.stack([_system(keys, c, seed=s) for s in range(3)]),
                    jnp.float32)
    probe = jnp.asarray(rng.normal(size=a.shape), jnp.float32)
    mask = jnp.tril(jnp.ones((c, c), jnp.float32), -1)

    def through(inverse):
        # `a` is masked where it is built, as in `_delta_row`
        return jax.jit(jax.grad(
            lambda x: jnp.sum(probe * inverse(x * mask))))(a)

    got, want = through(unit_lower_inverse), through(_solve)
    assert _worst(got, want) < 1e-5 * float(jnp.linalg.norm(want))
    assert _worst(jnp.triu(got), jnp.zeros_like(got)) == 0.0


def test_no_triangular_solve_is_left_in_the_rule():
    """The mechanism's "engaged" reading: every chunk of every `L` block
    takes the products, so the solve is in neither the forward nor the
    backward - not as a primitive, and not in the text lowered for the TPU
    (for a CPU the solve lowers to a LAPACK call of another name)."""
    step = jax.jit(jax.grad(
        lambda *args: jnp.sum(gated_delta_rule(*args, chunk=8)),
        argnums=tuple(range(5))))
    traced = step.trace(*_rule_inputs(24, seed=0))
    assert "triangular_solve" not in str(traced.jaxpr)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "dot_general" in text
    assert "triangular_solve" not in text


@pytest.mark.parametrize("block_rematerialized, runs", [(False, 1), (True, 2)])
def test_the_backward_keeps_the_inverse_and_does_not_rebuild_it(
        block_rematerialized, runs):
    """The row is rematerialized but for `T`: a gradient runs the ten
    products of a 64-chunk once on the way forward (twice where the block
    around the rule is rematerialized too, as models/block_stack.py
    rematerializes it) and the backward's two, never a third time."""
    rule = jax.checkpoint(gated_delta_rule) if block_rematerialized \
        else gated_delta_rule
    text = jax.jit(jax.grad(lambda *args: jnp.sum(rule(*args) ** 2),
                            argnums=tuple(range(5)))).lower(
        *_rule_inputs(128, seed=1)).as_text()
    exact = re.findall(r"dot_general.*precision = \[HIGHEST, HIGHEST\]", text)
    assert len(exact) == 10 * runs + 2
