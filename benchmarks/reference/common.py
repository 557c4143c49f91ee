"""What the plain references share: the initial weights re-derived from the
seed, the weighted-MSE loss, the dense Adadelta update, the precisions.

Nothing here imports `shifu_tpu`.  The program initialises its own weights
inside `train()` (flax `Module.init` from `PRNGKey(train.seed)`), so the
reference cannot be handed the same arrays without taking them from the
program; it derives them again, by the rule the configuration states:
glorot-uniform kernels (and biases, the reference trainer's quirk) drawn
with the key flax gives a parameter — `fold_in(PRNGKey(seed), first four
bytes of sha1(module path + the scope's parameter counter))`.  A test
holds that derivation to the program's, bit for bit, at a tiny size.
"""

from __future__ import annotations

import hashlib
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

# TF 1.4 AdadeltaOptimizer defaults; the configuration files state them
ADADELTA_RHO = 0.95
ADADELTA_EPS = 1e-8

#: `compute` names a precision of the reference's matrix products:
#: "float32" is the reference itself, "bfloat16" what the configurations
#: state, "float8" the control (the nearest precision below bfloat16)
_ROUND = {
    "float32": None,
    "bfloat16": jnp.bfloat16,
    "float8": jnp.float8_e4m3fn,
}


def rounder(compute: str) -> Callable[[jax.Array], jax.Array]:
    """Round an operand of a product to `compute` and bring it back to
    float32 (the accumulation stays float32 in every precision)."""
    dt = _ROUND[compute]
    if dt is None:
        return lambda x: x.astype(jnp.float32)
    return lambda x: x.astype(dt).astype(jnp.float32)


def param_key(seed: int, path: Sequence[str], counter: int) -> jax.Array:
    """The key flax hands the `counter`-th parameter (from 1) of the module
    at `path`, under `Module.init(PRNGKey(seed), ...)`."""
    m = hashlib.sha1()
    for x in (*path, counter):
        if isinstance(x, str):
            m.update(x.encode("utf-8"))
        else:
            m.update(x.to_bytes((x.bit_length() + 7) // 8, byteorder="big"))
    h = int.from_bytes(m.digest()[:4], byteorder="big")
    return jax.random.fold_in(jax.random.PRNGKey(seed), jnp.uint32(h))


_glorot = jax.nn.initializers.glorot_uniform()


def glorot(seed: int, path: Sequence[str], counter: int, shape) -> jax.Array:
    return _glorot(param_key(seed, path, counter), tuple(shape), jnp.float32)


def glorot_bias(seed: int, path: Sequence[str], counter: int,
                n: int) -> jax.Array:
    """TF's xavier on a rank-1 shape: fan_in = fan_out = n."""
    limit = jnp.sqrt(3.0 / n).astype(jnp.float32)
    return jax.random.uniform(param_key(seed, path, counter), (n,),
                              jnp.float32, minval=-limit, maxval=limit)


def dense_init(seed: int, path: Sequence[str], n_in: int, n_out: int) -> dict:
    """One `ShifuDense`: an `nn.Dense` auto-named Dense_0 under `path`."""
    p = (*path, "Dense_0")
    return {"Dense_0": {"kernel": glorot(seed, p, 1, (n_in, n_out)),
                        "bias": glorot_bias(seed, p, 2, n_out)}}


def dense(p: dict, x: jax.Array, rnd) -> jax.Array:
    d = p["Dense_0"]
    return rnd(x) @ rnd(d["kernel"]) + d["bias"]


def weighted_mse(logits, target, weight):
    """sum(w (sigmoid(z) - y)^2) / count(w != 0): the reference trainer's
    `tf.losses.mean_squared_error` under its default reduction."""
    p = jax.nn.sigmoid(logits)
    nonzero = jnp.maximum(jnp.sum(weight != 0.0), 1).astype(jnp.float32)
    return jnp.sum(weight * jnp.square(p - target)) / nonzero


def adadelta_init(params):
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    return {"e_g": zeros(), "e_x": zeros()}


def adadelta_update(params, grads, slots, lr: float,
                    rho: float = ADADELTA_RHO, eps: float = ADADELTA_EPS):
    """Dense Adadelta (Zeiler 2012) on every leaf, every step."""
    tm = jax.tree_util.tree_map
    e_g = tm(lambda a, g: rho * a + (1.0 - rho) * g * g, slots["e_g"], grads)
    delta = tm(lambda g, a, x: jnp.sqrt(x + eps) / jnp.sqrt(a + eps) * g,
               grads, e_g, slots["e_x"])
    e_x = tm(lambda x, d: rho * x + (1.0 - rho) * d * d, slots["e_x"], delta)
    params = tm(lambda p, d: p - lr * d, params, delta)
    return params, {"e_g": e_g, "e_x": e_x}


def make_epoch(forward: Callable, lr: float, compute: str,
               fault: str = ""):
    """`epoch(params, slots, blocks) -> (params, slots, loss_sum)`: one
    optimizer step per leading index of `blocks` ({"features", "target",
    "weight"}, each (nb, B, ...)), in order.  Jitted by the caller.

    `fault` plants a fault for the calibration and the tests, in the
    reference put in the program's place: "half_batch" leaves the second
    half of every batch out and takes the mean over the rest."""
    rnd = rounder(compute)

    def loss_fn(params, xs):
        logits = forward(params, xs["features"].astype(jnp.float32), rnd)
        weight = xs["weight"].astype(jnp.float32)
        if fault == "half_batch":
            weight = weight.at[weight.shape[0] // 2:].set(0.0)
        elif fault:
            raise ValueError(f"unknown fault {fault!r}")
        return weighted_mse(logits, xs["target"].astype(jnp.float32), weight)

    def epoch(params, slots, blocks):
        def body(carry, xs):
            p, s, acc = carry
            loss, grads = jax.value_and_grad(loss_fn)(p, xs)
            p, s = adadelta_update(p, grads, s, lr)
            return (p, s, acc + loss), None

        (params, slots, acc), _ = jax.lax.scan(
            body, (params, slots, jnp.float32(0.0)), blocks)
        return params, slots, acc

    return epoch


def make_scores(forward: Callable, compute: str = "float32"):
    rnd = rounder(compute)

    def scores(params, features):
        return jax.nn.sigmoid(
            forward(params, features.astype(jnp.float32), rnd))[:, 0]

    return scores
