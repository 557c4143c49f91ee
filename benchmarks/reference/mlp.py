"""Plain reference of the Shifu MLP: hidden dense layers with their
activations, a linear head that gives logits (the sigmoid sits in the loss).

Float32 `jax.numpy`, no kernels, nothing of `shifu_tpu`.  The module paths
are the program's parameter names: they seed the initial weights
(`common.param_key`) and key the comparison leaf by leaf.
"""

from __future__ import annotations

import jax

from .common import dense, dense_init

_ACT = {"relu": jax.nn.relu, "tanh": jax.numpy.tanh,
        "sigmoid": jax.nn.sigmoid}


def init_params(cfg: dict, seed: int) -> dict:
    dims = [cfg["num_numeric"], *cfg["hidden_nodes"]]
    trunk = {f"hidden_layer{i}": dense_init(
        seed, ("trunk", f"hidden_layer{i}"), a, b)
        for i, (a, b) in enumerate(zip(dims, dims[1:]))}
    head = {"shifu_output_0": dense_init(
        seed, ("head", "shifu_output_0"), dims[-1], 1)}
    return {"trunk": trunk, "head": head}


def make_forward(cfg: dict):
    acts = [_ACT[a] for a in cfg["activations"]]

    def forward(params, x, rnd):
        for i, act in enumerate(acts):
            x = act(dense(params["trunk"][f"hidden_layer{i}"], x, rnd))
        return dense(params["head"]["shifu_output_0"], x, rnd)

    return forward
