"""Plain reference of DeepFM (Guo et al., arXiv:1703.04247) as the program
lays it out: every selected column is a field with a k-dim latent vector
(numeric fields x_j * w_j + b_j, categorical fields one row of a table);
logit = first-order terms + FM second-order term + the deep trunk over the
flattened field vectors.

Float32 `jax.numpy`, a plain gather and its scatter-add gradient, the dense
Adadelta of `common` over whole tables; nothing of `shifu_tpu`.  Features
arrive as the program's wire has them: one float32 matrix, numeric columns
first, then the categorical ids as floats.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import dense, dense_init, glorot

_ACT = {"relu": jax.nn.relu, "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid}


def init_params(cfg: dict, seed: int) -> dict:
    nn_, nc, v, k = (cfg["num_numeric"], cfg["num_categorical"],
                     cfg["vocab_size"], cfg["embedding_dim"])
    dims = [(nn_ + nc) * k, *cfg["hidden_nodes"]]
    return {
        "numeric_embedding": {
            "weight": glorot(seed, ("numeric_embedding",), 1, (nn_, k)),
            "bias": jnp.zeros((nn_, k), jnp.float32)},
        "cat_embedding": {
            "embedding": glorot(seed, ("cat_embedding",), 1, (nc, v, k))},
        "first_order_cat": {
            "embedding": glorot(seed, ("first_order_cat",), 1, (nc, v, 1))},
        "first_order_numeric": dense_init(
            seed, ("first_order_numeric",), nn_, 1),
        "trunk": {f"hidden_layer{i}": dense_init(
            seed, ("trunk", f"hidden_layer{i}"), a, b)
            for i, (a, b) in enumerate(zip(dims, dims[1:]))},
        "shifu_output_0": dense_init(seed, ("shifu_output_0",), dims[-1], 1),
    }


def make_forward(cfg: dict):
    nn_, nc, v = cfg["num_numeric"], cfg["num_categorical"], cfg["vocab_size"]
    acts = [_ACT[a] for a in cfg["activations"]]
    field = jnp.arange(nc, dtype=jnp.int32)[None, :]

    def forward(params, x, rnd):
        numeric = x[:, :nn_]
        # unseen or out-of-range ids land in the last bucket
        ids = jnp.clip(x[:, nn_:nn_ + nc].astype(jnp.int32), 0, v - 1)
        ne = params["numeric_embedding"]
        num_vec = (rnd(numeric)[:, :, None] * rnd(ne["weight"])[None]
                   + ne["bias"][None])
        cat_vec = rnd(params["cat_embedding"]["embedding"][field, ids])
        cat_first = rnd(params["first_order_cat"]["embedding"][field, ids])
        vecs = jnp.concatenate([num_vec, cat_vec], axis=1)       # (B, F, k)
        first = (dense(params["first_order_numeric"], numeric, rnd)
                 + jnp.sum(cat_first, axis=1))
        fm = 0.5 * jnp.sum(jnp.square(jnp.sum(vecs, axis=1))
                           - jnp.sum(jnp.square(vecs), axis=1),
                           axis=-1, keepdims=True)
        h = vecs.reshape(vecs.shape[0], -1)
        for i, act in enumerate(acts):
            h = act(dense(params["trunk"][f"hidden_layer{i}"], h, rnd))
        return first + fm + dense(params["shifu_output_0"], h, rnd)

    return forward
