"""GPipe-style pipeline parallelism over the `pipe` mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.4 marks PP absent);
here it is a first-class mesh axis like `data`/`seq`/`model`: a stack of
identical layer stages — parameter leaves shaped (num_layers, ...), sharded
on the leading axis over `pipe` so each device holds only its stage's layers
— processes a train of microbatches.  Activations hop stage -> stage over ICI
via `ppermute` while every stage computes a different microbatch: the classic
fill/drain schedule of n_micro + n_stages - 1 ticks, with an idle-bubble
fraction of (n_stages - 1) / (n_micro + n_stages - 1).

Differentiable end-to-end: `jax.grad` transposes the scan + ppermute chain
into the reverse schedule automatically, so one `value_and_grad` over the
whole pipelined model yields stage-sharded gradients (and the optimizer
update runs stage-parallel too — each device updates only its own layers).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import DATA_AXIS, PIPE_AXIS

PyTree = Any


def stage_slice(stacked_params: PyTree, stage: int, n_stages: int) -> PyTree:
    """The per-stage slice of (num_layers, ...) stacked params: contiguous
    layers [stage * lps, (stage+1) * lps) where lps = num_layers / n_stages."""
    def cut(leaf):
        lps = leaf.shape[0] // n_stages
        return leaf[stage * lps:(stage + 1) * lps]
    return jax.tree_util.tree_map(cut, stacked_params)


def pipeline_reference(stage_fn: Callable[[PyTree, jax.Array], jax.Array],
                       stacked_params: PyTree, x: jax.Array,
                       n_stages: int) -> jax.Array:
    """Sequential oracle for tests: run every microbatch through all stages
    in order.  x: (n_micro, mb, ...) -> (n_micro, mb, ...)."""
    outs = []
    for m in range(x.shape[0]):
        h = x[m]
        for s in range(n_stages):
            h = stage_fn(stage_slice(stacked_params, s, n_stages), h)
        outs.append(h)
    return jnp.stack(outs)


def pipeline_apply(stage_fn: Callable[[PyTree, jax.Array], jax.Array],
                   stacked_params: PyTree, x: jax.Array, mesh: Mesh,
                   axis: str = PIPE_AXIS) -> jax.Array:
    """Run microbatches through the stage pipeline over `axis`.

    stage_fn(local_params, h) -> h applies ONE stage (its share of layers) to
    one microbatch; activation shape must be stage-invariant.  stacked_params
    leaves are (num_layers, ...) global arrays (place them with a
    P(`pipe`, ...) rule so each device materializes only its stage);
    x is (n_micro, mb, ...), batch dim sharded over `data` when the mesh has
    that axis.  Returns (n_micro, mb, ...) outputs, replicated over `axis`.

    Equivalent to `pipeline_reference` (validated in tests/test_pipeline.py,
    forward and gradients).
    """
    n_stages = int(mesh.shape[axis])
    if n_stages == 1:
        return pipeline_reference(stage_fn, stacked_params, x, 1)
    n_micro = x.shape[0]
    last = n_stages - 1

    def local(params, xloc):
        stage = jax.lax.axis_index(axis)

        def tick(carry, t):
            outputs, recv = carry
            mb = jnp.clip(t, 0, n_micro - 1)
            h_in = jnp.where(stage == 0, xloc[mb], recv)
            y = stage_fn(params, h_in)
            # the last stage finishes microbatch t-last at tick t
            out_idx = jnp.clip(t - last, 0, n_micro - 1)
            keep = jnp.logical_and(stage == last, t >= last)
            outputs = outputs.at[out_idx].set(
                jnp.where(keep, y, outputs[out_idx]))
            # hand the activation to the next stage (ICI neighbor hop);
            # stages not in the perm receive zeros, which stage 0 ignores
            recv = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)])
            return (outputs, recv), None

        outputs0 = jnp.zeros_like(xloc)
        recv0 = jnp.zeros_like(xloc[0])
        (outputs, _), _ = jax.lax.scan(
            tick, (outputs0, recv0), jnp.arange(n_micro + n_stages - 1))
        # only the last stage holds real outputs (others kept zeros):
        # psum replicates them across the pipe group
        return jax.lax.psum(outputs, axis)

    batch_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None
    x_spec = P(None, batch_axis, *([None] * (x.ndim - 2)))
    p_specs = jax.tree_util.tree_map(
        lambda leaf: P(axis, *([None] * (leaf.ndim - 1))), stacked_params)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(p_specs, x_spec),
                       out_specs=x_spec, check_vma=False)
    return fn(stacked_params, x)
