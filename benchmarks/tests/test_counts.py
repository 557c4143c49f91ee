"""The analytic counts against the compiler's own, at a tiny size."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness
from benchmarks.tests import util


def _step_flops(ctx) -> float:
    """cost_analysis() FLOPs of the program's forward + backward of one
    batch."""
    from shifu_tpu.train.loop import init_state
    from shifu_tpu.train.step import make_loss_fn

    driver = harness.load_module("drivers", ctx.traffic["driver"])
    job = driver.build_job(ctx.config, ctx.params, 1, 1)
    n_feat = ctx.config["num_numeric"] + ctx.config.get("num_categorical", 0)
    state = init_state(job, n_feat)
    loss_fn = make_loss_fn(job)
    b = ctx.config["batch_size"]
    batch = {"features": jnp.zeros((b, n_feat), jnp.float32),
             "target": jnp.zeros((b, 1), jnp.float32),
             "weight": jnp.ones((b, 1), jnp.float32)}
    grad = jax.jit(lambda p, x: jax.value_and_grad(loss_fn)(
        p, state.apply_fn, x))
    cost = grad.lower(state.params, batch).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


@pytest.mark.parametrize("cell", util.cells())
def test_flop_count_agrees_with_cost_analysis(cell):
    # wide layers, a small vocabulary: the matrix products are the work,
    # as they are at the cell's own size
    _, ctx = util.tiny_context(cell, compute_dtype="float32")
    counts = harness.load_module("counts", ctx.config["model_type"])
    analytic = counts.flops_per_sample(ctx.config) * ctx.config["batch_size"]
    compiled = _step_flops(ctx)
    assert 0.9 < compiled / analytic < 1.15, (compiled, analytic)


@pytest.mark.parametrize("cell", util.cells())
def test_byte_count_reads_the_work_not_the_implementation(cell):
    _, ctx = util.tiny_context(cell)
    counts = harness.load_module("counts", ctx.config["model_type"])
    b = ctx.config["batch_size"]
    base = counts.bytes_per_step(ctx.config, b)
    for mode in ("on", "off", "auto"):
        cfg = dict(ctx.config, job={"train": {"sparse_embedding_update": mode}})
        assert counts.bytes_per_step(cfg, b) == base
    # batch-proportional apart from the dense parameters: twice the batch
    # is less than twice the bytes, and more than the bytes
    assert base < counts.bytes_per_step(ctx.config, 2 * b) < 2 * base
    if ctx.config.get("num_categorical"):
        # the tables' share is the touched rows: the vocabulary is not in it
        big = dict(ctx.config, vocab_size=10 * ctx.config["vocab_size"])
        assert counts.bytes_per_step(big, b) == base
