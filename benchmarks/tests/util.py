"""Shared by the benchmark's own tests: a cell cut to a size the CPU holds,
driven through the driver with the harness's look for a chip skipped."""

from __future__ import annotations

import time

import jax

from benchmarks import harness

TINY_BATCH = 256
TINY_STEPS = 12
TINY_VOCAB = 500
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_context(cell_name: str, seed: int = 2147483651, trace: bool = False,
                 **config_overrides) -> tuple[dict, harness.Context]:
    bench, cell, config, traffic, params, _ = harness.load_cell(cell_name)
    config = dict(config, batch_size=TINY_BATCH)
    if config.get("num_categorical"):
        config["vocab_size"] = TINY_VOCAB
    config.update(config_overrides)
    params = dict(params, train_rows=TINY_BATCH * TINY_STEPS)
    ctx = harness.Context(
        cell=cell, config=config, traffic=traffic, params=params, seed=seed,
        seconds=0.5, trace=trace, devices=jax.devices()[:1],
        peaks=CPU_PEAKS, t_start=time.time(), log=None)
    return bench, ctx


def drive(cell_name: str, **kw):
    """(result line, outcome) of one run of the driver at the tiny size."""
    bench, ctx = tiny_context(cell_name, **kw)
    driver = harness.load_module("drivers", ctx.traffic["driver"])
    out = driver.run(ctx)
    return harness.result_line(bench, ctx, out), out


def cells() -> list[str]:
    return [w["name"] for w in harness.load_benchmark()["workloads"]]
