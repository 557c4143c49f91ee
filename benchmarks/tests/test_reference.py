"""Each plain reference against the program, at a tiny size on the CPU."""

import jax
import numpy as np
import pytest

from benchmarks import compare, harness, refrun
from benchmarks.tests import util

#: float32 program against float32 reference: rounding of a different order
#: of the same sums
F32_TOLERANCE = 2e-4


def _program_first_epoch(ctx):
    from shifu_tpu.train import train

    driver = harness.load_module("drivers", ctx.traffic["driver"])
    train_rows, valid_rows, train_ds, valid_ds = driver._datasets(
        ctx.config, ctx.params, ctx.seed)
    prog = driver.first_epoch_state(train, ctx.config, ctx.params, ctx.seed,
                                    train_ds, valid_ds, ctx.devices)
    return prog, train_rows, valid_rows


@pytest.mark.parametrize("cell", util.cells())
def test_initial_weights_are_the_programs_bit_for_bit(cell):
    from shifu_tpu.train.loop import init_state

    _, ctx = util.tiny_context(cell)
    driver = harness.load_module("drivers", ctx.traffic["driver"])
    job = driver.build_job(ctx.config, ctx.params, ctx.seed, 1)
    n_feat = ctx.config["num_numeric"] + ctx.config.get("num_categorical", 0)
    theirs = compare.flatten(init_state(job, n_feat).params)
    model = harness.load_module("reference", ctx.config["model_type"])
    ours = compare.flatten(model.init_params(ctx.config, ctx.seed))
    assert set(ours) == set(theirs)
    for k in ours:
        assert np.array_equal(np.asarray(ours[k]), np.asarray(theirs[k])), k


@pytest.mark.parametrize("cell", util.cells())
def test_reference_agrees_in_float32_and_not_in_lower_precision(cell):
    _, ctx = util.tiny_context(cell, compute_dtype="float32",
                               feature_dtype="float32")
    prog, train_rows, valid_rows = _program_first_epoch(ctx)
    ref = refrun.first_epoch(ctx.config, ctx.seed, train_rows, valid_rows)
    gaps, _ = compare.training_gaps(prog, ref)
    assert max(gaps.values()) < F32_TOLERANCE, gaps

    # the program as the configuration states it, in bfloat16: beyond the
    # float32 tolerance, so the comparison can tell the two apart
    _, low = util.tiny_context(cell, feature_dtype="float32")
    assert low.config["compute_dtype"] == "bfloat16"
    prog_low, _, _ = _program_first_epoch(low)
    gaps_low, _ = compare.training_gaps(prog_low, ref)
    assert max(gaps_low.values()) > 3 * F32_TOLERANCE, gaps_low


@pytest.mark.parametrize("cell", util.cells())
def test_control_in_float8_fails_the_cells_limits(cell):
    """The control of "How correct is decided": the reference in the
    nearest precision below the configuration's, in the program's place,
    has to come out as not correct; in the stated precision it passes."""
    _, ctx = util.tiny_context(cell)
    driver = harness.load_module("drivers", ctx.traffic["driver"])
    train_rows, valid_rows, _, _ = driver._datasets(ctx.config, ctx.params,
                                                    ctx.seed)
    limits = harness.load_limits(cell)["limits"]
    ref = refrun.first_epoch(ctx.config, ctx.seed, train_rows, valid_rows)
    verdict = {}
    for compute in ("bfloat16", "float8"):
        side = refrun.first_epoch(ctx.config, ctx.seed, train_rows,
                                  valid_rows, compute=compute)
        gaps, _ = compare.training_gaps(side, ref)
        verdict[compute] = harness.is_correct(
            {k: (gaps[k], limits[k]) for k in gaps if k in limits})
    assert verdict == {"bfloat16": True, "float8": False}


def test_dead_leaves_are_found_by_their_gradient_not_their_name():
    ref_grad = {"a": 1.0, "b": 2.0, "c": 1e-9, "d": 1.5}
    assert compare.dead_leaves(ref_grad) == {"c"}
    prog = {"a": 1.0, "b": 2.0, "c": 5.0, "d": 1.5}
    gap, _ = compare.worst_leaf_gap(prog, ref_grad, skip={"c"})
    assert gap == 0.0
    gap, where = compare.worst_leaf_gap(prog, ref_grad)
    assert where == "c" and gap > 1.0


def test_the_median_leaf_gap_is_deaf_to_one_leaf_and_hears_most_of_them():
    ref = {"a": 1.0, "b": 2.0, "c": 4.0, "d": 1.5, "e": 3.0}
    one_off = dict(ref, c=8.0)
    assert compare.median_leaf_gap(one_off, ref) == 0.0
    assert compare.worst_leaf_gap(one_off, ref)[0] == pytest.approx(1.0)
    most_off = {k: 1.1 * v for k, v in ref.items()}
    assert compare.median_leaf_gap(most_off, ref) == pytest.approx(0.1)
    assert compare.median_leaf_gap(dict(ref, a=float("nan")), ref) != \
        compare.median_leaf_gap(ref, ref)       # a NaN is never a pass
