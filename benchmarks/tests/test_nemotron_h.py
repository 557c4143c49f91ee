"""What PR 27 added to the benchmark, at a tiny size on the CPU: the counts
of `counts/nemotron_h.py`, the reference's pieces, the row generator of
`drivers/resident_sequences.py` and that driver's planted faults."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import compare, harness
from benchmarks.counts import nemotron_h as counts
from benchmarks.drivers import resident_sequences as driver
from benchmarks.reference import common, nemotron_h as ref
from benchmarks.tests.conftest import tiny_context

CELL = "nemotron3_nano_ep16.train_sequences"


def _config() -> dict:
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "nemotron3_nano_ep16.json")) as f:
        return json.load(f)


def test_the_counts_are_the_published_widths():
    cfg = _config()
    dense, table = counts.params(cfg)
    # 4 x 38.74 M + 4 x (20.30 M + 8 x 9.98 M) + 23.40 M, and the table
    assert table == 16384 * 2688
    assert dense + table == 622_925_441
    per = counts.block_flops_per_position(cfg)
    pattern = cfg["hybrid_override_pattern"]
    # every position through the eight blocks up to the last mixer, the
    # last position alone through the E block after it
    fwd = 4096 * sum(per[k] for k in pattern[:8]) + per["E"]
    assert counts.flops_per_sample(cfg) == 3 * fwd
    assert 1.63e9 < 3 * fwd / 4096 < 1.64e9     # a position trained
    share = {k: 4096 * per[k] * pattern[:8].count(k) / fwd for k in "M*E"}
    assert share["M"] == pytest.approx(0.588, abs=0.005)
    assert share["*"] == pytest.approx(0.147, abs=0.005)
    assert share["E"] == pytest.approx(0.265, abs=0.005)
    assert 3 * 4096 * per["E.routed"] / fwd == pytest.approx(0.041, abs=0.003)
    # every parameter but the table and both slots once, a row a position
    assert counts.bytes_per_step(cfg, 8) == (
        8 * (4096 * 4 + 5) + 24 * (dense + 8 * 4096 * 2688))


def test_the_counts_parameters_are_the_references():
    _, ctx = tiny_context(CELL)
    params = ref.init_params(ctx.config, 3)
    n = sum(int(np.prod(v.shape)) for v in compare.flatten(params).values())
    assert n == sum(counts.params(ctx.config))


def test_the_recurrence_is_the_sum_it_is_written_as():
    """y_t = C_t . sum_s exp(sum_{s<r<=t} delta_r A) delta_s x_s (x) B_s."""
    rng = np.random.default_rng(0)
    t, h, p, g, n = 6, 2, 3, 1, 4
    x = rng.normal(size=(t, h, p))
    dt = rng.uniform(0.01, 0.5, (t, h))
    a = -rng.uniform(1, 4, h)
    bm, cm = rng.normal(size=(t, g, n)), rng.normal(size=(t, g, n))
    d = rng.normal(size=h)
    want = np.zeros((t, h, p))
    for ti in range(t):
        for s in range(ti + 1):
            decay = np.exp((dt[s + 1:ti + 1] * a).sum(axis=0))     # (h,)
            want[ti] += (decay * dt[s])[:, None] * x[s] * (
                cm[ti, 0] @ bm[s, 0])
        want[ti] += d[:, None] * x[ti]
    got = ref.recurrence(*(jnp.asarray(v, jnp.float32)
                           for v in (x, dt, a, bm, cm, d)))
    assert np.allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_the_same_rows_whatever_the_count_asked_for():
    spec = dict(_config(), num_categorical=8, vocab_size=50)
    few = driver.make_rows(spec, 5, 9, driver.TRAIN_STREAM)
    many = driver.make_rows(spec, driver.CHUNK_ROWS + 7, 9,
                            driver.TRAIN_STREAM)
    for k in few:
        assert np.array_equal(few[k], many[k][:5]), k
    other = driver.make_rows(spec, 5, 9, driver.VALID_STREAM)
    assert not np.array_equal(few["features"], other["features"])
    ids = many["features"]
    assert ids.min() >= 0 and ids.max() < 50 and np.all(ids == ids.round())
    assert 0.5 <= many["weight"].min() and many["weight"].max() < 2.0
    assert set(np.unique(many["target"])) == {0.0, 1.0}
    # a skew of 3: the low ids are the hot ones
    assert np.mean(ids < 50 / 8) > 0.4


def test_the_job_is_the_configurations_widths():
    cfg = _config()
    _, cell, _, traffic, params, _ = harness.load_cell(CELL)
    job = driver.build_job(cfg, params, 5, 1)
    bs = job.model.block_stack
    assert job.model.model_type == "block_stack" and job.model.remat
    assert (bs.pattern, bs.hidden_size, bs.n_routed_experts,
            bs.experts_held, bs.num_experts_per_tok) == (
        "MEMEM*EME", 2688, 128, 8, 6)
    assert job.schema.feature_count == 4096
    assert job.data.batch_size == 8 and job.schema.weight_index == 1


@pytest.mark.parametrize("compute, fault", [
    ("float8", ""), ("bfloat16", "half_batch"), ("bfloat16", "no_routed")])
def test_a_planted_fault_fails_the_cells_limits(compute, fault):
    """Each upper reading the limits were set under, through the driver's
    own comparison and the harness's verdict: the control (the reference in
    float8 in the program's place) and the two planted faults."""
    _, ctx = tiny_context(CELL)
    train_rows, valid_rows, _, _ = driver._datasets(ctx.config, ctx.params,
                                                    ctx.seed)
    limits = harness.load_limits(CELL)["limits"]
    ref_run = driver.reference_first_epoch(ctx.config, ctx.seed, train_rows,
                                           valid_rows)
    side = driver.reference_first_epoch(ctx.config, ctx.seed, train_rows,
                                        valid_rows, compute=compute,
                                        fault=fault)
    gaps, _ = driver.training_gaps(side, ref_run)
    assert not harness.is_correct(
        {k: (gaps[k], limits[k]) for k in gaps if k in limits}), gaps


def test_the_reference_in_the_stated_precision_passes_the_cells_limits():
    _, ctx = tiny_context(CELL)
    train_rows, valid_rows, _, _ = driver._datasets(ctx.config, ctx.params,
                                                    ctx.seed)
    limits = harness.load_limits(CELL)["limits"]
    ref_run = driver.reference_first_epoch(ctx.config, ctx.seed, train_rows,
                                           valid_rows)
    side = driver.reference_first_epoch(ctx.config, ctx.seed, train_rows,
                                        valid_rows, compute="bfloat16")
    gaps, _ = driver.training_gaps(side, ref_run)
    assert harness.is_correct(
        {k: (gaps[k], limits[k]) for k in gaps if k in limits}), gaps


def test_an_unknown_fault_is_refused():
    with pytest.raises(ValueError, match="unknown fault"):
        driver.reference_first_epoch({}, 0, {}, {}, fault="none such")


def test_tokens_dropped_is_no_pass_without_its_events():
    assert driver.tokens_dropped([{"kind": "goodput"}]) != 0.0
    events = [{"kind": "moe", "layers": [{"tokens_dropped": 0},
                                         {"tokens_dropped": 2}]}]
    assert driver.tokens_dropped(events) == 2.0


def test_load_imbalance_reads_the_busiest_expert_of_the_worst_layer():
    reader = harness.load_metric("moe_load_imbalance")
    even = {"tokens_per_expert": [10, 10, 10, 10]}
    skew = {"tokens_per_expert": [25, 5, 5, 5]}
    run = {"journal": [{"kind": "moe", "layers": [even, skew]},
                       {"kind": "moe", "layers": [even, skew]}]}
    assert reader.read(run) == pytest.approx(2.5)
    assert reader.read({"journal": [{"kind": "goodput"}]}) is None
    # a layer that routes one position a row holds a handful of tokens: its
    # ratio is chance, and it is not the worst layer
    few = {"tokens_per_expert": [3, 0, 0, 0], "routed_slots": 48}
    run = {"journal": [{"kind": "moe", "layers": [
        dict(even, routed_slots=196608), dict(skew, routed_slots=196608),
        few]}]}
    assert reader.read(run) == pytest.approx(2.5)


def test_the_change_leaves_out_what_the_references_gradient_hardly_reached():
    leaves = {"block7/in_proj": 1.0, "block8/experts/w2": 1.0,
              "block8/shared/w2": 1.0, "block6/experts/w2": 1.0}
    support = {"block7/in_proj": 1.0, "block8/experts/w2": 0.07,
               "block8/shared/w2": 1.0, "block6/experts/w2": 0.93}
    ref_run = {"train_error": 1.0, "valid_error": 1.0, "grad": dict(leaves),
               "change": dict(leaves), "support": support}
    assert driver.sparse_leaves(ref_run) == {"block8/experts/w2": (0.07, 1.0)}
    prog = dict(ref_run, change=dict(leaves, **{"block8/experts/w2": 0.0}))
    gaps, notes = driver.training_gaps(prog, ref_run)
    assert gaps["change_norm_gap"] == 0.0 == gaps["change_global_gap"]
    assert gaps["change_norm_gap_all"] == 1.0
    assert list(notes["sparse_leaves"]) == ["block8/experts/w2"]
    # the same on a leaf whose gradient is everywhere is the fault it looks
    # like, and what the program's own gradient reached decides nothing
    prog = dict(ref_run, change=dict(leaves, **{"block6/experts/w2": 0.0}),
                support={k: 0.0 for k in support})
    gaps, notes = driver.training_gaps(prog, ref_run)
    assert gaps["change_norm_gap"] == 1.0
    assert notes["change_norm_gap"] == "block6/experts/w2"


def test_the_reference_run_reads_where_its_gradient_reached():
    _, ctx = tiny_context(CELL)
    train_rows, valid_rows, _, _ = driver._datasets(ctx.config, ctx.params,
                                                    ctx.seed)
    run = driver.reference_first_epoch(ctx.config, ctx.seed, train_rows,
                                       valid_rows)
    assert set(run["support"]) == set(run["grad"])
    assert all(0.0 <= v <= 1.0 for v in run["support"].values())
    assert run["support"]["block0/in_proj"] == 1.0
    # the token table's gradient reaches the rows that were drawn
    drawn = np.unique(train_rows["features"]).size
    assert run["support"]["embed_tokens"] == pytest.approx(
        drawn / ctx.config["vocab_size"])


def test_the_last_position_alone_is_what_the_whole_row_gives():
    """The blocks after the last sequence mixer run on the last position:
    the same logit as every block over every position."""
    _, ctx = tiny_context(CELL)
    cfg = ctx.config
    s = ref._shapes(cfg)
    params = ref.init_params(cfg, 3)
    ids = jnp.asarray(driver.make_rows(cfg, 2, 5, driver.TRAIN_STREAM)
                      ["features"])

    def rnd(v):
        return v.astype(jnp.float32)

    def whole_row(ids):
        x = params["embed_tokens"][ids.astype(jnp.int32)]
        for i, kind in enumerate(s["pattern"]):
            p = params[f"block{i}"]
            mixer = {"M": ref._mamba, "*": ref._attention,
                     "E": lambda p, s, x, r: ref._experts(p, s, x, r, True)}
            x = x + mixer[kind](p, s, ref.rms_norm(x, p["norm"], s["eps"]),
                                rnd)
        last = ref.rms_norm(x[-1], params["norm_f"], s["eps"])
        return common.dense(params["head"]["shifu_output_0"], last[None],
                            rnd)[0]

    want = jnp.stack([whole_row(r) for r in ids])
    got = ref.make_forward(cfg)(params, ids, rnd)
    assert s["pattern"].endswith("E")
    assert np.allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                       atol=1e-6)


def test_a_constant_of_the_program_stated_otherwise_is_refused():
    cfg = dict(_config(), chunk_size=64)
    with pytest.raises(harness.BenchError, match="chunk_size = 64"):
        driver.model_group(cfg)


def test_the_blocks_after_the_last_mixer_are_counted_at_one_position():
    cfg = _config()
    assert counts.block_positions(cfg) == [4096] * 8 + [1]
    assert counts.block_positions(
        dict(cfg, hybrid_override_pattern="EM")) == [4096, 4096]
