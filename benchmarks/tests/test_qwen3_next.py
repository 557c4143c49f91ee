"""What PR 32 added to the benchmark, at a tiny size on the CPU: the counts
of `counts/qwen3_next.py`, the wording of the job (`jobs/qwen3_next.py`), the
reference's pieces, the driver `resident_sequences_any_model`'s planted
faults against the cell's limits, and the reader of
`moe_block_fill_share`."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import compare, harness
from benchmarks.conftest import tiny_context
from benchmarks.counts import qwen3_next as counts
from benchmarks.drivers import resident_sequences, \
    resident_sequences_any_model as driver
from benchmarks.jobs import qwen3_next as jobs
from benchmarks.reference import common, qwen3_next as ref

CELL = "qwen3_next_ep16.train_sequences"


def _config() -> dict:
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "qwen3_next_ep16.json")) as f:
        return json.load(f)


def test_the_counts_are_the_published_widths():
    cfg = _config()
    dense, table = counts.params(cfg)
    # 3 x 33.72 M + 27.27 M + 4 x 104.86 M, the final norm, the head; the table
    assert table == 18992 * 2048
    assert counts.block_params(cfg) == {"L": 33_720_512, "A": 27_265_536,
                                        "F": 104_861_696}
    assert dense + table == 586_773_569
    per = counts.block_flops_per_position(cfg)
    pattern = counts.pattern(cfg)
    assert pattern == "LFLFLFAF"
    assert per["L"] == 71_106_560 and per["A"] == 88_088_576
    assert per["F"] == 12_324_864 and per["F.routed"] == 3_932_160
    # every position through the seven blocks up to the last mixer, the
    # last position alone through the F block after it
    fwd = 4096 * sum(per[k] for k in pattern[:7]) + per["F"]
    assert counts.flops_per_sample(cfg) == 3 * fwd
    assert 33.2e12 < 8 * 3 * fwd < 33.3e12          # a step of 8 rows
    share = {k: 4096 * per[k] * pattern[:7].count(k) / fwd for k in "LAF"}
    assert share["L"] == pytest.approx(0.630, abs=0.005)
    assert share["A"] == pytest.approx(0.260, abs=0.005)
    assert share["F"] == pytest.approx(0.109, abs=0.005)
    # every parameter but the table and both slots once, a row a position
    assert counts.bytes_per_step(cfg, 8) == (
        8 * (4096 * 4 + 5) + 24 * (dense + 8 * 4096 * 2048))


def test_the_counts_parameters_are_the_references():
    _, ctx = tiny_context(CELL)
    params = ref.init_params(ctx.config, 3)
    n = sum(int(np.prod(v.shape)) for v in compare.flatten(params).values())
    assert n == sum(counts.params(ctx.config))


def test_the_file_states_every_published_width_unchanged():
    cfg = _config()
    published = {
        "hidden_size": 2048, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_key_head_dim": 128,
        "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
        "num_attention_heads": 16, "num_key_value_heads": 2, "head_dim": 256,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "num_experts_per_tok": 10, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "full_attention_interval": 4}
    assert {k: cfg[k] for k in published} == published
    assert cfg["deployment"]["router_experts"] == 512
    assert cfg["num_experts"] * cfg["deployment"]["expert_parallel"] == 512
    assert cfg["vocab_size"] * 8 == cfg["deployment"]["published_vocab"]
    assert cfg["optimizer"]["learning_rate"] == 0.01


def test_the_job_is_the_configurations_widths():
    cfg = _config()
    _, cell, _, traffic, params, found = harness.load_cell(CELL)
    assert found is driver and traffic["driver"] == \
        "resident_sequences_any_model"
    job = driver.build_job(cfg, params, 5, 1)
    bs = job.model.block_stack
    assert job.model.model_type == "block_stack" and job.model.remat
    assert (bs.pattern, bs.hidden_size, bs.n_routed_experts,
            bs.experts_held, bs.num_experts_per_tok, bs.norm_eps) == (
        "LFLFLFAF", 2048, 512, 32, 10, 1e-6)
    assert (bs.linear_num_key_heads, bs.linear_num_value_heads,
            bs.head_dim, bs.rotary_dim, bs.rope_theta) == (16, 32, 256, 64,
                                                            1e7)
    assert job.schema.feature_count == 4096
    assert job.data.batch_size == 8 and job.schema.weight_index == 1
    assert job.train.optimizer.learning_rate == 0.01


def test_the_pattern_puts_full_attention_every_interval_th_layer():
    for layers, every, want in ((4, 4, "LFLFLFAF"), (8, 4, "LFLFLFAF" * 2),
                                (2, 2, "LFAF"), (3, 1, "AFAFAF")):
        cfg = {"num_hidden_layers": layers, "full_attention_interval": every}
        assert counts.pattern(cfg) == want
    assert jobs.pattern is counts.pattern is ref.pattern


def test_a_constant_of_the_program_stated_otherwise_is_refused():
    with pytest.raises(harness.BenchError, match="hidden_act = 'gelu'"):
        jobs.model_group(dict(_config(), hidden_act="gelu"))
    with pytest.raises(harness.BenchError, match="use_sliding_window"):
        jobs.model_group(dict(_config(), use_sliding_window=True))


def test_the_driver_hands_on_the_accepted_sequence_drivers_own():
    """Same rows, same reference run, same comparison: the objects are the
    older driver's, not copies."""
    for name in ("make_rows", "prepare", "_datasets", "reference_first_epoch",
                 "training_gaps", "sparse_leaves", "first_epoch_routing",
                 "tokens_dropped", "FAULTS"):
        assert getattr(driver, name) is getattr(resident_sequences, name)
    assert driver.run is not resident_sequences.run


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


def test_the_last_position_alone_is_what_the_whole_row_gives():
    """The block after the last sequence mixer runs on the last position:
    the same logit as every block over every position."""
    _, ctx = tiny_context(CELL)
    cfg = ctx.config
    s = ref._shapes(cfg)
    params = ref.init_params(cfg, 3)
    ids = jnp.asarray(driver.make_rows(cfg, 2, 5, driver.TRAIN_STREAM)
                      ["features"])

    def rnd(v):
        return v.astype(jnp.float32)

    mix = ref.mixers()

    def whole_row(ids):
        x = params["embed_tokens"][ids.astype(jnp.int32)]
        for i, kind in enumerate(s["pattern"]):
            p = params[f"block{i}"]
            x = x + mix[kind](p, s, ref.norm(x, p["norm"], s["eps"]), rnd)
        last = ref.norm(x[-1], params["norm_f"], s["eps"])
        return common.dense(params["head"]["shifu_output_0"], last[None],
                            rnd)[0]

    want = jnp.stack([whole_row(r) for r in ids])
    got = ref.make_forward(cfg)(params, ids, rnd)
    assert s["pattern"] == "LFAF"
    assert np.allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                       atol=1e-6)
    assert counts.block_positions(cfg) == [16, 16, 16, 1]


def test_the_delta_recurrence_is_the_sum_it_is_written_as():
    """Without the delta (beta's read of the state left out) the rule is a
    decayed linear attention, o_t = sum_s prod_{s<r<=t} alpha_r (q_t . k_s)
    beta_s v_s; with one key direction it is a value overwritten."""
    rng = np.random.default_rng(0)
    t, hk, hv, dk, dv = 6, 1, 2, 4, 3
    k = np.zeros((t, hk, dk), np.float32)
    k[np.arange(t), 0, np.arange(t) % dk] = 1.0       # orthogonal for 4 steps
    q = rng.normal(size=(t, hk, dk)).astype(np.float32)
    v = rng.normal(size=(t, hv, dv)).astype(np.float32)
    alpha = rng.uniform(0.5, 1.0, (t, hv)).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, (t, hv)).astype(np.float32)
    got = np.asarray(ref.delta_recurrence(*(jnp.asarray(x) for x in
                                            (q, k, v, alpha, beta))))
    # over the first four positions the keys are orthogonal: no read finds
    # anything, so the rule is the decayed sum
    for ti in range(4):
        want = np.zeros((hv, dv))
        for s in range(ti + 1):
            decay = alpha[s + 1:ti + 1].prod(axis=0)            # (hv,)
            want += (decay * beta[s] * (q[ti, 0] @ k[s, 0]))[:, None] * v[s]
        assert np.allclose(got[ti], want, rtol=1e-5, atol=1e-6)
    # position 4 writes at position 0's key again: what was there is read
    # and partly replaced, so the decayed sum is no longer the answer
    plain = sum((alpha[s + 1:5].prod(axis=0) * beta[s]
                 * (q[4, 0] @ k[s, 0]))[:, None] * v[s] for s in range(5))
    assert not np.allclose(got[4], plain, rtol=1e-3)


def test_block_fill_reads_the_layers_that_route_every_position():
    reader = harness.load_metric("moe_block_fill_share")
    full = {"routed_slots": 1000, "held_slots": 640, "live_blocks": 2,
            "block_rows": 512, "tokens_per_expert": [640]}
    half = dict(full, held_slots=256, live_blocks=1)
    last = {"routed_slots": 10, "held_slots": 1, "live_blocks": 1,
            "block_rows": 8, "tokens_per_expert": [1]}
    run = {"journal": [{"kind": "moe", "layers": [full, half, last]},
                       {"kind": "goodput"},
                       {"kind": "moe", "layers": [full, full, last]}]}
    assert reader.read(run) == pytest.approx(
        100.0 * (3 * 640 + 256) / (7 * 512))
    # a program that journals no such counters gives nothing to read
    old = {"routed_slots": 1000, "held_slots": 640,
           "tokens_per_expert": [640]}
    assert reader.read({"journal": [{"kind": "moe", "layers": [old]}]}) is None
    assert reader.read({"journal": [{"kind": "goodput"}]}) is None
