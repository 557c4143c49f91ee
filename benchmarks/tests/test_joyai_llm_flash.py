"""What PR 34 added to the benchmark, at a tiny size on the CPU: the counts
of `counts/joyai_llm_flash.py`, the configuration's file against the
published widths, the wording of the job (`jobs/joyai_llm_flash.py`), the
reference's pieces, and the driver's planted faults against the cell's
limits."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import compare, harness
from benchmarks.counts import joyai_llm_flash as counts
from benchmarks.drivers import resident_sequences_any_model as driver
from benchmarks.jobs import joyai_llm_flash as jobs
from benchmarks.reference import common, joyai_llm_flash as ref
from benchmarks.tests.sizes_joyai_llm_flash import tiny_context

CELL = "joyai_flash_ep16.train_sequences"

#: the catalog row's `config` (model-configs guide, `JoyAI-LLM-Flash`), the
#: keys of `reduced` at their published values
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}


def _config() -> dict:
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "joyai_flash_ep16.json")) as f:
        return json.load(f)


def test_the_counts_are_the_published_widths():
    cfg = _config()
    dense, table = counts.params(cfg)
    assert table == 16160 * 2048
    assert counts.block_params(cfg) == {"C": 26_349_568, "D": 44_042_240,
                                        "G": 80_742_400}
    assert dense + table == 638_951_425         # 10.22 GB at 16 B each
    per = counts.block_flops_per_position(cfg)
    pattern = counts.pattern(cfg)
    assert pattern == "CDCGCGCGCGCG"
    assert per["C"] == 94_644_224 and per["C.products"] == 41_953_280
    assert per["D"] == 88_080_384
    assert per["G"] == 15_204_352 and per["G.routed"] == 4_718_592
    # every position through the eleven blocks up to the last mixer, the
    # last position alone through the G block after it
    fwd = 4096 * sum(per[k] for k in pattern[:11]) + per["G"]
    assert counts.flops_per_sample(cfg) == 3 * fwd
    assert 70.4e12 < 8 * 3 * fwd < 70.5e12          # a step of 8 rows
    share = {k: 4096 * per[k] * pattern[:11].count(k) / fwd for k in "CDG"}
    assert share["C"] == pytest.approx(0.792, abs=0.005)
    assert share["D"] == pytest.approx(0.123, abs=0.005)
    assert share["G"] == pytest.approx(0.085, abs=0.005)
    assert 4096 * 6 * per["C.products"] / fwd == pytest.approx(0.351,
                                                               abs=0.005)
    # every parameter but the table and both slots once, a row a position
    assert counts.bytes_per_step(cfg, 8) == (
        8 * (4096 * 4 + 5) + 24 * (dense + 8 * 4096 * 2048))


def test_a_row_alone_is_counted_as_the_compiler_counts_it():
    """The compiler counts a loop's body once, so at the batch of
    `test_counts.py` it sees one row's up-projections, scores and values.
    A batch of one row is one trip of that loop: the whole of latent
    attention is then in the compiler's count, and inside the same band."""
    from benchmarks.tests.test_counts import _step_flops

    _, ctx = tiny_context(CELL, compute_dtype="float32", batch_size=1)
    compiled = _step_flops(ctx)
    analytic = counts.flops_per_sample(ctx.config)
    assert 0.9 < compiled / analytic < 1.15, (compiled, analytic)


def test_the_counts_parameters_are_the_references():
    _, ctx = tiny_context(CELL)
    params = ref.init_params(ctx.config, 3)
    n = sum(int(np.prod(v.shape)) for v in compare.flatten(params).values())
    assert n == sum(counts.params(ctx.config))


def test_the_file_states_every_published_width_unchanged():
    cfg = _config()
    entry = harness.find_cell(harness.load_benchmark(), CELL)[1]
    assert cfg["catalog_name"] == "JoyAI-LLM-Flash"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers", "head", "loss", "optimizer"])
    for key, published in PUBLISHED.items():
        if key in cfg["reduced"]:
            # a reduced key states its published value beside the cut one
            assert cfg[key] != published
            assert cfg["source_values"][key].startswith(str(published)), key
        else:
            assert cfg[key] == published, key
    for key in ("head", "loss", "optimizer"):
        assert cfg[key] and cfg["source_values"][key]
    dep = cfg["deployment"]
    assert dep["router_experts"] == 256 and dep["expert_parallel"] == 16
    assert cfg["n_routed_experts"] * dep["expert_parallel"] == 256
    assert cfg["vocab_size"] * 8 == dep["published_vocab"] == 129280
    assert cfg["num_hidden_layers"] == 6 and dep["published_layers"] == 40
    assert cfg["num_nextn_predict_layers"] == 0
    assert cfg["optimizer"]["learning_rate"] == 0.01
    assert cfg["assumed"] and cfg["left_out"]


def test_the_job_is_the_configurations_widths():
    cfg = _config()
    _, cell, _, traffic, params, found = harness.load_cell(CELL)
    assert found is driver and traffic["driver"] == \
        "resident_sequences_any_model" and cell["chips"] == 1
    job = driver.build_job(cfg, params, 5, 1)
    bs = job.model.block_stack
    assert job.model.model_type == "block_stack" and job.model.remat
    assert (bs.pattern, bs.hidden_size, bs.n_routed_experts,
            bs.experts_held, bs.num_experts_per_tok, bs.norm_eps) == (
        "CDCGCGCGCGCG", 2048, 256, 16, 8, 1e-6)
    assert (bs.num_attention_heads, bs.q_lora_rank, bs.kv_lora_rank,
            bs.qk_nope_head_dim, bs.qk_rope_head_dim, bs.v_head_dim,
            bs.rope_theta) == (32, 1536, 512, 128, 64, 128, 32e6)
    assert (bs.intermediate_size, bs.moe_intermediate_size,
            bs.n_shared_experts, bs.routed_scaling_factor) == (7168, 768, 1,
                                                               2.5)
    assert job.schema.feature_count == 4096
    assert job.data.batch_size == 8 and job.schema.weight_index == 1
    assert job.train.optimizer.learning_rate == 0.01
    assert params["train_rows"] == 64
    assert params["device_resident_bytes"] == 2 ** 31


def test_the_pattern_puts_the_dense_layers_first():
    for layers, dense, want in ((6, 1, "CDCGCGCGCGCG"), (3, 1, "CDCGCG"),
                                (2, 2, "CDCD"), (2, 0, "CGCG")):
        cfg = {"num_hidden_layers": layers, "first_k_dense_replace": dense}
        assert counts.pattern(cfg) == want
    assert jobs.pattern is counts.pattern is ref.pattern


@pytest.mark.parametrize("key, other", [
    ("scoring_func", "softmax"), ("topk_method", "greedy"), ("n_group", 8),
    ("topk_group", 4), ("norm_topk_prob", False), ("hidden_act", "gelu"),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("rope_interleave", False), ("moe_layer_freq", 2),
    ("attention_bias", True)])
def test_a_constant_of_the_program_stated_otherwise_is_refused(key, other):
    with pytest.raises(harness.BenchError, match=f"{key} = "):
        jobs.model_group(dict(_config(), **{key: other}))


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
    assert s["pattern"] == "CDCGCG"
    assert np.allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                       atol=1e-6)
    assert counts.block_positions(cfg) == [16, 16, 16, 16, 16, 1]


def test_the_references_scores_are_the_sum_they_are_written_as():
    """A head's score is its own product plus its product with the one
    shared rotary key, over sqrt(Dn + Dr); the softmax reads no later
    position; the values are of their own width."""
    _, ctx = tiny_context(CELL)
    s = ref._shapes(ctx.config)
    p = ref._init_block(s, 4, "C", ("block0",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(6, s["hidden"])), jnp.float32)

    def rnd(v):
        return v.astype(jnp.float32)

    q_nope, q_pe, k_nope, k_pe, v = (np.asarray(a, np.float64) for a in
                                     ref.latent_qkv(p, s, x, rnd))
    assert q_pe.shape == (6, 4, 4) and k_pe.shape == (6, 4)
    assert k_nope.shape == (6, 4, 8) and v.shape == (6, 4, 6)
    out = np.zeros((6, 4 * 6))
    for n in range(4):
        for t in range(6):
            score = np.array([(q_nope[t, n] @ k_nope[j, n]
                               + q_pe[t, n] @ k_pe[j]) / np.sqrt(12)
                              for j in range(t + 1)])
            w = np.exp(score - score.max())
            out[t, n * 6:(n + 1) * 6] = (w / w.sum()) @ v[:t + 1, n]
    want = out @ np.asarray(p["o_proj"], np.float64)
    got = ref._latent_attention(p, s, x, rnd)
    assert np.allclose(np.asarray(got), want, rtol=1e-4, atol=1e-7)
