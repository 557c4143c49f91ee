"""The block stack's `L`, `A` and `F` blocks (models/block_stack.py,
ops/gated_delta.py, ops/routed_experts.py) against the plain reference
(benchmarks/reference/qwen3_next.py), at a small size on the CPU.

Tolerances, and why each: the program in float32 and the reference compute
the same sums in another order (the delta rule by chunks through the
inverse of a triangular system, attention by query blocks, the experts by
dispatched blocks), so they differ by float32 rounding: 2e-4 relative on
scores, loss and three optimizer steps, 2e-3 of a leaf's norm on gradients
(a sum over 80 positions and the inverse amplify the last bits).  The
program in bfloat16, the precision below, misses the scores' tolerance at
least five times over, which is what makes it a test of the precision the
configuration states.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.jobs.qwen3_next import model_group
from benchmarks.reference import common, qwen3_next as ref
from shifu_tpu.config.schema import BlockStackSpec, ConfigError, JobConfig
from shifu_tpu.models.block_stack import rotate
from shifu_tpu.ops import routed_experts as rx
from shifu_tpu.ops.gated_delta import gated_delta_rule
from shifu_tpu.ops.ssd import causal_conv1d

SEQ, VOCAB, BATCH = 80, 97, 4       # 80: two chunks of the rule, one padded

#: the reference's configuration keys at the small size (the benchmark's
#: configuration file has the same keys at the published widths): two
#: layers, every other one full attention - the blocks `LFAF`
CFG = {
    "num_hidden_layers": 2, "full_attention_interval": 2, "hidden_size": 64,
    "vocab_size": VOCAB, "rms_norm_eps": 1e-6,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 48,
    "hidden_act": "silu", "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "rope_scaling": None, "use_sliding_window": False,
    "deployment": {"router_experts": 16, "first_expert_held": 0},
}


def make_job(cfg=CFG, compute="float32", seq=SEQ, batch=BATCH, epochs=1,
             remat=True, seed=11, **block_stack):
    columns = [{"index": 0, "name": "target", "is_target": True},
               {"index": 1, "name": "wgt", "is_weight": True}]
    columns += [{"index": 2 + i, "name": f"t{i}", "is_selected": True,
                 "is_categorical": True, "vocab_size": cfg["vocab_size"]}
                for i in range(seq)]
    return JobConfig.from_dict({
        "schema": {"columns": columns, "target_index": 0, "weight_index": 1,
                   "selected_indices": list(range(2, 2 + seq))},
        "data": {"batch_size": batch, "valid_ratio": 0.1, "shuffle": False,
                 "staged": True, "drop_remainder": True},
        "model": {"model_type": "block_stack", "hidden_nodes": [],
                  "activations": [], "compute_dtype": compute,
                  "remat": remat,
                  "block_stack": {**model_group(cfg)["block_stack"],
                                  **block_stack}},
        "train": {"epochs": epochs, "loss": "weighted_mse", "seed": seed,
                  "optimizer": {"name": "adadelta", "learning_rate": 0.01}},
    }).validate()


def make_rows(n, seed=0, seq=SEQ, vocab=VOCAB):
    rng = np.random.default_rng(seed)
    return {"features": rng.integers(0, vocab, (n, seq)).astype(np.float32),
            "target": rng.integers(0, 2, (n, 1)).astype(np.float32),
            "weight": rng.uniform(0.5, 2.0, (n, 1)).astype(np.float32)}


def program(job, seq=SEQ):
    from shifu_tpu.train.loop import init_state
    return init_state(job, seq)


def flat(tree):
    from benchmarks.compare import flatten
    return flatten(tree)


def identity(x):
    return x.astype(jnp.float32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_the_pattern_spells_the_period():
    assert model_group(CFG)["block_stack"]["pattern"] == "LFAF"
    assert ref.pattern(dict(CFG, num_hidden_layers=4,
                            full_attention_interval=4)) == "LFLFLFAF"


def test_initial_weights_are_the_references_bit_for_bit():
    theirs = flat(program(make_job()).params)
    ours = flat(ref.init_params(CFG, 11))
    assert set(ours) == set(theirs)
    for k in ours:
        assert np.array_equal(np.asarray(ours[k]), np.asarray(theirs[k])), k
    # zero-centred norms start at nought, the gated norm at one
    assert not np.any(np.asarray(ours["norm_f"]))
    assert not np.any(np.asarray(ours["block2/q_norm"]))
    assert np.all(np.asarray(ours["block0/gate_norm"]) == 1.0)


def _ref_loss(params, rows, forward=None):
    forward = forward or ref.make_forward(CFG)
    return common.weighted_mse(
        forward(params, jnp.asarray(rows["features"]), identity),
        jnp.asarray(rows["target"]), jnp.asarray(rows["weight"]))


@pytest.mark.parametrize("remat", [True, False])
def test_scores_loss_and_every_gradient_leaf_match_the_reference(remat):
    from shifu_tpu.train.step import _catching_counters, make_loss_fn

    job = make_job(remat=remat)
    state = program(job)
    rows = make_rows(BATCH)
    batch = {k: jnp.asarray(v) for k, v in rows.items()}
    params0 = ref.init_params(CFG, 11)

    scores = jax.jit(lambda p, x: state.apply_fn({"params": p}, x))(
        state.params, batch["features"])
    want = jax.jit(lambda p, x: ref.make_forward(CFG)(p, x, identity))(
        params0, batch["features"])
    assert _rel(scores, want) < 2e-4

    loss_fn = _catching_counters(make_loss_fn(job))
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, state.apply_fn, b, None), has_aux=True))(
        state.params, batch)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(_ref_loss))(
        params0, {k: jnp.asarray(v) for k, v in rows.items()})
    assert abs(float(loss) - float(ref_loss)) < 2e-4 * float(ref_loss)
    got, exp = flat(grads), flat(ref_grads)
    assert set(got) == set(exp)
    for k in exp:
        assert _rel(got[k], exp[k]) < 2e-3, k
    assert int(jnp.sum(counters["moe"]["tokens_dropped"])) == 0

    # the precision below the one stated fails the same tolerance
    low = program(make_job(compute="bfloat16", remat=remat))
    low_scores = jax.jit(lambda p, x: low.apply_fn({"params": p}, x))(
        low.params, batch["features"])
    assert _rel(low_scores, want) > 1e-3


def test_three_optimizer_steps_match_the_reference():
    from shifu_tpu.train.step import make_train_step

    job = make_job()
    state = program(job)
    step = make_train_step(job, donate=False)
    params = ref.init_params(CFG, 11)
    slots = common.adadelta_init(params)
    ref_grad = jax.jit(jax.value_and_grad(_ref_loss))
    for i in range(3):
        rows = make_rows(BATCH, seed=i)
        state, metrics = step(state, {k: jnp.asarray(v)
                                      for k, v in rows.items()})
        loss, grads = ref_grad(params, {k: jnp.asarray(v)
                                        for k, v in rows.items()})
        params, slots = common.adadelta_update(params, grads, slots, 0.01)
        assert abs(float(metrics["loss"]) - float(loss)) < 2e-4 * float(loss)
    got, exp, start = (flat(state.params), flat(params),
                       flat(ref.init_params(CFG, 11)))
    for k in exp:
        moved = np.asarray(exp[k]) - np.asarray(start[k])
        assert _rel(np.asarray(got[k]) - np.asarray(start[k]), moved) \
            < 2e-3, k


def _delta_inputs(rng, b, t, hk, hv, dk, dv):
    q = rng.normal(size=(b, t, hk, dk))
    k = rng.normal(size=(b, t, hk, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, t, hv, dv))
    log_alpha = -rng.uniform(0.0, 1.0, (b, t, hv))
    beta = rng.uniform(0.05, 0.95, (b, t, hv))
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q, k, v, log_alpha, beta))


@pytest.mark.parametrize("length", [32, 24, 13, 5])
def test_chunked_delta_rule_is_the_sequential_recurrence(length):
    """Forward and gradient, at lengths that are and are not multiples of
    the chunk (8)."""
    rng = np.random.default_rng(length)
    b, hk, hv, dk, dv = 2, 2, 4, 8, 6
    args = _delta_inputs(rng, b, length, hk, hv, dk, dv)
    probe = jnp.asarray(rng.normal(size=(b, length, hv, dv)), jnp.float32)

    def chunked(q, k, v, log_alpha, beta):
        return jnp.sum(probe * gated_delta_rule(q, k, v, log_alpha, beta,
                                                chunk=8))

    def sequential(q, k, v, log_alpha, beta):
        return jnp.sum(probe * jnp.stack([
            ref.delta_recurrence(q[i], k[i], v[i], jnp.exp(log_alpha[i]),
                                 beta[i]) for i in range(b)]))

    got, got_grads = jax.jit(jax.value_and_grad(
        chunked, argnums=tuple(range(5))))(*args)
    want, want_grads = jax.jit(jax.value_and_grad(
        sequential, argnums=tuple(range(5))))(*args)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want)) + 1e-4
    for gg, ww in zip(got_grads, want_grads):
        assert _rel(gg, ww) < 1e-4


def test_the_delta_rule_overwrites_a_key_it_has_seen():
    """What the rule is for: with no decay and beta 1, writing a second
    value at the same key replaces the first, where a plain linear
    attention would add the two."""
    key = jnp.zeros((1, 2, 1, 4), jnp.float32).at[..., 0].set(1.0)
    v = jnp.asarray([[[[1.0, 2.0]], [[5.0, -3.0]]]], jnp.float32)
    out = gated_delta_rule(key, key, v, jnp.zeros((1, 2, 1)),
                           jnp.ones((1, 2, 1)))
    assert np.allclose(np.asarray(out[0, :, 0]), [[1.0, 2.0], [5.0, -3.0]],
                       atol=1e-6)


def test_the_rotary_term_is_the_references():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 12, 3, 16)), jnp.float32)
    got = rotate(x, 1e7, 4)
    want = jnp.stack([ref.rope(x[i], 1e7, 4) for i in range(2)])
    assert _rel(got, want) < 1e-6
    assert np.array_equal(np.asarray(got[..., 4:]), np.asarray(x[..., 4:]))
    assert np.array_equal(np.asarray(got[:, 0]), np.asarray(x[:, 0]))


def test_a_common_shift_of_positions_leaves_the_scores_unchanged():
    """q_i . k_j after the rotary term depends on i - j alone: the products
    of positions 3.. of a row are those of 0.. of the same vectors."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 9, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 9, 2, 8)), jnp.float32)

    def scores(q, k):
        return jnp.einsum("bihd,bjhd->bhij", rotate(q, 100.0, 8),
                          rotate(k, 100.0, 8))

    shifted = scores(q, k)[:, :, 3:, 3:]
    unshifted = scores(q[:, 3:], k[:, 3:])
    assert _rel(shifted, unshifted) < 1e-5
    # and it is no identity: position matters
    assert _rel(scores(q, k)[:, :, 1, 0],
                jnp.einsum("bhd,bhd->bh", q[:, 1], k[:, 0])) > 1e-2


def test_the_convolution_without_a_bias_is_the_one_with_a_zero_bias():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 7, 5)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 5)), jnp.float32)
    assert np.array_equal(np.asarray(causal_conv1d(x, w)),
                          np.asarray(causal_conv1d(x, w, jnp.zeros((5,)))))


def test_softmax_routing_renormalises_the_chosen():
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    experts, weights = rx.route_softmax_topk(logits, 3)
    want_e, want_w = ref.route({"router": jnp.eye(16)}, {"top_k": 3}, logits)
    assert np.array_equal(np.asarray(experts), np.asarray(want_e))
    assert np.allclose(np.asarray(weights), np.asarray(want_w), atol=1e-6)
    assert np.allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    # the same as a softmax over the chosen logits alone
    chosen = jnp.take_along_axis(logits, experts, axis=-1)
    assert np.allclose(np.asarray(weights),
                       np.asarray(jax.nn.softmax(chosen, -1)), atol=1e-6)


def _gated_weights(rng, held, hidden=16, f=24):
    return tuple(jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
                 for shape in ((held, hidden, f), (held, hidden, f),
                               (held, f, hidden)))


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _routed(x, logits, wg, wu, wd, first, held, k=4, rows=8):
    experts, weights = rx.route_softmax_topk(logits, k)
    plan = rx.plan_dispatch(experts, first, held, rows)
    slot = plan["row_slot"]
    row_weight = jnp.append(weights.reshape(-1), 0.0)[slot]
    out = rx.routed_gated_mlp(x, wg, wu, wd, row_weight, slot // k,
                              plan["block_expert"], plan["live_blocks"],
                              rows)
    return out, plan


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _routed_reference(x, logits, wg, wu, wd, first, held, k=4):
    chosen, experts = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    weights = chosen / jnp.sum(chosen, -1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        out = out + w_e[:, None] * (
            (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return out


def test_routing_under_a_planted_skew_drops_nothing_with_32_held():
    """Every token's first choice is the same held expert: its group is
    many blocks long, the others' short or empty, and every choice on a
    held expert is computed."""
    rng = np.random.default_rng(3)
    t, hidden, n_experts, held = 96, 16, 64, 32
    x = jnp.asarray(rng.normal(size=(t, hidden)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(t, n_experts)), jnp.float32)
    logits = logits.at[:, 5].set(9.0)
    weights = _gated_weights(rng, held)

    def loss(x, logits, wg, wu, wd, fn):
        out = fn(x, logits, wg, wu, wd, 0, held)
        return jnp.sum(jnp.sin(out[0] if isinstance(out, tuple) else out))

    out, plan = _routed(x, logits, *weights, 0, held)
    assert int(plan["tokens_per_expert"][5]) == t
    assert int(plan["held_slots"]) == int(plan["dispatched_slots"])
    # blocks of 8 rows: expert 5 alone fills 12, and no block is lost
    assert int(plan["live_blocks"]) >= 12 + int(
        np.count_nonzero(np.asarray(plan["tokens_per_expert"])) - 1)
    assert _rel(out, _routed_reference(x, logits, *weights, 0, held)) < 1e-5
    got = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                  static_argnums=5)(x, logits, *weights, _routed)
    want = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                   static_argnums=5)(x, logits, *weights, _routed_reference)
    for gg, ww in zip(got, want):
        assert _rel(gg, ww) < 1e-4


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Guide section 4's share test: the routed parts that the 16 shares of
    an expert-parallel layer give, with the gated shared expert and the
    residual counted once, are the uncut reference's layer output."""
    rng = np.random.default_rng(7)
    cfg = dict(CFG, num_experts=32,
               deployment=dict(CFG["deployment"], router_experts=32))
    s = ref._shapes(cfg)
    whole = ref._init_block(s, 5, "F", ("block1",))
    x = jnp.asarray(rng.normal(size=(40, s["hidden"])), jnp.float32)
    h = ref.norm(x, whole["norm"], s["eps"])
    uncut = x + ref._experts(whole, s, h, identity, True)

    shares, held = 16, 2
    total = x + ref.shared_expert(whole, h, identity)
    logits = h @ whole["router"]
    counted = 0
    for r in range(shares):
        mine = slice(r * held, (r + 1) * held)
        part, plan = _routed(h, logits, whole["experts/w_gate"][mine],
                             whole["experts/w_up"][mine],
                             whole["experts/w_down"][mine], r * held, held,
                             s["top_k"])
        total = total + part
        counted += int(plan["held_slots"])
    assert counted == 40 * s["top_k"]       # every choice on one share
    assert _rel(total, uncut) < 1e-5


def test_train_journals_the_experts_load_and_the_blocks_walked():
    from shifu_tpu import obs
    from shifu_tpu.data.pipeline import TabularDataset
    from shifu_tpu.train import train

    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    try:
        # float32: evaluate() pads the 3 valid rows of these narrow rows to
        # its floor of 4,096, which the CPU takes minutes over in bfloat16
        job = make_job(epochs=2)
        res = train(job, TabularDataset(**make_rows(4 * BATCH)),
                    TabularDataset(**make_rows(3, seed=9)),
                    console=lambda s: None)
    finally:
        obs.set_journal(None)
    assert len(res.history) == 2
    assert all(np.isfinite(m.train_error) and np.isfinite(m.valid_error)
               for m in res.history)
    events = [r for r in journal.records if r.get("kind") == "moe"]
    assert [e["epoch"] for e in events] == [0, 1]
    held = CFG["num_experts"]
    for e in events:
        assert len(e["layers"]) == 2
        # the F block before the last mixer routes every position, the one
        # after it the last position of each row
        slots = [4 * BATCH * SEQ * 2, 4 * BATCH * 2]
        assert [layer["routed_slots"] for layer in e["layers"]] == slots
        for layer, per_step in zip(e["layers"], (BATCH * SEQ * 2, BATCH * 2)):
            assert layer["tokens_dropped"] == 0
            assert sum(layer["tokens_per_expert"]) == layer["held_slots"]
            # the block size is carried, not summed over the 4 steps; the
            # live blocks hold every held choice and under a block of
            # padding an expert a step
            rows = rx.default_block_rows(per_step, held)
            assert layer["block_rows"] == rows
            walked = layer["live_blocks"] * rows
            assert layer["held_slots"] <= walked \
                < layer["held_slots"] + 4 * held * rows
    tiers = [r for r in journal.records if r.get("kind") == "overlap_report"]
    assert tiers and all(r["tier"] == "resident" for r in tiers)
    assert all(r["eval_tier"] == "resident" for r in tiers)


WIDTHS = model_group(CFG)["block_stack"]


@pytest.mark.parametrize("change, message", [
    ({"pattern": "LFXF"}, "letters"),
    ({"linear_num_key_heads": 0}, "'L' block needs"),
    ({"linear_num_value_heads": 3}, "multiple of linear_num_key_heads"),
    ({"rope_theta": 0}, "'A' block needs"),
    ({"partial_rotary_factor": 0.3125}, "even count"),
    ({"partial_rotary_factor": 1.5}, "even count"),
    ({"shared_expert_intermediate_size": 0}, "'F' block needs"),
    ({"num_experts_per_tok": 17}, "exceeds"),
    ({"first_expert_held": 12}, "within"),
])
def test_validate_refuses_a_pattern_whose_letters_lack_their_widths(
        change, message):
    with pytest.raises(ConfigError, match=message):
        BlockStackSpec(**{**WIDTHS, **change}).validate()
    BlockStackSpec(**WIDTHS).validate()


def test_a_published_key_the_blocks_hold_otherwise_is_refused():
    from benchmarks.harness import BenchError

    with pytest.raises(BenchError, match="norm_topk_prob = False"):
        model_group(dict(CFG, norm_topk_prob=False))
