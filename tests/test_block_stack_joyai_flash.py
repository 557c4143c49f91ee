"""The block stack's `C`, `D` and `G` blocks (models/block_stack.py,
ops/attention.py, ops/routed_experts.py) against the plain reference
(benchmarks/reference/joyai_llm_flash.py), at a small size on the CPU.

Tolerances, and why each: the program in float32 and the reference compute
the same sums in another order (a score as one product over the joined dims
where the reference adds two, attention by query blocks, the experts by
dispatched blocks), so they differ by float32 rounding: 2e-4 relative on
scores, loss and three optimizer steps, 2e-3 of a leaf's norm on gradients
(a sum over 40 positions and a softmax amplify the last bits).  The program
in bfloat16, the precision below, misses the scores' tolerance at least five
times over, which is what makes it a test of the precision the configuration
states.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.jobs.joyai_llm_flash import model_group
from benchmarks.reference import common, joyai_llm_flash as ref
from shifu_tpu.config.schema import BlockStackSpec, ConfigError, JobConfig
from shifu_tpu.models.block_stack import rotate_pairs
from shifu_tpu.ops import attention, routed_experts as rx

SEQ, VOCAB, BATCH = 40, 97, 4

#: the reference's configuration keys at the small size (the benchmark's
#: configuration file has the same keys at the published widths): three
#: layers, the first dense - the blocks `CDCGCG`; the three head widths all
#: different and the two ranks distinct, so that no transposed width passes
CFG = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "vocab_size": VOCAB, "rms_norm_eps": 1e-6,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 6, "rope_theta": 32000000,
    "intermediate_size": 96, "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "hidden_act": "silu",
    "rope_scaling": None, "rope_interleave": True, "moe_layer_freq": 1,
    "attention_bias": False,
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


def test_the_pattern_puts_the_dense_layers_first():
    assert model_group(CFG)["block_stack"]["pattern"] == "CDCGCG"
    assert ref.pattern(dict(CFG, num_hidden_layers=6)) == "CDCGCGCGCGCG"
    assert ref.pattern(dict(CFG, num_hidden_layers=2,
                            first_k_dense_replace=2)) == "CDCD"


def test_initial_weights_are_the_references_bit_for_bit():
    theirs = flat(program(make_job()).params)
    ours = flat(ref.init_params(CFG, 11))
    assert set(ours) == set(theirs)
    for k in ours:
        assert np.array_equal(np.asarray(ours[k]), np.asarray(theirs[k])), k
    # plain norms, the two inside the low-rank projections among them
    for k in ("norm_f", "block0/norm", "block0/q_a_norm", "block2/kv_a_norm",
              "block1/norm", "block3/norm"):
        assert np.all(np.asarray(ours[k]) == 1.0), k
    assert ours["block0/q_a_norm"].shape == (24,)
    assert ours["block0/kv_a_proj"].shape == (64, 16 + 4)
    assert ours["block0/kv_b_proj"].shape == (16, 4 * (8 + 6))
    assert ours["block3/shared/w_down"].shape == (32, 64)


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


def test_the_interleaved_rotary_term_is_the_references():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 12, 3, 8)), jnp.float32)
    got = rotate_pairs(x, 32e6)
    want = jnp.stack([ref.rope(x[i], 32e6) for i in range(2)])
    assert _rel(got, want) < 1e-6
    assert np.array_equal(np.asarray(got[:, 0]), np.asarray(x[:, 0]))
    # neighbours turn together, at the pair's own rate: position 1 of a
    # vector that is 1 on dim 2i alone lands on dims 2i and 2i + 1
    for i in range(4):
        one = jnp.zeros((1, 2, 1, 8)).at[..., 2 * i].set(1.0)
        angle = 32e6 ** (-2.0 * i / 8)
        turned = np.asarray(rotate_pairs(one, 32e6))[0, 1, 0]
        want = np.zeros(8)
        want[2 * i], want[2 * i + 1] = np.cos(angle), np.sin(angle)
        assert np.allclose(turned, want, atol=1e-6)


def test_a_common_shift_of_positions_leaves_the_scores_unchanged():
    """q_i . k_j after the rotary term depends on i - j alone: the products
    of positions 3.. of a row are those of 0.. of the same vectors, with
    the one key every head shares."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 9, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 9, 1, 8)), jnp.float32)

    def scores(q, k):
        return jnp.einsum("bihd,bjd->bhij", rotate_pairs(q, 100.0),
                          rotate_pairs(k, 100.0)[:, :, 0])

    shifted = scores(q, k)[:, :, 3:, 3:]
    unshifted = scores(q[:, 3:], k[:, 3:])
    assert _rel(shifted, unshifted) < 1e-5
    # and it is no identity: position matters
    assert _rel(scores(q, k)[:, :, 1, 0],
                jnp.einsum("bhd,bd->bh", q[:, 1], k[:, 0, 0])) > 1e-2


def _latent_inputs(rng, b, t, h, dn, dr, dv):
    shapes = ((b, t, h, dn), (b, t, h, dr), (b, t, h, dn), (b, t, dr),
              (b, t, h, dv))
    return tuple(jnp.asarray(rng.normal(size=s), jnp.float32) for s in shapes)


def _materialised(q_nope, q_pe, k_nope, k_pe, v):
    """The same layer written with a head's keys materialised - the shared
    rotary key copied a head - through a dense causal softmax."""
    h = q_nope.shape[2]
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.stack([k_pe] * h, axis=2)], -1)
    t = q.shape[1]
    scores = jnp.einsum("bihd,bjhd->bhij", q, k,
                        precision="highest") / np.sqrt(q.shape[-1])
    w = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores,
                                 -jnp.inf), axis=-1)
    return jnp.einsum("bhij,bjhd->bihd", w, v, precision="highest")


@pytest.mark.parametrize("query_block", [1024, 8])
def test_latent_attention_is_the_layer_with_its_keys_materialised(
        query_block, monkeypatch):
    """Forward and gradient, in one block of queries and in three."""
    monkeypatch.setattr(attention, "CAUSAL_QUERY_BLOCK", query_block)
    rng = np.random.default_rng(6)
    args = _latent_inputs(rng, 2, 24, 3, 8, 4, 6)
    probe = jnp.asarray(rng.normal(size=(2, 24, 3, 6)), jnp.float32)

    def loss(fn, *a):
        return jnp.sum(probe * fn(*a))

    got, got_grads = jax.value_and_grad(
        functools.partial(loss, attention.causal_latent_attention),
        argnums=tuple(range(5)))(*args)
    want, want_grads = jax.value_and_grad(
        functools.partial(loss, _materialised), argnums=tuple(range(5)))(*args)
    assert attention.causal_latent_attention(*args).shape == (2, 24, 3, 6)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want)) + 1e-5
    for gg, ww in zip(got_grads, want_grads):
        assert gg.shape == ww.shape and _rel(gg, ww) < 1e-5


def test_with_equal_key_and_value_widths_it_is_causal_gqa_bit_for_bit():
    rng = np.random.default_rng(8)
    q_nope, q_pe, k_nope, k_pe, v = _latent_inputs(rng, 2, 16, 4, 8, 4, 12)
    got = attention.causal_latent_attention(q_nope, q_pe, k_nope, k_pe, v)
    k_all = jnp.broadcast_to(k_pe[:, :, None], (2, 16, 4, 4))
    want = attention.causal_gqa(jnp.concatenate([q_nope, q_pe], -1),
                                jnp.concatenate([k_nope, k_all], -1), v)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # and in the compute dtype of the cell, where a second product added to
    # the first would round another way
    low = tuple(a.astype(jnp.bfloat16) for a in (q_nope, q_pe, k_nope, k_pe,
                                                 v))
    got = attention.causal_latent_attention(*low)
    want = attention.causal_gqa(
        jnp.concatenate([low[0], low[1]], -1),
        jnp.concatenate([low[2], k_all.astype(jnp.bfloat16)], -1), low[4])
    assert got.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


def test_sigmoid_routing_renormalises_and_scales_the_chosen():
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    experts, weights = rx.route_topk(logits, 3, 2.5)
    want_e, want_w = ref.route({"router": jnp.eye(16)},
                               {"top_k": 3, "scale": 2.5}, logits)
    assert np.array_equal(np.asarray(experts), np.asarray(want_e))
    assert np.allclose(np.asarray(weights), np.asarray(want_w), atol=1e-6)
    assert np.allclose(np.asarray(weights).sum(-1), 2.5, atol=1e-5)


def _gated_weights(rng, held, hidden=16, f=24):
    return tuple(jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
                 for shape in ((held, hidden, f), (held, hidden, f),
                               (held, f, hidden)))


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _routed(x, logits, wg, wu, wd, first, held, k=4, rows=8):
    experts, weights = rx.route_topk(logits, k, 2.5)
    plan = rx.plan_dispatch(experts, first, held, rows)
    slot = plan["row_slot"]
    row_weight = jnp.append(weights.reshape(-1), 0.0)[slot]
    out = rx.routed_gated_mlp(x, wg, wu, wd, row_weight, slot // k,
                              plan["block_expert"], plan["live_blocks"],
                              rows)
    return out, plan


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _routed_reference(x, logits, wg, wu, wd, first, held, k=4):
    chosen, experts = jax.lax.top_k(jax.nn.sigmoid(logits), k)
    weights = 2.5 * chosen / jnp.sum(chosen, -1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        out = out + w_e[:, None] * (
            (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return out


def test_routing_under_a_planted_skew_drops_nothing_with_16_held():
    """Every token's first choice is the same held expert: its group is
    many blocks long, the others' short or empty, and every choice on a
    held expert is computed."""
    rng = np.random.default_rng(3)
    t, hidden, n_experts, held = 96, 16, 64, 16
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
    an expert-parallel layer give, with the shared expert and the residual
    counted once, are the uncut reference's layer output."""
    rng = np.random.default_rng(7)
    cfg = dict(CFG, n_routed_experts=32,
               deployment=dict(CFG["deployment"], router_experts=32))
    s = ref._shapes(cfg)
    whole = ref._init_block(s, 5, "G", ("block3",))
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
    # the shared expert once: one more copy of it misses a hundred times over
    assert _rel(total + ref.shared_expert(whole, h, identity), uncut) > 1e-3


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
    held = CFG["n_routed_experts"]
    for e in events:
        assert len(e["layers"]) == 2
        # the G block before the last mixer routes every position, the one
        # after it the last position of each row
        slots = [4 * BATCH * SEQ * 2, 4 * BATCH * 2]
        assert [layer["routed_slots"] for layer in e["layers"]] == slots
        for layer, per_step in zip(e["layers"], (BATCH * SEQ * 2, BATCH * 2)):
            assert layer["tokens_dropped"] == 0
            assert sum(layer["tokens_per_expert"]) == layer["held_slots"]
            rows = rx.default_block_rows(per_step, held)
            assert layer["block_rows"] == rows
            walked = layer["live_blocks"] * rows
            assert layer["held_slots"] <= walked \
                < layer["held_slots"] + 4 * held * rows
    tiers = [r for r in journal.records if r.get("kind") == "overlap_report"]
    assert tiers and all(r["tier"] == "resident" for r in tiers)
    assert all(r["eval_tier"] == "resident" for r in tiers)


def test_the_new_scopes_are_in_the_compiled_programs_metadata():
    import re

    job = make_job(remat=False)
    state = program(job)
    hlo = jax.jit(lambda p, x: state.apply_fn({"params": p}, x)).lower(
        state.params, jnp.zeros((BATCH, SEQ), jnp.float32)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    # a row's part of latent attention lies under the loop over the rows
    for block, scope in (("block0/latent_attention", "q_latent"),
                         ("block0/latent_attention", "kv_latent"),
                         ("block0/latent_attention", "up_proj"),
                         ("block0/latent_attention", "rope"),
                         ("block0/latent_attention", "scores"),
                         ("block0/latent_attention", "o_proj"),
                         ("block1", "dense_mlp"), ("block3/moe", "router"),
                         ("block3/moe", "dispatch"),
                         ("block3/moe", "experts"), ("block3/moe", "shared"),
                         ("block3/moe", "combine")):
        found = re.compile(rf"/{block}/(?:.*/)?{scope}(?:/|$)")
        assert any(found.search(n) for n in names), (block, scope)


WIDTHS = model_group(CFG)["block_stack"]
LEFT_OUT = object()   # a key the group does not give


@pytest.mark.parametrize("change, message", [
    ({"pattern": "CDXG"}, "letters"),
    ({"q_lora_rank": 0}, "'C' block needs"),
    ({"kv_lora_rank": 0}, "'C' block needs"),
    ({"v_head_dim": 0}, "'C' block needs"),
    ({"qk_rope_head_dim": 5}, "must be even"),
    ({"intermediate_size": 0}, "'D' block needs"),
    ({"n_shared_experts": LEFT_OUT}, "'G' block needs"),
    ({"num_experts_per_tok": 17}, "exceeds"),
    ({"first_expert_held": 12}, "within"),
    ({"moe_intermediate_size": 0}, "'G' block needs"),
])
def test_validate_refuses_a_pattern_whose_letters_lack_their_widths(
        change, message):
    widths = {k: v for k, v in {**WIDTHS, **change}.items()
              if v is not LEFT_OUT}
    with pytest.raises(ConfigError, match=message):
        BlockStackSpec(**widths).validate()
    BlockStackSpec(**WIDTHS).validate()
    # an explicit 0 is a layer with no shared expert, not a missing key
    BlockStackSpec(**{**WIDTHS, "n_shared_experts": 0}).validate()


def test_a_published_key_the_blocks_hold_otherwise_is_refused():
    from benchmarks.harness import BenchError

    with pytest.raises(BenchError, match="rope_interleave = False"):
        model_group(dict(CFG, rope_interleave=False))
    with pytest.raises(BenchError, match="num_key_value_heads"):
        model_group(dict(CFG, num_key_value_heads=2))
