"""The block stack's `V`, `Q`, `D` and shared-less `G` blocks
(models/block_stack.py, ops/routed_experts.py) against the plain reference
(benchmarks/reference/lfm2_moe.py), at a small size on the CPU.

Tolerances, and why each: the program in float32 and the reference compute
the same sums in another order (attention by query blocks, the experts by
dispatched blocks, the convolution's taps in the compute dtype's order), so
they differ by float32 rounding: 2e-4 relative on scores, loss and three
optimizer steps, 2e-3 of a leaf's norm on gradients (a sum over 40
positions and a softmax amplify the last bits), 1e-5 on one block, whose
output has no softmax or long sum to amplify them.  The program in
bfloat16, the precision below, misses the scores' tolerance at least five
times over, which is what makes it a test of the precision the
configuration states.
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.jobs.lfm2_moe import model_group
from benchmarks.reference import common, lfm2_moe as ref
from shifu_tpu.config.schema import BlockStackSpec, ConfigError, JobConfig
from shifu_tpu.models import block_stack as bs
from shifu_tpu.ops import routed_experts as rx

SEQ, VOCAB, BATCH = 40, 97, 4

#: the reference's configuration keys at the small size (the benchmark's
#: configuration file has the same keys at the published widths): five
#: layers, the first dense, one attention layer - the blocks `VDQGVGVGVG`,
#: the published cut's; 8 of 16 experts held, 4 a token
CFG = {
    "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "hidden_size": 64, "vocab_size": VOCAB, "norm_eps": 1e-5,
    "conv_L_cache": 3, "conv_bias": False,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "rope_theta": 1000000, "intermediate_size": 96,
    "num_experts": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "routed_scaling_factor": 1,
    "norm_topk_prob": True, "use_expert_bias": True,
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


def _block(kind, x, compute="float32", seed=3, **block_stack):
    """(one block of `kind` applied to x, its parameters) at CFG's widths."""
    spec = make_job(compute=compute, **block_stack).model
    module = bs._KINDS[kind](spec=spec)
    variables = module.init(jax.random.PRNGKey(seed), x)
    return module.apply(variables, x), variables["params"]


def test_the_pattern_puts_the_dense_layers_first_and_reads_layer_types():
    assert model_group(CFG)["block_stack"]["pattern"] == "VDQGVGVGVG"
    assert ref.pattern(dict(CFG, num_dense_layers=2)) == "VDQDVGVGVG"
    with pytest.raises(ValueError, match="layer_types"):
        ref.pattern(dict(CFG, num_hidden_layers=4))
    group = model_group(CFG)["block_stack"]
    assert group["head_dim"] == 16 and group["n_shared_experts"] == 0
    assert (group["n_routed_experts"], group["experts_held"]) == (16, 8)


def test_the_count_holds_the_program_and_the_published_cut():
    """`counts/lfm2_moe.py` counts the program's parameters at the small
    size, and at the configuration file's widths gives the cut's table:
    507,822,209 parameters, 8.12 GB with a gradient and two slots."""
    import json
    import os

    from benchmarks.counts import lfm2_moe as counts

    small = dict(CFG, num_categorical=SEQ)
    leaves = jax.tree.leaves(program(make_job()).params)
    assert sum(leaf.size for leaf in leaves) == sum(counts.params(small))
    path = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                        "configs", "lfm2_8b_a1b_ep4.json")
    with open(path) as f:
        cut = json.load(f)
    assert counts.block_params(cut) == {"V": 16_785_408, "Q": 10_487_936,
                                        "D": 44_042_240, "G": 88_147_968}
    assert counts.params(cut) == (474_267_777, 33_554_432)
    assert sum(counts.params(cut)) == 507_822_209


def test_initial_weights_are_the_references_bit_for_bit():
    theirs = flat(program(make_job()).params)
    ours = flat(ref.init_params(CFG, 11))
    assert set(ours) == set(theirs)
    for k in ours:
        assert np.array_equal(np.asarray(ours[k]), np.asarray(theirs[k])), k
    # plain norms, q's and k's a head wide among them; no shared expert
    for k in ("norm_f", "block0/norm", "block2/q_norm", "block2/k_norm",
              "block1/norm", "block3/norm"):
        assert np.all(np.asarray(ours[k]) == 1.0), k
    assert ours["block2/q_norm"].shape == (16,)
    assert ours["block0/in_proj"].shape == (64, 3 * 64)
    assert ours["block0/conv_w"].shape == (3, 64)
    assert ours["block2/k_proj"].shape == (64, 2 * 16)
    assert not [k for k in ours if "shared" in k]


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


def test_the_short_convolution_is_a_loop_over_positions():
    """The `V` block against the published equations written a position at
    a time in float64: the gates B * X, three taps over positions t-2..t
    (none before the row's start), the gate C, the output projection."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 9, 64)), jnp.float32)
    out, p = _block("V", x)
    xs = np.asarray(x, np.float64)
    w_in, w_out, taps = (np.asarray(p[k], np.float64)
                         for k in ("in_proj", "out_proj", "conv_w"))
    want = np.empty_like(xs)
    for b in range(2):
        h = xs[b] / np.sqrt(np.mean(xs[b] ** 2, -1, keepdims=True) + 1e-5)
        gates = h @ w_in
        z = gates[:, :64] * gates[:, 128:]
        for t in range(9):
            conv = sum(taps[j] * z[t - 2 + j] for j in range(3)
                       if t - 2 + j >= 0)
            want[b, t] = xs[b, t] + (gates[t, 64:128] * conv) @ w_out
    assert _rel(out, want) < 1e-5
    # and it is causal: a change at position 5 moves nothing before it
    moved, _ = _block("V", x.at[:, 5].add(1.0))
    assert np.array_equal(np.asarray(moved[:, :5]), np.asarray(out[:, :5]))
    assert not np.allclose(np.asarray(moved[:, 5:8]), np.asarray(out[:, 5:8]))
    # the reference's own convolution is the same block
    s = ref._shapes(CFG)
    ref_out = jnp.stack([x[b] + ref.short_conv(
        p, s, ref.norm(x[b], p["norm"], s["eps"]), identity)
        for b in range(2)])
    assert _rel(out, ref_out) < 1e-5


def test_the_attention_kind_is_the_references():
    """The `Q` block: q and k normed a head at a time, the rotary term on
    all of a head's dims (dims i and i + D/2 together), 2 query heads a
    key-value head, against the reference's dense masked softmax."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 24, 64)), jnp.float32)
    out, p = _block("Q", x)
    s = ref._shapes(CFG)
    want = jnp.stack([x[b] + ref._attention(
        p, s, ref.norm(x[b], p["norm"], s["eps"]), identity)
        for b in range(2)])
    assert _rel(out, want) < 1e-5
    # the rotary term is the program's own `rotate` over every dim
    q = jnp.asarray(rng.normal(size=(1, 12, 3, 16)), jnp.float32)
    assert _rel(bs.rotate(q, 1e6, 16), ref.rope(q[0], 1e6)[None]) < 1e-6
    # and the norms on q and k matter: doubling q's changes the output
    p2 = dict(p, q_norm=2.0 * p["q_norm"])
    spec = make_job().model
    doubled = bs.NormedAttentionBlock(spec=spec).apply({"params": p2}, x)
    assert _rel(doubled, out) > 1e-3


def test_selection_follows_the_bias_and_the_weights_do_not():
    """A planted expert bias moves which experts are chosen (the k largest
    of s + b), and the chosen weights are the sigmoid scores s renormalised,
    without b: as the reference has it."""
    rng = np.random.default_rng(6)
    logits = jnp.asarray(rng.normal(size=(12, 16)), jnp.float32)
    bias = jnp.zeros(16).at[3].set(5.0).at[7].set(-5.0)
    experts, weights = rx.route_topk(logits, 4, 1.0, bias)
    want_e, want_w = ref.route({"router": jnp.eye(16)},
                               {"top_k": 4, "scale": 1.0}, logits, bias)
    assert np.array_equal(np.asarray(experts), np.asarray(want_e))
    assert np.allclose(np.asarray(weights), np.asarray(want_w), atol=1e-6)
    e = np.asarray(experts)
    assert (e == 3).any(axis=1).all() and not (e == 7).any()
    s = np.asarray(jax.nn.sigmoid(logits))
    chosen = np.take_along_axis(s, e, axis=1)
    assert np.allclose(np.asarray(weights), chosen / chosen.sum(1, keepdims=True),
                       atol=1e-6)
    # no bias is the bias at zero
    plain = rx.route_topk(logits, 4, 1.0)
    zero = rx.route_topk(logits, 4, 1.0, jnp.zeros(16))
    assert np.array_equal(np.asarray(plain[0]), np.asarray(zero[0]))
    assert np.allclose(np.asarray(plain[1]), np.asarray(zero[1]))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Guide section 4's share test: the four ranks of a 4-way
    expert-parallel layer (experts 0-1, 2-3, 4-5, 6-7 of 8) each give the
    routed part of their own experts, and with the residual counted once
    they add up to the uncut reference's layer output.  There is no shared
    expert to count once."""
    rng = np.random.default_rng(7)
    cfg = dict(CFG, num_experts=8,
               deployment=dict(CFG["deployment"], router_experts=8))
    s = ref._shapes(cfg)
    whole = ref._init_block(s, 5, "G", ("block3",))
    x = jnp.asarray(rng.normal(size=(40, s["hidden"])), jnp.float32)
    uncut_spec = make_job(cfg).model

    def layer(params, first, held):
        spec = dataclasses.replace(
            uncut_spec, block_stack=dataclasses.replace(
                uncut_spec.block_stack, experts_held=held,
                first_expert_held=first))
        mine = slice(first, first + held)
        p = dict(params, **{k: params[k][mine] for k in params
                            if k.startswith("experts/")})
        return bs.SigmoidGatedExpertsBlock(spec=spec).apply({"params": p},
                                                            x[None])

    uncut, counts = layer(whole, 0, 8)
    h = ref.norm(x, whole["norm"], s["eps"])
    assert _rel(uncut[0], x + ref._experts(whole, s, h, identity, True)) \
        < 1e-5
    assert int(counts["tokens_unrouted"]) == 0     # every expert is held
    total, counted, unrouted = x, 0, []
    for r in range(4):
        out, c = layer(whole, 2 * r, 2)
        total = total + (out[0] - x)
        counted += int(c["held_slots"])
        unrouted.append(int(c["tokens_unrouted"]))
    assert counted == 40 * s["top_k"]       # every choice on one share
    assert _rel(total, uncut[0]) < 1e-5
    # a token's layer output is x exactly where none of its choices is held
    experts, _ = ref.route(whole, s, h)
    for r in range(4):
        none_held = ~np.isin(np.asarray(experts), [2 * r, 2 * r + 1]).any(1)
        assert unrouted[r] == int(none_held.sum())
        out, _ = layer(whole, 2 * r, 2)
        assert np.array_equal(np.asarray(out[0])[none_held],
                              np.asarray(x)[none_held])


def test_train_journals_the_unrouted_tokens_and_the_metric_reads_them():
    from benchmarks import harness
    from shifu_tpu import obs
    from shifu_tpu.data.pipeline import TabularDataset
    from shifu_tpu.train import train

    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    try:
        # float32: evaluate() pads the 3 valid rows of these narrow rows to
        # its floor of 4,096, which the CPU takes minutes over in bfloat16;
        # 2 of 16 experts held, so that many tokens choose none of them
        job = make_job(epochs=2, experts_held=2, first_expert_held=6)
        res = train(job, TabularDataset(**make_rows(4 * BATCH)),
                    TabularDataset(**make_rows(3, seed=9)),
                    console=lambda s: None)
    finally:
        obs.set_journal(None)
    assert all(np.isfinite(m.train_error) for m in res.history)
    events = [r for r in journal.records if r.get("kind") == "moe"]
    assert [e["epoch"] for e in events] == [0, 1]
    for e in events:
        assert len(e["layers"]) == 4
        # three G blocks route every position, the last one a position a row
        assert [layer["tokens"] for layer in e["layers"]] == \
            [4 * BATCH * SEQ] * 3 + [4 * BATCH]
        for layer in e["layers"]:
            assert layer["tokens_dropped"] == 0
            assert 0 < layer["tokens_unrouted"] < layer["tokens"]
            assert layer["held_slots"] <= 4 * (layer["tokens"]
                                               - layer["tokens_unrouted"])
    share = harness.load_metric("moe_unrouted_share").read(
        {"journal": journal.records})
    full = [layer for e in events for layer in e["layers"][:3]]
    assert share == pytest.approx(
        100.0 * sum(layer["tokens_unrouted"] for layer in full)
        / sum(layer["tokens"] for layer in full))
    # a program whose layers have a shared expert journals no such count
    assert harness.load_metric("moe_unrouted_share").read(
        {"journal": [{"kind": "moe", "layers": [
            {"routed_slots": 8, "held_slots": 2}]}]}) is None


def test_the_new_scopes_are_in_the_compiled_programs_metadata():
    import re

    job = make_job(remat=False)
    state = program(job)
    hlo = jax.jit(lambda p, x: state.apply_fn({"params": p}, x)).lower(
        state.params, jnp.zeros((BATCH, SEQ), jnp.float32)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for block, scope in (("block0/short_conv", "in_proj"),
                         ("block0/short_conv", "conv"),
                         ("block0/short_conv", "out_proj"),
                         ("block2/attention", "qkv"),
                         ("block2/attention", "qk_norm"),
                         ("block2/attention", "rope"),
                         ("block2/attention", "softmax"),
                         ("block2/attention", "o_proj"),
                         ("block1", "dense_mlp"), ("block3/moe", "router"),
                         ("block3/moe", "dispatch"),
                         ("block3/moe", "experts")):
        found = re.compile(rf"/{block}/(?:.*/)?{scope}(?:/|$)")
        assert any(found.search(n) for n in names), (block, scope)
    # no shared expert, so neither its scope nor the combine
    assert not any(re.search(r"/moe/(shared|combine)(/|$)", n)
                   for n in names)


WIDTHS = model_group(CFG)["block_stack"]


@pytest.mark.parametrize("change, message", [
    ({"pattern": "VDQGXG"}, "letters"),
    ({"num_attention_heads": 0}, "'Q' block needs"),
    ({"num_key_value_heads": 0}, "'Q' block needs"),
    ({"head_dim": 0}, "'Q' block needs"),
    ({"rope_theta": 0.0}, "'Q' block needs"),
    ({"head_dim": 15}, "head_dim must be even"),
    ({"num_key_value_heads": 3}, "multiple of num_key_value_heads"),
    ({"hidden_size": 0}, "hidden_size must be positive"),
    ({"moe_intermediate_size": 0}, "'G' block needs"),
    ({"n_shared_experts": -2}, "'G' block needs n_shared_experts"),
])
def test_validate_refuses_the_new_kinds_without_their_widths(change,
                                                             message):
    with pytest.raises(ConfigError, match=message):
        BlockStackSpec(**{**WIDTHS, **change}).validate()
    BlockStackSpec(**WIDTHS).validate()


def test_a_g_block_needs_its_shared_experts_stated_and_takes_none():
    # an absent key is refused, as before the shared-less form; an explicit
    # 0 builds the expert layer with no shared expert
    absent = {k: v for k, v in WIDTHS.items() if k != "n_shared_experts"}
    with pytest.raises(ConfigError, match="'G' block needs n_shared_experts"):
        BlockStackSpec(**absent).validate()
    assert WIDTHS["n_shared_experts"] == 0
    BlockStackSpec(**WIDTHS).validate()


@pytest.mark.parametrize("change, message", [
    ({"conv_bias": True}, "conv_bias = True"),
    ({"conv_L_cache": 4}, "conv_L_cache = 4"),
    ({"norm_topk_prob": False}, "norm_topk_prob = False"),
    ({"expert_bias": 0.5}, "expert bias"),
    ({"layer_types": ["conv", "sliding_attention", "conv", "conv", "conv"]},
     "layer_types"),
])
def test_a_published_key_the_blocks_hold_otherwise_is_refused(change,
                                                              message):
    from benchmarks.harness import BenchError

    with pytest.raises(BenchError, match=message):
        model_group(dict(CFG, **change))


#: sha256 of the StableHLO text of the accepted sequence cells' epoch
#: programs, at `util.tiny_context`'s batch, rows and vocabulary (joyai's
#: also at its CPU widths, `benchmarks/tests/sizes_joyai_llm_flash.py`, which
#: the root conftest puts in its place for this test's `cell`), as the tree
#: before the `V` and `Q` kinds and the shared-less `G` lowered them: those
#: changes leave the accepted cells' programs as they were
EPOCH_PROGRAM_SHA256 = {
    "nemotron3_nano_ep16.train_sequences":
        "5588dd37ebe2e6fa8f27f3f8340b0b45cb49832ee638068810bfe60a7b10362c",
    "qwen3_next_ep16.train_sequences":
        "080dbe2b2e756d613cab15421a7025ca9696b1b3327c9b66de1f77a25b2bc680",
    "joyai_flash_ep16.train_sequences":
        "e6ed91998be9d5caf741d975ef03e16c051bd9ff5e83a3b024068af911a2736f",
}


@pytest.mark.parametrize("cell", sorted(EPOCH_PROGRAM_SHA256))
def test_the_accepted_sequence_cells_epoch_programs_lower_as_before(cell):
    from benchmarks import harness
    from benchmarks.tests import util
    from shifu_tpu.train.loop import init_state
    from shifu_tpu.train.step import make_device_epoch_step

    _, ctx = util.tiny_context(cell)
    config, params = ctx.config, ctx.params
    driver = harness.load_module("drivers", ctx.traffic["driver"])
    job = driver.build_job(config, params, 1, 1)
    batch, width = config["batch_size"], config["num_categorical"]
    steps = params["train_rows"] // batch
    state = jax.eval_shape(lambda: init_state(job, width))
    blocks = {k: jax.ShapeDtypeStruct((steps, batch, w), jnp.float32)
              for k, w in (("features", width), ("target", 1), ("weight", 1))}
    order = jax.ShapeDtypeStruct((steps,), jnp.int32)
    text = make_device_epoch_step(job, None)._fn.trace(
        state, blocks, order).lower().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == EPOCH_PROGRAM_SHA256[cell]


FAULTS_CELL = "lfm2_8b_a1b_ep4.train_sequences"


def _reference_gaps(compute, fault=""):
    """The driver's gaps between the reference in `compute` with `fault`
    planted and the reference as the configuration states it, at the CPU
    sizes of `benchmarks/tests/sizes_lfm2_moe.py`, beside the cell's
    limits."""
    from benchmarks import harness
    from benchmarks.drivers import resident_sequences_any_model as driver
    from benchmarks.tests.sizes_lfm2_moe import tiny_context

    _, ctx = tiny_context(FAULTS_CELL)
    train_rows, valid_rows, _, _ = driver._datasets(ctx.config, ctx.params,
                                                    ctx.seed)
    limits = harness.load_limits(FAULTS_CELL)["limits"]
    ref_run = driver.reference_first_epoch(ctx.config, ctx.seed, train_rows,
                                           valid_rows)
    side = driver.reference_first_epoch(ctx.config, ctx.seed, train_rows,
                                        valid_rows, compute=compute,
                                        fault=fault)
    gaps, _ = driver.training_gaps(side, ref_run)
    return harness.is_correct(
        {k: (gaps[k], limits[k]) for k in gaps if k in limits}), gaps


@pytest.mark.parametrize("compute, fault", [
    ("float8", ""), ("bfloat16", "half_batch"), ("bfloat16", "no_routed")])
def test_a_planted_fault_fails_the_cells_limits(compute, fault):
    """The control (the reference in float8 in the program's place) and the
    two planted faults, through the driver's own comparison and the
    harness's verdict: the readings the limits were set under."""
    correct, gaps = _reference_gaps(compute, fault)
    assert not correct, gaps


def test_the_reference_in_the_stated_precision_passes_the_cells_limits():
    correct, gaps = _reference_gaps("bfloat16")
    assert correct, gaps
