"""The block-stack sequence scorer (models/block_stack.py and its ops)
against the plain reference (benchmarks/reference/nemotron_h.py), at a small
size on the CPU.

Tolerances, and why each: the program in float32 and the reference compute
the same sums in another order (the scan by chunks, attention by query
blocks, the experts by dispatched blocks), so they differ by float32
rounding: 2e-4 relative on scores, loss and three optimizer steps, 2e-3 of
a leaf's norm on gradients (Adadelta at learning rate 1 and a sum over 32
positions amplify the last bits).  The program in bfloat16, the precision
below, misses the same tolerances at least five times over, which is what
makes them a test of the precision the configuration states.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.resident_sequences import model_group
from benchmarks.reference import common, nemotron_h as ref
from shifu_tpu.config.schema import ConfigError, JobConfig
from shifu_tpu.ops import routed_experts as rx
from shifu_tpu.ops.ssd import ssd_chunked

SEQ, VOCAB, BATCH = 32, 97, 4

#: the reference's configuration keys at the small size (the benchmark's
#: configuration file has the same keys at the published widths)
CFG = {
    "hybrid_override_pattern": "ME*E", "hidden_size": 64, "vocab_size": VOCAB,
    "norm_eps": 1e-5, "mamba_num_heads": 8, "mamba_head_dim": 8,
    "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 128,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "routed_scaling_factor": 2.5,
    "deployment": {"router_experts": 16, "first_expert_held": 0,
                   "published_layers": 52},
}


def make_job(cfg=CFG, compute="float32", seq=SEQ, batch=BATCH, epochs=1,
             remat=True, seed=11, **model):
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
                  "block_stack": model_group(cfg)["block_stack"],
                  **model},
        "train": {"epochs": epochs, "loss": "weighted_mse", "seed": seed,
                  "optimizer": {"name": "adadelta", "learning_rate": 1.0}},
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


def test_initial_weights_are_the_references_bit_for_bit():
    theirs = flat(program(make_job()).params)
    ours = flat(ref.init_params(CFG, 11))
    assert set(ours) == set(theirs)
    for k in ours:
        assert np.array_equal(np.asarray(ours[k]), np.asarray(theirs[k])), k


def _ref_loss(params, rows, forward=None):
    forward = forward or ref.make_forward(CFG)
    return common.weighted_mse(
        forward(params, jnp.asarray(rows["features"]), identity),
        jnp.asarray(rows["target"]), jnp.asarray(rows["weight"]))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


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

    # the precision below the one stated fails the same tolerances
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
        params, slots = common.adadelta_update(params, grads, slots, 1.0)
        assert abs(float(metrics["loss"]) - float(loss)) < 2e-4 * float(loss)
    got, exp, start = (flat(state.params), flat(params),
                       flat(ref.init_params(CFG, 11)))
    for k in exp:
        moved = np.asarray(exp[k]) - np.asarray(start[k])
        assert _rel(np.asarray(got[k]) - np.asarray(start[k]), moved) \
            < 2e-3, k


@pytest.mark.parametrize("length", [32, 24, 13, 5])
def test_chunked_scan_is_the_sequential_recurrence(length):
    """Forward and gradient, at lengths that are and are not multiples of
    the chunk (8)."""
    rng = np.random.default_rng(length)
    b, h, p, g, n = 2, 4, 8, 2, 16
    x = jnp.asarray(rng.normal(size=(b, length, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, (b, length, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, length, g, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, length, g, n)), jnp.float32)
    d = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    probe = jnp.asarray(rng.normal(size=(b, length, h, p)), jnp.float32)

    def chunked(x, dt, a, bm, cm, d):
        return jnp.sum(probe * ssd_chunked(x, dt, a, bm, cm, d, chunk=8))

    def sequential(x, dt, a, bm, cm, d):
        return jnp.sum(probe * jnp.stack([
            ref.recurrence(x[i], dt[i], a, bm[i], cm[i], d)
            for i in range(b)]))

    args = (x, dt, a, bm, cm, d)
    got, got_grads = jax.jit(jax.value_and_grad(
        chunked, argnums=tuple(range(6))))(*args)
    want, want_grads = jax.jit(jax.value_and_grad(
        sequential, argnums=tuple(range(6))))(*args)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want)) + 1e-4
    for gg, ww in zip(got_grads, want_grads):
        assert _rel(gg, ww) < 1e-4


def _expert_weights(rng, held, hidden=16, f=24):
    return (jnp.asarray(rng.normal(size=(held, hidden, f)) * 0.3, jnp.float32),
            jnp.asarray(rng.normal(size=(held, f, hidden)) * 0.3, jnp.float32))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _routed(x, logits, w1, w2, first, held, k=2, rows=8):
    experts, weights = rx.route_topk(logits, k, 2.5)
    plan = rx.plan_dispatch(experts, first, held, rows)
    slot = plan["row_slot"]
    row_weight = jnp.append(weights.reshape(-1), 0.0)[slot]
    out = rx.routed_relu2_mlp(x, w1, w2, row_weight, slot // k,
                              plan["block_expert"], plan["live_blocks"],
                              rows)
    return out, plan


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _routed_reference(x, logits, w1, w2, first, held, k=2):
    scores = jax.nn.sigmoid(logits)
    chosen, experts = jax.lax.top_k(scores, k)
    weights = 2.5 * chosen / jnp.sum(chosen, -1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        out = out + w_e[:, None] * (jnp.square(jax.nn.relu(x @ w1[e]))
                                    @ w2[e])
    return out


def test_routing_under_a_planted_skew_drops_nothing():
    """Every token's first choice is the same held expert: its group is
    many blocks long, the others' short, and every choice is computed."""
    rng = np.random.default_rng(3)
    t, hidden, n_experts, held = 96, 16, 16, 8
    x = jnp.asarray(rng.normal(size=(t, hidden)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(t, n_experts)), jnp.float32)
    logits = logits.at[:, 5].set(9.0)
    w1, w2 = _expert_weights(rng, held)

    def loss(x, logits, w1, w2, fn):
        out = fn(x, logits, w1, w2, 0, held)
        return jnp.sum(jnp.sin(out[0] if isinstance(out, tuple) else out))

    out, plan = _routed(x, logits, w1, w2, 0, held)
    assert int(plan["tokens_per_expert"][5]) == t
    assert int(plan["held_slots"]) == int(plan["dispatched_slots"])
    assert _rel(out, _routed_reference(x, logits, w1, w2, 0, held)) < 1e-5
    got = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)),
                  static_argnums=4)(x, logits, w1, w2, _routed)
    want = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)),
                   static_argnums=4)(x, logits, w1, w2, _routed_reference)
    for gg, ww in zip(got, want):
        assert _rel(gg, ww) < 1e-4


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Guide section 4's share test: the routed parts that the 16 shares of
    an expert-parallel layer give, with the shared expert and the residual
    counted once, are the uncut reference's layer output."""
    rng = np.random.default_rng(7)
    cfg = dict(CFG, hybrid_override_pattern="E", n_routed_experts=32,
               deployment=dict(CFG["deployment"], router_experts=32))
    s = ref._shapes(cfg)
    whole = ref._init_block(cfg, s, 5, "E", ("block0",))
    x = jnp.asarray(rng.normal(size=(40, s["hidden"])), jnp.float32)
    h = ref.rms_norm(x, whole["norm"], s["eps"])
    uncut = x + ref._experts(whole, s, h, identity, True)

    shares, held = 16, 2
    total = x + ref._mlp(h, whole["shared/w1"], whole["shared/w2"], identity)
    logits = h @ whole["router"]
    counted = 0
    for r in range(shares):
        w1 = whole["experts/w1"][r * held:(r + 1) * held]
        w2 = whole["experts/w2"][r * held:(r + 1) * held]
        part, plan = _routed(h, logits, w1, w2, r * held, held)
        total = total + part
        counted += int(plan["held_slots"])
    assert counted == 40 * s["top_k"]       # every choice on one share
    assert _rel(total, uncut) < 1e-5


def test_the_tables_gradient_is_the_dense_one():
    job = make_job()
    state = program(job)
    rows = make_rows(BATCH)
    feats = jnp.asarray(rows["features"])

    def by_gather(table):
        p = dict(state.params, embed_tokens=table)
        return jnp.sum(jnp.sin(state.apply_fn({"params": p}, feats)))

    got = jax.jit(jax.grad(by_gather))(state.params["embed_tokens"])
    # the same rows through a one-hot product: the dense gradient
    onehot = jax.nn.one_hot(feats.astype(jnp.int32), VOCAB, dtype=jnp.float32)
    params0 = ref.init_params(CFG, 11)

    def by_product(table):
        def row(oh):
            x = oh @ table
            s = ref._shapes(CFG)
            for i, kind in enumerate(s["pattern"]):
                p = params0[f"block{i}"]
                mixer = {"M": ref._mamba, "*": ref._attention,
                         "E": lambda p, s, x, r: ref._experts(p, s, x, r,
                                                              True)}[kind]
                x = x + mixer(p, s, ref.rms_norm(x, p["norm"], s["eps"]),
                              identity)
            last = ref.rms_norm(x[-1], params0["norm_f"], s["eps"])
            return common.dense(params0["head"]["shifu_output_0"],
                                last[None], identity)[0]
        return jnp.sum(jnp.sin(jnp.stack([row(oh) for oh in onehot])))

    want = jax.jit(jax.grad(by_product))(params0["embed_tokens"])
    assert _rel(got, want) < 2e-3
    untouched = np.setdiff1d(np.arange(VOCAB), rows["features"].astype(int))
    assert not np.any(np.asarray(got)[untouched])


def test_train_journals_the_experts_load_once_an_epoch():
    from shifu_tpu import obs
    from shifu_tpu.data.pipeline import TabularDataset
    from shifu_tpu.train import train

    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    try:
        job = make_job(compute="bfloat16", epochs=2)
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
    for e in events:
        assert len(e["layers"]) == CFG["hybrid_override_pattern"].count("E")
        # the E block before the last mixer routes every position, the one
        # after it the last position of each row
        assert [layer["routed_slots"] for layer in e["layers"]] == [
            4 * BATCH * SEQ * 2, 4 * BATCH * 2]
        for layer in e["layers"]:
            assert layer["tokens_dropped"] == 0
            assert sum(layer["tokens_per_expert"]) == layer["held_slots"]
    tiers = [r for r in journal.records if r.get("kind") == "overlap_report"]
    assert tiers and all(r["tier"] == "resident" for r in tiers)


@pytest.mark.parametrize("change, message", [
    ({"pipeline_stages": 2}, "pipeline_stages"),
    ({"attention_impl": "ring"}, "causal"),
    ({"dropout_rate": 0.1}, "dropout"),
])
def test_validate_refuses_what_the_training_path_cannot_do(change, message):
    with pytest.raises(ConfigError, match=message):
        make_job(**change)


def test_numeric_columns_are_refused():
    job = make_job()
    cols = list(job.to_dict()["schema"]["columns"])
    cols[2] = dict(cols[2], is_categorical=False, vocab_size=0)
    d = job.to_dict()
    d["schema"]["columns"] = cols
    with pytest.raises(ConfigError, match="token ids"):
        JobConfig.from_dict(d).validate()


def test_export_and_serve_refuse_the_model_by_name(tmp_path):
    import json

    from shifu_tpu.export import save_artifact
    from shifu_tpu.export.program import build_program_v2
    from shifu_tpu.runtime.serve import load_engine

    job = make_job()
    message = "model_type 'block_stack'.*trained only"
    with pytest.raises(ConfigError, match=message):
        save_artifact(program(job).params, job, str(tmp_path / "out"))
    with pytest.raises(ConfigError, match=message):
        build_program_v2(job.model, job.schema)
    (tmp_path / "art").mkdir()
    (tmp_path / "art" / "topology.json").write_text(
        json.dumps({"model_type": "block_stack"}))
    with pytest.raises(ConfigError, match=message):
        load_engine(str(tmp_path / "art"), "jax")
