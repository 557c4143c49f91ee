"""Roofline push (ISSUE 11): int8-resident epoch cache + fused FT block.

Two Pallas kernels move the two worst roofline rows:

- `ops/pallas_int8_matmul.int8_matmul_dequant` makes int8 the in-HBM
  format for the device-resident tier (`data.resident_format=int8`) and
  fuses the static-grid dequant into the first-layer matmul — pinned
  here bit-identically against the `wire_dequantize`+matmul XLA
  reference, with tier parity (equal order digests, per-epoch metrics
  within int8-grid tolerance, kill+resume) against the cached-disk wire
  path.
- `ops/pallas_ft_block.fused_transformer_block` fuses a whole pre-LN
  attention+FFN block into one pass (`model.fused_block`) — forward and
  custom-VJP gradients pinned in CPU interpret mode against the unfused
  TransformerBlock / `_block_forward` math.

Both kernels gate on availability (`fused_available` /
`ft_block_applicable` + kill-switch envs) and fall back to the existing
XLA paths; the fallback-both-ways tests hold that contract.
"""

import dataclasses
import json

import numpy as np
import pytest

from shifu_tpu.config import (ConfigError, DataConfig, JobConfig, ModelSpec,
                              OptimizerConfig, TrainConfig)
from shifu_tpu.data import pipeline as pipe
from shifu_tpu.data import synthetic
from shifu_tpu import obs
from shifu_tpu.ops import pallas_ft_block as ftb
from shifu_tpu.ops import pallas_int8_matmul as i8

NUM_FEATURES = 30


def _job(wire="auto", resident="auto", num_features=NUM_FEATURES,
         epochs=3, **data_kw):
    schema = synthetic.make_schema(num_features=num_features)
    return JobConfig(
        schema=schema,
        data=DataConfig(batch_size=100, wire_dtype=wire,
                        resident_format=resident, **data_kw),
        model=ModelSpec(model_type="mlp", hidden_nodes=(16, 16),
                        activations=("relu", "relu"),
                        compute_dtype="bfloat16"),
        train=TrainConfig(epochs=epochs, loss="weighted_mse",
                          optimizer=OptimizerConfig(name="adam",
                                                    learning_rate=0.01)),
    ).validate()


def _ft_spec(**kw):
    kw.setdefault("token_dim", 32)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("mlp_ratio", 2)
    kw.setdefault("num_layers", 1)
    kw.setdefault("compute_dtype", "float32")
    return ModelSpec(model_type="ft_transformer", **kw)


# ------------------------------------------------ int8 kernel exactness


def _int8_operands(m=37, f=NUM_FEATURES, n=16, seed=0, offset=True):
    rng = np.random.default_rng(seed)
    q = rng.integers(-127, 128, (m, f)).astype(np.int8)
    w = rng.standard_normal((f, n)).astype(np.float32)
    b = rng.standard_normal((n,)).astype(np.float32)
    scale = np.full((f,), 8.0 / 127, np.float32)
    off = (rng.standard_normal((f,)).astype(np.float32) * 0.1
           if offset else None)
    return q, w, b, scale, off


@pytest.mark.parametrize("cdt", ["bfloat16", "float32"])
@pytest.mark.parametrize("offset", [True, False])
def test_int8_matmul_kernel_bit_identical_to_reference(cdt, offset):
    """The exactness pin: the fused kernel (interpret mode on CPU) equals
    the `wire_dequantize`+matmul XLA reference bit for bit — dequant in
    registers changes WHERE the math runs, not the math."""
    import jax.numpy as jnp

    q, w, b, scale, off = _int8_operands(offset=offset)
    dt = jnp.dtype(cdt)
    want = i8.xla_reference(jnp.asarray(q), jnp.asarray(w), jnp.asarray(b),
                            jnp.asarray(scale),
                            None if off is None else jnp.asarray(off),
                            compute_dtype=dt)
    got = i8.int8_matmul_dequant(jnp.asarray(q), jnp.asarray(w),
                                 jnp.asarray(b), jnp.asarray(scale),
                                 None if off is None else jnp.asarray(off),
                                 compute_dtype=dt, use_pallas=True)
    assert got.dtype == want.dtype
    if offset and cdt == "float32":
        # a non-zero offset makes the dequant values inexact, so the two
        # dots' accumulation orders differ at f32 ulp scale
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    else:
        # the production grid (symmetric: offset zeros -> None) and every
        # bf16 case are bit-identical to the fallback
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_matmul_grads_match_reference():
    """custom-VJP dW/db equal the reference path's grads (the int8 data
    itself is never differentiated — recomputed dequant, float0 tangent)."""
    import jax
    import jax.numpy as jnp

    q, w, b, scale, off = _int8_operands()
    qj, sj, oj = jnp.asarray(q), jnp.asarray(scale), jnp.asarray(off)

    def loss(fn, w_, b_):
        y = fn(qj, w_, b_, sj, oj, compute_dtype=jnp.float32)
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    ref = jax.grad(lambda w_, b_: loss(
        lambda *a, **k: i8.int8_matmul_dequant(*a, use_pallas=False, **k),
        w_, b_), argnums=(0, 1))(jnp.asarray(w), jnp.asarray(b))
    fused = jax.grad(lambda w_, b_: loss(
        lambda *a, **k: i8.int8_matmul_dequant(*a, use_pallas=True, **k),
        w_, b_), argnums=(0, 1))(jnp.asarray(w), jnp.asarray(b))
    for g_ref, g_fused in zip(ref, fused):
        np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-4)


def test_int8_fused_gate_both_ways(monkeypatch):
    """Availability gating: the kill switch and oversized shapes force the
    XLA fallback; engagement additionally needs TPU or the pallas opt-in."""
    assert i8.fused_available(NUM_FEATURES, 16)
    assert not i8.fused_available(i8.MAX_FEATURES + 1, 16)
    assert not i8.fused_available(NUM_FEATURES, i8.MAX_OUT + 1)
    monkeypatch.setenv(i8.ENV_DISABLE, "1")
    assert not i8.fused_available(NUM_FEATURES, 16)
    assert not i8.fused_engaged(NUM_FEATURES, 16)
    monkeypatch.delenv(i8.ENV_DISABLE)
    # CPU backend: engaged only under the explicit opt-in
    monkeypatch.delenv("SHIFU_TPU_PALLAS", raising=False)
    assert not i8.fused_engaged(NUM_FEATURES, 16)
    monkeypatch.setenv("SHIFU_TPU_PALLAS", "1")
    assert i8.fused_engaged(NUM_FEATURES, 16)
    # use_pallas=True degrades to the fallback when unavailable (instead
    # of tracing a kernel that cannot run)
    import jax.numpy as jnp
    q, w, b, scale, off = _int8_operands()
    monkeypatch.setenv(i8.ENV_DISABLE, "1")
    got = i8.int8_matmul_dequant(jnp.asarray(q), jnp.asarray(w),
                                 jnp.asarray(b), jnp.asarray(scale),
                                 jnp.asarray(off), use_pallas=True)
    want = i8.xla_reference(jnp.asarray(q), jnp.asarray(w), jnp.asarray(b),
                            jnp.asarray(scale), jnp.asarray(off))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_wire_dense_model_consumes_int8_natively(monkeypatch):
    """With the kernel engaged (opt-in), the MLP's first layer takes the
    int8 wire batch directly; without it, `_WireDense` runs the
    bit-identical XLA fallback — both equal decode-then-model."""
    import jax
    import jax.numpy as jnp

    from shifu_tpu.models.registry import build_model
    from shifu_tpu.train.step import make_wire_decode, wire_fused_into_model

    job = _job(wire="int8")
    scale, offset = pipe.wire_params(job.schema, job.data)
    wire = (tuple(float(v) for v in scale),
            tuple(float(v) for v in offset) if np.any(offset) else None)
    rng = np.random.default_rng(7)
    q = rng.integers(-127, 128, (64, NUM_FEATURES)).astype(np.int8)

    plain = build_model(job.model, job.schema)
    v = plain.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, NUM_FEATURES), jnp.float32))
    decoded = jnp.asarray(q.astype(np.float32) * scale + offset)
    want = plain.apply(v, decoded)

    for opt_in in (False, True):
        if opt_in:
            monkeypatch.setenv("SHIFU_TPU_PALLAS", "1")
        else:
            monkeypatch.delenv("SHIFU_TPU_PALLAS", raising=False)
        wired = build_model(job.model, job.schema, wire=wire)
        v2 = wired.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, NUM_FEATURES), jnp.float32))
        # identical param tree AND identical init values: checkpoints are
        # interchangeable between the wired and plain models
        assert jax.tree_util.tree_structure(v2) \
            == jax.tree_util.tree_structure(v)
        for a, b in zip(jax.tree_util.tree_leaves(v),
                        jax.tree_util.tree_leaves(v2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        got = wired.apply(v2, jnp.asarray(q))
        if opt_in:  # f32-accumulating kernel vs bf16 promotion: tolerance
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                rtol=0, atol=0.15)
            assert wire_fused_into_model(job)
            # the model consumes wire natively: no decode dispatch at all
            assert make_wire_decode(job) is None
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_wire_decode_skipped_when_format_is_model_dtype():
    """Satellite: the per-batch tier skips the decode dispatch entirely
    when the wire format already IS the model compute dtype (bf16 wire on
    a bf16 model used to pay an identity-cast dispatch per batch)."""
    from shifu_tpu.train.step import make_wire_decode

    # bf16 wire, bf16 model: no int8 anywhere -> no decode closure
    assert make_wire_decode(_job(wire="bfloat16")) is None
    assert make_wire_decode(_job(wire="float32")) is None
    assert make_wire_decode(_job(wire="auto")) is None
    # int8 wire still decodes (per-batch tier); int8 residency under a
    # wide wire decodes too (the resident blocks are quantized)
    assert make_wire_decode(_job(wire="int8")) is not None
    assert make_wire_decode(_job(wire="auto", resident="int8")) is not None


# ------------------------------------------------ fused FT block


def _ft_params(spec, seed=0):
    rng = np.random.default_rng(seed)
    d, r = spec.token_dim, spec.mlp_ratio
    shapes = {
        "ln_attn_scale": (d,), "ln_attn_bias": (d,),
        "qkv_kernel": (d, 3 * d), "qkv_bias": (3 * d,),
        "proj_kernel": (d, d), "proj_bias": (d,),
        "ln_mlp_scale": (d,), "ln_mlp_bias": (d,),
        "mlp_in_kernel": (d, r * d), "mlp_in_bias": (r * d,),
        "mlp_out_kernel": (r * d, d), "mlp_out_bias": (d,),
    }
    p = {}
    for k, shape in shapes.items():
        if k.startswith("ln") and k.endswith("scale"):
            p[k] = np.ones(shape, np.float32)
        elif k.endswith("bias") and k.startswith("ln"):
            p[k] = np.zeros(shape, np.float32)
        else:
            p[k] = (rng.standard_normal(shape) * 0.1).astype(np.float32)
    return p


def test_ft_fused_block_matches_block_forward():
    """Exactness pin (interpret mode): the fused kernel's forward equals
    `_block_forward`'s unfused math to f32 matmul tolerance, including a
    token count that does NOT hit the 8-sublane tile (padding masked)."""
    import jax.numpy as jnp

    from shifu_tpu.models.ft_transformer import _block_forward

    for s in (9, 16, 31):
        spec_on = _ft_spec(fused_block="on")
        spec_off = _ft_spec(fused_block="off")
        p = {k: jnp.asarray(v) for k, v in _ft_params(spec_on).items()}
        x = jnp.asarray(np.random.default_rng(s).standard_normal(
            (5, s, spec_on.token_dim)), jnp.float32)
        want = _block_forward(p, x, spec_off)
        got = ftb.fused_transformer_block(x, p, spec_on)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        # _block_forward itself routes through the kernel when engaged
        via = _block_forward(p, x, spec_on)
        np.testing.assert_array_equal(np.asarray(via), np.asarray(got))


def test_ft_fused_block_grads_match_reference():
    """The flash-style recompute VJP: gradients through the fused block
    (x and all 12 params) match the unfused block's to f32 tolerance."""
    import jax
    import jax.numpy as jnp

    from shifu_tpu.models.ft_transformer import _block_forward

    spec_on = _ft_spec(fused_block="on")
    spec_off = _ft_spec(fused_block="off")
    p = {k: jnp.asarray(v) for k, v in _ft_params(spec_on).items()}
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (4, 9, spec_on.token_dim)), jnp.float32)

    def loss(spec):
        return lambda x_, p_: jnp.sum(
            jnp.sin(_block_forward(p_, x_, spec).astype(jnp.float32)))

    gx_ref, gp_ref = jax.grad(loss(spec_off), argnums=(0, 1))(x, p)
    gx, gp = jax.grad(loss(spec_on), argnums=(0, 1))(x, p)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               rtol=1e-4, atol=1e-4)
    for k in gp_ref:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gp_ref[k]),
                                   rtol=1e-4, atol=1e-4, err_msg=k)


def test_transformer_block_module_fused_vs_unfused():
    """Module level: fused and unfused TransformerBlocks share the exact
    param tree AND init values (param-holder twins pin flax's path-based
    RNG), and agree on the forward — checkpoints are interchangeable."""
    import jax
    import jax.numpy as jnp

    from shifu_tpu.models.ft_transformer import TransformerBlock

    spec_on = _ft_spec(fused_block="on")
    spec_off = _ft_spec(fused_block="off")
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (4, 9, spec_on.token_dim)), jnp.float32)
    on, off = TransformerBlock(spec=spec_on), TransformerBlock(spec=spec_off)
    v_on = on.init(jax.random.PRNGKey(0), x)
    v_off = off.init(jax.random.PRNGKey(0), x)
    assert jax.tree_util.tree_structure(v_on) \
        == jax.tree_util.tree_structure(v_off)
    for a, b in zip(jax.tree_util.tree_leaves(v_on),
                    jax.tree_util.tree_leaves(v_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(on.apply(v_on, x)),
                               np.asarray(off.apply(v_off, x)),
                               rtol=2e-5, atol=2e-5)


def test_ft_gate_fallback_both_ways(monkeypatch):
    """Engagement gating: off/kill-switch/unfusable-shape/dropout/
    seq-parallel all fall back to the unfused module; `on` forces the
    kernel (interpret off-TPU); `auto` needs TPU or the opt-in."""
    spec = _ft_spec(fused_block="on")
    assert ftb.fused_block_engaged(spec, 31)
    assert not ftb.fused_block_engaged(_ft_spec(fused_block="off"), 31)
    # auto on CPU: only under the opt-in
    monkeypatch.delenv("SHIFU_TPU_PALLAS", raising=False)
    assert not ftb.fused_block_engaged(_ft_spec(fused_block="auto"), 31)
    monkeypatch.setenv("SHIFU_TPU_PALLAS", "1")
    assert ftb.fused_block_engaged(_ft_spec(fused_block="auto"), 31)
    # kill switch beats even "on"
    monkeypatch.setenv(ftb.ENV_DISABLE, "1")
    assert not ftb.fused_block_engaged(spec, 31)
    monkeypatch.delenv(ftb.ENV_DISABLE)
    # unfusable rides: train-time dropout, ring/ulysses, seq-parallel
    assert not ftb.fused_block_engaged(
        _ft_spec(fused_block="on", dropout_rate=0.1), 31, train=True)
    assert ftb.fused_block_engaged(
        _ft_spec(fused_block="on", dropout_rate=0.1), 31, train=False)
    assert not ftb.fused_block_engaged(
        _ft_spec(fused_block="on", attention_impl="ring"), 31)
    assert not ftb.fused_block_engaged(spec, 31, n_seq_parallel=2)
    # shape caps
    assert not ftb.fused_block_engaged(spec, ftb.MAX_TOKENS + 1)
    assert not ftb.ft_block_applicable(31, ftb.MAX_TOKEN_DIM + 2, 4, 2)
    assert not ftb.ft_block_applicable(31, 32, 5, 2)  # heads don't divide
    # a mis-gated direct call raises instead of silently computing
    import jax.numpy as jnp
    with pytest.raises(ValueError, match="fused_block_engaged"):
        ftb.fused_transformer_block(
            jnp.zeros((2, 9, 32), jnp.float32), {}, spec, use_pallas=False)


# ------------------------------------------------ int8-resident tier


def _split(rows, job):
    feats = rows[:, 1:].astype(np.float32)
    target = rows[:, :1].astype(np.float32)
    weight = np.ones_like(target)
    n_valid = len(rows) // 5
    tds = pipe.TabularDataset(feats[n_valid:], target[n_valid:],
                              weight[n_valid:])
    vds = pipe.TabularDataset(feats[:n_valid], target[:n_valid],
                              weight[:n_valid])
    return tds, vds


@pytest.fixture(scope="module")
def learnable_rows():
    schema = synthetic.make_schema(num_features=NUM_FEATURES)
    return synthetic.make_rows(2000, schema, seed=9, noise=0.25)


def _run(job, tmp_path, tag, train_ds, valid_ds):
    from shifu_tpu.train import train

    tele = tmp_path / f"tele_{tag}"
    obs.reset_for_tests()
    obs.configure(str(tele), flush_every=1)
    r = train(job, train_ds, valid_ds, console=lambda s: None)
    obs.flush()
    recs = obs.read_journal(str(tele / "journal.jsonl"))
    obs.shutdown()
    return r, recs


def _reports(recs):
    return {r["epoch"]: r for r in recs if r["kind"] == "overlap_report"}


def test_resident_format_resolution_and_config_surface():
    """`resident_format` resolves int8 residency independently of the
    wire; categorical schemas reject it at validate (same contract as
    wire_dtype=int8); the XML keys reach DataConfig / ModelSpec."""
    from shifu_tpu.utils.xmlconfig import apply_to_job

    job = _job(wire="auto", resident="int8")
    assert pipe.resident_feature_format(job.schema, job.data,
                                        "bfloat16") == "int8"
    # auto defers to the wire mode exactly
    auto = _job(wire="auto", resident="auto")
    assert pipe.resident_feature_format(auto.schema, auto.data, "bfloat16") \
        == pipe.wire_mode(auto.schema, auto.data, "bfloat16")
    q = _job(wire="int8", resident="auto")
    assert pipe.resident_feature_format(q.schema, q.data, "bfloat16") == "int8"

    cat_schema = synthetic.make_schema(num_features=8, num_categorical=2,
                                       vocab_size=50)
    with pytest.raises(ConfigError, match="resident_format"):
        JobConfig(schema=cat_schema,
                  data=DataConfig(batch_size=10, resident_format="int8"),
                  model=ModelSpec(model_type="wide_deep")).validate()
    with pytest.raises(ConfigError):
        _job(resident="int9")

    out = apply_to_job(_job(), {"shifu.data.resident-format": "INT8",
                                "shifu.model.fused-block": "ON"})
    assert out.data.resident_format == "int8"
    assert out.model.fused_block == "on"


def test_int8_resident_parity_with_wire_path(tmp_path, learnable_rows):
    """THE tier parity gate: forced int8 residency under a float32 wire
    trains on byte-identical device blocks as the int8-wire run — same
    per-epoch order digests, same train trajectory, AUC within the int8
    grid's tolerance of the f32 run — and the overlap_report journals
    `resident_format` so zero-steady-state-H2D residency is attributable."""
    job_res = _job(wire="auto", resident="int8")
    job_wire = _job(wire="int8", resident="auto")
    job_f32 = _job(wire="auto", resident="auto")
    tds, vds = _split(learnable_rows, job_res)

    r_res, recs_res = _run(job_res, tmp_path, "res", tds, vds)
    r_wire, recs_wire = _run(job_wire, tmp_path, "wire", tds, vds)
    r_f32, recs_f32 = _run(job_f32, tmp_path, "f32", tds, vds)

    rep_res, rep_wire, rep_f32 = map(_reports, (recs_res, recs_wire, recs_f32))
    assert sorted(rep_res) == sorted(rep_wire) == sorted(rep_f32)
    for ep in rep_res:
        assert rep_res[ep]["tier"] == "resident"  # upload once, scan epochs
        assert rep_res[ep]["resident_format"] == "int8"
        assert rep_wire[ep]["resident_format"] == "int8"
        # the auto job resolves to the wire mode (bf16 under a bf16 model)
        assert rep_f32[ep]["resident_format"] == "bfloat16"
        # identical (seed, epoch, tier) order on every run
        assert rep_res[ep]["order_digest"] == rep_wire[ep]["order_digest"] \
            == rep_f32[ep]["order_digest"] is not None
    # identical int8 train blocks -> identical train trajectory; eval wire
    # differs (f32 vs int8 eval batches), so valid metrics get tolerance
    for a, b in zip(r_res.history, r_wire.history):
        assert a.train_error == pytest.approx(b.train_error, rel=1e-5)
        assert a.valid_auc == pytest.approx(b.valid_auc, abs=0.02)
    assert r_f32.history[-1].valid_auc > 0.6
    assert abs(r_res.history[-1].valid_auc
               - r_f32.history[-1].valid_auc) < 0.02


def test_int8_resident_fits_027x_budget(tmp_path, learnable_rows):
    """The HBM claim: a device_resident_bytes budget of 0.27x the f32
    staging footprint admits the int8-resident tier and rejects the f32
    one — int8 residency quarters the feature bytes (plus the compact
    u8 label / elided weight), landing under 0.27x, not just under 1x."""
    tds, vds = _split(learnable_rows, _job())
    f32_bytes = (tds.features.nbytes + tds.target.nbytes // 4)  # u8 label
    budget = int(0.27 * f32_bytes)

    job_int8 = _job(wire="auto", resident="int8", epochs=1,
                    device_resident_bytes=budget, block_batches=4)
    job_f32 = _job(wire="auto", resident="auto", epochs=1,
                   device_resident_bytes=budget, block_batches=4)
    _r, recs_int8 = _run(job_int8, tmp_path, "fit", tds, vds)
    _r, recs_f32 = _run(job_f32, tmp_path, "nofit", tds, vds)
    assert _reports(recs_int8)[0]["tier"] == "resident"
    assert _reports(recs_f32)[0]["tier"] == "staged"  # f32 over budget


def test_int8_resident_kill_resume(tmp_path, learnable_rows):
    """Restart determinism through the int8-resident tier: kill at an
    epoch boundary, resume from checkpoint — same per-epoch digests and
    trajectory as an uninterrupted run."""
    ckpt = tmp_path / "ckpt"

    def mk(epochs, ckpt_dir):
        base = _job(wire="auto", resident="int8", epochs=epochs)
        if ckpt_dir is None:
            return base
        return base.replace(runtime=dataclasses.replace(
            base.runtime, checkpoint=dataclasses.replace(
                base.runtime.checkpoint, directory=str(ckpt_dir)))).validate()

    tds, vds = _split(learnable_rows, mk(2, None))
    _run(mk(2, ckpt), tmp_path, "first", tds, vds)  # terminal at epoch 2
    r_resumed, recs_resumed = _run(mk(4, ckpt), tmp_path, "resumed", tds, vds)
    assert r_resumed.resumed_from_epoch == 2
    r_straight, recs_straight = _run(mk(4, None), tmp_path, "straight",
                                     tds, vds)
    d_res, d_str = _reports(recs_resumed), _reports(recs_straight)
    for ep in (2, 3):
        assert d_res[ep]["order_digest"] == d_str[ep]["order_digest"] \
            is not None
        assert d_res[ep]["resident_format"] == "int8"
    straight_tail = {m.epoch: m for m in r_straight.history}
    for m in r_resumed.history:
        assert m.train_error == pytest.approx(
            straight_tail[m.epoch].train_error, rel=1e-5)
        assert m.valid_auc == pytest.approx(
            straight_tail[m.epoch].valid_auc, abs=1e-5)


# ------------------------------------------------ measurement loop


def test_roofline_join_classifies_new_kernels(monkeypatch):
    """Tentpole (c): both new kernels inherit their instrumented module's
    `bound` verdict in device_profile rollups (time-proportional
    attribution via the epoch_step alias, obs/devprof.py)."""
    from shifu_tpu.obs import devprof

    monkeypatch.setattr(devprof, "peaks",
                        lambda kind=None: (100.0, 1000.0))
    rollup = {"kernels": [
        {"name": "int8_matmul_dequant", "module": "jit_epoch_step",
         "device_us": 500.0, "calls": 10},
        {"name": "ft_fused_block", "module": "jit_epoch_step",
         "device_us": 500.0, "calls": 10},
    ]}
    stats = {"epoch_scan_step": {"flops": 1e10, "bytes_accessed": 1e9}}
    devprof.roofline_join(rollup, stats=stats)
    for k in rollup["kernels"]:
        assert k["bound"] in ("compute", "hbm"), k
