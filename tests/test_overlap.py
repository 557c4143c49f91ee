"""Epoch-pipelined overlap engine (ISSUE 4): the persistent cross-epoch
feeder, async eval, adaptive prefetch depth, and the determinism contract.

Pins: (1) the feeder delivers byte-identical blocks to the per-epoch path
it replaced, across epochs and across a kill+resume; (2) training with
overlap on equals overlap off (loss/AUC and the journaled per-epoch
`order_digest`); (3) a feeder death (the `data.feeder` chaos site) fails
the epoch loudly instead of deadlocking the consumer queue; (4) the
`overlap_report` journal schema and its `shifu-tpu profile` rendering;
(5) the async single-host eval path computes exactly what the per-batch
blocking path computed.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from shifu_tpu import chaos, obs
from shifu_tpu.chaos import plan as plan_mod
from shifu_tpu.config import (ConfigError, DataConfig, JobConfig, ModelSpec,
                              OptimizerConfig, TrainConfig)
from shifu_tpu.data import pipeline as pipe
from shifu_tpu.data import reader, synthetic


@pytest.fixture(autouse=True)
def _clean_chaos_and_obs():
    chaos.reset_for_tests()
    obs.reset_for_tests()
    yield
    chaos.reset_for_tests()
    obs.reset_for_tests()


def _dataset(n=512, f=8, seed=0):
    rng = np.random.default_rng(seed)
    return pipe.TabularDataset(
        rng.standard_normal((n, f)).astype(np.float32),
        (rng.random((n, 1)) < 0.5).astype(np.float32),
        np.ones((n, 1), np.float32))


# --------------------------------------------------------------- config

def test_prefetch_depth_config_validation():
    DataConfig(prefetch_depth=0).validate()   # 0 = auto
    DataConfig(prefetch_depth=8).validate()
    with pytest.raises(ConfigError, match="prefetch_depth"):
        DataConfig(prefetch_depth=-1).validate()


def test_xmlconfig_maps_prefetch_depth_and_overlap():
    from shifu_tpu.utils import xmlconfig

    job = JobConfig()
    out = xmlconfig.apply_to_job(job, {
        "shifu.data.prefetch-depth": "7",
        "shifu.data.overlap-epochs": "false",
    })
    assert out.data.prefetch_depth == 7
    assert out.data.overlap_epochs is False


def test_streaming_loader_parse_queue_uses_prefetch_depth():
    schema = synthetic.make_schema(num_features=4)
    loader = pipe.StreamingLoader(schema, DataConfig(prefetch_depth=2))
    assert loader._q.maxsize == 2
    loader.datasets()  # drain the (empty) background parse
    # auto (0) keeps the historical depth of 4
    loader = pipe.StreamingLoader(schema, DataConfig(prefetch_depth=0))
    assert loader._q.maxsize == 4
    loader.datasets()


def test_next_prefetch_depth_policy():
    assert pipe.next_prefetch_depth(2, 0.5) == 4     # starved: double
    assert pipe.next_prefetch_depth(8, 0.5) == 8     # HBM cap (8 chunks)
    assert pipe.next_prefetch_depth(6, 0.5) == 8     # doubling clamps
    assert pipe.next_prefetch_depth(4, 0.0) == 3     # hidden: decay
    assert pipe.next_prefetch_depth(2, 0.0) == 2     # floor
    assert pipe.next_prefetch_depth(4, 0.03) == 4    # dead band: hold


# --------------------------------------------------------------- feeder

def test_feeder_matches_per_epoch_path_byte_identical():
    """The persistent feeder yields the SAME blocks, in the SAME order, as
    the per-epoch staged iterator it replaced — across multiple epochs."""
    ds = _dataset(n=200, f=4)
    bs, bb, seed = 16, 3, 11

    def source(ep):
        return pipe.staged_epoch_blocks(ds, bs, shuffle=True, seed=seed,
                                        epoch=ep, block_batches=bb)

    feeder = pipe.EpochFeeder(source, lambda b: b, range(3), depth=2,
                              host_depth=2)
    try:
        for ep in range(3):
            got = list(feeder.epoch(ep))
            want = list(source(ep))
            assert len(got) == len(want) > 0
            for g, w in zip(got, want):
                for k in w:
                    np.testing.assert_array_equal(g[k], w[k])
    finally:
        feeder.close()


def test_feeder_runs_ahead_across_the_epoch_boundary():
    """After epoch N is fully consumed, epoch N+1's items appear in the
    device queue WITHOUT the consumer asking — the cross-epoch run-ahead
    that hides shuffle/assembly behind eval."""
    import time

    ds = _dataset(n=64, f=4)

    def source(ep):
        return pipe.staged_epoch_blocks(ds, 16, shuffle=True, seed=1,
                                        epoch=ep, block_batches=2)

    feeder = pipe.EpochFeeder(source, lambda b: b, range(2), depth=4,
                              host_depth=4)
    try:
        list(feeder.epoch(0))
        deadline = time.monotonic() + 10.0
        while feeder.ready_ahead() == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert feeder.ready_ahead() > 0  # epoch 1 staged before requested
        list(feeder.epoch(1))  # and it is still byte-correct epoch 1 data
    finally:
        feeder.close()


def test_feeder_chaos_raise_fails_epoch_loudly():
    """A `data.feeder` chaos raise in the producer thread propagates to
    the consumer as the injected error — no deadlocked queue."""
    chaos.configure(plan_mod.parse_plan({"faults": [
        {"site": "data.feeder", "at_call": 1}]}))
    ds = _dataset(n=64, f=4)

    def source(ep):
        return pipe.staged_epoch_blocks(ds, 16, epoch=ep, block_batches=2)

    feeder = pipe.EpochFeeder(source, lambda b: b, range(2), depth=2)
    try:
        with pytest.raises(chaos.ChaosError):
            list(feeder.epoch(0))
    finally:
        feeder.close()


def test_feeder_source_error_forwarded_and_death_detected():
    def bad_source(ep):
        raise RuntimeError("shard went away")
        yield  # pragma: no cover

    feeder = pipe.EpochFeeder(bad_source, lambda b: b, range(1), depth=2)
    try:
        with pytest.raises(RuntimeError, match="shard went away"):
            list(feeder.epoch(0))
    finally:
        feeder.close()

    # an exhausted feeder (or one whose threads died after close) raises
    # FeederError at the consumer's next poll instead of blocking forever
    feeder = pipe.EpochFeeder(lambda ep: iter(()), lambda b: b, [])
    with pytest.raises(pipe.FeederError):
        list(feeder.epoch(0))
    feeder.close()
    feeder = pipe.EpochFeeder(lambda ep: iter(()), lambda b: b, [])
    feeder.close()
    with pytest.raises(pipe.FeederError):
        list(feeder.epoch(0))


def test_depth_gate_resize_absorbs_and_grows():
    g = pipe._DepthGate(2)
    assert g.acquire(timeout=0.1) and g.acquire(timeout=0.1)
    assert not g.acquire(timeout=0.05)  # bound enforced
    g.resize(3)
    assert g.acquire(timeout=0.1)       # grew by one slot
    g.resize(1)                          # shrink: next 2 releases absorbed
    g.release()
    g.release()
    assert not g.acquire(timeout=0.05)
    g.release()                          # now a real slot again
    assert g.acquire(timeout=0.1)


# --------------------------------------------------------- order digests

def test_staged_order_model_matches_real_iterator():
    """epoch_order_digest's staged order model (offset + block
    permutation) reproduces exactly the row sequence staged_epoch_blocks
    emits — the digest is a faithful fingerprint, not a parallel guess."""
    n, bs, bb, seed, epoch = 20, 3, 2, 9, 4
    ds = pipe.TabularDataset(
        np.arange(n, dtype=np.float32).reshape(n, 1),
        np.zeros((n, 1), np.float32), np.ones((n, 1), np.float32))
    got_rows = np.concatenate([
        blk["features"].reshape(-1) for blk in pipe.staged_epoch_blocks(
            ds, bs, shuffle=True, seed=seed, epoch=epoch, block_batches=bb)])
    # the digest helper's model of the same order
    nb_total = n // bs
    slack = n - nb_total * bs
    offset = (epoch * 997) % (slack + 1)
    order = np.random.default_rng(
        np.random.PCG64(seed * 1_000_003 + epoch)).permutation(nb_total)
    want_rows = np.concatenate(
        [np.arange(offset + i * bs, offset + (i + 1) * bs) for i in order])
    np.testing.assert_array_equal(got_rows.astype(np.int64), want_rows)


def test_epoch_order_digest_properties():
    d = lambda **kw: pipe.epoch_order_digest("staged", 1000, 64, seed=3,
                                             **kw)
    assert d(epoch=1) == d(epoch=1)          # pure in (seed, epoch)
    assert d(epoch=1) != d(epoch=2)
    assert d(epoch=1, shuffle=False) != d(epoch=1)
    assert pipe.epoch_order_digest("stream", 1000, 64) is None
    assert pipe.epoch_order_digest("batch", 0, 64) is None
    for tier in ("staged", "batch", "resident"):
        h = pipe.epoch_order_digest(tier, 1000, 64, seed=1, epoch=0)
        int(h, 16)  # hex digest
        assert len(h) == 32


# -------------------------------------------------- end-to-end train runs

def _staged_job(epochs=3, overlap=True, ckpt_dir=None, prefetch_depth=3):
    schema = synthetic.make_schema(num_features=10)
    job = JobConfig(
        schema=schema,
        data=DataConfig(batch_size=64, valid_ratio=0.1,
                        device_resident_bytes=0,  # force the staged tier
                        prefetch_depth=prefetch_depth,
                        overlap_epochs=overlap),
        model=ModelSpec(model_type="mlp", hidden_nodes=(8,),
                        activations=("relu",), compute_dtype="float32"),
        train=TrainConfig(epochs=epochs,
                          optimizer=OptimizerConfig(name="adam",
                                                    learning_rate=1e-2)))
    if ckpt_dir:
        job = job.replace(runtime=dataclasses.replace(
            job.runtime, checkpoint=dataclasses.replace(
                job.runtime.checkpoint, directory=str(ckpt_dir))))
    return job.validate()


def _train_data(schema, n=2048):
    rows = synthetic.make_rows(n, schema, seed=5, noise=0.3)
    cols = reader.project_columns(rows, schema)
    full = pipe.TabularDataset(cols["features"], cols["target"],
                               cols["weight"])
    split = int(n * 0.9)
    return full.take(np.arange(split)), full.take(np.arange(split, n))


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


def test_overlap_on_off_identical_training_and_order(tmp_path):
    """THE parity gate: overlap on vs off — identical loss/AUC trajectory
    and byte-identical (digested) batch order per (seed, epoch)."""
    job_on = _staged_job(epochs=3, overlap=True)
    job_off = _staged_job(epochs=3, overlap=False)
    train_ds, valid_ds = _train_data(job_on.schema)

    r_on, recs_on = _run(job_on, tmp_path, "on", train_ds, valid_ds)
    r_off, recs_off = _run(job_off, tmp_path, "off", train_ds, valid_ds)

    assert len(r_on.history) == len(r_off.history) == 3
    for a, b in zip(r_on.history, r_off.history):
        assert a.train_error == pytest.approx(b.train_error, rel=1e-6)
        assert a.valid_error == pytest.approx(b.valid_error, rel=1e-6)
        assert a.valid_auc == pytest.approx(b.valid_auc, abs=1e-6)

    def reports(recs):
        return {r["epoch"]: r for r in recs if r["kind"] == "overlap_report"}

    rep_on, rep_off = reports(recs_on), reports(recs_off)
    assert sorted(rep_on) == sorted(rep_off) == [0, 1, 2]
    for ep in rep_on:
        assert rep_on[ep]["tier"] == rep_off[ep]["tier"] == "staged"
        assert rep_on[ep]["order_digest"] == rep_off[ep]["order_digest"]
        assert rep_on[ep]["order_digest"] is not None
    assert all(rep_on[ep]["overlap"] is True for ep in rep_on)
    assert all(rep_off[ep]["overlap"] is False for ep in rep_off)


def test_overlap_resume_order_byte_identical(tmp_path):
    """Kill+resume at an epoch boundary: the resumed overlap run draws the
    SAME per-epoch batch order (digests) and the same metrics as an
    uninterrupted non-overlapped run — restart determinism survives the
    feeder."""
    ckpt = tmp_path / "ckpt"
    job2 = _staged_job(epochs=2, overlap=True, ckpt_dir=ckpt)
    train_ds, valid_ds = _train_data(job2.schema)
    _run(job2, tmp_path, "first", train_ds, valid_ds)  # terminal at epoch 2

    job4 = _staged_job(epochs=4, overlap=True, ckpt_dir=ckpt)
    r_resumed, recs_resumed = _run(job4, tmp_path, "resumed",
                                   train_ds, valid_ds)
    assert r_resumed.resumed_from_epoch == 2
    assert [m.epoch for m in r_resumed.history] == [2, 3]

    job4_off = _staged_job(epochs=4, overlap=False)
    r_straight, recs_straight = _run(job4_off, tmp_path, "straight",
                                     train_ds, valid_ds)

    def digests(recs):
        return {r["epoch"]: r["order_digest"] for r in recs
                if r["kind"] == "overlap_report"}

    d_resumed, d_straight = digests(recs_resumed), digests(recs_straight)
    for ep in (2, 3):
        assert d_resumed[ep] == d_straight[ep] is not None
    # the resumed trajectory equals the uninterrupted one (checkpoint
    # restores exact state; order is identical; math is deterministic)
    straight_tail = {m.epoch: m for m in r_straight.history}
    for m in r_resumed.history:
        assert m.train_error == pytest.approx(
            straight_tail[m.epoch].train_error, rel=1e-5)
        assert m.valid_auc == pytest.approx(
            straight_tail[m.epoch].valid_auc, abs=1e-5)


def test_feeder_chaos_fails_train_epoch_loudly(tmp_path):
    """End-to-end: a chaos raise at the feeder boundary fails train()
    with the injected error (and the injection is journaled) rather than
    hanging the epoch."""
    chaos.configure(plan_mod.parse_plan({"faults": [
        {"site": "data.feeder", "at_call": 1}]}))
    job = _staged_job(epochs=2, overlap=True)
    train_ds, valid_ds = _train_data(job.schema, n=512)
    tele = tmp_path / "tele"
    obs.configure(str(tele), flush_every=1)
    from shifu_tpu.train import train
    with pytest.raises(chaos.ChaosError):
        train(job, train_ds, valid_ds, console=lambda s: None)
    obs.flush()
    recs = obs.read_journal(str(tele / "journal.jsonl"))
    assert any(r["kind"] == "chaos_inject" and r["site"] == "data.feeder"
               for r in recs)


def test_overlap_report_schema_and_profile_rendering(tmp_path, capsys):
    """overlap_report journal schema + the profile surfaces (the
    tests/test_obs.py-style contract for the new event)."""
    from shifu_tpu.launcher import cli
    from shifu_tpu.obs import render as obs_render

    job = _staged_job(epochs=2, overlap=True, prefetch_depth=0)  # auto
    train_ds, valid_ds = _train_data(job.schema)
    _r, recs = _run(job, tmp_path, "sch", train_ds, valid_ds)

    reps = [r for r in recs if r["kind"] == "overlap_report"]
    assert [r["epoch"] for r in reps] == [0, 1]
    for r in reps:
        assert r["tier"] == "staged"
        assert r["overlap"] is True
        assert r["prefetch_depth"] >= 1
        for k in ("input_exposed_s", "input_production_s", "input_hidden_s",
                  "eval_s"):
            assert isinstance(r[k], (int, float)) and r[k] >= 0
        assert r["input_hidden_s"] <= r["input_production_s"] + 1e-9
        assert r["prefetched_chunks"] >= 0
        eff = r["overlap_efficiency"]
        assert eff is None or 0.0 <= eff <= 1.0
        int(r["order_digest"], 16)

    # registry series ride along
    reg = obs.default_registry()
    assert reg.counter("overlap_exposed_seconds_total").value(
        kind="eval") > 0

    # profile: summary dict + text rendering carry the overlap view
    summary = obs_render.profile_summary(str(tmp_path / "tele_sch"))
    assert summary["overlap"] is not None
    assert [e["epoch"] for e in summary["overlap"]["epochs"]] == [0, 1]
    capsys.readouterr()
    assert cli.main(["profile", str(tmp_path / "tele_sch")]) == 0
    text = capsys.readouterr().out
    assert "overlap engine:" in text


def test_async_eval_matches_blocking_reference():
    """The windowed async eval computes exactly what a per-batch blocking
    fetch computes (same scores, same streaming accumulation)."""
    import jax

    from shifu_tpu.ops import metrics as metrics_lib
    from shifu_tpu.train import init_state, make_eval_step
    from shifu_tpu.train.loop import evaluate

    job = _staged_job(epochs=1)
    ds = _dataset(n=300, f=10, seed=3)  # non-multiple of 4096: pads
    state = init_state(job, 10)
    eval_step = make_eval_step(job)
    err, auc = evaluate(state, ds, job, eval_step)

    sm = metrics_lib.StreamingMetrics()
    bs = 4096
    for lo in range(0, ds.num_rows, bs):
        batch = {"features": ds.features[lo:lo + bs],
                 "target": ds.target[lo:lo + bs],
                 "weight": ds.weight[lo:lo + bs]}
        padded, mask = pipe.pad_to_batch(batch, bs)
        s = np.asarray(jax.device_get(eval_step(state, padded)))
        n = int(mask.sum())
        sm.update(s[:n, 0], batch["target"][:, 0], batch["weight"][:, 0])
    assert err == pytest.approx(sm.weighted_error(), rel=1e-6)
    assert auc == pytest.approx(sm.auc(), abs=1e-9)


def test_perbatch_tier_overlap_parity(tmp_path):
    """The feeder also serves the per-batch dispatch tier (staged=False):
    same metrics and journaled order with overlap on vs off."""
    def job_for(overlap):
        j = _staged_job(epochs=2, overlap=overlap)
        return j.replace(data=dataclasses.replace(
            j.data, staged=False)).validate()

    train_ds, valid_ds = _train_data(job_for(True).schema, n=1024)
    r_on, recs_on = _run(job_for(True), tmp_path, "pb_on",
                         train_ds, valid_ds)
    r_off, recs_off = _run(job_for(False), tmp_path, "pb_off",
                           train_ds, valid_ds)
    for a, b in zip(r_on.history, r_off.history):
        assert a.train_error == pytest.approx(b.train_error, rel=1e-6)
        assert a.valid_auc == pytest.approx(b.valid_auc, abs=1e-6)

    def digests(recs):
        return {r["epoch"]: (r["tier"], r["order_digest"]) for r in recs
                if r["kind"] == "overlap_report"}

    assert digests(recs_on) == digests(recs_off)
    assert all(t == "batch" for t, _d in digests(recs_on).values())


# ------------------------------------- the resident tiers, one epoch deep
#
# ISSUE 35: where the train tier and the eval tier are both resident,
# epoch e+1's scan is dispatched from inside epoch e's eval, once the eval
# pass is queued and before the host fetches and accumulates its scores.
# Same chunks into the same accumulator in the same order: every number
# train() reports equals the sequential run's, bit for bit.  It engages
# where the newest evaluated epoch's accumulation took at least
# `_AHEAD_MIN_HOST_SHARE` of its wall: a matter of timing, so the cases pin
# that threshold (0: whenever one has been observed, i.e. never at the
# first boundary; inf: never) and one case holds the rule to what the
# journal says was observed.

N_AHEAD_TRAIN = 2048
N_AHEAD_VALID = 2 * 4096 + 333   # three eval blocks, the last with a tail


@pytest.fixture(autouse=True)
def _ahead_whenever_observed(monkeypatch):
    from shifu_tpu.train import loop
    monkeypatch.setattr(loop, "_AHEAD_MIN_HOST_SHARE", 0.0)


def _ahead_job(epochs=4, overlap=True, ckpt_dir=None, save_every=1,
               patience=0, eval_every=1, resident_bytes=None, obs_cfg=None):
    job = _staged_job(epochs=epochs, overlap=overlap)
    if obs_cfg is not None:
        job = job.replace(obs=obs_cfg)
    data = dataclasses.replace(
        job.data, device_resident_bytes=(
            DataConfig().device_resident_bytes if resident_bytes is None
            else resident_bytes))
    train_cfg = dataclasses.replace(job.train, early_stop_patience=patience,
                                    eval_every_epochs=eval_every)
    job = job.replace(data=data, train=train_cfg)
    if ckpt_dir is not None:
        job = job.replace(runtime=dataclasses.replace(
            job.runtime, checkpoint=dataclasses.replace(
                job.runtime.checkpoint, directory=str(ckpt_dir),
                save_every_epochs=save_every)))
    return job.validate()


@pytest.fixture(scope="module")
def ahead_data():
    schema = synthetic.make_schema(num_features=10)
    rows = synthetic.make_rows(N_AHEAD_TRAIN + N_AHEAD_VALID, schema, seed=35,
                               noise=0.3)
    cols = reader.project_columns(rows, schema)
    full = pipe.TabularDataset(cols["features"], cols["target"],
                               cols["weight"])
    return (full.take(np.arange(N_AHEAD_TRAIN)),
            full.take(np.arange(N_AHEAD_TRAIN, full.num_rows)))


def _ahead_run(job, data, callback=None, console=None, mesh=None):
    """(TrainResult or the exception train() left through, the journal's
    records, `eval_overlapped_epochs_total`)."""
    from shifu_tpu.train import train

    if mesh is not None:
        from shifu_tpu.parallel import data_parallel_mesh
        mesh = data_parallel_mesh(mesh)

    obs.reset_for_tests()
    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    try:
        out = train(job, *data, mesh=mesh,
                    console=console or (lambda s: None),
                    epoch_callback=callback)
    except BaseException as e:  # SystemExit(75) of the SIGTERM drain too
        out = e
    overlapped = obs.counter("eval_overlapped_epochs_total", "").total()
    obs.set_journal(None)
    return out, journal.records, overlapped


def _numbers(m):
    """What an epoch reports, the times apart."""
    return (m.epoch, m.train_error, m.valid_error, m.valid_auc)


def _epoch_events(recs):
    return [tuple(r[k] for k in ("epoch", "train_error", "valid_error",
                                 "valid_auc"))
            for r in recs if r["kind"] == "epoch"]


def _train_bytes(ds) -> int:
    return sum(a.nbytes for a in (ds.features, ds.target, ds.weight))


#: case -> (job settings, which boundaries dispatch the next scan ahead):
#: never the first (nothing observed yet) and never the last
AHEAD_RULE = {
    "plain": (dict(epochs=4), [False, True, True, False]),
    "three_epochs": (dict(epochs=3), [False, True, False]),
    # one process over a four-device mesh qualifies as one chip does
    "mesh_of_four": (dict(epochs=4, mesh=4), [False, True, True, False]),
    # an accumulation too short to be worth hiding: the sequential order
    "not_worth_hiding": (dict(epochs=4, min_share=float("inf")),
                         [False] * 4),
    "early_stopping": (dict(epochs=4, patience=50), [False] * 4),
    "save_every_epoch": (dict(epochs=4, save_every=1), [False] * 4),
    "save_every_other": (dict(epochs=6, save_every=2),
                         [False, False, True, False, True, False]),
    # the observation is the newest epoch's that evaluated (0, then 2)
    "eval_every_other": (dict(epochs=6, eval_every=2),
                         [False, False, True, False, True, False]),
    "streamed_eval": (dict(epochs=3, resident_bytes="train"), [False] * 3),
    "staged_train": (dict(epochs=3, resident_bytes=0), [False] * 3),
}


@pytest.mark.parametrize("case", sorted(AHEAD_RULE))
def test_scan_ahead_rule_and_bit_identity(case, ahead_data, tmp_path,
                                          monkeypatch):
    """The rule, a case each: the counter and the reports read what it
    says, and history, the journal's `epoch` events, the order digests and
    the returned state equal the sequential run's bit for bit."""
    import jax
    from shifu_tpu.train import loop

    settings, want = AHEAD_RULE[case]
    settings = dict(settings)
    mesh = settings.pop("mesh", None)
    if "min_share" in settings:
        monkeypatch.setattr(loop, "_AHEAD_MIN_HOST_SHARE",
                            settings.pop("min_share"))
    if settings.get("resident_bytes") == "train":
        settings["resident_bytes"] = _train_bytes(ahead_data[0])
    needs_ckpt = "save_every" in settings

    def job(overlap):
        ckpt = (tmp_path / f"ckpt_{overlap}") if needs_ckpt else None
        return _ahead_job(overlap=overlap, ckpt_dir=ckpt, **settings)

    r_on, recs_on, n_on = _ahead_run(job(True), ahead_data, mesh=mesh)
    r_off, recs_off, n_off = _ahead_run(job(False), ahead_data, mesh=mesh)

    def reports(recs):
        return [r for r in recs if r["kind"] == "overlap_report"]

    assert [r["eval_overlapped"] for r in reports(recs_on)] == want
    assert n_on == sum(want) and n_off == 0
    assert not any(r["eval_overlapped"] for r in reports(recs_off))
    for r in reports(recs_on):
        assert (r["eval_beside_scan_s"] > 0) == r["eval_overlapped"]
        assert r["eval_beside_scan_s"] <= r["eval_s"]
    tiers = {r["tier"] for r in reports(recs_on)}
    assert tiers == ({"staged"} if case == "staged_train" else {"resident"})
    evals = {r["eval_tier"] for r in reports(recs_on)} - {None}
    assert evals == ({"streamed"} if case in ("staged_train", "streamed_eval")
                     else {"resident"})

    assert len(r_on.history) == settings["epochs"]
    # assert_equal: an epoch that skipped eval reports NaN on both sides
    np.testing.assert_equal([_numbers(m) for m in r_on.history],
                            [_numbers(m) for m in r_off.history])
    np.testing.assert_equal(_epoch_events(recs_on), _epoch_events(recs_off))
    assert ([r["order_digest"] for r in reports(recs_on)]
            == [r["order_digest"] for r in reports(recs_off)])
    for a, b in zip(jax.tree_util.tree_leaves(r_on.state.params),
                    jax.tree_util.tree_leaves(r_off.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert r_on.baseline_profile == r_off.baseline_profile


def test_scan_ahead_engages_by_what_the_journal_says_was_observed(
        ahead_data, monkeypatch):
    """With a threshold of its own the rule follows the timing, whatever it
    is: boundary e dispatches ahead exactly where epoch e-1's accumulation
    (phase `epoch/eval/accumulate` of its `goodput` event) took that share
    of its wall."""
    from shifu_tpu.train import loop

    share = 0.02
    monkeypatch.setattr(loop, "_AHEAD_MIN_HOST_SHARE", share)
    _r, recs, n = _ahead_run(_ahead_job(epochs=8), ahead_data)
    good = {r["epoch"]: r for r in recs if r["kind"] == "goodput"}
    took = [r["eval_overlapped"] for r in recs
            if r["kind"] == "overlap_report"]
    assert took[0] is False and took[7] is False and n == sum(took)
    for e in range(1, 7):
        seen = (good[e - 1]["phases"]["epoch/eval/accumulate"][0]
                / good[e - 1]["wall_s"])
        if abs(seen - share) > 1e-3:    # the event's seconds are rounded
            assert took[e] == (seen >= share), (e, seen)


def test_scan_ahead_leaves_a_scheduled_capture_its_whole_scan(ahead_data,
                                                              tmp_path):
    """`obs.trace_epochs` schedules epoch 2: its scan is not dispatched
    from epoch 1's eval, so the capture, which opens at the epoch's start,
    sees the epoch program run."""
    from shifu_tpu.config import ObsConfig

    cfg = ObsConfig(trace_epochs="2", trace_dir=str(tmp_path / "tr"))
    _r, recs, n = _ahead_run(_ahead_job(epochs=5, obs_cfg=cfg), ahead_data)
    assert [r["eval_overlapped"] for r in recs
            if r["kind"] == "overlap_report"] == [False, False, True, True,
                                                  False]
    assert n == 2
    prof = [r for r in recs if r["kind"] == "device_profile"]
    assert [p["epoch"] for p in prof] == [2]
    assert any("epoch_step" in name for name in prof[0]["modules"])


class _Killed(Exception):
    pass


def _kill_at(epoch, seen):
    def callback(m):
        seen.append(m)
        if m.epoch == epoch:
            raise _Killed
    return callback


@pytest.mark.parametrize("kill_at", [2, 3])
def test_scan_ahead_cadence_save_resumes_to_the_same_history(
        kill_at, ahead_data, tmp_path):
    """Saves every other epoch, so the look-ahead is on at the boundaries
    between them; the run dies in its callback after epoch 2 (epoch 3's
    scan in flight, the newest save labelled 2) or after epoch 3 (just
    saved, labelled 4).  The resume starts at the label and, with what the
    first run reported before it, gives the uninterrupted run's history:
    no epoch's training applied twice, none skipped."""
    ckpt = tmp_path / "ckpt"
    first: list = []
    out, _recs, n = _ahead_run(
        _ahead_job(epochs=6, ckpt_dir=ckpt, save_every=2), ahead_data,
        callback=_kill_at(kill_at, first))
    assert isinstance(out, _Killed)
    assert n == 1                       # boundary 2 (a save is due at 1, 3)
    label = 2 if kill_at == 2 else 4
    resumed, _recs, _n = _ahead_run(
        _ahead_job(epochs=6, ckpt_dir=ckpt, save_every=2), ahead_data)
    assert resumed.resumed_from_epoch == label
    straight, _recs, _n = _ahead_run(_ahead_job(epochs=6, overlap=False),
                                     ahead_data)
    assert ([_numbers(m) for m in first[:label]]
            + [_numbers(m) for m in resumed.history]
            == [_numbers(m) for m in straight.history])


def test_scan_ahead_sigterm_drain_resumes_to_the_same_history(
        ahead_data, tmp_path):
    """SIGTERM lands at epoch 1's close with epoch 2's scan in flight: the
    state in hand is the one after epoch 2, so the drain waits for that
    epoch's boundary, saves it under label 3 and exits 75; the resume
    replays nothing and skips nothing."""
    import signal

    ckpt = tmp_path / "ckpt"

    def console(line):
        # the epoch's console line is printed after its eval — here with
        # the next scan dispatched — and before the boundary's drain point
        if line.startswith("Epoch 1:"):
            os.kill(os.getpid(), signal.SIGTERM)

    def job():
        return _ahead_job(epochs=6, ckpt_dir=ckpt, save_every=10_000)

    out, recs, n = _ahead_run(job(), ahead_data, console=console)
    assert isinstance(out, SystemExit) and out.code == 75
    assert n == 1                       # boundary 1; none at 2
    first = _epoch_events(recs)         # epoch 2 reported before the drain
    assert [e[0] for e in first] == [0, 1, 2]
    grace = [r for r in recs if r["kind"] == "preemption_grace"]
    assert [(g["epoch"], g["saved"]) for g in grace] == [(3, True)]
    resumed, _recs, _n = _ahead_run(job(), ahead_data)
    assert resumed.resumed_from_epoch == 3
    straight, _recs, _n = _ahead_run(_ahead_job(epochs=6, overlap=False),
                                     ahead_data)
    assert (first + [_numbers(m) for m in resumed.history]
            == [_numbers(m) for m in straight.history])


def test_scan_ahead_callback_raises_with_a_scan_in_flight(ahead_data):
    """The benchmark's window closes by an exception out of
    `epoch_callback`: with epoch 3's scan in flight it still leaves through
    the `finally` block — `train_end` journaled, no span, ledger, feeder or
    handler left behind — and the next `train()` in the process is whole."""
    import signal
    import threading

    before = signal.getsignal(signal.SIGTERM)
    seen: list = []
    out, recs, n = _ahead_run(_ahead_job(epochs=6), ahead_data,
                              callback=_kill_at(2, seen))
    assert isinstance(out, _Killed)
    assert n == 2 and [m.epoch for m in seen] == [0, 1, 2]
    ends = [r for r in recs if r["kind"] == "train_end"]
    assert [r["epochs_completed"] for r in ends] == [3]
    assert recs[-1]["kind"] == "train_end"
    assert obs.current_path() == ""
    assert obs.goodput.current() is None
    assert signal.getsignal(signal.SIGTERM) is before
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith("shifu-")]
    again, _recs, _n = _ahead_run(_ahead_job(epochs=3), ahead_data)
    assert [_numbers(m) for m in again.history] == [_numbers(m)
                                                    for m in seen]


AHEAD_PHASES = ("epoch/eval/prep", "epoch/eval/dispatch", "epoch/eval/fetch",
                "epoch/eval/accumulate", "epoch/train/device_wait")


@pytest.mark.parametrize("overlap", [True, False])
def test_scan_ahead_goodput_buckets_sum_to_each_epochs_wall(overlap,
                                                            ahead_data):
    """Every epoch's buckets sum to its wall, the eval bucket is the eval
    span, and the five phases the benchmark reads are there under their
    paths — also in an epoch whose scan was dispatched during the one
    before (its `step` bucket is what was left to wait for)."""
    _r, recs, n = _ahead_run(_ahead_job(epochs=4, overlap=overlap),
                             ahead_data)
    assert n == (2 if overlap else 0)    # boundaries 1 and 2
    good = [r for r in recs if r["kind"] == "goodput"]
    reps = {r["epoch"]: r for r in recs if r["kind"] == "overlap_report"}
    assert [g["epoch"] for g in good] == [0, 1, 2, 3]
    for g in good:
        assert sum(g["buckets"].values()) == pytest.approx(g["wall_s"],
                                                           abs=1e-5)
        assert g["buckets"]["other"] > 0      # nothing counted twice
        assert set(AHEAD_PHASES) <= set(g["phases"])
        assert ("epoch/eval/scan_ahead" in g["phases"]) == (
            overlap and g["epoch"] in (1, 2))
        if g["buckets"]["compile"] == 0:    # a compile is taken out of it
            assert g["buckets"]["eval"] == pytest.approx(
                reps[g["epoch"]]["eval_s"], abs=1e-5)
        wait = g["phases"]["epoch/train/device_wait"][0]
        if overlap and g["epoch"] > 1:
            # dispatched ahead: the step bucket is the wait alone
            assert g["buckets"]["step"] == pytest.approx(wait, abs=2e-6)
        else:
            assert g["buckets"]["step"] + g["buckets"]["compile"] >= wait
