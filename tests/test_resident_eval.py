"""The resident eval tier (ISSUE 30): where `train()` keeps the train rows on
the device and the valid rows fit the same budget beside them, the valid
set's features are placed once and every epoch's `evaluate()` is one
dispatch over them; everywhere else the batches are streamed from the host
as before.  One eval algorithm with two sources of batches: the same
forward, the same chunks into the same accumulation, the same four phases."""

import dataclasses

import jax
import numpy as np
import pytest

from shifu_tpu import obs
from shifu_tpu.train import loop as loop_mod
from shifu_tpu.train import train
from shifu_tpu.train.step import make_eval_step

EVAL_PHASES = ("epoch/eval/prep", "epoch/eval/dispatch", "epoch/eval/fetch",
               "epoch/eval/accumulate")
#: not a multiple of the eval batch (4,096): three whole blocks and a tail
N_VALID = 3 * 4096 + 777
N_TRAIN = 512


@pytest.fixture(autouse=True)
def _reset_obs():
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()


@pytest.fixture(scope="module")
def rows(small_job):
    """(train set, valid set): the valid set's weight column is not
    constant and has rows of weight 0."""
    from shifu_tpu.data import pipeline, reader, synthetic

    raw = synthetic.make_rows(N_TRAIN + N_VALID, small_job.schema, seed=30,
                              noise=0.3)
    cols = reader.project_columns(raw, small_job.schema)
    full = pipeline.TabularDataset(cols["features"], cols["target"],
                                   cols["weight"])
    valid = full.take(np.arange(N_TRAIN, full.num_rows))
    rng = np.random.default_rng(30)
    valid.weight[:, 0] = rng.choice([0.0, 0.5, 1.0, 2.5], N_VALID).astype(
        np.float32)
    valid.weight[-5:, 0] = (0.0, 1.5, 0.0, 2.0, 0.25)   # in the tail too
    return full.take(np.arange(N_TRAIN)), valid


def _job(small_job, wire="float32", **data):
    compute = "bfloat16" if wire == "bfloat16" else "float32"
    return small_job.replace(
        data=dataclasses.replace(small_job.data, wire_dtype=wire, **data),
        model=dataclasses.replace(small_job.model, compute_dtype=compute),
        train=dataclasses.replace(small_job.train, epochs=2)).validate()


def _train_bytes(train_ds) -> int:
    """A budget the train rows fill: what they take is at most this, and
    with the valid set's features beside them it is more."""
    return sum(a.nbytes for a in (train_ds.features, train_ds.target,
                                  train_ds.weight))


def _both_passes(state, valid, job, mesh=None):
    """((error, auc), sunk scores, rows counted) of the streamed pass and
    of the resident pass over one state."""
    resident = loop_mod.place_resident_eval(valid, job, mesh, 1 << 30)
    assert resident is not None
    assert resident.features.shape == (4, 4096, 30)
    rows = obs.counter("eval_rows_total", "rows evaluated (nonzero weight)")
    out = []
    for tier in (None, resident):
        sunk, before = [], rows.total()
        metrics = loop_mod.evaluate(state, valid, job, make_eval_step(job),
                                    mesh, score_sink=sunk.append,
                                    resident=tier)
        out.append((metrics, np.concatenate(sunk), rows.total() - before))
    return out


@pytest.mark.parametrize("wire", ["float32", "bfloat16", "int8"])
def test_resident_pass_equals_streamed_pass(small_job, rows, wire):
    """On one trained state the two passes give the same scores and the
    same (weighted_error, auc).  Bit for bit here: on the CPU the mapped
    program compiles the same forward as the per-batch one.  Where a
    backend fuses the mapped program differently the scores may differ in
    their last bit (1e-6 is the line the chip's runs are held to through
    `valid_gap`)."""
    train_ds, valid = rows
    job = _job(small_job, wire)
    state = train(job, train_ds, valid, console=lambda s: None).state
    passes = obs.counter("eval_resident_passes_total", "")
    engaged = passes.total()
    (m_s, s_s, n_s), (m_r, s_r, n_r) = _both_passes(state, valid, job)
    assert passes.total() - engaged == 1
    assert s_s.dtype == s_r.dtype == np.float32
    np.testing.assert_array_equal(s_r, s_s)
    assert m_r == m_s
    assert np.isfinite(m_s[0]) and 0.5 < m_s[1] <= 1.0
    # the sink sees the rows of positive weight, the counter those of
    # nonzero weight: the same rows on both paths, none of the padded tail
    assert s_s.size == int(np.count_nonzero(valid.weight > 0))
    assert n_s == n_r == int(np.count_nonzero(valid.weight))
    assert obs.current_path() == ""


def test_resident_pass_equals_streamed_pass_on_a_mesh(small_job, rows):
    """The same on a 4-device CPU mesh: the blocks are placed as the train
    blocks are (`shard_blocks`, the batch axis split), and the journal says
    the tier engaged."""
    from shifu_tpu.parallel import data_parallel_mesh

    train_ds, valid = rows
    mesh = data_parallel_mesh(4)
    job = _job(small_job)
    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    try:
        state = train(job, train_ds, valid, mesh=mesh,
                      console=lambda s: None).state
    finally:
        obs.set_journal(None)
    assert [(r["tier"], r["eval_tier"]) for r in journal.records
            if r["kind"] == "overlap_report"] == [("resident", "resident")] * 2
    (m_s, s_s, n_s), (m_r, s_r, n_r) = _both_passes(state, valid, job, mesh)
    np.testing.assert_allclose(s_r, s_s, rtol=0, atol=1e-6)
    assert m_r == pytest.approx(m_s, abs=1e-6)
    assert n_s == n_r == int(np.count_nonzero(valid.weight))
    resident = loop_mod.place_resident_eval(valid, job, mesh, 1 << 30)
    assert len(resident.features.sharding.device_set) == 4
    assert resident.features.sharding.shard_shape(
        resident.features.shape) == (4, 1024, 30)


def _run(job, train_ds, valid):
    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    try:
        result = train(job, train_ds, valid, console=lambda s: None)
    finally:
        obs.set_journal(None)
    reports = [r for r in journal.records if r["kind"] == "overlap_report"]
    good = [r for r in journal.records if r["kind"] == "goodput"]
    return result, reports, good


@pytest.mark.parametrize("case,tier,eval_tier", [
    ("both fit", "resident", "resident"),
    ("the valid rows do not fit", "resident", "streamed"),
    ("staged", "staged", "streamed"),
    ("per batch", "batch", "streamed"),
])
def test_the_byte_rule_picks_the_eval_tier(small_job, rows, case, tier,
                                           eval_tier):
    """Resident when train + valid fit `device_resident_bytes`, streamed
    when they do not and on the staged and per-batch tiers: read from the
    journal's `eval_tier`.  Both paths count the same rows and carry the
    four phases."""
    train_ds, valid = rows
    data = {"both fit": {},
            "the valid rows do not fit":
                {"device_resident_bytes": _train_bytes(train_ds)},
            "staged": {"device_resident_bytes": 0},
            "per batch": {"staged": False}}[case]
    job = _job(small_job, **data)
    _, reports, good = _run(job, train_ds, valid)
    assert [(r["tier"], r["eval_tier"]) for r in reports] == [
        (tier, eval_tier)] * 2
    assert obs.counter("eval_resident_passes_total", "").total() == (
        2 if eval_tier == "resident" else 0)
    assert obs.counter("eval_rows_total", "").total() == 2 * int(
        np.count_nonzero(valid.weight))
    for r in good:
        assert set(EVAL_PHASES) <= set(r["phases"])
        assert r["phases"]["epoch/eval/accumulate"][1] == 5   # 4 chunks + 1


def test_a_job_reports_the_same_errors_on_either_eval_tier(small_job, rows):
    """The same job, the valid rows resident or streamed: every epoch's
    errors and AUC are the same (bit for bit on the CPU, see above)."""
    train_ds, valid = rows
    fits, _, _ = _run(_job(small_job), train_ds, valid)
    tight, _, _ = _run(_job(small_job,
                            device_resident_bytes=_train_bytes(train_ds)),
                       train_ds, valid)
    assert [(m.train_error, m.valid_error, m.valid_auc)
            for m in fits.history] == [
        (m.train_error, m.valid_error, m.valid_auc) for m in tight.history]


def test_an_epoch_that_skips_eval_names_no_eval_tier(small_job, rows):
    train_ds, valid = rows
    job = _job(small_job)
    job = job.replace(train=dataclasses.replace(job.train, epochs=3,
                                                eval_every_epochs=2))
    _, reports, _ = _run(job, train_ds, valid)
    assert [r["eval_tier"] for r in reports] == ["resident", None, "resident"]
    assert obs.counter("eval_resident_passes_total", "").total() == 2


def test_the_tier_is_not_placed_where_there_is_nothing_to_place(small_job,
                                                                rows):
    train_ds, valid = rows
    job = _job(small_job)
    blocks_bytes = 4 * 4096 * 30 * 4
    assert loop_mod.place_resident_eval(valid, job, None,
                                        blocks_bytes - 1) is None
    assert loop_mod.place_resident_eval(valid, job, None,
                                        blocks_bytes) is not None
    assert loop_mod.place_resident_eval(valid.take(np.arange(0)), job, None,
                                        1 << 30) is None
    # the wire format is what the budget is reckoned in: int8 is a quarter
    q = loop_mod.place_resident_eval(valid, _job(small_job, "int8"), None,
                                     blocks_bytes // 4)
    assert q is not None and q.features.dtype == np.int8
    # the padded tail is zero rows
    tail = np.asarray(jax.device_get(q.features))[-1, 777:]
    assert not tail.any()
