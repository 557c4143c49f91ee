"""Phase spans (ISSUE 25): a hot `obs.span` is a phase of the epoch's
goodput ledger and an annotation on the profiler's clock; `train()` opens
them inside `evaluate()` and around the epoch's device wait, credits the
wait to the `step` bucket, and holds one `gc.callbacks` hook while it
runs."""

import dataclasses
import gc
import glob
import os
import subprocess
import sys

import numpy as np
import pytest

from shifu_tpu import obs
from shifu_tpu.obs import goodput as goodput_mod
from shifu_tpu.train import loop as loop_mod
from shifu_tpu.train import train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EVAL_PHASES = ("epoch/eval/prep", "epoch/eval/dispatch", "epoch/eval/fetch",
               "epoch/eval/accumulate")
WAIT = "epoch/train/device_wait"


@pytest.fixture(autouse=True)
def _reset_obs():
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()


EVAL_BATCH = 16384


@pytest.fixture(scope="module")
def datasets(small_job):
    """(512 train rows; a valid set of twenty eval batches of 16,384, so
    that a batch is long beside the generator's glue between two spans,
    ~50 us, and the pass beside `evaluate()`'s fixed costs; two train
    batches of that size)."""
    from shifu_tpu.data import pipeline, reader, synthetic

    rows = synthetic.make_rows(512 + 22 * EVAL_BATCH, small_job.schema,
                               seed=11, noise=0.3)
    cols = reader.project_columns(rows, small_job.schema)
    full = pipeline.TabularDataset(cols["features"], cols["target"],
                                   cols["weight"])
    at = 512 + 2 * EVAL_BATCH
    return (full.take(np.arange(512)),
            full.take(np.arange(at, full.num_rows)),
            full.take(np.arange(512, at)))


@pytest.fixture(scope="module", params=["resident", "streamed"])
def two_epochs(request, small_job, datasets):
    """(the `goodput` events of a two-epoch `train()`, gc.callbacks' length
    before it, after it, the span path after it, and the eval tier asked
    for).  The job's batch is the eval batch: `evaluate()` takes the larger
    of it and 4,096.  The model is 2x256 wide so that an eval pass is
    ~0.4 s here: under six xdist workers a thread descheduled between two
    spans has cost 14 ms, a sixth of the ~85 ms pass of the 2x16 model and
    a thirtieth of this.  The train rows are resident in both cases;
    "resident" leaves the budget at its default, beside which the valid
    rows fit too (ISSUE 30), "streamed" states a budget that the train
    rows alone fill, so that `evaluate()` streams the valid rows."""
    obs.reset_for_tests()
    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    data = dataclasses.replace(small_job.data, batch_size=EVAL_BATCH)
    if request.param == "streamed":
        data = dataclasses.replace(data, device_resident_bytes=sum(
            a.nbytes for a in (datasets[2].features, datasets[2].target,
                               datasets[2].weight)))
    job = small_job.replace(
        data=data,
        model=dataclasses.replace(small_job.model, hidden_nodes=(256, 256)),
        train=dataclasses.replace(small_job.train, epochs=2))
    hooks = len(gc.callbacks)
    try:
        train(job, datasets[2], datasets[1], console=lambda s: None)
    finally:
        obs.set_journal(None)
    good = [r for r in journal.records if r["kind"] == "goodput"]
    tiers = [(r["tier"], r["eval_tier"]) for r in journal.records
             if r["kind"] == "overlap_report"]
    assert tiers == [("resident", request.param)] * 2
    return good, hooks, len(gc.callbacks), obs.current_path(), request.param


def test_every_goodput_event_carries_the_phases(two_epochs):
    good, eval_tier = two_epochs[0], two_epochs[4]
    assert [r["epoch"] for r in good] == [0, 1]
    for r in good:
        assert set(EVAL_PHASES) | {WAIT} <= set(r["phases"])
        for path, (seconds, count) in r["phases"].items():
            assert seconds >= 0 and count >= 1, path
        assert r["phases"][WAIT][1] == 1
        counts = {p.rsplit("/", 1)[1]: r["phases"][p][1] for p in EVAL_PHASES}
        if eval_tier == "streamed":
            # twenty batches: one more `prep` finds the set exhausted, one
            # more `accumulate` is the final reduction
            assert counts == {"prep": 21, "dispatch": 20, "fetch": 20,
                              "accumulate": 21}
        else:
            # one pass: the views, the one call, one slice of scores (twenty
            # blocks of 64 KB), and the same twenty chunks accumulated
            assert counts == {"prep": 1, "dispatch": 1, "fetch": 1,
                              "accumulate": 21}


def test_eval_phases_fill_the_eval_bucket(two_epochs):
    for r in two_epochs[0]:
        in_eval = sum(r["phases"][p][0] for p in EVAL_PHASES)
        # phases are raw host seconds; the buckets move a compile that ran
        # inside one into `compile`
        assert in_eval <= (r["buckets"]["eval"] + r["buckets"]["compile"]
                           + 1e-5)
        if r["compiles"] == 0:
            assert in_eval <= r["buckets"]["eval"] + 1e-5
            assert in_eval >= 0.9 * r["buckets"]["eval"]
    assert two_epochs[0][1]["compiles"] == 0


def test_step_bucket_holds_the_device_wait(two_epochs):
    for r in two_epochs[0]:
        wait = r["phases"][WAIT][0]
        assert r["buckets"]["step"] + r["buckets"]["compile"] >= wait - 1e-5
        if r["compiles"] == 0:
            assert r["buckets"]["step"] >= wait - 1e-5
        assert sum(r["buckets"].values()) == pytest.approx(r["wall_s"],
                                                           abs=1e-4)
        assert 0.0 <= r["goodput_fraction"] <= 1.0


def test_train_leaves_no_hook_and_no_open_span(two_epochs):
    _, before, after, path, _ = two_epochs
    assert after == before
    assert path == ""


def test_train_that_raises_leaves_no_hook(small_job, datasets):
    before = len(gc.callbacks)

    def boom(_metrics):
        assert len(gc.callbacks) == before + 1   # held while it runs
        raise RuntimeError("from the epoch callback")

    with pytest.raises(RuntimeError, match="epoch callback"):
        train(small_job, datasets[0], datasets[0], console=lambda s: None,
              epoch_callback=boom)
    assert len(gc.callbacks) == before
    assert obs.current_path() == ""


def test_evaluate_closes_its_spans_also_when_a_phase_raises(small_job,
                                                            datasets):
    from shifu_tpu.train.step import make_eval_step

    state = loop_mod.init_state(small_job, 30)
    eval_step = make_eval_step(small_job)
    with obs.span("epoch/eval"):
        loop_mod.evaluate(state, datasets[0], small_job, eval_step)
        assert obs.current_path() == "epoch/eval"
    assert obs.current_path() == ""

    def sink(_scores):
        raise RuntimeError("from the accumulate phase")

    with pytest.raises(RuntimeError, match="accumulate phase"):
        loop_mod.evaluate(state, datasets[0], small_job, eval_step,
                          score_sink=sink)
    assert obs.current_path() == ""

    def bad_step(_state, _batch):
        raise RuntimeError("from the dispatch phase")

    with pytest.raises(RuntimeError, match="dispatch phase"):
        loop_mod.evaluate(state, datasets[0], small_job, bad_step)
    assert obs.current_path() == ""


def test_accumulate_feeds_the_sink_and_the_row_counter_from_one_update():
    """PR 26: the accumulate phase takes its row count and the score sink's
    mask from `StreamingMetrics.update` (it used to compute both again).
    The sink still gets exactly the scores whose weight is > 0, in order,
    and `eval_rows_total` still counts the rows whose weight is not 0."""
    from shifu_tpu.ops.metrics import StreamingMetrics

    rng = np.random.default_rng(26)
    chunks = []
    for n in (4096, 1, 0, 300, 4096):
        s = rng.random(n).astype(np.float32)
        t = (rng.random(n) < 0.4).astype(np.float32)
        w = rng.choice([0.0, -2.0, 0.5, 1.0, 3.0], n).astype(np.float32)
        chunks.append((s, t, w))
    chunks.append((chunks[0][0], chunks[0][1], np.zeros(4096, np.float32)))

    counter = obs.counter("eval_rows_total", "rows evaluated (nonzero weight)")
    before = counter.total()
    sunk = []
    err, auc = loop_mod._accumulate_streaming(iter(chunks), sunk.append)

    assert len(sunk) == len(chunks)  # one call a chunk, empty ones too
    for got, (s, _, w) in zip(sunk, chunks):
        assert got.dtype == s.dtype and got.ndim == 1
        np.testing.assert_array_equal(got, s[w > 0])
    assert sunk[-1].size == 0
    assert counter.total() - before == sum(
        int(np.count_nonzero(w)) for _, _, w in chunks)
    assert counter.total() - before > sum(g.size for g in sunk)  # negatives

    sm = StreamingMetrics()
    for c in chunks:
        sm.update(*c)
    assert (err, auc) == (sm.weighted_error(), sm.auc())
    assert loop_mod._accumulate_streaming(iter(chunks)) == (err, auc)
    assert obs.current_path() == ""


def test_a_hot_span_goes_to_the_open_ledger_and_nowhere_else():
    led = goodput_mod.begin_epoch()
    with obs.span("epoch/eval"):
        for _ in range(3):
            with obs.span("prep", journal=False):
                pass
    rec = led.summary(1.0)
    seconds, count = rec["phases"]["epoch/eval/prep"]
    assert count == 3 and seconds >= 0
    assert "epoch/eval" not in rec["phases"]      # journaled spans are not
    hist = obs.default_registry().histogram("span_seconds")
    assert set(hist._snapshot()["values"]) == {"span=epoch/eval"}
    goodput_mod.end_epoch(0, 1.0)
    with obs.span("prep", journal=False):         # no ledger: the histogram
        pass
    assert set(hist._snapshot()["values"]) == {"span=epoch/eval",
                                               "span=prep"}


def test_ledger_rejects_a_phase_that_is_no_duration():
    led = goodput_mod.GoodputLedger()
    for bad in (float("nan"), float("inf"), -1.0):
        led.add_phase("x", bad)
    led.add_phase("x", 0.0)
    led.add_phase("x", 0.25)
    assert led.summary(1.0)["phases"] == {"x": [0.25, 2]}
    assert goodput_mod.note_phase("x", 1.0) is False   # between epochs


def test_gc_hook_times_collections_by_generation():
    led = goodput_mod.begin_epoch()
    hook = obs.spans.GcPhases()
    try:
        gc.collect(2)
        hook.fold()     # before the ledger's time: dropped
        gc.collect(0)
        gc.collect(2)
        gc.collect(2)
        assert "gc/gen2" not in led.summary(1.0)["phases"]   # not yet folded
        hook.fold(led)
        hook.fold(led)  # nothing since: adds nothing
    finally:
        hook.close()
    hook.close()    # twice is no error
    gc.collect(2)   # unhooked: not counted
    hook.fold(led)
    phases = led.summary(1.0)["phases"]
    assert phases["gc/gen2"][1] == 2
    assert phases["gc/gen0"][1] >= 1
    assert "gc/gen1" not in phases      # a generation with no pause
    assert hook not in gc.callbacks


@pytest.mark.parametrize("holder", ["add_phase", "add", "span_exit"])
def test_a_collection_under_the_ledgers_lock_does_not_deadlock(holder):
    """A collection runs on whichever thread trips it, at any bytecode:
    also inside `add_phase` / `add`, where that thread holds the ledger's
    (non-reentrant) lock.  The hook must not want that lock."""
    import threading

    led = goodput_mod.begin_epoch()
    hook = obs.spans.GcPhases()
    done = threading.Event()

    def body():
        if holder == "span_exit":
            # the real path: a hot span's exit reaches add_phase, whose
            # dict collects as soon as it is touched under the lock
            class Collecting(dict):
                def get(self, key, default=None):
                    gc.collect(0)
                    return dict.get(self, key, default)
            led._phases = Collecting()
            with obs.span("prep", journal=False):
                pass
        else:
            with led._lock:
                gc.collect(0)           # the hook's start and stop, here
            getattr(led, holder)("step", 0.5)
        done.set()

    worker = threading.Thread(target=body, daemon=True)
    try:
        worker.start()
        assert done.wait(30), "the gc hook blocked on the ledger's lock"
        hook.fold(led)
    finally:
        hook.close()
    phases = led.summary(1.0)["phases"]
    assert phases["gc/gen0"][1] == 1
    if holder == "span_exit":
        assert phases["prep"][1] == 1


def test_train_folds_the_collectors_pauses_into_each_epochs_event(
        small_job, datasets):
    """A collection inside an epoch is in that epoch's `goodput` event; one
    between two ledgers is in neither."""
    obs.reset_for_tests()
    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    job = small_job.replace(train=dataclasses.replace(small_job.train,
                                                      epochs=2))

    def between_epochs(_metrics):
        assert goodput_mod.current() is None
        gc.collect(1)

    real_evaluate = loop_mod.evaluate

    def evaluate_and_collect(*args, **kwargs):
        gc.collect(2)
        return real_evaluate(*args, **kwargs)

    loop_mod.evaluate = evaluate_and_collect
    gc.disable()    # only the planted collections run (the hook sees them)
    try:
        train(job, datasets[0], datasets[0], console=lambda s: None,
              epoch_callback=between_epochs)
    finally:
        gc.enable()
        loop_mod.evaluate = real_evaluate
        obs.set_journal(None)
    good = [r for r in journal.records if r["kind"] == "goodput"]
    assert len(good) == 2
    for r in good:
        assert r["phases"]["gc/gen2"][1] == 1
        assert "gc/gen1" not in r["phases"]     # ran with no ledger open
        assert sum(r["buckets"].values()) == pytest.approx(r["wall_s"],
                                                           abs=1e-4)


def test_spans_land_on_the_profilers_clock(tmp_path):
    """The annotation: a span open during a profiler session is a host
    event named `shifu:<full path>` in the trace."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.span("epoch/eval"):
            with obs.span("fetch", journal=False):
                jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert {"shifu:epoch/eval", "shifu:epoch/eval/fetch"} <= names


def test_spans_module_imports_without_jax():
    code = ("import sys, shifu_tpu.obs.spans as s\n"
            "with s.span('a', journal=False):\n"
            "    assert s.current_path() == 'a'\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
