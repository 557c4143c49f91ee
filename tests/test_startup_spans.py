"""Startup, opened (ISSUE 36): from `train()`'s entry to its first epoch a
ledger of its own takes the hot spans under `startup/...`; every compile is
split by JAX's own durations, with JAX's own verdict on the persistent cache;
and the first trained epoch's boundary journals both as one `startup` event,
after that epoch's `epoch_callback` has returned."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from shifu_tpu import obs
from shifu_tpu.obs import goodput as goodput_mod
from shifu_tpu.obs import introspect
from shifu_tpu.train import train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPLIT = introspect.SPLIT_FIELDS
TIERS = ("startup/tiers/flags", "startup/tiers/blocks", "startup/tiers/h2d",
         "startup/tiers/eval_tier")


@pytest.fixture(autouse=True)
def _reset_obs():
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()


@pytest.fixture(scope="module")
def datasets(small_job):
    from shifu_tpu.data import pipeline, reader, synthetic

    rows = synthetic.make_rows(640, small_job.schema, seed=5, noise=0.3)
    cols = reader.project_columns(rows, small_job.schema)
    full = pipeline.TabularDataset(cols["features"], cols["target"],
                                   cols["weight"])
    return full.take(np.arange(512)), full.take(np.arange(512, 640))


def _job(small_job, epochs, ckpt_dir=None):
    job = small_job.replace(
        data=dataclasses.replace(small_job.data, batch_size=64),
        train=dataclasses.replace(small_job.train, epochs=epochs))
    if ckpt_dir is not None:
        job = job.replace(runtime=dataclasses.replace(
            job.runtime, checkpoint=dataclasses.replace(
                job.runtime.checkpoint, directory=str(ckpt_dir))))
    return job.validate()


def _run(job, datasets, callback=None):
    """The journal's records of one `train()` call; the callback's calls
    are journalled as `callback` events, so that the order can be read."""
    journal = obs.RunJournal(None)
    obs.set_journal(journal)

    def marked(m):
        obs.event("callback", epoch=m.epoch)
        if callback is not None:
            callback(m)

    try:
        train(job, datasets[0], datasets[1], console=lambda s: None,
              epoch_callback=marked)
    finally:
        obs.set_journal(None)
    return journal.records


@pytest.fixture(scope="module")
def three_epochs(small_job, datasets):
    obs.reset_for_tests()
    return _run(_job(small_job, 3), datasets)


def _startups(records):
    return [r for r in records if r["kind"] == "startup"]


# ------------------------------------------------------------ the one event


def test_one_startup_event_after_the_first_callback(three_epochs):
    kinds = [(r["kind"], r.get("epoch")) for r in three_epochs
             if r["kind"] in ("startup", "callback", "goodput")]
    assert kinds == [("goodput", 0), ("callback", 0), ("startup", 0),
                     ("goodput", 1), ("callback", 1),
                     ("goodput", 2), ("callback", 2)]


def test_startup_event_fields_exactly(three_epochs):
    ev, = _startups(three_epochs)
    assert set(ev) - {"ts", "seq", "kind", "host", "span"} == {
        "wall_s", "phases", "first_epoch", "compiles", "epoch"}
    assert set(ev["first_epoch"]) == {"wall_s", "buckets", "phases"}
    for c in ev["compiles"]:
        assert set(c) == {"fn", "span", "cache", *SPLIT}, c
        assert c["cache"] in ("off", "hit", "miss")


def test_startup_phases_are_the_spans_by_full_path(three_epochs):
    ev, = _startups(three_epochs)
    assert {"startup/init_state", "startup/tiers", *TIERS} <= set(
        ev["phases"])
    # nothing was loaded from disk and nothing restored in this call
    assert "startup/ingest" not in ev["phases"]
    assert "startup/restore" not in ev["phases"]
    for path, (seconds, count) in ev["phases"].items():
        assert path.startswith("startup/") and seconds >= 0 and count == 1
    children = sum(ev["phases"][p][0] for p in TIERS)
    assert children <= ev["phases"]["startup/tiers"][0] + 1e-6


def test_first_epoch_is_the_first_goodput_record(three_epochs):
    ev, = _startups(three_epochs)
    good = [r for r in three_epochs if r["kind"] == "goodput"][0]
    assert ev["first_epoch"] == {k: good[k] for k in
                                 ("wall_s", "buckets", "phases")}
    assert ev["first_epoch"]["buckets"]["compile"] > 0


def test_phases_and_first_epoch_fit_in_the_wall(three_epochs):
    ev, = _startups(three_epochs)
    top = [p for p in ev["phases"]
           if p.rsplit("/", 1)[0] not in ev["phases"]]
    covered = (sum(ev["phases"][p][0] for p in top)
               + ev["first_epoch"]["wall_s"])
    assert covered <= ev["wall_s"] + 1e-3
    # little of the call is outside both (the issue's 10 %, with room for
    # a CPU under six workers)
    assert ev["wall_s"] - covered <= 0.25 * ev["wall_s"]
    # every compile ran inside a phase or the first epoch, so JAX's
    # seconds fit in what the spans cover
    assert sum(c[f] for c in ev["compiles"] for f in SPLIT) <= covered


def test_compiles_name_the_programs_and_the_spans_they_ran_under(
        three_epochs):
    ev, = _startups(three_epochs)
    by_fn = {c["fn"]: c for c in ev["compiles"]}
    assert by_fn["device_epoch_step"]["span"] == "epoch/train"
    assert by_fn["device_epoch_step"]["backend_compile_s"] > 0
    # `init_state`'s jits are no instrumented call's: kept under the span
    init = by_fn["startup/init_state"]
    assert init["span"] == "startup/init_state"
    assert init["trace_s"] > 0 and init["lower_s"] > 0
    # and the same split rides the program's own `xla_compile` event
    xla = [r for r in three_epochs if r["kind"] == "xla_compile"
           and r["fn"] == "device_epoch_step"][0]
    assert {f: xla[f] for f in SPLIT} == {
        f: by_fn["device_epoch_step"][f] for f in SPLIT}
    assert sum(xla[f] for f in SPLIT) <= xla["compile_s"] + 1e-3


def test_later_epochs_keep_their_ledgers_clean_of_startup(three_epochs):
    for r in three_epochs:
        if r["kind"] == "goodput":
            assert not any(p.startswith("startup/") for p in r["phases"])


def test_a_call_that_ends_inside_its_first_callback_still_writes_it(
        small_job, datasets):
    class Closed(Exception):
        pass

    def close(m):
        raise Closed

    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    with pytest.raises(Closed):
        train(_job(small_job, 3), datasets[0], datasets[1],
              console=lambda s: None, epoch_callback=close)
    obs.set_journal(None)
    kinds = [r["kind"] for r in journal.records]
    assert kinds.count("startup") == 1
    assert kinds.index("startup") < kinds.index("train_end")
    assert _startups(journal.records)[0]["epoch"] == 0


def test_a_resumed_call_names_the_epoch_it_trained_first(
        small_job, datasets, tmp_path):
    first = _run(_job(small_job, 2, tmp_path / "ckpt"), datasets)
    assert _startups(first)[0]["epoch"] == 0
    obs.reset_for_tests()
    again = _run(_job(small_job, 4, tmp_path / "ckpt"), datasets)
    ev, = _startups(again)
    assert ev["epoch"] == 2
    assert ev["phases"]["startup/restore"][1] == 1
    assert [r["epoch"] for r in again if r["kind"] == "callback"] == [2, 3]


def test_a_call_with_nothing_left_to_train_writes_no_startup_event(
        small_job, datasets, tmp_path):
    _run(_job(small_job, 1, tmp_path / "ckpt"), datasets)
    obs.reset_for_tests()
    again = _run(_job(small_job, 1, tmp_path / "ckpt"), datasets)
    assert not _startups(again)
    assert [r["kind"] for r in again][-1] == "train_end"


def test_a_blocking_ingest_is_a_startup_phase_and_counted_once(
        small_job, tmp_path):
    from shifu_tpu.data import synthetic

    rows = synthetic.make_rows(600, small_job.schema, seed=9, noise=0.3)
    synthetic.write_files(rows, str(tmp_path / "data"), num_files=2)
    job = small_job.replace(
        data=dataclasses.replace(
            small_job.data, batch_size=64, paths=(str(tmp_path / "data"),),
            stream_first_epoch=False),
        train=dataclasses.replace(small_job.train, epochs=2)).validate()
    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    train(job, console=lambda s: None)
    obs.set_journal(None)
    ev, = _startups(journal.records)
    ingest = ev["phases"]["startup/ingest"][0]
    assert ingest > 0
    good = [r for r in journal.records if r["kind"] == "goodput"][0]
    # the epoch's own record is charged with the load; the event is not
    assert ev["first_epoch"]["wall_s"] == pytest.approx(
        good["wall_s"] - ingest, abs=1e-5)
    assert ev["first_epoch"]["buckets"]["input"] == pytest.approx(
        good["buckets"]["input"] - ingest, abs=1e-5)


# ------------------------------------------------------------- the ledgers


def test_a_span_with_no_ledger_open_behaves_as_before():
    assert goodput_mod.current() is None
    with obs.span("startup/init_state", journal=False):
        pass
    hist = obs.default_registry().histogram("span_seconds")
    assert hist.count(span="startup/init_state") == 1
    assert goodput_mod.note_phase("startup/tiers", 1.0) is False


def test_the_startup_ledger_is_open_until_the_first_epochs():
    led = goodput_mod.begin_startup()
    assert goodput_mod.current() is led
    with obs.span("startup/tiers", journal=False):
        with obs.span("h2d", journal=False):
            pass
    epoch_led = goodput_mod.begin_epoch()
    assert goodput_mod.current() is epoch_led is not led
    with obs.span("epoch/tiers", journal=False):
        pass
    assert set(led.summary(1.0)["phases"]) == {"startup/tiers",
                                               "startup/tiers/h2d"}
    assert set(epoch_led.summary(1.0)["phases"]) == {"epoch/tiers"}
    # and nothing of the startup ledger reached the histogram
    hist = obs.default_registry().histogram("span_seconds")
    assert hist.count(span="startup/tiers") == 0


# ---------------------------------------------------- JAX's own durations


def _arrived(*items, tid=1):
    """Listener records: (field, seconds, end) -> the tuples `_split`
    takes."""
    return [(tid, f, s, end, "") for f, s, end in items]


@pytest.mark.parametrize("items, want", [
    # three stages one after another
    ([("trace_s", 1.0, 1.0), ("lower_s", 0.5, 1.5),
      ("backend_compile_s", 2.0, 3.5)],
     {"trace_s": 1.0, "lower_s": 0.5, "backend_compile_s": 2.0,
      "cache_retrieval_s": 0.0, "cache": "off"}),
    # a trace that traced two inner jits: their seconds are not counted twice
    ([("trace_s", 0.2, 0.3), ("trace_s", 0.3, 0.7), ("trace_s", 1.0, 1.0)],
     {"trace_s": 1.0, "lower_s": 0.0, "backend_compile_s": 0.0,
      "cache_retrieval_s": 0.0, "cache": "off"}),
    # a constant computed inside a trace compiles there
    ([("trace_s", 0.05, 0.25), ("backend_compile_s", 0.2, 0.5),
      ("trace_s", 1.0, 1.0)],
     {"trace_s": 0.8, "lower_s": 0.0, "backend_compile_s": 0.2,
      "cache_retrieval_s": 0.0, "cache": "off"}),
    # a program the cache served: its backend stage is the cache's work
    ([("trace_s", 1.0, 1.0), ("lower_s", 0.5, 1.5), ("hit", 0.0, 1.6),
      ("cache_retrieval_s", 0.3, 1.95), ("backend_compile_s", 0.5, 2.0)],
     {"trace_s": 1.0, "lower_s": 0.5, "backend_compile_s": 0.0,
      "cache_retrieval_s": 0.5, "cache": "hit"}),
    # compiled and written
    ([("backend_compile_s", 2.0, 2.0), ("miss", 0.0, 2.0)],
     {"trace_s": 0.0, "lower_s": 0.0, "backend_compile_s": 2.0,
      "cache_retrieval_s": 0.0, "cache": "miss"}),
    # one served, one compiled in the same call: work was done
    ([("hit", 0.0, 0.1), ("cache_retrieval_s", 0.1, 0.2),
      ("backend_compile_s", 0.2, 0.2), ("backend_compile_s", 1.0, 1.5),
      ("miss", 0.0, 1.5)],
     {"trace_s": 0.0, "lower_s": 0.0, "backend_compile_s": 1.0,
      "cache_retrieval_s": 0.2, "cache": "miss"}),
    ([], {"trace_s": 0.0, "lower_s": 0.0, "backend_compile_s": 0.0,
          "cache_retrieval_s": 0.0, "cache": "off"}),
], ids=["in_turn", "nested_traces", "compile_inside_trace", "served",
        "compiled_and_written", "served_and_compiled", "nothing"])
def test_split_counts_every_second_once(items, want):
    got = introspect._split(_arrived(*items))
    assert got == pytest.approx(want)


def test_split_keeps_threads_apart():
    both = (_arrived(("trace_s", 1.0, 1.0), tid=1)
            + _arrived(("trace_s", 0.5, 0.9), tid=2))
    assert introspect._split(both)["trace_s"] == pytest.approx(1.5)


def test_an_instrumented_compile_is_split_and_cached_calls_are_not():
    import jax.numpy as jnp

    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    mark = introspect.compile_mark()
    fn = introspect.instrument_jit(lambda x: jnp.tanh(x @ x.T).sum(),
                                   "split_probe")
    fn(jnp.ones((16, 16), jnp.float32))
    fn(jnp.ones((16, 16), jnp.float32))
    obs.set_journal(None)
    ev, = [r for r in journal.records if r["kind"] == "xla_compile"]
    assert ev["trace_s"] > 0 and ev["lower_s"] > 0
    assert ev["backend_compile_s"] + ev["cache_retrieval_s"] > 0
    # the capture's second compile of the program is not in the split
    assert sum(ev[f] for f in SPLIT) <= ev["compile_s"] + 1e-3
    mine = [c for c in introspect.compiles_since(mark)
            if c["fn"] == "split_probe"]
    assert len(mine) == 1 and mine[0]["span"] == ""


def test_durations_outside_an_instrumented_call_keep_their_span_path():
    import jax
    import jax.numpy as jnp

    mark = introspect.compile_mark()
    with obs.span("startup/init_state", journal=False):
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones((5, 7), jnp.float32))
    got = {c["fn"]: c for c in introspect.compiles_since(mark)}
    assert got["startup/init_state"]["span"] == "startup/init_state"
    assert got["startup/init_state"]["trace_s"] > 0
    assert introspect.compiles_since(mark) == list(got.values())  # once


def test_compile_span_is_split_too():
    import jax
    import jax.numpy as jnp

    journal = obs.RunJournal(None)
    obs.set_journal(journal)
    with introspect.compile_span("export_probe", bucket=4):
        jax.jit(lambda x: x - 2.0)(jnp.ones((3, 9), jnp.float32))
    obs.set_journal(None)
    ev, = [r for r in journal.records if r["kind"] == "xla_compile"]
    assert ev["fn"] == "export_probe" and ev["bucket"] == 4
    assert ev["trace_s"] > 0 and ev["cache"] in ("off", "hit", "miss")


def test_the_listeners_are_registered_once():
    from jax._src import monitoring

    introspect.listen()
    introspect.listen()
    assert monitoring.get_event_duration_listeners().count(
        introspect._on_duration) == 1
    assert monitoring.get_event_listeners().count(introspect._on_event) == 1


# --------------------------------------- the persistent cache, cold and warm

_PROBE = """
import json
import jax, jax.numpy as jnp
from shifu_tpu import obs
from shifu_tpu.obs import introspect
from shifu_tpu.utils import compilecache
compilecache.enable_persistent_cache(min_compile_time_secs=0.0)
journal = obs.RunJournal(None)
obs.set_journal(journal)
def f(x):
    for _ in range(6):
        x = jnp.tanh(x @ x.T) + jnp.sin(x)
    return x.sum()
introspect.instrument_jit(f, "cache_probe")(jnp.ones((32, 32), jnp.float32))
print(json.dumps([r for r in journal.records
                  if r["kind"] == "xla_compile"][0]))
"""


def _probe(cache_dir: str) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "SHIFU_TPU_NO_COMPILE_CACHE"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["SHIFU_TPU_XLA_COST"] = "0"   # one compile a process, as on a chip
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=180,
                         check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_cache_verdict_and_split_cold_then_warm(tmp_path):
    cache_dir = str(tmp_path / "empty")
    cold, warm = _probe(cache_dir), _probe(cache_dir)
    assert cold["cache"] == "miss"
    assert cold["backend_compile_s"] > 0 and cold["cache_retrieval_s"] == 0
    assert warm["cache"] == "hit"
    assert warm["cache_retrieval_s"] > 0 and warm["backend_compile_s"] == 0
    # traced and lowered both times: the cache is looked up by the module
    assert cold["trace_s"] > 0 and warm["trace_s"] > 0
    assert cold["lower_s"] > 0 and warm["lower_s"] > 0


# ------------------------------------------------- the operator's reader


def test_profile_shows_the_startup_event_in_a_few_lines(
        small_job, datasets, tmp_path, monkeypatch, capsys):
    from shifu_tpu.launcher import cli
    from shifu_tpu.obs import render

    monkeypatch.setenv("SHIFU_TPU_METRICS_DIR", str(tmp_path / "telemetry"))
    train(_job(small_job, 2), datasets[0], datasets[1],
          console=lambda s: None)
    obs.shutdown()
    doc = render.profile_summary(str(tmp_path))
    st = doc["startup"]
    assert st["epoch"] == 0 and st["wall_s"] > 0
    assert "startup/tiers/h2d" in st["phases"]
    assert any(c["fn"] == "device_epoch_step" for c in st["compiles"])
    assert cli.main(["profile", str(tmp_path)]) == 0
    text = capsys.readouterr().out
    head, = [ln for ln in text.splitlines() if ln.startswith("startup (")]
    for word in ("train call", "ingest", "restore", "init", "tiers",
                 "flags", "blocks", "h2d", "eval_tier", "first epoch",
                 "elsewhere"):
        assert word in head, word
    assert "programs before that boundary:" in text
    line, = [ln for ln in text.splitlines()
             if ln.startswith("  device_epoch_step [epoch/train]:")]
    for word in ("trace+lower", "compiled", "cache-loaded"):
        assert word in line
    # `--json` is the same dict
    assert cli.main(["profile", str(tmp_path), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["startup"] == st


def test_profile_without_a_startup_event_says_nothing_of_it(tmp_path):
    from shifu_tpu.obs import render

    journal = obs.RunJournal(str(tmp_path / "journal.jsonl"))
    journal.event("goodput", epoch=0, wall_s=1.0, goodput_fraction=0.5,
                  compiles=0, buckets={"step": 0.5, "other": 0.5})
    journal.close()
    doc = render.profile_summary(str(tmp_path))
    assert doc["startup"] is None
    assert "startup (" not in render.render_profile_text(doc)
