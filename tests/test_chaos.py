"""Chaos plane + self-healing checkpoint tests (docs/ROBUSTNESS.md).

The fast subset (everything not marked slow) runs in tier-1; `-m chaos
--runslow` additionally runs the end-to-end supervised drill.  Covers: plan
parsing/validation, deterministic replay, the legacy SHIFU_TPU_FAULT_* shim,
fsio retry telemetry + jittered backoff, digest-manifest integrity
(truncate + bit-flip, local and mock:// remote), the restore recovery
ladder, checkpoint-GC journaling + `status` surfacing, preemption-grace
resume, and the `chaos-verify` audit."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from shifu_tpu import chaos, obs
from shifu_tpu.chaos import plan as plan_mod

pytestmark = pytest.mark.chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos_and_obs():
    chaos.reset_for_tests()
    obs.reset_for_tests()
    yield
    chaos.reset_for_tests()
    obs.reset_for_tests()


# --- plan schema ----------------------------------------------------------

def test_plan_parsing_and_validation():
    p = plan_mod.parse_plan({
        "seed": 9,
        "faults": [
            {"site": "fsio.read_bytes", "at_call": 2},
            {"site": "train.epoch", "at_epoch": 1, "action": "exit",
             "exit_code": 17, "scope": "job", "max_times": 1},
        ]})
    assert p.seed == 9
    assert p.faults[0].site == "fsio.read_bytes"
    assert p.faults[1].scope == "job"
    # round-trips through its own JSON rendering
    p2 = plan_mod.load_plan(p.to_json())
    assert p2 == p

    with pytest.raises(plan_mod.ChaosPlanError, match="unknown field"):
        plan_mod.parse_plan({"faults": [{"site": "x", "typo": 1}]})
    with pytest.raises(plan_mod.ChaosPlanError, match="no trigger"):
        plan_mod.parse_plan({"faults": [{"site": "x"}]})
    with pytest.raises(plan_mod.ChaosPlanError, match="unknown action"):
        plan_mod.parse_plan({"faults": [{"site": "x", "at_call": 1,
                                         "action": "explode"}]})
    with pytest.raises(plan_mod.ChaosPlanError, match="not valid JSON"):
        plan_mod.load_plan("{nope")


def test_plan_determinism_same_seed():
    """Same plan + seed => byte-identical injection sequence (the probe's
    coin is a pure function of seed, site, and call number)."""
    p = plan_mod.parse_plan({"seed": 42, "faults": [
        {"site": "fsio.read_bytes", "prob": 0.25}]})

    def run():
        chaos.configure(p)
        fired = []
        for i in range(1, 101):
            try:
                chaos.maybe_fail("fsio.read_bytes", echo=lambda s: None)
            except chaos.ChaosError:
                fired.append(i)
        return fired

    a, b = run(), run()
    assert a == b
    assert 5 < len(a) < 50  # the coin actually flips both ways


def test_trigger_matrix():
    """at_call / every / max_times / rank / glob-site semantics."""
    p = plan_mod.parse_plan({"faults": [
        {"site": "a.b", "at_call": 3},
        {"site": "fsio.*", "every": 2, "max_times": 2},
    ]})
    chaos.configure(p)
    fired = []
    for i in range(1, 7):
        try:
            chaos.maybe_fail("a.b", echo=lambda s: None)
        except chaos.ChaosError:
            fired.append(i)
    assert fired == [3]
    fired = []
    for i in range(1, 9):
        try:
            chaos.maybe_fail("fsio.read_bytes", echo=lambda s: None)
        except chaos.ChaosError:
            fired.append(i)
    assert fired == [2, 4]  # every=2 capped at max_times=2

    # rank filter: this process is rank 0 by default
    chaos.configure(plan_mod.parse_plan({"faults": [
        {"site": "r", "every": 1, "rank": 3}]}))
    chaos.maybe_fail("r")  # must not fire
    os.environ["SHIFU_TPU_PROCESS_ID"] = "3"
    try:
        with pytest.raises(chaos.ChaosError):
            chaos.maybe_fail("r", echo=lambda s: None)
    finally:
        del os.environ["SHIFU_TPU_PROCESS_ID"]


def test_job_scope_counters_survive_process_restart(tmp_path,
                                                    monkeypatch):
    """scope="job" call counters persist in SHIFU_TPU_CHAOS_STATE, so "the
    first restore of the JOB" stays first across a supervised restart
    (modeled here as a chaos.configure() reset, which clears the
    process-local counters)."""
    state = tmp_path / "chaos_state.json"
    monkeypatch.setenv(plan_mod.ENV_CHAOS_STATE, str(state))
    p = plan_mod.parse_plan({"faults": [
        {"site": "checkpoint.restore", "at_call": 1, "scope": "job"}]})
    chaos.configure(p)
    with pytest.raises(chaos.ChaosError):
        chaos.maybe_fail("checkpoint.restore", echo=lambda s: None)
    chaos.maybe_fail("checkpoint.restore")  # call 2: no fire
    chaos.configure(p)  # "new process"
    chaos.maybe_fail("checkpoint.restore")  # call 3 per the state file
    st = json.loads(state.read_text())
    assert st["calls"]["checkpoint.restore"] == 3
    assert sum(st["fires"].values()) == 1


def test_legacy_env_shim_synthesizes_plan():
    """The four SHIFU_TPU_FAULT_* hooks + SHIFU_TPU_HANG_EPOCH map onto
    chaos-plan faults with the legacy messages preserved byte-for-byte
    (the resilience tests assert on them)."""
    env = {"SHIFU_TPU_FAULT_EPOCH": "2", "SHIFU_TPU_FAULT_PROCESS": "1",
           "SHIFU_TPU_FAULT_EVERY_EPOCH": "3", "SHIFU_TPU_HANG_EPOCH": "0",
           "SHIFU_TPU_FAULT_HOST_DOWN": "4"}
    faults = plan_mod.plan_from_legacy_env(env)
    kill = next(f for f in faults if f.at_epoch == 2)
    assert (kill.site, kill.action, kill.rank, kill.exit_code) == \
        ("train.epoch", "exit", 1, 17)
    assert kill.message == \
        "FAULT INJECTION: killing process after epoch {epoch}"
    every = next(f for f in faults if f.before_epoch == 3)
    assert every.action == "exit" and every.rank == 1
    hang = next(f for f in faults if f.action == "hang")
    assert (hang.site, hang.at_epoch) == ("train.epoch", 0)
    assert hang.message == "HANG INJECTION: stalling after epoch {epoch}"
    down = next(f for f in faults if f.site == "launcher.start")
    assert (down.rank, down.exit_code) == (4, 1)
    assert down.message == \
        "FAULT INJECTION: host (rank 4) is permanently down"
    assert plan_mod.plan_from_legacy_env({}) == ()

    # merged with an explicit plan: both fire, plan seed kept
    merged = plan_mod.load_plan_env({
        plan_mod.ENV_CHAOS_PLAN:
            '{"seed": 5, "faults": [{"site": "x", "at_call": 1}]}',
        "SHIFU_TPU_FAULT_EPOCH": "1"})
    assert merged.seed == 5
    assert {f.site for f in merged.faults} == {"x", "train.epoch"}


# --- fsio retry telemetry + jitter ----------------------------------------

def test_fsio_retry_recovers_and_counts(monkeypatch):
    from shifu_tpu.data import fsio

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient datanode hiccup")
        return "ok"

    monkeypatch.setattr(fsio, "_RETRY_BASE_S", 0.0)
    assert fsio._retry_transient(flaky, op_name="read_bytes") == "ok"
    reg = obs.default_registry()
    assert reg.counter("fsio_retry_total").value(op="read_bytes") == 2
    assert reg.counter("fsio_terminal_total").total() == 0


def test_fsio_terminal_counts_and_no_auth_retry(monkeypatch):
    from shifu_tpu.data import fsio

    monkeypatch.setattr(fsio, "_RETRY_BASE_S", 0.0)

    def always_fails():
        raise OSError("broken pipe")

    with pytest.raises(OSError):
        fsio._retry_transient(always_fails, op_name="write_bytes")
    reg = obs.default_registry()
    assert reg.counter("fsio_terminal_total").value(
        op="write_bytes", reason="exhausted") == 1

    calls = {"n": 0}

    def auth_fails():
        calls["n"] += 1
        raise OSError("Permission denied: kerberos ticket expired")

    with pytest.raises(OSError):
        fsio._retry_transient(auth_fails, op_name="read_bytes")
    assert calls["n"] == 1  # auth-shaped errors never retry
    assert reg.counter("fsio_terminal_total").value(
        op="read_bytes", reason="auth") == 1


def test_fsio_backoff_uses_decorrelated_jitter(monkeypatch):
    """Backoff sleeps are sampled from U[base, 3*prev] and capped — NOT the
    old fixed 0.1*2^k ladder that synchronized gang-wide retries."""
    import time as time_mod

    from shifu_tpu.data import fsio

    sleeps: list[float] = []
    monkeypatch.setattr(time_mod, "sleep", sleeps.append)
    monkeypatch.setenv("SHIFU_TPU_FS_RETRIES", "6")

    def always_fails():
        raise OSError("flaky")

    import random
    random.seed(1234)
    with pytest.raises(OSError):
        fsio._retry_transient(always_fails, op_name="x")
    assert len(sleeps) == 6
    assert all(fsio._RETRY_BASE_S <= s <= fsio._RETRY_CAP_S for s in sleeps)
    # jitter: the sequence is not the deterministic exponential ladder
    assert sleeps != [0.1 * (2 ** k) for k in range(6)]
    prev = fsio._RETRY_BASE_S
    for s in sleeps:
        assert s <= max(3 * prev, fsio._RETRY_BASE_S) + 1e-9
        prev = s


def test_chaos_injected_fsio_read_retries_to_success(tmp_path, monkeypatch):
    """An injected read fault at a file:// URI is retried like the real
    transient error it models, and the injection is journaled."""
    from shifu_tpu.data import fsio

    monkeypatch.setattr(fsio, "_RETRY_BASE_S", 0.0)
    tele = tmp_path / "tele"
    obs.configure(str(tele), flush_every=1)
    f = tmp_path / "x.bin"
    f.write_bytes(b"payload")
    chaos.configure(plan_mod.parse_plan({"faults": [
        {"site": "fsio.read_bytes", "at_call": 1}]}))
    assert fsio.read_bytes(f"file://{f}") == b"payload"
    assert obs.default_registry().counter(
        "chaos_injected_total").value(site="fsio.read_bytes",
                                      action="raise") == 1
    assert obs.default_registry().counter(
        "fsio_retry_total").value(op="read_bytes") == 1
    obs.flush()
    recs = [json.loads(l) for l in
            (tele / "journal.jsonl").read_text().splitlines()]
    assert any(r["kind"] == "chaos_inject"
               and r["site"] == "fsio.read_bytes" for r in recs)


# --- checkpoint integrity: manifests + recovery ladder --------------------

def _save_n(tmp_path, small_job, n, max_to_keep=5):
    from shifu_tpu.train import checkpoint as ckpt_lib
    from shifu_tpu.train import init_state

    d = str(tmp_path / "ckpt")
    mgr = ckpt_lib.make_manager(d, max_to_keep=max_to_keep)
    state = init_state(small_job, 30)
    for i in range(1, n + 1):
        ckpt_lib.save(mgr, i, state, extra={"epoch": i}, block=True)
    return d, mgr, state


def _largest_file(step_dir):
    files = [p for p in pathlib.Path(step_dir).rglob("*")
             if p.is_file() and p.stat().st_size > 0]
    return max(files, key=lambda p: p.stat().st_size)


def _bit_flip(path):
    b = bytearray(path.read_bytes())
    b[len(b) // 2] ^= 0xFF
    path.write_bytes(bytes(b))


def test_manifest_written_and_verifies(tmp_path, small_job):
    from shifu_tpu.train import checkpoint as ckpt_lib

    d, mgr, _state = _save_n(tmp_path, small_job, 2)
    for step in mgr.all_steps():
        assert os.path.exists(ckpt_lib.manifest_path(d, step))
        assert ckpt_lib.verify_manifest(d, step) is True
    # no manifest => None (legacy checkpoints restore on trust)
    os.unlink(ckpt_lib.manifest_path(d, 1))
    assert ckpt_lib.verify_manifest(d, 1) is None


@pytest.mark.parametrize("corruption", ["bit_flip", "truncate", "delete"])
def test_restore_falls_back_to_verified_step(tmp_path, small_job,
                                             corruption):
    """The recovery ladder: latest step corrupted (bit-flip / truncation /
    a missing blob) => restore lands on the previous VERIFIED step and the
    fallback is journaled."""
    from shifu_tpu.train import checkpoint as ckpt_lib

    tele = tmp_path / "tele"
    obs.configure(str(tele), flush_every=1)
    d, mgr, state = _save_n(tmp_path, small_job, 3)
    latest = max(mgr.all_steps())
    victim = _largest_file(os.path.join(d, str(latest)))
    if corruption == "bit_flip":
        _bit_flip(victim)
    elif corruption == "truncate":
        victim.write_bytes(victim.read_bytes()[: victim.stat().st_size // 2])
    else:
        victim.unlink()

    restored, extra, step = ckpt_lib.restore_latest(mgr, state,
                                                    with_extra=True)
    assert step == latest - 1
    assert extra["epoch"] == latest - 1
    obs.flush()
    recs = [json.loads(l) for l in
            (tele / "journal.jsonl").read_text().splitlines()]
    falls = [r for r in recs if r["kind"] == "checkpoint_fallback"]
    assert len(falls) == 1 and falls[0]["failed_step"] == latest
    assert falls[0]["reason"] == "CheckpointCorruptError"
    assert any(r["kind"] == "checkpoint_fallback_resolved"
               and r["step"] == step for r in recs)


def test_all_steps_corrupt_raises(tmp_path, small_job):
    from shifu_tpu.train import checkpoint as ckpt_lib

    d, mgr, state = _save_n(tmp_path, small_job, 2)
    for step in mgr.all_steps():
        _bit_flip(_largest_file(os.path.join(d, str(step))))
    with pytest.raises(ckpt_lib.CheckpointCorruptError):
        ckpt_lib.restore_latest(mgr, state, with_extra=True)


def test_train_resumes_through_corrupt_latest(tmp_path, small_job,
                                              small_data):
    """End-to-end through train(): a 3-epoch run whose LATEST checkpoint is
    corrupted resumes from the previous verified epoch and completes —
    max_to_keep as a recovery ladder, not just a disk policy."""
    from shifu_tpu.config import CheckpointConfig, RuntimeConfig
    from shifu_tpu.train import train
    from shifu_tpu.train import checkpoint as ckpt_lib

    train_ds, valid_ds = small_data
    d = str(tmp_path / "ckpt")

    def with_epochs(n):
        return small_job.replace(
            train=small_job.train.__class__(
                epochs=n, optimizer=small_job.train.optimizer),
            runtime=RuntimeConfig(checkpoint=CheckpointConfig(
                directory=d, save_every_epochs=1)))

    train(with_epochs(3), train_ds, valid_ds, console=lambda s: None)
    mgr = ckpt_lib.make_manager(d)
    latest = max(mgr.all_steps())
    _bit_flip(_largest_file(os.path.join(d, str(latest))))

    lines = []
    r = train(with_epochs(4), train_ds, valid_ds, console=lines.append)
    # the corrupt terminal checkpoint (epoch 3) is skipped; the job resumes
    # from the verified epoch-2 rung and retrains to completion
    assert r.resumed_from_epoch == 2
    assert [m.epoch for m in r.history] == [2, 3]
    assert any("Resumed from checkpoint" in l for l in lines)


def test_remote_manifest_mock_fs(tmp_path):
    """Digest manifests over a mock:// (pyarrow in-memory) checkpoint tree:
    write, verify, detect a remote bit-flip and a truncation."""
    pafs = pytest.importorskip("pyarrow.fs")
    from shifu_tpu.data import fsio
    from shifu_tpu.train import checkpoint as ckpt_lib

    filesystem, _ = pafs.FileSystem.from_uri("mock://seed")
    with fsio._fs_lock:
        fsio._fs_cache[("mock", "")] = filesystem
    try:
        root = "mock://bucket/ckpt"
        fsio.write_bytes(f"{root}/7/data/weights.bin", b"A" * 1000)
        fsio.write_bytes(f"{root}/7/metadata", b'{"ok": true}')
        assert ckpt_lib.write_manifest(root, 7) is not None
        assert ckpt_lib.verify_manifest(root, 7) is True
        # remote bit-flip
        blob = bytearray(fsio.read_bytes(f"{root}/7/data/weights.bin"))
        blob[500] ^= 0xFF
        fsio.write_bytes(f"{root}/7/data/weights.bin", bytes(blob))
        assert ckpt_lib.verify_manifest(root, 7) is False
        # remote truncation
        fsio.write_bytes(f"{root}/7/data/weights.bin", b"A" * 10)
        assert ckpt_lib.verify_manifest(root, 7) is False
        assert ckpt_lib.verify_manifest(root, 8) is None

        # the chaos `corrupt` action finds the largest file of a REMOTE
        # step tree (recursive) and the digest check catches the damage
        fsio.write_bytes(f"{root}/9/data/weights.bin", b"B" * 1000)
        fsio.write_bytes(f"{root}/9/metadata", b"{}")
        assert ckpt_lib.write_manifest(root, 9) is not None
        chaos.configure(plan_mod.parse_plan({"faults": [
            {"site": "checkpoint.post_save", "at_call": 1,
             "action": "corrupt"}]}))
        chaos.maybe_fail("checkpoint.post_save", path=f"{root}/9",
                         echo=lambda s: None)
        assert fsio.read_bytes(f"{root}/9/data/weights.bin") != b"B" * 1000
        assert ckpt_lib.verify_manifest(root, 9) is False
    finally:
        with fsio._fs_lock:
            fsio._fs_cache.pop(("mock", ""), None)


def test_checkpoint_gc_journaled_and_status_surfaces(tmp_path, small_job):
    """Retention is an auditable event: GC'd steps emit checkpoint_gc with
    freed bytes, their manifests are cleaned up, and `shifu-tpu status`
    surfaces kept/GC'd counts from the scrape file."""
    from shifu_tpu.launcher import detach
    from shifu_tpu.train import checkpoint as ckpt_lib

    job_dir = tmp_path / "job"
    tele = job_dir / "telemetry"
    obs.configure(str(tele), flush_every=1)
    from shifu_tpu.train import init_state
    d = str(job_dir / "tmp_model")
    mgr = ckpt_lib.make_manager(d, max_to_keep=2)
    state = init_state(small_job, 30)
    for i in range(1, 5):
        ckpt_lib.save(mgr, i, state, extra={"epoch": i}, block=True)
    obs.flush()
    recs = [json.loads(l) for l in
            (tele / "journal.jsonl").read_text().splitlines()]
    gcs = [r for r in recs if r["kind"] == "checkpoint_gc"]
    assert [g["step"] for g in gcs] == [1, 2]
    assert all(g["freed_bytes"] > 0 for g in gcs)
    # GC'd steps lose their manifests; kept steps retain them
    assert not os.path.exists(ckpt_lib.manifest_path(d, 1))
    assert os.path.exists(ckpt_lib.manifest_path(d, 4))

    st = detach.job_state(str(job_dir))
    assert st["checkpoints"]["kept_steps"] == sorted(mgr.all_steps())
    assert st["checkpoints"]["manifests"] == len(mgr.all_steps())
    assert st["checkpoints"]["gc_steps"] == 2
    assert st["checkpoints"]["gc_freed_bytes"] > 0


def test_sigterm_grace_resumes_from_current_epoch(tmp_path, small_job,
                                                  small_data):
    """Preemption grace: with NO epoch-cadence saves configured, a SIGTERM
    mid-run still leaves a grace checkpoint at the epoch it interrupted —
    the resume starts there, not at epoch 0, and the drain is journaled."""
    import signal
    import threading

    from shifu_tpu.config import CheckpointConfig, RuntimeConfig
    from shifu_tpu.train import train

    train_ds, valid_ds = small_data
    d = str(tmp_path / "ckpt")
    tele = tmp_path / "tele"
    obs.configure(str(tele), flush_every=1)

    def job_for(epochs):
        return small_job.replace(
            train=small_job.train.__class__(
                epochs=epochs, optimizer=small_job.train.optimizer),
            # save_every_epochs huge: the ONLY mid-run checkpoint can come
            # from the SIGTERM drain itself
            runtime=RuntimeConfig(checkpoint=CheckpointConfig(
                directory=d, save_every_epochs=10_000)))

    # prewarm jit caches so the handler is installed before the timer fires
    warm = small_job.replace(train=small_job.train.__class__(
        epochs=1, optimizer=small_job.train.optimizer))
    train(warm, train_ds, valid_ds, console=lambda s: None)

    killer = threading.Timer(
        1.5, lambda: os.kill(os.getpid(), signal.SIGTERM))
    killer.start()
    try:
        with pytest.raises(SystemExit) as exc:
            train(job_for(100_000), train_ds, valid_ds,
                  console=lambda s: None)
    finally:
        killer.cancel()
    assert exc.value.code == 75

    obs.flush()
    recs = [json.loads(l) for l in
            (tele / "journal.jsonl").read_text().splitlines()]
    graces = [r for r in recs if r["kind"] == "preemption_grace"]
    assert graces and graces[-1]["saved"] is True
    grace_epoch = graces[-1]["epoch"]
    assert grace_epoch >= 1  # mid-run, past the first epoch

    r = train(job_for(grace_epoch + 2), train_ds, valid_ds,
              console=lambda s: None)
    # resumes from the grace-saved epoch, not an earlier boundary (there
    # IS no earlier checkpoint to fall back to)
    assert r.resumed_from_epoch == grace_epoch
    assert [m.epoch for m in r.history] == [grace_epoch, grace_epoch + 1]


# --- chaos-verify ---------------------------------------------------------

def test_chaos_verify_reports_and_flags_silent_sites(tmp_path, capsys):
    from shifu_tpu.launcher import cli

    job = tmp_path / "job"
    (job / "telemetry").mkdir(parents=True)
    events = [
        {"ts": 1, "seq": 1, "kind": "chaos_inject", "site": "train.epoch",
         "action": "exit", "call": 1},
        {"ts": 2, "seq": 2, "kind": "supervisor_restart", "attempt": 1},
        {"ts": 3, "seq": 3, "kind": "checkpoint_fallback", "failed_step": 4},
        {"ts": 4, "seq": 4, "kind": "run_end", "exit": 0},
    ]
    with open(job / "telemetry" / "journal.jsonl", "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    (job / "chaos_plan.json").write_text(json.dumps({"faults": [
        {"site": "train.epoch", "at_epoch": 1, "action": "exit"}]}))

    assert cli.main(["chaos-verify", str(job), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["verdict"] == "PASS"
    assert report["injected"] == {"train.epoch": 1}
    assert report["recovered"]["supervisor_restart"] == 1

    # a planned site that never fired fails the audit
    (job / "chaos_plan.json").write_text(json.dumps({"faults": [
        {"site": "train.epoch", "at_epoch": 1, "action": "exit"},
        {"site": "fsio.read_bytes", "at_call": 99}]}))
    assert cli.main(["chaos-verify", str(job), "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["verdict"] == "SILENT_SITES"
    assert report["silent_sites"] == ["fsio.read_bytes"]


def test_cli_rejects_malformed_plan(tmp_path):
    """A typo'd chaos plan fails the launch, not silently never-injects."""
    from shifu_tpu.launcher import cli

    args = cli.build_parser().parse_args(
        ["train", "--modelconfig", "m", "--columnconfig", "c",
         "--chaos-plan", '{"faults": [{"site": "x", "bogus": 1}]}'])
    try:
        assert cli._activate_chaos(args) == cli.EXIT_FAIL
    finally:
        os.environ.pop(plan_mod.ENV_CHAOS_PLAN, None)
        chaos.reset_for_tests()


def test_plan_coerces_numeric_strings_at_load():
    """JSON plans with string-typed numbers coerce at LOAD (or fail there)
    — never a TypeError inside a probe mid-run."""
    p = plan_mod.parse_plan({"faults": [
        {"site": "x", "at_call": "2", "rank": "1", "prob": "0.0",
         "max_times": "3", "exit_code": "9"}]})
    f = p.faults[0]
    assert (f.at_call, f.rank, f.max_times, f.exit_code) == (2, 1, 3, 9)
    assert isinstance(f.prob, float)
    with pytest.raises(plan_mod.ChaosPlanError, match="rank must be"):
        plan_mod.parse_plan({"faults": [{"site": "x", "at_call": 1,
                                         "rank": "chief"}]})


def test_activate_chaos_exports_plan_content_not_path(tmp_path):
    """A file-path --chaos-plan must export the resolved JSON, not the
    path: ssh-dispatched pod ranks inherit the env on machines where the
    dispatcher's local plan file does not exist."""
    from shifu_tpu.launcher import cli

    plan_file = tmp_path / "plan.json"
    plan_file.write_text(json.dumps({"faults": [
        {"site": "train.epoch", "at_epoch": 1, "action": "exit"}]}))
    args = cli.build_parser().parse_args(
        ["train", "--modelconfig", "m", "--columnconfig", "c",
         "--output", str(tmp_path / "job"),
         "--chaos-plan", str(plan_file)])
    try:
        assert cli._activate_chaos(args) == cli.EXIT_OK
        exported = os.environ[plan_mod.ENV_CHAOS_PLAN]
        assert exported.strip().startswith("{")  # content, not a path
        assert plan_mod.load_plan(exported).faults[0].site == "train.epoch"
    finally:
        os.environ.pop(plan_mod.ENV_CHAOS_PLAN, None)
        os.environ.pop(plan_mod.ENV_CHAOS_STATE, None)
        chaos.reset_for_tests()


def test_activate_chaos_pins_state_and_persists_plan(tmp_path):
    from shifu_tpu.launcher import cli

    out = tmp_path / "job"
    plan = {"seed": 3, "faults": [{"site": "train.epoch", "at_epoch": 1,
                                   "action": "exit", "scope": "job"}]}
    args = cli.build_parser().parse_args(
        ["train", "--modelconfig", "m", "--columnconfig", "c",
         "--output", str(out), "--chaos-plan", json.dumps(plan)])
    try:
        assert cli._activate_chaos(args) == cli.EXIT_OK
        assert os.environ[plan_mod.ENV_CHAOS_STATE] == \
            str(out / "chaos_state.json")
        persisted = plan_mod.load_plan(str(out / "chaos_plan.json"))
        assert persisted.seed == 3
        assert persisted.faults[0].site == "train.epoch"
        assert chaos.active_plan() is not None
    finally:
        os.environ.pop(plan_mod.ENV_CHAOS_PLAN, None)
        os.environ.pop(plan_mod.ENV_CHAOS_STATE, None)
        chaos.reset_for_tests()


# --- the end-to-end drill -------------------------------------------------

@pytest.mark.slow
def test_e2e_chaos_drill_supervised_run(tmp_path):
    """The acceptance drill: a supervised CPU training run whose plan
    (a) kills the child at epoch 1, (b) fails the first post-restart
    checkpoint read, and (c) corrupts the then-latest checkpoint — must
    still complete rc=0 by falling back to the previous verified step,
    with chaos_inject, checkpoint_fallback, and supervisor_restart all in
    the journal, and `chaos-verify` passing the audit."""
    import json as json_lib

    from shifu_tpu.data import synthetic

    mc = {"dataSet": {"targetColumnName": "target"},
          "train": {"validSetRate": 0.1, "numTrainEpochs": 3,
                    "algorithm": "NN",
                    "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                               "ActivationFunc": ["tanh"],
                               "LearningRate": 0.003,
                               "Optimizer": "adam"}}}
    cols = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    cols += [{"columnNum": i, "columnName": f"f{i}", "columnType": "N",
              "finalSelect": True} for i in range(1, 11)]
    (tmp_path / "ModelConfig.json").write_text(json_lib.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json_lib.dumps(cols))
    schema = synthetic.make_schema(num_features=10)
    rows = synthetic.make_rows(2500, schema, seed=3, noise=0.3)
    synthetic.write_files(rows, str(tmp_path / "normalized"), num_files=4)

    plan = {"seed": 1, "faults": [
        # (a) hard-kill after epoch 1's save — once for the whole job
        {"site": "train.epoch", "at_epoch": 1, "action": "exit",
         "exit_code": 17, "scope": "job", "max_times": 1},
        # (b) the job's FIRST checkpoint read (attempt 2's newest rung)
        # fails — the ladder must fall through it
        {"site": "checkpoint.restore", "at_call": 1, "scope": "job",
         "action": "raise"},
        # (c) the epoch-1 save (the job's 2nd durable save = the latest at
        # kill time) is corrupted on disk — the digest verify must catch it
        {"site": "checkpoint.post_save", "at_call": 2, "scope": "job",
         "action": "corrupt", "max_times": 1},
    ]}
    plan_path = tmp_path / "plan.json"
    plan_path.write_text(json_lib.dumps(plan))

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_NUM_CPU_DEVICES"] = "4"
    out = tmp_path / "out"
    r = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
         "--modelconfig", str(tmp_path / "ModelConfig.json"),
         "--columnconfig", str(tmp_path / "ColumnConfig.json"),
         "--data", str(tmp_path / "normalized"),
         "--output", str(out), "--epochs", "3",
         "--supervise", "--max-restarts", "3",
         "--chaos-plan", str(plan_path)],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert (out / "final_model" / "weights.npz").exists()

    recs = [json_lib.loads(l) for l in
            (out / "telemetry" / "journal.jsonl").read_text().splitlines()]
    kinds = {rec["kind"] for rec in recs}
    assert "chaos_inject" in kinds
    assert "checkpoint_fallback" in kinds
    assert "supervisor_restart" in kinds
    injected_sites = {rec["site"] for rec in recs
                      if rec["kind"] == "chaos_inject"}
    assert {"train.epoch", "checkpoint.restore",
            "checkpoint.post_save"} <= injected_sites

    # the audit agrees: everything planned fired, and the run survived
    r2 = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "chaos-verify",
         str(out), "--json"],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    report = json_lib.loads(r2.stdout)
    assert report["verdict"] == "PASS"
    assert report["silent_sites"] == []
    assert report["recovered"].get("supervisor_restart", 0) >= 1
