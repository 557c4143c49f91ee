"""Launcher / supervisor tests: the one-command operator UX that succeeds the
reference's client->AM->executor stack, plus deliberate fault injection
(doing on purpose what yarn/util/CommonUtils.java:265-274 did in comments)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODEL_CONFIG = {
    "dataSet": {"targetColumnName": "target"},
    "train": {"validSetRate": 0.1, "numTrainEpochs": 2, "algorithm": "NN",
              "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                         "ActivationFunc": ["tanh"], "LearningRate": 0.003,
                         "Optimizer": "adam"}},
}


@pytest.fixture()
def job_dir(tmp_path):
    """A complete Shifu-style job dir: configs + gzip data."""
    from shifu_tpu.data import synthetic

    schema = synthetic.make_schema(num_features=10)
    rows = synthetic.make_rows(2500, schema, seed=3, noise=0.3)
    synthetic.write_files(rows, str(tmp_path / "normalized"), num_files=4)

    columns = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    for i in range(1, 11):
        columns.append({"columnNum": i, "columnName": f"f{i}",
                        "columnType": "N", "finalSelect": True})
    (tmp_path / "ModelConfig.json").write_text(json.dumps(MODEL_CONFIG))
    (tmp_path / "ColumnConfig.json").write_text(json.dumps(columns))
    return tmp_path


def _cli_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_NUM_CPU_DEVICES"] = "4"
    return env


def _run_cli(args, env=None, timeout=300):
    return subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", *args],
        capture_output=True, text=True, timeout=timeout, env=env or _cli_env(),
        cwd=REPO)


def test_train_cli_end_to_end(job_dir):
    out = job_dir / "out"
    r = _run_cli(["train",
                  "--modelconfig", str(job_dir / "ModelConfig.json"),
                  "--columnconfig", str(job_dir / "ColumnConfig.json"),
                  "--data", str(job_dir / "normalized"),
                  "--output", str(out)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Epoch 0:" in r.stdout and "Epoch 1:" in r.stdout
    assert (out / "console.board").exists()
    assert (out / "global-final.xml").exists()
    assert (out / "job-config.json").exists()
    # exported artifact with native pack
    final = out / "final_model"
    for f in ("GenericModelConfig.json", "topology.json", "weights.npz", "model.bin"):
        assert (final / f).exists(), f
    # structured per-epoch metrics next to the board
    import json
    lines = [json.loads(l) for l in (out / "metrics.jsonl").read_text().splitlines()]
    assert len(lines) >= 2
    assert {"epoch", "train_error", "valid_error", "valid_auc"} <= set(lines[0])


def test_score_cli(job_dir):
    out = job_dir / "out"
    r = _run_cli(["train",
                  "--modelconfig", str(job_dir / "ModelConfig.json"),
                  "--columnconfig", str(job_dir / "ColumnConfig.json"),
                  "--data", str(job_dir / "normalized"),
                  "--output", str(out), "--epochs", "1"])
    assert r.returncode == 0, r.stdout + r.stderr
    # score the feature columns (1..10) of a small file
    from shifu_tpu.data import reader, synthetic
    from shifu_tpu.data import synthetic as syn
    schema = syn.make_schema(num_features=10)
    rows = syn.make_rows(50, schema, seed=9)
    feat_file = job_dir / "feats.psv"
    with open(feat_file, "w") as f:
        for row in rows[:, 1:11]:
            f.write("|".join(f"{v:.6f}" for v in row) + "\n")
    r2 = _run_cli(["score", "--model", str(out / "final_model"),
                   "--input", str(feat_file)])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    scores = [float(l) for l in r2.stdout.strip().splitlines()]
    assert len(scores) == 50
    assert all(0.0 <= s <= 1.0 for s in scores)


def test_timeout_exit_code(job_dir):
    out = job_dir / "out_t"
    r = _run_cli(["train",
                  "--modelconfig", str(job_dir / "ModelConfig.json"),
                  "--columnconfig", str(job_dir / "ColumnConfig.json"),
                  "--data", str(job_dir / "normalized"),
                  "--output", str(out), "--epochs", "500",
                  "--timeout", "1"])
    assert r.returncode == 3, r.stdout + r.stderr
    assert "timeout" in r.stdout.lower()


def test_exit_timeout_constants_in_sync():
    """The supervisor keeps its own EXIT_TIMEOUT (it must not import the CLI
    module it launches); the two spellings must agree."""
    from shifu_tpu.launcher import cli, supervisor
    assert cli.EXIT_TIMEOUT == supervisor.EXIT_TIMEOUT == 3


def test_supervised_timeout_is_terminal(job_dir):
    """--supervise --timeout N must stop at N with exit 3 — ONE attempt, no
    restart.  (Round-2 bug: EXIT_TIMEOUT was treated as a restartable
    failure and each attempt checkpointed + re-derived a fresh deadline, so
    the job looped forever in N-second chunks.  Reference semantics: the
    client kills the app once, terminally — TensorflowClient.java:625-658.)"""
    import time as _time
    out = job_dir / "out_st"
    t0 = _time.monotonic()
    r = _run_cli(["train",
                  "--modelconfig", str(job_dir / "ModelConfig.json"),
                  "--columnconfig", str(job_dir / "ColumnConfig.json"),
                  "--data", str(job_dir / "normalized"),
                  "--output", str(out), "--epochs", "500",
                  "--timeout", "1", "--supervise", "--max-restarts", "3"],
                 timeout=240)
    elapsed = _time.monotonic() - t0
    assert r.returncode == 3, r.stdout + r.stderr
    assert "timeout" in r.stdout.lower()
    # exactly one attempt: the supervisor's job deadline killed it or the
    # child exited 3 — either way nothing restarted
    assert "attempt 2" not in r.stdout, r.stdout
    assert "restart budget" not in r.stdout, r.stdout
    # bounded wall time: one attempt's startup + the 1s budget, nowhere
    # near max_restarts * attempt length
    assert elapsed < 200, f"took {elapsed:.0f}s — timeout not terminal?"


@pytest.mark.slow
def test_supervisor_sigterm_drains_child_tree(job_dir):
    """A scheduler SIGTERM to the supervisor parent must reach the child
    (which runs in its own session and would otherwise be orphaned): the
    supervisor forwards SIGTERM to the child's process group, the child's
    drain saves a checkpoint, and the parent exits 143."""
    import signal
    import subprocess as sp
    import time as _time

    out = job_dir / "out_sig"
    proc = sp.Popen(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
         "--modelconfig", str(job_dir / "ModelConfig.json"),
         "--columnconfig", str(job_dir / "ColumnConfig.json"),
         "--data", str(job_dir / "normalized"),
         "--output", str(out), "--epochs", "50000", "--supervise"],
        env=_cli_env(), cwd=REPO, stdout=sp.PIPE, stderr=sp.STDOUT, text=True)
    # wait for training to actually start (board exists => child is mid-job)
    board = out / "console.board"
    deadline = _time.monotonic() + 120
    while _time.monotonic() < deadline and not board.exists():
        _time.sleep(0.5)
    assert board.exists(), "training never started"
    _time.sleep(1)
    proc.send_signal(signal.SIGTERM)
    stdout, _ = proc.communicate(timeout=60)
    assert proc.returncode == 143, stdout
    assert "SIGTERM" in stdout, stdout
    # nothing from this job tree survives the drain
    _time.sleep(2)
    r = subprocess.run(["pgrep", "-f", str(out)], capture_output=True,
                       text=True)
    assert r.stdout.strip() == "", f"orphans: {r.stdout}"


@pytest.mark.slow
def test_pod_timeout_is_terminal(job_dir):
    """A --hosts pod run with --timeout (pod implies supervision) is likewise
    terminal: exit 3, one gang attempt, no whole-gang restart loop."""
    out = job_dir / "out_pt"
    env = _cli_env()
    env["JAX_NUM_CPU_DEVICES"] = "2"
    r = _run_cli(["train",
                  "--modelconfig", str(job_dir / "ModelConfig.json"),
                  "--columnconfig", str(job_dir / "ColumnConfig.json"),
                  "--data", str(job_dir / "normalized"),
                  "--output", str(out), "--epochs", "500",
                  "--timeout", "1", "--hosts", "local:2"],
                 env=env, timeout=300)
    assert r.returncode == 3, r.stdout + r.stderr
    assert "timeout" in r.stdout.lower()
    assert "attempt 2" not in r.stdout, r.stdout
    assert "terminal" in r.stdout, r.stdout


@pytest.mark.slow
def test_supervisor_recovers_from_injected_fault(job_dir):
    """Fault injection: child dies after epoch 0; supervisor restarts it and
    checkpoint-resume finishes the job — the backup-worker capability at SPMD
    semantics."""
    out = job_dir / "out_s"
    env = _cli_env()
    env["SHIFU_TPU_FAULT_EPOCH"] = "0"
    r = _run_cli(["train",
                  "--modelconfig", str(job_dir / "ModelConfig.json"),
                  "--columnconfig", str(job_dir / "ColumnConfig.json"),
                  "--data", str(job_dir / "normalized"),
                  "--output", str(out), "--epochs", "3",
                  "--supervise", "--max-restarts", "3"],
                 env=env, timeout=600)
    # Every attempt re-injects the fault at epoch 0, but resume skips epoch 0
    # after the first checkpoint, so attempt 2 starts at epoch 1 and survives.
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FAULT INJECTION" in r.stdout
    assert "attempt 1 exited rc=17" in r.stdout
    board = (out / "console.board").read_text()
    assert "Resumed from checkpoint" in board
    assert (out / "final_model" / "weights.npz").exists()


@pytest.mark.slow
def test_supervisor_budget_resets_on_progress(job_dir):
    """The restart budget bounds CONSECUTIVE no-progress failures, not
    lifetime restarts: a job preempted after every epoch (each attempt
    resuming one epoch further) must finish under a budget smaller than the
    total number of preemptions."""
    out = job_dir / "out_p"
    env = _cli_env()
    env["SHIFU_TPU_FAULT_EVERY_EPOCH"] = "3"  # die after epochs 0, 1, 2
    r = _run_cli(["train",
                  "--modelconfig", str(job_dir / "ModelConfig.json"),
                  "--columnconfig", str(job_dir / "ColumnConfig.json"),
                  "--data", str(job_dir / "normalized"),
                  "--output", str(out), "--epochs", "4",
                  "--supervise", "--max-restarts", "1"],
                 env=env, timeout=600)
    # 3 failures against a budget of 1 — only possible because every
    # attempt completed (and checkpointed) one more epoch
    assert r.returncode == 0, r.stdout + r.stderr
    assert "restart budget reset" in r.stdout
    assert "succeeded after 4 attempts" in r.stdout
    assert (out / "final_model" / "weights.npz").exists()


@pytest.mark.slow
def test_supervisor_liveness_kills_hung_child(job_dir):
    """Heartbeat-liveness parity (TensorflowApplicationMaster.java:63-112):
    a child that stops writing board progress for shifu.liveness.seconds is
    killed and restarted; checkpoint-resume finishes the job."""
    from shifu_tpu.utils import xmlconfig
    xml = job_dir / "global.xml"
    xmlconfig.write_configuration_xml({"shifu.liveness.seconds": "30"},
                                      str(xml))
    out = job_dir / "out_h"
    env = _cli_env()
    env["SHIFU_TPU_HANG_EPOCH"] = "0"
    r = _run_cli(["train",
                  "--modelconfig", str(job_dir / "ModelConfig.json"),
                  "--columnconfig", str(job_dir / "ColumnConfig.json"),
                  "--data", str(job_dir / "normalized"),
                  "--globalconfig", str(xml),
                  "--output", str(out), "--epochs", "3",
                  "--supervise", "--max-restarts", "3"],
                 env=env, timeout=600)
    # attempt 1 hangs after epoch 0 (checkpoint already saved), the
    # supervisor's liveness monitor kills it; attempt 2 resumes at epoch 1
    # where the hang injection no longer fires, and finishes.  The 30s
    # window must exceed jax import+compile time on a loaded host — the
    # board is silent until the first epoch line
    assert r.returncode == 0, r.stdout + r.stderr
    assert "no progress for 30" in r.stdout, r.stdout
    assert "liveness kill" in r.stdout
    board = (out / "console.board").read_text()
    assert "HANG INJECTION" in board
    assert "Resumed from checkpoint" in board
    assert (out / "final_model" / "weights.npz").exists()


def test_liveness_config_keys():
    """shifu.liveness.seconds wires through; the reference heartbeat pair is
    preserved but deliberately NOT mapped (its 1s-heartbeat semantics would
    false-kill long epochs on a per-epoch board heartbeat)."""
    from shifu_tpu.config import JobConfig
    from shifu_tpu.utils import xmlconfig

    job = JobConfig()
    out = xmlconfig.apply_to_job(job, {"shifu.liveness.seconds": "40"})
    assert out.runtime.liveness_seconds == 40.0
    out2 = xmlconfig.apply_to_job(job, {
        "shifu.task.heartbeat-interval-ms": "1000",
        "shifu.task.max-missed-heartbeats": "25"})
    assert out2.runtime.liveness_seconds == 0.0
    assert job.runtime.liveness_seconds == 0.0  # default: off


@pytest.mark.slow
def test_supervisor_budget_exhausted(job_dir):
    out = job_dir / "out_b"
    env = _cli_env()
    env["SHIFU_TPU_FAULT_EPOCH"] = "999999"  # never fires
    # point data at a nonexistent dir -> every attempt fails immediately
    r = _run_cli(["train",
                  "--modelconfig", str(job_dir / "ModelConfig.json"),
                  "--columnconfig", str(job_dir / "ColumnConfig.json"),
                  "--data", str(job_dir / "missing_dir"),
                  "--output", str(out), "--epochs", "2",
                  "--supervise", "--max-restarts", "1"],
                 env=env, timeout=600)
    assert r.returncode != 0
    assert "restart budget exhausted" in r.stdout


@pytest.mark.slow
def test_globalconfig_xml_overrides(job_dir):
    from shifu_tpu.utils import xmlconfig
    xml = job_dir / "global.xml"
    xmlconfig.write_configuration_xml({
        "shifu.application.epochs": "1",
        "shifu.application.batch-size": "128",
    }, str(xml))
    out = job_dir / "out_x"
    r = _run_cli(["train",
                  "--modelconfig", str(job_dir / "ModelConfig.json"),
                  "--columnconfig", str(job_dir / "ColumnConfig.json"),
                  "--data", str(job_dir / "normalized"),
                  "--globalconfig", str(xml),
                  "--output", str(out)])
    assert r.returncode == 0, r.stdout + r.stderr
    job = json.loads((out / "job-config.json").read_text())
    assert job["train"]["epochs"] == 1
    assert job["data"]["batch_size"] == 128
    assert "Epoch 1:" not in r.stdout


@pytest.mark.slow
def test_mesh_from_globalconfig_sequence_parallel(job_dir):
    """shifu.mesh.* XML keys drive the device mesh: a data x seq topology
    trains an FT-Transformer with ring attention through the CLI — the full
    operator path for the sequence-parallel capability."""
    from shifu_tpu.data import synthetic
    from shifu_tpu.utils import xmlconfig
    # 15 features + CLS = 16 tokens, divisible by the seq axis (2)
    schema = synthetic.make_schema(num_features=15)
    rows = synthetic.make_rows(1500, schema, seed=5, noise=0.3)
    synthetic.write_files(rows, str(job_dir / "normalized15"), num_files=4)
    columns = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    for i in range(1, 16):
        columns.append({"columnNum": i, "columnName": f"f{i}",
                        "columnType": "N", "finalSelect": True})
    (job_dir / "ColumnConfig.json").write_text(json.dumps(columns))
    mc = dict(MODEL_CONFIG)
    mc["train"] = dict(MODEL_CONFIG["train"],
                       numTrainEpochs=1,
                       params=dict(MODEL_CONFIG["train"]["params"],
                                   ModelType="ft_transformer", TokenDim=8,
                                   NumAttentionHeads=2, NumLayers=1,
                                   AttentionImpl="ring"))
    (job_dir / "ModelConfig.json").write_text(json.dumps(mc))
    xml = job_dir / "global.xml"
    xmlconfig.write_configuration_xml({
        "shifu.mesh.data": "2",
        "shifu.mesh.seq": "2",
        "shifu.application.batch-size": "64",
    }, str(xml))
    out = job_dir / "out_sp"
    r = _run_cli(["train",
                  "--modelconfig", str(job_dir / "ModelConfig.json"),
                  "--columnconfig", str(job_dir / "ColumnConfig.json"),
                  "--data", str(job_dir / "normalized15"),
                  "--globalconfig", str(xml),
                  "--output", str(out)])
    assert r.returncode == 0, r.stdout + r.stderr
    job = json.loads((out / "job-config.json").read_text())
    mesh = job["runtime"]["mesh"]
    assert (mesh["data"], mesh["model"], mesh["seq"]) == (2, 1, 2)
    assert job["model"]["attention_impl"] == "ring"
    assert "falling back to local attention" not in r.stdout
    assert "Epoch 0:" in r.stdout


def test_kerberos_config_and_kinit(monkeypatch, tmp_path):
    """shifu.security.kerberos.* keys reach RuntimeConfig and drive kinit
    (successor of the reference's delegation-token fetch,
    TensorflowClient.java:481-502)."""
    from shifu_tpu.config.schema import RuntimeConfig
    from shifu_tpu.launcher.security import KerberosError, ensure_kerberos_ticket
    from shifu_tpu.utils import xmlconfig

    conf = {xmlconfig.KEY_KERBEROS_PRINCIPAL: "shifu@EXAMPLE.COM",
            xmlconfig.KEY_KERBEROS_KEYTAB: "/etc/shifu.keytab"}

    class _Job:
        train = None
        data = None
        runtime = RuntimeConfig()

        def replace(self, **kw):
            for k, v in kw.items():
                setattr(self, k, v)
            return self

    job = xmlconfig.apply_to_job(_Job(), conf)
    assert job.runtime.kerberos_principal == "shifu@EXAMPLE.COM"
    assert job.runtime.kerberos_keytab == "/etc/shifu.keytab"

    # no principal -> no-op
    assert ensure_kerberos_ticket() is False
    # half-configured is a misconfiguration, not a silent no-op
    with pytest.raises(KerberosError, match="without shifu.security.kerberos.principal"):
        ensure_kerberos_ticket(keytab="/k.keytab")
    with pytest.raises(KerberosError, match="without shifu.security.kerberos.keytab"):
        ensure_kerberos_ticket(principal="p@R")

    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)

        class R:
            returncode = 0
            stderr = ""
            stdout = ""
        return R()

    monkeypatch.setattr("shutil.which", lambda name: "/usr/bin/kinit")
    monkeypatch.setattr("subprocess.run", fake_run)
    assert ensure_kerberos_ticket(job.runtime.kerberos_principal,
                                  job.runtime.kerberos_keytab) is True
    assert calls == [["/usr/bin/kinit", "-kt", "/etc/shifu.keytab",
                      "shifu@EXAMPLE.COM"]]

    # kinit missing -> fail fast with a clear error
    monkeypatch.setattr("shutil.which", lambda name: None)
    with pytest.raises(KerberosError, match="no `kinit`"):
        ensure_kerberos_ticket(job.runtime.kerberos_principal,
                               job.runtime.kerberos_keytab)

    # kinit failure -> surfaced stderr
    monkeypatch.setattr("shutil.which", lambda name: "/usr/bin/kinit")

    def fail_run(cmd, **kw):
        class R:
            returncode = 1
            stderr = "keytab not found"
            stdout = ""
        return R()

    monkeypatch.setattr("subprocess.run", fail_run)
    with pytest.raises(KerberosError, match="keytab not found"):
        ensure_kerberos_ticket(job.runtime.kerberos_principal,
                               job.runtime.kerberos_keytab)


@pytest.mark.slow
def test_eval_cli_multi_target_per_head(tmp_path):
    """Multi-target mode through the full CLI: train MTL from JSON, then
    `eval` reports per-head AUC/error alongside the head-0 summary."""
    from shifu_tpu.data import synthetic

    mc = {
        "dataSet": {"multiTargetColumnNames": ["fraud", "chargeback"]},
        "train": {"validSetRate": 0.2, "numTrainEpochs": 1, "algorithm": "MTL",
                  "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                             "ActivationFunc": ["relu"], "LearningRate": 0.01}},
    }
    cols = [{"columnNum": 0, "columnName": "fraud", "columnType": "N"},
            {"columnNum": 1, "columnName": "chargeback", "columnType": "N"}]
    cols += [{"columnNum": i + 2, "columnName": f"f{i}", "columnType": "N",
              "finalSelect": True} for i in range(8)]
    (tmp_path / "ModelConfig.json").write_text(json.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json.dumps(cols))

    rng = np.random.default_rng(3)
    rows = rng.standard_normal((800, 10)).astype(np.float32)
    rows[:, 0] = (rng.random(800) < 0.5).astype(np.float32)
    rows[:, 1] = (rng.random(800) < 0.3).astype(np.float32)
    synthetic.write_files(rows, str(tmp_path / "normalized"), num_files=2)

    out = tmp_path / "out"
    r = _run_cli(["train",
                  "--modelconfig", str(tmp_path / "ModelConfig.json"),
                  "--columnconfig", str(tmp_path / "ColumnConfig.json"),
                  "--data", str(tmp_path / "normalized"),
                  "--output", str(out)])
    assert r.returncode == 0, r.stdout + r.stderr

    r2 = _run_cli(["eval", "--model", str(out / "final_model"),
                   "--modelconfig", str(tmp_path / "ModelConfig.json"),
                   "--columnconfig", str(tmp_path / "ColumnConfig.json"),
                   "--data", str(tmp_path / "normalized")])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    summary = json.loads(r2.stdout.strip().splitlines()[-1])
    assert summary["rows"] == 800
    heads = summary["heads"]
    assert [h["name"] for h in heads] == ["fraud", "chargeback"]
    for h in heads:
        assert h["auc"] is None or 0.0 <= h["auc"] <= 1.0
        assert h["weighted_error"] is not None
    # head 0 of the per-head block matches the top-level summary
    assert heads[0]["auc"] == summary["auc"]


def test_export_cli_from_checkpoint(tmp_path, small_job, small_data):
    """`shifu-tpu export` rebuilds the artifact from the newest checkpoint
    without retraining — the crash-after-train recovery path."""
    import json

    import numpy as np

    from shifu_tpu.config import CheckpointConfig, RuntimeConfig
    from shifu_tpu.export import load_scorer
    from shifu_tpu.launcher import cli
    from shifu_tpu.train import train

    train_ds, valid_ds = small_data
    ckpt = str(tmp_path / "ckpt")
    job = small_job.replace(
        train=small_job.train.__class__(epochs=2,
                                        optimizer=small_job.train.optimizer),
        runtime=RuntimeConfig(checkpoint=CheckpointConfig(directory=ckpt)))
    r = train(job, train_ds, valid_ds, console=lambda s: None)

    # Shifu configs matching small_job's 30-feature schema
    mc = {"dataSet": {"targetColumnName": "target"},
          "train": {"numTrainEpochs": 2, "validSetRate": 0.1,
                    "algorithm": "NN",
                    "params": {"NumHiddenLayers": 2,
                               "NumHiddenNodes": [16, 16],
                               "ActivationFunc": ["tanh", "tanh"],
                               "Optimizer": "adam",
                               "LearningRate": 0.003}}}
    cols = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    cols += [{"columnNum": i, "columnName": f"f{i}", "columnType": "N",
              "finalSelect": True} for i in range(1, 31)]
    (tmp_path / "ModelConfig.json").write_text(json.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json.dumps(cols))

    out = str(tmp_path / "artifact")
    rc = cli.main(["export", "--modelconfig", str(tmp_path / "ModelConfig.json"),
                   "--columnconfig", str(tmp_path / "ColumnConfig.json"),
                   "--checkpoint-dir", ckpt, "--output", out])
    assert rc == 0
    scorer = load_scorer(out)
    scores = np.asarray(scorer.compute_batch(valid_ds.features))
    # the exported artifact IS the trained state: scores match its forward
    from shifu_tpu.train import make_eval_step
    import jax.numpy as jnp
    want = np.asarray(make_eval_step(job)(r.state, {
        "features": jnp.asarray(valid_ds.features),
        "target": jnp.asarray(valid_ds.target),
        "weight": jnp.asarray(valid_ds.weight)}))
    np.testing.assert_allclose(scores, want, rtol=1e-4, atol=1e-5)

    rc_missing = cli.main(["export", "--modelconfig",
                           str(tmp_path / "ModelConfig.json"),
                           "--columnconfig",
                           str(tmp_path / "ColumnConfig.json"),
                           "--checkpoint-dir", str(tmp_path / "nope"),
                           "--output", out])
    assert rc_missing == 1


def test_score_cli_engine_tiers(tmp_path, small_job, small_data):
    """--engine selects an explicit scorer tier; every tier reproduces the
    auto tier's scores on the same artifact."""
    import numpy as np

    from shifu_tpu.export import save_artifact
    from shifu_tpu.launcher import cli
    from shifu_tpu.train import init_state, make_forward_fn

    import jax

    state = init_state(small_job, 30)
    art = str(tmp_path / "artifact")
    save_artifact(jax.device_get(state.params), small_job, art,
                  forward_fn=make_forward_fn(small_job))
    train_ds, _ = small_data
    rows = train_ds.features[:32]
    inp = tmp_path / "rows.psv"
    inp.write_text("\n".join("|".join(f"{v:.6f}" for v in r) for r in rows))

    outs = {}
    for engine in ("auto", "native", "numpy", "stablehlo", "jax"):
        out = tmp_path / f"scores_{engine}.txt"
        rc = cli.main(["score", "--model", art, "--input", str(inp),
                       "--output", str(out), "--engine", engine])
        assert rc == 0, engine
        outs[engine] = np.loadtxt(out)
    for engine, s in outs.items():
        np.testing.assert_allclose(s, outs["auto"], rtol=1e-4, atol=1e-5,
                                   err_msg=engine)


def test_score_cli_engine_conflicts_and_missing_program(tmp_path, small_job):
    import jax

    from shifu_tpu.export import save_artifact
    from shifu_tpu.launcher import cli
    from shifu_tpu.train import init_state

    state = init_state(small_job, 30)
    art = str(tmp_path / "artifact")
    save_artifact(jax.device_get(state.params), small_job, art)
    inp = tmp_path / "rows.psv"
    inp.write_text("|".join(["0.1"] * 30) + "\n")

    rc = cli.main(["score", "--model", art, "--input", str(inp),
                   "--native", "--engine", "jax"])
    assert rc == 1  # contradictory flags fail loudly, not silently


def test_score_cli_unavailable_tier_reports(tmp_path, small_job):
    """A tier the artifact cannot serve exits 1 with a message, not a
    traceback (e.g. stablehlo without scoring.jaxexport)."""
    import jax

    from shifu_tpu.export import save_artifact
    from shifu_tpu.launcher import cli
    from shifu_tpu.train import init_state

    state = init_state(small_job, 30)
    art = str(tmp_path / "artifact")
    save_artifact(jax.device_get(state.params), small_job, art)  # no forward_fn
    inp = tmp_path / "rows.psv"
    inp.write_text("|".join(["0.1"] * 30) + "\n")
    rc = cli.main(["score", "--model", art, "--input", str(inp),
                   "--engine", "stablehlo"])
    assert rc == 1


def test_score_cli_bad_native_artifact_reports(tmp_path, small_job):
    """A corrupt/unloadable native model.bin exits 1 with the clean
    'scorer: ...' message instead of a RuntimeError traceback (ADVICE
    round 1, launcher/cli.py)."""
    import struct

    import jax

    from shifu_tpu.export import save_artifact
    from shifu_tpu.launcher import cli
    from shifu_tpu.runtime import native_scorer as ns
    from shifu_tpu.train import init_state

    state = init_state(small_job, 30)
    art = str(tmp_path / "artifact")
    save_artifact(jax.device_get(state.params), small_job, art)
    # current magic+version AND a matching source digest so NativeScorer
    # skips the repack path, but a truncated body the C loader must reject
    with open(tmp_path / "artifact" / ns.MODEL_BIN, "wb") as f:
        f.write(struct.pack("<2I", ns._MAGIC, ns._VERSION))
    with open(tmp_path / "artifact" / (ns.MODEL_BIN + ".meta"), "w") as f:
        json.dump({"format_version": ns._VERSION,
                   "src_digest": ns._src_digest(art)}, f)
    inp = tmp_path / "rows.psv"
    inp.write_text("|".join(["0.1"] * 30) + "\n")
    rc = cli.main(["score", "--model", art, "--input", str(inp),
                   "--engine", "native"])
    assert rc == 1


def test_pdeathsig_env_name_in_sync():
    """cli._arm_pdeathsig reads the env var by literal name (the cold
    status/attach/kill path must not import the supervisor module); the
    literal must match supervisor.ENV_PDEATHSIG."""
    import inspect

    from shifu_tpu.launcher import cli, supervisor
    assert supervisor.ENV_PDEATHSIG == "SHIFU_TPU_PDEATHSIG"
    assert '"SHIFU_TPU_PDEATHSIG"' in inspect.getsource(cli._arm_pdeathsig)
