"""Detached job tests: submission outliving the client, status/attach/kill
— the YARN-parity surface (the reference job ran under YARN and survived
its submitting client, which merely polled and tailed,
yarn/client/TensorflowClient.java:625-658,829-841)."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_NUM_CPU_DEVICES"] = "2"
    return env


def _cli(args, timeout=300):
    return subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", *args],
        capture_output=True, text=True, timeout=timeout, env=_env(), cwd=REPO)


@pytest.fixture()
def job_files(tmp_path):
    from shifu_tpu.data import synthetic

    mc = {"dataSet": {"targetColumnName": "target"},
          "train": {"validSetRate": 0.1, "numTrainEpochs": 2,
                    "algorithm": "NN",
                    "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                               "ActivationFunc": ["tanh"],
                               "LearningRate": 0.003, "Optimizer": "adam"}}}
    cols = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    cols += [{"columnNum": i, "columnName": f"f{i}", "columnType": "N",
              "finalSelect": True} for i in range(1, 11)]
    (tmp_path / "ModelConfig.json").write_text(json.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json.dumps(cols))
    schema = synthetic.make_schema(num_features=10)
    rows = synthetic.make_rows(1500, schema, seed=3, noise=0.3)
    synthetic.write_files(rows, str(tmp_path / "data"), num_files=3)
    return tmp_path


def _submit(job_files, out, extra=()):
    r = _cli(["train",
              "--modelconfig", str(job_files / "ModelConfig.json"),
              "--columnconfig", str(job_files / "ColumnConfig.json"),
              "--data", str(job_files / "data"),
              "--output", str(out), "--detach", *extra])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "submitted: pid" in r.stdout
    return r


@pytest.mark.slow
def test_detached_job_survives_client_and_finishes(job_files):
    """Submit returns immediately; the submitting process is gone while the
    job still runs; the job completes, `status` reports FINISHED, and
    `attach` replays the board and exits with the job's code."""
    out = job_files / "out_d"
    _submit(job_files, out)
    # the client process already exited — the daemon must finish on its own
    deadline = time.monotonic() + 240
    state = {}
    while time.monotonic() < deadline:
        r = _cli(["status", str(out)])
        state = json.loads(r.stdout.strip().splitlines()[-1])
        if state["state"] in ("FINISHED", "FAILED", "DEAD"):
            break
        time.sleep(1)
    log = (out / "supervisor.log")
    assert state["state"] == "FINISHED", (
        state, log.read_text() if log.exists() else "no log")
    assert state["exit"] == 0
    assert "Epoch 1:" in state.get("last_progress", "") or "final" in \
        state.get("last_progress", "")
    assert (out / "final_model" / "weights.npz").exists()
    # attach after the fact: replays the board, exits with the job's code
    r2 = _cli(["attach", str(out)])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "Epoch 0:" in r2.stdout
    assert "job finished (exit 0)" in r2.stdout


@pytest.mark.slow
def test_detached_job_kill_drains(job_files):
    """`kill <job_dir>` terminates the whole detached tree; status then
    reports the non-zero terminal state and nothing is left running."""
    out = job_files / "out_k"
    _submit(job_files, out, extra=["--epochs", "50000"])
    # wait for the job to actually train (board exists)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and not (out / "console.board").exists():
        time.sleep(0.5)
    assert (out / "console.board").exists(), "job never started"
    pid = json.loads((out / "job.json").read_text())["pid"]
    r = _cli(["kill", str(out)])
    assert r.returncode == 0, r.stdout + r.stderr
    time.sleep(2)
    # no survivors in the job's process group
    try:
        os.killpg(pid, 0)
        alive = True
    except ProcessLookupError:
        alive = False
    assert not alive, "detached tree survived kill"
    r2 = _cli(["status", str(out)])
    state = json.loads(r2.stdout.strip().splitlines()[-1])
    assert state["state"] in ("FAILED", "DEAD")


def test_status_unknown_dir(tmp_path):
    r = _cli(["status", str(tmp_path / "nope")])
    assert r.returncode == 1
    assert json.loads(r.stdout.strip())["state"] == "UNKNOWN"


@pytest.mark.slow
def test_detached_timeout_is_terminal_and_reported(job_files):
    """--detach + --timeout: the daemon's supervised child hits the job
    deadline ONCE (terminal, no restart loop — the round-2 verdict bug
    class), the daemon exits with the timeout code, and `status` reports
    FAILED with exit 3 within bounded wall time."""
    out = job_files / "out_t"
    _submit(job_files, out, extra=["--epochs", "50000", "--timeout", "5"])
    deadline = time.monotonic() + 150  # >> 5s timeout, << a restart loop
    state = {}
    while time.monotonic() < deadline:
        r = _cli(["status", str(out)])
        state = json.loads(r.stdout.strip().splitlines()[-1])
        if state["state"] in ("FINISHED", "FAILED", "DEAD"):
            break
        time.sleep(1)
    log = (out / "supervisor.log")
    assert state["state"] == "FAILED", (
        state, log.read_text() if log.exists() else "no log")
    assert state["exit"] == 3  # EXIT_TIMEOUT, recorded as the job's report


@pytest.mark.slow
@pytest.mark.skipif(sys.platform != "linux",
                    reason="pdeathsig reaping + /proc scan are Linux-only")
def test_detached_daemon_unclean_death_reports_dead(job_files):
    """SIGKILL the daemon directly (no chance to write job.status): status
    must report DEAD — never RUNNING (stale pid) or FINISHED."""
    out = job_files / "out_u"
    _submit(job_files, out, extra=["--epochs", "50000"])
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and not (out / "console.board").exists():
        time.sleep(0.5)
    assert (out / "console.board").exists(), "job never started"
    pid = json.loads((out / "job.json").read_text())["pid"]
    try:
        os.killpg(pid, signal.SIGKILL)
    except ProcessLookupError:
        log = out / "supervisor.log"
        raise AssertionError(
            "daemon died before the test could SIGKILL it: "
            + (log.read_text()[-2000:] if log.exists() else "no log"))
    deadline = time.monotonic() + 30
    state = {}
    while time.monotonic() < deadline:
        r = _cli(["status", str(out)])
        state = json.loads(r.stdout.strip().splitlines()[-1])
        if state["state"] != "RUNNING":
            break
        time.sleep(0.5)
    assert state["state"] == "DEAD", state
    assert state.get("exit") is None
    # NO SURVIVORS: the supervised attempt runs in its own session, so the
    # daemon's SIGKILL cannot reach it by group — PR_SET_PDEATHSIG must
    # reap it (without it, a 50000-epoch orphan spins at full CPU forever)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if not _procs_mentioning(str(out)):
            break
        time.sleep(0.5)
    leftovers = _procs_mentioning(str(out))
    assert not leftovers, f"orphaned training processes: {leftovers}"


def _procs_mentioning(needle: str) -> list[int]:
    """Pids (other than ours) whose cmdline contains `needle`."""
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if needle.encode() in f.read():
                    out.append(int(pid))
        except OSError:
            continue
    return out


@pytest.mark.slow
@pytest.mark.skipif(sys.platform != "linux",
                    reason="pdeathsig reaping + /proc scan are Linux-only")
def test_detached_multiprocess_unclean_death_no_survivors(job_files):
    """The pod-rank variant of the orphan hazard: SIGKILL the daemon of a
    --num-processes gang; the attempt dispatcher AND every rank must be
    reaped (ranks arm PR_SET_PDEATHSIG against the dispatcher, the
    dispatcher against the supervisor)."""
    out = job_files / "out_mp"
    _submit(job_files, out,
            extra=["--epochs", "50000", "--num-processes", "2"])
    deadline = time.monotonic() + 150
    while time.monotonic() < deadline and not (out / "console.board").exists():
        time.sleep(0.5)
    assert (out / "console.board").exists(), "gang never started"
    pid = json.loads((out / "job.json").read_text())["pid"]
    try:
        os.killpg(pid, signal.SIGKILL)
    except ProcessLookupError:
        log = out / "supervisor.log"
        raise AssertionError(
            "daemon died before the test could SIGKILL it: "
            + (log.read_text()[-2000:] if log.exists() else "no log"))
    deadline = time.monotonic() + 45
    while time.monotonic() < deadline:
        if not _procs_mentioning(str(out)):
            break
        time.sleep(0.5)
    leftovers = _procs_mentioning(str(out))
    assert not leftovers, f"orphaned gang processes: {leftovers}"
