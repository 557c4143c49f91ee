"""TPU slice provisioning tests — the compute-acquisition layer driven
end-to-end against a fake `gcloud` on PATH (the same technique as the
fake-ssh transport e2e), per the reference's one-command acquisition
(yarn/client/TensorflowClient.java:339-426)."""

import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_FAKE_GCLOUD = f"""#!{sys.executable}
import json, os, sys
args = sys.argv[1:]
with open(os.environ["FAKE_GCLOUD_LOG"], "a") as f:
    f.write(json.dumps(args) + chr(10))
cmd = " ".join(args)
if "queued-resources create" in cmd:
    mode = os.environ.get("FAKE_GCLOUD_FAIL_CREATE")
    if mode == "ALREADY_EXISTS":
        sys.stderr.write("ERROR: ALREADY_EXISTS: resource exists" + chr(10))
        sys.exit(1)
    if mode:
        sys.stderr.write("ERROR: (gcloud) quota exceeded" + chr(10))
        sys.exit(1)
    sys.exit(0)
if "queued-resources describe" in cmd:
    sf = os.environ["FAKE_GCLOUD_STATE"]
    n = int(open(sf).read()) if os.path.exists(sf) else 0
    open(sf, "w").write(str(n + 1))
    states = os.environ.get("FAKE_GCLOUD_STATES", "ACTIVE").split(",")
    state = states[min(n, len(states) - 1)]
    print(json.dumps({{"state": {{"state": state}}}}))
    sys.exit(0)
if "tpu-vm describe" in cmd:
    print(json.dumps({{"networkEndpoints": [
        {{"ipAddress": "localhost"}}, {{"ipAddress": "localhost"}}]}}))
    sys.exit(0)
if "queued-resources delete" in cmd:
    if os.environ.get("FAKE_GCLOUD_DELETE_NOT_FOUND"):
        sys.stderr.write("ERROR: NOT_FOUND: no such queued resource" + chr(10))
        sys.exit(1)
    if os.environ.get("FAKE_GCLOUD_FAIL_DELETE_MSG"):
        sys.stderr.write(os.environ["FAKE_GCLOUD_FAIL_DELETE_MSG"] + chr(10))
        sys.exit(1)
    sys.exit(1 if os.environ.get("FAKE_GCLOUD_FAIL_DELETE") else 0)
sys.exit(64)
"""


@pytest.fixture
def fake_gcloud(tmp_path, monkeypatch):
    fake_bin = tmp_path / "bin"
    fake_bin.mkdir()
    (fake_bin / "gcloud").write_text(_FAKE_GCLOUD)
    (fake_bin / "gcloud").chmod(0o755)
    log = tmp_path / "gcloud.log"
    monkeypatch.setenv("PATH", f"{fake_bin}{os.pathsep}{os.environ['PATH']}")
    monkeypatch.setenv("FAKE_GCLOUD_LOG", str(log))
    monkeypatch.setenv("FAKE_GCLOUD_STATE", str(tmp_path / "gcloud.state"))
    return fake_bin, log


def _calls(log):
    if not log.exists():
        return []
    return [json.loads(l) for l in log.read_text().splitlines()]


def test_spec_from_xml_and_flags():
    from shifu_tpu.launcher.provision import (ProvisionError, ProvisionSpec,
                                              spec_from_xml)

    conf = {"shifu.provision.name": "shifu-job",
            "shifu.provision.accelerator-type": "v5litepod-16",
            "shifu.provision.zone": "us-west4-a",
            "shifu.provision.spot": "true",
            "shifu.provision.ready-timeout-seconds": "600"}
    spec = spec_from_xml(conf)
    assert spec.name == "shifu-job"
    assert spec.accelerator_type == "v5litepod-16"
    assert spec.spot is True
    assert spec.ready_timeout_seconds == 600.0
    # CLI flags override the XML layer
    spec2 = spec_from_xml(conf, zone="europe-west4-b", name="other")
    assert spec2.zone == "europe-west4-b" and spec2.name == "other"
    with pytest.raises(ProvisionError, match="accelerator-type"):
        ProvisionSpec(name="x", accelerator_type="", zone="z").validate()


def test_provision_lifecycle_argv(fake_gcloud):
    """create -> await -> hosts -> delete issue the exact gcloud surface."""
    from shifu_tpu.launcher import provision as prov

    _, log = fake_gcloud
    spec = prov.ProvisionSpec(name="s1", accelerator_type="v5litepod-8",
                              zone="us-west4-a", spot=True,
                              poll_seconds=0.01)
    prov.create(spec, echo=lambda s: None)
    prov.await_ready(spec, echo=lambda s: None)
    assert prov.worker_hosts(spec) == ["localhost", "localhost"]
    prov.delete(spec, echo=lambda s: None)
    calls = _calls(log)
    assert calls[0][:5] == ["compute", "tpus", "queued-resources", "create",
                            "s1"]
    assert "--spot" in calls[0] and "--node-id" in calls[0]
    assert ["compute", "tpus", "tpu-vm", "describe", "s1"] == calls[-2][:5]
    assert calls[-1][:5] == ["compute", "tpus", "queued-resources", "delete",
                             "s1"]


def test_await_ready_waits_through_queue_and_rejects_dead(fake_gcloud,
                                                          monkeypatch):
    from shifu_tpu.launcher import provision as prov

    spec = prov.ProvisionSpec(name="s2", accelerator_type="a", zone="z",
                              poll_seconds=0.01)
    monkeypatch.setenv("FAKE_GCLOUD_STATES",
                       "ACCEPTED,WAITING_FOR_RESOURCES,ACTIVE")
    seen = []
    prov.await_ready(spec, echo=seen.append)
    assert any("WAITING_FOR_RESOURCES" in s for s in seen)
    assert any("ACTIVE" in s for s in seen)

    monkeypatch.setenv("FAKE_GCLOUD_STATES", "FAILED")
    monkeypatch.setenv("FAKE_GCLOUD_STATE",
                       os.environ["FAKE_GCLOUD_STATE"] + ".none")
    with pytest.raises(prov.ProvisionError, match="FAILED"):
        prov.await_ready(prov.ProvisionSpec(
            name="s3", accelerator_type="a", zone="z", poll_seconds=0.01))


def test_provision_and_run_releases_on_failure(fake_gcloud):
    from shifu_tpu.launcher import provision as prov

    _, log = fake_gcloud
    spec = prov.ProvisionSpec(name="s4", accelerator_type="a", zone="z",
                              poll_seconds=0.01)
    with pytest.raises(RuntimeError, match="boom"):
        prov.provision_and_run(spec, lambda hosts: (_ for _ in ()).throw(
            RuntimeError("boom")), echo=lambda s: None)
    # the slice was still released — a failed job must not leak a TPU
    assert _calls(log)[-1][:4] == ["compute", "tpus", "queued-resources",
                                   "delete"]


@pytest.mark.slow
def test_train_provision_end_to_end(tmp_path):
    """One command, nothing -> slice -> gang -> released: `train
    --provision` against a fake gcloud (slice lifecycle) + fake ssh
    (dispatch onto the 'provisioned' hosts), trained artifact out, slice
    deleted afterward."""
    from shifu_tpu.data import synthetic

    fake_bin = tmp_path / "bin"
    fake_bin.mkdir()
    (fake_bin / "gcloud").write_text(_FAKE_GCLOUD)
    (fake_bin / "gcloud").chmod(0o755)
    (fake_bin / "ssh").write_text(
        "#!/bin/sh\n"
        "[ \"$1\" = -tt ] || { echo 'missing -tt' >&2; exit 64; }\n"
        "shift\n"
        "[ \"$1\" = -o ] && shift 2\n"
        "host=\"$1\"; shift\n"
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
    (tmp_path / "ModelConfig.json").write_text(json.dumps(mc))
    (tmp_path / "ColumnConfig.json").write_text(json.dumps(cols))
    schema = synthetic.make_schema(num_features=8)
    rows = synthetic.make_rows(800, schema, seed=6, noise=0.3)
    synthetic.write_files(rows, str(tmp_path / "data"), num_files=2)

    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
                "PATH": f"{fake_bin}{os.pathsep}{env.get('PATH', '')}",
                "FAKE_GCLOUD_LOG": str(tmp_path / "gcloud.log"),
                "FAKE_GCLOUD_STATE": str(tmp_path / "gcloud.state"),
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", "")})
    out = tmp_path / "job"
    r = subprocess.run(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
         "--modelconfig", str(tmp_path / "ModelConfig.json"),
         "--columnconfig", str(tmp_path / "ColumnConfig.json"),
         "--data", str(tmp_path / "data"),
         "--output", str(out),
         "--provision", "--provision-name", "shifu-e2e",
         "--accelerator-type", "v5litepod-8", "--zone", "us-west4-a"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "provision: requesting v5litepod-8" in r.stdout
    assert "ACTIVE" in r.stdout
    assert "2 worker hosts" in r.stdout
    assert "provision: released shifu-e2e" in r.stdout
    for f in ("GenericModelConfig.json", "weights.npz"):
        assert (out / "final_model" / f).exists(), f
    calls = [json.loads(l)
             for l in (tmp_path / "gcloud.log").read_text().splitlines()]
    assert calls[0][3] == "create" and calls[-1][3] == "delete"


def test_marker_written_during_run_and_cleared_after(fake_gcloud, tmp_path):
    """provision_and_run records the acquisition in the job dir while the
    job runs (the release trail an unclean dispatcher death needs) and
    clears it after the normal release."""
    from shifu_tpu.launcher import provision as prov

    spec = prov.ProvisionSpec(name="m1", accelerator_type="v5litepod-8",
                              zone="us-west4-a")
    out = tmp_path / "job"
    seen = {}

    def run_fn(hosts):
        seen["marker"] = prov.read_marker(str(out))
        return 0

    rc = prov.provision_and_run(spec, run_fn, echo=lambda s: None,
                                marker_dir=str(out))
    assert rc == 0
    assert seen["marker"]["name"] == "m1"
    assert seen["marker"]["zone"] == "us-west4-a"
    assert prov.read_marker(str(out)) is None  # cleared on release


def test_marker_kept_slice_respected(fake_gcloud, tmp_path):
    """--keep-slice: the marker stays (flagged) and release_from_marker
    refuses to delete a deliberately kept slice."""
    from shifu_tpu.launcher import provision as prov

    spec = prov.ProvisionSpec(name="m2", accelerator_type="v5litepod-8",
                              zone="us-west4-a")
    out = tmp_path / "jobk"
    rc = prov.provision_and_run(spec, lambda hosts: 0, echo=lambda s: None,
                                keep=True, marker_dir=str(out))
    assert rc == 0
    marker = prov.read_marker(str(out))
    assert marker and marker["keep"] is True
    assert prov.release_from_marker(str(out), echo=lambda s: None) is False
    assert prov.read_marker(str(out)) is not None  # still recorded


def test_kill_releases_slice_after_unclean_daemon_death(fake_gcloud,
                                                       tmp_path, monkeypatch):
    """A provisioning daemon SIGKILLed between create and release leaks a
    billing slice with only provision.json as the trail: `kill <job_dir>`
    must find it, release through gcloud, and clear the marker."""
    import json as _json

    from shifu_tpu.launcher import detach, provision as prov

    fake_bin, log = fake_gcloud
    out = tmp_path / "leaked"
    out.mkdir()
    spec = prov.ProvisionSpec(name="leaked-slice",
                              accelerator_type="v5litepod-8",
                              zone="us-west4-a", project="p1")
    prov.write_marker(spec, str(out))
    # a GUARANTEED-dead pid: spawn and reap a real child (a hardcoded
    # large pid can be live under raised kernel.pid_max)
    dead = subprocess.Popen([sys.executable, "-c", "pass"])
    dead.wait()
    (out / detach.JOB_FILE).write_text(_json.dumps(
        {"pid": dead.pid, "host": os.uname().nodename}))
    msgs = []
    rc = detach.kill(str(out), echo=msgs.append)
    assert rc == 0
    assert any("released leaked-slice" in m for m in msgs), msgs
    assert prov.read_marker(str(out)) is None
    deletes = [c for c in _calls(log) if "delete" in c]
    assert deletes and "leaked-slice" in deletes[-1]
    assert "--project" in deletes[-1] and "p1" in deletes[-1]
    # status surfaces nothing anymore; before the release it would have
    prov.write_marker(spec, str(out))
    st = detach.job_state(str(out))
    assert st["provisioned_slice"] == "leaked-slice"


def test_release_failure_keeps_marker(fake_gcloud, tmp_path, monkeypatch):
    """A failed gcloud delete must NOT clear provision.json — the marker is
    the only release trail for a still-billing slice."""
    from shifu_tpu.launcher import provision as prov

    out = tmp_path / "failrel"
    spec = prov.ProvisionSpec(name="sticky", accelerator_type="v5litepod-8",
                              zone="us-west4-a")
    prov.write_marker(spec, str(out))
    monkeypatch.setenv("FAKE_GCLOUD_FAIL_DELETE", "1")
    assert prov.release_from_marker(str(out), echo=lambda s: None) is False
    assert prov.read_marker(str(out)) is not None  # trail preserved
    monkeypatch.delenv("FAKE_GCLOUD_FAIL_DELETE")
    assert prov.release_from_marker(str(out), echo=lambda s: None) is True
    assert prov.read_marker(str(out)) is None


def test_failed_create_drains_marker(fake_gcloud, tmp_path, monkeypatch):
    """create() itself failing (quota, bad flags) must not orphan the
    provision.json marker: the release path still runs, gcloud answers
    NOT_FOUND (the resource never materialized), and NOT_FOUND counts as
    released so the marker drains instead of pinning a phantom slice."""
    from shifu_tpu.launcher import provision as prov

    out = tmp_path / "nocreate"
    spec = prov.ProvisionSpec(name="phantom", accelerator_type="v5litepod-8",
                              zone="us-west4-a")
    monkeypatch.setenv("FAKE_GCLOUD_FAIL_CREATE", "1")
    monkeypatch.setenv("FAKE_GCLOUD_DELETE_NOT_FOUND", "1")
    with pytest.raises(prov.ProvisionError, match="quota"):
        prov.provision_and_run(spec, lambda hosts: 0, echo=lambda s: None,
                               marker_dir=str(out))
    assert prov.read_marker(str(out)) is None  # no phantom slice recorded


def test_delete_not_found_counts_as_released(fake_gcloud, tmp_path,
                                             monkeypatch):
    """An already-gone resource (operator deleted it by hand) must let the
    marker drain: a NOT_FOUND delete is a successful release, not a
    failure to retry forever."""
    from shifu_tpu.launcher import provision as prov

    out = tmp_path / "gone"
    spec = prov.ProvisionSpec(name="gone-slice",
                              accelerator_type="v5litepod-8",
                              zone="us-west4-a")
    prov.write_marker(spec, str(out))
    monkeypatch.setenv("FAKE_GCLOUD_DELETE_NOT_FOUND", "1")
    assert prov.release_from_marker(str(out), echo=lambda s: None) is True
    assert prov.read_marker(str(out)) is None


def test_already_exists_create_failure_releases_nothing(fake_gcloud,
                                                        tmp_path,
                                                        monkeypatch):
    """A name-collision create (ALREADY_EXISTS: e.g. a prior --keep-slice
    run holds the name) must NOT run the release drain — deleting would
    tear down a live slice this run never created.  Only our marker is
    dropped."""
    from shifu_tpu.launcher import provision as prov

    _, log = fake_gcloud
    out = tmp_path / "collide"
    spec = prov.ProvisionSpec(name="held", accelerator_type="v5litepod-8",
                              zone="us-west4-a")
    monkeypatch.setenv("FAKE_GCLOUD_FAIL_CREATE", "ALREADY_EXISTS")
    with pytest.raises(prov.ProvisionError, match="ALREADY_EXISTS"):
        prov.provision_and_run(spec, lambda hosts: 0, echo=lambda s: None,
                               marker_dir=str(out))
    assert prov.read_marker(str(out)) is None  # our marker dropped
    assert not [c for c in _calls(log) if "delete" in c]  # slice untouched


def test_already_exists_keeps_prior_unclean_death_trail(fake_gcloud,
                                                        tmp_path,
                                                        monkeypatch):
    """A retry after an UNCLEAN death of the same-named run: the dead run's
    slice still exists (create answers ALREADY_EXISTS) and still bills —
    the marker is its ONLY release trail, so it must be KEPT (and kept
    UNKEPT even when the retry passed --keep-slice: the keep flag is
    recorded only once create() proves the slice is this run's own), so
    `kill`/`release_from_marker` can still drain the orphan."""
    from shifu_tpu.launcher import provision as prov

    _, log = fake_gcloud
    out = tmp_path / "retry"
    spec = prov.ProvisionSpec(name="orphaned", accelerator_type="v5litepod-8",
                              zone="us-west4-a")
    prov.write_marker(spec, str(out))  # the dead run's trail
    monkeypatch.setenv("FAKE_GCLOUD_FAIL_CREATE", "ALREADY_EXISTS")
    for keep in (False, True):
        with pytest.raises(prov.ProvisionError, match="ALREADY_EXISTS"):
            prov.provision_and_run(spec, lambda hosts: 0,
                                   echo=lambda s: None, keep=keep,
                                   marker_dir=str(out))
        marker = prov.read_marker(str(out))
        assert marker and marker["name"] == "orphaned"  # trail preserved
        assert not marker.get("keep")  # and still releasable
    monkeypatch.delenv("FAKE_GCLOUD_FAIL_CREATE")
    assert prov.release_from_marker(str(out), echo=lambda s: None) is True
    assert prov.read_marker(str(out)) is None
    assert [c for c in _calls(log) if "delete" in c]


def test_kill_refuses_cross_host_marker(fake_gcloud, tmp_path):
    """A marker written on ANOTHER host (shared-filesystem job dir) must
    not be released from here — this host's pid table says nothing about
    the recording host's dispatcher; --force overrides."""
    import json as _json

    from shifu_tpu.launcher import detach, provision as prov

    _, log = fake_gcloud
    out = tmp_path / "nfs"
    out.mkdir()
    (out / prov.MARKER_FILE).write_text(_json.dumps(
        {"name": "far-slice", "zone": "us-west4-a", "project": "",
         "keep": False, "pid": 1234, "host": "other-host.example"}))
    msgs = []
    assert detach.kill(str(out), echo=msgs.append) == 1
    assert any("other-host.example" in m for m in msgs), msgs
    assert not [c for c in _calls(log) if "delete" in c]
    detach.kill(str(out), echo=msgs.append, force=True)
    assert [c for c in _calls(log) if "delete" in c]


def test_kill_guard_covers_stale_jobjson_branch(fake_gcloud, tmp_path):
    """A stale job.json (dead detached job) in the SAME dir as a LIVE
    foreground --provision run's marker: `kill` takes the dead-pid branch
    but the marker-liveness guard (now inside _release_slice) must still
    refuse to delete the live run's slice."""
    import json as _json

    from shifu_tpu.launcher import detach, provision as prov

    _, log = fake_gcloud
    out = tmp_path / "mixed"
    out.mkdir()
    dead = subprocess.Popen([sys.executable, "-c", "pass"])
    dead.wait()
    (out / detach.JOB_FILE).write_text(_json.dumps(
        {"pid": dead.pid, "host": os.uname().nodename}))
    live = subprocess.Popen(
        [sys.executable, "-c", "import shifu_tpu, time; time.sleep(600)"],
        env={**os.environ, "PYTHONPATH":
             REPO + os.pathsep + os.environ.get("PYTHONPATH", "")})
    # wait for exec to land: _is_our_job reads /proc/<pid>/cmdline, and on
    # a loaded machine the guard could otherwise race the fork->exec window
    # and misread the live dispatcher as not-ours (observed flake)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            with open(f"/proc/{live.pid}/cmdline", "rb") as f:
                if b"shifu_tpu" in f.read():
                    break
        except OSError:
            pass
        time.sleep(0.05)
    try:
        spec = prov.ProvisionSpec(name="mixed-slice",
                                  accelerator_type="v5litepod-8",
                                  zone="us-west4-a")
        prov.write_marker(spec, str(out))
        marker = prov.read_marker(str(out))
        marker["pid"] = live.pid
        (out / prov.MARKER_FILE).write_text(_json.dumps(marker))
        msgs = []
        rc = detach.kill(str(out), echo=msgs.append)
        assert rc == 1  # refused release surfaces in the exit code
        assert any("LIVE dispatcher" in m for m in msgs), msgs
        assert prov.read_marker(str(out)) is not None
        assert not [c for c in _calls(log) if "delete" in c]
        assert detach.kill(str(out), echo=msgs.append, force=True) == 0
        assert prov.read_marker(str(out)) is None
    finally:
        live.kill()
        live.wait()


def test_is_our_job_matches_console_script_cmdline(tmp_path):
    """The installed `shifu-tpu` console script's cmdline carries only the
    HYPHENATED form — the identity guard must match it, or a stray kill
    would fail open and delete a live run's slice."""
    from shifu_tpu.launcher import detach

    (tmp_path / "shifu-tpu").write_text("import sys, time\n"
                                        "print('up', flush=True)\n"
                                        "time.sleep(float(sys.argv[1]))\n")
    live = subprocess.Popen(
        [sys.executable, str(tmp_path / "shifu-tpu"), "60"],
        stdout=subprocess.PIPE, text=True)
    try:
        live.stdout.readline()  # child has exec'd: cmdline is final
        assert detach._is_our_job(live.pid, None) is True
    finally:
        live.kill()
        live.wait()


def test_marker_clobber_refused_for_kept_or_foreign_slice(fake_gcloud,
                                                          tmp_path):
    """provision_and_run must not overwrite a marker that is the only
    release trail of a KEPT slice or of a DIFFERENT slice; re-running the
    same (unkept) name refreshes its own trail normally."""
    from shifu_tpu.launcher import provision as prov

    out = tmp_path / "trail"
    kept = prov.ProvisionSpec(name="kept-x", accelerator_type="v5litepod-8",
                              zone="us-west4-a")
    prov.write_marker(kept, str(out), keep=True)
    with pytest.raises(prov.ProvisionError, match="kept-x"):
        prov.provision_and_run(kept, lambda h: 0, echo=lambda s: None,
                               marker_dir=str(out))
    assert prov.read_marker(str(out))["name"] == "kept-x"  # trail intact

    out2 = tmp_path / "trail2"
    other = prov.ProvisionSpec(name="other-y",
                               accelerator_type="v5litepod-8",
                               zone="us-west4-a")
    prov.write_marker(other, str(out2))
    new = prov.ProvisionSpec(name="new-z", accelerator_type="v5litepod-8",
                             zone="us-west4-a")
    with pytest.raises(prov.ProvisionError, match="other-y"):
        prov.provision_and_run(new, lambda h: 0, echo=lambda s: None,
                               marker_dir=str(out2))
    # same unkept name: overwrite allowed, normal lifecycle completes
    rc = prov.provision_and_run(other, lambda h: 0, echo=lambda s: None,
                                marker_dir=str(out2))
    assert rc == 0
    assert prov.read_marker(str(out2)) is None  # released + cleared


def test_delete_not_found_is_anchored_to_the_resource(fake_gcloud, tmp_path,
                                                      monkeypatch):
    """'project/zone ... not found' environment errors at release time must
    stay FAILURES (trail preserved); only the resource's own NOT_FOUND
    counts as released."""
    from shifu_tpu.launcher import provision as prov

    out = tmp_path / "env"
    spec = prov.ProvisionSpec(name="envslice",
                              accelerator_type="v5litepod-8",
                              zone="us-west4-a")
    prov.write_marker(spec, str(out))
    monkeypatch.setenv("FAKE_GCLOUD_FAIL_DELETE_MSG",
                       "ERROR: project my-proj not found")
    assert prov.release_from_marker(str(out), echo=lambda s: None) is False
    assert prov.read_marker(str(out)) is not None  # trail preserved
    monkeypatch.setenv("FAKE_GCLOUD_FAIL_DELETE_MSG",
                       "ERROR: queued resource envslice not found")
    assert prov.release_from_marker(str(out), echo=lambda s: None) is True
    assert prov.read_marker(str(out)) is None


def test_kill_refuses_live_foreground_provision(fake_gcloud, tmp_path):
    """A foreground `train --provision` run writes no job.json but its
    marker records the dispatcher pid: a stray `kill <job_dir>` while that
    dispatcher is ALIVE must refuse to delete the slice out from under the
    live gang — and --force must override for a stuck operator."""
    from shifu_tpu.launcher import detach, provision as prov

    _, log = fake_gcloud
    out = tmp_path / "live"
    spec = prov.ProvisionSpec(name="live-slice",
                              accelerator_type="v5litepod-8",
                              zone="us-west4-a")
    # a LIVE stand-in dispatcher whose cmdline mentions shifu_tpu
    live = subprocess.Popen(
        [sys.executable, "-c",
         "import shifu_tpu, time; time.sleep(600)"],
        env={**os.environ, "PYTHONPATH":
             REPO + os.pathsep + os.environ.get("PYTHONPATH", "")})
    try:
        # Popen returns once the child's execve has closed the error pipe,
        # which is before the kernel has set the new image's arguments:
        # under load /proc/<pid>/cmdline still reads empty then, and the
        # stand-in would not look like a dispatcher yet
        import time
        deadline = time.monotonic() + 30
        while (not detach._is_our_job(live.pid, None)
               and time.monotonic() < deadline):
            time.sleep(0.01)
        prov.write_marker(spec, str(out))
        # overwrite the recorded pid with the live stand-in's
        marker = prov.read_marker(str(out))
        marker["pid"] = live.pid
        with open(os.path.join(str(out), prov.MARKER_FILE), "w") as f:
            json.dump(marker, f)
        msgs = []
        rc = detach.kill(str(out), echo=msgs.append)
        assert rc == 1
        assert any("LIVE dispatcher" in m for m in msgs), msgs
        assert prov.read_marker(str(out)) is not None  # slice untouched
        assert not [c for c in _calls(log) if "delete" in c]
        # --force releases anyway
        rc = detach.kill(str(out), echo=msgs.append, force=True)
        assert prov.read_marker(str(out)) is None
        assert [c for c in _calls(log) if "delete" in c]
    finally:
        live.kill()
        live.wait()


@pytest.mark.slow
def test_foreground_sigterm_releases_slice(tmp_path):
    """SIGTERM a FOREGROUND `train --provision` while it awaits capacity:
    Python's default SIGTERM disposition would skip finally blocks and
    leak the slice — the CLI's handler must turn it into an unwind so the
    release still runs (and the marker is cleared)."""
    import signal as signal_lib
    import time as time_lib

    fake_bin = tmp_path / "bin"
    fake_bin.mkdir()
    (fake_bin / "gcloud").write_text(_FAKE_GCLOUD)
    (fake_bin / "gcloud").chmod(0o755)
    (tmp_path / "ModelConfig.json").write_text(json.dumps(
        {"dataSet": {"targetColumnName": "target"},
         "train": {"numTrainEpochs": 1, "algorithm": "NN",
                   "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [4],
                              "ActivationFunc": ["relu"]}}}))
    (tmp_path / "ColumnConfig.json").write_text(json.dumps(
        [{"columnNum": 0, "columnName": "target", "columnFlag": "Target"},
         {"columnNum": 1, "columnName": "f1", "columnType": "N",
          "finalSelect": True}]))
    (tmp_path / "data").mkdir()
    (tmp_path / "data" / "part-0.psv").write_text("1|0.5\n0|0.1\n")

    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", "JAX_NUM_CPU_DEVICES": "1",
                "PATH": f"{fake_bin}{os.pathsep}{env.get('PATH', '')}",
                "FAKE_GCLOUD_LOG": str(tmp_path / "gcloud.log"),
                "FAKE_GCLOUD_STATE": str(tmp_path / "gcloud.state"),
                # hold in the capacity queue so SIGTERM lands mid-await
                "FAKE_GCLOUD_STATES": "WAITING_FOR_RESOURCES",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", "")})
    out = tmp_path / "job"
    child_log = open(tmp_path / "child.log", "wb")  # diagnosable on timeout
    proc = subprocess.Popen(
        [sys.executable, "-m", "shifu_tpu.launcher.cli", "train",
         "--modelconfig", str(tmp_path / "ModelConfig.json"),
         "--columnconfig", str(tmp_path / "ColumnConfig.json"),
         "--data", str(tmp_path / "data"), "--output", str(out),
         "--provision", "--provision-name", "sigterm-slice",
         "--accelerator-type", "v5litepod-8", "--zone", "us-west4-a"],
        env=env, cwd=str(tmp_path), stdout=child_log,
        stderr=subprocess.STDOUT)
    log = tmp_path / "gcloud.log"

    def _tail() -> str:
        child_log.flush()
        try:
            return (tmp_path / "child.log").read_text()[-2000:]
        except OSError:
            return "<no child log>"

    try:
        deadline = time_lib.monotonic() + 180
        while time_lib.monotonic() < deadline:
            if any("describe" in c for c in _calls(log)):
                break
            time_lib.sleep(0.2)
        assert any("describe" in c for c in _calls(log)), \
            f"never reached await; child output:\n{_tail()}"
        proc.send_signal(signal_lib.SIGTERM)
        # generous margin: this rig is 1-core, and the release unwind has
        # to start a fresh interpreter for the fake gcloud delete
        rc = proc.wait(timeout=180)
    except subprocess.TimeoutExpired:
        raise AssertionError(
            f"child did not exit after SIGTERM; output:\n{_tail()}")
    finally:
        if proc.poll() is None:  # any assert/timeout: never leak the child
            proc.kill()
            proc.wait()
        child_log.close()
    assert rc == 128 + signal_lib.SIGTERM, (rc, _tail())
    calls = _calls(log)
    deletes = [c for c in calls if "delete" in c]
    assert deletes and "sigterm-slice" in deletes[-1], calls[-3:]
    assert not (out / "provision.json").exists()
