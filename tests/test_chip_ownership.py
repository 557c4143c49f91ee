"""A TPU chip belongs to one process: starting several processes on one host
that would each open the accelerator is an immediate, named error
(launcher/pod.require_one_chip_owner), not a hang.  Children held to the
CPU by JAX_PLATFORMS=cpu are a simulation and stay allowed."""

import sys

import pytest

from shifu_tpu.config.schema import FleetConfig, ServingConfig
from shifu_tpu.launcher import pod
from shifu_tpu.runtime import fleet


def test_rule(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    pod.require_one_chip_owner(4, "gang", local=True)      # CPU simulation
    with pytest.raises(pod.ChipOwnershipError):
        # remote children do not inherit this environment
        pod.require_one_chip_owner(2, "two members on host h1", local=False)
    monkeypatch.delenv("JAX_PLATFORMS")
    pod.require_one_chip_owner(1, "one process", local=True)
    with pytest.raises(pod.ChipOwnershipError, match="one process at a time"):
        pod.require_one_chip_owner(2, "gang", local=True)


def test_local_gang_is_refused_before_any_spawn(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_PLATFORMS")
    spec = pod.parse_hosts("local:2")
    with pytest.raises(pod.ChipOwnershipError, match="training gang of 2"):
        pod.launch_gang(spec, ["train"], str(tmp_path), attempt=1)
    assert not (tmp_path / "logs").exists()      # nothing was dispatched

    same_host = pod.PodSpec(hosts=("h1", "h1"), transport="ssh")
    with pytest.raises(pod.ChipOwnershipError):
        pod.launch_gang(same_host, ["train"], str(tmp_path), attempt=1)

    # host-side ranks never open the chip: dispatched (and here failing on
    # their missing arguments), not refused
    rc, _failed = pod.launch_gang(spec, ["data-dryrun"], str(tmp_path),
                                  attempt=1, echo=lambda _s: None)
    assert rc != 0 and (tmp_path / "logs").exists()


def test_second_device_engine_member_on_a_host_is_refused(monkeypatch,
                                                          tmp_path):
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(fleet.ProcessMember, "_device_children", {})
    sleeper = [sys.executable, "-c", "import time; time.sleep(60)"]

    def member(i, engine, host_id="local-0"):
        return fleet.ProcessMember(
            f"member-{i}", "/artifact", serving=ServingConfig(engine=engine),
            fleet=FleetConfig(), tele_dir=str(tmp_path / f"m{i}"),
            port=0, host_id=host_id, argv=sleeper)

    started = [member(0, "jax")]
    try:
        with pytest.raises(pod.ChipOwnershipError, match="member-1"):
            member(1, "aot")
        started.append(member(2, "numpy"))            # host engine: fine
        started.append(member(3, "jax", "local-1"))   # another host: fine
        started[0].kill()
        started[0].proc.wait(timeout=30)
        started.append(member(4, "jax"))     # the chip's owner is gone
    finally:
        for m in started:
            m.kill()
            m.proc.wait(timeout=30)
