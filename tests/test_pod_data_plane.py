"""Pod-scale training data plane (ISSUE 20).

Covers the sharded-ingest plane end to end without multi-process
collectives (the CPU backend cannot run them — the gloo-gated companion
lives at the bottom, slow-marked):

- `host_shard_assignment` / `shard_rotation` / `shard_assignment_digest`:
  pure-function partition of the source files across hosts, deterministic
  in (seed, epoch, n_hosts, mode), epoch 0 pinned to the legacy round-robin,
  stable across an elastic width change on resume.
- per-host ingest accounting: 4 simulated hosts each cold-ingest
  <= total/4 x 1.15 source bytes, and together exactly the total.
- `interleaved_epoch_order`: the loss/AUC-identity contract — a single
  process emulating N shards reproduces the N-host global batch order
  bit-for-bit, on the staged and per-batch digest tiers, across
  kill+resume re-derivation.
- `parse_hosts` edge cases: duplicate hosts, local:1, coordinator port
  collisions.
- `pod_verify_events` + the tier-1 elastic drill: kill 1 of 2 local hosts
  mid-epoch via chaos site `data.host_shard`, gang restarts, rebalances,
  rejoins, and `pod-verify` holds green.
- journal planes: `pod_ingest_rollup`, `digest_agreement`, and the
  profile renderer's pod block.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from shifu_tpu.config.schema import ConfigError, DataConfig
from shifu_tpu.data import pipeline as pipe
from shifu_tpu.data import synthetic


# ------------------------------------------------------------ shard scheme


@pytest.mark.parametrize("mode", ["static", "auto", "rotate"])
@pytest.mark.parametrize("n_hosts", [1, 2, 3, 4])
@pytest.mark.parametrize("epoch", [0, 1, 5])
def test_shard_assignment_is_a_partition(mode, n_hosts, epoch):
    n_files = 11
    shards = [pipe.host_shard_assignment(n_files, h, n_hosts, seed=3,
                                         epoch=epoch, mode=mode)
              for h in range(n_hosts)]
    flat = [i for s in shards for i in s]
    assert sorted(flat) == list(range(n_files))  # disjoint + complete
    # near-even: no host owns more than ceil(n/N)
    assert max(len(s) for s in shards) <= -(-n_files // n_hosts)


def test_shard_assignment_epoch0_pinned_to_legacy_round_robin():
    """Epoch 0 must be bit-identical across all modes AND to the legacy
    `i % num_hosts` scheme — cache/out-of-core entries keyed before the
    rotating plane stay hot."""
    for n_hosts in (2, 4):
        legacy = [[i for i in range(10) if i % n_hosts == h]
                  for h in range(n_hosts)]
        for mode in ("static", "auto", "rotate"):
            got = [pipe.host_shard_assignment(10, h, n_hosts, seed=9,
                                              epoch=0, mode=mode)
                   for h in range(n_hosts)]
            assert got == legacy, (mode, n_hosts)


def test_shard_rotation_deterministic_and_epoch0_zero():
    assert pipe.shard_rotation(7, 0, 4) == 0
    assert pipe.shard_rotation(7, 3, 4) == pipe.shard_rotation(7, 3, 4)
    assert pipe.shard_rotation(7, 3, 1) == 0
    # across epochs the rotation visits more than one offset
    offsets = {pipe.shard_rotation(7, e, 4) for e in range(1, 20)}
    assert len(offsets) > 1
    assert all(0 <= r < 4 for r in offsets)


def test_shard_assignment_survives_width_change_on_resume():
    """Elastic reshape: the assignment is a pure function of the CURRENT
    width — after 4 hosts shrink to 3 mid-job, the survivors re-derive a
    complete disjoint partition for the new width at the next epoch
    boundary, and a later rejoin back to 4 reproduces the original
    4-wide assignment exactly."""
    n_files, seed = 13, 5
    four_a = [pipe.host_shard_assignment(n_files, h, 4, seed=seed, epoch=2,
                                         mode="rotate") for h in range(4)]
    three = [pipe.host_shard_assignment(n_files, h, 3, seed=seed, epoch=3,
                                        mode="rotate") for h in range(3)]
    assert sorted(i for s in three for i in s) == list(range(n_files))
    four_b = [pipe.host_shard_assignment(n_files, h, 4, seed=seed, epoch=2,
                                         mode="rotate") for h in range(4)]
    assert four_a == four_b  # rejoining host re-derives the same slices


def test_shard_digest_pure_and_sensitive():
    d = pipe.shard_assignment_digest
    # every host computes the same digest independently — no allgather
    assert d(8, 4, seed=1, epoch=2, mode="rotate") == \
        d(8, 4, seed=1, epoch=2, mode="rotate")
    # static mode: the ASSIGNMENT is epoch-invariant even though the
    # digest pins the epoch the gang thinks it is in (an off-by-one-epoch
    # host must split the digest even when its file slices happen to match)
    assert pipe.host_shard_assignment(8, 1, 4, seed=1, epoch=0,
                                      mode="static") == \
        pipe.host_shard_assignment(8, 1, 4, seed=1, epoch=7, mode="static")
    base = d(8, 4, seed=1, epoch=0, mode="static")
    assert d(8, 4, seed=1, epoch=7, mode="static") != base   # epoch desync
    assert d(9, 4, seed=1, epoch=0, mode="static") != base   # file listing
    assert d(8, 2, seed=1, epoch=0, mode="static") != base   # gang width
    # rotate mode: some epoch > 0 rotates away from the epoch-0 digest
    rot0 = d(8, 4, seed=1, epoch=0, mode="rotate")
    assert any(d(8, 4, seed=1, epoch=e, mode="rotate") != rot0
               for e in range(1, 10))


def test_host_file_shard_preserves_global_indices(tmp_path):
    schema = synthetic.make_schema(num_features=4)
    synthetic.write_files(synthetic.make_rows(64, schema, seed=0),
                          str(tmp_path), num_files=6)
    data = DataConfig(paths=(str(tmp_path),), host_shard="rotate",
                      shuffle_seed=3)
    seen: dict[int, str] = {}
    for h in range(3):
        for idx, path in pipe.host_file_shard(data, h, 3, epoch=2):
            assert idx not in seen  # disjoint
            seen[idx] = path
    assert sorted(seen) == list(range(6))
    # global index i names the i-th file of the global listing on EVERY
    # host — row ids (file_idx << 40) + row never depend on the reader
    from shifu_tpu.data import reader
    listing = reader.list_data_files(str(tmp_path))
    assert [seen[i] for i in range(6)] == listing
    assert pipe.count_source_files(data) == 6


def test_data_config_host_shard_validation():
    DataConfig(host_shard="rotate").validate()
    with pytest.raises(ConfigError):
        DataConfig(host_shard="roundrobin").validate()


def test_xmlconfig_pod_keys():
    from shifu_tpu.config import JobConfig
    from shifu_tpu.utils import xmlconfig
    out = xmlconfig.apply_to_job(JobConfig(), {
        "shifu.data.host-shard": "Rotate",
        # a key the schema no longer (or never) maps passes through: an
        # XML file written for an older release must not start failing
        "shifu.train.retired-key": "0.75",
    })
    assert out.data.host_shard == "rotate"


# ------------------------------------------------- per-host ingest balance


def test_four_host_ingest_reads_quarter_of_source_bytes(tmp_path,
                                                        monkeypatch):
    """THE sharded-ingest acceptance pin: with 4 simulated hosts each
    host's cold `ingest_source_bytes_total` is <= (total / 4) x 1.15,
    and the gang together reads the total exactly once."""
    monkeypatch.delenv("SHIFU_TPU_DATA_CACHE", raising=False)
    from shifu_tpu import obs
    from shifu_tpu.data import cache as cache_mod

    schema = synthetic.make_schema(num_features=6)
    paths = synthetic.write_files(
        synthetic.make_rows(2048, schema, seed=4), str(tmp_path),
        num_files=8)
    total = cache_mod.source_bytes(paths)
    assert total > 0
    data = DataConfig(paths=(str(tmp_path),), valid_ratio=0.1)
    ctr = obs.default_registry().counter("ingest_source_bytes_total")
    per_host = []
    for h in range(4):
        before = ctr.total()
        pipe.load_datasets(schema, data, h, 4)
        per_host.append(int(ctr.total() - before))
    assert sum(per_host) == total
    even = total / 4
    for h, b in enumerate(per_host):
        assert b <= even * 1.15, (h, per_host, even)


# -------------------------------------------- global order identity pins


def test_interleaved_epoch_order_matches_emulated_hosts():
    """Loss/AUC-identity contract, order half: the global batch order is
    the rank-order interleave of every host's slices of the SAME
    permutation — one process emulating 2 shards reproduces it
    bit-for-bit."""
    lbs, min_rows = 4, 16
    h0 = np.arange(0, min_rows, dtype=np.int64) * 10       # host-local ids
    h1 = np.arange(0, min_rows, dtype=np.int64) * 10 + 1
    order = pipe.interleaved_epoch_order([h0, h1], lbs, shuffle=True,
                                         seed=3, epoch=2)
    perm = pipe.epoch_permutation(min_rows, shuffle=True, seed=3, epoch=2)
    steps = min_rows // lbs
    manual = []
    for b in range(steps):
        take = perm[b * lbs:(b + 1) * lbs]
        manual.extend(h0[take])        # rank 0's local batch first
        manual.extend(h1[take])        # then rank 1's — rank order
    assert np.array_equal(order, np.asarray(manual))
    # deterministic re-derivation (kill+resume re-runs the epoch)
    again = pipe.interleaved_epoch_order([h0, h1], lbs, shuffle=True,
                                         seed=3, epoch=2)
    assert np.array_equal(order, again)
    # imbalanced shards: rows past min_rows are dropped, like the train
    # loop's min-host-rows agreement
    h1_long = np.concatenate([h1, [999]])
    assert np.array_equal(order, pipe.interleaved_epoch_order(
        [h0, h1_long], lbs, shuffle=True, seed=3, epoch=2))


def test_sharded_training_loss_identical_to_single_host():
    """Loss/AUC-identity contract, training half: driving the SAME train
    step with global batches assembled (a) from the single-host global
    order and (b) by concatenating two emulated hosts' local batches in
    rank order yields bit-identical loss trajectories and parameters."""
    import jax

    from shifu_tpu.config import (DataConfig as DC, JobConfig, ModelSpec,
                                  OptimizerConfig, TrainConfig)
    from shifu_tpu.data import reader
    from shifu_tpu.train import init_state, make_train_step

    schema = synthetic.make_schema(num_features=6)
    rows = synthetic.make_rows(64, schema, seed=8, noise=0.25)
    feats = reader.project_columns(rows, schema)
    n, lbs = len(rows) // 2, 8
    # shard rows across 2 emulated hosts by the even/odd row id split
    host_ids = [np.arange(0, 2 * n, 2), np.arange(1, 2 * n, 2)]
    order = pipe.interleaved_epoch_order(host_ids, lbs, shuffle=True,
                                         seed=1, epoch=0)
    steps = len(order) // (2 * lbs)
    assert steps >= 3

    job = JobConfig(
        schema=schema, data=DC(batch_size=2 * lbs),
        model=ModelSpec(model_type="mlp", hidden_nodes=(8,),
                        activations=("relu",), compute_dtype="float32"),
        train=TrainConfig(epochs=1, loss="weighted_mse",
                          optimizer=OptimizerConfig(name="adadelta",
                                                    learning_rate=1.0)),
    ).validate()
    step = make_train_step(job, mesh=None, donate=False)

    def batch_at(ids):
        return {k: v[ids] for k, v in feats.items()}

    def run(order_fn):
        state = init_state(job, schema.feature_count, None)
        losses = []
        for b in range(steps):
            _, bl = order_fn(b)
            state, metrics = step(state, batch_at(bl))
            losses.append(float(metrics["loss"]))
        return losses, jax.device_get(state.params)

    perm = pipe.epoch_permutation(n, shuffle=True, seed=1, epoch=0)

    # (a) single host replaying the global interleaved order
    global_view = order.reshape(steps, 2 * lbs)
    la, pa = run(lambda b: (b, global_view[b]))
    # (b) two emulated shards, each taking ITS slice of the same
    # permutation, concatenated in rank order — a real 2-host global batch
    def sharded(b):
        take = perm[b * lbs:(b + 1) * lbs]
        return b, np.concatenate([host_ids[0][take], host_ids[1][take]])
    lb_, pb_ = run(sharded)

    assert la == lb_
    for ka, kb in zip(jax.tree_util.tree_leaves(pa),
                      jax.tree_util.tree_leaves(pb_)):
        assert np.array_equal(np.asarray(ka), np.asarray(kb))


@pytest.mark.parametrize("tier", ["staged", "batch"])
def test_order_digest_agreement_across_hosts_and_resume(tier):
    """Each host derives the SAME per-epoch order digest from the agreed
    (min_rows, batch, seed) inputs — on the staged and per-batch tiers,
    including a fresh re-derivation after kill+resume."""
    digests = {pipe.epoch_order_digest(tier, 96, 8, shuffle=True, seed=2,
                                       epoch=3) for _ in range(4)}
    assert len(digests) == 1
    # resume re-runs the epoch: same pure inputs, same digest
    assert pipe.epoch_order_digest(tier, 96, 8, shuffle=True, seed=2,
                                   epoch=3) == digests.pop()
    # and the digest actually pins the order: any input shift splits it
    assert pipe.epoch_order_digest(tier, 96, 8, shuffle=True, seed=2,
                                   epoch=4) != \
        pipe.epoch_order_digest(tier, 96, 8, shuffle=True, seed=2, epoch=3)


# --------------------------------------------------- parse_hosts edges


def test_parse_hosts_duplicates_preserved():
    from shifu_tpu.launcher import pod
    spec = pod.parse_hosts("tpu-vm-0,tpu-vm-0,tpu-vm-1")
    # ranks are positional: the same machine may host two ranks (2 chips,
    # 2 processes) — the parser must not dedupe
    assert spec.hosts == ("tpu-vm-0", "tpu-vm-0", "tpu-vm-1")


def test_parse_hosts_local_one():
    from shifu_tpu.launcher import pod
    spec = pod.parse_hosts("local:1")
    assert spec.hosts == ("local",)
    assert spec.transport == "local"


def test_parse_hosts_coordinator_port_collisions(monkeypatch):
    from shifu_tpu.launcher import pod
    # explicit flag beats the env (the collision escape hatch)
    monkeypatch.setenv("SHIFU_TPU_COORDINATOR_PORT", "9100")
    assert pod.parse_hosts("h0,h1").coordinator_port == 9100
    assert pod.parse_hosts("h0,h1", 9000).coordinator_port == 9000
    # garbage env port: ssh path raises with the var named...
    monkeypatch.setenv("SHIFU_TPU_COORDINATOR_PORT", "bogus")
    with pytest.raises(ValueError, match="SHIFU_TPU_COORDINATOR_PORT"):
        pod.parse_hosts("h0,h1")
    # ...but local transport picks its own free port and must survive it
    assert pod.parse_hosts("local:2").transport == "local"
    monkeypatch.delenv("SHIFU_TPU_COORDINATOR_PORT")
    with pytest.raises(ValueError, match="out of range"):
        pod.parse_hosts("h0,h1", 70000)


# ------------------------------------------------------- pod-verify audit


def _close(epoch, rank, hosts, od="od0", sd="sd0", b=100, s=1.0):
    return {"kind": "pod_epoch_close", "epoch": epoch, "rank": rank,
            "hosts": hosts, "order_digest": od, "shard_digest": sd,
            "ingest_bytes": b, "ingest_s": s}


def test_pod_verify_events_green_and_each_failure_mode():
    from shifu_tpu.launcher.pod import pod_verify_events

    ok = [_close(e, r, 2, od=f"od{e}", sd=f"sd{e}", b=100 + r)
          for e in range(2) for r in range(2)]
    rep = pod_verify_events(ok)
    assert rep["verdict"] == "PASS", rep
    assert all(c["ok"] for c in rep["checks"])

    # a hole in coverage: no complete cohort ever closed epoch 1
    rep = pod_verify_events([r for r in ok
                             if not (r["epoch"] == 1 and r["rank"] == 1)])
    assert rep["verdict"] == "FAIL"
    assert [c for c in rep["checks"]
            if c["check"] == "epoch_coverage" and not c["ok"]]

    # order digest split inside a complete cohort
    bad = [dict(r) for r in ok]
    bad[3]["order_digest"] = "DESYNC"
    rep = pod_verify_events(bad)
    assert [c for c in rep["checks"]
            if c["check"] == "order_digest_agreement" and not c["ok"]]

    # lopsided ingest: one host reading 10x its share
    fat = [_close(0, 0, 2, b=1000), _close(0, 1, 2, b=100)]
    rep = pod_verify_events(fat, balance_limit=1.5)
    assert [c for c in rep["checks"]
            if c["check"] == "ingest_balance" and not c["ok"]]

    # recovery: an injected kill with no cohort at/after it fails...
    inj = {"kind": "chaos_inject", "site": "data.host_shard", "rank": 1,
           "action": "exit", "epoch": 5}
    rep = pod_verify_events(ok + [inj])
    assert [c for c in rep["checks"]
            if c["check"] == "recovery" and not c["ok"]]
    # ...and a complete (re-run) cohort at the injection epoch clears it
    rep = pod_verify_events(
        ok + [dict(inj, epoch=1)])
    assert rep["verdict"] == "PASS", rep
    assert [c for c in rep["checks"]
            if c["check"] == "recovery" and c["ok"]]


def test_pod_verify_accepts_elastic_reshape_cohorts():
    """A narrower cohort (post-reshape width 1) closing later epochs is a
    COMPLETE cohort — survivors rebalanced, not a coverage hole."""
    from shifu_tpu.launcher.pod import pod_verify_events
    events = ([_close(0, r, 2) for r in range(2)]
              + [_close(1, 1, 2)]              # partial: rank 0 died here
              + [_close(1, 0, 1, od="od1b", sd="sd1b")])  # width-1 re-run
    rep = pod_verify_events(events)
    assert rep["verdict"] == "PASS", rep


# ------------------------------------------------ tier-1 elastic drill


def test_elastic_drill_kill_rebalance_rejoin(tmp_path, monkeypatch):
    """THE elastic recovery acceptance pin: a local:2 data-dryrun gang,
    chaos kills rank 1 mid-epoch at the shard-derivation seam
    (`data.host_shard`), the supervisor restarts the gang, resume picks
    the min cross-rank progress (the dead rank's missed epochs re-run),
    and `pod-verify` holds green — coverage, digest agreement, ingest
    balance, recovery."""
    from shifu_tpu.launcher import pod
    from shifu_tpu.launcher.pod import pod_verify_events
    from shifu_tpu.obs import timeline as timeline_mod

    data_dir = tmp_path / "data"
    data_dir.mkdir()
    schema = synthetic.make_schema(num_features=6)
    synthetic.write_files(synthetic.make_rows(64, schema, seed=3),
                          str(data_dir), num_files=4)
    out = str(tmp_path / "out")
    plan = {"seed": 7, "faults": [{
        "site": "data.host_shard", "rank": 1, "at_epoch": 1,
        "action": "exit", "exit_code": 23, "scope": "job",
        "max_times": 1}]}
    monkeypatch.setenv("SHIFU_TPU_CHAOS_PLAN", json.dumps(plan))
    monkeypatch.setenv("SHIFU_TPU_CHAOS_STATE",
                       str(tmp_path / "chaos_state.json"))
    monkeypatch.delenv("SHIFU_TPU_METRICS_DIR", raising=False)

    rc = pod.supervise_pod(
        pod.parse_hosts("local:2"),
        child_args=["data-dryrun", "--data", str(data_dir), "--out", out,
                    "--features", "6", "--epochs", "2", "--seed", "5"],
        out_dir=out, max_restarts=2)
    assert rc == 0

    merged = timeline_mod.load_merged(out, tail_bytes=None)
    assert merged is not None
    rep = pod_verify_events(merged["events"])
    assert rep["verdict"] == "PASS", rep
    assert rep["counts"]["injections"] == 1       # the kill actually fired
    assert rep["counts"]["ranks"] == 2            # the dead rank rejoined
    by_check = {c["check"]: c for c in rep["checks"]}
    assert by_check["recovery"]["ok"]
    assert by_check["order_digest_agreement"]["ok"]
    assert by_check["shard_digest_agreement"]["ok"]

    # CLI face over the same journals
    from shifu_tpu.launcher import cli
    assert cli.main(["pod-verify", out]) == 0
    assert cli.main(["pod-verify", str(tmp_path / "nothing_here")]) == 1


# ------------------------------------------------------ journal rollups


def test_pod_ingest_rollup_folds_reports_and_skew_rows():
    from shifu_tpu.obs import aggregate
    events = [
        {"kind": "ingest_report", "src": 0, "files": 4, "parse_s": 1.0,
         "inflate_s": 0.5, "source_bytes": 400},
        {"kind": "ingest_report", "src": 1, "host": "worker-1", "files": 4,
         "parse_s": 1.2, "inflate_s": 0.4, "source_bytes": 420},
        {"kind": "host_skew", "epoch": 1, "hosts": [
            {"rank": 0, "ingest_bytes": 500, "ingest_s": 2.0},
            {"rank": 1, "host": "worker-1", "ingest_bytes": 510,
             "ingest_s": 2.1}]},
    ]
    roll = aggregate.pod_ingest_rollup(events)
    assert roll["pod"]["hosts"] == 2
    # host_skew rows are cumulative counters: newest total WINS over the
    # summed ingest_report deltas
    assert roll["hosts"]["rank0"]["ingest_bytes"] == 500
    assert roll["hosts"]["worker-1"]["ingest_bytes"] == 510
    assert roll["pod"]["ingest_bytes_total"] == 1010
    assert roll["pod"]["imbalance"] == pytest.approx(510 / 500, abs=1e-3)


def test_digest_agreement_tristate():
    from shifu_tpu.obs.aggregate import digest_agreement
    assert digest_agreement([{"order_digest": "a"},
                             {"order_digest": "a"}], "order_digest") is True
    assert digest_agreement([{"order_digest": "a"},
                             {"order_digest": "b"}], "order_digest") is False
    # partial presence = a host missing the field while others carry it
    assert digest_agreement([{"order_digest": "a"}, {}],
                            "order_digest") is False
    assert digest_agreement([{}, {}], "order_digest") is None


def test_skew_line_renders_ingest_segment():
    from shifu_tpu.obs.aggregate import skew_line
    line = skew_line(2, [
        {"host": "h0", "rank": 0, "input_s": 1.0, "epoch_s": 3.0,
         "valid_s": 0.1, "ingest_bytes": 2_500_000, "ingest_s": 1.5},
        {"host": "h1", "rank": 1, "input_s": 2.0, "epoch_s": 3.0,
         "valid_s": 0.1}])
    assert "ingest 2.5MB/1.5s" in line
    # rows without the pod fields render the legacy segment unchanged
    assert line.index("h1[1]") < line.index("h0[0]")  # slowest first


def test_profile_render_pod_block(tmp_path):
    from shifu_tpu.obs import render
    events = [
        {"kind": "ingest_report", "files": 4, "rows": 100, "mb": 1.0,
         "parse_s": 1.0, "inflate_s": 0.2, "tier": "parse",
         "source_bytes": 12345, "host_index": 2},
        {"kind": "host_skew", "epoch": 1, "order_digest_agree": True,
         "shard_digest_agree": True, "hosts": [
             {"host": "h0", "rank": 0, "input_s": 1.0,
              "ingest_bytes": 600, "ingest_s": 1.0,
              "order_digest": "x", "shard_digest": "y"},
             {"host": "h1", "rank": 1, "input_s": 2.0,
              "ingest_bytes": 620, "ingest_s": 1.1,
              "order_digest": "x", "shard_digest": "y"}]},
        {"kind": "dcn_placement", "epoch": 1, "tier": "staged",
         "hosts": 2, "slices": 1, "local_devices": 4,
         "input_local_bytes": 1000, "input_dcn_bytes": 0,
         "input_dcn_saved_bytes": 1000, "local_sgd_window": 2,
         "sync_rounds": 5, "sync_rounds_skipped": 5,
         "dcn_sync_saved_bytes": 4000},
    ]
    jdir = tmp_path / "telemetry"
    jdir.mkdir()
    with open(jdir / "journal.jsonl", "w") as f:
        for ev in events:
            f.write(json.dumps({"ts": 1.0, **ev}) + "\n")
    summary = render.profile_summary(str(tmp_path))
    assert summary is not None
    podb = summary["pod"]
    assert len(podb["hosts"]) == 2   # the last epoch's per-host rows
    assert podb["order_digest_agree"] is True
    assert podb["dcn"]["input_dcn_saved_bytes_total"] == 1000
    assert podb["dcn"]["dcn_sync_saved_bytes_total"] == 4000
    text = render.render_profile_text(summary)
    assert "pod data plane:" in text
    assert "dcn placement:" in text
    assert "[host 2:" in text          # per-host ingest source segment
    assert "ingest 620" in text or "620" in text


def test_dcn_topology_single_process():
    import jax

    from shifu_tpu.parallel import mesh as mesh_lib
    topo = mesh_lib.dcn_topology()
    assert topo["processes"] == 1
    assert topo["process_index"] == 0
    assert topo["local_devices"] == topo["devices"] == len(jax.devices())
    assert topo["slices"] >= 1


# ------------------------------------- gloo-gated real multihost train


@pytest.mark.slow
def test_real_two_host_train_journals_pod_plane(tmp_path):
    """Real local:2 multihost training (gloo collectives): the chief's
    `host_skew` rows must carry each host's ingest extras and agreeing
    order/shard digests, and a `dcn_placement` event must record the
    input bytes the per-host construction kept off the DCN."""
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "fixtures", "pod_data_worker.py")
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    data_dir = tmp_path / "data"
    data_dir.mkdir()
    schema = synthetic.make_schema(num_features=6)
    synthetic.write_files(synthetic.make_rows(512, schema, seed=7),
                          str(data_dir), num_files=4)
    out = tmp_path / "out"

    base_env = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                             "SHIFU_TPU_METRICS_DIR",
                             "SHIFU_TPU_DATA_CACHE")}
    base_env.update({
        "SHIFU_TPU_COORDINATOR": f"127.0.0.1:{port}",
        "SHIFU_TPU_NUM_PROCESSES": "2",
        "POD_DATA_DIR": str(data_dir),
        "POD_OUT_DIR": str(out),
    })
    procs = []
    for pid in (0, 1):
        env = {**base_env, "SHIFU_TPU_PROCESS_ID": str(pid)}
        procs.append(subprocess.Popen(
            [sys.executable, "-u", worker], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        try:
            o, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("pod data worker timed out")
        outs.append((p.returncode, o))
    if any("RESULT-SKIP" in o for _, o in outs):
        pytest.skip("jax build lacks gloo CPU collectives")
    for rc, o in outs:
        assert rc == 0, f"worker failed (rc={rc}):\n{o[-3000:]}"

    from shifu_tpu.launcher.pod import pod_verify_events
    from shifu_tpu.obs import timeline as timeline_mod
    merged = timeline_mod.load_merged(str(out), tail_bytes=None)
    assert merged is not None
    skews = [e for e in merged["events"] if e.get("kind") == "host_skew"]
    assert skews, "chief journaled no host_skew"
    for ev in skews:
        assert ev.get("order_digest_agree") is True, ev
        assert ev.get("shard_digest_agree") is True, ev
        rows = ev["hosts"]
        assert len(rows) == 2
        for r in rows:
            assert r.get("ingest_bytes") is not None
            assert r.get("ingest_s") is not None
    dcn = [e for e in merged["events"] if e.get("kind") == "dcn_placement"]
    assert dcn, "no dcn_placement event"
    for ev in dcn:
        assert ev["hosts"] == 2
        assert ev["input_dcn_bytes"] == 0
        assert ev["input_dcn_saved_bytes"] == ev["input_local_bytes"]
    rep = pod_verify_events(merged["events"])
    assert rep["verdict"] == "PASS", rep
