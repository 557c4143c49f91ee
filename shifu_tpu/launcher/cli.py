"""Job launcher CLI — the one-command successor of the reference's
TensorflowClient (yarn/client/TensorflowClient.java:290 main, args
`-globalconfig <xml> ...` at :147-154).

Usage:
    python -m shifu_tpu.launcher.cli train \
        --modelconfig ModelConfig.json --columnconfig ColumnConfig.json \
        --data /path/to/normalized [...] \
        [--globalconfig global.xml] [--output out_dir] [--devices N]
        [--supervise]

Where the reference client uploaded resources to HDFS, submitted a YARN AM,
and polled it every 10s (TensorflowClient.java:333-426,625-658), this runs
the single SPMD program in-process (or under the supervisor for
checkpoint-restart fault tolerance), streams per-epoch lines to the console
board, enforces the job timeout, exports the scoring artifact, and returns a
Shifu-style exit status (0 success / 1 failure / 3 timeout).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional, Sequence

EXIT_OK = 0
EXIT_FAIL = 1
EXIT_TIMEOUT = 3


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="shifu-tpu")
    sub = p.add_subparsers(dest="command", required=True)

    t = sub.add_parser("train", help="train a model from Shifu configs")
    t.add_argument("--modelconfig", required=True, help="Shifu ModelConfig.json")
    t.add_argument("--columnconfig", required=True, help="Shifu ColumnConfig.json")
    t.add_argument("--data", nargs="*", default=[], help="training data files/dirs")
    t.add_argument("--globalconfig", default=None,
                   help="Hadoop-style XML (-globalconfig parity)")
    t.add_argument("--output", default=None, help="job output dir")
    t.add_argument("--devices", type=int, default=0,
                   help="limit device count (0 = all)")
    t.add_argument("--epochs", type=int, default=0, help="override epochs")
    t.add_argument("--batch-size", type=int, default=0, help="override batch size")
    t.add_argument("--cache-dir", default=None,
                   help="parse-once columnar data cache dir (also via "
                        "SHIFU_TPU_DATA_CACHE)")
    t.add_argument("--timeout", type=int, default=0,
                   help="job timeout seconds (0 = none)")
    t.add_argument("--supervise", action="store_true",
                   help="run under the restart supervisor")
    t.add_argument("--num-processes", type=int, default=0,
                   help="spawn N coordinated processes on this machine "
                        "(multi-host simulation / multi-process training); "
                        "on a real pod run one process per host with the "
                        "SHIFU_TPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID env")
    t.add_argument("--hosts", default=None,
                   help="pod-scale launch: dispatch one process per host "
                        "with whole-gang supervised restart. Forms: "
                        "'h1,h2,...' (ssh, list in TPU worker order — the "
                        "TPU_WORKER_HOSTNAMES value), '@hostfile', or "
                        "'local:N' (simulated pod on this machine). Env "
                        "spelling: SHIFU_TPU_HOSTS")
    t.add_argument("--max-restarts", type=int, default=-1,
                   help="supervisor restart budget (-1 = from config)")
    t.add_argument("--coordinator-port", type=int, default=0,
                   help="ssh-pod rendezvous port on hosts[0] (default 8476; "
                        "env spelling: SHIFU_TPU_COORDINATOR_PORT)")
    t.add_argument("--detach", action="store_true",
                   help="submit and return immediately: the job runs under "
                        "a detached session-leader dispatcher that survives "
                        "this client (status/attach/kill drive it from the "
                        "job dir afterwards)")
    t.add_argument("--chaos-plan", default=None,
                   help="declarative fault-injection plan: inline JSON or a "
                        "path to a JSON file (schema in shifu_tpu/chaos/"
                        "plan.py, site catalog in docs/ROBUSTNESS.md); "
                        "exported to children as SHIFU_TPU_CHAOS_PLAN so a "
                        "supervised/pod job injects the same plan on every "
                        "attempt")
    t.add_argument("--provision", action="store_true",
                   help="acquire a TPU slice first (shifu.provision.* keys "
                        "/ --provision-* flags), dispatch the pod onto its "
                        "workers, release the slice when the job ends")
    t.add_argument("--keep-slice", action="store_true",
                   help="with --provision: leave the slice running after "
                        "the job (inspect/reuse; release with "
                        "`shifu-tpu provision delete`)")
    _add_provision_flags(t)

    pv = sub.add_parser(
        "provision", help="TPU slice lifecycle (queued resources): the "
                          "compute-acquisition step the reference client "
                          "got from YARN submitApplication")
    pv.add_argument("action", choices=["create", "status", "hosts", "delete"])
    pv.add_argument("--globalconfig", default=None,
                    help="Hadoop-style XML carrying shifu.provision.* keys")
    pv.add_argument("--wait", action="store_true",
                    help="with create: block until the slice is ACTIVE")
    _add_provision_flags(pv)

    st = sub.add_parser("status", help="report a detached job's state "
                                       "(RUNNING/FINISHED/FAILED + last "
                                       "progress line + telemetry summary) "
                                       "from its job dir")
    st.add_argument("job_dir")
    mt = sub.add_parser(
        "metrics", help="render a job's telemetry — run journal + "
                        "Prometheus scrape file — for a running or "
                        "finished job (see docs/OBSERVABILITY.md)")
    mt.add_argument("job_dir",
                    help="job dir, telemetry dir, or journal.jsonl path "
                         "(local or gs:// hdfs:// URI)")
    mt.add_argument("--json", action="store_true",
                    help="machine-readable summary dict instead of text")
    mt.add_argument("--follow", action="store_true",
                    help="stream journal events as JSONL until ^C "
                         "(tail_board for the structured stream)")
    pf = sub.add_parser(
        "profile", help="render a job's goodput ledger: per-epoch wall-time "
                        "buckets (compile/input/step/checkpoint/restore/"
                        "eval/other), top compiled functions by XLA "
                        "cost, and the recovery tax "
                        "(docs/OBSERVABILITY.md 'Goodput ledger')")
    pf.add_argument("job_dir",
                    help="job dir, telemetry dir, or journal.jsonl path "
                         "(local or gs:// hdfs:// URI)")
    pf.add_argument("--json", action="store_true",
                    help="machine-readable profile dict instead of text")
    tr = sub.add_parser(
        "trace", help="render a job's device flight recorder: per-kernel "
                      "device-time rollups from the captured trace "
                      "windows (compute- vs HBM-bound), the anomaly log "
                      "with its per-chunk ring, and HBM watermarks "
                      "(docs/OBSERVABILITY.md 'Device flight recorder')")
    tr.add_argument("job_dir",
                    help="job dir, telemetry dir, or journal.jsonl path "
                         "(local or gs:// hdfs:// URI)")
    tr.add_argument("--json", action="store_true",
                    help="machine-readable trace dict instead of text")
    tp = sub.add_parser(
        "top", help="live streaming view of a job or serving daemon — "
                    "rate/p50/p99, queue depth, lifecycle stage breakdown "
                    "(queue/coalesce/dispatch/device), active SLO alerts; "
                    "pass several dirs for a multi-daemon fleet rollup "
                    "(journal/scrape tail only — no jax import; "
                    "docs/OBSERVABILITY.md 'Serving SLO engine')")
    tp.add_argument("job_dirs", nargs="+",
                    help="job dir(s), telemetry dir(s), or journal.jsonl "
                         "path(s) — N dirs render the fleet rollup "
                         "(obs/aggregate.serving_rollup)")
    tp.add_argument("--once", action="store_true",
                    help="render one frame and exit (scripting / CI)")
    tp.add_argument("--json", action="store_true",
                    help="machine-readable frame(s): one JSON dict per "
                         "frame (JSONL when streaming)")
    tp.add_argument("--interval", type=float, default=2.0,
                    help="refresh seconds for the streaming view "
                         "(default 2)")
    tp.add_argument("--stale-after", type=float, default=None,
                    help="mark a daemon DOWN when its freshest signal "
                         "(fleet lease or journal tail) is older than "
                         "this many seconds (default: each member's own "
                         "lease ttl when present, else never)")
    ch = sub.add_parser(
        "cache", help="inspect the columnar data cache: list entries "
                      "(tier/version/bytes/source) and prune superseded, "
                      "orphaned, or legacy-format ones (data/cache.py, "
                      "docs/DATA.md 'Columnar cache')")
    ch.add_argument("cache_dir",
                    help="cache directory (DataConfig.cache_dir / "
                         "SHIFU_TPU_DATA_CACHE)")
    ch.add_argument("--prune", action="store_true",
                    help="remove tmp leftovers, legacy pre-v2 entries, and "
                         "entries whose source changed or vanished")
    ch.add_argument("--json", action="store_true",
                    help="machine-readable entry list instead of text")
    cv = sub.add_parser(
        "chaos-verify", help="audit a finished chaos drill: replay the "
                             "recorded plan against the run journal and "
                             "report injected-vs-recovered counts "
                             "(docs/ROBUSTNESS.md)")
    cv.add_argument("job_dir", help="job dir (or telemetry dir / journal "
                                    "path) of the finished run")
    cv.add_argument("--plan", default=None,
                    help="chaos plan to check against (inline JSON or "
                         "path); default: <job_dir>/chaos_plan.json")
    cv.add_argument("--json", action="store_true",
                    help="machine-readable report dict instead of text")
    at = sub.add_parser("attach", help="follow a detached job's console "
                                       "board until it ends (TailThread "
                                       "parity); exits with the job's code")
    at.add_argument("job_dir")
    at.add_argument("--tail", action="store_true",
                    help="start from the board's current end, not the top")
    kl = sub.add_parser("kill", help="terminate a detached job's whole "
                                     "process tree (SIGTERM drain, then "
                                     "SIGKILL)")
    kl.add_argument("job_dir")
    kl.add_argument("--force", action="store_true",
                    help="release a provisioned slice even when the marker "
                         "records a live foreground dispatcher")

    s = sub.add_parser("score", help="score rows with an exported artifact")
    s.add_argument("--model", required=True, help="artifact dir")
    s.add_argument("--input", required=True, help="rows file (pipe-delimited or .parquet)")
    s.add_argument("--output", default="-", help="output file (- = stdout)")
    s.add_argument("--native", action="store_true", help="use the C++ engine")
    s.add_argument("--engine", default="auto",
                   choices=["auto", "native", "numpy", "stablehlo", "jax",
                            "aot"],
                   help="scoring engine tier (auto = best available)")
    s.add_argument("--globalconfig", default=None,
                   help="Hadoop-style XML (shifu.security.* for secured HDFS)")

    sv = sub.add_parser(
        "serve", help="run the persistent scoring daemon on an exported "
                      "artifact: admission queue + adaptive micro-batching "
                      "under a latency budget, multi-model hot-swap, TCP "
                      "wire front-end (docs/SERVING.md)")
    sv.add_argument("model", help="artifact dir (the export output)")
    sv.add_argument("--engine", default=None,
                    choices=["auto", "native", "numpy", "stablehlo", "jax",
                            "aot"],
                    help="scoring engine tier (default: serving.engine / "
                         "auto)")
    sv.add_argument("--port", type=int, default=-1,
                    help="TCP port (0 = ephemeral, printed at startup; "
                         "default: shifu.serving.port / 8571)")
    sv.add_argument("--host", default=None,
                    help="bind host (default: shifu.serving.host / "
                         "127.0.0.1)")
    sv.add_argument("--budget-ms", type=float, default=0,
                    help="micro-batcher latency budget in ms: a lone "
                         "request is dispatched after at most this wait "
                         "(default: shifu.serving.latency-budget-ms / 2)")
    sv.add_argument("--max-batch", type=int, default=0,
                    help="largest coalesced batch (default: "
                         "shifu.serving.max-batch / 4096)")
    sv.add_argument("--workers", type=int, default=0,
                    help="scoring worker threads (default: "
                         "shifu.serving.workers / 1)")
    sv.add_argument("--globalconfig", default=None,
                    help="Hadoop-style XML carrying shifu.serving.* keys "
                         "(flags override)")
    sv.add_argument("--chaos-plan", default=None,
                    help="fault-injection plan for serving drills "
                         "(runtime.serve probe site, docs/ROBUSTNESS.md)")
    sv.add_argument("--allow-swap", action="store_true",
                    help="permit wire SWAP frames on a non-loopback bind "
                         "(hot-loads a filesystem path as the model — "
                         "loopback binds allow it by default; see the "
                         "trust model in docs/SERVING.md)")
    sv.add_argument("--heartbeat-s", type=float, default=0.0,
                    help="write a fleet membership lease into the metrics "
                         "dir every N seconds (0 = off; a FleetManager in "
                         "another process reads it — docs/SERVING.md "
                         "'Fleet')")
    sv.add_argument("--heartbeat-misses", type=int, default=3,
                    help="missed beats before the fleet marks this "
                         "daemon DOWN (rides in the lease; default 3)")

    fl = sub.add_parser(
        "fleet", help="run a fault-tolerant serving fleet: N scoring "
                      "daemons + hot standbys under heartbeat "
                      "supervision, a consistent-hash routing front-end "
                      "with hedged retries and overload shedding, "
                      "fleet-wide hot-swap, burn-rate scale loop "
                      "(runtime/fleet.py, docs/SERVING.md 'Fleet')")
    fl.add_argument("model", help="artifact dir (the export output)")
    fl.add_argument("--n-daemons", type=int, default=0,
                    help="fleet members (default: shifu.fleet.n-daemons "
                         "/ 2)")
    fl.add_argument("--standbys", type=int, default=-1,
                    help="hot-standby daemons pre-warmed on the current "
                         "artifact (default: shifu.fleet.standbys / 1)")
    fl.add_argument("--heartbeat-s", type=float, default=0,
                    help="membership lease cadence (default: "
                         "shifu.fleet.heartbeat-every-s / 0.5)")
    fl.add_argument("--heartbeat-misses", type=int, default=0,
                    help="missed beats before failover (default: "
                         "shifu.fleet.heartbeat-misses / 3)")
    fl.add_argument("--port", type=int, default=8571,
                    help="router front-end TCP port (0 = ephemeral, "
                         "printed at startup; default 8571)")
    fl.add_argument("--host", default="127.0.0.1",
                    help="router bind host (default 127.0.0.1)")
    fl.add_argument("--engine", default=None,
                    choices=["auto", "native", "numpy", "stablehlo",
                             "jax", "aot"],
                    help="member scoring engine tier")
    fl.add_argument("--budget-ms", type=float, default=0,
                    help="member micro-batcher latency budget "
                         "(default: shifu.serving.latency-budget-ms / 2)")
    fl.add_argument("--workers", type=int, default=0,
                    help="scoring worker threads per member")
    fl.add_argument("--scale-every-s", type=float, default=-1,
                    help="burn-rate scale-loop cadence, 0 disables "
                         "(default: shifu.fleet.scale-every-s / 0)")
    fl.add_argument("--root-dir", default=None,
                    help="fleet state dir for member leases + telemetry "
                         "(default: <model>/fleet)")
    fl.add_argument("--globalconfig", default=None,
                    help="Hadoop-style XML carrying shifu.fleet.* and "
                         "shifu.serving.* keys (flags override)")
    fl.add_argument("--hosts", default=None,
                    help="cross-host member placement (launcher/pod.py "
                         "grammar: local:N simulated hosts, h1,h2 or "
                         "@file over ssh; default: shifu.fleet.hosts / "
                         "single-host in-proc)")
    fl.add_argument("--member-mode", default=None,
                    choices=["auto", "inproc", "process"],
                    help="member spawn mode (default: "
                         "shifu.fleet.member-mode / auto — in-proc on "
                         "local transport, process children over ssh)")
    fl.add_argument("--chaos-plan", default=None,
                    help="fault-injection plan (fleet.heartbeat / "
                         "fleet.lease / fleet.sync / fleet.route / "
                         "runtime.serve sites, docs/ROBUSTNESS.md)")

    fv = sub.add_parser(
        "fleet-verify", help="audit a fleet run's journal: every "
                             "failover promoted a standby, swap "
                             "generations never regress, every swap "
                             "reached every live member exactly once "
                             "(the chaos-verify analog for the serving "
                             "fleet, docs/SERVING.md)")
    fv.add_argument("job_dir", help="fleet telemetry/job dir (or any "
                                    "dir holding its journal.jsonl)")
    fv.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")

    pdv = sub.add_parser(
        "pod-verify", help="audit a pod training run's per-rank journals: "
                           "every epoch closed by a complete agreeing "
                           "cohort (order + shard digests), per-host "
                           "ingest stayed balanced, and every injected "
                           "host kill was followed by recovery (the "
                           "fleet-verify analog for the training gang, "
                           "docs/DATA.md 'Multi-host data plane')")
    pdv.add_argument("job_dir", help="pod job/telemetry dir (per-rank "
                                     "journals are discovered one level "
                                     "below the root journal)")
    pdv.add_argument("--json", action="store_true",
                     help="machine-readable report on stdout")
    pdv.add_argument("--balance-limit", type=float, default=1.5,
                     help="max per-rank ingest bytes as a multiple of the "
                          "even share (default 1.5)")

    dd = sub.add_parser(
        "data-dryrun", help="pod data-plane dryrun rank: shard-local "
                            "ingest, per-epoch order/shard digests "
                            "journaled per rank, no device training — "
                            "the gang child the elastic recovery drill "
                            "dispatches under `supervise_pod` "
                            "(docs/DATA.md)")
    dd.add_argument("--data", required=True,
                    help="directory (or file) of delimited part files; "
                         "layout [target, f0..fN-1]")
    dd.add_argument("--out", required=True, help="job dir for per-rank "
                                                 "telemetry + progress")
    dd.add_argument("--features", type=int, default=8,
                    help="numeric feature count in the files (default 8)")
    dd.add_argument("--epochs", type=int, default=3)
    dd.add_argument("--batch-size", type=int, default=32)
    dd.add_argument("--delimiter", default="|")
    dd.add_argument("--seed", type=int, default=0,
                    help="shuffle seed pinning permutations and digests")
    dd.add_argument("--host-shard", default="auto",
                    choices=["auto", "static", "rotate"],
                    help="shard-assignment mode "
                         "(data/pipeline.host_shard_assignment)")
    dd.add_argument("--epoch-seconds", type=float, default=0.0,
                    help="simulated per-epoch wall (sleep) so kill/"
                         "liveness windows have something to land in")

    dr = sub.add_parser(
        "drift", help="model-quality / data-drift panel for a serving "
                      "daemon: per-feature PSI vs the frozen baseline "
                      "profile, score-distribution divergence, live AUC "
                      "decay from labeled feedback, and firing drift "
                      "alerts (journal tail only — no jax import; "
                      "docs/OBSERVABILITY.md 'Drift observatory')")
    dr.add_argument("job_dir",
                    help="serving job dir, telemetry dir, or "
                         "journal.jsonl path (train dirs render the "
                         "journaled baseline-profile summary)")
    dr.add_argument("--json", action="store_true",
                    help="machine-readable drift dict instead of text")
    dr.add_argument("--model", default=None,
                    help="restrict to one model_id (default: all)")
    dr.add_argument("--feature", default=None,
                    help="restrict the PSI table to one named feature")

    tl = sub.add_parser(
        "timeline", help="skew-corrected causal fleet timeline: merge "
                         "every member's journal into one ordered "
                         "event stream, stitch incidents (failover / "
                         "SLO / degraded-swap episodes) and show "
                         "sampled request traces end to end "
                         "(docs/OBSERVABILITY.md)")
    tl.add_argument("job_dir", help="fleet telemetry/job dir (member "
                                    "journals are discovered one "
                                    "level below)")
    tl.add_argument("--json", action="store_true",
                    help="machine-readable summary on stdout")
    tl.add_argument("--trace-id", default=None,
                    help="show one trace: its router hop spans and "
                         "per-member stage decompositions")
    tl.add_argument("--incident", action="store_true",
                    help="incident records only (root event, causal "
                         "chain, affected traces, recovery)")
    tl.add_argument("--no-skew-correct", action="store_true",
                    help="merge on raw per-host timestamps (skip the "
                         "heartbeat-derived clock-offset correction)")

    lt = sub.add_parser(
        "loadtest", help="open-loop (Poisson-arrival) load harness for "
                         "the scoring plane: reports scores/s and "
                         "p50/p99 latency (runtime/loadtest.py, "
                         "docs/SERVING.md)")
    lt.add_argument("--model", default=None,
                    help="artifact dir — in-process mode: spin up a "
                         "daemon and drive it directly")
    lt.add_argument("--connect", default=None,
                    help="host:port of a running `shifu-tpu serve` "
                         "daemon — socket mode")
    lt.add_argument("--rate", type=float, default=50_000,
                    help="offered request rate per second (Poisson "
                         "arrivals; default 50000)")
    lt.add_argument("--duration", type=float, default=5.0,
                    help="seconds of offered load (default 5)")
    lt.add_argument("--engine", default="auto",
                    choices=["auto", "native", "numpy", "stablehlo", "jax",
                            "aot"],
                    help="engine tier for --model mode")
    lt.add_argument("--senders", type=int, default=2,
                    help="open-loop sender threads (the Poisson stream is "
                         "striped across them; default 2)")
    lt.add_argument("--budget-ms", type=float, default=0,
                    help="daemon latency budget for --model mode "
                         "(default: serving default)")
    lt.add_argument("--capacity", action="store_true",
                    help="ramp the offered rate to find the highest one "
                         "meeting the p99 target instead of a single run")
    lt.add_argument("--p99-target-ms", type=float, default=10.0,
                    help="p99 target for --capacity (default 10ms)")
    lt.add_argument("--trace-sample", type=int, default=0,
                    help="trace 1-in-N requests and report the trace "
                         "ids of the slowest sampled ones (p99 "
                         "exemplars; 0 = off, default)")
    lt.add_argument("--trace-exemplars", type=int, default=5,
                    help="how many slowest-trace exemplars to report "
                         "(default 5)")
    lt.add_argument("--drift-after", type=float, default=0.0,
                    help="drift drill: after this many seconds, draw "
                         "requests from a pool whose --drift-features "
                         "columns are shifted by --drift-shift "
                         "(0 = off, default; docs/OBSERVABILITY.md "
                         "'Drift observatory')")
    lt.add_argument("--drift-shift", type=float, default=2.0,
                    help="feature shift applied after --drift-after, in "
                         "raw feature units (default 2.0 — ~2 sigma on "
                         "the synthetic standard-normal pool)")
    lt.add_argument("--drift-features", default=None,
                    help="comma-separated feature indices to shift "
                         "(default: 0,1)")
    lt.add_argument("--feedback", action="store_true",
                    help="ship synthetic labeled feedback after the run "
                         "(calibrated labels pre-drift, coin-flips "
                         "post-drift) so the daemon's live AUC decays")
    lt.add_argument("--json", action="store_true",
                    help="machine-readable report instead of text")

    x = sub.add_parser(
        "export", help="re-export the scoring artifact from a checkpoint "
                       "(no retraining; crash-after-train recovery)")
    x.add_argument("--modelconfig", required=True, help="Shifu ModelConfig.json")
    x.add_argument("--columnconfig", required=True, help="Shifu ColumnConfig.json")
    x.add_argument("--checkpoint-dir", required=True,
                   help="orbax checkpoint dir (the job's tmp_model)")
    x.add_argument("--output", required=True, help="artifact output dir")
    x.add_argument("--globalconfig", default=None,
                   help="Hadoop-style XML (same layering as train)")
    x.add_argument("--aot-pack", action="store_true",
                   help="also compile + serialize the serving bucket-"
                        "ladder executables into aot/ (export/aot.py; "
                        "same opt-in as the shifu.serving.aot-pack key) "
                        "— fleet members then cold-start without XLA "
                        "compiles")

    e = sub.add_parser(
        "eval", help="score labeled rows and report AUC/error (the Shifu "
                     "eval step against this backend's artifacts)")
    e.add_argument("--model", required=True, help="artifact dir")
    e.add_argument("--columnconfig", required=True,
                   help="Shifu ColumnConfig.json (locates target/weight cols)")
    e.add_argument("--data", nargs="+", required=True,
                   help="labeled normalized data files/dirs")
    e.add_argument("--modelconfig", default=None,
                   help="optional ModelConfig.json (target/weight col names)")
    e.add_argument("--scores-output", default=None,
                   help="also write per-row scores to this file")
    e.add_argument("--native", action="store_true", help="use the C++ engine")
    e.add_argument("--engine", default="auto",
                   choices=["auto", "native", "numpy", "stablehlo", "jax",
                            "aot"],
                   help="scoring engine tier (auto = best available)")
    e.add_argument("--globalconfig", default=None,
                   help="Hadoop-style XML (shifu.security.* for secured HDFS)")
    return p


def _add_provision_flags(p) -> None:
    p.add_argument("--provision-name", default="",
                   help="queued-resource / node id (shifu.provision.name)")
    p.add_argument("--accelerator-type", default="",
                   help="e.g. v5litepod-16 (shifu.provision.accelerator-type)")
    p.add_argument("--zone", default="",
                   help="e.g. us-west4-a (shifu.provision.zone)")
    p.add_argument("--project", default="",
                   help="GCP project (shifu.provision.project; default = "
                        "gcloud's configured project)")
    p.add_argument("--runtime-version", default="",
                   help="TPU VM runtime (shifu.provision.runtime-version)")
    p.add_argument("--spot", action="store_true",
                   help="request spot/preemptible capacity "
                        "(shifu.provision.spot)")


def _provision_spec(args):
    """ProvisionSpec from --globalconfig shifu.provision.* keys with CLI
    flags as the top override layer."""
    from ..utils import xmlconfig
    from .provision import spec_from_xml

    conf: dict = {}
    if getattr(args, "globalconfig", None):
        conf = xmlconfig.parse_configuration_xml(args.globalconfig)
    return spec_from_xml(
        conf,
        name=getattr(args, "provision_name", ""),
        accelerator_type=getattr(args, "accelerator_type", ""),
        zone=getattr(args, "zone", ""),
        project=getattr(args, "project", ""),
        runtime_version=getattr(args, "runtime_version", ""),
        spot=getattr(args, "spot", False),
    )


def run_provision(args) -> int:
    from . import provision as prov

    try:
        spec = _provision_spec(args)
        if args.action == "create":
            prov.create(spec)
            if args.wait:
                prov.await_ready(spec)
            return EXIT_OK
        if args.action == "status":
            spec.validate()
            print(prov.state(spec))
            return EXIT_OK
        if args.action == "hosts":
            spec.validate()
            print(",".join(prov.worker_hosts(spec)))
            return EXIT_OK
        if args.action == "delete":
            spec.validate()
            prov.delete(spec)
            return EXIT_OK
    except prov.ProvisionError as e:
        print(f"provision: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    return EXIT_FAIL


def _kerberos_from_xml(globalconfig) -> int:
    """Acquire a Kerberos ticket for score/eval when --globalconfig carries
    shifu.security.kerberos.* keys (same fail-fast as run_train); returns an
    exit code (EXIT_OK to proceed)."""
    if not globalconfig:
        return EXIT_OK
    from ..utils import xmlconfig
    from .security import KerberosError, ensure_kerberos_ticket

    conf = xmlconfig.parse_configuration_xml(globalconfig)
    try:
        ensure_kerberos_ticket(conf.get(xmlconfig.KEY_KERBEROS_PRINCIPAL, ""),
                               conf.get(xmlconfig.KEY_KERBEROS_KEYTAB, ""))
    except KerberosError as e:
        print(f"kerberos auth failed: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    return EXIT_OK


def _assemble_job(args, write_files: bool = True) -> "JobConfig":
    import dataclasses

    from ..config import job_config_from_shifu
    from ..config.schema import CheckpointConfig
    from ..data import fsio
    from ..utils import xmlconfig

    job = job_config_from_shifu(args.modelconfig, args.columnconfig,
                                data_paths=tuple(args.data))

    merged_xml: dict[str, str] = {}
    if args.globalconfig:
        merged_xml = xmlconfig.parse_configuration_xml(args.globalconfig)
        job = xmlconfig.apply_to_job(job, merged_xml)

    out_dir = _resolve_out_dir(args)
    remote_out = fsio.is_remote(out_dir)
    if not remote_out:
        os.makedirs(out_dir, exist_ok=True)

    # overrides, highest precedence (the reference's programmatic layer)
    train = job.train
    if args.epochs:
        train = dataclasses.replace(train, epochs=args.epochs)
    data = job.data
    if args.batch_size:
        data = dataclasses.replace(data, batch_size=args.batch_size)
    if getattr(args, "cache_dir", None):
        data = dataclasses.replace(data, cache_dir=args.cache_dir)
    runtime = job.runtime
    if args.timeout:
        runtime = dataclasses.replace(runtime, timeout_seconds=args.timeout)
    if not runtime.checkpoint.directory:
        runtime = dataclasses.replace(
            runtime, checkpoint=dataclasses.replace(
                runtime.checkpoint,
                directory=fsio.join(out_dir, "tmp_model")))
    if not runtime.final_model_path:
        runtime = dataclasses.replace(
            runtime, final_model_path=fsio.join(out_dir, "final_model"))
    job = job.replace(train=train, data=data, runtime=runtime)

    if write_files:  # chief-only under multi-process (shared job dir)
        # persist the raw Shifu inputs beside the derived configs, like the
        # reference client's per-app upload of ModelConfig/ColumnConfig
        # (TensorflowClient.java:356-382) — the job dir alone reproduces the
        # run.  A remote (gs:// hdfs://) job dir writes through fsio, the
        # same contract the reference had with its per-app HDFS dir.
        for src in (args.modelconfig, args.columnconfig):
            dst = fsio.join(out_dir, os.path.basename(src))
            if remote_out:
                with open(src, "rb") as f:
                    fsio.write_bytes(dst, f.read())
            else:
                import shutil
                # realpath: a symlinked cwd can alias src and dst
                if os.path.realpath(src) != os.path.realpath(dst):
                    shutil.copyfile(src, dst)

        # persist the merged view (global-final.xml parity + typed JSON)
        final_conf = {**merged_xml,
                      "shifu.application.epochs": str(job.train.epochs),
                      "shifu.application.final-model-path":
                          job.runtime.final_model_path,
                      "shifu.application.tmp-model-path":
                          job.runtime.checkpoint.directory}
        if remote_out:
            fsio.write_bytes(fsio.join(out_dir, "global-final.xml"),
                             xmlconfig.configuration_xml_bytes(final_conf))
            fsio.write_bytes(fsio.join(out_dir, "job-config.json"),
                             job.to_json().encode())
        else:
            xmlconfig.write_configuration_xml(
                final_conf, os.path.join(out_dir, "global-final.xml"))
            with open(os.path.join(out_dir, "job-config.json"), "w") as f:
                f.write(job.to_json())
    return job, out_dir


def _resolve_out_dir(args) -> str:
    """The job output dir, resolved once (children/attempts must share it)."""
    return args.output or os.path.join(
        os.getcwd(), f"shifu_tpu_job_{time.strftime('%Y%m%d_%H%M%S')}")


def _child_train_args(args, out_dir: str,
                      num_processes: int = 0) -> list[str]:
    """Rebuild a `train` child argv from parsed args, with --output pinned
    (shared checkpoints/board) and supervisor/multi-process flags stripped
    unless re-requested via num_processes."""
    child = ["train",
             "--modelconfig", args.modelconfig,
             "--columnconfig", args.columnconfig,
             "--output", out_dir]
    if args.data:
        child += ["--data", *args.data]
    if args.globalconfig:
        child += ["--globalconfig", args.globalconfig]
    if num_processes > 1:
        child += ["--num-processes", str(num_processes)]
    for flag, val in (("--devices", args.devices), ("--epochs", args.epochs),
                      ("--batch-size", args.batch_size),
                      ("--timeout", args.timeout),
                      ("--cache-dir", getattr(args, "cache_dir", None))):
        if val:
            child += [flag, str(val)]
    return child


def _spawn_processes(args, out_dir: str) -> int:
    """Local multi-process mode (`--num-processes N`): a simulated pod on
    this machine — the single-machine spelling of `--hosts local:N`,
    delegating to the pod launcher for the spawn/stream/teardown mechanics
    (one gang attempt; restarts come from the outer `--supervise` wrapper,
    which re-enters here with a fresh gang)."""
    from . import pod as pod_lib

    if args.devices:
        # a device *prefix* of the global list would strand non-chief
        # processes outside the mesh; device counts are per-process here
        print("--devices cannot combine with --num-processes "
              "(set JAX_NUM_CPU_DEVICES per process instead)",
              file=sys.stderr, flush=True)
        return EXIT_FAIL

    os.makedirs(out_dir, exist_ok=True)
    spec = pod_lib.PodSpec(hosts=("local",) * args.num_processes,
                           transport="local")
    try:
        rc, _failed = pod_lib.launch_gang(
            spec, _child_train_args(args, out_dir), out_dir, attempt=1)
    except pod_lib.ChipOwnershipError as e:
        print(f"--num-processes: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    return rc


def _activate_chaos(args) -> int:
    """Export `--chaos-plan` into the environment (children inherit it on
    every restart), validate it NOW (a typo'd plan must fail the launch,
    not silently never inject), pin the job-scoped trigger state file into
    the job dir, and persist the resolved plan beside the job so
    `chaos-verify` can replay it.  Returns nonzero on a bad plan."""
    from .. import chaos

    plan_arg = getattr(args, "chaos_plan", None)
    try:
        if plan_arg:
            # export the resolved plan CONTENT, never a path: ssh-dispatched
            # pod ranks inherit the env on other machines where a local
            # plan file does not exist (and the detach daemon may run from
            # another cwd) — inline JSON works everywhere
            base = chaos.load_plan(plan_arg.strip())
            os.environ[chaos.ENV_CHAOS_PLAN] = base.to_json(indent=None)
        plan = chaos.reload_from_env()
    except chaos.ChaosPlanError as e:
        print(f"chaos plan: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    if plan is None or not plan.faults:
        return EXIT_OK
    if chaos.ENV_CHAOS_STATE not in os.environ:
        out_dir = _resolve_out_dir(args)
        args.output = out_dir  # pin: a re-resolve could timestamp anew
        from ..data import fsio
        if not fsio.is_remote(out_dir):
            os.makedirs(out_dir, exist_ok=True)
            os.environ[chaos.ENV_CHAOS_STATE] = os.path.join(
                out_dir, "chaos_state.json")
            try:  # the audit trail chaos-verify replays
                with open(os.path.join(out_dir, "chaos_plan.json"),
                          "w") as f:
                    f.write(plan.to_json())
            except OSError:
                pass
        else:
            try:  # remote job dir: the audit trail still persists via fsio
                fsio.write_bytes(fsio.join(out_dir, "chaos_plan.json"),
                                 plan.to_json().encode())
            except Exception:
                pass
            if any(f.scope == "job" for f in plan.faults):
                # no local state file to pin -> job-scoped counters degrade
                # to per-process and would re-fire each restart; say so
                # LOUDLY instead of silently changing the drill's semantics
                print("chaos: job dir is remote and SHIFU_TPU_CHAOS_STATE "
                      "is unset — scope=\"job\" triggers degrade to "
                      "per-process counters (set SHIFU_TPU_CHAOS_STATE to "
                      "a local path to keep job-wide counting)",
                      file=sys.stderr, flush=True)
    return EXIT_OK


def run_train(args) -> int:
    # Order matters: the supervisor parent must NOT join the distributed
    # rendezvous (its child re-registers the same process id), and a
    # supervised multi-process job restarts as a whole gang — supervisor
    # wraps the spawner, spawner wraps the worker processes.

    # chaos plane first: the plan env must be exported before ANY child
    # (detach daemon, supervisor attempt, pod rank) is spawned, and a
    # malformed plan must fail here, at submit time
    rc_chaos = _activate_chaos(args)
    if rc_chaos != EXIT_OK:
        return rc_chaos

    # --detach: re-launch this dispatcher as a session-leader daemon and
    # return (YARN parity: the job outlives the submitting client,
    # TensorflowClient.java:625-658; status/attach/kill drive it after)
    from . import detach as detach_lib
    if getattr(args, "detach", False) \
            and detach_lib.ENV_DETACHED not in os.environ:
        out_dir = _resolve_out_dir(args)
        args.output = out_dir
        child = _child_train_args(
            args, out_dir, num_processes=getattr(args, "num_processes", 0))
        # preserve the orchestration flags the slim child argv strips
        if getattr(args, "hosts", None):
            child += ["--hosts", args.hosts]
        if getattr(args, "provision", False):
            child += ["--provision"]
            for flag, attr in (("--provision-name", "provision_name"),
                               ("--accelerator-type", "accelerator_type"),
                               ("--zone", "zone"), ("--project", "project"),
                               ("--runtime-version", "runtime_version")):
                if getattr(args, attr, ""):
                    child += [flag, getattr(args, attr)]
            if getattr(args, "spot", False):
                child += ["--spot"]
            if getattr(args, "keep_slice", False):
                child += ["--keep-slice"]
        elif getattr(args, "supervise", False) or not getattr(args, "hosts", None):
            child += ["--supervise"]  # a detached job should self-heal
        if getattr(args, "max_restarts", -1) >= 0:
            child += ["--max-restarts", str(args.max_restarts)]
        if getattr(args, "coordinator_port", 0):
            child += ["--coordinator-port", str(args.coordinator_port)]
        return detach_lib.submit(child, out_dir)

    # pod-scale launch (successor of the YARN submit/monitor path): the
    # dispatcher routes here only in the PARENT — dispatched children carry
    # the SHIFU_TPU_PROCESS_ID env and run the plain train path below.
    # Gang supervision (restart budget + liveness) is built into the pod
    # path, so --supervise is implied.
    from ..parallel.distributed import ENV_PROCESS_ID
    from . import pod as pod_lib
    pod_hosts = getattr(args, "hosts", None) or pod_lib.detect_hosts_env()

    # --provision: acquire a slice, dispatch the pod onto its workers,
    # release on every exit path (successor of createApplication ->
    # submitApplication -> monitorApplication, TensorflowClient.java:339-426)
    if getattr(args, "provision", False) and ENV_PROCESS_ID not in os.environ:
        from . import provision as prov
        if pod_hosts:
            print("--provision and --hosts are exclusive (provisioning "
                  "derives the hosts from the new slice)",
                  file=sys.stderr, flush=True)
            return EXIT_FAIL
        try:
            spec = _provision_spec(args)
            spec.validate()
        except prov.ProvisionError as e:
            print(f"provision: {e}", file=sys.stderr, flush=True)
            return EXIT_FAIL

        def _dispatch(hosts: list) -> int:
            args.hosts = ",".join(hosts)
            args.provision = False  # re-entry takes the pod branch below
            return run_train(args)

        # a scheduler SIGTERM mid-lifecycle would terminate Python WITHOUT
        # running finally blocks (default disposition) — the release in
        # provision_and_run's finally must still run, so SIGTERM raises
        # SystemExit for the duration (the marker covers SIGKILL; this
        # covers the catchable case without waiting for a manual `kill`)
        import signal as signal_lib

        def _term_to_exit(signum, frame):
            # first SIGTERM starts the unwind; LATER ones are ignored until
            # the finally restores the disposition — schedulers often repeat
            # SIGTERM on a cadence, and a second signal landing inside the
            # release's own gcloud call would abort the delete and leak the
            # slice the unwind exists to release
            signal_lib.signal(signal_lib.SIGTERM, signal_lib.SIG_IGN)
            raise SystemExit(128 + signum)

        old_term, installed = None, False
        try:
            old_term = signal_lib.signal(signal_lib.SIGTERM, _term_to_exit)
            installed = True  # old_term may be None (C-installed handler)
        except ValueError:
            pass  # non-main thread: no handler; the marker still covers it
        try:
            # marker in the job dir: an UNCLEAN dispatcher death between
            # create and release must leave a trail `kill <job_dir>` (or
            # an operator) can release from — see provision.write_marker
            args.output = _resolve_out_dir(args)
            return prov.provision_and_run(
                spec, _dispatch, keep=getattr(args, "keep_slice", False),
                marker_dir=args.output)
        except prov.ProvisionError as e:
            print(f"provision: {e}", file=sys.stderr, flush=True)
            return EXIT_FAIL
        finally:
            if installed:
                signal_lib.signal(signal_lib.SIGTERM,
                                  old_term if old_term is not None
                                  else signal_lib.SIG_DFL)

    if pod_hosts and ENV_PROCESS_ID not in os.environ:
        try:
            spec = pod_lib.parse_hosts(
                pod_hosts, getattr(args, "coordinator_port", 0))
        except (ValueError, OSError) as e:
            print(f"--hosts: {e}", file=sys.stderr, flush=True)
            return EXIT_FAIL
        if getattr(args, "num_processes", 0) > 1:
            print("--hosts and --num-processes are alternative spellings of "
                  "a process gang; use one", file=sys.stderr, flush=True)
            return EXIT_FAIL
        from ..data import fsio as fsio_mod
        out_dir = _resolve_out_dir(args)
        args.output = out_dir  # pin: a second resolve could timestamp anew,
        if not fsio_mod.is_remote(out_dir):  # desyncing the checkpoint probe
            os.makedirs(out_dir, exist_ok=True)
        sup_job = _assemble_job(args, write_files=False)[0]
        max_restarts = (args.max_restarts if args.max_restarts >= 0
                        else sup_job.runtime.max_restarts)
        try:
            return pod_lib.supervise_pod(
                spec, _child_train_args(args, out_dir), out_dir,
                max_restarts=max_restarts,
                liveness_seconds=sup_job.runtime.liveness_seconds,
                checkpoint_dir=sup_job.runtime.checkpoint.directory,
                timeout_seconds=sup_job.runtime.timeout_seconds,
                min_hosts=sup_job.runtime.min_hosts)
        except pod_lib.ChipOwnershipError as e:
            print(f"--hosts: {e}", file=sys.stderr, flush=True)
            return EXIT_FAIL

    if args.supervise:
        from ..data import fsio as fsio_mod
        from .supervisor import supervise
        out_dir = _resolve_out_dir(args)
        args.output = out_dir  # pin: a second resolve could timestamp anew,
        if not fsio_mod.is_remote(out_dir):  # desyncing the checkpoint probe
            os.makedirs(out_dir, exist_ok=True)
        sup_job = _assemble_job(args, write_files=False)[0]
        max_restarts = (args.max_restarts if args.max_restarts >= 0
                        else sup_job.runtime.max_restarts)
        child_args = _child_train_args(
            args, out_dir, num_processes=getattr(args, "num_processes", 0))
        return supervise(child_args, max_restarts=max_restarts,
                         board_path=fsio_mod.join(out_dir, "console.board"),
                         liveness_seconds=sup_job.runtime.liveness_seconds,
                         checkpoint_dir=sup_job.runtime.checkpoint.directory,
                         timeout_seconds=sup_job.runtime.timeout_seconds)

    if getattr(args, "num_processes", 0) > 1:
        return _spawn_processes(args, _resolve_out_dir(args))

    # chaos site "launcher.start": process startup, BEFORE the rendezvous —
    # a fault here models a host that never joins (the dead rank's peers
    # are torn down by the gang dispatcher; a permanently-down rank drives
    # the pod supervisor's elastic reshape).  The legacy
    # SHIFU_TPU_FAULT_HOST_DOWN env hook synthesizes exactly this fault
    # (chaos/plan.py plan_from_legacy_env).
    from .. import chaos as chaos_lib
    try:
        chaos_lib.maybe_fail("launcher.start")
    except chaos_lib.ChaosError as e:
        print(f"chaos: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL

    # multi-host rendezvous (no-op without the env contract / pod runtime);
    # must run before any jax device use so every process joins the global
    # mesh — the successor of the ZooKeeper ip:port registration dance
    # (TensorflowSession.java:551-594)
    from ..parallel import distributed
    distributed.initialize()
    chief = distributed.is_chief()

    job, out_dir = _assemble_job(args, write_files=chief)

    # secured HDFS: acquire the Kerberos ticket before any data access
    # (successor of the reference client's delegation-token fetch,
    # TensorflowClient.java:481-502); no-op unless a principal is configured
    from .security import KerberosError, ensure_kerberos_ticket
    try:
        # supervisor restarts re-enter run_train in fresh child processes,
        # re-running kinit; healthy long runs renew periodically from the
        # epoch callback below
        ensure_kerberos_ticket(job.runtime.kerberos_principal,
                               job.runtime.kerberos_keytab)
    except KerberosError as e:
        print(f"kerberos auth failed: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL

    import jax

    if jax.process_count() > 1 and args.devices:
        print("--devices is not supported under multi-host (device counts "
              "are per-process)", file=sys.stderr, flush=True)
        return EXIT_FAIL

    from ..parallel import data_parallel_mesh
    from ..train import train
    from .console import ConsoleBoard

    from .. import obs
    from ..data import fsio as fsio_lib
    t_run = time.monotonic()
    if chief:
        # telemetry sinks: SHIFU_TPU_METRICS_DIR wins, else the job dir —
        # `shifu-tpu metrics <job_dir>` then finds journal + scrape file
        # under <job_dir>/telemetry without any env setup
        metrics_dir = obs.resolve_metrics_dir() \
            or fsio_lib.join(out_dir, "telemetry")
        try:
            obs.configure(metrics_dir)
        except Exception:
            pass  # telemetry must never block the job
    obs.counter("launcher_runs_total", "train runs started").inc()
    if chief:
        board = ConsoleBoard(fsio_lib.join(out_dir, "console.board"))
    else:  # non-chief processes train silently (reference: only the AM's
        class board:  # aggregated view reached the console board)
            def __call__(self, _s): pass
            def close(self): pass
        board = board()
    n_devices = len(jax.devices())
    if args.devices:
        n_devices = min(n_devices, args.devices)
    mesh_cfg = job.runtime.mesh
    need = mesh_cfg.num_devices
    if need > 1:
        # explicit topology from config (shifu.mesh.* — dp size, tp,
        # sequence and/or pipeline parallelism); all-axes-1 means "unset"
        # and defaults to data parallelism over every visible device
        from ..parallel import make_mesh
        if need > n_devices:
            board(f"mesh {mesh_cfg} needs {need} devices, have {n_devices}")
            board.close()
            return EXIT_FAIL
        mesh = make_mesh(mesh_cfg, jax.devices()[:need])
        devices_in_use = need
    else:
        mesh = data_parallel_mesh(n_devices) if n_devices > 1 else None
        devices_in_use = n_devices
    if job.model.attention_impl in ("ring", "ulysses") and (
            mesh is None or mesh.shape.get("seq", 1) <= 1):
        board(f"warning: attention_impl={job.model.attention_impl!r} needs a "
              "mesh with a seq axis > 1 (runtime.mesh.seq); falling back to "
              "local attention")
    if job.model.attention_impl == "flash" and (
            mesh is not None and mesh.shape.get("seq", 1) > 1):
        board("warning: attention_impl='flash' is a per-device kernel and "
              "ignores the mesh seq axis; use 'ring' or 'ulysses' for "
              "sequence parallelism")
    if job.model.pipeline_stages > 1 and (
            mesh is None or mesh.shape.get("pipe", 1) <= 1):
        board(f"warning: pipeline_stages={job.model.pipeline_stages} needs a "
              "mesh with a pipe axis > 1 (shifu.mesh.pipe); running the "
              "stacked trunk on one stage")
    if job.model.pipeline_stages <= 1 and (
            mesh is not None and mesh.shape.get("pipe", 1) > 1):
        board(f"warning: mesh pipe axis = {mesh.shape['pipe']} but the model "
              "is not pipelined (PipelineStages in ModelConfig params); the "
              "pipe group replicates work — fold those devices into "
              "shifu.mesh.data instead")

    board(f"shifu_tpu train: {job.runtime.app_name} "
          f"devices={devices_in_use}/{n_devices} "
          f"mesh={dict(mesh.shape) if mesh is not None else None} "
          f"model={job.model.model_type} epochs={job.train.epochs} "
          f"batch={job.data.batch_size}")
    obs.gauge("launcher_devices_in_use",
              "devices this run trains on").set(devices_in_use)
    obs.event("run_start", command="train", app_name=job.runtime.app_name,
              devices=devices_in_use,
              mesh=dict(mesh.shape) if mesh is not None else None,
              model=job.model.model_type, epochs=job.train.epochs,
              batch_size=job.data.batch_size,
              processes=jax.process_count())

    def _finish(rc: int) -> int:
        # terminal journal record + scrape-file write on EVERY exit path,
        # so `shifu-tpu metrics` reads a complete story for failed and
        # timed-out runs too
        obs.event("run_end", exit=rc,
                  wall_s=round(time.monotonic() - t_run, 2))
        obs.flush()
        return rc

    from .supervisor import JobDeadline
    deadline = JobDeadline(job.runtime.timeout_seconds)

    # ticket renewal for healthy long runs: re-kinit from the per-epoch
    # callback once half a typical 10h ticket lifetime has passed, so a job
    # streaming hdfs:// data never outlives its credentials mid-read
    kinit_renew_s = 4 * 3600
    last_kinit = time.monotonic()

    def check_timeout(_m):
        nonlocal last_kinit
        if deadline.expired():
            board(f"job timeout ({job.runtime.timeout_seconds}s) exceeded — aborting")
            raise TimeoutError("job timeout")
        if (job.runtime.kerberos_principal
                and time.monotonic() - last_kinit > kinit_renew_s):
            ensure_kerberos_ticket(job.runtime.kerberos_principal,
                                   job.runtime.kerberos_keytab)
            last_kinit = time.monotonic()
        _maybe_inject_fault(_m, board)

    try:
        result = train(job, mesh=mesh, console=board, epoch_callback=check_timeout)
    except TimeoutError:
        board.close()
        return _finish(EXIT_TIMEOUT)
    except Exception as e:  # noqa: BLE001 - job boundary
        board(f"training failed: {type(e).__name__}: {e}")
        obs.event("run_error", error=f"{type(e).__name__}: {e}"[:500])
        board.close()
        return _finish(EXIT_FAIL)

    params = result.state.params
    if jax.process_count() > 1 and mesh is not None:
        # collective: EVERY process participates in replicating (all-gather)
        # any model-sharded params so the chief holds full values to export
        from jax.sharding import NamedSharding, PartitionSpec
        replicate = jax.jit(
            lambda t: t, out_shardings=NamedSharding(mesh, PartitionSpec()))
        params = jax.device_get(replicate(params))
    if chief:
        # make_forward_fn inside: meshless rebuild for single-host export
        # (the training loop's frozen reference profile rides along as
        # baseline_profile.json — the drift observatory's anchor)
        aot_pack, aot_buckets = _export_aot_opts(args)
        _export_and_pack(params, job, job.runtime.final_model_path, board,
                         baseline_profile=result.baseline_profile,
                         aot_pack=aot_pack, aot_buckets=aot_buckets)
        _write_metrics_jsonl(result, fsio_lib.join(out_dir, "metrics.jsonl"))
        if result.history:
            last = result.history[-1]
            board(f"final: valid_error={last.valid_error:.6f} "
                  f"valid_auc={last.valid_auc:.4f}")
    if jax.process_count() > 1:
        from ..parallel import distributed as dist
        dist.barrier("export_done")
    board.close()
    return _finish(EXIT_OK)


def _write_metrics_jsonl(result, path: str) -> None:
    """Structured per-epoch metrics next to the human console board — the
    machine-readable successor of the reference's Java-serialized
    TrainingIntermediateResult znodes (core/TrainingIntermediateResult.java:
    97-102; SURVEY.md section 5.5 flagged Java serialization as a quirk)."""
    import dataclasses
    import json
    import math

    def _clean(v):
        # NaN/Inf are not valid JSON; strict JSONL consumers need null
        if isinstance(v, float) and not math.isfinite(v):
            return None
        return v

    lines = []
    for m in result.history:
        rec = {k: _clean(v) for k, v in dataclasses.asdict(m).items()}
        lines.append(json.dumps(rec, allow_nan=False))
    payload = ("\n".join(lines) + "\n") if lines else ""
    try:
        from ..data import fsio
        if fsio.is_remote(path):
            fsio.write_bytes(path, payload.encode())
        else:
            with open(path, "w") as f:
                f.write(payload)
    except Exception:
        pass  # metrics sink is best-effort; the board already has the lines


def _maybe_inject_fault(metrics, board) -> None:
    """Chaos site "train.epoch": the post-epoch boundary (after the epoch's
    conditional checkpoint save) — the successor of the reference's
    commented-out PS-killer (yarn/util/CommonUtils.java:265-274).  The
    legacy SHIFU_TPU_FAULT_EPOCH / _FAULT_EVERY_EPOCH / _FAULT_PROCESS /
    SHIFU_TPU_HANG_EPOCH env hooks still work: chaos/plan.py synthesizes
    equivalent plan faults from them (crash-after-epoch-k, die-after-every-
    epoch-below-n, rank-limited injection, hang-for-liveness-detection)."""
    from .. import chaos

    def echo(msg: str) -> None:
        # print as well: a non-chief rank's board is silent, but its stdout
        # is captured into the per-host log by the pod launcher
        print(msg, flush=True)
        board(msg)

    chaos.maybe_fail("train.epoch", echo=echo, epoch=metrics.epoch)


def _load_scorer(model_dir: str, native: bool, engine: str = "auto"):
    """Pick a scoring engine: `--native` or --engine native = the C++
    op-list engine; numpy / stablehlo / jax select an explicit tier
    (debugging, cross-engine verification); auto = best available
    (export.load_scorer's order).  Raises ValueError with the fix spelled
    out on contradictory flags or a tier the artifact cannot serve.
    The tier ladder itself is runtime/serve.load_engine — one resolver
    for score/eval and the serving daemon's model loads."""
    if native and engine not in ("auto", "native"):
        raise ValueError(
            f"--native contradicts --engine {engine}; drop one of them")
    from ..runtime.serve import load_engine
    return load_engine(model_dir, "native" if native else engine)


def _project_features(rows, model_dir: str, scorer):
    """Select the artifact's feature columns from raw normalized rows.

    The artifact's own `topology.json` selected_indices are the authority
    (the ColumnConfig on disk may have drifted since training — e.g. variable
    selection re-run); full-width inputs pass through, and NaNs impute to 0
    the way training did (data/reader.py project_columns)."""
    import numpy as np

    n_feat = getattr(scorer, "num_features", None) or rows.shape[1]
    if rows.shape[1] != n_feat:
        sel = None
        try:
            with open(os.path.join(model_dir, "topology.json")) as f:
                sel = json.load(f).get("selected_indices")
        except (OSError, ValueError):
            pass
        if sel and rows.shape[1] > max(sel):
            rows = rows[:, sel]
        else:
            rows = rows[:, :n_feat]
    return np.nan_to_num(rows, nan=0.0)


def run_metrics(args) -> int:
    """`shifu-tpu metrics <dir>`: render the run journal + registry scrape
    for a running or finished job — the operator view of the unified
    telemetry layer (obs/), succeeding the reference client's poll of the
    AM's aggregated metrics."""
    from .. import obs
    from ..obs import render as obs_render

    if getattr(args, "follow", False):
        jpath = obs_render.find_journal(args.job_dir)
        if jpath is None:
            print(f"no telemetry journal found under {args.job_dir}",
                  file=sys.stderr, flush=True)
            return EXIT_FAIL
        try:
            for rec in obs.tail_journal(jpath):
                print(json.dumps(rec), flush=True)
        except KeyboardInterrupt:
            pass
        return EXIT_OK
    try:
        summary = obs_render.summarize(args.job_dir)
    except Exception as e:
        print(f"metrics: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    if summary is None:
        print(f"no telemetry journal found under {args.job_dir} (expected "
              f"<job_dir>/telemetry/journal.jsonl — run with "
              f"SHIFU_TPU_METRICS_DIR or a CLI train job)",
              file=sys.stderr, flush=True)
        return EXIT_FAIL
    print(json.dumps(summary) if args.json
          else obs_render.render_text(summary))
    return EXIT_OK


def run_profile(args) -> int:
    """`shifu-tpu profile <dir>`: the goodput / XLA-cost view of a run —
    where the wall time and FLOPs went, epoch by epoch, straight from the
    `goodput` / `xla_compile` journal events (obs/goodput.py,
    obs/introspect.py)."""
    from ..obs import render as obs_render

    try:
        summary = obs_render.profile_summary(args.job_dir)
    except Exception as e:
        print(f"profile: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    if summary is None:
        print(f"no telemetry journal found under {args.job_dir} (expected "
              f"<job_dir>/telemetry/journal.jsonl — run with "
              f"SHIFU_TPU_METRICS_DIR or a CLI train job)",
              file=sys.stderr, flush=True)
        return EXIT_FAIL
    print(json.dumps(summary) if args.json
          else obs_render.render_profile_text(summary))
    return EXIT_OK


def run_trace(args) -> int:
    """`shifu-tpu trace <dir>`: the device flight-recorder view of a run —
    which kernels own the device time (and whether each is compute- or
    HBM-bound), what the anomaly detector caught, and where HBM peaked —
    straight from the `device_profile` / `anomaly` / `hbm_watermark`
    journal events (obs/devprof.py)."""
    from ..obs import render as obs_render

    try:
        summary = obs_render.trace_summary(args.job_dir)
    except Exception as e:
        print(f"trace: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    if summary is None:
        print(f"no telemetry journal found under {args.job_dir} (expected "
              f"<job_dir>/telemetry/journal.jsonl — run with "
              f"SHIFU_TPU_METRICS_DIR or a CLI train job)",
              file=sys.stderr, flush=True)
        return EXIT_FAIL
    print(json.dumps(summary) if args.json
          else obs_render.render_trace_text(summary))
    return EXIT_OK


def run_top(args) -> int:
    """`shifu-tpu top <dir> [...]`: the live operator view of the serving
    and device planes joined — rate / p50 / p99 / queue depth, the
    per-request lifecycle stage breakdown (where a p99 excursion's time
    actually goes), and active SLO burn-rate alerts; a train job dir
    renders epoch progress + goodput instead.  Journal/scrape-file reads
    only — safe to point at a LIVE daemon from any machine that can read
    the dir, and never imports jax."""
    from ..obs import aggregate as obs_aggregate
    from ..obs import render as obs_render

    stale_after = getattr(args, "stale_after", None)

    def frame() -> tuple:
        if len(args.job_dirs) > 1:
            rollup = obs_aggregate.serving_rollup(
                args.job_dirs, stale_after_s=stale_after)
            return rollup, obs_render.render_top_fleet_text(rollup)
        summary = obs_render.top_summary(args.job_dirs[0],
                                         stale_after_s=stale_after)
        if summary is None:
            return None, None
        return summary, obs_render.render_top_text(summary)

    try:
        while True:
            data, text = frame()
            if data is None:
                print(f"no telemetry journal found under "
                      f"{args.job_dirs[0]} (expected <dir>/telemetry/"
                      f"journal.jsonl — a `shifu-tpu serve`/train job "
                      f"writes one)", file=sys.stderr, flush=True)
                return EXIT_FAIL
            if args.json:
                print(json.dumps(data), flush=True)
            else:
                if not args.once:
                    # clear + home: a terminal frame, not a scrolling log
                    print("\x1b[2J\x1b[H", end="")
                print(text, flush=True)
            if args.once:
                return EXIT_OK
            time.sleep(max(args.interval, 0.1))
    except KeyboardInterrupt:
        return EXIT_OK


def run_drift(args) -> int:
    """`shifu-tpu drift <dir>`: the model-quality / data-drift panel —
    per-feature PSI vs the frozen baseline profile, score-distribution
    divergence, and live AUC decay from labeled feedback, straight off
    the journal tail (obs/render.drift_summary).  Never imports jax —
    safe to point at a LIVE daemon from any machine reading the dir."""
    from ..obs import render as obs_render

    try:
        summary = obs_render.drift_summary(
            args.job_dir, model=getattr(args, "model", None),
            feature=getattr(args, "feature", None))
    except Exception as e:
        print(f"drift: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    if summary is None:
        print(f"no telemetry journal found under {args.job_dir} "
              f"(expected <dir>/telemetry/journal.jsonl — a `shifu-tpu "
              f"serve` daemon with a baseline profile writes drift "
              f"reports there)", file=sys.stderr, flush=True)
        return EXIT_FAIL
    print(json.dumps(summary) if args.json
          else obs_render.render_drift_text(summary))
    return EXIT_OK


def run_cache(args) -> int:
    """`shifu-tpu cache <dir>`: the operator view of the columnar cache —
    every artifact classified (raw / projected / consolidated dataset,
    format version, bytes, recorded source, freshness), and `--prune` to
    reclaim the disk held by superseded, orphaned, legacy, or half-written
    entries.  File reads only: no jax import."""
    from ..data import cache as cache_lib

    if not os.path.isdir(args.cache_dir):
        print(f"cache: no such directory: {args.cache_dir}",
              file=sys.stderr, flush=True)
        return EXIT_FAIL
    try:
        entries = cache_lib.scan_cache(args.cache_dir)
    except OSError as e:
        print(f"cache: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    removed = cache_lib.prune_cache(args.cache_dir, entries) \
        if args.prune else []
    kept = [e for e in entries if e not in removed]
    if args.json:
        print(json.dumps({"cache_dir": args.cache_dir, "entries": kept,
                          "pruned": removed,
                          "total_bytes": sum(e["bytes"] for e in kept)}))
        return EXIT_OK
    if not entries:
        print(f"{args.cache_dir}: empty cache")
        return EXIT_OK

    def line(e):
        src = e["source"] or "-"
        ver = e["version"] if e["version"] is not None else "-"
        return (f"  {e['tier']:<9} v{ver:<3} {e['bytes']:>12,} B  "
                f"{e['status']:<8} {e['name']}"
                + (f"  <- {src}" if src != "-" else ""))

    print(f"{args.cache_dir}: {len(kept)} entries, "
          f"{sum(e['bytes'] for e in kept):,} bytes")
    for e in kept:
        print(line(e))
    if args.prune:
        print(f"pruned {len(removed)} entries "
              f"({sum(e['bytes'] for e in removed):,} bytes reclaimed)")
        for e in removed:
            print(f"  removed [{e['status']}] {e['name']}")
    else:
        stale = [e for e in kept
                 if e["status"] in cache_lib.PRUNE_STATUSES]
        if stale:
            print(f"{len(stale)} prunable entries "
                  f"({sum(e['bytes'] for e in stale):,} bytes) — "
                  f"rerun with --prune to reclaim")
    return EXIT_OK


def run_chaos_verify(args) -> int:
    """`shifu-tpu chaos-verify <job_dir>`: audit a finished chaos drill.

    Replays the recorded plan (default: the `chaos_plan.json` the launcher
    persisted beside the job) against the run journal: which sites actually
    injected, how often, and what the recovery machinery did about it
    (restarts, checkpoint fallbacks, preemption-grace saves, resumes).
    Exit 0 = the run completed (a `run_end exit=0` / `supervisor_done` is
    present) AND every planned fault site injected at least once — i.e. the
    drill both FIRED and was SURVIVED; anything else is exit 1."""
    from .. import chaos
    from ..data import fsio
    from ..obs import journal as journal_mod
    from ..obs import render as obs_render

    jpath = obs_render.find_journal(args.job_dir)
    if jpath is None:
        print(f"no telemetry journal found under {args.job_dir}",
              file=sys.stderr, flush=True)
        return EXIT_FAIL
    events = journal_mod.read_journal(jpath)

    plan = None
    plan_src = getattr(args, "plan", None)
    if not plan_src:
        cand = fsio.join(args.job_dir, "chaos_plan.json")
        if os.path.exists(cand) or (fsio.is_remote(cand)
                                    and obs_render._exists(cand)):
            plan_src = cand
    if plan_src:
        try:
            plan = chaos.load_plan(plan_src)
        except chaos.ChaosPlanError as e:
            print(f"chaos plan: {e}", file=sys.stderr, flush=True)
            return EXIT_FAIL

    injected: dict[str, int] = {}
    recovered: dict[str, int] = {}
    run_exits: list[int] = []
    recovery_kinds = ("supervisor_restart", "supervisor_done",
                      "checkpoint_fallback", "checkpoint_fallback_resolved",
                      "train_resume", "preemption_grace",
                      "supervisor_liveness_kill", "chaos_corrupt")
    for rec in events:
        kind = rec.get("kind")
        if kind == "chaos_inject":
            site = str(rec.get("site", "?"))
            injected[site] = injected.get(site, 0) + 1
        elif kind in recovery_kinds:
            recovered[kind] = recovered.get(kind, 0) + 1
        elif kind == "run_end":
            try:
                run_exits.append(int(rec.get("exit")))
            except (TypeError, ValueError):
                pass

    planned_sites = sorted({f.site for f in plan.faults}) if plan else []
    # a glob site ("fsio.*") counts as fired when ANY injected site matches
    import fnmatch as fnmatch_mod
    silent = [s for s in planned_sites
              if not any(i == s or fnmatch_mod.fnmatchcase(i, s)
                         for i in injected)]
    completed = (recovered.get("supervisor_done", 0) > 0
                 or (run_exits and run_exits[-1] == 0))
    report = {
        "journal": jpath,
        "plan": plan_src,
        "planned_sites": planned_sites,
        "injected": dict(sorted(injected.items())),
        "injected_total": sum(injected.values()),
        "silent_sites": silent,
        "recovered": dict(sorted(recovered.items())),
        "final_run_exit": run_exits[-1] if run_exits else None,
        "completed": bool(completed),
        "verdict": ("PASS" if completed and not silent
                    else "INCOMPLETE" if not completed else "SILENT_SITES"),
    }
    if getattr(args, "json", False):
        print(json.dumps(report))
    else:
        print(f"chaos-verify: {report['verdict']} — "
              f"{report['injected_total']} injection(s) across "
              f"{len(injected)} site(s), final exit "
              f"{report['final_run_exit']}")
        if planned_sites:
            print(f"  planned sites: {', '.join(planned_sites)}")
        for site, n in sorted(injected.items()):
            print(f"  injected  {site}: {n}")
        for kind, n in sorted(recovered.items()):
            print(f"  recovered {kind}: {n}")
        if silent:
            print(f"  NEVER FIRED: {', '.join(silent)} (trigger never "
                  "matched — check at_call/at_epoch/rank against the run)")
    return EXIT_OK if report["verdict"] == "PASS" else EXIT_FAIL


def run_fleet_verify(args) -> int:
    """`shifu-tpu fleet-verify <dir>`: audit a fleet run's journal
    against the fleet lifecycle invariants (runtime/fleet.py
    fleet_verify_events — the chaos-verify analog for the serving
    plane).  Exit 0 = every check holds.

    Process-mode members journal into their own tele dirs on their own
    clocks, so the audit runs on the skew-corrected merged timeline
    (obs/timeline.py): raw cross-host timestamps can make a later swap
    generation appear to precede an earlier one and fail the ordering
    checks spuriously."""
    from ..obs import timeline as timeline_mod
    from ..runtime.fleet import fleet_verify_events

    merged = timeline_mod.load_merged(args.job_dir, tail_bytes=None)
    if merged is None:
        print(f"no telemetry journal found under {args.job_dir}",
              file=sys.stderr, flush=True)
        return EXIT_FAIL
    report = fleet_verify_events(merged["events"])
    report["journal"] = merged["journals"][0]
    report["journals"] = merged["journals"]
    report["skew_correct"] = merged["skew_correct"]
    if getattr(args, "json", False):
        print(json.dumps(report))
    else:
        counts = report["counts"]
        print(f"fleet-verify: {report['verdict']} — "
              f"{counts['failovers']} failover(s), "
              f"{counts['swaps']} fleet swap(s), "
              f"{counts['member_swaps']} member application(s), "
              f"{counts['rejoins']} rejoin(s), "
              f"{counts['degraded']} degraded, "
              f"{counts['syncs']} host sync(s)")
        for c in report["checks"]:
            mark = "ok " if c["ok"] else "FAIL"
            print(f"  [{mark}] {c['check']}: {c['detail']}")
    return EXIT_OK if report["verdict"] == "PASS" else EXIT_FAIL


def run_pod_verify(args) -> int:
    """`shifu-tpu pod-verify <dir>`: audit a pod training run's merged
    per-rank journals against the pod data-plane invariants
    (launcher/pod.pod_verify_events — epoch coverage by complete cohorts,
    cross-host order/shard digest agreement, ingest balance, recovery
    after injected kills).  Exit 0 = every check holds."""
    from ..obs import timeline as timeline_mod
    from .pod import pod_verify_events

    merged = timeline_mod.load_merged(args.job_dir, tail_bytes=None)
    if merged is None:
        print(f"no telemetry journal found under {args.job_dir}",
              file=sys.stderr, flush=True)
        return EXIT_FAIL
    report = pod_verify_events(merged["events"],
                               balance_limit=args.balance_limit)
    report["journals"] = merged["journals"]
    if getattr(args, "json", False):
        print(json.dumps(report))
    else:
        counts = report["counts"]
        print(f"pod-verify: {report['verdict']} — "
              f"{counts['epochs']} epoch(s), "
              f"{counts['close_rows']} close row(s) from "
              f"{counts['ranks']} rank(s), "
              f"{counts['injections']} injection(s)")
        for c in report["checks"]:
            mark = "ok " if c["ok"] else "FAIL"
            print(f"  [{mark}] {c['check']}: {c['detail']}")
    return EXIT_OK if report["verdict"] == "PASS" else EXIT_FAIL


def _dryrun_progress_start(prog_dir: str, num_hosts: int) -> int:
    """First epoch this attempt should run: min completed epoch across the
    CURRENT gang's ranks + 1 (a rank file missing → that rank completed
    nothing → start at 0).  The gang-wide min makes a restart re-run any
    epoch a killed rank never closed, so the journal always ends with a
    complete per-epoch cohort — rank-local resume would let the survivors'
    head start leave holes `pod-verify` flags."""
    start = None
    for rank in range(num_hosts):
        p = os.path.join(prog_dir, f"rank-{rank}.json")
        try:
            with open(p) as f:
                done = int(json.load(f).get("epoch", -1))
        except (OSError, ValueError):
            done = -1
        start = done if start is None else min(start, done)
    return (start if start is not None else -1) + 1


def _dryrun_progress_mark(prog_dir: str, rank: int, epoch: int) -> None:
    os.makedirs(prog_dir, exist_ok=True)
    tmp = os.path.join(prog_dir, f".rank-{rank}.tmp")
    with open(tmp, "w") as f:
        json.dump({"epoch": int(epoch)}, f)
    os.replace(tmp, os.path.join(prog_dir, f"rank-{rank}.json"))


def run_data_dryrun(args) -> int:
    """`shifu-tpu data-dryrun`: one pod data-plane rank — shard-local
    ingest of this host's slice, per-epoch order/shard digests, one
    `pod_epoch_close` journal row per epoch — with NO device training and
    NO cross-process collectives, so it runs on any backend (the CPU
    backend cannot run multi-process collectives; the data plane is pure
    host work and needs none).  Rank identity comes from the pod env
    contract (SHIFU_TPU_PROCESS_ID / SHIFU_TPU_NUM_PROCESSES) that
    `supervise_pod` re-derives each attempt, so an elastic reshape
    rebalances the shard assignment automatically.  Every digest is a pure
    function of (seed, epoch, gang width), and the drill dataset's equal
    part files give every rank the same local row count — so the journaled
    cohorts must agree, which is exactly what `pod-verify` audits."""
    from .. import chaos
    from .. import obs
    from ..config.schema import DataConfig
    from ..data import pipeline as pipe
    from ..data import synthetic

    try:
        rank = int(os.environ.get("SHIFU_TPU_PROCESS_ID", "0") or 0)
        nproc = int(os.environ.get("SHIFU_TPU_NUM_PROCESSES", "1") or 1)
    except ValueError:
        rank, nproc = 0, 1
    chaos.reload_from_env()
    out = args.out
    tele = (os.path.join(out, "telemetry") if rank == 0
            else os.path.join(out, "telemetry", f"rank-{rank}"))
    from ..obs import _sinks
    _sinks.configure(tele)
    schema = synthetic.make_schema(num_features=args.features)
    # valid_ratio=0: the drill's agreement contract needs every rank's
    # LOCAL train-row count equal (no allgathered min without
    # collectives), and the hash split would skew counts per shard
    data = DataConfig(paths=(args.data,), delimiter=args.delimiter,
                      batch_size=int(args.batch_size), valid_ratio=0.0,
                      shuffle_seed=int(args.seed),
                      host_shard=args.host_shard)
    data.validate()
    prog_dir = os.path.join(out, "data_progress")
    start = _dryrun_progress_start(prog_dir, nproc)
    obs.event("pod_data_dryrun_start", rank=rank, hosts=nproc,
              epoch_start=start, epochs=int(args.epochs),
              host_shard=args.host_shard)
    n_files = pipe.count_source_files(data)
    reg = obs.default_registry()
    train_rows = None
    for ep in range(start, int(args.epochs)):
        # fires the `data.host_shard` chaos probe with epoch context —
        # the elastic drill's kill lands here, mid-epoch
        mine = pipe.host_file_shard(data, rank, nproc, epoch=ep)
        if train_rows is None:
            train_ds, _valid_ds = pipe.load_datasets(schema, data, rank,
                                                     nproc)
            train_rows = int(train_ds.num_rows)
        if args.epoch_seconds > 0:
            time.sleep(float(args.epoch_seconds))
        order_digest = pipe.epoch_order_digest(
            "batch", train_rows, int(args.batch_size), shuffle=True,
            seed=int(args.seed), epoch=ep)
        shard_digest = pipe.shard_assignment_digest(
            n_files, nproc, seed=int(args.seed), epoch=ep,
            mode=args.host_shard)
        obs.event(
            "pod_epoch_close", epoch=ep, rank=rank, hosts=nproc,
            files=len(mine), rows=train_rows,
            order_digest=order_digest, shard_digest=shard_digest,
            ingest_bytes=int(
                reg.counter("ingest_source_bytes_total").total()),
            ingest_s=round(
                reg.counter("ingest_seconds_total").total(), 6))
        obs.flush()
        _dryrun_progress_mark(prog_dir, rank, ep)
        print(f"data-dryrun rank {rank}/{nproc}: epoch {ep} "
              f"files={len(mine)} rows={train_rows}", flush=True)
    obs.event("pod_data_dryrun_done", rank=rank, hosts=nproc,
              epochs=int(args.epochs))
    obs.flush()
    return EXIT_OK


def run_timeline(args) -> int:
    """`shifu-tpu timeline <dir>`: the skew-corrected causal fleet
    timeline (obs/timeline.py) — merged member journals, incident
    records, sampled request traces.  Journal reads only: never imports
    jax, bounded tails, safe against a live fleet from any machine."""
    from ..obs import timeline as timeline_mod

    summary = timeline_mod.timeline_summary(
        args.job_dir,
        trace_id=getattr(args, "trace_id", None),
        incidents_only=getattr(args, "incident", False),
        skew_correct=not getattr(args, "no_skew_correct", False))
    if summary is None:
        print(f"no telemetry journal found under {args.job_dir}",
              file=sys.stderr, flush=True)
        return EXIT_FAIL
    if getattr(args, "json", False):
        print(json.dumps(summary))
    else:
        print(timeline_mod.render_timeline_text(summary))
    return EXIT_OK


def run_score(args) -> int:
    from .. import obs
    from ..data import reader

    obs.configure_from_env()
    rc = _kerberos_from_xml(args.globalconfig)
    if rc != EXIT_OK:
        return rc
    rows = reader.read_file(args.input)
    try:
        scorer = _load_scorer(args.model, args.native, args.engine)
    except (ValueError, OSError, KeyError, RuntimeError) as e:
        # a tier the artifact cannot serve (missing jaxexport/model_spec)
        # or contradictory flags: report, don't traceback
        print(f"scorer: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    feats = _project_features(rows, args.model, scorer)
    out = sys.stdout if args.output == "-" else open(args.output, "w")
    # chunked scoring + incremental writes: peak memory stays bounded by the
    # chunk, not the input (the reference scored one row per JNI call)
    chunk = 65536
    for lo in range(0, feats.shape[0], chunk):
        for s in scorer.compute_batch(feats[lo:lo + chunk]):
            out.write("|".join(f"{v:.6f}" for v in s) + "\n")
    if out is not sys.stdout:
        out.close()
    obs.event("score_run", rows=int(feats.shape[0]), model=args.model)
    obs.flush()
    return EXIT_OK


def _serving_config(args) -> "ServingConfig":
    """ServingConfig from `--globalconfig` shifu.serving.* keys with CLI
    flags as the top override layer (the same layering train uses)."""
    import dataclasses

    from ..config.schema import ServingConfig
    from ..utils import xmlconfig

    cfg = ServingConfig()
    if getattr(args, "globalconfig", None):
        conf = xmlconfig.parse_configuration_xml(args.globalconfig)
        cfg = xmlconfig.serving_config_from_conf(conf, cfg)
    kw = {}
    if getattr(args, "engine", None):
        kw["engine"] = args.engine
    if getattr(args, "port", -1) >= 0:
        kw["port"] = args.port
    if getattr(args, "host", None):
        kw["host"] = args.host
    if getattr(args, "budget_ms", 0):
        kw["latency_budget_ms"] = args.budget_ms
    if getattr(args, "max_batch", 0):
        kw["max_batch"] = args.max_batch
    if getattr(args, "workers", 0):
        kw["workers"] = args.workers
    if kw:
        cfg = dataclasses.replace(cfg, **kw)
    cfg.validate()
    return cfg


def run_serve(args) -> int:
    """`shifu-tpu serve <artifact>`: the persistent scoring daemon —
    admission queue + adaptive micro-batching under a latency budget,
    hot-swappable model registry, TCP wire front-end (runtime/serve.py,
    docs/SERVING.md).  Telemetry lands like a train job's: the
    SHIFU_TPU_METRICS_DIR env wins, else <artifact>/telemetry — so
    `shifu-tpu metrics <artifact>` reads the serving_report stream."""
    from .. import chaos, obs
    from ..config.schema import ConfigError
    from ..data import fsio

    if getattr(args, "chaos_plan", None):
        try:
            base = chaos.load_plan(args.chaos_plan.strip())
            os.environ[chaos.ENV_CHAOS_PLAN] = base.to_json(indent=None)
            chaos.reload_from_env()
        except chaos.ChaosPlanError as e:
            print(f"chaos plan: {e}", file=sys.stderr, flush=True)
            return EXIT_FAIL
    try:
        config = _serving_config(args)
    except (ConfigError, ValueError) as e:
        print(f"serve: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    metrics_dir = obs.resolve_metrics_dir() \
        or fsio.join(args.model, "telemetry")
    try:
        obs.configure(metrics_dir)
    except Exception:
        pass  # telemetry must never block serving
    from ..runtime.serve import serve_forever
    try:
        rc = serve_forever(args.model, config,
                           echo=lambda s: print(s, flush=True),
                           allow_swap=(True if getattr(args, "allow_swap",
                                                       False) else None),
                           heartbeat_every_s=getattr(args, "heartbeat_s",
                                                     0.0) or 0.0,
                           heartbeat_misses=getattr(args,
                                                    "heartbeat_misses",
                                                    3))
    except (ValueError, OSError, KeyError, RuntimeError) as e:
        print(f"serve: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    obs.flush()
    return rc


def run_fleet(args) -> int:
    """`shifu-tpu fleet <artifact>`: N scoring daemons + hot standbys
    under heartbeat supervision behind a hedging router front-end
    (runtime/fleet.py, runtime/router.py, docs/SERVING.md 'Fleet')."""
    import dataclasses

    from .. import chaos, obs
    from ..config.schema import ConfigError, FleetConfig
    from ..data import fsio
    from ..utils import xmlconfig

    if getattr(args, "chaos_plan", None):
        try:
            base = chaos.load_plan(args.chaos_plan.strip())
            os.environ[chaos.ENV_CHAOS_PLAN] = base.to_json(indent=None)
            chaos.reload_from_env()
        except chaos.ChaosPlanError as e:
            print(f"chaos plan: {e}", file=sys.stderr, flush=True)
            return EXIT_FAIL
    fleet_cfg = FleetConfig()
    if getattr(args, "globalconfig", None):
        conf = xmlconfig.parse_configuration_xml(args.globalconfig)
        fleet_cfg = xmlconfig.fleet_config_from_conf(conf, fleet_cfg)
    kw = {}
    if args.n_daemons > 0:
        kw["n_daemons"] = args.n_daemons
    if args.standbys >= 0:
        kw["standbys"] = args.standbys
    if args.heartbeat_s > 0:
        kw["heartbeat_every_s"] = args.heartbeat_s
    if args.heartbeat_misses > 0:
        kw["heartbeat_misses"] = args.heartbeat_misses
    if args.scale_every_s >= 0:
        kw["scale_every_s"] = args.scale_every_s
    if getattr(args, "hosts", None) is not None:
        kw["hosts"] = args.hosts
    if getattr(args, "member_mode", None) is not None:
        kw["member_mode"] = args.member_mode
    if kw:
        fleet_cfg = dataclasses.replace(fleet_cfg, **kw)
    try:
        fleet_cfg.validate()
        serving = _serving_config(args)
    except (ConfigError, ValueError) as e:
        print(f"fleet: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    metrics_dir = obs.resolve_metrics_dir() \
        or fsio.join(args.model, "telemetry")
    try:
        obs.configure(metrics_dir)
    except Exception:
        pass  # telemetry must never block serving
    root_dir = getattr(args, "root_dir", None) \
        or fsio.join(args.model, "fleet")
    from ..runtime.fleet import fleet_forever
    try:
        rc = fleet_forever(args.model, fleet=fleet_cfg, serving=serving,
                           router_host=args.host, router_port=args.port,
                           root_dir=root_dir,
                           echo=lambda s: print(s, flush=True))
    except (ValueError, OSError, KeyError, RuntimeError) as e:
        print(f"fleet: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    obs.flush()
    return rc


def run_loadtest(args) -> int:
    """`shifu-tpu loadtest`: the open-loop Poisson harness
    (runtime/loadtest.py)."""
    from .. import obs
    from ..config.schema import ServingConfig
    from ..runtime import loadtest as lt

    if bool(args.model) == bool(args.connect):
        print("loadtest: exactly one of --model / --connect",
              file=sys.stderr, flush=True)
        return EXIT_FAIL
    obs.configure_from_env()
    config = None
    if getattr(args, "budget_ms", 0):
        config = ServingConfig(engine=args.engine,
                               latency_budget_ms=args.budget_ms,
                               report_every_s=0.0)
    try:
        if args.capacity:
            if not args.model:
                print("loadtest: --capacity needs --model",
                      file=sys.stderr, flush=True)
                return EXIT_FAIL
            report = lt.find_capacity(args.model, engine=args.engine,
                                      p99_target_ms=args.p99_target_ms,
                                      senders=args.senders, config=config)
        else:
            feats = getattr(args, "drift_features", None)
            if feats:
                feats = [int(v) for v in str(feats).split(",") if v]
            report = lt.run_loadtest(
                args.model, connect=args.connect,
                engine=args.engine, rate=args.rate,
                duration=args.duration, senders=args.senders,
                config=config,
                trace_sample=getattr(args, "trace_sample", 0),
                trace_exemplars=getattr(args, "trace_exemplars", 5),
                drift_after=getattr(args, "drift_after", 0.0),
                drift_shift=getattr(args, "drift_shift", 2.0),
                drift_features=feats,
                feedback=getattr(args, "feedback", False))
    except (ValueError, OSError, KeyError, RuntimeError) as e:
        print(f"loadtest: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    print(json.dumps(report) if args.json else lt.render_report(report))
    obs.flush()
    return EXIT_OK if report.get("completed") \
        or report.get("capacity_scores_per_sec") else EXIT_FAIL


def run_eval(args) -> int:
    """The Shifu `eval` step against this backend: score labeled normalized
    rows, report AUC + weighted error (successor of the reference's eval
    module feeding scores back into Shifu's PerformanceEvaluator via
    TensorflowModel.compute, TensorflowModel.java:52-109) — with the batch
    scoring and in-process metrics the reference's row-at-a-time JNI path
    could not offer."""

    from .. import obs
    from ..config.shifu_compat import load_json, parse_column_config
    from ..data import reader

    obs.configure_from_env()
    rc = _kerberos_from_xml(args.globalconfig)
    if rc != EXIT_OK:
        return rc
    target_name = weight_name = multi_targets = None
    if args.modelconfig:
        dataset = load_json(args.modelconfig).get("dataSet", {}) or {}
        target_name = dataset.get("targetColumnName")
        weight_name = dataset.get("weightColumnName")
        multi_targets = dataset.get("multiTargetColumnNames")
    schema = parse_column_config(load_json(args.columnconfig),
                                 target_column_name=target_name,
                                 weight_column_name=weight_name,
                                 multi_target_names=multi_targets)

    paths: list[str] = []
    for p in args.data:
        # handles local/remote, file-or-directory, with marker-file filtering
        paths.extend(reader.list_data_files(p))
    if not paths:
        print("eval: no data files found", file=sys.stderr)
        return EXIT_FAIL
    try:
        scorer = _load_scorer(args.model, args.native, args.engine)
    except (ValueError, OSError, KeyError, RuntimeError) as e:
        # a tier the artifact cannot serve (missing jaxexport/model_spec)
        # or contradictory flags: report, don't traceback
        print(f"scorer: {e}", file=sys.stderr, flush=True)
        return EXIT_FAIL
    # Stream file by file: metrics accumulate out-of-core (exact weighted
    # error; binned weighted AUC over the [0,1] sigmoid range, error <1e-6)
    # so eval-set size is bounded by disk, not RAM — the reference's eval
    # was row-at-a-time through JNI with aggregation left to the Shifu host.
    from ..ops.metrics import StreamingMetrics

    accs: list = []
    n_heads = 0
    score_sum = 0.0
    pos_count = 0
    scores_out = None  # created lazily so failure paths leave no stray file
    try:
        for p in sorted(paths):
            raw = reader.read_file(p)
            if raw.shape[0] == 0:
                continue
            if args.scores_output and scores_out is None:
                scores_out = open(args.scores_output, "w")
            cols = reader.project_columns(raw, schema)
            scores = scorer.compute_batch(
                _project_features(raw, args.model, scorer))
            labels_m, weights = cols["target"], cols["weight"][:, 0]
            if not accs:
                if scores.shape[1] != labels_m.shape[1]:
                    print(f"eval: artifact has {scores.shape[1]} heads but "
                          f"{labels_m.shape[1]} target columns resolved from "
                          "the configs — reporting the overlap only",
                          file=sys.stderr)
                n_heads = min(scores.shape[1], labels_m.shape[1])
                accs = [StreamingMetrics() for _ in range(n_heads)]
            for h in range(n_heads):
                accs[h].update(scores[:, h], labels_m[:, h], weights)
            score_sum += float(scores[:, 0].sum())
            pos_count += int((labels_m[:, 0] > 0.5).sum())
            if scores_out is not None:
                for row in scores:
                    scores_out.write("|".join(f"{v:.6f}" for v in row) + "\n")
    finally:
        if scores_out is not None:
            scores_out.close()
    if not accs:
        print("eval: no data rows found", file=sys.stderr)
        return EXIT_FAIL

    def _round_finite(v: float, nd: int = 6):
        # NaN (e.g. single-class AUC) is not valid JSON; emit null instead
        import math
        return round(float(v), nd) if math.isfinite(float(v)) else None

    # Head names come from the schema's *resolved* target columns (in
    # target-index order), not the raw multiTargetColumnNames list — a name
    # the ColumnConfig doesn't contain would otherwise shift every
    # subsequent head's metrics under the wrong label.
    name_by_index = {c.index: c.name for c in schema.columns}
    resolved_names = [name_by_index.get(i, f"head_{h}")
                      for h, i in enumerate(schema.all_target_indices)]
    rows = accs[0].rows
    heads = [
        {"name": resolved_names[h] if h < len(resolved_names) else f"head_{h}",
         "auc": _round_finite(accs[h].auc()),
         "weighted_error": _round_finite(accs[h].weighted_error())}
        for h in range(n_heads)]
    summary = {
        "rows": int(rows),
        "auc": heads[0]["auc"],
        "weighted_error": heads[0]["weighted_error"],
        "mean_score": _round_finite(score_sum / max(rows, 1)),
        "positive_rate": _round_finite(pos_count / max(rows, 1)),
    }
    if n_heads > 1:
        summary["heads"] = heads
    print(json.dumps(summary))
    obs.event("eval_run", rows=int(rows), auc=summary["auc"],
              weighted_error=summary["weighted_error"], model=args.model)
    obs.flush()
    return EXIT_OK


def _export_aot_opts(args) -> tuple:
    """(aot_pack, aot_buckets) for the export sequence: opt-in via the
    `shifu.serving.aot-pack` key in --globalconfig or the export
    command's --aot-pack flag; the rung grid comes from the SAME conf's
    serving ladder keys so the pack matches what the fleet will serve."""
    from ..utils import xmlconfig

    cfg = None
    if getattr(args, "globalconfig", None):
        try:
            conf = xmlconfig.parse_configuration_xml(args.globalconfig)
            cfg = xmlconfig.serving_config_from_conf(conf)
        except Exception:
            cfg = None
    if not (getattr(args, "aot_pack", False) or (cfg and cfg.aot_pack)):
        return False, None
    from ..config.schema import ServingConfig
    from ..runtime.serve import bucket_ladder

    sc = cfg or ServingConfig()
    return True, bucket_ladder(sc.min_batch_bucket, sc.max_batch)


def _export_and_pack(params, job, out_dir, console,
                     baseline_profile=None, aot_pack=False,
                     aot_buckets=None) -> str:
    """The one export sequence (artifact + best-effort native pack) shared
    by the train tail and the export recovery command — divergence here
    would give the recovery path different artifacts than training.

    A remote (gs:// hdfs://) destination builds the artifact in a local
    temp dir (the exporters and the native pack write real files) and
    uploads it through fsio — the reference likewise exported to
    FINAL_MODEL_PATH on HDFS (ssgd_monitor.py:302-345)."""
    from .. import obs
    from ..data import fsio
    from ..export import save_artifact
    from ..train import make_forward_fn

    with obs.span("export", journal=False):
        remote = fsio.is_remote(out_dir)
        local_dir = out_dir
        if remote:
            import tempfile
            local_dir = tempfile.mkdtemp(prefix="shifu_tpu_export_")
        export_dir = save_artifact(params, job, local_dir,
                                   forward_fn=make_forward_fn(job),
                                   baseline_profile=baseline_profile,
                                   aot_pack=aot_pack,
                                   aot_buckets=aot_buckets)
        try:
            from ..runtime import pack_native
            pack_native(export_dir)
        except Exception as e:  # native pack is best-effort
            console(f"native pack skipped: {e}")
        if remote:
            import shutil
            fsio.upload_dir(export_dir, out_dir)
            shutil.rmtree(local_dir, ignore_errors=True)
            export_dir = out_dir
    obs.event("export", dest=export_dir)
    console(f"model exported to {export_dir}")
    return export_dir


def run_export(args) -> int:
    """Rebuild the scoring artifact from the newest checkpoint — the
    recovery path when a job trained but died before (or during) export,
    and the way to ship a resumed/early-stopped state without retraining."""
    import jax

    from ..config import job_config_from_shifu
    from ..train import init_state
    from ..train import checkpoint as ckpt_lib
    from ..utils import xmlconfig

    job = job_config_from_shifu(args.modelconfig, args.columnconfig)
    if args.globalconfig:
        job = xmlconfig.apply_to_job(
            job, xmlconfig.parse_configuration_xml(args.globalconfig))

    if not os.path.isdir(args.checkpoint_dir):
        # restore-only path: never materialize an empty orbax tree at a
        # typo'd location as a side effect of the manager
        print(f"no checkpoint directory: {args.checkpoint_dir}",
              file=sys.stderr, flush=True)
        return EXIT_FAIL
    manager = ckpt_lib.make_manager(args.checkpoint_dir)
    state = init_state(job, job.schema.feature_count)
    from ..train.loop import restore_latest_any_layout
    restored = restore_latest_any_layout(manager, state, job,
                                         lambda s: print(s, flush=True))
    if restored is None:
        print(f"no checkpoint found under {args.checkpoint_dir}",
              file=sys.stderr, flush=True)
        return EXIT_FAIL
    r_state, extra, step = restored
    print(f"exporting checkpoint step {step} "
          f"(epoch {(extra or {}).get('epoch', '?')})", flush=True)
    aot_pack, aot_buckets = _export_aot_opts(args)
    _export_and_pack(jax.device_get(r_state.params), job, args.output,
                     lambda s: print(s, flush=True),
                     aot_pack=aot_pack, aot_buckets=aot_buckets)
    return EXIT_OK


def _arm_pdeathsig() -> None:
    """Supervised attempt children die with their supervisor.

    The supervisor spawns attempts in their OWN session (so kill-tree
    reaches the gang), which also detaches them from the supervisor's
    fate: a SIGTERM is forwarded by handler, but an UNCATCHABLE
    supervisor death (SIGKILL, OOM kill) would orphan the attempt to
    train its full epoch budget alone — observed as a 50k-epoch child
    spinning after its detached daemon was SIGKILLed.  When the
    supervisor marks the environment (supervisor.ENV_PDEATHSIG = its own
    pid), arm Linux PR_SET_PDEATHSIG(SIGTERM) so the kernel itself
    delivers the drain signal on parent death; SIGTERM (not SIGKILL) so
    the train loop's drain still checkpoints.  Closes the fork->arm race
    by self-signaling when os.getppid() no longer matches the recorded
    spawner — a pid compare, not a `== 1` check, so a supervisor that
    legitimately IS pid 1 (container entrypoint) or a subreaper
    environment cannot false-positive.
    """
    # literal env name: supervisor.ENV_PDEATHSIG (kept in sync by
    # tests/test_launcher.py); the cold path (status/attach/kill polls)
    # must not import the supervisor module just to read this.
    # Value: "<spawner_pid>" or "<spawner_pid>:<signum>".  The spawner
    # picks the signal: SIGTERM (default) for a single supervised child
    # whose drain handler checkpoints; SIGKILL for gang ranks — a rank
    # must terminate IMMEDIATELY on dispatcher death (divergent drains
    # deadlock collectives, train/loop.py), and libraries in the rank
    # (orbax preemption hooks) register SIGTERM handlers that would
    # swallow a catchable signal and leave the rank training forever.
    # pop, don't read: the arm applies to THIS process only, and any
    # descendant spawned with inherited env (a hook shelling out to
    # `shifu-tpu export`, a rank, a nested dispatcher) would otherwise see
    # a stale parent pid, fail the getppid compare, and self-kill at
    # startup; spawners that want armed children set the var fresh
    val = os.environ.pop("SHIFU_TPU_PDEATHSIG", None)
    if not val or sys.platform != "linux":
        return
    try:
        import signal as signal_lib

        parts = val.split(":")
        expected_parent = int(parts[0])
        sig = int(parts[1]) if len(parts) > 1 else int(signal_lib.SIGTERM)
    except ValueError:
        return
    try:
        import ctypes

        libc = ctypes.CDLL(None, use_errno=True)
        libc.prctl(1, sig, 0, 0, 0)  # 1 = PR_SET_PDEATHSIG
        if os.getppid() != expected_parent:
            # parent died (or we were reparented) before the arm landed
            os.kill(os.getpid(), sig)
    except Exception:
        pass  # best-effort hardening; never block startup


def main(argv: Optional[Sequence[str]] = None) -> int:
    _arm_pdeathsig()
    args = build_parser().parse_args(argv)
    if args.command in ("train", "score", "eval", "export", "serve",
                        "loadtest", "fleet"):
        # repeat compiles (supervisor restarts, re-runs of the same job)
        # deserialize from the persistent cache instead of recompiling.
        # Only for commands that compile: status/attach/kill/provision are
        # file/CLI operations and must not pay the jax import.  Serving
        # paths drop the persistence floor to 0: padded-bucket scorer
        # programs compile in tens of ms — below the 0.5s train-path
        # floor, which would silently skip exactly the compiles a member
        # restart pays again (JAX's hit/miss verdict rides every
        # xla_compile event, obs/introspect.py)
        from ..utils.compilecache import enable_persistent_cache
        serving_cmd = args.command in ("serve", "loadtest", "fleet")
        enable_persistent_cache(
            min_compile_time_secs=0.0 if serving_cmd else 0.5)
    if args.command == "train":
        # daemonized dispatcher: record the terminal state for `status`
        # even when the run unwinds via SystemExit (the provision branch
        # turns a scheduler SIGTERM into one so release finallys run) —
        # a cleanly drained kill must read as FAILED(143), not DEAD
        from . import detach as detach_lib
        detached_dir = os.environ.get(detach_lib.ENV_DETACHED)

        def _record(rc: int) -> None:
            if detached_dir and not getattr(args, "detach", False):
                detach_lib.write_status(detached_dir, rc)

        try:
            rc = run_train(args)
        except SystemExit as e:
            _record(e.code if isinstance(e.code, int) else 1)
            raise
        _record(rc)
        return rc
    if args.command == "score":
        return run_score(args)
    if args.command == "serve":
        return run_serve(args)
    if args.command == "fleet":
        return run_fleet(args)
    if args.command == "loadtest":
        return run_loadtest(args)
    if args.command == "eval":
        return run_eval(args)
    if args.command == "export":
        return run_export(args)
    if args.command == "provision":
        return run_provision(args)
    if args.command == "metrics":
        # pure file reads — must not pay the jax import or compile cache
        return run_metrics(args)
    if args.command == "profile":
        # likewise journal reads only — no jax import
        return run_profile(args)
    if args.command == "trace":
        # likewise journal reads only — no jax import
        return run_trace(args)
    if args.command == "top":
        # likewise journal/scrape tail only — no jax import, safe to
        # point at a live daemon from any machine
        return run_top(args)
    if args.command == "drift":
        # likewise journal tail only — no jax import
        return run_drift(args)
    if args.command == "chaos-verify":
        # likewise journal/plan reads only — no jax import
        return run_chaos_verify(args)
    if args.command == "fleet-verify":
        # likewise journal reads only — no jax import
        return run_fleet_verify(args)
    if args.command == "pod-verify":
        # likewise journal reads only — no jax import
        return run_pod_verify(args)
    if args.command == "data-dryrun":
        # host-side ingest only — no device work, no collectives
        return run_data_dryrun(args)
    if args.command == "timeline":
        # likewise journal reads only — no jax import
        return run_timeline(args)
    if args.command == "cache":
        # cache-dir file reads only — no jax import
        return run_cache(args)
    from . import detach as detach_lib
    if args.command == "status":
        return detach_lib.run_status(args.job_dir)
    if args.command == "attach":
        return detach_lib.attach(args.job_dir, from_start=not args.tail)
    if args.command == "kill":
        return detach_lib.kill(args.job_dir,
                               force=getattr(args, "force", False))
    return EXIT_FAIL


if __name__ == "__main__":
    sys.exit(main())
