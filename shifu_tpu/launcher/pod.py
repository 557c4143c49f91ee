"""Pod-scale launch: one command → one training process per host, per-host
log collection, and whole-gang supervised restart from checkpoint.

Successor of the reference's compute-acquisition path — the YARN client's
createApplication/submitApplication/monitorApplication loop
(yarn/client/TensorflowClient.java:339-426), the AM's container allocation
(yarn/appmaster/AMRMCallbackHandler.java:148-190), and its failed-worker
recovery (yarn/appmaster/TensorflowApplicationMaster.java:410-426).  On TPU
the accelerators are already attached to the pod's hosts, so "provisioning"
collapses to: derive the host list (explicit --hosts, SHIFU_TPU_HOSTS, or the
TPU runtime's own metadata), dispatch one SPMD process per host with ranks
assigned from list order, stream every host's output back into per-host log
files under the job dir, and supervise the gang as a unit: the first host
failure tears the rest down (a half-gang would block in collectives forever —
the SPMD analog of "any failed worker breaks the monitor loop",
TensorflowApplicationMaster.java:363-371) and the whole gang restarts from
the shared checkpoint, bounded by the same restart budget the single-host
supervisor uses.  Hot-standby backup containers have no SPMD equivalent;
checkpoint-restart of the full gang is the recovery story (SURVEY.md §5.3).

Transports:
- ``local`` (``--hosts local:N``): N coordinated processes on this machine —
  the simulated pod used by tests and dev runs (virtual CPU devices per
  process).
- ``ssh`` (``--hosts h1,h2,...`` or ``--hosts @hostfile``): one process per
  host over ``ssh -tt`` (the tty makes a parent-side kill propagate as HUP).
  Host order defines the jax.distributed process id, so list hosts in the
  TPU runtime's worker order (TPU_WORKER_HOSTNAMES order on Cloud TPU).
  Checkpoint/export paths must live on storage all hosts share (gs://,
  hdfs://, NFS) — the same contract the reference had with HDFS model paths.

The operator UX stays the reference's: one command, per-epoch lines on the
console (rank 0's stream is echoed live, every rank is captured to
``<out>/logs/host-<rank>.attempt-<k>.log``), per-host log locations printed,
exit status 0/1/3.
"""

from __future__ import annotations

import os
import shlex
import subprocess
import sys
import threading
import time
from dataclasses import dataclass
from typing import Optional, Sequence

ENV_HOSTS = "SHIFU_TPU_HOSTS"
ENV_COORDINATOR_PORT = "SHIFU_TPU_COORDINATOR_PORT"
DEFAULT_COORDINATOR_PORT = 8476
# per-host reconnects for ssh rc=255 with NO output yet (connect-level
# failure — host booting, transient network); a host that produced output
# and then died is a worker failure, handled by gang restart instead
SSH_CONNECT_RETRIES = 3


class ChipOwnershipError(RuntimeError):
    """More than one process on a host would open the accelerator."""


def require_one_chip_owner(n_on_host: int, what: str, *,
                           local: bool) -> None:
    """Refuse to start `n_on_host` > 1 processes that each open the
    default JAX backend on one host.  A TPU chip belongs to one process
    at a time: the second one dies at start-up on libtpu's lock file
    ("Unable to initialize backend 'tpu': ABORTED"; chip_smoke.py's serve
    phase records it on every run).  Local children inherit this
    environment, so with `JAX_PLATFORMS=cpu` they are a CPU simulation
    and may be as many as asked; pinning one chip per child is not
    implemented."""
    if n_on_host <= 1:
        return
    if local and os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return
    raise ChipOwnershipError(
        f"{what}: {n_on_host} processes on one host would each open the "
        "accelerator, and a TPU chip belongs to one process at a time (the "
        "others fail at start-up). Run one such process per host, or set "
        "JAX_PLATFORMS=cpu for a CPU simulation")


@dataclass(frozen=True)
class PodSpec:
    hosts: tuple[str, ...]           # rank i runs on hosts[i]
    transport: str                   # "local" | "ssh"
    coordinator_port: int = DEFAULT_COORDINATOR_PORT
    remote_python: str = sys.executable  # interpreter on the hosts


def parse_hosts(value: str, coordinator_port: int = 0) -> PodSpec:
    """``local:N`` → N simulated hosts here; ``@file`` → newline-separated
    host list; ``h1,h2,...`` → ssh to each host.

    `coordinator_port` (or SHIFU_TPU_COORDINATOR_PORT) overrides the ssh
    rendezvous port on hosts[0] — the escape hatch when the default 8476
    conflicts.  Resolved only on the ssh path: local transport picks a free
    port and ignores it, so a bad env value must not break local runs."""
    value = value.strip()
    if value.startswith("local:"):
        n = int(value.split(":", 1)[1])
        if n < 1:
            raise ValueError(f"--hosts {value!r}: need at least 1 process")
        return PodSpec(hosts=("local",) * n, transport="local")
    try:
        port = (coordinator_port
                or int(os.environ.get(ENV_COORDINATOR_PORT, "0") or 0)
                or DEFAULT_COORDINATOR_PORT)
    except ValueError:
        raise ValueError(
            f"{ENV_COORDINATOR_PORT}="
            f"{os.environ.get(ENV_COORDINATOR_PORT)!r} is not a port number")
    if not (0 < port < 65536):
        raise ValueError(f"coordinator port {port} out of range")
    if value.startswith("@"):
        with open(value[1:]) as f:
            hosts = tuple(h.strip() for h in f if h.strip()
                          and not h.lstrip().startswith("#"))
    else:
        hosts = tuple(h.strip() for h in value.split(",") if h.strip())
    if not hosts:
        raise ValueError(f"--hosts {value!r}: no hosts")
    return PodSpec(hosts=hosts, transport="ssh", coordinator_port=port)


def detect_hosts_env() -> Optional[str]:
    """The no-flag spelling: SHIFU_TPU_HOSTS.  Deliberately NOT
    TPU_WORKER_HOSTNAMES: the TPU runtime sets that on EVERY pod worker, and
    the established managed-pod pattern is to run the plain train command on
    all workers at once (`gcloud ... --worker=all`), each auto-joining via
    jax.distributed — auto-dispatching there would turn every worker into a
    dispatcher and launch N colliding gangs.  Dispatching is an explicit
    opt-in; `--hosts` docs point operators at the TPU_WORKER_HOSTNAMES value
    when they want driver-style launch from one machine."""
    return os.environ.get(ENV_HOSTS) or None


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _host_command(spec: PodSpec, rank: int, child_args: Sequence[str],
                  env_contract: dict[str, str]) -> tuple[list[str], Optional[dict]]:
    """(argv, env-or-None): local runs inherit+extend the parent env; ssh
    carries the contract inline (`env K=V ...`) so no remote shell profile
    can drop it."""
    module_argv = ["-m", "shifu_tpu.launcher.cli", *child_args]
    if spec.transport == "local":
        env = dict(os.environ)
        env.update(env_contract)
        # ranks die with THIS dispatcher even on its uncatchable death
        # (cli._arm_pdeathsig).  SIGKILL, not SIGTERM: a rank must stop
        # IMMEDIATELY (divergent drains deadlock gang collectives), and
        # rank-side libraries register SIGTERM handlers that would swallow
        # a catchable signal.  Set per-spawn — an inherited value from an
        # armed ancestor would record the WRONG parent pid and self-kill
        # the rank at startup.  ssh transport must NOT carry this: the
        # dispatcher pid is meaningless on the remote host (the ssh -tt
        # HUP tether covers remote parent-death there).
        import signal as signal_lib

        from .supervisor import ENV_PDEATHSIG
        env[ENV_PDEATHSIG] = f"{os.getpid()}:{int(signal_lib.SIGKILL)}"
        return [sys.executable, *module_argv], env
    assigns = [f"{k}={v}" for k, v in env_contract.items()]
    remote = " ".join(
        shlex.quote(p) for p in
        ["env", *assigns, spec.remote_python, *module_argv])
    return (["ssh", "-tt", "-o", "BatchMode=yes", spec.hosts[rank], remote],
            None)


def member_command(spec: PodSpec, rank: int, child_args: Sequence[str],
                   env_contract: dict[str, str]
                   ) -> tuple[list[str], Optional[dict]]:
    """(argv, env-or-None) to run one `shifu-tpu` child on host `rank` —
    the serving fleet's spawn path (runtime/fleet.py HostPlane): the
    SAME local/ssh transport wrapping the training gang uses, exposed
    for per-member dispatch instead of gang dispatch.  Local transport
    inherits+extends this env (with the pdeathsig tether); ssh carries
    the contract inline so no remote shell profile can drop it."""
    if not (0 <= rank < len(spec.hosts)):
        raise ValueError(f"member rank {rank} outside the host list "
                         f"({len(spec.hosts)} hosts)")
    return _host_command(spec, rank, child_args, env_contract)


def launch_gang(spec: PodSpec, child_args: Sequence[str], out_dir: str,
                attempt: int, liveness_seconds: float = 0.0,
                echo=print, deadline=None) -> tuple[int, tuple[int, ...]]:
    """Run one gang attempt: dispatch every rank, stream rank 0 to the
    console, capture all ranks to per-host logs, tear everyone down on the
    first failure (or on a liveness stall), return (gang exit code,
    culprit ranks).

    Culprit ranks are the ranks observed failing BEFORE the teardown began
    (failures after it are collateral SIGTERMs) — the signal the elastic
    reshape in supervise_pod uses to identify a permanently lost host.
    Empty on success, timeout, and liveness kills (a stall has no
    attributable culprit).

    `deadline` is a supervisor.JobDeadline for the JOB-level timeout: past
    it the gang is torn down and EXIT_TIMEOUT returned (the supervisor
    treats that as terminal)."""
    from .supervisor import EXIT_TIMEOUT
    n = len(spec.hosts)
    if list(child_args[:1]) == ["train"]:
        # `train` ranks open the default backend; a `data-dryrun` gang is
        # host-side work and may share a host freely
        local = spec.transport == "local"
        require_one_chip_owner(
            n if local else max(spec.hosts.count(h) for h in spec.hosts),
            f"training gang of {n} ({spec.transport})", local=local)
    try:
        from ..data import fsio
        remote_out = fsio.is_remote(out_dir)
    except Exception:
        remote_out = False
    if remote_out:
        # per-host log PIPES are local files; a remote job dir keeps its
        # board/metrics/checkpoints remote while the dispatcher's raw host
        # logs live beside it on the dispatching machine
        import tempfile
        log_dir = tempfile.mkdtemp(prefix="shifu_tpu_pod_logs_")
    else:
        log_dir = os.path.join(out_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
    if spec.transport == "local":
        coordinator = f"127.0.0.1:{_free_port()}"
    else:
        coordinator = f"{spec.hosts[0]}:{spec.coordinator_port}"

    procs: list[subprocess.Popen] = []
    threads: list[threading.Thread] = []
    log_paths: list[str] = []
    # per-rank monotonic timestamp of the last output line — any rank's
    # output counts as gang progress for the liveness monitor (epoch lines
    # come from rank 0; other ranks are quiet when healthy)
    progress = [time.monotonic()] * n
    ssh_retries = [0] * n
    lock = threading.Lock()

    def _contract(rank: int) -> dict[str, str]:
        contract = {
            "SHIFU_TPU_COORDINATOR": coordinator,
            "SHIFU_TPU_NUM_PROCESSES": str(n),
            "SHIFU_TPU_PROCESS_ID": str(rank),
        }
        # an active chaos plan must reach every rank — local transport
        # inherits the dispatcher env, but ssh carries ONLY the contract
        # (the state path is only meaningful on shared storage; rank-scoped
        # process triggers need no state at all)
        from ..chaos import ENV_CHAOS_PLAN, ENV_CHAOS_STATE
        for key in (ENV_CHAOS_PLAN, ENV_CHAOS_STATE):
            val = os.environ.get(key)
            if val:
                contract[key] = val
        return contract

    def pump(rank: int, proc: subprocess.Popen, log_path: str,
             mode: str = "w") -> None:
        with open(log_path, mode) as log:
            for line in proc.stdout:  # text mode; closes on child exit
                log.write(line)
                log.flush()
                with lock:
                    progress[rank] = time.monotonic()
                if rank == 0:
                    echo(line.rstrip("\n"))

    def dispatch(rank: int, mode: str = "w") -> None:
        argv, env = _host_command(spec, rank, child_args, _contract(rank))
        try:
            # chaos site "pod.dispatch": the transport to one host fails
            # (ssh refused, container runtime down) — modeled as a stub
            # child exiting with the fault's code so the gang teardown /
            # ssh-retry / reshape machinery sees a real dead rank.  255
            # exercises the ssh transport budget specifically.
            from .. import chaos
            chaos.maybe_fail("pod.dispatch", rank=rank, attempt=attempt,
                             host=spec.hosts[rank])
        except chaos.ChaosError as e:
            echo(f"pod: chaos: host {rank} dispatch fails ({e})")
            argv = [sys.executable, "-c",
                    f"import sys; sys.exit({int(e.exit_code)})"]
            env = None
        proc = subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        procs[rank] = proc
        t = threading.Thread(target=pump,
                             args=(rank, proc, log_paths[rank], mode),
                             daemon=True)
        t.start()
        threads.append(t)

    for rank in range(n):
        log_paths.append(
            os.path.join(log_dir, f"host-{rank}.attempt-{attempt}.log"))
        procs.append(None)  # type: ignore[arg-type]
        dispatch(rank)

    echo(f"pod: attempt {attempt}: {n} processes "
         f"({spec.transport}), coordinator {coordinator}, "
         f"logs {log_dir}/host-*.attempt-{attempt}.log")

    status = 0
    failed_ranks: list[int] = []
    # teardown is deferred one short grace window after the FIRST failure
    # so every rank that fails on its own in that window is recorded as a
    # culprit too: blaming only the first-polled exit would let a
    # fast-dying collateral victim (a peer aborting on the dead host's
    # collective error inside the same poll interval) absorb the blame —
    # and the elastic reshape would then evict a healthy host.  Collateral
    # victims caught in the window make the culprit set ambiguous (size >
    # 1), which the reshape treats as "not one lost host" — conservative
    # by design.
    teardown_at: Optional[float] = None
    try:
        remaining = set(range(n))
        while remaining:
            for rank in sorted(remaining):
                rc = procs[rank].poll()
                if rc is None:
                    continue
                if (rc == 255 and spec.transport == "ssh"
                        and ssh_retries[rank] < SSH_CONNECT_RETRIES):
                    # rc=255 is the ssh CLIENT's own exit code — a
                    # transport-level failure, not a child exit: retry THIS
                    # host with backoff.  A pre-rendezvous connect failure
                    # (host booting, flaky network) reconnects cleanly; a
                    # mid-run drop killed the remote worker (-tt HUP), the
                    # re-join then fails fast and the gang restarts under
                    # supervise_pod's TRANSPORT budget — either way the
                    # training restart budget is never charged
                    ssh_retries[rank] += 1
                    echo(f"pod: host {rank} ({spec.hosts[rank]}) ssh "
                         f"connect failed (rc=255) — reconnect "
                         f"{ssh_retries[rank]}/{SSH_CONNECT_RETRIES}")
                    time.sleep(min(2.0 * ssh_retries[rank], 10.0))
                    dispatch(rank, mode="a")
                    continue
                remaining.discard(rank)
                if rc != 0:
                    if teardown_at is None:
                        failed_ranks.append(rank)
                        echo(f"pod: host {rank} ({spec.hosts[rank]}) "
                             f"exited rc={rc} — tearing down the gang "
                             f"(see {log_paths[rank]})")
                        teardown_at = time.monotonic() + 1.0
                    elif time.monotonic() < teardown_at:
                        # failed on its own inside the grace window:
                        # also a culprit (ambiguity blocks the reshape)
                        failed_ranks.append(rank)
                    status = status or rc
            if (teardown_at is not None and remaining
                    and time.monotonic() >= teardown_at):
                # culprit grace over: stop the survivors (idempotent —
                # repeat sweeps just re-signal already-terminating procs)
                for other in sorted(remaining):
                    procs[other].terminate()
            # deadline AFTER the poll drain: a gang that finished during the
            # last sleep must report its real status, not a phantom timeout
            if deadline is not None and remaining and deadline.expired():
                # no graceful drain here: multihost ranks deliberately do NOT
                # catch SIGTERM (one rank draining while peers issue
                # collectives would deadlock the step — train/loop.py), so
                # progress durability comes from the periodic checkpoint
                # cadence, and the teardown is immediate
                echo("pod: job timeout exceeded — tearing down the gang")
                for other in sorted(remaining):
                    procs[other].terminate()
                return EXIT_TIMEOUT, ()
            if liveness_seconds > 0 and remaining:
                with lock:
                    newest = max(progress)
                if time.monotonic() - newest > liveness_seconds:
                    echo(f"pod: no output from any host for "
                         f"{liveness_seconds}s — killing the gang")
                    status = status or -9
                    for other in sorted(remaining):
                        procs[other].kill()
            if remaining:
                time.sleep(0.5)
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        for t in threads:
            t.join(timeout=5)
    return status, tuple(failed_ranks)


def supervise_pod(spec: PodSpec, child_args: Sequence[str], out_dir: str,
                  max_restarts: int = 2, liveness_seconds: float = 0.0,
                  echo=print, checkpoint_dir: Optional[str] = None,
                  timeout_seconds: float = 0.0, min_hosts: int = 0) -> int:
    """Whole-gang restart supervision: any host failure restarts the ENTIRE
    gang (checkpoint auto-resume continues the job), bounded by max_restarts
    CONSECUTIVE failures without durable progress — the cross-host successor
    of `supervise()` and of the reference's backup-promotion recovery.
    Progress = the shared checkpoint's epoch advanced during the attempt
    (supervisor.ProgressProbe over the PROGRESS marker; works for gs://,
    hdfs://, NFS checkpoint dirs — which is also the shared-storage
    contract ssh pods already have): preemption-heavy pods legitimately
    restart many times, each resuming further, and only a crash loop that
    persists nothing exhausts the budget.

    timeout_seconds bounds the WHOLE JOB across attempts (one
    supervisor.JobDeadline from the first attempt's start); a timeout —
    whether hit by the gang's own children (exit 3) or by the dispatcher's
    deadline — is TERMINAL, never restarted (TensorflowClient.java:625-658
    kills the app once).

    min_hosts > 0 enables ELASTIC RESHAPE (RuntimeConfig.min_hosts): when
    the restart budget is exhausted and the attempts' culprit is one
    identifiable host, that host is presumed permanently lost — the gang
    restarts WITHOUT it (file shards rebalance through the env contract's
    new NUM_PROCESSES/PROCESS_ID, the train loop re-rounds the global
    batch to the new mesh, checkpoint auto-resume continues) with a fresh
    budget, as long as at least min_hosts remain.  The SPMD answer to the
    reference's >=95%-of-workers degraded start with task-index re-packing
    (TensorflowApplicationMaster.java:230-338).  Reshape assumes the job's
    state survives a world-size change — true for data-parallel jobs
    (replicated params; the default); model/pipe-sharded topologies should
    keep min_hosts=0."""
    import dataclasses as _dc

    from .supervisor import (EXIT_TIMEOUT, JobDeadline, ProgressProbe,
                             charge_restart_budget)

    attempts = 0
    failures_since_progress = 0
    transport_failures = 0
    # culprit accounting across the no-progress window: reshape drops a
    # host only when EVERY budgeted failure blames the same host (mixed
    # culprits look like a cluster-wide problem, not one lost host)
    window_culprits: set[int] = set()
    deadline = JobDeadline(timeout_seconds)

    def _reshape(reason: str) -> bool:
        nonlocal spec, failures_since_progress, transport_failures
        if min_hosts <= 0 or len(spec.hosts) <= max(min_hosts, 1):
            return False
        if len(window_culprits) != 1:
            return False
        drop = next(iter(window_culprits))
        gone = spec.hosts[drop]
        new_hosts = tuple(h for i, h in enumerate(spec.hosts) if i != drop)
        echo(f"pod: host {drop} ({gone}) {reason} — presumed permanently "
             f"lost; reshaping the gang to {len(new_hosts)} hosts "
             f"(floor {max(min_hosts, 1)}), rebalancing file shards, and "
             "resuming from checkpoint")
        spec = _dc.replace(spec, hosts=new_hosts)
        failures_since_progress = 0
        transport_failures = 0
        window_culprits.clear()
        return True

    while True:
        if deadline.expired():
            # don't dispatch a doomed gang just to kill it one poll later
            echo("pod: job timeout exceeded — terminal, no restart")
            return EXIT_TIMEOUT
        attempts += 1
        start = time.monotonic()
        probe = ProgressProbe(checkpoint_dir)
        rc, failed = launch_gang(spec, child_args, out_dir, attempts,
                                 liveness_seconds=liveness_seconds, echo=echo,
                                 deadline=deadline)
        if rc == 0:
            if attempts > 1:
                echo(f"pod: succeeded after {attempts} attempts")
            return 0
        if rc == EXIT_TIMEOUT:
            echo(f"pod: attempt {attempts} hit the job timeout — terminal, "
                 "no restart")
            return EXIT_TIMEOUT
        if rc == 255 and spec.transport == "ssh":
            # a mid-run ssh-level failure (rc=255 is the ssh client's own
            # code) is a TRANSPORT fault, not a training crash: restart the
            # gang on its own bounded budget so one flaky link cannot eat
            # the failure budget meant for real crash loops.  Like the
            # restart budget, it bounds CONSECUTIVE no-progress failures —
            # a multi-day job's occasional link drops, each resuming
            # further, must not accumulate to a terminal failure
            if probe.advanced():
                transport_failures = 0
                window_culprits.clear()
            transport_failures += 1
            window_culprits.update(failed)
            if transport_failures <= SSH_CONNECT_RETRIES:
                echo(f"pod: ssh transport failure — restarting the gang "
                     f"without charging the restart budget "
                     f"({transport_failures}/{SSH_CONNECT_RETRIES})")
                continue
            # an unreachable-forever host is the clearest permanent loss
            if _reshape("is unreachable over ssh after "
                        f"{transport_failures} consecutive attempts"):
                continue
            echo("pod: ssh transport failure budget exhausted")
            return 1
        progressed = probe.advanced()
        if progressed:
            window_culprits.clear()
        window_culprits.update(failed)
        failures_since_progress = charge_restart_budget(
            failures_since_progress, progressed, echo=echo, what="pod")
        echo(f"pod: attempt {attempts} failed rc={rc} after "
             f"{time.monotonic() - start:.1f}s")
        if failures_since_progress > max_restarts:
            if _reshape(f"failed {failures_since_progress} consecutive "
                        "attempts without progress"):
                continue
            echo(f"pod: restart budget exhausted ({max_restarts} restarts "
                 "without progress)")
            return rc if isinstance(rc, int) and rc > 0 else 1


# -- pod data-plane journal audit -------------------------------------------


def _pod_close_rows(events: Sequence[dict]) -> list[dict]:
    """Normalize per-epoch close records out of a merged event stream:
    `pod_epoch_close` rows (one per rank per epoch — the data-dryrun gang
    child journals them) plus the per-host rows embedded in each chief
    `host_skew` event (real multihost training runs).  Each normalized row:
    {epoch, rank, hosts, order_digest, shard_digest, ingest_bytes,
    ingest_s}."""
    rows: list[dict] = []
    for ev in events:
        kind = ev.get("kind")
        if kind == "pod_epoch_close":
            rows.append({
                "epoch": ev.get("epoch"), "rank": ev.get("rank"),
                "hosts": ev.get("hosts"),
                "order_digest": ev.get("order_digest"),
                "shard_digest": ev.get("shard_digest"),
                "ingest_bytes": ev.get("ingest_bytes"),
                "ingest_s": ev.get("ingest_s"),
            })
        elif kind == "host_skew":
            members = ev.get("hosts") or []
            for r in members:
                if not isinstance(r, dict):
                    continue
                rows.append({
                    "epoch": ev.get("epoch"), "rank": r.get("rank"),
                    "hosts": len(members),
                    "order_digest": r.get("order_digest"),
                    "shard_digest": r.get("shard_digest"),
                    "ingest_bytes": r.get("ingest_bytes"),
                    "ingest_s": r.get("ingest_s"),
                })
    return [r for r in rows
            if isinstance(r["epoch"], int) and isinstance(r["rank"], int)]


def pod_verify_events(events: Sequence[dict],
                      balance_limit: float = 1.5) -> dict:
    """Audit a pod training run's merged journals (obs/timeline.load_merged:
    root journal + one per-rank journal) against the pod data-plane
    invariants — the fleet-verify analog for the training gang.

    Checks:
    - epoch_coverage: every epoch up to the max observed was closed by a
      COMPLETE cohort — some gang width n whose ranks 0..n-1 all journaled
      a close row for it.  A killed attempt's partial rows are fine; an
      elastic reshape's narrower cohort is fine; an epoch NO cohort ever
      completed is not.
    - order_digest_agreement / shard_digest_agreement: inside every
      complete cohort all ranks carry the identical digest (the allgather-
      of-digests contract; rows without the field are skipped, so
      pre-field journals stay un-audited rather than failing).
    - ingest_balance: max/min cumulative per-rank source bytes at the last
      epoch <= balance_limit x the even share (only when >= 2 ranks
      ingested anything).
    - recovery: every injected `exit`/`raise` chaos fault is followed by a
      later (or same-epoch, re-run) complete cohort — the gang rebalanced
      / the host rejoined and the run still closed its epochs.
    """
    rows = _pod_close_rows(events)
    injections = [ev for ev in events
                  if ev.get("kind") == "chaos_inject"
                  and ev.get("action") in ("exit", "raise", "hang")]
    checks: list[dict] = []

    def check(name: str, ok: bool, detail: str) -> None:
        checks.append({"check": name, "ok": bool(ok), "detail": detail})

    by_epoch: dict[int, list[dict]] = {}
    for r in rows:
        by_epoch.setdefault(int(r["epoch"]), []).append(r)

    def complete_cohorts(epoch_rows: list[dict]) -> list[list[dict]]:
        """Groups by gang width whose ranks cover 0..n-1 (newest row per
        (width, rank) wins — a rank re-running an epoch after a restart
        supersedes its earlier row)."""
        by_width: dict[int, dict[int, dict]] = {}
        for r in epoch_rows:
            n = r.get("hosts")
            if isinstance(n, int) and n > 0:
                by_width.setdefault(n, {})[int(r["rank"])] = r
        return [list(ranks.values())
                for n, ranks in sorted(by_width.items())
                if set(ranks) == set(range(n))]

    epochs = sorted(by_epoch)
    missing = []
    disagree_order: list[int] = []
    disagree_shard: list[int] = []
    for ep in (range(epochs[-1] + 1) if epochs else ()):
        cohorts = complete_cohorts(by_epoch.get(ep, []))
        if not cohorts:
            missing.append(ep)
            continue
        for cohort in cohorts:
            for key, sink in (("order_digest", disagree_order),
                              ("shard_digest", disagree_shard)):
                vals = {r[key] for r in cohort if r.get(key) is not None}
                if len(vals) > 1:
                    sink.append(ep)
    n_epochs = (epochs[-1] + 1) if epochs else 0
    check("epoch_coverage", not missing and n_epochs > 0,
          f"{n_epochs - len(missing)}/{n_epochs} epochs closed by a "
          f"complete cohort" + (f"; missing {missing}" if missing else ""))
    check("order_digest_agreement", not disagree_order,
          "all complete cohorts agree" if not disagree_order
          else f"disagreement at epochs {sorted(set(disagree_order))}")
    check("shard_digest_agreement", not disagree_shard,
          "all complete cohorts agree" if not disagree_shard
          else f"disagreement at epochs {sorted(set(disagree_shard))}")

    # cumulative ingest bytes at each rank's LAST row (counters are
    # monotonic within an attempt; the last row is the attempt's total)
    last_by_rank: dict[int, int] = {}
    for r in sorted(rows, key=lambda r: (r["epoch"])):
        if isinstance(r.get("ingest_bytes"), (int, float)):
            last_by_rank[int(r["rank"])] = int(r["ingest_bytes"])
    loads = [b for b in last_by_rank.values() if b > 0]
    if len(loads) >= 2:
        share = sum(loads) / len(loads)
        worst = max(loads)
        ok = worst <= share * balance_limit
        check("ingest_balance", ok,
              f"max {worst} bytes vs even share {share:.0f} "
              f"(limit x{balance_limit:g}) across {len(loads)} ranks")
    else:
        check("ingest_balance", True,
              "fewer than 2 ranks recorded ingest bytes — skipped")
    if injections:
        last_inj_epoch = max(int(ev.get("epoch") or 0) for ev in injections)
        recovered = any(
            ep >= last_inj_epoch and complete_cohorts(by_epoch.get(ep, []))
            for ep in epochs)
        check("recovery", recovered,
              f"{len(injections)} injected kill(s), last at epoch "
              f"{last_inj_epoch}; "
              + ("a complete cohort closed at/after it"
                 if recovered else "no complete cohort after it"))
    verdict = "PASS" if all(c["ok"] for c in checks) else "FAIL"
    return {
        "verdict": verdict,
        "checks": checks,
        "counts": {
            "epochs": n_epochs,
            "close_rows": len(rows),
            "ranks": len({r["rank"] for r in rows}),
            "injections": len(injections),
        },
    }
