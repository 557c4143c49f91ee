"""Serving fleet: shared-storage lease membership, hot-standby failover,
cross-host placement, atomic artifact sync, and burn-rate-driven scale
decisions (docs/SERVING.md "Fleet" / "Cross-host fleet").

The production successor of the reference AM's container supervision
(PAPER.md L2/L3: the AM placed containers across hosts, heartbeated N
workers, and promoted pre-warmed hot-standby backups on failure).  Our
unit is the scoring daemon (runtime/serve.py); the fleet plane adds:

- **membership via leases on shared storage** — every member runs a
  `Heartbeat` thread that writes a small lease file in its telemetry dir
  each beat (routed through data/fsio, so a gs://-style fleet root works
  exactly like a local one; the `fleet.heartbeat` and `fleet.lease`
  chaos probes let drills silence a member without killing it).  A lease
  older than its TTL marks the member DOWN no matter which host can see
  whom — liveness is a property of the durable lease, not of any
  point-to-point connection.  The monitor journals `fleet_failover`
  while promoting a hot standby (preferring one on a DIFFERENT host than
  the victim).  Split-brain guard: a partitioned member whose lease
  comes back REJOINS AS A STANDBY (`fleet_rejoin`) — it never
  double-promotes into a slot its replacement already serves.
- **host plane** — `HostPlane` places members across hosts riding
  launcher/pod.py's transports (`local:N` simulated hosts for tests and
  dev, `ssh` for real pods); `scale_tick` and failover replenishment
  spawn/retire through the same placement.
- **fleet-wide hot-swap with atomic artifact sync** — the exporter
  writes the artifact plus a blake2b manifest; each HOST pulls once,
  digest-verifies, atomically renames into its local artifact cache,
  and only then do that host's members swap and join the generation
  barrier.  A torn or corrupt pull quarantines the member
  (`fleet_swap_degraded`, old version keeps serving) and the monitor
  re-pulls; once the barrier is set the router refuses members not on
  the target generation, so no request is ever served by a stale
  version past the barrier.  Every successful per-member application is
  journaled (`fleet_member_swap`) — `shifu-tpu fleet-verify` audits
  that each swap reached each live member exactly once.
- **scale loop** — `decide_scale` closes the loop PR 8 opened: when the
  fast AND slow burn windows agree (worst member's burn >= up threshold,
  or every member <= down threshold), the manager promotes/spawns or
  retires a member and journals `fleet_scale`.

The routing front-end (consistent ring, hedged retry, overload shedding,
reconnect backoff) lives in runtime/router.py; `shifu-tpu fleet` drives
both.  Members are in-proc by default (each with its own loopback wire
server — the tier-1 drill mode); `ProcessMember` spawns real
`shifu-tpu serve` children through the launcher plane's process-group
machinery (launcher/supervisor._kill_tree) and, via the host plane's
ssh transport, on remote hosts."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
import time
from typing import Callable, Optional

from ..config.schema import FleetConfig, ServingConfig

# the heartbeat probe: every beat passes here, so a chaos plan can
# silence a member's lease (partition / wedged-reporter drill) without
# touching its scoring path — the manager must then mark it DOWN and
# fail over even though the daemon still answers (docs/ROBUSTNESS.md)
HEARTBEAT_SITE = "fleet.heartbeat"
# the lease-WRITE probe: fires inside write_lease itself, member-targeted
# (`"member": "member-1"` in the fault spec) — the blackhole-one-member's
# -lease drill, the storage-level sibling of fleet.heartbeat
LEASE_SITE = "fleet.lease"
# the artifact-sync probe: fires between a host's pull and its digest
# verify — a `corrupt` action here models silent storage corruption of
# the synced copy; `raise` models a torn pull
SYNC_SITE = "fleet.sync"
LEASE_FILE = "lease.json"
MANIFEST_FILE = "sync_manifest.json"
# host identity a process-mode member stamps into its lease (the host
# plane exports it to `shifu-tpu serve` children)
ENV_FLEET_HOST = "SHIFU_TPU_FLEET_HOST"


# -- leases ----------------------------------------------------------------


def write_lease(lease_dir: str, member_id: str, seq: int,
                ttl_s: float, pid: Optional[int] = None,
                host: Optional[str] = None) -> str:
    """Atomically write `<lease_dir>/lease.json` — the membership beat.
    `ttl_s` rides IN the lease so any reader (serving_rollup, `top`)
    knows this member's own staleness bound without extra config; `host`
    rides along so the fleet view can group members by placement.

    Routed through data/fsio: a remote lease dir (gs://-style shared
    storage) gets the same no-torn-reads publish as a local one
    (fsio.write_bytes_atomic), which is what makes the lease the fleet's
    cross-host liveness authority."""
    from ..data import fsio

    from .. import chaos
    chaos.maybe_fail(LEASE_SITE, member=member_id, path=lease_dir)
    path = fsio.join(lease_dir, LEASE_FILE)
    rec = {"member": member_id, "ts": round(time.time(), 3),
           "seq": int(seq), "ttl_s": round(float(ttl_s), 3),
           "pid": int(pid if pid is not None else os.getpid())}
    if host is None:
        host = os.environ.get(ENV_FLEET_HOST) or None
    if host:
        rec["host"] = str(host)
    if not fsio.is_remote(lease_dir):
        os.makedirs(lease_dir, exist_ok=True)
    fsio.write_bytes_atomic(path, json.dumps(rec).encode())
    return path


def read_lease(lease_dir: str) -> Optional[dict]:
    """Tolerant lease read: a torn/garbage/absent/unreachable lease is
    None, never an exception — the monitor treats unreadable exactly
    like stale.  Remote lease dirs route through data/fsio."""
    from ..data import fsio

    try:
        path = fsio.join(lease_dir, LEASE_FILE)
        if fsio.is_remote(path):
            rec = json.loads(fsio.read_bytes(path).decode())
        else:
            with open(path) as f:
                rec = json.load(f)
        return rec if isinstance(rec, dict) else None
    except Exception:
        return None


def lease_age_s(lease: Optional[dict],
                now: Optional[float] = None) -> Optional[float]:
    if not lease or not isinstance(lease.get("ts"), (int, float)):
        return None
    return max(0.0, (time.time() if now is None else now)
               - float(lease["ts"]))


class Heartbeat:
    """One member's lease writer: beats every `every_s` through the
    `fleet.heartbeat` chaos probe.  An injected fault SKIPS the beat
    (the lease ages — exactly what a partitioned/wedged member looks
    like from the manager); the thread itself never dies from chaos."""

    def __init__(self, lease_dir: str, member_id: str, every_s: float,
                 ttl_s: float,
                 is_alive: Optional[Callable[[], bool]] = None,
                 host: Optional[str] = None):
        self._dir = lease_dir
        self._member_id = member_id
        self._every_s = every_s
        self._ttl_s = ttl_s
        self._is_alive = is_alive or (lambda: True)
        self._host = host
        self._stop = threading.Event()
        self._seq = 0
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "Heartbeat":
        self.beat()  # first lease lands synchronously: a member is never
        #              observed lease-less between spawn and first tick
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"fleet-heartbeat-{self._member_id}")
        self._thread.start()
        return self

    def stop(self) -> None:
        """Abrupt: no farewell beat — a killed member's lease must AGE,
        not be refreshed on the way down."""
        self._stop.set()

    def beat(self) -> bool:
        from .. import chaos
        try:
            chaos.maybe_fail(HEARTBEAT_SITE, member=self._member_id)
            self._seq += 1
            write_lease(self._dir, self._member_id, self._seq,
                        self._ttl_s, host=self._host)
            return True
        except Exception:
            # chaos (or a full/readonly disk) silenced this beat: the
            # lease ages and the manager decides — the heartbeat thread
            # must survive to beat again if the fault clears
            return False

    def _loop(self) -> None:
        while not self._stop.wait(self._every_s):
            if not self._is_alive():
                return
            self.beat()


# -- atomic artifact sync --------------------------------------------------


class SyncError(OSError):
    """An artifact pull that cannot be trusted: torn copy, digest
    mismatch, unreadable manifest.  An OSError subclass so callers'
    existing degraded-swap handling treats it like any other I/O
    failure — the OLD version keeps serving."""


def write_sync_manifest(export_dir: str) -> str:
    """Write `<export_dir>/sync_manifest.json`: a blake2b digest per
    artifact file (manifest itself excluded).  The exporter calls this
    after `save_artifact`; each host verifies its pull against it before
    the atomic rename — the \"torn or corrupt pull never swaps in\"
    guarantee is exactly this digest check."""
    from ..data import fsio

    prefix = export_dir.rstrip("/") + "/" if fsio.is_remote(export_dir) \
        else export_dir.rstrip(os.sep) + os.sep
    files = {}
    for path, _size in fsio.walk_files(export_dir):
        rel = path[len(prefix):] if path.startswith(prefix) else path
        if rel == MANIFEST_FILE or rel.endswith("/" + MANIFEST_FILE):
            continue
        digest = hashlib.blake2b(fsio.read_bytes(path),
                                 digest_size=16).hexdigest()
        files[rel.replace(os.sep, "/")] = digest
    manifest = {"algo": "blake2b-16", "files": files}
    path = fsio.join(export_dir, MANIFEST_FILE)
    fsio.write_bytes_atomic(path, json.dumps(manifest, indent=2,
                                             sort_keys=True).encode())
    return path


def read_sync_manifest(export_dir: str) -> Optional[dict]:
    from ..data import fsio

    try:
        raw = fsio.read_bytes(fsio.join(export_dir, MANIFEST_FILE))
        rec = json.loads(raw.decode())
        if isinstance(rec, dict) and isinstance(rec.get("files"), dict):
            return rec
    except Exception:
        pass
    return None


def sync_artifact(src: str, cache_dir: str, generation: int, *,
                  host: str = "", member: str = "") -> str:
    """Pull `src` into `<cache_dir>/gen-NNNNNN` with the torn/corrupt
    guard: copy into a staging dir, digest-verify every file against the
    exporter's manifest, then one atomic `os.rename` publishes the whole
    tree — a reader either sees the complete verified artifact or
    nothing.  Idempotent: a generation already published returns its
    path untouched (the exactly-once-per-host half of fleet-verify's
    audit).  Raises SyncError (staging cleaned up) on any mismatch."""
    import shutil

    from .. import chaos
    from ..data import fsio

    dest = os.path.join(cache_dir, f"gen-{int(generation):06d}")
    if os.path.isdir(dest):
        return dest
    manifest = read_sync_manifest(src)
    if manifest is None:
        # exporter predates the manifest (or a bare dir): build one at
        # the source so every host verifies against the SAME digests
        try:
            write_sync_manifest(src)
        except Exception as e:
            raise SyncError(f"sync {src}: cannot write manifest: {e}")
        manifest = read_sync_manifest(src)
        if manifest is None:
            raise SyncError(f"sync {src}: unreadable manifest")
    staging = f"{dest}.incoming.{os.getpid()}"
    try:
        os.makedirs(staging, exist_ok=True)
        for rel in manifest["files"]:
            data = fsio.read_bytes(fsio.join(src, rel))
            local = os.path.join(staging, rel.replace("/", os.sep))
            os.makedirs(os.path.dirname(local), exist_ok=True)
            with open(local, "wb") as f:
                f.write(data)
        # the drill hook sits between pull and verify: a `corrupt`
        # action here is silent storage damage the digest check below
        # MUST catch; `raise` is a torn pull
        chaos.maybe_fail(SYNC_SITE, member=member, host=host,
                         path=staging, generation=int(generation))
        for rel, want in manifest["files"].items():
            local = os.path.join(staging, rel.replace("/", os.sep))
            with open(local, "rb") as f:
                got = hashlib.blake2b(f.read(),
                                      digest_size=16).hexdigest()
            if got != want:
                raise SyncError(
                    f"sync {src}: digest mismatch on {rel!r} "
                    f"(want {want[:12]}, got {got[:12]})")
        try:
            os.rename(staging, dest)  # the atomic publish
        except OSError:
            if os.path.isdir(dest):   # a concurrent pull won the rename
                shutil.rmtree(staging, ignore_errors=True)
                return dest
            raise
    except SyncError:
        shutil.rmtree(staging, ignore_errors=True)
        raise
    except Exception as e:
        shutil.rmtree(staging, ignore_errors=True)
        raise SyncError(f"sync {src}: {type(e).__name__}: {e}")
    try:
        from .. import obs
        obs.event("fleet_sync", path=src, dest=dest, host=host,
                  generation=int(generation),
                  files=len(manifest["files"]))
    except Exception:
        pass
    return dest


# -- the host plane --------------------------------------------------------


class HostPlane:
    """Member placement across hosts, riding launcher/pod.py's transport
    grammar: `local:N` yields N simulated hosts (`local-0`..`local-N-1`,
    the tier-1 drill substrate — in-proc members tagged with a host id),
    a comma/@file host list yields ssh-transported `shifu-tpu serve`
    children.  Placement is least-loaded with ties broken by host order,
    so a fixed config places deterministically — drills can kill \"the
    host member-1 landed on\" by name."""

    def __init__(self, hosts: str, root_dir: str):
        from ..launcher import pod

        self.spec = pod.parse_hosts(hosts)
        if self.spec.transport == "local":
            self.host_ids = tuple(f"local-{i}"
                                  for i in range(len(self.spec.hosts)))
        else:
            self.host_ids = tuple(self.spec.hosts)
        self._root = root_dir
        self._load: dict[str, int] = {h: 0 for h in self.host_ids}

    @property
    def n_hosts(self) -> int:
        return len(self.host_ids)

    def place(self) -> str:
        """Pick the least-loaded host (first wins ties) and count the
        slot against it."""
        host = min(self.host_ids, key=lambda h: self._load[h])
        self._load[host] += 1
        return host

    def release(self, host_id: str) -> None:
        if host_id in self._load and self._load[host_id] > 0:
            self._load[host_id] -= 1

    def cache_dir(self, host_id: str) -> str:
        """This host's local artifact cache — where `sync_artifact`
        publishes verified generations.  Per-host-id subdirs under the
        fleet root keep simulated hosts' caches apart (on real ssh hosts
        each machine sees only its own path)."""
        d = os.path.join(self._root, "sync", host_id)
        os.makedirs(d, exist_ok=True)
        return d

    def serve_command(self, host_id: str, serve_args: list,
                      env_contract: Optional[dict] = None):
        """(argv, env) to spawn one `shifu-tpu serve` member on
        `host_id`, built by launcher/pod.py's transport machinery — the
        same argv/ssh-wrapping the training gang uses."""
        from ..launcher import pod

        rank = self.host_ids.index(host_id)
        contract = dict(env_contract or {})
        contract[ENV_FLEET_HOST] = host_id
        return pod.member_command(self.spec, rank, list(serve_args),
                                  contract)


# -- members ---------------------------------------------------------------

STATE_ACTIVE = "active"
STATE_STANDBY = "standby"
STATE_STALE = "stale"     # failed the fleet swap: out of rotation
STATE_DOWN = "down"
STATE_RETIRED = "retired"


class FleetMember:
    """One in-proc serving daemon under fleet management: its own
    ScoringDaemon + loopback wire server + heartbeat lease.  `kill()` is
    the SIGKILL analog for drills — no drain, no farewell beat."""

    def __init__(self, member_id: str, export_dir: Optional[str], *,
                 serving: ServingConfig, fleet: FleetConfig,
                 tele_dir: str,
                 loader: Optional[Callable] = None,
                 model_id: str = "default",
                 host_id: str = ""):
        from . import serve, serve_wire

        self.member_id = member_id
        self.tele_dir = tele_dir
        os.makedirs(tele_dir, exist_ok=True)
        self.state = STATE_STANDBY
        self.generation = 0
        self.export_dir = export_dir
        # which simulated/real host this member occupies ("" = no host
        # plane); NOT the wire bind — that stays `self.host`
        self.host_id = host_id
        self._fleet = fleet
        # a custom-loader registry still gets the daemon's bucket grid so
        # its loads pre-warm the full ladder exactly like an owned one
        registry = serve.ModelRegistry(
            loader=loader,
            warm_ladder=(serve.bucket_ladder(serving.min_batch_bucket,
                                             serving.max_batch)
                         if serving.prewarm_ladder else None)) \
            if loader else None
        if registry is not None and export_dir is not None:
            registry.load(export_dir, engine=serving.engine,
                          model_id=model_id)
            export_dir = None  # already loaded through the injected loader
        self.daemon = serve.ScoringDaemon(
            export_dir, config=serving, registry=registry,
            model_id=model_id)
        if registry is not None:
            self.daemon._owns_registry = True  # the member built it
        self.daemon.start()
        self.server = serve_wire.ServeServer(
            self.daemon, host="127.0.0.1", port=0).start()
        self.host, self.port = self.server.host, self.server.port
        self.heartbeat = Heartbeat(
            tele_dir, member_id, fleet.heartbeat_every_s,
            fleet.heartbeat_ttl_s,
            is_alive=lambda: self.daemon._running,
            host=host_id or None).start()

    @property
    def version(self) -> Optional[int]:
        handle = self.daemon._registry.current(self.daemon.model_id)
        return handle.version if handle else None

    def swap(self, export_dir: str,
             engine: Optional[str] = None) -> dict:
        return self.daemon.swap(export_dir, engine=engine)

    def burns(self) -> list:
        """[(burn_fast, burn_slow)] per SLO objective — the scale loop's
        and router-shedding's signal; [] when SLO is disabled."""
        eng = self.daemon._slo
        if eng is None:
            return []
        return [(b.get("burn_fast", 0.0), b.get("burn_slow", 0.0))
                for b in eng.state().get("burns", {}).values()]

    def stats(self) -> dict:
        return self.daemon.stats()

    def kill(self) -> None:
        """SIGKILL semantics for in-proc drills: the wire server closes
        mid-connection, queued requests fail, the heartbeat stops with
        NO farewell beat — the lease ages into the DOWN verdict.

        Deliberately does NOT touch `self.state`: a process that dies
        cannot update the manager's bookkeeping either — the DOWN
        verdict belongs to the monitor's lease check (failover)."""
        self.heartbeat.stop()
        self.server.kill()   # sever live conns too — peers must see
        self.daemon.kill()   # transport death, not app-error zombies

    def stop(self) -> None:
        """Graceful retire: drain the daemon, close the wire server."""
        self.heartbeat.stop()
        self.server.close()
        self.daemon.stop()
        self.state = STATE_RETIRED


class ProcessMember:
    """A fleet member as a real `shifu-tpu serve` child process — the
    production spawn path, riding the launcher plane's process-group
    teardown (launcher/supervisor._kill_tree).  The child writes its own
    lease (`shifu-tpu serve --heartbeat-s`) into its telemetry dir, so
    the manager's monitor reads it exactly like an in-proc member's.

    A child with a device engine opens the chip, and a chip belongs to
    one process: a second such child on the same host is refused with
    `ChipOwnershipError` (launcher/pod.py) unless the children are a
    local CPU simulation.  In-proc members share this process's chip and
    are not affected."""

    # host id -> live `serve` children there that score on the device
    _device_children: dict = {}

    def __init__(self, member_id: str, export_dir: str, *,
                 serving: ServingConfig, fleet: FleetConfig,
                 tele_dir: str, port: int,
                 python: Optional[str] = None,
                 host_id: str = "",
                 argv: Optional[list] = None,
                 env_extra: Optional[dict] = None):
        import subprocess
        import sys

        from .serve import DEVICE_ENGINES

        on_device = serving.engine in DEVICE_ENGINES
        if on_device:
            from ..launcher import pod
            peers = [p for p in self._device_children.get(host_id, ())
                     if p.poll() is None]
            self._device_children[host_id] = peers
            local = argv is None or argv[0] == sys.executable
            pod.require_one_chip_owner(
                len(peers) + 1,
                f"fleet member {member_id} (--engine {serving.engine}) on "
                f"host {host_id or 'local'!r}", local=local)
        self.member_id = member_id
        self.tele_dir = tele_dir
        os.makedirs(tele_dir, exist_ok=True)
        self.state = STATE_STANDBY
        self.generation = 0
        self.export_dir = export_dir
        self.host_id = host_id
        self.host, self.port = serving.host, port
        env = dict(os.environ)
        env["SHIFU_TPU_METRICS_DIR"] = tele_dir
        if host_id:
            env[ENV_FLEET_HOST] = host_id
        if env_extra:
            env.update(env_extra)
        # `argv` is the host plane's override: an ssh-wrapped command
        # from HostPlane.serve_command (launcher/pod.py transports);
        # default is a local child of this interpreter
        cmd = list(argv) if argv else [
            python or sys.executable, "-m",
            "shifu_tpu.launcher.cli", "serve", export_dir,
            "--engine", serving.engine, "--port", str(port),
            "--host", serving.host,
            "--heartbeat-s", str(fleet.heartbeat_every_s),
            "--heartbeat-misses", str(fleet.heartbeat_misses)]
        # own session: retire/kill signals the whole tree, never just
        # the CLI shim (launcher/supervisor.py's spawn contract)
        self.proc = subprocess.Popen(cmd, env=env,
                                     start_new_session=True)
        if on_device:
            self._device_children[host_id].append(self.proc)

    @property
    def version(self) -> Optional[int]:
        try:
            return self.stats().get("version")
        except Exception:
            return None

    def _client(self):
        from . import serve_wire
        return serve_wire.ServeClient(self.host, self.port, timeout=5.0)

    def swap(self, export_dir: str,
             engine: Optional[str] = None) -> dict:
        try:
            with self._client() as c:
                return c.swap(export_dir, engine=engine)
        except Exception as e:  # noqa: BLE001 — degrade like daemon.swap
            return {"ok": False,
                    "error": f"{type(e).__name__}: {e}"[:300]}

    def burns(self) -> list:
        try:
            slo = self.stats().get("slo") or {}
            return [(b.get("burn_fast", 0.0), b.get("burn_slow", 0.0))
                    for b in (slo.get("burns") or {}).values()]
        except Exception:
            return []

    def stats(self) -> dict:
        with self._client() as c:
            return c.stats()

    def kill(self) -> None:
        # state bookkeeping stays with the manager — see FleetMember.kill
        from ..launcher.supervisor import _kill_tree
        _kill_tree(self.proc, sig=None)

    def stop(self) -> None:
        import signal

        from ..launcher.supervisor import _kill_tree
        _kill_tree(self.proc, sig=signal.SIGTERM)
        self.state = STATE_RETIRED


# -- scale decisions -------------------------------------------------------


def decide_scale(burns: list, n_active: int, cfg: FleetConfig) -> str:
    """"up" / "down" / "hold" from per-member (fast, slow) burn pairs —
    pure, so the policy is unit-testable without a live fleet.

    Both windows must AGREE (the PR 8 multiwindow rule lifted to fleet
    scope): scale up when the worst member burns >= scale_up_burn on
    fast AND slow (a fast-only spike is noise; a slow-only burn is
    already recovering); scale down only when EVERY member is idle on
    both windows."""
    if not burns or n_active < 1:
        return "hold"
    worst_fast = max(f for f, _s in burns)
    worst_slow = max(s for _f, s in burns)
    if (worst_fast >= cfg.scale_up_burn
            and worst_slow >= cfg.scale_up_burn
            and n_active < cfg.max_daemons):
        return "up"
    if (worst_fast <= cfg.scale_down_burn
            and worst_slow <= cfg.scale_down_burn
            and n_active > cfg.min_daemons):
        return "down"
    return "hold"


# -- the manager -----------------------------------------------------------


class FleetManager:
    """Spawns and supervises N members + hot standbys, owns the router
    membership, runs the heartbeat monitor / swap-retry / scale loop.

    In-proc members only here (`member_factory` swaps in ProcessMember
    spawning for production); the drill-critical behaviors — lease
    expiry -> failover -> standby promotion, fleet swap with straggler
    quarantine + re-admission, burn-driven scale — are identical in both
    modes because they only touch leases, the member protocol, and the
    router table."""

    def __init__(self, export_dir: str, *,
                 fleet: Optional[FleetConfig] = None,
                 serving: Optional[ServingConfig] = None,
                 root_dir: Optional[str] = None,
                 loader: Optional[Callable] = None,
                 member_factory: Optional[Callable] = None,
                 model_id: str = "default"):
        import tempfile

        from .router import FleetRouter

        self.fleet = fleet or FleetConfig()
        self.fleet.validate()
        # per-member daemons inherit the serving config minus the wire
        # bind (each member binds its own ephemeral loopback port)
        base = serving or ServingConfig()
        self.serving = dataclasses.replace(base, port=0)
        self.export_dir = export_dir
        self.model_id = model_id
        self._loader = loader
        self._factory = member_factory or self._spawn_inproc
        self.root_dir = root_dir or tempfile.mkdtemp(prefix="fleet_")
        # the host plane: absent (hosts="") the fleet is single-host
        # in-proc exactly as before; `local:N`/host-list activates
        # cross-host placement + per-host artifact sync
        self.hosts: Optional[HostPlane] = (
            HostPlane(self.fleet.hosts, self.root_dir)
            if self.fleet.hosts else None)
        self.router = FleetRouter(self.fleet)
        # ingress trace sampling rides the serving config: the router
        # mints 1-in-N; members force-sample whatever arrives sampled
        self.router.trace_sample = self.serving.trace_sample
        self._lock = threading.RLock()
        # per-host clock-offset estimation (see _observe_skew)
        self._skew_offsets: dict = {}
        self._skew_published: dict = {}
        self._skew_samples: dict = {}
        self.members: dict[str, FleetMember] = {}   # in rotation or stale
        self.standbys: list[FleetMember] = []
        # split-brain ledger: DOWN members kept (not killed) awaiting
        # either a lease resurrection -> standby rejoin, or the reap
        # deadline -> kill.  member_id -> (member, downed_at_monotonic)
        self._downed: dict = {}
        # per-host verified artifact cache: (host_id, generation) ->
        # local synced path, so one host pulls each export exactly once
        self._sync_cache: dict = {}
        self._next_id = 0
        self._generation = 0
        self._running = False
        self._monitor_thread: Optional[threading.Thread] = None
        self._last_scale_t = 0.0
        self._failovers = 0

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "FleetManager":
        from .. import obs

        with self._lock:
            if self._running:
                return self
            self._running = True
            for _ in range(self.fleet.n_daemons):
                m = self._spawn()
                self._admit(m)
            for _ in range(self.fleet.standbys):
                self.standbys.append(self._spawn())
        obs.event("fleet_start", n_daemons=self.fleet.n_daemons,
                  standbys=self.fleet.standbys, path=self.export_dir,
                  heartbeat_every_s=self.fleet.heartbeat_every_s,
                  heartbeat_misses=self.fleet.heartbeat_misses)
        self._monitor_thread = threading.Thread(
            target=self._monitor_loop, daemon=True, name="fleet-monitor")
        self._monitor_thread.start()
        return self

    def stop(self) -> None:
        with self._lock:
            self._running = False
            members = list(self.members.values()) + list(self.standbys)
            downed = [m for m, _t in self._downed.values()]
            self.members.clear()
            self.standbys.clear()
            self._downed.clear()
        if self._monitor_thread is not None:
            self._monitor_thread.join(timeout=5)
        self.router.close()
        for m in members:
            if m.state not in (STATE_DOWN, STATE_RETIRED):
                try:
                    m.stop()
                except Exception:
                    pass
        for m in downed:
            # a blackholed-lease member in the DOWN ledger is still a
            # live daemon — it must not outlive the manager
            try:
                m.kill()
            except Exception:
                pass

    def __enter__(self) -> "FleetManager":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- membership ----------------------------------------------------

    def _spawn_inproc(self, member_id: str, tele_dir: str,
                      host_id: str = "") -> FleetMember:
        export = self.export_dir
        if host_id:
            export = self._host_artifact(host_id, export,
                                         self._generation,
                                         member=member_id)
        return FleetMember(member_id, export,
                           serving=self.serving, fleet=self.fleet,
                           tele_dir=tele_dir, loader=self._loader,
                           model_id=self.model_id, host_id=host_id)

    def _spawn(self):
        with self._lock:
            member_id = f"member-{self._next_id}"
            self._next_id += 1
            host_id = self.hosts.place() if self.hosts else ""
        tele_dir = os.path.join(self.root_dir, member_id)
        try:
            m = self._factory(member_id, tele_dir, host_id)
        except Exception:
            if self.hosts and host_id:
                self.hosts.release(host_id)
            raise
        m.generation = self._generation
        m._spawn_wall_t = time.time()  # standby-sweep warm-up grace
        return m

    # -- per-host artifact sync ----------------------------------------

    def _syncable(self, export_dir: str) -> bool:
        """Only real file trees sync: loader-scheme handles (stub://,
        the test loaders) and anything else fsio can't walk serve
        straight from the source path, exactly like the single-host
        fleet."""
        from ..data import fsio

        if self.hosts is None or not self.fleet.sync_artifacts:
            return False
        if fsio.is_remote(export_dir):
            return True
        return "://" not in export_dir and os.path.isdir(export_dir)

    def _host_artifact(self, host_id: str, export_dir: str,
                       generation: int, member: str = "") -> str:
        """The path a member on `host_id` should load `export_dir`
        from: the host's digest-verified local copy when the sync plane
        applies (pulled at most once per (host, generation) — the cache
        is what fleet-verify's exactly-once audit observes), else the
        source path itself.  Raises SyncError on a torn/corrupt pull."""
        if not host_id or not self._syncable(export_dir):
            return export_dir
        key = (host_id, int(generation), export_dir)
        with self._lock:
            hit = self._sync_cache.get(key)
        if hit:
            return hit
        dest = sync_artifact(export_dir, self.hosts.cache_dir(host_id),
                             generation, host=host_id, member=member)
        with self._lock:
            self._sync_cache[key] = dest
        return dest

    def _admit(self, m) -> None:
        """Into the membership table and router rotation (caller ensures
        it is on the current generation)."""
        m.state = STATE_ACTIVE
        self.members[m.member_id] = m
        self.router.add(m.member_id, m.host, m.port,
                        generation=m.generation,
                        host_id=getattr(m, "host_id", ""))

    def member_dirs(self) -> list:
        """Telemetry dirs of every member (active + standby + stale +
        DOWN-ledgered) — the `serving_rollup` / `shifu-tpu top` fleet
        view's input; downed members render DOWN off their aged lease."""
        with self._lock:
            return [m.tele_dir for m in self.members.values()] + \
                   [m.tele_dir for m in self.standbys] + \
                   [m.tele_dir for m, _t in self._downed.values()]

    def summary(self) -> dict:
        with self._lock:
            return {
                "active": [mid for mid, m in self.members.items()
                           if m.state == STATE_ACTIVE],
                "stale": [mid for mid, m in self.members.items()
                          if m.state == STATE_STALE],
                "standbys": [m.member_id for m in self.standbys],
                "down": sorted(self._downed),
                "hosts": list(self.hosts.host_ids) if self.hosts else [],
                "generation": self._generation,
                "failovers": self._failovers,
            }

    # -- heartbeat monitor + failover ----------------------------------

    def _monitor_loop(self) -> None:
        tick = self.fleet.heartbeat_every_s
        while self._running:
            time.sleep(min(tick, 0.2))
            if not self._running:
                return
            try:
                self.check_members()
                self._retry_stale()
                if self.fleet.scale_every_s > 0:
                    now = time.monotonic()
                    if now - self._last_scale_t \
                            >= self.fleet.scale_every_s:
                        self._last_scale_t = now
                        self.scale_tick()
            except Exception:
                # the control plane must outlive any single bad tick
                continue

    def check_members(self) -> list:
        """One monitor pass: expire leases, fail over, sweep dead
        standbys, tend the DOWN ledger (rejoin or reap).  Returns the
        member ids failed over this pass (tests drive this directly)."""
        ttl = self.fleet.heartbeat_ttl_s
        now = time.time()
        failed = []
        with self._lock:
            suspects = [m for m in self.members.values()
                        if m.state == STATE_ACTIVE]
        for m in suspects:
            lease = read_lease(m.tele_dir)
            self._observe_skew(lease, now)
            age = lease_age_s(lease, now=now)
            if age is None or age > ttl:
                self.failover(m, lease_age=age)
                failed.append(m.member_id)
        self._sweep_standbys(now, ttl)
        self._tend_downed(now, ttl)
        return failed

    def _observe_skew(self, lease: Optional[dict], now: float) -> None:
        """Per-host clock-offset estimation off the lease round-trips
        already flowing through the monitor: every fresh lease gives one
        sample of ``manager_now - member_lease_ts``.  True lease age is
        >= 0, so the RUNNING MIN of the samples approximates the host's
        clock offset (manager frame) with a positive bias bounded by one
        heartbeat period — tight enough to causally order cross-host
        journal events at failover scale (obs/timeline.py).  Publishes a
        `fleet_clock_skew` journal event per host on first observation
        and whenever the estimate moves > 5ms; |offset| is clamped to
        `timeline_max_offset_s` (a lease stamped by a wildly wrong clock
        must not fling the merge)."""
        if not self.fleet.timeline_skew_correct:
            return
        if not lease or not isinstance(lease.get("ts"), (int, float)):
            return
        host = lease.get("host")
        if not host:
            return  # single-host in-proc fleet: one clock, no offsets
        from .. import obs

        cap = self.fleet.timeline_max_offset_s
        sample = max(-cap, min(cap, now - float(lease["ts"])))
        with self._lock:
            n = self._skew_samples.get(host, 0) + 1
            self._skew_samples[host] = n
            prev = self._skew_offsets.get(host)
            est = sample if prev is None else min(prev, sample)
            self._skew_offsets[host] = est
            published = self._skew_published.get(host)
            if published is not None and abs(est - published) <= 0.005:
                return
            self._skew_published[host] = est
        obs.event("fleet_clock_skew", host=str(host),
                  offset_s=round(est, 4),
                  rtt_bound_s=round(self.fleet.heartbeat_every_s, 4),
                  samples=n)

    def _sweep_standbys(self, now: float, ttl: float) -> None:
        """A standby is only a standby while ITS lease is fresh: a dead
        one promoted during failover would turn one outage into two.
        Swept standbys are replaced so the warm pool keeps its depth."""
        from .. import obs

        grace = max(ttl, 2.0)  # spawn warm-up: process-mode children
        #                        write their first lease asynchronously
        with self._lock:
            pool = list(self.standbys)
        dead = []
        for s in pool:
            age = lease_age_s(read_lease(s.tele_dir), now=now)
            if age is not None and age <= ttl:
                continue
            if now - getattr(s, "_spawn_wall_t", now) < grace:
                continue
            dead.append(s)
        for s in dead:
            with self._lock:
                if s not in self.standbys:
                    continue
                self.standbys.remove(s)
            obs.event("fleet_standby_down", member=s.member_id,
                      host=getattr(s, "host_id", ""))
            try:
                s.kill()
            except Exception:
                pass
            if self.hosts and getattr(s, "host_id", ""):
                self.hosts.release(s.host_id)
            if self._running:
                try:
                    replacement = self._spawn()
                    with self._lock:
                        self.standbys.append(replacement)
                except Exception:
                    pass

    def _tend_downed(self, now: float, ttl: float) -> None:
        """The split-brain guard's second half.  A DOWN member whose
        lease RESURRECTS (its partition healed — the process was alive
        all along, only its lease writes were blackholed) rejoins as a
        STANDBY: its old slot already has a promoted replacement, and a
        direct re-promotion would double-serve the slot.  A member whose
        lease stays dead past the reap deadline is killed for real."""
        from .. import obs

        reap_after = max(10.0 * ttl, 5.0 * self.fleet.heartbeat_every_s)
        with self._lock:
            ledger = list(self._downed.items())
        for member_id, (m, downed_t) in ledger:
            age = lease_age_s(read_lease(m.tele_dir), now=now)
            if (age is not None and age <= ttl
                    and self.fleet.rejoin_standby):
                with self._lock:
                    if self._downed.pop(member_id, None) is None:
                        continue
                    gen = self._generation
                caught_up = m.generation == gen
                if not caught_up:
                    # catch the returnee up BEFORE it is promotable —
                    # a rejoined member must never serve a generation
                    # the barrier has left behind
                    try:
                        target = self._host_artifact(
                            getattr(m, "host_id", ""), self.export_dir,
                            gen, member=member_id)
                        r = m.swap(target)
                    except SyncError as e:
                        r = {"ok": False, "error": str(e)}
                    if r.get("ok"):
                        m.generation = gen
                        caught_up = True
                        obs.event("fleet_member_swap", member=member_id,
                                  generation=gen,
                                  host=getattr(m, "host_id", ""),
                                  via="rejoin",
                                  baseline_digest=r.get(
                                      "baseline_digest"))
                with self._lock:
                    m.state = STATE_STANDBY
                    self.standbys.append(m)
                obs.event("fleet_rejoin", member=member_id,
                          generation=m.generation, caught_up=caught_up,
                          host=getattr(m, "host_id", ""))
            elif time.monotonic() - downed_t > reap_after:
                with self._lock:
                    if self._downed.pop(member_id, None) is None:
                        continue
                try:
                    m.kill()
                except Exception:
                    pass
                if self.hosts and getattr(m, "host_id", ""):
                    self.hosts.release(m.host_id)

    def failover(self, member, lease_age: Optional[float] = None) -> None:
        """DOWN member out of rotation; a pre-warmed standby promoted in
        its place — the reference AM's backup-worker takeover, journaled
        as ONE `fleet_failover` event.  With a host plane the standby on
        a DIFFERENT host than the victim is preferred (anti-affinity: a
        whole-host loss must not promote onto the same dead host)."""
        from .. import obs

        t0 = time.perf_counter()
        promoted_swap = promoted_digest = None
        with self._lock:
            if self.members.get(member.member_id) is not member:
                return  # already handled (monitor/drill race)
            self.router.remove(member.member_id)
            del self.members[member.member_id]
            member.state = STATE_DOWN
            idx = 0
            victim_host = getattr(member, "host_id", "")
            if victim_host:
                for i, s in enumerate(self.standbys):
                    if getattr(s, "host_id", "") != victim_host:
                        idx = i
                        break
            standby = self.standbys.pop(idx) if self.standbys else None
            if standby is not None:
                if standby.generation != self._generation:
                    # a fleet swap landed while this standby idled:
                    # catch it up BEFORE it takes traffic (the barrier
                    # would refuse it anyway)
                    try:
                        target = self._host_artifact(
                            getattr(standby, "host_id", ""),
                            self.export_dir, self._generation,
                            member=standby.member_id)
                        r = standby.swap(target)
                    except SyncError as e:
                        r = {"ok": False, "error": str(e)}
                    if r.get("ok"):
                        standby.generation = self._generation
                        promoted_swap = self._generation
                        promoted_digest = r.get("baseline_digest")
                self.members[standby.member_id] = standby
                self._admit(standby)
                if standby.generation != self._generation:
                    # catch-up failed: serve nothing stale — quarantine
                    # behind the barrier and let the monitor's retry
                    # bring it up (the old code admitted it at the old
                    # generation and never retried)
                    standby.state = STATE_STALE
                    self.router.remove(standby.member_id)
            # the corpse goes to the DOWN ledger, NOT straight to
            # kill(): a blackholed-lease member is still alive and may
            # rejoin as a standby when its partition heals
            self._downed[member.member_id] = (member, time.monotonic())
            self._failovers += 1
        obs.counter("fleet_failover_total",
                    "members failed over after missed heartbeats").inc()
        obs.event("fleet_failover", member=member.member_id,
                  standby=standby.member_id if standby else None,
                  host=getattr(member, "host_id", ""),
                  standby_host=(getattr(standby, "host_id", "")
                                if standby else None),
                  lease_age_s=(round(lease_age, 3)
                               if lease_age is not None else None),
                  ttl_s=round(self.fleet.heartbeat_ttl_s, 3),
                  promoted_in_s=round(time.perf_counter() - t0, 4))
        if promoted_swap is not None:
            obs.event("fleet_member_swap", member=standby.member_id,
                      generation=promoted_swap,
                      host=getattr(standby, "host_id", ""),
                      via="promote", baseline_digest=promoted_digest)
        try:
            obs.flush()
        except Exception:
            pass
        # restore the standby pool AFTER journaling (a straggling spawn
        # must never delay the fleet_failover record) so the NEXT
        # failure also has a warm takeover
        if standby is not None and self._running:
            try:
                replacement = self._spawn()
                with self._lock:
                    if self._running:
                        self.standbys.append(replacement)
                    else:
                        replacement.stop()
            except Exception:
                pass  # degraded: fleet serves on without a standby

    def kill_host(self, host_id: str) -> list:
        """SIGKILL everything placed on `host_id` — the whole-host-loss
        drill (and the ssh transport's host-decommission path).  Dead
        standbys leave the pool immediately (a corpse must never be
        promoted); actives keep their slot until the lease verdict
        drives `failover`, exactly like a real host vanishing."""
        from .. import obs

        with self._lock:
            victims = [m for m in list(self.members.values())
                       + list(self.standbys)
                       if getattr(m, "host_id", "") == host_id]
        killed = []
        for m in victims:
            try:
                m.kill()
            except Exception:
                pass
            killed.append(m.member_id)
        with self._lock:
            dead_standbys = [s for s in self.standbys
                             if getattr(s, "host_id", "") == host_id]
            self.standbys = [s for s in self.standbys
                             if getattr(s, "host_id", "") != host_id]
        for s in dead_standbys:
            obs.event("fleet_standby_down", member=s.member_id,
                      host=host_id)
        return killed

    # -- fleet-wide hot swap -------------------------------------------

    def swap_fleet(self, export_dir: str,
                   engine: Optional[str] = None) -> dict:
        """One export -> every member (actives AND standbys, so a later
        promotion is already current).  Failures quarantine the member
        (STALE, out of rotation, journaled) and the monitor retries it;
        the swap barrier then refuses any member still on the old
        generation — after this returns, only new-version members serve.
        """
        from .. import obs

        with self._lock:
            self._generation += 1
            gen = self._generation
            self.export_dir = export_dir
            targets = list(self.members.values()) + list(self.standbys)
        swapped, failed = [], []
        for m in targets:
            try:
                # with a host plane each member loads its HOST's
                # digest-verified synced copy (pulled once per host —
                # the cache); a torn/corrupt pull fails this member's
                # swap exactly like a bad artifact would
                target = self._host_artifact(
                    getattr(m, "host_id", ""), export_dir, gen,
                    member=m.member_id)
                r = m.swap(target, engine=engine)
            except SyncError as e:
                r = {"ok": False, "error": f"sync: {e}"}
            if r.get("ok"):
                m.generation = gen
                m.export_dir = export_dir
                self.router.set_generation(m.member_id, gen)
                swapped.append(m.member_id)
                obs.event("fleet_member_swap", member=m.member_id,
                          generation=gen,
                          host=getattr(m, "host_id", ""), via="fanout",
                          baseline_digest=r.get("baseline_digest"))
            else:
                failed.append({"member": m.member_id,
                               "error": r.get("error")})
                with self._lock:
                    if m.member_id in self.members:
                        m.state = STATE_STALE
                        self.router.remove(m.member_id)
                obs.event("fleet_swap_degraded", member=m.member_id,
                          path=export_dir,
                          error=str(r.get("error"))[:300])
        # the barrier: from here the router refuses any member whose
        # generation predates this swap — stragglers stay refused until
        # the monitor's retry catches them up and re-admits them
        self.router.set_barrier(gen)
        obs.event("fleet_swap", path=export_dir, generation=gen,
                  swapped=swapped,
                  failed=[f["member"] for f in failed])
        return {"ok": not failed, "generation": gen,
                "swapped": swapped, "failed": failed}

    def _retry_stale(self) -> list:
        """Monitor leg: re-swap STALE members toward the current target;
        success re-admits them behind the barrier (`fleet_readmit`)."""
        from .. import obs

        with self._lock:
            stale = [m for m in self.members.values()
                     if m.state == STATE_STALE]
            target, gen = self.export_dir, self._generation
        readmitted = []
        for m in stale:
            try:
                # a member quarantined by a CORRUPT sync retries the
                # pull here — the per-host cache only holds verified
                # publishes, so a failed generation is re-pulled fresh
                host_target = self._host_artifact(
                    getattr(m, "host_id", ""), target, gen,
                    member=m.member_id)
                r = m.swap(host_target)
            except SyncError:
                continue
            if not r.get("ok"):
                continue
            m.generation = gen
            m.export_dir = target
            with self._lock:
                if self.members.get(m.member_id) is m:
                    self._admit(m)
                    self.router.set_generation(m.member_id, gen)
            readmitted.append(m.member_id)
            obs.event("fleet_member_swap", member=m.member_id,
                      generation=gen, host=getattr(m, "host_id", ""),
                      via="retry",
                      baseline_digest=r.get("baseline_digest"))
            obs.event("fleet_readmit", member=m.member_id,
                      generation=gen, path=target)
        return readmitted

    # -- scale loop ----------------------------------------------------

    def scale_tick(self, burns: Optional[list] = None) -> str:
        """One scale decision over the live members' burn pairs (or
        injected `burns` — deterministic tests).  "up" promotes a
        standby (or spawns fresh); "down" retires the least-burned
        member.  Journals `fleet_scale` on every non-hold action."""
        from .. import obs

        with self._lock:
            active = [m for m in self.members.values()
                      if m.state == STATE_ACTIVE]
        if burns is None:
            burns = []
            for m in active:
                pairs = m.burns()
                if pairs:
                    burns.append((max(f for f, _ in pairs),
                                  max(s for _, s in pairs)))
        action = decide_scale(burns, len(active), self.fleet)
        if action == "hold":
            return action
        n_before = len(active)
        if action == "up":
            with self._lock:
                grown = self.standbys.pop(0) if self.standbys else None
            if grown is None:
                grown = self._spawn()
            if grown.generation != self._generation:
                try:
                    target = self._host_artifact(
                        getattr(grown, "host_id", ""), self.export_dir,
                        self._generation, member=grown.member_id)
                    r = grown.swap(target)
                except SyncError as e:
                    r = {"ok": False, "error": str(e)}
                if r.get("ok"):
                    grown.generation = self._generation
                    obs.event("fleet_member_swap",
                              member=grown.member_id,
                              generation=self._generation,
                              host=getattr(grown, "host_id", ""),
                              via="scale",
                              baseline_digest=r.get("baseline_digest"))
            with self._lock:
                self.members[grown.member_id] = grown
                self._admit(grown)
                n_after = sum(1 for m in self.members.values()
                              if m.state == STATE_ACTIVE)
        else:
            # retire the least-burned active member, gracefully: drain,
            # don't drop — scale-down must never cost a request
            victim = active[-1]
            if burns and len(burns) == len(active):
                victim = min(zip(burns, active),
                             key=lambda p: p[0][0])[1]
            with self._lock:
                self.router.remove(victim.member_id)
                self.members.pop(victim.member_id, None)
                n_after = sum(1 for m in self.members.values()
                              if m.state == STATE_ACTIVE)
            try:
                victim.stop()
            except Exception:
                pass
            if self.hosts and getattr(victim, "host_id", ""):
                self.hosts.release(victim.host_id)
        worst_fast = max((f for f, _ in burns), default=0.0)
        worst_slow = max((s for _, s in burns), default=0.0)
        obs.counter("fleet_scale_total",
                    "burn-rate-driven fleet scale actions").inc(
            action=action)
        obs.event("fleet_scale", action=action, n_before=n_before,
                  n_after=n_after, burn_fast=round(worst_fast, 4),
                  burn_slow=round(worst_slow, 4))
        return action

    def push_burns(self) -> None:
        """Feed each member's fast-window burn to the router (overload
        shedding reads it) — monitor cadence in `shifu-tpu fleet`,
        direct calls in tests."""
        with self._lock:
            active = [m for m in self.members.values()
                      if m.state == STATE_ACTIVE]
        for m in active:
            pairs = m.burns()
            if pairs:
                self.router.set_burn(
                    m.member_id, max(f for f, _ in pairs))


# -- fleet-verify: the journal audit ---------------------------------------


def fleet_verify_events(events: list) -> dict:
    """`shifu-tpu fleet-verify` body (pure over journal events — the
    chaos-verify analog).  Audits the fleet's lifecycle invariants:

    - every `fleet_failover` promoted a standby (no unanswered loss)
    - `fleet_swap` generations strictly increase (no barrier rollback)
    - every swap reached every targeted member EXACTLY once — counting
      `fleet_member_swap` applications per (member, generation); a
      member that died before its retry (it appears in a later failover
      or standby-down record) is excused
    - no member's applied generation ever regresses
    - every `fleet_rejoin` follows that member's own failover — the
      split-brain guard's paper trail (nobody rejoins who never left)
    - within a generation, every member that reported a baseline-profile
      digest reported the SAME one — the drift observatory's "the whole
      fleet alerts against one frozen baseline" guarantee (a member with
      no digest is fine: artifact without a profile, drift disabled)
    """
    from collections import Counter

    failovers = [e for e in events if e.get("kind") == "fleet_failover"]
    swaps = [e for e in events if e.get("kind") == "fleet_swap"]
    applies = [e for e in events
               if e.get("kind") == "fleet_member_swap"]
    checks = []

    unanswered = [e.get("member") for e in failovers
                  if not e.get("standby")]
    checks.append({"check": "failover_promotion", "ok": not unanswered,
                   "detail": ("every failover promoted a standby"
                              if not unanswered else
                              f"no standby for: {unanswered}")})

    gens = [e.get("generation") for e in swaps]
    mono = (all(isinstance(g, int) for g in gens)
            and all(b > a for a, b in zip(gens, gens[1:])))
    checks.append({"check": "swap_generations_increase", "ok": mono,
                   "detail": f"fleet_swap generations: {gens}"})

    counts = Counter((e.get("member"), e.get("generation"))
                     for e in applies)
    dupes = sorted(f"{m}@gen{g}" for (m, g), n in counts.items()
                   if n > 1)
    checks.append({"check": "swap_applied_exactly_once",
                   "ok": not dupes,
                   "detail": ("no duplicate applications" if not dupes
                              else f"applied more than once: {dupes}")})

    died = {e.get("member") for e in failovers} | \
           {e.get("member") for e in events
            if e.get("kind") == "fleet_standby_down"}
    uncovered = []
    for e in swaps:
        g = e.get("generation")
        for mid in (list(e.get("swapped") or [])
                    + list(e.get("failed") or [])):
            if counts.get((mid, g), 0) == 0 and mid not in died:
                uncovered.append(f"{mid}@gen{g}")
    checks.append({"check": "swap_reached_every_member",
                   "ok": not uncovered,
                   "detail": ("every swap reached every live member"
                              if not uncovered else
                              f"never applied: {sorted(uncovered)}")})

    regressions, last_gen = [], {}
    for e in applies:
        mid, g = e.get("member"), e.get("generation")
        if not isinstance(g, int):
            continue
        if g < last_gen.get(mid, g):
            regressions.append(f"{mid}: gen{last_gen[mid]} -> gen{g}")
        last_gen[mid] = max(g, last_gen.get(mid, g))
    checks.append({"check": "member_generation_monotonic",
                   "ok": not regressions,
                   "detail": ("no per-member regressions"
                              if not regressions else
                              f"regressed: {regressions}")})

    ghost_rejoins, down_now = [], set()
    for e in events:
        kind = e.get("kind")
        if kind == "fleet_failover":
            down_now.add(e.get("member"))
        elif kind == "fleet_rejoin":
            if e.get("member") not in down_now:
                ghost_rejoins.append(e.get("member"))
            else:
                down_now.discard(e.get("member"))
    checks.append({"check": "rejoin_follows_failover",
                   "ok": not ghost_rejoins,
                   "detail": ("every rejoin had a prior failover"
                              if not ghost_rejoins else
                              f"rejoin without failover: {ghost_rejoins}")})

    gen_digests: dict = {}
    for e in applies:
        d = e.get("baseline_digest")
        if d:
            gen_digests.setdefault(e.get("generation"), set()).add(d)
    split = sorted(f"gen{g}: {sorted(ds)}"
                   for g, ds in gen_digests.items() if len(ds) > 1)
    checks.append({"check": "baseline_profile_consistent",
                   "ok": not split,
                   "detail": ("every generation served one baseline "
                              "profile" if not split else
                              f"digest split within generation: {split}")})

    ok = all(c["ok"] for c in checks)
    return {
        "verdict": "PASS" if ok else "FAIL",
        "checks": checks,
        "counts": {
            "failovers": len(failovers),
            "swaps": len(swaps),
            "member_swaps": len(applies),
            "rejoins": sum(1 for e in events
                           if e.get("kind") == "fleet_rejoin"),
            "degraded": sum(1 for e in events
                            if e.get("kind") == "fleet_swap_degraded"),
            "syncs": sum(1 for e in events
                         if e.get("kind") == "fleet_sync"),
        },
    }


def fleet_forever(export_dir: str, *, fleet: FleetConfig,
                  serving: ServingConfig, router_host: str,
                  router_port: int, root_dir: Optional[str] = None,
                  echo=print) -> int:
    """`shifu-tpu fleet` body: manager + router front-end until
    SIGINT/SIGTERM.  Returns a process exit code."""
    import signal

    from .. import obs
    from .router import RouterServer

    manager = FleetManager(export_dir, fleet=fleet, serving=serving,
                           root_dir=root_dir)
    manager.start()
    try:
        front = RouterServer(manager.router, host=router_host,
                             port=router_port, manager=manager).start()
    except OSError:
        manager.stop()
        raise
    stop_evt = threading.Event()

    def _stop(signum, _frame):
        echo(f"fleet: signal {signum} — draining")
        stop_evt.set()

    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            signal.signal(sig, _stop)
        except ValueError:
            pass  # non-main thread (tests)
    echo(f"fleet: {fleet.n_daemons} member(s) + {fleet.standbys} "
         f"standby(s) on {front.host}:{front.port} "
         f"(heartbeat {fleet.heartbeat_every_s}s x "
         f"{fleet.heartbeat_misses}, artifact {export_dir})")
    obs.event("fleet_serve_start", path=export_dir, port=front.port,
              n_daemons=fleet.n_daemons, pid=os.getpid())
    try:
        while not stop_evt.wait(max(fleet.heartbeat_every_s, 0.5)):
            manager.push_burns()
    except KeyboardInterrupt:
        pass
    front.close()
    manager.stop()
    echo("fleet: stopped — " + json.dumps(manager.router.router_stats()))
    return 0
