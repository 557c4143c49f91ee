"""Detached job submission: jobs that survive the submitting client.

A reference job ran under YARN and outlived its client — the client merely
polled the application report every 10s and tailed the progress log
(yarn/client/TensorflowClient.java:625-658,829-841); an operator could
disconnect and come back.  The pod/ssh gang here is deliberately tethered
to its dispatcher (parent death tears the gang down), so `train --detach`
re-launches the dispatcher as a session-leader daemon whose stdout goes to
`<job>/supervisor.log`, records `<job>/job.json`, and returns immediately;
the daemon writes `<job>/job.status` when the job ends.  `status`,
`attach`, and `kill` drive the job from its directory afterwards — the
poll/tail/kill surface the reference client had.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Optional, Sequence

# marks the daemonized dispatcher so run_train records its final status
ENV_DETACHED = "SHIFU_TPU_DETACHED_JOB_DIR"

JOB_FILE = "job.json"
STATUS_FILE = "job.status"
LOG_FILE = "supervisor.log"
BOARD_FILE = "console.board"


def submit(child_argv: Sequence[str], out_dir: str, echo=print) -> int:
    """Launch `python -m shifu_tpu.launcher.cli <child_argv>` as a detached
    session leader and return immediately (exit 0 = submitted)."""
    try:
        from ..data import fsio
        if fsio.is_remote(out_dir):
            echo("--detach needs a LOCAL job dir (job.json/pid live beside "
                 "the daemon); use a local --output whose board/checkpoint "
                 "paths may still be remote", )
            return 1
    except Exception:
        pass
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, LOG_FILE)
    env = dict(os.environ)
    env[ENV_DETACHED] = out_dir
    with open(log_path, "ab") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "shifu_tpu.launcher.cli", *child_argv],
            stdout=log, stderr=subprocess.STDOUT,
            stdin=subprocess.DEVNULL,
            start_new_session=True,  # survives the client's session/terminal
            env=env, cwd=os.getcwd())
    with open(os.path.join(out_dir, JOB_FILE), "w") as f:
        json.dump({"pid": proc.pid, "argv": list(child_argv),
                   "submitted_at": time.time(),
                   "host": os.uname().nodename}, f)
    echo(f"submitted: pid {proc.pid}, job dir {out_dir}")
    echo(f"  follow:  shifu-tpu attach {out_dir}")
    echo(f"  status:  shifu-tpu status {out_dir}")
    echo(f"  stop:    shifu-tpu kill {out_dir}")
    return 0


def write_status(out_dir: str, exit_code: int) -> None:
    """Called by the daemonized dispatcher when the job ends (job.status is
    the 'application report' a later `status` reads).

    Guarded by pid: ENV_DETACHED inherits into the dispatcher's whole tree
    (supervisor attempts, gang ranks), and a worker exiting mid-restart
    must not record ITS code as the job's terminal state — only the
    process `submit` recorded may write."""
    job = _read_json(os.path.join(out_dir, JOB_FILE))
    if not job or job.get("pid") != os.getpid():
        return
    try:
        with open(os.path.join(out_dir, STATUS_FILE), "w") as f:
            json.dump({"exit": int(exit_code), "finished_at": time.time()}, f)
    except OSError:
        pass


def _read_json(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


def _is_our_job(pid: int, job: Optional[dict]) -> bool:
    """Guard against stale/recycled pids and wrong-machine job dirs: the
    recorded pid must belong to a shifu_tpu dispatcher ON the recording
    host — an unclean daemon death followed by pid reuse must not make
    `kill` SIGKILL an innocent process tree.  Both spellings are matched:
    `python -m shifu_tpu...` AND the installed `shifu-tpu` console script
    (whose cmdline carries only the hyphenated form)."""
    if job and job.get("host") and job["host"] != os.uname().nodename:
        return False
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            cmd = f.read()
        return b"shifu_tpu" in cmd or b"shifu-tpu" in cmd
    except OSError:
        # no /proc (or no permission): fall back to pid liveness alone
        return True


def job_state(out_dir: str) -> dict:
    """One dict describing the job: RUNNING / FINISHED(exit) / FAILED /
    UNKNOWN, plus the last board line when there is one."""
    job = _read_json(os.path.join(out_dir, JOB_FILE))
    status = _read_json(os.path.join(out_dir, STATUS_FILE))
    out: dict = {"job_dir": out_dir}
    if job:
        out.update(pid=job.get("pid"), submitted_at=job.get("submitted_at"))
    try:  # surface an acquired-but-unreleased slice (provision.json)
        from .provision import read_marker
        marker = read_marker(out_dir)
        if marker and marker.get("name"):
            out["provisioned_slice"] = marker["name"]
    except Exception:
        pass
    if status is not None:
        rc = int(status.get("exit", 1))
        out.update(state="FINISHED" if rc == 0 else "FAILED", exit=rc,
                   finished_at=status.get("finished_at"))
    elif (job and isinstance(job.get("pid"), int) and _alive(job["pid"])
          and _is_our_job(job["pid"], job)):
        out["state"] = "RUNNING"
    elif job:
        # pid gone with no status file: the daemon was killed uncleanly
        out.update(state="DEAD", exit=None)
    else:
        out["state"] = "UNKNOWN"
    board = os.path.join(out_dir, BOARD_FILE)
    try:
        with open(board) as f:
            lines = f.read().splitlines()
        if lines:
            out["last_progress"] = lines[-1]
    except OSError:
        pass
    try:  # telemetry summary when the job dir carries a run journal (obs/)
        tele = _telemetry_quick_summary(
            os.path.join(out_dir, "telemetry", "journal.jsonl"))
        if tele:
            out["telemetry"] = tele
    except Exception:
        pass
    try:  # checkpoint retention: kept steps + GC'd totals (recovery ladder)
        ckpt = _checkpoint_summary(out_dir)
        if ckpt:
            out["checkpoints"] = ckpt
    except Exception:
        pass
    return out


def _checkpoint_summary(out_dir: str) -> Optional[dict]:
    """Kept checkpoint steps (the recovery ladder's rungs) from the job's
    default tmp_model dir, plus GC'd-step totals from the scrape file —
    bounded work (one listing + one small file), fit for status polls."""
    ckpt_dir = os.path.join(out_dir, "tmp_model")
    if not os.path.isdir(ckpt_dir):
        return None
    kept = sorted(int(n) for n in os.listdir(ckpt_dir)
                  if n.isdigit() and os.path.isdir(os.path.join(ckpt_dir, n)))
    verified = sum(
        1 for s in kept
        if os.path.exists(os.path.join(ckpt_dir, f"manifest-{s}.json")))
    summary = {"kept_steps": kept, "manifests": verified}
    prom = os.path.join(out_dir, "telemetry", "metrics.prom")
    try:
        from ..obs.render import parse_scrape_totals
        with open(prom) as f:
            totals = parse_scrape_totals(f.read())
        if "checkpoint_gc_total" in totals:
            summary["gc_steps"] = int(totals["checkpoint_gc_total"])
        if "checkpoint_gc_bytes_total" in totals:
            summary["gc_freed_bytes"] = int(
                totals["checkpoint_gc_bytes_total"])
    except OSError:
        pass
    return summary


def _telemetry_quick_summary(jpath: str) -> Optional[dict]:
    """Bounded journal probe for `status` polls: count newlines in one
    chunked pass and json-decode ONLY the last complete line — a long run
    journals tens of thousands of events, and a status poll must not pay
    an O(run-length) decode each call (`shifu-tpu metrics` does the full
    parse on demand)."""
    if not os.path.exists(jpath):
        return None
    n = 0
    tail = b""
    with open(jpath, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                break
            n += chunk.count(b"\n")
            # 64 KiB window: a host_skew event on a large pod can exceed
            # 4 KiB in ONE line, and a tail that holds only a mid-line
            # fragment would report last_event=null on a healthy journal
            tail = (tail + chunk)[-65536:]
    last_kind = None
    goodput = None
    hbm = None
    serving = None
    slo_firing: dict = {}
    slo_seen: set = set()
    for line in reversed(tail.splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if not isinstance(rec, dict):
            continue
        if last_kind is None:
            last_kind = rec.get("kind")
        if serving is None and rec.get("kind") == "serving_report":
            # latest serving_report within the tail: the at-a-glance
            # daemon health next to goodput (docs/SERVING.md telemetry)
            serving = {"requests": rec.get("requests"),
                       "scores_per_sec": rec.get("scores_per_sec"),
                       "p99_ms": rec.get("p99_ms"),
                       "queue_depth": rec.get("queue_depth"),
                       "errors": rec.get("errors")}
        if rec.get("kind") == "slo_alert":
            # walk is newest-first: the FIRST state seen per objective is
            # its current one — firing objectives are the active alerts
            obj = str(rec.get("objective", "?"))
            if obj not in slo_seen:
                slo_seen.add(obj)
                if rec.get("state") == "firing":
                    slo_firing[obj] = {
                        "burn_fast": rec.get("burn_fast"),
                        "observed_p99_ms": rec.get("observed_p99_ms")}
        if goodput is None and rec.get("kind") == "goodput":
            # latest goodput ledger record within the tail window: the
            # at-a-glance "is the job actually stepping" number
            # (docs/OBSERVABILITY.md "Goodput ledger"); a run that never
            # emitted one (pre-ledger journal) just omits the key
            goodput = {"epoch": rec.get("epoch"),
                       "goodput_fraction": rec.get("goodput_fraction")}
        if hbm is None and rec.get("kind") == "hbm_watermark":
            # latest HBM watermark (obs/devprof.py): the at-a-glance
            # "how close to the memory cliff" number next to goodput
            hbm = {"epoch": rec.get("epoch"),
                   "peak_bytes": rec.get("peak_bytes"),
                   "bytes_in_use": rec.get("bytes_in_use"),
                   "source": rec.get("source")}
        if last_kind is not None and goodput is not None and hbm is not None:
            break
    out = {"events": n, "last_event": last_kind}
    if goodput is not None:
        out["goodput"] = goodput
    if hbm is not None:
        out["hbm"] = hbm
    if serving is not None:
        out["serving"] = serving
    if slo_seen:
        out["slo"] = {"firing": sorted(slo_firing),
                      "alerts": slo_firing}
    return out


def run_status(out_dir: str, echo=print) -> int:
    st = job_state(out_dir)
    echo(json.dumps(st))
    if st["state"] == "UNKNOWN":
        return 1
    return 0


def attach(out_dir: str, echo=print, poll_seconds: float = 0.5,
           from_start: bool = True) -> int:
    """Follow the job's console board until it finishes — the reference
    client's TailThread over the HDFS progress file
    (TensorflowClient.java:829-841).  Returns the job's exit code."""
    try:
        from ..data import fsio
        if fsio.is_remote(out_dir):
            # remote job dir: follow the board object from ANY machine that
            # can read it (no local pid/status to consult — ^C to stop)
            from .console import tail_board
            for line in tail_board(fsio.join(out_dir, BOARD_FILE),
                                   from_start=from_start):
                echo(line)
            return 0
    except KeyboardInterrupt:
        return 0
    board = os.path.join(out_dir, BOARD_FILE)
    pos = 0
    if not from_start and os.path.exists(board):
        pos = os.path.getsize(board)
    try:
        while True:
            if os.path.exists(board):
                with open(board) as f:
                    f.seek(pos)
                    chunk = f.read()
                    pos = f.tell()
                for line in chunk.splitlines():
                    echo(line)
            st = job_state(out_dir)
            if st["state"] in ("FINISHED", "FAILED"):
                # drain anything written between the read and the status
                if os.path.exists(board):
                    with open(board) as f:
                        f.seek(pos)
                        for line in f.read().splitlines():
                            echo(line)
                echo(f"job {st['state'].lower()} (exit {st.get('exit')})")
                return int(st.get("exit") or 0)
            if st["state"] in ("DEAD", "UNKNOWN"):
                echo(f"job state: {st['state']}")
                return 1
            time.sleep(poll_seconds)
    except KeyboardInterrupt:
        return 0  # stop following; the job keeps running


def _release_slice(out_dir: str, echo, force: bool = False,
                   killed_pid: Optional[int] = None) -> bool:
    """Best-effort release of a provisioned slice the job dir records —
    killing the application frees its compute (YARN-RM parity), and an
    unclean dispatcher death must not leave a billing TPU behind.

    Guarded at THIS level so every kill() branch gets it: when the marker
    records a LIVE provisioning dispatcher (a foreground `--provision` run
    — it writes no job.json, so a stale job.json in the same dir must not
    bypass the check) or was written on another host (this host's pid
    table proves nothing), refuse unless `force`.  Returns False when the
    release was refused."""
    try:
        from .provision import read_marker, release_from_marker
        marker = read_marker(out_dir)
        if marker and not force:
            mpid = marker.get("pid")
            mhost = marker.get("host")
            if mhost and mhost != os.uname().nodename:
                echo(f"provision marker was written on {mhost!r} — run kill "
                     "there (its pid table can check dispatcher liveness) "
                     "or re-run with --force")
                return False
            # A detached --provision job's marker pid IS the job pid; when
            # kill() just signalled that exact pid, _alive can still answer
            # True for a just-SIGKILLed (or zombie) process — that is not a
            # live foreground dispatcher, so the guard must not fire.
            if (isinstance(mpid, int) and mpid != killed_pid
                    and _alive(mpid) and _is_our_job(mpid, marker)):
                echo(f"provision marker records a LIVE dispatcher (pid "
                     f"{mpid}) — a foreground --provision run is still "
                     "using the slice; SIGTERM that process (or re-run "
                     "with --force) instead")
                return False
        release_from_marker(out_dir, echo=echo)
        return True
    except Exception as e:
        echo(f"provision: release check failed ({e}); see provision.json "
             f"in {out_dir}")
        return True


def kill(out_dir: str, echo=print, grace_seconds: float = 10.0,
         force: bool = False) -> int:
    """SIGTERM the detached dispatcher's process group (it is a session
    leader, so the whole supervisor->gang tree drains), escalating to
    SIGKILL; the client-side 'kill application' the reference had.  Also
    releases a provisioned slice the job dir records (provision.json) —
    including one left behind by an earlier unclean daemon death."""
    job = _read_json(os.path.join(out_dir, JOB_FILE))
    if not job or not isinstance(job.get("pid"), int):
        echo(f"no submitted job under {out_dir}")
        # a FOREGROUND --provision run writes no job.json but may have
        # left a provision.json trail (unclean dispatcher death) — the
        # rescue release must still run.  _release_slice refuses when the
        # marker records a LIVE dispatcher or a foreign host (a stray
        # `kill` must not delete the slice under a live gang).
        _release_slice(out_dir, echo, force=force)
        return 1
    pid = job["pid"]
    if not _alive(pid):
        echo(f"job pid {pid} is not running")
        # exit 1 when a recorded slice was deliberately NOT released (live
        # foreground dispatcher / foreign host): the operator must act
        return 0 if _release_slice(out_dir, echo, force=force) else 1
    if not _is_our_job(pid, job):
        echo(f"pid {pid} is not this job's dispatcher (recycled pid or a "
             f"different host — job.json says {job.get('host')!r}); "
             "refusing to signal it")
        if not (job.get("host") and job["host"] != os.uname().nodename):
            # same host, recycled pid: the dispatcher is truly gone — a
            # recorded slice can still be released safely (the marker
            # guard in _release_slice still protects a separate live
            # foreground run sharing this dir)
            _release_slice(out_dir, echo, force=force)
        return 1
    try:
        os.killpg(pid, signal.SIGTERM)
    except (ProcessLookupError, PermissionError, OSError):
        try:
            os.kill(pid, signal.SIGTERM)
        except OSError:
            pass
    deadline = time.monotonic() + grace_seconds
    while time.monotonic() < deadline:
        if not _alive(pid):
            echo(f"job pid {pid} terminated")
            return 0 if _release_slice(out_dir, echo, force=force) else 1
        time.sleep(0.2)
    try:
        os.killpg(pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        pass
    # give the kernel a beat to reap: _alive() answers True for a
    # just-SIGKILLed or zombie process, which would trip the live-
    # dispatcher guard on the marker we are about to release
    reap_deadline = time.monotonic() + 2.0
    while time.monotonic() < reap_deadline and _alive(pid):
        time.sleep(0.1)
    echo(f"job pid {pid} killed")
    return 0 if _release_slice(out_dir, echo, force=force,
                               killed_pid=pid) else 1
