"""Multi-host initialization and coordination.

Control-plane successor of the reference's rendezvous machinery: an embedded
ZooKeeper in the ApplicationMaster collected each container's ip:port into a
ClusterSpec and published `/tensorflow_cluster/final`
(reference: appmaster/TensorflowSession.java:188-200,551-594; container side
TensorflowTaskExecutor.java:93-111).  On TPU the provisioner already knows the
slice topology, so rendezvous collapses to `jax.distributed.initialize` —
the coordinator address plays ZooKeeper's role, and the published "final
cluster" is simply `jax.devices()` spanning all hosts.

Environment contracts supported (first match wins):
- explicit args / SHIFU_TPU_COORDINATOR + SHIFU_TPU_NUM_PROCESSES +
  SHIFU_TPU_PROCESS_ID env vars,
- TPU pod metadata (jax.distributed.initialize() with no args — GKE/GCE
  autodetection),
- single-process fallback (no-op).
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

log = logging.getLogger(__name__)

ENV_COORDINATOR = "SHIFU_TPU_COORDINATOR"
ENV_NUM_PROCESSES = "SHIFU_TPU_NUM_PROCESSES"
ENV_PROCESS_ID = "SHIFU_TPU_PROCESS_ID"

_initialized = False


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Bring up the multi-host runtime; returns True if distributed init ran.

    Safe to call unconditionally: single-host jobs no-op.  Idempotent.
    """
    global _initialized
    if _initialized:
        return True

    coordinator = coordinator or os.environ.get(ENV_COORDINATOR)
    if num_processes is None and os.environ.get(ENV_NUM_PROCESSES):
        num_processes = int(os.environ[ENV_NUM_PROCESSES])
    if process_id is None and os.environ.get(ENV_PROCESS_ID):
        process_id = int(os.environ[ENV_PROCESS_ID])

    if coordinator:
        # CPU backends need an explicit cross-process collectives transport
        # (gloo) — the stand-in for ICI/DCN when simulating hosts locally;
        # must be set before backend init or collectives silently hang
        if "cpu" in str(jax.config.jax_platforms or "cpu"):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
        log.info("jax.distributed initialized: process %d/%d via %s",
                 jax.process_index(), jax.process_count(), coordinator)
        return True

    # TPU pod autodetection: only meaningful when the runtime reports >1
    # expected processes; otherwise stay single-process.
    if os.environ.get("TPU_WORKER_HOSTNAMES", "").count(",") >= 1:
        jax.distributed.initialize()
        _initialized = True
        log.info("jax.distributed auto-initialized: process %d/%d",
                 jax.process_index(), jax.process_count())
        return True

    return False


def is_chief() -> bool:
    """The logging/checkpoint-writing host — successor of the reference's
    chief worker (worker:0, ssgd_monitor.py:171-175)."""
    return jax.process_index() == 0


def barrier(name: str = "barrier") -> None:
    """Cross-host sync point (ZK-watch-latch successor): every process
    blocks until all have reached the barrier of this `name`."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)
