"""Persistent XLA compilation cache for job processes.

Every `train` CLI invocation (and every supervisor restart attempt — the
checkpoint-restart fault-tolerance story launches a fresh process per
attempt) retraces and recompiles the same programs; the reference paid the
same tax re-building its TF graph on every container start.  JAX's
persistent compilation cache turns those repeat compiles into
deserializations: on a TPU v5 lite the flagship train job's summed
`compile_s` read 6.85 s cold and 2.50 s warm (CHANGES.md PR 21;
`chip_smoke.py` prints both on every run).

Where the cache lives is decided from outside.  With
`JAX_COMPILATION_CACHE_DIR` set, JAX itself reads it at import and this
module sets no directory; without it the cache goes to one fixed path
inside the checkout (`<repo>/.jax_cache`, git-ignored).  The directory is
part of the cache key, so it is never derived from `~`, a temp name, a pid
or a time.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_DISABLE = "SHIFU_TPU_NO_COMPILE_CACHE"
ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# the directory the cache was enabled at.  Whether it served a compile is
# JAX's to say (obs/introspect.py listens to its `cache_hits` /
# `cache_misses` events); nothing here reads the directory
_active_dir: Optional[str] = None


def active_dir() -> Optional[str]:
    """The persistent-cache directory in use this process, or None."""
    return _active_dir


def enable_persistent_cache(min_compile_time_secs: float = 0.5
                            ) -> str | None:
    """Enable JAX's persistent compilation cache (idempotent).

    The directory is `JAX_COMPILATION_CACHE_DIR` when set — JAX has then
    already configured it and no directory is set here — else
    `DEFAULT_DIR`.  SHIFU_TPU_NO_COMPILE_CACHE=1 disables.  Returns the
    directory in use, or None when disabled or not creatable.

    `min_compile_time_secs` is the persistence floor: compiles faster
    than this are never written.  The 0.5s default fits the TRAIN path
    (multi-second epoch programs; skipping tiny helper jits keeps the
    cache dir from filling with entries that cost more to look up than
    to recompile).  The SERVING paths pass 0: the padded-bucket scorer
    programs compile in tens of milliseconds each, exactly the band the
    default silently skips — and a fleet member's cold-start is the sum
    of those "too small to persist" compiles.  Tradeoff of 0: every
    compile writes an entry, so the cache dir grows with each distinct
    shape; acceptable for the bounded bucket ladder, wasteful for
    unbounded-shape workloads."""
    if os.environ.get(ENV_DISABLE):
        return None
    import jax

    global _active_dir
    from_env = os.environ.get(ENV_DIR)
    path = from_env or DEFAULT_DIR
    try:
        os.makedirs(path, exist_ok=True)
    except OSError:
        return None  # e.g. a read-only install: the job runs uncached
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    # default thresholds skip small/fast programs; job programs are the
    # multi-second compiles this cache exists for, serving bucket
    # programs the sub-second ones (callers pick the floor)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    _active_dir = path
    from .. import obs
    # registry-only (sinks are usually configured later in run_train):
    # the scrape file records whether repeat compiles could deserialize
    obs.gauge("compile_cache_enabled",
              "1 when the persistent XLA compile cache is active").set(1)
    obs.event("compile_cache", directory=path)
    return path
