"""Seconds spent loading programs from JAX's persistent compilation cache
before the window: Σ `cache_retrieval_s` over the `startup` event's
`compiles` (the backend stage of each program the cache served: JAX's
`/jax/compilation_cache/cache_retrieval_time_sec` and the key's hashing
around it, obs/introspect.py).  0 on a cold run."""

from benchmarks import startup


def read(run: dict):
    ev = startup.event(run)
    if ev is None:
        return None
    return startup.compile_s(ev, "cache_retrieval_s")
