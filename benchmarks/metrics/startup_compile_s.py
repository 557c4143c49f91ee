"""Seconds the backend compiled before the window: Σ `backend_compile_s`
over the `startup` event's `compiles` (JAX's
`/jax/core/compile/backend_compile_duration` of the programs its persistent
cache did not serve, obs/introspect.py).  0 when the cache served every
program, so it says whether a reading of `setup_s` was a cold one."""

from benchmarks import startup


def read(run: dict):
    ev = startup.event(run)
    if ev is None:
        return None
    return startup.compile_s(ev, "backend_compile_s")
