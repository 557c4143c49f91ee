"""Seconds in `init_state()` with the wait for its result (startup phase
`startup/init_state`: model and optimizer built, the parameters'
initialisation run on the device), less the compile seconds JAX reported
under that span, which `startup_trace_lower_s`, `startup_compile_s` and
`startup_cache_load_s` hold."""

from benchmarks import startup

PATH = "startup/init_state"


def read(run: dict):
    ev = startup.event(run)
    if ev is None:
        return None
    return max(startup.phase_s(ev, PATH) - startup.compile_s(
        ev, *startup.COMPILE_FIELDS, under=PATH), 0.0)
