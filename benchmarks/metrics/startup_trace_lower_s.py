"""Seconds JAX spent tracing and lowering before the window: Σ `trace_s` +
`lower_s` over the `startup` event's `compiles` (JAX's
`/jax/core/compile/jaxpr_trace_duration` and
`.../jaxpr_to_mlir_module_duration`, obs/introspect.py).  Paid cold or warm:
the persistent cache is looked up by the lowered module."""

from benchmarks import startup


def read(run: dict):
    ev = startup.event(run)
    if ev is None:
        return None
    return startup.compile_s(ev, "trace_s", "lower_s")
