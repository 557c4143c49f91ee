"""XLA cost introspection: what every compiled program costs, journaled.

The jit entry points the hot paths build (train/step.py's step/scan/eval
programs, the export scorer's forward) route through `instrument_jit`
instead of bare `jax.jit`.  The wrapper is transparent at call time (one
`_cache_size()` probe per dispatch); when a call triggers a compile it:

- journals an `xla_compile` event — function name, compile wall
  (`compile_s`: the compiling call's wall, i.e. trace + XLA compile +
  first dispatch), per-program `cost_analysis()` (FLOPs, bytes
  accessed) and `memory_analysis()` (argument/output/temp/code bytes,
  derived peak), and the persistent-cache verdict from
  utils/compilecache.py (`cache`: off / miss / hit);
- feeds the registry: `xla_compiles_total{fn}`,
  `xla_compile_seconds`, `xla_flops{fn}` / `xla_bytes_accessed{fn}` /
  `xla_peak_bytes{fn}` gauges;
- credits the compile wall to the active goodput ledger's `compile`
  bucket (obs/goodput.py), so a recompile-heavy epoch shows up as lost
  goodput, not as a mysteriously slow "step".

Cost capture itself runs the AOT path (`fn.lower(avals).compile()`),
which pays a SECOND compile of the program.  That is nearly free on CPU
(tier-1, tests) but real seconds on a TPU, so capture defaults to
CPU-only.  The FLOP count itself is sound there: on a TPU v5 lite with
jax 0.9.0 / libtpu 0.0.34 `cost_analysis()["flops"]` of the flagship
train step reads 139,330 per sample against the analytic 138,600 (ratio
1.005; `chip_smoke.py` prints it on every run, CHANGES.md PR 21 has this
reading).  `SHIFU_TPU_XLA_COST=1` forces capture everywhere (accepting
the recompile; the persistent cache usually absorbs it), `=0` disables
even on CPU.  The `xla_compile` event itself is always journaled —
capture gates only the cost/memory fields.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Callable, Iterator, Optional

ENV_COST = "SHIFU_TPU_XLA_COST"

_lock = threading.Lock()
# fn name -> {"compiles": n, "compile_s": total, "flops": last,
#             "bytes_accessed": last, "peak_bytes": last}
_stats: dict[str, dict] = {}


def capture_enabled() -> bool:
    """Whether cost/memory capture (the second AOT compile) is on."""
    mode = os.environ.get(ENV_COST, "auto").lower()
    if mode in ("1", "on", "true", "force"):
        return True
    if mode in ("0", "off", "false"):
        return False
    try:  # auto: CPU backends only (see module docstring)
        import jax
        return jax.default_backend() == "cpu"
    except Exception:
        return False


def stats() -> dict[str, dict]:
    """Per-function compile/cost stats captured so far this process."""
    with _lock:
        return {k: dict(v) for k, v in _stats.items()}


def dispatch_counts() -> dict[str, int]:
    """Per-function dispatch tallies (every call, compiling or cached).
    The device flight recorder snapshots this around a trace window to
    scale per-dispatch cost_analysis numbers to the work the window
    actually executed (obs/devprof.roofline_join)."""
    with _lock:
        return {k: int(v.get("dispatches", 0)) for k, v in _stats.items()}


def _aval(x):
    """Shape/dtype/sharding abstraction of a pytree leaf — enough to
    re-lower without touching buffers (donated args stay untouched).

    Only mesh placements (NamedSharding) ride into the aval: the real
    dispatch may freely move an uncommitted single-device array (a bare
    jnp.arange riding next to mesh-placed state), but an aval's explicit
    SingleDeviceSharding would make the AOT lowering reject the mix as
    "incompatible devices"."""
    import jax
    from jax.sharding import NamedSharding

    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return x  # static / python leaf: pass through
    sharding = getattr(x, "sharding", None)
    if isinstance(sharding, NamedSharding):
        try:
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        except TypeError:
            pass
    return jax.ShapeDtypeStruct(shape, dtype)


def _normalize_cost(ca) -> dict:
    """cost_analysis() returns a dict on some backends, a 1-list of
    dicts on others; empty when unavailable."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca if isinstance(ca, dict) else {}


def _analyze(fn, args, kwargs) -> dict:
    """AOT cost/memory analysis for one signature (the second compile —
    gated by capture_enabled at the call site)."""
    import jax

    avals_args, avals_kwargs = jax.tree_util.tree_map(_aval, (args, kwargs))
    compiled = fn.lower(*avals_args, **avals_kwargs).compile()
    out: dict = {}
    try:
        cost = _normalize_cost(compiled.cost_analysis())
        if "flops" in cost:
            out["flops"] = float(cost["flops"])
        if "bytes accessed" in cost:
            out["bytes_accessed"] = float(cost["bytes accessed"])
    except Exception:
        pass
    try:
        mem = compiled.memory_analysis()
        arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
        out_b = int(getattr(mem, "output_size_in_bytes", 0))
        tmp_b = int(getattr(mem, "temp_size_in_bytes", 0))
        alias_b = int(getattr(mem, "alias_size_in_bytes", 0))
        out.update(argument_bytes=arg_b, output_bytes=out_b,
                   temp_bytes=tmp_b,
                   generated_code_bytes=int(getattr(
                       mem, "generated_code_size_in_bytes", 0)),
                   # the program's device-memory high water: live args +
                   # outputs + XLA temporaries, donated aliases counted once
                   peak_bytes=max(arg_b + out_b + tmp_b - alias_b, 0))
    except Exception:
        pass
    return out


def _record_compile(name: str, fn, args, kwargs, wall_s: float,
                    capture: Optional[bool] = None) -> dict:
    """Journal + registry + goodput for one observed compile; returns
    the captured analysis (possibly empty).  Never raises."""
    from ..utils import compilecache
    from . import _sinks, goodput, metrics as metrics_mod

    analysis: dict = {}
    try:
        if capture_enabled() if capture is None else capture:
            analysis = _analyze(fn, args, kwargs)
    except Exception:
        analysis = {}
    try:
        cache = compilecache.observe_compile()
    except Exception:
        cache = "off"
    try:
        with _lock:
            st = _stats.setdefault(name, {"compiles": 0, "compile_s": 0.0})
            st["compiles"] += 1
            st["compile_s"] = round(st["compile_s"] + wall_s, 6)
            st.update({k: analysis[k] for k in
                       ("flops", "bytes_accessed", "peak_bytes")
                       if k in analysis})
        metrics_mod.counter(
            "xla_compiles_total",
            "XLA compiles observed per instrumented function").inc(fn=name)
        metrics_mod.histogram(
            "xla_compile_seconds",
            "compiling-call wall (trace + compile + first dispatch)",
        ).observe(wall_s, fn=name)
        if "flops" in analysis:
            metrics_mod.gauge(
                "xla_flops", "per-dispatch FLOPs of the last compiled "
                "program (cost_analysis)").set(analysis["flops"], fn=name)
        if "bytes_accessed" in analysis:
            metrics_mod.gauge(
                "xla_bytes_accessed", "per-dispatch HBM bytes of the last "
                "compiled program").set(analysis["bytes_accessed"], fn=name)
        if "peak_bytes" in analysis:
            metrics_mod.gauge(
                "xla_peak_bytes", "device-memory high water of the last "
                "compiled program").set(analysis["peak_bytes"], fn=name)
        goodput.note("compile", wall_s)
        _sinks.event("xla_compile", fn=name, compile_s=round(wall_s, 6),
                     cache=cache, **analysis)
    except Exception:
        pass
    return analysis


class InstrumentedJit:
    """jax.jit with compile observation (see module docstring).  Drop-in
    for the call/lower surface the code base uses; `donate_argnums` etc.
    pass straight through to jit."""

    def __init__(self, fun: Callable, name: str, **jit_kwargs) -> None:
        import jax

        self._fn = jax.jit(fun, **jit_kwargs)
        self.name = name
        # resolved ONCE: the env read + backend probe must not ride the
        # per-batch dispatch path (the flag is process-stable in practice;
        # flipping SHIFU_TPU_XLA_COST applies to fns built after the flip)
        self._capture = capture_enabled()

    def _note_dispatch(self) -> None:
        # per-name dispatch tally: a plain dict bump (GIL-atomic enough —
        # an off-by-one under a race is noise next to the window sizes
        # devprof divides by), skipped until the first compile creates
        # the stats entry, so the steady-state cost is one dict.get
        st = _stats.get(self.name)
        if st is not None:
            st["dispatches"] = st.get("dispatches", 0) + 1

    def __call__(self, *args, **kwargs):
        fn = self._fn
        try:
            n0 = fn._cache_size()
        except Exception:
            n0 = None
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
        if n0 is not None:
            try:
                compiled = fn._cache_size() > n0
            except Exception:
                compiled = False
            if compiled:
                _record_compile(self.name, fn, args, kwargs, wall,
                                capture=self._capture)
        self._note_dispatch()
        return out

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)


def instrument_jit(fun: Callable, name: str, **jit_kwargs) -> InstrumentedJit:
    """`jax.jit(fun, **jit_kwargs)` + compile/cost observation under
    `name` — the spelling train/step.py and the export scorer use."""
    return InstrumentedJit(fun, name, **jit_kwargs)


@contextlib.contextmanager
def compile_span(name: str, **fields) -> Iterator[None]:
    """Journal a compile that happens outside an instrumented jit (the
    export path's jax_export lowering, AOT warmups): times the block and
    emits the same `xla_compile` event shape, minus the cost fields."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        wall = time.perf_counter() - t0
        try:
            from ..utils import compilecache
            from . import _sinks, goodput, metrics as metrics_mod

            with _lock:
                st = _stats.setdefault(name,
                                       {"compiles": 0, "compile_s": 0.0})
                st["compiles"] += 1
                st["compile_s"] = round(st["compile_s"] + wall, 6)
            metrics_mod.counter(
                "xla_compiles_total",
                "XLA compiles observed per instrumented function",
            ).inc(fn=name)
            metrics_mod.histogram(
                "xla_compile_seconds",
                "compiling-call wall (trace + compile + first dispatch)",
            ).observe(wall, fn=name)
            goodput.note("compile", wall)
            _sinks.event("xla_compile", fn=name, compile_s=round(wall, 6),
                         cache=compilecache.observe_compile(), **fields)
        except Exception:
            pass


def reset_for_tests() -> None:
    with _lock:
        _stats.clear()


# re-exported through obs/__init__ for call sites
__all__ = ["instrument_jit", "InstrumentedJit", "compile_span",
           "capture_enabled", "stats", "reset_for_tests"]
