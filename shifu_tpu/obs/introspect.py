"""XLA cost introspection: what every compiled program costs, journaled.

The jit entry points the hot paths build (train/step.py's step/scan/eval
programs, the export scorer's forward) route through `instrument_jit`
instead of bare `jax.jit`.  The wrapper is transparent at call time (one
`_cache_size()` probe per dispatch); when a call triggers a compile it:

- journals an `xla_compile` event — function name, compile wall
  (`compile_s`: the compiling call's wall, i.e. trace + XLA compile +
  first dispatch), that wall split by JAX's own durations (`trace_s`,
  `lower_s`, `backend_compile_s`, `cache_retrieval_s`) with JAX's own
  persistent-cache verdict (`cache`: off / miss / hit; "JAX's durations"
  below), per-program `cost_analysis()` (FLOPs, bytes accessed) and
  `memory_analysis()` (argument/output/temp/code bytes, derived peak);
- feeds the registry: `xla_compiles_total{fn}`, `xla_compile_seconds`;
- credits the compile wall to the active goodput ledger's `compile`
  bucket (obs/goodput.py), so a recompile-heavy epoch shows up as lost
  goodput, not as a mysteriously slow "step".

JAX's durations.  `jax.monitoring` publishes what each stage of a compile
took (`/jax/core/compile/jaxpr_trace_duration`,
`.../jaxpr_to_mlir_module_duration`, `.../backend_compile_duration`,
`/jax/compilation_cache/cache_retrieval_time_sec`) and whether the
persistent cache served the program (`/jax/compilation_cache/cache_hits`,
`.../cache_misses`).  Two listeners, registered once a process
(`listen()`), only append what arrives to a list; `_record_compile` /
`compile_span` - which run only after a compile was seen - take what
arrived on their thread during the compiling call.  What arrives outside
any instrumented call (the jits under `init_state`, helper jits) is kept
under the span path that was open then.  Every compile so split is also
kept in a short log (`compile_mark()` / `compiles_since()`), from which
`train()` writes its `startup` event's `compiles`.

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

import collections
import contextlib
import os
import threading
import time
from typing import Any, Callable, Iterator, Optional

from . import spans

ENV_COST = "SHIFU_TPU_XLA_COST"

_lock = threading.Lock()
# fn name -> {"compiles": n, "compile_s": total, "flops": last,
#             "bytes_accessed": last, "peak_bytes": last}
_stats: dict[str, dict] = {}


# JAX's event -> the field of the split its seconds go to, or the verdict
# it is
_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
SPLIT_FIELDS = ("trace_s", "lower_s", "backend_compile_s",
                "cache_retrieval_s")

_listening = False
# what the listeners saw and nobody took yet:
# (thread id, field or verdict, seconds, perf_counter at arrival, span path)
_arrived: list[tuple] = []
# every compile split so far, newest last: (sequence number, entry)
_compile_log: collections.deque = collections.deque(maxlen=4096)
_compile_seq = 0


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    field = _EVENTS.get(event)
    if field is not None:
        _arrived.append((threading.get_ident(), field, float(duration_secs),
                         time.perf_counter(), spans.current_path()))


def _on_event(event: str, **_kw) -> None:
    _on_duration(event, 0.0)


def listen() -> None:
    """Register the two `jax.monitoring` listeners, once a process.  They
    run only where JAX traces, lowers or compiles, never on a dispatch."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    try:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
    except Exception:
        pass  # telemetry must never fail the call it measures


def _split(arrived: list[tuple]) -> dict:
    """`trace_s`, `lower_s`, `backend_compile_s`, `cache_retrieval_s` and
    the `cache` verdict of what the listeners saw.

    JAX's stages nest: tracing a function traces the jitted functions it
    calls, an operation on a constant compiles inside a trace, the cache's
    retrieval is inside `backend_compile_duration`.  A duration arrives
    when its stage ends, so it covers [arrival - seconds, arrival], and
    whatever started inside that interval on the same thread is its child:
    each stage is counted for its own seconds less its children's, and the
    fields sum to the time the thread spent in any of them.  The backend
    stage of a program the cache served (a retrieval is its child) is the
    cache's work - the key, the read, the load - and counts as
    `cache_retrieval_s`, so `backend_compile_s` is 0 when nothing was
    compiled.  `cache` is `miss` where a program was compiled and written,
    else `hit` where one was served, else `off`: the cache took no part
    (disabled, or every program was under its persistence floor)."""
    out = dict.fromkeys(SPLIT_FIELDS, 0.0)
    verdicts = set()
    open_by_thread: dict[int, list] = {}
    for tid, field, secs, t_end, _path in arrived:
        if field in ("hit", "miss"):
            verdicts.add(field)
            continue
        start = t_end - secs
        stack = open_by_thread.setdefault(tid, [])
        own, served = secs, field == "cache_retrieval_s"
        while stack and stack[-1][0] >= start - 2e-5:
            _, child_field, child_secs = stack.pop()
            own -= child_secs
            served = served or child_field == "cache_retrieval_s"
        stack.append((start, field, secs))
        if field == "backend_compile_s" and served:
            field = "cache_retrieval_s"
        out[field] += max(own, 0.0)
    split = {k: round(v, 6) for k, v in out.items()}
    split["cache"] = ("miss" if "miss" in verdicts else
                      "hit" if "hit" in verdicts else "off")
    return split


def _take(since: float) -> list[tuple]:
    """What arrived on this thread since perf_counter `since`, taken off
    the list.  The rest of what was there belonged to no instrumented call
    (it is older, or another thread's): it goes to the log under the span
    paths it arrived in."""
    with _lock:
        n = len(_arrived)
        taken = _arrived[:n]
        del _arrived[:n]   # a listener's append meanwhile lands behind n
    me = threading.get_ident()
    mine: list[tuple] = []
    by_path: dict[str, list] = {}
    for a in taken:
        if a[0] == me and a[3] >= since:
            mine.append(a)
        else:
            by_path.setdefault(a[4], []).append(a)
    for path, rest in by_path.items():
        _log_compile(path or "(no span)", path, _split(rest))
    return mine


def _log_compile(fn: str, span: str, split: dict) -> None:
    global _compile_seq
    with _lock:
        _compile_seq += 1
        _compile_log.append((_compile_seq, {"fn": fn, "span": span, **split}))


def compile_mark() -> int:
    """A mark for `compiles_since` (and the listeners are on from here)."""
    listen()
    return _compile_seq


def compiles_since(mark: int) -> list[dict]:
    """One entry a compile split since `compile_mark()` gave `mark`: `fn`
    (an instrumented function's name, or the span path open when JAX
    compiled outside one), `span` (the span path open then), the four
    durations and `cache`."""
    _take(float("inf"))   # what no instrumented call claimed, by span path
    with _lock:
        return [dict(e) for seq, e in _compile_log if seq > mark]


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


def _observe(name: str, wall_s: float, split: dict, **fields) -> None:
    """Registry + goodput + log + journal for one compile of `wall_s`
    seconds, split by `_split`."""
    from . import _sinks, goodput, metrics as metrics_mod

    with _lock:
        st = _stats.setdefault(name, {"compiles": 0, "compile_s": 0.0})
        st["compiles"] += 1
        st["compile_s"] = round(st["compile_s"] + wall_s, 6)
        st.update({k: fields[k] for k in
                   ("flops", "bytes_accessed", "peak_bytes") if k in fields})
    metrics_mod.counter(
        "xla_compiles_total",
        "XLA compiles observed per instrumented function").inc(fn=name)
    metrics_mod.histogram(
        "xla_compile_seconds",
        "compiling-call wall (trace + compile + first dispatch)",
    ).observe(wall_s, fn=name)
    goodput.note("compile", wall_s)
    _log_compile(name, spans.current_path(), split)
    _sinks.event("xla_compile", fn=name, compile_s=round(wall_s, 6),
                 **split, **fields)


def _record_compile(name: str, fn, args, kwargs, wall_s: float,
                    capture: Optional[bool] = None,
                    notes: Optional[dict] = None) -> dict:
    """Journal + registry + goodput for one observed compile; returns
    the captured analysis (possibly empty).  Never raises."""
    try:
        # what JAX published during the call that just ended, before the
        # capture below compiles the program a second time
        split = _split(_take(time.perf_counter() - wall_s))
    except Exception:
        split = {}
    analysis: dict = {}
    try:
        if capture_enabled() if capture is None else capture:
            t0 = time.perf_counter()
            analysis = _analyze(fn, args, kwargs)
            _take(t0)   # the capture's own compile is no one's
    except Exception:
        analysis = {}
    try:
        _observe(name, wall_s, split, **analysis, **(notes or {}))
    except Exception:
        pass
    return analysis


_notes = threading.local()


def note(**fields) -> None:
    """Add trace-time facts (numbers, summed by key) to the `xla_compile`
    event of the instrumented program being traced on this thread: what a
    step builder decided while the program was traced (the fused Adadelta
    apply's leaves and bytes).  Outside such a trace it does nothing."""
    got = getattr(_notes, "fields", None)
    if got is not None:
        for k, v in fields.items():
            got[k] = got.get(k, 0) + v


class InstrumentedJit:
    """jax.jit with compile observation (see module docstring).  Drop-in
    for the call/lower surface the code base uses; `donate_argnums` etc.
    pass straight through to jit."""

    def __init__(self, fun: Callable, name: str, **jit_kwargs) -> None:
        import jax

        listen()
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
        outer, _notes.fields = getattr(_notes, "fields", None), {}
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kwargs)
        finally:
            notes, _notes.fields = _notes.fields, outer
        wall = time.perf_counter() - t0
        if n0 is not None:
            try:
                compiled = fn._cache_size() > n0
            except Exception:
                compiled = False
            if compiled:
                _record_compile(self.name, fn, args, kwargs, wall,
                                capture=self._capture, notes=notes)
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
    listen()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        wall = time.perf_counter() - t0
        try:
            _observe(name, wall, _split(_take(t0)), **fields)
        except Exception:
            pass


def reset_for_tests() -> None:
    with _lock:
        _stats.clear()
        _compile_log.clear()
        del _arrived[:]


# re-exported through obs/__init__ for call sites
__all__ = ["instrument_jit", "InstrumentedJit", "compile_span", "note",
           "capture_enabled", "stats", "reset_for_tests", "listen",
           "compile_mark", "compiles_since"]
