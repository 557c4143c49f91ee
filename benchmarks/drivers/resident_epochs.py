"""The driver of training cells whose data lives in the device-resident
tier: `shifu_tpu.train.train(job, train_ds, valid_ds)` on in-memory
datasets, in the process that holds the chip.

One `train()` call holds set-up's end and the whole window.  Set-up: rows
from the seed (`datagen`), then the call's start - tier preparation, the H2D
of the resident blocks, every program compiled or loaded from the persistent
cache - and its first epoch from the seed.  The window opens at that epoch's
boundary (`epoch_callback`) on the same state and the same compiled
programs, and closes at the first epoch boundary `--seconds` later, where
the callback ends the call.  Throughput is the rows trained between the two
boundaries over the wall between them: every step, every per-epoch
evaluate, every epoch boundary.  Loading is set-up, as the builder's
contract has it, and shows in `setup_s`.

`correct`, once the window has closed and `memory_peak_bytes` is read: a
second `train()` call of one epoch from the same seed has to repeat the
first epoch's errors bit for bit (`replay_gap`, limit 0) and hands back the
state after that epoch, which is read (`compare.grad_norms`, `compare.change_norms`) and freed; the
plain reference then follows the same epoch's steps over the same rows, and
the gaps of `compare.training_gaps` are held to the cell's limits.
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import threading
import time
from concurrent.futures import Future

import jax
import numpy as np

from .. import compare, datagen, harness, refrun, tracered


def build_job(config: dict, params: dict, seed: int, epochs: int):
    """The JobConfig a user with this model and this much data writes."""
    from shifu_tpu.config.schema import JobConfig

    n_num, n_cat = config["num_numeric"], config.get("num_categorical", 0)
    with_weight = bool(config.get("with_weight"))
    columns = [{"index": 0, "name": "target", "is_target": True}]
    if with_weight:
        columns.append({"index": 1, "name": "wgt", "is_weight": True})
    first = len(columns)
    for i in range(n_num + n_cat):
        is_cat = i >= n_num
        columns.append({"index": first + i, "name": f"f{i}",
                        "is_selected": True, "is_categorical": is_cat,
                        "vocab_size": config["vocab_size"] if is_cat else 0})
    opt = config["optimizer"]
    job = {
        "schema": {"columns": columns, "target_index": 0,
                   "weight_index": 1 if with_weight else -1,
                   "selected_indices": list(range(first,
                                                  first + n_num + n_cat))},
        "data": {"batch_size": config["batch_size"],
                 "valid_ratio": config["valid_ratio"],
                 "shuffle_seed": seed % (1 << 31),
                 "device_resident_bytes": params["device_resident_bytes"]},
        "model": {k: config[k] for k in (
            "model_type", "hidden_nodes", "activations", "embedding_dim",
            "num_heads", "dropout_rate", "param_dtype", "compute_dtype")
            if k in config},
        "train": {"epochs": epochs, "loss": config["loss"], "seed": seed,
                  "eval_every_epochs": config["eval_every_epochs"],
                  "optimizer": {"name": opt["name"],
                                "learning_rate": opt["learning_rate"]}},
    }
    for section, extra in config.get("job", {}).items():
        job.setdefault(section, {}).update(extra)
    if params.get("checkpoint", "off") != "off":
        raise harness.BenchError("this driver runs with no checkpoint "
                                 "directory; a resume cell is another driver")
    return JobConfig.from_dict(job).validate()


def build_mesh(job, devices):
    """None on one chip; on several, the mesh the CLI builds: the job's
    `runtime.mesh` group where it names a topology, else data parallelism
    over the cell's chips."""
    if len(devices) == 1:
        return None
    from shifu_tpu.parallel import data_parallel_mesh, make_mesh

    if job.runtime.mesh.num_devices > 1:
        return make_mesh(job.runtime.mesh, devices)
    return data_parallel_mesh(len(devices))


def _rows(config: dict, params: dict, seed: int) -> tuple[dict, dict]:
    """(train rows, valid rows) from the seed, as host arrays."""
    n_train = int(params["train_rows"])
    ratio = float(config["valid_ratio"])
    n_valid = int(round(n_train * ratio / (1.0 - ratio)))
    return (datagen.make_rows(config, n_train, seed, datagen.TRAIN_STREAM),
            datagen.make_rows(config, n_valid, seed, datagen.VALID_STREAM))


def prepare(config: dict, params: dict, seed: int) -> Future:
    """Called by `run.py` before it looks for the chip: the rows are made
    on a thread of their own while JAX reaches it - both are set-up, and
    neither needs the other.  A daemon thread, so that a run that finds no
    chip exits at once."""
    rows: Future = Future()

    def make():
        try:
            rows.set_result(_rows(config, params, seed))
        except BaseException as e:  # raised again where the rows are taken
            rows.set_exception(e)

    threading.Thread(target=make, daemon=True, name="perfbench-rows").start()
    return rows


def _datasets(config: dict, params: dict, seed: int, ahead=None):
    from shifu_tpu.data.pipeline import TabularDataset

    train_rows, valid_rows = (ahead.result() if ahead is not None
                              else _rows(config, params, seed))
    return (train_rows, valid_rows, TabularDataset(**train_rows),
            TabularDataset(**valid_rows))


def _adadelta_e_g(opt_state):
    """Adadelta's running mean of squared gradients, wherever the
    optimizer's state keeps it."""
    nodes = jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda n: hasattr(n, "e_g"))
    for n in nodes:
        if hasattr(n, "e_g"):
            return n.e_g
    raise harness.BenchError("the optimizer state holds no Adadelta e_g")


def observe_state(state, config: dict, seed: int) -> dict:
    """What the program's state after its first epoch says, then free it:
    first the gradient norms, then the optimizer's slots go, and only then
    are the reference's initial weights made beside the parameters, so that
    this reading never holds more than the window does."""
    model = harness.load_module("reference", config["model_type"])
    grad = compare.grad_norms(_adadelta_e_g(state.opt_state))
    for leaf in jax.tree_util.tree_leaves(state.opt_state):
        if hasattr(leaf, "delete"):
            leaf.delete()
    params0 = jax.jit(lambda: model.init_params(config, seed))()
    change = compare.change_norms(state.params, params0)
    for leaf in jax.tree_util.tree_leaves((state.params, params0)):
        leaf.delete()
    return {"grad": grad, "change": change}


class _SliceTrace:
    """Trace a few whole epochs of the window, from outside: started and
    stopped in `epoch_callback`, each traced epoch under a mark of its
    own."""

    def __init__(self, after: int, count: int):
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        self.first = max(after, 1) + 1      # epochs first..last are traced
        self.last = self.first + max(count, 1) - 1
        self.done = False
        self.overhead_s = 0.0   # spent starting and stopping the profiler
        self._mark = None
        self._on = False

    def _open_mark(self, epoch: int):
        self._mark = jax.profiler.TraceAnnotation(
            f"{tracered.MARK_PREFIX}e{epoch}")
        self._mark.__enter__()

    def _close_mark(self):
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
            self._mark = None

    def callback(self, done_epoch: int):
        t0 = time.perf_counter()
        try:
            self._callback(done_epoch)
        finally:
            self.overhead_s += time.perf_counter() - t0

    def _callback(self, done_epoch: int):
        if done_epoch + 1 == self.first and not self.done:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host TraceMe events name the gaps
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._on = True
            self._open_mark(self.first)
        elif self._on and done_epoch < self.last:
            self._close_mark()
            self._open_mark(done_epoch + 1)
        elif self._on:
            self.stop()

    def stop(self):
        if self._on:
            self._close_mark()
            jax.profiler.stop_trace()
            self._on = False
        self.done = True

    def reduce(self, module_prefix: str) -> dict:
        path = tracered.find_xplane(self.dir)
        try:
            if path is None:
                return {}
            return tracered.reduce(tracered.load_events(path), module_prefix)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


_CACHE_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": 0,
                 "/jax/compilation_cache/cache_hits": 0}
_listening = False


def _count_cache_event(event: str, **_kw) -> None:
    if event in _CACHE_EVENTS:
        _CACHE_EVENTS[event] += 1


def _compiles_not_served() -> int:
    """Compile requests so far that JAX's persistent cache did not serve,
    by JAX's own events: the journal's hit/miss verdict is read off a
    directory listing, which the harness's own programs also write to."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_count_cache_event)
        _listening = True
    return (_CACHE_EVENTS["/jax/compilation_cache/compile_requests_use_cache"]
            - _CACHE_EVENTS["/jax/compilation_cache/cache_hits"])


class _WindowClosed(Exception):
    """Raised from `epoch_callback` to end the window's `train()` call."""


class _Window:
    """The window, kept by `epoch_callback`: opens at the first epoch's
    boundary, closes at the first boundary `seconds` later (and not before a
    traced slice is whole)."""

    def __init__(self, seconds: float, t_start: float, journal, tracer):
        self.seconds, self.t_start = seconds, t_start
        self.journal, self.tracer = journal, tracer
        self.first = None           # the first epoch's EpochMetrics
        self.history: list = []     # the window's epochs' EpochMetrics
        self.setup_s = self.t0 = self.t1 = self.overhead_s = 0.0
        self.mark = self.compiles0 = self.compiles = 0

    def __call__(self, m) -> None:
        now = time.perf_counter()
        if self.first is None:
            self.first = m
            self.setup_s = time.time() - self.t_start
            self.mark = len(self.journal.records)
            self.compiles0 = _compiles_not_served()
            self.t0 = time.perf_counter()
            return
        self.history.append(m)
        self.t1 = now
        if self.tracer is not None:   # what the profiler took before t1
            self.overhead_s = self.tracer.overhead_s
        self.compiles = _compiles_not_served() - self.compiles0
        if self.tracer is not None:
            self.tracer.callback(m.epoch)
        if self.wall_s >= self.seconds and (
                self.tracer is None or self.tracer.done):
            raise _WindowClosed

    @property
    def wall_s(self) -> float:
        """The window's wall; in a traced run, less what starting and
        stopping the profiler took inside it, which no untraced run pays
        and which would dilute every share of the wall."""
        return self.t1 - self.t0 - self.overhead_s


#: the window's call is ended by its callback, not by its count of epochs
_MANY_EPOCHS = 100_000


def first_epoch_state(train, config, params, seed, train_ds, valid_ds,
                      devices) -> dict:
    """What the program says after one epoch from the seed: its errors and
    the norms of its state, which is freed."""
    job = build_job(config, params, seed, 1)
    res = train(job, train_ds, valid_ds, mesh=build_mesh(job, devices),
                console=lambda s: None)
    prog = observe_state(res.state, config, seed)
    prog["train_error"] = res.history[0].train_error
    prog["valid_error"] = res.history[0].valid_error
    return prog


def run(ctx: harness.Context) -> harness.Outcome:
    from shifu_tpu import obs
    from shifu_tpu.train import train
    from shifu_tpu.utils.compilecache import enable_persistent_cache

    log = ctx.log or (lambda s: None)
    config, params, seed = ctx.config, ctx.params, ctx.seed
    # every program is kept, whatever its compile time: a second run finds
    # all of them, the small ones too
    enable_persistent_cache(min_compile_time_secs=0.0)
    _compiles_not_served()
    journal = obs.RunJournal(None)   # in memory: the readers get the records
    obs.set_journal(journal)

    # -- set-up: the rows, then the call's start and its first epoch --------
    train_rows, valid_rows, train_ds, valid_ds = _datasets(
        config, params, seed, ctx.prepared)
    log(f"rows made: {train_ds.num_rows} train, {valid_ds.num_rows} valid")
    tracer = None
    if ctx.trace:
        tracer = _SliceTrace(int(params.get("trace_after_epoch", 1)),
                             int(params.get("trace_epochs", 2)))
    win = _Window(ctx.seconds, ctx.t_start, journal, tracer)
    job = build_job(config, params, seed, _MANY_EPOCHS)
    try:
        train(job, train_ds, valid_ds, mesh=build_mesh(job, ctx.devices),
              console=lambda s: None, epoch_callback=win)
    except _WindowClosed:
        pass    # -- the window closed at an epoch boundary ----------------
    finally:
        if tracer:
            tracer.stop()
    if win.first is None or not win.history:
        raise harness.BenchError("the window's call ended before its window")
    batch = int(config["batch_size"])
    steps_per_epoch = train_ds.num_rows // batch
    epochs_done = len(win.history)
    rows_trained = epochs_done * steps_per_epoch * batch
    window_records = journal.records[win.mark:]
    finite = [np.isfinite(m.train_error) for m in win.history]
    peak = harness.memory_peak_bytes(ctx.devices)
    log(f"set-up {win.setup_s:.2f} s; window: {epochs_done} epochs, "
        f"{rows_trained} rows, {win.wall_s:.3f} s")
    obs.set_journal(None)
    gc.collect()    # the ended call's state and resident blocks go

    # -- correct: the first epoch again, then the reference over it ---------
    trace = tracer.reduce(params["step_module"]) if tracer else {}
    prog = first_epoch_state(train, config, params, seed, train_ds, valid_ds,
                             ctx.devices)
    del train_ds, valid_ds
    ref = refrun.first_epoch(config, seed, train_rows, valid_rows, log=log)
    gaps, notes = compare.training_gaps(prog, ref)
    gaps["replay_gap"] = max(
        abs(win.first.train_error - prog["train_error"]),
        abs(win.first.valid_error - prog["valid_error"]))
    gaps["compiles_in_window"] = float(win.compiles)
    limits = harness.load_limits(ctx.cell["name"])["limits"]
    checks = {k: (gaps.get(k), lim) for k, lim in limits.items()}
    log(f"widest leaves: {notes}")
    log(f"read, and held to no limit: "
        f"{ {k: v for k, v in gaps.items() if k not in limits} }")

    chips = len(ctx.devices)
    counts = harness.load_module("counts", config["model_type"])
    run_view = {
        "wall_s": win.wall_s, "rows": rows_trained, "chips": chips,
        "steps_per_epoch": steps_per_epoch, "epochs": epochs_done,
        "journal": window_records, "trace": trace, "peaks": ctx.peaks,
        "flops_per_sample": counts.flops_per_sample(config),
        "bytes_per_step": counts.bytes_per_step(config, batch),
        "batch": batch, "memory_peak_bytes": peak,
        "compiles_in_window": win.compiles,
    }
    return harness.Outcome(
        checks=checks,
        attempted=epochs_done * steps_per_epoch,
        failed=sum(steps_per_epoch for ok in finite if not ok),
        end_to_end={
            "train_samples_per_s_per_chip":
                rows_trained / win.wall_s / chips,
            "setup_s": win.setup_s},
        run=run_view, memory_peak_bytes=peak)
