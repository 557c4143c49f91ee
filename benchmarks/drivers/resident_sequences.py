"""The driver of training cells whose rows are fixed-width sequences of token
ids and live in the device-resident tier: `shifu_tpu.train.train(job,
train_ds, valid_ds)` on in-memory datasets, in the process that holds the
chip.

What it shares with `drivers/resident_epochs.py` (imported, not copied): the
window kept by `epoch_callback` and its throughput, the traced slice, the
count of compiles the cache did not serve, `build_job` and `build_mesh`, the
reading of the program's state (`observe_state`), the order of `correct`
(the first epoch again, `replay_gap` 0, then the plain reference over the
same epoch, `compare.training_gaps` held to the cell's limits).

What it replaces, and why: the row generator (`datagen._chunk` always draws
a whole chunk of 1,048,576 rows, 17 GB of draws at 4,096 id columns: here a
chunk is 512 rows) and the reference run (`refrun` evaluates in batches of
65,536 rows; here the valid rows are one batch, and the planted faults
include this model's own, the routed experts' sum left out).  It adds one
check, `tokens_dropped` (limit 0), from the `moe` events the window
journalled, and leaves out of the parameters' change the leaves whose
reference gradient is zero on most of its entries (`sparse_leaves`).

The configuration's top-level keys are its published `config.json`'s;
`model_group` words them as the program's `block_stack` group.
"""

from __future__ import annotations

import gc
import statistics
import threading
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from .. import compare, harness
from ..reference import common
from . import resident_epochs as shared

CHUNK_ROWS = 512
TRAIN_STREAM, VALID_STREAM = 0, 1
_TRUTH = 7

#: the configuration's keys that the program's group takes under their name
_SAME_KEYS = (
    "hidden_size", "norm_eps", "mamba_num_heads", "mamba_head_dim",
    "n_groups", "ssm_state_size", "conv_kernel", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_experts_per_tok",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "routed_scaling_factor")


def _fixed_in_the_program() -> dict:
    """What the one published stack states and the program holds as
    constants, under the configuration's keys."""
    from shifu_tpu.models import block_stack as bs
    from shifu_tpu.ops import ssd

    return {"chunk_size": ssd.CHUNK, "time_step_min": bs.TIME_STEP_MIN,
            "time_step_max": bs.TIME_STEP_MAX,
            "time_step_floor": bs.TIME_STEP_FLOOR,
            "published_layers": bs.RESCALE_LAYERS}


def model_group(config: dict) -> dict:
    """The `model` section of the job a user writes for this configuration:
    the block stack, its pattern and its published widths.  A configuration
    that states another value than a constant of the program is refused
    here, not run as something else."""
    dep = config["deployment"]
    for key, held in _fixed_in_the_program().items():
        stated = (dep if key == "published_layers" else config)[key]
        if stated != held:
            raise harness.BenchError(
                f"the configuration states {key} = {stated}; the program "
                f"holds {held} as a constant (models/block_stack.py, "
                "ops/ssd.py)")
    return {
        "model_type": "block_stack", "hidden_nodes": [], "activations": [],
        "remat": bool(config.get("remat", False)),
        "block_stack": {
            **{k: config[k] for k in _SAME_KEYS},
            "pattern": config["hybrid_override_pattern"],
            "n_routed_experts": dep["router_experts"],
            "experts_held": config["n_routed_experts"],
            "first_expert_held": dep["first_expert_held"]}}


def build_job(config: dict, params: dict, seed: int, epochs: int):
    job = {k: dict(v) for k, v in config.get("job", {}).items()}
    job.setdefault("model", {}).update(model_group(config))
    return shared.build_job(dict(config, job=job), params, seed, epochs)


# -- the rows ---------------------------------------------------------------

def _rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), *path])))


def make_rows(spec: dict, num_rows: int, seed: int, stream: int) -> dict:
    """{"features" (N, T) float32 token ids, "target" (N, 1), "weight"
    (N, 1)} as host arrays: ids `floor(V u**id_skew)`; the target drawn from
    a seeded logistic ground truth (an effect a token, summed over the row
    and scaled by 1.5 / sqrt(T), plus noise); weights uniform in [0.5, 2)
    where the schema has a weight column.  Chunk `i` of 512 rows depends on
    (seed, stream, i) alone and is always drawn whole, so a row does not
    depend on how many were asked for."""
    t, vocab = int(spec["num_categorical"]), int(spec["vocab_size"])
    effect = _rng(seed, _TRUTH).standard_normal(vocab, dtype=np.float32)
    out = {"features": np.empty((num_rows, t), np.float32),
           "target": np.empty((num_rows, 1), np.float32),
           "weight": np.ones((num_rows, 1), np.float32)}
    for index, lo in enumerate(range(0, num_rows, CHUNK_ROWS)):
        keep = min(CHUNK_ROWS, num_rows - lo)
        rng = _rng(seed, stream, index)
        u = rng.random((CHUNK_ROWS, t), dtype=np.float32)
        ids = np.minimum((vocab * u ** float(spec.get("id_skew", 1.0)))
                         .astype(np.int32), vocab - 1)
        logits = (1.5 / np.sqrt(t)) * effect[ids].sum(axis=1)
        logits += float(spec.get("label_noise", 0.5)) * rng.standard_normal(
            CHUNK_ROWS, dtype=np.float32)
        target = rng.random(CHUNK_ROWS, dtype=np.float32) < 1.0 / (
            1.0 + np.exp(-logits))
        weight = rng.uniform(0.5, 2.0, CHUNK_ROWS)
        out["features"][lo:lo + keep] = ids[:keep]
        out["target"][lo:lo + keep, 0] = target[:keep]
        if spec.get("with_weight"):
            out["weight"][lo:lo + keep, 0] = weight[:keep]
    return out


def _rows(config: dict, params: dict, seed: int) -> tuple[dict, dict]:
    n_train = int(params["train_rows"])
    ratio = float(config["valid_ratio"])
    n_valid = int(round(n_train * ratio / (1.0 - ratio)))
    return (make_rows(config, n_train, seed, TRAIN_STREAM),
            make_rows(config, n_valid, seed, VALID_STREAM))


def prepare(config: dict, params: dict, seed: int) -> Future:
    """The rows, on a thread of their own while JAX reaches the chip."""
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


# -- the program's first epoch, and the reference's -------------------------

def first_epoch_state(train, config, params, seed, train_ds, valid_ds,
                      devices) -> dict:
    """What the program says after one epoch from the seed: its errors and
    the norms of its state, which is freed."""
    job = build_job(config, params, seed, 1)
    res = train(job, train_ds, valid_ds,
                mesh=shared.build_mesh(job, devices), console=lambda s: None)
    prog = shared.observe_state(res.state, config, seed)
    prog["train_error"] = res.history[0].train_error
    prog["valid_error"] = res.history[0].valid_error
    return prog


#: the faults planted in the reference put in the program's place
FAULTS = ("", "half_batch", "no_routed")


def make_reference_epoch(row, lr: float, compute: str, half_batch: bool):
    """`epoch(params, slots, blocks) -> (params, slots, loss_sum)`: one
    optimizer step a leading index of `blocks`, in order, as
    `common.make_epoch` has it, with the gradient of a batch taken a row at
    a time and summed: the batch's loss is a sum over its rows, and a row's
    backward pass then follows its own forward pass, where a gradient
    through `lax.map` over rematerialized rows runs every row's forward pass
    once more."""
    rnd = common.rounder(compute)

    def step(params, slots, xs):
        weight = xs["weight"].astype(jnp.float32)
        if half_batch:
            weight = weight.at[weight.shape[0] // 2:].set(0.0)
        nonzero = jnp.maximum(jnp.sum(weight != 0.0), 1).astype(jnp.float32)

        def row_loss(p, ids, y, w):
            prob = jax.nn.sigmoid(row(p, ids, rnd))
            return jnp.sum(w * jnp.square(prob - y)) / nonzero

        def one(acc, r):
            loss, grads = jax.value_and_grad(row_loss)(params, *r)
            return (acc[0] + loss,
                    jax.tree_util.tree_map(jnp.add, acc[1], grads)), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (loss, grads), _ = jax.lax.scan(
            one, (jnp.float32(0.0), zeros),
            (xs["features"].astype(jnp.float32),
             xs["target"].astype(jnp.float32), weight))
        params, slots = common.adadelta_update(params, grads, slots, lr)
        return params, slots, loss

    def epoch(params, slots, blocks):
        def body(carry, xs):
            p, s, acc = carry
            p, s, loss = step(p, s, xs)
            return (p, s, acc + loss), None

        (params, slots, acc), _ = jax.lax.scan(
            body, (params, slots, jnp.float32(0.0)), blocks)
        return params, slots, acc

    return epoch


def reference_first_epoch(config: dict, seed: int, train_rows: dict,
                          valid_rows: dict, compute: str = "float32",
                          fault: str = "", log=None) -> dict:
    """{"train_error", "valid_error", "grad", "change", "support"} of one
    epoch of the plain reference from the seed's initial weights ("support":
    the share of a leaf's entries its gradient ever reached, `sparse_leaves`),
    in float32 at `highest` matmul precision: every optimizer step in row order in one scan (the
    rows are a megabyte), a batch's gradient a row at a time, then the valid
    rows as one batch.

    `compute` rounds the operands of the reference's products ("float8" is
    the control, "bfloat16" what the configuration states).  `fault`:
    "half_batch" leaves the second half of every batch out; "no_routed"
    leaves the routed experts' sum out (the shared expert alone)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    model = harness.load_module("reference", config["model_type"])
    batch = int(config["batch_size"])
    lr = float(config["optimizer"]["learning_rate"])
    routed = fault != "no_routed"
    forward = model.make_forward(config, routed=routed)
    nb = train_rows["features"].shape[0] // batch
    blocks = {k: jnp.asarray(v[:nb * batch]).reshape(nb, batch, v.shape[1])
              for k, v in train_rows.items()}
    with jax.default_matmul_precision("highest"):
        init = jax.jit(lambda: model.init_params(config, seed))
        params = init()
        slots = common.adadelta_init(params)
        epoch = jax.jit(make_reference_epoch(
            model.make_row(config, routed=routed), lr, compute,
            fault == "half_batch"), donate_argnums=(0, 1))
        params, slots, loss_sum = epoch(params, slots, blocks)
        train_error = float(loss_sum) / nb
        if log:
            log(f"reference[{compute}{' ' + fault if fault else ''}]: {nb} "
                f"steps, train_error {train_error:.6f}")
        grad = compare.grad_norms(slots["e_g"])
        support = {k: float(jnp.mean(v != 0.0))
                   for k, v in compare.flatten(slots["e_g"]).items()}
        del slots
        params0 = init()   # again, for the change: the epoch donated them
        change = compare.change_norms(params, params0)
        del params0
        scores = np.asarray(jax.jit(common.make_scores(forward, compute))(
            params, jnp.asarray(valid_rows["features"])), np.float64)
    y, w = valid_rows["target"][:, 0], valid_rows["weight"][:, 0]
    valid_error = float(np.sum(w * (scores - y) ** 2)
                        / max(int(np.count_nonzero(w)), 1))
    return {"train_error": train_error, "valid_error": valid_error,
            "grad": grad, "change": change, "support": support}


#: a leaf whose reference gradient was nonzero on under this share of its
#: entries, all through the epoch, is left out of the parameters' change
SPARSE_GRADIENT_SHARE = 0.5


def sparse_leaves(ref: dict) -> dict:
    """{leaf: (share of its entries the reference's gradient ever reached,
    its gradient norm over the median leaf's)} of the leaves under
    `SPARSE_GRADIENT_SHARE`.  Under Adadelta an entry moves by about the
    same step whatever its gradient's size, so such a leaf's change counts
    which entries were ever touched - which expert a handful of tokens were
    routed to - and a single near-tie in a router, resolved the other way
    in another precision, moves it by its whole norm.  In this model they
    are the routed leaves of an `E` block that follows the last sequence
    mixer: it acts on one position a row, `batch_size` tokens a step that
    look alike.  The rule reads the reference's gradient alone, so nothing
    the program does decides what it is compared on."""
    med = statistics.median(ref["grad"].values())
    return {k: (share, ref["grad"][k] / max(med, 1e-30))
            for k, share in ref["support"].items()
            if share < SPARSE_GRADIENT_SHARE}


def training_gaps(prog: dict, ref: dict) -> tuple[dict, dict]:
    """`compare.training_gaps`, with the three gaps of the parameters'
    change taken over the leaves that are neither dead nor `sparse_leaves`;
    the worst leaf with the sparse ones in stays beside them as
    `change_norm_gap_all`."""
    gaps, notes = compare.training_gaps(prog, ref)
    sparse = sparse_leaves(ref)
    skip = set(notes["dead_leaves"]) | set(sparse)
    gaps["change_norm_gap_all"] = gaps["change_norm_gap"]
    gaps["change_norm_gap"], notes["change_norm_gap"] = \
        compare.worst_leaf_gap(prog["change"], ref["change"], skip=skip)
    gaps["change_median_gap"] = compare.median_leaf_gap(
        prog["change"], ref["change"], skip=skip)
    gaps["change_global_gap"] = compare.global_gap(
        prog["change"], ref["change"], skip=skip)
    notes["sparse_leaves"] = sparse
    return gaps, notes


def first_epoch_routing(records) -> list:
    """[(choices made, those on held experts)] an `E` layer, of the first
    `moe` event journalled: what the program routed in the epoch the
    reference follows."""
    first = next((r for r in records if r.get("kind") == "moe"), None)
    return [(layer["routed_slots"], layer["held_slots"])
            for layer in (first["layers"] if first else [])]


def tokens_dropped(records) -> float:
    """Σ `tokens_dropped` over the E layers of the `moe` journal events;
    NaN where there is no such event, which is no pass."""
    events = [r for r in records if r.get("kind") == "moe"]
    if not events:
        return float("nan")
    return float(sum(layer["tokens_dropped"] for r in events
                     for layer in r["layers"]))


def run(ctx: harness.Context) -> harness.Outcome:
    from shifu_tpu import obs
    from shifu_tpu.train import train
    from shifu_tpu.utils.compilecache import enable_persistent_cache

    log = ctx.log or (lambda s: None)
    config, params, seed = ctx.config, ctx.params, ctx.seed
    enable_persistent_cache(min_compile_time_secs=0.0)
    shared._compiles_not_served()
    journal = obs.RunJournal(None)   # in memory: the readers get the records
    obs.set_journal(journal)

    # -- set-up: the rows, then the call's start and its first epoch --------
    train_rows, valid_rows, train_ds, valid_ds = _datasets(
        config, params, seed, ctx.prepared)
    log(f"rows made: {train_ds.num_rows} train, {valid_ds.num_rows} valid")
    tracer = None
    if ctx.trace:
        tracer = shared._SliceTrace(int(params.get("trace_after_epoch", 1)),
                                    int(params.get("trace_epochs", 2)))
    win = shared._Window(ctx.seconds, ctx.t_start, journal, tracer)
    job = build_job(config, params, seed, shared._MANY_EPOCHS)
    try:
        train(job, train_ds, valid_ds,
              mesh=shared.build_mesh(job, ctx.devices),
              console=lambda s: None, epoch_callback=win)
    except shared._WindowClosed:
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
    ref = reference_first_epoch(config, seed, train_rows, valid_rows, log=log)
    gaps, notes = training_gaps(prog, ref)
    gaps["replay_gap"] = max(
        abs(win.first.train_error - prog["train_error"]),
        abs(win.first.valid_error - prog["valid_error"]))
    gaps["compiles_in_window"] = float(win.compiles)
    gaps["tokens_dropped"] = tokens_dropped(journal.records)
    limits = harness.load_limits(ctx.cell["name"])["limits"]
    checks = {k: (gaps.get(k), lim) for k, lim in limits.items()}
    log(f"widest leaves: {notes}")
    log("the first epoch's routing, (choices, on held experts) an E layer: "
        f"{first_epoch_routing(journal.records)}")
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
