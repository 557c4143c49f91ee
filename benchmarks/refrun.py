"""Drive a plain reference over the rows a training cell fed the program:
the first epoch's optimizer steps in row order, then the valid error, in
float32 with `jax.default_matmul_precision("highest")`, in chunks of blocks
so that it fits beside nothing else on the chip.

`compute` lowers the precision of the reference's products: "float32" is
the reference; "float8" is the control of "How `correct` is decided" (the
nearest precision below the bfloat16 the configurations state), which has
to come out as not correct; "bfloat16" is what the configurations state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import compare, harness
from .reference import common

#: at most this many blocks are scanned by one call of the reference's epoch
#: program, so that a chunk of rows fits beside the reference's state
MAX_CHUNK_BLOCKS = 64
EVAL_ROWS = 65536


def _chunk_blocks(nb: int) -> int:
    """The largest divisor of `nb` up to the cap: every chunk then has one
    shape and the epoch program compiles once.  A count with no divisor
    worth having is cut at the cap, and its tail compiles a second."""
    best = max(d for d in range(1, MAX_CHUNK_BLOCKS + 1) if nb % d == 0)
    return best if best >= 8 else min(nb, MAX_CHUNK_BLOCKS)


def _flat(rows: dict, lo: int, hi: int) -> dict:
    """Rows lo..hi of each column as one-dimensional device arrays: those
    cross from the host as they lie, where an (n, B, F) array is re-tiled
    on the host first, many times slower; the program reshapes on the
    device."""
    return {k: jnp.asarray(v[lo:hi].reshape(-1)) for k, v in rows.items()}


def _unflat(flat: dict, batch: int, like: dict) -> dict:
    return {k: v.reshape(-1, batch, like[k].shape[1])
            for k, v in flat.items()}


def first_epoch(config: dict, seed: int, train_rows: dict, valid_rows: dict,
                compute: str = "float32", fault: str = "",
                log=None) -> dict:
    """{"train_error", "valid_error", "grad": {leaf: norm}, "change": {leaf:
    norm}} of one epoch from the seed's initial weights."""
    model = harness.load_module("reference", config["model_type"])
    batch = int(config["batch_size"])
    lr = float(config["optimizer"]["learning_rate"])
    forward = model.make_forward(config)
    nb = train_rows["features"].shape[0] // batch
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda: model.init_params(config, seed))()
        slots = common.adadelta_init(params)
        scan = common.make_epoch(forward, lr, compute, fault)
        epoch = jax.jit(
            lambda p, s, flat: scan(p, s, _unflat(flat, batch, train_rows)),
            donate_argnums=(0, 1))
        loss_sum = jnp.float32(0.0)
        chunk = _chunk_blocks(nb)
        for lo in range(0, nb, chunk):
            hi = min(lo + chunk, nb)
            params, slots, part = epoch(
                params, slots, _flat(train_rows, lo * batch, hi * batch))
            loss_sum = loss_sum + part
        train_error = float(loss_sum) / nb
        if log:
            log(f"reference[{compute}{' ' + fault if fault else ''}]: {nb} steps, train_error "
                f"{train_error:.6f}")
        # the initial weights again, for the change: the epoch donated them
        params0 = jax.jit(lambda: model.init_params(config, seed))()
        norms = {"grad": compare.grad_norms(slots["e_g"]),
                 "change": compare.change_norms(params, params0)}
        del params0, slots
        valid_error = _valid_error(
            jax.jit(common.make_scores(forward, compute)), params, valid_rows)
    return {"train_error": train_error, "valid_error": valid_error, **norms}


def _valid_error(scores, params, rows: dict) -> float:
    """sum(w (p - y)^2) / count(w != 0) over every valid row: the error the
    job reports each epoch."""
    n = rows["features"].shape[0]
    err, nonzero = 0.0, 0
    for lo in range(0, n, EVAL_ROWS):
        hi = min(lo + EVAL_ROWS, n)
        feats = rows["features"][lo:hi]
        if hi - lo < EVAL_ROWS:  # one shape: pad the tail, drop its scores
            feats = np.concatenate([feats, np.zeros(
                (EVAL_ROWS - (hi - lo), feats.shape[1]), feats.dtype)])
        p = np.asarray(scores(params, jnp.asarray(feats.reshape(-1)).reshape(
            feats.shape)))[:hi - lo]
        y = rows["target"][lo:hi, 0]
        w = rows["weight"][lo:hi, 0]
        err += float(np.sum(w.astype(np.float64)
                            * (p.astype(np.float64) - y) ** 2))
        nonzero += int(np.count_nonzero(w))
    return err / max(nonzero, 1)
