"""The arithmetic of the comparison that decides `correct` for a training
cell: gaps between what the program's state says and what the plain
reference's says, leaf by leaf.

A state is read the same way on both sides (`grad_norms`, `change_norms`),
and only norms cross: the gap is between the program's norm and the reference's, not the
norm of their difference, measured against the reference's norm of that leaf
or of the median leaf, whichever is larger (some gradients are all but zero).
"""

from __future__ import annotations

import statistics
from typing import Optional

import jax
import jax.numpy as jnp

#: leaves whose reference gradient norm is under this share of the median
#: leaf's are nought to rounding: under Adadelta they move by round-off
#: alone, and are left out of the parameters' change
DEAD_GRADIENT_SHARE = 1e-3


def flatten(tree, prefix: str = "") -> dict:
    """{"a/b/kernel": leaf} of a nested mapping of arrays."""
    out: dict = {}
    for k in sorted(tree):
        v = tree[k]
        path = f"{prefix}{k}"
        if hasattr(v, "keys"):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


@jax.jit
def _root_sum(a):
    return jnp.sqrt(jnp.sum(a.astype(jnp.float32)))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def grad_norms(e_g) -> dict:
    """{leaf: sqrt(sum(e_g))}: `e_g` is Adadelta's running mean of squared
    gradients, the gradient as the optimizer got it."""
    return {k: float(_root_sum(v)) for k, v in flatten(e_g).items()}


def change_norms(params, params0) -> dict:
    """{leaf: |p - p0|}: `params0` are the initial weights as the reference
    derives them from the seed (it takes none from the program)."""
    p, p0 = flatten(params), flatten(params0)
    if set(p) != set(p0):
        raise ValueError(f"parameter trees differ: {sorted(set(p) ^ set(p0))}")
    return {k: float(_diff_norm(p[k], p0[k])) for k in p}


def leaf_gaps(prog: dict, ref: dict, skip: Optional[set] = None) -> dict:
    """{leaf: gap} over the leaves not in `skip`."""
    keys = [k for k in ref if not skip or k not in skip]
    floor = statistics.median(ref[k] for k in keys)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in keys}


def worst_leaf_gap(prog: dict, ref: dict,
                   skip: Optional[set] = None) -> tuple[float, str]:
    """(the widest gap, its leaf) over the leaves not in `skip`."""
    worst, where = 0.0, ""
    for k, gap in leaf_gaps(prog, ref, skip).items():
        if not gap <= worst:      # NaN lands here too, and stays
            worst, where = gap, k
    return worst, where


def median_leaf_gap(prog: dict, ref: dict,
                    skip: Optional[set] = None) -> float:
    """The median leaf's gap: what most of the leaves read, whatever one
    small leaf does.  A NaN on any leaf is the answer."""
    gaps = list(leaf_gaps(prog, ref, skip).values())
    if any(g != g for g in gaps):
        return float("nan")
    return statistics.median(gaps)


def global_gap(prog: dict, ref: dict, skip: Optional[set] = None) -> float:
    """The gap between the norms over all the leaves together."""
    keys = [k for k in ref if not skip or k not in skip]
    p = sum(prog[k] ** 2 for k in keys) ** 0.5
    r = sum(ref[k] ** 2 for k in keys) ** 0.5
    return abs(p - r) / max(r, 1e-30)


def dead_leaves(ref_grad: dict) -> set:
    med = statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v < DEAD_GRADIENT_SHARE * med}


def relative(prog: float, ref: float) -> float:
    return abs(prog - ref) / max(abs(ref), 1e-30)


def training_gaps(prog: dict, ref: dict) -> tuple[dict, dict]:
    """The numbers compared, and where each was widest.

    `prog` and `ref` hold "train_error", "valid_error" and the maps "grad" and
    "change" of `grad_norms` and `change_norms`, each over the same first epoch from the same seed."""
    dead = dead_leaves(ref["grad"])
    g_gap, g_leaf = worst_leaf_gap(prog["grad"], ref["grad"])
    c_gap, c_leaf = worst_leaf_gap(prog["change"], ref["change"], skip=dead)
    gaps = {
        "loss_gap": relative(prog["train_error"], ref["train_error"]),
        "valid_gap": relative(prog["valid_error"], ref["valid_error"]),
        "grad_norm_gap": g_gap,
        "change_norm_gap": c_gap,
        "change_median_gap": median_leaf_gap(prog["change"], ref["change"],
                                             skip=dead),
        "change_global_gap": global_gap(prog["change"], ref["change"],
                                        skip=dead),
    }
    notes = {"grad_norm_gap": g_leaf, "change_norm_gap": c_leaf,
             "dead_leaves": sorted(dead)}
    return gaps, notes
