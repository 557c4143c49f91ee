"""Operations and bytes one train step of DeepFM needs, from its shapes.

The same rule as `counts/mlp.py`: the work the algorithm requires, whatever
implements it.  Operations: the deep trunk over the flattened field vectors
and the numeric first-order term as dense chains, the numeric field vectors
(a multiply-add a component) and the FM second-order term (about four
operations a component of a field vector), each three times over for
forward + backward; a table lookup is a copy and counts no operation.
Bytes: the batch's rows in their wire format; every dense parameter and
both Adadelta slots read and written once; and for the tables the rows a
batch touches - batch x fields rows of (dim + 1) floats, parameters and both
slots read and written once - not the whole-table passes that a dense
update makes.  `sparse_embedding_update` therefore changes nothing here.
"""

from __future__ import annotations

from .mlp import _dense_chain, row_wire_bytes  # noqa: F401  (same rule)


def _shapes(cfg: dict):
    n_num, n_cat, k = (cfg["num_numeric"], cfg["num_categorical"],
                       cfg["embedding_dim"])
    return n_num, n_cat, k, n_num + n_cat


def flops_per_sample(cfg: dict) -> float:
    n_num, n_cat, k, fields = _shapes(cfg)
    deep, _ = _dense_chain([fields * k, *cfg["hidden_nodes"], 1])
    first, _ = _dense_chain([n_num, 1])
    fwd = (deep + first + n_cat       # first-order sum over the fields
           + 2 * n_num * k            # numeric field vectors
           + 4 * fields * k)          # FM: sum, square, squares, sum
    return 3.0 * fwd


def bytes_per_step(cfg: dict, batch: int) -> float:
    n_num, n_cat, k, fields = _shapes(cfg)
    _, deep = _dense_chain([fields * k, *cfg["hidden_nodes"], 1])
    _, first = _dense_chain([n_num, 1])
    dense_params = deep + first + 2 * n_num * k
    touched = batch * n_cat * (k + 1)
    return (batch * row_wire_bytes(cfg)
            + 6 * 4 * (dense_params + touched))
