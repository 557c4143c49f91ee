"""Operations and bytes one train step of the MLP needs, from its shapes.

They count the work the algorithm requires, whatever implements it: forward
+ backward of the dense chain (2mn a multiply-add matrix entry, backward
twice the forward: one product for the input gradient, one for the weight
gradient), and the least HBM traffic a step can have: the batch's rows read
once in their wire format, every parameter and both Adadelta slots read and
written once.  Activations are not counted: they can stay on the chip.
Elementwise work (bias, relu, sigmoid, the loss) is left out of the
operations, as in an MFU: `cost_analysis()` of the program's step read
1.005x this count at the flagship width (PERF.md, PR 21).
"""

from __future__ import annotations

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def _dense_chain(dims) -> tuple[int, int]:
    """(forward FLOPs a sample, parameters) of a chain of dense layers."""
    flops = sum(2 * a * b for a, b in zip(dims, dims[1:]))
    params = sum(a * b + b for a, b in zip(dims, dims[1:]))
    return flops, params


def flops_per_sample(cfg: dict) -> float:
    fwd, _ = _dense_chain([cfg["num_numeric"], *cfg["hidden_nodes"],
                           cfg.get("num_heads", 1)])
    return 3.0 * fwd


def row_wire_bytes(cfg: dict) -> int:
    """One row in the resident tier: features in the wire dtype, a u8
    label, an f32 weight where the rows carry one."""
    per = _DTYPE_BYTES[cfg.get("feature_dtype", "float32")]
    n_feat = cfg["num_numeric"] + cfg.get("num_categorical", 0)
    return n_feat * per + 1 + (4 if cfg.get("with_weight") else 0)


def bytes_per_step(cfg: dict, batch: int) -> float:
    _, params = _dense_chain([cfg["num_numeric"], *cfg["hidden_nodes"],
                              cfg.get("num_heads", 1)])
    return batch * row_wire_bytes(cfg) + 6 * 4 * params
