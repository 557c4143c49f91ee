"""Pallas TPU kernel: Adadelta's whole apply over one leaf, in place.

`optax.adadelta` followed by `optax.apply_updates` is one elementwise
update of four arrays (parameter, gradient, both running means), but XLA
splits it in two fusions - one writes the two means, the other recomputes
the step to write the parameter - and, where the first overwrote the old
`e_x` in place, copies it for the second: twelve passes over a leaf where
seven do (read four, write three).  No rewriting of the jnp expression
fuses better (ISSUE 37).  This kernel is the seven passes: one grid walk
that reads `p`, `g`, `e_g`, `e_x` a block at a time and writes `p`, `e_g`,
`e_x` back into their own buffers (`input_output_aliases`).

The kernel sees the leaf in the view whose row-major order is the layout
the program holds it in, so that the view is a bitcast (a Mosaic call
takes its operands row-major; any other view costs a copy of the leaf in
and out of the loop, held for the whole program).  The TPU lays out the
last two dimensions in whichever order pads less to its (8, 128) tiles:
`(8, 2688, 1856)` is held `2688`-minor, and a stacked table `(F, V, D)`
with D under 128 lanes vocabulary-minor (which is also what the step's
row gather and per-field scatter ask for).  Such a leaf goes in with its
last two axes swapped, any other as it is; the leading dimensions are
collapsed.  The learning rate is an SMEM scalar, so a schedule's value for
the step is an operand, not a constant.

Off a TPU the kernel runs in interpret mode (the tests' exactness path);
train/optimizers.py decides where it engages.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_common import on_tpu, pltpu

#: f32 elements of one VMEM block per array (1 MiB): seven arrays
#: double-buffered hold 14 MiB, inside the 64 MiB the call allows.
_BLOCK_ELEMENTS = 256 * 1024


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def minor_swapped(shape: tuple) -> bool:
    """True where the TPU holds the last two dimensions swapped: that
    order pads less to (8, 128) tiles than the row-major one."""
    rows, cols = shape[-2:]
    return _up(cols, 8) * _up(rows, 128) < _up(rows, 8) * _up(cols, 128)


def _block(rows: int, cols: int) -> tuple[int, int]:
    """(rows, cols) of the block over one (rows, cols) matrix: the whole
    matrix where it fits the budget, else bands of whole rows, else column
    tiles of at most 64 rows (a stacked table's D padded to 8 sublanes)."""
    r8, c128 = _up(rows, 8), _up(cols, 128)
    if r8 * c128 <= _BLOCK_ELEMENTS:
        return rows, cols
    if 8 * c128 <= _BLOCK_ELEMENTS:
        return (_BLOCK_ELEMENTS // c128) // 8 * 8, cols
    br = rows if r8 <= 64 else 8
    return br, (_BLOCK_ELEMENTS // _up(br, 8)) // 128 * 128


def _kernel(rho: float, eps: float):
    # the same expressions, in the same order, as optax's
    # scale_by_adadelta + scale_by_learning_rate + apply_updates
    def kernel(lr_ref, p_ref, g_ref, eg_ref, ex_ref, p_out, eg_out, ex_out):
        g = g_ref[...]
        e_g = (1 - rho) * (g * g) + rho * eg_ref[...]
        e_x = ex_ref[...]
        u = jnp.sqrt(e_x + eps) / jnp.sqrt(e_g + eps) * g
        eg_out[...] = e_g
        ex_out[...] = (1 - rho) * (u * u) + rho * e_x
        p_out[...] = p_ref[...] + (-lr_ref[0]) * u

    return kernel


@functools.partial(jax.jit, static_argnames=("rho", "eps", "interpret"))
def _apply3(lr, p, g, e_g, e_x, *, rho: float, eps: float, interpret: bool):
    """The kernel over (L, R, C) arrays; jitted so that leaves of one
    shape share one trace and one lowering."""
    lead, rows, cols = p.shape
    br, bc = _block(rows, cols)
    spec = pl.BlockSpec((1, br, bc), lambda l, i, j: (l, i, j))
    out = jax.ShapeDtypeStruct(p.shape, p.dtype)
    return pl.pallas_call(
        _kernel(rho, eps),
        grid=(lead, pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [spec] * 4,
        out_specs=[spec] * 3,
        out_shape=[out] * 3,
        input_output_aliases={1: 0, 3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="adadelta_apply",
    )(lr, p, g, e_g, e_x)


def adadelta_apply(p, g, e_g, e_x, lr, *, rho: float, eps: float,
                   interpret: bool | None = None):
    """(p', e_g', e_x') of one Adadelta step over a float32 leaf of rank 2
    or more: e_g' = rho e_g + (1-rho) g^2, u = g sqrt(e_x+eps)/sqrt(e_g'+eps),
    e_x' = rho e_x + (1-rho) u^2, p' = p - lr u; the three written over
    their inputs' buffers."""
    if interpret is None:
        interpret = not on_tpu()
    shape = p.shape
    swap = minor_swapped(shape)
    held = shape[:-2] + (shape[-1:] + shape[-2:-1] if swap else shape[-2:])

    def view(a):
        a = jnp.swapaxes(a, -1, -2) if swap else a
        return a.reshape((-1,) + held[-2:])

    def back(a):
        a = a.reshape(held)
        return jnp.swapaxes(a, -1, -2) if swap else a

    lr = jnp.asarray(lr, jnp.float32).reshape(1)
    outs = _apply3(lr, *(view(a) for a in (p, g, e_g, e_x)),
                   rho=rho, eps=eps, interpret=interpret)
    return tuple(back(a) for a in outs)
