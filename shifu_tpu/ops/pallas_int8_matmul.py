"""Fused int8-dequant + first-layer matmul Pallas kernel (roofline push).

The int8 wire (data/pipeline.wire_params) stores features in HBM at 1 B
each; today the device-resident tier dequantizes them with a separate XLA
op (`train/step.make_wire_decode`: `q.astype(f32) * scale + offset`) whose
f32 result round-trips HBM before the first layer's matmul reads it back.
This kernel applies the static per-column scale/offset INSIDE the tile
load — one pass over the int8 block, dequant in registers, straight into
the MXU — so int8 is the in-HBM format end to end and the first layer
reads a quarter of the f32 bytes (the `bound` row the flight recorder
shows for `device_epoch_step` is HBM on this shape class).

Contract (pinned by tests/test_roofline.py against the
`wire_dequantize`+matmul XLA reference):

    int8_matmul_dequant(q, w, b, scale, offset)
      == dense(dequant(q))   where dequant(q) = q.astype(f32)*scale+offset
                             and dense is the flax nn.Dense compute-dtype
                             promotion (models/base.ShifuDense)

Availability gating follows ops/pallas_embedding.fused_update_available:
`fused_available()` is False wherever the kernel cannot actually run
(no TPU pallas namespace, oversized shapes, SHIFU_TPU_NO_INT8_FUSED set),
and callers (models/base._WireDense) then fall back bit-identically to the
current decode path.  Gradient: custom VJP — dW/db are the standard dense
grads computed from the recomputed dequant (int8 input re-read at 1 B/el,
the flash-attention recompute pattern); the int8 data itself gets a float0
cotangent (it is data, never differentiated).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .pallas_common import on_tpu, pallas_opt_in, pltpu

# batch rows per grid step: f32 intermediates want sublane multiples of 8;
# 256 rows x (F<=1024) int8 + the (BM, N) f32 output tile stay well under
# the 64 MB VMEM budget for every ladder schema
BLOCK_ROWS = 256
MAX_FEATURES = 4096
MAX_OUT = 4096
ENV_DISABLE = "SHIFU_TPU_NO_INT8_FUSED"


def fused_available(n_features: int, n_out: int) -> bool:
    """True where the fused dequant+matmul kernel can actually run: the
    layer shape fits the kernel's VMEM plan.  The kill switch
    SHIFU_TPU_NO_INT8_FUSED forces the XLA decode path without a rebuild."""
    if os.environ.get(ENV_DISABLE, "").lower() not in ("", "0", "false", "no"):
        return False
    return 0 < n_features <= MAX_FEATURES and 0 < n_out <= MAX_OUT


def fused_engaged(n_features: int, n_out: int) -> bool:
    """The auto gate models consult: available AND licensed — a real TPU
    backend runs it natively, anything else only under the explicit
    SHIFU_TPU_PALLAS opt-in (interpret mode; CI exactness pins)."""
    if not fused_available(n_features, n_out):
        return False
    return on_tpu() or pallas_opt_in()


def _dequant_reference(q: jax.Array, scale: jax.Array,
                       offset) -> jax.Array:
    """The exact decode math of train/step.make_wire_decode (f32 grid
    inverse), kept here so kernel, fallback, and backward all share it."""
    x = q.astype(jnp.float32) * scale
    return x if offset is None else x + offset


def xla_reference(q: jax.Array, w: jax.Array, b, scale: jax.Array,
                  offset, compute_dtype=jnp.bfloat16) -> jax.Array:
    """The unfused path: f32 dequant op, then the flax-Dense promotion
    (everything cast to compute dtype, matmul, bias add).  This IS the
    bit-identical fallback `_WireDense` runs when fused_available() says
    no, and the reference the exactness tests pin the kernel against."""
    x = _dequant_reference(q, scale, offset).astype(compute_dtype)
    y = x @ w.astype(compute_dtype)
    if b is not None:
        y = y + b.astype(compute_dtype)
    return y


def _fwd_kernel(q_ref, w_ref, b_ref, scale_ref, offset_ref, out_ref,
                *, compute_dtype):
    """One (BLOCK_ROWS, F) int8 tile: dequant in registers, one MXU matmul.
    scale/offset ride as (1, F) f32 rows broadcast over the tile."""
    x = q_ref[...].astype(jnp.float32) * scale_ref[...]
    if offset_ref is not None:
        x = x + offset_ref[...]
    x = x.astype(compute_dtype)
    # f32 MXU accumulation, then the exact flax-Dense promotion: cast to
    # the compute dtype BEFORE the bias add — bit-parity with
    # xla_reference (the fallback) so fused and unfused training match
    acc = jax.lax.dot_general(
        x, w_ref[...].astype(compute_dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(compute_dtype)
    if b_ref is not None:
        acc = acc + b_ref[...].astype(compute_dtype)
    out_ref[...] = acc.astype(out_ref.dtype)


def _compiler_params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _run_fwd(q, w, b, scale, offset, compute_dtype, interpret):
    m, f = q.shape
    n = w.shape[1]
    bm = min(BLOCK_ROWS, max(8, -(-m // 8) * 8))
    mp = -(-m // bm) * bm
    if mp != m:  # pad batch rows; the grid ignores garbage rows on slice-out
        q = jnp.pad(q, ((0, mp - m), (0, 0)))
    scale2 = scale.reshape(1, f).astype(jnp.float32)
    offset2 = (None if offset is None
               else offset.reshape(1, f).astype(jnp.float32))
    b2 = None if b is None else b.reshape(1, n)

    args = [q, w]
    in_specs = [
        pl.BlockSpec((bm, f), lambda i: (i, 0)),
        pl.BlockSpec((f, n), lambda i: (0, 0)),
    ]
    if b2 is not None:
        args.append(b2)
        in_specs.append(pl.BlockSpec((1, n), lambda i: (0, 0)))
    args.append(scale2)
    in_specs.append(pl.BlockSpec((1, f), lambda i: (0, 0)))
    if offset2 is not None:
        args.append(offset2)
        in_specs.append(pl.BlockSpec((1, f), lambda i: (0, 0)))

    def kernel(*refs):
        it = iter(refs)
        q_ref = next(it)
        w_ref = next(it)
        b_ref = next(it) if b2 is not None else None
        scale_ref = next(it)
        offset_ref = next(it) if offset2 is not None else None
        out_ref = next(it)
        _fwd_kernel(q_ref, w_ref, b_ref, scale_ref, offset_ref, out_ref,
                    compute_dtype=compute_dtype)

    out = pl.pallas_call(
        kernel,
        grid=(mp // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, n), compute_dtype),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
        name="int8_matmul_dequant",
    )(*args)
    return out[:m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _int8_matmul(q, w, b, scale, offset, has_offset, cdt_name, interpret):
    offset_arr = offset if has_offset else None
    return _run_fwd(q, w, b, scale, offset_arr,
                    jnp.dtype(cdt_name).type, interpret)


def _int8_matmul_fwd(q, w, b, scale, offset, has_offset, cdt_name, interpret):
    y = _int8_matmul(q, w, b, scale, offset, has_offset, cdt_name, interpret)
    return y, (q, w, scale, offset)


def _int8_matmul_bwd(has_offset, cdt_name, interpret, res, dy):
    q, w, scale, offset = res
    cdt = jnp.dtype(cdt_name).type
    # recompute the dequant (1 B/el re-read) instead of storing the f32
    # activations across fwd->bwd; same grads as the XLA reference path
    x = _dequant_reference(q, scale, offset if has_offset else None)
    x = x.astype(cdt)
    dyc = dy.astype(cdt)
    dw = jax.lax.dot_general(
        x, dyc, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(w.dtype)
    db = jnp.sum(dy, axis=0).astype(w.dtype)
    dq = np.zeros(q.shape, jax.dtypes.float0)  # int8 data: never diff'd
    dscale = jnp.zeros_like(scale)  # static grid constants
    doffset = jnp.zeros_like(offset)
    return dq, dw, db, dscale, doffset


_int8_matmul.defvjp(_int8_matmul_fwd, _int8_matmul_bwd)


def int8_matmul_dequant(q: jax.Array, w: jax.Array, b, scale, offset,
                        compute_dtype=jnp.bfloat16,
                        use_pallas=None) -> jax.Array:
    """Fused `dequant(q) @ w + b` for int8 wire features.

    q (M, F) int8 on the wire grid; w (F, N) / b (N,) the first layer's
    params; scale/offset the (F,) static grid from data/pipeline.wire_params
    (offset may be None — the default grid is symmetric).  `use_pallas`:
    None = auto (fused_engaged), True = force (interpret off-TPU — the test
    path), False = the bit-identical XLA decode fallback.
    """
    m, f = q.shape
    n = w.shape[1]
    use = fused_engaged(f, n) if use_pallas is None else (
        use_pallas and fused_available(f, n))
    if not use:
        return xla_reference(q, w, b, scale, offset, compute_dtype)
    scale = jnp.asarray(scale, jnp.float32)
    has_offset = offset is not None
    offset_arr = (jnp.asarray(offset, jnp.float32) if has_offset
                  else jnp.zeros_like(scale))
    bias = b if b is not None else jnp.zeros((n,), w.dtype)
    return _int8_matmul(q, w, bias, scale, offset_arr, has_offset,
                        jnp.dtype(compute_dtype).name, not on_tpu())
