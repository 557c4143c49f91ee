"""Batch-in-lanes attention kernel for SMALL tokens and SMALL head dims.

The FT-Transformer rung attends over ~31 feature tokens with head_dim 8.
On TPU, the classic formulation materializes the (B, H, S, S) float32 score
tensor whose minor dim (S=31) pads to the 128-lane register width — a 4x
physical bloat that turns a few hundred MB of logical scores into
multi-GB HBM round trips; the MXU matmuls themselves are tiny (K = 8) and
contribute almost nothing.  Measured on a v5e: the whole rung runs at ~2%
MFU and the cost scales with HEAD COUNT, not FLOPs — the score tensor's
layout is the bottleneck (ops/pallas_attention.py's flash kernel does not
help here: its per-head blocks hit the same lane padding).

This kernel flips the layout: the BATCH rides the 128-lane axis.  Queries
arrive as (S, H*D, B-tile) and keys/values as (H, D, S, B-tile), so per
query token the scores live as (H, S_k, 128) — key tokens on the SUBLANE
axis, which makes the softmax reductions the native sublane-reduce mosaic
pattern — and the whole attention is pure VPU elementwise work: no MXU, no
(S, S) tensor, no HBM traffic beyond q, k, v in and o out.  The backward
kernel recomputes the softmax per query token (flash-style) and
accumulates dk/dv in VMEM.

Same math as ops/attention.mha (float32 softmax; same reductions),
validated against it in tests/test_pallas_attention.py, in interpret mode
on CPU and compiled on TPU.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .attention import mha
from .pallas_common import on_tpu, pltpu

# auto-routing bounds: the lanes formulation wins when the score tensor's
# lane padding dominates (S well under 128) and heads are fragmented; above
# these, the classic/flash paths are the right tool
MAX_S = 64
MAX_D = 16
LANES = 128
ENV_DISABLE = "SHIFU_TPU_NO_SMALL_ATTENTION"


def small_attention_applicable(s: int, d: int, h: int = 1) -> bool:
    """Shape envelope for auto-routing.  Besides the small-token/small-dim
    bounds, the kernel keeps k/v plus f32 grad accumulators and (H, D, S,
    128) temporaries resident per batch tile — cap the estimated footprint
    well under the raised scoped-VMEM limit so a many-headed config never
    auto-routes into a Mosaic OOM that the mha path would have survived."""
    s_pad = -(-s // 8) * 8
    vmem_estimate = 8 * h * d * s_pad * LANES * 4  # ~8 resident buffers
    return (s <= MAX_S and d <= MAX_D
            and vmem_estimate <= 48 * 1024 * 1024
            and not os.environ.get(ENV_DISABLE))


def _softmax_over_keys(scores: jax.Array, s_real: int) -> jax.Array:
    """Masked softmax over the key-token SUBLANE axis of (H, S_pad, L):
    padded key rows (>= s_real) are forced to -1e30 (exact zeros after
    exp) so S needs no tile alignment from callers."""
    s_pad = scores.shape[1]
    if s_pad != s_real:
        ki = jax.lax.broadcasted_iota(jnp.int32, (1, s_pad, 1), 1)
        scores = jnp.where(ki < s_real, scores, -1e30)
    m = scores.max(axis=1, keepdims=True)
    p = jnp.exp(scores - m)
    l = p.sum(axis=1, keepdims=True)
    return p / l


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, s: int, s_real: int,
                h: int, d: int, scale: float):
    """One 128-lane batch tile, streaming over query tokens.
    q_ref/o_ref: (S, H*D, L); k_ref/v_ref: (H, D, S, L)."""
    k4 = k_ref[...].astype(jnp.float32)                     # (H,D,S,L)
    v4 = v_ref[...].astype(jnp.float32)

    def qi_body(qi, carry):
        qrow = q_ref[pl.ds(qi, 1), :, :].astype(jnp.float32)  # (1,HD,L)
        q4 = qrow.reshape(h, d, 1, LANES)
        scores = (q4 * k4).sum(axis=1) * scale                # (H,S,L)
        w = _softmax_over_keys(scores, s_real)                # (H,S,L)
        o4 = (w[:, None, :, :] * v4).sum(axis=2)              # (H,D,L)
        o_ref[pl.ds(qi, 1), :, :] = o4.reshape(1, h * d, LANES
                                               ).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, s_real, qi_body, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, dq_ref, dk_ref, dv_ref, *,
                s: int, s_real: int, h: int, d: int, scale: float):
    """Flash-style backward on the same layout: per query token, recompute
    the softmax, then
        dv += w * dO ; dP = sum_d dO v ; dS = w (dP - sum_k dP w)
        dq = sum_k dS k * scale ; dk += dS q * scale
    q_ref/g_ref/dq_ref: (S, H*D, L); k/v/dk/dv refs: (H, D, S, L).
    dk/dv accumulate IN their output refs (VMEM) — no extra carry
    allocation, which is what kept the first cut over the scoped-vmem
    limit."""
    k4 = k_ref[...].astype(jnp.float32)
    v4 = v_ref[...].astype(jnp.float32)
    dk_ref[...] = jnp.zeros_like(dk_ref)
    dv_ref[...] = jnp.zeros_like(dv_ref)

    def qi_body(qi, carry):
        qrow = q_ref[pl.ds(qi, 1), :, :].astype(jnp.float32)
        grow = g_ref[pl.ds(qi, 1), :, :].astype(jnp.float32)
        q4 = qrow.reshape(h, d, 1, LANES)
        g4 = grow.reshape(h, d, 1, LANES)
        scores = (q4 * k4).sum(axis=1) * scale                # (H,S,L)
        w = _softmax_over_keys(scores, s_real)                # (H,S,L)

        dv_q = w[:, None, :, :] * g4                          # (H,D,S,L)
        dP = (g4 * v4).sum(axis=1)                            # (H,S,L)
        row = (dP * w).sum(axis=1, keepdims=True)             # (H,1,L)
        dS = w * (dP - row)                                   # (H,S,L)
        dq4 = (dS[:, None, :, :] * k4).sum(axis=2) * scale    # (H,D,L)
        dk_q = dS[:, None, :, :] * q4 * scale                 # (H,D,S,L)
        dq_ref[pl.ds(qi, 1), :, :] = dq4.reshape(
            1, h * d, LANES).astype(dq_ref.dtype)
        dk_ref[...] = (dk_ref[...].astype(jnp.float32)
                       + dk_q).astype(dk_ref.dtype)
        dv_ref[...] = (dv_ref[...].astype(jnp.float32)
                       + dv_q).astype(dv_ref.dtype)
        return carry

    jax.lax.fori_loop(0, s_real, qi_body, 0)


def _q_to_lanes(x: jax.Array) -> jax.Array:
    """(B, H, S, D) -> (S, H*D, B)."""
    b, h, s, d = x.shape
    return x.transpose(2, 1, 3, 0).reshape(s, h * d, b)


def _kv_to_lanes(x: jax.Array) -> jax.Array:
    """(B, H, S, D) -> (H, D, S, B)."""
    return x.transpose(1, 3, 2, 0)


def _q_from_lanes(x: jax.Array, b: int, h: int, s: int, d: int) -> jax.Array:
    return x.reshape(s, h, d, b).transpose(3, 1, 0, 2)


def _kv_from_lanes(x: jax.Array) -> jax.Array:
    """(H, D, S, B) -> (B, H, S, D)."""
    return x.transpose(3, 0, 2, 1)


def _pad_b(x: jax.Array) -> jax.Array:
    pad = (-x.shape[-1]) % LANES
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x


def _pad_s_q(x: jax.Array, s_pad: int) -> jax.Array:
    """(S, HD, B): pad the query-token axis 0 to a sublane multiple."""
    if x.shape[0] == s_pad:
        return x
    return jnp.pad(x, ((0, s_pad - x.shape[0]), (0, 0), (0, 0)))


def _pad_s_kv(x: jax.Array, s_pad: int) -> jax.Array:
    """(H, D, S, B): pad the key-token axis 2 to a sublane multiple (the
    kernel masks the pad rows to exact-zero softmax weight)."""
    if x.shape[2] == s_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - x.shape[2]), (0, 0)))


def _compiler_params(interpret: bool):
    if interpret:
        return None
    # the default 16MB scoped-vmem limit is tight for the backward's
    # resident k/v + f32 grad accumulators; v5e has headroom
    return pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _run_fwd(q, k, v, scale: float, interpret: bool):
    b, h, s, d = q.shape
    s_pad = -(-s // 8) * 8  # sublane-aligned key axis
    ql = _pad_b(_pad_s_q(_q_to_lanes(q), s_pad))
    kl, vl = (_pad_b(_pad_s_kv(_kv_to_lanes(t), s_pad)) for t in (k, v))
    bp = ql.shape[-1]
    grid = (bp // LANES,)
    q_spec = pl.BlockSpec((s_pad, h * d, LANES), lambda i: (0, 0, i))
    kv_spec = pl.BlockSpec((h, d, s_pad, LANES), lambda i: (0, 0, 0, i))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, s=s_pad, s_real=s, h=h, d=d,
                          scale=scale),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((s_pad, h * d, bp), q.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(ql, kl, vl)
    return _q_from_lanes(out[:s, :, :b], b, h, s, d)


def _run_bwd(q, k, v, g, scale: float, interpret: bool):
    b, h, s, d = q.shape
    s_pad = -(-s // 8) * 8
    ql, gl = (_pad_b(_pad_s_q(_q_to_lanes(t), s_pad)) for t in (q, g))
    kl, vl = (_pad_b(_pad_s_kv(_kv_to_lanes(t), s_pad)) for t in (k, v))
    bp = ql.shape[-1]
    grid = (bp // LANES,)
    q_spec = pl.BlockSpec((s_pad, h * d, LANES), lambda i: (0, 0, i))
    kv_spec = pl.BlockSpec((h, d, s_pad, LANES), lambda i: (0, 0, 0, i))
    # grads accumulate (and return) in f32: 31 bf16 += steps would round
    q_shape = jax.ShapeDtypeStruct((s_pad, h * d, bp), jnp.float32)
    kv_shape = jax.ShapeDtypeStruct((h, d, s_pad, bp), jnp.float32)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, s=s_pad, s_real=s, h=h, d=d,
                          scale=scale),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[q_shape, kv_shape, kv_shape],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(ql, kl, vl, gl)
    return (_q_from_lanes(dq[:s, :, :b], b, h, s, d).astype(q.dtype),
            _kv_from_lanes(dk[:, :, :s, :b]).astype(q.dtype),
            _kv_from_lanes(dv[:, :, :s, :b]).astype(q.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _small_attn(q, k, v, scale: float, interpret: bool):
    return _run_fwd(q, k, v, scale, interpret)


def _small_attn_fwd(q, k, v, scale: float, interpret: bool):
    return _run_fwd(q, k, v, scale, interpret), (q, k, v)


def _small_attn_bwd(scale: float, interpret: bool, res, g):
    q, k, v = res
    return _run_bwd(q, k, v, g, scale, interpret)


_small_attn.defvjp(_small_attn_fwd, _small_attn_bwd)


def small_token_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          scale: Optional[float] = None,
                          use_pallas: Optional[bool] = None) -> jax.Array:
    """Drop-in for ops/attention.mha on (B, H, S, D) with S <= 64, D <= 16.

    use_pallas: None = auto (TPU backend + applicable shape; interpret mode
    on CPU is exercised by tests but NOT auto-selected — it is orders of
    magnitude slower than XLA); True forces the kernels (interpret
    off-TPU); False routes to mha.
    """
    b, h, s, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if use_pallas is None:
        use_pallas = on_tpu() and small_attention_applicable(s, d, h)
    if not use_pallas:
        return mha(q, k, v, scale=scale)
    return _small_attn(q, k, v, scale, not on_tpu())
