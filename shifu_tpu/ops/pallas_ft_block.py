"""Fused FT-Transformer block: attention + FFN in one Pallas pass.

Unfused, each TransformerBlock dispatches LayerNorm, qkv, attention,
proj, LN, mlp_in, gelu, mlp_out as separate HLO regions whose (B, S, D)
intermediates round-trip HBM eight times per block.  Feature-token
attention is tiny (S ~ 31 tokens, head_dim 8); the arithmetic lives in
the FFN matmuls, so the win is keeping one batch tile's activations in
VMEM across the WHOLE block: flash-attention-style tiling over the
feature-token axis, LN->qkv->attention->proj->residual->LN->FFN->residual
fused into a single kernel.

Exactness contract (tests/test_roofline.py): at float32 compute dtype the
kernel output matches `models/ft_transformer._block_forward` (and the
TransformerBlock module) to f32 matmul tolerance; at bfloat16 the kernel
is the MORE precise path (true f32 accumulation end to end — the
small_token_attention precedent) and matches to bf16 tolerance.

Gradient: custom VJP with flash-style recompute — the backward pass
re-derives the forward from the exact same f32 math (no activation
storage across the block) via jax.vjp of the in-module reference, so
fused grads are bit-identical to the recomputed reference's.

Gating mirrors ops/pallas_small_attention: `ft_block_applicable` caps the
shapes the VMEM plan covers, SHIFU_TPU_NO_FT_FUSED is the kill switch,
and ModelSpec.fused_block ("auto"/"on"/"off") drives engagement from
config (docs/CONFIG.md `shifu.model.fused-block`).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_common import on_tpu, pallas_opt_in, pltpu

MAX_TOKENS = 64        # feature-token counts; beyond this flash_attention wins
MAX_TOKEN_DIM = 128
MAX_MLP_RATIO = 8
BATCH_TILE = 8         # samples per grid step (f32 sublane multiple)
LN_EPS = 1e-6          # flax nn.LayerNorm default, same as _layernorm
ENV_DISABLE = "SHIFU_TPU_NO_FT_FUSED"


def ft_block_applicable(seq_len: int, token_dim: int, num_heads: int,
                        mlp_ratio: int) -> bool:
    """True where the fused block kernel can actually run: head split
    exact, and the (S, D, R) shape class inside the kernel's VMEM plan
    (~(BT*S) x max(3D, R*D) f32 intermediates; the bench rung's
    31 x 64 x 4 uses ~2 MB)."""
    if os.environ.get(ENV_DISABLE, "").lower() not in ("", "0", "false", "no"):
        return False
    if num_heads <= 0 or token_dim % num_heads != 0:
        return False
    return (0 < seq_len <= MAX_TOKENS and 0 < token_dim <= MAX_TOKEN_DIM
            and 0 < mlp_ratio <= MAX_MLP_RATIO)


def fused_block_engaged(spec, seq_len: int, train: bool = False,
                        n_seq_parallel: int = 1) -> bool:
    """Config-level auto gate (ModelSpec.fused_block) consulted by
    TransformerBlock and `_block_forward`: engaged when the shape is
    applicable, nothing unfusable rides the block (train-time dropout,
    ring/ulysses sequence parallelism), and the platform licenses pallas
    ("on" forces interpret mode off-TPU — the CI exactness path)."""
    mode = getattr(spec, "fused_block", "off")
    if mode == "off":
        return False
    if train and spec.dropout_rate > 0:
        return False  # dropout applies between fused stages: not fusable
    if n_seq_parallel > 1 or spec.attention_impl in ("ring", "ulysses"):
        return False
    if not ft_block_applicable(seq_len, spec.token_dim,
                               spec.num_attention_heads, spec.mlp_ratio):
        return False
    if mode == "on":
        return True
    return on_tpu() or pallas_opt_in()


def _ln(x2d, scale, bias):
    """f32-statistics LayerNorm over the last axis of a 2D tile — the same
    math as models/ft_transformer._layernorm with the cdt cast deferred
    (the kernel stays f32 throughout)."""
    mean = jnp.mean(x2d, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x2d - mean), axis=-1, keepdims=True)
    y = (x2d - mean) * jax.lax.rsqrt(var + LN_EPS)
    return y * scale + bias


def _block_math(x, p, *, s_real, heads):
    """The fused block body on one (BT, Sp, D) f32 tile.  Shared verbatim
    by the Pallas kernel and the recompute backward (jax.vjp over this
    function), so fwd and grad can never diverge."""
    bt, sp, d = x.shape
    dh = d // heads
    m = bt * sp
    x2 = x.reshape(m, d)

    # pre-LN attention
    y = _ln(x2, p["ln_attn_scale"], p["ln_attn_bias"])
    qkv = jax.lax.dot_general(
        y, p["qkv_kernel"], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + p["qkv_bias"]
    qkv = qkv.reshape(bt, sp, 3 * d)
    q, k, v = qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:]
    inv = dh ** -0.5
    # pad keys past the real token count get -inf scores (padded tiles)
    key_live = (jax.lax.broadcasted_iota(jnp.int32, (sp, sp), 1)
                < s_real)
    outs = []
    for h in range(heads):  # heads are few (<=16) and static: unrolled
        qh = q[..., h * dh:(h + 1) * dh] * inv       # (BT, Sp, dh)
        kh = k[..., h * dh:(h + 1) * dh]
        vh = v[..., h * dh:(h + 1) * dh]
        # per-sample (Sp, Sp) scores via a broadcast multiply-reduce: the
        # VPU path — attention is O(S^2 dh) flops, ~1% of the FFN's, so
        # lanes go to the MXU matmuls instead
        scores = jnp.sum(qh[:, :, None, :] * kh[:, None, :, :], axis=-1)
        scores = jnp.where(key_live[None], scores, -1e30)
        smax = jnp.max(scores, axis=-1, keepdims=True)
        ex = jnp.exp(scores - smax)
        probs = ex / jnp.sum(ex, axis=-1, keepdims=True)
        outs.append(jnp.sum(probs[:, :, :, None] * vh[:, None, :, :],
                            axis=2))                 # (BT, Sp, dh)
    attn = jnp.concatenate(outs, axis=-1).reshape(m, d)
    attn = jax.lax.dot_general(
        attn, p["proj_kernel"], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + p["proj_bias"]
    x2 = x2 + attn

    # pre-LN FFN
    y = _ln(x2, p["ln_mlp_scale"], p["ln_mlp_bias"])
    y = jax.lax.dot_general(
        y, p["mlp_in_kernel"], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + p["mlp_in_bias"]
    y = jax.nn.gelu(y)  # approximate (tanh) — the flax nn.gelu default
    y = jax.lax.dot_general(
        y, p["mlp_out_kernel"], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + p["mlp_out_bias"]
    return (x2 + y).reshape(bt, sp, d)


_PARAM_ORDER = (
    "ln_attn_scale", "ln_attn_bias", "qkv_kernel", "qkv_bias",
    "proj_kernel", "proj_bias", "ln_mlp_scale", "ln_mlp_bias",
    "mlp_in_kernel", "mlp_in_bias", "mlp_out_kernel", "mlp_out_bias")


def _compiler_params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _run_fwd(x, flat_params, s_real, heads, interpret):
    b, sp, d = x.shape
    grid = (b // BATCH_TILE,)

    def kernel(x_ref, *refs):
        p = {name: refs[i][...] for i, name in enumerate(_PARAM_ORDER)}
        out_ref = refs[len(_PARAM_ORDER)]
        out_ref[...] = _block_math(x_ref[...], p, s_real=s_real, heads=heads)

    in_specs = [pl.BlockSpec((BATCH_TILE, sp, d), lambda i: (i, 0, 0))]
    for arr in flat_params:  # whole param tensors resident per grid step
        in_specs.append(pl.BlockSpec(
            arr.shape, lambda i, nd=arr.ndim: (0,) * nd))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((BATCH_TILE, sp, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sp, d), jnp.float32),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
        name="ft_fused_block",
    )(x, *flat_params)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_block(x, flat_params, s_real, heads, interpret):
    return _run_fwd(x, flat_params, s_real, heads, interpret)


def _fused_block_fwd(x, flat_params, s_real, heads, interpret):
    y = _run_fwd(x, flat_params, s_real, heads, interpret)
    return y, (x, flat_params)


def _fused_block_bwd(s_real, heads, interpret, res, dy):
    x, flat_params = res

    def ref(x_, flat_):
        p = dict(zip(_PARAM_ORDER, flat_))
        return _block_math(x_, p, s_real=s_real, heads=heads)

    # flash-style recompute: no stored activations — the backward re-derives
    # the forward from the identical _block_math and differentiates that
    _, vjp = jax.vjp(ref, x, flat_params)
    dx, dflat = vjp(dy)
    return dx, dflat


_fused_block.defvjp(_fused_block_fwd, _fused_block_bwd)


def fused_transformer_block(x: jax.Array, p: dict, spec,
                            use_pallas=None) -> jax.Array:
    """One fused pre-LN transformer block (attention + FFN) over
    (B, S, D) tokens with the stacked-name param dict of
    models/ft_transformer._BLOCK_PARAM_PATHS.  Computes in f32 internally
    and returns x.dtype.  `use_pallas`: None = auto, True = force
    (interpret off-TPU), False = raise (callers route unfused math
    themselves — TransformerBlock IS the fallback)."""
    b, s, d = x.shape
    heads = spec.num_attention_heads
    if use_pallas is False or not ft_block_applicable(
            s, d, heads, spec.mlp_ratio):
        raise ValueError(
            "fused_transformer_block called while not applicable; gate "
            "call sites on fused_block_engaged()")
    in_dtype = x.dtype
    sp = -(-s // 8) * 8
    bp = -(-b // BATCH_TILE) * BATCH_TILE
    xf = x.astype(jnp.float32)
    if sp != s or bp != b:
        xf = jnp.pad(xf, ((0, bp - b), (0, sp - s), (0, 0)))
    flat = tuple(jnp.asarray(p[name], jnp.float32) for name in _PARAM_ORDER)
    out = _fused_block(xf, flat, s, heads, not on_tpu())
    return out[:b, :s].astype(in_dtype)
