"""Attention ops: fused-softmax MHA and ring attention for sequence/context
parallelism.

The reference has no attention at all (tabular MLP only — SURVEY.md section
5.7); these ops serve the FT-Transformer ladder rung and make long-context
first-class: `ring_attention` shards the sequence axis across the mesh's
`seq` axis and rotates K/V blocks over ICI with `ppermute`, computing a
numerically-stable streaming softmax (flash-style running max/normalizer) so
no device ever materializes the full S x S score matrix.  Inputs of any
sequence length scale across the ring with O(S/n) memory per device.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def mha(q: jax.Array, k: jax.Array, v: jax.Array,
        scale: Optional[float] = None) -> jax.Array:
    """Standard multi-head attention.  q,k,v: (B, H, S, D) -> (B, H, S, D).

    Softmax accumulates in float32 regardless of input dtype (bf16-safe).
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)


def _ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array,
                          axis_name: str, scale: float) -> jax.Array:
    """Per-device body: stream K/V blocks around the ring, accumulating a
    stable softmax.  Shapes per device: q (B,H,Sq,D), k/v (B,H,Sk,D)."""
    n = jax.lax.psum(1, axis_name)
    b, h, sq, d = q.shape

    qf = q.astype(jnp.float32)

    def step(i, carry):
        o, m, l, k_blk, v_blk = carry
        scores = jnp.einsum("bhqd,bhkd->bhqk", qf,
                            k_blk.astype(jnp.float32)) * scale
        blk_max = jnp.max(scores, axis=-1)                      # (B,H,Sq)
        new_m = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[..., None])                  # (B,H,Sq,Sk)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        # rotate K/V one step around the ring (ICI neighbor exchange)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return o, new_m, l, k_blk, v_blk

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o, m, l, _, _ = jax.lax.fori_loop(0, n, step, (o0, m0, l0, k, v))
    return (o / l[..., None]).astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Mesh, seq_axis: str = "seq",
                   scale: Optional[float] = None) -> jax.Array:
    """Sequence-parallel attention: q,k,v (B,H,S,D) sharded on S over
    `seq_axis`; returns (B,H,S,D) with the same sharding.

    Equivalent to `mha` (same math, streamed); validated against it in
    tests/test_attention.py.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    spec = _sp_spec(mesh, seq_axis)
    fn = jax.shard_map(
        functools.partial(_ring_attention_local, axis_name=seq_axis, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def _sp_spec(mesh: Mesh, seq_axis: str) -> P:
    """Partition spec for sequence-parallel q/k/v: sequence on `seq_axis`
    AND batch on `data` when the mesh has one — omitting the data axis would
    make shard_map all-gather the batch and recompute attention identically
    on every data replica (n_data x FLOPs/memory for nothing)."""
    from ..parallel.mesh import DATA_AXIS
    batch_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None
    return P(batch_axis, None, seq_axis, None)


def _ulysses_local(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, scale: float) -> jax.Array:
    """Per-device body: all-to-all re-shards heads<->sequence so each device
    holds H/n full-sequence heads, computes exact local attention, then
    re-shards back.  One fused XLA all-to-all each way (ICI-friendly), versus
    the ring's n ppermute hops — the better trade when H >= n and per-step
    latency matters more than peak memory."""
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    # (B, H, S/n, D) -> (B, H/n, S, D): scatter heads, gather sequence
    qh = a2a(q, split_axis=1, concat_axis=2)
    kh = a2a(k, split_axis=1, concat_axis=2)
    vh = a2a(v, split_axis=1, concat_axis=2)
    out = mha(qh, kh, vh, scale=scale)
    # (B, H/n, S, D) -> (B, H, S/n, D): gather heads, scatter sequence
    return a2a(out, split_axis=2, concat_axis=1)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      mesh: Mesh, seq_axis: str = "seq",
                      scale: Optional[float] = None) -> jax.Array:
    """All-to-all sequence-parallel attention (DeepSpeed-Ulysses style):
    q,k,v (B,H,S,D) sharded on S over `seq_axis`; returns the same sharding.

    The complement of `ring_attention` for long-context scale-out: identical
    math (validated against `mha` in tests/test_attention.py), different
    communication shape — two all-to-alls total instead of n ppermute
    rotations.  Requires H to be divisible by the `seq_axis` size (heads are
    the scatter dimension).
    """
    n = mesh.shape[seq_axis]
    h = q.shape[1]
    if h % n != 0:
        raise ValueError(
            f"ulysses_attention needs heads ({h}) divisible by the "
            f"'{seq_axis}' mesh axis ({n}); use ring_attention otherwise")
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    spec = _sp_spec(mesh, seq_axis)
    fn = jax.shard_map(
        functools.partial(_ulysses_local, axis_name=seq_axis, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


#: queries of one row are taken this many at a time, each against the keys
#: up to its own end: a (heads, 1024, T) score block, not (heads, T, T)
CAUSAL_QUERY_BLOCK = 1024


def _causal_block(q, k, v, first: int, scale: float):
    """Queries first..first+Q of one row against keys 0..first+Q.  q (Q, G,
    R, D) with R query heads a key-value head, k / v (S, G, D), S = first+Q.
    Softmax in float32; the products take operands in v's dtype."""
    scores = jnp.einsum("qgrd,sgd->grqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    qpos = first + jnp.arange(q.shape[0])
    mask = qpos[:, None] >= jnp.arange(k.shape[0])[None, :]
    w = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("grqs,sgd->qgrd", w.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def causal_gqa(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal grouped-query attention, no positional term.  q (B, T, Hq, D),
    k (B, T, Hkv, D), v (B, T, Hkv, Dv), Hq a multiple of Hkv (query head h
    reads key-value head h // (Hq // Hkv)).  Returns (B, T, Hq, Dv).

    Plain XLA: a row at a time, a block of queries at a time, each block
    rematerialized so that the backward holds a row's q, k, v and one
    block's scores; keys past a block's last query are never read."""
    b, t, hq, d = q.shape
    g = k.shape[2]
    scale = 1.0 / (d ** 0.5)
    blk = CAUSAL_QUERY_BLOCK if t % CAUSAL_QUERY_BLOCK == 0 else t
    block = jax.checkpoint(_causal_block, static_argnums=(3, 4))

    def row(xs):
        q_r, k_r, v_r = xs
        q_r = q_r.reshape(t, g, hq // g, d)
        out = [block(q_r[lo:lo + blk], k_r[:lo + blk], v_r[:lo + blk], lo,
                     scale) for lo in range(0, t, blk)]
        return jnp.concatenate(out, axis=0).reshape(t, hq, v.shape[-1])

    return jax.lax.map(row, (q, k, v))


def causal_latent_attention(q_nope: jax.Array, q_pe: jax.Array,
                            k_nope: jax.Array, k_pe: jax.Array,
                            v: jax.Array) -> jax.Array:
    """Causal multi-head latent attention in its training form: a score is
    a head's own product plus a product with one rotary key all heads share,
    `(q_nope . k_nope + q_pe . k_pe) / sqrt(Dn + Dr)`, and the values are of
    a width of their own.  q_nope, k_nope (B, T, H, Dn), q_pe (B, T, H, Dr),
    k_pe (B, T, Dr) - one head - v (B, T, H, Dv).  Returns (B, T, H, Dv).

    The shared key is copied a head and joined to the head's own dims, so
    the score is one product over Dn + Dr dims: `causal_gqa` of the joined
    queries and keys."""
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :],
                            (*k_nope.shape[:3], k_pe.shape[-1]))
    return causal_gqa(jnp.concatenate([q_nope, q_pe], axis=-1),
                      jnp.concatenate([k_nope, k_pe], axis=-1), v)
