"""The gated delta rule (Gated DeltaNet: Yang, Kautz & Hatamizadeh,
arXiv:2412.06464), a linear-attention sequence mixer, computed by chunks.

The recurrence, per value head, from a zero state at each row's start (the
state `S` is (d_k, d_v); `k` and `q` are the head's normalised key and query,
`alpha` its decay in (0, 1], `beta` its write strength in (0, 1)):

    S'  = alpha_t S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

`gated_delta_rule` computes it a chunk of C positions at a time, as matrix
products the MXU takes.  With `G_i` the running sum of `log alpha` inside a
chunk and `S_0` the state entering it, the writes `u_i = beta_i (v_i - (alpha_i
S_{i-1})^T k_i)` obey the unit lower-triangular system

    (I + A) U = beta V - (beta exp(G) K) S_0,
    A_ij = beta_i exp(G_i - G_j) (k_i . k_j)   for i > j,

so `T = (I + A)^-1` is found once a chunk and `U = T beta V - (T beta exp(G)
K) S_0`; then `O = (exp(G) Q) S_0 + ((Q K^T) exp(G_i - G_j))_{i >= j} U` and
the state leaves the chunk as `exp(G_C) S_0 + (exp(G_C - G) K)^T U`.  The
chunks' own matrices are made for all chunks at once; the (d_k, d_v) state is
carried across them in float32 by a scan.

`T` is made of float32 matrix products and of no solve
(`unit_lower_inverse`): a block forward substitution.  A diagonal block of 8
positions is inverted by its nilpotent series, `(I - A_bb)(I + A_bb^2)(I +
A_bb^4)`, exact on paper since `A_bb^8 = 0`; the inverse of a unit
lower-triangular matrix is, by halves, `[[T11, 0], [-T22 A21 T11, T22]]`, so
from there the diagonal blocks double - 16, 32, ... C - and a doubling is
`T <- T - T (A . M) T` with `M` the blocks that the doubling joins.  All
blocks of a size are handled at once, under a mask: every product is one of
whole (C, C) matrices, ten of them at C = 64.  The series stops at 8 on
purpose: over the whole chunk it is as exact on paper and loses every digit
in float32 once the keys of a chunk share a direction
(tests/test_gated_delta.py), because its powers hold entries near 1e17 that
have to cancel.  The backward is closed too: `dA = -(T^T dT T^T)` below the
diagonal, two products and nothing saved but `T`.

Decays, `A` and `T` are float32 whatever the compute dtype, and the products
that make `T` take float32 operands at the matmul precision that keeps them
float32 on a TPU (whose default is one bfloat16 pass); the other products
take operands in `v`'s dtype and accumulate in float32.  The rest of the
backward pass is autodiff's: the row function is rematerialized, so what the
backward holds of a row is the row's inputs and its `T` (float32, named
`_KEPT` for the checkpoint's policy: rebuilding it would be a third run of
the ten products a layer, where a rematerialized block has run them twice),
not its other (heads, chunks, C, C) matrices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

#: positions a chunk: the (C, C) system is inverted in float32 once a chunk,
#: and half the MXU's width keeps it small beside the (C, d) products
CHUNK = 64

#: positions a diagonal block inverted by its series: at 16 the series' own
#: rounding comes within a factor of two of what the tests allow
_BLOCK = 8

#: the one value a rematerialized row keeps for its backward: `T`, float32
_KEPT = "delta_rule_chunk_inverse"

#: the products that make `T`: float32 operands stay float32 on the MXU (six
#: bfloat16 passes; three miss the tests' limit on the chip and are no faster)
_EXACT = jax.lax.Precision.HIGHEST


def _product(x, y):
    return jnp.matmul(x, y, precision=_EXACT)


def _inverse_by_blocks(a):
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    within = jnp.where(row // _BLOCK == col // _BLOCK, a, 0.0)
    t, power, order = eye - within, within, 2
    while order < min(_BLOCK, c):          # within^order is the next factor's
        power = _product(power, power)
        t = _product(t, eye + power)
        order *= 2
    size = _BLOCK
    while size < c:
        joined = (row // (2 * size) == col // (2 * size)) \
            & (row // size != col // size)
        t = t - _product(_product(t, jnp.where(joined, a, 0.0)), t)
        size *= 2
    return t


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """`(I + a)^-1` for `a` (..., C, C) float32 and strictly lower, any C, by
    block forward substitution: diagonal blocks of 8 by their nilpotent
    series, then doubled to the whole, two masked (C, C) products a
    doubling.  What it does depends on C and on nothing else."""
    return _inverse_by_blocks(a)


def _inverse_fwd(a):
    t = checkpoint_name(_inverse_by_blocks(a), _KEPT)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (jnp.tril(-_product(_product(tt, dt), tt), -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _delta_row(q, k, v, g, beta, chunk: int):
    """One row.  q, k (T, G, D) normalised, v (T, G, R, P) with R value
    heads a key head, g (T, G, R) = log alpha and beta (T, G, R), float32;
    T a multiple of `chunk`.  Returns o (T, G, R, P) in v's dtype."""
    t, kg, r, p = v.shape
    d = k.shape[-1]
    nc, c = t // chunk, chunk
    cdt, f32 = v.dtype, jnp.float32
    q = q.reshape(nc, c, kg, d)
    k = k.reshape(nc, c, kg, d)
    v = v.reshape(nc, c, kg, r, p)
    beta = beta.reshape(nc, c, kg, r)
    cs = jnp.cumsum(g.reshape(nc, c, kg, r), axis=1)       # G, float32

    # the decays between two positions of a chunk, exp(G_i - G_j), i >= j
    cs_h = jnp.moveaxis(cs, 1, -1)                         # (nc, G, R, C)
    diff = cs_h[..., :, None] - cs_h[..., None, :]
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))      # (nc, G, R, Ci, Cj)

    # T = (I + A)^-1, A strictly lower: one system a chunk and a value head
    kk = jnp.einsum("cigd,cjgd->cgij", k, k, preferred_element_type=f32)
    beta_h = jnp.moveaxis(beta, 1, -1)                     # (nc, G, R, C)
    a = (kk[:, :, None] * decay * beta_h[..., :, None]
         * jnp.tril(jnp.ones((c, c), f32), -1))
    tri = unit_lower_inverse(a).astype(cdt)

    # U0 = T beta V and W = T beta exp(G) K, the writes with and without
    # the entering state's part; what the scan takes is laid a head first
    bv = (v.astype(f32) * beta[..., None]).astype(cdt)
    bk = (k.astype(f32)[:, :, :, None]
          * (beta * jnp.exp(cs))[..., None]).astype(cdt)   # (nc, C, G, R, D)
    u0 = jnp.einsum("cgrij,cjgrp->cgrip", tri, bv, preferred_element_type=f32)
    w = jnp.einsum("cgrij,cjgrd->cgrid", tri, bk,
                   preferred_element_type=f32).astype(cdt)
    qk = jnp.einsum("cigd,cjgd->cgij", q, k, preferred_element_type=f32)
    attn = (qk[:, :, None] * decay).astype(cdt)            # (nc, G, R, Ci, Cj)
    q_h = jnp.moveaxis(q, 1, 2).astype(f32)[:, :, None]    # (nc, G, 1, C, D)
    k_h = jnp.moveaxis(k, 1, 2).astype(f32)[:, :, None]
    q_in = (q_h * jnp.exp(cs_h)[..., None]).astype(cdt)    # exp(G) Q
    k_out = (k_h * jnp.exp(cs_h[..., -1:] - cs_h)[..., None]).astype(cdt)
    total = jnp.exp(cs_h[..., -1])                         # (nc, G, R)

    def chunk_step(state, xs):
        u0_c, w_c, attn_c, q_c, k_c, total_c = xs
        s = state.astype(cdt)                              # (G, R, D, P)
        u = (u0_c - jnp.einsum("grid,grdp->grip", w_c, s,
                               preferred_element_type=f32)).astype(cdt)
        o = (jnp.einsum("grid,grdp->grip", q_c, s, preferred_element_type=f32)
             + jnp.einsum("grij,grjp->grip", attn_c, u,
                          preferred_element_type=f32))
        state = (state * total_c[..., None, None]
                 + jnp.einsum("grid,grip->grdp", k_c, u,
                              preferred_element_type=f32))
        return state, o.astype(cdt)

    _, o = jax.lax.scan(chunk_step, jnp.zeros((kg, r, d, p), f32),
                        (u0, w, attn, q_in, k_out, total))
    return jnp.moveaxis(o, 3, 1).reshape(t, kg, r, p)      # (nc, C, G, R, P)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, chunk: int = CHUNK) -> jax.Array:
    """The rule by chunks.  q, k (B, T, Hk, D): a key head's query (scaled)
    and key, both of unit length or under; v (B, T, Hv, P) with value head h
    reading key head h // (Hv // Hk); g (B, T, Hv) = log alpha <= 0 and beta
    (B, T, Hv), float32.  Returns o (B, T, Hv, P) in v's dtype.  A row
    shorter than `chunk` is one chunk; a length that is no multiple of
    `chunk` is padded with positions of alpha 1 and beta 0, which leave the
    state as it is, and cut again."""
    b, t, hv, p = v.shape
    hk = k.shape[2]
    r = hv // hk
    chunk = min(chunk, t)
    pad = (-t) % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (x.ndim - 2)) for x in (q, k, v, g, beta))
    cdt = v.dtype
    xs = (q.astype(cdt), k.astype(cdt), v.reshape(b, t + pad, hk, r, p),
          g.astype(jnp.float32).reshape(b, t + pad, hk, r),
          beta.astype(jnp.float32).reshape(b, t + pad, hk, r))
    row = jax.checkpoint(
        lambda x: _delta_row(*x, chunk),
        policy=jax.checkpoint_policies.save_only_these_names(_KEPT))
    o = jax.lax.map(row, xs)
    return o.reshape(b, t + pad, hv, p)[:, :t]
