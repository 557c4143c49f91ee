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

so `T = (I + A)^-1` is found once a chunk (a triangular solve in float32) and
`U = T beta V - (T beta exp(G) K) S_0`; then `O = (exp(G) Q) S_0 + ((Q K^T)
exp(G_i - G_j))_{i >= j} U` and the state leaves the chunk as `exp(G_C) S_0 +
(exp(G_C - G) K)^T U`.  The chunks' own matrices are made for all chunks at
once; the (d_k, d_v) state is carried across them in float32 by a scan.
Decays and the solve are float32 whatever the compute dtype; the products
take operands in `v`'s dtype and accumulate in float32.  The backward pass is
autodiff's: the row function is rematerialized, so what the backward holds
of a row is the row's inputs, not its (heads, chunks, C, C) matrices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions a chunk: the (C, C) system is solved in float32 once a chunk,
#: and half the MXU's width keeps it small beside the (C, d) products
CHUNK = 64


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

    # T = (I + A)^-1, A strictly lower: one solve a chunk and a value head
    kk = jnp.einsum("cigd,cjgd->cgij", k, k, preferred_element_type=f32)
    beta_h = jnp.moveaxis(beta, 1, -1)                     # (nc, G, R, C)
    a = (kk[:, :, None] * decay * beta_h[..., :, None]
         * jnp.tril(jnp.ones((c, c), f32), -1))
    eye = jnp.eye(c, dtype=f32)
    tri = jax.lax.linalg.triangular_solve(
        eye + a, jnp.broadcast_to(eye, a.shape), left_side=True, lower=True,
        unit_diagonal=True).astype(cdt)

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
    row = jax.checkpoint(lambda x: _delta_row(*x, chunk))
    o = jax.lax.map(row, xs)
    return o.reshape(b, t + pad, hv, p)[:, :t]
