"""Mamba-2's sequence mixer pieces: the causal depthwise convolution and the
state-space scan computed by chunks (SSD: Dao & Gu, arXiv:2405.21060,
section 6).

The recurrence, per head, from a zero state at each row's start:

    h_t = exp(delta_t A) h_{t-1} + delta_t x_t (x) B_t        (P x N state)
    y_t = h_t C_t + D x_t

`ssd_chunked` computes it a chunk of Q positions at a time, as matrix
products the MXU takes: inside a chunk the contribution of position s to
position t >= s is `exp(cs_t - cs_s) (C_t . B_s) delta_s x_s` with `cs` the
running sum of `delta A` (a masked (Q, Q) product); across chunks a state of
(P, N) a head is carried by an exact float32 recurrence over the chunks.
Decays are float32 whatever the compute dtype; the products take operands in
`x`'s dtype and accumulate in float32.  The backward pass is autodiff's: the
row function is rematerialized, so what the backward holds of a row is the
row's inputs, not its (heads, chunks, Q, Q) decay matrices.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

#: positions a chunk of the scan: the published `chunk_size`, and the MXU's
#: width on the chip
CHUNK = 128


def causal_conv1d(x: jax.Array, w: jax.Array,
                  b: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal convolution along axis 1: `y_t = b + sum_j w[j]
    x_{t-(K-1)+j}`, positions before the row's start zero.  x (B, T, C),
    w (K, C), b (C,) or None where the convolution has no bias."""
    k = w.shape[0]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = None if b is None else b.astype(x.dtype)
    for j in range(k):
        term = xp[:, j:j + t, :] * w[j].astype(x.dtype)
        y = term if y is None else y + term
    return y


def _ssd_row(x, dt, a_head, d_head, bm, cm, chunk: int):
    """One row.  x (T, G, Hg, P), dt (T, G, Hg) float32, a_head and d_head
    (G, Hg) float32 (A negative), bm / cm (T, G, N); T a multiple of
    `chunk`.  Returns y (T, G, Hg, P) in x's dtype."""
    t, g, hg, p = x.shape
    n = bm.shape[-1]
    nc, q = t // chunk, chunk
    cdt = x.dtype
    f32 = jnp.float32
    x = x.reshape(nc, q, g, hg, p)
    dt = dt.reshape(nc, q, g, hg)
    bm = bm.reshape(nc, q, g, n)
    cm = cm.reshape(nc, q, g, n)
    cs = jnp.cumsum(dt * a_head, axis=1)              # (nc, Q, G, Hg) f32
    xd = (x.astype(f32) * dt[..., None]).astype(cdt)  # delta_s x_s

    # inside the chunk: (C_t . B_s) exp(cs_t - cs_s), s <= t
    cb = jnp.einsum("ctgn,csgn->cgts", cm, bm, preferred_element_type=f32)
    cs_h = jnp.moveaxis(cs, 1, -1)                    # (nc, G, Hg, Q)
    diff = cs_h[..., :, None] - cs_h[..., None, :]    # (nc, G, Hg, Qt, Qs)
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    m = (cb[:, :, None] * decay).astype(cdt)
    y = jnp.einsum("cghts,csghp->ctghp", m, xd, preferred_element_type=f32)

    # each chunk's own state at its end, then the carry across chunks
    to_end = jnp.exp(cs[:, -1:] - cs)                 # (nc, Q, G, Hg)
    xe = (xd.astype(f32) * to_end[..., None]).astype(cdt)
    own = jnp.einsum("csghp,csgn->cghpn", xe, bm, preferred_element_type=f32)
    total = jnp.exp(cs[:, -1])                        # (nc, G, Hg)

    def carry(state, xs):
        own_c, total_c = xs
        return state * total_c[..., None, None] + own_c, state

    _, entering = jax.lax.scan(carry, jnp.zeros_like(own[0]), (own, total))
    y_in = jnp.einsum("ctgn,cghpn->ctghp", cm, entering.astype(cdt),
                      preferred_element_type=f32)
    y = (y + y_in * jnp.exp(cs)[..., None]
         + x.astype(f32) * d_head[..., None])
    return y.astype(cdt).reshape(t, g, hg, p)


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
                cm: jax.Array, d: jax.Array, chunk: int = CHUNK) -> jax.Array:
    """The scan by chunks.  x (B, T, H, P); dt (B, T, H) = delta, float32;
    a (H,) = A, negative; bm, cm (B, T, G, N) with head h in group
    h // (H // G); d (H,).  Returns y (B, T, H, P) in x's dtype.  A row
    shorter than `chunk` is one chunk; a length that is no multiple of
    `chunk` is padded with positions of delta 0, which leave the state as it
    is, and cut again."""
    b, t, h, p = x.shape
    g = bm.shape[2]
    hg = h // g
    chunk = min(chunk, t)
    pad = (-t) % chunk
    if pad:
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (v.ndim - 2)) for v in (x, dt, bm, cm))
    xg = x.reshape(b, t + pad, g, hg, p)
    dtg = dt.astype(jnp.float32).reshape(b, t + pad, g, hg)
    a_head = a.astype(jnp.float32).reshape(g, hg)
    d_head = d.astype(jnp.float32).reshape(g, hg)
    row = jax.checkpoint(lambda xs: _ssd_row(
        xs[0], xs[1], a_head, d_head, xs[2], xs[3], chunk))
    y = jax.lax.map(row, (xg, dtg, bm, cm))
    return y.reshape(b, t + pad, h, p)[:, :t]

