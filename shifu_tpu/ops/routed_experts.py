"""A routed expert layer told which experts it holds (expert parallelism's
share of a layer): top-k routing over all the experts, and the held experts'
part of the result, with no token dropped whatever the load.

`route_topk` (sigmoid scores) and `route_softmax_topk` (softmax
probabilities, renormalised over the chosen) score every expert and pick k a
token.  `plan_dispatch` lays
the (token, slot) choices that fell on held experts out in rows grouped by
expert, each group padded to whole blocks of `block_rows`; it is sized for
the worst case (every choice on a held expert), so nothing is ever dropped.
`routed_relu2_mlp` then walks the blocks that are live - a `while` whose trip
count is the load, not the worst case - gathers a block's tokens, runs them
through that block's expert (`W2 relu(W1 x)^2`) and scatter-adds the weighted
result to the tokens' rows.  Its backward pass walks the same blocks again
(recomputing the hidden activation) and accumulates the experts' weight
gradients in float32.  `routed_gated_mlp` is the same walk through gated
experts (`W_down (silu(W_gate x) * W_up x)`, three matrices an expert).
Plain XLA: no kernel, no capacity factor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def route_topk(logits: jax.Array, k: int, scale: float):
    """Sigmoid-score routing (`n_group` 1, the correction bias at zero):
    logits (T, E) float32 -> (experts (T, k) int32, weights (T, k) float32,
    `scale * s / sum(s)` over the k chosen scores)."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    chosen, experts = jax.lax.top_k(s, k)
    weights = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights


def route_softmax_topk(logits: jax.Array, k: int):
    """Softmax routing with the chosen weights renormalised
    (`norm_topk_prob`): logits (T, E) float32 -> (experts (T, k) int32,
    weights (T, k) float32, `p / sum(p)` over the k largest of
    `p = softmax(logits)`)."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    chosen, experts = jax.lax.top_k(p, k)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights


def default_block_rows(slots: int, held: int) -> int:
    """512 rows a block where an expert's share is at least that (one MXU
    pass per weight read is then worth it), else the power of two under an
    expert's mean share, at least 8."""
    share = max(slots // max(held, 1), 8)
    return min(512, 1 << (share.bit_length() - 1))


def plan_dispatch(experts: jax.Array, first: int, held: int,
                  block_rows: int) -> dict:
    """Where each (token, slot) choice on a held expert goes.

    experts (T, k): the chosen expert ids over all the experts.  Returns
    `row_slot` (N,): the flat choice t*k+j a row of the dispatch holds, T*k
    where the row is padding; `block_expert` (N / block_rows,): the held
    expert (0..held) a block belongs to; `live_blocks`: how many leading
    blocks hold rows; and the counters `tokens_per_expert` (held,),
    `held_slots`, `dispatched_slots`.  N = T * min(k, held) + held *
    (block_rows - 1), up to whole blocks: every choice fits, so
    `held_slots - dispatched_slots` is 0 by construction."""
    t, k = experts.shape
    slots = t * k
    local = experts.reshape(-1) - first
    is_held = (local >= 0) & (local < held)
    e = jnp.where(is_held, local, 0)
    onehot = (is_held[:, None]
              & (e[:, None] == jnp.arange(held)[None, :])).astype(jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - onehot          # (slots, held)
    counts = jnp.sum(onehot, axis=0)
    padded = -(-counts // block_rows) * block_rows
    ends = jnp.cumsum(padded)
    n_blocks = -(-(t * min(k, held) + held * (block_rows - 1)) // block_rows)
    n_rows = n_blocks * block_rows
    own_rank = jnp.take_along_axis(rank, e[:, None], axis=1)[:, 0]
    pos = jnp.where(is_held, (ends - padded)[e] + own_rank, n_rows)
    row_slot = jnp.full((n_rows,), slots, jnp.int32).at[pos].set(
        jnp.arange(slots, dtype=jnp.int32), mode="drop")
    block_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_blocks) * block_rows,
                         side="right"), held - 1).astype(jnp.int32)
    return {"row_slot": row_slot, "block_expert": block_expert,
            "live_blocks": (ends[-1] // block_rows).astype(jnp.int32),
            "tokens_per_expert": counts,
            "held_slots": jnp.sum(counts),
            "dispatched_slots": jnp.sum(row_slot < slots)}


def _block(i, block_rows, row_token, row_weight, block_expert):
    lo = i * block_rows
    return (jax.lax.dynamic_slice(row_token, (lo,), (block_rows,)),
            jax.lax.dynamic_slice(row_weight, (lo,), (block_rows,)),
            block_expert[i])


def _hidden(xb, w1_e):
    h = jnp.dot(xb, w1_e, preferred_element_type=jnp.float32)
    r = jax.nn.relu(h)
    return r, (r * r).astype(xb.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def routed_relu2_mlp(x, w1, w2, row_weight, row_token, block_expert,
                     live_blocks, block_rows: int):
    """sum over a token's choices on held experts of `w * W2_e relu(W1_e
    x)^2`.  x (T, H) in the compute dtype; w1 (E, H, F), w2 (E, F, H);
    row_weight (N,) float32 and row_token (N,) int32 (T where the row is
    padding) in the order of `plan_dispatch`.  Returns (T, H) float32."""
    return _routed_fwd(x, w1, w2, row_weight, row_token, block_expert,
                       live_blocks, block_rows)[0]


def _routed_fwd(x, w1, w2, row_weight, row_token, block_expert, live_blocks,
                block_rows):
    cdt = x.dtype
    w1c, w2c = w1.astype(cdt), w2.astype(cdt)

    def body(i, out):
        tok, rw, e = _block(i, block_rows, row_token, row_weight,
                            block_expert)
        xb = x.at[tok].get(mode="fill", fill_value=0)
        _, a = _hidden(xb, w1c[e])
        y = jnp.dot(a, w2c[e], preferred_element_type=jnp.float32)
        return out.at[tok].add(rw[:, None] * y, mode="drop")

    out = jax.lax.fori_loop(0, live_blocks, body,
                            jnp.zeros(x.shape, jnp.float32))
    return out, (x, w1, w2, row_weight, row_token, block_expert, live_blocks)


def _routed_bwd(block_rows, res, g):
    x, w1, w2, row_weight, row_token, block_expert, live_blocks = res
    cdt = x.dtype
    w1c, w2c = w1.astype(cdt), w2.astype(cdt)
    g = g.astype(cdt)

    def body(i, carry):
        dx, dw1, dw2, drw = carry
        tok, rw, e = _block(i, block_rows, row_token, row_weight,
                            block_expert)
        xb = x.at[tok].get(mode="fill", fill_value=0)
        gb = g.at[tok].get(mode="fill", fill_value=0)
        r, a = _hidden(xb, w1c[e])
        y = jnp.dot(a, w2c[e], preferred_element_type=jnp.float32)
        drw = jax.lax.dynamic_update_slice(
            drw, jnp.sum(gb.astype(jnp.float32) * y, axis=-1),
            (i * block_rows,))
        gy = (rw[:, None] * gb).astype(cdt)
        da = jnp.dot(gy, w2c[e].T, preferred_element_type=jnp.float32)
        dh = (da * 2.0 * r).astype(cdt)
        dxb = jnp.dot(dh, w1c[e].T, preferred_element_type=jnp.float32)
        dw2 = dw2.at[e].add(jnp.dot(a.T, gy,
                                    preferred_element_type=jnp.float32))
        dw1 = dw1.at[e].add(jnp.dot(xb.T, dh,
                                    preferred_element_type=jnp.float32))
        return dx.at[tok].add(dxb, mode="drop"), dw1, dw2, drw

    dx, dw1, dw2, drw = jax.lax.fori_loop(
        0, live_blocks, body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros(w1.shape, jnp.float32),
         jnp.zeros(w2.shape, jnp.float32),
         jnp.zeros(row_weight.shape, jnp.float32)))
    return (dx.astype(cdt), dw1.astype(w1.dtype), dw2.astype(w2.dtype), drw,
            None, None, None)


routed_relu2_mlp.defvjp(_routed_fwd, _routed_bwd)


def _gated_hidden(xb, wg_e, wu_e):
    hg = jnp.dot(xb, wg_e, preferred_element_type=jnp.float32)
    hu = jnp.dot(xb, wu_e, preferred_element_type=jnp.float32)
    return hg, hu, (jax.nn.silu(hg) * hu).astype(xb.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def routed_gated_mlp(x, w_gate, w_up, w_down, row_weight, row_token,
                     block_expert, live_blocks, block_rows: int):
    """sum over a token's choices on held experts of `w * W_down_e
    (silu(W_gate_e x) * W_up_e x)`.  x (T, H) in the compute dtype; w_gate,
    w_up (E, H, F), w_down (E, F, H); the rest as `routed_relu2_mlp` takes
    it.  Returns (T, H) float32."""
    return _gated_fwd(x, w_gate, w_up, w_down, row_weight, row_token,
                      block_expert, live_blocks, block_rows)[0]


def _gated_fwd(x, w_gate, w_up, w_down, row_weight, row_token, block_expert,
               live_blocks, block_rows):
    cdt = x.dtype
    wgc, wuc, wdc = (w.astype(cdt) for w in (w_gate, w_up, w_down))

    def body(i, out):
        tok, rw, e = _block(i, block_rows, row_token, row_weight,
                            block_expert)
        xb = x.at[tok].get(mode="fill", fill_value=0)
        _, _, a = _gated_hidden(xb, wgc[e], wuc[e])
        y = jnp.dot(a, wdc[e], preferred_element_type=jnp.float32)
        return out.at[tok].add(rw[:, None] * y, mode="drop")

    out = jax.lax.fori_loop(0, live_blocks, body,
                            jnp.zeros(x.shape, jnp.float32))
    return out, (x, w_gate, w_up, w_down, row_weight, row_token,
                 block_expert, live_blocks)


def _gated_bwd(block_rows, res, g):
    (x, w_gate, w_up, w_down, row_weight, row_token, block_expert,
     live_blocks) = res
    cdt = x.dtype
    wgc, wuc, wdc = (w.astype(cdt) for w in (w_gate, w_up, w_down))
    g = g.astype(cdt)

    def body(i, carry):
        dx, dwg, dwu, dwd, drw = carry
        tok, rw, e = _block(i, block_rows, row_token, row_weight,
                            block_expert)
        xb = x.at[tok].get(mode="fill", fill_value=0)
        gb = g.at[tok].get(mode="fill", fill_value=0)
        hg, hu, a = _gated_hidden(xb, wgc[e], wuc[e])
        y = jnp.dot(a, wdc[e], preferred_element_type=jnp.float32)
        drw = jax.lax.dynamic_update_slice(
            drw, jnp.sum(gb.astype(jnp.float32) * y, axis=-1),
            (i * block_rows,))
        gy = (rw[:, None] * gb).astype(cdt)
        da = jnp.dot(gy, wdc[e].T, preferred_element_type=jnp.float32)
        sig = jax.nn.sigmoid(hg)
        dhu = (da * hg * sig).astype(cdt)
        dhg = (da * hu * sig * (1.0 + hg * (1.0 - sig))).astype(cdt)
        dxb = (jnp.dot(dhg, wgc[e].T, preferred_element_type=jnp.float32)
               + jnp.dot(dhu, wuc[e].T, preferred_element_type=jnp.float32))
        dwd = dwd.at[e].add(jnp.dot(a.T, gy,
                                    preferred_element_type=jnp.float32))
        dwg = dwg.at[e].add(jnp.dot(xb.T, dhg,
                                    preferred_element_type=jnp.float32))
        dwu = dwu.at[e].add(jnp.dot(xb.T, dhu,
                                    preferred_element_type=jnp.float32))
        return dx.at[tok].add(dxb, mode="drop"), dwg, dwu, dwd, drw

    dx, dwg, dwu, dwd, drw = jax.lax.fori_loop(
        0, live_blocks, body,
        (jnp.zeros(x.shape, jnp.float32),
         jnp.zeros(w_gate.shape, jnp.float32),
         jnp.zeros(w_up.shape, jnp.float32),
         jnp.zeros(w_down.shape, jnp.float32),
         jnp.zeros(row_weight.shape, jnp.float32)))
    return (dx.astype(cdt), dwg.astype(w_gate.dtype), dwu.astype(w_up.dtype),
            dwd.astype(w_down.dtype), drw, None, None, None)


routed_gated_mlp.defvjp(_gated_fwd, _gated_bwd)
