"""Plain reference of the gated-DeltaNet / gated-attention / routed-expert
stack (`model_type` `qwen3_next`: Qwen3-Next-80B-A3B's `config.json`) as a
sequence scorer: one token table, a published layer as two pre-norm residual
blocks (its mixer, then its expert layer), a final RMSNorm, the last
position's vector into the one-logit head `shifu_output_0`.

Float32 `jax.numpy`, nothing of `shifu_tpu`, no kernel and no chunking of
the mathematics: the gated delta rule a position at a time as written,
attention as one masked softmax a head, the experts as a loop over the held
ones with the router's mask.  `rnd` rounds the operands of every product
(`common`).

    N_w(x) = x rsqrt(mean(x^2) + eps) (1 + w)            every norm but one
    layer l: x <- x + mixer_l(N(x)); x <- x + moe_l(N(x))
    mixer_l: full attention where (l + 1) % full_attention_interval == 0,
             the gated delta rule otherwise

The gated delta rule, a value head (key head h // 2), from a zero state:
`S' = alpha_t S`, `S = S' + beta_t k_t (v_t - S'^T k_t)^T`, `o_t = S^T q_t`,
with q and k after a causal depthwise convolution and silu, normalised to
unit length (q also by 1 / sqrt(d_k)), `beta = sigmoid(b)`, `alpha =
exp(-exp(A_log) softplus(a + dt_bias))`; its output through a plain RMSNorm
over a head's dims (weight `g` from one) and the gate `silu(z)`.  Attention:
per-head `N` on q and k, the rotary term on the first
`partial_rotary_factor` of a head's dims, causal softmax, the result times
`sigmoid(gate)`.  Experts: softmax over all the router's experts, the
`num_experts_per_tok` largest renormalised, gated experts `W_d (silu(W_g x)
* W_u x)`, and one shared expert behind `sigmoid(x . w_sg)`.

What it does to fit beside 9 GB of its own state changes no arithmetic: a
row at a time (`lax.map`), each row and each block rematerialized, the
recurrence's steps rematerialized a segment at a time, attention a head at
a time; and the blocks that follow the last sequence mixer are computed for
the last position alone, the only one the head reads (an expert block mixes
nothing along the sequence, so that position's value is the same).

Departures from the published description, each in the configuration file
too: the columns of the two in-projections lie `[q | k | v | z]` and
`[b | a]` (the checkpoint interleaves them a key head; with seeded weights
the order is the file's to state); the chip's share - experts
`first_expert_held .. +num_experts` of `router_experts`, what the others
would add left out; no multi-token-prediction module; the head and the loss
are Shifu's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..counts.qwen3_next import pattern
from .common import dense, dense_init, param_key

INIT_STD = 0.02
SEGMENT = 64        # steps of the recurrence rematerialized together
UNROLL = 8          # of which so many a trip of the compiled loop
QK_EPS = 1e-6       # under the root of q's and k's squared length


def _shapes(cfg: dict) -> dict:
    dep = cfg["deployment"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    d = cfg["head_dim"]
    return {
        "pattern": pattern(cfg), "hidden": cfg["hidden_size"],
        "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "hk": hk, "hv": hv, "dk": dk, "dv": dv, "d_key": hk * dk,
        "d_value": hv * dv, "d_conv": 2 * hk * dk + hv * dv,
        "k_conv": cfg["linear_conv_kernel_dim"],
        "hq": cfg["num_attention_heads"], "hkv": cfg["num_key_value_heads"],
        "d": d, "rotary": int(d * cfg["partial_rotary_factor"]),
        "theta": float(cfg["rope_theta"]),
        "router": dep["router_experts"], "held": cfg["num_experts"],
        "first": dep["first_expert_held"],
        "top_k": cfg["num_experts_per_tok"],
        "f": cfg["moe_intermediate_size"],
        "fs": cfg["shared_expert_intermediate_size"],
    }


def _normal(seed, path, counter, shape):
    return INIT_STD * jax.random.normal(param_key(seed, path, counter), shape,
                                        jnp.float32)


def _init_block(s: dict, seed: int, kind: str, path) -> dict:
    """One block's weights, drawn in the program's order of declaration
    (the counter is the parameter's number in its module, from 1)."""
    zeros = jnp.zeros((s["hidden"],), jnp.float32)
    if kind == "L":
        bound = s["k_conv"] ** -0.5
        return {
            "norm": zeros,
            "in_proj_qkvz": _normal(seed, path, 2, (s["hidden"], s["d_conv"]
                                                    + s["d_value"])),
            "in_proj_ba": _normal(seed, path, 3, (s["hidden"], 2 * s["hv"])),
            "conv_w": jax.random.uniform(
                param_key(seed, path, 4), (s["k_conv"], s["d_conv"]),
                jnp.float32, -bound, bound),
            "dt_bias": jnp.ones((s["hv"],), jnp.float32),
            "A_log": jnp.log(jax.random.uniform(
                param_key(seed, path, 6), (s["hv"],), jnp.float32, 0.0,
                16.0)),
            "gate_norm": jnp.ones((s["dv"],), jnp.float32),
            "out_proj": _normal(seed, path, 8, (s["d_value"], s["hidden"])),
        }
    if kind == "A":
        return {
            "norm": zeros,
            "q_proj": _normal(seed, path, 2, (s["hidden"],
                                              s["hq"] * 2 * s["d"])),
            "k_proj": _normal(seed, path, 3, (s["hidden"],
                                              s["hkv"] * s["d"])),
            "v_proj": _normal(seed, path, 4, (s["hidden"],
                                              s["hkv"] * s["d"])),
            "q_norm": jnp.zeros((s["d"],), jnp.float32),
            "k_norm": jnp.zeros((s["d"],), jnp.float32),
            "o_proj": _normal(seed, path, 7, (s["hq"] * s["d"],
                                              s["hidden"])),
        }
    return {
        "norm": zeros,
        "router": _normal(seed, path, 2, (s["hidden"], s["router"])),
        "experts/w_gate": _normal(seed, path, 3, (s["held"], s["hidden"],
                                                  s["f"])),
        "experts/w_up": _normal(seed, path, 4, (s["held"], s["hidden"],
                                                s["f"])),
        "experts/w_down": _normal(seed, path, 5, (s["held"], s["f"],
                                                  s["hidden"])),
        "shared/w_gate": _normal(seed, path, 6, (s["hidden"], s["fs"])),
        "shared/w_up": _normal(seed, path, 7, (s["hidden"], s["fs"])),
        "shared/w_down": _normal(seed, path, 8, (s["fs"], s["hidden"])),
        "shared/gate": _normal(seed, path, 9, (s["hidden"], 1)),
    }


def init_params(cfg: dict, seed: int) -> dict:
    """The seed's initial weights, in one compiled program as the program's
    own initialisation is."""
    return jax.jit(lambda: _init_params(cfg, seed))()


def _init_params(cfg: dict, seed: int) -> dict:
    s = _shapes(cfg)
    params = {f"block{i}": _init_block(s, seed, kind, (f"block{i}",))
              for i, kind in enumerate(s["pattern"])}
    params["embed_tokens"] = _normal(seed, (), 1, (s["vocab"], s["hidden"]))
    params["norm_f"] = jnp.zeros((s["hidden"],), jnp.float32)
    params["head"] = {"shifu_output_0": dense_init(
        seed, ("head", "shifu_output_0"), s["hidden"], 1)}
    return params


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def norm(x, weight, eps):
    """N_w: zero-centred, `x_hat (1 + w)` over the last axis."""
    return _rms(x, eps) * (1.0 + weight)


def delta_recurrence(q, k, v, alpha, beta, rnd=lambda x: x):
    """One row's gated delta rule as written.  q, k (T, Hk, Dk) normalised,
    v (T, Hv, Dv), alpha and beta (T, Hv); value head h reads key head
    h // (Hv // Hk).  Returns o (T, Hv, Dv)."""
    t, hv, dv = v.shape
    hk, dk = k.shape[1:]
    qh, kh = (jnp.repeat(x, hv // hk, axis=1) for x in (q, k))  # (T, Hv, Dk)

    def step(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        state = state * a_t[:, None, None]
        read = jnp.einsum("hkv,hk->hv", rnd(state), rnd(k_t))
        write = rnd(b_t[:, None] * (v_t - read))
        state = state + rnd(k_t)[:, :, None] * write[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", rnd(state), rnd(q_t))

    seg = max(n for n in range(1, SEGMENT + 1) if t % n == 0)
    segment = jax.checkpoint(
        lambda state, xs: jax.lax.scan(step, state, xs, unroll=UNROLL))
    xs = tuple(x.reshape(t // seg, seg, *x.shape[1:])
               for x in (qh, kh, v, alpha, beta))
    _, o = jax.lax.scan(segment, jnp.zeros((hv, dk, dv), jnp.float32), xs)
    return o.reshape(t, hv, dv)


def _linear_attention(p, s, x, rnd):
    t = x.shape[0]
    qkvz = rnd(x) @ rnd(p["in_proj_qkvz"])
    qkv, z = jnp.split(qkvz, [s["d_conv"]], axis=-1)
    b, a = jnp.split(rnd(x) @ rnd(p["in_proj_ba"]), 2, axis=-1)
    kc = s["k_conv"]
    padded = jnp.pad(qkv, ((kc - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j:j + t] * p["conv_w"][j]
                          for j in range(kc)))
    q, k, v = jnp.split(qkv, [s["d_key"], 2 * s["d_key"]], axis=-1)
    q = q.reshape(t, s["hk"], s["dk"])
    k = k.reshape(t, s["hk"], s["dk"])
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + QK_EPS) \
        / math.sqrt(s["dk"])
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + QK_EPS)
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"]))
    o = delta_recurrence(q, k, v.reshape(t, s["hv"], s["dv"]), alpha,
                         jax.nn.sigmoid(b), rnd)
    y = _rms(o, s["eps"]) * p["gate_norm"] * jax.nn.silu(
        z.reshape(t, s["hv"], s["dv"]))
    return rnd(y.reshape(t, s["d_value"])) @ rnd(p["out_proj"])


def rope(x, theta: float, rotary: int):
    """The rotary term on a head's first `rotary` dims: x (T, H, D), the
    position the index along axis 0; dims i and i + rotary / 2 turn by
    `t theta^(-2i / rotary)`."""
    half = rotary // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rotary)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
    turn, keep = x[..., :rotary], x[..., rotary:]
    half_turned = jnp.concatenate([-turn[..., half:], turn[..., :half]],
                                  axis=-1)
    return jnp.concatenate([turn * cos + half_turned * sin, keep], axis=-1)


def _attention(p, s, x, rnd):
    t = x.shape[0]
    hq, hkv, d = s["hq"], s["hkv"], s["d"]
    q, gate = jnp.split((rnd(x) @ rnd(p["q_proj"])).reshape(t, hq, 2 * d), 2,
                        axis=-1)
    k = (rnd(x) @ rnd(p["k_proj"])).reshape(t, hkv, d)
    v = (rnd(x) @ rnd(p["v_proj"])).reshape(t, hkv, d)
    q = rope(norm(q, p["q_norm"], s["eps"]), s["theta"], s["rotary"])
    k = rope(norm(k, p["k_norm"], s["eps"]), s["theta"], s["rotary"])
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(xs):
        q_h, kv = xs                                   # (T, D), head index
        scores = rnd(q_h) @ rnd(k[:, kv]).T / math.sqrt(d)
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return rnd(w) @ rnd(v[:, kv])

    o = jax.lax.map(head, (jnp.moveaxis(q, 1, 0),
                           jnp.arange(hq) // (hq // hkv)))
    o = jnp.moveaxis(o, 0, 1) * jax.nn.sigmoid(gate)
    return rnd(o.reshape(t, hq * d)) @ rnd(p["o_proj"])


def route(p, s, x):
    """(chosen experts (T, k) over all the router's experts, their weights
    (T, k)): softmax in float32, the k largest, renormalised."""
    prob = jax.nn.softmax(jnp.dot(x, p["router"],
                                  precision=jax.lax.Precision.HIGHEST),
                          axis=-1)
    chosen, experts = jax.lax.top_k(prob, s["top_k"])
    return experts, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def _mlp(x, w_gate, w_up, w_down, rnd):
    return rnd(jax.nn.silu(rnd(x) @ rnd(w_gate))
               * (rnd(x) @ rnd(w_up))) @ rnd(w_down)


def shared_expert(p, x, rnd):
    return (_mlp(x, p["shared/w_gate"], p["shared/w_up"], p["shared/w_down"],
                 rnd) * jax.nn.sigmoid(rnd(x) @ rnd(p["shared/gate"])))


def _experts(p, s, x, rnd, routed: bool):
    out = shared_expert(p, x, rnd)
    if not routed:
        return out
    experts, weights = route(p, s, x)

    def add_expert(out, xs):       # a held expert, over every token
        e, w_gate, w_up, w_down = xs
        w_e = jnp.sum(jnp.where(experts == s["first"] + e, weights, 0.0),
                      axis=-1)
        return out + w_e[:, None] * _mlp(x, w_gate, w_up, w_down, rnd), None

    # the held experts one after the other: a compiled loop, so that the
    # program holds one expert's code and not `held` copies of it
    out, _ = jax.lax.scan(add_expert, out, (
        jnp.arange(s["held"]), p["experts/w_gate"], p["experts/w_up"],
        p["experts/w_down"]))
    return out


def mixers(routed: bool = True) -> dict:
    return {"L": _linear_attention, "A": _attention,
            "F": lambda p, s, x, rnd: _experts(p, s, x, rnd, routed)}


def make_row(cfg: dict, routed: bool = True):
    """`row(params, ids (T,) as floats, rnd) -> logit (1,)`: one row's
    forward pass, a block rematerialized at a time.  `routed=False` plants
    the fault "the routed experts' sum left out"."""
    s = _shapes(cfg)
    mix = mixers(routed)
    last_mixer = max(s["pattern"].rfind("L"), s["pattern"].rfind("A"))

    def row(params, ids, rnd):
        x = params["embed_tokens"][jnp.clip(ids.astype(jnp.int32), 0,
                                            s["vocab"] - 1)]
        for i, kind in enumerate(s["pattern"]):
            if i == last_mixer + 1:
                x = x[-1:]          # nothing mixes positions from here on
            block = jax.checkpoint(
                lambda p, x, kind=kind: x + mix[kind](
                    p, s, norm(x, p["norm"], s["eps"]), rnd))
            x = block(params[f"block{i}"], x)
        last = norm(x[-1], params["norm_f"], s["eps"])
        return dense(params["head"]["shifu_output_0"], last[None], rnd)[0]

    return row


def make_forward(cfg: dict, routed: bool = True):
    """`forward(params, ids (B, T) as floats, rnd) -> logits (B, 1)`: the
    rows one after the other, each rematerialized whole, so that a gradient
    through a batch holds one row's blocks at a time."""
    row = make_row(cfg, routed)

    def forward(params, features, rnd):
        return jax.lax.map(
            jax.checkpoint(lambda ids: row(params, ids, rnd)), features)

    return forward
