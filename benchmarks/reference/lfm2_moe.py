"""Plain reference of the gated-short-convolution / attention / routed-expert
stack (`model_type` `lfm2_moe`: LFM2-8B-A1B's `config.json`) as a sequence
scorer: one token table, a published layer as two pre-norm residual blocks
(its mixer, then its feed-forward), a final RMSNorm, the last position's
vector into the one-logit head `shifu_output_0`.

Float32 `jax.numpy`, nothing of `shifu_tpu`, no kernel and no chunking of
the mathematics: attention as one masked softmax a head, the convolution as
a sum over its taps, the experts as a loop over the held ones with the
router's mask.  `rnd` rounds the operands of every matrix product
(`common`).  The mixers follow `Lfm2ShortConv.slow_forward`,
`Lfm2Attention` and `Lfm2RMSNorm` of `transformers`' `models/lfm2`.

    N_w(x) = x rsqrt(mean(x^2) + eps) w                   every norm, w from 1
    layer l: x <- x + mixer_l(N(x)); x <- x + ffn_l(N(x))
    mixer_l: the short convolution for a `conv` layer, attention for a
             `full_attention` one (`layer_types`)
    ffn_l: the dense MLP where l < num_dense_layers, the experts after

The short convolution (h the normed x, C channels = hidden):

    [B | C | X] = h W_in                                  W_in (C, 3C)
    z = B * X
    z'_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t            per channel, 0 before t = 0
    out = (C * z') W_out

Attention (Hq query heads, Hkv key-value heads, D = hidden / Hq):

    q = h W_q, k = h W_k, v = h W_v;  q, k <- N over each head's D dims
    q, k <- the rotary term on all D dims: dims i and i + D/2 turn by
            t theta^(-2i / D), t the position
    o[t, n] = sum_{s <= t} softmax_s(q[t, n] . k[s, m] / sqrt(D)) v[s, m],
              m = n // (Hq / Hkv);  out = o W_o

The dense MLP: `W_d (silu(W_g h) * W_u h)`.  Experts: `s = sigmoid(h W_r)`
over all the router's experts, the `num_experts_per_tok` largest of `s + b`
(b the expert bias of `use_expert_bias`, a buffer no gradient reaches, zero
here), weights `routed_scaling_factor s_e / sum_chosen s`, gated experts of
the dense MLP's form, and no shared expert.

What it does to fit beside its own state changes no arithmetic: a row at a
time, each row and each block rematerialized, attention a head at a time,
the held experts one at a time; and the blocks that follow the last mixer
are computed for the last position alone, the only one the head reads
(neither the dense MLP nor an expert block mixes anything along the
sequence, so that position's value is the same).

Departures from the published description, each in the configuration file
too: the chip's share - experts `first_expert_held .. +num_experts` of
`router_experts`, what the others would add left out, so that a token none
of whose choices is held leaves the expert layer unchanged; the router, which
`transformers` 4.57.6 does not hold, as the configuration's `assumed` states
it; the head and the loss are Shifu's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..counts.lfm2_moe import MIXERS, head_dim, pattern
from .common import dense, dense_init, param_key

INIT_STD = 0.02


def _shapes(cfg: dict) -> dict:
    dep = cfg["deployment"]
    return {
        "pattern": pattern(cfg), "hidden": cfg["hidden_size"],
        "vocab": cfg["vocab_size"], "eps": cfg["norm_eps"],
        "taps": cfg["conv_L_cache"], "hq": cfg["num_attention_heads"],
        "hkv": cfg["num_key_value_heads"], "d": head_dim(cfg),
        "theta": float(cfg["rope_theta"]), "fd": cfg["intermediate_size"],
        "router": dep["router_experts"], "held": cfg["num_experts"],
        "first": dep["first_expert_held"],
        "top_k": cfg["num_experts_per_tok"],
        "scale": float(cfg["routed_scaling_factor"]),
        "f": cfg["moe_intermediate_size"],
    }


def _normal(seed, path, counter, shape):
    """N(0, INIT_STD) of `shape`, drawn as a matrix and reshaped: the
    numbers are the same (threefry counts a draw's elements by their flat
    index), and where the seed is a constant of the program, as the
    driver's `jit(lambda: init_params(config, seed))` makes it, the
    compiler takes a few seconds over it where it took 20-30 s over the
    experts' 3-D draws."""
    flat = (math.prod(shape[:-1]), shape[-1])
    return INIT_STD * jax.random.normal(param_key(seed, path, counter), flat,
                                        jnp.float32).reshape(shape)


def _init_block(s: dict, seed: int, kind: str, path) -> dict:
    """One block's weights, drawn in the program's order of declaration
    (the counter is the parameter's number in its module, from 1)."""
    hidden, hq, hkv, d = s["hidden"], s["hq"], s["hkv"], s["d"]
    ones = jnp.ones((hidden,), jnp.float32)
    if kind == "V":
        return {
            "norm": ones,
            "in_proj": _normal(seed, path, 2, (hidden, 3 * hidden)),
            "conv_w": _normal(seed, path, 3, (s["taps"], hidden)),
            "out_proj": _normal(seed, path, 4, (hidden, hidden)),
        }
    if kind == "Q":
        return {
            "norm": ones,
            "q_proj": _normal(seed, path, 2, (hidden, hq * d)),
            "k_proj": _normal(seed, path, 3, (hidden, hkv * d)),
            "v_proj": _normal(seed, path, 4, (hidden, hkv * d)),
            "q_norm": jnp.ones((d,), jnp.float32),
            "k_norm": jnp.ones((d,), jnp.float32),
            "o_proj": _normal(seed, path, 7, (hq * d, hidden)),
        }
    if kind == "D":
        return {
            "norm": ones,
            "gate_proj": _normal(seed, path, 2, (hidden, s["fd"])),
            "up_proj": _normal(seed, path, 3, (hidden, s["fd"])),
            "down_proj": _normal(seed, path, 4, (s["fd"], hidden)),
        }
    return {
        "norm": ones,
        "router": _normal(seed, path, 2, (hidden, s["router"])),
        "experts/w_gate": _normal(seed, path, 3, (s["held"], hidden, s["f"])),
        "experts/w_up": _normal(seed, path, 4, (s["held"], hidden, s["f"])),
        "experts/w_down": _normal(seed, path, 5, (s["held"], s["f"], hidden)),
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
    params["norm_f"] = jnp.ones((s["hidden"],), jnp.float32)
    params["head"] = {"shifu_output_0": dense_init(
        seed, ("head", "shifu_output_0"), s["hidden"], 1)}
    return params


def norm(x, weight, eps):
    """N_w over the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rope(x, theta: float):
    """The rotary term on every dim of a head, halves together: x (T, H, D),
    the position the index along axis 0; dims i and i + D/2 turn by
    `t theta^(-2i / D)` (`rotate_half`)."""
    half = x.shape[-1] // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                    / x.shape[-1])
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(p, s, x, rnd):
    """The gated short convolution of one row's normed x (T, C)."""
    c = s["hidden"]
    bcx = rnd(x) @ rnd(p["in_proj"])
    b, gate, u = bcx[:, :c], bcx[:, c:2 * c], bcx[:, 2 * c:]
    z = b * u
    taps, t = s["taps"], x.shape[0]
    padded = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    conv = sum(padded[j:j + t] * p["conv_w"][j] for j in range(taps))
    return rnd(gate * conv) @ rnd(p["out_proj"])


def _attention(p, s, x, rnd):
    t, hq, hkv, d = x.shape[0], s["hq"], s["hkv"], s["d"]
    q = norm((rnd(x) @ rnd(p["q_proj"])).reshape(t, hq, d), p["q_norm"],
             s["eps"])
    k = norm((rnd(x) @ rnd(p["k_proj"])).reshape(t, hkv, d), p["k_norm"],
             s["eps"])
    v = (rnd(x) @ rnd(p["v_proj"])).reshape(t, hkv, d)
    q, k = rope(q, s["theta"]), rope(k, s["theta"])
    causal = jnp.tril(jnp.ones((t, t), bool))
    group = hq // hkv

    @jax.checkpoint
    def head(xs):
        n, q_h = xs                         # query head n reads n // group
        k_h = jax.lax.dynamic_index_in_dim(k, n // group, 1, False)
        v_h = jax.lax.dynamic_index_in_dim(v, n // group, 1, False)
        scores = rnd(q_h) @ rnd(k_h).T / math.sqrt(d)
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return rnd(w) @ rnd(v_h)

    o = jax.lax.map(head, (jnp.arange(hq), jnp.moveaxis(q, 1, 0)))
    return rnd(jnp.moveaxis(o, 0, 1).reshape(t, hq * d)) @ rnd(p["o_proj"])


def _mlp(x, w_gate, w_up, w_down, rnd):
    return rnd(jax.nn.silu(rnd(x) @ rnd(w_gate))
               * (rnd(x) @ rnd(w_up))) @ rnd(w_down)


def _dense_mlp(p, s, x, rnd):
    return _mlp(x, p["gate_proj"], p["up_proj"], p["down_proj"], rnd)


def route(p, s, x, bias=None):
    """(chosen experts (T, k) over all the router's experts, their weights
    (T, k)): sigmoid scores in float32, the k largest of scores + `bias`
    (the expert bias, zero where None), the chosen scores renormalised and
    scaled."""
    score = jax.nn.sigmoid(jnp.dot(x, p["router"],
                                   precision=jax.lax.Precision.HIGHEST))
    pick = score if bias is None else score + bias
    _, experts = jax.lax.top_k(pick, s["top_k"])
    chosen = jnp.take_along_axis(score, experts, axis=-1)
    return experts, s["scale"] * chosen / jnp.sum(chosen, axis=-1,
                                                  keepdims=True)


def _experts(p, s, x, rnd, routed: bool):
    out = jnp.zeros_like(x)
    if not routed:
        return out
    experts, weights = route(p, s, x)

    @jax.checkpoint     # a gradient holds one expert's activations
    def expert(e, w_gate, w_up, w_down):    # a held expert, over every token
        w_e = jnp.sum(jnp.where(experts == s["first"] + e, weights, 0.0),
                      axis=-1)
        return w_e[:, None] * _mlp(x, w_gate, w_up, w_down, rnd)

    # the held experts one after the other: a compiled loop, so that the
    # program holds one expert's code and not `held` copies of it
    out, _ = jax.lax.scan(lambda out, xs: (out + expert(*xs), None), out, (
        jnp.arange(s["held"]), p["experts/w_gate"], p["experts/w_up"],
        p["experts/w_down"]))
    return out


def mixers(routed: bool = True) -> dict:
    return {"V": short_conv, "Q": _attention, "D": _dense_mlp,
            "G": lambda p, s, x, rnd: _experts(p, s, x, rnd, routed)}


def make_row(cfg: dict, routed: bool = True):
    """`row(params, ids (T,) as floats, rnd) -> logit (1,)`: one row's
    forward pass, a block rematerialized at a time.  `routed=False` plants
    the fault "the routed experts' sum left out"."""
    s = _shapes(cfg)
    mix = mixers(routed)
    last_mixer = max(s["pattern"].rfind(m) for m in MIXERS.values())

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
