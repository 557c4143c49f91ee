"""Plain reference of the hybrid Mamba-2 / routed-expert / attention stack
(`model_type` `nemotron_h`: NVIDIA-Nemotron-3-Nano-30B-A3B's `config.json`)
as a sequence scorer: one token table, pre-norm residual blocks by the
pattern's letters, a final RMSNorm, the last position's vector into the
one-logit head `shifu_output_0`.

Float32 `jax.numpy`, nothing of `shifu_tpu`, no kernel and no chunking of
the mathematics: the Mamba-2 recurrence a position at a time as written,
attention as one masked softmax a head, the experts as a loop over the held
ones with a mask.  `rnd` rounds the operands of every product (`common`).

What it does to fit beside 10 GB of its own state changes no arithmetic: a
row at a time (`lax.map`), each row and each block rematerialized, the
recurrence's steps rematerialized a segment at a time, attention a head at
a time; and the blocks that follow the last `M` or `*` are computed for the
last position alone, the only one the head reads (an `E` block mixes nothing
along the sequence, so that position's value is the same).

Departures from the published description, each in the configuration file
too: no rotary or other positional term in attention (the family applies
none); `e_score_correction_bias` held at zero; the chip's share - experts
`first_expert_held .. +n_routed_experts` of `router_experts`, what the
others would add left out; the head and the loss are Shifu's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import dense, dense_init, param_key

INIT_STD = 0.02
SEGMENT = 64        # steps of the recurrence rematerialized together
UNROLL = 8          # of which so many a trip of the compiled loop


def _shapes(cfg: dict) -> dict:
    dep = cfg["deployment"]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {
        "pattern": cfg["hybrid_override_pattern"], "hidden": cfg["hidden_size"],
        "vocab": cfg["vocab_size"], "eps": cfg["norm_eps"],
        "heads": heads, "p": p, "g": g, "n": n, "d_inner": heads * p,
        "d_conv": heads * p + 2 * g * n, "k_conv": cfg["conv_kernel"],
        "hq": cfg["num_attention_heads"], "hkv": cfg["num_key_value_heads"],
        "d": cfg["head_dim"], "router": dep["router_experts"],
        "held": cfg["n_routed_experts"], "first": dep["first_expert_held"],
        "top_k": cfg["num_experts_per_tok"], "f": cfg["moe_intermediate_size"],
        "fs": cfg["moe_shared_expert_intermediate_size"],
        "scale": cfg["routed_scaling_factor"],
        "out_std": INIT_STD / math.sqrt(dep["published_layers"]),
    }


def _normal(seed, path, counter, shape, std):
    return std * jax.random.normal(param_key(seed, path, counter), shape,
                                   jnp.float32)


def _uniform(seed, path, counter, shape, lo, hi):
    return jax.random.uniform(param_key(seed, path, counter), shape,
                              jnp.float32, lo, hi)


def _init_block(cfg: dict, s: dict, seed: int, kind: str, path) -> dict:
    """One block's weights, drawn in the program's order of declaration
    (the counter is the parameter's number in its module, from 1)."""
    ones = jnp.ones((s["hidden"],), jnp.float32)
    if kind == "M":
        bound = s["k_conv"] ** -0.5
        u = jax.random.uniform(param_key(seed, path, 5), (s["heads"],),
                               jnp.float32)
        lo, hi = cfg["time_step_min"], cfg["time_step_max"]
        dt = jnp.maximum(jnp.exp(u * (jnp.log(hi) - jnp.log(lo))
                                 + jnp.log(lo)), cfg["time_step_floor"])
        return {
            "norm": ones,
            "in_proj": _normal(seed, path, 2, (s["hidden"], s["d_inner"]
                                               + s["d_conv"] + s["heads"]),
                               INIT_STD),
            "conv_w": _uniform(seed, path, 3, (s["k_conv"], s["d_conv"]),
                               -bound, bound),
            "conv_b": _uniform(seed, path, 4, (s["d_conv"],), -bound, bound),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(_uniform(seed, path, 6, (s["heads"],), 1.0,
                                      16.0)),
            "D": jnp.ones((s["heads"],), jnp.float32),
            "gate_norm": jnp.ones((s["d_inner"],), jnp.float32),
            "out_proj": _normal(seed, path, 9, (s["d_inner"], s["hidden"]),
                                s["out_std"]),
        }
    if kind == "*":
        return {
            "norm": ones,
            "q_proj": _normal(seed, path, 2, (s["hidden"], s["hq"] * s["d"]),
                              INIT_STD),
            "k_proj": _normal(seed, path, 3, (s["hidden"], s["hkv"] * s["d"]),
                              INIT_STD),
            "v_proj": _normal(seed, path, 4, (s["hidden"], s["hkv"] * s["d"]),
                              INIT_STD),
            "o_proj": _normal(seed, path, 5, (s["hq"] * s["d"], s["hidden"]),
                              s["out_std"]),
        }
    return {
        "norm": ones,
        "router": _normal(seed, path, 2, (s["hidden"], s["router"]),
                          INIT_STD),
        "experts/w1": _normal(seed, path, 3, (s["held"], s["hidden"],
                                              s["f"]), INIT_STD),
        "experts/w2": _normal(seed, path, 4, (s["held"], s["f"],
                                              s["hidden"]), s["out_std"]),
        "shared/w1": _normal(seed, path, 5, (s["hidden"], s["fs"]),
                             INIT_STD),
        "shared/w2": _normal(seed, path, 6, (s["fs"], s["hidden"]),
                             s["out_std"]),
    }


def init_params(cfg: dict, seed: int) -> dict:
    """The seed's initial weights.  One compiled program, as the program's
    own initialisation is: op by op, `dt_bias`'s multiply-then-add is
    rounded twice where a compiled program fuses it, one ulp apart."""
    return jax.jit(lambda: _init_params(cfg, seed))()


def _init_params(cfg: dict, seed: int) -> dict:
    s = _shapes(cfg)
    params = {f"block{i}": _init_block(cfg, s, seed, kind, (f"block{i}",))
              for i, kind in enumerate(s["pattern"])}
    params["embed_tokens"] = _normal(seed, (), 1, (s["vocab"], s["hidden"]),
                                     INIT_STD)
    params["norm_f"] = jnp.ones((s["hidden"],), jnp.float32)
    params["head"] = {"shifu_output_0": dense_init(
        seed, ("head", "shifu_output_0"), s["hidden"], 1)}
    return params


def rms_norm(x, weight, eps, groups: int = 1):
    shape = x.shape
    x = x.reshape(*shape[:-1], groups, -1)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x.reshape(shape) * weight


def recurrence(x, delta, a, bm, cm, d_skip, rnd=lambda v: v):
    """One row's scan as written.  x (T, H, P), delta (T, H), a (H,)
    negative, bm / cm (T, G, N), d_skip (H,): h_t = exp(delta_t A) h_{t-1}
    + delta_t x_t (x) B_t, y_t = h_t C_t + D x_t, from a zero state."""
    t, h, p = x.shape
    g, n = bm.shape[1:]
    bh, ch = (jnp.repeat(v, h // g, axis=1) for v in (bm, cm))   # (T, H, N)

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = (state * jnp.exp(dt_t * a)[:, None, None]
                 + rnd(dt_t[:, None] * x_t)[:, :, None] * rnd(b_t)[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", rnd(state), rnd(c_t))

    seg = max(q for q in range(1, SEGMENT + 1) if t % q == 0)
    segment = jax.checkpoint(
        lambda state, xs: jax.lax.scan(step, state, xs, unroll=UNROLL))
    xs = tuple(v.reshape(t // seg, seg, *v.shape[1:])
               for v in (x, delta, bh, ch))
    _, y = jax.lax.scan(segment, jnp.zeros((h, p, n), jnp.float32), xs)
    return y.reshape(t, h, p) + x * d_skip[:, None]


def _mamba(p, s, x, rnd):
    t = x.shape[0]
    zxbcdt = rnd(x) @ rnd(p["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [s["d_inner"], s["d_inner"] + s["d_conv"]],
                           axis=-1)
    k = s["k_conv"]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[j:j + t] * p["conv_w"][j] for j in range(k))
                      + p["conv_b"])
    xs, bm, cm = jnp.split(xbc, [s["d_inner"],
                                 s["d_inner"] + s["g"] * s["n"]], axis=-1)
    y = recurrence(xs.reshape(t, s["heads"], s["p"]),
                   jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                   bm.reshape(t, s["g"], s["n"]),
                   cm.reshape(t, s["g"], s["n"]), p["D"], rnd)
    y = rms_norm(y.reshape(t, s["d_inner"]) * jax.nn.silu(z), p["gate_norm"],
                 s["eps"], s["g"])
    return rnd(y) @ rnd(p["out_proj"])


def _attention(p, s, x, rnd):
    t = x.shape[0]
    hq, hkv, d = s["hq"], s["hkv"], s["d"]
    q = (rnd(x) @ rnd(p["q_proj"])).reshape(t, hq, d)
    k = (rnd(x) @ rnd(p["k_proj"])).reshape(t, hkv, d)
    v = (rnd(x) @ rnd(p["v_proj"])).reshape(t, hkv, d)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(xs):
        q_h, kv = xs                                   # (T, D), head index
        scores = rnd(q_h) @ rnd(k[:, kv]).T / math.sqrt(d)
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return rnd(w) @ rnd(v[:, kv])

    o = jax.lax.map(head, (jnp.moveaxis(q, 1, 0),
                           jnp.arange(hq) // (hq // hkv)))
    return rnd(jnp.moveaxis(o, 0, 1).reshape(t, hq * d)) @ rnd(p["o_proj"])


def route(p, s, x):
    """(chosen experts (T, k) over all the router's experts, their weights
    (T, k)): sigmoid scores in float32, the k largest, `scale * s / sum`."""
    scores = jax.nn.sigmoid(jnp.dot(x, p["router"],
                                    precision=jax.lax.Precision.HIGHEST))
    chosen, experts = jax.lax.top_k(scores, s["top_k"])
    return experts, s["scale"] * chosen / jnp.sum(chosen, axis=-1,
                                                  keepdims=True)


def _mlp(x, w1, w2, rnd):
    return rnd(jnp.square(jax.nn.relu(rnd(x) @ rnd(w1)))) @ rnd(w2)


def _experts(p, s, x, rnd, routed: bool):
    out = _mlp(x, p["shared/w1"], p["shared/w2"], rnd)
    if not routed:
        return out
    experts, weights = route(p, s, x)
    for e in range(s["held"]):     # the held experts, each over every token
        w_e = jnp.sum(jnp.where(experts == s["first"] + e, weights, 0.0),
                      axis=-1)
        out = out + w_e[:, None] * _mlp(x, p["experts/w1"][e],
                                        p["experts/w2"][e], rnd)
    return out


def make_row(cfg: dict, routed: bool = True):
    """`row(params, ids (T,) as floats, rnd) -> logit (1,)`: one row's
    forward pass, a block rematerialized at a time.  `routed=False` plants
    the fault "the routed experts' sum left out"."""
    s = _shapes(cfg)
    mixers = {"M": _mamba, "*": _attention,
              "E": lambda p, s_, x, rnd: _experts(p, s_, x, rnd, routed)}

    last_mixer = max(s["pattern"].rfind("M"), s["pattern"].rfind("*"))

    def row(params, ids, rnd):
        x = params["embed_tokens"][jnp.clip(ids.astype(jnp.int32), 0,
                                            s["vocab"] - 1)]
        for i, kind in enumerate(s["pattern"]):
            if i == last_mixer + 1:
                x = x[-1:]          # nothing mixes positions from here on
            block = jax.checkpoint(
                lambda p, x, kind=kind: x + mixers[kind](
                    p, s, rms_norm(x, p["norm"], s["eps"]), rnd))
            x = block(params[f"block{i}"], x)
        last = rms_norm(x[-1], params["norm_f"], s["eps"])
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
