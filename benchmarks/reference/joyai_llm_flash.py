"""Plain reference of the latent-attention / routed-expert stack
(`model_type` `joyai_llm_flash`: JoyAI-LLM-Flash's `config.json`) as a
sequence scorer: one token table, a published layer as two pre-norm residual
blocks (its mixer, then its feed-forward), a final RMSNorm, the last
position's vector into the one-logit head `shifu_output_0`.

Float32 `jax.numpy`, nothing of `shifu_tpu`, no kernel and no chunking of
the mathematics: attention as one masked softmax a head over the scores as
written (a head's own product plus its product with the shared rotary key),
the experts as a loop over the held ones with the router's mask.  `rnd`
rounds the operands of every product (`common`).

    N_w(x) = x rsqrt(mean(x^2) + eps) w                   every norm, w from 1
    layer l: x <- x + attn_l(N(x)); x <- x + ffn_l(N(x))
    ffn_l: the dense MLP where l < first_k_dense_replace, the experts after

Latent attention (h the normed x, H heads):

    c_q = N(h W_qa);  [q_nope | q_pe] = c_q W_qb        a head: Dn + Dr dims
    [c_kv | k_pe] = h W_kva                             k_pe is ONE head, Dr
    [k_nope | v] = N(c_kv) W_kvb                        a head: Dn + Dv dims
    q_pe, k_pe <- the rotary term, neighbours together: dims 2i and 2i + 1
                  turn by t theta^(-2i / Dr), t the position
    score[n, t, s] = (q_nope[t, n] . k_nope[s, n] + q_pe[t, n] . k_pe[s])
                     / sqrt(Dn + Dr),  s <= t
    o[t, n] = sum_s softmax_s(score)[n, t, s] v[s, n];  x <- x + o W_o

The dense MLP: `W_d (silu(W_g h) * W_u h)`.  Experts: `s = sigmoid(h W_r)`
over all the router's experts, the `num_experts_per_tok` largest of `s`
(plus `e_score_correction_bias`, a buffer no gradient reaches, zero here;
`n_group` and `topk_group` 1 make the group step the identity), weights
`routed_scaling_factor s_e / sum_chosen s`, gated experts of the dense MLP's
form, and one shared expert of the same form added with no gate.

What it does to fit beside 10 GB of its own state changes no arithmetic: a
row at a time (`lax.map`), each row and each block rematerialized, attention
a head at a time, the held experts one at a time; and the blocks that follow
the last `C` are computed for the last position alone, the only one the head
reads (neither the dense MLP nor an expert block mixes anything along the
sequence, so that position's value is the same).

Departures from the published description, each in the configuration file
too: the chip's share - experts `first_expert_held .. +n_routed_experts` of
`router_experts`, what the others would add left out; no
multi-token-prediction layer; the head and the loss are Shifu's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..counts.joyai_llm_flash import pattern
from .common import dense, dense_init, param_key

INIT_STD = 0.02


def _shapes(cfg: dict) -> dict:
    dep = cfg["deployment"]
    return {
        "pattern": pattern(cfg), "hidden": cfg["hidden_size"],
        "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "hq": cfg["num_attention_heads"], "ql": cfg["q_lora_rank"],
        "kvl": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
        "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
        "theta": float(cfg["rope_theta"]), "fd": cfg["intermediate_size"],
        "router": dep["router_experts"], "held": cfg["n_routed_experts"],
        "first": dep["first_expert_held"],
        "top_k": cfg["num_experts_per_tok"],
        "scale": float(cfg["routed_scaling_factor"]),
        "f": cfg["moe_intermediate_size"],
        "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
    }


def _normal(seed, path, counter, shape):
    return INIT_STD * jax.random.normal(param_key(seed, path, counter), shape,
                                        jnp.float32)


def _init_block(s: dict, seed: int, kind: str, path) -> dict:
    """One block's weights, drawn in the program's order of declaration
    (the counter is the parameter's number in its module, from 1)."""
    hidden, hq = s["hidden"], s["hq"]
    ones = jnp.ones((hidden,), jnp.float32)
    if kind == "C":
        return {
            "norm": ones,
            "q_a_proj": _normal(seed, path, 2, (hidden, s["ql"])),
            "q_a_norm": jnp.ones((s["ql"],), jnp.float32),
            "q_b_proj": _normal(seed, path, 4, (s["ql"],
                                                hq * (s["dn"] + s["dr"]))),
            "kv_a_proj": _normal(seed, path, 5, (hidden,
                                                 s["kvl"] + s["dr"])),
            "kv_a_norm": jnp.ones((s["kvl"],), jnp.float32),
            "kv_b_proj": _normal(seed, path, 7, (s["kvl"],
                                                 hq * (s["dn"] + s["dv"]))),
            "o_proj": _normal(seed, path, 8, (hq * s["dv"], hidden)),
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
        "shared/w_gate": _normal(seed, path, 6, (hidden, s["fs"])),
        "shared/w_up": _normal(seed, path, 7, (hidden, s["fs"])),
        "shared/w_down": _normal(seed, path, 8, (s["fs"], hidden)),
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
    """The rotary term on every dim of a head, neighbours together: x (T, H,
    D), the position the index along axis 0; dims 2i and 2i + 1 turn by
    `t theta^(-2i / D)`."""
    d = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def latent_qkv(p, s, x, rnd):
    """(q_nope (T, H, Dn), q_pe (T, H, Dr), k_nope (T, H, Dn), k_pe (T, Dr),
    v (T, H, Dv)) of one row's normed x, the rotary term applied."""
    t, hq, dn = x.shape[0], s["hq"], s["dn"]
    c_q = norm(rnd(x) @ rnd(p["q_a_proj"]), p["q_a_norm"], s["eps"])
    q = (rnd(c_q) @ rnd(p["q_b_proj"])).reshape(t, hq, dn + s["dr"])
    kv_a = rnd(x) @ rnd(p["kv_a_proj"])
    c_kv, k_pe = kv_a[:, :s["kvl"]], kv_a[:, s["kvl"]:]
    kv = (rnd(norm(c_kv, p["kv_a_norm"], s["eps"]))
          @ rnd(p["kv_b_proj"])).reshape(t, hq, dn + s["dv"])
    return (q[..., :dn], rope(q[..., dn:], s["theta"]), kv[..., :dn],
            rope(k_pe[:, None, :], s["theta"])[:, 0], kv[..., dn:])


def _latent_attention(p, s, x, rnd):
    t = x.shape[0]
    q_nope, q_pe, k_nope, k_pe, v = latent_qkv(p, s, x, rnd)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(xs):
        qn, qp, kn, v_h = xs                # (T, Dn), (T, Dr), (T, Dn), (T, Dv)
        scores = (rnd(qn) @ rnd(kn).T + rnd(qp) @ rnd(k_pe).T) \
            / math.sqrt(s["dn"] + s["dr"])
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return rnd(w) @ rnd(v_h)

    o = jax.lax.map(head, tuple(jnp.moveaxis(a, 1, 0)
                                for a in (q_nope, q_pe, k_nope, v)))
    return rnd(jnp.moveaxis(o, 0, 1).reshape(t, s["hq"] * s["dv"])) \
        @ rnd(p["o_proj"])


def _mlp(x, w_gate, w_up, w_down, rnd):
    return rnd(jax.nn.silu(rnd(x) @ rnd(w_gate))
               * (rnd(x) @ rnd(w_up))) @ rnd(w_down)


def _dense_mlp(p, s, x, rnd):
    return _mlp(x, p["gate_proj"], p["up_proj"], p["down_proj"], rnd)


def route(p, s, x):
    """(chosen experts (T, k) over all the router's experts, their weights
    (T, k)): sigmoid scores in float32, the k largest, renormalised and
    scaled."""
    score = jax.nn.sigmoid(jnp.dot(x, p["router"],
                                   precision=jax.lax.Precision.HIGHEST))
    chosen, experts = jax.lax.top_k(score, s["top_k"])
    return experts, s["scale"] * chosen / jnp.sum(chosen, axis=-1,
                                                  keepdims=True)


def shared_expert(p, x, rnd):
    return _mlp(x, p["shared/w_gate"], p["shared/w_up"], p["shared/w_down"],
                rnd)


def _experts(p, s, x, rnd, routed: bool):
    out = shared_expert(p, x, rnd)
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
    return {"C": _latent_attention, "D": _dense_mlp,
            "G": lambda p, s, x, rnd: _experts(p, s, x, rnd, routed)}


def make_row(cfg: dict, routed: bool = True):
    """`row(params, ids (T,) as floats, rnd) -> logit (1,)`: one row's
    forward pass, a block rematerialized at a time.  `routed=False` plants
    the fault "the routed experts' sum left out"."""
    s = _shapes(cfg)
    mix = mixers(routed)
    last_mixer = s["pattern"].rfind("C")

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
