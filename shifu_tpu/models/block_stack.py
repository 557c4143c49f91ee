"""A causal sequence scorer built from a layer-pattern string: pre-norm
residual blocks of three mixer kinds over one token table, the last
position's vector into the `shifu_output_0` head every model shares.

    x_0 = table[ids]                                   (B, T, hidden)
    x_{l+1} = x_l + mixer_l(RMSNorm_l(x_l))            one mixer a block
    logit = head(RMSNorm(x_L)[:, -1])

`M` is a Mamba-2 mixer (ops/ssd.py), `*` causal grouped-query attention with
no positional term (ops/attention.causal_gqa), `E` a routed-expert layer
beside one shared expert (ops/routed_experts.py), each as
`config.schema.BlockStackSpec` words it.  The residual stream stays in the
compute dtype; router logits, norms' statistics, the scan's decays and the
softmax are float32.  A row is a fixed-width sequence: every selected column
is one position's token id, all of one vocabulary.

Only the last position reaches the head, and an `E` block mixes nothing
along the sequence: the blocks that follow the last `M` or `*` run on the
last position alone, one token a row.

The `E` layer holds `experts_held` of the `n_routed_experts` the router
scores (expert parallelism's share of the layer): a token's choices that
fall on other experts add nothing here.  Each `E` block counts where its
tokens went; the model sows the counts (collection `counters`, name `moe`)
and the train step sums them over an epoch.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config.schema import BlockStackSpec, ModelSpec
from ..ops.attention import causal_gqa
from ..ops.routed_experts import (default_block_rows, plan_dispatch,
                                  route_topk, routed_relu2_mlp)
from ..ops.ssd import causal_conv1d, ssd_chunked
from .base import ScoringHead, dtype_of

INIT_STD = 0.02
#: the output projections' initial scale, `rescale_prenorm_residual` at the
#: depth of the one published stack there is; the initial range of the
#: Mamba-2 step, its `time_step_min` / `_max` / `_floor`.  Constants until a
#: second configuration states other values
RESCALE_LAYERS = 52
OUT_STD = INIT_STD / RESCALE_LAYERS ** 0.5
TIME_STEP_MIN, TIME_STEP_MAX, TIME_STEP_FLOOR = 0.001, 0.1, 1e-4


def uniform_init(lo: float, hi: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(dtype)
    return init


def dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of a step drawn log-uniformly in [TIME_STEP_MIN,
    TIME_STEP_MAX] and floored at TIME_STEP_FLOOR."""
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.exp(u * (jnp.log(TIME_STEP_MAX) - jnp.log(TIME_STEP_MIN))
                 + jnp.log(TIME_STEP_MIN))
    dt = jnp.maximum(dt, TIME_STEP_FLOOR)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


def rms_norm(x, weight, eps: float, groups: int = 1):
    """RMSNorm in float32 over the last axis, or over `groups` equal parts
    of it; the result in x's dtype."""
    shape = x.shape
    xf = x.astype(jnp.float32).reshape(*shape[:-1], groups, -1)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf.reshape(shape) * weight.astype(jnp.float32)).astype(x.dtype)


def relu2(x):
    r = jax.nn.relu(x)
    return r * r


class _Block(nn.Module):
    """What the three kinds of block share: the spec, the dtypes, the
    pre-norm, a dense product in the compute dtype."""

    spec: ModelSpec

    @property
    def bs(self) -> BlockStackSpec:
        return self.spec.block_stack

    @property
    def cdt(self):
        return dtype_of(self.spec.compute_dtype)

    def weight(self, name: str, shape, init):
        return self.param(name, init, shape, dtype_of(self.spec.param_dtype))

    def pre_norm(self, x):
        return rms_norm(x, self.weight("norm", (self.bs.hidden_size,),
                                       nn.initializers.ones),
                        self.bs.norm_eps)

    def dot(self, x, w):
        return jnp.dot(x, w.astype(self.cdt),
                       preferred_element_type=jnp.float32).astype(self.cdt)


class MambaBlock(_Block):
    @nn.compact
    def __call__(self, x):
        bs, cdt = self.bs, self.cdt
        heads, p, g, n = (bs.mamba_num_heads, bs.mamba_head_dim, bs.n_groups,
                          bs.ssm_state_size)
        d_inner, d_conv = heads * p, heads * p + 2 * g * n
        h = self.pre_norm(x)
        w_in = self.weight("in_proj", (bs.hidden_size,
                                       d_inner + d_conv + heads),
                           nn.initializers.normal(INIT_STD))
        k_bound = bs.conv_kernel ** -0.5
        conv_w = self.weight("conv_w", (bs.conv_kernel, d_conv),
                             uniform_init(-k_bound, k_bound))
        conv_b = self.weight("conv_b", (d_conv,),
                             uniform_init(-k_bound, k_bound))
        dt_bias = self.weight("dt_bias", (heads,), dt_bias_init)
        a_log = self.weight("A_log", (heads,), a_log_init)
        d_skip = self.weight("D", (heads,), nn.initializers.ones)
        gate_norm = self.weight("gate_norm", (d_inner,),
                                nn.initializers.ones)
        w_out = self.weight("out_proj", (d_inner, bs.hidden_size),
                            nn.initializers.normal(OUT_STD))
        with jax.named_scope("mamba2"):
            with jax.named_scope("in_proj"):
                zxbcdt = self.dot(h, w_in)
                z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + d_conv],
                                       axis=-1)
            with jax.named_scope("conv"):
                xbc = jax.nn.silu(causal_conv1d(xbc, conv_w, conv_b))
            with jax.named_scope("ssd"):
                xs, bm, cm = jnp.split(xbc, [d_inner, d_inner + g * n],
                                       axis=-1)
                b_, t = x.shape[:2]
                delta = jax.nn.softplus(dt.astype(jnp.float32)
                                        + dt_bias.astype(jnp.float32))
                y = ssd_chunked(
                    xs.reshape(b_, t, heads, p), delta,
                    -jnp.exp(a_log.astype(jnp.float32)),
                    bm.reshape(b_, t, g, n), cm.reshape(b_, t, g, n),
                    d_skip).reshape(b_, t, d_inner)
                # the gate before the norm, the norm over n_groups parts
                y = rms_norm((y.astype(jnp.float32)
                              * jax.nn.silu(z.astype(jnp.float32))
                              ).astype(cdt), gate_norm, bs.norm_eps, g)
            with jax.named_scope("out_proj"):
                return x + self.dot(y, w_out)


class AttentionBlock(_Block):
    @nn.compact
    def __call__(self, x):
        bs = self.bs
        hq, hkv, d = (bs.num_attention_heads, bs.num_key_value_heads,
                      bs.head_dim)
        h = self.pre_norm(x)
        init = nn.initializers.normal(INIT_STD)
        w_q = self.weight("q_proj", (bs.hidden_size, hq * d), init)
        w_k = self.weight("k_proj", (bs.hidden_size, hkv * d), init)
        w_v = self.weight("v_proj", (bs.hidden_size, hkv * d), init)
        w_o = self.weight("o_proj", (hq * d, bs.hidden_size),
                          nn.initializers.normal(OUT_STD))
        b_, t = x.shape[:2]
        with jax.named_scope("attention"):
            q = self.dot(h, w_q).reshape(b_, t, hq, d)
            k = self.dot(h, w_k).reshape(b_, t, hkv, d)
            v = self.dot(h, w_v).reshape(b_, t, hkv, d)
            o = causal_gqa(q, k, v).reshape(b_, t, hq * d)
            return x + self.dot(o, w_o)


class ExpertsBlock(_Block):
    """Returns (x, counters): `tokens_per_expert` (held,), `routed_slots`,
    `held_slots`, `tokens_dropped` of this call."""

    @nn.compact
    def __call__(self, x):
        bs, cdt = self.bs, self.cdt
        hidden, f, fs, held = (bs.hidden_size, bs.moe_intermediate_size,
                               bs.moe_shared_expert_intermediate_size,
                               bs.held)
        h = self.pre_norm(x)
        init = nn.initializers.normal(INIT_STD)
        out_init = nn.initializers.normal(OUT_STD)
        w_r = self.weight("router", (hidden, bs.n_routed_experts), init)
        w1 = self.weight("experts/w1", (held, hidden, f), init)
        w2 = self.weight("experts/w2", (held, f, hidden), out_init)
        s1 = self.weight("shared/w1", (hidden, fs), init)
        s2 = self.weight("shared/w2", (fs, hidden), out_init)
        tokens = h.reshape(-1, hidden)
        k = bs.num_experts_per_tok
        with jax.named_scope("moe"):
            with jax.named_scope("router"):
                logits = jnp.dot(tokens.astype(jnp.float32),
                                 w_r.astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST)
                experts, weights = route_topk(logits, k,
                                              bs.routed_scaling_factor)
            with jax.named_scope("dispatch"):
                rows = default_block_rows(tokens.shape[0] * k, held)
                plan = plan_dispatch(experts, bs.first_expert_held, held,
                                     rows)
                slot = plan["row_slot"]
                row_weight = jnp.append(weights.reshape(-1), 0.0)[slot]
            with jax.named_scope("experts"):
                routed = routed_relu2_mlp(
                    tokens, w1, w2, row_weight, slot // k,
                    plan["block_expert"], plan["live_blocks"], rows)
            with jax.named_scope("shared"):
                shared = self.dot(relu2(self.dot(tokens, s1)), s2)
            with jax.named_scope("combine"):
                y = (shared.astype(jnp.float32) + routed).astype(cdt)
        counters = {
            "tokens_per_expert": plan["tokens_per_expert"],
            "routed_slots": jnp.int32(tokens.shape[0] * k),
            "held_slots": plan["held_slots"],
            "tokens_dropped": plan["held_slots"] - plan["dispatched_slots"],
        }
        return x + y.reshape(x.shape), counters


_KINDS = {"M": MambaBlock, "*": AttentionBlock, "E": ExpertsBlock}


class BlockStack(nn.Module):
    spec: ModelSpec
    vocab_size: int

    @nn.compact
    def __call__(self, features: jax.Array, *, train: bool = False):
        bs = self.spec.block_stack
        cdt = dtype_of(self.spec.compute_dtype)
        # unseen or out-of-range ids land in the last bucket
        ids = jnp.clip(features.astype(jnp.int32), 0, self.vocab_size - 1)
        table = self.param("embed_tokens", nn.initializers.normal(INIT_STD),
                           (self.vocab_size, bs.hidden_size),
                           dtype_of(self.spec.param_dtype))
        with jax.named_scope("embed_tokens"):
            x = jnp.take(table, ids, axis=0).astype(cdt)
        counters = []
        last_mixer = max(bs.pattern.rfind("M"), bs.pattern.rfind("*"))
        for i, kind in enumerate(bs.pattern):
            if i == last_mixer + 1:
                # nothing mixes along the sequence from here on: the head
                # reads the last position, so only it goes further
                x = x[:, -1:]
            cls = _KINDS[kind]
            if self.spec.remat:
                cls = nn.remat(cls)
            x = cls(spec=self.spec, name=f"block{i}")(x)
            if kind == "E":
                x, c = x
                counters.append(c)
        if counters:
            # one entry an E layer, in the pattern's order
            self.sow("counters", "moe", jax.tree_util.tree_map(
                lambda *v: jnp.stack(v), *counters),
                reduce_fn=lambda _, new: new, init_fn=lambda: None)
        norm_f = self.param("norm_f", nn.initializers.ones,
                            (bs.hidden_size,),
                            dtype_of(self.spec.param_dtype))
        last = rms_norm(x[:, -1], norm_f, bs.norm_eps)
        return ScoringHead(spec=self.spec, name="head")(last)
