"""A causal sequence scorer built from a layer-pattern string: pre-norm
residual blocks of eleven kinds over one token table, the last position's
vector into the `shifu_output_0` head every model shares.

    x_0 = table[ids]                                   (B, T, hidden)
    x_{l+1} = x_l + mixer_l(RMSNorm_l(x_l))            one mixer a block
    logit = head(RMSNorm(x_L)[:, -1])

A letter of the pattern names a kind of block, each as
`config.schema.BlockStackSpec` words it.  Sequence mixers: `M` a Mamba-2
mixer (ops/ssd.py), `*` causal grouped-query attention with no positional
term (ops/attention.causal_gqa), `L` a gated-DeltaNet linear-attention mixer
(ops/gated_delta.py), `A` gated causal grouped-query attention (a per-head
RMSNorm on q and k, a partial rotary term, a sigmoid output gate, then
`causal_gqa`), `C` causal multi-head latent attention (q, and the keys and
values, through low-rank projections with an RMSNorm inside each; a head's
key is dims of its own plus rotary dims all heads share, its values of
another width: ops/attention.causal_latent_attention), `V` a gated short
convolution (`[B | C | X] = u W_in`, then `(C * conv(B * X)) W_out`, a
depthwise causal convolution of SHORT_CONV_TAPS taps with no bias), `Q`
causal grouped-query attention with a plain per-head RMSNorm on q and k and
the rotary term on every dim of a head, no gate.  Expert layers
(ops/routed_experts.py): `E` sigmoid-routed relu^2 experts beside one shared
expert, `F` softmax-routed gated experts beside one shared expert behind a
sigmoid gate, `G` sigmoid-routed gated experts beside `n_shared_experts`
shared experts' width with no gate, or beside none.  `D` is a dense gated
MLP.  The norms of `L`, `A` and `F` blocks are zero-centred, `x_hat * (1 +
w)` with `w` from zero; the final norm is of the last block's kind.  The
residual stream stays in the compute dtype; router logits, norms'
statistics, the short convolution's gates, the scans' decays, the delta
rule's chunk systems and their inverse, the rotary term and the softmax are
float32.  A row is a fixed-width sequence: every selected column is one
position's token id, all of one vocabulary.

Only the last position reaches the head, and an expert block mixes nothing
along the sequence: the blocks that follow the last sequence mixer run on
the last position alone, one token a row.

An expert layer holds `experts_held` of the `n_routed_experts` the router
scores (expert parallelism's share of the layer): a token's choices that
fall on other experts add nothing here.  Each expert block counts where its
tokens went and how many blocks of the dispatch held rows (a `G` block with
no shared expert also how many of its tokens chose no held expert, and so
left the layer unchanged here); the model sows
the counts (collection `counters`, name `moe`) and the train step sums them
over an epoch.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config.schema import BlockStackSpec, ModelSpec
from ..ops.attention import causal_gqa, causal_latent_attention
from ..ops.gated_delta import gated_delta_rule
from ..ops.routed_experts import (default_block_rows, plan_dispatch,
                                  route_softmax_topk, route_topk,
                                  routed_gated_mlp, routed_relu2_mlp)
from ..ops.ssd import causal_conv1d, ssd_chunked
from .base import ScoringHead, dtype_of

INIT_STD = 0.02
#: of the `M`, `*` and `E` blocks alone: the output projections' initial
#: scale, `rescale_prenorm_residual` at the depth of the published stack
#: they come from, and the initial range of the Mamba-2 step, its
#: `time_step_min` / `_max` / `_floor`.  The other kinds draw every
#: projection at INIT_STD, as their families do
RESCALE_LAYERS = 52
OUT_STD = INIT_STD / RESCALE_LAYERS ** 0.5
TIME_STEP_MIN, TIME_STEP_MAX, TIME_STEP_FLOOR = 0.001, 0.1, 1e-4
#: under the root where an `L` block scales q and k to unit length
UNIT_LENGTH_EPS = 1e-6
#: the taps of a `V` block's depthwise convolution (`conv_L_cache`)
SHORT_CONV_TAPS = 3


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


def log_uniform_init(lo: float, hi: float):
    """log U(lo, hi): a recurrence's decay rates at the start."""
    def init(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo,
                                          hi)).astype(dtype)
    return init


def rms_norm(x, weight, eps: float, groups: int = 1):
    """RMSNorm in float32 over the last axis, or over `groups` equal parts
    of it; the result in x's dtype."""
    shape = x.shape
    xf = x.astype(jnp.float32).reshape(*shape[:-1], groups, -1)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf.reshape(shape) * weight.astype(jnp.float32)).astype(x.dtype)


def rms_norm_zero_centred(x, weight, eps: float):
    """`x_hat * (1 + w)` over the last axis (which `weight` spans), in
    float32; the result in x's dtype."""
    return rms_norm(x, 1.0 + weight.astype(jnp.float32), eps)


def rotate(x, theta: float, rotary_dim: int):
    """The rotary term on the first `rotary_dim` dims of a head, position =
    index along axis 1: x (B, T, H, D) -> the same shape and dtype.  Dims i
    and i + rotary_dim / 2 turn together by `t * theta**(-2i / rotary_dim)`,
    in float32."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = (xf[..., :half], xf[..., half:rotary_dim],
                    xf[..., rotary_dim:])
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1).astype(x.dtype)


def rotate_pairs(x, theta: float):
    """The rotary term on every dim of a head, neighbours together
    (`rope_interleave`), position = index along axis 1: x (B, T, H, D) ->
    the same shape and dtype.  Dims 2i and 2i + 1 turn together by
    `t * theta**(-2i / D)`, in float32."""
    d = x.shape[-1]
    dim = jnp.arange(d)
    inv = theta ** (-(dim // 2).astype(jnp.float32) * 2.0 / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    xf = x.astype(jnp.float32)
    # a dim's partner in its pair, signed: -x[2i+1] at 2i, x[2i] at 2i+1
    partner = jnp.where(dim % 2 == 0, -jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)


def relu2(x):
    r = jax.nn.relu(x)
    return r * r


class _Block(nn.Module):
    """What the kinds of block share: the spec, the dtypes, the pre-norm
    (zero-centred where the kind says so), a dense product in the compute
    dtype."""

    spec: ModelSpec
    zero_centred_norm = False

    @property
    def bs(self) -> BlockStackSpec:
        return self.spec.block_stack

    @property
    def cdt(self):
        return dtype_of(self.spec.compute_dtype)

    def weight(self, name: str, shape, init):
        return self.param(name, init, shape, dtype_of(self.spec.param_dtype))

    def pre_norm(self, x):
        if self.zero_centred_norm:
            return rms_norm_zero_centred(
                x, self.weight("norm", (self.bs.hidden_size,),
                               nn.initializers.zeros), self.bs.norm_eps)
        return rms_norm(x, self.weight("norm", (self.bs.hidden_size,),
                                       nn.initializers.ones),
                        self.bs.norm_eps)

    def dot(self, x, w):
        return jnp.dot(x, w.astype(self.cdt),
                       preferred_element_type=jnp.float32).astype(self.cdt)

    @nn.nowrap
    def gated_mlp(self, x, w_gate, w_up, w_down):
        """`(silu(x W_gate) * x W_up) W_down`, the gate in float32."""
        f32 = jnp.float32
        hidden = (jax.nn.silu(self.dot(x, w_gate).astype(f32))
                  * self.dot(x, w_up).astype(f32)).astype(self.cdt)
        return self.dot(hidden, w_down)

    @nn.nowrap    # no scope of its own: the parts stay `block<i>/moe/...`
    def experts_layer(self, x, tokens, w_router, route, experts, shared):
        """What the expert kinds share: (x + the layer's output, what
        `_dispatch_counters` counts of this call).  `tokens` is the normed
        x, a row a token; `route(logits)` gives (the chosen experts, their
        weights); `experts(row_weight, row_token, block_expert, live_blocks,
        block_rows)` the held experts' part and `shared(tokens)` the shared
        expert's, both float32.  `shared` None: the layer has no shared
        expert, its output is the held experts' part alone, and the counters
        say how many tokens chose no held expert."""
        bs, f32 = self.bs, jnp.float32
        k = bs.num_experts_per_tok
        with jax.named_scope("moe"):
            with jax.named_scope("router"):
                logits = jnp.dot(tokens.astype(f32), w_router.astype(f32),
                                 precision=jax.lax.Precision.HIGHEST)
                chosen, weights = route(logits)
            with jax.named_scope("dispatch"):
                plan, rows, row_weight, row_token = _dispatch(bs, chosen,
                                                              weights)
            with jax.named_scope("experts"):
                routed = experts(row_weight, row_token, plan["block_expert"],
                                 plan["live_blocks"], rows)
            if shared is None:
                y = routed.astype(self.cdt)
                unrouted = _tokens_unrouted(bs, chosen)
            else:
                with jax.named_scope("shared"):
                    beside = shared(tokens)
                with jax.named_scope("combine"):
                    y = (beside + routed).astype(self.cdt)
                unrouted = None
        counters = _dispatch_counters(plan, tokens, k, rows, unrouted)
        return x + y.reshape(x.shape), counters


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
        a_log = self.weight("A_log", (heads,), log_uniform_init(1.0, 16.0))
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
    """Returns (x, counters): what `_dispatch_counters` counts of this
    call."""

    @nn.compact
    def __call__(self, x):
        bs = self.bs
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
        return self.experts_layer(
            x, tokens, w_r,
            lambda logits: route_topk(logits, bs.num_experts_per_tok,
                                      bs.routed_scaling_factor),
            lambda *plan: routed_relu2_mlp(tokens, w1, w2, *plan),
            lambda t: self.dot(relu2(self.dot(t, s1)), s2)
            .astype(jnp.float32))


def _dispatch(bs: BlockStackSpec, experts, weights):
    """The held experts' rows for the (T, k) choices `experts` and their
    `weights`: (the plan, the rows a block, each row's weight, each row's
    token), the last two in the plan's order, padding rows at weight 0 and
    token T."""
    t, k = experts.shape
    rows = default_block_rows(t * k, bs.held)
    plan = plan_dispatch(experts, bs.first_expert_held, bs.held, rows)
    slot = plan["row_slot"]
    row_weight = jnp.append(weights.reshape(-1), 0.0)[slot]
    return plan, rows, row_weight, slot // k


def _tokens_unrouted(bs: BlockStackSpec, experts):
    """How many tokens of the (T, k) choices `experts` chose no held
    expert."""
    local = experts - bs.first_expert_held
    held = jnp.any((local >= 0) & (local < bs.held), axis=-1)
    return jnp.sum(~held).astype(jnp.int32)


def _dispatch_counters(plan: dict, tokens, k: int, block_rows: int,
                       unrouted=None) -> dict:
    """What an expert block counts of one call.  `live_rows` is the rows
    of the dispatch's live blocks, `live_blocks * block_rows`: summed over
    an epoch beside `live_blocks`, it carries the block size out.  A layer
    with no shared expert also counts its `tokens` and, of them, the
    `tokens_unrouted` (`unrouted`), for which it did no work."""
    counters = {
        "tokens_per_expert": plan["tokens_per_expert"],
        "routed_slots": jnp.int32(tokens.shape[0] * k),
        "held_slots": plan["held_slots"],
        "tokens_dropped": plan["held_slots"] - plan["dispatched_slots"],
        "live_blocks": plan["live_blocks"],
        "live_rows": plan["live_blocks"] * block_rows,
    }
    if unrouted is not None:
        counters["tokens"] = jnp.int32(tokens.shape[0])
        counters["tokens_unrouted"] = unrouted
    return counters


class GatedDeltaBlock(_Block):
    zero_centred_norm = True

    @nn.compact
    def __call__(self, x):
        bs, cdt = self.bs, self.cdt
        hk, hv, dk, dv = (bs.linear_num_key_heads, bs.linear_num_value_heads,
                          bs.linear_key_head_dim, bs.linear_value_head_dim)
        d_key, d_value = hk * dk, hv * dv
        d_conv = 2 * d_key + d_value
        h = self.pre_norm(x)
        init = nn.initializers.normal(INIT_STD)
        w_qkvz = self.weight("in_proj_qkvz", (bs.hidden_size,
                                              d_conv + d_value), init)
        w_ba = self.weight("in_proj_ba", (bs.hidden_size, 2 * hv), init)
        k_bound = bs.linear_conv_kernel_dim ** -0.5
        conv_w = self.weight("conv_w", (bs.linear_conv_kernel_dim, d_conv),
                             uniform_init(-k_bound, k_bound))
        dt_bias = self.weight("dt_bias", (hv,), nn.initializers.ones)
        a_log = self.weight("A_log", (hv,), log_uniform_init(0.0, 16.0))
        gate_norm = self.weight("gate_norm", (dv,), nn.initializers.ones)
        w_out = self.weight("out_proj", (d_value, bs.hidden_size), init)
        b_, t = x.shape[:2]
        f32 = jnp.float32
        with jax.named_scope("gated_delta"):
            with jax.named_scope("in_proj"):
                qkv, z = jnp.split(self.dot(h, w_qkvz), [d_conv], axis=-1)
                b, a = jnp.split(self.dot(h, w_ba).astype(f32), 2, axis=-1)
            with jax.named_scope("conv"):
                qkv = jax.nn.silu(causal_conv1d(qkv, conv_w))
            with jax.named_scope("delta_rule"):
                q, k, v = jnp.split(qkv, [d_key, 2 * d_key], axis=-1)
                q = q.astype(f32).reshape(b_, t, hk, dk)
                k = k.astype(f32).reshape(b_, t, hk, dk)
                q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True)
                                      + UNIT_LENGTH_EPS) * dk ** -0.5
                k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True)
                                      + UNIT_LENGTH_EPS)
                log_alpha = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                    a + dt_bias.astype(f32))
                o = gated_delta_rule(q.astype(cdt), k.astype(cdt),
                                     v.reshape(b_, t, hv, dv), log_alpha,
                                     jax.nn.sigmoid(b))
            with jax.named_scope("gate_norm"):
                # the norm before the gate, over a head's dims
                o = rms_norm(o, gate_norm, bs.norm_eps)
                y = (o.astype(f32) * jax.nn.silu(
                    z.reshape(b_, t, hv, dv).astype(f32))).astype(cdt)
            with jax.named_scope("out_proj"):
                return x + self.dot(y.reshape(b_, t, d_value), w_out)


class GatedAttentionBlock(_Block):
    zero_centred_norm = True

    @nn.compact
    def __call__(self, x):
        bs, cdt = self.bs, self.cdt
        hq, hkv, d = (bs.num_attention_heads, bs.num_key_value_heads,
                      bs.head_dim)
        h = self.pre_norm(x)
        init = nn.initializers.normal(INIT_STD)
        w_q = self.weight("q_proj", (bs.hidden_size, hq * 2 * d), init)
        w_k = self.weight("k_proj", (bs.hidden_size, hkv * d), init)
        w_v = self.weight("v_proj", (bs.hidden_size, hkv * d), init)
        q_norm = self.weight("q_norm", (d,), nn.initializers.zeros)
        k_norm = self.weight("k_norm", (d,), nn.initializers.zeros)
        w_o = self.weight("o_proj", (hq * d, bs.hidden_size), init)
        b_, t = x.shape[:2]
        with jax.named_scope("gated_attention"):
            with jax.named_scope("qkv"):
                # a query head's columns: its query, then its gate
                q, gate = jnp.split(
                    self.dot(h, w_q).reshape(b_, t, hq, 2 * d), 2, axis=-1)
                k = self.dot(h, w_k).reshape(b_, t, hkv, d)
                v = self.dot(h, w_v).reshape(b_, t, hkv, d)
            with jax.named_scope("qk_norm"):
                q = rms_norm_zero_centred(q, q_norm, bs.norm_eps)
                k = rms_norm_zero_centred(k, k_norm, bs.norm_eps)
            with jax.named_scope("rope"):
                q = rotate(q, bs.rope_theta, bs.rotary_dim)
                k = rotate(k, bs.rope_theta, bs.rotary_dim)
            with jax.named_scope("softmax"):
                o = causal_gqa(q, k, v)
            with jax.named_scope("gate"):
                o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(cdt)
            with jax.named_scope("o_proj"):
                return x + self.dot(o.reshape(b_, t, hq * d), w_o)


class GatedExpertsBlock(_Block):
    """Returns (x, counters), as `ExpertsBlock` does."""

    zero_centred_norm = True

    @nn.compact
    def __call__(self, x):
        bs = self.bs
        hidden, f, fs, held = (bs.hidden_size, bs.moe_intermediate_size,
                               bs.shared_expert_intermediate_size, bs.held)
        h = self.pre_norm(x)
        init = nn.initializers.normal(INIT_STD)
        w_r = self.weight("router", (hidden, bs.n_routed_experts), init)
        w_gate = self.weight("experts/w_gate", (held, hidden, f), init)
        w_up = self.weight("experts/w_up", (held, hidden, f), init)
        w_down = self.weight("experts/w_down", (held, f, hidden), init)
        s_gate = self.weight("shared/w_gate", (hidden, fs), init)
        s_up = self.weight("shared/w_up", (hidden, fs), init)
        s_down = self.weight("shared/w_down", (fs, hidden), init)
        s_open = self.weight("shared/gate", (hidden, 1), init)
        tokens = h.reshape(-1, hidden)
        f32 = jnp.float32
        return self.experts_layer(
            x, tokens, w_r,
            lambda logits: route_softmax_topk(logits,
                                              bs.num_experts_per_tok),
            lambda *plan: routed_gated_mlp(tokens, w_gate, w_up, w_down,
                                           *plan),
            lambda t: (self.gated_mlp(t, s_gate, s_up, s_down).astype(f32)
                       * jax.nn.sigmoid(self.dot(t, s_open).astype(f32))))


class LatentAttentionBlock(_Block):
    @nn.compact
    def __call__(self, x):
        bs = self.bs
        hq, dn, dr, dv = (bs.num_attention_heads, bs.qk_nope_head_dim,
                          bs.qk_rope_head_dim, bs.v_head_dim)
        h = self.pre_norm(x)
        init = nn.initializers.normal(INIT_STD)
        ones = nn.initializers.ones
        w_qa = self.weight("q_a_proj", (bs.hidden_size, bs.q_lora_rank), init)
        q_norm = self.weight("q_a_norm", (bs.q_lora_rank,), ones)
        w_qb = self.weight("q_b_proj", (bs.q_lora_rank, hq * (dn + dr)), init)
        # the latent's columns, then the one rotary key's
        w_kva = self.weight("kv_a_proj", (bs.hidden_size,
                                          bs.kv_lora_rank + dr), init)
        kv_norm = self.weight("kv_a_norm", (bs.kv_lora_rank,), ones)
        # a head's columns: its key's dims, then its value's
        w_kvb = self.weight("kv_b_proj", (bs.kv_lora_rank, hq * (dn + dv)),
                            init)
        w_o = self.weight("o_proj", (hq * dv, bs.hidden_size), init)
        b_, t = x.shape[:2]

        def row(xs):
            """One row from its latents on: its queries, keys and values are
            up-projected where they are read, so a batch's never stand in
            memory together, and the backward holds one row's."""
            c_q, c_kv, k_pe = (a[None] for a in xs)
            with jax.named_scope("up_proj"):
                q_nope, q_pe = jnp.split(
                    self.dot(c_q, w_qb).reshape(1, t, hq, dn + dr), [dn],
                    axis=-1)
                k_nope, v = jnp.split(
                    self.dot(c_kv, w_kvb).reshape(1, t, hq, dn + dv), [dn],
                    axis=-1)
            with jax.named_scope("rope"):
                q_pe = rotate_pairs(q_pe, bs.rope_theta)
                k_pe = rotate_pairs(k_pe[:, :, None, :], bs.rope_theta)[:, :, 0]
            with jax.named_scope("scores"):
                return causal_latent_attention(q_nope, q_pe, k_nope, k_pe,
                                               v)[0]

        with jax.named_scope("latent_attention"):
            with jax.named_scope("q_latent"):
                c_q = rms_norm(self.dot(h, w_qa), q_norm, bs.norm_eps)
            with jax.named_scope("kv_latent"):
                c_kv, k_pe = jnp.split(self.dot(h, w_kva), [bs.kv_lora_rank],
                                       axis=-1)
                c_kv = rms_norm(c_kv, kv_norm, bs.norm_eps)
            o = jax.lax.map(jax.checkpoint(row), (c_q, c_kv, k_pe))
            with jax.named_scope("o_proj"):
                return x + self.dot(o.reshape(b_, t, hq * dv), w_o)


class DenseMlpBlock(_Block):
    @nn.compact
    def __call__(self, x):
        bs = self.bs
        h = self.pre_norm(x)
        init = nn.initializers.normal(INIT_STD)
        w_gate = self.weight("gate_proj", (bs.hidden_size,
                                           bs.intermediate_size), init)
        w_up = self.weight("up_proj", (bs.hidden_size, bs.intermediate_size),
                           init)
        w_down = self.weight("down_proj", (bs.intermediate_size,
                                           bs.hidden_size), init)
        with jax.named_scope("dense_mlp"):
            return x + self.gated_mlp(h, w_gate, w_up, w_down)


class SigmoidGatedExpertsBlock(_Block):
    """`ExpertsBlock`'s router over `GatedExpertsBlock`'s experts, the
    shared expert added with no gate; with `n_shared_experts` 0 there is
    none, and no leaf of it.  Returns (x, counters), as they do."""

    @nn.compact
    def __call__(self, x):
        bs = self.bs
        hidden, f, held = bs.hidden_size, bs.moe_intermediate_size, bs.held
        fs = f * bs.n_shared_experts
        h = self.pre_norm(x)
        init = nn.initializers.normal(INIT_STD)
        w_r = self.weight("router", (hidden, bs.n_routed_experts), init)
        w_gate = self.weight("experts/w_gate", (held, hidden, f), init)
        w_up = self.weight("experts/w_up", (held, hidden, f), init)
        w_down = self.weight("experts/w_down", (held, f, hidden), init)
        shared = None
        if fs:
            s_gate = self.weight("shared/w_gate", (hidden, fs), init)
            s_up = self.weight("shared/w_up", (hidden, fs), init)
            s_down = self.weight("shared/w_down", (fs, hidden), init)

            def shared(t):
                return self.gated_mlp(t, s_gate, s_up, s_down).astype(
                    jnp.float32)
        tokens = h.reshape(-1, hidden)
        return self.experts_layer(
            x, tokens, w_r,
            lambda logits: route_topk(logits, bs.num_experts_per_tok,
                                      bs.routed_scaling_factor),
            lambda *plan: routed_gated_mlp(tokens, w_gate, w_up, w_down,
                                           *plan),
            shared)


class ShortConvBlock(_Block):
    """The gated short convolution: `[B | C | X] = h W_in`, `z = B * X`,
    `z'_t = sum_j w_j * z_{t-2+j}` per channel (zero before the row), then
    `(C * z') W_out`; no bias anywhere.  The gates in float32."""

    @nn.compact
    def __call__(self, x):
        bs, cdt, f32 = self.bs, self.cdt, jnp.float32
        hidden = bs.hidden_size
        h = self.pre_norm(x)
        init = nn.initializers.normal(INIT_STD)
        w_in = self.weight("in_proj", (hidden, 3 * hidden), init)
        conv_w = self.weight("conv_w", (SHORT_CONV_TAPS, hidden), init)
        w_out = self.weight("out_proj", (hidden, hidden), init)
        with jax.named_scope("short_conv"):
            with jax.named_scope("in_proj"):
                b, c, u = jnp.split(self.dot(h, w_in), 3, axis=-1)
            with jax.named_scope("conv"):
                z = causal_conv1d((b.astype(f32) * u.astype(f32)).astype(cdt),
                                  conv_w)
                y = (c.astype(f32) * z.astype(f32)).astype(cdt)
            with jax.named_scope("out_proj"):
                return x + self.dot(y, w_out)


class NormedAttentionBlock(_Block):
    """Causal grouped-query attention with a plain RMSNorm over each head's
    dims of q and k (weights from 1) and the rotary term on every dim of a
    head (dims i and i + head_dim / 2 together), no output gate."""

    @nn.compact
    def __call__(self, x):
        bs = self.bs
        hq, hkv, d = (bs.num_attention_heads, bs.num_key_value_heads,
                      bs.head_dim)
        h = self.pre_norm(x)
        init = nn.initializers.normal(INIT_STD)
        ones = nn.initializers.ones
        w_q = self.weight("q_proj", (bs.hidden_size, hq * d), init)
        w_k = self.weight("k_proj", (bs.hidden_size, hkv * d), init)
        w_v = self.weight("v_proj", (bs.hidden_size, hkv * d), init)
        q_norm = self.weight("q_norm", (d,), ones)
        k_norm = self.weight("k_norm", (d,), ones)
        w_o = self.weight("o_proj", (hq * d, bs.hidden_size), init)
        b_, t = x.shape[:2]
        with jax.named_scope("attention"):
            with jax.named_scope("qkv"):
                q = self.dot(h, w_q).reshape(b_, t, hq, d)
                k = self.dot(h, w_k).reshape(b_, t, hkv, d)
                v = self.dot(h, w_v).reshape(b_, t, hkv, d)
            with jax.named_scope("qk_norm"):
                q = rms_norm(q, q_norm, bs.norm_eps)
                k = rms_norm(k, k_norm, bs.norm_eps)
            with jax.named_scope("rope"):
                q = rotate(q, bs.rope_theta, d)
                k = rotate(k, bs.rope_theta, d)
            with jax.named_scope("softmax"):
                o = causal_gqa(q, k, v)
            with jax.named_scope("o_proj"):
                return x + self.dot(o.reshape(b_, t, hq * d), w_o)


_KINDS = {"M": MambaBlock, "*": AttentionBlock, "E": ExpertsBlock,
          "L": GatedDeltaBlock, "A": GatedAttentionBlock,
          "F": GatedExpertsBlock, "C": LatentAttentionBlock,
          "D": DenseMlpBlock, "G": SigmoidGatedExpertsBlock,
          "V": ShortConvBlock, "Q": NormedAttentionBlock}
#: the kinds that mix along the sequence, and the kinds that hand back
#: counters beside x
SEQUENCE_MIXERS, EXPERT_KINDS = "M*LACVQ", "EFG"


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
        last_mixer = max(bs.pattern.rfind(c) for c in SEQUENCE_MIXERS)
        for i, kind in enumerate(bs.pattern):
            if i == last_mixer + 1:
                # nothing mixes along the sequence from here on: the head
                # reads the last position, so only it goes further
                x = x[:, -1:]
            cls = _KINDS[kind]
            if self.spec.remat:
                cls = nn.remat(cls)
            x = cls(spec=self.spec, name=f"block{i}")(x)
            if kind in EXPERT_KINDS:
                x, c = x
                counters.append(c)
        if counters:
            # one entry an expert layer, in the pattern's order
            self.sow("counters", "moe", jax.tree_util.tree_map(
                lambda *v: jnp.stack(v), *counters),
                reduce_fn=lambda _, new: new, init_fn=lambda: None)
        # the final norm is of the last block's kind
        zero_centred = _KINDS[bs.pattern[-1]].zero_centred_norm
        norm_f = self.param("norm_f", nn.initializers.zeros if zero_centred
                            else nn.initializers.ones, (bs.hidden_size,),
                            dtype_of(self.spec.param_dtype))
        last = (rms_norm_zero_centred if zero_centred else rms_norm)(
            x[:, -1], norm_f, bs.norm_eps)
        return ScoringHead(spec=self.spec, name="head")(last)
