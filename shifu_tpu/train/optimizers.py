"""Optimizer factory.

The reference backend uses exactly one optimizer — Adadelta at ModelConfig's
LearningRate (resources/ssgd_monitor.py:140, fallback lr 0.003), wrapped in
SyncReplicasOptimizer for cross-worker aggregation.  Under SPMD the
aggregation is the mean-gradient all-reduce XLA inserts for a data-sharded
batch, so the optimizer here is just the local update rule.  Gradient
accumulation (optax.MultiSteps) is the analog of SAGN's k-step local window
(resources/SAGN.py:110-142).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import optax

from ..config.schema import ConfigError, OptimizerConfig

# TF 1.4 AdadeltaOptimizer defaults (the reference passes only learning_rate):
# rho=0.95, epsilon=1e-8.
_TF_ADADELTA_RHO = 0.95
_TF_ADADELTA_EPS = 1e-8


def _learning_rate(cfg: OptimizerConfig):
    """The LR or optax schedule per OptimizerConfig.schedule (counted in
    optimizer steps; the reference only ever had a constant LR)."""
    lr = cfg.learning_rate
    if cfg.schedule == "constant":
        return lr
    if cfg.schedule == "cosine":
        return optax.cosine_decay_schedule(lr, cfg.decay_steps,
                                           alpha=cfg.end_lr_factor)
    if cfg.schedule == "exponential":
        return optax.exponential_decay(lr, cfg.decay_steps, cfg.decay_rate)
    if cfg.schedule == "warmup_cosine":
        return optax.warmup_cosine_decay_schedule(
            0.0, lr, cfg.warmup_steps, cfg.decay_steps,
            end_value=lr * cfg.end_lr_factor)
    raise ConfigError(f"unknown schedule {cfg.schedule!r}")


def build_optimizer(cfg: OptimizerConfig) -> optax.GradientTransformation:
    name = cfg.name.lower()
    lr = _learning_rate(cfg)
    if name == "adadelta":
        tx = optax.adadelta(learning_rate=lr, rho=_TF_ADADELTA_RHO, eps=_TF_ADADELTA_EPS)
    elif name == "adam":
        tx = optax.adam(lr)
    elif name == "adamw":
        tx = optax.adamw(lr, weight_decay=cfg.weight_decay)
    elif name in ("sgd", "gradientdescent"):
        tx = optax.sgd(lr)
    elif name == "momentum":
        tx = optax.sgd(lr, momentum=cfg.momentum)
    elif name == "rmsprop":
        tx = optax.rmsprop(lr)
    elif name == "adagrad":
        tx = optax.adagrad(lr)
    else:
        raise ConfigError(f"unknown optimizer {cfg.name!r}")

    chain = []
    if cfg.grad_clip_norm > 0:
        chain.append(optax.clip_by_global_norm(cfg.grad_clip_norm))
    chain.append(tx)
    out = optax.chain(*chain) if len(chain) > 1 else tx
    if cfg.accumulate_steps > 1:
        out = optax.MultiSteps(out, every_k_schedule=cfg.accumulate_steps)
    return out


#: a leaf under this many elements keeps optax's apply: a custom call's
#: fixed cost is worth more than the passes the fused one saves
FUSED_MIN_ELEMENTS = 1 << 20


def fused_leaf(leaf) -> bool:
    """True for a leaf the fused Adadelta apply takes: float32, of rank 2
    or more, and of FUSED_MIN_ELEMENTS or more."""
    return (leaf.size >= FUSED_MIN_ELEMENTS and leaf.ndim >= 2
            and leaf.dtype == jnp.float32)


def fused_adadelta_engages(cfg: OptimizerConfig, mesh=None) -> bool:
    """True where the step applies Adadelta through the fused kernel
    (ops/pallas_adadelta.py) for its large leaves: a TPU backend, plain
    Adadelta (no accumulation window), one device.  A sharded leaf on a
    multi-chip mesh keeps optax until a shard_map form exists."""
    from ..ops.pallas_common import on_tpu

    return (on_tpu() and cfg.name.lower() == "adadelta"
            and cfg.accumulate_steps == 1
            and (mesh is None or mesh.size == 1))


def make_fused_adadelta_apply(cfg: OptimizerConfig):
    """(state, grads) -> state, as `TrainState.apply_gradients` gives it for
    `build_optimizer(cfg)` (Adadelta, `grad_clip_norm` clip optional): the
    leaves `fused_leaf` takes are updated by one in-place kernel call each,
    the others by optax in the same call (optax's results for the fused
    leaves are dead and leave the program).  `opt_state` keeps optax's
    structure, so checkpoints and readers of `e_g` see no difference."""
    from ..obs import introspect, metrics
    from ..ops.pallas_adadelta import adadelta_apply

    lr = _learning_rate(cfg)
    clip = cfg.grad_clip_norm
    inner = build_optimizer(dataclasses.replace(cfg, grad_clip_norm=0.0))

    def apply(st, grads):
        old_p, treedef = jax.tree_util.tree_flatten(st.params)
        fused = [i for i, p in enumerate(old_p) if fused_leaf(p)]
        nbytes = sum(old_p[i].size * old_p[i].dtype.itemsize for i in fused)
        metrics.gauge("adadelta_fused_leaves",
                      "leaves of the traced step under the fused Adadelta "
                      "kernel").set(len(fused))
        metrics.gauge("adadelta_fused_bytes",
                      "parameter bytes of those leaves").set(nbytes)
        introspect.note(adadelta_fused_leaves=len(fused),
                        adadelta_fused_bytes=nbytes)
        if not fused:
            return st.apply_gradients(grads)
        opt = st.opt_state
        if clip > 0:
            grads, _ = optax.clip_by_global_norm(clip).update(grads, opt[0])
        ada_opt = opt[1] if clip > 0 else opt
        updates, (decay, ada, sched) = inner.update(grads, ada_opt, st.params)
        # chain(add_decayed_weights(0), scale_by_adadelta, scale_by_learning_rate)
        _, old, count = ada_opt
        rate = lr(count.count) if callable(lr) else lr
        g, e_g, e_x = (treedef.flatten_up_to(t)
                       for t in (grads, old.e_g, old.e_x))
        new_p = treedef.flatten_up_to(optax.apply_updates(st.params, updates))
        new_eg = treedef.flatten_up_to(ada.e_g)
        new_ex = treedef.flatten_up_to(ada.e_x)
        for i in fused:
            new_p[i], new_eg[i], new_ex[i] = adadelta_apply(
                old_p[i], g[i], e_g[i], e_x[i], rate,
                rho=_TF_ADADELTA_RHO, eps=_TF_ADADELTA_EPS)
        ada_opt = (decay, ada._replace(e_g=treedef.unflatten(new_eg),
                                       e_x=treedef.unflatten(new_ex)), sched)
        return st.replace(step=st.step + 1,
                          params=treedef.unflatten(new_p),
                          opt_state=(opt[0], ada_opt) if clip > 0
                          else ada_opt)

    return apply
