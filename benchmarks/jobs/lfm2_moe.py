"""The job a user writes for an `lfm2_moe` configuration: its published keys
worded as the program's `block_stack` group.

A published layer is two of the program's blocks: its mixer - `V` the gated
short convolution for a `conv` entry of `layer_types`, `Q` attention with
normed q and k for a `full_attention` one - then its feed-forward, `D` the
dense gated MLP in the `num_dense_layers` leading layers, `G` the expert
layer with no shared expert after them.  What the published file states
and the program holds as the block kinds' own arithmetic is compared here: a
file that states another value is refused, not run as something else.
"""

from __future__ import annotations

from .. import harness
from ..counts.lfm2_moe import head_dim, pattern

#: the configuration's keys that the program's group takes under their name
_SAME_KEYS = (
    "hidden_size", "norm_eps", "num_attention_heads", "num_key_value_heads",
    "rope_theta", "intermediate_size", "num_experts_per_tok",
    "moe_intermediate_size", "routed_scaling_factor")


def _fixed_in_the_program() -> dict:
    """What the published file states and the `V` and `G` blocks hold as
    their arithmetic (models/block_stack.py, ops/routed_experts.py): a
    convolution of `SHORT_CONV_TAPS` taps with no bias, the chosen experts'
    weights renormalised."""
    from shifu_tpu.models.block_stack import SHORT_CONV_TAPS

    return {"conv_bias": False, "conv_L_cache": SHORT_CONV_TAPS,
            "norm_topk_prob": True}


def model_group(config: dict) -> dict:
    for key, held in _fixed_in_the_program().items():
        if config[key] != held:
            raise harness.BenchError(
                f"the configuration states {key} = {config[key]!r}; the "
                f"program's V and G blocks hold {held!r} "
                "(models/block_stack.py, ops/routed_experts.py)")
    if config.get("expert_bias", 0.0) != 0.0:
        raise harness.BenchError(
            "the configuration states an expert bias of "
            f"{config['expert_bias']!r}; the program holds it at its "
            "initial zero and has no leaf for another value")
    if set(config["layer_types"]) - {"conv", "full_attention"}:
        raise harness.BenchError(
            "layer_types may name conv and full_attention layers only: "
            f"{sorted(set(config['layer_types']))}")
    dep = config["deployment"]
    return {
        "model_type": "block_stack", "hidden_nodes": [], "activations": [],
        "remat": bool(config.get("remat", False)),
        "block_stack": {
            **{k: config[k] for k in _SAME_KEYS},
            "pattern": pattern(config),
            "head_dim": head_dim(config),
            "n_shared_experts": 0,
            "n_routed_experts": dep["router_experts"],
            "experts_held": config["num_experts"],
            "first_expert_held": dep["first_expert_held"]}}
