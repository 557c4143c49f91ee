"""The job a user writes for a `joyai_llm_flash` configuration: its published
keys worded as the program's `block_stack` group.

A published layer is two of the program's blocks: its mixer `C` (multi-head
latent attention), then its feed-forward - `D` the dense gated MLP in the
`first_k_dense_replace` leading layers, `G` the expert layer after them.
What the published file states and the program holds as the block kinds' own
arithmetic is compared here: a file that states another value is refused,
not run as something else.
"""

from __future__ import annotations

from .. import harness
from ..counts.joyai_llm_flash import pattern

#: the configuration's keys that the program's group takes under their name
_SAME_KEYS = (
    "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
    "intermediate_size", "num_experts_per_tok", "moe_intermediate_size",
    "n_shared_experts", "routed_scaling_factor")

#: what the published file states and the `C`, `D` and `G` blocks hold as
#: their arithmetic (models/block_stack.py, ops/routed_experts.py): sigmoid
#: scores with a correction bias at zero and one group, the chosen experts'
#: weights renormalised; silu in the gated MLPs; the rotary term on
#: neighbouring pairs with one base and no scaling; an expert layer in every
#: layer after the leading dense ones; no bias in the attention projections
_FIXED_IN_THE_PROGRAM = {
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "hidden_act": "silu",
    "rope_scaling": None, "rope_interleave": True, "moe_layer_freq": 1,
    "attention_bias": False}


def model_group(config: dict) -> dict:
    for key, held in _FIXED_IN_THE_PROGRAM.items():
        if config[key] != held:
            raise harness.BenchError(
                f"the configuration states {key} = {config[key]!r}; the "
                f"program's C, D and G blocks hold {held!r} "
                "(models/block_stack.py, ops/routed_experts.py)")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise harness.BenchError(
            "latent attention gives every query head keys and values of its "
            "own: num_key_value_heads has to be num_attention_heads")
    dep = config["deployment"]
    return {
        "model_type": "block_stack", "hidden_nodes": [], "activations": [],
        "remat": bool(config.get("remat", False)),
        "block_stack": {
            **{k: config[k] for k in _SAME_KEYS},
            "pattern": pattern(config),
            "norm_eps": config["rms_norm_eps"],
            "n_routed_experts": dep["router_experts"],
            "experts_held": config["n_routed_experts"],
            "first_expert_held": dep["first_expert_held"]}}
