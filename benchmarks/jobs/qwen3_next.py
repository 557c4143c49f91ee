"""The job a user writes for a `qwen3_next` configuration: its published
keys worded as the program's `block_stack` group.

A published layer is two of the program's blocks: its mixer (`A` gated full
attention where `(l + 1) % full_attention_interval == 0`, `L` the gated delta
rule otherwise), then its expert layer `F`.  What the published file states
and the program holds as the block kinds' own arithmetic is compared here: a
file that states another value is refused, not run as something else.
"""

from __future__ import annotations

from .. import harness
from ..counts.qwen3_next import pattern

#: the configuration's keys that the program's group takes under their name
_SAME_KEYS = (
    "hidden_size", "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "partial_rotary_factor", "rope_theta", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size")

#: what the published file states and the `L`, `A` and `F` blocks hold as
#: their arithmetic (models/block_stack.py, ops/routed_experts.py): silu in
#: the convolution, the gates and the experts; the chosen experts' weights
#: renormalised; an expert layer after every mixer; one rotary base, no
#: scaling; no window on the full-attention layers
_FIXED_IN_THE_PROGRAM = {
    "hidden_act": "silu", "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "rope_scaling": None, "use_sliding_window": False}


def model_group(config: dict) -> dict:
    for key, held in _FIXED_IN_THE_PROGRAM.items():
        if config[key] != held:
            raise harness.BenchError(
                f"the configuration states {key} = {config[key]!r}; the "
                f"program's L, A and F blocks hold {held!r} "
                "(models/block_stack.py, ops/routed_experts.py)")
    dep = config["deployment"]
    return {
        "model_type": "block_stack", "hidden_nodes": [], "activations": [],
        "remat": bool(config.get("remat", False)),
        "block_stack": {
            **{k: config[k] for k in _SAME_KEYS},
            "pattern": pattern(config),
            "norm_eps": config["rms_norm_eps"],
            "n_routed_experts": dep["router_experts"],
            "experts_held": config["num_experts"],
            "first_expert_held": dep["first_expert_held"]}}
