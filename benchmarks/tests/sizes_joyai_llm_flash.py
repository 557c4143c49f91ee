"""The CPU sizes of `model_type` `joyai_llm_flash`, under the configuration
file's own keys: what the repository's root `conftest.py` puts in
`util.tiny_context`'s place for a cell of this model, and what
`test_joyai_llm_flash.py` runs at.

Three published layers, the first dense (the blocks `CDCGCG`: each kind of
block, and a `G` block before as well as after the last mixer, so that one
of them routes every position); no rematerialization (the counts leave it
out, and the compiler's own count in `test_counts.py` would put it in); a
hidden size wide beside the heads and the two ranks, so that the projections
outside the loops are most of the operations (the compiler counts a loop's
body once: a row's up-projections and products, an expert's block - 5 % of
the count here; `test_joyai_llm_flash.py` holds a batch of one row, whose
loop is one trip, to the same band); the two ranks distinct and the three
head widths all different, so that a transposed width cannot pass; 4 of 16
routed experts held and 2 a token, half an expert a position as at the
published 16 of 256 and 8."""

from benchmarks import harness
from benchmarks.tests import util

MODEL_TYPE = "joyai_llm_flash"

TINY_WIDTHS = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_categorical": 16, "remat": False,
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "qk_head_dim": 12,
    "head_dim": 4, "v_head_dim": 6,
    "intermediate_size": 256,
    "n_routed_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32,
    "deployment": {"router_experts": 16, "first_expert_held": 0},
}

_tiny_context = util.tiny_context


def is_cell_of_this_model(cell_name: str) -> bool:
    try:
        _, _, config, _, _, _ = harness.load_cell(cell_name)
    except harness.BenchError:   # a `cell` parameter that names no cell
        return False
    return config.get("model_type") == MODEL_TYPE


def tiny_context(cell_name: str, **overrides):
    """`util.tiny_context` with the widths cut (an override the caller
    passes still wins)."""
    return _tiny_context(cell_name, **{**TINY_WIDTHS, **overrides})
