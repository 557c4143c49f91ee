"""The CPU sizes of `model_type` `lfm2_moe`, under the configuration file's
own keys: what the repository's root `conftest.py` puts in
`util.tiny_context`'s place for a cell of this model, found by this file's
name, and what `tests/test_block_stack_lfm2.py` runs at.

Three published layers, the first dense (`layer_types` conv, attention,
conv: the blocks `VDQGVG`, each kind of block, and a `G` block before as
well as after the last mixer, so that one of them routes every position);
no rematerialization (the counts leave it out, and the compiler's own count
in `test_counts.py` would put it in); a hidden size wide beside the heads,
so that the projections outside the loops are most of the operations (the
compiler counts a loop's body once: a row's attention products, an
expert's block); 4 query heads over 2 key-value heads of 32 dims; 4 of 16
routed experts held and 4 a token, one held expert a position on average
as at the published 8 of 32 and 4."""

from benchmarks.tests import util

TINY_WIDTHS = {
    "num_hidden_layers": 3, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv"],
    "num_categorical": 16, "remat": False,
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 256,
    "num_experts": 4, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32,
    "deployment": {"router_experts": 16, "first_expert_held": 0},
}

_tiny_context = util.tiny_context


def tiny_context(cell_name: str, **overrides):
    """`util.tiny_context` with the widths cut (an override the caller
    passes still wins)."""
    return _tiny_context(cell_name, **{**TINY_WIDTHS, **overrides})
