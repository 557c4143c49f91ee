"""CPU sizes for cells whose `model_type` `benchmarks/tests/conftest.py` does
not know, by that file's own mechanism: a test that is run for such a cell
(its `cell` parameter) sees `tiny_context` here in `util.tiny_context`'s
place, which cuts the configuration's widths as well as its batch, rows and
vocabulary; every other test sees `util` as it is.  This file lies one
directory above that one so that it is loaded whichever test file is named,
`tests/test_counts.py` through the tier-1 command among them.  The two
tables of sizes become one, found by file, in the `benchmark` issue ROADMAP
Queue 3 holds.

The sizes: two published layers with every other one full attention (the
pattern `LFAF`: each kind of block, an `F` block before and after the last
mixer), no rematerialization (the counts leave it out, and the compiler's
own count in `test_counts.py` would put it in), a hidden size wide beside
the heads' state so that the projections are most of the operations, as at
the published widths."""

import pytest

from benchmarks import harness
from benchmarks.tests import util

#: sizes of the CPU tests, under the configuration file's own keys
TINY_WIDTHS = {
    "qwen3_next": {
        "num_hidden_layers": 2, "full_attention_interval": 2,
        "num_categorical": 16, "remat": False,
        "hidden_size": 128,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 16, "linear_value_head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 4, "num_experts_per_tok": 2,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 48,
        "deployment": {"router_experts": 8, "first_expert_held": 0},
    },
}

_tiny_context = util.tiny_context


def _widths(cell_name: str) -> dict:
    _, _, config, _, _, _ = harness.load_cell(cell_name)
    return TINY_WIDTHS.get(config.get("model_type"), {})


def tiny_context(cell_name: str, **overrides):
    """`util.tiny_context` with the cell's widths cut (an override the
    caller passes still wins)."""
    return _tiny_context(cell_name, **{**_widths(cell_name), **overrides})


@pytest.fixture(autouse=True)
def _cut_widths_for_a_cell_of_these_model_types(request, monkeypatch):
    callspec = getattr(request.node, "callspec", None)
    cell = callspec.params.get("cell") if callspec else None
    if isinstance(cell, str) and _widths(cell):
        monkeypatch.setattr(util, "tiny_context", tiny_context)
