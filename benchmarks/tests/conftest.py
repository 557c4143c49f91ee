"""The benchmark's tests cut a cell to a size the CPU holds with
`util.tiny_context`, which shrinks the batch, the rows and the vocabulary
and keeps a configuration's widths.  A configuration at a large model's
published widths needs its widths cut too: `tiny_context` here gives them,
by `model_type`, and a test that is run for a cell of such a configuration
(its `cell` parameter) sees it in `util.tiny_context`'s place; every other
test sees `util` as it is.  The sequence cell also keeps its own batch of 8
rows: half of a batch of 256 left out moves a gradient far less than half of
8, and it is the cell's own limits that the planted faults have to fail; and
it runs without rematerialization, which the counts leave out and the
compiler's own count (`test_counts.py`) would put in."""

import pytest

from benchmarks import harness
from benchmarks.tests import util

#: sizes of the CPU tests, under the configuration file's own keys
TINY_WIDTHS = {
    "nemotron_h": {
        "hybrid_override_pattern": "ME*E", "num_hidden_layers": 4,
        "num_categorical": 16, "remat": False,
        "hidden_size": 128,
        "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
        "ssm_state_size": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "n_routed_experts": 4,
        "num_experts_per_tok": 2, "moe_intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 48,
        "deployment": {"router_experts": 8, "first_expert_held": 0,
                       "published_layers": 52},
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
def _cut_widths_for_a_wide_cell(request, monkeypatch):
    callspec = getattr(request.node, "callspec", None)
    cell = callspec.params.get("cell") if callspec else None
    if isinstance(cell, str) and _widths(cell):
        monkeypatch.setattr(util, "tiny_context", tiny_context)
