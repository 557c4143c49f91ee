"""CPU sizes for the benchmark's cells, found by file: a test that is run for
a cell (its `cell` parameter) whose configuration's `model_type` has a
`benchmarks/tests/sizes_<model_type>.py` sees that file's `tiny_context` in
`util.tiny_context`'s place, which cuts the configuration's widths as well
as its batch, rows and vocabulary; every other test, and every other cell,
sees what it saw.  A new model brings its file and edits nothing here.
This file lies at the repository's root (pytest's rootdir) so that it is
loaded for `tests/` and `benchmarks/tests/` alike.  The older tables of
`benchmarks/tests/conftest.py` (`nemotron_h`) and `benchmarks/conftest.py`
(`qwen3_next`) swap `util.tiny_context` by the same rule, each for its own
model."""

import importlib
import os

import pytest

_SIZES_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmarks", "tests")


def _sizes_module(cell: str):
    """`benchmarks/tests/sizes_<model_type>.py` of the cell's configuration,
    or None: a cell without such a file, or a `cell` parameter that names
    no cell."""
    from benchmarks import harness

    try:
        _, _, config, _, _, _ = harness.load_cell(cell)
    except harness.BenchError:
        return None
    name = f"sizes_{config.get('model_type')}"
    if not os.path.exists(os.path.join(_SIZES_DIR, f"{name}.py")):
        return None
    return importlib.import_module(f"benchmarks.tests.{name}")


@pytest.fixture(autouse=True)
def _cut_widths_for_a_cell_with_a_sizes_file(request, monkeypatch):
    callspec = getattr(request.node, "callspec", None)
    cell = callspec.params.get("cell") if callspec else None
    if not isinstance(cell, str):
        return
    sizes = _sizes_module(cell)
    if sizes is not None:
        from benchmarks.tests import util

        monkeypatch.setattr(util, "tiny_context", sizes.tiny_context)
