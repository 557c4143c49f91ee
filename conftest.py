"""CPU sizes for the benchmark's cells of `model_type` `joyai_llm_flash`, by
the mechanism of `benchmarks/conftest.py` and `benchmarks/tests/conftest.py`
(both taken, each by one model's table): a test that is run for such a cell
(its `cell` parameter) sees `benchmarks/tests/sizes_joyai_llm_flash.py`'s
`tiny_context` in `util.tiny_context`'s place, which cuts the configuration's
widths as well as its batch, rows and vocabulary; every other test, and
every other cell, sees what it saw.  This file lies at the repository's root
(pytest's rootdir) so that it is loaded for `tests/` and `benchmarks/tests/`
alike.  The three tables of sizes become one, found by file, in the
`benchmark` issue ROADMAP Queue 3 holds."""

import pytest


@pytest.fixture(autouse=True)
def _cut_widths_for_a_cell_of_joyai_llm_flash(request, monkeypatch):
    callspec = getattr(request.node, "callspec", None)
    cell = callspec.params.get("cell") if callspec else None
    if not isinstance(cell, str):
        return
    from benchmarks.tests import sizes_joyai_llm_flash as sizes, util

    if sizes.is_cell_of_this_model(cell):
        monkeypatch.setattr(util, "tiny_context", sizes.tiny_context)
