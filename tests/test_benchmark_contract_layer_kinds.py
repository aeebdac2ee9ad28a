"""The CPU rehearsals of the layer-kind families' cells: the body of
``tests/test_benchmark_contract.py`` over their cases, in a second file so
that ``--dist loadfile`` gives them a worker of their own."""

import pytest

from tests.test_benchmark_contract import cells_of, compile_cache, rehearse  # noqa: F401


@pytest.mark.parametrize("cell", cells_of(True))
def test_cell_rehearses_end_to_end_on_the_cpu(cell, compile_cache, monkeypatch):
    rehearse(cell, compile_cache, monkeypatch)
