"""The control at a small size on the CPU: the plain reference put in the
program's place at int4 fails the cell's limits, where the program (its
FP8 path, at the same size) passes them."""
import pytest

import control
from conftest import tiny_cell


@pytest.mark.parametrize("kind", ["train"])
def test_control_fails_where_the_program_passes(no_cache, kind):
    cell = tiny_cell(kind)
    line = control.readings(cell[0]["name"], 2 ** 31 + 7, 1.0, cell=cell,
                            require_chip=False)
    limits = cell[2]["limits"][kind]
    assert line["correct"]
    assert all(line["program"][k] <= v for k, v in limits.items())
    assert any(line["control"][k] > limits[k] for k in limits), line
