"""The comparison's control. On the chip it is the program at matmul
precision `high` (the configuration's `control`), which a CPU ignores; here
the reference computed in bfloat16, put in the program's place, stands in
and fails the cell's limits while the served views pass them (CPU, tiny
widths)."""
import pytest

from bench.tests.common import ROOT, cells


@pytest.mark.parametrize("cell", cells()[:2])
def test_control_fails_the_limits(cell):
    from bench import compare, control
    limits = compare.load_limits(ROOT, cell)
    rows = control.readings(cell, [2**31 + 5, 7], views=1, control_seeds=2,
                            rehearse=True, log=lambda m: None,
                            control={"dtype": "bfloat16"})
    for _, prog, con in rows:
        assert compare.judge(prog, limits)[0]
        assert not compare.judge(con, limits)[0]


def test_program_precision_control_is_read_on_its_seeds():
    from bench import control
    rows = control.readings(cells()[0], [11, 12], views=1, control_seeds=1,
                            rehearse=True, log=lambda m: None)
    assert rows[0][2] is not None and set(rows[0][2]) == {"rmse"}
    assert rows[1][2] is None
